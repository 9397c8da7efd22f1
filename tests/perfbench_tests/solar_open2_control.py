"""The controls of the delta-rule cell's check, read at the cell's own
widths: ``serving_control.py``'s way (greedy tokens of the reference
computed wrongly, through the runner's own ``_logit_gap``), with the wrong
models this family invites.  Each sets a key that only the reference reads
and only this control sets: the recurrence's state DROPPED
(``control_state_dropped``: every token from a zero state, which is what a
KDA layer reading another layer's slots, or a pool indexed by the model's
layer, amounts to); ``β`` NOT doubled (``control_beta_not_doubled``: ``β =
sigmoid``, the rule without its negative eigenvalue); the decay a HEAD
(``control_decay_a_head``: a channel's log decay replaced by the mean over
its head's channels, what a kernel that took the state-space update's
decay would compute); the attention's output gate dropped
(``control_gate_dropped``); and the routed sum dropped whole
(``routed_scaling_factor`` 0: what an expert kernel that writes zeros, or
a plan that loses its rows, amounts to).  The reference in e4m3 is
``serving_control``'s own.

    python3 tests/perfbench_tests/solar_open2_control.py --workload <cell> \
        --seeds <n> ... [--prompts 96 640]
"""

from __future__ import annotations

import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
for path in (str(HERE.parents[1]), str(HERE)):
    if path not in sys.path:
        sys.path.insert(0, path)

import serving_control  # noqa: E402


def _with(**keys):
    return lambda cfg: dict(cfg, **keys)


WRONG = {"state_dropped": _with(control_state_dropped=True),
         "beta_not_doubled": _with(control_beta_not_doubled=True),
         "decay_a_head": _with(control_decay_a_head=True),
         "gate_dropped": _with(control_gate_dropped=True),
         "routed_dropped": _with(routed_scaling_factor=0)}

if __name__ == "__main__":
    serving_control.WRONG = WRONG
    sys.exit(serving_control.main())
