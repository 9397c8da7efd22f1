"""The controls of the one-part-layer cell's check, read at the cell's own
widths: ``serving_control.py``'s way (greedy tokens of the reference
computed wrongly, through the runner's own ``_logit_gap``), with the wrong
models this family invites: the recurrence's state DROPPED
(``control_state_dropped``, a key only the reference's recurrence reads and
only this control sets: every token starts from a zero state, which is
what a mixer layer reading another layer's slot, or a pool indexed by the
model's layer, amounts to), one routed expert a token fewer
(``num_experts_per_tok - 1``), the routed experts' scale dropped
(``routed_scaling_factor`` 1), and the routed sum dropped whole
(``routed_scaling_factor`` 0: what an expert kernel that writes zeros, or
a plan that loses its rows, amounts to).  The reference in e4m3 is
``serving_control``'s own.

    python3 tests/perfbench_tests/nemotron_h_control.py --workload <cell> \
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


def state_dropped(cfg):
    return dict(cfg, control_state_dropped=True)


def scale_dropped(cfg):
    return dict(cfg, routed_scaling_factor=1)


def routed_dropped(cfg):
    return dict(cfg, routed_scaling_factor=0)


WRONG = {"state_dropped": state_dropped,
         "one_expert_fewer": serving_control.WRONG["one_expert_fewer"],
         "scale_dropped": scale_dropped,
         "routed_dropped": routed_dropped}

if __name__ == "__main__":
    serving_control.WRONG = WRONG
    sys.exit(serving_control.main())
