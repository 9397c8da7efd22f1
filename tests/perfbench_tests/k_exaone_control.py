"""The controls of the drafting cell's check, read at the cell's own
widths: ``serving_control.py``'s way (greedy tokens of the reference
computed wrongly, through the runner's own ``_logit_gap``), with the wrong
models this family invites.  Two set a key that only the reference reads
and only this control sets: rotary applied in the FULL layers too
(``control_rotary_in_full``: what an adapter that gave the full kind the
window kind's ``theta`` would serve) and Q and K left as projected
(``control_no_qk_norm``); one changes a published key:
``routed_scaling_factor`` read as 1.  The reference in e4m3 is
``serving_control``'s own.

    python3 tests/perfbench_tests/k_exaone_control.py --workload <cell> \
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


WRONG = {"rotary_in_full": _with(control_rotary_in_full=True),
         "no_qk_norm": _with(control_no_qk_norm=True),
         "scale_1": _with(routed_scaling_factor=1.0)}

if __name__ == "__main__":
    serving_control.WRONG = WRONG
    sys.exit(serving_control.main())
