"""The controls of the state-space cell's check, read at the cell's own
widths: ``serving_control.py``'s way (greedy tokens of the reference
computed wrongly, through the runner's own ``_logit_gap``), with the wrong
models this family invites in place of the sparse-expert ones: the SSM
state held in bfloat16 between tokens (``control_state_held_in``, a key
only the reference's recurrence reads and only this control sets: the
program's state is float32 by a constant, not by a key), and one of
the mixer's µP multipliers read as 1 (``ssm_multipliers[3]``, on ``C``).
The reference in e4m3 is ``serving_control``'s own.

    python3 tests/perfbench_tests/falcon_h1_control.py --workload <cell> \
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


def state_in_bfloat16(cfg):
    return dict(cfg, control_state_held_in="bfloat16")


def multiplier_dropped(cfg):
    kept = list(cfg["ssm_multipliers"])
    kept[3] = 1.0
    return dict(cfg, ssm_multipliers=kept)


WRONG = {"state_in_bfloat16": state_in_bfloat16,
         "multiplier_dropped": multiplier_dropped}

if __name__ == "__main__":
    serving_control.WRONG = WRONG
    sys.exit(serving_control.main())
