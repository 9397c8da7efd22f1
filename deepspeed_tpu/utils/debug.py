"""Debug / sanitizer mode.

Reference: the closest surfaces are ``deepspeed.comm`` async-op debug
checks, NaN/Inf grad screening (``check_grad_overflow``), and ``DS_DEBUG``
env logging [K] (SURVEY §5.2 — no TSAN/ASAN integration exists upstream).

TPU story per SURVEY §5.2's plan: XLA programs are race-free; the risk
surface is host↔device async (offload streams, async checkpointing) and
silent NaN propagation.  Debug mode therefore:

* forces a device fence after every ``train_step`` (the loss scalar is
  fetched, which waits for the step), so failures surface at the step
  that caused them;
* enables ``jax_debug_nans`` (XLA re-runs the failing op un-jitted and
  points at it) and raises on non-finite loss.

Activated by ``configure(...)`` or env ``DS_DEBUG=1`` at import.
"""

from __future__ import annotations

import os
from typing import Optional

import jax

from .logging import log_dist

_FORCE_SYNC = False
_NAN_CHECK = False


def configure(force_sync: Optional[bool] = None,
              nan_check: Optional[bool] = None) -> None:
    """Turn sanitizer behaviors on/off (both default ON when called)."""
    global _FORCE_SYNC, _NAN_CHECK
    if force_sync is None and nan_check is None:
        force_sync = nan_check = True
    if force_sync is not None:
        _FORCE_SYNC = bool(force_sync)
    if nan_check is not None:
        _NAN_CHECK = bool(nan_check)
        jax.config.update("jax_debug_nans", _NAN_CHECK)
    log_dist(f"debug mode: force_sync={_FORCE_SYNC} nan_check={_NAN_CHECK}")


def enabled() -> bool:
    return _FORCE_SYNC or _NAN_CHECK


def check_step(metrics) -> None:
    """Called by the engine after each train_step when debug mode is on."""
    if not (_FORCE_SYNC or _NAN_CHECK):
        return
    loss = float(metrics["loss"])  # real fence: drains the dispatch queue
    if _NAN_CHECK:
        import math

        if not math.isfinite(loss):
            raise FloatingPointError(
                f"non-finite loss {loss} (debug nan_check); enable "
                "jax_debug_nans tracebacks by re-running the step un-jitted")


if os.environ.get("DS_DEBUG", "") not in ("", "0", "false"):
    configure()
