"""Memory introspection — ``see_memory_usage`` parity.

Reference: ``deepspeed/runtime/utils.py:see_memory_usage(message, force)``
[K]: prints allocator stats at checkpoints in the engine lifecycle (the
single most-used debugging helper in reference issue reports).

Since the memory plane landed (``telemetry/memory/``) this module is a
thin veneer over the :class:`~..telemetry.memory.MemoryLedger`: BOTH
report the same numbers because both read the same account — the ledger
adds per-pool breakdowns (``pool_params_GB`` etc.) when it is enabled,
and honors the device-unresponsive latch so an unresponsive runtime
cannot hang a memory print on a failure path.
"""

from __future__ import annotations

from typing import Dict

from .logging import log_dist


def memory_status() -> Dict[str, float]:
    """Device + host memory numbers (GB), via the memory ledger (plus
    per-pool ``pool_*_GB`` fields when the ledger is enabled)."""
    from ..telemetry.memory import get_memory_ledger

    return get_memory_ledger().status()


def see_memory_usage(message: str, force: bool = False) -> None:
    """Reference signature; logs device HBM + host memory at ``message``."""
    if not force:
        return
    s = memory_status()
    parts = [f"{k}={v:.2f}" for k, v in s.items()]
    log_dist(f"MEMSTATS {message} | " + " ".join(parts))
