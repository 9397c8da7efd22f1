"""Memory observability plane (ISSUE 7).

Three cooperating pieces that make *memory* — the entire point of the
ZeRO/offload/Infinity lineage — a first-class observable, symmetric to
the perf plane:

* :mod:`.ledger` — the :class:`MemoryLedger`: per-pool byte accounting
  (params, grads, optimizer shards, activations, KV cache, swap
  staging, snapshot buffers, collective scratch) fed by registration
  hooks at the real allocation sites, cross-checked each sample against
  ``device.memory_stats()`` and a ``jax.live_arrays()`` census; plus
  the bounded device-liveness probe an unresponsive runtime can't hang.
* :mod:`.oom` — OOM forensics: recognize ``RESOURCE_EXHAUSTED``, write
  ``memory.json`` (pool breakdown + top-K live arrays with provenance)
  into the flight-recorder bundle, raise a descriptive
  :class:`HBMExhaustedError` naming the top pools.
* :mod:`.cli` — ``python -m deepspeed_tpu.telemetry mem {show,top,diff}``
  (diff exits 3 on a leak verdict).
"""

from .ledger import (IO_KINDS, POOLS, MemoryLedger, clear_device_unresponsive,
                     configure_memory_ledger, device_unresponsive,
                     get_memory_ledger, host_memory_bytes,
                     mark_device_unresponsive, probe_device_liveness,
                     tree_nbytes, unique_key)
from .oom import (MEMORY_JSON, HBMExhaustedError, augment_bundle_on_oom,
                  handle_oom, is_oom_error, oom_report, top_pools_of,
                  write_memory_json)

__all__ = [
    "MemoryLedger", "get_memory_ledger", "configure_memory_ledger",
    "POOLS", "IO_KINDS", "tree_nbytes", "unique_key", "host_memory_bytes",
    "probe_device_liveness", "mark_device_unresponsive",
    "clear_device_unresponsive", "device_unresponsive",
    "HBMExhaustedError", "is_oom_error", "handle_oom", "oom_report",
    "top_pools_of", "write_memory_json", "augment_bundle_on_oom",
    "MEMORY_JSON",
]
