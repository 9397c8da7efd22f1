"""Production serving plane — paged prefix-sharing KV cache, SLO-aware
streaming front-end, multi-replica routing (ROADMAP item 1, the
DeepSpeed-FastGen/MII lineage's service layer, arXiv 2401.08671; prefix
sharing after vLLM's PagedAttention, arXiv 2309.06180).

Layering (each importable on its own):

* :mod:`.prefix_cache` — refcounted page allocator + hash-trie prefix
  index over ``inference/v2``'s block pool.
* :mod:`.scheduler` — :class:`ServingScheduler`, the v2 ragged planner
  with prefix-shared reservations and preemptible decode slots.
* :mod:`.frontend` — submit/stream/cancel, latency-class queues,
  admission control, preemption, replica drain.
* :mod:`.router` — replica health + prefix-affine least-outstanding
  routing.
* :mod:`.synthetic` — the host-only engine for tests and dry-runs.

``build_serving_frontend`` assembles the real thing: N v2 engine
replicas over a model, each with its own KV pool registered in the
memory ledger under distinct per-replica keys.
"""

from __future__ import annotations

from typing import Any, List, Optional

from .frontdoor import (CLASS_HEADER, FrontDoor, FrontDoorParams,
                        door_params_from_config)
from .frontend import (NoHealthyReplicaError, ServingFrontend,
                       ServingHandle, ServingParams)
from .metrics import (CLASSES, LatencyTracker, RequestLog, RequestRecord,
                      ServingMetrics, head_sampled)
from .autoscaler import Autoscaler, ScalingDecision
from .replay import (read_access_log, replay_report, replayable_records,
                     run_replay, synthesize_diurnal_log)
from .slo import (SLOMonitor, SLOObjective, objectives_from_config,
                  render_slo_table, sample_from_rollup,
                  sample_from_snapshot, slo_rows_from_rollup)
from .tracing import (REQUESTS_PREFIX, TRACE_HEADER, AccessLog,
                      assemble_timeline, configure_request_log,
                      configure_tracing_from_config, fetch_request_docs,
                      find_trace, get_request_log, mint_trace_id,
                      render_timeline, sanitize_trace_id,
                      timeline_chrome_trace)
from .prefix_cache import PrefixCache, RefcountedBlockAllocator
from .remote import (NetworkFrontend, NetworkParams, ReplicaEndpoint,
                     discover_endpoints, jsonline_rpc)
from .router import Replica, ReplicaRouter
from .scheduler import ServingScheduler
from .synthetic import FakeClock, SyntheticEngine, synthetic_token
from .worker import SRV_PREFIX, ServingWorker

__all__ = [
    "AccessLog", "Autoscaler", "CLASSES", "CLASS_HEADER", "FakeClock",
    "FrontDoor", "FrontDoorParams", "LatencyTracker", "NetworkFrontend",
    "NetworkParams", "NoHealthyReplicaError", "PrefixCache",
    "REQUESTS_PREFIX", "RefcountedBlockAllocator", "Replica",
    "ReplicaEndpoint", "ReplicaRouter", "RequestLog", "RequestRecord",
    "SLOMonitor", "SLOObjective", "SRV_PREFIX", "ScalingDecision",
    "ServingFrontend", "ServingHandle", "ServingMetrics",
    "ServingParams", "ServingScheduler", "ServingWorker",
    "SyntheticEngine", "TRACE_HEADER", "assemble_timeline",
    "build_serving_frontend", "configure_request_log",
    "configure_tracing_from_config", "discover_endpoints",
    "door_params_from_config", "fetch_request_docs", "find_trace",
    "get_request_log", "head_sampled", "jsonline_rpc", "mint_trace_id",
    "net_params_from_config", "objectives_from_config",
    "params_from_config", "read_access_log", "render_slo_table",
    "render_timeline", "replay_report", "replayable_records",
    "run_replay", "sample_from_rollup", "sample_from_snapshot",
    "sanitize_trace_id", "slo_rows_from_rollup", "synthesize_diurnal_log",
    "synthetic_token", "timeline_chrome_trace",
]


def params_from_config(scfg: Any) -> ServingParams:
    """Map the ``serving.*`` config group onto :class:`ServingParams`."""
    return ServingParams(
        max_outstanding_tokens=int(
            getattr(scfg, "max_outstanding_tokens", 8192)),
        interactive_reserve_frac=float(
            getattr(scfg, "interactive_reserve_frac", 0.10)),
        min_hbm_headroom_frac=float(
            getattr(scfg, "min_hbm_headroom_frac", 0.0)),
        preemption=bool(getattr(scfg, "preemption", True)),
        affinity_min_tokens=int(getattr(scfg, "affinity_min_tokens", 16)),
        temperature=float(getattr(scfg, "temperature", 0.0)),
        eos_token_id=getattr(scfg, "eos_token_id", None),
        stream_buffer=int(getattr(scfg, "stream_buffer", 4096)),
        interactive_ttft_slo_ms=float(
            getattr(scfg, "interactive_ttft_slo_ms", 500.0)),
        preempt_release_pages=bool(
            getattr(scfg, "preempt_release_pages", True)))


def net_params_from_config(ncfg: Any) -> NetworkParams:
    """Map the ``serving.network.*`` config group onto
    :class:`NetworkParams`."""
    return NetworkParams(
        rpc_timeout_s=float(getattr(ncfg, "rpc_timeout_s", 30.0)),
        probe_timeout_s=float(getattr(ncfg, "probe_timeout_s", 2.0)),
        probe_every_s=float(getattr(ncfg, "probe_every_s", 1.0)),
        poll_interval_s=float(getattr(ncfg, "poll_interval_s", 0.005)),
        kv_chunk_bytes=int(getattr(ncfg, "kv_chunk_bytes", 64 * 1024)),
        disaggregate=bool(getattr(ncfg, "disaggregate", False)))


def build_serving_frontend(model: Any, params: Any = None,
                           replicas: int = 1,
                           cache_config: Any = None,
                           max_batch_slots: int = 8,
                           prefill_chunk: int = 128,
                           prefill_batch: int = 2,
                           decode_burst: int = 8,
                           prefix_sharing: bool = True,
                           max_cached_blocks: int = 0,
                           serving_params: Optional[ServingParams] = None,
                           mesh: Any = None) -> ServingFrontend:
    """N real v2 engine replicas behind one front-end.  Each replica
    owns a full KV pool (HBM cost scales with ``replicas``) and is
    registered in the memory ledger under ``serving/replica<i>/*``.  The
    whole of it is the start-up record's root
    ``startup/serving_frontend``."""
    import jax

    from ..telemetry import startup_span

    with startup_span("startup/serving_frontend",
                      {"replicas": int(replicas)}):
        with startup_span("startup/import",
                          {"module": "deepspeed_tpu.inference.v2"}):
            from ..inference.v2 import build_engine_v2

        if params is None:
            with startup_span("startup/place/weights", {"of": "init"}):
                params = model.init_params(jax.random.PRNGKey(0))

        def factory(cc, slots, chunk, pbatch):
            return ServingScheduler(cc, max_batch_slots=slots,
                                    prefill_chunk=chunk,
                                    prefill_batch=pbatch,
                                    prefix_sharing=prefix_sharing,
                                    max_cached_blocks=max_cached_blocks)

        reps: List[Replica] = []
        for i in range(int(replicas)):
            with startup_span("startup/engine_v2", {"replica": i}):
                eng = build_engine_v2(
                    model, params, cache_config=cache_config,
                    max_batch_slots=max_batch_slots,
                    prefill_chunk=prefill_chunk,
                    prefill_batch=prefill_batch, decode_burst=decode_burst,
                    mesh=mesh, scheduler_factory=factory,
                    ledger_key=f"serving/replica{i}/kv_pool")
            reps.append(Replica(eng, i))
        with startup_span("startup/frontend"):
            return ServingFrontend(reps, params=serving_params)
