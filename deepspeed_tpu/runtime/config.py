"""ds_config parsing + validation.

Capability parity with the reference ``deepspeed/runtime/config.py`` [K]; key
inventory and batch-size invariant from SURVEY §5.6.  Accepts the same JSON
documents (path, dict, or base64 string [L ACC-DS:145-156]) an HF/accelerate
user would pass to DeepSpeed, including ``"auto"`` placeholders.

Batch math [L HF-DS:139-140, ACC:2223-2228]:

    train_batch_size = micro_batch × gradient_accumulation_steps × dp_world

where ``dp_world = world_size / (tp × pp × sp)`` — sequence-parallel ranks
consume the SAME batch shards (they split the sequence dim), so sp divides
out exactly like tp/pp.
"""

from __future__ import annotations

import base64
import json
import os
from typing import Any, Dict, List, Literal, Optional, Union

from pydantic import Field, model_validator

from ..utils.logging import logger
from .config_utils import AUTO, DeepSpeedConfigModel, is_auto
from .zero.config import DeepSpeedZeroConfig


# ---------------------------------------------------------------------------
# precision
# ---------------------------------------------------------------------------


class FP16Config(DeepSpeedConfigModel):
    enabled: Union[bool, str] = False  # may be "auto"
    auto_cast: bool = False
    loss_scale: float = 0.0  # 0 → dynamic
    initial_scale_power: int = 16
    loss_scale_window: int = 1000
    hysteresis: int = 2
    consecutive_hysteresis: bool = False
    min_loss_scale: float = 1.0


class BF16Config(DeepSpeedConfigModel):
    enabled: Union[bool, str] = False
    # reference: bf16 grad accumulation dtype option
    immediate_grad_update: bool = True


class AMPConfig(DeepSpeedConfigModel):
    enabled: Union[bool, str] = False
    opt_level: str = "O1"


# ---------------------------------------------------------------------------
# optimizer / scheduler
# ---------------------------------------------------------------------------


class OptimizerParams(DeepSpeedConfigModel):
    lr: Union[float, str] = 1e-3
    betas: Union[List[float], str] = Field(default_factory=lambda: [0.9, 0.999])
    eps: Union[float, str] = 1e-8
    weight_decay: Union[float, str] = 0.0
    momentum: float = 0.0  # sgd
    # onebit/compression extras accepted via extra="allow"


class OptimizerConfig(DeepSpeedConfigModel):
    type: str = "AdamW"
    params: OptimizerParams = Field(default_factory=OptimizerParams)
    legacy_fusion: bool = False


class SchedulerParams(DeepSpeedConfigModel):
    # WarmupLR / WarmupDecayLR / WarmupCosineLR
    warmup_min_lr: Union[float, str] = 0.0
    warmup_max_lr: Union[float, str] = 1e-3
    warmup_num_steps: Union[int, str] = 1000
    warmup_type: str = "log"
    total_num_steps: Union[int, str, None] = None
    # WarmupCosineLR
    warmup_min_ratio: float = 0.0
    cos_min_ratio: float = 1e-4
    # OneCycle / LRRangeTest take their own keys via extra="allow"


class SchedulerConfig(DeepSpeedConfigModel):
    type: str = "WarmupLR"
    params: SchedulerParams = Field(default_factory=SchedulerParams)


# ---------------------------------------------------------------------------
# feature subsystems (schema parity; behavior lives in their modules)
# ---------------------------------------------------------------------------


class ActivationCheckpointingConfig(DeepSpeedConfigModel):
    """Reference ``activation_checkpointing`` group.  On TPU these map onto
    ``jax.checkpoint`` policies: ``partition_activations`` → remat with
    sharded residuals; ``cpu_checkpointing`` → offload policy."""

    partition_activations: bool = False
    cpu_checkpointing: bool = False
    contiguous_memory_optimization: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False


class AioConfig(DeepSpeedConfigModel):
    """NVMe async-IO engine knobs (ZeRO-Infinity) [L ACC-DC:1187-1194]."""

    block_size: int = 1048576
    queue_depth: int = 8
    thread_count: int = 1
    single_submit: bool = False
    overlap_events: bool = True
    use_gds: bool = False


class CommsLoggerConfig(DeepSpeedConfigModel):
    enabled: bool = False
    verbose: bool = False
    #: also count in-graph collectives per EXECUTION via effectful host
    #: callbacks (per-local-shard counts; measurable overhead — see
    #: comm.CommsLogger)
    exec_counts: bool = False
    prof_all: bool = True
    prof_ops: List[str] = Field(default_factory=list)
    debug: bool = False


class FlopsProfilerConfig(DeepSpeedConfigModel):
    enabled: bool = False
    recompute_fwd_factor: float = 0.0
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None


class TensorBoardConfig(DeepSpeedConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedJobName"


class WandbConfig(DeepSpeedConfigModel):
    enabled: bool = False
    group: Optional[str] = None
    team: Optional[str] = None
    project: str = "deepspeed"


class CSVConfig(DeepSpeedConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedJobName"


class TelemetryWatchdogConfig(DeepSpeedConfigModel):
    """``telemetry.watchdog`` — hang/straggler watchdog
    (``telemetry/watchdog.py``).  Fed by ``engine.train_step`` progress
    notifications (comms-logger activity is a secondary liveness
    signal); on no progress within ``hang_timeout_s`` it dumps a
    flight-recorder debug bundle and runs ``action``.  Independent of
    ``telemetry.enabled`` — a production run can keep the hub off and
    the watchdog on."""

    enabled: bool = False
    hang_timeout_s: float = 300.0
    #: 0 → hang_timeout_s / 4, capped at 10s
    poll_interval_s: float = 0.0
    action: Literal["log", "raise", "exit"] = "log"
    #: treat comms-logger counter movement as liveness (a long compile or
    #: giant eager collective is slow, not hung)
    comm_liveness: bool = True
    #: bounded device-liveness check on the trip path: jax.devices()/
    #: memory_stats() on a deadline thread, so a runtime that stopped
    #: answering yields a fail-fast bundle with a ``device_unresponsive``
    #: annotation instead of an unbounded hang
    device_probe: bool = True
    device_probe_timeout_s: float = 20.0
    #: byte cap on the heartbeat payload (JSON size).  The payload is
    #: versioned (``v``) and fields drop in a deterministic order
    #: (``telemetry.watchdog.HEARTBEAT_DROP_ORDER``) when over the cap,
    #: counted by ``elastic/heartbeat_fields_dropped_total``; <= 0
    #: disables the cap
    heartbeat_max_bytes: int = 1024


class TelemetryHealthConfig(DeepSpeedConfigModel):
    """``telemetry.health`` — streaming anomaly detectors over the
    engine's StepRecords (``telemetry/health.py``): NaN/Inf loss,
    loss-spike z-score, grad-norm explosion, fp16 loss-scale collapse,
    throughput regression.  Active when telemetry step records are on."""

    enabled: bool = True
    window: int = 32
    min_points: int = 8
    loss_spike_zscore: float = 6.0
    grad_norm_ratio: float = 10.0
    loss_scale_floor: float = 1.0
    consecutive_scale_drops: int = 3
    throughput_frac: float = 0.5
    #: steps whose compile_ms >= frac * step_time are compile-dominated:
    #: excluded from the throughput-regression window (and from the
    #: watchdog step-time EWMA)
    compile_dominated_frac: float = 0.5
    #: recompile events within `window` steps that raise a
    #: recompile_storm health event; <= 0 disables the rule
    recompile_storm_threshold: int = 3
    #: raise a control_plane_degraded event when a rendezvous-store
    #: client exhausts its retry budget (one per outage streak)
    control_plane: bool = True


class FlightRecorderConfig(DeepSpeedConfigModel):
    """``telemetry.flight_recorder`` — the black box
    (``telemetry/flight_recorder.py``): bounded rings of recent
    StepRecords/HealthEvents/annotations, dumped as a debug bundle
    (manifest + Chrome-trace slice + env report + per-thread stacks) on
    demand, fatal signal, unhandled exception, or watchdog trip."""

    enabled: bool = True
    max_records: int = 256
    #: default: <telemetry.output_path>/<job_name>/debug_bundles
    output_path: str = ""
    #: install SIGTERM/SIGABRT handlers + sys.excepthook at initialize()
    install_handlers: bool = True
    #: keep only the newest N bundle dirs per dump dir (repeated watchdog
    #: trips must not fill the disk); <= 0 keeps everything
    retain_bundles: int = 5


class TelemetryAggregationConfig(DeepSpeedConfigModel):
    """``telemetry.aggregation`` — the cross-host observability plane
    (``telemetry/{aggregator,collective_ledger}.py``): each host
    publishes its debug bundle through the elastic rendezvous store
    (shared-FS fallback) and rank 0 / the operator CLI assembles ONE
    cluster archive; a per-rank collective ledger rides the heartbeats
    for live desync detection and lands full tails in the archive."""

    enabled: bool = False
    #: store-value chunk size for published bundle tarballs
    chunk_bytes: int = 262144
    #: size cap per published bundle (largest side files dropped first;
    #: the manifest always ships)
    max_bundle_bytes: int = 33554432
    #: shared-filesystem fallback drop dir ("" = store transport only)
    shared_fs_path: str = ""
    #: rank-0 / operator collect timeout
    collect_timeout_s: float = 30.0
    #: per-rank monotonic ledger of collectives fed by the comms logger
    ledger_enabled: bool = True
    ledger_max_entries: int = 4096
    #: ledger entries embedded in each debug bundle (comparison window)
    ledger_tail: int = 64
    #: also feed the ledger's EXEC lane from execution probes
    #: (comms_logger.record_exec).  Off by default: device callbacks are
    #: unordered, so the exec chain is per-host forensics only — the
    #: trace-sourced census (profiling.collective_trace.feed_exec_census)
    #: is the cross-rank-comparable execution-order source
    ledger_exec_feed: bool = False
    #: cross-process metrics rollup (telemetry/rollup.py): every worker
    #: ships its registry snapshot + step-record batch on the publisher
    #: tick; rank 0 merges them into one per-node-labeled view
    metrics_rollup: bool = True
    #: publish cadence (seconds) for the snapshot/step batch; the
    #: heartbeat tick is the transport, this bounds its payload rate
    metrics_push_every_s: float = 2.0
    #: compact StepRecord streaming to the rollup: bounded ring, batched
    #: on the publisher tick, degraded-mode buffered (flushes exactly
    #: once after a store restart — the rollup dedups by sequence)
    step_stream: bool = True
    step_stream_len: int = 256


class TelemetryMemoryConfig(DeepSpeedConfigModel):
    """``telemetry.memory`` — the memory observability plane
    (``telemetry/memory/``): the per-pool HBM/host byte ledger fed by
    allocation-site hooks, per-step ``peak_hbm_bytes``/RSS/swap-IO on
    StepRecords, OOM forensics (``memory.json`` + descriptive
    ``HBMExhaustedError``), and the memory health rules.  Active when
    ``telemetry.enabled`` is on or a flight recorder exists."""

    enabled: bool = True
    #: jax.live_arrays() census cadence in steps (O(all buffers) — too
    #: expensive per step); <= 0 disables the census
    live_census_every: int = 16
    #: live arrays kept in forensics breakdowns (memory.json, `mem top`)
    top_k: int = 10
    #: memory_pressure health rule: HBM used fraction threshold and the
    #: consecutive steps above it before the rule fires; frac <= 0
    #: disables
    pressure_frac: float = 0.92
    pressure_steps: int = 8
    #: host_memory_leak health rule: consecutive-growth window and the
    #: minimum growth of the newest sample over the window median;
    #: window < 2 disables
    leak_window: int = 16
    leak_frac: float = 0.05


class TelemetryNumericsConfig(DeepSpeedConfigModel):
    """``telemetry.numerics`` — the numerics observability plane
    (``telemetry/numerics/``): in-graph per-layer tensor-health probes
    (nonfinite/absmax/underflow/saturation stat vectors riding the
    step's aux output), grad-path norms and update/param ratios, MoE
    gate telemetry, NaN origin bisection on ``nan_loss``
    (``numerics.json`` + ``NonFiniteOriginReport``), and the
    ``underflow_creep``/``layer_grad_explosion``/``router_collapse``
    health rules.  Probes are an IDENTITY when disabled — same jaxpr,
    zero recompiles."""

    enabled: bool = False
    #: sampled-capture cadence in steps: every Nth step dispatches the
    #: probed step program (its own jit site — compiled once); <= 0
    #: means forensic-only (the probed program never runs unless a
    #: non-finite loss triggers the bisection)
    every: int = 32
    #: run the all-probes forward bisection when a fenced loss goes
    #: non-finite, naming the first bad layer in the health event /
    #: rollback annotation / numerics.json
    forensic_on_nan: bool = True
    #: underflow_creep health rule: worst per-probe bf16-subnormal
    #: fraction threshold and consecutive sampled captures above it
    #: before the rule fires (suggesting a loss-scale bump); frac <= 0
    #: disables
    underflow_frac: float = 0.05
    underflow_steps: int = 3
    #: layer_grad_explosion health rule: a single layer's grad norm
    #: exceeding ``ratio`` x the median layer grad norm (with the
    #: median above ``floor``) names that layer; ratio <= 0 disables
    layer_grad_ratio: float = 20.0
    layer_grad_floor: float = 1e-8
    #: router_collapse health rule: mean gating entropy (nats) below
    #: this floor for ``entropy_steps`` consecutive MoE captures means
    #: the router is sending everything to one expert; floor <= 0
    #: disables
    entropy_floor: float = 0.30
    entropy_steps: int = 3
    #: sample MoE gate telemetry (moe/* gauges) even when ``enabled``
    #: is false — the gate stats are already computed by top_k_gating,
    #: so publishing them costs one extra scan output, not a probe pass
    moe_gauges: bool = True


class TelemetryPerfConfig(DeepSpeedConfigModel):
    """``telemetry.perf`` — the performance observability plane
    (``telemetry/perf/``): compile/recompile tracking over every engine
    jit site, the goodput wall-clock ledger, and the perf-regression
    sentinel's knobs.  Active when ``telemetry.enabled`` is on."""

    enabled: bool = True
    #: tracked_jit at every engine jit site: compile events, recompile
    #: cause diffs, per-site program table in debug bundles
    compile_tracker: bool = True
    compile_max_events: int = 512
    #: classify step-loop wall time into productive/compile/stall/
    #: recovery/checkpoint buckets; rolling goodput rides heartbeats
    goodput: bool = True
    #: rolling-goodput window (seconds) for the heartbeat fraction
    goodput_window_s: float = 600.0
    #: step-anatomy plane (``telemetry/anatomy``): harvest FLOPs/bytes
    #: rooflines from every AOT compile, enable engine.capture_anatomy
    anatomy: bool = True
    #: fenced steps per capture_anatomy trace window
    anatomy_capture_steps: int = 2
    #: programs in the roofline predicted-vs-measured join
    anatomy_top_k: int = 5


class TelemetryProfilerConfig(DeepSpeedConfigModel):
    """``telemetry.profiler`` — the fleet-synchronized profiler capture
    plane (``telemetry/profiler/``): each worker polls the rendezvous
    store for ``telemetry profile`` capture commands, arms
    ``jax.profiler`` for the agreed step-index window, publishes its
    measured device lanes + calibration report back through the store,
    and (optionally) runs a duty-cycled continuous capture.  When
    disabled the train step never sees the plane — same jaxpr, zero
    recompiles."""

    enabled: bool = True
    #: bounded ring of on-disk trace dirs per worker (oldest evicted)
    ring: int = 4
    #: steps of arming lead when proposing the shared capture window
    lead: int = 3
    #: duty-cycle continuous capture: percent of each period spent
    #: tracing (0 disables); capture time is booked to the goodput
    #: ``profiler`` bucket
    duty_cycle_pct: float = 0.0
    #: steps per duty-cycle period
    duty_period_steps: int = 64
    #: trace-dir ring location (default: a tmpdir per process)
    out_dir: str = ""


class TelemetryConfig(DeepSpeedConfigModel):
    """``telemetry`` config group — the unified telemetry subsystem
    (``deepspeed_tpu/telemetry/``): span tracer + metrics registry +
    per-step records, exported as JSONL / Prometheus text / Chrome trace.
    Registered as a fourth ``MonitorMaster`` backend, so it composes with
    the ``tensorboard``/``wandb``/``csv_monitor`` groups."""

    enabled: bool = False
    output_path: str = ""            # base dir (default: telemetry_logs/)
    job_name: str = "DeepSpeedJobName"
    #: append one JSON object per event/step to <out>/events.jsonl
    jsonl: bool = True
    #: write Prometheus text exposition to <out>/metrics.prom on flush()
    prometheus: bool = True
    #: export host spans as <out>/trace.json (Chrome-trace JSON,
    #: correlatable with profiling/collective_trace.py device lanes)
    chrome_trace: bool = False
    #: assemble a per-optimizer-step StepRecord in the engine
    step_records: bool = True
    #: fence the device (fetch the loss scalar) before stamping step time —
    #: step_time_ms then measures DEVICE time, not dispatch backpressure.
    #: false = ASYNC recording: no per-step sync at all — records keep
    #: dispatch time + comm/memory stats but carry NaN metric fields and
    #: no rates (pulling loss would block; the whole point is overlap)
    device_fence: bool = True
    max_span_events: int = 100000
    watchdog: TelemetryWatchdogConfig = Field(
        default_factory=TelemetryWatchdogConfig)
    health: TelemetryHealthConfig = Field(
        default_factory=TelemetryHealthConfig)
    flight_recorder: FlightRecorderConfig = Field(
        default_factory=FlightRecorderConfig)
    aggregation: TelemetryAggregationConfig = Field(
        default_factory=TelemetryAggregationConfig)
    perf: TelemetryPerfConfig = Field(default_factory=TelemetryPerfConfig)
    memory: TelemetryMemoryConfig = Field(
        default_factory=TelemetryMemoryConfig)
    numerics: TelemetryNumericsConfig = Field(
        default_factory=TelemetryNumericsConfig)
    profiler: TelemetryProfilerConfig = Field(
        default_factory=TelemetryProfilerConfig)


class ServingTracingConfig(DeepSpeedConfigModel):
    """``serving.tracing`` config group — distributed request tracing
    (``deepspeed_tpu/serving/tracing.py``): per-request lifecycle
    records (queue wait, admission, preempt/replay, prefill/transfer/
    decode phases, token timings) in a bounded ring, head-based sampled
    with always-on capture of anomalous requests, shipped cross-process
    over the telemetry rollup and assembled by ``python -m
    deepspeed_tpu.serving trace <id>``."""

    enabled: bool = True
    #: head-based sample rate (deterministic on the trace id, so every
    #: process that touches a request reaches the same verdict);
    #: anomalous requests (replayed / preempted / failed / slow TTFT)
    #: are ALWAYS recorded, even at 0.0
    sample_rate: float = 1.0
    #: committed records retained (the ring is also the window each
    #: rollup publication ships — the store holds the recent history)
    ring: int = 256
    #: TTFT above this (ms) force-samples the request as anomalous
    #: (0 disables the threshold)
    anomaly_ttft_ms: float = 2000.0
    #: per-record cap on token timestamps kept for gap percentiles
    token_timings: int = 512


class ServingSLOConfig(DeepSpeedConfigModel):
    """``serving.slo`` config group — declarative service-level
    objectives (``deepspeed_tpu/serving/slo.py``): per-class TTFT/TPOT
    p99 bounds, availability (1 − 429/5xx rate), and token-budget
    saturation, evaluated continuously against the PR-13 metrics
    rollup with fast/slow multi-window burn rates.  Alert transitions
    become health events, ``serving_slo_*`` gauges, and flight-recorder
    annotations."""

    enabled: bool = True
    #: per-class TTFT p99 bound (ms); 0 disables that class's objective
    interactive_ttft_p99_ms: float = 2000.0
    batch_ttft_p99_ms: float = 10000.0
    background_ttft_p99_ms: float = 0.0
    #: per-class TPOT p50 bound (ms/token); 0 disables
    interactive_tpot_p50_ms: float = 500.0
    #: availability objective: 1 − (429 + 5xx) / requests
    availability_target: float = 0.999
    #: queued-token budget saturation bound (fraction of
    #: ``serving.network.queue_token_budget`` queued, worst class)
    token_budget_saturation: float = 0.9
    #: multi-window burn-rate evaluation windows (seconds) — the alert
    #: fires only when BOTH windows burn error budget faster than
    #: ``burn_rate_threshold`` (fast window confirms it is happening
    #: NOW, slow window that it is sustained)
    fast_window_s: float = 60.0
    slow_window_s: float = 300.0
    burn_rate_threshold: float = 2.0
    #: evaluation cadence (s) — each tick consumes one rollup snapshot
    evaluate_every_s: float = 1.0


class ServingAutoscalerConfig(DeepSpeedConfigModel):
    """``serving.autoscaler`` config group — the rollup-driven policy
    loop (``deepspeed_tpu/serving/autoscaler.py``): replaces dead
    workers through the launcher, scales decode workers on queue depth
    + token-budget saturation, scales prefill workers on TTFT prefill
    share, and scales down only through the kill-safe drain path.
    Every decision is a trace-id-stamped scaling event riding the
    telemetry rollup into ``cluster_trace.json`` and debug bundles."""

    enabled: bool = False
    min_workers: int = 1
    max_workers: int = 8
    #: scale decode UP past this mean queued-requests-per-worker
    queue_depth_high: float = 4.0
    #: scale decode DOWN below this (with the fleet above min_workers)
    queue_depth_low: float = 0.5
    #: scale decode UP past this outstanding-token saturation (fraction
    #: of ``serving.max_outstanding_tokens`` per worker)
    token_saturation_high: float = 0.85
    #: scale prefill UP past this fraction of TTFT spent in prefill
    #: (disaggregated fleets only)
    ttft_prefill_share_high: float = 0.6
    #: consecutive breaching evaluations before a scaling action
    hysteresis_ticks: int = 3
    #: minimum seconds between scaling actions (replacements exempt —
    #: a dead worker is replaced immediately)
    cooldown_s: float = 30.0
    evaluate_every_s: float = 1.0


class ServingConfig(DeepSpeedConfigModel):
    """``serving`` config group — the production serving plane
    (``deepspeed_tpu/serving/``): paged prefix-sharing KV cache over the
    inference-v2 block pool, an SLO-aware streaming front-end
    (submit/stream/cancel with ``interactive``/``batch``/``background``
    latency classes, admission control, preemptible decode slots), and
    multi-replica routing (prefix affinity + least outstanding tokens,
    replica health from the device-liveness latch / hang watchdog)."""

    enabled: bool = False
    #: engine replicas behind the router (each owns a full KV pool)
    replicas: int = 1
    #: share identical prompt-prefix pages across requests (the trie)
    prefix_sharing: bool = True
    #: cached (refcount-0, trie-indexed) pages kept at most; 0 = bounded
    #: only by pool pressure (LRU reclaimed by allocation)
    prefix_cache_max_blocks: int = 0
    #: per-replica admitted-but-unfinished token budget
    max_outstanding_tokens: int = 8192
    #: fraction of the allocatable pool kept clear of batch/background
    #: reservations so interactive admission never waits on pages
    interactive_reserve_frac: float = 0.10
    #: admit only interactive work when the memory ledger reports HBM
    #: headroom below this fraction (0 disables the check)
    min_hbm_headroom_frac: float = 0.0
    #: interactive may preempt background decode slots (KV retained)
    preemption: bool = True
    #: router prefix-affinity threshold (tokens)
    affinity_min_tokens: int = 16
    #: decode sampling temperature (0 = greedy; greedy makes the
    #: replica-death re-queue splice exact)
    temperature: float = 0.0
    eos_token_id: Optional[int] = None
    #: per-handle stream bound (tokens): a consumer stalled past this
    #: many unread tokens loses the oldest (drop-oldest; pump never
    #: blocks)
    stream_buffer: int = 4096
    #: interactive TTFT target (ms), exported with the serving metrics
    interactive_ttft_slo_ms: float = 500.0
    #: under the HBM-headroom floor, preemption RELEASES the victim's
    #: KV pages to the cached-free LRU tier (re-admission recomputes
    #: via the prefix trie) instead of keeping them resident
    preempt_release_pages: bool = True
    #: the network serving plane (HTTP/SSE front door,
    #: process-per-replica workers, disaggregated prefill/decode)
    network: "ServingNetworkConfig" = Field(
        default_factory=lambda: ServingNetworkConfig())
    #: distributed request tracing (per-request lifecycle records,
    #: cross-process timeline assembly)
    tracing: ServingTracingConfig = Field(
        default_factory=ServingTracingConfig)
    #: declarative SLOs with multi-window burn-rate alerting over the
    #: cross-process metrics rollup
    slo: ServingSLOConfig = Field(default_factory=ServingSLOConfig)
    #: rollup-driven fleet autoscaler (traced scaling decisions,
    #: drain-path scale-down)
    autoscaler: ServingAutoscalerConfig = Field(
        default_factory=ServingAutoscalerConfig)


class ServingNetworkConfig(DeepSpeedConfigModel):
    """``serving.network`` config group — the network serving plane
    (``deepspeed_tpu/serving/{frontdoor,worker,remote,kv_transfer}``):
    an HTTP/SSE front door over the submit/stream/cancel API,
    process-per-replica worker backends registered in the rendezvous
    store, and disaggregated prefill/decode over the page-granular
    checksum-gated KV transport."""

    enabled: bool = False
    #: front-door bind address (port 0 = ephemeral)
    host: str = "127.0.0.1"
    port: int = 0
    #: replica worker PROCESSES to launch behind the door
    workers: int = 2
    #: of the fleet, dedicated prefill replicas (with ``disaggregate``)
    prefill_workers: int = 1
    #: run the prefill -> KV-page-stream -> decode pipeline
    disaggregate: bool = False
    #: per-class queued-token budget: past it the door answers 429 +
    #: Retry-After (backpressure) instead of queueing
    queue_token_budget: int = 32768
    retry_after_s: float = 1.0
    #: SSE idle heartbeat period (also dead-client detection cadence)
    sse_heartbeat_s: float = 5.0
    #: KV-page transfer chunk size (base64 chars per protocol line)
    kv_chunk_bytes: int = 64 * 1024
    #: network front-end pump idle sleep
    poll_interval_s: float = 0.005
    #: worker health-probe (ping) timeout
    probe_timeout_s: float = 2.0
    #: ping cadence (a fresh TCP connection per endpoint per probe;
    #: transport failures mark endpoints dead instantly regardless)
    probe_every_s: float = 1.0
    rpc_timeout_s: float = 30.0
    #: rendezvous store for worker registration/discovery (None: the
    #: launcher wires endpoints directly)
    store_endpoint: Optional[str] = None
    #: front-door structured access log: one JSONL line per request
    #: (ts, method, path, status, class, trace id, duration, tokens,
    #: close reason); "" disables
    access_log: str = ""
    #: rotate the live access log past this size (one ``.1``
    #: predecessor kept)
    access_log_max_bytes: int = 8 << 20


class ResilienceConfig(DeepSpeedConfigModel):
    """``resilience`` config group — the self-healing plane
    (``deepspeed_tpu/resilience/``): tiered async snapshots of the full
    training state, an automatic recovery policy (rollback on NaN/scale
    collapse, resume-from-snapshot on restart, emergency save on
    watchdog trip), and a deterministic fault-injection harness."""

    enabled: bool = False
    #: engine-driven snapshot cadence (optimizer steps)
    snapshot_interval: int = 50
    #: tier-1 flush root (``<dir>/snap-<step>[-emergency]/``)
    snapshot_dir: str = "resilience_snapshots"
    #: newest tier-1 snapshot dirs kept on disk (double-buffered default)
    keep_snapshots: int = 2
    #: tier 0 (double-buffered in-host-memory copies) is structurally
    #: required — tiers 1/2 flush FROM it — so it has no off switch.
    #: tier 1: async background flush through the checkpoint engine,
    #: checksummed manifest gating every restore
    disk_tier: bool = True
    #: "sync" | "async" — tier-1 flush mode (async = the whole flush
    #: job runs on a background worker thread over the tier-0 host
    #: copy; only the device→host capture blocks the step path)
    flush_engine: Literal["sync", "async"] = "async"
    #: tier 2: replicate each flushed snapshot to the buddy host's store
    #: slot via the chunked rendezvous transport (needs an elastic store)
    buddy_tier: bool = False
    buddy_chunk_bytes: int = 262144
    buddy_max_bytes: int = 268435456
    #: health-event kinds that trigger an automatic rollback
    rollback_on: List[str] = Field(default_factory=lambda: [
        "nan_loss", "loss_scale_collapse"])
    #: recoveries (rollbacks + resumes) before the policy gives up
    max_recoveries: int = 3
    #: capped exponential backoff between recoveries
    backoff_base_s: float = 1.0
    backoff_max_s: float = 60.0
    #: healthy steps after which the recovery budget re-arms
    recovery_reset_steps: int = 100
    #: flush the newest tier-0 snapshot to disk when the watchdog trips
    #: (the host is responsive enough to run the listener; params may be
    #: hung on device, but the host copy is already taken)
    emergency_save_on_trip: bool = True
    #: deterministic fault specs (``kind@step[:k=v,...]``), e.g.
    #: ``kill_rank@120:rank=1``, ``nan_loss@64``, ``stall@32:seconds=90``,
    #: ``corrupt_snapshot@40``; the DS_FAULTS env var appends more
    faults: List[str] = Field(default_factory=list)


class CheckpointConfig(DeepSpeedConfigModel):
    tag_validation: str = "Warn"
    load_universal: bool = False
    use_node_local_storage: bool = False  # [L HF-DS:179-182]
    parallel_write: Dict[str, Any] = Field(default_factory=dict)
    writer: Optional[Dict[str, Any]] = None
    #: {"type": "sync"|"async"} — async = orbax AsyncCheckpointer (the
    #: reference's DecoupledCheckpointEngine role)
    checkpoint_engine: Dict[str, Any] = Field(default_factory=dict)


class TensorParallelConfig(DeepSpeedConfigModel):
    """``tensor_parallel`` group (AutoTP training) [L HF-DS:464]."""

    autotp_size: int = 1
    tp_overlap_comm: bool = False


class SequenceParallelConfig(DeepSpeedConfigModel):
    """TPU-native grouping of the fork's ALST/Ulysses knobs."""

    sp_size: int = 1
    seq_length_is_variable: bool = True
    attention_backend: str = "auto"  # auto|splash|dot


class PipelineConfig(DeepSpeedConfigModel):
    stages: int = 1
    partition_method: str = "parameters"
    num_micro_batches: Optional[int] = None
    activation_checkpoint_interval: int = 0
    pipe_partitioned: bool = True
    grad_partitioned: bool = True
    #: which schedule executes when stages > 1 (reference TrainSchedule =
    #: 1f1b; SURVEY §3.5).  "1f1b": one-forward-one-backward via
    #: parallel.pipeline.pipeline_train_1f1b (O(pp) stashed activations);
    #: "gpipe": fill/drain forward + autodiff backward; "interleaved":
    #: gpipe with virtual stages
    schedule: str = "1f1b"


class ElasticityConfig(DeepSpeedConfigModel):
    enabled: bool = False
    max_train_batch_size: int = 2000
    micro_batch_sizes: List[int] = Field(default_factory=lambda: [2, 4, 6])
    min_gpus: int = 1
    max_gpus: int = 10000
    min_time: int = 0
    prefer_larger_batch: bool = True
    ignore_non_elastic_batch_info: bool = False
    version: float = 0.1


class AutotuningConfig(DeepSpeedConfigModel):
    enabled: bool = False
    fast: bool = True
    results_dir: str = "autotuning_results"
    exps_dir: str = "autotuning_exps"
    overwrite: bool = False
    metric: str = "throughput"
    start_profile_step: int = 3
    end_profile_step: int = 5
    tuner_type: str = "gridsearch"
    tuner_early_stopping: int = 5
    tuner_num_trials: int = 50
    max_train_batch_size: Optional[int] = None
    mp_size: int = 1


class TuningConfig(DeepSpeedConfigModel):
    """``tuning`` config group — the telemetry-driven autotuning plane
    (``deepspeed_tpu/tuning/``): offline search scored from telemetry,
    the best-known-config store keyed by (model fingerprint, mesh,
    device kind, jax version), and sentinel-gated promotion.  Distinct
    from the legacy ``autotuning`` group (the launcher-driven reference
    API shape, now a shim over this plane)."""

    enabled: bool = True
    #: consult the store at initialize() and apply the promoted entry's
    #: overrides (user-pinned knobs always win)
    auto_apply: bool = True
    #: store file ("" = $DS_TUNING_STORE, else the per-user default;
    #: the package-shipped seeded store is always the read-only
    #: fallback)
    store_path: str = ""
    #: search defaults — ``tuning.SearchEngine.from_config(runner, space,
    #: cfg.tuning)`` consumes strategy/warmup/timed/max_candidates/score
    #: and pushes hbm_margin_frac onto the memory model
    strategy: Literal["grid", "successive_halving"] = "successive_halving"
    warmup_steps: int = 1
    timed_steps: int = 3
    #: cap on candidates entering the measurement phase (0 = all)
    max_candidates: int = 0
    #: score metric for trial ranking
    score: str = "tokens_per_sec"
    #: HBM fraction the calibrated memory model keeps clear of the
    #: state estimate when pruning (activations/scratch headroom)
    hbm_margin_frac: float = 0.05


class DataEfficiencyConfig(DeepSpeedConfigModel):
    enabled: bool = False
    seed: int = 1234
    data_sampling: Dict[str, Any] = Field(default_factory=dict)
    data_routing: Dict[str, Any] = Field(default_factory=dict)


class HybridEngineConfig(DeepSpeedConfigModel):
    """Reference ``hybrid_engine`` group (``runtime/hybrid_engine.py`` [K]):
    one engine flipping between ZeRO-3 training and inference generate for
    RLHF.  TP size / cache-release knobs kept for config parity; on TPU the
    flip is free (same sharded arrays serve both programs)."""

    enabled: bool = False
    max_out_tokens: int = 512
    inference_tp_size: int = 1
    release_inference_cache: bool = False
    pin_parameters: bool = True
    tp_gather_partition_size: int = 8


class CompileConfig(DeepSpeedConfigModel):
    """torch.compile interop group — on TPU everything is compiled; kept so
    configs round-trip and so `deepcompile` flags are visible."""

    deepcompile: bool = False
    offload_activation: bool = False
    offload_opt_states: bool = False


class KernelsConfig(DeepSpeedConfigModel):
    """``kernels`` config group — the Pallas kernel plane
    (``deepspeed_tpu/ops/pallas/``): which custom kernels serve the step
    hot path, and their tuning knobs.  Every knob here is a tuning-plane
    dimension (``tuning/space.py``) so the PR-9 search picks winners per
    (model, mesh, device_kind); the defaults are the conservative
    XLA-reference paths."""

    #: route model attention (llama/bert builders honor this) through the
    #: Pallas flash kernel family instead of the XLA einsum+softmax
    flash_attention: bool = False
    #: flash kernel block sizes; 0 = the seq-length-aware table
    #: (``ops/pallas/lattice.auto_flash_blocks``)
    flash_block_q: int = 0
    flash_block_k: int = 0
    #: one-pass fused Adam over ZeRO shards (``ops/pallas/
    #: fused_optimizer.py``): moments + grad-norm + unscale/clip in two
    #: HBM passes instead of the optax chain's 3–4 sweeps.  Requires a
    #: config-built adam/adamw-family optimizer; silently kept off for
    #: offload/1-bit/1F1B paths (logged).
    fused_adam: bool = False
    #: ZeRO-3 collective–compute overlap: explicit chunked-ppermute ring
    #: all-gather/reduce-scatter (``comm/overlap.py``) instead of the
    #: monolithic GSPMD collectives that serialize against the matmuls
    #: they feed
    overlap_collectives: bool = False
    #: ring payload granularity (chunks per shard); more chunks = finer
    #: pipelining but more per-hop latency — a tuning dimension
    overlap_chunks: int = 4


class MoEConfig(DeepSpeedConfigModel):
    """``moe`` config group — the expert-parallel execution plane
    (``deepspeed_tpu/moe/``): how many ways the ``expert`` mesh axis is
    carved, how much slack the capacity budget gets, and which dispatch
    implementation moves tokens.  Capacity factor / ep degree / dispatch
    impl are tuning-plane dimensions (``tuning/space.py``); ZeRO composes
    over the flattened ``("expert", "data")`` tuple so expert-sharded
    params still shard their optimizer state over all data ranks."""

    #: expert-parallel degree: size of the ``expert`` mesh axis.  1 keeps
    #: the axis trivial (pre-PR-19 behavior); >1 requires
    #: world/(tp·pp·sp) divisible by it and is mutually exclusive with
    #: MiCS, which repurposes the expert axis as its replica axis.
    expert_parallel_size: int = 1
    #: token dispatch implementation: ``auto`` | ``dense`` | ``sparse`` |
    #: ``pallas`` (``ops/pallas/moe_dispatch.choose_dispatch_impl``)
    dispatch_impl: str = "auto"
    #: override the model's train capacity factor (0 = keep the model's)
    capacity_factor: float = 0.0
    #: pad expert capacity up to the next multiple of the expert axis so
    #: expert-axis sharding constraints never silently drop
    pad_capacity_to_ep: bool = True
    #: random-token-selection under capacity pressure (reference use_rts);
    #: active only when a gating rng is threaded through the step
    use_rts: bool = False

    @model_validator(mode="after")
    def _check(self):
        if self.expert_parallel_size < 1:
            raise ValueError("moe.expert_parallel_size must be >= 1")
        if self.dispatch_impl not in ("auto", "dense", "sparse", "pallas"):
            raise ValueError(
                f"moe.dispatch_impl {self.dispatch_impl!r} not in "
                "auto|dense|sparse|pallas")
        return self


# ---------------------------------------------------------------------------
# top-level
# ---------------------------------------------------------------------------


def _load_config_payload(config: Union[str, Dict[str, Any]]) -> Dict[str, Any]:
    """Accept dict, JSON file path, or base64-encoded JSON [L ACC-DS:145-156]."""
    if isinstance(config, dict):
        return dict(config)
    if isinstance(config, (str, os.PathLike)):
        path = os.fspath(config)
        if os.path.exists(path):
            with open(path) as fh:
                return json.load(fh)
        try:
            return json.loads(base64.urlsafe_b64decode(path).decode())
        except Exception:
            try:
                return json.loads(path)
            except Exception:
                raise ValueError(
                    f"Expected a dict, JSON file path, JSON string, or base64 "
                    f"payload; got {path!r} (file does not exist)")
    raise TypeError(f"unsupported config type {type(config)}")


class DeepSpeedConfig(DeepSpeedConfigModel):
    """The validated top-level config (reference class of the same name)."""

    train_batch_size: Union[int, str, None] = None
    train_micro_batch_size_per_gpu: Union[int, str, None] = None
    gradient_accumulation_steps: Union[int, str, None] = None
    steps_per_print: Union[int, float] = 10
    wall_clock_breakdown: bool = False
    dump_state: bool = False
    prescale_gradients: bool = False
    gradient_predivide_factor: float = 1.0
    gradient_clipping: Union[float, str] = 0.0
    memory_breakdown: bool = False
    disable_allgather: bool = False
    sparse_gradients: bool = False
    zero_allow_untested_optimizer: bool = False  # [L HF-DS:392]
    zero_force_ds_cpu_optimizer: bool = True  # [L ACC:2365-2367]
    seed: int = 1234

    optimizer: Optional[OptimizerConfig] = None
    scheduler: Optional[SchedulerConfig] = None
    fp16: FP16Config = Field(default_factory=FP16Config)
    bf16: BF16Config = Field(default_factory=BF16Config)
    amp: AMPConfig = Field(default_factory=AMPConfig)
    zero_optimization: DeepSpeedZeroConfig = Field(default_factory=DeepSpeedZeroConfig)
    activation_checkpointing: ActivationCheckpointingConfig = Field(
        default_factory=ActivationCheckpointingConfig)
    aio: AioConfig = Field(default_factory=AioConfig)
    comms_logger: CommsLoggerConfig = Field(default_factory=CommsLoggerConfig)
    flops_profiler: FlopsProfilerConfig = Field(default_factory=FlopsProfilerConfig)
    tensorboard: TensorBoardConfig = Field(default_factory=TensorBoardConfig)
    wandb: WandbConfig = Field(default_factory=WandbConfig)
    csv_monitor: CSVConfig = Field(default_factory=CSVConfig)
    telemetry: TelemetryConfig = Field(default_factory=TelemetryConfig)
    serving: ServingConfig = Field(default_factory=ServingConfig)
    resilience: ResilienceConfig = Field(default_factory=ResilienceConfig)
    checkpoint: CheckpointConfig = Field(default_factory=CheckpointConfig)
    tensor_parallel: TensorParallelConfig = Field(default_factory=TensorParallelConfig)
    sequence_parallel: SequenceParallelConfig = Field(
        default_factory=SequenceParallelConfig)
    pipeline: PipelineConfig = Field(default_factory=PipelineConfig)
    elasticity: ElasticityConfig = Field(default_factory=ElasticityConfig)
    autotuning: AutotuningConfig = Field(default_factory=AutotuningConfig)
    tuning: TuningConfig = Field(default_factory=TuningConfig)
    data_efficiency: DataEfficiencyConfig = Field(default_factory=DataEfficiencyConfig)
    hybrid_engine: HybridEngineConfig = Field(default_factory=HybridEngineConfig)
    compile: CompileConfig = Field(default_factory=CompileConfig)
    kernels: KernelsConfig = Field(default_factory=KernelsConfig)
    moe: MoEConfig = Field(default_factory=MoEConfig)
    compression_training: Dict[str, Any] = Field(default_factory=dict)
    curriculum_learning: Dict[str, Any] = Field(default_factory=dict)

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------

    @classmethod
    def from_dict_or_path(cls, config: Union[str, Dict[str, Any]],
                          world_size: Optional[int] = None,
                          tp: int = 1, pp: int = 1, sp: int = 1) -> "DeepSpeedConfig":
        payload = _load_config_payload(config)
        cfg = cls.model_validate(payload)
        cfg.resolve_batch_sizes(world_size=world_size, tp=tp, pp=pp, sp=sp)
        return cfg

    # ------------------------------------------------------------------
    # batch math — the reference invariant
    # ------------------------------------------------------------------

    def resolve_batch_sizes(self, world_size: Optional[int] = None,
                            tp: int = 1, pp: int = 1, sp: int = 1) -> None:
        """Given any subset of (train_batch, micro_batch, grad_accum), infer
        the rest and validate  train = micro × gas × dp_world.
        """
        if world_size is None:
            import jax

            world_size = jax.device_count()
        denom = tp * pp * sp
        if world_size % denom:
            raise ValueError(f"world_size={world_size} not divisible by "
                             f"tp*pp*sp={denom}")
        dp_world = world_size // denom

        tb = None if is_auto(self.train_batch_size) else self.train_batch_size
        mb = (None if is_auto(self.train_micro_batch_size_per_gpu)
              else self.train_micro_batch_size_per_gpu)
        gas = (None if is_auto(self.gradient_accumulation_steps)
               else self.gradient_accumulation_steps)

        if tb is not None and mb is not None and gas is None:
            if tb % (mb * dp_world):
                raise ValueError(
                    f"train_batch_size={tb} not divisible by micro_batch×dp "
                    f"({mb}×{dp_world})")
            gas = tb // (mb * dp_world)
        elif tb is not None and gas is not None and mb is None:
            if tb % (gas * dp_world):
                raise ValueError(
                    f"train_batch_size={tb} not divisible by grad_accum×dp "
                    f"({gas}×{dp_world})")
            mb = tb // (gas * dp_world)
        elif mb is not None:
            gas = gas or 1
            tb = tb or mb * gas * dp_world
        elif tb is not None:
            gas = 1
            if tb % dp_world:
                raise ValueError(f"train_batch_size={tb} not divisible by "
                                 f"dp_world={dp_world}")
            mb = tb // dp_world
        else:
            tb, mb, gas = dp_world, 1, 1  # reference default micro=1,gas=1

        if tb != mb * gas * dp_world:
            raise ValueError(
                f"Batch invariant violated: train_batch_size={tb} != "
                f"micro={mb} × grad_accum={gas} × dp_world={dp_world}. "
                f"(world={world_size}, tp={tp}, pp={pp}, sp={sp})")

        self.train_batch_size = tb
        self.train_micro_batch_size_per_gpu = mb
        self.gradient_accumulation_steps = gas

    # ------------------------------------------------------------------
    # convenience
    # ------------------------------------------------------------------

    def dtype(self):
        """Precedence: bf16 > fp16 > fp32 (TPU-first: bf16 needs no scaler)."""
        import jax.numpy as jnp

        if self.bf16.enabled is True:
            return jnp.bfloat16
        if self.fp16.enabled is True:
            return jnp.float16
        return jnp.float32

    def resolve_auto_precision(self, default: str = "bf16") -> None:
        if is_auto(self.bf16.enabled):
            self.bf16.enabled = default == "bf16"
        if is_auto(self.fp16.enabled):
            self.fp16.enabled = default == "fp16"
        if is_auto(self.amp.enabled):
            self.amp.enabled = False

    def print_config(self) -> None:
        logger.info(json.dumps(self.model_dump(mode="json"), indent=2, default=str))
