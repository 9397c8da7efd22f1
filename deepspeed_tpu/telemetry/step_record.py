"""Per-optimizer-step telemetry record.

The engine assembles ONE of these each ``train_step`` (device-fenced
step wall time, throughput, loss/grad-norm/loss-scale, cumulative comm
bytes from ``comm.comms_logger``, JAX live-buffer/host memory stats) and
publishes it through the metrics registry + JSONL event log — so
the benchmark, the autotuner, and any monitor backend all read the SAME
numbers the runtime measured, instead of re-deriving their own
(ISSUE 1: the round-5 headline numbers were unwitnessed precisely
because the measuring code lived outside the engine).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

from .metrics import MetricsRegistry

#: step-time histogram buckets (ms) — spans CPU-test steps through
#: multi-second streamed Infinity steps
STEP_TIME_BUCKETS_MS = (5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0,
                        1000.0, 2500.0, 5000.0, 15000.0, 60000.0)


@dataclasses.dataclass
class StepRecord:
    step: int
    step_time_ms: float          # device-fenced wall time of this step
    device_fenced: bool          # True when a real fence closed the timing
    samples_per_sec: float
    tokens_per_sec: float
    loss: float
    grad_norm: float
    lr: float
    loss_scale: float
    overflow: bool
    skipped_steps: int
    comm_bytes: int              # cumulative comms_logger bytes so far
    comm_ops: int                # cumulative comms_logger op count so far
    tflops: float = 0.0          # 0 when flops_per_step unknown
    mfu: float = 0.0             # 0 when peak unknown
    memory: Dict[str, float] = dataclasses.field(default_factory=dict)
    extra: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        extra = d.pop("extra")
        d.update(extra)
        return d


def publish_step_record(registry: MetricsRegistry, rec: StepRecord) -> None:
    """Write one StepRecord through the registry (gauges for the latest
    values, counters for totals, a histogram for step-time distribution)
    and append it to the JSONL event log as ``kind="step"``."""
    registry.counter("train/steps_total",
                     "optimizer steps taken (incl. overflow skips)").inc()
    if rec.overflow:
        registry.counter("train/overflow_steps_total",
                         "fp16 overflow-skipped steps").inc()
    if rec.device_fenced:
        # the histogram is documented as DEVICE time; async-mode records
        # carry dispatch time and must not pollute it
        registry.histogram(
            "train/step_time_ms", "device-fenced optimizer step time (ms)",
            buckets=STEP_TIME_BUCKETS_MS).observe(rec.step_time_ms)
    g = registry.gauge
    g("train/step", "last optimizer step index").set(rec.step)
    g("train/step_time_ms_last", "last step time (ms)").set(rec.step_time_ms)
    g("train/samples_per_sec", "last-step samples/sec").set(
        rec.samples_per_sec)
    g("train/tokens_per_sec", "last-step tokens/sec").set(rec.tokens_per_sec)
    g("train/loss", "last-step mean loss").set(rec.loss)
    g("train/grad_norm", "last-step global grad norm").set(rec.grad_norm)
    g("train/lr", "last-step learning rate").set(rec.lr)
    g("train/loss_scale", "current fp16 loss scale").set(rec.loss_scale)
    g("train/skipped_steps", "cumulative overflow skips").set(
        rec.skipped_steps)
    g("comm/bytes_total", "cumulative comms_logger bytes").set(rec.comm_bytes)
    g("comm/ops_total", "cumulative comms_logger op count").set(rec.comm_ops)
    if rec.tflops:
        g("train/tflops", "achieved model TFLOP/s").set(rec.tflops)
    if rec.mfu:
        g("train/mfu", "model FLOPs utilization").set(rec.mfu)
    for k, v in rec.memory.items():
        g(f"memory/{k}", "memory_status() field").set(v)
    registry.emit_event("step", rec.to_dict())


def collect_memory_stats(include_live_buffers: bool = False
                         ) -> Dict[str, float]:
    """Device HBM + host memory stats, best-effort.  The live-buffer
    count is opt-in: ``jax.live_arrays()`` enumerates EVERY live array
    (O(all buffers)) — too expensive to pay on each step, so the engine
    samples it every few steps instead."""
    from ..utils.memory import memory_status

    out = dict(memory_status())
    if include_live_buffers:
        try:
            import jax

            out["live_buffers"] = float(len(jax.live_arrays()))
        except Exception as e:  # introspection API drift across jax
            from ..utils.logging import debug_once

            debug_once("step_record/live_buffers",
                       f"live-buffer count unavailable ({e!r})")
    return out
