"""Perf-regression sentinel — baseline persistence + tolerance check.

``BENCH_r*.json`` has been a *log*: every round appends a number, nobody
is forced to look when it drifts down.  This module makes it a *gated
trajectory*: a bench run persists a perf baseline (step-time p50, MFU,
compile seconds, goodput, tokens/sec) and
``python -m deepspeed_tpu.telemetry perf {show,baseline,check}``
compares any later run against it, exiting **3** on regression beyond
configurable tolerances — the same scriptable-exit-code contract as the
``desync`` command.

A *run file* is a bench JSON line (one flat object of metric keys), a
driver ``BENCH_r*.json`` artifact (the same object under ``"parsed"``),
or a previously saved baseline file — all three carry the same metric
keys at top level or under ``metrics``.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional, Tuple

#: metric -> (direction, default relative tolerance).  "higher" means
#: higher is better (a drop beyond tol regresses); "lower" the reverse.
PERF_METRICS: Dict[str, Tuple[str, float]] = {
    "tokens_per_sec": ("higher", 0.10),
    "mfu": ("higher", 0.10),
    "goodput": ("higher", 0.05),
    "step_time_p50_ms": ("lower", 0.10),
    "compile_time_s": ("lower", 0.25),
    # memory plane (telemetry/memory): the same config suddenly holding
    # more HBM is a regression long before it is an OOM
    "peak_hbm_bytes": ("lower", 0.10),
    "hbm_headroom_frac": ("higher", 0.10),
    # tuning plane (deepspeed_tpu/tuning): the best-known-config path —
    # the MFU the headline model reaches UNDER the stored tuned config.
    # Gated so a store regression (a bad promotion, a stale entry) shows
    # up in the trajectory exactly like a code regression.
    "tuned_mfu": ("higher", 0.10),
    # serving plane (deepspeed_tpu/serving): the multi-tenant SLO gate —
    # interactive tail latency, shared-prefix effectiveness, per-class
    # goodput.  TTFT tails are noisier than throughput medians, hence
    # the wider tolerance + absolute floor.
    "serving_p99_ttft_ms": ("lower", 0.25),
    "prefix_hit_rate": ("higher", 0.10),
    "tok_s_interactive": ("higher", 0.15),
    "tok_s_background": ("higher", 0.25),
    # kernel plane (ops/pallas — ISSUE 12): no kernel ships without a
    # number.  Speedups are ratios vs the XLA reference ladder rung the
    # dispatch would otherwise take; the fused-adam figure is effective
    # HBM GB/s over the 7-floats/param logical traffic (same accounting
    # as optax_adam_hbm_gbps so the two compare); hiding_frac is the
    # share of collective time the ring decomposition buries under
    # compute.  A drop beyond tolerance exits 3 like any other metric.
    "flash_speedup_s2048": ("higher", 0.10),
    "flash_speedup_s8192": ("higher", 0.10),
    "flash_speedup_s32768": ("higher", 0.10),
    "block_sparse_speedup_s4096": ("higher", 0.10),
    "fused_adam_hbm_gbps": ("higher", 0.15),
    "overlap_hiding_frac": ("higher", 0.15),
    # anatomy plane (ISSUE 17): the trace-measured exposed-collective
    # share of step wall time.  LOWER is better — a rise means formerly
    # hidden (or absent) collective time is now serializing the step.
    # Gated one-sided like every metric: absent from an older baseline
    # → SKIPPED, never a fail.
    "comm_fraction": ("lower", 0.25),
    # network serving plane (ISSUE 14): the same SLO gate measured
    # through the REAL stack — HTTP/SSE front door + replica worker
    # processes.  Socket + process scheduling jitter is wider than the
    # in-process path, hence the looser tolerances + TTFT abs floor.
    "serving_net_p99_ttft_ms": ("lower", 0.30),
    "serving_net_qps_sustained": ("higher", 0.25),
    "serving_net_prefix_hit_rate": ("higher", 0.10),
    # SLO control plane (ISSUE 16): the worst slow-window burn rate
    # across the latency objectives during the replay workload.  A
    # burn < 1.0 means the error budget outlives the window, so the
    # signal is only meaningful near/above 1.0 — wide tolerance (burn
    # is a ratio of tail latencies, double jitter) plus an absolute
    # floor below which changes are error-budget noise.
    "serving_slo_burn_rate_p99": ("lower", 0.50),
    # numerics plane (ISSUE 18): fractional step-time cost of running the
    # sampled probes-on step variant vs the base step on the same
    # problem.  LOWER is better — the plane's whole contract is "stats
    # ride the step for (nearly) free"; a rise means a probe started
    # forcing a host sync or broke an XLA fusion.
    "numerics_overhead_frac": ("lower", 0.50),
    # expert-parallel plane (ISSUE 19): the Mixtral proxy trained with
    # the expert mesh axis > 1.  tokens/sec gates the whole ep pipeline
    # (sharded experts + sparse dispatch + ZeRO over (expert, data));
    # dispatch_speedup is the index-form dispatch vs the dense [T,E,C]
    # einsum on the same routing (sub-1.0 = the crossover auto-dispatch
    # regressed); drop_rate is the capacity-dropped token fraction at
    # the bench's fixed capacity factor — a rise means routing skew or
    # a capacity/padding regression, long before loss curves show it.
    "moe_ep_tokens_per_sec": ("higher", 0.15),
    "moe_dispatch_speedup": ("higher", 0.15),
    "moe_drop_rate": ("lower", 0.25),
    # fleet profiler plane (ISSUE 20): percent step-time cost of the
    # duty-cycled continuous capture (duty-cycle on vs off over the same
    # fenced steps).  LOWER is better — always-on capture only earns its
    # keep with a bounded overhead budget; a rise means the trace
    # stop/parse/census machinery started eating the step loop.  Wide
    # tolerance: the number is a ratio of two small wall times.
    "profiler_overhead_pct": ("lower", 0.50),
}

#: ignore regressions on metrics whose baseline is this close to zero —
#: a 0.001s compile baseline must not flag a 0.002s run
ABS_FLOORS: Dict[str, float] = {
    "compile_time_s": 1.0,
    "step_time_p50_ms": 1.0,
    # sub-64MiB HBM jitter (allocator rounding, cache growth) is noise
    "peak_hbm_bytes": 64 * 1024 * 1024,
    # sub-50ms TTFT jitter is host dispatch noise
    "serving_p99_ttft_ms": 50.0,
    # the network tail additionally rides loopback + SSE write jitter
    "serving_net_p99_ttft_ms": 75.0,
    # a fleet comfortably inside its SLO burns < 0.25 of budget-rate;
    # movement below that is noise, not a regression
    "serving_slo_burn_rate_p99": 0.25,
    # a step whose exposed-collective share is under 5% is effectively
    # compute-bound; scheduler jitter down there is not a regression
    "comm_fraction": 0.05,
    # ISSUE 18 acceptance ceiling: probe overhead under 5% of step time
    # is sampling noise, not a regression
    "numerics_overhead_frac": 0.05,
    # a top-2 router dropping under 2% of tokens is routing jitter at
    # the bench's capacity factor, not a capacity regression
    "moe_drop_rate": 0.02,
    # capture overhead under 5% of step time is scheduler noise on a
    # CPU-backend bench, not a profiler regression
    "profiler_overhead_pct": 5.0,
}

DEFAULT_BASELINE = "PERF_BASELINE.json"


def load_run(path: str) -> Dict[str, Any]:
    """Load a run file and normalize to a flat dict of values."""
    with open(path) as fh:
        data = json.load(fh)
    if isinstance(data, dict) and isinstance(data.get("parsed"), dict):
        data = data["parsed"]  # driver BENCH_r*.json artifact
    if isinstance(data, dict) and isinstance(data.get("metrics"), dict):
        merged = dict(data)
        merged.update(data["metrics"])  # saved baseline file
        data = merged
    if not isinstance(data, dict):
        raise ValueError(f"{path}: not a JSON object")
    return data


def extract_perf(run: Dict[str, Any]) -> Dict[str, float]:
    """Pull the sentinel metrics out of a normalized run dict.  The
    bench headline value doubles as tokens_per_sec when the metric name
    says so."""
    out: Dict[str, float] = {}
    metric = str(run.get("metric", ""))
    if "tokens_per_sec" in metric and "value" in run:
        try:
            v = float(run["value"])
            if v > 0:
                out["tokens_per_sec"] = v
        except (TypeError, ValueError):
            pass
    for name in PERF_METRICS:
        if name in run:
            try:
                out[name] = float(run[name])
            except (TypeError, ValueError):
                continue
    return out


def environment_failure_reason(run: Dict[str, Any]) -> Optional[str]:
    """A *no-data* artifact's named reason, or ``None`` for a real run.

    Matches two shapes of recorded artifact (nothing in the repo writes
    either any more; the reader stays until ROADMAP D1 retires the
    gate): an
    explicit ``environment_failure`` marker, and the older
    probe-failure line — ``value`` 0 with an ``error`` field and NO
    ``debug_bundle`` key.  The key matters: a bench that
    *crashed* (a code regression — OOM, assertion) also emits value 0 +
    error, but its line carries ``debug_bundle`` (``_emit_crash_line``)
    and no marker — that must stay a LOUD failure of the gate, never a
    skip.  ``perf check`` skips only genuine environment failures, with
    the reason printed."""
    if run.get("environment_failure"):
        return str(run.get("error") or "environment_failure marker set")
    err = run.get("error")
    if not err or "debug_bundle" in run:
        return None  # a crash artifact is a real failure, not a skip
    try:
        value = float(run.get("value", 0.0) or 0.0)
    except (TypeError, ValueError):
        value = 0.0
    if value == 0.0:
        return str(err)
    return None


def save_baseline(path: str, run: Dict[str, Any],
                  source: str = "") -> Dict[str, Any]:
    metrics = extract_perf(run)
    if not metrics:
        raise ValueError(
            "run carries none of the sentinel metrics "
            f"({', '.join(PERF_METRICS)}) — nothing to baseline")
    doc = {"created_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
           "source": source, "metrics": metrics}
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(doc, fh, indent=2)
    os.replace(tmp, path)  # atomic: a concurrent check never sees a torn file
    return doc


def load_baseline(path: str) -> Dict[str, float]:
    with open(path) as fh:
        doc = json.load(fh)
    metrics = doc.get("metrics", doc)
    return {k: float(v) for k, v in metrics.items() if k in PERF_METRICS}


def check_regression(current: Dict[str, float], baseline: Dict[str, float],
                     tolerances: Optional[Dict[str, float]] = None
                     ) -> Dict[str, Any]:
    """Compare run vs baseline metric-by-metric.

    Returns ``{regressions: [...], improvements: [...], compared: [...],
    skipped: [...]}`` — a metric present in only one side is *skipped*
    (named, never silently dropped), so adding a new bench field does
    not fail every old baseline."""
    tolerances = tolerances or {}
    out: Dict[str, Any] = {"regressions": [], "improvements": [],
                           "compared": [], "skipped": []}
    for name, (direction, default_tol) in PERF_METRICS.items():
        if name not in current or name not in baseline:
            if name in current or name in baseline:
                out["skipped"].append(name)
            continue
        cur, base = current[name], baseline[name]
        tol = float(tolerances.get(name, default_tol))
        floor = ABS_FLOORS.get(name, 0.0)
        entry = {"metric": name, "current": cur, "baseline": base,
                 "tolerance": tol, "direction": direction}
        out["compared"].append(name)
        if direction == "higher":
            limit = base * (1.0 - tol)
            entry["limit"] = limit
            if cur < limit:
                entry["delta_frac"] = (cur - base) / base if base else 0.0
                out["regressions"].append(entry)
            elif cur > base:
                out["improvements"].append(entry)
        else:
            limit = base * (1.0 + tol)
            entry["limit"] = limit
            if cur > limit and cur - base > floor:
                entry["delta_frac"] = (cur - base) / base if base else 0.0
                out["regressions"].append(entry)
            elif cur < base:
                out["improvements"].append(entry)
    return out


def format_check_report(result: Dict[str, Any]) -> str:
    lines: List[str] = []
    for r in result["regressions"]:
        arrow = "dropped" if r["direction"] == "higher" else "grew"
        lines.append(
            f"REGRESSION {r['metric']}: {r['baseline']:g} -> "
            f"{r['current']:g} ({arrow} {abs(r['delta_frac']):.1%}, "
            f"tolerance {r['tolerance']:.0%})")
    for r in result["improvements"]:
        lines.append(f"improved {r['metric']}: {r['baseline']:g} -> "
                     f"{r['current']:g}")
    ok = [m for m in result["compared"]
          if m not in {r["metric"] for r in result["regressions"]}
          and m not in {r["metric"] for r in result["improvements"]}]
    if ok:
        lines.append(f"within tolerance: {', '.join(ok)}")
    if result["skipped"]:
        lines.append("not comparable (present on one side only): "
                     + ", ".join(result["skipped"]))
    if not result["compared"]:
        lines.append("no overlapping metrics between run and baseline")
    return "\n".join(lines)


def parse_tolerances(specs: List[str]) -> Dict[str, float]:
    """``["mfu=0.05", "step_time_p50_ms=0.2"]`` → dict; unknown metric
    names are an error (a typo must not silently widen nothing)."""
    out: Dict[str, float] = {}
    for spec in specs or []:
        if "=" not in spec:
            raise ValueError(f"--tol {spec!r}: expected metric=fraction")
        name, _, frac = spec.partition("=")
        name = name.strip()
        if name not in PERF_METRICS:
            raise ValueError(f"--tol {name!r}: unknown metric "
                             f"(one of {', '.join(PERF_METRICS)})")
        out[name] = float(frac)
    return out
