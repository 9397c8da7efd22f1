"""Serving observability — per-latency-class TTFT / per-token latency
and per-request lifecycle records (ISSUE 15 tentpole b).

The serving plane's SLOs are *distributional* (p50/p99 time-to-first-
token per class), which the telemetry registry's fixed-bucket histograms
approximate too coarsely to gate on.  :class:`LatencyTracker` keeps a
bounded sample window and computes exact percentiles over it — and,
since a p99 with no identity is a dead end at 3am, each sample may carry
an *exemplar* reference (the request's trace id) so the slowest request
in the window is traceable, not anonymous.  :class:`ServingMetrics` owns
one TTFT and one TPOT (time-per-output-token) tracker per class plus the
serving counters, publishes gauges through the existing
:class:`MetricsRegistry`, and renders the ``serving`` section of debug
bundles.

:class:`RequestRecord` is the per-request sibling of the training
plane's StepRecord: one request's whole lifecycle — queue wait,
admission attempts, preempt/resume, replica placement and replays,
prefill/transfer/decode phases, token timings — stamped on
``time.perf_counter()`` so the PR-13 clocksync offset lands every event
on the shared store clock.  :class:`RequestLog` is the bounded ring the
records commit into: head-based sampled (``serving.tracing.
sample_rate`` — deterministic on the trace id, so every process that
touches a request makes the SAME decision) with always-on sampling for
anomalous requests (replayed, preempted, failed, expired, or TTFT over
the threshold), shipped cross-process over the PR-13 rollup transport.

ServingMetrics is written with the front-end's lock held (single
writer); ``publish`` alone also runs without it, on a registry reader's
thread, and reads only what is safe beside the writer.
RequestRecord/RequestLog carry their own lock (door handler threads,
worker protocol threads, and the pump all touch them).
Reads used by tests/CLI take point-in-time copies.
"""

from __future__ import annotations

import hashlib
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

#: latency classes in strict priority order — admission drains them
#: left-to-right, preemption moves rightmost work out of the way
CLASSES = ("interactive", "batch", "background")


class LatencyTracker:
    """Bounded sample window with exact percentiles (ms).  Samples may
    carry an exemplar ref (a request trace id) so the window's tail is
    traceable."""

    def __init__(self, max_samples: int = 512):
        self._samples: deque = deque(maxlen=int(max_samples))
        self._refs: deque = deque(maxlen=int(max_samples))

    def observe(self, ms: float, ref: Optional[str] = None) -> None:
        self._samples.append(float(ms))
        self._refs.append(ref)

    @property
    def count(self) -> int:
        return len(self._samples)

    def percentile(self, p: float) -> float:
        """Exact percentile over the window (nearest-rank); 0.0 empty.
        Safe beside the writer: the registry's collect hook reads while
        the pump appends, so a copy the deque reports as torn ("mutated
        during iteration") is taken again."""
        while True:
            try:
                ordered = sorted(self._samples)
                break
            except RuntimeError:
                continue
        if not ordered:
            return 0.0
        rank = min(len(ordered) - 1,
                   max(0, int(round(p / 100.0 * (len(ordered) - 1)))))
        return ordered[rank]

    def exemplar(self) -> Optional["tuple"]:
        """``(ms, ref)`` of the slowest ref-carrying sample in the
        window — the request id behind the p99, not just its number."""
        best = None
        for ms, ref in zip(self._samples, self._refs):
            if ref is not None and (best is None or ms > best[0]):
                best = (ms, ref)
        return best

    def summary(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "count": float(self.count),
            "p50_ms": round(self.percentile(50), 3),
            "p99_ms": round(self.percentile(99), 3)}
        ex = self.exemplar()
        if ex is not None:
            # the id a `serving trace <id>` can assemble — surfaced
            # right next to the percentile it explains
            out["p99_exemplar"] = ex[1]
            out["p99_exemplar_ms"] = round(ex[0], 3)
        return out


#: why an admission attempt bounced (ISSUE 16 satellite): no free
#: decode slot / not enough KV pages / per-replica outstanding-token
#: budget / HBM-headroom floor deferral.  One increment per blocked
#: pump round, not per unique request — it is a pressure rate.
ADMISSION_REJECT_REASONS = ("slots", "pages", "token_budget", "headroom")


def count_admission_reject(metrics: "ServingMetrics", reason: str) -> None:
    """One admission rejection, attributed: the local counter shows in
    ``/v1/metrics``; the telemetry counter rides the rollup so the
    cluster view can tell "add workers" (slots/tokens) from "add HBM"
    (pages/headroom)."""
    metrics.inc(f"admission_rejected_{reason}")
    from ..telemetry import get_telemetry

    tel = get_telemetry()
    if tel.enabled:
        tel.inc_counter(f"serving/admission_rejected_{reason}_total",
                        help="admission attempts bounced, by blocking "
                             "resource")


class ServingMetrics:
    """The serving plane's numbers: per-class latency + global counters."""

    def __init__(self, window: int = 512):
        self.ttft = {c: LatencyTracker(window) for c in CLASSES}
        self.tpot = {c: LatencyTracker(window) for c in CLASSES}
        self.tokens = {c: 0 for c in CLASSES}
        self.completed = {c: 0 for c in CLASSES}
        #: disaggregated-mode TTFT attribution: where the first token's
        #: latency went (prefill replica / KV-page transfer / decode
        #: replica's first burst)
        self.disagg = {k: LatencyTracker(window)
                       for k in ("prefill_ms", "transfer_ms", "decode_ms")}
        self.counters: Dict[str, int] = {
            "submitted": 0, "cancelled": 0, "failed": 0,
            "preemptions": 0, "preempt_pages_released": 0,
            "requeued_replica_death": 0,
            "admission_deferred_headroom": 0,
            "disagg_requests": 0,
        }
        # seeded so a zero shows in /v1/metrics before the first
        # rejection — an operator diffing reasons must see the absence
        for r in ADMISSION_REJECT_REASONS:
            self.counters[f"admission_rejected_{r}"] = 0

    def inc(self, name: str, v: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + v

    def record_ttft(self, klass: str, ms: float,
                    ref: Optional[str] = None) -> None:
        self.ttft[klass].observe(ms, ref=ref)

    def record_disagg(self, breakdown: Dict[str, float],
                      count: bool = True) -> None:
        """One disaggregated request's TTFT attribution (ms per
        stage); missing stages are skipped.  ``count=False`` records a
        late-arriving stage (decode_ms lands with the first decoded
        token) without double-counting the request."""
        if count:
            self.counters["disagg_requests"] += 1
        for k, tracker in self.disagg.items():
            v = breakdown.get(k)
            if v is not None:
                tracker.observe(float(v))
        from ..telemetry import get_telemetry

        tel = get_telemetry()
        if tel.enabled:
            for k, tracker in self.disagg.items():
                if tracker.count:
                    tel.set_gauge(
                        f"serving/disagg_ttft_{k.replace('_ms', '')}_p50_ms",
                        tracker.percentile(50),
                        help="disaggregated TTFT attribution p50 by stage")

    def record_completion(self, klass: str, n_tokens: int,
                          gen_time_s: float) -> None:
        self.completed[klass] += 1
        self.tokens[klass] += int(n_tokens)
        if n_tokens > 1 and gen_time_s > 0:
            self.tpot[klass].observe(gen_time_s * 1e3 / (n_tokens - 1))

    # -- export ------------------------------------------------------------

    def publish(self, queue_depths: Dict[str, int],
                prefix_hit_rate: float,
                moe_imbalance: Optional[Dict[int, float]] = None) -> None:
        """Push the current numbers as gauges through the telemetry hub
        (no-op when telemetry is off).  The front-end calls this from the
        registry's collect hook, when the registry is read, and not once
        a round: nine percentile sorts are a reader's cost, not the
        pump's.  ``moe_imbalance``
        maps replica id → hot-expert imbalance (max/mean expert load) so
        the autoscaler and dashboards see which replica is routing
        skewed."""
        from ..telemetry import get_telemetry

        tel = get_telemetry()
        if not tel.enabled:
            return
        if moe_imbalance:
            for rid, imb in sorted(moe_imbalance.items()):
                tel.set_gauge(f"serving/replica{rid}_moe_imbalance",
                              float(imb),
                              help="max/mean expert load of the replica's "
                                   "recent decodes (1.0 = balanced)")
            tel.set_gauge("serving/moe_imbalance_max",
                          max(float(v) for v in moe_imbalance.values()),
                          help="worst hot-expert imbalance across "
                               "replicas — the fleet's routing-skew "
                               "signal")
        for c in CLASSES:
            tel.set_gauge(f"serving/{c}_ttft_p50_ms",
                          self.ttft[c].percentile(50),
                          help="time-to-first-token p50 by class")
            tel.set_gauge(f"serving/{c}_ttft_p99_ms",
                          self.ttft[c].percentile(99),
                          help="time-to-first-token p99 by class")
            tel.set_gauge(f"serving/{c}_tpot_p50_ms",
                          self.tpot[c].percentile(50),
                          help="per-output-token latency p50 by class")
            tel.set_gauge(f"serving/{c}_queue_depth",
                          float(queue_depths.get(c, 0)),
                          help="requests queued (not yet admitted)")
        tel.set_gauge("serving/prefix_hit_rate", prefix_hit_rate,
                      help="fraction of prompt tokens served from shared "
                           "prefix pages")

    def snapshot(self) -> Dict[str, Any]:
        classes: Dict[str, Any] = {}
        for c in CLASSES:
            classes[c] = {"ttft": self.ttft[c].summary(),
                          "tpot": self.tpot[c].summary(),
                          "tokens": self.tokens[c],
                          "completed": self.completed[c]}
        out = {"classes": classes, "counters": dict(self.counters)}
        if self.counters.get("disagg_requests"):
            out["disagg_ttft"] = {k: t.summary()
                                  for k, t in self.disagg.items()}
        return out


# ---------------------------------------------------------------------------
# per-request lifecycle records (ISSUE 15 tentpole b)
# ---------------------------------------------------------------------------

#: bound on non-token events kept per record (a pathological admission
#: storm must not grow one record without bound)
MAX_RECORD_EVENTS = 128


def head_sampled(trace_id: str, sample_rate: float) -> bool:
    """Deterministic head-based sampling decision: every process that
    hashes the same trace id reaches the same verdict, so a sampled
    request is sampled on EVERY lane it crosses (and an unsampled one
    costs nothing anywhere) without a flag having to ride each hop."""
    rate = float(sample_rate)
    if rate >= 1.0:
        return True
    if rate <= 0.0:
        return False
    h = int(hashlib.sha1(str(trace_id).encode()).hexdigest()[:8], 16)
    return (h / float(0xFFFFFFFF)) < rate


class RequestRecord:
    """One request's lifecycle on ONE process — the serving sibling of
    the training StepRecord.  Event/phase timestamps are raw
    ``time.perf_counter()`` seconds: the node's clocksync offset
    (shipped alongside, see ``serving/tracing.py``) lands them on the
    shared store clock, which is what lets N processes' records merge
    into one aligned timeline."""

    def __init__(self, trace_id: str, uid: Any, klass: str,
                 prompt_tokens: int, max_new_tokens: int,
                 sampled: bool, lock: Optional[threading.Lock] = None,
                 token_cap: int = 512):
        self.trace_id = str(trace_id)
        self.uid = uid
        self.klass = str(klass)
        self.prompt_tokens = int(prompt_tokens)
        self.max_new_tokens = int(max_new_tokens)
        self.sampled = bool(sampled)
        self.start_ts = time.perf_counter()
        self.end_ts: Optional[float] = None
        self.status = "open"
        self.events: List[Dict[str, Any]] = []
        self.phases: List[Dict[str, Any]] = []
        #: perf-counter stamps of the first ``token_cap`` delivered
        #: tokens (enough for gap percentiles without unbounded growth)
        self.token_ts: List[float] = []
        self._token_cap = int(token_cap)
        self.tokens = 0
        self.replays = 0
        self.preempts = 0
        self.admission_attempts = 0
        self.replicas: List[Any] = []
        self.admitted_ts: Optional[float] = None
        self.ttft_ms: Optional[float] = None
        self.breakdown: Optional[Dict[str, float]] = None
        self.error: Optional[str] = None
        self.anomaly: Optional[str] = None
        self.events_dropped = 0
        self._lock = lock or threading.Lock()

    # -- producers (any thread) --------------------------------------------

    def event(self, name: str, **extra: Any) -> None:
        ev = {"name": str(name), "ts": time.perf_counter()}
        ev.update(extra)
        with self._lock:
            if name == "replayed":
                self.replays += 1
            elif name == "preempted":
                self.preempts += 1
            elif name == "admitted":
                self.admitted_ts = ev["ts"]
                if "replica" in extra:
                    self.replicas.append(extra["replica"])
            if len(self.events) >= MAX_RECORD_EVENTS:
                self.events_dropped += 1
                return
            self.events.append(ev)

    def phase(self, name: str, start_ts: Optional[float] = None,
              end_ts: Optional[float] = None,
              dur_ms: Optional[float] = None, **extra: Any) -> None:
        """One timed phase (prefill / transfer batch / decode burst).
        Either ``start_ts``/``end_ts`` (perf-counter) or an externally
        measured ``dur_ms`` anchored at ``end_ts`` (default: now)."""
        end = float(end_ts) if end_ts is not None else time.perf_counter()
        if dur_ms is None:
            start = float(start_ts) if start_ts is not None else end
            dur_ms = (end - start) * 1e3
        else:
            start = end - float(dur_ms) / 1e3
        ph = {"phase": str(name), "ts": start,
              "dur_ms": round(float(dur_ms), 3)}
        ph.update(extra)
        with self._lock:
            if len(self.phases) >= MAX_RECORD_EVENTS:
                self.events_dropped += 1
                return
            self.phases.append(ph)

    def note_blocked_admission(self) -> None:
        with self._lock:
            self.admission_attempts += 1

    def token(self, n: int = 1) -> None:
        """``n`` tokens delivered now (a round hands a stream its burst
        at once: one stamp for them)."""
        now = time.perf_counter()
        with self._lock:
            self.tokens += n
            room = self._token_cap - len(self.token_ts)
            if room > 0:
                self.token_ts.extend([now] * min(n, room))

    def finish(self, status: str, ttft_ms: Optional[float] = None,
               error: Optional[BaseException] = None,
               breakdown: Optional[Dict[str, float]] = None) -> None:
        with self._lock:
            self.end_ts = time.perf_counter()
            self.status = str(status)
            if ttft_ms is not None:
                self.ttft_ms = float(ttft_ms)
            if error is not None:
                self.error = repr(error)
            if breakdown:
                self.breakdown = dict(breakdown)

    def propagate_sampled(self) -> bool:
        """The sampling verdict a downstream hop should honor: the
        head-based decision, forced on once the request turned
        anomalous (a replayed request must be recorded on the worker it
        replays to, even at sample_rate=0)."""
        with self._lock:
            return bool(self.sampled or self.replays or self.preempts)

    # -- read side -----------------------------------------------------------

    def token_timing_summary(self) -> Dict[str, float]:
        with self._lock:
            ts = list(self.token_ts)
        if len(ts) < 2:
            return {}
        gaps = sorted((b - a) * 1e3 for a, b in zip(ts, ts[1:]))

        def pct(p: float) -> float:
            return gaps[min(len(gaps) - 1,
                            int(round(p / 100.0 * (len(gaps) - 1))))]

        return {"gap_p50_ms": round(pct(50), 3),
                "gap_p99_ms": round(pct(99), 3),
                "gap_max_ms": round(gaps[-1], 3)}

    def to_dict(self) -> Dict[str, Any]:
        with self._lock:
            out: Dict[str, Any] = {
                "trace_id": self.trace_id, "uid": self.uid,
                "klass": self.klass,
                "prompt_tokens": self.prompt_tokens,
                "max_new_tokens": self.max_new_tokens,
                "sampled": self.sampled, "status": self.status,
                "start_ts": self.start_ts, "end_ts": self.end_ts,
                "tokens": self.tokens, "replays": self.replays,
                "preempts": self.preempts,
                "admission_attempts": self.admission_attempts,
                "replicas": list(self.replicas),
                "events": [dict(e) for e in self.events],
                "phases": [dict(p) for p in self.phases],
            }
            if self.admitted_ts is not None:
                out["queue_wait_ms"] = round(
                    (self.admitted_ts - self.start_ts) * 1e3, 3)
            for k in ("ttft_ms", "breakdown", "error", "anomaly"):
                v = getattr(self, k)
                if v is not None:
                    out[k] = v
            if self.events_dropped:
                out["events_dropped"] = self.events_dropped
        out.update(self.token_timing_summary())
        return out


class RequestLog:
    """Bounded ring of committed :class:`RequestRecord` documents plus
    the registry of still-open ones — the process-local half of the
    request-tracing plane.

    Commit policy: a finished record lands in the ring when it was
    head-sampled OR turned anomalous (replayed / preempted / failed /
    expired / TTFT over ``anomaly_ttft_ms``) — so at ``sample_rate=0``
    the ring still holds exactly the requests worth asking about.  The
    ring doubles as the retention window the PR-13 rollup transport
    ships (``pending()``/``mark_pushed()``): the store key always holds
    the last ``maxlen`` records plus a snapshot of open sampled ones, so
    a ``kill -9``'d process's final publication still shows its partial
    lanes."""

    def __init__(self, maxlen: int = 256, sample_rate: float = 1.0,
                 anomaly_ttft_ms: float = 2000.0, enabled: bool = True,
                 token_cap: int = 512):
        self.enabled = bool(enabled)
        self.maxlen = int(maxlen)
        self.sample_rate = float(sample_rate)
        self.anomaly_ttft_ms = float(anomaly_ttft_ms)
        self.token_cap = int(token_cap)
        self._ring: deque = deque(maxlen=self.maxlen)
        self._open: Dict[int, RequestRecord] = {}
        self._rid = 0
        self._seq = 0
        self._pushed_seq = -1
        self.dropped = 0
        self.stream_id = f"{os.getpid()}-{time.time_ns()}"
        self._lock = threading.Lock()

    def configure(self, enabled: Optional[bool] = None,
                  sample_rate: Optional[float] = None,
                  maxlen: Optional[int] = None,
                  anomaly_ttft_ms: Optional[float] = None,
                  token_cap: Optional[int] = None) -> "RequestLog":
        with self._lock:
            if enabled is not None:
                self.enabled = bool(enabled)
            if sample_rate is not None:
                self.sample_rate = float(sample_rate)
            if anomaly_ttft_ms is not None:
                self.anomaly_ttft_ms = float(anomaly_ttft_ms)
            if token_cap is not None:
                self.token_cap = int(token_cap)
            if maxlen is not None and int(maxlen) != self.maxlen:
                self.maxlen = int(maxlen)
                self._ring = deque(self._ring, maxlen=self.maxlen)
        return self

    # -- producer surface ----------------------------------------------------

    def start(self, trace_id: str, uid: Any, klass: str,
              prompt_tokens: int, max_new_tokens: int,
              sampled: Optional[bool] = None) -> RequestRecord:
        """Open a record.  ``sampled=None`` takes the deterministic
        head-based decision; an explicit flag (propagated over an RPC by
        an upstream hop that already KNOWS the request is anomalous)
        wins."""
        if sampled is None:
            sampled = head_sampled(trace_id, self.sample_rate)
        rec = RequestRecord(trace_id, uid, klass, prompt_tokens,
                            max_new_tokens, sampled,
                            token_cap=self.token_cap)
        with self._lock:
            self._rid += 1
            rec._open_id = self._rid
            if self.enabled:
                self._open[self._rid] = rec
        return rec

    def anomaly_of(self, rec: RequestRecord) -> Optional[str]:
        if rec.replays:
            return "replayed"
        if rec.preempts:
            return "preempted"
        if rec.status in ("failed", "expired"):
            return rec.status
        if rec.ttft_ms is not None \
                and rec.ttft_ms > self.anomaly_ttft_ms > 0:
            return "slow_ttft"
        return None

    def commit(self, rec: RequestRecord) -> bool:
        """Close a record: ring it when sampled or anomalous.  Always
        drops it from the open registry."""
        anomaly = self.anomaly_of(rec)
        rec.anomaly = anomaly
        with self._lock:
            self._open.pop(getattr(rec, "_open_id", -1), None)
            if not self.enabled or not (rec.sampled or anomaly):
                return False
            self._seq += 1
            doc = rec.to_dict()
            doc["seq"] = self._seq
            doc["done"] = True
            if len(self._ring) == self._ring.maxlen:
                self.dropped += 1  # oldest record falls off the window
            self._ring.append(doc)
        from ..telemetry import get_telemetry

        tel = get_telemetry()
        tel.inc_counter("serving/trace_records_total",
                        help="request records committed to the trace ring")
        if anomaly:
            tel.inc_counter(
                "serving/trace_anomaly_records_total",
                help="request records force-sampled as anomalous "
                     "(replayed/preempted/failed/slow-TTFT)")
        return True

    # -- transport surface (the rollup aux-stream protocol) ------------------

    def pending(self) -> Optional[List[Dict[str, Any]]]:
        """The publication batch: the whole committed window plus a
        snapshot of open sampled records (``done: false`` — a process
        killed mid-request leaves its partial lane behind).  ``None``
        when nothing moved since the last successful push."""
        with self._lock:
            if not self.enabled:
                return None
            open_recs = [r for r in self._open.values()
                         if r.sampled or r.replays or r.preempts]
            if self._seq == self._pushed_seq and not open_recs:
                return None
            out = [dict(d) for d in self._ring]
        for r in open_recs:
            d = r.to_dict()
            d["seq"] = 0  # never acked: re-shipped until committed
            d["done"] = False
            out.append(d)
        return out

    def mark_pushed(self, batch: List[Dict[str, Any]]) -> None:
        with self._lock:
            self._pushed_seq = max(
                [self._pushed_seq]
                + [int(d.get("seq", 0)) for d in batch if d.get("done")])

    # -- read side -----------------------------------------------------------

    def records(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(d) for d in self._ring]

    def open_count(self) -> int:
        with self._lock:
            return len(self._open)

    def open_records(self) -> List["RequestRecord"]:
        """The live (uncommitted) records — the profiler fold hook
        stamps a captured decode-burst's device time onto these."""
        with self._lock:
            return list(self._open.values())

    def find(self, trace_id: str) -> List[Dict[str, Any]]:
        """Committed + open records for one trace id (exact match)."""
        tid = str(trace_id)
        with self._lock:
            hits = [dict(d) for d in self._ring
                    if d.get("trace_id") == tid]
            open_recs = [r for r in self._open.values()
                         if r.trace_id == tid]
        for r in open_recs:
            d = r.to_dict()
            d["done"] = False
            hits.append(d)
        return hits

    def reset(self) -> None:
        with self._lock:
            self._ring.clear()
            self._open.clear()
            self._seq = 0
            self._pushed_seq = -1
            self.dropped = 0
            self.stream_id = f"{os.getpid()}-{time.time_ns()}"
