"""Metrics registry — counters, gauges, fixed-bucket histograms.

Two exporters, both text-based and dependency-free:

* **JSONL event log** — every ``emit_event`` appends one JSON object per
  line (``{"ts": ..., "kind": ..., ...payload}``) to ``events.jsonl``;
  the engine's per-step :class:`~.step_record.StepRecord` rides this as
  ``kind="step"`` so BENCH artifacts and post-hoc analysis read the same
  numbers the runtime logged.
* **Prometheus text exposition** — ``prometheus_text()`` renders the
  whole registry in the exposition format (``# TYPE``/``# HELP`` +
  samples; histograms as cumulative ``_bucket{le=...}``/``_sum``/
  ``_count``), writable to a file for node-exporter textfile collection
  or servable directly.

Everything is thread-safe (the swapper's pipeline worker and debug
callbacks bump counters off the main thread).
"""

from __future__ import annotations

import bisect
import json
import math
import os
import re
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence


_NAME_RE = re.compile(r"[^a-zA-Z0-9_:]")


def prom_name(name: str) -> str:
    """Sanitize a slash-namespaced metric name ('swap/evictions') into a
    legal Prometheus metric name ('swap_evictions')."""
    out = _NAME_RE.sub("_", name)
    if out and out[0].isdigit():
        out = "_" + out
    return out


def escape_help(text: str) -> str:
    """Exposition-format HELP escaping: backslash and newline (a raw
    newline in help text would truncate the comment line and leave the
    remainder as a malformed sample)."""
    return str(text).replace("\\", "\\\\").replace("\n", "\\n")


def escape_label_value(value: Any) -> str:
    """Exposition-format label-value escaping: backslash, double-quote,
    newline — the three characters that can break out of ``v="..."``."""
    return (str(value).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def format_labels(labels: Dict[str, Any]) -> str:
    """Render ``{k="v",...}`` with escaped values ('' for no labels)."""
    if not labels:
        return ""
    inner = ",".join(f'{prom_name(str(k))}="{escape_label_value(v)}"'
                     for k, v in labels.items())
    return "{" + inner + "}"


class Counter:
    """Monotonic counter."""

    kind = "counter"

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self._value = 0.0
        self._lock = threading.Lock()

    def inc(self, v: float = 1.0) -> None:
        if v < 0:
            raise ValueError(f"counter {self.name}: negative increment {v}")
        with self._lock:
            self._value += v

    @property
    def value(self) -> float:
        return self._value

    def samples(self) -> List[tuple]:
        return [(prom_name(self.name), "", self._value)]


class Gauge:
    """Set-to-current-value metric."""

    kind = "gauge"

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self._value = 0.0
        self._lock = threading.Lock()

    def set(self, v: float) -> None:
        with self._lock:
            self._value = float(v)

    def add(self, v: float) -> None:
        with self._lock:
            self._value += float(v)

    @property
    def value(self) -> float:
        return self._value

    def samples(self) -> List[tuple]:
        return [(prom_name(self.name), "", self._value)]


#: default buckets suit step/IO latencies in milliseconds
DEFAULT_BUCKETS = (1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0,
                   1000.0, 2500.0, 5000.0, 10000.0)


class Histogram:
    """Fixed-bucket histogram (Prometheus cumulative-bucket semantics)."""

    kind = "histogram"

    def __init__(self, name: str, help: str = "",
                 buckets: Sequence[float] = DEFAULT_BUCKETS):
        self.name = name
        self.help = help
        bs = sorted(float(b) for b in buckets)
        if not bs:
            raise ValueError(f"histogram {name}: need at least one bucket")
        self.buckets = bs
        self._counts = [0] * (len(bs) + 1)  # +1 for the +Inf bucket
        self._sum = 0.0
        self._count = 0
        self._lock = threading.Lock()

    def observe(self, v: float) -> None:
        v = float(v)
        idx = bisect.bisect_left(self.buckets, v)
        with self._lock:
            self._counts[idx] += 1
            self._sum += v
            self._count += 1

    def bucket_counts(self) -> Dict[str, int]:
        """Cumulative count per upper bound (the exposition shape)."""
        out: Dict[str, int] = {}
        cum = 0
        with self._lock:
            for ub, c in zip(self.buckets, self._counts):
                cum += c
                out[repr(ub) if ub != math.inf else "+Inf"] = cum
            out["+Inf"] = cum + self._counts[-1]
        return out

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    def samples(self) -> List[tuple]:
        base = prom_name(self.name)
        out = []
        for ub, cum in self.bucket_counts().items():
            out.append((base + "_bucket", format_labels({"le": ub}), cum))
        out.append((base + "_sum", "", self._sum))
        out.append((base + "_count", "", self._count))
        return out


def _render_value(value) -> str:
    """Exposition-format sample value.  Non-finite floats are legal
    samples (``NaN``/``+Inf``/``-Inf``) — an fp16 overflow step records
    loss=nan / grad_norm=inf, and export must survive exactly those
    unstable runs it exists to observe."""
    if isinstance(value, float):
        if math.isnan(value):
            return "NaN"
        if math.isinf(value):
            return "+Inf" if value > 0 else "-Inf"
        if value == int(value) and abs(value) < 2 ** 53:
            return str(int(value))
    return str(value)


class JSONLExporter:
    """Append-only JSON-lines event log (one object per line)."""

    def __init__(self, path: str):
        self.path = path
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        self._lock = threading.Lock()
        self._fh = open(path, "a")

    def write(self, obj: Dict[str, Any]) -> None:
        line = json.dumps(obj, default=str)
        with self._lock:
            self._fh.write(line + "\n")
            self._fh.flush()

    def close(self) -> None:
        with self._lock:
            try:
                self._fh.close()
            except OSError:
                pass  # flush-on-close of a dead fd; nothing left to save


class MetricsRegistry:
    """Get-or-create registry of named metrics + the two exporters."""

    def __init__(self, before_read: Optional[Callable[[], None]] = None
                 ) -> None:
        self._metrics: Dict[str, Any] = {}
        self._lock = threading.Lock()
        self._event_lock = threading.Lock()
        self.event_log: Optional[JSONLExporter] = None
        #: called before the registry is read as a whole (``snapshot()``,
        #: ``prometheus_text()``).  The hub runs its collect hooks here:
        #: gauges derived from state kept elsewhere (a percentile over a
        #: sample window) are computed when somebody looks, not on the
        #: hot path that feeds them
        self._before_read = before_read

    # -- get-or-create -----------------------------------------------------

    def _get(self, cls, name: str, help: str, **kw):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls(name, help=help, **kw)
            elif not isinstance(m, cls):
                raise TypeError(f"metric {name!r} already registered as "
                                f"{type(m).__name__}, not {cls.__name__}")
            return m

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get(Counter, name, help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get(Gauge, name, help)

    def histogram(self, name: str, help: str = "",
                  buckets: Sequence[float] = DEFAULT_BUCKETS) -> Histogram:
        return self._get(Histogram, name, help, buckets=buckets)

    def metrics(self) -> Dict[str, Any]:
        with self._lock:
            return dict(self._metrics)

    def reset(self) -> None:
        with self._lock:
            self._metrics = {}

    # -- cross-process snapshot (telemetry/rollup.py) ----------------------

    def snapshot(self) -> Dict[str, Any]:
        """JSON-able value snapshot of every metric — the unit the
        cross-process rollup ships over the store.  Counters/gauges
        carry their value; histograms carry RAW per-bucket counts (not
        cumulative) plus sum/count, so N snapshots merge by plain
        elementwise addition.  Help text rides along so the merged
        Prometheus export can render it without sharing a registry."""
        if self._before_read is not None:
            self._before_read()
        out: Dict[str, Any] = {"counters": {}, "gauges": {},
                               "histograms": {}}
        for name, m in self.metrics().items():
            if isinstance(m, Counter):
                out["counters"][name] = {"value": m.value, "help": m.help}
            elif isinstance(m, Gauge):
                out["gauges"][name] = {"value": m.value, "help": m.help}
            elif isinstance(m, Histogram):
                with m._lock:
                    counts = list(m._counts)
                    hsum, hcount = m._sum, m._count
                out["histograms"][name] = {
                    "buckets": list(m.buckets), "counts": counts,
                    "sum": hsum, "count": hcount, "help": m.help}
        return out

    # -- JSONL -------------------------------------------------------------

    # Lookup + write and every swap hold ``_event_lock``: a reconfigure
    # on another thread (in-process hosts share one hub) must not close
    # the log between an emitter's lookup and its write; the event goes
    # to whichever log is attached when the emitter gets the lock.

    def attach_event_log(self, path: str) -> None:
        new = JSONLExporter(path)
        with self._event_lock:
            old, self.event_log = self.event_log, new
            if old is not None:
                old.close()

    def detach_event_log(self) -> None:
        with self._event_lock:
            old, self.event_log = self.event_log, None
            if old is not None:
                old.close()

    def emit_event(self, kind: str, payload: Dict[str, Any]) -> None:
        if self.event_log is None:
            return
        with self._event_lock:
            if self.event_log is not None:
                self.event_log.write(
                    {"ts": time.time(), "kind": kind, **payload})

    # -- Prometheus --------------------------------------------------------

    def prometheus_text(self) -> str:
        """The registry in Prometheus text exposition format 0.0.4."""
        if self._before_read is not None:
            self._before_read()
        lines: List[str] = []
        snapshot = self.metrics()  # index the snapshot: a concurrent
        for name in sorted(snapshot):  # reset() must not KeyError a flush
            m = snapshot[name]
            base = prom_name(name)
            if m.help:
                lines.append(f"# HELP {base} {escape_help(m.help)}")
            lines.append(f"# TYPE {base} {m.kind}")
            for sample_name, labels, value in m.samples():
                lines.append(f"{sample_name}{labels} {_render_value(value)}")
        return "\n".join(lines) + ("\n" if lines else "")

    def save_prometheus(self, path: str) -> str:
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as fh:
            fh.write(self.prometheus_text())
        os.replace(tmp, path)  # atomic for textfile-collector consumers
        return path


def parse_prometheus_text(text: str) -> Dict[str, float]:
    """Tiny exposition-format parser (used by tests and bench sanity
    checks): returns ``{sample_name{labels}: value}``.  Raises ValueError
    on a malformed sample line, which is exactly what 'parses cleanly'
    means in the acceptance criteria."""
    out: Dict[str, float] = {}
    for ln in text.splitlines():
        ln = ln.strip()
        if not ln or ln.startswith("#"):
            continue
        try:
            key, val = ln.rsplit(" ", 1)
            out[key] = float(val)
        except Exception as e:
            raise ValueError(f"bad exposition line {ln!r}: {e}")
        if not re.match(r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{.*\})?$", key):
            raise ValueError(f"bad sample name {key!r}")
    return out
