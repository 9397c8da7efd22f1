"""Unified telemetry: span tracing + metrics registry + step records.

ONE pipeline correlating what used to be fragments (ISSUE 1):

* :mod:`.tracer` — nested host-side spans (``telemetry.span("zero/...")``)
  kept in a ring and entered as ``jax.profiler.TraceAnnotation``s, so a
  profiler session shows them on the device's clock.
* :mod:`.metrics` — counters / gauges / fixed-bucket histograms with a
  JSONL event log and Prometheus text exposition.
* :mod:`.step_record` — the per-optimizer-step record the engine emits
  (device-fenced step time, throughput, loss, comm bytes, memory), the
  single source every consumer (bench, autotuner, monitors) reads.
* the start-up record (``Telemetry.startup``, ``startup_span()``): the
  same span primitive on a second, small ring that is written whether the
  hub is on or off, so that every start can say where it went
  (``startup_report()``: one log line a start, the ``startup`` context of
  a debug bundle, the gauges ``startup/<phase>_s``).

The module-level hub is a process-global singleton, DISABLED by default:
``span()`` returns one shared no-op object and the counter/gauge
helpers early-return, so instrumented hot paths cost one attribute read
when telemetry is off.  Enable via the ``telemetry`` config group
(``{"telemetry": {"enabled": true, ...}}``) — wired through
``MonitorMaster`` as a fourth backend — or programmatically with
:func:`configure`.
"""

from __future__ import annotations

import inspect
import os
import sys
import threading
import weakref
from typing import Any, Callable, Dict, List, Optional

from .collective_ledger import (CollectiveLedger, attach_collective_ledger,
                                configure_collective_ledger,
                                desync_from_heartbeats,
                                find_first_divergence,
                                format_divergence_report,
                                get_collective_ledger)
from .flight_recorder import (FlightRecorder, configure_flight_recorder,
                              get_flight_recorder, load_bundle)
from .health import HealthEvent, HealthMonitor
from .metrics import (DEFAULT_BUCKETS, Counter, Gauge, Histogram,
                      JSONLExporter, MetricsRegistry, escape_help,
                      escape_label_value, format_labels,
                      parse_prometheus_text, prom_name)
from .memory import (HBMExhaustedError, MemoryLedger,
                     configure_memory_ledger, get_memory_ledger,
                     is_oom_error, probe_device_liveness)
from .perf import (CompileTracker, GoodputLedger, configure_compile_tracker,
                   configure_goodput_ledger, get_compile_tracker,
                   get_goodput_ledger, tracked_jit)
from .clocksync import ClockSync, get_clock_sync, maybe_sync_clock
from .numerics import (NonFiniteOriginReport, STAT_FIELDS, tensor_stats)
from .rollup import (MetricsRollup, StepStream, collect_rollup,
                     configure_step_stream, get_rollup, get_step_stream,
                     push_node_telemetry, render_top, rollup_tick)
from .step_record import (StepRecord, collect_memory_stats,
                          publish_step_record)
from .tracer import (NOOP_SPAN, STARTUP_ROOTS, SpanTracer, StartupRecord,
                     startup_phases)
from .watchdog import (HEARTBEAT_SCHEMA_V, HangWatchdog, WatchdogTimeout,
                       cap_heartbeat_payload, get_watchdog, set_watchdog)

__all__ = [
    "Telemetry", "StepRecord", "MetricsRegistry", "SpanTracer",
    "Counter", "Gauge", "Histogram", "JSONLExporter",
    "configure", "configure_from_config", "get_telemetry", "span",
    "startup_span",
    "publish_step_record", "collect_memory_stats", "parse_prometheus_text",
    "prom_name", "DEFAULT_BUCKETS",
    "FlightRecorder", "configure_flight_recorder", "get_flight_recorder",
    "load_bundle", "HealthEvent", "HealthMonitor",
    "HangWatchdog", "WatchdogTimeout", "get_watchdog", "set_watchdog",
    "CollectiveLedger", "attach_collective_ledger",
    "configure_collective_ledger", "get_collective_ledger",
    "desync_from_heartbeats", "find_first_divergence",
    "format_divergence_report",
    "escape_help", "escape_label_value", "format_labels",
    "CompileTracker", "configure_compile_tracker", "get_compile_tracker",
    "tracked_jit", "GoodputLedger", "configure_goodput_ledger",
    "get_goodput_ledger",
    "MemoryLedger", "configure_memory_ledger", "get_memory_ledger",
    "HBMExhaustedError", "is_oom_error", "probe_device_liveness",
    "MetricsRollup", "StepStream", "collect_rollup",
    "configure_step_stream", "get_rollup", "get_step_stream",
    "push_node_telemetry", "render_top", "rollup_tick",
    "ClockSync", "get_clock_sync", "maybe_sync_clock",
    "NonFiniteOriginReport", "STAT_FIELDS", "tensor_stats",
    "HEARTBEAT_SCHEMA_V", "cap_heartbeat_payload",
]


class Telemetry:
    """The hub: one tracer + one registry + output plumbing."""

    def __init__(self) -> None:
        self.enabled = False
        self.tracer = SpanTracer()
        #: the start-up record: written whether the hub is on or off
        self.startup = StartupRecord(self._startup_closed)
        #: run before the registry is read as a whole; kept here and not
        #: on the registry so that ``reset()`` carries them over
        self._collect_hooks: List[Callable[[], Any]] = [
            weakref.WeakMethod(self._startup_gauges)]
        self.registry = MetricsRegistry(before_read=self.collect)
        self.output_path: Optional[str] = None
        self.chrome_trace = False
        self.prometheus = True
        self.device_fence_steps = True
        self._lock = threading.Lock()

    # -- configuration -----------------------------------------------------

    def configure(self, enabled: bool = True, output_path: str = "",
                  job_name: str = "DeepSpeedJobName", jsonl: bool = True,
                  prometheus: bool = True, chrome_trace: bool = False,
                  device_fence: bool = True,
                  max_span_events: int = 100_000) -> "Telemetry":
        with self._lock:
            self.enabled = bool(enabled)
            self.prometheus = bool(prometheus)
            self.chrome_trace = bool(chrome_trace)
            self.device_fence_steps = bool(device_fence)
            self.tracer.max_events = int(max_span_events)
            if not jsonl:
                # a reconfigure to in-memory-only must stop appending to
                # the PREVIOUS job's events.jsonl
                self.registry.detach_event_log()
            if enabled and (jsonl or prometheus or chrome_trace):
                base = os.path.join(output_path or "telemetry_logs", job_name)
                self.output_path = base
                if jsonl:
                    self.registry.attach_event_log(
                        os.path.join(base, "events.jsonl"))
            elif not enabled:
                self.output_path = None
        return self

    def reset(self) -> None:
        """Test isolation: drop all metrics/spans and disable."""
        with self._lock:
            self.registry.detach_event_log()
            self.enabled = False
            self.output_path = None
            self.tracer = SpanTracer(self.tracer.max_events)
            self.startup = StartupRecord(self._startup_closed)
            self.registry = MetricsRegistry(before_read=self.collect)

    # -- collect hooks -----------------------------------------------------

    def add_collect_hook(self, fn: Callable[[], None]) -> None:
        """``fn()`` runs, on the reader's thread, each time the registry
        is read as a whole (``snapshot()``, ``prometheus_text()``, so
        ``flush()`` and the rollup beat too): the place to set gauges
        that are derived from state kept elsewhere.  A bound method is
        held weakly, so a hook never keeps its object alive; a reader
        must not be made to wait, so a hook takes no lock that a hot path
        holds for long."""
        ref = weakref.WeakMethod(fn) if inspect.ismethod(fn) else (lambda: fn)
        with self._lock:
            # owners that were collected since: a process that makes many
            # engines and never reads its registry keeps no list of them
            self._collect_hooks = [r for r in self._collect_hooks
                                   if r() is not None] + [ref]

    def remove_collect_hook(self, fn: Callable[[], None]) -> None:
        with self._lock:
            self._collect_hooks = [r for r in self._collect_hooks
                                   if r() not in (None, fn)]

    def collect(self) -> None:
        """Run the collect hooks: what a read of the whole registry does
        first.  A source calls it before it goes away, so that the gauges
        it feeds keep its last state (a front-end's ``close()``)."""
        with self._lock:
            live = [(r, fn) for r in self._collect_hooks
                    for fn in [r()] if fn is not None]
            self._collect_hooks = [r for r, _ in live]  # owners collected
        for _, fn in live:
            try:
                fn()
            except Exception as e:  # an export must survive its sources
                from ..utils.logging import logger

                logger.warning(f"telemetry: collect hook {fn!r} failed "
                               f"({e!r})", exc_info=True)

    # -- hot-path surface (cheap no-ops when disabled) ---------------------

    def span(self, name: str, args: Optional[Dict[str, Any]] = None):
        if not self.enabled:
            return NOOP_SPAN
        return self.tracer.span(name, args)

    def startup_span(self, name: str,
                     args: Optional[Dict[str, Any]] = None):
        """A span of a START (``startup/...``): the tracer's own span on
        the start-up record, hub on or off; while the hub is on it lands
        in the hub's ring too.  For the few dozen phases of a start and a
        program's first call: a step has none."""
        return self.startup.span(name, args)

    def _startup_closed(self, name: str, start: float, end: float,
                        args: Dict[str, Any]) -> None:
        if self.enabled:
            self.tracer._record(name, start, end, args)
        if name in STARTUP_ROOTS and not args.get("depth"):
            from ..utils.logging import log_dist

            log_dist(self.startup_line())

    def startup_report(self) -> Dict[str, Any]:
        """The start-up record as a document: its spans, each root by
        phase (``tracer.startup_phases``) with the programs compiled and
        loaded under it, and the compile account's totals.  The
        ``startup`` context of a flight-recorder bundle."""
        account = get_compile_tracker().account
        events = self.startup.events()
        roots = startup_phases(events)
        for root in roots:
            made = account.sums(root["start"], root["end"])
            root["programs_loaded"] = int(made["cache_hits"])
            root["programs_compiled"] = int(made["programs"]
                                            - made["cache_hits"])
        return {"spans": events, "roots": roots,
                "compile_account": dict(account.totals)}

    def startup_line(self) -> str:
        """The newest start in one line: ``start-up 31.4 s: import 13.9
        (<the largest module> 13.7), config 0.1, ... | programs: 0
        compiled, 1 loaded``."""
        roots = self.startup_report()["roots"]
        if not roots:
            return "start-up: nothing recorded"
        root = roots[-1]
        parts = []
        for phase, seconds in root["phases"].items():
            part = f"{phase} {seconds:.1f}"
            if phase == "import" and root["largest_import"]:
                module, s = root["largest_import"]
                part += f" ({module} {s:.1f})"
            parts.append(part)
        return (f"start-up {root['total_s']:.1f} s ({root['root']}): "
                + ", ".join(parts)
                + f" | programs: {root['programs_compiled']} compiled, "
                  f"{root['programs_loaded']} loaded")

    def _startup_gauges(self) -> None:
        """Collect hook: ``startup/<phase>_s`` over the record's roots,
        worked out when the registry is read and never at a start."""
        if not self.enabled:
            return
        total: Dict[str, float] = {}
        for root in startup_phases(self.startup.events()):
            for phase, seconds in dict(root["phases"],
                                       total=root["total_s"]).items():
                total[phase] = total.get(phase, 0.0) + seconds
        for phase, seconds in total.items():
            self.set_gauge(f"startup/{phase}_s", seconds,
                           help="seconds of the process's starts "
                                "(startup/initialize, "
                                "startup/serving_frontend) by phase")

    def inc_counter(self, name: str, v: float = 1.0, help: str = "") -> None:
        if not self.enabled:
            return
        self.registry.counter(name, help).inc(v)

    def set_gauge(self, name: str, v: float, help: str = "") -> None:
        if not self.enabled:
            return
        self.registry.gauge(name, help).set(v)

    def observe(self, name: str, v: float, help: str = "",
                buckets=DEFAULT_BUCKETS) -> None:
        if not self.enabled:
            return
        self.registry.histogram(name, help, buckets=buckets).observe(v)

    def emit_event(self, kind: str, payload: Dict[str, Any]) -> None:
        if not self.enabled:
            return
        self.registry.emit_event(kind, payload)

    def record_step(self, rec: StepRecord) -> None:
        if not self.enabled:
            return
        publish_step_record(self.registry, rec)
        # cross-process streaming (telemetry/rollup.py): a compact copy
        # rides the bounded ring until the next publisher beat ships it
        # to rank 0's rollup (no-op unless aggregation enabled it)
        from .rollup import get_step_stream

        get_step_stream().push(rec)

    # -- export ------------------------------------------------------------

    def prometheus_text(self) -> str:
        return self.registry.prometheus_text()

    def flush(self) -> Dict[str, str]:
        """Write the configured exports (Prometheus textfile, Chrome trace)
        under ``output_path``; returns {kind: path}."""
        out: Dict[str, str] = {}
        if not (self.enabled and self.output_path):
            return out
        if self.prometheus:
            out["prometheus"] = self.registry.save_prometheus(
                os.path.join(self.output_path, "metrics.prom"))
        if self.chrome_trace:
            out["chrome_trace"] = self.tracer.save_chrome_trace(
                os.path.join(self.output_path, "trace.json"))
        return out


_default = Telemetry()
# a compile's seconds go to the start-up span it ran under
get_compile_tracker().account.open_span = \
    lambda: _default.startup.innermost()
# the package's own import, stamped by its first and last line (this
# package is first imported after both, unless the package's import
# itself reaches here)
_stamps = getattr(sys.modules.get(__name__.rpartition(".")[0]),
                  "_IMPORT_STAMPS", None)
if _stamps and None not in _stamps:
    _default.startup.add("startup/package_import", *_stamps)


def get_telemetry() -> Telemetry:
    return _default


def configure(**kw) -> Telemetry:
    return _default.configure(**kw)


def configure_from_config(tcfg: Any) -> Telemetry:
    """Configure the hub from a ``TelemetryConfig`` (runtime/config.py)."""
    return _default.configure(
        enabled=bool(getattr(tcfg, "enabled", False)),
        output_path=getattr(tcfg, "output_path", "") or "",
        job_name=getattr(tcfg, "job_name", "DeepSpeedJobName"),
        jsonl=bool(getattr(tcfg, "jsonl", True)),
        prometheus=bool(getattr(tcfg, "prometheus", True)),
        chrome_trace=bool(getattr(tcfg, "chrome_trace", False)),
        device_fence=bool(getattr(tcfg, "device_fence", True)),
        max_span_events=int(getattr(tcfg, "max_span_events", 100_000)))


def span(name: str, args: Optional[Dict[str, Any]] = None):
    """Module-level convenience: ``with telemetry.span("zero/gather"): ...``"""
    return _default.span(name, args)


def startup_span(name: str, args: Optional[Dict[str, Any]] = None):
    """``with telemetry.startup_span("startup/import", {"module": ...}):``
    (:meth:`Telemetry.startup_span`)."""
    return _default.startup_span(name, args)
