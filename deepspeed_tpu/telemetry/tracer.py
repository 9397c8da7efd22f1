"""Span tracer: nested timed regions of host code, written to two sinks.

``telemetry.span("inference/plan")`` around a piece of host work records
one event in this tracer's ring (name, start, duration, depth, parent:
what the benchmark's per-layer readers and the flight recorder read) and
enters a ``jax.profiler.TraceAnnotation`` of the same name.  The second
sink costs nothing while no profiler session runs; while one does, the
span lands on its host thread in the profiler's own trace, on the
device's clock, so an idle gap of the device can be named by the program
span the host was in.  There is one file to open: the profiler's.

Spans nest per thread (a thread-local stack carries depth and parent)
and are bounded in memory (``max_events`` ring).  A span times the host:
around dispatched device work it measures the enqueue unless the work's
result is fetched (or blocked on) inside it.  ``add()`` records a span
whose two ends were stamped elsewhere (a request's phases).  The ring
can still be exported as Chrome-trace JSON for the cluster timeline
(``telemetry collect``), shifted by the clock-sync offset.

:class:`StartupRecord` is the same tracer as a second, small ring that is
written whether the hub is on or off: the few dozen spans of a start
(``startup/initialize``, ``startup/serving_frontend`` and what lies under
them, each program's ``startup/first_call``).  A step has none of them.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

_TraceAnnotation = None


def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, imported on the first live span:
    a process with the hub off never imports the profiler for it."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation

        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation


class _NoopSpan:
    """What ``span()`` returns with the hub off: one shared object."""

    __slots__ = ()
    #: a live span's two stamps; the no-op has none
    start = end = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args: Any) -> None:
        pass


NOOP_SPAN = _NoopSpan()


class _Span:
    """One live span: the tracer's ring and the profiler's trace."""

    #: ``start`` / ``end``: the span's own stamps (``time.perf_counter()``
    #: seconds, None until it is entered / left), for a caller that
    #: records a longer span between the ends of two of its spans
    #: (``SpanTracer.add``)
    __slots__ = ("_tracer", "_name", "_args", "_annotation", "start", "end")

    def __init__(self, tracer: "SpanTracer", name: str,
                 args: Optional[Dict[str, Any]]):
        self._tracer = tracer
        self._name = name
        self._args = dict(args) if args else {}
        self._annotation = _trace_annotation()(name, **self._args)
        self.start = self.end = None

    def set(self, **args: Any) -> None:
        """Arguments known only once the work is done (how many were
        admitted): they reach the ring, not the profiler, whose
        annotation took its arguments when the span opened."""
        self._args.update(args)

    # The span's own bookkeeping lies INSIDE its annotation (entered
    # first, left last), so that in the profiler's trace two spans in a
    # row abut: what is left between them is the caller's.  On the chip's
    # host a boundary took 30-50 us of a round's gap with the ring written
    # after the annotation closed (PERF.md, PR 38).

    def __enter__(self):
        self._annotation.__enter__()
        self._tracer._stack().append(self._name)
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.end = time.perf_counter()
        stack = self._tracer._stack()
        stack.pop()
        self._args["depth"] = len(stack)
        if stack:
            self._args["parent"] = stack[-1]
        self._tracer._record(self._name, self.start, self.end, self._args)
        self._annotation.__exit__(*exc)
        return False


class SpanTracer:
    """Bounded in-memory span buffer with Chrome-trace JSON export."""

    def __init__(self, max_events: int = 100_000):
        self.max_events = int(max_events)
        #: ring: once full, the OLDEST span is evicted — a long run's
        #: export keeps the window around its end (stalls near the end of
        #: a run are what traces get opened for)
        self._events: "collections.deque[Dict[str, Any]]" = \
            collections.deque(maxlen=self.max_events)
        self._dropped = 0
        self._lock = threading.Lock()
        self._tls = threading.local()
        #: one stable origin so span timestamps are comparable across
        #: threads (perf_counter has an arbitrary epoch per process)
        self._t0 = time.perf_counter()
        #: store-clock mapping (telemetry/clocksync.py): set when this
        #: process estimated its offset to the rendezvous store clock —
        #: exported in the trace metadata so N hosts' traces merge onto
        #: ONE timeline (``telemetry collect`` -> cluster_trace.json)
        self._clock_sync: Optional[Dict[str, Any]] = None

    @property
    def max_events(self) -> int:
        return self._max_events

    @max_events.setter
    def max_events(self, n: int) -> None:
        self._max_events = int(n)
        ring = getattr(self, "_events", None)
        if ring is not None and ring.maxlen != self._max_events:
            self._events = collections.deque(ring, maxlen=self._max_events)

    # ------------------------------------------------------------------

    def _stack(self) -> List[str]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _append(self, ev: Dict[str, Any]) -> None:
        with self._lock:
            if len(self._events) == self._events.maxlen:
                self._dropped += 1  # ring full: oldest event falls off
            self._events.append(ev)

    def _record(self, name: str, start: float, end: float,
                args: Dict[str, Any]) -> None:
        self._append({
            "ph": "X", "cat": "host", "name": name,
            "pid": os.getpid(), "tid": threading.get_ident(),
            "ts": round((start - self._t0) * 1e6, 1),
            "dur": round((end - start) * 1e6, 1), "args": args})

    def span(self, name: str, args: Optional[Dict[str, Any]] = None
             ) -> _Span:
        """Time a nested region: ``with tracer.span("zero/gather"): ...``.
        ``args`` given here reach both sinks; ``set()`` on the span adds
        what is known only at its end."""
        return _Span(self, name, args)

    def add(self, name: str, start: float, end: float,
            args: Optional[Dict[str, Any]] = None,
            parent: Optional[str] = None, depth: Optional[int] = None
            ) -> None:
        """A span whose ends were stamped elsewhere, in
        ``time.perf_counter()`` seconds (a request's queue wait, from its
        record's own stamps).  It belongs to no thread's stack, so it
        carries neither depth nor parent unless the caller knows them
        (an import that ran before the tracer's own module was loaded),
        and it goes to the ring only."""
        args = dict(args) if args else {}
        if depth is not None:
            args["depth"] = depth
        if parent is not None:
            args["parent"] = parent
        self._record(name, start, end, args)

    # ------------------------------------------------------------------

    def set_clock_sync(self, offset_s: float, rtt_s: Optional[float] = None,
                       generation: Any = None,
                       node_id: Optional[str] = None) -> None:
        """Record this process's estimated offset to the store clock
        (``store_time ~= perf_counter() + offset_s``).  Span ``ts``
        values stay in the tracer's private timebase; the metadata
        carries ``trace_to_store_offset_us`` so any consumer can shift
        ``ev.ts + trace_to_store_offset_us`` onto the shared store
        timeline — that arithmetic is what clock-aligns the per-process
        lanes in ``cluster_trace.json``."""
        with self._lock:
            self._clock_sync = {
                "offset_s": float(offset_s),
                "rtt_s": None if rtt_s is None else float(rtt_s),
                "generation": generation,
                "node_id": node_id,
                # ts (us since _t0) + this = us on the STORE clock
                "trace_to_store_offset_us": round(
                    (self._t0 + float(offset_s)) * 1e6, 1),
            }

    def clock_sync(self) -> Optional[Dict[str, Any]]:
        with self._lock:
            return dict(self._clock_sync) if self._clock_sync else None

    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    @property
    def dropped(self) -> int:
        return self._dropped

    def reset(self) -> None:
        with self._lock:
            self._events = collections.deque(maxlen=self._max_events)
            self._dropped = 0

    def chrome_trace(self) -> Dict[str, Any]:
        """The ``{"traceEvents": [...]}`` document chrome://tracing and
        Perfetto load; the ``X`` event shape matches what
        ``profiling/collective_trace.parse_trace`` consumes from the XLA
        profiler, so host spans and device lanes merge into one view."""
        meta: Dict[str, Any] = {"source": "deepspeed_tpu.telemetry",
                                "dropped_events": self._dropped}
        sync = self.clock_sync()
        if sync is not None:
            meta["clock_sync"] = sync
        return {"traceEvents": self.events(),
                "displayTimeUnit": "ms",
                "metadata": meta}

    def save_chrome_trace(self, path: str) -> str:
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(self.chrome_trace(), fh)
        os.replace(tmp, path)  # atomic: a crashed flush never tears the file
        return path



#: the spans that are a start of their own: when one closes at depth 0 the
#: hub says in one line where the start went
STARTUP_ROOTS = ("startup/initialize", "startup/serving_frontend")


class StartupRecord(SpanTracer):
    """The start-up record: a :class:`SpanTracer` of a few hundred events
    that is written whether the hub is on or off (``Telemetry.startup``).

    Its spans are the tracer's own ``_Span`` (ring and profiler's trace as
    any span); each event also carries its two stamps unrounded, ``start``
    and ``end`` in ``time.perf_counter()`` seconds, so that a reader can
    hold them against stamps of its own.  ``on_close(name, start, end,
    args)`` is told of every event: the hub copies it into its own ring
    while it is on.  The spans open on a thread are kept (innermost last),
    so that the compile account can add a compile's seconds to the span it
    ran under (``innermost()``)."""

    def __init__(self, on_close: Callable[[str, float, float, Dict], Any],
                 max_events: int = 512):
        super().__init__(max_events)
        self._on_close = on_close

    def _open(self) -> List[_Span]:
        spans = getattr(self._tls, "open", None)
        if spans is None:
            spans = self._tls.open = []
        return spans

    def span(self, name: str, args: Optional[Dict[str, Any]] = None
             ) -> _Span:
        """A start-up span, to be entered at once (``with``): it counts as
        open on this thread until it is recorded."""
        span = _Span(self, name, args)
        spans = self._open()
        # one that was made and never entered is dropped here
        spans[:] = [s for s in spans if s.start is not None] + [span]
        return span

    def innermost(self) -> Optional[_Span]:
        """The innermost start-up span open on the calling thread."""
        for span in reversed(self._open()):
            if span.start is not None and span.end is None:
                return span
        return None

    def _record(self, name: str, start: float, end: float,
                args: Dict[str, Any]) -> None:
        spans = self._open()
        for i in range(len(spans) - 1, -1, -1):
            if spans[i]._args is args:     # the span that closes, and
                del spans[i:]              # any inside it that never did
                break
        if name == "startup/first_call":
            args["cache_hit"] = bool(args.get("cache_hits")
                                     and not args.get("cache_misses"))
        self._append({
            "ph": "X", "cat": "startup", "name": name,
            "pid": os.getpid(), "tid": threading.get_ident(),
            "ts": round((start - self._t0) * 1e6, 1),
            "dur": round((end - start) * 1e6, 1),
            "start": start, "end": end, "args": args})
        self._on_close(name, start, end, args)


def startup_phases(events: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Each root of a start-up record by phase: ``{"root", "start",
    "end", "total_s", "phases": {phase: seconds}, "largest_import":
    (module, seconds)}``.  A span's SELF time (its own less its
    children's) goes to the phase its name gives it (``startup/place/*``
    is ``place``, ``startup/engine/optimizer`` is ``engine``, the root's
    own is ``other``), so the phases add up to the root.  A span is a
    child of the innermost span of the same thread that contains it."""
    out = []
    nested = [e for e in events if "depth" in e["args"]]
    for root in nested:
        if root["name"] not in STARTUP_ROOTS or root["args"]["depth"]:
            continue
        inside = sorted(
            (e for e in nested if e["tid"] == root["tid"]
             and root["start"] <= e["start"] and e["end"] <= root["end"]),
            key=lambda e: (e["start"], -e["end"]))
        self_s = {id(e): e["end"] - e["start"] for e in inside}
        stack: List[Dict[str, Any]] = []
        for e in inside:
            while stack and stack[-1]["end"] < e["end"]:
                stack.pop()
            if stack:
                self_s[id(stack[-1])] -= e["end"] - e["start"]
            stack.append(e)
        phases: Dict[str, float] = {}
        imports: Dict[str, float] = {}
        for e in inside:
            phase = "other" if e is root else e["name"].split("/")[1]
            phases[phase] = phases.get(phase, 0.0) + self_s[id(e)]
            if e["name"] == "startup/import":
                module = str(e["args"].get("module"))
                imports[module] = imports.get(module, 0.0) + self_s[id(e)]
        out.append({"root": root["name"], "start": root["start"],
                    "end": root["end"],
                    "total_s": root["end"] - root["start"],
                    "phases": phases,
                    "largest_import": max(imports.items(),
                                          key=lambda kv: kv[1],
                                          default=None)})
    return out
