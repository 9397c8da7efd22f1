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
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

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
            args: Optional[Dict[str, Any]] = None) -> None:
        """A span whose ends were stamped elsewhere, in
        ``time.perf_counter()`` seconds (a request's queue wait, from its
        record's own stamps).  It belongs to no thread's stack, so it
        carries neither depth nor parent, and it goes to the ring only."""
        self._record(name, start, end, dict(args) if args else {})

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

