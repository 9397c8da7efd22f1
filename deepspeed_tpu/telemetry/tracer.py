"""Host-side span tracer — nested timed regions, Chrome-trace export.

Role: the correlation layer the reproduction lacked (ISSUE 1).  The
device side of every hot path is already observable through
``profiling/collective_trace.py`` (XLA lanes under ``jax.profiler``);
this module adds the HOST side — ``telemetry.span("zero/all_gather")``
around dispatch/placement/IO work — and exports the same Chrome-trace
JSON event shape (``ph: "X"`` duration events, microsecond timestamps)
so both can be loaded into one Perfetto/chrome://tracing view and read
against each other.

Spans nest per thread (a thread-local stack carries depth and parent),
are bounded in memory (``max_events`` ring), and can optionally close
with a device fence so a span around dispatched device work measures
execution, not enqueue.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional


def device_fence(value=None) -> None:
    """Best-effort device drain.  ``jax.effects_barrier()`` only flushes
    EFFECTS (debug callbacks, io) — it does NOT wait for dispatched pure
    computations, so pass the ``value`` a span's work produced to get a
    real execution fence (``block_until_ready`` on it, which waits for
    the device on the TPU — checked on the v5e, PR 21)."""
    try:
        import jax

        if value is not None:
            jax.block_until_ready(value)
        jax.effects_barrier()
    except Exception as e:  # fence failure ⇒ host-time spans, say so once
        from ..utils.logging import debug_once

        debug_once("tracer/device_fence",
                   f"device fence failed ({e!r}); span timings reflect "
                   f"dispatch, not device completion")


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

    @contextmanager
    def span(self, name: str, fence: bool = False,
             args: Optional[Dict[str, Any]] = None):
        """Time a nested region.  ``fence=True`` flushes jax EFFECTS
        before the end stamp; dispatched pure computations are only
        fenced by blocking on their results — do that INSIDE the span
        (``jax.block_until_ready(out)`` / a dependent scalar fetch) when
        the span must measure execution rather than enqueue."""
        stack = self._stack()
        stack.append(name)
        start = time.perf_counter()
        try:
            yield self
        finally:
            if fence:
                device_fence()
            end = time.perf_counter()
            stack.pop()
            ev = {
                "ph": "X", "cat": "host", "name": name,
                "pid": os.getpid(), "tid": threading.get_ident(),
                "ts": round((start - self._t0) * 1e6, 1),
                "dur": round((end - start) * 1e6, 1),
            }
            span_args = dict(args or {})
            span_args["depth"] = len(stack)
            if stack:
                span_args["parent"] = stack[-1]
            ev["args"] = span_args
            self._append(ev)

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


@contextmanager
def _noop_cm():
    yield None


NOOP_SPAN = _noop_cm
