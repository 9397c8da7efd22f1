"""Per-collective device timing from a profiler trace.

Reference: the ``comms_logger`` timing wrapper (``deepspeed/comm/comm.py``
[K], SURVEY §2.4) times every collective at the call site.  Under XLA the
hot-path collectives live INSIDE compiled programs where Python cannot
time them, so the equivalent is trace-sourced: run the step under
``jax.profiler.trace`` and aggregate the device lanes' collective op
durations.

Works wherever the profiler emits device/XLA op events (TPU-VMs, the CPU
backend used by the test suite).  Where the device trace comes back
empty the helper returns ``{}`` and logs once; eager
verbs (``comm.all_reduce`` etc. with ``comms_logger.configure(True)``)
and the ``ds_bench`` CLI remain the measured-latency paths there.
"""

from __future__ import annotations

import collections
import contextlib
import glob
import gzip
import json
import os
import re
import tempfile
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax

from ..utils.logging import logger

#: substrings of HLO/op names that identify collectives across backends
#: (TPU HLO names like "all-reduce.3"; CPU lanes use lowered primitive
#: names like "psum.7")
COLLECTIVE_PATTERNS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective", "psum", "pmean", "pmax",
    "all_gather", "all_to_all", "ppermute", "send", "recv",
)


# ---------------------------------------------------------------------------
# shared profiler session
# ---------------------------------------------------------------------------
# ``jax.profiler.trace`` sessions DO NOT NEST — opening a second one
# raises.  Every trace consumer in this repo (the exec-order census, the
# anatomy capture, ad-hoc ``profile_collectives``) therefore goes
# through ONE shared session: the first opener owns the real
# ``jax.profiler.trace`` context, nested openers reuse its output dir,
# and work that needs the *written* trace files (they only exist after
# the owning session closes) registers an ``on_session_close`` hook.

_session_lock = threading.Lock()
_active_session: Optional[Dict[str, Any]] = None  # {"dir": str, "post": []}


def active_trace_session() -> Optional[str]:
    """The output dir of the currently open shared session, or None."""
    with _session_lock:
        return _active_session["dir"] if _active_session else None


def on_session_close(fn: Callable[[str], Any]) -> bool:
    """Run ``fn(trace_dir)`` when the open shared session closes (trace
    files are on disk by then).  Returns False — and does nothing — when
    no session is open (caller should act immediately instead)."""
    with _session_lock:
        if _active_session is None:
            return False
        _active_session["post"].append(fn)
        return True


@contextlib.contextmanager
def shared_trace_session(trace_dir: Optional[str] = None):
    """ONE ``jax.profiler.trace`` for however many consumers are
    stacked.  The outermost caller opens (and later closes) the real
    profiler session; nested callers get the same dir and never open a
    second session (which would raise).  Yields the trace output dir."""
    global _active_session
    with _session_lock:
        if _active_session is not None:
            nested_dir = _active_session["dir"]
        else:
            nested_dir = None
            tmp = trace_dir or tempfile.mkdtemp(prefix="ds_anatomy_trace_")
            _active_session = {"dir": tmp, "post": []}
    if nested_dir is not None:
        yield nested_dir
        return
    try:
        with jax.profiler.trace(tmp):
            yield tmp
    finally:
        with _session_lock:
            posts = _active_session["post"] if _active_session else []
            _active_session = None
        for fn in posts:
            try:
                fn(tmp)
            except Exception as e:  # a post-hook must not mask the trace
                logger.warning(
                    f"shared trace session: close hook failed ({e!r})")


def begin_shared_session(trace_dir: Optional[str] = None) -> Optional[str]:
    """Open the shared profiler session WITHOUT a context manager — the
    fleet profiler plane arms at one train step and disarms N steps
    later, so the open and the close live in different calls.

    Returns the trace output dir when THIS caller became the owner, or
    ``None`` when a session is already open (the caller must not close
    it — re-arm after the owner finishes instead).  Pair every non-None
    return with :func:`end_shared_session`."""
    global _active_session
    with _session_lock:
        if _active_session is not None:
            return None
        tmp = trace_dir or tempfile.mkdtemp(prefix="ds_fleet_trace_")
        _active_session = {"dir": tmp, "post": []}
    try:
        jax.profiler.start_trace(tmp)
    except Exception:
        with _session_lock:
            _active_session = None
        raise
    return tmp


def end_shared_session() -> Optional[str]:
    """Close a session opened with :func:`begin_shared_session`: stop the
    profiler, run the registered close hooks (trace files are on disk),
    and return the trace dir — or ``None`` when no session was open."""
    global _active_session
    with _session_lock:
        if _active_session is None:
            return None
        tmp = _active_session["dir"]
        posts = list(_active_session["post"])
        _active_session = None
    try:
        jax.profiler.stop_trace()
    finally:
        for fn in posts:
            try:
                fn(tmp)
            except Exception as e:  # a post-hook must not mask the trace
                logger.warning(
                    f"shared trace session: close hook failed ({e!r})")
    return tmp


#: XLA HLO instruction names: lowercase identifier, optional dashes and
#: dotted suffixes — nothing host-side matches this shape
_HLO_NAME_RE = re.compile(r"[a-z][a-z0-9_.\-]*")


def parse_trace_events(trace_dir: str,
                       patterns: Optional[Sequence[str]]
                       = COLLECTIVE_PATTERNS
                       ) -> list:
    """Individual collective op events from a ``jax.profiler.trace``
    output dir, in device-timestamp order →
    ``[{ts_us, dur_us, name, lane}, ...]``.  Only events on device/XLA
    lanes count — host Python frames are excluded.  ``patterns=None``
    keeps EVERY device-lane op (the anatomy plane's full-timeline view);
    the default keeps collectives only.

    The ordering is what makes this the EXECUTION-order source: within
    one device lane, XLA runs a compiled program's thunks in a
    deterministic sequence, so two ranks executing the same SPMD
    program see the same collective order here — unlike the
    ``comms_logger`` execution probes, whose host callbacks interleave
    arbitrarily across device shards."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"),
                      recursive=True)
    out = []
    for fp in files:
        with gzip.open(fp) as f:
            tr = json.load(f)
        events = tr.get("traceEvents", [])
        lanes = {e["pid"]: e.get("args", {}).get("name", "")
                 for e in events
                 if e.get("ph") == "M" and e.get("name") == "process_name"}
        for e in events:
            if e.get("ph") != "X":
                continue
            lane = lanes.get(e.get("pid"), "")
            # device lanes: '/device:TPU:0', '/host:CPU' XLA lane; skip
            # pure-python lanes ('/host:python' frames carry $file refs)
            if not (lane.startswith("/device")
                    or lane.startswith("/host:CPU")):
                continue
            name = e.get("name", "")
            low = name.lower()
            if low.startswith("end:") or name.startswith("$"):
                continue  # CPU tracer end markers / python source refs
            # the CPU tracer folds host-side spans into the '/host:CPU'
            # process — on SOME builds onto the very thread the XLA
            # thunks report on, so thread names can't separate them.
            # Shape does: XLA thunk names are lowercase HLO identifiers
            # ('dot.4', 'multiply_add_fusion', 'all-reduce.3') while
            # host spans carry call syntax or CamelCase
            # ('PjitFunction(jit(f))', 'TfrtCpuExecutable::Execute',
            # 'np.asarray(jax.Array)')
            if lane.startswith("/host:CPU") \
                    and not _HLO_NAME_RE.fullmatch(name):
                continue
            if patterns is None or any(p in low for p in patterns):
                out.append({"ts_us": float(e.get("ts", 0.0)),
                            "dur_us": float(e.get("dur", 0.0)),
                            "name": name, "lane": lane})
    out.sort(key=lambda ev: (ev["ts_us"], ev["name"]))
    return out


def parse_device_events(trace_dir: str) -> List[Dict[str, Any]]:
    """EVERY device-lane op event from a profiler trace dir, timestamp
    ordered — the anatomy classifier's input (collectives + compute +
    infeed/host waits, not just the collective subset)."""
    return parse_trace_events(trace_dir, patterns=None)


def parse_trace(trace_dir: str,
                patterns: Sequence[str] = COLLECTIVE_PATTERNS
                ) -> Dict[str, Dict[str, float]]:
    """Aggregate collective op durations from a ``jax.profiler.trace``
    output dir → ``{op_name: {count, total_us, mean_us}}``.  Only events
    on device/XLA lanes count — host Python frames are excluded."""
    durs: Dict[str, float] = collections.defaultdict(float)
    counts: collections.Counter = collections.Counter()
    for ev in parse_trace_events(trace_dir, patterns):
        durs[ev["name"]] += ev["dur_us"]
        counts[ev["name"]] += 1
    return {n: {"count": float(counts[n]), "total_us": round(durs[n], 1),
                "mean_us": round(durs[n] / max(counts[n], 1), 2)}
            for n in durs}


def feed_exec_census(trace_dir: str, ledger: Optional[Any] = None,
                     patterns: Sequence[str] = COLLECTIVE_PATTERNS,
                     dedupe_lanes: bool = True) -> int:
    """Opt-in execution-order census (ROADMAP item): replay a profiler
    trace's device-lane collective events, in timestamp order, into the
    :class:`~..telemetry.collective_ledger.CollectiveLedger` EXEC lane.

    The exec chain hashes only op identity (timings differ across ranks
    by nature), so two ranks that ran the same compiled program under
    the profiler agree on ``exec_tail_hash`` — this lane IS cross-rank
    comparable, unlike the unordered ``record_exec`` probe feed.  With
    ``dedupe_lanes`` (default) only the first device lane is replayed:
    in a single-process multi-device mesh every shard's lane shows the
    same program, and feeding all of them would count each collective
    ``local_device_count`` times.  Returns the number of entries fed.
    """
    if ledger is None:
        from ..telemetry.collective_ledger import get_collective_ledger

        ledger = get_collective_ledger()
    if not ledger.enabled:
        # calling the census IS the opt-in: an offline post-mortem
        # process never ran telemetry config, and a disabled ledger
        # would silently swallow every record_exec while this function
        # still reported N entries fed
        ledger.configure(enabled=True)
    events = parse_trace_events(trace_dir, patterns)
    if not events:
        logger.warning(
            "feed_exec_census: no device collective events in the trace "
            "(this backend exported no device lanes)")
        return 0
    if dedupe_lanes:
        first_lane = events[0]["lane"]
        events = [ev for ev in events if ev["lane"] == first_lane]
    for ev in events:
        ledger.record_exec(ev["name"], 0, dur_us=ev["dur_us"],
                           ts_us=ev["ts_us"], source="exec_trace")
    return len(events)


def collect_exec_census(fn: Callable[..., Any], *args,
                        iters: int = 1,
                        ledger: Optional[Any] = None,
                        trace_dir: Optional[str] = None,
                        patterns: Sequence[str] = COLLECTIVE_PATTERNS,
                        **kwargs) -> int:
    """Run ``fn(*args)`` under the SHARED profiler session and feed the
    execution-order census from the resulting trace.

    This is the session-safe wrapper around :func:`feed_exec_census`:
    when another consumer (the anatomy capture) already holds the shared
    session, no second ``jax.profiler.trace`` is opened — the steps run
    inside the existing window and the census feed is deferred to the
    owning session's close (the trace files exist only then).  Returns
    the entries fed, or ``-1`` when the feed was deferred."""
    out = fn(*args, **kwargs)  # warmup/compile outside the window
    jax.block_until_ready(out)
    nested = active_trace_session() is not None
    with shared_trace_session(trace_dir) as tdir:
        for _ in range(max(int(iters), 1)):
            out = fn(*args, **kwargs)
        jax.block_until_ready(out)
        if nested:
            on_session_close(
                lambda d: feed_exec_census(d, ledger=ledger,
                                           patterns=patterns))
            return -1
    return feed_exec_census(tdir, ledger=ledger, patterns=patterns)


def profile_collectives(fn: Callable[..., Any], *args,
                        iters: int = 3,
                        trace_dir: Optional[str] = None,
                        patterns: Sequence[str] = COLLECTIVE_PATTERNS,
                        **kwargs) -> Dict[str, Dict[str, float]]:
    """Run ``fn(*args)`` ``iters`` times under the profiler and return the
    per-collective device-time table.  ``fn`` should be the compiled step
    (compile OUTSIDE the trace window: the first call is warmed here)."""
    out = fn(*args, **kwargs)  # warmup/compile outside the trace
    jax.block_until_ready(out)
    tmp = trace_dir or tempfile.mkdtemp(prefix="ds_comms_trace_")
    with shared_trace_session(tmp) as tmp:
        for _ in range(iters):
            out = fn(*args, **kwargs)
        jax.block_until_ready(out)
    table = parse_trace(tmp, patterns)
    if not table:
        logger.warning(
            "profile_collectives: no device collective events in the trace "
            "(this backend exported no device lanes) — use "
            "eager comm verbs with comms_logger or the ds_bench CLI for "
            "measured latencies")
    return table
