"""Compile/recompile tracker — ``tracked_jit`` over every engine jit site.

The engine compiles ~19 distinct XLA programs with documented recompile
hazards (tail-batch shapes, 1-bit warmup boundaries, random-LTD keep
buckets — ``runtime/engine.py``), and until now not one compile event
was recorded anywhere: a recompile storm showed up only as mysteriously
slow steps.  This module is the missing ledger:

* :func:`tracked_jit` — a thin wrapper around ``jax.jit`` that goes
  through the AOT path (``jit(fn).lower(*args).compile()``) on the
  first call per **program signature** so lower and compile wall time
  are measured separately, and dispatches the cached executable on
  every later call (one dict lookup over a signature key — the same
  work jax's own C++ cache does).
* A **program signature**: the abstract avals (shape/dtype/weak-type)
  of every argument leaf, the donate set, and a ``static_context``
  dict for closure-baked statics (gas, 1-bit warmup flag, LTD keep
  bucket).  A second distinct signature at the same *site* is a
  **recompile**, and the event carries a structured diff naming the
  cause — which leaf, which dimension, old → new (shape / dtype /
  static / structure change).
* Counters/gauges in the metrics registry (``compile/events_total``,
  ``compile/recompiles_total``, ``compile/time_ms_total``,
  ``compile/live_programs``) and a per-site program table embedded in
  every flight-recorder debug bundle (context ``compile_programs``).

Anything the AOT path cannot handle (exotic arg types, backend quirks)
falls back to calling the plain jitted function — the event is still
recorded (with ``fallback: true`` and combined timing), the program
just isn't separately lower/compile-split.

:class:`CompileAccount` is the account of the WHOLE process, whatever the
tracker is set to: what JAX itself reports of every trace, lowering,
compile and read of the persistent cache (``jax.monitoring``), from the
moment this module is imported.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import re
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from ...utils.logging import logger


def _leaf_sig(leaf: Any) -> Tuple:
    """(shape, dtype, weak_type) for array-likes; repr for the rest."""
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is not None and dtype is not None:
        return (tuple(shape), str(dtype),
                bool(getattr(leaf, "weak_type", False)))
    return ("pyval", repr(leaf))


def signature_of(args: Tuple, kwargs: Dict[str, Any],
                 static_context: Optional[Dict[str, Any]] = None,
                 donate: Tuple = ()) -> Dict[str, Any]:
    """The cross-call comparison key for one compiled program: per-leaf
    avals (keyed by argument path), the static context, the donate set."""
    from jax.tree_util import keystr, tree_flatten_with_path

    leaves: Dict[str, Tuple] = {}
    for i, a in enumerate(args):
        for path, leaf in tree_flatten_with_path(a)[0]:
            leaves[f"arg{i}{keystr(path)}"] = _leaf_sig(leaf)
    for k in sorted(kwargs):
        for path, leaf in tree_flatten_with_path(kwargs[k])[0]:
            leaves[f"kwarg[{k}]{keystr(path)}"] = _leaf_sig(leaf)
    return {"leaves": leaves,
            "static": dict(static_context or {}),
            "donate": tuple(donate)}


def _avals_key(args: Tuple, kwargs: Dict[str, Any]) -> Tuple:
    """What :func:`signature_of` tells apart, without its paths and
    strings: the tree's structure and each leaf's :func:`_leaf_sig`
    parts as they come (``signature_of`` walks ~40 leaves' paths in
    Python, 0.4 ms of every dispatch)."""
    from jax.tree_util import tree_flatten

    leaves, treedef = tree_flatten((args, kwargs))
    return (treedef, tuple(
        (leaf.shape, leaf.dtype, getattr(leaf, "weak_type", False))
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype")
        else repr(leaf) for leaf in leaves))


def signature_key(sig: Dict[str, Any]) -> Tuple:
    """Hashable form of :func:`signature_of` (the program-cache key)."""
    return (tuple(sorted(sig["leaves"].items())),
            tuple(sorted((k, repr(v)) for k, v in sig["static"].items())),
            sig["donate"])


def diff_signatures(old: Dict[str, Any],
                    new: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Structured recompile-cause diff: which leaf / static key changed,
    and HOW (the changed dimension by index, dtype old→new, ...) — the
    line an operator reads to know *why* step N stalled for a compile."""
    causes: List[Dict[str, Any]] = []
    ol, nl = old["leaves"], new["leaves"]
    for name in sorted(set(ol) | set(nl)):
        a, b = ol.get(name), nl.get(name)
        if a == b:
            continue
        if a is None or b is None:
            causes.append({"kind": "structure_change", "leaf": name,
                           "old": a and list(a), "new": b and list(b)})
            continue
        if a[0] == "pyval" or b[0] == "pyval":
            causes.append({"kind": "value_change", "leaf": name,
                           "old": a[-1], "new": b[-1]})
            continue
        (ashape, adt, awk), (bshape, bdt, bwk) = a, b
        if ashape != bshape:
            if len(ashape) == len(bshape):
                for d, (x, y) in enumerate(zip(ashape, bshape)):
                    if x != y:
                        causes.append({"kind": "shape_change", "leaf": name,
                                       "dim": d, "old": x, "new": y})
            else:
                causes.append({"kind": "rank_change", "leaf": name,
                               "old": list(ashape), "new": list(bshape)})
        if adt != bdt:
            causes.append({"kind": "dtype_change", "leaf": name,
                           "old": adt, "new": bdt})
        if awk != bwk:
            causes.append({"kind": "weak_type_change", "leaf": name,
                           "old": awk, "new": bwk})
    for key in sorted(set(old["static"]) | set(new["static"])):
        a, b = old["static"].get(key), new["static"].get(key)
        if a != b:
            causes.append({"kind": "static_change", "key": key,
                           "old": a, "new": b})
    if old["donate"] != new["donate"]:
        causes.append({"kind": "donate_change",
                       "old": list(old["donate"]),
                       "new": list(new["donate"])})
    return causes


@dataclasses.dataclass
class CompileEvent:
    site: str
    kind: str                 # "compile" (first at site) | "recompile"
    program: int              # per-site program ordinal (0-based)
    lower_ms: float
    compile_ms: float
    total_ms: float
    n_leaves: int
    static: Dict[str, Any]
    causes: List[Dict[str, Any]]  # empty on the first compile of a site
    fallback: bool = False
    ts: float = dataclasses.field(default_factory=time.time)

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)



class CompileAccount:
    """What JAX reports of making programs ready, for the whole process.

    ONE ``jax.monitoring`` duration listener and one event listener
    (:meth:`register`), called by JAX on the compiling thread once a
    trace, a lowering, a compile: a Python call a COMPILE, none a
    dispatch.  It keeps totals by kind, a bounded list of ``(stamp, kind,
    value)`` with ``stamp`` the event's END in ``time.perf_counter()``
    seconds, and adds each value to the arguments of the innermost
    start-up span open on that thread (``open_span``, set by the hub), so
    that a ``startup/first_call`` says how long its program was traced,
    lowered and compiled or loaded, and a phase of a start says what it
    compiled.  What falls under no span is the caller's.

    Kinds: ``trace_s`` (JAX reports every jitted function traced, the
    inner ones of a program too: an outer trace is counted less the inner
    ones that ended inside it, so the seconds add up), ``lower_s`` (jaxpr
    to MLIR), ``compile_s`` (the backend's compile, which INCLUDES a read
    of the persistent cache), ``cache_read_s`` and ``cache_saved_s`` (the
    cache's own: the read alone, and what the compile took when it was
    stored less the read), and the counts ``cache_hits`` /
    ``cache_misses`` (a miss is counted when the compiled program is
    written to the cache)."""

    DURATIONS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "compile_s",
        "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read_s",
        "/jax/compilation_cache/compile_time_saved_sec": "cache_saved_s",
    }
    COUNTS = {
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_misses",
    }
    #: the kinds a start-up span is told of
    SPAN_KINDS = frozenset(("trace_s", "lower_s", "compile_s",
                            "cache_hits", "cache_misses"))

    def __init__(self, max_events: int = 8192):
        self._lock = threading.Lock()
        self._tls = threading.local()
        self.totals: Dict[str, float] = dict.fromkeys(
            list(self.DURATIONS.values()) + list(self.COUNTS.values()), 0.0)
        #: ``[stamp, kind, value, thread]``, oldest first; a thread's run of
        #: traces (one program's) is one entry
        self._events: "collections.deque[List[Any]]" = collections.deque(
            maxlen=int(max_events))
        #: ``() -> the innermost start-up span open on this thread``
        self.open_span: Optional[Callable[[], Any]] = None
        self._registered = False

    def register(self) -> "CompileAccount":
        """Listen to ``jax.monitoring`` (once: JAX has no way to take a
        listener back)."""
        import jax

        if not self._registered:
            self._registered = True
            jax.monitoring.register_event_duration_secs_listener(
                self._on_duration)
            jax.monitoring.register_event_listener(self._on_event)
        return self

    def _on_duration(self, event: str, seconds: float, **_: Any) -> None:
        kind = self.DURATIONS.get(event)
        if kind is None:
            return
        now = time.perf_counter()
        if kind == "trace_s":
            # traces nest: the ones that ended inside this one are in it
            started = now - seconds
            traces = getattr(self._tls, "traces", None)
            if traces is None:
                traces = self._tls.traces = []
            while traces and traces[-1][0] >= started:
                seconds -= traces.pop()[1]
            traces.append((started, now - started))
            del traces[:-4096]
            seconds = max(seconds, 0.0)
        self._add(now, kind, seconds)

    def _on_event(self, event: str, **_: Any) -> None:
        kind = self.COUNTS.get(event)
        if kind is not None:
            self._add(time.perf_counter(), kind, 1.0)

    def _add(self, stamp: float, kind: str, value: float) -> None:
        thread = threading.get_ident()
        with self._lock:
            self.totals[kind] += value
            last = self._events[-1] if self._events else None
            if (kind == "trace_s" and last is not None
                    and last[1] == kind and last[3] == thread):
                last[0], last[2] = stamp, last[2] + value
            else:
                self._events.append([stamp, kind, value, thread])
        if kind in self.SPAN_KINDS and self.open_span is not None:
            span = self.open_span()
            if span is not None:
                span._args[kind] = span._args.get(kind, 0.0) + value

    def events(self, since: float = float("-inf"),
               until: float = float("inf")) -> List[Tuple[float, str, float]]:
        """``(stamp, kind, value)`` of the events kept that ended in
        ``(since, until]``."""
        with self._lock:
            return [(e[0], e[1], e[2]) for e in self._events
                    if since < e[0] <= until]

    def sums(self, since: float = float("-inf"),
             until: float = float("inf")) -> Dict[str, float]:
        """The kept events of ``(since, until]`` added up by kind, with
        ``programs``: how many compiles (or reads of the cache) ended
        there."""
        out = dict.fromkeys(self.totals, 0.0)
        out["programs"] = 0.0
        for _, kind, value in self.events(since, until):
            out[kind] += value
            out["programs"] += kind == "compile_s"
        return out

    def reset(self) -> None:
        """Test isolation: forget what was counted (the listeners stay)."""
        with self._lock:
            self.totals = dict.fromkeys(self.totals, 0.0)
            self._events.clear()


class CompileTracker:
    """Per-site program table + compile-event stream.

    Cheap when disabled (``tracked_jit`` then returns plain ``jax.jit``
    output); when enabled every tracked site pays one signature build +
    dict lookup per call — noise next to an XLA dispatch.
    """

    def __init__(self, enabled: bool = False, max_events: int = 512):
        self.enabled = bool(enabled)
        self.max_events = int(max_events)
        self._lock = threading.Lock()
        #: site -> list of program dicts (signature, timings, use counts)
        self._sites: Dict[str, List[Dict[str, Any]]] = {}
        self._events: List[CompileEvent] = []
        self.events_total = 0
        self.recompiles_total = 0
        self.time_ms_total = 0.0
        #: fns called with each CompileEvent (engine per-step attribution)
        self._listeners: List[Callable[[CompileEvent], Any]] = []
        #: fns called with (site, program, compiled_executable) right
        #: after a successful AOT compile — the anatomy plane's cost
        #: ledger harvests ``compiled.cost_analysis()`` here, at compile
        #: time, so the steady state pays nothing
        self._cost_harvesters: List[Callable[[str, int, Any], Any]] = []

    @property
    def account(self) -> CompileAccount:
        """The process-wide :class:`CompileAccount`: one, whichever
        tracker is asked and whether it is enabled or not."""
        return _account

    def configure(self, enabled: Optional[bool] = None,
                  max_events: Optional[int] = None) -> "CompileTracker":
        with self._lock:
            if enabled is not None:
                self.enabled = bool(enabled)
            if max_events:
                self.max_events = int(max_events)
        return self

    def reset(self) -> None:
        with self._lock:
            self._sites = {}
            self._events = []
            self.events_total = 0
            self.recompiles_total = 0
            self.time_ms_total = 0.0
            self._listeners = []
            self._cost_harvesters = []

    def add_listener(self, fn: Callable[[CompileEvent], Any]) -> None:
        self._listeners.append(fn)

    def add_cost_harvester(self, fn: Callable[[str, int, Any], Any]
                           ) -> None:
        """Register ``fn(site, program, compiled)`` to run after each
        successful AOT compile (fallback-path programs have no
        executable and are not harvested)."""
        self._cost_harvesters.append(fn)

    def harvest_cost(self, site: str, program: int, compiled: Any) -> None:
        for fn in list(self._cost_harvesters):
            try:
                fn(site, program, compiled)
            except Exception as e:  # harvest is best-effort telemetry
                logger.warning(f"compile tracker cost harvest failed at "
                               f"{site} ({e!r})")

    # -- recording ---------------------------------------------------------

    def record(self, site: str, sig: Dict[str, Any], lower_ms: float,
               compile_ms: float, fallback: bool = False) -> CompileEvent:
        with self._lock:
            progs = self._sites.setdefault(site, [])
            causes: List[Dict[str, Any]] = []
            kind = "compile"
            if progs:
                kind = "recompile"
                causes = diff_signatures(progs[-1]["signature"], sig)
            ev = CompileEvent(
                site=site, kind=kind, program=len(progs),
                lower_ms=round(lower_ms, 3), compile_ms=round(compile_ms, 3),
                total_ms=round(lower_ms + compile_ms, 3),
                n_leaves=len(sig["leaves"]), static=dict(sig["static"]),
                causes=causes, fallback=fallback)
            progs.append({"signature": sig, "event": ev.to_dict(),
                          "calls": 0})
            self._events.append(ev)
            del self._events[:-self.max_events]
            self.events_total += 1
            if kind == "recompile":
                self.recompiles_total += 1
            self.time_ms_total += ev.total_ms
            live = sum(len(p) for p in self._sites.values())
            listeners = list(self._listeners)
        self._publish(ev, live)
        for fn in listeners:
            try:
                fn(ev)
            except Exception as e:
                logger.warning(f"compile tracker listener failed: {e!r}")
        if kind == "recompile":
            logger.info(
                f"compile tracker: RECOMPILE at {site} "
                f"(program #{ev.program}, {ev.total_ms:.0f}ms): "
                + ("; ".join(format_cause(c) for c in causes[:4])
                   or "no signature diff (first call after cache reset?)"))
        return ev

    def note_call(self, site: str, program: int) -> None:
        with self._lock:
            progs = self._sites.get(site)
            if progs and 0 <= program < len(progs):
                progs[program]["calls"] += 1

    def _publish(self, ev: CompileEvent, live_programs: int) -> None:
        try:
            from .. import get_telemetry

            tel = get_telemetry()
            tel.inc_counter("compile/events_total",
                            help="XLA compile events (tracked jit sites)")
            if ev.kind == "recompile":
                tel.inc_counter("compile/recompiles_total",
                                help="recompiles of an already-compiled "
                                     "site (shape/dtype/static change)")
            tel.inc_counter("compile/time_ms_total", v=ev.total_ms,
                            help="cumulative lower+compile wall time (ms)")
            tel.set_gauge("compile/live_programs", live_programs,
                          help="distinct compiled programs across sites")
            tel.emit_event("compile", ev.to_dict())
        except Exception as e:  # metrics publish is best-effort
            logger.debug(f"compile tracker: metrics publish failed ({e!r})")

    # -- read side ---------------------------------------------------------

    def events(self, last: Optional[int] = None) -> List[CompileEvent]:
        with self._lock:
            evs = list(self._events)
        return evs[-last:] if last else evs

    def table(self) -> Dict[str, Any]:
        """Per-site program table — the flight-recorder context provider
        (``context["compile_programs"]`` in every debug bundle)."""
        with self._lock:
            sites = {
                site: [{"program": p["event"]["program"],
                        "kind": p["event"]["kind"],
                        "lower_ms": p["event"]["lower_ms"],
                        "compile_ms": p["event"]["compile_ms"],
                        "total_ms": p["event"]["total_ms"],
                        "static": p["event"]["static"],
                        "causes": p["event"]["causes"],
                        "fallback": p["event"]["fallback"],
                        "calls": p["calls"]}
                       for p in progs]
                for site, progs in self._sites.items()}
            return {"events_total": self.events_total,
                    "recompiles_total": self.recompiles_total,
                    "time_ms_total": round(self.time_ms_total, 3),
                    "sites": sites}


def format_cause(c: Dict[str, Any]) -> str:
    """One-line human rendering of a recompile cause (shared with the
    CLI's bundle summary)."""
    k = c.get("kind")
    if k == "shape_change":
        return (f"{c['leaf']} dim {c['dim']}: {c['old']} -> {c['new']}")
    if k == "dtype_change":
        return f"{c['leaf']} dtype {c['old']} -> {c['new']}"
    if k == "static_change":
        return f"static {c['key']}: {c['old']} -> {c['new']}"
    return f"{k}: {c.get('leaf', c.get('key', ''))}"


def program_name(site: str, static_context: Optional[Dict[str, Any]] = None
                 ) -> str:
    """The name a site's program carries on the device: ``site`` with its
    separators as ``_``, then each static that has a value as its key and
    the value (``inference_v2/decode_burst`` with ``n_steps=8`` gives
    ``inference_v2_decode_burst_n_steps8``, which the profiler shows as
    module ``jit_inference_v2_decode_burst_n_steps8``)."""
    parts = [re.sub(r"\W+", "_", site).strip("_")]
    for key, value in sorted((static_context or {}).items()):
        if value is None:
            continue
        if isinstance(value, bool):
            value = int(value)
        parts.append(key + re.sub(r"\W+", "_", str(value)))
    return "_".join(parts)


def _named(fn: Callable, name: str) -> Callable:
    """``fn`` under ``name``: ``jax.jit`` names a module after its
    function's ``__name__``, which a ``functools.partial`` or a bound
    method cannot be given (the first shows as ``jit__unknown``)."""
    @functools.wraps(fn)
    def call(*args, **kwargs):
        return fn(*args, **kwargs)

    call.__name__ = call.__qualname__ = name
    return call


class TrackedJit:
    """``jax.jit`` with a signature-keyed AOT cache + compile telemetry.

    Call surface matches the jitted function.  The ``lower`` attribute
    is forwarded so AOT callers keep working.
    """

    def __init__(self, fn: Callable, site: str, tracker: CompileTracker,
                 static_context: Optional[Dict[str, Any]] = None,
                 **jit_kwargs: Any):
        import jax

        self.site = site
        self.tracker = tracker
        self.static_context = dict(static_context or {})
        donate = jit_kwargs.get("donate_argnums", ())
        self._donate = (tuple(donate) if isinstance(donate, (tuple, list))
                        else (donate,))
        static = jit_kwargs.get("static_argnames", ())
        #: an AOT executable has its static arguments baked in and refuses
        #: them at call time; they are part of the signature key instead
        self._static_names = {static} if isinstance(static, str) \
            else set(static)
        self._jitted = jax.jit(fn, **jit_kwargs)
        self._programs: Dict[Tuple, Any] = {}  # sig key -> (idx, compiled)
        #: the same entries under a key that is cheap to make at every
        #: call (no paths, no strings): the steady state's only lookup
        self._by_avals: Dict[Tuple, Any] = {}
        self._fell_back = False
        self._lock = threading.Lock()

    def lower(self, *args, **kwargs):
        return self._jitted.lower(*args, **kwargs)

    def executables(self) -> List[Any]:
        """The AOT executables compiled at this site so far (their
        ``as_text()`` is the partitioned HLO, collectives included)."""
        with self._lock:
            return [c for _, c in self._programs.values() if c is not None]

    def __call__(self, *args, **kwargs):
        if not self.tracker.enabled:
            return self._jitted(*args, **kwargs)
        dynamic = kwargs if not self._static_names else {
            k: v for k, v in kwargs.items() if k not in self._static_names}
        avals = _avals_key(args, kwargs)
        entry = self._by_avals.get(avals)
        if entry is None:
            sig = signature_of(args, kwargs, self.static_context,
                               self._donate)
            key = signature_key(sig)
            with self._lock:
                entry = self._programs.get(key)
                if entry is not None:
                    self._by_avals[avals] = entry
        if entry is not None:
            idx, compiled = entry
            self.tracker.note_call(self.site, idx)
            if compiled is None:  # this signature runs on the fallback path
                return self._jitted(*args, **kwargs)
            return compiled(*args, **dynamic)
        # cache miss: this program's first call, a span of the start-up
        # record from here to the call's return
        from .. import get_telemetry

        with get_telemetry().startup_span(
                "startup/first_call",
                {"site": self.site, "static": self.static_context}) as first:
            return self._first_call(first, args, kwargs, dynamic, avals,
                                    sig, key)

    def _first_call(self, first, args, kwargs, dynamic, avals, sig, key):
        """The AOT path, so lower and compile are timed apart."""
        compiled = None
        try:
            t0 = time.perf_counter()
            lowered = self._jitted.lower(*args, **kwargs)
            t1 = time.perf_counter()
            compiled = lowered.compile()
            t2 = time.perf_counter()
            lower_ms, compile_ms = (t1 - t0) * 1e3, (t2 - t1) * 1e3
            fallback = False
        except Exception as e:
            if not self._fell_back:
                self._fell_back = True
                logger.warning(
                    f"compile tracker: AOT lower/compile failed at "
                    f"{self.site} ({e!r}) — falling back to plain jit "
                    f"(combined timing)")
            t0 = time.perf_counter()
            out = self._jitted(*args, **kwargs)
            lower_ms, compile_ms = 0.0, (time.perf_counter() - t0) * 1e3
            fallback = True
        ev = self.tracker.record(self.site, sig, lower_ms, compile_ms,
                                 fallback=fallback)
        first.set(program=ev.program)
        if compiled is not None:
            # compile-time cost harvest (anatomy plane): the AOT handle
            # is in hand exactly once, here — cost_analysis() now costs
            # the steady state nothing
            self.tracker.harvest_cost(self.site, ev.program, compiled)
        with self._lock:
            self._programs[key] = self._by_avals[avals] = (ev.program,
                                                           compiled)
        self.tracker.note_call(self.site, ev.program)
        if fallback:
            return out
        try:
            return compiled(*args, **dynamic)
        except Exception as e:
            # an executable the AOT path built but cannot dispatch (layout
            # or weak-type mismatch): route THIS signature through the
            # plain jitted path from now on
            logger.warning(f"compile tracker: compiled dispatch failed at "
                           f"{self.site} ({e!r}) — using plain jit for "
                           f"this signature")
            with self._lock:
                self._programs[key] = self._by_avals[avals] = (ev.program,
                                                               None)
            return self._jitted(*args, **kwargs)


def tracked_jit(fn: Callable, site: str,
                tracker: Optional[CompileTracker] = None,
                static_context: Optional[Dict[str, Any]] = None,
                **jit_kwargs: Any):
    """``jax.jit`` that records compile/recompile events at ``site``.

    The program is named from ``site`` and ``static_context``
    (:func:`program_name`) whatever the tracker is, so a traced and an
    untraced run execute the same programs under the same names.  With
    ``tracker=None`` (tracking off) this IS ``jax.jit`` of that function:
    zero overhead, zero behavior change."""
    import jax

    fn = _named(fn, program_name(site, static_context))
    if tracker is None:
        return jax.jit(fn, **jit_kwargs)
    return TrackedJit(fn, site, tracker, static_context=static_context,
                      **jit_kwargs)


_default = CompileTracker()
#: registered when this module is first imported, which every engine,
#: model and serving module of the package does: the account counts from
#: there
_account = CompileAccount().register()


def get_compile_tracker() -> CompileTracker:
    return _default


def configure_compile_tracker(enabled: bool = True,
                              max_events: Optional[int] = None,
                              recorder: Any = None) -> CompileTracker:
    """Resolve config into the global tracker; when a flight recorder is
    given, register the per-site program table as a bundle context
    provider so every debug bundle answers "what compiled, when, why"."""
    trk = _default.configure(enabled=enabled, max_events=max_events)
    if recorder is not None and enabled:
        recorder.register_context("compile_programs", trk.table)
    return trk
