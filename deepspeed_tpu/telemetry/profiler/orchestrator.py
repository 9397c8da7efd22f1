"""Store-coordinated fleet profiler capture (ISSUE 20 tentpole).

One command — ``telemetry profile --steps N`` or ``POST /debug/profile``
on the serving front door — bumps a counter in the rendezvous store;
every gang worker's publisher beat (and every serving worker's heartbeat
loop) notices, agrees on a *shared step-index window* through a
max-merge in the store, arms ``jax.profiler`` for exactly that window,
and publishes a compact device-lane document back.  Rank 0 (or the CLI)
merges the lanes into the clock-aligned ``cluster_trace.json`` timeline
next to the host spans and joins measured per-op durations against the
anatomy roofline (:mod:`.calibration`).

Store protocol (all under ``profiler/``):

=====================================  ==================================
``profiler/cmd``                       capture-request counter (operator
                                       bumps via :func:`post_capture_
                                       command`)
``profiler/cmd/<req>/spec``            the capture spec (steps, lead,
                                       mode, posted_at store-clock)
``profiler/cmd/<req>/start``           max-merged start step: every
                                       worker proposes ``local_step +
                                       lead``; the max wins, so the
                                       window opens after EVERY rank has
                                       seen the command (data-parallel
                                       ranks advance in lockstep)
``profiler/cmd/<req>/acks``            workers that proposed (progress /
                                       debugging surface)
``profiler/pub/<node>``                one worker's capture result:
                                       census + compact device events +
                                       store-clock anchor + calibration
=====================================  ==================================

Step windows arm from :meth:`ProfilerPlane.on_step` — a two-attribute
check when idle, called outside the jitted step, so a disabled (or
merely unarmed) plane changes neither the step's jaxpr nor its compile
cache.  Capture wall time is booked to the goodput ledger's
``profiler`` bucket.  Duty-cycle continuous mode self-arms a window of
``duty_cycle_pct`` percent of every ``duty_period_steps`` steps into the
same bounded ring of trace dirs — always-on capture with a bounded
overhead budget.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from ...utils.logging import debug_once, logger

CMD_KEY = "profiler/cmd"
PUB_PREFIX = "profiler/pub/"

#: a command older than this (store clock) is ignored — a worker joining
#: long after a capture must not replay it
STALE_CMD_S = 120.0

#: compact device events kept in a store publication (the full trace
#: stays in the worker's ring dir)
MAX_PUB_EVENTS = 1500

#: per-op census rows kept in a publication
PUB_CENSUS_TOP_K = 48


def _spec_key(req: int) -> str:
    return f"profiler/cmd/{int(req)}/spec"


def _start_key(req: int) -> str:
    return f"profiler/cmd/{int(req)}/start"


def _acks_key(req: int) -> str:
    return f"profiler/cmd/{int(req)}/acks"


def pub_key(node_id: str) -> str:
    return PUB_PREFIX + str(node_id)


def post_capture_command(client: Any, steps: int = 4, lead: int = 3,
                         mode: str = "window",
                         duration_ms: float = 250.0) -> int:
    """Operator side: post ONE capture command; returns the request id
    the publications will carry.

    ``mode="window"`` captures ``steps`` train steps starting at the
    max-merged start index; ``mode="duration"`` captures ``duration_ms``
    of wall time immediately (the serving fleet has no shared step
    counter — a decode burst is windowed by time, not index)."""
    if mode not in ("window", "duration"):
        raise ValueError(f"unknown capture mode {mode!r} "
                         "(window | duration)")
    req = int(client.add(CMD_KEY, 1))
    client.set(_spec_key(req), {
        "steps": max(int(steps), 1),
        "lead": max(int(lead), 1),
        "mode": mode,
        "duration_ms": float(duration_ms),
        "posted_at": float(client.now()),
    }, journal=True)
    return req


class ProfilerPlane:
    """Per-process capture service: polls the command channel from the
    publisher/heartbeat beat, arms ``jax.profiler`` for the agreed
    window from the engine's step hook, keeps a bounded ring of trace
    dirs, and publishes the measured census."""

    def __init__(self, node_id: str, out_dir: Optional[str] = None,
                 ring: int = 4, lead: int = 3,
                 duty_cycle_pct: float = 0.0,
                 duty_period_steps: int = 64,
                 site: Optional[str] = None,
                 goodput: Optional[Any] = None):
        self.node_id = str(node_id)
        self.out_dir = out_dir or os.path.join(
            tempfile.gettempdir(), f"ds_profiler_{self.node_id}")
        self.ring = max(int(ring), 1)
        self.lead = max(int(lead), 1)
        self.duty_cycle_pct = float(duty_cycle_pct)
        self.duty_period_steps = max(int(duty_period_steps), 2)
        #: anatomy site whose roofline entry the calibration joins
        #: against (the engine stamps its own; CLI captures pass theirs)
        self.site = site
        self._goodput = goodput
        self._lock = threading.Lock()
        self._step = 0
        self._last_req: Optional[int] = None
        #: the armed window: None when idle (the per-step fast path)
        self._armed: Optional[Dict[str, Any]] = None
        self._pending_pub: Optional[Dict[str, Any]] = None
        self._ring_dirs: List[str] = []
        self._captures = 0
        self.last_result: Optional[Dict[str, Any]] = None
        #: serving fold hook: called with the finished capture doc so a
        #: decode-burst's measured device time lands on the open request
        #: lifecycle records (serving/worker.py registers one)
        self._fold_hooks: List[Callable[[Dict[str, Any]], Any]] = []
        #: duty-cycle continuous mode: next self-armed window start
        self._duty_next_start: Optional[int] = None

    # -- wiring --------------------------------------------------------------

    def add_fold_hook(self, fn: Callable[[Dict[str, Any]], Any]) -> None:
        with self._lock:
            self._fold_hooks.append(fn)

    def register_bundle_context(self, recorder: Any = None) -> None:
        """``context.profiler`` in every flight-recorder bundle: the ring,
        the last capture summary, and whether a window is armed NOW."""
        if recorder is None:
            from ..flight_recorder import get_flight_recorder

            recorder = get_flight_recorder()
        if recorder is not None:
            recorder.register_context("profiler", self.context)

    def context(self) -> Dict[str, Any]:
        with self._lock:
            armed = dict(self._armed) if self._armed else None
            last = dict(self.last_result) if self.last_result else None
        if last:
            last.pop("events", None)  # bundles carry summaries, not lanes
            last.pop("census", None)
        return {"node": self.node_id, "step": self._step,
                "captures": self._captures, "armed": armed,
                "ring": list(self._ring_dirs),
                "duty_cycle_pct": self.duty_cycle_pct,
                "last_capture": last}

    # -- command channel (publisher/heartbeat beat) --------------------------

    def poll(self, client: Any) -> Optional[int]:
        """One command-channel beat.  Cheap when nothing changed: one
        ``get``.  Raises the client's ConnectionError family upward —
        the caller's degraded path (publisher tick) counts and retries.
        Returns the request id when a NEW command was adopted."""
        self._flush_pub(client)
        req = int(client.get(CMD_KEY) or 0)
        with self._lock:
            if self._last_req is None:
                # first beat: adopt the current counter as the baseline,
                # then look at the newest command below — a fresh command
                # posted moments before this process came up still runs,
                # anything stale is skipped by posted_at
                self._last_req = max(req - 1, 0)
            nothing_new = req <= self._last_req
        if nothing_new:
            self._refresh_start(client)
            return None
        spec = client.get(_spec_key(req))
        with self._lock:
            self._last_req = req
        if not isinstance(spec, dict):
            return None
        posted = float(spec.get("posted_at", 0.0))
        try:
            if posted and float(client.now()) - posted > STALE_CMD_S:
                debug_once("profiler/stale_cmd",
                           f"profiler: ignoring stale capture command "
                           f"#{req} (posted {posted:.0f})")
                return None
        except (OSError, ValueError):
            pass
        if spec.get("mode") == "duration":
            # time-windowed capture (serving fleet): run it right here on
            # the beat thread — the profiler traces the whole process, so
            # decode bursts on the serving threads land in the window
            self._capture_duration(client, req, spec)
            return req
        lead = int(spec.get("lead", self.lead))
        proposed = self._step + lead
        start = int(client.max(_start_key(req), proposed))
        client.add(_acks_key(req), 1)
        with self._lock:
            self._armed = {"req": req, "start": max(start, proposed),
                           "steps": max(int(spec.get("steps", 4)), 1),
                           "state": "pending", "source": "command"}
        logger.info(f"profiler[{self.node_id}]: armed capture #{req} for "
                    f"steps [{self._armed['start']}, "
                    f"{self._armed['start'] + self._armed['steps']})")
        return req

    def _refresh_start(self, client: Any) -> None:
        """While pending, other ranks may still be raising the max-merged
        start — track it so every rank opens at the same index."""
        with self._lock:
            a = self._armed
            if a is None or a["state"] != "pending" \
                    or a.get("source") != "command":
                return
            req = a["req"]
        start = client.get(_start_key(req))
        if isinstance(start, (int, float)):
            with self._lock:
                a = self._armed
                if a is not None and a["state"] == "pending" \
                        and a["req"] == req:
                    a["start"] = max(a["start"], int(start))

    def _flush_pub(self, client: Any) -> None:
        with self._lock:
            doc = self._pending_pub
        if doc is None:
            return
        client.set(pub_key(self.node_id), doc, journal=False)
        with self._lock:
            if self._pending_pub is doc:  # a newer capture may have won
                self._pending_pub = None

    # -- step hook (engine train loop) ---------------------------------------

    def on_step(self, step: int) -> None:
        """Called at the top of every train step, OUTSIDE the jitted
        program.  Idle cost: two attribute reads."""
        self._step = int(step)
        if self._armed is None:
            if self._duty_next_start is None:
                return
            self._maybe_duty_arm(step)
            if self._armed is None:
                return
        with self._lock:
            a = self._armed
            if a is None:
                return
            state, start = a["state"], a["start"]
        if state == "pending" and step >= start:
            self._begin_window(a)
        elif state == "active" and step >= a["start"] + a["steps"]:
            self._end_window(a)

    def enable_duty_cycle(self) -> None:
        """Arm the continuous mode: every ``duty_period_steps`` steps,
        capture ``duty_cycle_pct`` percent of them."""
        if self.duty_cycle_pct > 0.0:
            self._duty_next_start = self._step + self.duty_period_steps

    def _maybe_duty_arm(self, step: int) -> None:
        nxt = self._duty_next_start
        if nxt is None or step < nxt:
            return
        steps = max(int(round(self.duty_period_steps
                              * self.duty_cycle_pct / 100.0)), 1)
        with self._lock:
            if self._armed is None:
                self._armed = {"req": 0, "start": step, "steps": steps,
                               "state": "pending", "source": "duty"}
        self._duty_next_start = step + self.duty_period_steps

    # -- the window itself ---------------------------------------------------

    def _ring_slot(self, tag: str) -> str:
        os.makedirs(self.out_dir, exist_ok=True)
        path = os.path.join(self.out_dir, f"trace_{tag}")
        if os.path.isdir(path):  # re-captured tag: fresh slot
            shutil.rmtree(path, ignore_errors=True)
        with self._lock:
            self._ring_dirs.append(path)
            evict = (self._ring_dirs[:-self.ring]
                     if len(self._ring_dirs) > self.ring else [])
            self._ring_dirs = self._ring_dirs[-self.ring:]
        for old in evict:
            shutil.rmtree(old, ignore_errors=True)
        return path

    def _begin_window(self, a: Dict[str, Any]) -> None:
        from ...profiling.collective_trace import begin_shared_session

        tag = f"req{a['req']}_s{a['start']}" if a["req"] \
            else f"duty_s{a['start']}"
        tdir = self._ring_slot(tag)
        try:
            owned = begin_shared_session(tdir)
        except Exception as e:
            logger.warning(f"profiler[{self.node_id}]: trace start failed "
                           f"({e!r}); capture #{a['req']} dropped")
            with self._lock:
                self._armed = None
            return
        if owned is None:
            # someone else (an anatomy capture) holds the session — the
            # window re-arms one period later instead of fighting for it
            debug_once("profiler/session_busy",
                       f"profiler[{self.node_id}]: shared trace session "
                       f"busy; capture #{a['req']} skipped")
            with self._lock:
                self._armed = None
            return
        with self._lock:
            a["state"] = "active"
            a["trace_dir"] = owned
            a["t0_perf"] = time.perf_counter()
            a["t0_wall"] = time.time()

    def _end_window(self, a: Dict[str, Any]) -> None:
        from ...profiling.collective_trace import end_shared_session

        t_cap0 = time.perf_counter()
        try:
            end_shared_session()
        except Exception as e:
            logger.warning(f"profiler[{self.node_id}]: trace stop failed "
                           f"({e!r})")
            with self._lock:
                self._armed = None
            return
        window_s = t_cap0 - a["t0_perf"]
        doc = self._harvest(a, window_s)
        stop_s = time.perf_counter() - t_cap0
        # the window's steps already landed in productive/compile via
        # add_step; only the capture MACHINERY (trace stop + parse +
        # census) is profiler overhead — charging the steps themselves
        # would double-book them
        self._book_goodput(stop_s)
        with self._lock:
            self._armed = None
            self._captures += 1
            self.last_result = doc
            if a.get("source") == "command":
                self._pending_pub = doc
            hooks = list(self._fold_hooks)
        for fn in hooks:
            try:
                fn(doc)
            except Exception as e:
                debug_once("profiler/fold_hook",
                           f"profiler fold hook failed ({e!r})")
        logger.info(
            f"profiler[{self.node_id}]: capture "
            f"#{a['req']} done — {doc['census']['device_per_step_us']:.0f}"
            f"us device/step over {a['steps']} steps -> {a['trace_dir']}")

    def _book_goodput(self, seconds: float) -> None:
        led = self._goodput
        if led is None:
            from ..perf import get_goodput_ledger

            led = get_goodput_ledger()
        try:
            if led is not None:
                led.add("profiler", max(float(seconds), 0.0))
        except Exception as e:
            debug_once("profiler/goodput",
                       f"profiler goodput booking failed ({e!r})")

    def _harvest(self, a: Dict[str, Any], window_s: float
                 ) -> Dict[str, Any]:
        """Parse the trace, build the census + calibration, and shape
        the compact publication document."""
        from ...profiling.collective_trace import parse_trace_events
        from .calibration import (apply_report_to_store,
                                  build_calibration_report)
        from .census import op_census

        steps = int(a.get("steps", 1))
        events = parse_trace_events(a["trace_dir"], patterns=None)
        census = op_census(events, steps=steps, top_k=PUB_CENSUS_TOP_K)
        device_kind = self._device_kind()
        ledger_entry = self._ledger_entry()
        report = build_calibration_report(census, ledger_entry,
                                          device_kind=device_kind,
                                          node=self.node_id)
        try:
            report["factors"] = apply_report_to_store(report)
        except Exception as e:
            debug_once("profiler/calibration_store",
                       f"calibration persist failed ({e!r})")
            report["factors"] = {}
        compact = [
            {"ts_us": ev["ts_us"], "dur_us": ev["dur_us"],
             "name": ev["name"], "lane": ev["lane"]}
            for ev in sorted(events, key=lambda e: -e["dur_us"])
            [:MAX_PUB_EVENTS]]
        compact.sort(key=lambda e: e["ts_us"])
        clock = self._clock_anchor(a)
        return {
            "req": int(a["req"]),
            "node": self.node_id,
            "mode": a.get("mode", "window"),
            "start_step": int(a["start"]),
            "steps": steps,
            "window_s": round(window_s, 6),
            "trace_dir": a["trace_dir"],
            "device_kind": device_kind,
            "clock": clock,
            "census": census,
            "calibration": report,
            "events": compact,
            "events_truncated": max(len(events) - MAX_PUB_EVENTS, 0),
        }

    def _device_kind(self) -> str:
        try:
            import jax

            d = jax.devices()[0]
            return (getattr(d, "device_kind", "")
                    or getattr(d, "platform", "") or "unknown")
        except Exception:
            return "unknown"

    def _ledger_entry(self) -> Optional[Dict[str, Any]]:
        try:
            from ..anatomy.ledger import get_cost_ledger

            led = get_cost_ledger()
            if self.site:
                e = led.entry_for(self.site)
                if e:
                    return e
            top = led.top(1)
            return top[0] if top else None
        except Exception:
            return None

    def _clock_anchor(self, a: Dict[str, Any]) -> Dict[str, Any]:
        """The lane's place on the shared store clock: capture-start
        mapped through the clocksync offset (perf_counter -> store
        seconds), ``aligned`` false when no estimate is held."""
        from ..clocksync import get_clock_sync

        sync = get_clock_sync()
        off = sync.offset_s if sync.synced else None
        t0 = float(a.get("t0_perf", 0.0))
        return {
            "aligned": off is not None,
            "store_t0_s": (t0 + off) if off is not None else None,
            "wall_t0_s": float(a.get("t0_wall", 0.0)),
            "offset_s": off,
        }

    # -- duration mode (serving fleet) ---------------------------------------

    def _capture_duration(self, client: Any, req: int,
                          spec: Dict[str, Any]) -> None:
        from ...profiling.collective_trace import begin_shared_session

        ms = max(float(spec.get("duration_ms", 250.0)), 10.0)
        tdir = self._ring_slot(f"req{req}_t")
        try:
            owned = begin_shared_session(tdir)
        except Exception as e:
            logger.warning(f"profiler[{self.node_id}]: duration capture "
                           f"#{req} failed to start ({e!r})")
            return
        if owned is None:
            debug_once("profiler/session_busy",
                       f"profiler[{self.node_id}]: shared session busy; "
                       f"duration capture #{req} skipped")
            return
        a = {"req": req, "start": self._step, "steps": 1,
             "state": "active", "trace_dir": owned, "mode": "duration",
             "t0_perf": time.perf_counter(), "t0_wall": time.time()}
        time.sleep(ms / 1e3)  # the beat thread sleeps; serving threads run
        self._end_window(a)
        self._flush_pub(client)


_plane: Optional[ProfilerPlane] = None
_plane_lock = threading.Lock()


def get_profiler_plane() -> Optional[ProfilerPlane]:
    with _plane_lock:
        return _plane


def configure_profiler_plane(node_id: str, **kw: Any
                             ) -> ProfilerPlane:
    """Install the process-global plane (idempotent per node_id: a
    re-initialize with the same node reuses the instance so an armed
    window survives engine rebuilds)."""
    global _plane
    with _plane_lock:
        if _plane is None or _plane.node_id != str(node_id):
            _plane = ProfilerPlane(node_id, **kw)
        return _plane


def reset_profiler_plane() -> None:
    """Test isolation."""
    global _plane
    with _plane_lock:
        _plane = None
