"""Hang / straggler watchdog over train-step progress notifications.

MegaScale (arXiv:2402.15627) attributes most of its >90% effective
training time to automated hang diagnosis; the failure mode it targets —
a collective that never completes, a host that silently stalls — leaves
NO error anywhere, just a process that stops making progress.  This
watchdog is that detector for this runtime:

* the engine calls :meth:`HangWatchdog.notify_progress` after every
  completed ``train_step`` (step index + step time, folded into an EWMA);
* a daemon thread (or an explicit :meth:`check` call — the tests drive a
  **fake clock** through it, no sleeps) compares the injectable clock
  against the last progress stamp;
* ``comms_logger`` activity is a secondary liveness signal: a long
  compile or a giant eager collective moves comm counters without
  finishing a step, and must not be declared a hang;
* on trip it dumps a flight-recorder debug bundle (last spans,
  StepRecords, per-thread stacks, peer heartbeat ages) and runs the
  configured action: ``log`` (keep running), ``raise``
  (:class:`WatchdogTimeout` — from the daemon thread this interrupts the
  main thread), or ``exit`` (``os._exit(2)`` for supervisors that
  restart on death, e.g. the elastic agent).

The per-host :meth:`heartbeat_payload` (step index, step-time EWMA,
progress age) is what the elastic agent folds into its rendezvous
heartbeat so rank 0 can publish straggler-skew gauges across hosts.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Callable, Dict, Optional

from ..utils.logging import debug_once, logger

ACTIONS = ("log", "raise", "exit")

#: heartbeat-payload schema version (satellite, ISSUE 13).  The payload
#: accreted step/EWMA/goodput/coll_seq/hbm fields across PRs 2-7 with no
#: version and no size bound; consumers (rank 0's straggler publisher,
#: the rollup, `telemetry top`) now key behavior on ``v`` instead of
#: sniffing fields, and producers cap the byte size below.
HEARTBEAT_SCHEMA_V = 1

#: default byte cap for heartbeat payloads — the watchdog ctor default
#: AND the cap producers without a watchdog config (the agent's
#: ledger-only path) apply, so the bound is defined exactly once
DEFAULT_HEARTBEAT_MAX_BYTES = 1024

#: deterministic field-drop order under the byte cap: least
#: operator-critical first.  ``v`` and ``step`` are never dropped (the
#: version is what makes the drop legible downstream; the step index is
#: the minimum liveness signal every consumer needs).  Fields NOT in
#: this order (a future producer's additions) drop before everything
#: listed, in sorted-name order — deterministic by construction.
HEARTBEAT_DROP_ORDER = (
    "goodput_total",    # the rolling figure is the live one
    "hbm_headroom",
    "hbm_frac",
    "goodput",
    "progress_age_s",   # derivable from the store-stamped hb age
    "coll_hash",        # desync detection degrades to seq-skew only
    "coll_seq",
    "step_time_ewma_ms",
)


def cap_heartbeat_payload(payload: Dict[str, Any],
                          max_bytes: int) -> Dict[str, Any]:
    """Bound a heartbeat payload's JSON size by dropping fields in
    :data:`HEARTBEAT_DROP_ORDER` (unknown fields first).  Dropped
    fields are counted (``elastic/heartbeat_fields_dropped_total``) and
    the payload records how many went missing (``dropped``) so the
    consumer can tell 'field absent' from 'field capped'."""
    import json as _json

    if max_bytes <= 0:
        return payload
    payload = dict(payload)
    payload.setdefault("v", HEARTBEAT_SCHEMA_V)

    def size() -> int:
        return len(_json.dumps(payload, default=str))

    if size() <= max_bytes:
        return payload
    protected = ("v", "step", "dropped")
    known = [f for f in HEARTBEAT_DROP_ORDER if f in payload]
    unknown = sorted(f for f in payload
                     if f not in HEARTBEAT_DROP_ORDER
                     and f not in protected)
    dropped = 0
    for field in unknown + known:
        if size() <= max_bytes:
            break
        payload.pop(field, None)
        dropped += 1
        payload["dropped"] = dropped
    if dropped:
        try:
            from . import get_telemetry

            get_telemetry().inc_counter(
                "elastic/heartbeat_fields_dropped_total", v=dropped,
                help="heartbeat payload fields dropped by the byte cap")
        except Exception as e:  # counter publish is best-effort
            debug_once("watchdog/hb_cap_counter",
                       f"heartbeat-cap counter publish failed ({e!r})")
        debug_once("watchdog/hb_cap",
                   f"heartbeat payload over {max_bytes}B — dropped "
                   f"{dropped} field(s) (deterministic order; see "
                   f"HEARTBEAT_DROP_ORDER)")
    return payload


class WatchdogTimeout(RuntimeError):
    """No train-step progress within ``hang_timeout_s``."""


class HangWatchdog:
    #: default ``recorder``: resolve the process-global flight recorder
    #: at trip time.  Pass an explicit ``None`` to trip WITHOUT dumping
    #: (the engine does when ``telemetry.flight_recorder`` is disabled).
    GLOBAL_RECORDER = object()

    def __init__(self, hang_timeout_s: float = 300.0,
                 poll_interval_s: float = 0.0,
                 action: str = "log",
                 comm_liveness: bool = True,
                 clock: Callable[[], float] = time.monotonic,
                 recorder: Any = GLOBAL_RECORDER,
                 device_probe: bool = True,
                 device_probe_timeout_s: float = 20.0,
                 heartbeat_max_bytes: int = DEFAULT_HEARTBEAT_MAX_BYTES):
        if action not in ACTIONS:
            raise ValueError(f"watchdog action {action!r} not in {ACTIONS}")
        self.hang_timeout_s = float(hang_timeout_s)
        #: 0 → a quarter of the timeout, capped at 10s (fast enough to
        #: catch a hang within ~1.25x the configured budget)
        self.poll_interval_s = (float(poll_interval_s) if poll_interval_s
                                else min(self.hang_timeout_s / 4.0, 10.0))
        self.action = action
        self.comm_liveness = bool(comm_liveness)
        #: bounded device-liveness check on the trip path (ISSUE 7): a
        #: runtime that has stopped answering hangs jax.devices()
        #: indefinitely, and the bundle dump's memory providers would walk
        #: straight into that hang — probe first, latch the verdict,
        #: annotate the bundle with ``device_unresponsive`` (ROADMAP D8
        #: decides whether the probe outlives the setup it was built for)
        self.device_probe = bool(device_probe)
        self.device_probe_timeout_s = float(device_probe_timeout_s)
        #: byte cap on heartbeat_payload (<= 0 disables): the payload
        #: rides every rendezvous heartbeat — an unbounded dict would
        #: let one noisy producer bloat every store beat in the gang
        self.heartbeat_max_bytes = int(heartbeat_max_bytes)
        #: test seam: injectable probe body (a hanging fake backend)
        self.device_probe_fn: Optional[Callable[[], Any]] = None
        self._clock = clock
        self._recorder = recorder
        self._lock = threading.Lock()
        self._last_progress = self._clock()
        self._last_step = -1
        self._ewma_ms = 0.0
        self._last_comm_ops = self._comm_ops()
        self._tripped = False
        self.trips = 0
        #: fns called on every trip edge with (reason, bundle_path_or_None)
        #: — the resilience policy's emergency-save subscribes here; ran
        #: BEFORE the configured action (an action="exit" must not skip
        #: the emergency flush), each guarded so one listener's failure
        #: cannot mask another's
        self._trip_listeners: list = []
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def add_trip_listener(self, fn: Callable[[str, Optional[str]], Any]
                          ) -> None:
        self._trip_listeners.append(fn)

    def remove_trip_listener(self, fn: Callable[[str, Optional[str]], Any]
                             ) -> None:
        """Detach a listener added with :meth:`add_trip_listener` (no-op
        if absent) — listeners are strong references, so a subscriber
        with a bounded lifetime must detach to be collectable."""
        try:
            self._trip_listeners.remove(fn)
        except ValueError:
            pass  # already removed / never added: detach is idempotent

    # -- progress feed (engine hot path: one lock + a few floats) ----------

    def notify_progress(self, step: int,
                        step_time_s: Optional[float] = None) -> None:
        with self._lock:
            self._last_progress = self._clock()
            self._last_step = int(step)
            if step_time_s is not None:
                ms = float(step_time_s) * 1e3
                self._ewma_ms = (ms if self._ewma_ms == 0.0
                                 else 0.9 * self._ewma_ms + 0.1 * ms)
            self._tripped = False  # re-arm: progress resumed

    def heartbeat_payload(self) -> Dict[str, float]:
        """Per-host liveness summary for the rendezvous heartbeat: rank 0
        folds every peer's payload into straggler-skew gauges.  When the
        collective ledger is on, its ``coll_seq``/``coll_hash`` ride
        along so rank 0 can detect collective desync live."""
        with self._lock:
            payload = {"v": HEARTBEAT_SCHEMA_V,
                       "step": self._last_step,
                       "step_time_ewma_ms": round(self._ewma_ms, 3),
                       "progress_age_s": round(
                           self._clock() - self._last_progress, 3)}
        from .collective_ledger import get_collective_ledger

        led = get_collective_ledger()
        if led.enabled:
            payload.update(led.heartbeat_summary())
        from .perf.goodput import get_goodput_ledger

        gp = get_goodput_ledger()
        if gp.enabled:
            # rolling goodput rides the heartbeat: rank 0 folds every
            # host's fraction into cluster gauges
            # (rendezvous.publish_straggler_stats)
            payload.update(gp.heartbeat_summary())
        from .memory import get_memory_ledger

        mem = get_memory_ledger()
        if mem.enabled:
            # HBM high-water + headroom ride along: rank 0 publishes
            # elastic/cluster_hbm_{max,headroom_min} and the cluster
            # manifest shows per-host memory
            payload.update(mem.heartbeat_summary())
        return cap_heartbeat_payload(payload, self.heartbeat_max_bytes)

    # -- the check ---------------------------------------------------------

    def _comm_ops(self) -> int:
        try:
            from ..comm.comm import comms_logger

            ops = comms_logger.total_ops()
            for e in comms_logger.exec_stats.values():
                ops += int(e.get("count", 0))
            return ops
        except Exception:
            return 0

    def check(self) -> bool:
        """One watchdog tick against the injected clock.  Returns True if
        this call tripped; the configured action runs on the trip edge
        only (re-armed by the next :meth:`notify_progress`)."""
        now = self._clock()
        if self.comm_liveness:
            ops = self._comm_ops()
            with self._lock:
                if ops != self._last_comm_ops:
                    # collectives are still flowing — a long compile or a
                    # giant eager gather is slow, not hung
                    self._last_comm_ops = ops
                    self._last_progress = now
        with self._lock:
            age = now - self._last_progress
            if age <= self.hang_timeout_s or self._tripped:
                return False
            self._tripped = True
            step, ewma = self._last_step, self._ewma_ms
        self._trip(age, step, ewma)
        return True

    def _trip(self, age: float, step: int, ewma_ms: float) -> None:
        reason = (f"watchdog: no train_step progress for {age:.1f}s "
                  f"(hang_timeout_s={self.hang_timeout_s}, last step "
                  f"{step}, step-time EWMA {ewma_ms:.1f}ms)")
        try:
            from .perf.goodput import get_goodput_ledger

            # the no-progress interval is detected stall time: charge it
            # so cluster goodput reflects the hang even if the process
            # survives (action="log")
            get_goodput_ledger().add("stall", age)
        except Exception as e:  # accounting is optional mid-incident
            debug_once("watchdog/stall_charge",
                       f"stall goodput charge failed ({e!r})")
        probe = None
        if self.device_probe:
            try:
                from .memory.ledger import probe_device_liveness

                probe = probe_device_liveness(
                    self.device_probe_timeout_s,
                    probe_fn=self.device_probe_fn)
                if probe.get("timed_out"):
                    # fail-fast verdict INSTEAD of an unbounded hang: the
                    # latch probe_device_liveness set makes every memory
                    # provider in the dump below skip the device.  Only
                    # a TIMEOUT is "unresponsive" — a probe the runtime
                    # ANSWERED with an error is responsive-but-unhealthy
                    # and must not send the operator down the dead-
                    # device path (the probe result still rides extra)
                    reason += (f" [device unresponsive: "
                               f"{probe.get('detail')}]")
            except Exception as e:  # the dump itself matters more
                debug_once("watchdog/device_probe",
                           f"device-liveness probe failed ({e!r})")
        bundle = None
        recorder = self._recorder
        if recorder is HangWatchdog.GLOBAL_RECORDER:
            from .flight_recorder import get_flight_recorder

            recorder = get_flight_recorder()
        if recorder is not None:  # None = flight recorder disabled
            extra = {"last_step": step, "step_time_ewma_ms": ewma_ms,
                     "progress_age_s": age}
            if probe is not None:
                extra["device_probe"] = probe
                if probe.get("timed_out"):
                    extra["device_unresponsive"] = True
            try:
                from .collective_ledger import get_collective_ledger

                led = get_collective_ledger()
                if led.enabled:
                    # the hang headline names the last collective this
                    # rank issued — the first thing a desync post-mortem
                    # compares across hosts
                    extra.update(led.heartbeat_summary())
            except Exception as e:  # the dump itself matters more
                debug_once("watchdog/ledger_summary",
                           f"ledger summary for trip bundle failed "
                           f"({e!r})")
            try:
                bundle = recorder.dump(reason, extra=extra)
            except Exception as e:
                logger.error(f"watchdog: bundle dump failed: {e!r}")
        for listener in list(self._trip_listeners):
            try:
                listener(reason, bundle)
            except Exception as e:
                logger.error(f"watchdog: trip listener failed: {e!r}")
        # bump AFTER the dump: a monitor polling `trips` may read the
        # bundle path the moment the counter moves
        self.trips += 1
        try:
            from . import get_telemetry

            get_telemetry().inc_counter(
                "watchdog/trips", help="hang watchdog trips")
        except Exception as e:  # counter publish is best-effort
            debug_once("watchdog/trip_counter",
                       f"trip counter publish failed ({e!r})")
        msg = f"{reason}; debug bundle: {bundle}"
        if self.action == "exit":
            logger.error(msg + " — exiting (watchdog action=exit)")
            os._exit(2)
        if self.action == "raise":
            raise WatchdogTimeout(msg)
        logger.error(msg)

    # -- daemon thread -----------------------------------------------------

    @property
    def started(self) -> bool:
        return self._thread is not None

    def start(self) -> None:
        """Idempotent: spawn the daemon poll thread (real clock mode)."""
        if self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="ds-hang-watchdog")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        self._thread = None
        if t is not None:
            t.join(timeout=2)

    def _loop(self) -> None:
        while not self._stop.wait(self.poll_interval_s):
            try:
                self.check()
            except WatchdogTimeout as e:
                # action="raise" from the daemon thread: the exception
                # cannot cross threads, so interrupt the main thread (a
                # KeyboardInterrupt at its next bytecode boundary) after
                # logging — a hung COLLECTIVE won't be interruptible, but
                # the bundle is already on disk either way
                logger.error(f"watchdog: {e}")
                import _thread

                _thread.interrupt_main()
                return
            except Exception as e:
                logger.warning(f"watchdog check failed: {e!r}")


_watchdog: Optional[HangWatchdog] = None


def get_watchdog() -> Optional[HangWatchdog]:
    """The process-global watchdog, if one was installed (the elastic
    agent reads it to fold progress into rendezvous heartbeats)."""
    return _watchdog


def set_watchdog(wd: Optional[HangWatchdog]) -> None:
    global _watchdog
    _watchdog = wd
