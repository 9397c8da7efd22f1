"""Flight recorder — bounded black-box buffers + crash/debug bundles.

PR 1 gave the runtime passive telemetry (spans, metrics, StepRecords);
all of it evaporates with the process when a run dies.  This module is
the black box: it keeps bounded rings of the most recent StepRecords,
HealthEvents, and free-form annotations, and on demand — or on fatal
signal, unhandled exception, or watchdog trip — writes a self-contained
**debug bundle** an operator can read post-mortem:

* ``bundle.json``  — manifest: reason, recent StepRecords/HealthEvents/
  annotations, comms-logger summaries, a Prometheus snapshot of the
  metrics registry, and every registered context provider (e.g. the
  elastic agent's per-peer heartbeat ages, so a hang dump distinguishes
  "my host stalled" from "a peer died").
* ``trace.json``   — the span tracer's Chrome-trace slice (last-N host
  spans), loadable in Perfetto next to the XLA device lanes.
* ``env_report.json`` — the ``ds_report`` environment snapshot
  (versions, devices, native-op toolchain probes).
* ``stacks.txt``   — a faulthandler dump of EVERY thread's Python stack
  at dump time — for a hang, this is usually the answer.

The recorder is a process-global singleton (like the telemetry hub) so
the engine, the watchdog and the elastic agent all feed one black
box.  Recording is cheap (deque appends under a
lock); all the expensive work happens at dump time.
"""

from __future__ import annotations

import collections
import faulthandler
import json
import os
import signal
import socket
import sys
import threading
import time
import traceback
from typing import Any, Callable, Dict, Optional

from ..utils.logging import logger

BUNDLE_MANIFEST = "bundle.json"
BUNDLE_TRACE = "trace.json"
BUNDLE_ENV = "env_report.json"
BUNDLE_STACKS = "stacks.txt"
#: OOM forensics side file (telemetry/memory/oom.py) — present when the
#: bundle was dumped for a recognized device OOM
BUNDLE_MEMORY = "memory.json"


def _jsonable(obj: Any) -> Any:
    """Best-effort conversion for manifest payloads (numpy scalars, etc.)."""
    try:
        json.dumps(obj)
        return obj
    except (TypeError, ValueError):
        return str(obj)


class FlightRecorder:
    """Bounded in-memory black box with on-demand bundle dumps."""

    def __init__(self, max_records: int = 256,
                 output_path: str = "debug_bundles", retain: int = 5):
        self.max_records = int(max_records)
        self.output_path = output_path
        #: keep only the newest N bundle dirs under ``output_path`` —
        #: a watchdog stuck in trip/re-arm cycles must not fill the disk.
        #: <= 0 disables pruning.
        self.retain = int(retain)
        self._steps: "collections.deque" = collections.deque(
            maxlen=self.max_records)
        self._health: "collections.deque" = collections.deque(
            maxlen=self.max_records)
        self._annotations: "collections.deque" = collections.deque(
            maxlen=self.max_records)
        #: name -> zero-arg callable returning JSON-able context, invoked
        #: at DUMP time (providers see the state at failure, not at
        #: registration); failures are captured per provider, never fatal
        self._context_providers: Dict[str, Callable[[], Any]] = {}
        # REENTRANT: the fatal-signal handler runs dump() on the main
        # thread, possibly interrupting a record_* call that already
        # holds this lock — a plain Lock would deadlock the teardown
        # path the recorder exists to serve
        self._lock = threading.RLock()
        self._seq = 0
        self._installed = False
        self._prev_excepthook = None
        self._prev_signal_handlers: Dict[int, Any] = {}
        self.last_bundle_path: Optional[str] = None

    def configure(self, max_records: Optional[int] = None,
                  output_path: Optional[str] = None,
                  retain: Optional[int] = None) -> "FlightRecorder":
        with self._lock:
            if output_path:
                self.output_path = output_path
            if retain is not None:
                self.retain = int(retain)
            if max_records and int(max_records) != self.max_records:
                self.max_records = int(max_records)
                for name in ("_steps", "_health", "_annotations"):
                    setattr(self, name, collections.deque(
                        getattr(self, name), maxlen=self.max_records))
        return self

    def reset(self) -> None:
        """Test isolation: drop ring contents, context providers, and the
        last-bundle pointer (configuration and installed hooks stay)."""
        with self._lock:
            self._steps.clear()
            self._health.clear()
            self._annotations.clear()
            self._context_providers = {}
            self.last_bundle_path = None

    # -- recording (hot-ish path: deque append under a lock) ---------------

    def record_step(self, rec: Any) -> None:
        """Append a StepRecord (anything with ``to_dict()`` or a dict)."""
        d = rec.to_dict() if hasattr(rec, "to_dict") else dict(rec)
        with self._lock:
            self._steps.append(d)

    def record_health(self, event: Any) -> None:
        d = event.to_dict() if hasattr(event, "to_dict") else dict(event)
        with self._lock:
            self._health.append(d)

    def annotate(self, kind: str, payload: Dict[str, Any]) -> None:
        """Free-form breadcrumb (rendezvous joins, watchdog resets, ...)."""
        with self._lock:
            self._annotations.append(
                {"ts": time.time(), "kind": kind,
                 **{k: _jsonable(v) for k, v in payload.items()}})

    def register_context(self, name: str, fn: Callable[[], Any]) -> None:
        """Attach a named provider whose return value is embedded in every
        future bundle under ``context[name]`` (evaluated at dump time)."""
        with self._lock:
            self._context_providers[name] = fn

    def unregister_context(self, name: str) -> None:
        """Remove a provider added with :meth:`register_context` (no-op
        if absent).  Providers are strong references — a provider bound
        to an object with a shorter lifetime than the recorder (e.g. a
        bench-scoped serving front-end) must unregister to be
        collectable."""
        with self._lock:
            self._context_providers.pop(name, None)

    # -- dump --------------------------------------------------------------

    def _comm_snapshot(self) -> Dict[str, Any]:
        try:
            from ..comm.comm import comms_logger

            out: Dict[str, Any] = {
                "summary": {k: dict(v)
                            for k, v in comms_logger.summary().items()},
                "total_bytes": comms_logger.total_bytes(),
                "total_ops": comms_logger.total_ops(),
            }
            if comms_logger.exec_counts:
                out["exec_summary"] = {
                    k: dict(v)
                    for k, v in comms_logger.exec_summary().items()}
            return out
        except Exception as e:
            return {"error": repr(e)}

    def dump(self, reason: str, extra: Optional[Dict[str, Any]] = None
             ) -> str:
        """Write a bundle directory and return its path.  Never raises on
        a partially-failing section — a crash handler calling this must
        get whatever CAN be written."""
        with self._lock:
            self._seq += 1
            seq = self._seq
            steps = list(self._steps)
            health = list(self._health)
            annotations = list(self._annotations)
            providers = dict(self._context_providers)
        stamp = time.strftime("%Y%m%d-%H%M%S", time.gmtime())
        bundle_dir = os.path.join(self.output_path,
                                  f"bundle-{stamp}-{seq:03d}")
        os.makedirs(bundle_dir, exist_ok=True)

        context: Dict[str, Any] = {}
        for name, fn in providers.items():
            try:
                context[name] = _jsonable(fn())
            except Exception as e:  # a dead provider must not kill the dump
                context[name] = {"error": repr(e)}

        from . import get_telemetry

        hub = get_telemetry()
        manifest: Dict[str, Any] = {
            "reason": reason,
            "ts": time.time(),
            "time_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "pid": os.getpid(),
            "host": socket.gethostname(),
            "argv": list(sys.argv),
            "steps": steps,
            "health_events": health,
            "annotations": annotations,
            "comm": self._comm_snapshot(),
            "context": context,
            "extra": {k: _jsonable(v) for k, v in (extra or {}).items()},
            "files": [BUNDLE_TRACE, BUNDLE_ENV, BUNDLE_STACKS],
        }
        try:
            # store-clock mapping (telemetry/clocksync.py): the manifest
            # twin of the trace metadata, so archive tooling can reason
            # about alignment without parsing trace.json
            manifest["clock_sync"] = hub.tracer.clock_sync()
        except Exception as e:
            manifest["clock_sync"] = {"error": repr(e)}
        try:
            manifest["metrics_prom"] = hub.registry.prometheus_text()
        except Exception as e:
            manifest["metrics_prom"] = f"unavailable: {e!r}"
        try:
            with open(os.path.join(bundle_dir, BUNDLE_MANIFEST), "w") as fh:
                json.dump(manifest, fh, indent=2, default=str)
        except Exception as e:
            logger.error(f"flight recorder: manifest write failed: {e!r}")

        try:
            hub.tracer.save_chrome_trace(
                os.path.join(bundle_dir, BUNDLE_TRACE))
        except Exception as e:
            logger.warning(f"flight recorder: trace export failed: {e!r}")
        try:
            from ..env_report import collect as collect_env

            with open(os.path.join(bundle_dir, BUNDLE_ENV), "w") as fh:
                json.dump(collect_env(), fh, indent=2, default=str)
        except Exception as e:
            logger.warning(f"flight recorder: env report failed: {e!r}")
        try:
            with open(os.path.join(bundle_dir, BUNDLE_STACKS), "w") as fh:
                # every thread's Python stack — for a hang this is
                # usually the answer (which thread sits in which wait)
                faulthandler.dump_traceback(file=fh, all_threads=True)
        except Exception as e:
            logger.warning(f"flight recorder: stack dump failed: {e!r}")

        self.last_bundle_path = bundle_dir
        self._prune_bundles()
        logger.error(f"flight recorder: debug bundle written to "
                     f"{bundle_dir} ({reason})")
        return bundle_dir

    def _prune_bundles(self) -> None:
        """Retention: drop the oldest bundle dirs beyond ``retain`` —
        best-effort, a failed prune must never fail the dump."""
        if self.retain <= 0:
            return
        try:
            dirs = [os.path.join(self.output_path, d)
                    for d in os.listdir(self.output_path)
                    if d.startswith("bundle-")
                    and os.path.isdir(os.path.join(self.output_path, d))]
            # mtime with the (stamp, seq) name as tiebreak — several dumps
            # inside one mtime granule still prune oldest-first
            dirs.sort(key=lambda p: (os.path.getmtime(p),
                                     os.path.basename(p)))
            import shutil

            for stale in dirs[:-self.retain]:
                if stale == self.last_bundle_path:
                    continue
                shutil.rmtree(stale, ignore_errors=True)
        except OSError:
            pass

    # -- crash hooks -------------------------------------------------------

    def install(self, signals: bool = True, excepthook: bool = True) -> None:
        """Install the fatal-signal (SIGTERM/SIGABRT) and unhandled-
        exception hooks.  Idempotent; previous handlers are chained, so a
        launcher's own SIGTERM cleanup still runs after the dump."""
        if self._installed:
            return
        self._installed = True
        if excepthook:
            self._prev_excepthook = sys.excepthook
            sys.excepthook = self._excepthook
        if signals and threading.current_thread() is threading.main_thread():
            for signum in (signal.SIGTERM, signal.SIGABRT):
                try:
                    self._prev_signal_handlers[signum] = signal.signal(
                        signum, self._signal_handler)
                except (ValueError, OSError):  # not main thread / blocked
                    pass

    def uninstall(self) -> None:
        """Test isolation: restore the hooks install() replaced."""
        if not self._installed:
            return
        self._installed = False
        if self._prev_excepthook is not None:
            sys.excepthook = self._prev_excepthook
            self._prev_excepthook = None
        for signum, prev in self._prev_signal_handlers.items():
            try:
                signal.signal(signum, prev)
            except (ValueError, OSError):
                pass
        self._prev_signal_handlers = {}

    def _excepthook(self, exc_type, exc, tb) -> None:
        # an exception that already carries a bundle (the engine's OOM
        # catch dumped one before re-raising HBMExhaustedError) must not
        # produce a near-identical duplicate here
        if getattr(exc, "ds_bundle_path", None):
            prev = self._prev_excepthook or sys.__excepthook__
            prev(exc_type, exc, tb)
            return
        try:
            self.dump(f"unhandled exception: {exc_type.__name__}: {exc}",
                      extra={"traceback": "".join(
                          traceback.format_exception(exc_type, exc, tb))})
        except Exception as e:  # the original exception must still print
            from ..utils.logging import debug_once

            debug_once("flight_recorder/excepthook_dump",
                       f"crash-bundle dump failed in excepthook ({e!r})")
        try:
            # OOM forensics (telemetry/memory): a RESOURCE_EXHAUSTED that
            # escaped the engine's own catch (placement, first compile,
            # user code) still gets memory.json next to the manifest
            from .memory.oom import augment_bundle_on_oom

            augment_bundle_on_oom(exc, self.last_bundle_path)
        except Exception as e:
            from ..utils.logging import debug_once

            debug_once("flight_recorder/oom_augment",
                       f"oom bundle augmentation failed ({e!r})")
        prev = self._prev_excepthook or sys.__excepthook__
        prev(exc_type, exc, tb)

    def _signal_handler(self, signum, frame) -> None:
        try:
            self.dump(f"fatal signal {signal.Signals(signum).name}")
        except Exception as e:  # the signal's default action must proceed
            from ..utils.logging import debug_once

            debug_once("flight_recorder/signal_dump",
                       f"signal-bundle dump failed ({e!r})")
        prev = self._prev_signal_handlers.get(signum)
        if callable(prev):
            prev(signum, frame)
        elif prev == signal.SIG_IGN:
            return  # the caller explicitly ignored this signal — honor it
        else:
            # restore the default disposition and re-raise so the process
            # still dies with the signal's semantics (exit code, core)
            signal.signal(signum, signal.SIG_DFL)
            os.kill(os.getpid(), signum)


def load_bundle(path: str) -> Dict[str, Any]:
    """Reload a dumped bundle: the manifest plus the side files (the
    round-trip the tests assert).  Missing side files load as ``None``."""
    with open(os.path.join(path, BUNDLE_MANIFEST)) as fh:
        out: Dict[str, Any] = {"manifest": json.load(fh)}
    for key, name, is_json in (("trace", BUNDLE_TRACE, True),
                               ("env_report", BUNDLE_ENV, True),
                               ("memory", BUNDLE_MEMORY, True),
                               ("stacks", BUNDLE_STACKS, False)):
        p = os.path.join(path, name)
        if not os.path.exists(p):
            out[key] = None
            continue
        with open(p) as fh:
            out[key] = json.load(fh) if is_json else fh.read()
    return out


_default = FlightRecorder()


def get_flight_recorder() -> FlightRecorder:
    return _default


def configure_flight_recorder(max_records: Optional[int] = None,
                              output_path: Optional[str] = None,
                              retain: Optional[int] = None
                              ) -> FlightRecorder:
    return _default.configure(max_records=max_records,
                              output_path=output_path, retain=retain)


def recorder_from_config(tcfg: Any) -> Optional[FlightRecorder]:
    """Resolve the ``telemetry`` config group into the configured global
    recorder, or ``None`` when disabled — the ONE place the enable gate
    and default-bundle-path derivation live (entry.initialize and the
    engine both call this; duplicating it would drift)."""
    fr = tcfg.flight_recorder
    if not (fr.enabled and (tcfg.enabled or tcfg.watchdog.enabled)):
        return None
    rec = configure_flight_recorder(
        max_records=fr.max_records,
        output_path=fr.output_path or os.path.join(
            tcfg.output_path or "telemetry_logs", tcfg.job_name,
            "debug_bundles"),
        retain=fr.retain_bundles)
    # every bundle carries a memory snapshot (ISSUE 7 satellite): the
    # same numbers see_memory_usage prints, honoring the ledger and the
    # device-unresponsive latch — no separate enable gate needed
    from ..utils.memory import memory_status

    rec.register_context("memory_status", memory_status)
    return rec
