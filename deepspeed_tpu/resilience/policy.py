"""Automatic recovery policy — the state machine that ACTS on failures.

ISSUE 4 tentpole, pillar 2.  PRs 1–3 can *name* a failure (watchdog
trip, NaN'd loss, desynced collective, dead peer); this module turns
the detection into a bounded amount of lost work:

* **NaN/Inf loss or fp16 loss-scale collapse** → roll back to the last
  good snapshot (tier 0 → tier 1 → tier 2 fallback, checksum-gated) and
  SKIP the offending data window — the batches consumed between the
  snapshot and the failure are not refed, because refeeding the batch
  that NaN'd the loss would NaN it again.
* **Hang (watchdog trip)** → emergency-save-if-responsive: flush the
  newest tier-0 host copy through a SYNC writer from the watchdog
  thread, so the supervisor's kill that usually follows a trip costs at
  most ``snapshot_interval`` steps.
* **Crash / worker exit** → the elastic agent restarts the worker
  (capped exponential backoff); on re-entry
  :meth:`RecoveryPolicy.resume_if_restarted` loads the newest VALID
  snapshot — falling back across tiers when the newest is torn or
  corrupt — and training continues from there.

Every recovery consumes a budget: capped exponential backoff between
recoveries, and after ``max_recoveries`` within the reset window the
policy raises :class:`ResilienceGiveUp` — at some point a human has to
look.  All transitions land in telemetry counters and flight-recorder
annotations, so the debug bundle of a recovered run TELLS the story.
"""

from __future__ import annotations

import math
import os
import time
from typing import Any, Callable, Dict, List, Optional

from ..utils.logging import log_dist, logger
from .snapshot import Snapshot, SnapshotManager, choose_resume_snapshot

#: policy states (exposed for tests/operators; the machine is linear)
ST_RUNNING = "running"
ST_RECOVERING = "recovering"
ST_GAVE_UP = "gave_up"


class ResilienceGiveUp(RuntimeError):
    """The recovery budget is exhausted (or no valid snapshot exists) —
    the run needs a human."""


class RecoveryPolicy:
    """Subscribed to the engine's step metrics/health events and the
    watchdog's trip edge; owns rollback, resume, backoff, and give-up."""

    def __init__(self, engine: Any, snapshots: SnapshotManager, cfg: Any,
                 recorder: Any = None,
                 clock: Callable[[], float] = time.monotonic,
                 sleep: Callable[[float], None] = time.sleep):
        self.engine = engine
        self.snapshots = snapshots
        self.cfg = cfg
        self.recorder = recorder
        self._clock = clock
        self._sleep = sleep
        self.rollback_on = set(cfg.rollback_on or [])
        self.max_recoveries = int(cfg.max_recoveries)
        self.backoff_base_s = float(cfg.backoff_base_s)
        self.backoff_max_s = float(cfg.backoff_max_s)
        self.recovery_reset_steps = int(cfg.recovery_reset_steps)
        self.state = ST_RUNNING
        self.recoveries = 0        # within the current reset window
        self.rollbacks_total = 0
        self.resumes_total = 0
        self._last_recovery_step = -1
        #: True between a rollback and the next HEALTHY step: a second
        #: failure in that window means the restored snapshot itself is
        #: suspect (e.g. params already NaN under a still-finite loss)
        #: and the next rollback must dig DEEPER instead of re-restoring
        #: the same poisoned capture until the budget burns out
        self._unproven_restore = False

    # -- budget ------------------------------------------------------------

    def _charge_recovery(self, kind: str) -> None:
        """One recovery against the budget: capped exponential backoff,
        then give up past ``max_recoveries``.  The budget re-arms after
        ``recovery_reset_steps`` healthy steps (a run that hits one NaN
        a week must not die on the 4th week)."""
        self.recoveries += 1
        if self.recoveries > self.max_recoveries:
            self.state = ST_GAVE_UP
            self._annotate("resilience_give_up",
                           {"trigger": kind, "recoveries": self.recoveries})
            self._counter("resilience/give_ups_total",
                          "recovery budget exhaustions")
            raise ResilienceGiveUp(
                f"resilience: giving up after {self.recoveries - 1} "
                f"recoveries within {self.recovery_reset_steps} steps "
                f"(last trigger: {kind}) — the failure is not transient")
        delay = min(self.backoff_base_s * (2 ** (self.recoveries - 1)),
                    self.backoff_max_s)
        log_dist(f"resilience: recovery #{self.recoveries} ({kind}); "
                 f"backing off {delay:.2f}s")
        self._sleep(delay)
        self._last_recovery_step = self.engine.global_steps

    def _maybe_rearm(self) -> None:
        if (self.recoveries
                and self.engine.global_steps - self._last_recovery_step
                >= self.recovery_reset_steps):
            self.recoveries = 0

    # -- step observation (engine hot path) --------------------------------

    def observe_step(self, metrics: Dict[str, Any],
                     health_events: Optional[List[Any]] = None) -> bool:
        """Called by ``train_step`` after every optimizer step.  Returns
        True when the step triggered a rollback (the engine then skips
        its post-step snapshot — the state was just REWOUND).

        The loss check pulls the scalar (a device sync): resilience
        deliberately trades dispatch/execute overlap for the ability to
        catch the NaN before it propagates another ``snapshot_interval``
        steps.
        """
        if self.state == ST_GAVE_UP:
            return False
        self._maybe_rearm()
        trigger = None
        if "nan_loss" in self.rollback_on:
            loss = float(metrics.get("loss", 0.0))
            if not math.isfinite(loss):
                detail = f"non-finite loss {loss}"
                # the numerics plane's forensic capture (run by the
                # engine before this observe) localized the poison —
                # the rollback NAMES the first bad layer
                report = getattr(self.engine, "_last_nonfinite_report",
                                 None)
                if report is not None and getattr(report, "first_layer",
                                                  ""):
                    detail += (f"; first non-finite tensor: "
                               f"'{report.report.get('first_nonfinite')}'"
                               f" (layer {report.first_layer})")
                trigger = ("nan_loss", detail)
        if trigger is None and health_events:
            for ev in health_events:
                kind = getattr(ev, "kind", None)
                if kind in self.rollback_on:
                    trigger = (kind, getattr(ev, "message", kind))
                    break
        if trigger is None:
            self._unproven_restore = False  # a healthy step vindicates it
            return False
        self.rollback(kind=trigger[0], detail=trigger[1])
        return True

    # -- rollback ----------------------------------------------------------

    def rollback(self, kind: str = "manual", detail: str = "") -> None:
        """Restore the last good snapshot and skip the offending data
        window.  Tier fallback: tier-0 buffers (newest first) → newest
        valid tier-1 dir → tier-2 buddy replica."""
        eng = self.engine
        failed_step = eng.global_steps
        t_rollback0 = self._clock()
        self.state = ST_RECOVERING
        if self._unproven_restore:
            # the snapshot restored by the PREVIOUS rollback failed
            # again without a single healthy step in between — burn it
            # and fall back to the next-older capture
            burned = self.snapshots.discard_newest()
            if burned is not None:
                logger.warning(
                    f"resilience: snapshot at step {burned.global_steps} "
                    f"failed immediately after restore — discarding it "
                    f"and falling back to an older one")
        # locate the snapshot BEFORE charging the budget: when nothing
        # is restorable there is no point sleeping a backoff first
        snap, applied = self._best_snapshot()
        if snap is None:
            self.state = ST_GAVE_UP
            raise ResilienceGiveUp(
                "resilience: rollback requested but no valid snapshot "
                "exists in any tier (memory/disk/buddy)")
        self._charge_recovery(kind)  # may raise ResilienceGiveUp
        if not applied:  # tier-1/2 loads land applied; don't re-put
            self.snapshots.restore(snap)
        self._unproven_restore = True
        skipped = failed_step - eng.global_steps
        if getattr(eng, "health", None) is not None:
            # the health windows saw the anomaly; replayed steps must be
            # judged against a fresh baseline
            eng.health.reset_windows()
        self.rollbacks_total += 1
        self._counter("resilience/rollbacks_total",
                      "automatic rollbacks to a snapshot")
        self._counter("resilience/steps_skipped_total",
                      "training steps lost to rollbacks (the skipped "
                      "data window)", v=max(skipped, 0))
        self._charge_goodput_recovery(failed_step, skipped, t_rollback0)
        ann = {
            "trigger": kind, "detail": detail, "failed_step": failed_step,
            "restored_step": eng.global_steps,
            "skipped_window": [eng.global_steps + 1, failed_step]}
        report = getattr(eng, "_last_nonfinite_report", None)
        if kind == "nan_loss" and report is not None:
            # forensic localization rides the annotation (and was already
            # dumped as numerics.json in the forensics bundle)
            ann["first_nonfinite"] = report.report.get("first_nonfinite", "")
            ann["first_layer"] = report.first_layer
            ann["numerics_bundle"] = report.bundle_path
            eng._last_nonfinite_report = None  # consumed by this rollback
        self._annotate("resilience_rollback", ann)
        logger.warning(
            f"resilience: rolled back {kind} at step {failed_step} -> "
            f"step {eng.global_steps}; data window "
            f"({eng.global_steps + 1}..{failed_step}) skipped")
        self.state = ST_RUNNING

    def _charge_goodput_recovery(self, failed_step: int, skipped: int,
                                 t_rollback0: float) -> None:
        """Account the rollback in the goodput ledger (telemetry/perf):
        the rollback/backoff wall time goes to the ``recovery`` bucket,
        and the skipped window's step time — charged ``productive`` as
        those steps ran — is RECLASSIFIED to ``recovery``: the rollback
        just proved that work was lost."""
        try:
            from ..telemetry.perf import get_goodput_ledger

            gp = get_goodput_ledger()
            if not gp.enabled:
                return
            gp.add("recovery", max(self._clock() - t_rollback0, 0.0))
            lost_prod_s = lost_compile_s = 0.0
            records = getattr(self.engine, "step_records", None) or []
            window = {failed_step - i for i in range(max(skipped, 0))}
            for rec in records:
                if rec.step not in window:
                    continue
                # split like add_step did: the compile share of a lost
                # step was charged "compile", not "productive" — each
                # bucket gives back exactly what it was credited
                step_s = float(rec.step_time_ms) / 1e3
                comp_s = min(float(rec.extra.get("compile_ms", 0.0) or 0.0)
                             / 1e3, step_s)
                lost_compile_s += comp_s
                lost_prod_s += step_s - comp_s
            if lost_prod_s > 0.0:
                gp.reclassify("productive", "recovery", lost_prod_s)
            if lost_compile_s > 0.0:
                gp.reclassify("compile", "recovery", lost_compile_s)
        except Exception as e:
            logger.debug(f"resilience: goodput accounting failed: {e!r}")

    def _best_snapshot(self) -> tuple:
        """Newest restorable snapshot across tiers, as ``(snap,
        applied)`` — ``applied`` is True when locating it ALREADY loaded
        it into the engine (the disk path restores in place; repeating
        the multi-GB device_put and the restore hooks would double
        recovery cost)."""
        for snap in self.snapshots.buffered():  # tier 0, newest first
            return snap, False
        path = self._choose_disk_snapshot()
        if path is not None:
            try:
                return self.snapshots.load_from_disk(path), True
            except Exception as e:
                logger.error(f"resilience: tier-1 restore of {path} "
                             f"failed: {e!r}")
        return None, False

    def _choose_disk_snapshot(self) -> Optional[str]:
        self.snapshots.wait()  # join any in-flight flush first
        rdzv = self.snapshots._rdzv
        # rdzv unlocks the replacement-node fallbacks (adopt a dead
        # peer's orphaned replica via the sealed-ring diff, bootstrap a
        # scale-up joiner from a live peer) — the adopted snapshot lands
        # in the local dir, so the policy treats it exactly as local
        return choose_resume_snapshot(
            self.snapshots.snapshot_dir,
            client=getattr(rdzv, "c", None),
            node_id=getattr(rdzv, "node_id", None),
            rdzv=rdzv if hasattr(rdzv, "ring_diff") else None)

    # -- restart/resume path ------------------------------------------------

    def resume_if_restarted(self, force: bool = False) -> Optional[str]:
        """Entry-point hook for the elastic restart path: when this
        worker is a RESTART (``DS_ELASTIC_RESTART_COUNT`` > 0, exported
        by the agent), a scale-up JOINER into a running gang
        (``DS_ELASTIC_JOINED_RUNNING``, exported when the rendezvous had
        to bump a sealed round to admit us) — or ``force`` — load the
        policy-chosen newest VALID snapshot from disk (buddy/adoption/
        bootstrap fallbacks included) and resume.  The load path
        reshards a snapshot taken on a different mesh onto the current
        one.  Returns the snapshot path used, or None (fresh start)."""
        restarts = int(os.environ.get("DS_ELASTIC_RESTART_COUNT", "0") or 0)
        joined = os.environ.get("DS_ELASTIC_JOINED_RUNNING", "") == "1"
        if not (force or restarts > 0 or joined):
            return None
        path = self._choose_disk_snapshot()
        if path is None:
            logger.warning(
                "resilience: restarted worker found NO valid snapshot "
                "in any tier — starting from step 0")
            self._annotate("resilience_resume",
                           {"restarts": restarts, "snapshot": None})
            return None
        self.snapshots.load_from_disk(path)
        self.resumes_total += 1
        self._counter("resilience/resumes_total",
                      "restarted workers resumed from a snapshot")
        self._annotate("resilience_resume", {
            "restarts": restarts, "snapshot": path,
            "resumed_step": self.engine.global_steps})
        log_dist(f"resilience: restart #{restarts} resumed from {path} "
                 f"at step {self.engine.global_steps}")
        return path

    # -- watchdog trip ------------------------------------------------------

    def on_watchdog_trip(self, reason: str,
                         bundle: Optional[str] = None) -> None:
        """Trip-edge listener (runs on the watchdog thread, BEFORE its
        configured action): the host is responsive enough to run this,
        so make the newest tier-0 copy durable — the supervisor kill
        that usually follows then costs ≤ one snapshot interval."""
        if not self.cfg.emergency_save_on_trip:
            return
        try:
            # another thread holds the save entry only for its
            # device→host copy: one that outlasts the watchdog's own
            # bound on a device answer is part of the hang
            wd = getattr(self.engine, "watchdog", None)
            path = self.snapshots.emergency_flush(
                entry_timeout_s=getattr(wd, "device_probe_timeout_s", None))
            if path:
                log_dist(f"resilience: emergency snapshot at watchdog "
                         f"trip -> {path}")
        except Exception as e:
            logger.error(f"resilience: emergency save failed: {e!r}")

    # -- plumbing -----------------------------------------------------------

    def _counter(self, name: str, help_: str, v: float = 1.0) -> None:
        from ..telemetry import get_telemetry

        get_telemetry().inc_counter(name, v=v, help=help_)

    def _annotate(self, kind: str, payload: Dict[str, Any]) -> None:
        if self.recorder is not None:
            try:
                self.recorder.annotate(kind, payload)
            except Exception as e:  # diagnostics must not block recovery
                from ..utils.logging import debug_once

                debug_once("resilience/annotate",
                           f"recovery annotation '{kind}' failed ({e!r})")
