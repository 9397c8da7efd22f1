"""Tiered async snapshots of the FULL training state.

The recovery half of a production training stack (ISSUE 4 tentpole,
pillar 1).  Checkpoints answer "resume tomorrow"; snapshots answer
"lose at most ``snapshot_interval`` steps to a NaN, a kill -9, or a
host loss".  Three tiers, each a strictly cheaper/closer copy:

* **tier 0 — host memory**: a double-buffered ``jax.device_get`` of the
  whole :class:`~..runtime.engine.TrainState` (params, optimizer state,
  loss-scale, step, comm residuals) plus engine bookkeeping
  (global/micro steps, LR-scheduler state, registered data-sampler
  cursors, host RNG states).  Rollback from tier 0 is a ``device_put``
  — milliseconds, no storage round-trip.
* **tier 1 — local disk**: the tier-0 copy flushed through
  ``runtime/checkpoint_engine.py`` (async by default: the WHOLE job —
  serialize, hash, commit, replicate, prune — runs on one background
  worker thread over the already-taken immutable host copy, so the step
  path never blocks on storage).  Every flush commits a
  ``snapshot.json`` marker ONLY after the checksummed sidecar manifest
  is durable — restores are checksum-gated, torn flushes are invisible.
* **tier 2 — off-host replica, peer-to-peer**: the flushed snapshot dir
  is served by this node's :class:`~.replica_server.ReplicaServer` and
  PUSHED to the NEXT node in the sealed ring (the "buddy", the expected
  adopter), which holds a physical copy on its own disk and serves it
  too.  The rendezvous store carries only **index/placement metadata**
  (``resil/pub/<node>``: tag, bytes, sha256, holder endpoints) — never
  snapshot bytes — and that metadata is write-journaled, so a killed
  store neither destroys the tier nor forgets where the replicas live:
  adoption and scale-up bootstrap fetch from a holder peer through the
  same transport checksum gate.

The manager is engine-owned (``engine.snapshots``) and driven from
``train_step`` (:meth:`maybe_snapshot`); the recovery policy
(``policy.py``) consumes :meth:`latest`, :meth:`restore`, and the
module-level :func:`choose_resume_snapshot` tier-fallback.
"""

from __future__ import annotations

import json
import os
import pickle
import random
import shutil
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..runtime.checkpoint_engine import (CheckpointCorruptionError,
                                         TorchCheckpointEngine,
                                         join_inflight_save,
                                         orbax_checkpoint,
                                         register_inflight_save,
                                         release_inflight_save,
                                         verify_sidecar_manifest)
from ..utils.logging import log_dist, logger

#: per-snapshot commit marker (meta + "the flush completed durably")
SNAPSHOT_MANIFEST = "snapshot.json"


class SnapshotUnsupportedError(RuntimeError):
    """Tiered snapshots cannot cover this engine's state.

    Raised by :func:`check_snapshot_support` when part of the training
    state lives outside the on-device TrainState (ZeRO-Offload / ZeRO-
    Infinity keep optimizer masters host-side in their own engines).
    The engine catches this and DEGRADES — logs once, disables
    snapshots/recovery, keeps training — instead of refusing to start
    (ROADMAP item 5: real snapshot support for those engines is the
    follow-up; until then a running job beats an error)."""


def check_snapshot_support(engine: Any) -> None:
    """Raise :class:`SnapshotUnsupportedError` naming the engine and the
    workaround when tiered snapshots cannot capture its full state."""
    if getattr(engine, "infinity", None) is not None:
        raise SnapshotUnsupportedError(
            "resilience snapshots cover the on-device TrainState, but "
            "ZeRO-Infinity streams trunk params and keeps optimizer "
            "masters in per-layer host/NVMe planes outside it — a "
            "snapshot would silently miss them.  Workaround: rely on "
            "ordinary checkpoints (save_checkpoint covers Infinity "
            "state), or disable offload_param/Infinity to get tiered "
            "snapshots.  (ROADMAP item 5 tracks native support.)")
    if getattr(engine, "offload_enabled", False):
        raise SnapshotUnsupportedError(
            "resilience snapshots cover the on-device TrainState, but "
            "ZeRO-Offload keeps fp32 masters and moments host-side in "
            "the C++ optimizer — a snapshot would capture stale device "
            "params and no optimizer state.  Workaround: rely on "
            "ordinary checkpoints (save_checkpoint covers offload "
            "state), or disable offload_optimizer to get tiered "
            "snapshots.  (ROADMAP item 5 tracks native support.)")
#: tier-2 store keys: INDEX/placement metadata only (the bytes live on
#: peers — see replica_server.py).  The chunk prefix remains only for
#: reading replicas published by pre-P2P builds.
RESIL_META_KEY = "resil/pub/{node}"
RESIL_CHUNK_PREFIX = "resil/chunk/{node}"
#: each node's replica-server endpoint (journaled, so a restarted store
#: re-learns the placement map from survivors)
RESIL_SRV_KEY = "resil/srv/{node}"


# ---------------------------------------------------------------------------
# mesh-elastic recovery: origin-topology stamping + reshard compatibility
# ---------------------------------------------------------------------------

def format_topology(topo: Optional[Dict[str, Any]]) -> str:
    """One-line human form of a :func:`~..parallel.mesh.mesh_topology`
    dict, used by :class:`MeshMismatchError` and the operator CLI."""
    if not isinstance(topo, dict):
        return "<unknown mesh>"
    axes = topo.get("axes") or {}
    ax = ",".join(f"{a}={s}" for a, s in axes.items()) or "shape unknown"
    return (f"world={topo.get('world_size', '?')} mesh({ax}) "
            f"device={topo.get('device_kind', '?')} "
            f"processes={topo.get('num_processes', '?')} "
            f"coverage={topo.get('host_coverage', '?')}")


class MeshMismatchError(RuntimeError):
    """A snapshot taken on mesh A cannot serve the engine's current mesh
    B.  Carries both topologies and a per-tier reshardability verdict so
    the 3am operator (and the ``verify --target-mesh`` pre-check) can
    read exactly WHY instead of a device_put shape error deep in
    restore."""

    def __init__(self, origin: Optional[Dict[str, Any]],
                 target: Optional[Dict[str, Any]], reason: str,
                 tiers: Optional[Dict[str, str]] = None):
        self.origin = origin
        self.target = target
        self.reason = reason
        self.tiers = tiers or {}
        tier_s = ("; tiers: " + ", ".join(
            f"{t}: {v}" for t, v in self.tiers.items())) if self.tiers \
            else ""
        super().__init__(
            f"snapshot mesh mismatch — origin {format_topology(origin)} "
            f"cannot serve target {format_topology(target)}: "
            f"{reason}{tier_s}")


def check_reshardable(meta: Dict[str, Any],
                      target: Dict[str, Any]) -> Tuple[bool, str]:
    """Can a snapshot whose manifest ``meta`` names its origin mesh be
    re-laid onto ``target``?  Returns ``(ok, reason)``.

    The state tree a snapshot holds is the GLOBAL logical tree (ZeRO
    shards via shardings, never by reshaping leaves), so resharding is a
    ``device_put`` onto the target's shardings — UNLESS

    * the origin capture only covered this host's shards
      (multi-controller ``host_coverage == "partial"``), or
    * part of the state is shaped BY the world size (the 1-bit
      error-feedback residuals are ``[dp_world, ...]`` per leaf).
    """
    origin = meta.get("mesh") if isinstance(meta.get("mesh"), dict) \
        else None
    if origin is None:
        return True, ("origin topology unknown (pre-reshard snapshot) — "
                      "proceeding as a same-mesh restore")
    same = (origin.get("axes") == target.get("axes")
            and origin.get("world_size") == target.get("world_size"))
    if same:
        return True, "identical topology"
    if origin.get("host_coverage") == "partial":
        return False, (
            f"origin snapshot covers only process "
            f"{origin.get('process_index')}'s shards "
            f"({origin.get('num_processes')} origin processes) — a "
            f"different shape needs every origin host's shards")
    baked = meta.get("world_baked_state") or []
    if baked:
        return False, (
            "state leaves are shaped by the origin world size and cannot "
            "be re-laid: " + "; ".join(baked))
    return True, ("global state tree reshards via device_put onto the "
                  "target mesh's shardings")


def reshard_tier_report(meta: Dict[str, Any],
                        target: Dict[str, Any]) -> Dict[str, str]:
    """Per-tier verdict for :class:`MeshMismatchError` / the CLI: which
    tiers could serve ``target``.  Tier 0/2 hold the same host tree as
    tier 1, so reshardability is uniform — EXCEPT partial coverage,
    where tier 1's per-host trees are exactly the shards that are
    missing."""
    ok, reason = check_reshardable(meta, target)
    verdict = "reshardable" if ok else f"NOT reshardable ({reason})"
    return {"tier0 (host memory)": verdict,
            "tier1 (local disk)": verdict,
            "tier2 (buddy replica)": verdict}


class Snapshot:
    """One tier-0 capture: the host-side state tree + JSON-able meta."""

    __slots__ = ("step", "global_steps", "state", "meta", "ts")

    def __init__(self, step: int, global_steps: int, state: Any,
                 meta: Dict[str, Any]):
        self.step = int(step)              # applied optimizer step
        self.global_steps = int(global_steps)
        self.state = state                 # host numpy TrainState tree
        self.meta = meta
        self.ts = time.time()


def _tag(step: int, emergency: bool = False) -> str:
    return f"snap-{step:08d}" + ("-emergency" if emergency else "")


class SnapshotManager:
    """Engine-driven tiered snapshots.  Hot-path cost: one deque-free
    double buffer write every ``snapshot_interval`` steps; everything
    else (serialization, hashing, replication) is off the step path."""

    def __init__(self, engine: Any, cfg: Any,
                 recorder: Any = None,
                 clock: Callable[[], float] = time.perf_counter):
        self.engine = engine
        self.cfg = cfg
        self.recorder = recorder
        self._clock = clock
        # a run with snapshots on saves on deadlines (the emergency flush
        # on the watchdog's thread before its exit action, a SIGTERM's
        # grace period): the checkpoint library is loaded here, at the
        # start, and never by the first of those
        orbax_checkpoint()
        self.snapshot_interval = max(1, int(cfg.snapshot_interval))
        self.snapshot_dir = cfg.snapshot_dir
        self.keep = max(1, int(cfg.keep_snapshots))
        # tier 0: double buffer — the newest capture never overwrites
        # the previous one in place, so a crash MID-capture still leaves
        # one intact copy
        self._buffers: List[Optional[Snapshot]] = [None, None]
        self._active = 0
        #: name -> (capture_fn() -> jsonable, restore_fn(payload)) for
        #: state the engine doesn't own (data-sampler cursors, user
        #: counters); registered by entry.initialize / user code
        self._meta_hooks: Dict[str, Tuple[Callable[[], Any],
                                          Optional[Callable[[Any], None]]]] \
            = {}
        #: async = the WHOLE tier-1 job (serialize, hash, commit,
        #: replicate, prune) runs on one background worker thread; the
        #: step path only pays the already-taken host copy.  Each flush
        #: uses its own throwaway sync engine, so the emergency path
        #: never races a shared engine's pending state.
        self._async = str(cfg.flush_engine) == "async"
        self._flush_pool = None
        self._pending_flush = None
        self._pending_path = None
        #: tier-2 plumbing, attached when an elastic rendezvous exists
        self._rdzv = None
        self.snapshots_taken = 0
        self.flushes = 0
        # restart fence: an in-process restart (elastic agent) builds
        # this manager while the attempt it replaces may still be
        # flushing into the same dir on its background thread; that
        # write finishes before this one lists or writes snapshots
        join_inflight_save(self.snapshot_dir)

    # -- registration ------------------------------------------------------

    def register_meta(self, name: str, capture: Callable[[], Any],
                      restore: Optional[Callable[[Any], None]] = None
                      ) -> None:
        """Attach a named (capture, restore) hook: ``capture()`` is
        folded into every snapshot's meta under ``extras[name]``;
        ``restore(payload)`` (optional) runs on rollback/resume."""
        self._meta_hooks[name] = (capture, restore)

    def attach_rendezvous(self, rdzv: Any) -> None:
        """Enable tier 2 against this elastic rendezvous: its sealed
        ring names the buddy, its client carries the INDEX metadata.
        With the buddy tier on, this also starts (or joins) the
        process-local replica server and publishes its endpoint — a
        journaled write, so a restarted store re-learns the placement
        map from the survivors."""
        self._rdzv = rdzv
        if not self.cfg.buddy_tier or rdzv is None:
            return
        try:
            from .replica_server import get_local_server

            server = get_local_server(
                create=True,
                base_dir=os.path.join(self.snapshot_dir, "replica_store"),
                chunk_bytes=self.cfg.buddy_chunk_bytes,
                max_bytes=self.cfg.buddy_max_bytes)
            rdzv.c.set(RESIL_SRV_KEY.format(node=rdzv.node_id),
                       server.endpoint, journal=True)
        except Exception as e:
            # tier 2 degrades to owner-only serving; tiers 0/1 are whole
            logger.warning(f"resilience: replica server start/publish "
                           f"failed: {e!r}")

    # -- capture (tier 0) --------------------------------------------------

    def _collect_meta(self) -> Dict[str, Any]:
        eng = self.engine
        extras: Dict[str, Any] = {}
        for name, (capture, _restore) in self._meta_hooks.items():
            try:
                extras[name] = capture()
            except Exception as e:  # a dead hook must not lose the snapshot
                extras[name] = {"error": repr(e)}
        return {
            "global_steps": int(eng.global_steps),
            "micro_steps": int(eng.micro_steps),
            "lr_scheduler": eng.lr_scheduler.state_dict(),
            "skipped_steps": int(eng.state.skipped_steps),
            "rng": {
                # host RNG driving data order/augmentation; pickled+hex so
                # the tuple structure survives the JSON manifest
                "python_random": pickle.dumps(random.getstate()).hex(),
                "numpy_global": pickle.dumps(np.random.get_state()).hex(),
            },
            "extras": extras,
            **self._origin_meta(),
        }

    def _origin_meta(self) -> Dict[str, Any]:
        """Origin-topology stamp (mesh-elastic recovery): every snapshot
        records the mesh it was taken on, the jax version, the resolved
        global batch, the state leaf layout, and any world-size-baked
        state — everything :func:`check_reshardable` and the offline
        ``verify --target-mesh`` pre-check need."""
        import jax

        from ..parallel.mesh import mesh_topology

        eng = self.engine
        out: Dict[str, Any] = {"jax_version": str(jax.__version__)}
        try:
            out["mesh"] = (eng.mesh_topology()
                           if hasattr(eng, "mesh_topology")
                           else mesh_topology(eng.mesh))
        except Exception as e:  # a stamp failure must not lose the snapshot
            logger.warning(f"resilience: mesh topology stamp failed: {e!r}")
            return out
        tb = getattr(eng, "train_batch_size", None)
        if tb:
            out["train_batch_size"] = int(tb)
        baked = []
        comm_leaves = jax.tree.leaves(
            getattr(eng.state, "comm_state", ()) or ())
        if comm_leaves:
            baked.append(
                "comm_state: 1-bit error-feedback residuals shaped "
                f"[dp_world={np.shape(comm_leaves[0])[0]}, ...] — baked "
                "to the origin DP world")
        out["world_baked_state"] = baked
        # per-leaf (path, shape) inventory: lets the CLI answer "can I
        # resume this on 3 hosts, and which leaves would still shard?"
        # without loading a single byte of state
        try:
            paths = jax.tree_util.tree_flatten_with_path(eng.state)[0]
            out["state_shapes"] = [
                [jax.tree_util.keystr(kp), list(np.shape(leaf))]
                for kp, leaf in paths]
        except Exception as e:
            logger.warning(f"resilience: state shape stamp failed: {e!r}")
        return out

    def take(self, emergency: bool = False) -> Snapshot:
        """Capture tier 0 NOW (device→host copy of the full state) and,
        when the disk tier is on, hand it to the async flusher."""
        import jax

        eng = self.engine
        t0 = self._clock()
        host_state = jax.tree.map(lambda x: np.asarray(jax.device_get(x)),
                                  eng.state)
        snap = Snapshot(step=int(host_state.step),
                        global_steps=eng.global_steps,
                        state=host_state, meta=self._collect_meta())
        # double buffer: write the NON-active slot, then flip
        self._active ^= 1
        self._buffers[self._active] = snap
        self.snapshots_taken += 1
        dt_ms = (self._clock() - t0) * 1e3
        from ..telemetry.memory import get_memory_ledger

        mem = get_memory_ledger()
        if mem.enabled:
            # tier-0 buffers are a full host copy of the TrainState per
            # slot — the biggest host allocation most runs make; keyed
            # per buffer slot so the double buffer accounts as two
            # entries, each replaced in place on reuse
            mem.register_tree(
                "snapshot", f"resilience/tier0_buffer{self._active}",
                host_state, space="host",
                tag=f"tier-0 snapshot (step {snap.global_steps})")
        from ..telemetry import get_telemetry
        from ..telemetry.perf import get_goodput_ledger

        # the device→host capture blocks the step loop: checkpoint time
        # in the goodput account (the async flush that follows does not)
        get_goodput_ledger().add("checkpoint", dt_ms / 1e3)
        tel = get_telemetry()
        tel.inc_counter("resilience/snapshots_total",
                        help="tier-0 training-state snapshots taken")
        tel.set_gauge("resilience/snapshot_last_ms", dt_ms,
                      help="device->host capture latency of the last "
                           "snapshot")
        tel.set_gauge("resilience/snapshot_last_step", snap.global_steps,
                      help="global step of the newest snapshot")
        if self.recorder is not None:
            self.recorder.annotate("snapshot", {
                "step": snap.global_steps, "capture_ms": round(dt_ms, 3),
                "emergency": emergency})
        if self.cfg.disk_tier:
            self.flush(snap, emergency=emergency)
        return snap

    def maybe_snapshot(self) -> Optional[Snapshot]:
        """The engine's per-step hook: snapshot on the configured
        cadence (cheap no-op between intervals)."""
        if self.engine.global_steps % self.snapshot_interval:
            return None
        return self.take()

    def latest(self) -> Optional[Snapshot]:
        """Newest tier-0 snapshot (the double buffer's active slot)."""
        return self._buffers[self._active] or self._buffers[self._active ^ 1]

    def buffered(self) -> List[Snapshot]:
        """Both tier-0 buffers, newest first."""
        out = [self._buffers[self._active], self._buffers[self._active ^ 1]]
        return [s for s in out if s is not None]

    def discard_newest(self) -> Optional[Snapshot]:
        """Drop the newest tier-0 buffer (the policy calls this when a
        restored snapshot immediately fails again — the capture itself
        is suspect, e.g. params that were already NaN when a later
        step's finite loss let the snapshot through).  Returns the
        discarded snapshot."""
        dropped = self._buffers[self._active]
        self._buffers[self._active] = None
        if self._buffers[self._active ^ 1] is not None:
            self._active ^= 1
        return dropped

    # -- flush (tier 1) ----------------------------------------------------

    def flush(self, snap: Optional[Snapshot] = None,
              emergency: bool = False) -> Optional[str]:
        """Flush ``snap`` (default: newest tier-0) under
        ``snapshot_dir/snap-<step>/``.  Async mode hands the ENTIRE job
        (serialize → checksummed sidecar → commit marker → tier-2
        replicate → prune) to the background worker; the step path only
        joins a still-running PREVIOUS flush (queue depth 1, like the
        reference decoupled engine — bounds host memory to two copies).
        A dir without the ``snapshot.json`` marker is an aborted flush
        and never restores."""
        snap = snap or self.latest()
        if snap is None:
            return None
        path = os.path.join(self.snapshot_dir,
                            _tag(snap.global_steps, emergency=emergency))
        if not self._async or emergency:
            return self._flush_sync(snap, emergency)
        t0 = self._clock()
        self.wait()  # queue depth 1
        if self._flush_pool is None:
            import concurrent.futures

            self._flush_pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="ds-snapshot-flush")
        register_inflight_save(path, self)
        self._pending_path = path
        self._pending_flush = self._flush_pool.submit(
            self._flush_sync, snap, emergency)
        from ..telemetry import get_telemetry

        get_telemetry().set_gauge(
            "resilience/snapshot_flush_dispatch_ms",
            (self._clock() - t0) * 1e3,
            help="step-path cost of dispatching the tier-1 flush "
                 "(async: excludes the background write)")
        return path

    def _flush_sync(self, snap: Snapshot, emergency: bool,
                    entry_timeout_s: Optional[float] = None) -> str:
        """The full tier-1 job, on whatever thread calls it.  Uses a
        throwaway sync engine per call: concurrent emergency + regular
        flushes target different dirs and share no writer state (the
        engine serializes their entry into orbax)."""
        tag = _tag(snap.global_steps, emergency=emergency)
        path = os.path.join(self.snapshot_dir, tag)
        if os.path.isdir(path) and not os.path.exists(
                os.path.join(path, SNAPSHOT_MANIFEST)):
            # a flush of this step that never committed (the attempt
            # that started it was killed, or failed mid-write): nothing
            # restores from it, and its half-written tree must not be
            # what this write trips over
            shutil.rmtree(path, ignore_errors=True)
        os.makedirs(path, exist_ok=True)
        t0 = self._clock()
        state_path = os.path.join(path, "state")
        TorchCheckpointEngine().save(snap.state, state_path,
                                     entry_timeout_s=entry_timeout_s)
        # sha256 sidecar on EVERY host: the engine only stamps it on
        # process 0 (user checkpoints share one tree), but snapshots are
        # per-host local trees — each host gates its own restores.
        # (process 0's save already stamped it; don't hash twice)
        from ..runtime.checkpoint_engine import (_is_write_coordinator,
                                                 write_sidecar_manifest)

        if not _is_write_coordinator():
            write_sidecar_manifest(state_path)
        manifest = {"tag": tag, "step": snap.step,
                    "global_steps": snap.global_steps,
                    "emergency": bool(emergency),
                    "ts": snap.ts, "meta": snap.meta}
        tmp = os.path.join(path, SNAPSHOT_MANIFEST + ".tmp")
        with open(tmp, "w") as fh:
            json.dump(manifest, fh, indent=1, default=str)
        os.replace(tmp, os.path.join(path, SNAPSHOT_MANIFEST))  # commit
        self.flushes += 1
        from ..telemetry import get_telemetry

        tel = get_telemetry()
        tel.inc_counter("resilience/snapshot_flushes_total",
                        help="tier-1 snapshot flushes committed durably")
        tel.set_gauge("resilience/snapshot_flush_ms",
                      (self._clock() - t0) * 1e3,
                      help="wall time of the last tier-1 flush "
                           "(background thread in async mode)")
        self._replicate(path)
        self._prune()
        return path

    def wait(self) -> None:
        """Join any in-flight async flush (tests / teardown / before a
        deliberate corruption or a restore decision)."""
        pending = self._pending_flush
        if pending is None:
            return
        try:
            pending.result()
        except Exception as e:
            # a failed background flush must surface (loudly) but
            # not kill the training step that joined it — the next
            # interval retries with a fresh snapshot
            logger.error(f"resilience: background snapshot flush "
                         f"failed: {e!r}")
        # cleared only once joined: a second joiner (the next attempt's
        # manager, on another thread) waits on the same future
        if self._pending_flush is pending:
            self._pending_flush = None
            release_inflight_save(self._pending_path, self)

    def emergency_flush(self, entry_timeout_s: Optional[float] = None
                        ) -> Optional[str]:
        """Watchdog-trip path: the device may be hung, but the newest
        tier-0 HOST copy is already taken — make it durable NOW, on the
        calling (watchdog) thread with its own sync writer (the
        background flusher may be the thing that is stuck).
        ``entry_timeout_s`` bounds the wait behind another thread's
        entry into a save (its device→host copy; the background write
        holds nothing): past it this one writes unserialized."""
        snap = self.latest()
        if snap is None:
            return None
        path = self._flush_sync(snap, emergency=True,
                                entry_timeout_s=entry_timeout_s)
        from ..telemetry import get_telemetry

        get_telemetry().inc_counter(
            "resilience/emergency_saves_total",
            help="emergency snapshot flushes on watchdog trip")
        if self.recorder is not None:
            self.recorder.annotate("resilience_emergency_save",
                                   {"path": path})
        return path

    def _prune(self) -> None:
        """Keep the newest ``keep`` committed snapshot dirs (plus any
        still-uncommitted flush target) — best-effort."""
        try:
            snaps = list_snapshots(self.snapshot_dir)
            for entry in snaps[self.keep:]:
                shutil.rmtree(entry["path"], ignore_errors=True)
        except OSError:
            pass

    # -- replicate (tier 2) ------------------------------------------------

    def _replicate(self, path: str) -> None:
        if not (self.cfg.buddy_tier and self._rdzv is not None):
            return
        try:
            buddy = self._rdzv.buddy()
            if buddy is None:
                return  # no surviving peer could ever adopt the replica
            meta = replicate_snapshot(self._rdzv.c, self._rdzv.node_id,
                                      path, rdzv=self._rdzv,
                                      chunk_bytes=self.cfg.buddy_chunk_bytes,
                                      max_bytes=self.cfg.buddy_max_bytes)
            if meta.get("dropped"):
                # a size-capped tar that dropped state files is a TORN
                # replica — it can never pass the checksum gate, so it
                # must not count as a successful replication
                logger.warning(
                    f"resilience: tier-2 replica of {path} exceeds "
                    f"buddy_max_bytes ({self.cfg.buddy_max_bytes}); "
                    f"dropped {meta['dropped']} — replica NOT restorable, "
                    f"raise the cap or disable buddy_tier")
                return
            from ..telemetry import get_telemetry

            get_telemetry().inc_counter(
                "resilience/buddy_replications_total",
                help="tier-2 snapshot replications through the store")
        except Exception as e:
            # replication is the LAST tier; its failure must never fail
            # the flush that tier 1 already committed
            logger.warning(f"resilience: buddy replication failed: {e!r}")

    # -- restore -----------------------------------------------------------

    def _reshard_guard(self, meta: Dict[str, Any],
                       source: str) -> Optional[Dict[str, Any]]:
        """Mesh-elastic restore gate: compare the snapshot's origin
        topology against the engine's CURRENT mesh.  Same mesh → None
        (the ordinary restore).  Different but reshardable → a reshape
        info dict (origin/target/direction) the caller accounts after
        the re-lay succeeds.  Not reshardable → a descriptive
        :class:`MeshMismatchError` naming both topologies and the
        per-tier verdict, instead of an opaque device_put error deep in
        the load."""
        from ..parallel.mesh import mesh_topology

        eng = self.engine
        target = (eng.mesh_topology() if hasattr(eng, "mesh_topology")
                  else mesh_topology(eng.mesh))
        origin = meta.get("mesh") if isinstance(meta.get("mesh"), dict) \
            else None
        if origin is None:
            return None  # pre-reshard snapshot: same-mesh semantics
        if (origin.get("axes") == target.get("axes")
                and origin.get("world_size") == target.get("world_size")):
            return None
        ok, reason = check_reshardable(meta, target)
        if not ok:
            raise MeshMismatchError(origin, target, reason,
                                    tiers=reshard_tier_report(meta, target))
        o_w, t_w = int(origin["world_size"]), int(target["world_size"])
        direction = "shrink" if t_w < o_w else "grow"
        logger.warning(
            f"resilience: resharding {source} snapshot taken on "
            f"[{format_topology(origin)}] onto the current mesh "
            f"[{format_topology(target)}] ({direction})")
        return {"origin": origin, "target": target,
                "direction": direction, "source": source,
                "origin_train_batch_size": meta.get("train_batch_size")}

    def _account_reshape(self, info: Dict[str, Any],
                         reshard_ms: float) -> None:
        """A cross-mesh restore COMPLETED: counters (total + the
        direction breakdown — the registry has no labels, so
        ``{direction}`` is a counter pair), latency gauge, and a
        ``reshape`` annotation carrying both topologies into the next
        debug bundle."""
        from ..telemetry import get_telemetry

        tel = get_telemetry()
        # reshard_restores = the ENGINE actually re-laid state across
        # meshes; reshapes_total (agent) = the gang resealed at a new
        # world size.  Separate names so in-process deployments (agent +
        # worker share one registry) never double-count one event.
        tel.inc_counter("resilience/reshard_restores_total",
                        help="snapshot restores that re-laid state onto "
                             "a DIFFERENT mesh shape")
        tel.inc_counter(
            f"resilience/reshard_restores_{info['direction']}_total",
            help="cross-mesh snapshot restores, by direction (the "
                 "{direction} breakdown of "
                 "resilience/reshard_restores_total)")
        tel.set_gauge("resilience/reshard_last_ms", reshard_ms,
                      help="state re-lay latency of the last cross-mesh "
                           "restore")
        if self.recorder is not None:
            self.recorder.annotate("reshape", {
                "direction": info["direction"], "source": info["source"],
                "origin": info["origin"], "target": info["target"],
                "reshard_ms": round(reshard_ms, 3),
                "resumed_step": int(self.engine.global_steps)})

    def restore(self, snap: Snapshot) -> None:
        """Roll the ENGINE back to ``snap``: device_put the host tree
        onto the engine's current shardings, rewind the bookkeeping, and
        run every registered restore hook.  The host tree is the GLOBAL
        logical state, so a snapshot taken on a different mesh re-lays
        onto the current shardings in the same device_put — gated by
        :meth:`_reshard_guard`."""
        import jax

        eng = self.engine
        reshape = self._reshard_guard(snap.meta, "tier-0")
        t0 = self._clock()
        shardings = eng._state_shardings(eng.state)
        eng.state = jax.device_put(snap.state, shardings)
        self._restore_meta(snap.meta)
        if reshape is not None:
            self._account_reshape(reshape, (self._clock() - t0) * 1e3)
        log_dist(f"resilience: restored training state to step "
                 f"{snap.global_steps}")

    def _restore_meta(self, meta: Dict[str, Any]) -> None:
        eng = self.engine
        eng.global_steps = int(meta["global_steps"])
        eng.micro_steps = int(meta["micro_steps"])
        eng.lr_scheduler.load_state_dict(meta["lr_scheduler"])
        eng.last_metrics = {}
        rng = meta.get("rng") or {}
        try:
            if rng.get("python_random"):
                random.setstate(pickle.loads(
                    bytes.fromhex(rng["python_random"])))
            if rng.get("numpy_global"):
                np.random.set_state(pickle.loads(
                    bytes.fromhex(rng["numpy_global"])))
        except Exception as e:
            logger.warning(f"resilience: host RNG restore failed: {e!r}")
        extras = meta.get("extras") or {}
        for name, (_capture, restore_fn) in self._meta_hooks.items():
            if restore_fn is not None and name in extras:
                try:
                    restore_fn(extras[name])
                except Exception as e:
                    logger.warning(f"resilience: meta hook {name!r} "
                                   f"restore failed: {e!r}")

    def load_from_disk(self, path: str) -> Snapshot:
        """Checksum-gated tier-1 restore: verify the commit marker and
        the sidecar, load the state tree INTO the engine's sharded
        layout (orbax reshard-on-load re-lays a snapshot taken on a
        different mesh, gated by :meth:`_reshard_guard`), apply it, and
        return the reconstructed snapshot."""
        import jax

        manifest = read_snapshot_manifest(path)  # raises when torn
        reshape = self._reshard_guard(manifest.get("meta") or {}, "tier-1")
        state_path = os.path.join(path, "state")
        verify_sidecar_manifest(state_path, strict=True)
        eng = self.engine
        t0 = self._clock()

        def abstract(x):
            return jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                        sharding=getattr(x, "sharding",
                                                         None))

        target = jax.tree.map(abstract, eng.state)
        # the sync loader verifies + restores resharded onto this
        # engine's mesh (orbax reshard-on-load)
        eng.state = TorchCheckpointEngine().load(state_path, target)
        self._restore_meta(manifest["meta"])
        if reshape is not None:
            self._account_reshape(reshape, (self._clock() - t0) * 1e3)
        snap = Snapshot(step=int(manifest["step"]),
                        global_steps=int(manifest["global_steps"]),
                        state=jax.tree.map(
                            lambda x: np.asarray(jax.device_get(x)),
                            eng.state),
                        meta=manifest["meta"])
        # seed tier 0 so the next rollback needn't touch disk
        self._active ^= 1
        self._buffers[self._active] = snap
        return snap


# ---------------------------------------------------------------------------
# on-disk inventory + validation (policy + operator CLI)
# ---------------------------------------------------------------------------

def read_snapshot_manifest(path: str) -> Dict[str, Any]:
    mp = os.path.join(path, SNAPSHOT_MANIFEST)
    if not os.path.exists(mp):
        raise CheckpointCorruptionError(
            f"snapshot {path!r} has no {SNAPSHOT_MANIFEST} commit marker "
            f"— the flush never completed")
    try:
        with open(mp) as fh:
            return json.load(fh)
    except (OSError, ValueError) as e:
        raise CheckpointCorruptionError(
            f"snapshot {path!r}: unreadable {SNAPSHOT_MANIFEST} "
            f"({e!r})") from e


def verify_snapshot(path: str) -> Tuple[bool, str]:
    """Full integrity check of one snapshot dir.  Returns
    ``(valid, detail)`` — detail is the human-readable failure."""
    try:
        manifest = read_snapshot_manifest(path)
        verify_sidecar_manifest(os.path.join(path, "state"), strict=True)
        return True, f"ok (step {manifest.get('global_steps')})"
    except CheckpointCorruptionError as e:
        return False, str(e)


def list_snapshots(snapshot_dir: str) -> List[Dict[str, Any]]:
    """Committed snapshots under ``snapshot_dir``, NEWEST first (by
    step, emergency flushes ranked beneath a regular flush of the same
    step).  Uncommitted dirs (no marker) are skipped."""
    out: List[Dict[str, Any]] = []
    if not os.path.isdir(snapshot_dir):
        return out
    for d in os.listdir(snapshot_dir):
        path = os.path.join(snapshot_dir, d)
        if not (d.startswith("snap-") and os.path.isdir(path)):
            continue
        try:
            m = read_snapshot_manifest(path)
        except CheckpointCorruptionError:
            continue
        out.append({"path": path, "tag": m.get("tag", d),
                    "step": int(m.get("global_steps", -1)),
                    "emergency": bool(m.get("emergency")),
                    "ts": m.get("ts")})
    out.sort(key=lambda e: (e["step"], not e["emergency"], e["tag"]),
             reverse=True)
    return out


def choose_resume_snapshot(snapshot_dir: str,
                           client: Any = None,
                           node_id: Optional[str] = None,
                           fetch_dir: Optional[str] = None,
                           rdzv: Any = None) -> Optional[str]:
    """The policy's tier-fallback: newest LOCAL snapshot that passes the
    checksum gate; when none survives and a store client is given, pull
    the tier-2 buddy replica of ``node_id`` into ``fetch_dir`` (default:
    the snapshot dir) and validate that.  With ``rdzv`` (an
    :class:`~..elasticity.rendezvous.ElasticRendezvous`), two further
    fallbacks close the replacement-node gap: ADOPT a dead peer's
    orphaned replica (sealed-ring diff names the dead; this node re-keys
    the replica under its own id), then BOOTSTRAP from any live peer's
    replica (a scale-up joiner has no history of its own).  Returns a
    verified snapshot path or None."""
    for entry in list_snapshots(snapshot_dir):
        ok, detail = verify_snapshot(entry["path"])
        if ok:
            return entry["path"]
        logger.warning(f"resilience: skipping invalid snapshot "
                       f"{entry['path']}: {detail}")
    if client is None and rdzv is not None:
        client = rdzv.c
    if node_id is None and rdzv is not None:
        node_id = rdzv.node_id
    if client is not None and node_id:
        try:
            pulled = fetch_buddy_snapshot(client, node_id,
                                          fetch_dir or snapshot_dir)
        except Exception as e:
            logger.warning(f"resilience: buddy snapshot fetch failed: "
                           f"{e!r}")
            pulled = None
        if pulled:
            ok, detail = verify_snapshot(pulled)
            if ok:
                return pulled
            logger.warning(f"resilience: buddy replica invalid: {detail}")
    if rdzv is not None:
        adopted = adopt_orphaned_replica(rdzv, fetch_dir or snapshot_dir)
        if adopted:
            return adopted
        return bootstrap_from_peer_replica(rdzv,
                                           fetch_dir or snapshot_dir)
    return None


# ---------------------------------------------------------------------------
# replacement-node adoption + scale-up bootstrap (ROADMAP item 5)
# ---------------------------------------------------------------------------

def adopt_orphaned_replica(rdzv: Any, out_dir: str,
                           retries: int = 6,
                           retry_delay_s: float = 2.0) -> Optional[str]:
    """Replacement-node adoption: a node with a FRESH node id that
    sealed into the ring after a death walks the sealed-ring diff,
    discovers which dead peer's tier-2 replica is orphaned, fetches it,
    verifies the checksum gate, and RE-KEYS it under its own id (so its
    future buddy — and its own future restarts — find the slot where
    they expect it).  Deterministic assignment: the k-th joined node
    (sorted) adopts the k-th dead peer (sorted, wrapping), so two
    replacements never fight over one corpse.  Fetches retry briefly
    (``retries`` rounds, ``retry_delay_s`` apart): adoption runs while
    the gang is RE-FORMING, so a surviving holder may itself be
    mid-restart with its replica server not yet re-bound.  Returns the
    local adopted snapshot path, or None."""
    try:
        diff = rdzv.ring_diff()
    except Exception as e:
        logger.warning(f"resilience: sealed-ring diff failed: {e!r}")
        return None
    dead = sorted(diff.get("left") or [])
    joined = sorted(diff.get("joined") or [])
    me = rdzv.node_id
    if not dead or me not in joined:
        # a restarted SAME-id node owns its own slot (handled by the
        # plain buddy fetch above); nothing orphaned to adopt
        return None
    k = joined.index(me) % len(dead)
    candidates = dead[k:] + dead[:k]
    pulled = None
    peer = None
    for attempt in range(max(1, int(retries))):
        if attempt:
            time.sleep(retry_delay_s)
            logger.warning(f"resilience: adoption retry "
                           f"{attempt + 1}/{retries} (holders may be "
                           f"re-binding mid-reform)")
        for cand in candidates:
            try:
                got = fetch_buddy_snapshot(rdzv.c, cand, out_dir)
            except Exception as e:
                logger.warning(f"resilience: fetch of dead peer "
                               f"{cand!r}'s replica failed: {e!r}")
                continue
            if not got:
                continue  # that peer never replicated
            ok, detail = verify_snapshot(got)
            if not ok:
                logger.warning(f"resilience: dead peer {cand!r}'s "
                               f"replica invalid: {detail}")
                continue
            pulled, peer = got, cand
            break
        if pulled:
            break
    if not pulled:
        return None
    try:
        # re-key under OUR id: serve the adopted dir from our own
        # replica server (+ push to our buddy) and re-point the index
        replicate_snapshot(rdzv.c, me, pulled, rdzv=rdzv)
    except Exception as e:
        logger.warning(f"resilience: re-keying adopted replica under "
                       f"{me!r} failed (adoption still valid): {e!r}")
    from ..telemetry import get_telemetry

    get_telemetry().inc_counter(
        "resilience/replica_adoptions_total",
        help="dead peers' tier-2 replicas adopted by replacement "
             "nodes (sealed-ring diff)")
    log_dist(f"resilience: node {me} adopted dead peer {peer}'s "
             f"tier-2 replica -> {pulled}")
    return pulled


def bootstrap_from_peer_replica(rdzv: Any, out_dir: str) -> Optional[str]:
    """Scale-up bootstrap: a JOINING node with no local history and no
    orphan to adopt pulls the newest live peer's replica as its starting
    point — the reshard-on-restore path then lays it onto whatever mesh
    the new world builds.  Returns the local path, or None."""
    try:
        gang = [n for n in rdzv.sealed_ring() if n != rdzv.node_id]
    except Exception as e:
        logger.warning(f"resilience: sealed-ring read failed: {e!r}")
        return None
    best: Optional[Tuple[float, str]] = None
    for peer in gang:
        meta = rdzv.c.get(RESIL_META_KEY.format(node=peer))
        if isinstance(meta, dict):
            ts = float(meta.get("ts") or 0.0)
            if best is None or ts > best[0]:
                best = (ts, peer)
    if best is None:
        return None
    pulled = None
    for attempt in range(3):
        if attempt:
            # the gang is re-forming: the peer's replica server may be
            # re-binding — brief bounded retry, same as adoption
            time.sleep(2.0)
        try:
            pulled = fetch_buddy_snapshot(rdzv.c, best[1], out_dir)
        except Exception as e:
            logger.warning(f"resilience: bootstrap fetch from "
                           f"{best[1]!r} failed: {e!r}")
            pulled = None
        if pulled:
            break
    if not pulled:
        return None
    ok, detail = verify_snapshot(pulled)
    if not ok:
        logger.warning(f"resilience: bootstrap replica from {best[1]!r} "
                       f"invalid: {detail}")
        return None
    from ..telemetry import get_telemetry

    get_telemetry().inc_counter(
        "resilience/replica_bootstraps_total",
        help="joining nodes bootstrapped from a live peer's tier-2 "
             "replica (scale-up)")
    log_dist(f"resilience: joining node {rdzv.node_id} bootstrapped from "
             f"peer {best[1]}'s replica -> {pulled}")
    return pulled


# ---------------------------------------------------------------------------
# tier-2 transport (peer-to-peer replica servers; the store carries
# index/placement metadata only)
# ---------------------------------------------------------------------------

def replicate_snapshot(client: Any, node_id: str, snap_dir: str,
                       chunk_bytes: int = 256 * 1024,
                       max_bytes: int = 256 * 1024 * 1024,
                       rdzv: Any = None,
                       buddy: Optional[str] = None) -> Dict[str, Any]:
    """Make one committed snapshot dir fetchable by the gang:

    1. serve it from this process's replica server (started on demand);
    2. PUSH a physical copy to the buddy's replica server when one is
       reachable (``rdzv``/``buddy`` name it; its endpoint comes from
       the store's ``resil/srv/<buddy>`` slot) — the copy that survives
       this host's death;
    3. publish the INDEX metadata (tag, bytes, sha256, holder
       endpoints) under ``resil/pub/<node_id>`` — a journaled write, so
       it buffers through a store outage and re-seeds a restarted
       store.  **No snapshot bytes ever enter the store.**
    """
    import hashlib as _hashlib

    from ..telemetry.aggregator import _tar_dir
    from .replica_server import get_local_server, push_replica

    tag = os.path.basename(snap_dir.rstrip(os.sep))
    data, dropped = _tar_dir(snap_dir, max_bytes,
                             priority_file=SNAPSHOT_MANIFEST,
                             recursive=True)
    sha = _hashlib.sha256(data).hexdigest()
    server = get_local_server(
        create=True, base_dir=os.path.join(os.path.dirname(
            snap_dir.rstrip(os.sep)), "replica_store"),
        chunk_bytes=chunk_bytes, max_bytes=max_bytes)
    server.serve(node_id, tag, snap_dir, tar=(data, sha),
                 max_bytes=max_bytes)
    holders: List[Dict[str, Any]] = [
        {"node": node_id, "endpoint": server.endpoint, "path": snap_dir}]
    if buddy is None and rdzv is not None:
        try:
            buddy = rdzv.buddy()
        except Exception as e:
            logger.warning(f"resilience: buddy lookup failed: {e!r}")
            buddy = None
    if buddy and buddy != node_id:
        buddy_ep = None
        try:
            buddy_ep = client.get(RESIL_SRV_KEY.format(node=buddy))
        except (OSError, ConnectionError) as e:
            logger.warning(f"resilience: buddy endpoint lookup failed "
                           f"(store degraded?): {e!r}")
        if buddy_ep:
            try:
                held = push_replica(str(buddy_ep), node_id, tag, data,
                                    sha, chunk_bytes=chunk_bytes)
                holders.append({"node": buddy, "endpoint": str(buddy_ep),
                                "path": held})
            except Exception as e:
                # owner-only serving still covers restarts; only a
                # simultaneous owner+store loss needs the buddy copy
                logger.warning(f"resilience: replica push to buddy "
                               f"{buddy!r} ({buddy_ep}) failed: {e!r}")
        else:
            logger.warning(f"resilience: buddy {buddy!r} has no replica "
                           f"server endpoint published — replica held "
                           f"by owner only")
    meta = {"bundle": tag, "owner": node_id, "bytes": len(data),
            "sha256": sha, "dropped": dropped, "ts": time.time(),
            "holders": holders}
    try:
        client.set(RESIL_META_KEY.format(node=node_id), meta,
                   journal=True)
    except TypeError:
        # a minimal client without the journal kwarg (tests/fakes)
        client.set(RESIL_META_KEY.format(node=node_id), meta)
    return meta


def fetch_buddy_snapshot(client: Any, node_id: str,
                         out_dir: str) -> Optional[str]:
    """Pull ``node_id``'s replica using the store's INDEX metadata:
    try each holder endpoint in order (owner first, then the buddy) and
    fall through past dead peers; every fetch passes the transport
    sha256 gate.  Returns the extracted snapshot path, None when that
    node never replicated, and raises when holders exist but none could
    serve a VALID copy (all dead, or all corrupt — the caller's tier
    fallback treats that as 'no tier-2')."""
    meta = client.get(RESIL_META_KEY.format(node=node_id))
    if not isinstance(meta, dict):
        return None
    if "holders" not in meta:
        # pre-P2P publication: bytes chunked into the store
        from ..telemetry.aggregator import fetch_dir_chunked

        return fetch_dir_chunked(
            client, RESIL_META_KEY.format(node=node_id),
            RESIL_CHUNK_PREFIX.format(node=node_id), out_dir)
    from .replica_server import fetch_replica

    owner = str(meta.get("owner") or node_id)
    tag = str(meta["bundle"])
    errors: List[str] = []
    for holder in meta.get("holders") or []:
        # a holder NODE is stable; its endpoint is not (worker restarts
        # re-bind).  Prefer the holder's CURRENTLY-published server
        # endpoint, falling back to the one recorded at placement time.
        endpoints = []
        hnode = holder.get("node")
        if hnode:
            try:
                live = client.get(RESIL_SRV_KEY.format(node=hnode))
            except (OSError, ConnectionError):
                live = None  # store degraded — recorded endpoint only
            if live:
                endpoints.append(str(live))
        recorded = str(holder.get("endpoint") or "")
        if recorded and recorded not in endpoints:
            endpoints.append(recorded)
        dead_here = None
        for ep in endpoints:
            try:
                return fetch_replica(ep, owner, tag, out_dir,
                                     expect_sha=meta.get("sha256"))
            except (OSError, ConnectionError) as e:
                dead_here = e
            except CheckpointCorruptionError as e:
                dead_here = None
                errors.append(f"{hnode}@{ep}: {e}")
                break  # corrupt copy: this holder is done, move on
        if dead_here is not None:
            # dead/unreachable holder: fall through to the next
            # placement candidate
            errors.append(f"{hnode}@{endpoints}: {dead_here!r}")
            from ..telemetry import get_telemetry

            get_telemetry().inc_counter(
                "resilience/replica_fetch_fallthroughs_total",
                help="replica holders skipped because they were "
                     "unreachable (fetch fell through to the next "
                     "placement candidate)")
    raise CheckpointCorruptionError(
        f"tier-2 replica of {node_id!r} ({tag}) could not be fetched "
        f"from any holder: " + "; ".join(errors or ["no holder had an "
                                                    "endpoint"]))
