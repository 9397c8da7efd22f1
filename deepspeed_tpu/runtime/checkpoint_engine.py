"""Pluggable checkpoint engines — sync + decoupled (async) backends.

Reference: ``deepspeed/runtime/checkpoint_engine/`` [K] (SURVEY §2.1 row
"Checkpoint engines"): ``TorchCheckpointEngine`` (synchronous
``torch.save``), ``DecoupledCheckpointEngine`` (background async save),
``NebulaCheckpointEngine`` (MSFT service — documented out of scope).

TPU-first: orbax already implements the hard part — ``AsyncCheckpointer``
blocks only for the device→host copy, then serializes to storage on a
background thread, which is donation-safe (the next ``train_step`` can
invalidate the device buffers; the host copy is already taken).  The
engine classes here supply the reference's lifecycle surface
(create/save/load/commit/wait) around the two orbax modes.

Where the library is loaded: ``orbax.checkpoint`` costs seconds to import
(13 s on the chip's host, PERF.md PR 55: its logging pulls
``google.cloud`` and a walk over the installed distributions), and this
module is imported by every training start for the names of its
exceptions.  So nothing here imports it at module level:
:func:`orbax_checkpoint` imports it where it is first used.  That is the
first save or load of a run that configured nothing that saves, and the
START of a run that will save on a deadline: ``SnapshotManager.__init__``
(``resilience.enabled``) and :class:`DecoupledCheckpointEngine`'s
constructor (the engine makes its checkpoint engine as it is built) call
it, so neither an emergency flush on the watchdog's thread nor an async
save that exists to return at once meets a cold import.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import sys
import threading
import time
import weakref
from typing import Any, Dict, Optional

import jax
import numpy as np

from ..utils.logging import log_dist, logger


def orbax_checkpoint():
    """``orbax.checkpoint``, imported by whoever asks first (the module
    docstring says who that is).  The import that loads it is a span
    ``startup/import`` with ``module == "orbax.checkpoint"``: on the
    start-up record where a start is open on this thread (a leaf of its
    own in ``import_s`` and the start's line), else a span of the hub, so
    that a first save seconds longer than the second says why."""
    span = contextlib.nullcontext()
    if "orbax.checkpoint" not in sys.modules:
        from ..telemetry import get_telemetry

        tel = get_telemetry()
        under_start = tel.startup.innermost() is not None
        span = (tel.startup_span if under_start else tel.span)(
            "startup/import", {"module": "orbax.checkpoint"})
    with span:  # a second thread waits here for the first one's import
        import orbax.checkpoint as ocp

    return ocp


#: sidecar integrity manifest written next to every saved checkpoint tree
SIDECAR_MANIFEST = "ds_manifest.json"

#: ONE thread at a time inside the synchronous part of an orbax
#: ``save()``.  orbax keys a save's "directory created" signals on
#: ``OperationIdGenerator._operation_id``, a process-global that every
#: ``save()`` advances and that the futures it builds read back while
#: the call is still on the caller's thread: two threads in there at
#: once read each other's id, so one save's array writer is released by
#: the OTHER save's signal and writes into a tmp dir its own creator
#: then finds (``FileExistsError``), removes under the writer
#: (``OSError 39`` / tensorstore ``NOT_FOUND``) or never signals (the
#: writer waits out orbax's coordination timeout).  Once ``save()``
#: returns every future holds its own id, so the background write needs
#: no lock.  The training thread's checkpoint, the snapshot flusher, an
#: emergency flush on the watchdog thread and every in-process host of
#: an elastic gang all save from their own threads (ROADMAP D0).
_save_entry_lock = threading.Lock()


@contextlib.contextmanager
def save_entry(path: str, timeout_s: Optional[float] = None):
    """Hold :data:`_save_entry_lock` around one orbax ``save()`` call.
    ``timeout_s`` is for a caller that must not wait forever behind a
    save that may itself be what hangs (the emergency flush): after
    that long it goes in unserialized, and says so."""
    locked = _save_entry_lock.acquire(
        timeout=-1 if timeout_s is None else max(timeout_s, 0.0))
    if not locked:
        logger.warning(
            f"checkpoint save of {path}: another save held orbax's "
            f"entry for {timeout_s:.1f}s; writing unserialized (the "
            f"write may fail or tear: the manifest gate refuses it on "
            f"load if so)")
        from ..telemetry import get_telemetry

        get_telemetry().inc_counter(
            "checkpoint/unserialized_saves_total",
            help="orbax saves started without the entry lock after "
                 "waiting out their bound behind another save")
    try:
        yield
    finally:
        if locked:
            _save_entry_lock.release()


class CheckpointCorruptionError(RuntimeError):
    """A checkpoint failed integrity validation (truncated / corrupt /
    missing files).  The message names the first offending file — the
    resilience tier-fallback catches this and tries the next snapshot
    instead of restoring garbage."""


def _iter_payload_files(path: str):
    """Every regular file under ``path`` except the sidecar itself,
    as (relative_name, absolute_path), deterministic order."""
    for root, _dirs, files in os.walk(path):
        for f in sorted(files):
            rel = os.path.relpath(os.path.join(root, f), path)
            if rel in (SIDECAR_MANIFEST, SIDECAR_MANIFEST + ".tmp"):
                continue
            yield rel, os.path.join(root, f)


def _sha256_file(p: str) -> str:
    h = hashlib.sha256()
    with open(p, "rb") as fh:
        for block in iter(lambda: fh.read(1 << 20), b""):
            h.update(block)
    return h.hexdigest()


def _is_write_coordinator() -> bool:
    """Multi-controller: exactly ONE process may stamp the sidecar —
    N processes writing (and hashing files mid-finalize on other hosts)
    over one shared tree would race each other into a manifest that
    matches nobody.  Orbax's save barrier has completed by the time the
    engines call this, so process 0 sees the finished tree."""
    try:
        return jax.process_index() == 0
    except Exception:
        return True  # single-controller / distributed not initialized


def write_sidecar_manifest(path: str) -> Dict[str, Any]:
    """Stamp ``<path>/ds_manifest.json`` with per-file size + sha256 of
    everything the serializer wrote.  Called AFTER the write is complete
    (sync: right after save; async: after wait_until_finished) and
    BEFORE any durability marker, so a manifest's existence implies the
    payload it describes was fully on disk at stamp time."""
    files = {rel: {"bytes": os.path.getsize(p), "sha256": _sha256_file(p)}
             for rel, p in _iter_payload_files(path)}
    manifest = {"version": 1, "files": files}
    tmp = os.path.join(path, SIDECAR_MANIFEST + ".tmp")
    with open(tmp, "w") as fh:
        json.dump(manifest, fh, indent=1)
    os.replace(tmp, os.path.join(path, SIDECAR_MANIFEST))  # atomic
    return manifest


def verify_sidecar_manifest(path: str, strict: bool = False,
                            deep: Optional[bool] = None) -> bool:
    """Validate ``path`` against its sidecar manifest.

    Returns True when a sidecar exists and every file matches.  Without
    a sidecar: False when ``strict`` (resilience snapshots REQUIRE the
    manifest — a missing one means the flush never committed), else
    True (legacy checkpoints predate the sidecar).  Raises
    :class:`CheckpointCorruptionError` naming the first mismatch.

    ``deep`` (default: same as ``strict``) controls whether file
    CONTENTS are re-hashed.  The shallow pass (existence + size) is one
    ``stat`` per file and catches torn/truncated trees; the deep pass
    re-reads everything — right for the resilience checksum gate, too
    expensive to impose on every ordinary multi-GB checkpoint load.
    """
    deep = strict if deep is None else deep
    mp = os.path.join(path, SIDECAR_MANIFEST)
    if not os.path.isdir(path):
        raise CheckpointCorruptionError(
            f"checkpoint {path!r} does not exist or is not a directory")
    if not os.path.exists(mp):
        if strict:
            raise CheckpointCorruptionError(
                f"checkpoint {path!r} has no {SIDECAR_MANIFEST} sidecar — "
                f"the save never completed (or predates integrity "
                f"manifests)")
        return True
    try:
        with open(mp) as fh:
            manifest = json.load(fh)
        files = manifest["files"]
    except (OSError, ValueError, KeyError) as e:
        raise CheckpointCorruptionError(
            f"checkpoint {path!r}: unreadable sidecar manifest "
            f"{SIDECAR_MANIFEST} ({e!r})") from e
    for rel, meta in sorted(files.items()):
        p = os.path.join(path, rel)
        if not os.path.exists(p):
            raise CheckpointCorruptionError(
                f"checkpoint {path!r}: file {rel!r} listed in the "
                f"manifest is missing (torn/partial checkpoint)")
        size = os.path.getsize(p)
        if size != int(meta["bytes"]):
            raise CheckpointCorruptionError(
                f"checkpoint {path!r}: file {rel!r} is {size} bytes, "
                f"manifest says {meta['bytes']} (truncated write)")
        if deep and _sha256_file(p) != meta["sha256"]:
            raise CheckpointCorruptionError(
                f"checkpoint {path!r}: file {rel!r} fails its sha256 "
                f"checksum (bit-rot or partial overwrite)")
    return True


class CheckpointEngine:
    """Reference base-class surface."""

    def __init__(self, config_params: Any = None):
        self.config_params = config_params

    def create(self, tag: str) -> None:  # bookkeeping hook
        pass

    def save(self, state_tree: Any, path: str,
             commit_fn: Optional[Any] = None) -> None:
        """``commit_fn()`` runs only once the write is DURABLE — the sync
        engine calls it immediately, the async engine defers it to
        wait()/commit() so durability markers (the ``latest`` file) never
        name a checkpoint that is still being written."""
        raise NotImplementedError

    def load(self, path: str, target: Any = None,
             map_location: Any = None) -> Any:
        raise NotImplementedError

    def commit(self, tag: str) -> bool:
        """Reference semantics: returns True once the tag is durable."""
        return True

    def wait(self) -> None:
        pass


def _charge_checkpoint_goodput(seconds: float) -> None:
    """Feed blocking checkpoint time into the goodput ledger
    (telemetry/perf) — MAIN-thread saves only: a background flush
    (async snapshot worker, watchdog emergency writer) overlaps the
    step loop and charging it would double-count wall time."""
    try:
        if threading.current_thread() is not threading.main_thread():
            return
        from ..telemetry.perf import get_goodput_ledger

        get_goodput_ledger().add("checkpoint", max(seconds, 0.0))
    except Exception as e:  # accounting is optional; the save is not
        from ..utils.logging import debug_once

        debug_once("checkpoint/goodput",
                   f"checkpoint goodput charge failed ({e!r})")


class TorchCheckpointEngine(CheckpointEngine):
    """Synchronous save (reference name kept for config parity; the
    serialization is orbax, not torch)."""

    def save(self, state_tree: Any, path: str,
             commit_fn: Optional[Any] = None,
             entry_timeout_s: Optional[float] = None) -> None:
        t0 = time.perf_counter()
        with orbax_checkpoint().StandardCheckpointer() as saver:
            with save_entry(path, entry_timeout_s):
                saver.save(path, state_tree, force=True)
        # integrity sidecar BEFORE the durability marker: a manifest's
        # existence implies the payload it hashes was fully written.
        # Process 0 only — the tree is shared, the stamp must not race
        if _is_write_coordinator():
            write_sidecar_manifest(path)
        if commit_fn is not None:
            commit_fn()
        _charge_checkpoint_goodput(time.perf_counter() - t0)

    def load(self, path: str, target: Any = None,
             map_location: Any = None) -> Any:
        # integrity-gate the read: a truncated/torn file raises a
        # DESCRIPTIVE CheckpointCorruptionError here instead of orbax
        # deserializing garbage.  Shallow (stat-only) by design — the
        # resilience restore path layers the deep sha256 pass on top
        # (verify strict=True); ordinary checkpoint loads must not pay
        # a full re-read of a multi-GB tree
        verify_sidecar_manifest(path)
        with orbax_checkpoint().StandardCheckpointer() as loader:
            if target is None:
                meta = loader.metadata(path).item_metadata.tree
                target = jax.tree.map(
                    lambda am: jax.ShapeDtypeStruct(tuple(am.shape),
                                                    am.dtype), meta)
            try:
                return loader.restore(path, target)
            except CheckpointCorruptionError:
                raise
            except Exception as e:
                # orbax's failure on a torn tree is opaque — but only
                # claim corruption when the bytes actually fail a DEEP
                # verify; a clean-hashing tree means the failure is
                # structural (wrong target/shape/dtype) and must surface
                # as the programming error it is, not get silently
                # discarded by the resilience tier fallback
                try:
                    verify_sidecar_manifest(path, deep=True)
                except CheckpointCorruptionError as ce:
                    raise CheckpointCorruptionError(
                        f"checkpoint {path!r} failed to restore "
                        f"({type(e).__name__}: {e}); integrity check "
                        f"agrees: {ce}") from e
                raise


#: process-wide in-flight async saves, keyed by absolute path.  A READER
#: must never race a background writer — even one owned by a different
#: engine instance (a fresh engine loading the tag another engine is
#: still flushing).  Relying on GC to __del__-join the writer is a race.
#: Values are WEAK references: an engine abandoned mid-save still joins
#: through its __del__ (pre-existing behavior); the registry must not
#: pin it — and its checkpointer — for the process lifetime.  The owner
#: is whatever has the ``wait()`` that joins the write: an async engine
#: here, a resilience ``SnapshotManager`` with a background flush.
_inflight_lock = threading.Lock()
_inflight: Dict[str, Any] = {}  # path -> weakref to the owner


def register_inflight_save(path: str, owner: Any) -> None:
    """``owner.wait()`` joins the background write of ``path``."""
    with _inflight_lock:
        _inflight[os.path.abspath(path)] = weakref.ref(owner)


def release_inflight_save(path: str, owner: Any) -> None:
    """``owner`` has joined its write of ``path`` (a newer owner's entry
    for the same path stays)."""
    path = os.path.abspath(path)
    with _inflight_lock:
        ref = _inflight.get(path)
        if ref is not None and ref() in (owner, None):
            _inflight.pop(path, None)


def join_inflight_save(path: str) -> None:
    """Join ANY engine's in-flight async save of ``path`` or a tree
    above/below it.  Called by every load path before reading."""
    path = os.path.abspath(path)
    with _inflight_lock:
        engines = set()
        for p in list(_inflight):
            if (p == path or p.startswith(path + os.sep)
                    or path.startswith(p + os.sep)):
                eng = _inflight[p]()
                if eng is None:
                    _inflight.pop(p, None)  # collected; __del__ joined it
                else:
                    engines.add(eng)
    for eng in engines:
        eng.wait()


class DecoupledCheckpointEngine(CheckpointEngine):
    """Async save: returns after the device→host snapshot; storage writes
    happen on orbax's background thread.  ``wait()``/``commit()`` join the
    in-flight save (the engine calls ``wait`` before the next save and on
    teardown, so at most one save is in flight — reference decoupled
    engine's queue-depth-1 behavior)."""

    def __init__(self, config_params: Any = None):
        super().__init__(config_params)
        ocp = orbax_checkpoint()
        self._ckptr = ocp.AsyncCheckpointer(ocp.StandardCheckpointHandler())
        self._save_args = ocp.args.StandardSave
        self._pending: Optional[str] = None
        self._pending_commit: Optional[Any] = None

    def save(self, state_tree: Any, path: str,
             commit_fn: Optional[Any] = None) -> None:
        t0 = time.perf_counter()
        self.wait()
        with save_entry(path):
            self._ckptr.save(path, args=self._save_args(state_tree),
                             force=True)
        # only the BLOCKING part (join previous + device→host snapshot)
        # counts as checkpoint time; the storage write overlaps training
        _charge_checkpoint_goodput(time.perf_counter() - t0)
        self._pending = path
        self._pending_commit = commit_fn
        register_inflight_save(path, self)
        log_dist(f"async checkpoint save started: {path}")

    def load(self, path: str, target: Any = None,
             map_location: Any = None) -> Any:
        self.wait()                # our own in-flight write
        join_inflight_save(path)   # ...and any OTHER engine's
        return TorchCheckpointEngine().load(path, target)

    def commit(self, tag: str) -> bool:
        self.wait()
        return True

    def wait(self) -> None:
        if self._pending is not None:
            self._ckptr.wait_until_finished()
            pending, self._pending = self._pending, None
            release_inflight_save(pending, self)
            try:
                # the background writer just finished: hash what it wrote
                # before the commit marker can name it (process 0 only)
                if _is_write_coordinator():
                    write_sidecar_manifest(pending)
            except OSError as e:
                logger.warning(f"async checkpoint: sidecar manifest for "
                               f"{pending} failed ({e!r})")
            if self._pending_commit is not None:
                commit, self._pending_commit = self._pending_commit, None
                commit()

    def __del__(self):
        try:
            self.wait()
            self._ckptr.close()
        except Exception as e:  # interpreter teardown
            from ..utils.logging import debug_once

            debug_once("checkpoint/del",
                       f"async checkpointer close in __del__ failed "
                       f"({e!r}); a background save may be truncated "
                       f"(the manifest gate will refuse it on load)")


def make_checkpoint_engine(config) -> CheckpointEngine:
    """Select the backend from ``checkpoint.checkpoint_engine`` config
    (``{"type": "sync"|"async"}``; reference selects decoupled/nebula the
    same way)."""
    ce = getattr(config.checkpoint, "checkpoint_engine", None) or {}
    kind = str(ce.get("type", "sync")).lower()
    if kind in ("async", "decoupled"):
        return DecoupledCheckpointEngine(ce)
    return TorchCheckpointEngine(ce)
