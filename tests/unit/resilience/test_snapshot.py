"""Snapshot tiers: round-trip determinism, checksum gating, tier
fallback, and the checkpoint-engine sidecar the gating rides on."""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.resilience import (choose_resume_snapshot,
                                      list_snapshots, verify_snapshot)
from deepspeed_tpu.runtime.checkpoint_engine import (
    SIDECAR_MANIFEST, CheckpointCorruptionError, TorchCheckpointEngine,
    verify_sidecar_manifest, write_sidecar_manifest)


def test_tier0_roundtrip_is_exact(tiny_engine_factory):
    """Rollback from a tier-0 snapshot restores params, optimizer
    state, step counters, and scheduler exactly: replaying the same
    batches yields the same losses."""
    engine, batches = tiny_engine_factory("t0", resilience={
        "snapshot_interval": 1})
    first = [float(engine.train_step(b)["loss"]) for b in batches[:3]]
    snap = engine.snapshots.latest()
    assert snap is not None and snap.global_steps == 3
    # keep training past the snapshot, then roll back
    for b in batches[3:6]:
        engine.train_step(b)
    assert engine.global_steps == 6
    engine.snapshots.restore(snap)
    assert engine.global_steps == 3
    replay = [float(engine.train_step(b)["loss"]) for b in batches[3:6]]
    engine.snapshots.restore(snap)
    replay2 = [float(engine.train_step(b)["loss"]) for b in batches[3:6]]
    assert replay == replay2  # bit-identical replay from the same state


def test_tier1_flush_commit_and_checksum_gate(tiny_engine_factory):
    engine, batches = tiny_engine_factory("t1")
    for b in batches[:4]:
        engine.train_step(b)
    engine.snapshots.wait()
    snaps = list_snapshots(engine.snapshots.snapshot_dir)
    assert [s["step"] for s in snaps] == [4, 2]  # newest first
    ok, detail = verify_snapshot(snaps[0]["path"])
    assert ok, detail
    # corrupt the newest flush: the gate must reject it DESCRIPTIVELY
    # and the chooser must fall back to the older valid snapshot
    from deepspeed_tpu.resilience import corrupt_newest_snapshot

    victim = corrupt_newest_snapshot(engine.snapshots.snapshot_dir)
    assert victim is not None
    ok, detail = verify_snapshot(snaps[0]["path"])
    assert not ok and "sha256" in detail
    chosen = choose_resume_snapshot(engine.snapshots.snapshot_dir)
    assert chosen == snaps[1]["path"]


def test_uncommitted_flush_is_invisible(tmp_path, tiny_engine_factory):
    """A snapshot dir without the commit marker (flush died mid-write)
    never lists and never restores."""
    engine, batches = tiny_engine_factory("t2")
    for b in batches[:2]:
        engine.train_step(b)
    engine.snapshots.wait()
    snaps = list_snapshots(engine.snapshots.snapshot_dir)
    assert [s["step"] for s in snaps] == [2, 0]  # interval snap + baseline
    for entry in snaps:
        os.remove(os.path.join(entry["path"], "snapshot.json"))
    assert list_snapshots(engine.snapshots.snapshot_dir) == []
    assert choose_resume_snapshot(engine.snapshots.snapshot_dir) is None


def test_async_flush_commits_on_background_thread(tiny_engine_factory):
    """flush_engine=async: the step path only dispatches; the
    background worker serializes, hashes, commits, prunes — and the
    artifacts it leaves are byte-for-byte verifiable."""
    engine, batches = tiny_engine_factory(
        "async", resilience={"snapshot_interval": 1,
                             "flush_engine": "async"})
    for b in batches[:3]:
        engine.train_step(b)
    engine.snapshots.wait()
    snaps = list_snapshots(engine.snapshots.snapshot_dir)
    assert [s["step"] for s in snaps] == [3, 2]  # keep=2 default
    for entry in snaps:
        ok, detail = verify_snapshot(entry["path"])
        assert ok, detail
    # and the checksum-gated restore path accepts the async artifact
    engine2, _ = tiny_engine_factory("async2")
    engine2.snapshots.load_from_disk(snaps[0]["path"])
    assert engine2.global_steps == 3


def test_retention_keeps_newest(tiny_engine_factory):
    engine, batches = tiny_engine_factory(
        "t3", resilience={"snapshot_interval": 1, "keep_snapshots": 2})
    for b in batches[:5]:
        engine.train_step(b)
    engine.snapshots.wait()
    steps = [s["step"] for s in
             list_snapshots(engine.snapshots.snapshot_dir)]
    assert steps == [5, 4]


def test_disk_resume_restores_meta(tiny_engine_factory):
    """load_from_disk rebuilds engine state AND bookkeeping (steps,
    scheduler, registered data-sampler cursor) from the manifest."""
    engine, batches = tiny_engine_factory("t4")
    cursor = {"epoch": 0}
    engine.snapshots.register_meta(
        "data_sampler", lambda: dict(cursor),
        restore=lambda p: cursor.update(p))
    cursor["epoch"] = 3
    for b in batches[:4]:
        engine.train_step(b)
    engine.snapshots.wait()
    path = choose_resume_snapshot(engine.snapshots.snapshot_dir)
    cursor["epoch"] = 99  # diverge, then restore
    engine2, _ = tiny_engine_factory("t4b")
    engine2.snapshots.snapshot_dir = engine.snapshots.snapshot_dir
    engine2.snapshots.register_meta(
        "data_sampler", lambda: dict(cursor),
        restore=lambda p: cursor.update(p))
    snap = engine2.snapshots.load_from_disk(path)
    assert snap.global_steps == 4 and engine2.global_steps == 4
    assert cursor["epoch"] == 3
    w1 = np.asarray(engine.snapshots.latest().state.params["w"])
    w2 = np.asarray(engine2.state.params["w"])
    np.testing.assert_array_equal(w1, w2)


def test_flush_clears_an_uncommitted_dir_of_its_step_only(
        tiny_engine_factory):
    """What a killed attempt leaves of step N (a tree with no commit
    marker) goes before step N is written again; a COMMITTED snapshot
    of another step is not touched."""
    engine, batches = tiny_engine_factory("u")
    for b in batches[:2]:
        engine.train_step(b)
    snap_dir = engine.snapshots.snapshot_dir
    assert [s["step"] for s in list_snapshots(snap_dir)] == [2, 0]
    torn = os.path.join(snap_dir, "snap-00000004")
    os.makedirs(os.path.join(torn, "state.orbax-checkpoint-tmp", "d"))
    with open(os.path.join(torn, "leftover"), "w") as fh:
        fh.write("half a write")
    kept = os.path.join(snap_dir, "snap-00000002", "snapshot.json")
    before = os.stat(kept).st_mtime_ns
    for b in batches[2:4]:
        engine.train_step(b)  # flushes step 4 over the torn dir
    assert not os.path.exists(os.path.join(torn, "leftover"))
    ok, detail = verify_snapshot(torn)
    assert ok, detail
    assert os.stat(kept).st_mtime_ns == before


def test_new_manager_joins_the_previous_attempts_flush(
        tiny_engine_factory, monkeypatch):
    """The restart fence: an attempt abandoned mid-flush (async) still
    owns its snapshot dir; the next attempt's manager on that dir is
    not built until that flush has committed, so it resumes from it."""
    import threading

    from deepspeed_tpu.resilience import snapshot as snapmod

    engine, batches = tiny_engine_factory(
        "f1", resilience={"flush_engine": "async"})
    release = threading.Event()
    real = snapmod.SnapshotManager._flush_sync

    def slow(self, *a, **kw):
        assert release.wait(timeout=30)
        return real(self, *a, **kw)

    monkeypatch.setattr(snapmod.SnapshotManager, "_flush_sync", slow)
    engine.snapshots.take()  # dispatched; held before it writes
    assert list_snapshots(engine.snapshots.snapshot_dir) == []
    threading.Timer(0.3, release.set).start()
    engine2, _ = tiny_engine_factory(
        "f1", resilience={"flush_engine": "async"})  # the same dir
    assert engine2.snapshots.snapshot_dir == engine.snapshots.snapshot_dir
    assert release.is_set(), "the new manager did not wait"
    assert engine2.resilience.resume_if_restarted(force=True) is not None


@pytest.mark.parametrize("checkpoint", [None, "sync", "async"],
                         ids=["flushes-only", "sync-checkpoint",
                              "async-checkpoint"])
def test_concurrent_saves_in_one_process_all_commit(tiny_engine_factory,
                                                    tmp_path, checkpoint):
    """ROADMAP D0: orbax keys a save's signals on a process-global
    operation id, so two threads saving at once corrupt or hang each
    other.  Five managers (the background flusher, an emergency flush
    and every in-process host of a gang are such threads) flush the
    same steps at once, with or without ``save_checkpoint`` on the
    training thread beside them: every flush commits a snapshot that
    verifies and every checkpoint loads back."""
    import sys
    import threading

    from deepspeed_tpu.runtime.checkpoint_engine import (
        DecoupledCheckpointEngine, TorchCheckpointEngine)

    engines = [tiny_engine_factory(f"c{i}")[0] for i in range(5)]
    snaps = [e.snapshots.take() for e in engines]  # step-0, flushed
    trainer = engines[0]
    trainer._ckpt_engine = (DecoupledCheckpointEngine()
                            if checkpoint == "async"
                            else TorchCheckpointEngine())
    tags = [f"t{i}" for i in range(4)] if checkpoint else []
    errors = []

    def flush(mgr, snap):
        try:
            for emergency in (False, True, False):
                mgr._flush_sync(snap, emergency)
        except Exception as e:
            errors.append(e)

    threads = [threading.Thread(target=flush, args=(e.snapshots, s),
                                daemon=True)
               for e, s in zip(engines, snaps)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for tag in tags:
            trainer.save_checkpoint(str(tmp_path / "ckpt"), tag=tag)
        trainer._ckpt_engine.wait()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads), "a flush hung"
    assert not errors, errors
    for e in engines:
        listed = list_snapshots(e.snapshots.snapshot_dir)
        assert [(s["step"], s["emergency"]) for s in listed] == \
            [(0, False), (0, True)]
        for entry in listed:
            ok, detail = verify_snapshot(entry["path"])
            assert ok, detail
    w = np.asarray(trainer.state.params["w"])
    for tag in tags:
        path, _ = trainer.load_checkpoint(str(tmp_path / "ckpt"), tag=tag)
        assert path is not None
        np.testing.assert_array_equal(
            np.asarray(trainer.state.params["w"]), w)


@pytest.mark.parametrize("released", [True, False],
                         ids=["entry-released-in-time", "entry-never-freed"])
def test_emergency_flush_behind_a_held_save_entry(tiny_engine_factory,
                                                  released):
    """The watchdog's emergency flush waits for another thread's entry
    into a save, but only as long as the watchdog waits for a device to
    answer: a holder that never lets go (a device→host copy on a hung
    device) costs it that bound, the flush still commits a snapshot that
    verifies, and the unserialized write is counted."""
    import threading

    from deepspeed_tpu.runtime import checkpoint_engine as ce
    from deepspeed_tpu.telemetry import get_telemetry

    engine, batches = tiny_engine_factory(
        "e", telemetry={"watchdog": {
            "enabled": True, "hang_timeout_s": 600.0,
            "device_probe_timeout_s": 30.0 if released else 0.3}})
    for b in batches[:2]:
        engine.train_step(b)
    assert ce._save_entry_lock.acquire(timeout=5)
    timer = threading.Timer(0.2, ce._save_entry_lock.release)
    try:
        if released:
            timer.start()
        engine.watchdog._last_progress -= 100_000.0  # the trip edge
        assert engine.watchdog.check() is True
    finally:
        engine.watchdog.stop()
        if not released:
            ce._save_entry_lock.release()
        timer.cancel()
    assert not ce._save_entry_lock.locked()
    path = os.path.join(engine.snapshots.snapshot_dir,
                        "snap-00000002-emergency")
    ok, detail = verify_snapshot(path)
    assert ok, detail
    counters = get_telemetry().registry.snapshot()["counters"]
    unserialized = counters.get("checkpoint/unserialized_saves_total",
                                {"value": 0})["value"]
    assert unserialized == (0 if released else 1)


# ---------------------------------------------------------------------------
# checkpoint-engine sidecar (ISSUE 4 satellite)
# ---------------------------------------------------------------------------

def test_sidecar_written_on_save_and_verified_on_load(tmp_path):
    eng = TorchCheckpointEngine()
    tree = {"a": jnp.arange(16, dtype=jnp.float32),
            "b": jnp.ones((4, 4), jnp.float32)}
    path = str(tmp_path / "ckpt")
    committed = []
    eng.save(tree, path, commit_fn=lambda: committed.append(True))
    assert committed == [True]
    assert os.path.exists(os.path.join(path, SIDECAR_MANIFEST))
    restored = eng.load(path)
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.arange(16, dtype=np.float32))


def test_truncated_file_raises_descriptive_error(tmp_path):
    eng = TorchCheckpointEngine()
    tree = {"a": jnp.arange(1024, dtype=jnp.float32)}
    path = str(tmp_path / "ckpt")
    eng.save(tree, path)
    # truncate the biggest payload file (not the sidecar)
    victims = []
    for root, _dirs, files in os.walk(path):
        for f in files:
            if f != SIDECAR_MANIFEST:
                p = os.path.join(root, f)
                victims.append((os.path.getsize(p), p))
    _, victim = max(victims)
    with open(victim, "r+b") as fh:
        fh.truncate(max(os.path.getsize(victim) // 2, 1))
    with pytest.raises(CheckpointCorruptionError) as ei:
        eng.load(path)
    msg = str(ei.value)
    assert os.path.relpath(victim, path) in msg
    assert "truncated" in msg


def test_missing_sidecar_strict_vs_legacy(tmp_path):
    d = tmp_path / "legacy"
    d.mkdir()
    (d / "data.bin").write_bytes(b"x" * 64)
    # legacy (non-strict): tolerated; strict (resilience): rejected
    assert verify_sidecar_manifest(str(d)) is True
    with pytest.raises(CheckpointCorruptionError, match="sidecar"):
        verify_sidecar_manifest(str(d), strict=True)
    write_sidecar_manifest(str(d))
    assert verify_sidecar_manifest(str(d), strict=True) is True
