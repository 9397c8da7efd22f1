"""Router acceptance: prefix affinity, load, replica death + drain."""

import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import KVCacheConfig
from deepspeed_tpu.serving import (FakeClock, Replica, ServingFrontend,
                                   ServingParams, SyntheticEngine,
                                   synthetic_token)


def make_cluster(n=2, slots=4, params=None, probes=None):
    clock = FakeClock()
    cache = KVCacheConfig(num_blocks=256, block_size=16, max_seq_len=512)
    reps = []
    for i in range(n):
        eng = SyntheticEngine(cache, max_batch_slots=slots,
                              prefill_chunk=64, prefill_batch=2,
                              decode_burst=4, clock=clock)
        reps.append(Replica(eng, i, probe=probes[i] if probes else None))
    fe = ServingFrontend(reps, params=params or ServingParams(),
                         clock=clock)
    return fe, reps, clock


def test_least_outstanding_tokens_routing():
    fe, reps, _ = make_cluster(n=2)
    # no shared prefixes anywhere: routing is purely load-based, and
    # requests spread instead of piling on replica 0
    for i in range(4):
        fe.submit([100 + i] * 24, max_new_tokens=32, klass="batch")
        fe.pump()
    assert all(len(r.active) > 0 for r in reps)


def test_prefix_affinity_beats_load():
    fe, reps, _ = make_cluster(n=2)
    rng = np.random.RandomState(3)
    header = rng.randint(2, 29000, size=64).tolist()
    # warm replica: one header-carrying request runs to completion
    h0 = fe.submit(header + [1, 2, 3], max_new_tokens=4)
    fe.run_until_idle()
    warm = fe._replica_by_id(h0.replica_id)
    cold = [r for r in reps if r.id != h0.replica_id][0]
    # load the warm replica so pure least-outstanding would avoid it
    for _ in range(2):
        fe.submit(rng.randint(2, 29000, size=40).tolist(),
                  max_new_tokens=48, klass="background")
        fe.pump()
    # the header-sharing request still routes to the warm replica
    h1 = fe.submit(header + [7, 8, 9], max_new_tokens=4)
    fe.pump()
    assert h1.replica_id == warm.id
    assert warm.scheduler.prefix.hit_tokens > 0
    fe.run_until_idle()
    assert cold.scheduler.prefix.hit_tokens == 0


def test_replica_death_drains_and_work_completes_elsewhere():
    """ISSUE 8 acceptance: a watchdog/probe-latched replica drains —
    the router stops sending to it and its in-flight request finishes
    on the healthy replica with the exact token sequence."""
    alive = {0: True, 1: True}
    fe, reps, _ = make_cluster(
        n=2, probes=[lambda: alive[0], lambda: alive[1]])
    prompt = [11] * 40
    h = fe.submit(prompt, max_new_tokens=24, klass="batch")
    for _ in range(3):
        fe.pump()
    assert h.status == "running"
    victim_id = h.replica_id
    streamed_before = h.delivered
    alive[victim_id] = False          # the liveness probe latches dead

    fe.pump()                         # drain pass
    assert h.status in ("queued", "running")
    fe.run_until_idle()
    assert h.status == "done"
    assert h.replica_id != victim_id  # finished on the healthy replica
    assert h.replays == 1
    # stream spliced exactly: every token once, in order
    assert h.result() == [synthetic_token(prompt, i) for i in range(24)]
    assert h.delivered == 24 and h.delivered >= streamed_before
    # the router never routes to the dead replica again
    assert all(r.id != victim_id
               for r in fe.router.route_candidates([1, 2, 3]))
    assert fe.metrics.counters["requeued_replica_death"] == 1
    # and new submissions land on the healthy one
    h2 = fe.submit([12] * 8, max_new_tokens=4)
    fe.run_until_idle()
    assert h2.replica_id != victim_id


def test_preempted_handle_survives_replica_death():
    """A preempted victim lives in a class QUEUE (not rep.active) while
    pinned to the replica holding its KV pages.  If that replica dies,
    the drain must reset the pin so the victim restarts on a healthy
    replica — it used to retry the dead pin forever, stalling its whole
    class queue."""
    alive = {0: True, 1: True}
    fe, reps, _ = make_cluster(
        n=2, slots=1, probes=[lambda: alive[0], lambda: alive[1]])
    p1, p2 = [21] * 40, [22] * 40
    bg1 = fe.submit(p1, max_new_tokens=24, klass="background")
    fe.pump()
    bg2 = fe.submit(p2, max_new_tokens=24, klass="background")
    fe.pump()
    assert bg1.status == bg2.status == "running"
    inter = fe.submit([23] * 8, max_new_tokens=4, klass="interactive")
    fe.pump()
    assert fe.metrics.counters["preemptions"] == 1
    victim = bg1 if bg1.preempted else bg2
    vprompt = p1 if victim is bg1 else p2
    assert victim.status == "queued" and victim.request is not None
    alive[victim.pinned_replica] = False   # kill the pinning replica

    fe.run_until_idle()
    assert victim.status == "done"
    assert victim.replays == 1
    assert victim.result() == [synthetic_token(vprompt, i)
                               for i in range(24)]
    assert all(h.status == "done" for h in (bg1, bg2, inter))


def test_drain_requeues_in_admission_order():
    """Re-queued in-flight work keeps earliest-admitted-first order
    (the drain used to reverse it)."""
    fe, reps, _ = make_cluster(n=2, slots=4)
    handles = [fe.submit([30 + i] * 24, max_new_tokens=16, klass="batch")
               for i in range(4)]
    for _ in range(2):
        fe.pump()
    dead = next(r.id for r in reps if r.active)
    on_dead = [h for h in handles if h.replica_id == dead]
    assert len(on_dead) >= 2
    reps[dead].mark_dead("test")
    with fe._lock:
        fe._drain_dead()
    requeued = [h for h in fe._queues["batch"] if h in on_dead]
    assert requeued == on_dead  # admission order preserved


def test_device_unresponsive_latch_kills_all_replicas():
    from deepspeed_tpu.telemetry.memory.ledger import (
        clear_device_unresponsive, mark_device_unresponsive)

    fe, reps, _ = make_cluster(n=2)
    h = fe.submit([9] * 8, max_new_tokens=4)
    mark_device_unresponsive("runtime stopped answering (test)")
    try:
        import pytest as _pytest

        with _pytest.raises(Exception, match="no healthy replica"):
            fe.run_until_idle()
        assert all(not r.healthy() for r in reps)
        assert "device unresponsive" in reps[0].dead_reason
    finally:
        clear_device_unresponsive()
    del h


def test_watchdog_trip_drains_replicas():
    from deepspeed_tpu.telemetry import HangWatchdog

    fe, reps, _ = make_cluster(n=2)
    wd = HangWatchdog(hang_timeout_s=1e9)
    fe.attach_watchdog(wd)
    # fire the trip edge through the watchdog's own listener plumbing
    for fn in wd._trip_listeners:
        fn("test trip", None)
    assert all(not r.healthy() for r in reps)
    assert "watchdog trip" in reps[0].dead_reason


def test_watchdog_trip_does_not_need_frontend_lock():
    """The trip fires exactly when a pump thread may be wedged in a
    device call while HOLDING the frontend lock — the listener must
    not acquire it, or the watchdog (and every listener behind it)
    deadlocks."""
    import threading

    fe, reps, _ = make_cluster(n=2)
    acquired, release, done = (threading.Event() for _ in range(3))

    def hold():
        with fe._lock:            # stands in for a wedged pump thread
            acquired.set()
            release.wait(5)

    holder = threading.Thread(target=hold)
    holder.start()
    assert acquired.wait(5)

    def trip():
        fe._on_watchdog_trip("hung step", None)
        done.set()

    tripper = threading.Thread(target=trip)
    tripper.start()
    assert done.wait(2), "trip listener blocked on the frontend lock"
    release.set()
    holder.join()
    tripper.join()
    assert all(not r.healthy() for r in reps)


def test_recorder_dump_completes_while_frontend_lock_wedged(tmp_path):
    """REVIEW regression: HangWatchdog._trip dumps a bundle BEFORE
    firing trip listeners, and dump() evaluates the front-end's
    ``serving`` context provider with no timeout of its own.  With a
    pump thread wedged holding the lock, the provider must degrade
    (bounded wait) so the bundle still gets written and the trip
    listeners behind it still drain the replicas."""
    import json
    import os
    import threading

    from deepspeed_tpu.telemetry import get_flight_recorder

    rec = get_flight_recorder().configure(output_path=str(tmp_path))
    fe, reps, _ = make_cluster(n=2)
    fe._snapshot_lock_timeout_s = 0.05
    acquired, release = threading.Event(), threading.Event()

    def wedged_pump():
        with fe._lock:             # stands in for a wedged pump thread
            acquired.set()
            release.wait(5)

    holder = threading.Thread(target=wedged_pump, daemon=True)
    holder.start()
    assert acquired.wait(5)
    try:
        # replay the watchdog-trip order: dump first, listeners after
        path = rec.dump("watchdog: test hang")
        fe._on_watchdog_trip("test hang", path)
    finally:
        release.set()
        holder.join(5)
    with open(os.path.join(path, "bundle.json")) as fh:
        manifest = json.load(fh)
    serving = manifest["context"]["serving"]
    assert "lock held" in serving["degraded"]
    assert serving["router"]["replicas"]  # best-effort forensics present
    assert all(not r.healthy() for r in reps)


def test_dead_replica_snapshot_names_reason():
    fe, reps, _ = make_cluster(n=2)
    reps[1].mark_dead("operator drain")
    snap = fe.snapshot()
    entry = snap["router"]["replicas"][1]
    assert entry["healthy"] is False
    assert entry["dead_reason"] == "operator drain"


def make_moe_cluster(n=2, num_experts=4):
    clock = FakeClock()
    cache = KVCacheConfig(num_blocks=256, block_size=16, max_seq_len=512)
    reps = []
    for i in range(n):
        eng = SyntheticEngine(cache, max_batch_slots=4, prefill_chunk=64,
                              prefill_batch=2, decode_burst=4, clock=clock,
                              num_experts=num_experts)
        reps.append(Replica(eng, i))
    fe = ServingFrontend(reps, params=ServingParams(), clock=clock)
    return fe, reps, clock


def test_moe_hot_expert_steers_placement():
    """ISSUE 19 acceptance: a replica whose engine reports hot experts
    loses new placements to a balanced one at equal outstanding load."""
    fe, reps, _ = make_moe_cluster(n=2)
    # replica 0 funnels everything to one expert; replica 1 is balanced
    reps[0].engine.expert_counts[:] = [100, 0, 0, 0]
    reps[1].engine.expert_counts[:] = [25, 25, 25, 25]
    assert reps[0].moe_load_imbalance() == pytest.approx(4.0)
    assert reps[1].moe_load_imbalance() == pytest.approx(1.0)
    # no prefix affinity, equal (zero) outstanding: without the MoE
    # signal the tiebreak would prefer replica 0 (lowest id)
    order = [r.id for r in fe.router.route_candidates([9, 9, 9])]
    assert order[0] == 1
    # the placement-score signal is surfaced in the snapshot
    snap = reps[0].snapshot()
    assert snap["moe_load_imbalance"] == pytest.approx(4.0)
    np.testing.assert_allclose(snap["moe_expert_load"], [1.0, 0, 0, 0])


def test_moe_imbalance_weight_zero_disables_signal():
    fe, reps, _ = make_moe_cluster(n=2)
    fe.router.moe_imbalance_weight = 0.0
    reps[0].engine.expert_counts[:] = [100, 0, 0, 0]
    reps[1].engine.expert_counts[:] = [25, 25, 25, 25]
    order = [r.id for r in fe.router.route_candidates([9, 9, 9])]
    assert order[0] == 0  # back to pure load + id tiebreak


def test_synthetic_engine_tracks_expert_counts_during_decode():
    fe, reps, _ = make_moe_cluster(n=1)
    fe.submit([5, 6, 7] * 8, max_new_tokens=8)
    fe.run_until_idle()
    eng = reps[0].engine
    assert eng.expert_counts.sum() > 0
    load = eng.moe_expert_load()
    assert load is not None and np.isclose(load.sum(), 1.0)
    assert eng.moe_load_imbalance() >= 1.0
    # same prompt replayed deterministically hits the same experts
    counts = eng.expert_counts.copy()
    fe.submit([5, 6, 7] * 8, max_new_tokens=8)
    fe.run_until_idle()
    assert (eng.expert_counts - counts).sum() > 0


def test_non_moe_engine_reads_as_balanced():
    fe, reps, _ = make_cluster(n=1)  # num_experts=0
    assert reps[0].moe_load_imbalance() == 0.0
    assert reps[0].engine.moe_expert_load() is None
    assert "moe_load_imbalance" not in reps[0].snapshot()
