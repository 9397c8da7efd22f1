"""Control-plane fault tolerance (ISSUE 11 tentpole a): the store is
killable.  Clients journal their durable writes and re-seed a
restarted (empty) store; exhausted retries flip DEGRADED mode —
buffered heartbeats, counters, and a ``control_plane_degraded`` health
event — instead of crashing the caller's loop."""

import time

import pytest

from deepspeed_tpu.elasticity.rendezvous import (ElasticRendezvous,
                                                 RendezvousClient,
                                                 RendezvousServer,
                                                 StoreUnavailableError,
                                                 control_plane_status,
                                                 partition_all)
from deepspeed_tpu.telemetry import get_telemetry, parse_prometheus_text


@pytest.fixture(autouse=True)
def _forget_clients_of_earlier_tests():
    """``control_plane_status`` is process-wide.  A gang test that failed
    earlier in the run (ROADMAP D0) leaves agent threads with degraded
    clients behind; these tests count only the clients they make."""
    from deepspeed_tpu.elasticity import rendezvous

    with rendezvous._registry_lock:
        rendezvous._all_clients.clear()
        rendezvous._degraded_clients.clear()
    yield


def _client(endpoint):
    # a tight retry budget so outage tests take milliseconds
    return RendezvousClient(endpoint, retries=1, backoff_s=0.001)


def test_server_gen_max_and_keys_ops():
    srv = RendezvousServer()
    try:
        c = _client(srv.endpoint)
        assert c.get("srv/gen")  # stamped at boot
        assert c.max("m", 5) == 5
        assert c.max("m", 3) == 5  # monotonic: never regresses
        assert c.max("m", 9) == 9
        c.set("a/x", 1)
        c.set("b/y", 2)
        assert c.keys("a/") == ["a/x"]
        assert set(c.keys("")) >= {"a/x", "b/y", "m", "srv/gen"}
    finally:
        srv.shutdown()


def test_kill_restart_replays_journal_and_counts():
    """The core failover loop: journaled writes + heartbeats buffer
    through the outage, the restarted (EMPTY) store is re-seeded from
    the client's journal on reconnect, and the outage lands in the
    elasticity/store_* counters."""
    get_telemetry().configure(enabled=True, jsonl=False, prometheus=False)
    srv = RendezvousServer()
    port = srv.port
    c = _client(srv.endpoint)
    c.set("rdzv/left/n0", False, journal=True)
    c.max("rdzv/round", 3, journal=True)
    c.hb("rdzv/hb/n0", journal=True)
    c.set("ephemeral", "not-journaled")
    srv.shutdown()  # kill -9 equivalent: connections severed, state gone

    with pytest.raises(StoreUnavailableError):
        c.get("rdzv/round")
    assert c.degraded
    st = control_plane_status()
    assert st["degraded"] and st["clients"] == 1
    # journaled writes BUFFER during the outage instead of raising
    c.set("resil/pub/n0", {"bundle": "snap-1"}, journal=True)
    c.hb("rdzv/hb/n0", journal=True)
    with pytest.raises(StoreUnavailableError):
        c.set("plain", 1)  # un-journaled writes still fail loudly

    srv2 = RendezvousServer("127.0.0.1", port)  # fresh, EMPTY state
    try:
        # first call reconnects, sees the new generation, replays
        assert c.get("rdzv/round") == 3
        assert c.get("rdzv/left/n0") is False
        assert c.get("resil/pub/n0") == {"bundle": "snap-1"}
        assert c.get("rdzv/hb/n0") is not None  # re-stamped liveness
        assert c.get("ephemeral") is None  # never journaled — gone
        assert not c.degraded and not control_plane_status()["degraded"]
        assert c.reconnects == 1 and c.journal_replays == 1
        assert c.degraded_seconds_total > 0
        parsed = parse_prometheus_text(get_telemetry().prometheus_text())
        assert parsed["elasticity_store_reconnects_total"] >= 1.0
        assert parsed["elasticity_store_outages_total"] >= 1.0
        assert parsed["elasticity_store_degraded_seconds_total"] > 0
        assert parsed["elasticity_store_state_replays_total"] >= 1.0
    finally:
        srv2.shutdown()


def test_rendezvous_round_and_sealed_ring_survive_store_restart():
    """A sealed gang's client re-seeds the round counter AND the frozen
    ring, so surviving monitors do NOT read a restarted store as
    'round moved' and tear their workers down."""
    srv = RendezvousServer()
    port = srv.port
    c = _client(srv.endpoint)
    rdzv = ElasticRendezvous(c, "n0", min_nodes=1, settle_s=0.01,
                             timeout_s=10.0)
    r, rank, world, _coord = rdzv.next_round()
    assert (rank, world) == (0, 1)
    srv.shutdown()
    srv2 = RendezvousServer("127.0.0.1", port)
    try:
        # the monitor's poll: same round, same sealed ring -> no teardown
        assert rdzv.current_round() == r
        assert rdzv.sealed_ring(r) == ["n0"]
        # heartbeat was replayed, so the node isn't stale either
        assert not rdzv.stale_peers(["n0"], ttl_s=5.0)
    finally:
        srv2.shutdown()


def test_heartbeat_buffers_through_outage_and_resumes():
    """Satellite: the heartbeat path never lets the post-retry error
    escape into the beat thread — it buffers, counts, and resumes on
    reconnect."""
    srv = RendezvousServer()
    port = srv.port
    c = _client(srv.endpoint)
    rdzv = ElasticRendezvous(c, "nb")
    rdzv.heartbeat({"step": 1})
    srv.shutdown()
    rdzv.heartbeat({"step": 2})  # store down: must NOT raise
    assert c.degraded
    srv2 = RendezvousServer("127.0.0.1", port)
    try:
        rdzv.heartbeat({"step": 3})  # resumes beating on reconnect
        assert not c.degraded
        assert c.get("rdzv/hbinfo/nb")["step"] == 3
    finally:
        srv2.shutdown()


def test_partition_all_blackholes_then_heals():
    srv = RendezvousServer()
    try:
        c = _client(srv.endpoint)
        c.set("k", 1)
        assert partition_all(0.2) >= 1
        with pytest.raises(StoreUnavailableError):
            c.get("k")
        assert control_plane_status()["degraded"]
        time.sleep(0.25)
        assert c.get("k") == 1  # healed
        assert not control_plane_status()["degraded"]
    finally:
        srv.shutdown()


def test_control_plane_degraded_health_rule_fires_once_per_streak():
    from deepspeed_tpu.telemetry import HealthMonitor
    from deepspeed_tpu.telemetry.step_record import StepRecord

    srv = RendezvousServer()
    c = _client(srv.endpoint)
    c.get("srv/gen")

    def rec(step):
        return StepRecord(step=step, step_time_ms=10.0,
                          device_fenced=True, samples_per_sec=10.0,
                          tokens_per_sec=100.0, loss=0.1, grad_norm=1.0,
                          lr=1e-3, loss_scale=1.0, overflow=False,
                          skipped_steps=0, comm_bytes=0, comm_ops=0)

    hm = HealthMonitor(min_points=2)
    assert hm.observe(rec(1)) == []  # healthy store: quiet
    srv.shutdown()
    with pytest.raises(StoreUnavailableError):
        c.get("k")
    events = hm.observe(rec(2))
    assert [e.kind for e in events] == ["control_plane_degraded"]
    assert "training continues" in events[0].message
    assert hm.observe(rec(3)) == []  # one event per streak
    srv2 = RendezvousServer("127.0.0.1", srv.port)
    try:
        assert c.get("srv/gen")  # reconnect heals
        assert hm.observe(rec(4)) == []
        srv2.shutdown()
        with pytest.raises(StoreUnavailableError):
            c.get("k")
        # a NEW outage is a NEW streak
        assert [e.kind for e in hm.observe(rec(5))] == \
            ["control_plane_degraded"]
    finally:
        srv2.shutdown()


def test_publisher_tick_degrades_and_counts_when_store_is_down():
    from deepspeed_tpu.telemetry.aggregator import BundlePublisher

    get_telemetry().configure(enabled=True, jsonl=False, prometheus=False)
    srv = RendezvousServer()
    c = _client(srv.endpoint)
    c.get("srv/gen")
    srv.shutdown()
    pub = BundlePublisher("nx")
    assert pub.tick(c) is None  # degrades, never raises
    parsed = parse_prometheus_text(get_telemetry().prometheus_text())
    assert parsed["aggregator_degraded_ticks_total"] >= 1.0


def test_journal_cap_drops_new_entries_with_warning():
    srv = RendezvousServer()
    try:
        c = _client(srv.endpoint)
        c.JOURNAL_CAP = 4
        for i in range(6):
            c.journal_note("set", f"k{i}", i)
        assert c.journal_size() == 4
        c.journal_note("set", "k0", 99)  # existing keys still update
        assert c.journal_size() == 4
    finally:
        srv.shutdown()


def test_same_generation_outage_flushes_buffered_writes_on_heal():
    """Review fix: a partition/flap with the store ALIVE (generation
    unchanged) must still flush journal-buffered one-shot writes on
    reconnect — the replica-server endpoint or a leave flag would
    otherwise never land."""
    srv = RendezvousServer()
    try:
        c = _client(srv.endpoint)
        c.get("srv/gen")  # connected once: generation learned
        c.partition(0.2)
        c.set("resil/srv/nz", "10.0.0.9:1234", journal=True)  # buffered
        with pytest.raises(StoreUnavailableError):
            c.get("resil/srv/nz")
        time.sleep(0.25)
        # heal: SAME store, SAME generation — the buffered write must
        # have replayed before this read
        assert c.get("resil/srv/nz") == "10.0.0.9:1234"
        assert c.journal_replays >= 1
    finally:
        srv.shutdown()


def test_server_conns_registry_stays_bounded():
    """Review fix: the store's live-connection registry must not
    accumulate dead sockets across client reconnect cycles."""
    srv = RendezvousServer()

    def conns():
        with srv._srv._conns_lock:
            return len(srv._srv._conns)

    try:
        for i in range(8):
            c = _client(srv.endpoint)
            c.set("k", 1)
            c.close()
            # each closed connection must leave the registry promptly
            deadline = time.time() + 5
            while conns() > 0 and time.time() < deadline:
                time.sleep(0.02)
            assert conns() == 0, \
                f"iteration {i}: {conns()} dead connection(s) retained"
    finally:
        srv.shutdown()
