"""Cross-host elastic recovery (VERDICT round-2 missing #6).

Reference behavior being mirrored: torch-elastic rendezvous + agent
(``DSElasticAgent`` [K], SURVEY §5.3) — N node agents coordinate through a
store; a worker failure on ANY node restarts the gang on every node; a
NODE loss (agent killed hard) is detected via heartbeats and the survivors
re-form at the smaller world.

"Multi-node" here = multiple agent PROCESSES on localhost sharing one TCP
store (the same one-box pattern the reference's elastic tests use).
"""

import os
import pathlib
import signal
import subprocess
import sys
import textwrap
import time

import pytest

from deepspeed_tpu.elasticity.rendezvous import (ElasticRendezvous,
                                                 RendezvousClient,
                                                 RendezvousServer)

_REPO = str(pathlib.Path(__file__).resolve().parents[2])


# ---------------------------------------------------------------------------
# store + rounds (in-process, threads)
# ---------------------------------------------------------------------------

def test_store_ops():
    srv = RendezvousServer()
    try:
        c = RendezvousClient(srv.endpoint)
        c.set("k", {"a": 1})
        assert c.get("k") == {"a": 1}
        assert c.add("n", 2) == 2
        assert c.add("n", 3) == 5
        assert c.append("lst", "x") == ["x"]
        assert c.append("lst", "x") == ["x"]  # idempotent
        assert c.append("lst", "y") == ["x", "y"]
        assert c.wait_ge("n", 5, timeout=1.0)
        assert not c.wait_ge("n", 99, timeout=0.2)
    finally:
        srv.shutdown()


def test_rendezvous_assigns_deterministic_ranks():
    srv = RendezvousServer()
    try:
        import threading

        results = {}

        def join(node_id):
            r = ElasticRendezvous(RendezvousClient(srv.endpoint), node_id,
                                  min_nodes=3, settle_s=0.2)
            results[node_id] = r.next_round()

        ts = [threading.Thread(target=join, args=(f"n{i}",))
              for i in range(3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        assert len(results) == 3
        rounds = {v[0] for v in results.values()}
        worlds = {v[2] for v in results.values()}
        coords = {v[3] for v in results.values()}
        assert len(rounds) == 1 and worlds == {3} and len(coords) == 1
        ranks = sorted((nid, v[1]) for nid, v in results.items())
        assert [r for _, r in ranks] == [0, 1, 2]  # sorted-node-id order
    finally:
        srv.shutdown()


def test_heartbeats_are_store_stamped_and_graced():
    """Heartbeat staleness math uses the STORE's clock (op=hb stamps
    server-side), and a peer with no heartbeat yet is graced for a full
    ttl instead of being declared dead on the first check (round-3
    advisor findings)."""
    srv = RendezvousServer()
    try:
        c = RendezvousClient(srv.endpoint)
        r = ElasticRendezvous(c, "me", min_nodes=1)
        # a peer that sealed but hasn't heartbeaten: graced, not stale
        assert r.stale_peers(["late"], ttl_s=0.3) == []
        time.sleep(0.4)
        assert r.stale_peers(["late"], ttl_s=0.3) == ["late"]
        # a fresh server-stamped heartbeat clears it — even if this
        # host's clock were skewed far ahead, the store clock governs
        c.hb("rdzv/hb/late")
        assert r.stale_peers(["late"], ttl_s=0.3) == []
        assert isinstance(c.now(), float)
    finally:
        srv.shutdown()


def test_membership_restarts_do_not_consume_failure_budget():
    """_RestartSignal (scale-up / peer-death teardowns) restarts without
    burning max_restarts; only real failures do (round-3 advisor)."""
    from deepspeed_tpu.elasticity.elastic_agent import (DSElasticAgent,
                                                        WorkerSpec,
                                                        _RestartSignal)
    calls = {"n": 0}

    def worker(restart_count, ckpt_dir):
        calls["n"] += 1
        if calls["n"] <= 5:  # 5 membership churns — more than max_restarts
            raise _RestartSignal("round moved")
        return "ok"

    agent = DSElasticAgent(WorkerSpec(fn=worker, max_restarts=2,
                                      monitor_interval=0.01))
    assert agent.run() == "ok"
    assert agent.failure_count == 0 and agent.restart_count == 5

    # real failures still exhaust the budget
    def always_fail(restart_count, ckpt_dir):
        raise RuntimeError("boom")

    agent2 = DSElasticAgent(WorkerSpec(fn=always_fail, max_restarts=2,
                                       monitor_interval=0.01))
    with pytest.raises(RuntimeError):
        agent2.run()
    assert agent2.failure_count == 3  # 2 retries + the give-up attempt


def test_coordinator_port_skips_bound_ports():
    """Each round publishes a BIND-TESTED coordinator endpoint through the
    store: a hung coordinator from an earlier round still bound on a port
    is skipped, never collided with (round-3 advisor).  The configured
    coordinator_port stays the base of the scan window so firewalled
    deployments keep a predictable range."""
    import socket as _socket

    srv = RendezvousServer()
    hog = _socket.socket(_socket.AF_INET, _socket.SOCK_STREAM)
    try:
        c = RendezvousClient(srv.endpoint)
        r = ElasticRendezvous(c, "solo", min_nodes=1, settle_s=0.05)
        # simulate a hung coordinator occupying the base port
        hog.bind(("", r.coordinator_port))
        hog.listen(1)
        _, _, _, coord0 = r.next_round()
        p0 = int(coord0.rsplit(":", 1)[1])
        assert p0 != r.coordinator_port  # bound port skipped
        assert p0 >= r.coordinator_port  # window stays firewall-friendly
        assert c.get("rdzv/round/0/coord") == coord0  # published via store
        r.bump_round("test")
        _, _, _, coord1 = r.next_round()
        assert c.get("rdzv/round/1/coord") == coord1
    finally:
        hog.close()
        srv.shutdown()


# ---------------------------------------------------------------------------
# multi-agent gang restart (real processes)
# ---------------------------------------------------------------------------

_WORKER = textwrap.dedent("""
    import os, sys, time
    log = os.environ["T_LOG"]
    rank = os.environ.get("PROCESS_ID", "?")
    world = os.environ.get("NUM_PROCESSES", "?")
    restart = os.environ.get("DS_ELASTIC_RESTART_COUNT", "?")
    with open(log, "a") as f:
        f.write(f"start rank={rank} world={world} restart={restart}\\n")
    if rank == "1" and restart == "0":
        time.sleep(0.3)
        sys.exit(1)  # simulated worker crash on node 1, first attempt
    time.sleep(%(run_s)s)
    with open(log, "a") as f:
        f.write(f"done rank={rank} world={world} restart={restart}\\n")
""")

_AGENT = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, %(repo)r)
    from deepspeed_tpu.elasticity.elastic_agent import (DSElasticAgent,
                                                        WorkerSpec)
    spec = WorkerSpec(cmd=[sys.executable, os.environ["T_WORKER"]],
                      max_restarts=4, monitor_interval=0.05,
                      heartbeat_ttl=%(ttl)s)
    DSElasticAgent(spec).run()
""")


def _spawn_agent(tmp_path, endpoint, node_id, worker_py, log,
                 min_nodes, ttl=5.0, run_s=1.0):
    env = dict(os.environ)
    env.update({
        "DS_RDZV_ENDPOINT": endpoint,
        "DS_ELASTIC_NODE_ID": node_id,
        "DS_ELASTIC_MIN_NODES": str(min_nodes),
        "T_WORKER": worker_py,
        "T_LOG": log,
        "JAX_PLATFORMS": "cpu",
    })
    return subprocess.Popen(
        [sys.executable, "-c",
         _AGENT % {"repo": _REPO, "ttl": ttl}], env=env)


@pytest.mark.slow
def test_gang_restart_on_worker_failure(tmp_path):
    """Worker dies on node 1 → BOTH nodes' workers restart and both
    complete at world=2 on the next round."""
    srv = RendezvousServer()
    worker_py = str(tmp_path / "worker.py")
    log = str(tmp_path / "log.txt")
    with open(worker_py, "w") as f:
        # run long enough that node 0's first attempt is still in flight
        # when node 1's crash bumps the round (teardown, not completion)
        f.write(_WORKER % {"run_s": 3.0})
    try:
        agents = [_spawn_agent(tmp_path, srv.endpoint, f"n{i}", worker_py,
                               log, min_nodes=2) for i in range(2)]
        for a in agents:
            assert a.wait(timeout=60) == 0
        lines = open(log).read().splitlines()
        done = [l for l in lines if l.startswith("done")]
        assert len(done) == 2
        # both completions happened in the SECOND attempt at world=2
        assert all("world=2" in l and "restart=1" in l for l in done), lines
        # node 0's first attempt was torn down by the round bump (no done
        # line with restart=0)
        assert not any(l.startswith("done") and "restart=0" in l
                       for l in lines)
    finally:
        for a in agents:
            if a.poll() is None:
                a.kill()
        srv.shutdown()


@pytest.mark.slow
def test_survivor_reforms_after_node_loss(tmp_path):
    """An agent killed HARD (node loss) → the survivor's heartbeat check
    bumps the round and it completes alone at world=1."""
    srv = RendezvousServer()
    worker_py = str(tmp_path / "worker.py")
    log = str(tmp_path / "log.txt")
    # long-running worker so the kill lands mid-attempt; no crash logic
    with open(worker_py, "w") as f:
        f.write(textwrap.dedent("""
            import os, time
            log = os.environ["T_LOG"]
            rank = os.environ.get("PROCESS_ID", "?")
            world = os.environ.get("NUM_PROCESSES", "?")
            restart = os.environ.get("DS_ELASTIC_RESTART_COUNT", "?")
            with open(log, "a") as f:
                f.write(f"start rank={rank} world={world} restart={restart}\\n")
            time.sleep(float(os.environ.get("T_RUN_S", "2.0")))
            with open(log, "a") as f:
                f.write(f"done rank={rank} world={world} restart={restart}\\n")
        """))
    try:
        os.environ["T_RUN_S"] = "4.0"
        a0 = _spawn_agent(tmp_path, srv.endpoint, "n0", worker_py, log,
                          min_nodes=1, ttl=1.0)
        a1 = _spawn_agent(tmp_path, srv.endpoint, "n1", worker_py, log,
                          min_nodes=1, ttl=1.0)
        time.sleep(2.0)  # both mid-attempt at world=2
        a1.send_signal(signal.SIGKILL)  # node loss — no goodbye
        a1.wait(timeout=10)
        assert a0.wait(timeout=60) == 0
        lines = open(log).read().splitlines()
        # the survivor finished a later attempt at world=1
        assert any(l.startswith("done") and "world=1" in l
                   for l in lines), lines
    finally:
        os.environ.pop("T_RUN_S", None)
        for a in (a0, a1):
            if a.poll() is None:
                a.kill()
        srv.shutdown()


@pytest.mark.slow
def test_scale_up_new_node_triggers_reformation(tmp_path):
    """A node joining a RUNNING (sealed) round bumps it: the running agent
    restarts its worker and both complete at world=2 (torch-elastic's
    scale-up semantics)."""
    srv = RendezvousServer()
    worker_py = str(tmp_path / "worker.py")
    log = str(tmp_path / "log.txt")
    with open(worker_py, "w") as f:
        f.write(textwrap.dedent("""
            import os, time
            log = os.environ["T_LOG"]
            rank = os.environ.get("PROCESS_ID", "?")
            world = os.environ.get("NUM_PROCESSES", "?")
            restart = os.environ.get("DS_ELASTIC_RESTART_COUNT", "?")
            with open(log, "a") as f:
                f.write(f"start rank={rank} world={world} restart={restart}\\n")
            time.sleep(2.0)
            with open(log, "a") as f:
                f.write(f"done rank={rank} world={world} restart={restart}\\n")
        """))
    try:
        a0 = _spawn_agent(tmp_path, srv.endpoint, "n0", worker_py, log,
                          min_nodes=1)
        time.sleep(1.0)  # n0's round 0 is sealed and running
        a1 = _spawn_agent(tmp_path, srv.endpoint, "n1", worker_py, log,
                          min_nodes=1)  # same job config; join → bump
        assert a0.wait(timeout=60) == 0
        assert a1.wait(timeout=60) == 0
        lines = open(log).read().splitlines()
        done2 = [l for l in lines if l.startswith("done") and "world=2" in l]
        assert len(done2) == 2, lines
    finally:
        for a in (a0, a1):
            if a.poll() is None:
                a.kill()
        srv.shutdown()


def test_heartbeat_payload_ages_and_straggler_stats():
    """ISSUE 2: heartbeats can carry the watchdog's liveness payload;
    peer_heartbeat_ages feeds debug bundles, and rank 0 folds payloads
    into straggler-skew gauges."""
    from deepspeed_tpu.telemetry import get_telemetry, parse_prometheus_text

    hub = get_telemetry()
    hub.reset()
    hub.configure(enabled=True, jsonl=False, prometheus=False)
    srv = RendezvousServer()
    try:
        c = RendezvousClient(srv.endpoint)
        r = ElasticRendezvous(c, "a", min_nodes=1, settle_s=0.05)
        r.next_round()
        r.heartbeat({"step": 10, "step_time_ewma_ms": 120.0})
        # two peers that joined elsewhere published their own payloads
        c.set("rdzv/hbinfo/b", {"step": 4, "step_time_ewma_ms": 360.0})
        c.set("rdzv/hbinfo/c", {"step": 9, "step_time_ewma_ms": 130.0})

        ages = r.peer_heartbeat_ages(["a", "b"])
        assert ages["a"]["age_s"] is not None and ages["a"]["age_s"] < 60
        assert ages["a"]["info"]["step"] == 10
        assert ages["b"]["age_s"] is None  # b never wrote a heartbeat
        assert ages["b"]["left"] is False

        stats = r.publish_straggler_stats(["a", "b", "c"])
        assert stats["step_skew"] == 6.0            # 10 - 4
        assert stats["ewma_ratio"] == pytest.approx(360.0 / 130.0)
        parsed = parse_prometheus_text(hub.prometheus_text())
        assert parsed["elastic_straggler_step_skew"] == 6.0
        assert parsed["elastic_straggler_ewma_ratio"] == pytest.approx(
            360.0 / 130.0, rel=1e-6)
    finally:
        srv.shutdown()
        hub.reset()


def test_agent_records_stale_peer_counter():
    """Satellite (ISSUE 2): stale-peer detection at the agent level bumps
    a telemetry counter before tearing the attempt down."""
    from deepspeed_tpu.elasticity.elastic_agent import (DSElasticAgent,
                                                        WorkerSpec)
    from deepspeed_tpu.telemetry import get_telemetry

    hub = get_telemetry()
    hub.reset()
    hub.configure(enabled=True, jsonl=False, prometheus=False)
    try:
        agent = DSElasticAgent(WorkerSpec(fn=lambda *a: 0))
        agent._record_stale_peers(["b", "c"])
        counter = hub.registry.counter("elastic/agent_stale_peer_events")
        assert counter.value == 2
    finally:
        hub.reset()


def test_client_retries_transient_errors_with_backoff(monkeypatch):
    """Satellite (ISSUE 3): a transient connect/read failure (store
    restart, ECONNRESET, EINTR) is retried with bounded backoff instead
    of killing the caller — a debug-bundle collector sweep must survive
    one reset.  The retry budget is bounded: a store that is GONE still
    fails, with the last error chained."""
    import socket as socket_mod

    from deepspeed_tpu.elasticity import rendezvous as rdzv_mod

    srv = RendezvousServer()
    try:
        real_connect = socket_mod.create_connection
        fails = {"n": 0}

        def flaky(addr, timeout=None):
            if fails["n"] < 2:
                fails["n"] += 1
                raise ConnectionResetError("transient reset")
            return real_connect(addr, timeout=timeout)

        monkeypatch.setattr(rdzv_mod.socket, "create_connection", flaky)
        c = RendezvousClient(srv.endpoint, retries=3, backoff_s=0.001)
        c.set("k", {"v": 1})          # survived two resets
        assert c.get("k") == {"v": 1}
        assert fails["n"] == 2

        def always_down(addr, timeout=None):
            raise ConnectionResetError("store is gone")

        monkeypatch.setattr(rdzv_mod.socket, "create_connection",
                            always_down)
        c2 = RendezvousClient(srv.endpoint, retries=2, backoff_s=0.001)
        with pytest.raises(ConnectionError, match="after 3 attempts"):
            c2.get("k")
    finally:
        srv.shutdown()
