"""ISSUE 10 E2E chaos acceptance: a 4-host in-process gang SHRINKS to 3
when a node_leave fault fires (survivors reshard-resume, loss sequence
matches an uninterrupted run on the 3-host shape) and GROWS to 5 when a
node_join fault launches a fresh host (the joiner bootstraps mid-run
state from a peer replica).

Choreography note: an in-process worker fn cannot be preempted, so each
attempt-0 worker GATES at the chaos step until the membership round
moves — modeling exactly what a real gang does (the collective with a
departed/about-to-join peer never completes, the membership change
tears the step down)."""

import os
import sys
import threading
import time
import traceback

import pytest

from deepspeed_tpu.elasticity.elastic_agent import (DSElasticAgent,
                                                    WorkerSpec,
                                                    _RestartSignal)
from deepspeed_tpu.elasticity.rendezvous import (ElasticRendezvous,
                                                 RendezvousClient,
                                                 RendezvousServer)
from deepspeed_tpu.telemetry import get_telemetry, parse_prometheus_text

TOTAL, CHAOS_AT = 6, 3
#: ONE deadline for a whole gang test: a passing gang is ~10-20 s, so a
#: gang that cannot finish fails here and says which host is stuck where
GANG_DEADLINE_S = 60.0


@pytest.fixture(autouse=True)
def _patched_dist(monkeypatch):
    import jax

    monkeypatch.setattr(jax.distributed, "initialize", lambda **kw: None)
    monkeypatch.setattr(jax.distributed, "shutdown", lambda: None)
    for k in ("COORDINATOR_ADDRESS", "NUM_PROCESSES", "PROCESS_ID",
              "DS_ELASTIC_JOINED_RUNNING"):
        monkeypatch.setenv(k, "")
    yield


def _reference_losses(tiny_engine_factory):
    """The uninterrupted run every post-resume sequence must match."""
    engine, batches = tiny_engine_factory("ref")
    out = {}
    while engine.global_steps < TOTAL:
        m = engine.train_step(batches[engine.global_steps])
        out[engine.global_steps] = float(m["loss"])
    return out


class Gang:
    """One in-process chaos gang: N agent threads over one store."""

    def __init__(self, tiny_engine_factory, srv, min_nodes, max_nodes,
                 faults_for=None, extra_resilience=None, on_engine=None,
                 gate_attempt0=True):
        self.factory = tiny_engine_factory
        self.srv = srv
        self.min_nodes, self.max_nodes = min_nodes, max_nodes
        self.faults_for = faults_for or {}
        self.extra_resilience = extra_resilience or {}
        self.on_engine = on_engine
        self.gate_attempt0 = gate_attempt0
        self.build_lock = threading.Lock()
        self.agents, self.results = {}, {}
        self.losses, self.worlds = {}, {}
        self.threads = {}
        #: node -> the exception that ended its agent thread
        self.errors = {}
        #: every wait in the gang (worker gates, rendezvous, join_all)
        #: runs against this one clock
        self.deadline = time.monotonic() + GANG_DEADLINE_S
        self.snap_dirs = {}
        #: grow tests set this to the joiner's node id: restarted
        #: incumbents then hold their first post-reseal step until the
        #: joiner has trained one — otherwise on a loaded box the
        #: incumbents can sprint to TOTAL and flush snap-6 replicas
        #: before the joiner's engine even builds, leaving it to
        #: bootstrap finished state with nothing left to train
        self.join_barrier = None

    def _snap_committed(self, node, step):
        """True once ``node``'s snap-<step> carries its commit marker
        on disk — the flush is async, so a peer can be past the step
        while the snapshot is still mid-write."""
        from deepspeed_tpu.resilience.snapshot import SNAPSHOT_MANIFEST

        d = self.snap_dirs.get(node)
        return d is not None and os.path.exists(
            os.path.join(d, f"snap-{step:08d}", SNAPSHOT_MANIFEST))

    def _worker(self, node):
        def worker(restart_count, ckpt_dir):
            agent = self.agents[node]
            with self.build_lock:
                # the JOINED env is per-process in production
                # (subprocess mode); in this shared-process sim it must
                # not leak between engine builds
                os.environ.pop("DS_ELASTIC_JOINED_RUNNING", None)
                res = {"faults": (self.faults_for.get(node, [])
                                  if restart_count == 0 else [])}
                res.update(self.extra_resilience)
                engine, batches = self.factory(node, resilience=res)
            engine.snapshots.attach_rendezvous(agent.rdzv)
            self.snap_dirs[node] = engine.snapshots.snapshot_dir
            if self.on_engine is not None:
                self.on_engine(node, restart_count, engine)
            self.worlds.setdefault(node, []).append(
                (restart_count, int(os.environ.get("NUM_PROCESSES") or 0)))
            if restart_count > 0 or agent.rdzv.joined_running:
                path = engine.resilience.resume_if_restarted(force=True)
                assert path is not None, \
                    f"{node} restart found no snapshot in any tier"
            if (restart_count > 0 and self.join_barrier
                    and node != self.join_barrier):
                while (time.monotonic() < self.deadline
                       and not self.losses.get(self.join_barrier)):
                    time.sleep(0.02)
            while engine.global_steps < TOTAL:
                if agent.rdzv.current_round() != agent._round:
                    raise _RestartSignal("gang changed mid-run")
                if (self.gate_attempt0 and restart_count == 0
                        and not agent.rdzv.joined_running
                        and engine.global_steps == CHAOS_AT):
                    # the chaos gate: block like the real collective
                    # would until the membership round moves
                    while (agent.rdzv.current_round() == agent._round
                           and time.monotonic() < self.deadline):
                        time.sleep(0.02)
                    raise _RestartSignal("peer set changed at the gate")
                if (restart_count == 0 and self.faults_for.get(node)
                        and engine.global_steps == CHAOS_AT - 1):
                    # the chaos step must not fire while a peer is still
                    # short of its pre-chaos snapshot (step CHAOS_AT-1):
                    # under full-suite load a slow survivor would be
                    # torn down before snap-2 exists and replay from
                    # step 0, which is a scheduling artifact — not the
                    # resume behavior these tests assert.  The fault
                    # fires at apply(global_steps + 1) — the ENTRY of
                    # the train_step numbered CHAOS_AT — so the wait
                    # must sit at CHAOS_AT-1 (at == CHAOS_AT the fault
                    # node is already gone).  The flush is ASYNC, so
                    # passing the step is not enough — wait for each
                    # peer's COMMITTED snap-2 marker on disk
                    while time.monotonic() < self.deadline and not all(
                            any(s >= CHAOS_AT - 1 for _rc, s, _l
                                in self.losses.get(p, []))
                            and self._snap_committed(p, CHAOS_AT - 1)
                            for p in self.agents if p != node):
                        time.sleep(0.02)
                m = engine.train_step(batches[engine.global_steps])
                self.losses.setdefault(node, []).append(
                    (restart_count, engine.global_steps,
                     float(m["loss"])))
            return "done"
        return worker

    def _run_agent(self, node):
        rdzv = ElasticRendezvous(
            RendezvousClient(self.srv.endpoint), node,
            min_nodes=self.min_nodes, max_nodes=self.max_nodes,
            settle_s=0.3, timeout_s=GANG_DEADLINE_S)
        agent = DSElasticAgent(
            WorkerSpec(fn=self._worker(node), max_restarts=3,
                       monitor_interval=0.05, heartbeat_ttl=30.0,
                       restart_backoff_s=0.05, restart_backoff_max_s=0.1),
            rdzv=rdzv, node_id=node)
        self.agents[node] = agent
        try:
            self.results[node] = agent.run()
        except Exception as e:  # reported by join_all, not lost
            self.errors[node] = e

    def start(self, node):
        t = threading.Thread(target=self._run_agent, args=(node,),
                             daemon=True)
        self.threads[node] = t
        t.start()
        return t

    def join_all(self):
        # one deadline for the gang, not one per thread; a host that
        # died or is still running when it passes is named, with the
        # exception that ended it or the frame it is stuck in
        for t in list(self.threads.values()):
            t.join(timeout=max(self.deadline - time.monotonic(), 0.0))
        frames = sys._current_frames()
        stuck = {n: "".join(traceback.format_stack(frames[t.ident])[-6:])
                 for n, t in self.threads.items()
                 if t.is_alive() and t.ident in frames}
        died = {n: "".join(traceback.format_exception(e)[-8:])
                for n, e in self.errors.items()}
        assert not stuck and not died, (
            f"gang did not finish inside {GANG_DEADLINE_S:.0f} s"
            + "".join(f"\n-- {n} died: {tb}" for n, tb in died.items())
            + "".join(f"\n-- {n} stuck at:\n{tb}"
                      for n, tb in stuck.items()))


def test_gang_shrinks_4_to_3_and_resumes(tiny_engine_factory):
    """ISSUE 10 acceptance (shrink): a 4-host gang loses host-d to a
    node_leave fault at step 3; the survivors reseal at world 3 and
    resume from their step-2 snapshots; the post-resume loss sequence
    matches an uninterrupted run on the 3-host shape."""
    ref = _reference_losses(tiny_engine_factory)
    srv = RendezvousServer()
    try:
        gang = Gang(tiny_engine_factory, srv, min_nodes=3, max_nodes=5,
                    faults_for={"host-d": [f"node_leave@{CHAOS_AT}"]})
        for n in ("host-a", "host-b", "host-c", "host-d"):
            gang.start(n)
        gang.join_all()

        survivors = ["host-a", "host-b", "host-c"]
        assert all(gang.results[n] == "done" for n in survivors)
        # the leaver exited its supervision loop without a failure
        assert gang.agents["host-d"].failure_count == 0
        d_steps = [s for _rc, s, _l in gang.losses["host-d"]]
        assert max(d_steps) < CHAOS_AT  # left AT step 3, never ran it

        for n in survivors:
            # the final attempt ran at the SHRUNK world
            assert gang.worlds[n][-1][1] == 3, gang.worlds[n]
            resumed = [(s, l) for rc, s, l in gang.losses[n] if rc > 0]
            steps = [s for s, _ in resumed]
            # resumed from the step-2 snapshot: replays 3..6; nothing
            # before the snapshot refed
            assert steps[0] == CHAOS_AT and steps[-1] == TOTAL, steps
            for s, l in resumed:
                assert l == ref[s], f"{n} step {s} diverged after resume"

        parsed = parse_prometheus_text(get_telemetry().prometheus_text())
        assert parsed["elastic_node_leaves_total"] == 1.0
        assert parsed["resilience_reshapes_total"] >= 3.0
        assert parsed["resilience_reshapes_shrink_total"] >= 3.0
        assert parsed["resilience_resumes_total"] >= 3.0

        from deepspeed_tpu.telemetry import get_flight_recorder, load_bundle

        m = load_bundle(
            get_flight_recorder().dump("post-shrink"))["manifest"]
        shr = [a for a in m["annotations"] if a["kind"] == "reshape"
               and a.get("direction") == "shrink"]
        assert shr and shr[-1]["origin"]["world_size"] == 4
        assert shr[-1]["target"]["world_size"] == 3
    finally:
        srv.shutdown()


def test_gang_grows_4_to_5_with_bootstrap_joiner(tiny_engine_factory):
    """ISSUE 10 acceptance (grow): a node_join fault on host-a launches
    host-e mid-run; the gang reseals at world 5; the joiner (fresh id,
    NO local history) bootstraps a peer's tier-2 replica and joins the
    loss sequence of an uninterrupted run; incumbents resume from their
    own snapshots."""
    ref = _reference_losses(tiny_engine_factory)
    srv = RendezvousServer()
    try:
        gang = Gang(
            tiny_engine_factory, srv, min_nodes=4, max_nodes=5,
            faults_for={"host-a": [f"node_join@{CHAOS_AT}:delay_s=0"]},
            extra_resilience={"buddy_tier": True})

        def on_engine(node, restart_count, engine):
            if node == "host-a" and restart_count == 0:
                engine.fault_injector.on_node_join(
                    lambda _delay: gang.start("host-e"))

        gang.on_engine = on_engine
        gang.join_barrier = "host-e"
        incumbents = ["host-a", "host-b", "host-c", "host-d"]
        for n in incumbents:
            gang.start(n)
        # host-e's thread is started by the fault callback
        while "host-e" not in gang.threads \
                and time.monotonic() < gang.deadline:
            time.sleep(0.05)
        assert "host-e" in gang.threads, "node_join never launched host-e"
        gang.join_all()

        assert all(gang.results[n] == "done"
                   for n in incumbents + ["host-e"])
        assert gang.agents["host-e"].rdzv.joined_running is True
        for n in incumbents + ["host-e"]:
            assert gang.worlds[n][-1][1] == 5, (n, gang.worlds[n])

        # the joiner never trained pre-join steps: it bootstrapped a
        # replica and continued the clean sequence to TOTAL
        e_losses = gang.losses["host-e"]
        assert e_losses, "host-e never trained"
        e_steps = [s for _rc, s, _l in e_losses]
        assert e_steps[-1] == TOTAL
        for _rc, s, l in e_losses:
            assert l == ref[s], f"host-e step {s} diverged after bootstrap"

        # incumbents' post-reshape sequences also match the clean run
        for n in incumbents:
            resumed = [(s, l) for rc, s, l in gang.losses[n] if rc > 0]
            assert resumed and resumed[-1][0] == TOTAL
            for s, l in resumed:
                assert l == ref[s], f"{n} step {s} diverged after reshape"

        parsed = parse_prometheus_text(get_telemetry().prometheus_text())
        assert parsed["resilience_reshapes_grow_total"] >= 4.0
        assert parsed["resilience_replica_bootstraps_total"] >= 1.0
        assert parsed["resilience_resumes_total"] >= 5.0
    finally:
        srv.shutdown()
