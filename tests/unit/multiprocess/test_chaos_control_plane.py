"""ISSUE 11 acceptance: control-plane chaos with REAL processes.

A 3-node gang of actual OS processes (agents supervising subprocess
workers) trains with per-step snapshots and the peer-to-peer buddy
tier.  Mid-training the rendezvous store is kill -9'd **by a worker's
own fault injector** (``kill_store``) — training continues in degraded
mode — then respawned (``restart_store``) and re-seeded from the
survivors' write-journals.  A worker node is then SIGKILLed; the
replacement (fresh node id) adopts its tier-2 replica fetched
peer-to-peer from the buddy holder, and every post-resume loss matches
an uninterrupted single-process run.  ``partition_node`` and
``sigstop_hang`` fire on another node along the way — real-process
chaos, not thread simulation.
"""

import json
import os
import pathlib
import signal
import socket
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

pytestmark = [pytest.mark.slow, pytest.mark.chaos,
              pytest.mark.timeout(600)]

_HERE = pathlib.Path(__file__).resolve().parent
_REPO = str(_HERE.parents[2])


def _free_port() -> int:
    s = socket.socket()
    try:
        s.bind(("", 0))
        return s.getsockname()[1]
    finally:
        s.close()


def _port_answers(port: float, timeout=0.3) -> bool:
    try:
        with socket.create_connection(("127.0.0.1", int(port)),
                                      timeout=timeout):
            return True
    except OSError:
        return False


def _spawn_store(port: int) -> subprocess.Popen:
    proc = subprocess.Popen(
        [sys.executable, "-m", "deepspeed_tpu.elasticity.store",
         "--host", "127.0.0.1", "--port", str(port)],
        env={**os.environ, "PYTHONPATH":
             _REPO + os.pathsep + os.environ.get("PYTHONPATH", "")},
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        start_new_session=True)
    deadline = time.time() + 30
    while time.time() < deadline:
        if _port_answers(port):
            return proc
        time.sleep(0.1)
    raise AssertionError("store never came up")


def _kill_stray_stores(port: int) -> None:
    """SIGKILL any store process bound to ``port`` that the
    restart_store fault spawned detached (scan /proc — no psutil in
    the image)."""
    needle = f"deepspeed_tpu.elasticity.store"
    for pid_dir in os.listdir("/proc"):
        if not pid_dir.isdigit():
            continue
        try:
            with open(f"/proc/{pid_dir}/cmdline", "rb") as fh:
                cmd = fh.read().decode(errors="replace")
        except OSError:
            continue
        if needle in cmd and str(port) in cmd:
            try:
                os.kill(int(pid_dir), signal.SIGKILL)
            except OSError:
                pass


def _read_losses(out_dir, node):
    """step -> loss for one node (torn tail lines skipped; duplicate
    steps — a replayed post-resume step — must agree, asserted by the
    oracle comparison)."""
    path = out_dir / f"{node}.losses.jsonl"
    entries = {}
    if not path.exists():
        return entries
    for line in path.read_text().splitlines():
        try:
            rec = json.loads(line)
        except ValueError:
            continue  # torn tail from a SIGTERM mid-write
        entries[int(rec["step"])] = float(rec["loss"])
    return entries


def _oracle_losses(steps: int):
    """The uninterrupted run: same engine, same batch stream, one
    process, no resilience — the ground truth every post-resume loss
    must match."""
    code = f"""
import os, sys, json
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS","") + \
    " --xla_force_host_platform_device_count=1"
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {_REPO!r}); sys.path.insert(0, {str(_HERE)!r})
import tempfile
from chaos_common import batch_for_step, build_engine
engine = build_engine(tempfile.mkdtemp(), resilience=False)
out = {{}}
for _ in range({steps}):
    m = engine.train_step(batch_for_step(engine.global_steps))
    out[int(engine.global_steps)] = float(m["loss"])
print("LOSSES=" + json.dumps(out))
"""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("DS_RDZV_ENDPOINT", None)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("LOSSES=")]
    return {int(k): v for k, v in
            json.loads(line[0][len("LOSSES="):]).items()}


def test_store_death_restart_and_p2p_adoption(tmp_path):
    from deepspeed_tpu.elasticity.rendezvous import RendezvousClient

    port = _free_port()
    endpoint = f"127.0.0.1:{port}"
    worker_py = str(_HERE / "worker_chaos_train.py")

    agent_code = textwrap.dedent(f"""
        import os, sys
        sys.path.insert(0, {_REPO!r})
        from deepspeed_tpu.elasticity.elastic_agent import (DSElasticAgent,
                                                            WorkerSpec)
        spec = WorkerSpec(cmd=[sys.executable, os.environ["T_WORKER"]],
                          max_restarts=6, monitor_interval=0.2,
                          heartbeat_ttl=20.0)
        DSElasticAgent(spec).run()
    """)

    logs = []
    agents = {}

    def spawn_agent(node_id, store_proc=None, faults=""):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env.update({
            "DS_RDZV_ENDPOINT": endpoint,
            "DS_ELASTIC_NODE_ID": node_id,
            "DS_ELASTIC_MIN_NODES": "3",
            "DS_ELASTIC_MAX_NODES": "8",
            "T_WORKER": worker_py,
            "T_REPO": _REPO,
            "T_OUT": str(tmp_path),
            "T_STEP_SLEEP": "0.3",
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": _REPO + os.pathsep + os.environ.get(
                "PYTHONPATH", ""),
        })
        if faults:
            env["DS_FAULTS"] = faults
        if store_proc is not None:
            env["DS_STORE_PID"] = str(store_proc.pid)
        log = open(tmp_path / f"agent_{node_id}.log", "w")
        logs.append(log)
        p = subprocess.Popen([sys.executable, "-c", agent_code], env=env,
                             stdout=log, stderr=subprocess.STDOUT,
                             start_new_session=True)
        agents[node_id] = p
        return p

    def _logs():
        out = []
        for n in agents:
            p = tmp_path / f"agent_{n}.log"
            if p.exists():
                out.append(f"===== {n} =====\n" + p.read_text()[-3000:])
        return "\n".join(out)

    def wait_for(cond, timeout, what):
        deadline = time.time() + timeout
        while time.time() < deadline:
            try:
                if cond():
                    return
            except (OSError, ConnectionError, ValueError, KeyError):
                pass  # store mid-churn — keep polling
            time.sleep(0.25)
        raise AssertionError(f"timed out waiting for: {what}\n" + _logs())

    store = _spawn_store(port)
    client = None
    try:
        # n0 drives the store chaos through the REAL fault harness:
        # SIGKILL at its step 8, respawn (detached store process) at
        # its step 10.  n1 takes a 2s client-side partition and a 1.5s
        # SIGSTOP along the way.  n2 is the node we later kill -9.
        spawn_agent("n0", store_proc=store,
                    faults="kill_store@8;restart_store@10:delay_s=0.5")
        spawn_agent("n1",
                    faults="partition_node@7:seconds=2;"
                           "sigstop_hang@9:seconds=1.5")
        spawn_agent("n2")

        gang = ("n0", "n1", "n2")
        wait_for(lambda: all(len(_read_losses(tmp_path, n)) >= 3
                             for n in gang),
                 timeout=180, what="all 3 nodes trained >= 3 steps")
        # the P2P tier must be fully placed before any chaos: every
        # node's index metadata names 2 holders (owner + ring buddy)
        client = RendezvousClient(endpoint, retries=1, backoff_s=0.01)
        wait_for(lambda: all(
            len((client.get(f"resil/pub/{n}") or {}).get("holders", []))
            >= 2 for n in gang),
            timeout=60, what="2 holders per replica in the index")
        pre_kill_round = int(client.get("rdzv/round") or 0)
        sealed = client.get(f"rdzv/round/{pre_kill_round}/sealed")
        assert sealed and sorted(sealed[0]) == list(gang), sealed
        meta_n0 = client.get("resil/pub/n0")  # placement map, pre-kill

        # --- phase 1: the store is kill -9'd by n0's fault ----------------
        assert store.wait(timeout=120) is not None  # SIGKILLed by n0
        marks = {n: max(_read_losses(tmp_path, n), default=0)
                 for n in gang}
        time.sleep(3.0)  # a store-down training window
        for n in gang:
            grown = max(_read_losses(tmp_path, n), default=0)
            assert grown > marks[n], \
                f"{n} stopped training during the store outage " \
                f"(step {marks[n]} -> {grown})\n" + _logs()
        # acceptance: tier-2 stays RESTORABLE with the store down —
        # ask a holder endpoint the index named before the kill for its
        # NEWEST held copy of n0 (per-step replication prunes old tags)
        # and pull it through the full verify gate
        from deepspeed_tpu.resilience import fetch_replica, verify_snapshot
        from deepspeed_tpu.resilience.replica_server import _rpc

        pulled = None
        for holder in meta_n0["holders"]:
            try:
                idx = _rpc(holder["endpoint"],
                           [{"op": "index"}])[0].get("v") or []
                tags = sorted(e["tag"] for e in idx
                              if e.get("owner") == "n0")
                if not tags:
                    continue
                pulled = fetch_replica(
                    holder["endpoint"], "n0", tags[-1],
                    str(tmp_path / "storeless"))
                break
            except (OSError, ConnectionError):
                continue
        assert pulled is not None, \
            "no holder served n0's replica with the store down\n" + _logs()
        assert verify_snapshot(pulled)[0]

        # --- phase 2: restart_store respawns it; journals re-seed ---------
        wait_for(lambda: _port_answers(port), timeout=120,
                 what="restart_store respawned the store")
        client.close()  # dial the NEW store process
        wait_for(lambda: int(client.get("rdzv/round") or 0)
                 >= pre_kill_round,
                 timeout=60, what="round counter re-seeded from journals")
        r = int(client.get("rdzv/round") or 0)
        resealed = client.get(f"rdzv/round/{r}/sealed")
        assert resealed and sorted(resealed[0]) == list(gang), \
            f"sealed ring not re-seeded: {resealed}\n" + _logs()
        wait_for(lambda: all(
            isinstance(client.get(f"resil/pub/{n}"), dict) for n in gang),
            timeout=60, what="replica index re-seeded from journals")

        # --- phase 2b: ISSUE 13 — the merged view knows what happened -----
        # every node publishes its registry through the store; each
        # one's degraded window (store-outage counters) appears in the
        # merged export under ITS OWN node label, next to its live step
        # counter — no shared registry, no bundle collection
        from deepspeed_tpu.telemetry.metrics import parse_prometheus_text
        from deepspeed_tpu.telemetry.rollup import collect_rollup

        def _merged():
            return parse_prometheus_text(
                collect_rollup(client, list(gang)).prometheus_text())

        def _outage_windows_visible():
            # an outage is counted when it starts, its seconds when the
            # client reconnects, and both reach the store a beat later:
            # wait for the CLOSED window (nothing on this process's way
            # here takes the seconds that would hide the gap)
            parsed = _merged()
            return all(
                parsed.get(f'train_steps_total{{node="{n}"}}', 0) > 0
                and parsed.get(
                    f'elasticity_store_outages_total{{node="{n}"}}', 0)
                >= 1 and parsed.get(
                    f'elasticity_store_degraded_seconds_total'
                    f'{{node="{n}"}}', 0) > 0 for n in gang)

        wait_for(_outage_windows_visible, timeout=90,
                 what="rollup shows every node's step counter AND its "
                      "store-outage degraded window")
        merged = _merged()
        for n in gang:
            assert merged.get(
                f'elasticity_store_degraded_seconds_total{{node="{n}"}}',
                0) > 0, (n, merged)
        # gang aggregate under the reserved label sums the per-node lanes
        assert merged['train_steps_total{node="_cluster"}'] == sum(
            merged[f'train_steps_total{{node="{n}"}}'] for n in gang)

        # the live operator view renders every node, bundle-free, exit 0
        top = subprocess.run(
            [sys.executable, "-m", "deepspeed_tpu.telemetry", "top",
             "--once", "--endpoint", endpoint],
            env={**os.environ, "PYTHONPATH":
                 _REPO + os.pathsep + os.environ.get("PYTHONPATH", "")},
            capture_output=True, text=True, timeout=120)
        assert top.returncode == 0, top.stdout + top.stderr
        for n in gang:
            assert n in top.stdout, top.stdout

        # first collect: every lane clock-aligned (also gives n2 — about
        # to be killed — a published bundle the later merged trace uses)
        from deepspeed_tpu.telemetry.aggregator import (
            collect_cluster_archive)

        archive1 = collect_cluster_archive(
            client, list(gang), out_dir=str(tmp_path / "arch1"),
            timeout_s=120)
        with open(os.path.join(archive1, "cluster_trace.json")) as fh:
            ct1 = json.load(fh)
        hosts1 = ct1["metadata"]["hosts"]
        assert set(hosts1) == set(gang), hosts1
        assert all(h["aligned"] for h in hosts1.values()), hosts1

        # --- phase 3: kill a worker node; the replacement adopts ----------
        wait_for(lambda: len(
            (client.get("resil/pub/n2") or {}).get("holders", [])) >= 2,
            timeout=60, what="n2's replica re-placed on 2 holders")
        n2_steps = max(_read_losses(tmp_path, "n2"))
        os.killpg(os.getpgid(agents["n2"].pid), signal.SIGKILL)
        spawn_agent("n3")  # fresh id: joins the sealed round -> reseal
        wait_for(lambda: len(_read_losses(tmp_path, "n3")) >= 3,
                 timeout=180, what="replacement n3 trained >= 3 steps")
        n3_losses = _read_losses(tmp_path, "n3")
        first = min(n3_losses)
        # adoption, not a cold start: n3 resumed from n2's replica (n2
        # had trained past step 3 before dying; a fresh start would
        # log step 1)
        assert first > 3, \
            f"n3 started at step {first} — no adoption\n" + _logs()
        assert first <= n2_steps + 1, (first, n2_steps)
        # the adopted replica was re-keyed under n3's id
        wait_for(lambda: isinstance(client.get("resil/pub/n3"), dict),
                 timeout=60, what="adopted replica re-keyed under n3")

        # --- phase 3b: ISSUE 13 — the kill is legible in the merged view --
        # the killed worker's heartbeat goes stale while its last
        # publications persist: `top` renders it SILENT next to the
        # LIVE survivors and the replacement
        wait_for(lambda: client.now()
                 - float(client.get("rdzv/hb/n2") or 0) > 5.0,
                 timeout=60, what="n2's heartbeat went stale")
        top2 = subprocess.run(
            [sys.executable, "-m", "deepspeed_tpu.telemetry", "top",
             "--once", "--endpoint", endpoint,
             "--peers", "n0,n1,n2,n3", "--silent-after", "5"],
            env={**os.environ, "PYTHONPATH":
                 _REPO + os.pathsep + os.environ.get("PYTHONPATH", "")},
            capture_output=True, text=True, timeout=120)
        assert top2.returncode == 0, top2.stdout + top2.stderr
        rows = {ln.split()[0]: ln for ln in top2.stdout.splitlines()
                if ln.split() and ln.split()[0] in
                ("n0", "n1", "n2", "n3")}
        assert set(rows) == {"n0", "n1", "n2", "n3"}, top2.stdout
        assert "SILENT" in rows["n2"], top2.stdout
        assert "LIVE" in rows["n3"], top2.stdout

        # second collect, while survivors + replacement are live: the
        # merged trace holds FOUR clock-aligned lanes — n2's from its
        # last (pre-kill) publication — and the lanes are mutually
        # ordered on the store clock: every n3 span happened after n2's
        # lane ended (n3 was spawned after the kill), which the raw
        # per-process timestamps (every tracer starts near zero) could
        # never show.  Tolerance: one heartbeat period.
        # timeout bounds how long we wait for the DEAD n2's fresh dump
        # (never coming — its last publication is the fallback)
        archive2 = collect_cluster_archive(
            client, ["n0", "n1", "n2", "n3"],
            out_dir=str(tmp_path / "arch2"), timeout_s=30)
        with open(os.path.join(archive2, "cluster_trace.json")) as fh:
            ct2 = json.load(fh)
        hosts2 = ct2["metadata"]["hosts"]
        assert set(hosts2) == {"n0", "n1", "n2", "n3"}, hosts2
        assert all(h["aligned"] for h in hosts2.values()), hosts2

        def lane(node):
            pid = hosts2[node]["pid"]
            return [e for e in ct2["traceEvents"]
                    if e.get("ph") == "X" and e.get("pid") == pid]

        assert all(lane(n) for n in ("n0", "n1", "n2", "n3")), hosts2
        hb_period_us = 2.0e6  # heartbeat/monitor cadence tolerance
        n2_end = max(e["ts"] + e.get("dur", 0.0) for e in lane("n2"))
        n3_start = min(e["ts"] for e in lane("n3"))
        assert n3_start > n2_end - hb_period_us, (n3_start, n2_end)
        assert n3_start > min(e["ts"] for e in lane("n2")), \
            "alignment lost: n3's lane overlaps n2's private-clock origin"

        # --- phase 4: wind down; every loss matches the oracle ------------
        (tmp_path / "stop").touch()
        for n in ("n0", "n1", "n3"):
            assert agents[n].wait(timeout=120) == 0, \
                f"agent {n} rc={agents[n].returncode}\n" + _logs()

        # acceptance: NO snapshot bytes ever transited the store —
        # index metadata + endpoints only (the storeless restorability
        # half was proven during the outage window above)
        resil_keys = client.keys("resil/")
        assert resil_keys and not [k for k in resil_keys
                                   if k.startswith("resil/chunk/")], \
            resil_keys

        # the post-resume loss sequences — survivors AND the adopted
        # replacement — match an uninterrupted single-process run
        all_steps = {}
        for n in ("n0", "n1", "n2", "n3"):
            all_steps.update(_read_losses(tmp_path, n))
        oracle = _oracle_losses(max(all_steps))
        for n in ("n0", "n1", "n2", "n3"):
            for step, loss in sorted(_read_losses(tmp_path, n).items()):
                np.testing.assert_allclose(
                    loss, oracle[step], rtol=1e-5,
                    err_msg=f"{n} step {step} diverged from the "
                            f"uninterrupted run")
        # and the replacement really carried n2's lineage forward
        final = json.load(open(tmp_path / "n3.final.json"))
        assert final["resumed_step"] >= 4
    finally:
        for p in agents.values():
            try:
                os.killpg(os.getpgid(p.pid), signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
        if store.poll() is None:
            store.kill()
        _kill_stray_stores(port)
        for log in logs:
            log.close()
