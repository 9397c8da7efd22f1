"""ISSUE 20 acceptance: ONE capture command against a 3-process CPU
gang produces (a) a single clock-aligned Perfetto timeline whose device
lanes come from every rank and (b) a calibration report of measured vs
modeled per-op deltas.  Real OS processes on the real production path
(``dst.initialize`` + publisher daemon + engine step hook) — tier-1 by
design, so this file is deliberately NOT slow-marked."""

import json
import os
import pathlib
import signal
import subprocess
import sys
import time

import pytest

pytestmark = pytest.mark.timeout(280)

_HERE = pathlib.Path(__file__).resolve().parent
_REPO = str(_HERE.parents[2])

NODES = ("pn0", "pn1", "pn2")


def _logs(tmp_path):
    out = []
    for n in NODES:
        p = tmp_path / f"worker_{n}.log"
        if p.exists():
            out.append(f"===== {n} =====\n" + p.read_text()[-3000:])
    return "\n".join(out)


def test_one_command_profiles_every_rank(tmp_path):
    from deepspeed_tpu.elasticity.rendezvous import (RendezvousClient,
                                                     RendezvousServer)
    from deepspeed_tpu.telemetry.profiler import post_capture_command
    from deepspeed_tpu.telemetry.profiler.fleet import (
        assemble_fleet_profile)

    srv = RendezvousServer()
    worker_py = str(_HERE / "worker_profiler_gang.py")
    procs, logs = [], []
    try:
        for node in NODES:
            env = dict(os.environ)
            env.pop("XLA_FLAGS", None)
            env.update({
                "DS_RDZV_ENDPOINT": srv.endpoint,
                "DS_ELASTIC_NODE_ID": node,
                "DS_CALIBRATION_PATH": str(tmp_path / f"cal_{node}.json"),
                "T_REPO": _REPO,
                "T_OUT": str(tmp_path),
                "T_DEADLINE_S": "150",
                "JAX_PLATFORMS": "cpu",
                "PYTHONPATH": _REPO + os.pathsep + env.get(
                    "PYTHONPATH", ""),
            })
            log = open(tmp_path / f"worker_{node}.log", "w")
            logs.append(log)
            procs.append(subprocess.Popen(
                [sys.executable, worker_py], env=env, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True))

        client = RendezvousClient(srv.endpoint)
        # ONE command for the whole fleet — every worker's publisher
        # beat adopts it and max-merges the shared window start
        req = post_capture_command(client, steps=3, lead=2)
        archive = str(tmp_path / "archive")
        summary = assemble_fleet_profile(client, req, archive,
                                         nodes=list(NODES),
                                         timeout_s=180.0)
        assert summary["missing"] == [], \
            f"ranks never published: {summary['missing']}\n" + \
            _logs(tmp_path)
        assert sorted(summary["nodes"]) == sorted(NODES)

        # (a) ONE clock-aligned timeline, device lanes from EVERY rank
        with open(summary["cluster_trace"]) as fh:
            trace = json.load(fh)
        hosts = trace["metadata"]["hosts"]
        for node in NODES:
            lane = hosts[f"{node} (device)"]
            assert lane["device"] is True
            assert lane["events"] > 0, f"{node} published an empty lane"
            assert lane["aligned"] is True, \
                f"{node} lane not on the store clock: {lane}"
        spans = [e for e in trace["traceEvents"]
                 if e.get("cat") == "device"]
        assert len({e["pid"] for e in spans}) == len(NODES)

        # (b) measured vs modeled per-op deltas for every rank
        with open(summary["calibration_report"]) as fh:
            rep = json.load(fh)
        assert sorted(rep["nodes"]) == sorted(NODES)
        for node in NODES:
            nrep = rep["nodes"][node]
            assert nrep["measured_step_ms"] > 0
            # the engine's AOT-compile roofline entry grounds the join
            assert nrep["modeled_step_ms"] is not None
            assert nrep["step_ratio"] is not None
            assert nrep["ops"], f"{node} census empty"
            assert all("measured_ms" in r and "modeled_ms" in r
                       for r in nrep["ops"])
        assert rep["factors"], "no per-device-kind EWMA factors persisted"
        (kind, factors), = list(rep["factors"].items())[:1] or [(None, {})]
        assert "step" in factors

        # every worker reports a clean capture + flush on its side too
        deadline = time.time() + 60
        while time.time() < deadline and not all(
                (tmp_path / f"{n}.done.json").exists() for n in NODES):
            time.sleep(0.5)
        for node in NODES:
            done = json.loads((tmp_path / f"{node}.done.json").read_text())
            assert done["published"], f"{node}: {done}\n" + _logs(tmp_path)
            assert done["captures"] >= 1
    finally:
        for p in procs:
            try:
                os.killpg(os.getpgid(p.pid), signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
        for log in logs:
            log.close()
        srv.shutdown()
