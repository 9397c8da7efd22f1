"""Serving-worker fleet launcher (ISSUE 14 tentpole b).

Spawns ``python -m deepspeed_tpu.serving worker`` replica processes —
the serving plane's process-per-replica backends — and waits for each
one's readiness line (``DS_SERVING_WORKER id=... endpoint=...``), the
same parse-one-line contract the standalone rendezvous store uses.
The front door, ``serving bench --network``, and the chaos shard all
launch fleets through here; chaos tests then ``kill -9`` members by
``pid`` and watch the router drain them.

A chip belongs to one process: the first worker to touch JAX on a TPU
host holds the chip, and a second one fails or hangs.  So a fleet of
real engines takes ONE worker per chip, each told which chip is its own
through ``env``; only ``synthetic`` (host-only) workers are pinned to the
CPU, so that they never claim a chip.  The launching process stays off
JAX.
"""

from __future__ import annotations

import dataclasses
import os
import signal
import subprocess
import sys
import time
from typing import Dict, List, Optional

from ..utils.logging import log_dist, warn_once


@dataclasses.dataclass
class WorkerProc:
    """One launched replica worker process."""

    id: str
    role: str
    endpoint: str
    pid: int
    proc: subprocess.Popen

    def kill9(self) -> None:
        """The chaos primitive: SIGKILL, no goodbye."""
        os.kill(self.pid, signal.SIGKILL)


def _worker_cmd(worker_id: str, role: str, engine: str,
                store: Optional[str], port: int,
                extra_args: Optional[List[str]]) -> List[str]:
    cmd = [sys.executable, "-m", "deepspeed_tpu.serving", "worker",
           "--id", worker_id, "--role", role, "--engine", engine,
           "--port", str(port)]
    if store:
        cmd += ["--store", store]
    if extra_args:
        cmd += list(extra_args)
    return cmd


def _worker_env(engine: str, env: Optional[Dict[str, str]]
                ) -> Dict[str, str]:
    """The child's environment: the caller's, with ``env`` on top.  A
    worker with a real engine inherits the platform as it is — defaulting
    it to the CPU would quietly serve a chip host's traffic from the CPU."""
    full_env = dict(os.environ)
    if engine == "synthetic":
        full_env.setdefault("JAX_PLATFORMS", "cpu")
    full_env.update(env or {})
    return full_env


def spawn_serving_worker(worker_id: str, role: str = "mixed",
                         engine: str = "synthetic",
                         store: Optional[str] = None, port: int = 0,
                         env: Optional[Dict[str, str]] = None,
                         extra_args: Optional[List[str]] = None,
                         ready_timeout_s: float = 120.0) -> WorkerProc:
    """Start one worker process and block until its readiness line."""
    full_env = _worker_env(engine, env)
    proc = subprocess.Popen(
        _worker_cmd(worker_id, role, engine, store, port, extra_args),
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True, env=full_env)
    endpoint = _await_ready(proc, worker_id, ready_timeout_s)
    log_dist(f"launched serving worker {worker_id} ({role}) pid "
             f"{proc.pid} at {endpoint}")
    return WorkerProc(id=worker_id, role=role, endpoint=endpoint,
                      pid=proc.pid, proc=proc)


def _await_ready(proc: subprocess.Popen, worker_id: str,
                 timeout_s: float) -> str:
    """Wait (bounded) for the worker's readiness line.

    Reads the RAW pipe fd with ``select`` + ``os.read`` and splits
    lines itself: a worker that wedges before printing (stuck import,
    dead store) produces no bytes and no exit, so a bare ``readline``
    would hang the launcher past any deadline — and mixing ``select``
    with the buffered text wrapper deadlocks the other way (an earlier
    ``readline`` slurps the readiness line into Python's buffer,
    leaving the OS pipe empty for ``select`` to block on forever)."""
    import select

    fd = proc.stdout.fileno()
    deadline = time.monotonic() + timeout_s
    buf = ""
    while True:
        while "\n" in buf:
            line, buf = buf.split("\n", 1)
            if line.startswith("DS_SERVING_WORKER"):
                for field in line.split():
                    if field.startswith("endpoint="):
                        return field[len("endpoint="):].strip()
                raise RuntimeError(
                    f"serving worker {worker_id} readiness line "
                    f"carries no endpoint: {line!r}")
        left = deadline - time.monotonic()
        if left <= 0:
            break
        ready, _, _ = select.select([fd], [], [], left)
        if not ready:
            break
        chunk = os.read(fd, 4096)
        if not chunk:
            rc = proc.poll()
            raise RuntimeError(
                f"serving worker {worker_id} exited (rc={rc}) before "
                f"its readiness line")
        buf += chunk.decode(errors="replace")
    proc.kill()
    raise TimeoutError(
        f"serving worker {worker_id} not ready within {timeout_s}s")


def launch_worker_fleet(n: int, prefill: int = 0,
                        engine: str = "synthetic",
                        store: Optional[str] = None,
                        env: Optional[Dict[str, str]] = None,
                        extra_args: Optional[List[str]] = None,
                        ready_timeout_s: float = 120.0
                        ) -> List[WorkerProc]:
    """``n`` serving workers (the first ``prefill`` of them dedicated
    prefill replicas, the rest mixed), spawned concurrently, each
    awaited to readiness.  Partial failures tear the fleet down."""
    specs = [(f"serving-p{i}" if i < prefill else
              f"serving-r{i - prefill}",
              "prefill" if i < prefill else "mixed")
             for i in range(int(n))]
    full_env = _worker_env(engine, env)
    procs = [subprocess.Popen(
        _worker_cmd(wid, role, engine, store, 0, extra_args),
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True, env=full_env) for wid, role in specs]
    fleet: List[WorkerProc] = []
    try:
        for proc, (wid, role) in zip(procs, specs):
            endpoint = _await_ready(proc, wid, ready_timeout_s)
            fleet.append(WorkerProc(id=wid, role=role, endpoint=endpoint,
                                    pid=proc.pid, proc=proc))
    except Exception:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
        raise
    log_dist(f"serving fleet up: {len(fleet)} worker processes "
             f"({prefill} prefill)")
    return fleet


def shutdown_fleet(fleet: List[WorkerProc],
                   timeout_s: float = 10.0) -> None:
    """SIGTERM the fleet, escalate to SIGKILL past the deadline."""
    for w in fleet:
        if w.proc.poll() is None:
            try:
                w.proc.terminate()
            except OSError as e:
                warn_once("launcher/fleet-term",
                          f"terminate failed ({e!r})")
    deadline = time.monotonic() + timeout_s
    for w in fleet:
        left = max(0.1, deadline - time.monotonic())
        try:
            w.proc.wait(timeout=left)
        except subprocess.TimeoutExpired:
            w.proc.kill()
            w.proc.wait(timeout=5.0)
        if w.proc.stdout is not None:
            w.proc.stdout.close()
