"""Multinode runners — pluggable remote-launch backends.

Reference: ``deepspeed/launcher/multinode_runner.py`` [K] —
``PDSHRunner``, ``OpenMPIRunner``, ``SlurmRunner``, ``MPICHRunner``
(SURVEY §2.5 "Launcher"): each turns (resource map, env, user cmd) into
the scheduler-specific launch invocation.

TPU adaptation: the launched unit is one process per HOST (libtpu owns
all local chips), and the exported env is the ``jax.distributed``
coordinator triple (COORDINATOR_ADDRESS / NUM_PROCESSES / PROCESS_ID)
alongside the reference RANK/WORLD_SIZE names.  Runners only BUILD
commands (pure, testable); ``launch`` shells out.
"""

from __future__ import annotations

import os
import shlex
import subprocess
from typing import Dict, List

from ..utils.logging import logger

def rank_env(rank: int, world: int, master_addr: str, master_port: int
             ) -> Dict[str, str]:
    from ..utils import compile_cache

    return {
        "RANK": str(rank), "WORLD_SIZE": str(world), "LOCAL_RANK": "0",
        "MASTER_ADDR": master_addr, "MASTER_PORT": str(master_port),
        "COORDINATOR_ADDRESS": f"{master_addr}:{master_port}",
        "NUM_PROCESSES": str(world), "PROCESS_ID": str(rank),
        # every child compiles into one persistent cache: the launcher's
        # own if it was given one, else the checkout's (the parent stays
        # off JAX, so it exports the variable rather than configuring)
        compile_cache.ENV_VAR: (os.environ.get(compile_cache.ENV_VAR)
                                or compile_cache.DEFAULT_DIR),
    }


class MultiNodeRunner:
    name = "base"

    def __init__(self, resources: Dict[str, int], master_addr: str,
                 master_port: int, workdir: str = None):
        self.resources = dict(resources)
        self.master_addr = master_addr
        self.master_port = master_port
        self.workdir = workdir or os.getcwd()

    @property
    def world(self) -> int:
        return len(self.resources)

    def backend_exists(self) -> bool:
        return True

    def get_cmd(self, user_cmd: List[str]) -> List[List[str]]:
        """→ list of commands to spawn locally (one per remote rank, or a
        single scheduler command that fans out itself)."""
        raise NotImplementedError

    def launch(self, user_cmd: List[str]) -> int:
        procs = [subprocess.Popen(c) for c in self.get_cmd(user_cmd)]
        # wait ALL before reducing — short-circuiting would orphan the
        # still-running remote jobs when an early rank fails
        rcs = [p.wait() for p in procs]
        return next((rc for rc in rcs if rc), 0)


class SSHRunner(MultiNodeRunner):
    """Plain ssh fan-out (the default; reference PDSH role without pdsh)."""

    name = "ssh"

    def __init__(self, *a, ssh_port: int = 22, **kw):
        super().__init__(*a, **kw)
        self.ssh_port = ssh_port

    def _remote(self, rank: int, user_cmd: List[str]) -> str:
        env = rank_env(rank, self.world, self.master_addr, self.master_port)
        exports = " ".join(f"{k}={shlex.quote(v)}" for k, v in env.items())
        return (f"cd {shlex.quote(self.workdir)} && {exports} "
                f"{' '.join(map(shlex.quote, user_cmd))}")

    def get_cmd(self, user_cmd: List[str]) -> List[List[str]]:
        return [["ssh", "-p", str(self.ssh_port), host,
                 self._remote(rank, user_cmd)]
                for rank, host in enumerate(self.resources)]


class PDSHRunner(MultiNodeRunner):
    """pdsh fan-out (reference ``PDSHRunner``): one pdsh invocation; the
    per-rank id comes from pdsh's %n substitution → PROCESS_ID."""

    name = "pdsh"

    def backend_exists(self) -> bool:
        from shutil import which

        return which("pdsh") is not None

    def get_cmd(self, user_cmd: List[str]) -> List[List[str]]:
        hosts = ",".join(self.resources)
        # rank = position in the hostlist; pdsh exports it via %n
        env = rank_env(0, self.world, self.master_addr, self.master_port)
        env.pop("RANK"), env.pop("PROCESS_ID")
        exports = " ".join(f"{k}={shlex.quote(v)}" for k, v in env.items())
        remote = (f"cd {shlex.quote(self.workdir)} && {exports} "
                  f"RANK=%n PROCESS_ID=%n "
                  f"{' '.join(map(shlex.quote, user_cmd))}")
        return [["pdsh", "-R", "ssh", "-w", hosts, remote]]


class OpenMPIRunner(MultiNodeRunner):
    """mpirun fan-out (reference ``OpenMPIRunner``): ranks from OMPI env;
    a tiny shim maps OMPI_COMM_WORLD_RANK → PROCESS_ID at startup."""

    name = "openmpi"

    def backend_exists(self) -> bool:
        from shutil import which

        return which("mpirun") is not None

    def get_cmd(self, user_cmd: List[str]) -> List[List[str]]:
        hosts = ",".join(f"{h}:1" for h in self.resources)
        env = rank_env(0, self.world, self.master_addr, self.master_port)
        flags: List[str] = []
        for k in ("MASTER_ADDR", "MASTER_PORT", "COORDINATOR_ADDRESS",
                  "NUM_PROCESSES", "WORLD_SIZE", "LOCAL_RANK"):
            flags += ["-x", f"{k}={env[k]}"]
        shim = ("import os,sys,runpy;"
                "r=os.environ.get('OMPI_COMM_WORLD_RANK','0');"
                "os.environ['RANK']=r;os.environ['PROCESS_ID']=r;"
                "sys.argv=sys.argv[1:];runpy.run_path(sys.argv[0],"
                "run_name='__main__')")
        return [["mpirun", "-np", str(self.world), "--host", hosts,
                 *flags, user_cmd[0], "-c", shim, *user_cmd[1:]]]


class SlurmRunner(MultiNodeRunner):
    """srun fan-out (reference ``SlurmRunner``): SLURM_PROCID is the rank."""

    name = "slurm"

    def backend_exists(self) -> bool:
        from shutil import which

        return which("srun") is not None

    def get_cmd(self, user_cmd: List[str]) -> List[List[str]]:
        env = rank_env(0, self.world, self.master_addr, self.master_port)
        exports = ",".join(
            f"{k}={env[k]}"
            for k in ("MASTER_ADDR", "MASTER_PORT", "COORDINATOR_ADDRESS",
                      "NUM_PROCESSES", "WORLD_SIZE", "LOCAL_RANK"))
        shim = ("import os,sys,runpy;"
                "r=os.environ.get('SLURM_PROCID','0');"
                "os.environ['RANK']=r;os.environ['PROCESS_ID']=r;"
                "sys.argv=sys.argv[1:];runpy.run_path(sys.argv[0],"
                "run_name='__main__')")
        return [["srun", f"--nodes={self.world}", "--ntasks-per-node=1",
                 f"--export=ALL,{exports}",
                 user_cmd[0], "-c", shim, *user_cmd[1:]]]


class LocalMultiRunner(MultiNodeRunner):
    """N local processes with the coordinator env — the DistributedTest
    analogue for REAL multi-process jax.distributed on one machine (the
    reference tests multi-node semantics exactly this way, SURVEY §4)."""

    name = "local-multi"

    def get_cmd(self, user_cmd: List[str]) -> List[List[str]]:
        # commands carry env inline via `env` so Popen needs no env= plumbing
        cmds = []
        for rank in range(self.world):
            env = rank_env(rank, self.world, self.master_addr,
                           self.master_port)
            pairs = [f"{k}={v}" for k, v in env.items()]
            cmds.append(["env", *pairs, *user_cmd])
        return cmds


RUNNERS = {r.name: r for r in (SSHRunner, PDSHRunner, OpenMPIRunner,
                               SlurmRunner, LocalMultiRunner)}


def get_runner(name: str, resources: Dict[str, int], master_addr: str,
               master_port: int, **kw) -> MultiNodeRunner:
    if name not in RUNNERS:
        raise ValueError(f"unknown launcher {name!r}; have {list(RUNNERS)}")
    runner = RUNNERS[name](resources, master_addr, master_port, **kw)
    if not runner.backend_exists():
        logger.warning(f"launcher backend {name} not found on PATH")
    return runner
