"""Elastic agent v2 — cross-host rendezvous + restart supervision.

Reference: ``deepspeed/elasticity/elastic_agent.py:DSElasticAgent`` [K]
(SURVEY §5.3): subclasses torch-elastic's agent — rendezvous store, worker
monitoring, restart on membership change or failure, each restart
re-initializing the process group and resuming from checkpoint.

TPU mapping: the process-group piece is ``jax.distributed.initialize``
driven by coordinator env vars; "resume at a different world size" is the
checkpoint reshard-on-load the runtime already provides (orbax restores
into whatever mesh the restarted world builds).  The agent owns:

* the CROSS-HOST rendezvous (``rendezvous.ElasticRendezvous`` over the
  TCP store — torch-elastic's TCPStore role): each round assigns
  ``(rank, world, coordinator)`` and rank 0's host coordinates
  ``jax.distributed`` for that round;
* supervision: run the worker (a subprocess for real deployments — a
  crash cannot take the agent down — or an in-process fn for embedding),
  heartbeat the store, and watch for (a) local worker failure, (b) a
  round bump by a peer, (c) stale peer heartbeats.  Any of the three
  tears the local worker down and re-rendezvouses — every surviving
  agent converges on the new membership within a heartbeat interval.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import time
from typing import Any, Callable, List, Optional

from ..utils.logging import debug_once, log_dist, logger
from .rendezvous import ElasticRendezvous, RendezvousClient, RendezvousServer


class WorkerSpec:
    """Reference-shaped description of the elastic worker: either a
    callable ``fn(restart_count, checkpoint_dir, *args)`` (in-process) or
    a ``cmd`` argv (subprocess — the production mode)."""

    def __init__(self, fn: Optional[Callable[..., Any]] = None,
                 args: tuple = (), cmd: Optional[List[str]] = None,
                 max_restarts: int = 3, monitor_interval: float = 0.1,
                 heartbeat_ttl: float = 5.0,
                 checkpoint_dir: Optional[str] = None,
                 restart_backoff_s: float = 1.0,
                 restart_backoff_max_s: float = 30.0,
                 scale_up_settle_s: float = 0.0):
        if (fn is None) == (cmd is None):
            raise ValueError("WorkerSpec needs exactly one of fn= or cmd=")
        self.fn = fn
        self.args = args
        self.cmd = list(cmd) if cmd else None
        self.max_restarts = int(max_restarts)
        self.monitor_interval = float(monitor_interval)
        self.heartbeat_ttl = float(heartbeat_ttl)
        self.checkpoint_dir = checkpoint_dir
        #: capped exponential backoff between FAILURE restarts
        #: (membership churn restarts stay prompt): delay =
        #: min(backoff * 2^(failures-1), backoff_max).  A worker dying
        #: instantly on startup (bad ckpt, OOM loop) must not respawn
        #: hot — it would burn the restart budget in milliseconds and
        #: hammer the rendezvous store
        self.restart_backoff_s = float(restart_backoff_s)
        self.restart_backoff_max_s = float(restart_backoff_max_s)
        #: settle window before re-rendezvousing on a JOIN-driven round
        #: bump (every previous peer still heartbeating): a flapping
        #: node that joins/leaves in a tight loop costs the gang at most
        #: one reshape per window instead of thrashing the mesh.
        #: Death-driven bumps (stale peers) stay prompt — capacity is
        #: already lost, waiting only loses more work.
        self.scale_up_settle_s = float(scale_up_settle_s)


class _RestartSignal(Exception):
    """Internal: membership changed / peer died — restart the attempt."""


class DSElasticAgent:
    """Supervise an elastic training worker across hosts.

    Without a rendezvous (``rdzv=None`` and no ``DS_RDZV_ENDPOINT``), this
    degrades to the single-host supervision loop (round-2 behavior).  With
    one, every attempt (re-)joins the current membership round first.
    """

    def __init__(self, spec: WorkerSpec, start_method: str = "inproc",
                 rdzv: Optional[ElasticRendezvous] = None,
                 node_id: Optional[str] = None):
        self.spec = spec
        self.start_method = start_method
        self.restart_count = 0   # total attempts (workers key resume off it)
        self.failure_count = 0   # only FAILURES consume max_restarts
        self.last_result: Any = None
        self.node_id = node_id or os.environ.get(
            "DS_ELASTIC_NODE_ID", f"node-{os.getpid()}")
        if rdzv is None and os.environ.get("DS_RDZV_ENDPOINT"):
            rdzv = ElasticRendezvous(
                RendezvousClient(os.environ["DS_RDZV_ENDPOINT"]),
                node_id=self.node_id,
                min_nodes=int(os.environ.get("DS_ELASTIC_MIN_NODES", "1")),
                max_nodes=int(os.environ.get("DS_ELASTIC_MAX_NODES", "64")))
        self.rdzv = rdzv
        self._round = -1
        self._rank = 0
        self._peers: List[str] = []
        #: world size of the last sealed round — a reseal at a different
        #: size is a RESHAPE, counted and annotated (origin vs target)
        self._world = 0
        #: injectable for tests (fake-clock backoff assertions)
        self._sleep: Callable[[float], None] = time.sleep
        # prefetch the resilience fault vocabulary OFF the supervision
        # path: the failure branches import it to map NODE_LEAVE_EXIT_
        # CODE, and a cold import there (the resilience package's tree,
        # tenths of a second; it holds no orbax.checkpoint, which only
        # what saves loads) would gate the crash->round-bump latency
        # every peer's teardown clock depends on
        import threading

        threading.Thread(
            target=self._prefetch_fault_vocabulary, daemon=True,
            name="ds-agent-import-prefetch").start()

    @staticmethod
    def _prefetch_fault_vocabulary() -> None:
        try:
            from ..resilience.faults import NODE_LEAVE_EXIT_CODE  # noqa: F401
        except Exception as e:
            # the failure branches re-import and surface any real error
            debug_once("elastic/prefetch",
                       f"resilience prefetch failed ({e!r})")

    def _hb_payload(self):
        """The local watchdog's liveness summary (step index, step-time
        EWMA, progress age), folded into every rendezvous heartbeat so
        rank 0 can publish straggler-skew gauges; None when no watchdog
        is installed (payload-less heartbeats, round-2 behavior).  The
        collective ledger's ``coll_seq``/``coll_hash`` ride along
        whenever the ledger is on — with or without a watchdog — so
        rank 0 can flag a collective desync live."""
        from ..telemetry import (cap_heartbeat_payload,
                                 get_collective_ledger, get_watchdog)
        from ..telemetry.watchdog import DEFAULT_HEARTBEAT_MAX_BYTES

        wd = get_watchdog()
        if wd is not None:
            # the watchdog assembles AND caps its own payload with its
            # configured bound — never re-add fields its cap dropped
            # (that would ship past the operator's limit and bump the
            # drop counter every single beat)
            return wd.heartbeat_payload()
        led = get_collective_ledger()
        if not led.enabled:
            return None
        # ledger-only path (no watchdog installed): same schema version
        # + the documented default bound
        return cap_heartbeat_payload(dict(led.heartbeat_summary()),
                                     DEFAULT_HEARTBEAT_MAX_BYTES)

    def _heartbeat_tick(self) -> None:
        """One liveness beat: heartbeat (+watchdog/ledger payload); the
        bundle publisher answers collect requests and pushes fresh trip
        bundles; rank 0 also folds peer payloads into the straggler-skew
        gauges and runs the live collective-desync check."""
        self.rdzv.heartbeat(self._hb_payload())
        from ..telemetry.aggregator import check_desync_live, get_publisher

        pub = get_publisher()
        if pub is not None:
            try:
                pub.tick(self.rdzv.c)
            except Exception as e:
                # store hiccup / dump failure; the next tick retries
                debug_once("elastic/publisher_tick",
                           f"bundle publisher tick failed ({e!r}); "
                           f"retrying next heartbeat")
        else:
            # subprocess mode: the WORKER owns the publisher (and its
            # tick runs the clock sync + metrics push); the agent still
            # keeps its own store-clock estimate fresh so agent-side
            # spans land aligned in merged traces
            try:
                from ..telemetry import maybe_sync_clock

                maybe_sync_clock(self.rdzv.c, node_id=self.node_id)
            except Exception as e:
                debug_once("elastic/clock_sync",
                           f"agent clock sync failed ({e!r}); retrying "
                           f"next heartbeat")
        if self._rank == 0 and len(self._peers) > 1:
            try:
                self.rdzv.publish_straggler_stats(self._peers)
                check_desync_live(self.rdzv.c, self._peers)
            except Exception as e:
                # store hiccup; the next tick retries
                debug_once("elastic/straggler_stats",
                           f"straggler/desync publication failed ({e!r}); "
                           f"retrying next heartbeat")
            try:
                # the live cross-process rollup (ISSUE 13): ingest every
                # peer's published registry snapshot + step batch, feed
                # the cluster gauges, keep the merged exports fresh
                from ..telemetry import get_telemetry, rollup_tick

                rollup_tick(self.rdzv.c, self._peers,
                            out_dir=get_telemetry().output_path)
            except Exception as e:
                # store hiccup / peers not publishing yet; next tick
                debug_once("elastic/rollup_tick",
                           f"metrics rollup tick failed ({e!r}); "
                           f"retrying next heartbeat")

    def _record_stale_peers(self, stale: List[str]) -> None:
        """Satellite (ISSUE 2): stale-peer detections at the AGENT level
        (where they trigger teardown) get their own counter, distinct
        from rendezvous-level detections."""
        from ..telemetry import get_telemetry

        get_telemetry().inc_counter(
            "elastic/agent_stale_peer_events", v=len(stale),
            help="stale peer heartbeats that triggered an agent restart")

    def _note_reshape(self, round_id: int, world: int) -> None:
        """A reseal at a DIFFERENT world size is a mesh reshape, not a
        mere restart: count it (total + direction — the agent-level
        mirror of the engine's reshard counters, so the two can be
        cross-checked against an injected chaos schedule) and annotate
        origin/target topology into the next debug bundle."""
        prev = self._world
        self._world = int(world)
        if not prev or prev == world:
            return
        direction = "shrink" if world < prev else "grow"
        from ..telemetry import get_flight_recorder, get_telemetry

        tel = get_telemetry()
        tel.inc_counter(
            "resilience/reshapes_total",
            help="snapshots restored onto a DIFFERENT mesh shape "
                 "(elastic reshard-on-restore)")
        tel.inc_counter(
            f"resilience/reshapes_{direction}_total",
            help="reshard-on-restore restores, by direction (the "
                 "{direction} breakdown of resilience/reshapes_total)")
        get_flight_recorder().annotate("reshape", {
            "direction": direction, "source": "rendezvous",
            "round": int(round_id),
            "origin": {"world_size": prev},
            "target": {"world_size": int(world),
                       "gang": list(self._peers)}})
        log_dist(f"elastic agent[{self.node_id}]: mesh RESHAPE "
                 f"({direction}): world {prev} -> {world} at round "
                 f"{round_id}")

    # -- rendezvous --------------------------------------------------------

    def _rendezvous(self) -> None:
        """(Re-)join the world.  Store-backed when available; else the
        static env the launcher set (COORDINATOR_ADDRESS / NUM_PROCESSES /
        PROCESS_ID)."""
        if self.rdzv is not None:
            r, rank, world, coord = self.rdzv.next_round()
            self._round = r
            self._rank = rank
            # monitor the FROZEN gang, not the raw members key: a node
            # squeezed out by max_nodes appended itself to members but is
            # parked as standby and never heartbeats — treating it as a
            # peer would churn the round forever
            sealed = self.rdzv.c.get(
                ElasticRendezvous._sealed_key(r)) or [[]]
            self._peers = list(sealed[0])
            os.environ["COORDINATOR_ADDRESS"] = coord
            os.environ["NUM_PROCESSES"] = str(world)
            os.environ["PROCESS_ID"] = str(rank)
            # scale-up joiner flag: the worker's resume path reads it to
            # bootstrap mid-run state from a peer replica instead of
            # starting at step 0 (cleared for ordinary members so a
            # stale export never misleads a later attempt)
            if getattr(self.rdzv, "joined_running", False):
                os.environ["DS_ELASTIC_JOINED_RUNNING"] = "1"
            else:
                os.environ.pop("DS_ELASTIC_JOINED_RUNNING", None)
            self._note_reshape(r, world)
            log_dist(f"elastic rendezvous: round={r} rank={rank}/{world} "
                     f"coordinator={coord}")
            # per-node heartbeat ages in every future debug bundle: a
            # watchdog hang dump then distinguishes "my host stalled"
            # from "a peer died" (satellite, ISSUE 2)
            from ..telemetry import get_flight_recorder

            get_flight_recorder().register_context(
                "heartbeat_ages",
                lambda: self.rdzv.peer_heartbeat_ages(self._peers))
            get_flight_recorder().annotate(
                "rendezvous", {"round": r, "rank": rank, "world": world,
                               "coordinator": coord})
        coord = os.environ.get("COORDINATOR_ADDRESS")
        if not coord or self.spec.cmd is not None:
            return  # subprocess workers init jax.distributed themselves
        import jax

        try:
            jax.distributed.shutdown()
        except Exception as e:
            # not initialized yet
            debug_once("elastic/dist_shutdown",
                       f"jax.distributed.shutdown before re-init: {e!r}")
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=int(os.environ.get("NUM_PROCESSES", "1")),
            process_id=int(os.environ.get("PROCESS_ID", "0")))

    # -- supervision loop --------------------------------------------------

    def run(self) -> Any:
        spec = self.spec
        while True:
            try:
                self._rendezvous()
                if spec.cmd is not None:
                    self.last_result = self._run_subprocess()
                else:
                    self.last_result = self._run_fn()
                if self.rdzv is not None:
                    # graceful leave: peers must not mistake a finished
                    # node's silent heartbeat for a death and tear down
                    # their own near-complete attempts
                    self.rdzv.leave()
                log_dist(f"elastic worker finished after "
                         f"{self.restart_count} restart(s)")
                return self.last_result
            except _RestartSignal as e:
                # membership changes (scale-up joins, peer death noticed
                # elsewhere, round bumps) are the elastic steady state, not
                # worker failures: they restart WITHOUT consuming the
                # max_restarts budget, so a healthy job that scales many
                # times never gives up (torch-elastic behavior)
                self._maybe_restart(e, announce=False, budgeted=False)
            except SystemExit as e:
                # scripts commonly end via sys.exit(main()); code 0/None is
                # success, anything else is a worker failure to supervise
                if e.code in (0, None):
                    return self.last_result
                self._maybe_restart(
                    RuntimeError(f"worker exited with code {e.code}"))
            except Exception as e:  # worker failure → restart or give up
                from ..resilience.faults import NodeLeaveRequested

                if isinstance(e, NodeLeaveRequested):
                    # scale-DOWN, not a crash: leave gracefully, bump so
                    # the survivors reseal at the smaller world, and
                    # EXIT the supervision loop — this host is done
                    return self._leave_gang(str(e))
                self._maybe_restart(e)

    def _run_fn(self) -> Any:
        """In-process attempt.  With a rendezvous attached, a daemon thread
        keeps heartbeating (so peers don't declare this node dead mid-
        attempt) and watches the round counter; an in-process fn cannot be
        preempted, so a round bump is honored AFTER the fn returns (the
        attempt's result is discarded and the agent re-rendezvouses —
        subprocess mode is the production path for prompt teardown)."""
        spec = self.spec
        if self.rdzv is None:
            return spec.fn(self.restart_count, spec.checkpoint_dir,
                           *spec.args)
        import threading

        stop = threading.Event()
        round_moved = threading.Event()

        def beat():
            while not stop.wait(spec.monitor_interval):
                try:
                    self._heartbeat_tick()
                    if self.rdzv.current_round() != self._round:
                        # the attempt is already doomed; latch and stop so
                        # we never bump a round someone else already moved
                        round_moved.set()
                        return
                    stale = self.rdzv.stale_peers(self._peers,
                                                  spec.heartbeat_ttl)
                    if stale:
                        # bump ONCE, then latch — re-bumping every tick
                        # would storm the counter past the round peers
                        # are trying to re-form on
                        self._record_stale_peers(stale)
                        self.rdzv.bump_round(f"stale peers {stale}")
                        round_moved.set()
                        return
                except ConnectionError as e:
                    # control plane degraded (the store is down or this
                    # node is partitioned): heartbeats are journaled so
                    # they buffer and replay on reconnect — keep beating;
                    # the client counts the outage
                    # (elasticity/store_reconnects_total + degraded
                    # seconds) when it heals
                    debug_once("elastic/heartbeat_degraded",
                               f"store unreachable in the beat thread "
                               f"({e!r}); heartbeats buffered, resuming "
                               f"on reconnect")
                except Exception as e:
                    # store hiccup — keep the attempt running
                    debug_once("elastic/heartbeat_beat",
                               f"worker heartbeat failed ({e!r}); "
                               f"retrying next interval")

        t = threading.Thread(target=beat, daemon=True)
        t.start()
        try:
            result = spec.fn(self.restart_count, spec.checkpoint_dir,
                             *spec.args)
        finally:
            stop.set()
            t.join(timeout=2)
        if round_moved.is_set():
            raise _RestartSignal(
                f"membership round moved past {self._round} during the "
                f"attempt — result discarded, re-rendezvousing")
        return result

    def _run_subprocess(self) -> int:
        """Spawn the worker argv and monitor it: heartbeat, watch the
        round counter and peer heartbeats, reap the child.  Returns the
        child's exit code (0) on success."""
        spec = self.spec
        env = dict(os.environ)
        env["DS_ELASTIC_RESTART_COUNT"] = str(self.restart_count)
        # the worker must present the SAME node id the agent sealed into
        # the ring: the resilience tier-2 buddy lookup and the bundle
        # publisher both key their store slots on it
        env["DS_ELASTIC_NODE_ID"] = self.node_id
        # lets the node_leave fault signal a GRACEFUL leave via the
        # well-known exit code instead of an uncatchable raised
        # exception (which would read as a budgeted crash)
        env["DS_ELASTIC_SUBPROCESS"] = "1"
        if spec.checkpoint_dir:
            env["DS_ELASTIC_CHECKPOINT_DIR"] = spec.checkpoint_dir
        proc = subprocess.Popen(spec.cmd, env=env)
        try:
            while True:
                rc = proc.poll()
                if rc is not None:
                    if rc == 0:
                        return 0
                    from ..resilience.faults import (NODE_LEAVE_EXIT_CODE,
                                                     NodeLeaveRequested)

                    if rc == NODE_LEAVE_EXIT_CODE:
                        # scale-down, not a crash: run() maps this to
                        # _leave_gang (graceful leave + bump + exit)
                        raise NodeLeaveRequested(
                            f"worker exited with the node-leave code "
                            f"({rc})")
                    if self.rdzv is not None:
                        self.rdzv.bump_round(
                            f"worker on {self.node_id} exited rc={rc}")
                    raise RuntimeError(
                        f"worker exited with code {rc}")
                if self.rdzv is not None:
                    try:
                        self._heartbeat_tick()
                        moved = self.rdzv.current_round() != self._round
                        stale = self.rdzv.stale_peers(self._peers,
                                                      spec.heartbeat_ttl)
                    except (OSError, ConnectionError):
                        # transient store hiccup must not kill a healthy
                        # worker (matches the in-process beat thread)
                        moved, stale = False, []
                    if moved:
                        raise _RestartSignal(
                            f"membership round moved past {self._round}")
                    if stale:
                        self._record_stale_peers(stale)
                        self.rdzv.bump_round(f"stale peers {stale}")
                        raise _RestartSignal(f"peers {stale} went silent")
                time.sleep(spec.monitor_interval)
        finally:
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
                try:
                    proc.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait()

    def _leave_gang(self, reason: str) -> Any:
        """Graceful scale-down exit: mark left (peers must not mistake
        our silence for a death), bump the round so the survivors reseal
        at the smaller world NOW (instead of after a heartbeat-ttl
        grace), and return the last result."""
        if self.rdzv is not None:
            try:
                self.rdzv.leave()
                self.rdzv.bump_round(
                    f"node {self.node_id} leaving (scale-down): {reason}")
            except Exception as e:
                # the peers' ttl-based stale detection still reseals;
                # leaving must not crash the leaver
                debug_once("elastic/leave",
                           f"graceful leave failed ({e!r}); peers will "
                           f"notice via heartbeat ttl")
        from ..telemetry import get_telemetry

        get_telemetry().inc_counter(
            "elastic/node_leaves_total",
            help="nodes that left the gang gracefully (scale-down)")
        log_dist(f"elastic agent[{self.node_id}]: left the gang "
                 f"({reason}) after {self.restart_count} restart(s)")
        return self.last_result

    def _maybe_restart(self, e: BaseException, announce: bool = True,
                       budgeted: bool = True) -> None:
        spec = self.spec
        self.restart_count += 1
        delay = spec.monitor_interval
        if not budgeted and spec.scale_up_settle_s > 0:
            # membership-churn restart: when every previous peer is
            # still heartbeating AND none left gracefully, the bump was
            # JOIN-driven — wait the settle window so a flapping node
            # costs one reshape per window, not one per flap.  A
            # capacity-LOSS bump (stale peers, or a graceful leaver —
            # who never goes stale because stale_peers skips left
            # nodes) keeps the prompt monitor_interval delay.
            try:
                stale = (self.rdzv.stale_peers(self._peers,
                                               spec.heartbeat_ttl)
                         if self.rdzv is not None else [])
                stale = stale or (self.rdzv.left_peers(self._peers)
                                  if self.rdzv is not None else [])
            except (OSError, ConnectionError):
                stale = []  # store hiccup — don't stall the re-form
            if self.rdzv is not None and not stale:
                delay = max(delay, spec.scale_up_settle_s)
                from ..telemetry import get_telemetry

                get_telemetry().inc_counter(
                    "elastic/scale_up_settles_total",
                    help="join-driven round bumps held for the "
                         "scale-up settle window")
        if budgeted:
            self.failure_count += 1
            if self.failure_count > spec.max_restarts:
                logger.error(f"elastic agent: giving up after "
                             f"{spec.max_restarts} failures ({e!r})")
                raise e
            # capped exponential backoff between FAILURE restarts: a
            # crash-looping worker must not respawn hot (membership-churn
            # restarts keep the prompt monitor_interval delay — peers are
            # actively waiting in the new round)
            delay = min(
                spec.restart_backoff_s * (2 ** (self.failure_count - 1)),
                spec.restart_backoff_max_s)
        from ..telemetry import get_telemetry

        get_telemetry().inc_counter(
            "elastic/worker_restarts_total",
            help="elastic worker restarts (membership churn + failures)")
        if budgeted:
            get_telemetry().inc_counter(
                "elastic/worker_failure_restarts_total",
                help="elastic worker restarts that consumed the failure "
                     "budget")
        level = logger.warning if announce else logger.info
        level(f"elastic agent[{self.node_id}]: restarting (attempt "
              f"{self.restart_count}, failures "
              f"{self.failure_count}/{spec.max_restarts}, backoff "
              f"{delay:.2f}s): {e!r}")
        self._sleep(delay)


def launch_elastic(fn: Callable[..., Any], args: tuple = (),
                   max_restarts: int = 3,
                   checkpoint_dir: Optional[str] = None) -> Any:
    """Convenience wrapper (reference ``ds_elastic`` entry role)."""
    spec = WorkerSpec(fn, args=args, max_restarts=max_restarts,
                      checkpoint_dir=checkpoint_dir)
    return DSElasticAgent(spec).run()


def cli_main(argv=None) -> int:
    """``ds_elastic`` CLI: supervise a user script under the agent.

    ``--rdzv_endpoint host:port`` joins a cross-host rendezvous store
    (start one with ``--standalone`` on the first node); without it the
    agent is the single-host supervision loop."""
    import argparse
    import runpy

    parser = argparse.ArgumentParser(prog="ds_elastic")
    parser.add_argument("--max_restarts", type=int, default=3)
    parser.add_argument("--checkpoint_dir", default=None)
    parser.add_argument("--rdzv_endpoint", default=None,
                        help="host:port of the rendezvous store")
    parser.add_argument("--standalone", action="store_true",
                        help="also host the rendezvous store here")
    parser.add_argument("--min_nodes", type=int, default=1)
    parser.add_argument("--max_nodes", type=int, default=64)
    parser.add_argument("--node_id", default=None)
    parser.add_argument("--scale_up_settle", type=float, default=0.0,
                        help="settle window (s) before re-rendezvousing "
                             "on a JOIN-driven round bump — a flapping "
                             "node costs one reshape per window instead "
                             "of thrashing the mesh")
    parser.add_argument("--subprocess", action="store_true",
                        help="run the script as a supervised subprocess "
                             "(recommended with a rendezvous)")
    parser.add_argument("user_script")
    parser.add_argument("user_args", nargs="*")
    args = parser.parse_args(argv)

    server = None
    if args.standalone:
        host = (args.rdzv_endpoint or "127.0.0.1:29499").rsplit(":", 1)
        server = RendezvousServer(host[0], int(host[1]))
        os.environ["DS_RDZV_ENDPOINT"] = server.endpoint
        print(f"rendezvous store: {server.endpoint}")
    elif args.rdzv_endpoint:
        os.environ["DS_RDZV_ENDPOINT"] = args.rdzv_endpoint
    os.environ["DS_ELASTIC_MIN_NODES"] = str(args.min_nodes)
    os.environ["DS_ELASTIC_MAX_NODES"] = str(args.max_nodes)
    if args.node_id:
        os.environ["DS_ELASTIC_NODE_ID"] = args.node_id

    try:
        if args.subprocess or os.environ.get("DS_RDZV_ENDPOINT"):
            spec = WorkerSpec(
                cmd=[sys.executable, args.user_script] + list(args.user_args),
                max_restarts=args.max_restarts,
                checkpoint_dir=args.checkpoint_dir,
                scale_up_settle_s=args.scale_up_settle)
            DSElasticAgent(spec).run()
            return 0

        def worker(restart_count, ckpt_dir):
            os.environ["DS_ELASTIC_RESTART_COUNT"] = str(restart_count)
            if ckpt_dir:
                os.environ["DS_ELASTIC_CHECKPOINT_DIR"] = ckpt_dir
            sys.argv = [args.user_script] + list(args.user_args)
            runpy.run_path(args.user_script, run_name="__main__")
            return 0

        launch_elastic(worker, max_restarts=args.max_restarts,
                       checkpoint_dir=args.checkpoint_dir)
        return 0
    finally:
        if server is not None:
            server.shutdown()
