"""Communication backend — DeepSpeed-verb API over XLA collectives.

Capability parity with the reference ``deepspeed/comm/comm.py`` [K]: the
module-level verbs (``all_reduce``, ``all_gather``, ``reduce_scatter``,
``all_to_all_single``, ``broadcast``, ``barrier``, ``init_distributed``,
``get_rank``/``get_world_size``) plus the ``comms_logger`` timing wrapper that
the reference installs around every collective.

Design (TPU-first, NOT a NCCL translation):

* **In-graph collectives** (``psum``/``all_gather``/``psum_scatter``/
  ``all_to_all``/``ppermute``) are the real data plane.  They are thin named
  wrappers over ``jax.lax`` usable inside ``shard_map``; the wrapper exists so
  the comms logger can count/annotate them and so group handles
  (:class:`~deepspeed_tpu.utils.groups.MeshAxisGroup`) can be passed instead
  of raw axis names.  Inside ``jit`` XLA schedules and overlaps these on ICI —
  there is no bucketing/stream machinery to port because GSPMD owns it.

* **Eager verbs** mirror the reference's host-called API for code that is not
  inside a jitted step (checkpoint consolidation, debugging, tests).  They jit
  a ``shard_map`` of the matching lax collective over the group's mesh on the
  fly (cached per shape/dtype/group).

* **Control plane**: ``init_distributed`` maps to ``jax.distributed.initialize``
  (multi-host rendezvous — the NCCL/TCP-store equivalent); ``barrier`` uses a
  tiny device all-reduce, falling back to ``multihost_utils.sync_global_devices``.
"""

from __future__ import annotations

import functools
import os
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from ..utils.jax_compat import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..utils import groups as groups_mod
from ..utils.groups import MeshAxisGroup
from ..utils.logging import logger

AxisName = Union[str, Tuple[str, ...]]

# ---------------------------------------------------------------------------
# ReduceOp — mirror of the reference's torch.distributed.ReduceOp surface.
# ---------------------------------------------------------------------------


class ReduceOp:
    SUM = "sum"
    AVG = "avg"
    MAX = "max"
    MIN = "min"
    PROD = "prod"


# ---------------------------------------------------------------------------
# comms logger (reference: deepspeed/comm/comm.py comms_logger + utils)
# ---------------------------------------------------------------------------


class CommsLogger:
    """Counts collective calls and (eager path) wall time per op name.

    Three surfaces, mirroring what can honestly be measured where:

    * eager verbs record at *execution* time (count/bytes/seconds real);
    * in-graph wrappers always record a *trace-time* census (structural
      collectives per compiled program — XLA runs without Python);
    * with ``exec_counts=True``, in-graph wrappers ALSO attach an
      effectful host callback that fires on every EXECUTION of the
      compiled program — ``exec_summary()`` counts scale with runs (a
      trace-time census cannot).  Counts are per LOCAL DEVICE SHARD per
      run (an 8-device mesh bumps a collective 8× per step; multi-host,
      each process counts its own shards) — ``exec_summary(per_step=
      True)`` normalizes by ``jax.local_device_count()``.  Opt-in: each
      callback is a device→host hop on the step's critical path — a
      diagnostics switch, like the reference's comms_logger.  Per-collective DEVICE timing still
      comes from ``profiling/collective_trace.py``.
    """

    def __init__(self) -> None:
        self.enabled = False
        self.verbose = False
        self.exec_counts = False
        self.stats: dict[str, dict[str, float]] = {}
        self.exec_stats: dict[str, dict[str, float]] = {}
        #: optional CollectiveLedger (telemetry/collective_ledger.py) fed
        #: INDEPENDENTLY of `enabled` — desync forensics must not depend
        #: on the stats logger being switched on.  Attached via
        #: telemetry.collective_ledger.attach_collective_ledger().
        self.ledger = None
        import threading

        self._exec_lock = threading.Lock()

    def configure(self, enabled: bool = True, verbose: bool = False,
                  exec_counts: bool = False) -> None:
        self.enabled = enabled
        self.verbose = verbose
        self.exec_counts = exec_counts

    def record(self, name: str, nbytes: int, seconds: float = 0.0) -> None:
        led = self.ledger
        if led is not None:
            # call-site order is deterministic per host (identical
            # programs issue identical sequences), which is what makes
            # cross-rank ledger comparison meaningful
            led.record(name, nbytes, source="census")
        if not self.enabled:
            return
        entry = self.stats.setdefault(name, {"count": 0, "bytes": 0, "seconds": 0.0})
        entry["count"] += 1
        entry["bytes"] += nbytes
        entry["seconds"] += seconds
        if self.verbose:
            logger.info(f"comm: {name} bytes={nbytes} time={seconds * 1e3:.3f}ms")

    def record_exec(self, name: str, nbytes: int) -> None:
        # gate at EXECUTION time too: probes baked into already-compiled
        # programs must stop counting the moment the logger is disabled.
        # Locked: unordered debug callbacks may fire concurrently from
        # several device shards, and += is not atomic.
        led = self.ledger
        if led is not None and getattr(led, "exec_feed", False):
            # opt-in: execution probes fire from UNORDERED device
            # callbacks, so their interleaving is not comparable across
            # ranks — they land in the ledger's separate EXEC lane
            # (per-host sequence forensics), never in the census chain
            # the live desync detection hashes
            led.record_exec(name, nbytes, source="exec_probe")
        if not (self.enabled and self.exec_counts):
            return
        with self._exec_lock:
            entry = self.exec_stats.setdefault(name,
                                               {"count": 0, "bytes": 0})
            entry["count"] += 1
            entry["bytes"] += nbytes

    def attach_exec_probe(self, name: str, x) -> None:
        """Called from in-graph wrappers at trace time: plant an effectful
        callback that bumps ``exec_stats`` on every EXECUTION of the
        compiled program (jax.debug.callback is an effect, so it is
        neither DCE'd nor cached away).

        The enable decision is baked in at TRACE time: programs compiled
        while ``exec_counts`` was off carry no probe and are not
        retrofitted when it is later enabled (only the disable direction
        is dynamic, via the exec-time gate in :meth:`record_exec`).
        Configure ``exec_counts=True`` before first compile of anything
        you want counted — planting callbacks unconditionally would tax
        every program with a device→host hop even when diagnostics are
        off."""
        if not (self.enabled and self.exec_counts):
            return
        nbytes = _nbytes(x)
        jax.debug.callback(
            functools.partial(self.record_exec, name, nbytes))

    def summary(self) -> dict[str, dict[str, float]]:
        return self.stats

    def total_bytes(self) -> int:
        """Cumulative bytes over every call-site record (eager timing +
        trace-time census); the engine's StepRecord carries this so BENCH
        artifacts and the telemetry registry report one number.  Execution-
        probe bytes are a separate measure — see :meth:`exec_summary`."""
        return int(sum(e.get("bytes", 0) for e in self.stats.values()))

    def total_ops(self) -> int:
        return int(sum(e.get("count", 0) for e in self.stats.values()))

    #: class-wide: log the first effects_barrier failure only — the
    #: fallback (stale-by-one counts) is benign, but silence hid real
    #: backend breakage behind a bare `except: pass` for two rounds
    _barrier_logged = False

    def _flush_effects(self, where: str) -> None:
        """Flush in-flight debug callbacks; on failure keep the fallback
        (counts may lag by the in-flight runs) but say so ONCE at debug
        level instead of swallowing the exception bare."""
        try:
            jax.effects_barrier()
        except Exception as e:
            if not CommsLogger._barrier_logged:
                CommsLogger._barrier_logged = True
                logger.debug(
                    f"comms_logger: jax.effects_barrier() failed in {where} "
                    f"({e!r}); execution counts may lag in-flight runs")

    def exec_summary(self, per_step: bool = False
                     ) -> dict[str, dict[str, float]]:
        """Per-execution stats.  Raw counts are per LOCAL DEVICE SHARD per
        run (see class docstring); ``per_step=True`` returns a normalized
        copy — counts/bytes divided by ``jax.local_device_count()`` — so
        callers stop hand-dividing (the engine's StepRecord comm-exec
        fields use this path)."""
        # debug callbacks are asynchronous; flush in-flight effects so
        # the summary reflects every completed run
        self._flush_effects("exec_summary")
        if not per_step:
            return self.exec_stats
        n = max(1, jax.local_device_count())
        with self._exec_lock:
            snap = {name: dict(e) for name, e in self.exec_stats.items()}
        return {name: {k: v / n for k, v in e.items()}
                for name, e in snap.items()}

    def exec_totals(self, per_step: bool = False) -> Tuple[float, float]:
        """(ops, bytes) summed over every probed collective; normalized
        per local device shard when ``per_step``."""
        summary = self.exec_summary(per_step=per_step)
        ops = sum(e.get("count", 0) for e in summary.values())
        nbytes = sum(e.get("bytes", 0) for e in summary.values())
        return ops, nbytes

    def reset(self) -> None:
        self.stats = {}
        # flush in-flight callbacks first, or counts from PRE-reset
        # runs would land in the fresh dict after the swap
        self._flush_effects("reset")
        with self._exec_lock:
            # same lock the execution probes take: a concurrent callback
            # must not land its increment in an abandoned dict
            self.exec_stats = {}


comms_logger = CommsLogger()


def _nbytes(x: Any) -> int:
    try:
        return int(np.prod(np.shape(x))) * jnp.dtype(jnp.result_type(x)).itemsize
    except Exception:
        return 0


def _axis(group: Union[MeshAxisGroup, AxisName, None]) -> AxisName:
    if group is None:
        return groups_mod.get_data_parallel_group().axis_name()
    if isinstance(group, MeshAxisGroup):
        return group.axis_name()
    return group


# ---------------------------------------------------------------------------
# In-graph collectives — call these inside shard_map/jit.
# ---------------------------------------------------------------------------


def psum(x, group: Union[MeshAxisGroup, AxisName, None] = None):
    axis = _axis(group)
    comms_logger.record("psum", _nbytes(x))
    comms_logger.attach_exec_probe("psum", x)
    return jax.lax.psum(x, axis_name=axis)


def pmean(x, group: Union[MeshAxisGroup, AxisName, None] = None):
    axis = _axis(group)
    comms_logger.record("pmean", _nbytes(x))
    comms_logger.attach_exec_probe("pmean", x)
    return jax.lax.pmean(x, axis_name=axis)


def pmax(x, group=None):
    comms_logger.record("pmax", _nbytes(x))
    comms_logger.attach_exec_probe("pmax", x)
    return jax.lax.pmax(x, axis_name=_axis(group))


def all_gather_in_graph(x, group=None, axis: int = 0, tiled: bool = True):
    comms_logger.record("all_gather", _nbytes(x))
    comms_logger.attach_exec_probe("all_gather", x)
    return jax.lax.all_gather(x, axis_name=_axis(group), axis=axis, tiled=tiled)


def reduce_scatter_in_graph(x, group=None, scatter_dimension: int = 0, tiled: bool = True):
    comms_logger.record("reduce_scatter", _nbytes(x))
    comms_logger.attach_exec_probe("reduce_scatter", x)
    return jax.lax.psum_scatter(
        x, axis_name=_axis(group), scatter_dimension=scatter_dimension, tiled=tiled)


def all_to_all_in_graph(x, group=None, split_axis: int = 0, concat_axis: int = 0,
                        tiled: bool = True):
    """Ulysses/MoE workhorse — first-class on ICI."""
    comms_logger.record("all_to_all", _nbytes(x))
    comms_logger.attach_exec_probe("all_to_all", x)
    return jax.lax.all_to_all(
        x, axis_name=_axis(group), split_axis=split_axis,
        concat_axis=concat_axis, tiled=tiled)


def ppermute(x, perm: Sequence[Tuple[int, int]], group=None):
    """Pipeline P2P: send/recv pairs as a collective-permute (ICI-native)."""
    comms_logger.record("ppermute", _nbytes(x))
    comms_logger.attach_exec_probe("ppermute", x)
    return jax.lax.ppermute(x, axis_name=_axis(group), perm=list(perm))


def axis_index(group=None):
    return jax.lax.axis_index(_axis(group))


# ---------------------------------------------------------------------------
# Eager verbs — the reference's host-called API shape.
# ---------------------------------------------------------------------------


def _group_or_dp(group) -> MeshAxisGroup:
    if isinstance(group, MeshAxisGroup):
        return group
    if group is None:
        return groups_mod.get_data_parallel_group()
    if isinstance(group, str):
        return MeshAxisGroup(mesh=groups_mod.get_mesh(), axes=(group,))
    return MeshAxisGroup(mesh=groups_mod.get_mesh(), axes=tuple(group))


@functools.lru_cache(maxsize=256)
def _eager_collective(kind: str, mesh: Mesh, axes: Tuple[str, ...],
                      shape: Tuple[int, ...], dtype: Any, extra: Any = None):
    """Build+cache a jitted shard_map collective over `axes` of `mesh`.

    The input is treated as sharded on its leading dim over `axes` (gather /
    reduce_scatter / all_to_all).  ``all_reduce`` shards the leading dim when
    it divides the group size; otherwise (scalars, odd shapes — e.g. the
    reference's loss averaging) it falls back to replicated semantics: the
    value is taken to be each rank's identical local tensor, so SUM returns
    value × group_size, matching ``torch.distributed.all_reduce`` of a
    replicated value."""
    axis_name = axes if len(axes) > 1 else axes[0]
    group_size = int(np.prod([mesh.shape[a] for a in axes]))
    sharded = PartitionSpec(axes)
    replicated = PartitionSpec()

    if kind == "all_reduce":
        op = extra
        divisible = len(shape) > 0 and shape[0] % group_size == 0
        spec = sharded if divisible else replicated

        def fn(x):
            if op == ReduceOp.SUM:
                return jax.lax.psum(x, axis_name)
            if op == ReduceOp.AVG:
                return jax.lax.pmean(x, axis_name)
            if op == ReduceOp.MAX:
                return jax.lax.pmax(x, axis_name)
            if op == ReduceOp.MIN:
                return jax.lax.pmin(x, axis_name)
            if op == ReduceOp.PROD:
                gathered = jax.lax.all_gather(x, axis_name, axis=0)
                return jnp.prod(gathered, axis=0)
            raise ValueError(f"unsupported reduce op {op}")

        return jax.jit(shard_map(fn, mesh=mesh, in_specs=(spec,),
                                 out_specs=spec, check_vma=False))
    if kind == "all_gather":
        def fn(x):
            return jax.lax.all_gather(x, axis_name, axis=0, tiled=True)

        return jax.jit(shard_map(fn, mesh=mesh, in_specs=(sharded,),
                                 out_specs=replicated, check_vma=False))
    if kind == "reduce_scatter":
        def fn(x):
            return jax.lax.psum_scatter(x, axis_name, scatter_dimension=0, tiled=True)

        return jax.jit(shard_map(fn, mesh=mesh, in_specs=(replicated,),
                                 out_specs=sharded, check_vma=False))
    if kind == "all_to_all":
        # torch all_to_all_single semantics: global leading dim indexes the
        # rank; each rank's local row is split into |group| chunks along the
        # next dim, chunk j goes to rank j. Globally: out[i, j·k:(j+1)·k] =
        # in[j, i·k:(i+1)·k].
        def fn(x):
            return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=1,
                                      tiled=True)

        return jax.jit(shard_map(fn, mesh=mesh, in_specs=(sharded,),
                                 out_specs=sharded, check_vma=False))
    raise ValueError(kind)


def _timed(name: str, fn, x):
    t0 = time.perf_counter()
    out = fn(x)
    if comms_logger.enabled:
        # dispatch is asynchronous: wait for the collective itself
        # (block_until_ready fences on the TPU — checked on the v5e, PR 21)
        jax.block_until_ready(out)
        comms_logger.record(name, _nbytes(x), time.perf_counter() - t0)
    elif comms_logger.ledger is not None:
        # stats logger off: record() is a stats no-op but still feeds the
        # collective ledger (desync forensics must see eager verbs too);
        # no fence — timing is only honest when the logger is on.  Guarded
        # so the everything-off default stays zero-cost per call.
        comms_logger.record(name, _nbytes(x))
    return out


def all_reduce(tensor, op: str = ReduceOp.SUM, group=None):
    """Eager all-reduce across the group; returns the reduced array
    (functional — JAX arrays are immutable, unlike the reference's in-place)."""
    g = _group_or_dp(group)
    x = jnp.asarray(tensor)
    fn = _eager_collective("all_reduce", g.mesh, g.axes, x.shape,
                           jnp.result_type(x), op)
    return _timed("all_reduce", fn, x)


def all_gather(tensor, group=None):
    """Gather leading-dim shards across the group → replicated concat."""
    g = _group_or_dp(group)
    x = jnp.asarray(tensor)
    fn = _eager_collective("all_gather", g.mesh, g.axes, x.shape, jnp.result_type(x))
    return _timed("all_gather", fn, x)


# reference name: all_gather_into_tensor
all_gather_into_tensor = all_gather


def reduce_scatter(tensor, group=None):
    """Reduce a replicated tensor and scatter leading-dim shards."""
    g = _group_or_dp(group)
    x = jnp.asarray(tensor)
    fn = _eager_collective("reduce_scatter", g.mesh, g.axes, x.shape, jnp.result_type(x))
    return _timed("reduce_scatter", fn, x)


reduce_scatter_tensor = reduce_scatter


def all_to_all_single(tensor, group=None):
    g = _group_or_dp(group)
    x = jnp.asarray(tensor)
    fn = _eager_collective("all_to_all", g.mesh, g.axes, x.shape, jnp.result_type(x))
    return _timed("all_to_all_single", fn, x)


def broadcast(tensor, src: int = 0, group=None):
    """Replicate ``tensor``'s value from group-rank ``src`` to every rank.

    In single-controller JAX a host value is already consistent across the
    mesh; for multihost process-level broadcast we use multihost_utils."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        return multihost_utils.broadcast_one_to_all(
            jnp.asarray(tensor), is_source=jax.process_index() == src)
    return jnp.asarray(tensor)


def barrier(group=None) -> None:
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices("deepspeed_tpu.comm.barrier")
    else:
        jax.effects_barrier()


#: per-tag monotonic round counters for monitored_barrier (each call on
#: the same tag is a fresh store key, so re-used tags never cross-talk)
_mon_barrier_seq: Dict[str, int] = {}
_mon_barrier_lock = threading.Lock()

#: the last monitored_barrier timeout, registered as flight-recorder
#: context ``monitored_barrier`` on first failure — the watchdog's hang
#: bundle then NAMES the ranks that never arrived
_mon_barrier_failure: Optional[Dict[str, Any]] = None


def _note_barrier_failure(doc: Dict[str, Any]) -> None:
    global _mon_barrier_failure
    first = _mon_barrier_failure is None
    _mon_barrier_failure = doc
    if first:
        try:
            from ..telemetry.flight_recorder import get_flight_recorder

            get_flight_recorder().register_context(
                "monitored_barrier", lambda: _mon_barrier_failure)
        except Exception as e:
            from ..utils.logging import debug_once

            debug_once("comm/mon_barrier_fr",
                       f"flight-recorder barrier context failed ({e!r})")


def monitored_barrier(group=None, timeout: float = 30.0,
                      tag: str = "default",
                      world: Optional[int] = None,
                      rank: Optional[int] = None,
                      store: Optional[Any] = None) -> None:
    """Barrier that, on timeout, NAMES the ranks that failed to arrive.

    The reference ``monitored_barrier`` is the debugging barrier: a hang
    inside a plain barrier says nothing; this one raises with the exact
    missing rank set.  With a rendezvous store (``store`` arg or
    ``DS_RDZV_ENDPOINT``), every rank appends its id under a per-round
    key and polls until all ``world`` ranks arrived — the timeout error
    lists whoever didn't make it, the collective ledger records the
    round either way, and the failure doc rides the watchdog's next
    flight-recorder bundle as context ``monitored_barrier``.  Without a
    store, multi-process falls back to ``sync_global_devices`` under a
    watchdog thread (a timeout is still detected, but the missing set is
    unknowable).  ``world``/``rank`` override process discovery for
    tests and out-of-band gangs."""
    world = int(world if world is not None else jax.process_count())
    rank = int(rank if rank is not None else jax.process_index())
    with _mon_barrier_lock:
        seq = _mon_barrier_seq.get(tag, 0) + 1
        _mon_barrier_seq[tag] = seq

    def _ledger(op: str) -> None:
        try:
            from ..telemetry.collective_ledger import get_collective_ledger

            get_collective_ledger().record(op, 0, source="barrier")
        except Exception as e:
            from ..utils.logging import debug_once

            debug_once("comm/mon_barrier_ledger",
                       f"barrier ledger record failed ({e!r})")

    if world <= 1 and store is None:
        jax.effects_barrier()
        _ledger(f"monitored_barrier:{tag}#{seq}")
        return

    if store is None:
        endpoint = os.environ.get("DS_RDZV_ENDPOINT")
        if endpoint:
            from ..elasticity.rendezvous import RendezvousClient

            store = RendezvousClient(endpoint)

    if store is not None:
        key = f"barrier/{tag}/{seq}"
        arrived = set(int(r) for r in store.append(key, rank))
        deadline = time.monotonic() + float(timeout)
        while len(arrived) < world and time.monotonic() < deadline:
            time.sleep(min(0.05, timeout / 20.0))
            got = store.get(key)
            if isinstance(got, list):
                arrived = set(int(r) for r in got)
        if len(arrived) >= world:
            _ledger(f"monitored_barrier:{tag}#{seq}")
            return
        missing = sorted(set(range(world)) - arrived)
        doc = {"tag": tag, "round": seq, "timeout_s": float(timeout),
               "world": world, "rank": rank,
               "arrived": sorted(arrived), "missing": missing,
               "ts": time.time()}
        _note_barrier_failure(doc)
        _ledger(f"monitored_barrier_timeout:{tag}#{seq}:"
                f"missing={','.join(map(str, missing))}")
        raise RuntimeError(
            f"monitored_barrier({tag!r} round {seq}) timed out after "
            f"{timeout}s: ranks {missing} never arrived "
            f"({len(arrived)}/{world} present)")

    # no store: the arrival set is unknowable — run the device barrier
    # under a watchdog thread so a hang still becomes a named timeout
    from jax.experimental import multihost_utils

    done = threading.Event()
    err: List[BaseException] = []

    def _sync() -> None:
        try:
            multihost_utils.sync_global_devices(
                f"deepspeed_tpu.comm.monitored_barrier:{tag}#{seq}")
        except BaseException as e:  # surfaced on the caller thread
            err.append(e)
        finally:
            done.set()

    t = threading.Thread(target=_sync, daemon=True,
                         name=f"ds-monitored-barrier-{tag}")
    t.start()
    if not done.wait(float(timeout)):
        doc = {"tag": tag, "round": seq, "timeout_s": float(timeout),
               "world": world, "rank": rank, "arrived": None,
               "missing": None, "ts": time.time()}
        _note_barrier_failure(doc)
        _ledger(f"monitored_barrier_timeout:{tag}#{seq}:missing=unknown")
        raise RuntimeError(
            f"monitored_barrier({tag!r} round {seq}) timed out after "
            f"{timeout}s (no rendezvous store — set DS_RDZV_ENDPOINT "
            f"to learn WHICH ranks were missing)")
    if err:
        raise err[0]
    _ledger(f"monitored_barrier:{tag}#{seq}")


# ---------------------------------------------------------------------------
# init / rank queries (reference: init_distributed + launcher env discovery)
# ---------------------------------------------------------------------------

_initialized = False


def is_initialized() -> bool:
    return _initialized


def init_distributed(dist_backend: str = "xla",
                     coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     timeout: Optional[int] = None,
                     auto_mpi_discovery: bool = True) -> None:
    """Multi-host rendezvous. Single-process (one TPU VM or local dev) is a
    no-op: all local chips are already visible to this controller.

    Env discovery mirrors the reference launcher contract: honors
    ``COORDINATOR_ADDRESS``/``MASTER_ADDR:MASTER_PORT``, ``WORLD_SIZE`` (as
    process count), ``RANK``.
    """
    global _initialized
    if _initialized:
        return
    coordinator_address = coordinator_address or os.environ.get("COORDINATOR_ADDRESS")
    if coordinator_address is None and os.environ.get("MASTER_ADDR"):
        coordinator_address = (f"{os.environ['MASTER_ADDR']}:"
                               f"{os.environ.get('MASTER_PORT', '12355')}")
    num_processes = num_processes or int(os.environ.get("WORLD_SIZE", "0")) or None
    process_id = process_id if process_id is not None else (
        int(os.environ["RANK"]) if "RANK" in os.environ else None)
    if coordinator_address and num_processes and num_processes > 1:
        try:
            # CPU backend: cross-process collectives need gloo (the test
            # substrate for multi-controller runs; TPU rides ICI/DCN and
            # ignores this).  Must be set before the backend exists.
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        except Exception as e:
            # backend already up or knob absent — TPU path
            from ..utils.logging import debug_once

            debug_once("comm/gloo_knob",
                       f"jax_cpu_collectives_implementation not set "
                       f"({e!r}); TPU path or backend already built")
        jax.distributed.initialize(coordinator_address=coordinator_address,
                                   num_processes=num_processes,
                                   process_id=process_id)
    _initialized = True


def get_rank(group=None) -> int:
    """Global rank of this controller within [0, get_world_size()).

    JAX is single-controller-per-host: one process drives many chips, so a
    per-chip rank does not exist on the host side.  We return the global id
    of the first local device — rank 0 on the lead host, a contiguous range
    start elsewhere — which keeps ``rank == 0`` gating (the dominant use)
    and ``0 <= rank < world_size`` correct.  In-graph code wanting a true
    per-shard rank must use :func:`axis_index`.
    """
    if group is None:
        return int(jax.local_devices()[0].id)
    return _group_or_dp(group).rank_of_process()


def get_world_size(group=None) -> int:
    if group is None:
        return jax.device_count()
    return _group_or_dp(group).size


def get_local_rank() -> int:
    return 0  # single controller per host; local chips are not separate ranks


def new_group(axes: Sequence[str]) -> MeshAxisGroup:
    """A 'new group' is just a named view over mesh axes — zero-cost."""
    return MeshAxisGroup(mesh=groups_mod.get_mesh(), axes=tuple(axes))
