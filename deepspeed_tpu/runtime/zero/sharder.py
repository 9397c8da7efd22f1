"""ZeRO as a GSPMD sharding policy.

The reference implements ZeRO with explicit machinery: flattened contiguous
buffers, bucketed reduce-scatter hooks, a gather/release state machine
(``deepspeed/runtime/zero/stage_1_and_2.py``, ``stage3.py``,
``partition_parameters.py``, ``partitioned_param_coordinator.py`` [K],
~11k LoC).  Under XLA/GSPMD the same memory states are *sharding
annotations*; the compiler inserts and overlaps the all-gathers and
reduce-scatters the reference schedules by hand (SURVEY §7):

    stage 0: params, grads, opt-state replicated; grads psum over DP.
    stage 1: opt-state sharded over DP; params replicated.
    stage 2: + grads reduce-scattered (transient inside the jitted step —
             realized as a sharding constraint on the grad pytree).
    stage 3: + params sharded over DP (FSDP); XLA all-gathers per use site
             with latency hiding ≈ the reference's prefetch coordinator.

Per-tensor rule: shard the largest dimension divisible by the DP world size
(ties → first), leaving tensors smaller than
``stage3_param_persistence_threshold`` replicated — the direct analogue of the
reference's persisted-small-params optimization [L ACC:2289-2319].

MiCS (``zero/mics.py`` [K]) falls out for free: a ``mics_shard_size`` < DP
world shards params over a sub-axis and replicates across the rest — we
express it by sharding over only the ``data`` axis while replicating over
``expert``, or via explicit shard sizes when finer control lands.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ...parallel.mesh import DP_AXES
from .config import DeepSpeedZeroConfig

# pytree-of-PartitionSpec utilities work leaf-wise via tree_map.


def dp_shardable_dim(shape: Tuple[int, ...], dp_size: int,
                     taken: Optional[Sequence[Optional[Any]]] = None
                     ) -> Optional[int]:
    """THE placement rule, factored out: the largest free dim of
    ``shape`` divisible by ``dp_size`` (ties → earliest), or None when
    nothing shards (the leaf replicates over DP).  ``taken`` marks dims
    a base spec already occupies.  Shared by the live sharding-spec
    computation below and the OFFLINE reshard pre-check
    (``resilience verify --target-mesh`` asks "how would this manifest's
    recorded leaves lay out at dp=N?" without building an engine)."""
    if dp_size <= 1 or not shape:
        return None
    entries = list(taken) if taken is not None else [None] * len(shape)
    entries += [None] * (len(shape) - len(entries))
    candidates = [(dim, i) for i, dim in enumerate(shape)
                  if entries[i] is None and dim % dp_size == 0]
    if not candidates:
        return None
    _, best = max(candidates, key=lambda t: (t[0], -t[1]))
    return best


def reshard_layout_report(state_shapes: Sequence[Sequence[Any]],
                          dp_size: int) -> Dict[str, Any]:
    """Offline layout preview for a snapshot manifest's recorded
    ``state_shapes`` (``[path, shape]`` pairs) at a TARGET dp world:
    which leaves would DP-shard under the placement rule and which
    would fall back to replication (correct either way — replication is
    the rule's documented fallback, so this is capacity guidance, not a
    compatibility gate)."""
    sharded: List[str] = []
    replicated: List[str] = []
    for entry in state_shapes or []:
        name, shape = str(entry[0]), tuple(int(d) for d in entry[1])
        if dp_shardable_dim(shape, dp_size) is not None:
            sharded.append(name)
        else:
            replicated.append(name)
    return {"dp_size": int(dp_size), "sharded": sharded,
            "replicated": replicated,
            "sharded_count": len(sharded),
            "replicated_count": len(replicated)}


@dataclasses.dataclass(frozen=True)
class ZeroShardingPolicy:
    """Maps a ZeRO stage onto PartitionSpecs for param/grad/opt-state leaves."""

    mesh: Mesh
    stage: int
    persistence_threshold: int = 0
    shard_axes: Tuple[str, ...] = DP_AXES
    #: hpZ (ZeRO++): the *param* (secondary) partition may span a SUB-group
    #: of the DP world — the bf16 compute copy shards only over the inner
    #: 'data' axis (ICI-local all-gathers) while grads/opt-state stay
    #: sharded over the full DP world.  None → same axes as everything.
    param_shard_axes: Tuple[str, ...] = None

    @classmethod
    def from_config(cls, mesh: Mesh, config: DeepSpeedZeroConfig) -> "ZeroShardingPolicy":
        threshold = config.stage3_param_persistence_threshold
        if isinstance(threshold, str):  # unresolved "auto"
            threshold = 100_000
        shard_axes = DP_AXES
        # MiCS: shard over the inner 'data' axis only; replicate over 'expert'.
        if config.mics_shard_size not in (-1, 0) and config.mics_shard_size < int(
                np.prod([mesh.shape[a] for a in DP_AXES])):
            shard_axes = ("data",)
        param_axes = None
        hpz = int(config.zero_hpz_partition_size or 1)
        if hpz > 1 and config.stage >= 3:
            inner = int(mesh.shape.get("data", 1))
            if hpz != inner:
                raise ValueError(
                    f"zero_hpz_partition_size={hpz} must equal the inner "
                    f"'data' mesh axis size ({inner}) — the secondary "
                    "partition maps onto the ICI-local axis (lay the mesh "
                    "out so data=hpz and expert carries the rest of DP)")
            param_axes = ("data",)
        return cls(mesh=mesh, stage=config.stage,
                   persistence_threshold=int(threshold),
                   shard_axes=shard_axes, param_shard_axes=param_axes)

    @property
    def dp_size(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.shard_axes]))

    # ------------------------------------------------------------------
    # per-leaf spec rules
    # ------------------------------------------------------------------

    def _shard_spec_for_shape(
            self, shape: Tuple[int, ...],
            base: Optional[PartitionSpec] = None,
            axes: Optional[Tuple[str, ...]] = None) -> PartitionSpec:
        """Largest free dim divisible by dp_size gets the DP axes.

        ``base`` carries model-provided specs (TP ``tensor`` axis, etc. —
        reference analogue: AutoTP's column/row decision); ZeRO composes by
        claiming a dim the base left unsharded.  With no eligible dim the
        tensor stays in its base placement (replicated over DP) — the
        reference's same fallback for unpartitionable tensors.
        """
        entries = list(base) if base is not None else []
        entries += [None] * (len(shape) - len(entries))
        base_spec = PartitionSpec(*entries) if any(
            e is not None for e in entries) else PartitionSpec()
        if not shape:
            return base_spec
        # never reuse a mesh axis the base already occupies (e.g. MoE expert-
        # stacked weights carry 'expert', which is also a ZeRO DP axis)
        used_axes = set()
        for e in entries:
            if e is not None:
                used_axes.update(e if isinstance(e, tuple) else (e,))
        shard_axes = axes if axes is not None else self.shard_axes
        free_axes = tuple(a for a in shard_axes if a not in used_axes)
        free_size = int(np.prod([dict(self.mesh.shape)[a]
                                 for a in free_axes])) if free_axes else 1
        if free_size == 1:
            return base_spec
        if int(np.prod(shape)) <= self.persistence_threshold:
            return base_spec  # persisted small param — stay replicated over DP
        best = dp_shardable_dim(shape, free_size, taken=entries)
        if best is None:
            return base_spec
        entries[best] = free_axes
        return PartitionSpec(*entries)

    def _base_or_empty(self, base: Optional[PartitionSpec],
                       shape: Tuple[int, ...]) -> PartitionSpec:
        if base is None:
            return PartitionSpec()
        entries = list(base) + [None] * (len(shape) - len(base))
        return PartitionSpec(*entries)

    def param_spec(self, leaf: Any,
                   base: Optional[PartitionSpec] = None) -> PartitionSpec:
        shape = tuple(np.shape(leaf))
        if self.stage < 3:
            return self._base_or_empty(base, shape)
        # hpZ: the compute copy shards over the inner (ICI-local) sub-axes
        return self._shard_spec_for_shape(shape, base,
                                          axes=self.param_shard_axes)

    def grad_spec(self, leaf: Any,
                  base: Optional[PartitionSpec] = None) -> PartitionSpec:
        # stage >= 2: grads live reduce-scattered; in-jit this is a constraint.
        shape = tuple(np.shape(leaf))
        if self.stage < 2:
            return self._base_or_empty(base, shape)
        return self._shard_spec_for_shape(shape, base)

    def opt_state_spec(self, leaf: Any,
                       base: Optional[PartitionSpec] = None) -> PartitionSpec:
        # stage >= 1: optimizer states (incl. fp32 master copies) sharded.
        shape = tuple(np.shape(leaf))
        if self.stage < 1:
            return self._base_or_empty(base, shape)
        return self._shard_spec_for_shape(shape, base)

    # ------------------------------------------------------------------
    # pytree-level helpers — ``base_specs`` is a matching pytree of
    # PartitionSpecs from the model (TP/SP placement) or None
    # ------------------------------------------------------------------

    def _map_with_base(self, fn, tree: Any, base_specs: Any) -> Any:
        if base_specs is None:
            return jax.tree.map(lambda p: fn(p, None), tree)
        return jax.tree.map(fn, tree, base_specs)

    def param_shardings(self, params: Any, base_specs: Any = None) -> Any:
        from ...telemetry import get_telemetry

        with get_telemetry().startup_span(
                "startup/place/shardings",
                {"stage": self.stage, "of": "params"}):
            return self._map_with_base(
                lambda p, b: NamedSharding(self.mesh, self.param_spec(p, b)),
                params, base_specs)

    def param_specs(self, params: Any, base_specs: Any = None) -> Any:
        return self._map_with_base(
            lambda p, b: self.param_spec(p, b), params, base_specs)

    def grad_specs(self, params: Any, base_specs: Any = None) -> Any:
        return self._map_with_base(
            lambda p, b: self.grad_spec(p, b), params, base_specs)

    def opt_state_shardings(self, opt_state: Any, tx: Any = None,
                            base_specs: Any = None) -> Any:
        """Shardings for an optax state pytree.  Leaves that mirror a param
        shape (mu/nu/master copies) shard like params-at-stage≥1; scalar
        counters replicate.  With model ``base_specs`` the param↔state
        correspondence comes from ``optax.tree_map_params`` so TP axes carry
        into the mirrored moments."""
        from ...telemetry import get_telemetry

        with get_telemetry().startup_span(
                "startup/place/shardings",
                {"stage": self.stage, "of": "opt_state"}):
            return self._opt_state_shardings(opt_state, tx, base_specs)

    def _opt_state_shardings(self, opt_state: Any, tx: Any = None,
                             base_specs: Any = None) -> Any:
        if base_specs is not None and tx is not None:
            import optax

            def for_param_leaf(leaf, base):
                return NamedSharding(
                    self.mesh, self.opt_state_spec(leaf, base)
                    if np.ndim(leaf) > 0 else PartitionSpec())

            def for_other_leaf(leaf):
                return NamedSharding(
                    self.mesh, self.opt_state_spec(leaf)
                    if np.ndim(leaf) > 0 else PartitionSpec())

            return optax.tree_map_params(
                tx, for_param_leaf, opt_state, base_specs,
                transform_non_params=for_other_leaf)

        def leaf_sharding(leaf):
            return NamedSharding(
                self.mesh, self.opt_state_spec(leaf)
                if np.ndim(leaf) > 0 else PartitionSpec())

        return jax.tree.map(leaf_sharding, opt_state)

    def offload_shardings(self, params: Any, base_specs: Any = None) -> Any:
        """Host-partition layout for ZeRO-Offload masters: each param leaf in
        its opt-state placement (stage ≥ 1 → DP-sharded), so every process
        keeps only its own slice of the fp32 master + moments — the
        reference's partitioning of CPU optimizer state across DP ranks."""
        return self._map_with_base(
            lambda p, b: NamedSharding(self.mesh, self.opt_state_spec(p, b)),
            params, base_specs)

    def apply_offload_grad_constraints(self, grads: Any,
                                       base_specs: Any = None) -> Any:
        """Inside-jit (offload mode): land grads in the host-partition layout
        so each process's d2h pull is exactly its master slice — a reduce-
        scatter instead of an all-reduce whenever stage ≥ 1."""
        if self.stage < 1:
            return grads
        return self._map_with_base(
            lambda g, b: jax.lax.with_sharding_constraint(
                g, NamedSharding(self.mesh, self.opt_state_spec(g, b))),
            grads, base_specs)

    def apply_grad_constraints(self, grads: Any, base_specs: Any = None) -> Any:
        """Inside-jit: force reduce-scatter placement of grads (stage ≥ 2)."""
        if self.stage < 2:
            return grads
        return self._map_with_base(
            lambda g, b: jax.lax.with_sharding_constraint(
                g, NamedSharding(self.mesh,
                                 self._shard_spec_for_shape(g.shape, b))),
            grads, base_specs)


def sharded_zeros_like(policy: ZeroShardingPolicy, tree: Any, kind: str = "param"):
    """Materialize a zeroed pytree directly in its sharded layout (never builds
    the full tensor on one device — the ``zero.Init`` principle)."""
    spec_fn = {"param": policy.param_spec, "grad": policy.grad_spec,
               "opt": policy.opt_state_spec}[kind]

    def make(leaf):
        sharding = NamedSharding(policy.mesh, spec_fn(leaf))
        # deliberately UNtracked: a fresh zero-arg lambda per leaf has an
        # empty, identical signature at one site, so the tracker would
        # misreport every leaf after the first as a causeless recompile
        # and inflate compile/recompiles_total at init
        return jax.jit(lambda: jax.numpy.zeros(np.shape(leaf), leaf.dtype),  # dslint: disable=untracked-jit
                       out_shardings=sharding)()

    out = jax.tree.map(make, tree)
    from ...telemetry.memory import get_memory_ledger, unique_key

    led = get_memory_ledger()
    if led.enabled:
        # zero.Init materialization is a real allocation site: account
        # the tree under its ZeRO role (unique key — callers materialize
        # several trees through this site)
        pool = {"param": "params", "grad": "grads",
                "opt": "optimizer"}[kind]
        led.register_tree(pool, unique_key(f"sharder/zeros_like/{kind}"),
                          out, tag=f"sharded_zeros_like kind={kind}")
    return out
