"""Sharded MoE: gating + expert-parallel dispatch, TPU-first.

Reference: ``deepspeed/moe/sharded_moe.py`` [K] — ``TopKGate`` (top-1/top-2,
capacity factor, load-balancing aux loss à la GShard/Switch), ``MOELayer``
(all-to-all token dispatch to expert-parallel ranks), token dropping +
random-token-selection.  Papers: GShard arXiv 2006.16668, Switch arXiv
2101.03961, DeepSpeed-MoE arXiv 2201.05596 [P].

TPU-first, two dispatch formulations sharing ONE gating core:

* dense — the GShard one-hot dispatch/combine tensors contracted with
  einsum, static capacity shapes.  The reference's explicit ``_AllToAll``
  autograd op disappears: GSPMD inserts the all-to-all from the sharding
  transition tokens→experts inside the one jitted train step.
* sparse — the same routing decision lowered to index form
  (:func:`top_k_gating_indices`) and executed as gathers via
  ``ops.pallas.moe_dispatch`` (jnp reference under GSPMD meshes, Pallas
  kernels on unsharded TPU).  ``MOELayer(dispatch_impl=...)`` picks the
  rung; ``auto`` keeps small T·E·C on the fused dense path.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..parallel.mesh import AXIS_DATA, AXIS_EXPERT, AXIS_SEQ, AXIS_TENSOR, DP_AXES
from ..utils.logging import logger

P = PartitionSpec


def _one_hot(idx: jnp.ndarray, num: int) -> jnp.ndarray:
    return jax.nn.one_hot(idx, num, dtype=jnp.float32)


class GateMeta(dict):
    """Gate metadata dict with a back-compat shim: historical callers got
    bare ``exp_counts`` in ``MoE.__call__``'s third tuple slot, so
    ``np.asarray(meta)`` still yields the per-expert assignment counts."""

    def __array__(self, dtype=None):
        a = np.asarray(self["exp_counts"])
        return a.astype(dtype) if dtype is not None else a


jax.tree_util.register_pytree_node(
    GateMeta,
    lambda d: (tuple(d[k] for k in sorted(d)), tuple(sorted(d))),
    lambda keys, vals: GateMeta(zip(keys, vals)))


@dataclasses.dataclass
class GateIndices:
    """Routing decision in index form (the sparse dispatch contract).

    Per choice k and token t: which expert (``expert_idx``), which slot
    within it (``slot``), whether the assignment survived capacity
    (``keep``), and the renormalized combine weight (``gate``, zero for
    dropped assignments).  ``capacity``/``num_experts`` are static.
    """

    expert_idx: jnp.ndarray  # [K, T] int32
    slot: jnp.ndarray        # [K, T] int32
    keep: jnp.ndarray        # [K, T] bool
    gate: jnp.ndarray        # [K, T] f32
    capacity: int
    num_experts: int


jax.tree_util.register_pytree_node(
    GateIndices,
    lambda g: ((g.expert_idx, g.slot, g.keep, g.gate),
               (g.capacity, g.num_experts)),
    lambda aux, leaves: GateIndices(*leaves, *aux))


def _gating_core(logits: jnp.ndarray, k: int, capacity: int,
                 noise_rng: Optional[jax.Array],
                 noisy_gate_policy: Optional[str],
                 drop_tokens: bool,
                 rts_rng: Optional[jax.Array]) -> Dict[str, Any]:
    """The one top-k routing computation both output forms are built from.

    Returns the raw pieces: softmax ``gates``, per-choice one-hot ``masks``
    (post capacity filter when ``drop_tokens``), ``positions`` (slot within
    the chosen expert), ``within`` (slot < capacity), expert ``idxs``,
    renormalized per-choice ``gate_k`` weights, ``l_aux`` and the
    pre-``drop_rate`` metadata.
    """
    if k not in (1, 2):
        raise ValueError(f"k must be 1 or 2, got {k}")
    T, E = logits.shape
    C = capacity

    route_logits = logits
    if noisy_gate_policy == "RSample" and noise_rng is not None:
        route_logits = logits + jax.random.normal(noise_rng, logits.shape,
                                                  logits.dtype)
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # [T, E]

    idx1 = jnp.argmax(route_logits, axis=-1)  # [T]
    mask1 = _one_hot(idx1, E)

    # load-balancing aux loss (Switch eq.4 / reference l_aux): E·Σ me·ce
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    l_aux = jnp.sum(me * ce) * E

    masks = [mask1]
    idxs = [idx1]
    if k == 2:
        logits2 = jnp.where(mask1.astype(bool), -jnp.inf, route_logits)
        idx2 = jnp.argmax(logits2, axis=-1)
        masks.append(_one_hot(idx2, E))
        idxs.append(idx2)

    # capacity priority order over tokens: arrival order by default;
    # random-token-selection (reference use_rts) shuffles it so overflow
    # drops a uniform sample instead of always the tail — deterministic
    # under the passed rng
    perm = inv = None
    if rts_rng is not None:
        perm = jax.random.permutation(rts_rng, T)
        inv = jnp.zeros_like(perm).at[perm].set(
            jnp.arange(T, dtype=perm.dtype))

    # positions within each expert: running count over tokens (in priority
    # order), per choice (second choices queue behind ALL first choices —
    # reference behavior)
    positions = []
    offset = jnp.zeros((E,), jnp.float32)
    for m in masks:
        mp = m[perm] if perm is not None else m
        loc = jnp.cumsum(mp, axis=0) - mp + offset[None, :]
        offset = offset + jnp.sum(mp, axis=0)
        pos = jnp.sum(loc * mp, axis=-1)  # [T] slot in priority order
        positions.append(pos[inv] if inv is not None else pos)

    exp_counts = jnp.sum(masks[0], axis=0)  # pre-drop assignment counts

    # routing telemetry from the PRE-capacity state: per-expert load share,
    # gating entropy over the mean softmax (collapse detector — ln(E) is
    # uniform, → 0 as the router funnels everything to one expert), and the
    # fraction of assignments that overflowed their expert's capacity
    load = exp_counts / jnp.maximum(jnp.float32(T), 1.0)
    entropy = -jnp.sum(me * jnp.log(jnp.maximum(me, 1e-9)))
    assigned = sum(jnp.sum(m) for m in masks)
    overflowed = sum(jnp.sum(m * (pos >= C).astype(m.dtype)[:, None])
                     for m, pos in zip(masks, positions))
    overflow_frac = overflowed / jnp.maximum(assigned, 1.0)

    within = [(pos < C) for pos in positions]

    # capacity-filter masks BEFORE renormalizing (reference top2gating order:
    # a token whose 2nd choice is dropped keeps FULL weight on its 1st)
    if drop_tokens:
        masks = [m * w.astype(m.dtype)[:, None]
                 for m, w in zip(masks, within)]

    denom = sum(jnp.sum(gates * m, axis=-1) for m in masks)
    denom = jnp.maximum(denom, 1e-9)
    gate_k = [jnp.sum(gates * m, axis=-1) / denom for m in masks]

    meta = GateMeta({"l_aux": l_aux, "exp_counts": exp_counts,
                     "load": load, "entropy": entropy,
                     "overflow_frac": overflow_frac})
    return dict(gates=gates, masks=masks, positions=positions,
                within=within, idxs=idxs, gate_k=gate_k, l_aux=l_aux,
                meta=meta, T=T, E=E, C=C, k=k)


def top_k_gating(logits: jnp.ndarray, k: int, capacity: int,
                 noise_rng: Optional[jax.Array] = None,
                 noisy_gate_policy: Optional[str] = None,
                 drop_tokens: bool = True,
                 rts_rng: Optional[jax.Array] = None,
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, Dict[str, jnp.ndarray]]:
    """GShard-style top-k gating over ``[T, E]`` router logits.

    Returns ``(combine_weights [T,E,C], dispatch_mask [T,E,C] bool,
    l_aux, metadata)``.  k ∈ {1, 2} (reference supports exactly these).
    ``rts_rng`` switches capacity overflow to random-token-selection.
    """
    core = _gating_core(logits, k, capacity, noise_rng, noisy_gate_policy,
                        drop_tokens, rts_rng)
    T, E, C = core["T"], core["E"], core["C"]

    combine = jnp.zeros((T, E, C), jnp.float32)
    dispatch = jnp.zeros((T, E, C), bool)
    for m, pos, g in zip(core["masks"], core["positions"], core["gate_k"]):
        # out-of-range pos rows one-hot to all-zero, but m is already zero
        # there after the capacity filter
        pos_oh = _one_hot(pos.astype(jnp.int32), C + 1)[:, :C]
        contrib = m[:, :, None] * pos_oh[:, None, :]
        combine = combine + g[:, None, None] * contrib
        dispatch = dispatch | (contrib > 0)

    meta = core["meta"]
    meta["drop_rate"] = 1.0 - jnp.sum(combine > 0) / jnp.maximum(k * T, 1)
    return combine, dispatch, core["l_aux"], meta


def top_k_gating_indices(logits: jnp.ndarray, k: int, capacity: int,
                         noise_rng: Optional[jax.Array] = None,
                         noisy_gate_policy: Optional[str] = None,
                         drop_tokens: bool = True,
                         rts_rng: Optional[jax.Array] = None,
                         ) -> Tuple[GateIndices, jnp.ndarray, Dict[str, jnp.ndarray]]:
    """:func:`top_k_gating` lowered to index form — same routing decision
    (one shared core), returned as ``(GateIndices, l_aux, meta)`` for the
    sparse dispatch path in ``ops.pallas.moe_dispatch``."""
    core = _gating_core(logits, k, capacity, noise_rng, noisy_gate_policy,
                        drop_tokens, rts_rng)
    T, E, C, kk = core["T"], core["E"], core["C"], core["k"]

    expert_idx = jnp.stack([i.astype(jnp.int32) for i in core["idxs"]])
    slot = jnp.stack([p.astype(jnp.int32) for p in core["positions"]])
    # an assignment lands iff its (possibly filtered) mask row is live AND
    # its slot is within capacity — exactly the dense contrib support
    keep = jnp.stack([(jnp.sum(m, axis=-1) > 0) & w
                      for m, w in zip(core["masks"], core["within"])])
    gate = jnp.stack(core["gate_k"])

    meta = core["meta"]
    kept = sum(jnp.sum((g > 0) & kp)
               for g, kp in zip(core["gate_k"], keep))
    meta["drop_rate"] = 1.0 - kept / jnp.maximum(kk * T, 1)
    gi = GateIndices(expert_idx=expert_idx, slot=slot, keep=keep,
                     gate=gate, capacity=C, num_experts=E)
    return gi, core["l_aux"], meta


def top_k_routing(wg: jnp.ndarray, x: jnp.ndarray, k: int,
                  renormalize: bool = False, scoring: str = "softmax",
                  choice_bias: Optional[jnp.ndarray] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, Dict[str, Any]]:
    """Dropless top-k routing for any ``k``: ``x [T, H]``, ``wg [H, E]`` →
    ``(expert_idx [T, k] int32, weights [T, k] float32, meta)``.

    The router product is accumulated in float32 and the scores over all
    ``E`` experts are float32: their ``softmax`` (OLMoE, Mixtral) or, each
    expert for itself, their ``sigmoid`` (the ``scoring_func`` of the
    DeepSeek-V3 line of routers).  The ``k`` experts are the largest of
    ``scores + choice_bias`` (``noaux_tc``: one learned bias an expert that
    moves the CHOICE only; None: the scores alone); the ``k`` weights are
    the chosen experts' scores as they are, or divided by their sum where
    ``renormalize`` (the published ``norm_topk_prob``).  No capacity: every
    assignment is computed, whatever else the batch holds, so
    ``drop_rate`` is 0 by construction.  ``l_aux`` is the load-balancing
    loss of the sparse-expert decoders (``E · Σ_e f_e · P_e`` with ``f_e``
    the assignments an expert gets per token and ``P_e`` its mean score)."""
    T, E = x.shape[0], wg.shape[1]
    logits = jnp.einsum("th,he->te", x, wg.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    if scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
    elif scoring == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"scoring: 'softmax' or 'sigmoid', not {scoring!r}")
    if choice_bias is None:
        weights, expert_idx = jax.lax.top_k(probs, k)
    else:
        _, expert_idx = jax.lax.top_k(
            probs + choice_bias.astype(jnp.float32)[None, :], k)
        weights = jnp.take_along_axis(probs, expert_idx, axis=-1)
    if renormalize:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    counts = jnp.sum(_one_hot(expert_idx.reshape(-1), E), axis=0)
    me = jnp.mean(probs, axis=0)
    zero = jnp.float32(0.0)
    meta = GateMeta({
        "l_aux": jnp.sum(counts / max(T, 1) * me) * E,
        "exp_counts": counts, "load": counts / max(T * k, 1),
        "entropy": -jnp.sum(me * jnp.log(jnp.maximum(me, 1e-9))),
        "overflow_frac": zero, "drop_rate": zero,
        "assignments": jnp.float32(T * k),
        "experts_active": jnp.sum(counts > 0).astype(jnp.float32)})
    return expert_idx.astype(jnp.int32), weights, meta


@dataclasses.dataclass
class TopKGate:
    """Router config + params-free apply (reference ``TopKGate`` ctor keys).

    The router projection weight lives in the caller's param pytree
    (``wg: [H, E]``) — functional style, no hidden state.  When a mesh is
    known, :meth:`capacity` auto-pads to the next multiple of the expert
    axis size so downstream expert-axis sharding never silently drops
    (``pad_to_ep=False`` restores the raw reference formula).
    """

    num_experts: int
    k: int = 1
    capacity_factor: float = 1.0
    eval_capacity_factor: float = 1.0
    min_capacity: int = 4
    noisy_gate_policy: Optional[str] = None
    drop_tokens: bool = True
    use_rts: bool = False
    pad_to_ep: bool = True
    mesh: Optional[Any] = None

    def _ep_size(self) -> int:
        if self.mesh is None:
            return 1
        try:
            return int(dict(self.mesh.shape).get(AXIS_EXPERT, 1))
        except Exception:
            return 1

    def capacity(self, num_tokens: int, train: bool = True) -> int:
        f = self.capacity_factor if train else self.eval_capacity_factor
        cap = int(np.ceil(self.k * num_tokens * f / self.num_experts))
        cap = max(cap, self.min_capacity)
        ep = self._ep_size()
        if self.pad_to_ep and ep > 1:
            cap = int(-(-cap // ep) * ep)  # ceil to next multiple of ep
        return cap

    def _rts_rng(self, noise_rng: Optional[jax.Array],
                 train: bool) -> Optional[jax.Array]:
        if not (self.use_rts and train) or noise_rng is None:
            return None
        # decorrelate from the RSample noise draw
        return jax.random.fold_in(noise_rng, 0x5eed)

    def __call__(self, wg: jnp.ndarray, x: jnp.ndarray, train: bool = True,
                 noise_rng: Optional[jax.Array] = None):
        """x: [T, H] tokens → gating tensors (see :func:`top_k_gating`)."""
        logits = x.astype(jnp.float32) @ wg.astype(jnp.float32)
        return top_k_gating(logits, self.k, self.capacity(x.shape[0], train),
                            noise_rng=noise_rng,
                            noisy_gate_policy=self.noisy_gate_policy
                            if train else None,
                            drop_tokens=self.drop_tokens,
                            rts_rng=self._rts_rng(noise_rng, train))

    def route(self, wg: jnp.ndarray, x: jnp.ndarray, train: bool = True,
              noise_rng: Optional[jax.Array] = None
              ) -> Tuple[GateIndices, jnp.ndarray, Dict[str, jnp.ndarray]]:
        """Index-form twin of :meth:`__call__` (sparse dispatch path)."""
        logits = x.astype(jnp.float32) @ wg.astype(jnp.float32)
        return top_k_gating_indices(
            logits, self.k, self.capacity(x.shape[0], train),
            noise_rng=noise_rng,
            noisy_gate_policy=self.noisy_gate_policy if train else None,
            drop_tokens=self.drop_tokens,
            rts_rng=self._rts_rng(noise_rng, train))


class MOELayer:
    """Expert-parallel MoE layer (reference ``MOELayer`` [K]).

    ``expert_fn(expert_params, x)`` maps ``[E, C, H] → [E, C, H]`` with
    expert-stacked params (leading dim E).  Experts shard over the ``expert``
    mesh axis; the tokens→experts transition (einsum on the dense rung,
    gather on the sparse rungs) IS the all-to-all under GSPMD.

    ``dispatch_impl``: ``auto`` | ``dense`` | ``sparse`` | ``pallas`` —
    see :func:`~..ops.pallas.moe_dispatch.choose_dispatch_impl`.
    """

    def __init__(self, gate: TopKGate,
                 expert_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
                 mesh: Optional[Mesh] = None,
                 dispatch_impl: str = "auto"):
        self.gate = gate
        self.expert_fn = expert_fn
        self.mesh = mesh
        if gate.mesh is None:
            gate.mesh = mesh  # capacity auto-pad sees the expert axis
        self.dispatch_impl = dispatch_impl
        self._warned_dropped = False

    # ------------------------------------------------------------------

    def _constrain(self, x, *spec):
        """Sharding constraint, skipped per-entry when a dim isn't divisible
        by its axes (standalone small-batch use outside the engine)."""
        if self.mesh is None:
            return x
        shape = dict(self.mesh.shape)

        def size_of(entry):
            axes = entry if isinstance(entry, tuple) else (entry,)
            return int(np.prod([shape[a] for a in axes]))

        entries = [None if e is not None and x.shape[i] % size_of(e) else e
                   for i, e in enumerate(spec)]
        dropped = [(i, e) for i, e in enumerate(spec)
                   if e is not None and entries[i] is None]
        if dropped:
            # a capacity/hidden size that doesn't divide the expert axis
            # silently replicates expert compute — count every occurrence
            # (trace-time events) and log the first
            from ..telemetry import get_telemetry

            get_telemetry().inc_counter(
                "moe/ep_constraint_dropped", float(len(dropped)),
                help="sharding constraints dropped on MoE tensors "
                     "(dim not divisible by mesh axis; EP disabled there)")
            if not self._warned_dropped:
                self._warned_dropped = True
                logger.warning(
                    "MOELayer: dropping sharding constraint(s) %s on shape %s "
                    "(dim not divisible by mesh axis) — expert parallelism is "
                    "DISABLED for this tensor; pad capacity/hidden to a "
                    "multiple of the axis size to restore EP",
                    dropped, tuple(x.shape))
        from ..parallel.mesh import strip_manual_axes

        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, strip_manual_axes(*entries)))

    # ------------------------------------------------------------------

    def _sharded(self) -> bool:
        if self.mesh is None:
            return False
        return int(np.prod(list(dict(self.mesh.shape).values()))) > 1

    def _resolve_impl(self, T: int, E: int, C: int) -> str:
        from ..ops.pallas.moe_dispatch import choose_dispatch_impl

        return choose_dispatch_impl(self.dispatch_impl, T, E, C,
                                    sharded=self._sharded())

    def _register_scratch(self, impl: str, T: int, E: int, C: int, H: int,
                          dtype) -> None:
        from ..ops.pallas.moe_dispatch import dispatch_scratch_bytes
        from ..telemetry.memory.ledger import get_memory_ledger

        ledger = get_memory_ledger()
        if not ledger.enabled:
            return
        item = jnp.dtype(dtype).itemsize
        if impl == "dense":
            # one-hot combine (f32) + dispatch (bool) masks + both buffers
            nbytes = T * E * C * 5 + 2 * E * C * H * item
        else:
            nbytes = dispatch_scratch_bytes(E, C, H, dtype, k=self.gate.k)
        ledger.register("collective_scratch", "moe/dispatch", int(nbytes),
                        tag=impl, transient=True)

    # ------------------------------------------------------------------

    def __call__(self, wg: jnp.ndarray, expert_params: Any, x: jnp.ndarray,
                 train: bool = True, noise_rng: Optional[jax.Array] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, Dict[str, jnp.ndarray]]:
        """x: [B, S, H] → (y [B, S, H], l_aux, metadata)."""
        from ..ops.pallas import moe_dispatch as md

        B, S, H = x.shape
        tokens = x.reshape(B * S, H)
        T, E = B * S, self.gate.num_experts
        C = self.gate.capacity(T, train)
        impl = self._resolve_impl(T, E, C)
        dtype = x.dtype
        self._register_scratch(impl, T, E, C, H, dtype)

        if impl == "dense":
            combine, dispatch, l_aux, meta = self.gate(wg, tokens, train,
                                                       noise_rng)
            # tokens → expert buffers: [E, C, H]; the einsum over T is the
            # all-to-all boundary (tokens sharded over DP, buffers over
            # expert)
            expert_in = jnp.einsum("tec,th->ech",
                                   dispatch.astype(dtype), tokens)
            expert_in = self._constrain(expert_in, AXIS_EXPERT, None, None)
            expert_out = self.expert_fn(expert_params, expert_in)
            expert_out = self._constrain(expert_out, AXIS_EXPERT, None, None)
            y = jnp.einsum("tec,ech->th", combine.astype(dtype), expert_out)
        else:
            gi, l_aux, meta = self.gate.route(wg, tokens, train, noise_rng)
            src_idx, flat_idx = md.routing_to_indices(
                gi.expert_idx, gi.slot, gi.keep, E, C)
            if impl == "pallas":
                expert_in = md.pallas_dispatch(tokens, src_idx)
            else:
                expert_in = md.dispatch_reference(tokens, src_idx)
            expert_in = self._constrain(expert_in, AXIS_EXPERT, None, None)
            expert_out = self.expert_fn(expert_params, expert_in)
            expert_out = self._constrain(expert_out, AXIS_EXPERT, None, None)
            gates_tk = gi.gate.T  # [T, K]
            if impl == "pallas":
                y = md.pallas_combine(expert_out, flat_idx, gates_tk)
            else:
                y = md.combine_reference(expert_out, flat_idx, gates_tk)
            y = y.astype(dtype)

        # static, host-side record of the resolved rung (meta stays a pure
        # array pytree so it can cross the jit boundary)
        self.last_impl = impl
        meta = GateMeta(meta)
        y = self._constrain(y.reshape(B, S, H), DP_AXES, AXIS_SEQ, None)
        return y, l_aux, meta
