"""MoE wrapper with the reference ctor surface (``deepspeed/moe/layer.py:MoE``
[K]: hidden_size, expert, num_experts, ep_size, k, capacity_factor,
eval_capacity_factor, min_capacity, noisy_gate_policy, drop_tokens,
enable_expert_tensor_parallelism).

TPU adaptation: ``expert`` is a functional ``(params, [E,C,H]) → [E,C,H]``
callable (or None for the built-in SwiGLU expert); params live in the
caller's pytree with expert-stacked leading dim E, sharded over the
``expert`` mesh axis by ``param_specs``.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ..parallel.mesh import AXIS_EXPERT, AXIS_TENSOR
from ..utils import groups as groups_mod
from .sharded_moe import MOELayer, TopKGate

P = PartitionSpec


def swiglu_expert_fn(params: Any, x: jnp.ndarray,
                     constrain_act: Optional[Callable] = None) -> jnp.ndarray:
    """Default expert: SwiGLU FFN with expert-stacked params
    ``{w_gate [E,H,I], w_up [E,H,I], w_down [E,I,H]}``.  ``constrain_act``
    optionally pins the inner activation's sharding (expert-TP)."""
    dt = x.dtype
    gate = jnp.einsum("ech,ehi->eci", x, params["w_gate"].astype(dt))
    up = jnp.einsum("ech,ehi->eci", x, params["w_up"].astype(dt))
    act = jax.nn.silu(gate) * up
    if constrain_act is not None:
        act = constrain_act(act)
    return jnp.einsum("eci,eih->ech", act, params["w_down"].astype(dt))


#: an expert's FORM: its leaves, and the grouped call of its up projection
#: (``ops/pallas/moe_grouped_matmul``); every form ends in the plain
#: grouped matmul over ``w_down``
EXPERT_FORMS = {
    # three matrices: silu(x·w_gate) ⊙ (x·w_up), then w_down
    "swiglu": (("w_gate", "w_up"), "grouped_swiglu"),
    # two matrices: relu(x·w_up)², then w_down
    "relu2": (("w_up",), "grouped_relu2"),
}


class DroplessMoE:
    """Top-k routed experts with no capacity: every assignment is
    computed (``sharded_moe.top_k_routing``), on the sorted layout of
    ``ops/pallas/moe_grouped_matmul`` (tokens gathered by expert, one
    grouped matmul for gate/up and one for down, the weighted sum back).
    Same call as :class:`~.sharded_moe.MOELayer`:
    ``(wg [H, E], {w_gate, w_up [E, H, I], w_down [E, I, H]}, x [B, S, H])
    → (y, l_aux, meta)``.  The kernels run on an unsharded TPU; anywhere else
    (and under a mesh of several devices, where a Mosaic call does not
    partition itself) the same layout runs ``jax.lax.ragged_dot``.

    The grouped matmul has one form, the stacked layers' weights plus a
    layer's index, and this is where a caller chooses.  ``layer=None``: the
    three leaves are one layer's, as a scan over the layers hands them
    (``models/llama.py``'s training scan), and become a stack of one here,
    ``w[None]`` at layer 0: free for a value the scan has sliced already.
    ``layer=l``: the leaves are the whole ``[L, E, …]`` stacks and the
    kernels read layer ``l``'s experts where they lie.  A serving program
    that let its layer scan slice them would copy every layer's experts
    for the custom call (the v2 engine's ``OlmoeV2Adapter`` keeps them out
    of the scan for that reason); ``wg`` is one layer's either way.

    ``form`` (:data:`EXPERT_FORMS`): gated experts of three matrices (the
    default) or experts of two with ``relu(·)²`` between.  The call's
    ``rows [B, S, w]`` are what the experts multiply where that is not what
    the router reads (a LatentMoE layer routes on ``x [B, S, H]`` and its
    experts work on a projection of it, ``w`` wide: their leaves are ``[E,
    w, I]`` / ``[E, I, w]`` and ``y`` is ``[B, S, w]``); None: ``x``.

    ``scoring`` and the call's ``choice_bias`` are the router's
    (``top_k_routing``).  ``held=(first, count)``: this chip's share under
    expert parallelism.  The router keeps its width ``num_experts`` and its
    ``k``; the expert leaves hold ``count`` experts, numbers ``first`` ..
    ``first + count − 1`` of the router's; only the assignments to those
    are planned and computed, and ``y`` is THEIR part of the layer's
    result (the shares' parts add up to the whole layer's).  Nothing
    stands in for the other chips or the exchange with them.  ``meta``
    then counts ``assignments`` and ``experts_active`` of the share, and
    ``assignments_routed`` is the router's rows x k.

    ``meta`` also counts ``rows_computed``, the rows of the tiles in use
    that the grouped matmuls multiply (``assignments`` over it is how full
    they are); the tile itself is static and stays on the layer,
    ``last_tile_rows``, as the last call traced was built."""

    def __init__(self, num_experts: int, k: int, renormalize: bool = False,
                 mesh: Any = None, scoring: str = "softmax",
                 held: Optional[Tuple[int, int]] = None,
                 form: str = "swiglu"):
        if form not in EXPERT_FORMS:
            raise ValueError(f"form: one of {sorted(EXPERT_FORMS)}, not "
                             f"{form!r}")
        self.form = form
        self.num_experts = num_experts
        self.k = k
        self.renormalize = renormalize
        self.mesh = mesh
        self.scoring = scoring
        if held is not None and not (
                0 <= held[0] and held[1] >= 1
                and held[0] + held[1] <= num_experts):
            raise ValueError(f"held=(first, count)={held} is no share of "
                             f"{num_experts} experts")
        self.held = held
        #: rows in a tile of the last call traced (``tile_rows_for``)
        self.last_tile_rows: Optional[int] = None

    def __call__(self, wg: jnp.ndarray, expert_params: Any, x: jnp.ndarray,
                 layer: Any = None, choice_bias: Optional[jnp.ndarray] = None,
                 rows: Optional[jnp.ndarray] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, Any]:
        from ..ops.pallas import moe_grouped_matmul as gm
        from .sharded_moe import top_k_routing

        if layer is None:
            expert_params = {name: w[None]
                             for name, w in expert_params.items()}
            layer = 0
        B, S, H = x.shape
        tokens = x.reshape(B * S, H)
        with jax.named_scope("moe/router"):
            expert_idx, weights, meta = top_k_routing(
                wg, tokens, self.k, self.renormalize, self.scoring,
                choice_bias)
        # a tile follows the group the ROUTER expects an expert to get,
        # rows x k / its experts, wherever the experts live: a held
        # expert's group is no larger for being one of a share
        self.last_tile_rows = tile_rows = gm.tile_rows_for(
            B * S * self.k, self.num_experts, x.dtype)
        if self.held is None:
            plan = gm.plan_groups(expert_idx, self.num_experts, tile_rows)
        else:
            # this chip's share: experts first .. first + count - 1 are
            # groups 0 .. count - 1, the others' assignments get no row
            # and no weight; what CAN land here sizes the plan's static
            # count of tiles (``plan_groups``), not the tile
            first, count = self.held
            local = expert_idx - first
            here = (local >= 0) & (local < count)
            weights = jnp.where(here, weights, 0.0)
            plan = gm.plan_groups(local, count, tile_rows, share=True)
            meta = type(meta)(
                meta, assignments_routed=meta["assignments"],
                assignments=jnp.sum(plan.group_sizes).astype(jnp.float32),
                experts_active=jnp.sum(plan.group_sizes > 0
                                       ).astype(jnp.float32))
        meta = type(meta)(meta, rows_computed=(
            plan.num_tiles[0] * tile_rows).astype(jnp.float32))
        sharded = self.mesh is not None and self.mesh.size > 1
        fed = tokens if rows is None else rows.reshape(B * S, -1)
        leaves, up = EXPERT_FORMS[self.form]
        act = getattr(gm, up)(
            gm.gather_rows(fed, plan), *(expert_params[n] for n in leaves),
            layer, plan, sharded=sharded)
        out = gm.grouped_matmul(act, expert_params["w_down"], layer, plan,
                                sharded=sharded)
        y = gm.combine_rows(out, plan, weights).astype(x.dtype)
        return y.reshape(B, S, -1), meta["l_aux"], meta


class MoE:
    """Reference-shaped MoE block."""

    def __init__(self, hidden_size: int,
                 expert: Optional[Callable[[Any, jnp.ndarray], jnp.ndarray]] = None,
                 num_experts: int = 1, ep_size: int = 1, k: int = 1,
                 capacity_factor: float = 1.0, eval_capacity_factor: float = 1.0,
                 min_capacity: int = 4, use_residual: bool = False,
                 noisy_gate_policy: Optional[str] = None,
                 drop_tokens: bool = True, use_rts: bool = True,
                 use_tutel: bool = False,
                 enable_expert_tensor_parallelism: bool = False,
                 mesh: Any = None, dispatch_impl: str = "auto"):
        if num_experts % max(ep_size, 1):
            raise ValueError(
                f"num_experts({num_experts}) % ep_size({ep_size}) != 0")
        if use_tutel:
            raise ValueError(
                "use_tutel is not supported on the TPU port: Tutel's fused "
                "dispatch kernels are CUDA-only — the equivalent fast path "
                "here is the Pallas sparse dispatch (dispatch_impl='pallas' "
                "or 'auto'); pass use_tutel=False")
        self.hidden_size = hidden_size
        self.num_experts = num_experts
        self.ep_size = ep_size
        self.use_residual = use_residual
        self.enable_expert_tensor_parallelism = enable_expert_tensor_parallelism
        self.gate = TopKGate(num_experts=num_experts, k=k,
                             capacity_factor=capacity_factor,
                             eval_capacity_factor=eval_capacity_factor,
                             min_capacity=min_capacity,
                             noisy_gate_policy=noisy_gate_policy,
                             drop_tokens=drop_tokens,
                             use_rts=use_rts)
        try:
            mesh = mesh if mesh is not None else groups_mod.get_mesh()
        except Exception:
            mesh = None
        self.moe_layer = MOELayer(self.gate, expert or swiglu_expert_fn,
                                  mesh=mesh, dispatch_impl=dispatch_impl)

    # ------------------------------------------------------------------

    def init_params(self, rng: jax.Array, intermediate_size: int) -> Any:
        """Params for the built-in SwiGLU expert + router (+ the residual
        dense MLP and 2-way mixing coefficient when ``use_residual``)."""
        E, H, I = self.num_experts, self.hidden_size, intermediate_size
        k1, k2, k3, k4, k5, k6, k7, k8 = jax.random.split(rng, 8)
        import numpy as np

        def normal(key, shape, fan_in):
            return (jax.random.normal(key, shape, jnp.float32)
                    / np.sqrt(fan_in))

        params = {
            "wg": normal(k1, (H, E), H),
            "experts": {
                "w_gate": normal(k2, (E, H, I), H),
                "w_up": normal(k3, (E, H, I), H),
                "w_down": normal(k4, (E, I, H), I),
            },
        }
        if self.use_residual:
            params["residual_mlp"] = {
                "w_gate": normal(k5, (1, H, I), H),
                "w_up": normal(k6, (1, H, I), H),
                "w_down": normal(k7, (1, I, H), I),
            }
            params["coefficient"] = normal(k8, (H, 2), H)
        return params

    def param_specs(self) -> Any:
        """Expert-stacked dims shard over the ``expert`` axis (+ optional TP
        on the FFN inner dim — reference enable_expert_tensor_parallelism)."""
        t = AXIS_TENSOR if self.enable_expert_tensor_parallelism else None
        specs = {
            "wg": P(None, None),
            "experts": {
                "w_gate": P(AXIS_EXPERT, None, t),
                "w_up": P(AXIS_EXPERT, None, t),
                "w_down": P(AXIS_EXPERT, t, None),
            },
        }
        if self.use_residual:
            specs["residual_mlp"] = {
                "w_gate": P(None, None, t),
                "w_up": P(None, None, t),
                "w_down": P(None, t, None),
            }
            specs["coefficient"] = P(None, None)
        return specs

    def __call__(self, params: Any, x: jnp.ndarray, train: bool = True,
                 noise_rng: Optional[jax.Array] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, Any]:
        """x: [B, S, H] → (y, l_aux, meta).

        ``meta`` is the FULL gate metadata (``l_aux``, ``exp_counts``,
        ``drop_rate``, ``load``, ``entropy``, ``overflow_frac``) so callers
        can feed the telemetry plane without re-deriving.  Back-compat: the
        tuple slot historically carried bare ``exp_counts`` —
        :class:`~.sharded_moe.GateMeta.__array__` keeps
        ``np.asarray(meta)`` meaning exactly that.
        """
        y, l_aux, meta = self.moe_layer(params["wg"], params["experts"], x,
                                        train=train, noise_rng=noise_rng)
        if self.use_residual:
            # reference Residual-MoE (moe/layer.py [K]): a dense MLP runs in
            # parallel and a learned 2-way softmax coefficient mixes the two
            dense = swiglu_expert_fn(params["residual_mlp"],
                                     x.reshape(1, -1, x.shape[-1]))
            dense = dense.reshape(x.shape)
            coef = jax.nn.softmax(
                jnp.einsum("...h,hc->...c", x,
                           params["coefficient"].astype(x.dtype)), axis=-1)
            y = y * coef[..., 0:1] + dense * coef[..., 1:2]
        return y, l_aux, meta
