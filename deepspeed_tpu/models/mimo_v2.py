"""MiMo-V2-family sparse decoder (``model_type: mimo_v2``; the language
model of ``XiaomiMiMo/MiMo-V2.5``: no vision or audio tower, no MTP
module).  A pre-norm RMSNorm decoder with an untied head whose layers are
of different KINDS, named by two published per-layer lists:

* ``hybrid_layer_pattern`` — attention: ``0`` full causal attention
  (``num_key_value_heads`` KV heads, rotary base ``rope_theta``, plain
  softmax), ``1`` sliding-window attention over the last
  ``sliding_window`` keys (``i − j < window``; ``swa_num_key_value_heads``
  KV heads, base ``swa_rope_theta``) whose softmax has a SINK: one learned
  logit a query head in the denominator, which takes mass and carries no
  value (``add_swa_attention_sink_bias``).  Both: K rows of ``head_dim``
  (192), V rows of ``v_head_dim`` (128) scaled by ``attention_value_scale``,
  rotary (half-split) on the first ``rotary_dim`` of a K/Q row only
  (``partial_rotary_factor``), scores scaled by ``1/sqrt(head_dim)``.
* ``moe_layer_freq`` — FFN: ``0`` a dense SwiGLU of ``intermediate_size``,
  ``1`` routed SwiGLU experts of ``moe_intermediate_size``:
  ``σ = sigmoid_f32(h·Wr)`` over ``n_routed_experts``, the ``top_k`` largest
  of ``σ + c`` chosen (``noaux_tc``: ``c`` a learned bias an expert, for the
  choice only), weights ``σ_e / Σ_chosen σ`` (``norm_topk_prob``); dropless
  (``moe.layer.DroplessMoE``), no shared expert.

**The chip's share** (``held_experts=(first, count)``): under expert
parallelism a chip holds ``count`` of the ``n_routed_experts``; the router
keeps its width and its ``top_k``, the expert leaves hold the share, and the
layer returns the share's part of the result, which goes on to the next
layer as it is (``DroplessMoE(held=…)``).  None: every expert is here.

**The layer plan** (:func:`layer_plan`): the layers are split into LEADING
layers and whole PERIODS of one repeated sequence of kinds (published: the
dense layer 0 and the irregular first group lead, then seven periods of
five window layers and a full one).  Weights: ``leading`` is a list of
single layers; ``layers`` holds one STACK for each kind over the periodic
layers (``full`` / ``window`` attention, ``moe`` / ``mlp`` FFN, the two
norms over all of them), which is what the serving engine scans a period
a step (``inference/v2/adapters.MimoV2Adapter``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .llama import _rms_norm, _rope

FULL, WINDOW = "full", "window"
DENSE, SPARSE = "mlp", "moe"
#: a sparse layer's leaves that are handed to the grouped matmul whole
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


@dataclasses.dataclass(frozen=True)
class MimoV2Config:
    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384          # the dense layers' FFN
    moe_intermediate_size: int = 2048       # one expert's
    num_heads: int = 64
    head_dim: int = 192                     # a K (and Q) row
    v_head_dim: int = 128                   # a V row
    num_kv_heads: int = 4                   # full-attention layers
    swa_num_kv_heads: int = 8               # window layers
    sliding_window: int = 128
    rotary_dim: int = 64                    # int(head_dim * 0.334)
    rope_theta: float = 1e7
    swa_rope_theta: float = 1e4
    value_scale: float = 0.707
    rms_norm_eps: float = 1e-5
    num_experts: int = 256                  # the router's width
    top_k: int = 8
    norm_topk_prob: bool = True
    #: (first, count): the experts this chip holds; None: all of them
    held_experts: Optional[Tuple[int, int]] = None
    #: per layer, 1 = window attention / 1 = routed experts
    attention_pattern: Tuple[int, ...] = (0, 1, 1, 1, 1, 1, 0)
    moe_pattern: Tuple[int, ...] = (0, 1, 1, 1, 1, 1, 1)
    max_seq_len: int = 8192
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if len(self.attention_pattern) != len(self.moe_pattern):
            raise ValueError("attention_pattern and moe_pattern name the "
                             "same layers: they must be equally long")

    @property
    def num_layers(self) -> int:
        return len(self.attention_pattern)

    @property
    def experts_held(self) -> int:
        return self.held_experts[1] if self.held_experts else self.num_experts

    @classmethod
    def tiny(cls, **kw) -> "MimoV2Config":
        """[full+dense, window, window, full] with every mechanism live."""
        d = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                 moe_intermediate_size=32, num_heads=8, head_dim=24,
                 v_head_dim=16, num_kv_heads=2, swa_num_kv_heads=4,
                 sliding_window=8, rotary_dim=8, num_experts=8, top_k=3,
                 attention_pattern=(0, 1, 1, 0), moe_pattern=(0, 1, 1, 1),
                 max_seq_len=256, dtype=jnp.float32)
        d.update(kw)
        return cls(**d)


@dataclasses.dataclass(frozen=True)
class LayerPlan:
    """``leading`` then ``periods`` x ``period``; a layer is the pair
    (attention kind, FFN kind)."""
    leading: Tuple[Tuple[str, str], ...]
    period: Tuple[Tuple[str, str], ...]
    periods: int

    def count(self, kind: str, where: str = "all") -> int:
        """Layers of ``kind`` (an attention or an FFN kind) among the
        ``leading`` layers, in one ``period``, or in ``all``."""
        of = lambda layers: sum(kind in layer for layer in layers)
        if where == "leading":
            return of(self.leading)
        if where == "period":
            return of(self.period)
        return of(self.leading) + self.periods * of(self.period)


def layer_plan(config: MimoV2Config) -> LayerPlan:
    """Split the layers so that as many as possible lie in whole periods:
    for each possible number of leading layers (at least the dense-FFN
    prefix) the rest's smallest period; the split with the most periods
    wins, the fewest leading layers among equals."""
    layers = tuple((WINDOW if a else FULL, SPARSE if m else DENSE)
                   for a, m in zip(config.attention_pattern,
                                   config.moe_pattern))
    n = len(layers)
    dense_prefix = next((i for i, (_, f) in enumerate(layers)
                         if f == SPARSE), n)
    best = None
    for lead in range(min(dense_prefix, n - 1), n):
        rest = layers[lead:]
        period = next(p for p in range(1, len(rest) + 1)
                      if len(rest) % p == 0
                      and rest == rest[:p] * (len(rest) // p))
        if best is None or len(rest) // period > best.periods:
            best = LayerPlan(layers[:lead], rest[:period],
                             len(rest) // period)
    return best


class MimoV2Model:
    """Weights and their layout; the forward pass that serves is the v2
    engine's, through ``MimoV2Adapter``, and :meth:`forward` here is the
    same layers over a whole sequence without a cache."""

    def __init__(self, config: MimoV2Config, mesh: Any = None):
        from ..moe.layer import DroplessMoE

        self.config = config
        self.mesh = mesh
        self.plan = layer_plan(config)
        c = config
        self._moe_layer = DroplessMoE(
            c.num_experts, c.top_k, renormalize=c.norm_topk_prob, mesh=mesh,
            scoring="sigmoid", held=c.held_experts)

    # -- weights -------------------------------------------------------------

    def _attention_shapes(self, kind: str) -> Dict[str, Tuple[int, ...]]:
        c = self.config
        kv = c.swa_num_kv_heads if kind == WINDOW else c.num_kv_heads
        shapes = {"wq": (c.hidden_size, c.num_heads, c.head_dim),
                  "wk": (c.hidden_size, kv, c.head_dim),
                  "wv": (c.hidden_size, kv, c.v_head_dim),
                  "wo": (c.num_heads, c.v_head_dim, c.hidden_size)}
        if kind == WINDOW:
            shapes["sink"] = (c.num_heads,)
        return shapes

    def _ffn_shapes(self, kind: str) -> Dict[str, Tuple[int, ...]]:
        c = self.config
        H = c.hidden_size
        if kind == DENSE:
            I = c.intermediate_size
            return {"w_gate": (H, I), "w_up": (H, I), "w_down": (I, H)}
        E, I = c.experts_held, c.moe_intermediate_size
        return {"wg": (H, c.num_experts), "bias": (c.num_experts,),
                "w_gate": (E, H, I), "w_up": (E, H, I), "w_down": (E, I, H)}

    @staticmethod
    def _draw(key, name: str, shape: Tuple[int, ...]) -> jnp.ndarray:
        """1/sqrt(fan_in) normal weights; the sink logits ~ N(0, 1) and the
        choice bias ~ N(0, 0.01), NOT zero, so that a path that ignores
        either computes something else.  The bias is small because what
        ``noaux_tc`` learns it FOR is an even load: 0.05 at the top-8
        threshold of 256 sigmoid scores is a factor 2.2 in how often an
        expert is chosen, and a share's held experts then had work or
        none as the seed drew it (PERF.md §6, PR 31)."""
        if name == "sink":
            return jax.random.normal(key, shape, jnp.float32)
        if name == "bias":
            return 0.01 * jax.random.normal(key, shape, jnp.float32)
        # rows of [.., H, heads, d], [.., heads, d, H] and [.., in, out]
        if name in ("wq", "wk", "wv"):
            fan_in = shape[-3]
        elif name == "wo":
            fan_in = shape[-3] * shape[-2]
        else:
            fan_in = shape[-2]
        return jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)

    def _group(self, key, shapes, lead: Tuple[int, ...] = ()) -> Dict:
        """One group of leaves.  An EXPERT's down projection (the sparse
        group's ``w_down [E, I, H]``) is drawn ``top_k`` times smaller than
        1/sqrt(fan_in): a layer adds ``top_k`` experts' rows, so one of
        them moves the stream by what 1/top_k of a dense FFN would, as in a
        trained model; at the full scale ONE expert of weight 1/top_k at a
        router's near-tie moved the logits as far as float8 products do
        (PERF.md §6, PR 31)."""
        keys = jax.random.split(key, len(shapes))
        group = {name: self._draw(k, name, lead + shape)
                 for k, (name, shape) in zip(keys, sorted(shapes.items()))}
        if "wg" in shapes:
            group["w_down"] = group["w_down"] / self.config.top_k
        return group

    def init_params(self, rng: jax.Array) -> Dict[str, Any]:
        c, plan = self.config, self.plan
        H = c.hidden_size
        k_embed, k_head, k_lead, k_stack = jax.random.split(rng, 4)
        leading = []
        for key, (attn, ffn) in zip(
                jax.random.split(k_lead, max(len(plan.leading), 1)),
                plan.leading):
            ka, kf = jax.random.split(key)
            group = self._group(kf, self._ffn_shapes(ffn))
            if ffn == SPARSE:   # its experts: a stack of one layer
                group = {n: (w[None] if n.startswith("w_") else w)
                         for n, w in group.items()}
            leading.append({"attn_norm": jnp.ones((H,), jnp.float32),
                            "mlp_norm": jnp.ones((H,), jnp.float32),
                            "attn": self._group(
                                ka, self._attention_shapes(attn)),
                            ffn: group})
        scanned = plan.periods * len(plan.period)
        layers = {"attn_norm": jnp.ones((scanned, H), jnp.float32),
                  "mlp_norm": jnp.ones((scanned, H), jnp.float32)}
        for key, kind in zip(jax.random.split(k_stack, 4),
                             (FULL, WINDOW, DENSE, SPARSE)):
            n = plan.periods * plan.count(kind, "period")
            if n:
                shapes = (self._attention_shapes(kind)
                          if kind in (FULL, WINDOW)
                          else self._ffn_shapes(kind))
                layers[kind] = self._group(key, shapes, (n,))
        return {
            "embed": jax.random.normal(k_embed, (c.vocab_size, H),
                                       jnp.float32),
            "leading": leading, "layers": layers,
            "final_norm": jnp.ones((H,), jnp.float32),
            "lm_head": jax.random.normal(k_head, (H, c.vocab_size),
                                         jnp.float32) / np.sqrt(H)}

    def _head(self, params: Any) -> jnp.ndarray:
        return params["lm_head"]

    # -- the layer's two halves (shared with the serving adapter) ------------

    def kv_heads(self, kind: str) -> int:
        c = self.config
        return c.swa_num_kv_heads if kind == WINDOW else c.num_kv_heads

    def theta(self, kind: str) -> float:
        c = self.config
        return c.swa_rope_theta if kind == WINDOW else c.rope_theta

    def qkv(self, lp: Any, x: jnp.ndarray, positions: jnp.ndarray,
            kind: str) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """``x [N, H]`` at ``positions [N]`` → q ``[N, h, 192]``, k ``[N,
        kv, 192]`` (rotary on the first ``rotary_dim``), v ``[N, kv, 128]``
        scaled."""
        c = self.config
        dt = c.dtype
        a = lp["attn"]
        h = _rms_norm(x, lp["attn_norm"].astype(dt), c.rms_norm_eps)
        q = jnp.einsum("nH,Hhd->nhd", h, a["wq"].astype(dt))
        k = jnp.einsum("nH,Hhd->nhd", h, a["wk"].astype(dt))
        v = jnp.einsum("nH,Hhd->nhd", h, a["wv"].astype(dt))
        v = (v.astype(jnp.float32) * c.value_scale).astype(dt)
        r, theta = c.rotary_dim, self.theta(kind)

        def rotary(t):
            return jnp.concatenate(
                [_rope(t[..., :r], positions, theta), t[..., r:]], axis=-1)

        return rotary(q), rotary(k), v

    def ffn(self, lp: Any, h: jnp.ndarray, stacks: Any = None
            ) -> jnp.ndarray:
        """``h [N, H]`` (normed) → ``[N, H]``.  A sparse layer cut out of
        the stacks (:meth:`period_layers`) carries ``expert_layer`` and its
        experts are read where they lie in ``stacks`` (``params["layers"]``:
        the three expert leaves whole); a leading sparse layer holds its
        own, a stack of one."""
        dt = self.config.dtype
        if DENSE in lp:
            m = lp[DENSE]
            act = jax.nn.silu(h @ m["w_gate"].astype(dt)) \
                * (h @ m["w_up"].astype(dt))
            return act @ m["w_down"].astype(dt)
        m = lp[SPARSE]
        experts, layer = (stacks[SPARSE], lp["expert_layer"]) \
            if "expert_layer" in lp else (m, 0)
        y, _, meta = self._moe_layer(
            m["wg"], {n: experts[n] for n in EXPERT_LEAVES}, h[None],
            layer=layer, choice_bias=m["bias"])
        from ..telemetry import numerics

        numerics.moe_stats(meta)
        return y[0]

    def post_attn(self, lp: Any, x: jnp.ndarray, attn: jnp.ndarray,
                  stacks: Any = None) -> jnp.ndarray:
        c = self.config
        dt = c.dtype
        x = x + jnp.einsum("nhd,hdH->nH", attn,
                           lp["attn"]["wo"].astype(dt))
        h = _rms_norm(x, lp["mlp_norm"].astype(dt), c.rms_norm_eps)
        return x + self.ffn(lp, h, stacks)

    # -- the stacks, a period at a time --------------------------------------

    def stacks_by_period(self, params: Any) -> Any:
        """Each stack as ``[periods, layers of its kind a period, …]``:
        what a scan over the periods slices.  The expert leaves are left
        out: they are read whole (a scan's slice of them would be copied
        for the grouped matmul, 805 MB a layer at OLMoE's widths)."""
        stacks = dict(params["layers"])
        if SPARSE in stacks:
            stacks[SPARSE] = {n: w for n, w in stacks[SPARSE].items()
                              if n not in EXPERT_LEAVES}
        return jax.tree.map(
            lambda w: w.reshape((self.plan.periods, -1) + w.shape[1:]),
            stacks)

    def period_layers(self, pp: Any, p: Any) -> List[Any]:
        """The ``lp`` of each layer of period ``p`` out of the period's
        slice ``pp`` of :meth:`stacks_by_period`, in order."""
        plan = self.plan
        at: Dict[str, int] = {}
        out = []
        for j, (attn, ffn) in enumerate(plan.period):
            a, f = at.get(attn, 0), at.get(ffn, 0)
            lp = {"attn_norm": pp["attn_norm"][j],
                  "mlp_norm": pp["mlp_norm"][j],
                  "attn": jax.tree.map(lambda w: w[a], pp[attn]),
                  ffn: jax.tree.map(lambda w: w[f], pp[ffn])}
            if ffn == SPARSE:
                # where this layer's experts lie in the whole stacks
                lp["expert_layer"] = p * plan.count(SPARSE, "period") + f
            out.append(lp)
            at[attn], at[ffn] = a + 1, f + 1
        return out

    # -- a whole sequence, no cache ------------------------------------------

    def layer_list(self, params: Any) -> List[Tuple[str, Any]]:
        """Every layer in order as ``(attention kind, lp)``: the leading
        ones, then each period's."""
        plan = self.plan
        out = [(attn, lp)
               for (attn, _), lp in zip(plan.leading, params["leading"])]
        by_period = self.stacks_by_period(params)
        for p in range(plan.periods):
            pp = jax.tree.map(lambda w: w[p], by_period)
            out += [(attn, lp) for (attn, _), lp in
                    zip(plan.period, self.period_layers(pp, p))]
        return out

    def forward(self, params: Any, input_ids: jnp.ndarray) -> jnp.ndarray:
        """``[B, S]`` ids → float32 logits ``[B, S, V]``."""
        c = self.config
        dt = c.dtype

        def one(ids):
            S = ids.shape[0]
            pos = jnp.arange(S)
            x = jnp.take(params["embed"].astype(dt), ids, axis=0)
            for attn, lp in self.layer_list(params):
                q, k, v = self.qkv(lp, x, pos, attn)
                rep = c.num_heads // self.kv_heads(attn)
                k, v = (jnp.repeat(t, rep, axis=1) for t in (k, v))
                s = jnp.einsum("qhd,khd->hqk", q, k).astype(jnp.float32) \
                    / np.sqrt(c.head_dim)
                seen = pos[None, :] <= pos[:, None]
                if attn == WINDOW:
                    seen &= pos[:, None] - pos[None, :] < c.sliding_window
                s = jnp.where(seen[None], s, -1e30)
                if "sink" in lp["attn"]:
                    beside = jnp.broadcast_to(
                        lp["attn"]["sink"].astype(jnp.float32)[:, None, None],
                        s.shape[:2] + (1,))
                    s = jnp.concatenate([s, beside], axis=-1)
                p = jax.nn.softmax(s, axis=-1)[..., :S].astype(dt)
                out = jnp.einsum("hqk,khd->qhd", p, v)
                x = self.post_attn(lp, x, out, params["layers"])
            x = _rms_norm(x, params["final_norm"].astype(dt), c.rms_norm_eps)
            return (x @ params["lm_head"].astype(dt)).astype(jnp.float32)

        return jax.lax.map(one, input_ids)    # ragged_dot has no vmap
