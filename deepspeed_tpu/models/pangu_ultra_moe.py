"""openPangu-Ultra-MoE-family sparse decoder (``model_type:
pangu_ultra_moe``; the language model of ``openPangu-Ultra-MoE-718B``: no
multi-token-prediction module).  Its keys are the DeepSeek-V3 line's but
one, ``sandwich_norm``: an RMSNorm on each sub-layer's OUTPUT as well as on
its input, four a layer.  ``N`` is RMSNorm with a learned weight:

* attention, latent (MLA): ``h = N_in(x)``; queries through a bottleneck
  with a norm inside it, ``c_q = N_q(h W_dq)``, ``q = c_q W_uq`` → heads of
  ``[q_nope | q_rope]``; keys and values from ONE compressed vector a
  token, ``[c_kv | k_rope] = h W_dkv``, ``c_kv ← N_kv(c_kv)``,
  ``k_nope = c_kv W_uk`` and ``v = c_kv W_uv`` a head (the published
  ``kv_b_proj`` is ``[W_uk | W_uv]`` a head; held here as two leaves);
  rotary (half-split) on each head's ``q_rope`` and on the one ``k_rope`` a
  token, which every head shares; scores ``q·[k_nope | k_rope] /
  sqrt(nope + rope)``, causal; ``x ← x + N_post_attn(concat(P v) W_o)``.
* FFN: ``h = N_pre_mlp(x)``; the first ``first_k_dense`` layers a dense
  SwiGLU of ``intermediate_size``; the others ``σ = sigmoid_f32(h·Wr)``
  over ``num_experts``, the ``top_k`` largest chosen (no choice bias, no
  groups), weights ``σ_e / Σ_chosen σ × routed_scaling_factor``, and
  ``y = Σ w_e E_e(h) + E_shared(h)``: every expert a SwiGLU of
  ``moe_intermediate_size``, the shared one beside the routed ones and
  unscaled; ``x ← x + N_post_mlp(y)``.

**Absorbed attention** (:meth:`PanguUltraMoeModel.qkv`,
:meth:`post_attn`: what the serving engine runs for decode rows and chunk
rows alike).  ``q_nope·k_nope = (q_nope W_ukᵀ)·c_kv``, so a token's cache
row is ``[N_kv(c_kv) | rot(k_rope)]``, ``kv_lora_rank + rope`` numbers and
ONE a layer whatever the number of heads; a query is ``[q_nope W_ukᵀ |
rot(q_rope)]`` a head; the attention's output is ``P·row[:kv_lora_rank]``,
in the latent space, and ``W_uv`` a head, then ``W_o``, bring it back.  The
V row is the K row's leading ``kv_lora_rank`` numbers
(``adapters.AttentionKind.v_in_k``).  No key or value is ever expanded.

**The chip's share** (``held_experts=(first, count)``): as
``models/mimo_v2.py``.  The routed part is the share's; the shared expert,
which every chip computes alike, is whole; ``N_post_mlp`` is applied to
that partial sum, which goes on as it is.

Weights: ``leading`` is a list of the dense layers; ``layers`` holds the
sparse layers' leaves stacked ``[n, …]``, the expert stacks ``moe.w_gate/
w_up/w_down [n, E, …]`` among them, which the serving engine keeps out of
its scan and hands to the grouped matmul whole with the layer's index
(``inference/v2/adapters.PanguUltraMoeV2Adapter``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .llama import _rms_norm, _rope

LATENT = "latent"
#: a sparse layer's leaves that are handed to the grouped matmul whole
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
NORMS = ("attn_norm", "post_attn_norm", "mlp_norm", "post_mlp_norm")


@dataclasses.dataclass(frozen=True)
class PanguUltraMoeConfig:
    vocab_size: int = 153600
    hidden_size: int = 7680
    intermediate_size: int = 18432          # the dense layers' FFN
    moe_intermediate_size: int = 2048       # one expert's
    num_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 25.6e6
    rms_norm_eps: float = 1e-5
    num_layers: int = 61
    first_k_dense: int = 3                  # leading layers with a dense FFN
    num_experts: int = 256                  # the router's width
    top_k: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    n_shared_experts: int = 1
    #: (first, count): the experts this chip holds; None: all of them
    held_experts: Optional[Tuple[int, int]] = None
    max_seq_len: int = 131072
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if not 0 <= self.first_k_dense < self.num_layers:
            raise ValueError("first_k_dense leaves no sparse layer")

    @property
    def latent_dim(self) -> int:
        """A token's cache row: the compressed vector and its rotary part."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def experts_held(self) -> int:
        return self.held_experts[1] if self.held_experts else self.num_experts

    @classmethod
    def tiny(cls, **kw) -> "PanguUltraMoeConfig":
        d = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                 moe_intermediate_size=32, num_heads=4, q_lora_rank=24,
                 kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                 v_head_dim=16, num_layers=3, first_k_dense=1,
                 num_experts=8, top_k=3, max_seq_len=256, dtype=jnp.float32)
        d.update(kw)
        return cls(**d)


class PanguUltraMoeModel:
    """Weights and their layout, and the layer's two halves in the
    absorbed form; the forward pass that serves is the v2 engine's, and
    :meth:`forward` here is the same halves over a whole sequence without
    a cache."""

    def __init__(self, config: PanguUltraMoeConfig, mesh: Any = None):
        from ..moe.layer import DroplessMoE

        self.config = config
        self.mesh = mesh
        c = config
        self._moe_layer = DroplessMoE(
            c.num_experts, c.top_k, renormalize=c.norm_topk_prob, mesh=mesh,
            scoring="sigmoid", held=c.held_experts)

    # -- weights -------------------------------------------------------------

    def _shapes(self, sparse: bool) -> Dict[str, Dict[str, Tuple[int, ...]]]:
        """A layer's leaves by group, each with its fan-in first (the
        number of inputs one output sums over)."""
        c = self.config
        H, h = c.hidden_size, c.num_heads
        groups = {"attn": {
            "w_dq": (H, (H, c.q_lora_rank)),
            "w_uq": (c.q_lora_rank, (c.q_lora_rank, h, c.qk_head_dim)),
            "w_dkv": (H, (H, c.latent_dim)),
            "w_uk": (c.kv_lora_rank, (c.kv_lora_rank, h, c.qk_nope_head_dim)),
            "w_uv": (c.kv_lora_rank, (c.kv_lora_rank, h, c.v_head_dim)),
            "wo": (h * c.v_head_dim, (h, c.v_head_dim, H))}}

        def swiglu(I, lead=()):
            return {"w_gate": (H, lead + (H, I)), "w_up": (H, lead + (H, I)),
                    "w_down": (I, lead + (I, H))}

        if not sparse:
            groups["mlp"] = swiglu(c.intermediate_size)
            return groups
        groups["moe"] = dict(swiglu(c.moe_intermediate_size,
                                    (c.experts_held,)),
                             wg=(H, (H, c.num_experts)))
        if c.n_shared_experts:
            groups["shared"] = swiglu(
                c.n_shared_experts * c.moe_intermediate_size)
        return groups

    def _layer(self, key, sparse: bool, lead: Tuple[int, ...] = ()) -> Dict:
        """One layer's leaves (``lead = (n,)``: a stack of ``n``):
        1/sqrt(fan_in) normal weights, norm weights 1.  A ROUTED expert's
        down projection is drawn ``top_k`` times smaller: a layer adds
        ``top_k`` experts' rows, so one of them moves the stream by what
        1/top_k of a dense FFN would, as in a trained model
        (``models/mimo_v2.py``, and PERF.md §6, PR 31, for what the full
        scale did to the serving check)."""
        c = self.config
        shapes = self._shapes(sparse)
        names = [(g, n) for g in sorted(shapes) for n in sorted(shapes[g])]
        out: Dict[str, Any] = {g: {} for g in shapes}
        for k, (g, n) in zip(jax.random.split(key, len(names)), names):
            fan_in, shape = shapes[g][n]
            out[g][n] = jax.random.normal(k, lead + shape, jnp.float32) \
                / np.sqrt(fan_in)
        if sparse:
            out["moe"]["w_down"] = out["moe"]["w_down"] / c.top_k
        for name in NORMS:
            out[name] = jnp.ones(lead + (c.hidden_size,), jnp.float32)
        out["attn"]["q_norm"] = jnp.ones(lead + (c.q_lora_rank,), jnp.float32)
        out["attn"]["kv_norm"] = jnp.ones(lead + (c.kv_lora_rank,),
                                          jnp.float32)
        return out

    def init_params(self, rng: jax.Array) -> Dict[str, Any]:
        c = self.config
        H = c.hidden_size
        k_embed, k_head, k_lead, k_stack = jax.random.split(rng, 4)
        leading = [self._layer(k, False) for k in
                   jax.random.split(k_lead, c.first_k_dense)]
        return {
            "embed": jax.random.normal(k_embed, (c.vocab_size, H),
                                       jnp.float32),
            "leading": leading,
            "layers": self._layer(k_stack, True,
                                  (c.num_layers - c.first_k_dense,)),
            "final_norm": jnp.ones((H,), jnp.float32),
            "lm_head": jax.random.normal(k_head, (H, c.vocab_size),
                                         jnp.float32) / np.sqrt(H)}

    def _head(self, params: Any) -> jnp.ndarray:
        return params["lm_head"]

    # -- the layer's two halves, absorbed (shared with the adapter) ----------

    def _norm(self, x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
        return _rms_norm(x, w.astype(self.config.dtype),
                         self.config.rms_norm_eps)

    def qkv(self, lp: Any, x: jnp.ndarray, positions: jnp.ndarray
            ) -> Tuple[jnp.ndarray, jnp.ndarray, None]:
        """``x [N, H]`` at ``positions [N]`` → absorbed queries ``[N, h,
        kv_lora_rank + rope]``, the token's ONE cache row ``[N, 1,
        kv_lora_rank + rope]``, and no V: it is the row's leading
        ``kv_lora_rank`` numbers."""
        c = self.config
        dt = c.dtype
        a = lp["attn"]
        h = self._norm(x, lp["attn_norm"])
        with jax.named_scope("mla/absorb_q"):
            c_q = self._norm(h @ a["w_dq"].astype(dt), a["q_norm"])
            q = jnp.einsum("nr,rhd->nhd", c_q, a["w_uq"].astype(dt))
            q_nope, q_rope = (q[..., :c.qk_nope_head_dim],
                              q[..., c.qk_nope_head_dim:])
            q = jnp.concatenate(
                [jnp.einsum("nhd,chd->nhc", q_nope, a["w_uk"].astype(dt)),
                 _rope(q_rope, positions, c.rope_theta)], axis=-1)
        kv = h @ a["w_dkv"].astype(dt)
        row = jnp.concatenate(
            [self._norm(kv[:, :c.kv_lora_rank], a["kv_norm"]),
             _rope(kv[:, None, c.kv_lora_rank:], positions,
                   c.rope_theta)[:, 0]], axis=-1)
        return q, row[:, None, :], None

    def routed(self, lp: Any, h: jnp.ndarray, stacks: Any = None
               ) -> jnp.ndarray:
        """The held experts' part of a sparse layer, scaled: ``h [N, H]``
        (normed) → ``[N, H]`` float32.  A layer cut out of the stacks
        carries ``expert_layer`` and its experts are read where they lie
        in ``stacks`` (``params["layers"]``)."""
        from ..telemetry import numerics

        m = lp["moe"]
        experts, layer = (stacks["moe"], lp["expert_layer"]) \
            if "expert_layer" in lp else (m, None)
        y, _, meta = self._moe_layer(
            m["wg"], {n: experts[n] for n in EXPERT_LEAVES}, h[None],
            layer=layer)
        numerics.moe_stats(meta)
        return y[0].astype(jnp.float32) * self.config.routed_scaling_factor

    def _swiglu(self, m: Any, h: jnp.ndarray) -> jnp.ndarray:
        dt = self.config.dtype
        act = jax.nn.silu(h @ m["w_gate"].astype(dt)) \
            * (h @ m["w_up"].astype(dt))
        return act @ m["w_down"].astype(dt)

    def shared(self, lp: Any, h: jnp.ndarray) -> jnp.ndarray:
        """The shared expert: a dense SwiGLU every token passes, on every
        chip alike."""
        with jax.named_scope("moe/shared_expert"):
            return self._swiglu(lp["shared"], h)

    def ffn(self, lp: Any, h: jnp.ndarray, stacks: Any = None
            ) -> jnp.ndarray:
        """``h [N, H]`` (normed) → the FFN's ``y [N, H]``, before
        ``N_post_mlp``."""
        if "mlp" in lp:
            return self._swiglu(lp["mlp"], h)
        y = self.routed(lp, h, stacks)
        if "shared" in lp:
            y = y + self.shared(lp, h).astype(jnp.float32)
        return y.astype(self.config.dtype)

    def post_attn(self, lp: Any, x: jnp.ndarray, attn: jnp.ndarray,
                  stacks: Any = None) -> jnp.ndarray:
        """``attn [N, h, kv_lora_rank]``, the attention's output in the
        latent space → the layer's output ``[N, H]``."""
        dt = self.config.dtype
        a = lp["attn"]
        with jax.named_scope("mla/expand_out"):
            out = jnp.einsum("nhc,chd->nhd", attn, a["w_uv"].astype(dt))
            out = jnp.einsum("nhd,hdH->nH", out, a["wo"].astype(dt))
        x = x + self._norm(out, lp["post_attn_norm"])
        y = self.ffn(lp, self._norm(x, lp["mlp_norm"]), stacks)
        return x + self._norm(y, lp["post_mlp_norm"])

    # -- the stacks ----------------------------------------------------------

    def scanned(self, params: Any) -> Any:
        """The sparse layers' stacks without the expert leaves: what a
        scan over the layers slices (a slice of an expert stack would be
        copied for the grouped matmul)."""
        stacks = dict(params["layers"])
        stacks["moe"] = {n: w for n, w in stacks["moe"].items()
                         if n not in EXPERT_LEAVES}
        return stacks

    # -- a whole sequence, no cache ------------------------------------------

    def forward(self, params: Any, input_ids: jnp.ndarray) -> jnp.ndarray:
        """``[B, S]`` ids → float32 logits ``[B, S, V]``."""
        c = self.config
        dt = c.dtype
        scanned = self.scanned(params)
        sparse = c.num_layers - c.first_k_dense
        layers = list(params["leading"]) + [
            dict(jax.tree.map(lambda w: w[l], scanned), expert_layer=l)
            for l in range(sparse)]

        def one(ids):
            S = ids.shape[0]
            pos = jnp.arange(S)
            seen = pos[None, :] <= pos[:, None]
            x = jnp.take(params["embed"].astype(dt), ids, axis=0)
            for lp in layers:
                q, row, _ = self.qkv(lp, x, pos)
                s = jnp.einsum("qhd,kd->hqk", q, row[:, 0]
                               ).astype(jnp.float32) / np.sqrt(c.qk_head_dim)
                p = jax.nn.softmax(jnp.where(seen[None], s, -1e30),
                                   axis=-1).astype(dt)
                out = jnp.einsum("hqk,kc->qhc", p,
                                 row[:, 0, :c.kv_lora_rank])
                x = self.post_attn(lp, x, out, params["layers"])
            x = self._norm(x, params["final_norm"])
            return jnp.einsum("sH,HV->sV", x, params["lm_head"].astype(dt),
                              preferred_element_type=jnp.float32)

        return jax.lax.map(one, input_ids)    # ragged_dot has no vmap
