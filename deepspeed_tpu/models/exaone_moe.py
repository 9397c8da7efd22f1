"""K-EXAONE sparse decoder (``model_type: exaone_moe``; the published
``LGAI-EXAONE/K-EXAONE-236B-A23B``): a pre-norm RMSNorm decoder with an
untied head whose layers are named by two published per-layer lists, and
behind them ONE multi-token-prediction layer that drafts the token after
next.

* ``layer_types`` — attention, 64 query and 8 KV heads of 128 in both
  kinds, each head of Q and K under an RMSNorm of its own width (one weight
  of ``head_dim`` shared by the heads, before any rotary):
  ``sliding_attention`` sees the last ``sliding_window`` keys (``i − j <
  window``) under a half-split rotary over the whole head, base
  ``rope_theta``; ``full_attention`` sees every key and has NO rotary.
  Plain causal softmax at ``head_dim^(−1/2)``, no sink, no bias.
* ``mlp_layer_types`` — FFN: ``dense`` a SwiGLU of ``intermediate_size``;
  ``sparse`` routed SwiGLU experts of ``moe_intermediate_size``:
  ``σ = sigmoid_f32(h·Wr)`` over ``num_experts``, the ``top_k`` largest of
  ``σ + c`` chosen (``c`` a learned bias an expert, for the choice only),
  weights ``σ_e / Σ_chosen σ × routed_scaling_factor``, dropless
  (``moe.layer.DroplessMoE``), plus ``num_shared_experts`` shared SwiGLU
  experts every token passes.

**The prediction layer** (``num_nextn_predict_layers`` 1; DeepSeek-V3's
form, arXiv:2412.19437 §2.2): with ``u_i`` the trunk's output at position
``i`` after the final norm and ``t_{i+1}`` the token that follows,

    z_i = M [ RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(u_i) ]     M: [2H, H]
    y_i = Layer(z_i)        a full-attention sparse layer, keys of its own
    d_{i+2} = argmax W_head RMSNorm_m(y_i)

embedding and head the trunk's.  The serving engine runs it behind the
trunk in the same program (``inference/v2/adapters.ExaoneMoeV2Adapter``:
the adapter's ``draft``), its keys one more layer of the full kind's pool.

The layer plan, the stacks a period at a time and the chip's share of the
experts (``held_experts``) are :mod:`.mimo_v2`'s; this model is that one
with another attention, a scaled router, a shared expert and the
prediction layer.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .llama import _rms_norm, _rope
from .mimo_v2 import (DENSE, EXPERT_LEAVES, FULL, SPARSE, WINDOW,
                      MimoV2Model)

#: the prediction layer's part, as the engine's gauges name it
MTP = "mtp"


@dataclasses.dataclass(frozen=True)
class ExaoneMoeConfig:
    vocab_size: int = 153600
    hidden_size: int = 6144
    intermediate_size: int = 18432          # the dense layers' FFN
    moe_intermediate_size: int = 2048       # one expert's
    num_heads: int = 64
    num_kv_heads: int = 8                   # both kinds
    head_dim: int = 128
    sliding_window: int = 128
    rope_theta: float = 1e6                 # window layers; full: no rotary
    rms_norm_eps: float = 1e-5
    num_experts: int = 128                  # the router's width
    top_k: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    num_shared_experts: int = 1
    #: (first, count): the experts this chip holds; None: all of them
    held_experts: Optional[Tuple[int, int]] = None
    #: per layer, 1 = window attention / 1 = routed experts
    attention_pattern: Tuple[int, ...] = (1, 1, 1, 1, 0)
    moe_pattern: Tuple[int, ...] = (0, 1, 1, 1, 1)
    #: prediction layers behind the trunk: 0 (none is built) or 1
    num_nextn_predict_layers: int = 1
    max_seq_len: int = 16384
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if len(self.attention_pattern) != len(self.moe_pattern):
            raise ValueError("attention_pattern and moe_pattern name the "
                             "same layers: they must be equally long")
        if self.num_nextn_predict_layers not in (0, 1):
            raise NotImplementedError(
                f"num_nextn_predict_layers "
                f"{self.num_nextn_predict_layers}: a step drafts one token "
                f"(ROADMAP R8)")

    @property
    def num_layers(self) -> int:
        return len(self.attention_pattern)

    @property
    def experts_held(self) -> int:
        return self.held_experts[1] if self.held_experts else self.num_experts

    # what ``mimo_v2``'s layout reads under its own names
    @property
    def v_head_dim(self) -> int:
        return self.head_dim

    @property
    def swa_num_kv_heads(self) -> int:
        return self.num_kv_heads

    @classmethod
    def tiny(cls, **kw) -> "ExaoneMoeConfig":
        """[window+dense, (window, full) x 2] and the prediction layer."""
        d = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                 moe_intermediate_size=32, num_heads=8, num_kv_heads=2,
                 head_dim=16, sliding_window=8, num_experts=8, top_k=3,
                 attention_pattern=(1, 1, 0, 1, 0),
                 moe_pattern=(0, 1, 1, 1, 1), max_seq_len=256,
                 dtype=jnp.float32)
        d.update(kw)
        return cls(**d)


class ExaoneMoeModel(MimoV2Model):
    """Weights and their layout; the forward pass that serves is the v2
    engine's, through ``ExaoneMoeV2Adapter``, and :meth:`forward` /
    :meth:`draft_forward` here are the same layers over a whole sequence
    without a cache."""

    # -- weights -------------------------------------------------------------

    def _attention_shapes(self, kind: str) -> Dict[str, Tuple[int, ...]]:
        c = self.config
        return {"wq": (c.hidden_size, c.num_heads, c.head_dim),
                "wk": (c.hidden_size, c.num_kv_heads, c.head_dim),
                "wv": (c.hidden_size, c.num_kv_heads, c.head_dim),
                "wo": (c.num_heads, c.head_dim, c.hidden_size)}

    def _attention(self, key, kind: str, lead: Tuple[int, ...] = ()) -> Dict:
        """An attention's leaves: the projections drawn, the two head
        norms' weights 1."""
        group = self._group(key, self._attention_shapes(kind), lead)
        for name in ("q_norm", "k_norm"):
            group[name] = jnp.ones(lead + (self.config.head_dim,),
                                   jnp.float32)
        return group

    def _shared_shapes(self) -> Dict[str, Tuple[int, ...]]:
        c = self.config
        H, I = c.hidden_size, c.num_shared_experts * c.moe_intermediate_size
        return {"w_gate": (H, I), "w_up": (H, I), "w_down": (I, H)}

    def _single(self, key, attn: str, ffn: str) -> Dict[str, Any]:
        """One layer on its own (a leading layer, the prediction layer's):
        a sparse one's experts a stack of one."""
        c = self.config
        H = c.hidden_size
        ka, kf, ks = jax.random.split(key, 3)
        group = self._group(kf, self._ffn_shapes(ffn))
        if ffn == SPARSE:
            group = {n: (w[None] if n in EXPERT_LEAVES else w)
                     for n, w in group.items()}
        lp = {"attn_norm": jnp.ones((H,), jnp.float32),
              "mlp_norm": jnp.ones((H,), jnp.float32),
              "attn": self._attention(ka, attn), ffn: group}
        if ffn == SPARSE and c.num_shared_experts:
            lp["shared"] = self._group(ks, self._shared_shapes())
        return lp

    def init_params(self, rng: jax.Array) -> Dict[str, Any]:
        c, plan = self.config, self.plan
        H = c.hidden_size
        k_embed, k_head, k_lead, k_stack, k_mtp = jax.random.split(rng, 5)
        leading = [self._single(key, attn, ffn) for key, (attn, ffn) in zip(
            jax.random.split(k_lead, max(len(plan.leading), 1)),
            plan.leading)]
        scanned = plan.periods * len(plan.period)
        layers = {"attn_norm": jnp.ones((scanned, H), jnp.float32),
                  "mlp_norm": jnp.ones((scanned, H), jnp.float32)}
        for key, kind in zip(jax.random.split(k_stack, 5),
                             (FULL, WINDOW, DENSE, SPARSE, "shared")):
            of = SPARSE if kind == "shared" else kind
            n = plan.periods * plan.count(of, "period")
            if not n or (kind == "shared" and not c.num_shared_experts):
                continue
            if kind in (FULL, WINDOW):
                layers[kind] = self._attention(key, kind, (n,))
            else:
                layers[kind] = self._group(
                    key, self._shared_shapes() if kind == "shared"
                    else self._ffn_shapes(kind), (n,))
        params = {
            "embed": jax.random.normal(k_embed, (c.vocab_size, H),
                                       jnp.float32),
            "leading": leading, "layers": layers,
            "final_norm": jnp.ones((H,), jnp.float32),
            "lm_head": jax.random.normal(k_head, (H, c.vocab_size),
                                         jnp.float32) / np.sqrt(H)}
        if c.num_nextn_predict_layers:
            k_proj, k_layer = jax.random.split(k_mtp)
            params[MTP] = {
                "enorm": jnp.ones((H,), jnp.float32),
                "hnorm": jnp.ones((H,), jnp.float32),
                "proj": jax.random.normal(k_proj, (2 * H, H), jnp.float32)
                / np.sqrt(2 * H),
                "layer": self._single(k_layer, FULL, SPARSE),
                "norm": jnp.ones((H,), jnp.float32)}
        return params

    # -- the layer's two halves (shared with the serving adapter) ------------

    def kv_heads(self, kind: str) -> int:
        return self.config.num_kv_heads

    def theta(self, kind: str) -> Optional[float]:
        """The rotary base: the window layers'; a full layer has none."""
        return self.config.rope_theta if kind == WINDOW else None

    def _norm(self, x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
        return _rms_norm(x, w.astype(self.config.dtype),
                         self.config.rms_norm_eps)

    def qkv(self, lp: Any, x: jnp.ndarray, positions: jnp.ndarray,
            kind: str) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """``x [N, H]`` at ``positions [N]`` → q ``[N, h, d]``, k, v ``[N,
        kv, d]``: Q and K normed a head, then rotary where the kind has
        one."""
        dt = self.config.dtype
        a = lp["attn"]
        h = self._norm(x, lp["attn_norm"])
        q = self._norm(jnp.einsum("nH,Hhd->nhd", h, a["wq"].astype(dt)),
                       a["q_norm"])
        k = self._norm(jnp.einsum("nH,Hhd->nhd", h, a["wk"].astype(dt)),
                       a["k_norm"])
        v = jnp.einsum("nH,Hhd->nhd", h, a["wv"].astype(dt))
        theta = self.theta(kind)
        if theta is not None:
            q, k = _rope(q, positions, theta), _rope(k, positions, theta)
        return q, k, v

    def _swiglu(self, m: Any, h: jnp.ndarray) -> jnp.ndarray:
        dt = self.config.dtype
        act = jax.nn.silu(h @ m["w_gate"].astype(dt)) \
            * (h @ m["w_up"].astype(dt))
        return act @ m["w_down"].astype(dt)

    def ffn(self, lp: Any, h: jnp.ndarray, stacks: Any = None
            ) -> jnp.ndarray:
        """``h [N, H]`` (normed) → ``[N, H]``: the dense SwiGLU, or the
        held experts' part scaled plus the shared expert (on every chip
        alike).  A sparse layer cut out of the stacks carries
        ``expert_layer`` (``mimo_v2``'s)."""
        from ..telemetry import numerics

        c = self.config
        if DENSE in lp:
            return self._swiglu(lp[DENSE], h)
        m = lp[SPARSE]
        experts, layer = (stacks[SPARSE], lp["expert_layer"]) \
            if "expert_layer" in lp else (m, 0)
        y, _, meta = self._moe_layer(
            m["wg"], {n: experts[n] for n in EXPERT_LEAVES}, h[None],
            layer=layer, choice_bias=m["bias"])
        numerics.moe_stats(meta)
        y = y[0].astype(jnp.float32) * c.routed_scaling_factor
        if "shared" in lp:
            with jax.named_scope("moe/shared_expert"):
                y = y + self._swiglu(lp["shared"], h).astype(jnp.float32)
        return y.astype(c.dtype)

    def period_layers(self, pp: Any, p: Any) -> List[Any]:
        """``mimo_v2``'s, each sparse layer with its shared expert."""
        out = super().period_layers(pp, p)
        if "shared" in pp:
            sparse = [lp for lp in out if SPARSE in lp]
            for f, lp in enumerate(sparse):
                lp["shared"] = jax.tree.map(lambda w: w[f], pp["shared"])
        return out

    # -- the prediction layer (shared with the serving adapter) --------------

    def draft_in(self, params: Any, u: jnp.ndarray, tokens: jnp.ndarray
                 ) -> jnp.ndarray:
        """``u [N, H]`` (the trunk's output after the final norm) and the
        tokens that FOLLOW its rows ``[N]`` → the prediction layer's input
        ``z [N, H]``."""
        dt = self.config.dtype
        m = params[MTP]
        e = jnp.take(params["embed"].astype(dt), tokens, axis=0)
        both = jnp.concatenate([self._norm(e, m["enorm"]),
                                self._norm(u, m["hnorm"])], axis=-1)
        return both @ m["proj"].astype(dt)

    def draft_logits(self, params: Any, y: jnp.ndarray) -> jnp.ndarray:
        """The prediction layer's output ``y [N, H]`` → float32 logits
        ``[N, V]`` of the token after next, through the trunk's head."""
        return jnp.einsum("nH,HV->nV", self._norm(y, params[MTP]["norm"]),
                          params["lm_head"].astype(self.config.dtype),
                          preferred_element_type=jnp.float32)

    # -- a whole sequence, no cache ------------------------------------------

    def _layer(self, lp: Any, attn: str, x: jnp.ndarray, stacks: Any
               ) -> jnp.ndarray:
        """One layer over a sequence's rows ``x [S, H]`` in order."""
        c = self.config
        pos = jnp.arange(x.shape[0])
        q, k, v = self.qkv(lp, x, pos, attn)
        rep = c.num_heads // c.num_kv_heads
        k, v = (jnp.repeat(t, rep, axis=1) for t in (k, v))
        s = jnp.einsum("qhd,khd->hqk", q, k).astype(jnp.float32) \
            / np.sqrt(c.head_dim)
        seen = pos[None, :] <= pos[:, None]
        if attn == WINDOW:
            seen &= pos[:, None] - pos[None, :] < c.sliding_window
        p = jax.nn.softmax(jnp.where(seen[None], s, -1e30),
                           axis=-1).astype(c.dtype)
        return self.post_attn(lp, x, jnp.einsum("hqk,khd->qhd", p, v),
                              stacks)

    def trunk(self, params: Any, ids: jnp.ndarray) -> jnp.ndarray:
        """``[S]`` ids → ``u [S, H]``: the trunk's output after the final
        norm (what the head and the prediction layer read)."""
        x = jnp.take(params["embed"].astype(self.config.dtype), ids, axis=0)
        for attn, lp in self.layer_list(params):
            x = self._layer(lp, attn, x, params["layers"])
        return self._norm(x, params["final_norm"])

    def _logits(self, params: Any, u: jnp.ndarray) -> jnp.ndarray:
        return jnp.einsum("sH,HV->sV", u,
                          params["lm_head"].astype(self.config.dtype),
                          preferred_element_type=jnp.float32)

    def forward(self, params: Any, input_ids: jnp.ndarray) -> jnp.ndarray:
        """``[B, S]`` ids → float32 logits ``[B, S, V]``."""
        return jax.lax.map(          # ragged_dot has no vmap
            lambda ids: self._logits(params, self.trunk(params, ids)),
            input_ids)

    def draft_forward(self, params: Any, input_ids: jnp.ndarray
                      ) -> jnp.ndarray:
        """``[B, S]`` ids → the prediction layer's float32 logits ``[B,
        S − 1, V]``: row ``i`` (from ``u_i`` and token ``i + 1``) scores
        token ``i + 2``."""
        def one(ids):
            u = self.trunk(params, ids)
            z = self.draft_in(params, u[:-1], ids[1:])
            y = self._layer(params[MTP]["layer"], FULL, z, None)
            return self.draft_logits(params, y)

        return jax.lax.map(one, input_ids)
