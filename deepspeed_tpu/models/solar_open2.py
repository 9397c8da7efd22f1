"""Solar-Open-2-family sparse decoder (``model_type: solar_open2``): every
published layer is a TOKEN MIXER and then EXPERTS, each under a pre-norm of
its own, ``x ← x + Mixer(N(x))``, ``x ← x + Experts(N(x))``.  The mixer of
the layers named in ``gqa_layers`` (every fourth, from 0) is a gated
grouped-query attention WITHOUT rotary; every other layer's is a gated
delta rule with a decay a key channel (KDA, ``models/delta_rule.py``).
Keys are the published config's; ``N`` is RMSNorm with a learned weight and
eps ``rms_norm_eps``; ``H`` the hidden size; ``h`` a part's normed input.
A final RMSNorm and an untied head; no bias anywhere.

* **KDA** (``linear_attn_config``: ``n`` heads of ``d``, conv ``K``):
  ``[q̂ | k̂ | v̂] = W_qkv h`` (three streams of ``n · d``), each through
  its causal depthwise conv and SiLU; the decay's pre-activation ``f =
  W_f↑ (W_f↓ h)`` and the output gate ``W_g↑ (W_g↓ h)`` through a
  bottleneck of ``d`` (``kda_use_full_proj`` false); ``β = 2 · sigmoid(W_β
  h)`` a head (``kda_allow_neg_eigval``); the recurrence (chunk form in
  prefill, ``delta_state_update`` in decode); ``Mixer = W_o [N_d(o) ⊙
  sigmoid(gate)]``, ``N_d`` over a head's ``d`` under one weight ``[d]``.
* **attention**: ``q = W_q h`` (``num_heads × head_dim``), ``k, v = W_k
  h, W_v h`` (``num_kv_heads × head_dim``), causal softmax at
  ``1/√head_dim``, **no rotary** (``use_rope`` false), ``Mixer = W_o [attn
  ⊙ sigmoid(W_γ h)]``: the gate elementwise over ``num_heads × head_dim``,
  from ``h`` by a matrix of its own (``use_gqa_gate``).
* **experts**: ``s = sigmoid(W_r h)`` over ``num_experts`` in float32; the
  chosen are the ``top_k`` largest of ``s + b`` (``b`` a choice bias an
  expert); weights ``routed_scaling_factor · s_e / Σ_chosen s``; SwiGLU
  experts of ``moe_intermediate_size`` and one shared SwiGLU expert of
  ``n_shared_experts · moe_intermediate_size`` every token passes.  Under
  expert parallelism (``held_experts``) the routed sum is this chip's
  experts' alone and the shared expert whole: the shares' parts add up
  with the shared expert counted once.

To the serving engine a published layer is TWO parts, a mixer's and the
experts' (``inference/v2/adapters.SolarOpen2V2Adapter``); ``num_layers``
here counts published layers.  Weights are stacked BY PART: ``attn:
{pre_norm [A, H], wq [A, H, h, d], wk, wv [A, H, kv, d], w_gate [A, H, h,
d], wo [A, h, d, H]}``, ``delta: {pre_norm [D, H], in_proj [D, H, 3·n·d],
conv_w [D, K, 3·n·d], f_down [D, H, d], f_up [D, d, n·d], dt_bias [D, n·d],
A_log [D, n], w_beta [D, H, n], g_down [D, H, d], g_up [D, d, n·d], norm
[D, d], out_proj [D, n·d, H]}``, ``moe: {pre_norm [L, H], wg [L, H,
experts], bias [L, experts], w_gate, w_up [L, held, H, I], w_down [L,
held, I, H], shared: {w_gate, w_up [L, H, S], w_down [L, S, H]}}``,
``embed [V, H]``, ``final_norm [H]``, ``lm_head [H, V]``.  A mixer's place
in its stack is its place among the layers of its own kind.  The model is
served; there is no trainer path.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import delta_rule, mamba2
from .delta_rule import DELTA, F32
from .llama import _rms_norm
from .nemotron_h import causal_attention

#: the name of the attention kind's pool
KV = "kv"
#: a layer's mixer, by a character of :attr:`SolarOpen2Config.mixers`, →
#: the stack it lies in
STACKS = {"*": "attn", "D": "delta"}
#: the expert leaves a layer scan must not slice (``DroplessMoE``)
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config:
    vocab_size: int = 196608
    hidden_size: int = 4096
    num_layers: int = 48                    # published layers, two parts each
    #: the layers whose mixer is attention; the others' is the delta rule
    gqa_layers: Tuple[int, ...] = tuple(range(0, 48, 4))
    num_heads: int = 64
    num_kv_heads: int = 8
    head_dim: int = 128
    linear_num_heads: int = 64
    linear_head_dim: int = 128
    conv_kernel: int = 4
    moe_intermediate_size: int = 1280
    num_experts: int = 320                  # the router's width
    top_k: int = 8
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    #: (first, count): the experts this chip holds; None: all of them
    held_experts: Optional[Tuple[int, int]] = None
    rms_norm_eps: float = 1e-5
    #: the block ``forward`` runs the chunk form in (the engine's is its
    #: prefill chunk)
    chunk_size: int = 128
    max_seq_len: int = 1048576
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if set(self.gqa_layers) - set(range(self.num_layers)):
            raise ValueError(f"gqa_layers {self.gqa_layers} name a layer "
                             f"beyond the {self.num_layers} there are")

    @property
    def hd(self) -> int:
        return self.head_dim

    @property
    def mixers(self) -> str:
        """One character a published layer: ``*`` attention, ``D`` KDA."""
        return "".join("*" if l in self.gqa_layers else "D"
                       for l in range(self.num_layers))

    @property
    def period(self) -> str:
        """The shortest stretch :attr:`mixers` repeats whole."""
        L, mixers = self.num_layers, self.mixers
        return next(mixers[:n] for n in range(1, L + 1)
                    if L % n == 0 and mixers[:n] * (L // n) == mixers)

    @property
    def delta(self) -> delta_rule.DeltaDims:
        return delta_rule.DeltaDims(self.linear_num_heads,
                                    self.linear_head_dim, self.conv_kernel)

    @property
    def experts_held(self) -> int:
        return self.held_experts[1] if self.held_experts else self.num_experts

    @classmethod
    def tiny(cls, **kw) -> "SolarOpen2Config":
        d = dict(vocab_size=256, hidden_size=64, num_layers=8,
                 gqa_layers=(0, 4), num_heads=4, num_kv_heads=2, head_dim=16,
                 linear_num_heads=4, linear_head_dim=16,
                 moe_intermediate_size=48, num_experts=8, top_k=3,
                 chunk_size=16, max_seq_len=256, dtype=jnp.float32)
        d.update(kw)
        return cls(**d)


class SolarOpen2Model:
    """Weights and their layout, and each part as the serving engine's
    hooks take it (``inference/v2/adapters.SolarOpen2V2Adapter``):
    :meth:`qkv` / :meth:`attn_out`, :meth:`mix_in` / :meth:`mix_chunk` or
    :meth:`mix_decode` / :meth:`mix_out`, :meth:`experts`.
    :meth:`forward` is the same parts over whole sequences without a
    cache."""

    def __init__(self, config: SolarOpen2Config, mesh: Any = None):
        from ..moe.layer import DroplessMoE

        self.config = config
        self.mesh = mesh
        c = config
        self._moe_layer = DroplessMoE(
            c.num_experts, c.top_k, renormalize=c.norm_topk_prob, mesh=mesh,
            scoring="sigmoid", held=c.held_experts)

    # -- weights -------------------------------------------------------------

    def init_params(self, rng: jax.Array) -> Dict[str, Any]:
        """1/sqrt(fan_in) normal matrices.  What a trained model holds away
        from its initial constants is drawn so here too, so that a path
        that ignores one of them computes another function: KDA's ``A_log
        = log U[1, 16]`` a head, ``dt_bias`` the inverse softplus of a step
        drawn log-uniform in [0.001, 0.1] a channel, every norm's weight
        ``1 + 0.1 N(0, 1)``, the router's choice bias ``0.01 N(0, 1)``
        (small, because what a trained router learns it FOR is an even
        load: ``models/mimo_v2.py``).  A ROUTED expert's down projection is
        drawn ``top_k / 2`` times smaller (4 at 8 experts a token), as
        ``models/nemotron_h.py`` draws its own and for its reasons: one
        expert swapped at a router's near-tie must not move the stream as
        far as a rounding of every product does, and a check on served
        tokens still has to SEE the routed sum."""
        c = self.config
        H, V, I = c.hidden_size, c.vocab_size, c.moe_intermediate_size
        S = c.n_shared_experts * I
        h, kv, d = c.num_heads, c.num_kv_heads, c.head_dim
        dd = c.delta
        nA, nD, L = c.mixers.count("*"), c.mixers.count("D"), c.num_layers
        k = iter(jax.random.split(rng, 40))

        def normal(shape, fan_in):
            return jax.random.normal(next(k), shape, F32) / np.sqrt(fan_in)

        def near_one(shape):
            return 1.0 + 0.1 * jax.random.normal(next(k), shape, F32)

        step = jnp.exp(jax.random.uniform(
            next(k), (nD, dd.width), F32, np.log(1e-3), np.log(1e-1)))
        return {
            "embed": normal((V, H), 1),
            "attn": {"pre_norm": near_one((nA, H)),
                     "wq": normal((nA, H, h, d), H),
                     "wk": normal((nA, H, kv, d), H),
                     "wv": normal((nA, H, kv, d), H),
                     "w_gate": normal((nA, H, h, d), H),
                     "wo": normal((nA, h, d, H), h * d)},
            "delta": {"pre_norm": near_one((nD, H)),
                      "in_proj": normal((nD, H, 3 * dd.width), H),
                      "conv_w": normal((nD, dd.d_conv, 3 * dd.width),
                                       dd.d_conv),
                      "f_down": normal((nD, H, dd.d_head), H),
                      "f_up": normal((nD, dd.d_head, dd.width), dd.d_head),
                      # softplus(dt_bias) = step
                      "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                      "A_log": jnp.log(jax.random.uniform(
                          next(k), (nD, dd.heads), F32, 1.0, 16.0)),
                      "w_beta": normal((nD, H, dd.heads), H),
                      "g_down": normal((nD, H, dd.d_head), H),
                      "g_up": normal((nD, dd.d_head, dd.width), dd.d_head),
                      "norm": near_one((nD, dd.d_head)),
                      "out_proj": normal((nD, dd.width, H), dd.width)},
            "moe": {"pre_norm": near_one((L, H)),
                    "wg": normal((L, H, c.num_experts), H),
                    "bias": 0.01 * jax.random.normal(
                        next(k), (L, c.num_experts), F32),
                    "w_gate": normal((L, c.experts_held, H, I), H),
                    "w_up": normal((L, c.experts_held, H, I), H),
                    "w_down": normal((L, c.experts_held, I, H), I)
                    * 2 / c.top_k,
                    "shared": {"w_gate": normal((L, H, S), H),
                               "w_up": normal((L, H, S), H),
                               "w_down": normal((L, S, H), S)}},
            "final_norm": near_one((H,)),
            "lm_head": normal((H, V), H),
        }

    def _head(self, params: Any) -> jnp.ndarray:
        return params["lm_head"]

    def _norm(self, x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
        return _rms_norm(x, w.astype(self.config.dtype),
                         self.config.rms_norm_eps)

    def state_parts(self) -> Tuple[Tuple[str, Tuple[int, ...], Any], ...]:
        """(name, shape, type) of what a sequence holds a KDA layer; the
        state float32 by this constant, whatever the model's type."""
        return self.config.delta.state_parts(self.config.dtype)

    def zero_state(self, rows: int) -> Dict[str, jnp.ndarray]:
        return self.config.delta.zero_state(rows, self.config.dtype)

    # -- the stacks ----------------------------------------------------------

    def scanned(self, params: Any) -> Dict[str, Any]:
        """The three stacks with a leading dim of the periods, as a scan
        over the periods slices them, WITHOUT the expert leaves (a slice of
        an expert stack would be copied for the grouped matmul: they ride
        whole and are read at their layer)."""
        c = self.config
        periods = c.num_layers // len(c.period)
        stacks = {name: params[name] for name in ("attn", "delta")
                  if jax.tree.leaves(params[name])[0].shape[0]}
        stacks["moe"] = {n: v for n, v in params["moe"].items()
                         if n not in EXPERT_LEAVES}
        return jax.tree.map(
            lambda v: v.reshape((periods, v.shape[0] // periods)
                                + v.shape[1:]), stacks)

    def period_layers(self, pp: Any, p: Any) -> List[Any]:
        """The ``lp`` of each PART of period ``p`` out of the period's
        slice ``pp`` of :meth:`scanned``, two a published layer: the
        mixer's leaves at its place among the period's layers of its kind,
        then the experts', which also say where the layer's experts lie in
        the whole stacks."""
        c = self.config
        seen = dict.fromkeys(STACKS, 0)
        out = []
        for j, mixer in enumerate(c.period):
            i = seen[mixer]
            seen[mixer] += 1
            out.append(jax.tree.map(lambda v: v[i], pp[STACKS[mixer]]))
            out.append(dict(jax.tree.map(lambda v: v[j], pp["moe"]),
                            expert_layer=p * len(c.period) + j))
        return out

    # -- attention -----------------------------------------------------------

    def qkv(self, lp: Any, x: jnp.ndarray
            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """``x [N, H]`` → q ``[N, h, d]``, k and v ``[N, kv, d]``: no
        rotary, so no positions."""
        dt = self.config.dtype
        with jax.named_scope("attn/qkv"):
            u = self._norm(x, lp["pre_norm"])
            return tuple(jnp.einsum("nH,Hhd->nhd", u, lp[name].astype(dt))
                         for name in ("wq", "wk", "wv"))

    def attn_out(self, lp: Any, x: jnp.ndarray, attn: jnp.ndarray
                 ) -> jnp.ndarray:
        """``attn [N, h, d]`` → the part's output: the attention gated
        elementwise by ``sigmoid(W_γ h)``, projected and added to the
        residual.  The gate reads the part's NORMED input, which the
        engine's hook does not hand on (it passes the residual ``x``), so
        ``x`` is normed again here: one RMSNorm a layer of four, and every
        other family's hook keeps its signature."""
        dt = self.config.dtype
        with jax.named_scope("attn/gate"):
            gate = jax.nn.sigmoid(jnp.einsum(
                "nH,Hhd->nhd", self._norm(x, lp["pre_norm"]),
                lp["w_gate"].astype(dt), preferred_element_type=F32))
            attn = (attn.astype(F32) * gate).astype(dt)
        with jax.named_scope("attn/out"):
            return x + jnp.einsum("nhd,hdH->nH", attn, lp["wo"].astype(dt))

    # -- the delta rule ------------------------------------------------------

    def mix_in(self, lp: Any, x: jnp.ndarray) -> Dict[str, jnp.ndarray]:
        """Row-wise: ``x [N, H]`` → the rows' input to the recurrence
        (``delta_rule``'s ``p``): the three streams before their conv, the
        decay's pre-activation, β's logits and the output gate's."""
        dt = self.config.dtype
        h = self._norm(x, lp["pre_norm"])
        with jax.named_scope("kda/in_proj"):
            low = lambda down, up, out: jnp.dot(
                h @ lp[down].astype(dt), lp[up].astype(dt),
                preferred_element_type=out)
            return {"qkv": h @ lp["in_proj"].astype(dt),
                    "f": low("f_down", "f_up", F32),
                    "beta": jnp.dot(h, lp["w_beta"].astype(dt),
                                    preferred_element_type=F32),
                    "gate": low("g_down", "g_up", dt)}

    def mix_chunk(self, lp, p, state, tokens: int, valid):
        c = self.config
        return delta_rule.chunk(c.delta, lp, p, state, tokens, valid, c.dtype)

    def mix_decode(self, lp, p, state, held, valid):
        del state                   # both parts are moved where they lie
        y, arrays = delta_rule.decode(self.config.delta, lp, p, held, valid)
        return y, {}, arrays

    def mix_out(self, lp: Any, p: Any, o: jnp.ndarray) -> jnp.ndarray:
        """Row-wise: the norm a head, the gate, ``out_proj`` → what the
        part adds to the residual ``[N, H]``."""
        c = self.config
        g = delta_rule.gated_norm(c.delta, lp, p["gate"], o, c.rms_norm_eps,
                                  c.dtype)
        with jax.named_scope("kda/out_proj"):
            return g @ lp["out_proj"].astype(c.dtype)

    def mix(self, lp, x, state, tokens: int, valid):
        """The KDA part over ``R`` sequences' rows with their state as
        values in and out (``mamba2.mix``, the form both recurrences
        share)."""
        return mamba2.mix(self, lp, x, state, tokens, valid)

    # -- the experts ---------------------------------------------------------

    def routed(self, lp: Any, h: jnp.ndarray, stacks: Any = None
               ) -> jnp.ndarray:
        """The held experts' part of the routed sum, scaled: ``h [N, H]``
        (normed) → ``[N, H]`` float32.  A layer cut out of the stacks
        carries ``expert_layer`` and its experts are read where they lie in
        ``stacks`` (``params["moe"]``)."""
        from ..telemetry import numerics

        experts, layer = (stacks, lp["expert_layer"]) \
            if "expert_layer" in lp else (lp, None)
        y, _, meta = self._moe_layer(
            lp["wg"], {n: experts[n] for n in EXPERT_LEAVES}, h[None],
            layer=layer, choice_bias=lp["bias"])
        numerics.moe_stats(meta)
        return y[0].astype(F32) * self.config.routed_scaling_factor

    def shared(self, lp: Any, h: jnp.ndarray) -> jnp.ndarray:
        """The shared expert: a dense SwiGLU every token passes, on every
        chip alike."""
        dt = self.config.dtype
        m = lp["shared"]
        with jax.named_scope("moe/shared_expert"):
            act = jax.nn.silu(h @ m["w_gate"].astype(dt)) \
                * (h @ m["w_up"].astype(dt))
            return act @ m["w_down"].astype(dt)

    def experts(self, lp: Any, x: jnp.ndarray, stacks: Any = None
                ) -> jnp.ndarray:
        """The experts' part: ``x [N, H]`` → ``x + routed(h) +
        shared(h)``."""
        h = self._norm(x, lp["pre_norm"])
        y = self.routed(lp, h, stacks) + self.shared(lp, h).astype(F32)
        return x + y.astype(self.config.dtype)

    # -- the ends ------------------------------------------------------------

    def embed(self, params: Any, tokens: jnp.ndarray) -> jnp.ndarray:
        return jnp.take(params["embed"].astype(self.config.dtype), tokens,
                        axis=0)

    def finalize(self, params: Any, x: jnp.ndarray) -> jnp.ndarray:
        return self._norm(x, params["final_norm"])

    def logits(self, params: Any, x: jnp.ndarray) -> jnp.ndarray:
        """The head over normed ``[N, H]`` → float32 ``[N, V]``."""
        return jnp.einsum("nH,HV->nV", x,
                          self._head(params).astype(self.config.dtype),
                          preferred_element_type=F32)

    # -- whole sequences, no cache -------------------------------------------

    def forward(self, params: Any, input_ids: jnp.ndarray) -> jnp.ndarray:
        """``[B, S]`` ids → float32 logits ``[B, S, V]``: the delta rule in
        blocks of ``chunk_size`` from a zero state, attention as a full
        causal softmax."""
        c = self.config
        B_, S_ = input_ids.shape
        at = dict.fromkeys(STACKS, 0)
        moe = {n: v for n, v in params["moe"].items()
               if n not in EXPERT_LEAVES}
        x = self.embed(params, input_ids.reshape(-1))
        for l, mixer in enumerate(c.mixers):
            lp = jax.tree.map(lambda v: v[at[mixer]], params[STACKS[mixer]])
            if mixer == "*":
                x = self.attn_out(lp, x, causal_attention(
                    *self.qkv(lp, x), B_, S_, c.dtype))
            else:
                x = x + mamba2.mix_sequences(self, lp, x, B_, S_,
                                             c.chunk_size)
            at[mixer] += 1
            x = self.experts(
                dict(jax.tree.map(lambda v: v[l], moe), expert_layer=l), x,
                params["moe"])
        return self.logits(params, self.finalize(params, x)
                           ).reshape(B_, S_, -1)
