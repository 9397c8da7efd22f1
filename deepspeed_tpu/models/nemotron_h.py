"""Nemotron-H-family hybrid decoder (``model_type: nemotron_h``): every layer
is ONE part alone, named by a character of the published
``hybrid_override_pattern``: ``M`` a Mamba-2 mixer, ``*`` a grouped-query
attention, ``E`` a LatentMoE expert layer.  No layer has two of them, and
none has an FFN behind its attention.  Keys are the published config's;
``N`` is RMSNorm with a learned weight and eps ``norm_eps``; ``H`` the
hidden size.  Every layer is ``x ← x + Part(N(x))``; then a final RMSNorm
and an untied head; no bias but the conv's.

* ``M`` (Mamba-2, ``models/mamba2.py``: the arithmetic Falcon-H1's mixer
  runs, at other sizes): ``[z | xBC | dt] = W_in h``; the conv, the
  recurrence (chunk form in prefill, ``ssm_state_update`` in decode), the
  skip ``D x``; gate first, then RMSNorm with one statistic a group of
  ``d_ssm / n_groups``; ``Part = W_out(·)``.  No multiplier anywhere.
* ``*``: ``q = W_q h`` (``num_heads × head_dim``), ``k, v = W_k h, W_v h``
  (``num_kv_heads × head_dim``), causal softmax at ``1/√head_dim``, **no
  rotary** (the family applies none: ``rope_theta`` is carried unread),
  ``Part = W_o attn``.
* ``E`` (LatentMoE): ``s = sigmoid(W_r h)`` over ``num_experts`` in
  float32; the chosen are the ``top_k`` largest of ``s + b`` (``b`` a
  choice bias an expert); ``g_e = routed_scaling_factor · s_e / Σ_chosen
  s``; ``u = W_↓ h ∈ R^w`` (``w = moe_latent_size``: the experts work in
  the latent, the router and the shared expert at ``H``); ``r = Σ_e g_e ·
  W2_e relu(W1_e u)²`` with ``W1_e [w, I]``, ``W2_e [I, w]``: an expert of
  TWO matrices; ``Part = W_↑ r + V2 relu(V1 h)²`` (the shared expert reads
  ``h``, not ``u``).  Under expert parallelism (``held_experts``) ``r``
  sums this chip's experts only; ``W_↑`` is linear, so the shares' parts
  add up with the shared expert counted once.

Weights are stacked BY PART, each stack as long as the pattern has layers
of the part: ``mixer: {pre_norm [M, H], in_proj [M, H, P], conv_w [M, K,
conv_dim], conv_b [M, conv_dim], dt_bias, A_log, D [M, heads], norm [M,
d_ssm], out_proj [M, d_ssm, H]}``, ``attn: {pre_norm [A, H], wq [A, H, h,
d], wk, wv [A, H, kv, d], wo [A, h, d, H]}``, ``moe: {pre_norm [E, H], wg
[E, H, experts], bias [E, experts], latent_down [E, H, w], latent_up [E, w,
H], w_up [E, held, w, I], w_down [E, held, I, w], shared_up [E, H, S],
shared_down [E, S, H]}``, ``embed [V, H]``, ``final_norm [H]``, ``lm_head
[H, V]``.  A layer's place in its stack is its place among the layers of
its own part, not the model's layer.  The multi-token-prediction module of
the published model is not built (a stack of one-part layers behind the
trunk: ROADMAP R8 (b)), and there is no trainer path: the model is served.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import mamba2
from .llama import _rms_norm
from .mamba2 import F32, SSM

#: the name of the attention kind's pool
KV = "kv"
#: a pattern's characters → the stack a layer of that part lies in
STACKS = {"M": "mixer", "*": "attn", "E": "moe"}
#: the expert leaves a layer scan must not slice (``DroplessMoE``)
EXPERT_LEAVES = ("w_up", "w_down")


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 4096
    #: one character a layer: M a mixer, * attention, E experts
    pattern: str = "MEMEMEM*EME"
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    num_experts: int = 512                  # the router's width
    top_k: int = 22
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 5.0
    #: (first, count): the experts this chip holds; None: all of them
    held_experts: Optional[Tuple[int, int]] = None
    norm_eps: float = 1e-5
    max_seq_len: int = 262144
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if not self.pattern or set(self.pattern) - set(STACKS):
            raise ValueError(f"pattern {self.pattern!r}: one of "
                             f"{sorted(STACKS)} a layer")

    @property
    def num_layers(self) -> int:
        return len(self.pattern)

    @property
    def hd(self) -> int:
        return self.head_dim

    @property
    def mamba(self) -> mamba2.Mamba2Dims:
        return mamba2.Mamba2Dims(self.mamba_num_heads, self.mamba_head_dim,
                                 self.ssm_state_size, self.n_groups,
                                 self.conv_kernel)

    @property
    def experts_held(self) -> int:
        return self.held_experts[1] if self.held_experts else self.num_experts

    def count(self, part: str) -> int:
        """The pattern's layers of ``part`` (one of its characters)."""
        return self.pattern.count(part)

    @property
    def period(self) -> str:
        """The shortest stretch the pattern repeats whole."""
        L = self.num_layers
        return next(self.pattern[:n] for n in range(1, L + 1)
                    if L % n == 0 and self.pattern[:n] * (L // n)
                    == self.pattern)

    @classmethod
    def tiny(cls, **kw) -> "NemotronHConfig":
        d = dict(vocab_size=256, hidden_size=64, pattern="ME*EME*E",
                 num_heads=4, num_kv_heads=2, head_dim=16,
                 mamba_num_heads=4, mamba_head_dim=64, ssm_state_size=16,
                 n_groups=2, chunk_size=16, moe_intermediate_size=48,
                 moe_latent_size=32, moe_shared_expert_intermediate_size=96,
                 num_experts=8, top_k=3, max_seq_len=256, dtype=jnp.float32)
        d.update(kw)
        return cls(**d)


def causal_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     batch: int, seq: int, dt: Any) -> jnp.ndarray:
    """Whole sequences without a cache: ``q [B·S, h, d]``, ``k`` and ``v
    [B·S, kv, d]`` (query head ``n`` reads KV head ``n // (h / kv)``) →
    ``[B·S, h, d]``, a full causal softmax at ``1/√d``, no positions."""
    heads, d = q.shape[1:]
    kv = k.shape[1]
    seen = jnp.arange(seq)[None, :] <= jnp.arange(seq)[:, None]
    q = q.reshape(batch, seq, kv, heads // kv, d)
    k, v = (t.reshape(batch, seq, kv, d) for t in (k, v))
    s = jnp.einsum("bqgrd,bkgd->bgrqk", q, k).astype(F32) / np.sqrt(d)
    p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1).astype(dt)
    return jnp.einsum("bgrqk,bkgd->bqgrd", p, v).reshape(
        batch * seq, heads, d)


class NemotronHModel:
    """Weights and their layout, and each part as the serving engine's
    hooks take it (``inference/v2/adapters.NemotronHV2Adapter``): :meth:`qkv`
    / :meth:`attn_out`, :meth:`mix_in` / :meth:`mix_chunk` or
    :meth:`mix_decode` / :meth:`mix_out`, :meth:`experts`.  :meth:`forward`
    is the same parts over whole sequences without a cache."""

    def __init__(self, config: NemotronHConfig, mesh: Any = None):
        from ..moe.layer import DroplessMoE

        self.config = config
        self.mesh = mesh
        c = config
        self._moe_layer = DroplessMoE(
            c.num_experts, c.top_k, renormalize=c.norm_topk_prob, mesh=mesh,
            scoring="sigmoid", held=c.held_experts, form="relu2")

    # -- weights -------------------------------------------------------------

    def init_params(self, rng: jax.Array) -> Dict[str, Any]:
        """1/sqrt(fan_in) normal matrices.  What a trained model holds away
        from its initial constants is drawn so here too, so that a path
        that ignores one of them computes another function: the mixer's
        ``A_log = log U[1, 16]``, ``dt_bias`` the inverse softplus of a
        ``Δ`` drawn log-uniform in [0.001, 0.1] (the published
        ``time_step_min/max``), ``D``, the conv's bias and every norm's
        weight near 1; the router's choice bias ``0.01 · N(0, 1)`` (small,
        because what a trained model learns it FOR is an even load: at
        0.1 a sixth of the experts were never chosen, a share's experts
        were busy or idle as the seed drew them, and a serving rate
        followed the seed by 1-2%; ``models/mimo_v2.py`` met the same).  A
        ROUTED expert's down projection is drawn ``top_k / 2`` times
        smaller (11 at 22 experts a token).  ``models/pangu_ultra_moe.py``
        says why it is smaller at all: one expert swapped at a router's
        near-tie must not move the stream as far as a rounding of every
        product does.  Why no smaller: a check on served tokens has to
        SEE the routed sum, and at ``1 / top_k`` it read the same with the
        sum dropped whole; at ``2 / top_k`` the sum dropped reads over the
        sound program's worst token, and from ``4 / top_k`` upward the
        swaps grow as fast as any fault does (measured on the chip at
        Nemotron-3-Super's widths: ``PERF.md`` section 6, PR 52)."""
        c = self.config
        H, V, w = c.hidden_size, c.vocab_size, c.moe_latent_size
        I, S = c.moe_intermediate_size, c.moe_shared_expert_intermediate_size
        h, kv, d = c.num_heads, c.num_kv_heads, c.head_dim
        m = c.mamba
        nM, nA, nE = (c.count(part) for part in "M*E")
        k = iter(jax.random.split(rng, 40))

        def normal(shape, fan_in):
            return jax.random.normal(next(k), shape, F32) / np.sqrt(fan_in)

        def near_one(shape):
            return 1.0 + 0.1 * jax.random.normal(next(k), shape, F32)

        delta = jnp.exp(jax.random.uniform(
            next(k), (nM, m.heads), F32, np.log(1e-3), np.log(1e-1)))
        return {
            "embed": normal((V, H), 1),
            "mixer": {"pre_norm": near_one((nM, H)),
                      "in_proj": normal((nM, H, m.proj_dim), H),
                      "conv_w": normal((nM, m.d_conv, m.conv_dim), m.d_conv),
                      "conv_b": 0.1 * jax.random.normal(
                          next(k), (nM, m.conv_dim), F32),
                      # softplus(dt_bias) = delta
                      "dt_bias": delta + jnp.log(-jnp.expm1(-delta)),
                      "A_log": jnp.log(jax.random.uniform(
                          next(k), (nM, m.heads), F32, 1.0, 16.0)),
                      "D": near_one((nM, m.heads)),
                      "norm": near_one((nM, m.d_ssm)),
                      "out_proj": normal((nM, m.d_ssm, H), m.d_ssm)},
            "attn": {"pre_norm": near_one((nA, H)),
                     "wq": normal((nA, H, h, d), H),
                     "wk": normal((nA, H, kv, d), H),
                     "wv": normal((nA, H, kv, d), H),
                     "wo": normal((nA, h, d, H), h * d)},
            "moe": {"pre_norm": near_one((nE, H)),
                    "wg": normal((nE, H, c.num_experts), H),
                    "bias": 0.01 * jax.random.normal(
                        next(k), (nE, c.num_experts), F32),
                    "latent_down": normal((nE, H, w), H),
                    "latent_up": normal((nE, w, H), w),
                    "w_up": normal((nE, c.experts_held, w, I), w),
                    "w_down": normal((nE, c.experts_held, I, w), I)
                    * 2 / c.top_k,
                    "shared_up": normal((nE, H, S), H),
                    "shared_down": normal((nE, S, H), S)},
            "final_norm": near_one((H,)),
            "lm_head": normal((H, V), H),
        }

    def _head(self, params: Any) -> jnp.ndarray:
        return params["lm_head"]

    def _norm(self, x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
        return _rms_norm(x, w.astype(self.config.dtype),
                         self.config.norm_eps)

    def state_parts(self) -> Tuple[Tuple[str, Tuple[int, ...], Any], ...]:
        """(name, shape, type) of what a sequence holds a MIXER layer; the
        state float32 by this constant, whatever the model's type."""
        return self.config.mamba.state_parts(self.config.dtype)

    def zero_state(self, rows: int) -> Dict[str, jnp.ndarray]:
        return self.config.mamba.zero_state(rows, self.config.dtype)

    # -- the stacks ----------------------------------------------------------

    def scanned(self, params: Any) -> Dict[str, Any]:
        """The three stacks with a leading dim of the pattern's periods, as
        a scan over the periods slices them, WITHOUT the expert leaves (a
        slice of an expert stack would be copied for the grouped matmul:
        they ride whole and are read at their layer)."""
        c = self.config
        periods = c.num_layers // len(c.period)
        stacks = {name: params[name] for name in STACKS.values()
                  if jax.tree.leaves(params[name])[0].shape[0]}
        if "moe" in stacks:
            stacks["moe"] = {n: v for n, v in stacks["moe"].items()
                             if n not in EXPERT_LEAVES}
        return jax.tree.map(
            lambda v: v.reshape((periods, v.shape[0] // periods)
                                + v.shape[1:]), stacks)

    def period_layers(self, pp: Any, p: Any) -> List[Any]:
        """The ``lp`` of each layer of period ``p`` out of the period's
        slice ``pp`` of :meth:`scanned`: the part's leaves at its place
        among the period's layers of the part; an expert layer also says
        where its experts lie in the whole stacks."""
        c = self.config
        seen = dict.fromkeys(STACKS, 0)
        out = []
        for part in c.period:
            i = seen[part]
            seen[part] += 1
            lp = jax.tree.map(lambda v: v[i], pp[STACKS[part]])
            if part == "E":
                lp = dict(lp, expert_layer=p * c.period.count("E") + i)
            out.append(lp)
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
        """``attn [N, h, d]`` → the layer's output: its projection added to
        the residual, and nothing behind it."""
        with jax.named_scope("attn/out"):
            return x + jnp.einsum("nhd,hdH->nH", attn,
                                  lp["wo"].astype(self.config.dtype))

    # -- the mixer -----------------------------------------------------------

    def mix_in(self, lp: Any, x: jnp.ndarray) -> jnp.ndarray:
        """Row-wise: ``x [N, H]`` → ``p [N, proj_dim]``, ``in_proj`` of the
        normed rows: ``[z | xs | B | C | dt]``."""
        with jax.named_scope("ssm/in_proj"):
            return self._norm(x, lp["pre_norm"]) \
                @ lp["in_proj"].astype(self.config.dtype)

    def mix_chunk(self, lp, p, state, tokens: int, valid):
        c = self.config
        return mamba2.chunk(c.mamba, lp, p, state, tokens, valid, c.dtype)

    def mix_decode(self, lp, p, state, held, valid):
        # the conv stays XLA's chain here and the kernel moves the tails
        # only (PR 60): with the kernel's own conv, in bfloat16 or float32,
        # this family's check read a token flipped at a near-tie on one
        # seed in twelve (0.088 of 0.10); with XLA's chain it reads the
        # values way's gaps on ten seeds of eleven and never over 0.05 on
        # nineteen; Falcon-H1's gaps never moved, so it keeps the kernel's
        c = self.config
        return mamba2.decode(c.mamba, lp, p, state, held, valid, c.dtype,
                             kernel_conv=False)

    def mix_out(self, lp: Any, p: jnp.ndarray, y: jnp.ndarray
                ) -> jnp.ndarray:
        """Row-wise: the gate, the norm a group, ``out_proj`` → what the
        layer adds to the residual ``[N, H]``."""
        c = self.config
        g = mamba2.gated_norm(c.mamba, lp, p, y, c.norm_eps, False, c.dtype)
        with jax.named_scope("ssm/out_proj"):
            return g @ lp["out_proj"].astype(c.dtype)

    def mix(self, lp, x, state, tokens: int, valid):
        """The mixer over ``R`` sequences' rows with their state as values
        in and out (``mamba2.mix``)."""
        return mamba2.mix(self, lp, x, state, tokens, valid)

    # -- the experts ---------------------------------------------------------

    def routed(self, lp: Any, h: jnp.ndarray, stacks: Any = None
               ) -> jnp.ndarray:
        """The held experts' part of the routed sum IN THE LATENT, scaled:
        ``h [N, H]`` (normed) → ``r [N, w]`` float32.  A layer cut out of
        the stacks carries ``expert_layer`` and its experts are read where
        they lie in ``stacks`` (``params["moe"]``)."""
        from ..telemetry import numerics

        dt = self.config.dtype
        experts, layer = (stacks, lp["expert_layer"]) \
            if "expert_layer" in lp else (lp, None)
        with jax.named_scope("moe/latent_down"):
            u = h @ lp["latent_down"].astype(dt)
        y, _, meta = self._moe_layer(
            lp["wg"], {n: experts[n] for n in EXPERT_LEAVES}, h[None],
            layer=layer, choice_bias=lp["bias"], rows=u[None])
        numerics.moe_stats(meta)
        return y[0].astype(F32) * self.config.routed_scaling_factor

    def shared(self, lp: Any, h: jnp.ndarray) -> jnp.ndarray:
        """The shared expert: two matrices at the hidden width every token
        passes, on every chip alike."""
        dt = self.config.dtype
        with jax.named_scope("moe/shared_expert"):
            up = jnp.square(jax.nn.relu(h @ lp["shared_up"].astype(dt)))
            return up @ lp["shared_down"].astype(dt)

    def experts(self, lp: Any, x: jnp.ndarray, stacks: Any = None
                ) -> jnp.ndarray:
        """An expert layer: ``x [N, H]`` → ``x + W_↑ r + shared(h)``."""
        dt = self.config.dtype
        h = self._norm(x, lp["pre_norm"])
        r = self.routed(lp, h, stacks)
        with jax.named_scope("moe/latent_up"):
            y = jnp.einsum("nw,wH->nH", r.astype(dt),
                           lp["latent_up"].astype(dt),
                           preferred_element_type=F32)
        return x + (y + self.shared(lp, h).astype(F32)).astype(dt)

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
        """``[B, S]`` ids → float32 logits ``[B, S, V]``: the mixer in
        blocks of ``chunk_size`` from a zero state, attention as a full
        causal softmax."""
        c = self.config
        dt = c.dtype
        B_, S_ = input_ids.shape

        def attention(lp, x):
            return self.attn_out(lp, x, causal_attention(
                *self.qkv(lp, x), B_, S_, dt))

        at = dict.fromkeys(STACKS, 0)
        x = self.embed(params, input_ids.reshape(-1))
        for part in c.pattern:
            stack = params[STACKS[part]]
            lp = jax.tree.map(lambda v: v[at[part]], {
                n: v for n, v in stack.items() if n not in EXPERT_LEAVES})
            if part == "M":
                x = x + mamba2.mix_sequences(self, lp, x, B_, S_,
                                             c.chunk_size)
            elif part == "*":
                x = attention(lp, x)
            else:
                x = self.experts(dict(lp, expert_layer=at[part]), x,
                                 params["moe"])
            at[part] += 1
        return self.logits(params, self.finalize(params, x)
                           ).reshape(B_, S_, -1)
