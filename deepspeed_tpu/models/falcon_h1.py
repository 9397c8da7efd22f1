"""Falcon-H1-family hybrid decoder (``model_type: falcon_h1``): every layer
runs a Mamba-2 mixer and a grouped-query attention IN PARALLEL on the same
normed input and adds both to the residual, then a SwiGLU MLP; eleven µP
multipliers scale fixed places.  Keys are the published config's; ``N`` is
RMSNorm with a learned weight and eps ``rms_norm_eps``; ``H`` the hidden
size.  Layer ``l``:

* Embedding: ``x = E[id] · embedding_multiplier``.
* ``u = N_in(x)``.  Both branches read ``u``.
* **Attention** (``num_heads`` query heads, ``num_kv_heads`` KV heads of
  ``head_dim``, no bias): ``q = W_q(u · attention_in_multiplier)``,
  ``k = W_k(u · attention_in_multiplier) · key_multiplier``,
  ``v = W_v(u · attention_in_multiplier)``; rotate-half rotary over the
  whole head, base ``rope_theta``, no scaling; causal
  ``softmax(q·kᵀ/√head_dim)·v``; ``o_attn = W_o(·) ·
  attention_out_multiplier``.
* **Mixer** (Mamba-2: ``d_ssm = mamba_n_heads × mamba_d_head``, state
  ``mamba_d_state``, ``mamba_n_groups`` groups, conv ``mamba_d_conv``):
  ``p = W_in(u · ssm_in_multiplier) ⊙ µ`` with ``µ`` piecewise constant
  from ``ssm_multipliers = [m0…m4]``: m0 on the gate ``z`` (d_ssm), m1 on
  ``xs`` (d_ssm), m2 on ``B`` (groups × state), m3 on ``C`` (the same), m4
  on ``dt`` (heads).  Split ``p = [z | xBC | dt]``.  Depthwise causal conv
  over time on ``xBC``, with bias, zeros before the sequence's first token:
  ``xBC_t ← silu(Σ_j w[:, j] ⊙ xBC_{t−(K−1)+j} + b)``.  Split ``xBC = [xs |
  B | C]``; head ``h`` uses group ``⌊h / (heads/groups)⌋``.
  ``Δ_{t,h} = softplus(dt_{t,h} + dt_bias_h)`` (no clamp: the published
  ``time_step_limit`` is (0, ∞)), ``A_h = −exp(A_log_h)``, ``a_{t,h} =
  exp(Δ_{t,h} A_h)``.  State ``S_h ∈ R^{d_head × d_state}``, zero at the
  sequence's start::

      S_t = a_t S_{t−1} + Δ_t · x_t B_tᵀ        y_t = S_t C_t + D_h x_t

  Gate then norm (``mamba_norm_before_gate`` false): ``g = y ⊙ silu(z)``,
  RMSNorm over each group of ``d_ssm / groups`` with a learned weight;
  ``o_ssm = W_out(g) · ssm_out_multiplier``.
* ``x' = x + o_attn + o_ssm``.
* **MLP**: ``n = N_ff(x')``, ``x'' = x' + W_down(W_up(n) ⊙ silu(W_gate(n) ·
  mlp_multipliers[0])) · mlp_multipliers[1]``.
* Head: final RMSNorm, ``logits = W_head(x) · lm_head_multiplier``, untied.

**Chunk form** (what prefill runs, :func:`mamba2.scan_chunk`; the
same mathematics): over a block of ``Q`` tokens with carried-in ``S_0`` and
``Λ_t = Σ_{s≤t} Δ_s A``::

    y_t = Σ_{s≤t} exp(Λ_t − Λ_s)(C_t·B_s) Δ_s x_s + exp(Λ_t)(S_0 C_t) + D x_t
    S_Q = exp(Λ_Q) S_0 + Σ_s exp(Λ_Q − Λ_s) Δ_s x_s B_sᵀ

A padded position has ``Δ = 0`` (so ``a = 1`` and no input: it moves no
state) and is not written into the conv tail.  Decode runs the one-token
form (``ssm_state_update``: a Pallas kernel on the chip, ``jax.numpy``
elsewhere); the chunk form is ``jax.numpy``.  The recurrence's own
numbers (``Δ``, ``a``, ``Λ``, the state) are float32 whatever the model's
type, the products take the model's type and sum in float32.

**What a sequence holds a layer** beside its keys and values: the state,
float32 whatever its length, held ``[heads, d_state, d_head]`` (the state's
width on the sublanes, the head's on the lanes: what the decode step's
kernel, ``ops/pallas/ssm_state_update.py``, moves without laying anything
out anew), and the conv's tail, its last ``K − 1`` inputs, time-major and
flat ``[(K − 1) · conv_dim]`` (held ``[…, K − 1, conv_dim]`` the pool's
array was re-laid out whole, twice a layer, around every access: its
second-minor dimension of 3 has another tiling inside the layer scan than
at the program's edge).  The serving engine keeps
both in a pool indexed by batch slot (``inference/v2/kv_cache.StateLayout``)
and hands the mixer a group of rows with their sequences' state: a chunk's
(:meth:`FalconH1Model.mix_chunk`) as values in and out, a decode step's
(:meth:`FalconH1Model.mix_decode`) as the pool's arrays with the layer and
the rows' first slot, which ``ssm_state_update`` and ``conv_tail_update``
move where they lie.

None of the multipliers is folded into a weight.  Weights are stacked
``[L, …]`` for the layer scan: ``layers: {attn_norm, mlp_norm [L, H], attn:
{wq [L, H, h, d], wk, wv [L, H, kv, d], wo [L, h, d, H]}, ssm: {in_proj
[L, H, P], conv_w [L, K, conv_dim], conv_b [L, conv_dim], dt_bias, A_log, D
[L, heads], norm [L, d_ssm], out_proj [L, d_ssm, H]}, mlp: {w_gate, w_up
[L, H, I], w_down [L, I, H]}}``, ``embed [V, H]``, ``final_norm [H]``,
``lm_head [H, V]``.  There is no backward-ready trainer path here: the
model is served (ROADMAP R7).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import mamba2
from .llama import _rms_norm, _rope
from .mamba2 import F32, SSM    # SSM: the name of the state's pool


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_layers: int = 72
    num_heads: int = 20
    num_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e11
    rms_norm_eps: float = 1e-5
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_d_state: int = 256
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    mamba_norm_before_gate: bool = False
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    #: on z, xs, B, C, dt
    ssm_multipliers: Tuple[float, ...] = (
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
        0.3535533905932738)
    #: on the gate's argument, on the down projection's result
    mlp_multipliers: Tuple[float, float] = (0.1767766952966369,
                                            0.011160714285714284)
    max_seq_len: int = 262144
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError("mamba_n_groups must divide mamba_n_heads")
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers has five entries (z, x, B, C, "
                             "dt) and mlp_multipliers two (gate, down)")

    @property
    def hd(self) -> int:
        return self.head_dim

    @property
    def d_ssm(self) -> int:
        return self.mamba.d_ssm

    @property
    def bc_dim(self) -> int:
        """``B`` (and ``C``) of one token: every group's."""
        return self.mamba.bc_dim

    @property
    def conv_dim(self) -> int:
        """The channels the conv runs over: ``[xs | B | C]``."""
        return self.mamba.conv_dim

    @property
    def proj_dim(self) -> int:
        """``in_proj``'s outputs: ``[z | xs | B | C | dt]``."""
        return self.mamba.proj_dim

    @property
    def mamba(self) -> mamba2.Mamba2Dims:
        """The mixer's sizes, as the shared arithmetic takes them."""
        return mamba2.Mamba2Dims(self.mamba_n_heads, self.mamba_d_head,
                                 self.mamba_d_state, self.mamba_n_groups,
                                 self.mamba_d_conv)

    @classmethod
    def tiny(cls, **kw) -> "FalconH1Config":
        d = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                 num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                 rope_theta=1e4, mamba_n_heads=4, mamba_d_head=8,
                 mamba_d_state=16, mamba_n_groups=2, mamba_chunk_size=16,
                 max_seq_len=256, dtype=jnp.float32)
        d.update(kw)
        return cls(**d)


class FalconH1Model:
    """Weights and their layout, and the layer's parts as the serving
    engine's hooks take them (``inference/v2/adapters.FalconH1V2Adapter``):
    :meth:`qkv`, :meth:`mix_in` / :meth:`mix_chunk` or :meth:`mix_decode` /
    :meth:`mix_out`, :meth:`post_attn`.  :meth:`forward` is the
    same parts over whole sequences without a cache."""

    def __init__(self, config: FalconH1Config, mesh: Any = None):
        self.config = config
        self.mesh = mesh

    # -- weights -------------------------------------------------------------

    def init_params(self, rng: jax.Array) -> Dict[str, Any]:
        """1/sqrt(fan_in) normal matrices.  What a trained mixer holds away
        from its initial constants is drawn so here too, so that a path
        that ignores one of them computes another function: ``A_log =
        log U[1, 16]``, ``dt_bias`` the inverse softplus of a ``Δ`` drawn
        log-uniform in [0.001, 0.1] (the mixer's own ``time_step_min/max``),
        ``D``, the conv's bias and every norm's weight near 1.  ``in_proj``'s
        columns for ``B`` and ``C`` are drawn ``1 / (ssm_in_multiplier ·
        ssm_multipliers[2 | 3])`` times larger, so that ``B`` and ``C``
        enter the conv at unit scale, as a trained mixer's do: at
        1/sqrt(fan_in) under the published multipliers the recurrence's
        ``S C`` was a thousandth of the skip's ``D x`` beside it, one
        multiplier on ``C`` dropped moved the logits by 2e-4 of their
        scale, and a path that lost the state altogether would have served
        the same tokens (PERF.md §6, PR 48)."""
        c = self.config
        H, I, V, L = (c.hidden_size, c.intermediate_size, c.vocab_size,
                      c.num_layers)
        h, kv, d = c.num_heads, c.num_kv_heads, c.head_dim
        heads, K = c.mamba_n_heads, c.mamba_d_conv
        k = iter(jax.random.split(rng, 24))

        def normal(shape, fan_in):
            return jax.random.normal(next(k), shape, F32) / np.sqrt(fan_in)

        def near_one(shape):
            return 1.0 + 0.1 * jax.random.normal(next(k), shape, F32)

        unit = np.ones((c.proj_dim,), np.float32)
        at = 2 * c.d_ssm
        for m in c.ssm_multipliers[2:4]:
            unit[at:at + c.bc_dim] = 1.0 / (c.ssm_in_multiplier * m)
            at += c.bc_dim
        delta = jnp.exp(jax.random.uniform(
            next(k), (L, heads), F32, np.log(1e-3), np.log(1e-1)))
        return {
            "embed": normal((V, H), H),
            "layers": {
                "attn_norm": near_one((L, H)),
                "mlp_norm": near_one((L, H)),
                "attn": {"wq": normal((L, H, h, d), H),
                         "wk": normal((L, H, kv, d), H),
                         "wv": normal((L, H, kv, d), H),
                         "wo": normal((L, h, d, H), h * d)},
                "ssm": {"in_proj": normal((L, H, c.proj_dim), H) * unit,
                        "conv_w": normal((L, K, c.conv_dim), K),
                        "conv_b": 0.1 * jax.random.normal(
                            next(k), (L, c.conv_dim), F32),
                        # softplus(dt_bias) = delta
                        "dt_bias": delta + jnp.log(-jnp.expm1(-delta)),
                        "A_log": jnp.log(jax.random.uniform(
                            next(k), (L, heads), F32, 1.0, 16.0)),
                        "D": near_one((L, heads)),
                        "norm": near_one((L, c.d_ssm)),
                        "out_proj": normal((L, c.d_ssm, H), c.d_ssm)},
                "mlp": {"w_gate": normal((L, H, I), H),
                        "w_up": normal((L, H, I), H),
                        "w_down": normal((L, I, H), I)},
            },
            "final_norm": near_one((H,)),
            "lm_head": normal((H, V), H),
        }

    def _head(self, params: Any) -> jnp.ndarray:
        return params["lm_head"]

    def _norm(self, x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
        return _rms_norm(x, w.astype(self.config.dtype),
                         self.config.rms_norm_eps)

    def zero_state(self, rows: int) -> Dict[str, jnp.ndarray]:
        """What ``rows`` sequences hold a layer before their first token."""
        return self.config.mamba.zero_state(rows, self.config.dtype)

    def state_parts(self) -> Tuple[Tuple[str, Tuple[int, ...], Any], ...]:
        """(name, shape, type) of what a sequence holds a layer.  The state
        is float32 whatever the model's type: it is multiplied by a decay
        near 1 once a token, thousands of times over."""
        return self.config.mamba.state_parts(self.config.dtype)

    # -- the layer's parts ---------------------------------------------------

    def embed(self, params: Any, tokens: jnp.ndarray) -> jnp.ndarray:
        dt = self.config.dtype
        return jnp.take(params["embed"].astype(dt), tokens, axis=0) \
            * jnp.asarray(self.config.embedding_multiplier, dt)

    def qkv(self, lp: Any, x: jnp.ndarray, positions: jnp.ndarray
            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """``x [N, H]`` at ``positions [N]`` → q ``[N, h, d]``, k and v
        ``[N, kv, d]``, rotary applied."""
        c = self.config
        dt = c.dtype
        a = lp["attn"]
        u = self._norm(x, lp["attn_norm"]) \
            * jnp.asarray(c.attention_in_multiplier, dt)
        q = jnp.einsum("nH,Hhd->nhd", u, a["wq"].astype(dt))
        k = jnp.einsum("nH,Hhd->nhd", u, a["wk"].astype(dt)) \
            * jnp.asarray(c.key_multiplier, dt)
        v = jnp.einsum("nH,Hhd->nhd", u, a["wv"].astype(dt))
        return (_rope(q, positions, c.rope_theta),
                _rope(k, positions, c.rope_theta), v)

    def _mup(self) -> np.ndarray:
        """``µ [P]``: ``ssm_multipliers`` laid over ``in_proj``'s outputs."""
        c = self.config
        widths = (c.d_ssm, c.d_ssm, c.bc_dim, c.bc_dim, c.mamba_n_heads)
        return np.concatenate([np.full((w,), m, np.float32)
                               for w, m in zip(widths, c.ssm_multipliers)])

    def mix(self, lp: Any, x: jnp.ndarray, state: Dict[str, jnp.ndarray],
            tokens: int, valid: jnp.ndarray
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        """The mixer over ``R`` sequences' rows with their state as values
        in and out (``mamba2.mix``: ``x [R·tokens, H]`` → ``o_ssm``, the
        state going out), through this family's four parts."""
        return mamba2.mix(self, lp, x, state, tokens, valid)

    def mix_in(self, lp: Any, x: jnp.ndarray) -> jnp.ndarray:
        """Row-wise: ``x [N, H]`` → ``p [N, proj_dim]``, ``in_proj`` of the
        normed rows under its multipliers: ``[z | xs | B | C | dt]``."""
        c = self.config
        dt = c.dtype
        with jax.named_scope("ssm/in_proj"):
            u = self._norm(x, lp["attn_norm"]) \
                * jnp.asarray(c.ssm_in_multiplier, dt)
            return (u @ lp["ssm"]["in_proj"].astype(dt)) \
                * jnp.asarray(self._mup(), dt)

    def mix_chunk(self, lp: Any, p: jnp.ndarray,
                  state: Dict[str, jnp.ndarray], tokens: int,
                  valid: jnp.ndarray
                  ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        """A group of ``R`` sequences' chunks ``p [R·tokens, proj_dim]``
        (:meth:`mix_in`'s) and their state coming in → (``y [R·tokens,
        d_ssm]`` float32, the state going out): the conv and one block of
        the chunk form."""
        c = self.config
        return mamba2.chunk(c.mamba, lp["ssm"], p, state, tokens, valid,
                            c.dtype)

    def mix_decode(self, lp: Any, p: jnp.ndarray,
                   state: Dict[str, jnp.ndarray],
                   held: Dict[str, Tuple[jnp.ndarray, Any, Any]],
                   valid: jnp.ndarray
                   ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray],
                              Dict[str, jnp.ndarray]]:
        """A decode step's ``R`` rows ``p [R, proj_dim]``, a token a
        sequence, whose state lies in the pool: ``held[part] = (array
        [layers, slots, …], layer, first slot)``, row ``r``'s at ``(layer,
        first + r)``, the conv's tails and the states alike (``state``, the
        parts handed over as values, is empty) → (``y [R, d_ssm]`` float32,
        no values, the two arrays with the rows' parts moved one step where
        they lie: ``mamba2.decode``)."""
        c = self.config
        return mamba2.decode(c.mamba, lp["ssm"], p, state, held, valid,
                             c.dtype)

    def mix_out(self, lp: Any, p: jnp.ndarray, y: jnp.ndarray
                ) -> jnp.ndarray:
        """Row-wise: ``p [N, proj_dim]`` (its gate ``z``) and ``y [N,
        d_ssm]`` float32 → ``o_ssm [N, H]``: the gate, the norm over each
        group, ``out_proj`` under its multiplier."""
        c = self.config
        dt = c.dtype
        m = lp["ssm"]
        g = mamba2.gated_norm(c.mamba, m, p, y, c.rms_norm_eps,
                              c.mamba_norm_before_gate, dt)
        with jax.named_scope("ssm/out_proj"):
            return (g @ m["out_proj"].astype(dt)) \
                * jnp.asarray(c.ssm_out_multiplier, dt)

    def post_attn(self, lp: Any, x: jnp.ndarray, attn: jnp.ndarray
                  ) -> jnp.ndarray:
        """``x [N, H]`` (the residual with the mixer's ``o_ssm`` already
        added), ``attn [N, h, d]`` → the layer's output: the attention's
        projection added, then the MLP."""
        c = self.config
        dt = c.dtype
        out = jnp.einsum("nhd,hdH->nH", attn, lp["attn"]["wo"].astype(dt)) \
            * jnp.asarray(c.attention_out_multiplier, dt)
        x = x + out
        n = self._norm(x, lp["mlp_norm"])
        m = lp["mlp"]
        gate = jax.nn.silu((n @ m["w_gate"].astype(dt))
                           * jnp.asarray(c.mlp_multipliers[0], dt))
        y = ((n @ m["w_up"].astype(dt)) * gate) @ m["w_down"].astype(dt)
        return x + y * jnp.asarray(c.mlp_multipliers[1], dt)

    def finalize(self, params: Any, x: jnp.ndarray) -> jnp.ndarray:
        return self._norm(x, params["final_norm"])

    def logits(self, params: Any, x: jnp.ndarray) -> jnp.ndarray:
        """The head over normed ``[N, H]`` → float32 ``[N, V]``."""
        dt = self.config.dtype
        return jnp.einsum("nH,HV->nV", x, self._head(params).astype(dt),
                          preferred_element_type=F32) \
            * self.config.lm_head_multiplier

    # -- whole sequences, no cache -------------------------------------------

    def forward(self, params: Any, input_ids: jnp.ndarray) -> jnp.ndarray:
        """``[B, S]`` ids → float32 logits ``[B, S, V]``: the mixer in
        blocks of ``mamba_chunk_size`` from a zero state, attention as a
        full causal softmax."""
        c = self.config
        dt = c.dtype
        B_, S_ = input_ids.shape
        pos = jnp.tile(jnp.arange(S_), B_)
        seen = jnp.arange(S_)[None, :] <= jnp.arange(S_)[:, None]
        rep = c.num_heads // c.num_kv_heads

        def layer(x, lp):
            q, k, v = self.qkv(lp, x, pos)
            q = q.reshape(B_, S_, c.num_kv_heads, rep, c.head_dim)
            k, v = (t.reshape(B_, S_, c.num_kv_heads, c.head_dim)
                    for t in (k, v))
            s = jnp.einsum("bqgrd,bkgd->bgrqk", q, k).astype(F32) \
                / np.sqrt(c.head_dim)
            p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1).astype(dt)
            attn = jnp.einsum("bgrqk,bkgd->bqgrd", p, v).reshape(
                B_ * S_, c.num_heads, c.head_dim)

            o_ssm = mamba2.mix_sequences(self, lp, x, B_, S_,
                                         c.mamba_chunk_size)
            return self.post_attn(lp, x + o_ssm, attn), None

        x = self.embed(params, input_ids.reshape(-1))
        x, _ = jax.lax.scan(layer, x, params["layers"])
        return self.logits(params, self.finalize(params, x)
                           ).reshape(B_, S_, -1)
