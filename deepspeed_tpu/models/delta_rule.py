"""A gated delta rule with a decay a KEY CHANNEL (Kimi Delta Attention, KDA:
arXiv 2510.26692), the recurrence's own arithmetic for the families that
have one (``models/solar_open2.py``): the conv step over its three
streams (a decode step's through ``ops/pallas/conv_tail_update``), the
chunk form, the decode step's update through
``ops/pallas/delta_state_update``, the gated norm.  What a family brings is
ARGUMENTS: the sizes (:class:`DeltaDims`), the leaves ``m`` of one layer's
recurrence (``conv_w [K, 3 · heads · d]``, ``dt_bias [heads · d]``, ``A_log
[heads]``, ``norm [d]``), the model's type, the norm's eps.  The projections
around it are the family's, and what they hand over is ``p``, a row a
token: ``{"qkv": [N, 3 · heads · d]`` before the conv (``q | k | v``),
``"f": [N, heads · d]`` the decay's pre-activation, ``"beta": [N, heads]``
logits, ``"gate": [N, heads · d]`` the output gate's pre-activation}``.

Depthwise causal conv over time on each stream, no bias, zeros before the
sequence's first token: ``x_t ← silu(Σ_j w[j] ⊙ x_{t−(K−1)+j})``.  A head
at a time, ``d`` numbers a head for keys and values alike: ``q = q̂ / ‖q̂‖
· d^(−1/2)``, ``k = k̂ / ‖k̂‖`` (the L2 norm over the head, ``x · rsqrt(Σ
x² + 1e-6)``), ``v = v̂``; ``g = −exp(A_log) · softplus(f + dt_bias) ∈
R^d`` a head, ``α = exp(g)``; ``β = 2 · sigmoid(beta)`` (the factor 2 lets
``I − β k kᵀ`` have a negative eigenvalue).  State ``S ∈ R^{d × d}`` a
head (key channels by value numbers), zero at the sequence's start::

    S' = Diag(α_t) S_{t−1}      u = k_tᵀ S'
    S_t = S' + k_t ⊗ β_t (v_t − u)          o_t = S_tᵀ q_t

**Chunk form** (what prefill runs, :func:`scan_chunk`; the same
mathematics): over a block of ``Q`` tokens with carried-in ``S_0`` and ``G_t
= Σ_{s≤t} g_s`` (a channel), the corrections ``w_t = β_t (v_t − u_t)``
solve a unit lower-triangular system, and everything else is products::

    A_ts = Σ_c k_t[c] k_s[c] exp(G_t[c] − G_s[c])   (s < t)
    (I + Diag(β) A) W = Diag(β) (V − (K ⊙ exp G) S_0)
    o_t = (q_t ⊙ exp G_t)ᵀ S_0 + Σ_{s≤t} [Σ_c q_t[c] k_s[c] exp(G_t[c] − G_s[c])] w_s
    S_Q = Diag(exp G_Q) S_0 + Σ_s (k_s ⊙ exp(G_Q − G_s)) ⊗ w_s

A channel may decay by a factor of five a token and more, so ``exp(−G)``
alone overflows inside a block of any useful length: every decay enters as
a DIFFERENCE ``exp(G_t − G_s)``, ``t ≥ s``, formed pairwise inside blocks of
:data:`BLOCK` tokens (never over 1; an underflow to 0 is the true value to
float32), and across blocks the state is carried as values.  The systems
of all the chunk's blocks are solved together before the blocks are walked
(``A`` reads no state): forward substitution over a block's ``Q`` rows.

A padded position has ``g = 0`` and ``β = 0`` (so ``α = 1`` and no
correction: it moves no state) and is not written into the conv's tail.
The recurrence's own numbers (``g``, ``β``, the state, the systems) are
float32 whatever the model's type; the products with the state take the
model's type and sum in float32.

**What a sequence holds a layer** (:meth:`DeltaDims.state_parts`): the
state, float32 whatever its length, key channels on the sublanes and the
value's numbers on the lanes (what the decode step's kernel moves without
laying anything out anew), and the conv's tail, the last ``K − 1`` inputs of
the three streams, time-major and flat ``[(K − 1) · 3 · heads · d]`` (as
``Mamba2Dims`` holds its own: no dimension of three is a tiled one, and a
decode step's kernel reads a tap as a stretch of lanes).  A decode step
moves BOTH where they lie.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops.pallas.conv_tail_update import conv_tail_update
from ..ops.pallas.delta_state_update import delta_state_update

#: the name of the per-sequence state's pool, and of its state part
DELTA = "delta"
F32 = jnp.float32
#: tokens a block of the chunk form (the largest divisor of a chunk at most
#: this): the pairwise decays are ``BLOCK`` numbers a token a channel
BLOCK = 16
HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class DeltaDims:
    heads: int
    d_head: int             # a key's and a value's numbers a head alike
    d_conv: int

    @property
    def width(self) -> int:
        """One stream (q, k or v) of one token: every head's."""
        return self.heads * self.d_head

    def state_parts(self, dtype: Any
                    ) -> Tuple[Tuple[str, Tuple[int, ...], Any], ...]:
        """(name, shape, type) of what a sequence holds a layer.  The state
        is float32 whatever the model's type: it is decayed and corrected
        once a token, thousands of times over."""
        return ((DELTA, (self.heads, self.d_head, self.d_head), F32),
                ("conv", ((self.d_conv - 1) * 3 * self.width,), dtype))

    def zero_state(self, rows: int, dtype: Any) -> Dict[str, jnp.ndarray]:
        """What ``rows`` sequences hold a layer before their first token."""
        return {name: jnp.zeros((rows,) + shape, dt)
                for name, shape, dt in self.state_parts(dtype)}


def _l2(x: jnp.ndarray) -> jnp.ndarray:
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _streams(dims: DeltaDims, out: jnp.ndarray):
    """The conv's output ``[R, T, 3·width]`` → ``q``, ``k`` normalised and
    ``v``, each ``[R, T, heads, d]`` float32."""
    q, k, v = (out[..., i * dims.width:(i + 1) * dims.width].astype(
        F32).reshape(out.shape[:2] + (dims.heads, dims.d_head))
        for i in range(3))
    return _l2(q) * dims.d_head ** -0.5, _l2(k), v


def conv(dims: DeltaDims, m: Any, qkv: jnp.ndarray, tail: jnp.ndarray,
         tokens: int, valid: jnp.ndarray, dt: Any):
    """A group's rows ``qkv [R·tokens, 3·width]`` through the conv from the
    sequences' tails ``[R, (K−1)·3·width]`` → (``q``, ``k`` normalised and
    ``v``, each ``[R, T, heads, d]`` float32, the tails going out)."""
    R, T, K = qkv.shape[0] // tokens, tokens, dims.d_conv
    with jax.named_scope("kda/conv"):
        # the tail's K−1 inputs, then the rows': output t sums inputs
        # t … t+K−1 of that; the tail going out ends at the last real one
        seq = jnp.concatenate([tail.astype(dt).reshape(R, K - 1, -1),
                               qkv.reshape(R, T, -1)], axis=1)
        w = m["conv_w"].astype(dt)
        out = jax.nn.silu(sum(seq[:, j:j + T] * w[j] for j in range(K)))
        left = jax.vmap(lambda s, n: jax.lax.dynamic_slice_in_dim(
            s, n, K - 1, 0))(seq, valid)
        q, k, v = _streams(dims, out)
    return q, k, v, left.reshape(tail.shape).astype(tail.dtype)


def gates(dims: DeltaDims, m: Any, p: Any, tokens: int, valid: jnp.ndarray):
    """The rows' log decay ``g [R, T, heads, d]`` and ``β [R, T, heads]``,
    float32, both 0 at a padded position."""
    R, T = p["f"].shape[0] // tokens, tokens
    real = (jnp.arange(T)[None, :] < valid[:, None])[..., None]   # [R, T, 1]
    with jax.named_scope("kda/gates"):
        step = jax.nn.softplus(p["f"].astype(F32) + m["dt_bias"].astype(F32))
        g = -jnp.exp(m["A_log"].astype(F32))[:, None] * step.reshape(
            R, T, dims.heads, dims.d_head)
        beta = 2.0 * jax.nn.sigmoid(p["beta"].astype(F32)).reshape(R, T, -1)
        return jnp.where(real[..., None], g, 0.0), jnp.where(real, beta, 0.0)


def chunk(dims: DeltaDims, m: Any, p: Any, state: Dict[str, jnp.ndarray],
          tokens: int, valid: jnp.ndarray, dt: Any
          ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """A group of ``R`` sequences' chunks (``p``'s leaves ``[R·tokens, …]``)
    and their state coming in → (``o [R·tokens, width]`` float32, the state
    going out): the conv and the chunk form."""
    q, k, v, tail = conv(dims, m, p["qkv"], state["conv"], tokens, valid, dt)
    g, beta = gates(dims, m, p, tokens, valid)
    o, S = scan_chunk(q, k, v, g, beta, state[DELTA].astype(F32), dt)
    return (o.reshape(-1, dims.width),
            {DELTA: S.astype(state[DELTA].dtype), "conv": tail})


def decode(dims: DeltaDims, m: Any, p: Any,
           held: Dict[str, Tuple[jnp.ndarray, Any, Any]], valid: jnp.ndarray
           ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """A decode step's ``R`` rows, a token a sequence, whose state lies in
    the pool: ``held[part] = (array [layers, slots, …], layer, first
    slot)``, row ``r``'s at ``(layer, first + r)``, the conv's tails
    ``[…, (K−1)·3·width]`` and the states ``[…, heads, d, d]`` → (``o [R,
    width]`` float32, the two arrays with the rows' parts moved one step
    where they lie: ``conv_tail_update``, which emits the conv's output,
    and ``delta_state_update``, which reads ``o = Sᵀ q`` off the new
    values).  A row with ``valid`` 0 moves neither."""
    array, layer, first = held["conv"]
    with jax.named_scope("kda/conv"):
        tails, out = conv_tail_update(array, layer, first, p["qkv"],
                                      m["conv_w"], None, valid)
        q, k, v = _streams(dims, out[:, None])
    g, beta = gates(dims, m, p, 1, valid)
    array, layer, first = held[DELTA]
    with jax.named_scope("kda/state_update"):
        # a row that is no sequence's: α = 1, β = 0 and no key or query
        live = (valid > 0)[:, None, None]
        array, o = delta_state_update(
            array, layer, first, a=jnp.exp(g[:, 0]),
            k=jnp.where(live, k[:, 0], 0.0), q=jnp.where(live, q[:, 0], 0.0),
            beta=beta[:, 0], v=v[:, 0])
    return o.reshape(-1, dims.width), {DELTA: array, "conv": tails}


def gated_norm(dims: DeltaDims, m: Any, gate: jnp.ndarray, o: jnp.ndarray,
               eps: float, dt: Any) -> jnp.ndarray:
    """``o [N, width]`` float32 and the rows' gate ``[N, width]`` → ``[N,
    width]`` in the model's type: RMSNorm over each head's ``d`` numbers
    under one learned weight ``[d]`` the heads share, times
    ``sigmoid(gate)``."""
    with jax.named_scope("kda/gated_norm"):
        o = o.reshape(-1, dims.heads, dims.d_head)
        normed = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                                   + eps) * m["norm"].astype(F32)
        return (normed.reshape(-1, dims.width)
                * jax.nn.sigmoid(gate.astype(F32))).astype(dt)


def _block(tokens: int) -> int:
    """The largest divisor of ``tokens`` that is at most :data:`BLOCK`."""
    return next(b for b in range(min(tokens, BLOCK), 0, -1)
                if tokens % b == 0)


def scan_chunk(q, k, v, g, beta, S, dt):
    """The chunk form over ``T`` tokens a sequence in blocks of ``Q``
    (module docstring): ``q``/``k``/``v``/``g [R, T, n, d]`` float32
    (``g`` 0 at a padded position), ``beta [R, T, n]`` (0 there),
    carried-in ``S [R, n, d, d]`` float32 → (``o [R, T, n, d]`` float32,
    the state after the chunk).  The decays, the systems and the blocks'
    small products are float32; the products with the state take ``dt`` and
    sum in float32."""
    with jax.named_scope("kda/chunk"):
        R, T, n, d = q.shape
        Q = _block(T)
        nb = T // Q
        # [R, nb, Q, n, d] → a head's blocks side by side [R, nb, n, Q, d]
        by_block = lambda x: jnp.moveaxis(
            x.reshape((R, nb, Q) + x.shape[2:]), 2, 3)
        q, k, v, g = (by_block(x) for x in (q, k, v, g))
        beta = by_block(beta)                               # [R, nb, n, Q]
        G = jnp.cumsum(g, axis=3)                           # ≤ 0, falling
        # the pairwise decays exp(G_t − G_s), t ≥ s, a channel: ≤ 1
        seen = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
        decay = jnp.exp(jnp.where(
            seen[..., None], G[..., :, None, :] - G[..., None, :, :],
            -jnp.inf))                                      # [R,nb,n,t,s,d]
        kd = k[..., None, :, :] * decay
        A = jnp.sum(k[..., :, None, :] * kd, axis=-1)       # [R,nb,n,t,s]
        B = jnp.sum(q[..., :, None, :] * kd, axis=-1)       # s ≤ t
        # (I + Diag(β) A_strict)⁻¹ by forward substitution: row t of the
        # inverse is e_t − β_t Σ_{s<t} A_ts · (row s)
        N = beta[..., None] * jnp.where(
            jnp.arange(Q)[:, None] > jnp.arange(Q)[None, :], A, 0.0)
        rows = []
        for t in range(Q):
            row = jax.nn.one_hot(t, Q, dtype=F32)
            if t:
                row = row - jnp.einsum("...s,...sj->...j", N[..., t, :t],
                                       jnp.stack(rows, axis=-2))
            rows.append(jnp.broadcast_to(row, N.shape[:-2] + (Q,)))
        inverse = jnp.stack(rows, axis=-2)                  # [R,nb,n,Q,Q]
        last = G[..., -1:, :]                               # [R,nb,n,1,d]
        k_in, q_in = k * jnp.exp(G), q * jnp.exp(G)         # from S_0
        k_out = k * jnp.exp(last - G)                       # to the end
        across = lambda x: jnp.moveaxis(x, 1, 0)            # blocks first

        def block(S, xs):
            inverse, B, beta, v, k_in, q_in, k_out, last = xs
            lhs = jnp.concatenate([k_in, q_in], axis=2).astype(dt)
            from_state = jnp.einsum("rntk,rnkv->rntv", lhs, S.astype(dt),
                                    preferred_element_type=F32)
            w = jnp.einsum("rnts,rnsv->rntv", inverse,
                           beta[..., None] * (v - from_state[:, :, :Q]),
                           precision=HIGHEST)
            o = from_state[:, :, Q:] + jnp.einsum(
                "rnts,rnsv->rntv", B, w, precision=HIGHEST)
            S = jnp.exp(last)[:, :, 0, :, None] * S + jnp.einsum(
                "rntk,rntv->rnkv", k_out.astype(dt), w.astype(dt),
                preferred_element_type=F32)
            return S, o

        S, o = jax.lax.scan(block, S, tuple(across(x) for x in (
            inverse, B, beta, v, k_in, q_in, k_out, last)))
        # [nb, R, n, Q, d] → [R, T, n, d]
        return jnp.moveaxis(o, (0, 3), (1, 2)).reshape(R, T, n, d), S
