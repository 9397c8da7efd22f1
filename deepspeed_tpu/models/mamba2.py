"""The Mamba-2 mixer's own arithmetic, shared by the families that have one
(``models/falcon_h1.py``, beside attention in every layer;
``models/nemotron_h.py``, a layer of its own): the conv step (a decode
step's through ``ops/pallas/conv_tail_update``), the chunk form, the decode
step's update through ``ops/pallas/ssm_state_update``, the gated norm.
What differs between families is ARGUMENTS: the sizes
(:class:`Mamba2Dims`), the leaves ``m`` of one layer's mixer (``conv_w [K,
conv_dim]``, ``conv_b [conv_dim]``, ``dt_bias``, ``A_log``, ``D [heads]``,
``norm [d_ssm]``), the model's type, the norm's eps.  The projections
around it (``in_proj``, ``out_proj``), the norm before them and any
multiplier are the family's.

``in_proj``'s outputs are ``p = [z | xs | B | C | dt]`` (``d_ssm | d_ssm |
groups·d_state | groups·d_state | heads``).  Depthwise causal conv over time
on ``xBC = [xs | B | C]``, with bias, zeros before the sequence's first
token: ``xBC_t ← silu(Σ_j w[j] ⊙ xBC_{t−(K−1)+j} + b)``.  Head ``h`` uses
group ``⌊h / (heads/groups)⌋``.  ``Δ_{t,h} = softplus(dt_{t,h} +
dt_bias_h)`` (no clamp), ``A_h = −exp(A_log_h)``, ``a_{t,h} = exp(Δ_{t,h}
A_h)``.  State ``S_h ∈ R^{d_state × d_head}``, zero at the sequence's
start::

    S_t = a_t S_{t−1} + Δ_t · B_t x_tᵀ        y_t = S_tᵀ C_t + D_h x_t

**Chunk form** (what prefill runs, :func:`scan_chunk`; the same
mathematics): over a block of ``Q`` tokens with carried-in ``S_0`` and
``Λ_t = Σ_{s≤t} Δ_s A``::

    y_t = Σ_{s≤t} exp(Λ_t − Λ_s)(C_t·B_s) Δ_s x_s + exp(Λ_t)(S_0 C_t) + D x_t
    S_Q = exp(Λ_Q) S_0 + Σ_s exp(Λ_Q − Λ_s) Δ_s x_s B_sᵀ

A padded position has ``Δ = 0`` (so ``a = 1`` and no input: it moves no
state) and is not written into the conv tail.  The recurrence's own numbers
(``Δ``, ``a``, ``Λ``, the state) are float32 whatever the model's type, the
products take the model's type and sum in float32.

**What a sequence holds a layer** (:meth:`Mamba2Dims.state_parts`): the
state, float32 whatever its length, ``d_state`` on the sublanes and the
head's numbers on the lanes (what the decode step's kernel moves without
laying anything out anew), and the conv's tail, its last ``K − 1`` inputs,
time-major and flat ``[(K − 1) · conv_dim]``; a decode step moves the
state where it lies, and the tail too where the family's kind states it
``in_place``.  A head of fewer than 128
numbers shares its lane row with its neighbours (:attr:`Mamba2Dims.pack`
heads of one group a row: ``[heads / pack, d_state, pack · d_head]``): an
array whose minor dimension is 64 is padded to 128 lanes in HBM, twice the
pool.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops.pallas.conv_tail_update import conv_tail_update
from ..ops.pallas.ssm_state_update import ssm_state_update

#: the name of the per-sequence state's pool, and of its state part
SSM = "ssm"
F32 = jnp.float32
LANES = 128


@dataclasses.dataclass(frozen=True)
class Mamba2Dims:
    heads: int
    d_head: int
    d_state: int
    groups: int
    d_conv: int

    def __post_init__(self):
        if self.heads % self.groups:
            raise ValueError("the mixer's groups must divide its heads")

    @property
    def d_ssm(self) -> int:
        return self.heads * self.d_head

    @property
    def bc_dim(self) -> int:
        """``B`` (and ``C``) of one token: every group's."""
        return self.groups * self.d_state

    @property
    def conv_dim(self) -> int:
        """The channels the conv runs over: ``[xs | B | C]``."""
        return self.d_ssm + 2 * self.bc_dim

    @property
    def proj_dim(self) -> int:
        """``in_proj``'s outputs: ``[z | xs | B | C | dt]``."""
        return self.d_ssm + self.conv_dim + self.heads

    @property
    def pack(self) -> int:
        """Heads of one group that share a lane row of the held state."""
        pack = max(1, LANES // self.d_head)
        return pack if (self.heads // self.groups) % pack == 0 else 1

    def state_parts(self, dtype: Any
                    ) -> Tuple[Tuple[str, Tuple[int, ...], Any], ...]:
        """(name, shape, type) of what a sequence holds a layer.  The state
        is float32 whatever the model's type: it is multiplied by a decay
        near 1 once a token, thousands of times over."""
        return ((SSM, (self.heads // self.pack, self.d_state,
                       self.pack * self.d_head), F32),
                ("conv", ((self.d_conv - 1) * self.conv_dim,), dtype))

    def zero_state(self, rows: int, dtype: Any) -> Dict[str, jnp.ndarray]:
        """What ``rows`` sequences hold a layer before their first token."""
        return {name: jnp.zeros((rows,) + shape, dt)
                for name, shape, dt in self.state_parts(dtype)}

    def by_head(self, held: jnp.ndarray) -> jnp.ndarray:
        """The held states ``[R, heads/pack, N, pack·P]`` → ``[R, G, k, N,
        P]`` float32, a head at a time."""
        R, N, P, G = held.shape[0], self.d_state, self.d_head, self.groups
        S = held.astype(F32)
        if self.pack > 1:
            S = S.reshape(R, -1, N, self.pack, P).transpose(0, 1, 3, 2, 4)
        return S.reshape(R, G, self.heads // G, N, P)

    def as_held(self, S: jnp.ndarray, like: jnp.ndarray) -> jnp.ndarray:
        """:meth:`by_head` back: ``[R, G, k, N, P]`` → ``like``'s shape and
        type."""
        if self.pack > 1:
            R, N, P = S.shape[0], self.d_state, self.d_head
            S = S.reshape(R, -1, self.pack, N, P).transpose(0, 1, 3, 2, 4)
        return S.reshape(like.shape).astype(like.dtype)


def _split(dims: Mamba2Dims, m: Any, out: jnp.ndarray, dt_raw: jnp.ndarray,
           valid: jnp.ndarray):
    """The conv's output ``[R, T, conv_dim]`` and the rows' ``dt [R, T,
    heads]`` → (``xs [R, T, G, k, P]``, ``B`` and ``C`` ``[R, T, G, N]``,
    ``Δ [R, T, G, k]`` float32, 0 at a padded position, ``A [G, k]``)."""
    R, T = out.shape[:2]
    heads, P, N, G = dims.heads, dims.d_head, dims.d_state, dims.groups
    d_ssm, bc = dims.d_ssm, dims.bc_dim
    real = jnp.arange(T)[None, :] < valid[:, None]             # [R, T]
    xs = out[..., :d_ssm].reshape(R, T, G, heads // G, P)
    B = out[..., d_ssm:d_ssm + bc].reshape(R, T, G, N)
    C = out[..., d_ssm + bc:].reshape(R, T, G, N)
    delta = jax.nn.softplus(dt_raw.astype(F32) + m["dt_bias"].astype(F32))
    delta = jnp.where(real[..., None], delta, 0.0
                      ).reshape(R, T, G, heads // G)
    A = -jnp.exp(m["A_log"].astype(F32)).reshape(G, heads // G)
    return xs, B, C, delta, A


def conv(dims: Mamba2Dims, m: Any, p: jnp.ndarray, tail: jnp.ndarray,
         tokens: int, valid: jnp.ndarray, dt: Any):
    """A group's rows ``p [R·tokens, proj_dim]`` through the conv from the
    sequences' tails ``[R, (K−1)·conv_dim]`` → (``xs [R, T, G, k, P]``,
    ``B`` and ``C`` ``[R, T, G, N]``, ``Δ [R, T, G, k]`` float32, 0 at a
    padded position, ``A [G, k]``, the tails going out)."""
    R, T, K = p.shape[0] // tokens, tokens, dims.d_conv
    d_ssm, conv_dim = dims.d_ssm, dims.conv_dim
    p = p.reshape(R, T, dims.proj_dim)
    xbc, dt_raw = (p[..., d_ssm:d_ssm + conv_dim], p[..., d_ssm + conv_dim:])
    with jax.named_scope("ssm/conv"):
        # the tail's K−1 inputs, then the rows': output t sums inputs
        # t … t+K−1 of that; the tail going out ends at the last real one
        seq = jnp.concatenate([tail.astype(dt).reshape(
            R, K - 1, conv_dim), xbc], axis=1)
        w = m["conv_w"].astype(dt)
        out = sum(seq[:, j:j + T] * w[j] for j in range(K)) \
            + m["conv_b"].astype(dt)
        out = jax.nn.silu(out)
        left = jax.vmap(lambda s, n: jax.lax.dynamic_slice_in_dim(
            s, n, K - 1, 0))(seq, valid)
    return _split(dims, m, out, dt_raw, valid) \
        + (left.reshape(tail.shape).astype(tail.dtype),)


def skip(dims: Mamba2Dims, m: Any, y: jnp.ndarray, xs: jnp.ndarray
         ) -> jnp.ndarray:
    """``y [R, T, G, k, P]`` float32 with the skip ``D x`` added → ``[R·T,
    d_ssm]``."""
    R, T, G, k, _ = xs.shape
    y = y + m["D"].astype(F32).reshape(G, k, 1) * xs.astype(F32)
    return y.reshape(R * T, dims.d_ssm)


def chunk(dims: Mamba2Dims, m: Any, p: jnp.ndarray,
          state: Dict[str, jnp.ndarray], tokens: int, valid: jnp.ndarray,
          dt: Any) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """A group of ``R`` sequences' chunks ``p [R·tokens, proj_dim]`` and
    their state coming in → (``y [R·tokens, d_ssm]`` float32, the state
    going out): the conv and one block of the chunk form."""
    xs, B, C, delta, A, tail = conv(dims, m, p, state["conv"], tokens, valid,
                                    dt)
    y, S = scan_chunk(xs, B, C, delta, A, dims.by_head(state[SSM]))
    return skip(dims, m, y, xs), {SSM: dims.as_held(S, state[SSM]),
                                  "conv": tail}


def decode(dims: Mamba2Dims, m: Any, p: jnp.ndarray,
           state: Dict[str, jnp.ndarray],
           held: Dict[str, Tuple[jnp.ndarray, Any, Any]], valid: jnp.ndarray,
           dt: Any, kernel_conv: bool = True
           ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray],
                      Dict[str, jnp.ndarray]]:
    """A decode step's ``R`` rows ``p [R, proj_dim]``, a token a sequence,
    whose state lies in the pool: ``held[part] = (array [layers, slots,
    …], layer, first slot)``, row ``r``'s at ``(layer, first + r)``, the
    states ``[…, heads/pack, d_state, pack·d_head]`` and, where the family
    states it ``in_place``, the conv's tails ``[…, (K−1)·conv_dim]`` (else
    ``state["conv"]`` holds the rows' tails as values) → (``y [R, d_ssm]``
    float32, the tails going out if they came as values, the arrays with
    the rows' parts moved one step where they lie: ``conv_tail_update``,
    which emits the conv's output, and ``ssm_state_update``, which reads
    ``y = S C`` off the new values).  A row with ``valid`` 0 moves
    neither.  A family that passes ``kernel_conv`` False has the kernel
    move its tails only and hand them back as they lay: the conv is then
    :func:`conv`, XLA's chain, as where the tail is a value."""
    d_ssm, conv_dim = dims.d_ssm, dims.conv_dim
    values, arrays = {}, {}
    if "conv" in held and kernel_conv:
        array, layer, first = held["conv"]
        with jax.named_scope("ssm/conv"):
            arrays["conv"], out = conv_tail_update(
                array, layer, first, p[:, d_ssm:d_ssm + conv_dim],
                m["conv_w"], m["conv_b"], valid)
        xs, B, C, delta, A = _split(dims, m, out[:, None],
                                    p[:, None, d_ssm + conv_dim:], valid)
    elif "conv" in held:
        array, layer, first = held["conv"]
        with jax.named_scope("ssm/conv"):
            arrays["conv"], tail = conv_tail_update(
                array, layer, first, p[:, d_ssm:d_ssm + conv_dim], None,
                None, valid)
        xs, B, C, delta, A, _ = conv(dims, m, p, tail, 1, valid, dt)
    else:
        xs, B, C, delta, A, values["conv"] = conv(
            dims, m, p, state["conv"], 1, valid, dt)
    R, _, G, k, P = xs.shape
    array, layer, first = held[SSM]
    with jax.named_scope("ssm/state_update"):
        d = delta[:, 0].reshape(R, G * k)
        a = jnp.exp(d * A.reshape(G * k))
        dx = d[..., None] * xs[:, 0].reshape(R, G * k, P).astype(F32)
        if dims.pack > 1:
            # pack heads a lane row: the decay a lane, as dx is
            rows = (R, G * k // dims.pack, dims.pack * P)
            a = jnp.broadcast_to(a[..., None], dx.shape).reshape(rows)
            dx = dx.reshape(rows)
        array, y = ssm_state_update(array, layer, first, a=a, dx=dx,
                                    b=B[:, 0], c=C[:, 0])
    return (skip(dims, m, y.reshape(xs.shape), xs), values,
            dict(arrays, **{SSM: array}))


def gated_norm(dims: Mamba2Dims, m: Any, p: jnp.ndarray, y: jnp.ndarray,
               eps: float, norm_before_gate: bool, dt: Any) -> jnp.ndarray:
    """``p [N, proj_dim]`` (its gate ``z``) and ``y [N, d_ssm]`` float32 →
    ``[N, d_ssm]`` in the model's type: the gate and the norm over each
    group of ``d_ssm / groups`` with a learned weight (one statistic a
    group)."""
    G, d_ssm = dims.groups, dims.d_ssm
    with jax.named_scope("ssm/gated_norm"):
        y = y.reshape(-1, G, d_ssm // G)
        gate = jax.nn.silu(p[:, :d_ssm].astype(F32)).reshape(y.shape)
        weight = m["norm"].astype(F32).reshape(G, d_ssm // G)

        def normed(v):
            return v * jax.lax.rsqrt(jnp.mean(
                v * v, axis=-1, keepdims=True) + eps) * weight

        g = normed(y) * gate if norm_before_gate else normed(y * gate)
        return g.reshape(-1, d_ssm).astype(dt)


def scan_chunk(xs, B, C, delta, A, S):
    """One block of ``Q`` tokens a sequence, the chunk form: ``xs [R, Q, G,
    k, P]``, ``B``/``C`` ``[R, Q, G, N]``, ``delta [R, Q, G, k]`` (0 at a
    padded position), ``A [G, k]``, carried-in ``S [R, G, k, N, P]``
    float32 → (``y [R, Q, G, k, P]`` float32 without the skip, the state
    after the block).  The decays are float32; the products take the
    inputs' type and sum in float32."""
    with jax.named_scope("ssm/scan_chunk"):
        dt = xs.dtype
        Q = xs.shape[1]
        lam = jnp.cumsum(delta * A, axis=1)                 # [R, Q, G, k]
        xd = delta[..., None] * xs.astype(F32)              # Δ_s x_s
        # within the block: weights exp(Λ_t − Λ_s)(C_t·B_s) for s ≤ t
        cb = jnp.einsum("rtgn,rsgn->rgts", C, B,
                        preferred_element_type=F32)
        lam_h = jnp.moveaxis(lam, 1, -1)                    # [R, G, k, Q]
        seen = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
        decay = jnp.exp(jnp.where(
            seen, lam_h[..., :, None] - lam_h[..., None, :], -jnp.inf))
        weights = (cb[:, :, None] * decay).astype(dt)       # [R,G,k,t,s]
        y = jnp.einsum("rgkts,rsgkp->rtgkp", weights, xd.astype(dt),
                       preferred_element_type=F32)
        # from the carried-in state: exp(Λ_t)(S_0 C_t)
        y = y + jnp.exp(lam)[..., None] * jnp.einsum(
            "rtgn,rgknp->rtgkp", C, S.astype(dt),
            preferred_element_type=F32)
        # the state after: exp(Λ_Q) S_0 + Σ_s exp(Λ_Q − Λ_s) Δ_s x_s B_sᵀ
        last = lam[:, -1]                                   # [R, G, k]
        to_end = jnp.exp(last[:, None] - lam)               # [R, Q, G, k]
        S = jnp.exp(last)[..., None, None] * S + jnp.einsum(
            "rsgkp,rsgn->rgknp", (xd * to_end[..., None]).astype(dt), B,
            preferred_element_type=F32)
        return y, S


def mix(model: Any, lp: Any, x: jnp.ndarray, state: Dict[str, jnp.ndarray],
        tokens: int, valid: jnp.ndarray
        ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """A family's mixer (its ``mix_in`` / ``mix_chunk`` or ``mix_decode`` /
    ``mix_out``) over ``R`` sequences' rows with their state as VALUES in
    and out: ``x [R·tokens, H]``, each sequence's ``tokens`` consecutive
    rows in order, of which the first ``valid[r]`` are real (the rest
    padding: they move no state); ``state``: what each sequence holds
    coming in → (what the mixer adds to the residual ``[R·tokens, H]``, the
    state going out).  One token a sequence is a decode step's update (here
    each part a pool of one layer, the rows its slots); more is a block of
    the chunk form.  The serving engine, whose rows are several groups and
    which keeps the state itself, calls the four parts."""
    p = model.mix_in(lp, x)
    if tokens == 1:
        y, _, held = model.mix_decode(
            lp, p, {}, {name: (part[None], 0, 0)
                        for name, part in state.items()}, valid)
        new = {name: array[0] for name, array in held.items()}
    else:
        y, new = model.mix_chunk(lp, p, state, tokens, valid)
    return model.mix_out(lp, p, y), new


def mix_sequences(model: Any, lp: Any, x: jnp.ndarray, batch: int, seq: int,
                  block: int) -> jnp.ndarray:
    """:func:`mix` over whole sequences without a cache: ``x [batch·seq,
    H]`` → ``[batch·seq, H]``, in blocks of ``block`` tokens from a zero
    state (the last block padded)."""
    blocks = -(-seq // block)
    rows = jnp.pad(x.reshape(batch, seq, -1),
                   ((0, 0), (0, blocks * block - seq), (0, 0)))

    def one(state, i):
        part = jax.lax.dynamic_slice_in_dim(rows, i * block, block, 1)
        out, state = mix(
            model, lp, part.reshape(batch * block, -1), state, block,
            jnp.full((batch,), jnp.clip(seq - i * block, 0, block)))
        return state, out.reshape(batch, block, -1)

    _, outs = jax.lax.scan(one, model.zero_state(batch), jnp.arange(blocks))
    out = jnp.moveaxis(outs, 0, 1).reshape(batch, blocks * block, -1)
    return out[:, :seq].reshape(batch * seq, -1)
