"""One decode step of a gated DELTA RULE with a decay a key channel (KDA)
for a batch of sequences, IN PLACE in the pool that holds their states.

A sequence's state in one layer is ``S [heads, d_k, d_v]`` float32 (4.19 MB
at 64 x 128 x 128), and a decode step moves every live sequence's: with
``α ∈ (0, 1)^{d_k}`` the token's decay a KEY CHANNEL, ``k`` and ``q`` its
(normalised) key and query, ``v`` its value and ``β ∈ (0, 2)`` a head,

    S' = Diag(α) S          u = kᵀ S'
    S  ← S' + k ⊗ β (v − u)          o = Sᵀ q

which is ``S ← (I − β k kᵀ) Diag(α) S + β k vᵀ``.  Unlike the state-space
update beside it (``ssm_state_update``: ``S ← a S + B ⊗ dx``), the
correction READS the decayed state before it writes (``u``): two reductions
over the key rows and a rank-1 update, all while a block is in VMEM.  The
work is the state's bytes, read once and written once (seven operations a
state element against eight bytes: memory-bound by a wide margin), and the
kernel keeps it that: the pool stays in HBM as one carried buffer aliased
in and out, the layer and each row's slot are scalar-prefetched, a grid
step fetches ONE (sequence, group of heads)'s block ``[heads/groups, d_k,
d_v]`` (2 MB), moves it, reads ``o`` off the new values and writes it back
where it lay.

The state lies ``d_k``-major (``[…, d_k, d_v]``: key channels on the
sublanes, the value's numbers on the lanes), so ``v``, ``u`` and ``o`` are
lane rows and the two reductions run down the sublanes.  What is a value a
KEY CHANNEL (``α``, ``k``, ``β k``, ``q``) has to come as a column, and
spread over the lanes by the caller it would be four times the state; so it
comes transposed, ``[R, groups, d_k, 4 · heads/groups]``: the channels on
the sublanes and the four vectors of a group's heads side by side on the
lanes (128 of them at 32 heads a group: 64 KB beside 2 MB of state), and
the kernel takes a head's column as a one-lane slice.  ``β`` enters folded
into ``β k`` and ``β v``, so no scalar is read.

A row that is no sequence's has ``α = 1``, ``k = q = 0`` and ``β = 0``: its
slot is written back as it lay.

``interpret``: as every entry point here (``select.py``).  Off the TPU the
``jax.numpy`` reference runs; the interpreter runs the kernel on the rows'
stretch cut out of the pool (it does not alias).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .select import record_route, reference_off_tpu

F32 = jnp.float32
#: a (sequence, group) block is fetched and written double-buffered: 4 x 2
#: MB at the published widths, over Mosaic's 16 MiB default with the rest
VMEM_LIMIT_BYTES = 32 * 1024 * 1024
#: the heads of one grid step's block: 2 MB of state at 128 x 128 a head,
#: and four columns a head fill the 128 lanes
HEADS_PER_BLOCK = 32


def delta_state_update_reference(pool, layer, first, a, k, q, beta, v
                                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`delta_state_update` in ``jax.numpy``."""
    R = k.shape[0]
    at = (layer, first, 0, 0, 0)
    S = jax.lax.dynamic_slice(pool, at, (1, R) + pool.shape[2:])[0]
    k, q, v = k.astype(F32), q.astype(F32), v.astype(F32)
    S = a.astype(F32)[..., None] * S.astype(F32)            # [R, h, dk, dv]
    u = jnp.sum(k[..., None] * S, axis=2)
    S = S + k[..., None] * (beta.astype(F32)[..., None] * (v - u)
                            )[:, :, None, :]
    o = jnp.sum(q[..., None] * S, axis=2)
    return (jax.lax.dynamic_update_slice(
        pool, S[None].astype(pool.dtype), at), o)


def _update_kernel(layer_ref, slots_ref, pool_ref, cols_ref, bv_ref,
                   out_ref, o_ref, *, hb: int):
    """One (sequence, group): ``pool_ref``/``out_ref [1, 1, hb, d_k, d_v]``
    the same block of the aliased pool, ``cols_ref [1, 1, d_k, 4·hb]`` the
    group's ``α | k | β k | q`` a head a lane, ``bv_ref``/``o_ref [1, hb,
    d_v]``."""
    del layer_ref, slots_ref        # the index maps read them
    for h in range(hb):
        col = lambda c: cols_ref[0, 0, :, c * hb + h:c * hb + h + 1]
        S = col(0) * pool_ref[0, 0, h].astype(F32)          # α ⊙ S by rows
        w = bv_ref[0, h:h + 1, :] \
            - jnp.sum(col(2) * S, axis=0, keepdims=True)    # β (v − kᵀS')
        S = S + col(1) * w
        out_ref[0, 0, h] = S.astype(out_ref.dtype)
        o_ref[0, h:h + 1, :] = jnp.sum(col(3) * S, axis=0, keepdims=True)


def _update_pallas(pool, layer, slots, a, k, q, beta, v, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, heads, dk = k.shape
    dv = v.shape[-1]
    hb = HEADS_PER_BLOCK if heads % HEADS_PER_BLOCK == 0 else heads
    G = heads // hb
    beta = beta.astype(F32)[..., None]
    # [4, R, heads, dk] → [R, G, dk, 4·hb]: a head's four columns
    cols = jnp.stack([a.astype(F32), k.astype(F32), beta * k.astype(F32),
                      q.astype(F32)])
    cols = cols.reshape(4, R, G, hb, dk).transpose(1, 2, 4, 0, 3).reshape(
        R, G, dk, 4 * hb)
    block = lambda r, g, layer, slots: (layer[0], slots[r], g, 0, 0)
    rows = lambda r, g, layer, slots: (r, g, 0)
    block_spec = pl.BlockSpec((1, 1, hb, dk, dv), block)
    kwargs = {}
    if not interpret:
        kwargs["input_output_aliases"] = {2: 0}     # the pool, in place
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
            dimension_semantics=("arbitrary", "arbitrary"))
    return pl.pallas_call(
        functools.partial(_update_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(R, G),
            in_specs=[
                block_spec,
                pl.BlockSpec((1, 1, dk, 4 * hb),
                             lambda r, g, layer, slots: (r, g, 0, 0)),
                pl.BlockSpec((1, hb, dv), rows),
            ],
            out_specs=[block_spec, pl.BlockSpec((1, hb, dv), rows)]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((R, heads, dv), F32)],
        interpret=interpret,
        name="delta_state_update",
        **kwargs,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots.astype(jnp.int32),
      pool, cols, beta * v.astype(F32))


def delta_state_update(pool: jnp.ndarray, layer, first, a: jnp.ndarray,
                       k: jnp.ndarray, q: jnp.ndarray, beta: jnp.ndarray,
                       v: jnp.ndarray, *, interpret: Optional[bool] = None
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``pool [layers, slots, heads, d_k, d_v]``: layer ``layer``'s slots
    ``first … first + R`` hold ``R`` sequences' states; ``a [R, heads,
    d_k]`` the step's decay a key channel (1 for a row that is no
    sequence's), ``k``/``q [R, heads, d_k]`` its key and query (0 for such
    a row), ``beta [R, heads]`` (0 for such a row), ``v [R, heads, d_v]`` →
    (the pool with those states moved one step, in place where the kernel
    runs; ``o [R, heads, d_v]`` float32, ``Sᵀ q`` of the new states)."""
    if reference_off_tpu(interpret):
        record_route("delta_state_update", "reference")
        return delta_state_update_reference(pool, layer, first, a, k, q,
                                            beta, v)
    R = k.shape[0]
    if interpret:
        # the interpreter does not alias: the rows' stretch, cut out
        record_route("delta_state_update", "interpret")
        at = (layer, first, 0, 0, 0)
        cut = jax.lax.dynamic_slice(pool, at, (1, R) + pool.shape[2:])
        cut, o = _update_pallas(cut, 0, jnp.arange(R), a, k, q, beta, v, True)
        return jax.lax.dynamic_update_slice(pool, cut, at), o
    record_route("delta_state_update", "kernel")
    return _update_pallas(pool, layer, first + jnp.arange(R), a, k, q, beta,
                          v, False)
