"""One decode step of a state-space (Mamba-2) recurrence for a batch of
sequences, IN PLACE in the pool that holds their states.

A sequence's state in one layer is ``S [heads, d_state, d_head]`` float32
(4.19 MB at 32 x 256 x 128), and a decode step moves every live
sequence's: with ``a_h = exp(Δ_h A_h)`` a scalar a head, ``dx = Δ · x`` a
row a head, and the token's ``B`` and ``C`` shared by the heads of a group,

    S_h ← a_h S_h + B ⊗ dx_h          y_h = Σ_n S_h[n, :] C[n]

The work is the state's bytes, read once and written once (six operations
a state element against eight bytes: memory-bound by a wide margin).  In
``jax.numpy`` on a pool ``[layers, slots, …]`` it does not stay that: XLA
does not fuse a ``dynamic-slice`` of the carried buffer into the pass that
writes the new state in place (it reads the buffer it writes) nor into the
reduction that reads ``y``, so each gets a copy of the layer's states made
for it, and they cross HBM seven times a step where two would do (the
programs compiled for a described v5e: PERF.md §6, PR 48).  So this is a kernel with the paged kernel's pattern: the pool
stays in HBM as one carried buffer aliased in and out, the layer and each
row's slot are scalar-prefetched, and a grid step fetches ONE (sequence,
group)'s block ``[heads/groups, d_state, d_head]`` (2 MB), updates it, reads
``y`` off the new values while they are in VMEM, and writes it back where it
lay.

The state lies ``d_state``-major (``[…, d_state, d_head]``: state on the
sublanes, head size on the lanes) so that nothing in a step is laid out
anew: ``dx_h`` and ``y_h`` are lane rows, ``a_h`` is a scalar from SMEM,
and ``B`` and ``C`` come spread over the lanes (``[d_state, d_head]``, 64 KB
in the activations' type beside 2 MB of state: 6% more traffic), made by
the caller's XLA program.

**Several heads a lane row.**  A head of fewer than 128 numbers would
leave its lanes half empty (and an array whose minor dimension is 64 is
padded to 128 in HBM: twice the pool), so a model of such heads holds
``pack`` heads of one group side by side on the lanes, ``[heads / pack,
d_state, pack · d_head]``.  Nothing changes but the decay, which is then a
value a LANE and no scalar: ``a [R, heads / pack, pack · d_head]``, as
``dx`` is (``models/mamba2.py`` lays both out).  The operand's rank says
which; ``a [R, heads]`` is the kernel it was.

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


def ssm_state_update_reference(pool, layer, first, a, dx, b, c
                               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`ssm_state_update` in ``jax.numpy``."""
    R, heads, P = dx.shape
    G, N = b.shape[1:]
    at = (layer, first, 0, 0, 0)
    S = jax.lax.dynamic_slice(pool, at, (1, R) + pool.shape[2:])[0]
    S = S.astype(F32).reshape(R, G, heads // G, N, P)
    # a decay a head, or (several heads a lane row) a lane
    new = a.astype(F32).reshape(R, G, heads // G, 1, -1) * S \
        + b.astype(F32)[:, :, None, :, None] \
        * dx.astype(F32).reshape(R, G, heads // G, 1, P)
    y = jnp.sum(new * c.astype(F32)[:, :, None, :, None], axis=3)
    new = new.reshape((1, R) + pool.shape[2:]).astype(pool.dtype)
    return (jax.lax.dynamic_update_slice(pool, new, at),
            y.reshape(R, heads, P))


def _update_kernel(layer_ref, slots_ref, a_ref, pool_ref, dx_ref, b_ref,
                   c_ref, out_ref, y_ref, *, k: int, lanes: bool):
    """One (sequence, group): ``pool_ref``/``out_ref [1, 1, k, N, P]`` the
    same block of the aliased pool, ``dx_ref``/``y_ref [1, k, P]``,
    ``b_ref``/``c_ref [1, 1, N, P]``; ``a_ref [R, heads]`` in SMEM, a
    scalar a head, or (``lanes``: a lane row holds several heads) ``[1, k,
    P]`` as ``dx_ref`` is."""
    from jax.experimental import pallas as pl

    del layer_ref, slots_ref        # the index maps read them
    r, g = pl.program_id(0), pl.program_id(1)
    b = b_ref[0, 0].astype(F32)
    c = c_ref[0, 0].astype(F32)
    for h in range(k):
        decay = a_ref[0, h:h + 1, :] if lanes else a_ref[r, g * k + h]
        new = decay * pool_ref[0, 0, h].astype(F32) \
            + b * dx_ref[0, h:h + 1, :]
        out_ref[0, 0, h] = new.astype(out_ref.dtype)
        y_ref[0, h:h + 1, :] = jnp.sum(new * c, axis=0, keepdims=True)


def _update_pallas(pool, layer, slots, a, dx, b, c, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, heads, P = dx.shape
    G, N = b.shape[1:]
    k = heads // G
    spread = lambda v: jnp.broadcast_to(v[..., None], v.shape + (P,))
    rows = lambda r, g, layer, slots: (r, g, 0)
    block = lambda r, g, layer, slots: (layer[0], slots[r], g, 0, 0)
    lanes = a.ndim == 3     # a decay a lane: a row a head, as dx
    a_spec = pl.BlockSpec((1, k, P), rows) if lanes else pl.BlockSpec(
        (R, heads), lambda r, g, layer, slots: (0, 0),
        memory_space=pltpu.SMEM)
    block_spec = pl.BlockSpec((1, 1, k, N, P), block)
    kwargs = {}
    if not interpret:
        kwargs["input_output_aliases"] = {3: 0}     # the pool, in place
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
            dimension_semantics=("arbitrary", "arbitrary"))
    return pl.pallas_call(
        functools.partial(_update_kernel, k=k, lanes=lanes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(R, G),
            in_specs=[
                a_spec,
                block_spec,
                pl.BlockSpec((1, k, P), rows),
                pl.BlockSpec((1, 1, N, P),
                             lambda r, g, layer, slots: (r, g, 0, 0)),
                pl.BlockSpec((1, 1, N, P),
                             lambda r, g, layer, slots: (r, g, 0, 0)),
            ],
            out_specs=[block_spec, pl.BlockSpec((1, k, P), rows)]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((R, heads, P), F32)],
        interpret=interpret,
        name="ssm_state_update_lanes" if lanes else "ssm_state_update",
        **kwargs,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots.astype(jnp.int32),
      a.astype(F32), pool, dx.astype(F32), spread(b), spread(c))


def ssm_state_update(pool: jnp.ndarray, layer, first, a: jnp.ndarray,
                     dx: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray, *,
                     interpret: Optional[bool] = None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``pool [layers, slots, heads, d_state, d_head]``: layer ``layer``'s
    slots ``first … first + R`` hold ``R`` sequences' states; ``a [R,
    heads]`` the step's decay a head (1 for a row that is no sequence's; or
    ``[R, heads, d_head]``, a decay a lane, where a lane row holds several
    of the model's heads: module docstring),
    ``dx [R, heads, d_head]`` its ``Δ · x`` (0 for such a row), ``b``/``c
    [R, groups, d_state]`` the token's ``B`` and ``C`` → (the pool with
    those states moved one step, in place where the kernel runs; ``y [R,
    heads, d_head]`` float32, ``S C`` of the new states)."""
    if reference_off_tpu(interpret):
        record_route("ssm_state_update", "reference")
        return ssm_state_update_reference(pool, layer, first, a, dx, b, c)
    R = dx.shape[0]
    if interpret:
        # the interpreter does not alias: the rows' stretch, cut out
        record_route("ssm_state_update", "interpret")
        at = (layer, first, 0, 0, 0)
        cut = jax.lax.dynamic_slice(pool, at, (1, R) + pool.shape[2:])
        cut, y = _update_pallas(cut, 0, jnp.arange(R), a, dx, b, c, True)
        return jax.lax.dynamic_update_slice(pool, cut, at), y
    record_route("ssm_state_update", "kernel")
    return _update_pallas(pool, layer, first + jnp.arange(R), a, dx, b, c,
                          False)
