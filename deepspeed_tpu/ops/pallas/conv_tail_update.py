"""One decode step of a depthwise causal CONV over time for a batch of
sequences, IN PLACE in the pool that holds the conv's tails.

A sequence holds, a layer, the conv's last ``K − 1`` inputs (its TAIL),
time-major and flat ``[(K − 1) · C]``: ``C`` channels a token, the oldest
token first.  A decode step brings one more input ``x [C]`` a sequence, and
with ``seq = tail[0], …, tail[K − 2], x``

    out = silu(Σ_j w[j] ⊙ seq[j] (+ b))          tail ← seq[1 … K − 1]

The work is the tail's bytes, read once and written once, and the step's
rows in and out: ``2 (K − 1) C + 2 C`` numbers a sequence against ``K``
multiply-adds a channel, memory-bound by a wide margin.  Carried as VALUES
(a layer's tails sliced out of the pool, concatenated with the rows, the
outgoing tail gathered a row and written back with an update-slice) the
same bytes crossed HBM five to eight times a layer; here the pool stays in
HBM as one carried buffer aliased in and out, the layer is
scalar-prefetched, and a grid step fetches ONE block of :data:`ROWS`
slots' whole tails, reads the ``K − 1`` taps off it as lane stretches,
emits the rows' output and writes the tails back where they lay, shifted.

The layer's slots are walked FROM SLOT 0 in whole tiles of rows, whichever
stretch ``first … first + R`` the call's rows hold (a serving step's are
slots ``1 … R``: a block of whole 8-row tiles that started at slot 1 would
be misaligned by one): the rows' ``x`` and ``valid`` are laid at their
slots' places among all the layer's, and a slot that is no row's is a dead
row.  A row with ``valid == 0`` has its tail written back AS IT LAY (a dead
decode row on a prefilling request's slot must not disturb what the chunk
wrote); what it emits is of no use to anyone.

The kernel needs the pool ROW-MAJOR, a layer's slots on the sublanes.
XLA:TPU holds an array in whichever order of its major dimensions pads
least, and a pool of few slots or of a whole number of tiles of LAYERS
(``[72, 97, …]``: 97 slots pad to 104, 72 layers to nothing) lies with its
layers on the sublanes; a kernel call would be handed a re-laid-out copy of
the whole pool and its result copied back, a layer.  :func:`rows_on_sublanes`
is that rule, held to the compiler by ``tests/unit/ops/
test_tpu_compile_state.py``, and where it says no the reference runs (the
tail as values: what every pool cost before), said once.

The kernel multiplies and sums in float32 and rounds once; the
``jax.numpy`` reference is the arithmetic of ``models/mamba2.conv`` at one
token, term for term in the rows' type.

TO MOVE ONLY: a caller that hands NO taps (``w`` None) gets, in place of
the conv's output, the rows' tails AS THEY LAY before the step, ``[R, (K −
1) · C]`` in the pool's type: the block the kernel fetched anyway, written
out beside the shifted one, and no conv is run.  Nemotron-H's mixers take
that (``models/mamba2.decode`` with ``kernel_conv`` False) and leave the
conv itself to XLA's fused chain (``models/mamba2.conv``): the step's
arithmetic stays what it was where the tail was a value, and only the tail's
movement changes (an XLA read of the pool beside the aliased call would make
the compiler copy the whole pool, a call).
Every other caller hands its taps.

``interpret``: as every entry point here (``select.py``).  Off the TPU the
``jax.numpy`` reference runs; the interpreter runs the kernel on the layer
cut out of the pool (it does not alias).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .select import record_route, reference_off_tpu, shape_refused

F32 = jnp.float32
LANES = 128
#: slots a grid step's block: whole tiles of the pool's rows, 2.36 MB of
#: tails at the widest published conv (3 x 24,576 channels, bfloat16)
ROWS = 16
#: the widest stretch of channels the kernel holds in registers at a time
CHUNK = 512
#: a block of tails in and one out, double-buffered, beside the rows'
VMEM_LIMIT_BYTES = 32 * 1024 * 1024


def conv_tail_update_reference(pool, layer, first, x, w, b, valid
                               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`conv_tail_update` in ``jax.numpy``, in ``x``'s type."""
    (R, C), dt = x.shape, x.dtype
    K = pool.shape[2] // C + 1
    at = (layer, first, 0)
    held = jax.lax.dynamic_slice(pool, at, (1, R, pool.shape[2]))[0]
    seq = jnp.concatenate([held.astype(dt).reshape(R, K - 1, C),
                           x[:, None]], axis=1)
    if w is not None:
        w = w.astype(dt)
        out = sum(seq[:, j] * w[j] for j in range(K))
        if b is not None:
            out = out + b.astype(dt)
    left = jnp.where((valid > 0)[:, None, None], seq[:, 1:], seq[:, :-1])
    return (jax.lax.dynamic_update_slice(
        pool, left.reshape(1, R, -1).astype(pool.dtype), at),
        held if w is None else jax.nn.silu(out))


def rows_on_sublanes(layers: int, slots: int, itemsize: int) -> bool:
    """Whether XLA:TPU holds a pool ``[layers, slots, n]`` row-major, its
    slots the tiled dimension beside ``n``: it does unless the LAYERS pad
    less.  A dimension on the sublanes is padded to whole tiles of 8 rows,
    or of the 4 or 2 (of 32-bit words) that hold it if it is that small."""
    def padded(n: int) -> int:
        words = 4 // itemsize       # numbers a 32-bit word of a sublane
        tile = next((t for t in (words, 2 * words, 4 * words)
                     if n <= t < 8), 8)
        return -(-n // tile) * tile

    return padded(slots) * layers <= padded(layers) * slots


def _chunk(C: int) -> int:
    """The widest stretch of whole lane tiles, at most :data:`CHUNK`, that
    divides ``C`` channels (all of them where they are no whole tiles: the
    interpreter's small shapes)."""
    return next((c for c in range(CHUNK, 0, -LANES) if C % c == 0), C)


def _update_kernel(layer_ref, pool_ref, x_ref, *refs, K: int, C: int,
                   conv: bool):
    """One block of slots: ``pool_ref``/``out_pool_ref [1, rows, (K−1)·C]``
    the same block of the aliased pool, ``x_ref [rows, C]``; then ``refs``:
    with ``conv``, ``taps_ref [K + 1, C]`` float32 (``w``, then the bias),
    ``valid_ref [rows, 1]``, ``out_pool_ref``, ``out_ref [rows, C]``; to
    move only, ``valid_ref``, ``out_pool_ref``, ``out_ref [rows,
    (K−1)·C]``, which takes the block as it was fetched."""
    from jax.experimental import pallas as pl

    del layer_ref                   # the index maps read it
    taps_ref, (valid_ref, out_pool_ref, out_ref) = (
        (refs[0], refs[1:]) if conv else (None, refs))
    live = valid_ref[...] > 0
    width = _chunk(C)

    def stretch(c, carry):
        at = lambda j: pl.ds(pl.multiple_of(j * C + c * width, width), width)
        seq = [pool_ref[0, :, at(j)].astype(F32) for j in range(K - 1)]
        seq.append(x_ref[:, at(0)].astype(F32))
        if conv:
            acc = taps_ref[K:K + 1, at(0)]
            for j in range(K):
                acc = acc + taps_ref[j:j + 1, at(0)] * seq[j]
            out_ref[:, at(0)] = (acc * jax.nn.sigmoid(acc)
                                 ).astype(out_ref.dtype)
        for j in range(K - 1):
            if not conv:
                out_ref[:, at(j)] = seq[j].astype(out_ref.dtype)
            out_pool_ref[0, :, at(j)] = jnp.where(
                live, seq[j + 1], seq[j]).astype(out_pool_ref.dtype)
        return carry

    jax.lax.fori_loop(0, C // width, stretch, None)


def _update_pallas(pool, layer, x, w, b, valid, interpret: bool):
    """The kernel over EVERY slot of ``pool``'s layer ``layer``: ``x [slots,
    C]`` and ``valid [slots]`` int32 a slot; ``w`` None: no conv, the second
    result the slots' tails as they lay."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, C = x.shape
    K = pool.shape[2] // C + 1
    rows = ROWS if S >= ROWS else S
    taps = [] if w is None else [jnp.concatenate([
        w.astype(x.dtype).astype(F32),
        (jnp.zeros((C,), F32) if b is None
         else b.astype(x.dtype).astype(F32))[None]])]
    block = pl.BlockSpec((1, rows, (K - 1) * C),
                         lambda i, layer: (layer[0], i, 0))
    by_rows = lambda width: pl.BlockSpec((rows, width),
                                         lambda i, layer: (i, 0))
    kwargs = {}
    if not interpret:
        kwargs["input_output_aliases"] = {1: 0}     # the pool, in place
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
            dimension_semantics=("arbitrary",))
    return pl.pallas_call(
        functools.partial(_update_kernel, K=K, C=C, conv=w is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(-(-S // rows),),
            in_specs=[block, by_rows(C)]
            + [pl.BlockSpec((K + 1, C), lambda i, layer: (0, 0))] * len(taps)
            + [by_rows(1)],
            out_specs=[block, by_rows(C if taps else (K - 1) * C)]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((S, C), x.dtype) if taps else
                   jax.ShapeDtypeStruct((S, (K - 1) * C), pool.dtype)],
        interpret=interpret,
        name="conv_tail_update",
        **kwargs,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), pool, x, *taps,
      valid[:, None])


def conv_tail_update(pool: jnp.ndarray, layer, first, x: jnp.ndarray,
                     w: Optional[jnp.ndarray], b: Optional[jnp.ndarray],
                     valid: jnp.ndarray, *, interpret: Optional[bool] = None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``pool [layers, slots, (K − 1) · C]``: layer ``layer``'s slots
    ``first … first + R`` hold ``R`` sequences' tails, time-major; ``x [R,
    C]`` the step's input a sequence, ``w [K, C]`` the conv's taps, ``b
    [C]`` its bias or None, ``valid [R]`` (0 for a row that is no
    sequence's) → (the pool with those tails shifted one step, ``x`` last,
    in place where the kernel runs and as they lay where ``valid`` is 0;
    ``out [R, C]`` in ``x``'s type, ``silu`` of the conv at the step's
    token).  ``w`` None: to move only (the module's text)."""
    if reference_off_tpu(interpret):
        record_route("conv_tail_update", "reference")
        return conv_tail_update_reference(pool, layer, first, x, w, b, valid)
    R, C = x.shape
    layers, slots = pool.shape[:2]
    refused = None
    if not interpret and C % LANES:
        refused = "the channels are no whole lane tiles"
    elif not interpret and not rows_on_sublanes(layers, slots,
                                                pool.dtype.itemsize):
        refused = "the chip holds the pool with its layers on the sublanes"
    if refused:
        shape_refused("conv_tail_update", pool.shape, refused)
        record_route("conv_tail_update", "reference")
        return conv_tail_update_reference(pool, layer, first, x, w, b, valid)
    # the layer's slots from 0: the rows at their slots' places among them
    x = jax.lax.dynamic_update_slice(jnp.zeros((slots, C), x.dtype), x,
                                     (first, 0))
    valid = jax.lax.dynamic_update_slice(
        jnp.zeros((slots,), jnp.int32), valid.astype(jnp.int32), (first,))
    if interpret:
        # the interpreter does not alias: the layer, cut out
        record_route("conv_tail_update", "interpret")
        at = (layer, 0, 0)
        cut = jax.lax.dynamic_slice(pool, at, (1,) + pool.shape[1:])
        cut, out = _update_pallas(cut, 0, x, w, b, valid, True)
        pool = jax.lax.dynamic_update_slice(pool, cut, at)
    else:
        record_route("conv_tail_update", "kernel")
        pool, out = _update_pallas(pool, layer, x, w, b, valid, False)
    return pool, jax.lax.dynamic_slice(out, (first, 0), (R, out.shape[1]))
