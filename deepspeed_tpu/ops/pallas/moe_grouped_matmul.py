"""Dropless expert computation: rows sorted by expert, one grouped matmul.

A capacity buffer ``[E, C, H]`` (``moe_dispatch.py``) either drops the
assignments over ``C`` or, at ``C = T``, multiplies ``E·T`` rows of which
``T·k`` are real.  Here the ``T·k`` assignments are laid out **sorted by
expert**, each expert's group padded up to whole tiles of ``tile_rows``
rows, so that every tile belongs to exactly one expert:

    rows     [tiles·tile_rows, H]  tokens gathered into that order, zero
                                   where a tile is not full
    tile_group [tiles] int32       which expert's weights tile i multiplies
    num_tiles  [1] int32           tiles in use; the rest are skipped

:func:`plan_groups` builds that layout from the router's ``[T, k]`` expert
choices with a one-hot running count and one stable sort: gathers only, no
scatter.  ``tiles`` is static, ``T·k // tile_rows + min(E, T·k)``: the most
any routing can need.  Nothing is dropped, whatever the routing.  Where
the ``E`` groups are a share of the router's experts (``share``),
the assignments to the others get no row and the rest is as before.

Two sizes, two rules.  The TILE (:func:`tile_rows_for`) follows the group
the router EXPECTS an expert to get, ``T·k`` over the experts it chooses
among, held here or not: a tile in use costs its rows' traffic (fetched
once a column slice, written once) at the HBM's rate whether the rows are
real or padding, and a group's second tile costs a pass of its own with no
weight fetch to hide under.  The static COUNT of tiles follows what CAN
land (``plan_groups``); a tile of the unused tail is a grid step that
fetches and writes nothing, ~0.07 µs on a v5e, but every static row is a
row of the plan's index arithmetic and of ``gather_rows`` (~0.045 µs)
whether a tile uses it or not (PERF.md §6, PR 53: the sweep of tiles at
the sparse serving cells' shapes).

The kernels (``name="moe_grouped_matmul…"`` on the device trace) walk the
tiles with the expert's weight block chosen by a scalar-prefetched
``tile_group``.  Consecutive tiles of one expert keep the block in VMEM, so
each non-empty expert's weights cross HBM once per call and an empty
expert's never: at decode widths (a few rows an expert) the kernel is a
stream of weights, and that is what its roofline counts.
:func:`grouped_swiglu` fuses the gate and up projections with
``silu(g)·u``; :func:`grouped_matmul` is the down projection.  An expert of
TWO matrices with ``relu(·)²`` between them (a LatentMoE expert: its rows
are the latent's width, not the hidden one) has :func:`grouped_relu2` for
its up projection, the plain kernel with the activation on the product
while it is in VMEM, and the same down projection.

**The weights have a layer**: both take the whole stack ``w [L, E, K, N]``
as the model holds it and ``layer``, an int32 scalar that may be traced
(the index of a layer scan).  The kernels read the stack's flat view
``[L·E, K, N]`` (two adjacent major dimensions merged: a bitcast) with
``layer·E`` added to ``tile_group``, so expert ``e`` of layer ``l`` is
block ``l·E + e`` and the layer's experts are read where they lie.  Handed
one layer's ``w[l]`` out of a scanned stack instead, XLA copies the slice
to make it a custom call's operand: 805 MB a layer at OLMoE-1B-7B's
widths, 60% of the serving cell's device time (PERF.md §6, PR 30).  There
is no entry for unstacked weights: a single layer is a stack of one
(``DroplessMoE`` builds it).

Off the TPU, and under a sharded mesh (a Mosaic call does not partition
itself), the same layout runs :func:`grouped_matmul_reference`
(``jax.lax.ragged_dot`` of ``w[layer]`` over the padded group sizes: XLA
may fuse or copy that slice as it likes, and a stack sharded over its
``E`` keeps its sharding, which a flat ``[L·E]`` view would not).  The
kernels are differentiable through that reference (``custom_vjp``): no
backward kernel.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .select import (reference_off_tpu, resident_compiler_params,
                     shape_refused)

#: a weight block (one expert's ``[K, tile_n]`` slice) is at most this big;
#: two weights, double-buffered, stay well under the scoped-VMEM ceiling
WEIGHT_BLOCK_BYTES = 4 * 1024 * 1024


class GroupPlan(NamedTuple):
    """Where every assignment sits in the sorted, tile-padded layout."""
    row_token: jnp.ndarray    # [R] int32  the token a row holds
    row_valid: jnp.ndarray    # [R] bool   False on padding rows
    dest: jnp.ndarray         # [T, k] int32  the row of token t's choice j
    tile_group: jnp.ndarray   # [tiles] int32, non-decreasing
    num_tiles: jnp.ndarray    # [1] int32
    group_sizes: jnp.ndarray  # [E] int32  assignments per expert, unpadded


def tile_rows_for(assignments: int, num_experts: int, dtype) -> int:
    """Rows in a tile: twice the mean group (so most groups are one tile),
    a power of two between the dtype's sublane tile and the MXU's 128.

    ``assignments`` over ``num_experts`` are the ROUTER's: its rows x k
    over the experts it chooses among.  That mean is a held expert's
    expected group whatever share of the experts the caller holds, so a
    share is given the same tile as the uncut layer.  What can land on a
    share, as if every one of a token's choices were local, bounds the
    static count of tiles (``plan_groups``) and has no say here: a tile
    sized by it is 128 rows a sixth full, whose padding the kernels fetch
    and write and whose static rows the plan walks."""
    floor = 32 // jnp.dtype(dtype).itemsize        # 8 for f32, 16 for bf16
    want = max(1, -(-2 * assignments // num_experts))
    return int(min(128, max(floor, 1 << (want - 1).bit_length())))


def plan_groups(expert_idx: jnp.ndarray, num_experts: int,
                tile_rows: int, share: bool = False) -> GroupPlan:
    """``expert_idx [T, k]`` → the layout.  Assignment ``a = t·k + j``.

    ``share``: the ``num_experts`` groups are a SHARE of the experts
    the router chose among (this chip's, under expert parallelism), and an
    index outside ``[0, num_experts)`` is an assignment to an expert that
    lives elsewhere.  It gets no row: it sorts behind every group, is in
    no group's size, and its ``dest`` is row 0, which is always computed
    (at least one tile is in use, of zeros where nothing landed here), so
    that the caller's zero weight for it meets a finite value.  The static
    ``tiles`` are sized for what CAN land here: a token's ``k`` choices are
    distinct experts, so at most ``min(k, num_experts)`` of them."""
    T, k = expert_idx.shape
    M, E, tm = T * k, num_experts, tile_rows
    can_land = T * min(k, E)
    tiles = can_land // tm + min(E, can_land)
    flat = expert_idx.reshape(M).astype(jnp.int32)
    if share:
        here = (flat >= 0) & (flat < E)
        flat = jnp.where(here, flat, E)            # behind every group
    onehot = (flat[:, None] == jnp.arange(E, dtype=jnp.int32)[None, :]
              ).astype(jnp.int32)                                  # [M, E]
    sizes = jnp.sum(onehot, axis=0)
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
    group_tiles = (sizes + tm - 1) // tm
    tile_end = jnp.cumsum(group_tiles)
    tile_start = tile_end - group_tiles
    num_tiles = tile_end[-1]
    if not share:
        dest = tile_start[flat] * tm + rank
    else:
        num_tiles = jnp.maximum(num_tiles, 1)
        dest = jnp.where(here, tile_start[jnp.minimum(flat, E - 1)] * tm
                         + rank, 0)
    tile_ids = jnp.arange(tiles, dtype=jnp.int32)
    # tile i belongs to the first expert whose tiles end after i; the
    # unused tail repeats the last used tile's expert (no new weight block)
    in_use = jnp.minimum(tile_ids, num_tiles - 1)
    tile_group = jnp.sum(in_use[:, None] >= tile_end[None, :], axis=1
                         ).astype(jnp.int32)
    if share:
        # nothing landed here: the one tile in use is group 0's, of zeros
        tile_group = jnp.minimum(tile_group, E - 1)
    # sorted position → assignment; a row's position within its group
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    sorted_start = jnp.cumsum(sizes) - sizes
    rows = jnp.arange(tiles * tm, dtype=jnp.int32)
    group = tile_group[rows // tm]
    within = rows - tile_start[group] * tm
    valid = (rows // tm < num_tiles) & (within < sizes[group])
    assignment = order[jnp.clip(sorted_start[group] + within, 0, M - 1)]
    return GroupPlan(row_token=assignment // k, row_valid=valid,
                     dest=dest.reshape(T, k), tile_group=tile_group,
                     num_tiles=num_tiles.reshape(1).astype(jnp.int32),
                     group_sizes=sizes)


def gather_rows(tokens: jnp.ndarray, plan: GroupPlan) -> jnp.ndarray:
    """``tokens [T, H]`` → rows ``[R, H]`` in the plan's order."""
    return jnp.where(plan.row_valid[:, None], tokens[plan.row_token],
                     jnp.zeros((), tokens.dtype))


def combine_rows(rows: jnp.ndarray, plan: GroupPlan,
                 weights: jnp.ndarray) -> jnp.ndarray:
    """``y[t] = Σ_j weights[t, j] · rows[dest[t, j]]``, summed in float32."""
    picked = rows[plan.dest].astype(jnp.float32)               # [T, k, H]
    return jnp.einsum("tkh,tk->th", picked, weights.astype(jnp.float32))


# -- the plain path ----------------------------------------------------------

def _padded_group_sizes(tile_group, num_tiles, num_experts, tile_rows):
    used = jnp.arange(tile_group.shape[0]) < num_tiles[0]
    per_group = jnp.sum((tile_group[:, None] == jnp.arange(num_experts))
                        & used[:, None], axis=0)
    return (per_group * tile_rows).astype(jnp.int32)


def _ragged(x, weights, layer, tile_group, num_tiles):
    """``x`` times layer ``layer`` of each of ``weights [L, E, K, N]``, in
    float32: tile i's rows times ``w[layer, tile_group[i]]``, zeros past the
    tiles in use."""
    sizes = _padded_group_sizes(tile_group, num_tiles, weights[0].shape[1],
                                x.shape[0] // tile_group.shape[0])
    return [jax.lax.ragged_dot(x, w[layer], sizes,
                               preferred_element_type=jnp.float32)
            for w in weights]


def grouped_matmul_reference(x, w, layer, tile_group, num_tiles):
    return _ragged(x, (w,), layer, tile_group, num_tiles)[0].astype(x.dtype)


def grouped_swiglu_reference(x, w_gate, w_up, layer, tile_group, num_tiles):
    gate, up = _ragged(x, (w_gate, w_up), layer, tile_group, num_tiles)
    return (jax.nn.silu(gate) * up).astype(x.dtype)


def grouped_relu2_reference(x, w_up, layer, tile_group, num_tiles):
    up, = _ragged(x, (w_up,), layer, tile_group, num_tiles)
    return jnp.square(jax.nn.relu(up)).astype(x.dtype)


# -- the kernels -------------------------------------------------------------

def _matmul_kernel(tile_group, num_tiles, x_ref, w_ref, o_ref):
    del tile_group                       # read by the index maps
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) < num_tiles[0])
    def _():
        o_ref[...] = jnp.dot(x_ref[...], w_ref[0],
                             preferred_element_type=jnp.float32
                             ).astype(o_ref.dtype)


def _swiglu_kernel(tile_group, num_tiles, x_ref, wg_ref, wu_ref, o_ref):
    del tile_group
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) < num_tiles[0])
    def _():
        x = x_ref[...]
        gate = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        up = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        o_ref[...] = (jax.nn.silu(gate) * up).astype(o_ref.dtype)


def _relu2_kernel(tile_group, num_tiles, x_ref, w_ref, o_ref):
    del tile_group
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) < num_tiles[0])
    def _():
        up = jnp.dot(x_ref[...], w_ref[0], preferred_element_type=jnp.float32)
        o_ref[...] = jnp.square(jnp.maximum(up, 0.0)).astype(o_ref.dtype)


def _tile_n(K: int, N: int, itemsize: int) -> int:
    """Widest slice of the N columns, a multiple of 128 lanes that divides
    N, whose ``[K, tile_n]`` weight block fits ``WEIGHT_BLOCK_BYTES``."""
    if N % 128:
        return N
    tn = N
    while tn % 256 == 0 and K * tn * itemsize > WEIGHT_BLOCK_BYTES:
        tn //= 2
    return tn


@functools.partial(jax.jit, static_argnums=(0, 1, 6))
def _grouped_call(kernel, name, x, weights, tile_group, num_tiles,
                  interpret: bool):
    """``weights``: each ``[G, K, N]``; tile m multiplies block
    ``tile_group[m]`` of the ``G``.  Jitted on its own so that a program
    with many sparse layers unrolled, and every further program of the
    process, traces the kernel once (``paged_attention._paged_kernel_call``
    says why)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, K = x.shape
    N = weights[0].shape[-1]
    tiles = tile_group.shape[0]
    tm = R // tiles
    tn = _tile_n(K, N, weights[0].dtype.itemsize)
    # the unused tail revisits the last used tile: nothing is fetched for
    # it and nothing written
    last = lambda m, nt: jnp.minimum(m, nt[0] - 1)
    w_spec = pl.BlockSpec((1, K, tn), lambda n, m, tg, nt: (tg[m], 0, n))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N // tn, tiles),
            in_specs=[pl.BlockSpec(
                (tm, K), lambda n, m, tg, nt: (last(m, nt), 0))]
            + [w_spec] * len(weights),
            out_specs=pl.BlockSpec(
                (tm, tn), lambda n, m, tg, nt: (last(m, nt), n)),
        ),
        out_shape=jax.ShapeDtypeStruct((R, N), x.dtype),
        interpret=interpret, name=name,
        **resident_compiler_params(interpret),
    )(tile_group, num_tiles, x, *weights)


def _differentiable(kernel, name, reference):
    """The kernel forward, the reference's gradient backward."""

    @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
    def run(interpret, tile_group, num_tiles, layer, x, *weights):
        # the stacks' flat view [L·E, K, N]; the layer's offset rides the
        # tile -> block map the kernel prefetches anyway
        experts = weights[0].shape[1]
        flat = [w.reshape((-1,) + w.shape[2:]) for w in weights]
        return _grouped_call(kernel, name, x, flat,
                             tile_group + layer * experts, num_tiles,
                             interpret)

    def fwd(interpret, tile_group, num_tiles, layer, x, *weights):
        return (run(interpret, tile_group, num_tiles, layer, x, *weights),
                (tile_group, num_tiles, layer, x, weights))

    def bwd(interpret, saved, g):
        tile_group, num_tiles, layer, x, weights = saved
        _, vjp = jax.vjp(
            lambda x_, *w_: reference(x_, *w_, layer, tile_group, num_tiles),
            x, *weights)
        return (None, None, None) + vjp(g)

    run.defvjp(fwd, bwd)
    return run


_matmul = _differentiable(_matmul_kernel, "moe_grouped_matmul",
                          grouped_matmul_reference)
_swiglu = _differentiable(_swiglu_kernel, "moe_grouped_matmul_swiglu",
                          grouped_swiglu_reference)
_relu2 = _differentiable(_relu2_kernel, "moe_grouped_matmul_relu2",
                         grouped_relu2_reference)


def _runs_reference(kernel: str, x, w, interpret: Optional[bool]) -> bool:
    """The reference off the TPU, and (said once) for a width the compiled
    kernel cannot tile: lanes in multiples of 128."""
    if reference_off_tpu(interpret):
        return True
    if not interpret and (x.shape[1] % 128 or w.shape[-1] % 128):
        shape_refused(kernel, (tuple(x.shape), tuple(w.shape)),
                      "a width is not a multiple of 128 lanes")
        return True
    return False


def _in_rows_dtype(weights, layer, dtype):
    """The stacks in the rows' dtype, and the layer's index in them.
    Where they are in it already (the serving cells: bf16 both) nothing
    is done and nothing copied.  Otherwise (float32 master weights under
    bf16 rows) the cast has to write what it casts, so it casts the one
    layer, ``w[layer]``, and hands it on as a stack of one: a caller that
    scans many layers of such weights through the kernels casts the stack
    once, outside its scan, if it wants the layer read in place."""
    if all(w.dtype == dtype for w in weights):
        return weights, jnp.asarray(layer, jnp.int32)
    return ([w[layer].astype(dtype)[None] for w in weights],
            jnp.zeros((), jnp.int32))


def grouped_matmul(x, w, layer, plan: GroupPlan,
                   interpret: Optional[bool] = None, sharded: bool = False):
    """``x [R, K]`` in the plan's layout times each tile's expert of layer
    ``layer`` of the stack ``w [L, E, K, N]`` → ``[R, N]``.  Rows of unused
    tiles are undefined (the plan's ``dest`` never points at them).
    ``sharded``: the operands live on a mesh of several devices, so the
    reference runs everywhere."""
    (w,), layer = _in_rows_dtype((w,), layer, x.dtype)
    if sharded or _runs_reference("moe_grouped_matmul", x, w, interpret):
        return grouped_matmul_reference(x, w, layer, plan.tile_group,
                                        plan.num_tiles)
    return _matmul(bool(interpret), plan.tile_group, plan.num_tiles, layer,
                   x, w)


def grouped_swiglu(x, w_gate, w_up, layer, plan: GroupPlan,
                   interpret: Optional[bool] = None, sharded: bool = False):
    """``silu(x·w_gate[layer, e]) ⊙ (x·w_up[layer, e])`` per tile: ``[R, H]``
    → ``[R, I]``; weights as in :func:`grouped_matmul`."""
    (w_gate, w_up), layer = _in_rows_dtype((w_gate, w_up), layer, x.dtype)
    if sharded or _runs_reference("moe_grouped_matmul_swiglu", x, w_gate,
                                  interpret):
        return grouped_swiglu_reference(x, w_gate, w_up, layer,
                                        plan.tile_group, plan.num_tiles)
    return _swiglu(bool(interpret), plan.tile_group, plan.num_tiles, layer,
                   x, w_gate, w_up)


def grouped_relu2(x, w_up, layer, plan: GroupPlan,
                  interpret: Optional[bool] = None, sharded: bool = False):
    """``relu(x·w_up[layer, e])²`` per tile: ``[R, K]`` → ``[R, I]``, the up
    projection of an expert of two matrices; weights as in
    :func:`grouped_matmul`."""
    (w_up,), layer = _in_rows_dtype((w_up,), layer, x.dtype)
    if sharded or _runs_reference("moe_grouped_matmul_relu2", x, w_up,
                                  interpret):
        return grouped_relu2_reference(x, w_up, layer, plan.tile_group,
                                       plan.num_tiles)
    return _relu2(bool(interpret), plan.tile_group, plan.num_tiles, layer,
                  x, w_up)
