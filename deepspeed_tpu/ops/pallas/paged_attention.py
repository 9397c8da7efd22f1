"""Paged decode attention — blocked-KV-cache kernel for inference v2.

Role parity: the reference FastGen ragged kernels
(``deepspeed/inference/v2/kernels/ragged_ops/`` — blocked KV cache with
linear/blocked attention over a block table [K], SURVEY §2.2 row "Inference
v2 kernels").  Sequences share one physical KV pool; a per-sequence block
table maps logical KV positions onto pool blocks, so memory is allocated in
``block_size`` pages instead of a padded ``[B, Smax]`` rectangle.

TPU-first formulation: the pool has a static shape ``[num_blocks,
block_size, kv_h, d]`` and the block table rides the kernel's scalar
prefetch, so the table lookup happens in the BlockSpec ``index_map`` —
the DMA engine fetches exactly the pages a sequence owns, one page per
sequential grid step, with the online-softmax state carried in VMEM
scratch (same discipline as ``decode_attention.py``; a page is the unit
of both allocation AND kernel tiling).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...utils.jax_compat import shard_map as _shard_map
from .select import reference_off_tpu, shape_refused


def paged_decode_reference(q, k_pool, v_pool, block_tables, lengths,
                           window=None):
    """Pure-jnp reference.  ``q [B, h, d]``; pools ``[N, bs, kv_h, d]``;
    ``block_tables [B, max_blocks]``; ``lengths [B]``; ``window`` =
    sliding-window reach (only the last ``window`` cache entries)."""
    B = q.shape[0]
    _, bs, kv_h, d = k_pool.shape
    max_blocks = block_tables.shape[1]
    # gather each sequence's pages into a padded [B, max_blocks*bs, kv_h, d]
    k = k_pool[block_tables].reshape(B, max_blocks * bs, kv_h, d)
    v = v_pool[block_tables].reshape(B, max_blocks * bs, kv_h, d)
    n_rep = q.shape[1] // kv_h
    if n_rep > 1:
        k = jnp.repeat(k, n_rep, axis=2)
        v = jnp.repeat(v, n_rep, axis=2)
    scale = 1.0 / np.sqrt(d)
    s = jnp.einsum("bhd,bkhd->bhk", q, k).astype(jnp.float32) * scale
    pos = jnp.arange(max_blocks * bs)[None, None, :]
    mask = pos < lengths[:, None, None]
    if window is not None:
        mask = mask & (pos >= lengths[:, None, None] - window)
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhk,bkhd->bhd", p, v)


def _num_valid_blocks(length, block_size):
    return jax.lax.div(length + block_size - 1, block_size)


def _paged_kernel(len_ref, table_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, block_size: int, num_blocks: int,
                  scale: float, n_rep: int, window=None):
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    ki = pl.program_id(1)
    length = len_ref[b]
    nk_valid = _num_valid_blocks(length, block_size)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if window is not None:
        # skip blocks wholly BEFORE the window: a fully-masked block would
        # otherwise poison the online softmax (exp(-1e30 - m) with m also
        # -1e30 is exp(0)); the boundary block always has >=1 live entry
        k0 = jnp.maximum(length - window, 0) // block_size
        in_range = (ki < nk_valid) & (ki >= k0)
    else:
        in_range = ki < nk_valid

    @pl.when(in_range)
    def _update():
        q = q_ref[0].astype(jnp.float32) * scale  # [h, d]
        h = q.shape[0]
        kblk = k_ref[0].astype(jnp.float32)  # [block_size, kv_h, d]
        vblk = v_ref[0].astype(jnp.float32)
        if n_rep > 1:  # GQA groups expand in VMEM, never in the pool
            kblk = jnp.repeat(kblk, n_rep, axis=1)
            vblk = jnp.repeat(vblk, n_rep, axis=1)
        s = jnp.sum(kblk * q[None, :, :], axis=-1)  # [block_size, h]
        pos = ki * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (block_size, h), 0)
        keep = pos < length
        if window is not None:  # sliding window: only the cache tail
            keep = keep & (pos >= length - window)
        s = jnp.where(keep, s, -1e30)
        m_prev = m_ref[0]
        l_prev = l_ref[0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
        p = jnp.exp(s - m_new[None, :])
        alpha = jnp.exp(m_prev - m_new)
        m_ref[0] = m_new
        l_ref[0] = l_prev * alpha + jnp.sum(p, axis=0)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jnp.sum(p[:, :, None] * vblk, axis=0))

    @pl.when(ki == num_blocks - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[0], 1e-9)[:, None]).astype(o_ref.dtype)


def paged_decode_impl(num_heads: int, kv_heads: int,
                      interpret: bool | None = None) -> str:
    """Which path :func:`paged_decode_attention` takes for these head
    counts: ``"pallas"``, ``"pallas_interpret"`` or ``"reference"`` — the
    serving engine records it (``last_attn_path``) from the same test the
    entry point decides by."""
    if reference_off_tpu(interpret) or num_heads % kv_heads:
        return "reference"
    return "pallas_interpret" if interpret else "pallas"


def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths,
                           interpret: bool | None = None, window=None):
    """One-token queries ``q [B, h, d]`` over a shared paged KV pool
    ``[N, block_size, kv_h, d]`` addressed by ``block_tables [B, max_blocks]``
    with true ``lengths [B]``.  ``window`` (sliding-window attention) is
    handled natively by the kernel: out-of-window pages are skipped via the
    k0 grid start in ``_paged_kernel`` and the clamped ``_kv_index``, so no
    dead-page work is done."""
    from jax.experimental import pallas as pl

    B, h, d = q.shape
    _, block_size, kv_h, _ = k_pool.shape
    max_blocks = block_tables.shape[1]
    n_rep = h // kv_h
    if paged_decode_impl(h, kv_h, interpret) == "reference":
        if h % kv_h:
            shape_refused("paged_decode_attention",
                          (tuple(q.shape), tuple(k_pool.shape)),
                          f"kv heads {kv_h} do not divide query heads {h}")
        return paged_decode_reference(q, k_pool, v_pool, block_tables,
                                      lengths, window)
    interpret = bool(interpret)

    kernel = functools.partial(_paged_kernel, block_size=block_size,
                               num_blocks=max_blocks,
                               scale=1.0 / np.sqrt(d), n_rep=n_rep,
                               window=window)
    from jax.experimental.pallas import tpu as pltpu

    def _kv_index(b, ki, lens, table):
        # in-range pages resolve through the block table; out-of-range grid
        # steps clamp onto a valid page (the repeated DMA is a no-op and
        # compute is masked); with a window, pages wholly BEFORE the
        # window clamp forward onto the window's first page — their
        # compute is fully masked, and their DMA collapses to a revisit
        nk_valid = _num_valid_blocks(lens[b], jnp.int32(block_size))
        ki_c = jnp.minimum(ki, jnp.maximum(nk_valid - 1, 0))
        if window is not None:
            k0 = jnp.maximum(lens[b] - window, 0) // block_size
            ki_c = jnp.maximum(ki_c, k0)
        return (table[b, ki_c], 0, 0, 0)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, max_blocks),
            in_specs=[
                pl.BlockSpec((1, h, d), lambda b, ki, lens, table: (b, 0, 0)),
                pl.BlockSpec((1, block_size, kv_h, d), _kv_index),
                pl.BlockSpec((1, block_size, kv_h, d), _kv_index),
            ],
            out_specs=pl.BlockSpec((1, h, d),
                                   lambda b, ki, lens, table: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((1, h), jnp.float32),
                pltpu.VMEM((1, h), jnp.float32),
                pltpu.VMEM((h, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, h, d), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(lengths.astype(jnp.int32), block_tables.astype(jnp.int32),
      q, k_pool, v_pool)
    return out


def paged_decode_attention_tp(q, k_pool, v_pool, block_tables, lengths,
                              mesh, window=None):
    """TENSOR-PARALLEL paged decode: the Pallas kernel itself is not
    GSPMD-partitionable (custom call, and jax lowers it only where every
    mesh axis is manual), so the partitioning is explicit — a
    ``shard_map`` over the whole mesh that splits the HEAD dims over the
    ``tensor`` axis and replicates over the rest.
    Attention heads are independent, so each TP rank runs the kernel on
    its local ``h/tp`` query heads against its local ``kv_h/tp`` pool
    slice with NO cross-rank communication; block tables and lengths are
    replicated metadata.  Requires ``tp | kv_heads`` (the serving engine
    enforces this at admission).

    Reference: the v2 inference kernels run TP-sharded the same way
    (SURVEY §2.2 inference-kernels row); this closes round 3's
    "einsum-fallback attention under TP serving" gap."""
    from ...parallel.mesh import AXIS_TENSOR

    P = jax.sharding.PartitionSpec

    def local(q_, kp, vp, bt, ln):
        return paged_decode_attention(q_, kp, vp, bt, ln, window=window)

    return _shard_map(
        local, mesh=mesh,
        in_specs=(P(None, AXIS_TENSOR, None),
                  P(None, None, AXIS_TENSOR, None),
                  P(None, None, AXIS_TENSOR, None), P(), P()),
        out_specs=P(None, AXIS_TENSOR, None),
        check_vma=False,
        axis_names=set(mesh.axis_names))(q, k_pool, v_pool,
                                         block_tables, lengths)
