"""Decode attention with KV cache — the inference-serving hot kernel.

Role parity: the reference's kernel-injection decode attention
(``csrc/transformer/inference/`` fused attention over a KV cache [K]) and
the inference-v2 ragged blocked-KV kernels.  Single-token queries attend
over a padded per-sequence cache with true lengths — the TPU-friendly
static-shape formulation of ragged batching.

VMEM discipline: the KV sequence dimension is blocked through the *grid*
(``grid=(B, nk)``) so only one ``[block_k, h, d]`` tile of K and V is
resident at a time, with the online-softmax state (m, l, acc) carried in
VMEM scratch across the sequential inner grid axis.  Loading the whole
``[Smax, h, d]`` cache per sequence (h=32, d=128, Smax=8k, bf16 → ~64 MiB)
would blow the ~16 MiB VMEM budget and fail to lower on real hardware.
Blocks entirely beyond a sequence's true length clamp their DMA index to
the last valid block and skip compute, so ragged batches do no wasted I/O.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .select import reference_off_tpu, shape_refused


def _reference_decode(q, k_cache, v_cache, lengths, window=None):
    # q: [B, h, d]; caches: [B, Smax, kv_h, d] with kv_h | h (GQA); lengths: [B]
    n_rep = q.shape[1] // k_cache.shape[2]
    if n_rep > 1:
        k_cache = jnp.repeat(k_cache, n_rep, axis=2)
        v_cache = jnp.repeat(v_cache, n_rep, axis=2)
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bhd,bkhd->bhk", q, k_cache).astype(jnp.float32) * scale
    Smax = k_cache.shape[1]
    pos = jnp.arange(Smax)[None, None, :]
    mask = pos < lengths[:, None, None]
    if window is not None:  # sliding window: only the last `window` tokens
        mask = mask & (pos >= lengths[:, None, None] - window)
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhk,bkhd->bhd", p, v_cache)


def _num_valid_blocks(length, block_k):
    return jax.lax.div(length + block_k - 1, block_k)


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                   *, block_k: int, num_blocks: int, scale: float,
                   n_rep: int):
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    ki = pl.program_id(1)
    length = len_ref[b]
    nk_valid = _num_valid_blocks(length, block_k)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ki < nk_valid)
    def _update():
        q = q_ref[0].astype(jnp.float32) * scale  # [h, d]
        h = q.shape[0]
        kblk = k_ref[0].astype(jnp.float32)  # [block_k, kv_h, d]
        vblk = v_ref[0].astype(jnp.float32)
        if n_rep > 1:  # GQA: expand KV heads in VMEM, not in the HBM cache
            kblk = jnp.repeat(kblk, n_rep, axis=1)
            vblk = jnp.repeat(vblk, n_rep, axis=1)
        # [block_k, h] scores — elementwise-multiply + d-reduce (VPU):
        # Mosaic cannot lower batched (per-head) dots, and decode is
        # memory-bound so the MXU is not the limiter here
        s = jnp.sum(kblk * q[None, :, :], axis=-1)
        pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, h), 0)
        s = jnp.where(pos < length, s, -1e30)
        m_prev = m_ref[0]  # [h]
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


def decode_attention(q, k_cache, v_cache, lengths, block_k: int = 128,
                     interpret: bool | None = None, window=None):
    """q ``[B, h, d]`` one-token queries over padded caches
    ``[B, Smax, kv_h, d]`` (``kv_h`` divides ``h`` — GQA groups expanded
    inside the kernel) with per-sequence ``lengths [B]``.  The kernel has
    no ``window`` (Mistral sliding window) support, so a windowed call
    runs the masked reference; the paged kernel, which the serving engine
    uses, does take a window."""
    from jax.experimental import pallas as pl

    if reference_off_tpu(interpret):
        return _reference_decode(q, k_cache, v_cache, lengths, window)
    B, Smax, kv_h, d = k_cache.shape
    h = q.shape[1]
    n_rep = h // kv_h
    block_k = min(block_k, Smax)
    refusal = None
    if window is not None:
        refusal = "the kernel has no sliding-window support"
    elif Smax % block_k:
        refusal = f"block_k={block_k} does not divide Smax={Smax}"
    elif h % kv_h:
        refusal = f"kv heads {kv_h} do not divide query heads {h}"
    if refusal is not None:
        shape_refused("decode_attention",
                      (tuple(q.shape), tuple(k_cache.shape)), refusal)
        return _reference_decode(q, k_cache, v_cache, lengths, window)
    interpret = bool(interpret)
    num_blocks = Smax // block_k

    kernel = functools.partial(_decode_kernel, block_k=block_k,
                               num_blocks=num_blocks, scale=1.0 / np.sqrt(d),
                               n_rep=n_rep)
    from jax.experimental.pallas import tpu as pltpu

    def _kv_index(b, ki, lens):
        # Clamp out-of-range blocks onto the last valid one: the revisited
        # block's DMA is a no-op and compute is @pl.when-skipped, so ragged
        # tails cost nothing.
        nk_valid = _num_valid_blocks(lens[b], jnp.int32(block_k))
        return (b, jnp.minimum(ki, jnp.maximum(nk_valid - 1, 0)), 0, 0)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, num_blocks),
            in_specs=[
                pl.BlockSpec((1, h, d), lambda b, ki, lens: (b, 0, 0)),
                pl.BlockSpec((1, block_k, kv_h, d), _kv_index),
                pl.BlockSpec((1, block_k, kv_h, d), _kv_index),
            ],
            out_specs=pl.BlockSpec((1, h, d), lambda b, ki, lens: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((1, h), jnp.float32),      # running max m
                pltpu.VMEM((1, h), jnp.float32),      # running denom l
                pltpu.VMEM((h, d), jnp.float32),      # output accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, h, d), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, k_cache, v_cache)
    return out
