"""Ring attention — sequence parallelism past the head-count limit.

Reference positioning: NOT in DeepSpeed core (SURVEY §5.7 row 3 — Ulysses
is its answer; ring belongs to other stacks).  Built here as the
parity-plus long-context path the survey plans: Ulysses' maximum SP
degree is ``num_heads/tp`` (each rank needs ≥1 head); ring attention
(arXiv 2310.01889 [P] / blockwise 2305.19370) shards the SEQUENCE through
the whole computation, so SP scales with chips, not heads.

TPU-first formulation: a ``shard_map`` over the ``seq`` axis; each device
owns one contiguous sequence block of Q/K/V; K/V blocks rotate around the
ring with ``lax.ppermute`` (ICI-neighbor traffic) while each device folds
the visiting block into its queries' online-softmax state (m, l, acc) —
the flash-attention accumulator generalized across devices.  Causality
skips fully-masked visits via ``jnp.where`` on the accumulator update
(the compute still runs — lockstep SPMD — but XLA sees a uniform ring
step it can pipeline with the permute).  The backward pass is jax.grad
through the scan+ppermute, the transpose ring.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec

from ...comm.comm import ppermute as _ppermute
from ...parallel.mesh import AXIS_SEQ, DP_AXES
from ...utils import groups as groups_mod
from ...utils.jax_compat import shard_map as _shard_map

P = PartitionSpec


def _ring_attention_local(q, k, v, *, axis_name: str, sp: int,
                          causal: bool, window=None):
    """Per-device body: ``q [B, Sl, h, d]``, ``k/v [B, Sl, kv_h, d]`` with
    ``kv_h | h`` — GQA groups rotate at their stored width and expand
    per-visit (rotating pre-expanded heads would multiply the ppermute
    bytes by h/kv_h for data derivable locally)."""
    B, Sl, h, d = q.shape
    n_rep = h // k.shape[2]
    my = jax.lax.axis_index(axis_name)
    scale = 1.0 / np.sqrt(d)
    qf = q.astype(jnp.float32) * scale

    ring = [(i, (i + 1) % sp) for i in range(sp)]

    def visit(carry, r):
        kb, vb, m, l, acc = carry
        src = (my - r) % sp  # whose block is visiting this round
        kbf = kb.astype(jnp.float32)
        vbf = vb.astype(jnp.float32)
        if n_rep > 1:
            kbf = jnp.repeat(kbf, n_rep, axis=2)
            vbf = jnp.repeat(vbf, n_rep, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kbf)
        if causal or window is not None:
            from ...ops.masks import local_attention_mask

            # global positions: mine = my*Sl + iq, theirs = src*Sl + ik
            iq = my * Sl + jnp.arange(Sl)
            ik = src * Sl + jnp.arange(Sl)
            mask = local_attention_mask(iq, ik, causal=causal, window=window)
            s = jnp.where(mask[None, None], s, -jnp.inf)
        m_blk = jnp.max(s, axis=-1)                      # [B, h, Sl]
        m_new = jnp.maximum(m, m_blk)
        # fully-masked visits (src entirely in my future) produce -inf
        # rows; keep the old state there
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_new), 1.0)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = (acc * alpha[..., None]
               + jnp.einsum("bhqk,bkhd->bhqd", p, vbf))
        m = m_new
        # rotate K/V to the next rank (a no-op compute-wise on the last
        # visit, but keeping the scan body uniform lets XLA overlap the
        # permute with the next visit's einsum)
        kb = _ppermute(kb, ring, axis_name)
        vb = _ppermute(vb, ring, axis_name)
        return (kb, vb, m, l, acc), None

    m0 = jnp.full((B, h, Sl), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, h, Sl), jnp.float32)
    acc0 = jnp.zeros((B, h, Sl, d), jnp.float32)
    (_, _, m, l, acc), _ = jax.lax.scan(
        visit, (k, v, m0, l0, acc0), jnp.arange(sp))
    out = acc / jnp.maximum(l, 1e-20)[..., None]         # [B, h, Sl, d]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   causal: bool = True,
                   mesh: Optional[Mesh] = None,
                   window: Optional[int] = None) -> jnp.ndarray:
    """Sequence-parallel attention over the ``seq`` mesh axis.

    ``q,k,v``: GLOBAL ``[B, S, h, d]`` arrays (seq-sharded or not — the
    shard_map partitions them); returns ``[B, S, h, d]``.  Unlike
    :func:`ulysses_attention` there is no head-count bound: SP degree is
    limited only by ``S % sp == 0``.  Positions are global, so RoPE must
    be applied BEFORE calling (on globally-indexed positions).
    """
    mesh = mesh if mesh is not None else groups_mod.get_mesh()
    sp = int(mesh.shape.get(AXIS_SEQ, 1))
    if sp == 1:
        return _plain_attention(q, k, v, causal, window)
    if q.shape[1] % sp:
        raise ValueError(f"sequence {q.shape[1]} not divisible by sp={sp}")

    # manualize ONLY the seq axis (batch/dp stays GSPMD-auto) — same
    # partial-manual convention as ulysses_attention so the two compose
    # with the surrounding engine shardings identically
    ctx = jax.sharding.get_abstract_mesh()
    sm_mesh = mesh if ctx.empty else ctx
    body = partial(_ring_attention_local, axis_name=AXIS_SEQ, sp=sp,
                   causal=causal, window=window)
    spec = P(None, AXIS_SEQ, None, None)
    return _shard_map(body, mesh=sm_mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False,
                         axis_names={AXIS_SEQ})(q, k, v)


def _plain_attention(q, k, v, causal, window=None):
    """Dense fallback/reference — one home for the math
    (``ops/pallas/flash_attention._reference_attention``), GQA-expanded."""
    n_rep = q.shape[2] // k.shape[2]
    if n_rep > 1:
        k = jnp.repeat(k, n_rep, axis=2)
        v = jnp.repeat(v, n_rep, axis=2)
    from ...ops.pallas.flash_attention import _reference_attention

    return _reference_attention(q, k, v, causal, window)
