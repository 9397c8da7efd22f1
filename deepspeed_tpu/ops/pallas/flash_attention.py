"""Flash attention — Pallas TPU kernel with online softmax.

Role parity: the reference's fused attention kernels
(``csrc/transformer/`` + inference attention [K]) — here as a blocked
q-loop × online-softmax k-loop kernel that never materializes the
``[S, S]`` score matrix in HBM.

The kernel family (dispatched by :func:`_flash_call` / :func:`_flash_bwd`):

* **resident** (fwd + one-pass backward): K/V (in the backward
  q/do/lse/Δ and the dq accumulator) ride VMEM whole; the loop walks
  the contiguous ``lattice.kv_block_bounds`` / ``q_block_bounds`` range,
  so causal work is the true triangle and windowed work is O(S·window).
  Taken while a head's planes fit the VMEM budget
  (``lattice.resident_fits``).  Heads narrower than the 128 lanes share
  a program side by side (``_heads_per_program``: two at d = 64), read
  from ``[B, S, h·d]`` as the operands lie, with no transpose on either
  side; and where one k-block spans the sequence the backward sums Δ in
  the tile (``delta_in_tile``).  Both from the shape alone.
* **streamed** (fwd + dq/dkv backward): beyond VMEM residency the grid
  grows a live-step dimension and a scalar-prefetched ``index_map``
  DMAs ONLY each step's live block (``lattice.plan_q_live`` /
  ``plan_k_live`` — the same gather machinery as the block-sparse
  kernels, here walking the causal/window lattice).  VMEM holds one
  block; S is unbounded.

Block sizes are seq-length-aware (``lattice.auto_flash_blocks``) unless
the caller (or the tuning plane's ``kernels.flash_block_*`` dimensions)
pins them.  ``segment_ids`` masks cross-segment pairs (packed sequences
/ BERT padding) on the resident kernels and every reference path.

Forward also emits the per-row log-sum-exp so the backward never has to
re-derive softmax normalization; backward uses the standard
``delta = Σ_d do·o`` trick for the softmax jacobian.  ``interpret=True``
(CPU testing) and the jnp reference path keep numerics checkable
everywhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from . import lattice
from .select import (record_residuals, record_route, reference_off_tpu,
                     resident_compiler_params, shape_refused)

#: what a call names its two residuals, ``out`` and ``lse``, where
#: :func:`keeps_residuals` says they are dearer to recompute than to hold:
#: the names a layer's remat policy keeps (``remat_policy`` in
#: ``runtime/activation_checkpointing``)
RESIDUAL_NAMES = ("flash_attention/out", "flash_attention/lse")


def _mask(S, T, causal, window=None):
    from ..masks import local_attention_mask

    return local_attention_mask(jnp.arange(S), jnp.arange(T),
                                causal=causal, window=window)


def _full_mask(S, T, causal, window, segment_ids):
    """[B or 1, 1, S, T] bool combined mask (positions ∩ segments)."""
    m = _mask(S, T, causal, window)[None, None]
    if segment_ids is not None:
        seg = (segment_ids[:, None, :, None]
               == segment_ids[:, None, None, :])
        m = m & seg
    return m


def _reference_attention(q, k, v, causal: bool, window=None,
                         segment_ids=None):
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal or window is not None or segment_ids is not None:
        s = jnp.where(_full_mask(s.shape[-2], s.shape[-1], causal, window,
                                 segment_ids), s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _reference_fwd_with_lse(q, k, v, causal: bool, window=None,
                            segment_ids=None):
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal or window is not None or segment_ids is not None:
        s = jnp.where(_full_mask(s.shape[-2], s.shape[-1], causal, window,
                                 segment_ids), s, -1e30)
    lse = jax.scipy.special.logsumexp(s, axis=-1)  # [B, h, S]
    p = jnp.exp(s - lse[..., None]).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v), lse


def _resolve_blocks(block_q, block_k, S, d, backward=False, itemsize=2):
    """0/None → :func:`lattice.auto_flash_blocks`; explicit values are
    honored (then shrunk to legal divisors).  The backward CAPS explicit
    sizes at the rule's choice: that is the largest tile whose VMEM plan
    fits the limit its resident passes hand to Mosaic."""
    abq, abk = lattice.auto_flash_blocks(S, d, backward=backward,
                                         itemsize=itemsize)
    block_q = min(block_q, abq) if (block_q and backward) else (block_q
                                                               or abq)
    block_k = min(block_k, abk) if (block_k and backward) else (block_k
                                                               or abk)
    return lattice.fit_block(block_q, S), lattice.fit_block(block_k, S)


def _kernel_refusal(S: int, d: int, block_q: int, block_k: int,
                    has_seg: bool):
    """Why the kernels cannot take this shape (None when they can) — the
    ONE eligibility test, shared by the forward, the backward and the
    public entry, so the three cannot disagree about which path ran."""
    for backward in (False, True):
        bq, bk = _resolve_blocks(block_q, block_k, S, d, backward=backward)
        if min(bq, bk) < 64:
            return (f"no block >= 64 divides S={S} on the 8-sublane grid "
                    f"({'backward' if backward else 'forward'} blocks "
                    f"{bq}x{bk})")
    if has_seg and not lattice.resident_fits(S, d):
        # the streamed plan is a pure position lattice; packed
        # long-sequence streaming is not written yet
        return (f"segment_ids ride the resident kernels only and S*d="
                f"{S * d} exceeds lattice.RESIDENT_VMEM_ELEMS")
    return None


def _heads_per_program(h: int, d: int) -> int:
    """Heads a resident program takes: as many as fill the 128 lanes when
    a head is narrower (two at d = 64), one otherwise.  From the shape
    alone: at d >= 128 nothing changes."""
    if d < 128 and 128 % d == 0 and h % (128 // d) == 0:
        return 128 // d
    return 1


def _planes(heads: int, B: int, S: int, h: int, d: int):
    """How the resident kernels see ``[B, S, h, d]`` operands, as
    ``(to_planes, from_planes, index)``.  One head a program: ``[B·h, S,
    d]`` planes (a transpose each way) at block index ``(g, i, 0)``.
    Several: the array as it lies, ``[B, S, h·d]`` (a reshape, no copy),
    a program's heads side by side in one 128-lane block at ``(row, i,
    group)``: no transpose on either side, lane-dense loads and stores."""
    if heads == 1:
        return (lambda a: a.transpose(0, 2, 1, 3).reshape(B * h, S, d),
                lambda a: a.reshape(B, h, S, d).transpose(0, 2, 1, 3),
                lambda g, i: (g, i, 0))
    groups = h // heads
    return (lambda a: a.reshape(B, S, h * d),
            lambda a: a.reshape(B, S, h, d),
            lambda g, i: (g // groups, i, g % groups))


def _head_lanes(heads: int, rows: int, width: int):
    """``[rows, width]`` int32: which of a block's ``heads`` heads each
    lane belongs to (None for one head: nothing to select)."""
    if heads == 1:
        return None
    return jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1) // (
        width // heads)


def _seg_operand(segment_ids, S: int, programs_a_row: int):
    """(array, BlockSpec) for the resident kernels' segment-id input:
    ``[B, 1, S]`` blocked one batch row at a time (``programs_a_row`` of
    the grid's leading axis share a row's ids) — the middle singleton
    keeps the block's last two dims equal to the array's, which Mosaic
    demands of any block that is not a multiple of (8, 128) — or a
    ``[1, 1, 1]`` placeholder when the caller packs nothing."""
    from jax.experimental import pallas as pl

    if segment_ids is None:
        return (jnp.zeros((1, 1, 1), jnp.int32),
                pl.BlockSpec((1, 1, 1), lambda bh, i: (0, 0, 0)))
    return (segment_ids.astype(jnp.int32)[:, None, :],
            pl.BlockSpec((1, 1, S),
                         lambda g, i: (g // programs_a_row, 0, 0)))


# ---------------------------------------------------------------------------
# resident kernels
# ---------------------------------------------------------------------------


def _fa_kernel(q_ref, k_ref, v_ref, seg_ref, o_ref, lse_ref, *,
               block_q: int, block_k: int, seq_len: int, causal: bool,
               scale: float, window=None, has_seg: bool = False,
               heads: int = 1):
    """``heads`` > 1: the blocks hold that many heads side by side on the
    lanes.  Each head in turn takes the block with the other heads' lanes
    of q zeroed, so q·kᵀ over all lanes is its own score tile and p·v
    gives its output on its own lanes (the MXU passes of a lone 64-wide
    head, which fills half the array either way)."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    q_all = q_ref[0].astype(jnp.float32) * scale  # [block_q, heads·d]
    nk = seq_len // block_k
    lane_head = _head_lanes(heads, block_q, q_all.shape[-1])

    m0 = jnp.full((block_q,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, q_all.shape[-1]), jnp.float32)
    q_seg = (seg_ref[0, 0, pl.ds(qi * block_q, block_q)] if has_seg else None)
    k0, nk_eff = lattice.kv_block_bounds(qi, block_q, block_k, nk, causal,
                                         window)
    out, stats = None, []
    for a in range(heads):
        q = q_all if heads == 1 else jnp.where(lane_head == a, q_all, 0.0)

        def body(ki, carry, q=q):
            m, l, acc = carry
            kblk = k_ref[0, pl.ds(ki * block_k, block_k), :].astype(
                jnp.float32)
            vblk = v_ref[0, pl.ds(ki * block_k, block_k), :].astype(
                jnp.float32)
            s = jax.lax.dot_general(q, kblk, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            k_seg = (seg_ref[0, 0, pl.ds(ki * block_k, block_k)] if has_seg
                     else None)
            keep = lattice.tile_keep(qi, ki, block_q, block_k, causal,
                                     window, q_seg, k_seg)
            if keep is not None:
                s = jnp.where(keep, s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[:, None])
            if has_seg:
                # a row fully masked in this tile must not accumulate the
                # exp(-1e30 − (-1e30)) = 1 garbage a pure -inf carry avoids
                p = jnp.where(keep, p, 0.0)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[:, None] + jax.lax.dot_general(
                p, vblk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new

        m, l, acc = jax.lax.fori_loop(k0, nk_eff, body, (m0, l0, acc0))
        l2 = l[:, None]
        o = jnp.where(l2 > 0, acc / jnp.where(l2 > 0, l2, 1.0), 0.0)
        out = o if out is None else jnp.where(lane_head == a, o, out)
        stats.append((m, l2))
    o_ref[0] = out.astype(o_ref.dtype)
    for a, (m, l2) in enumerate(stats):
        lse_ref[a] = jnp.where(l2 > 0, m[:, None] + jnp.log(
            jnp.where(l2 > 0, l2, 1.0)), 1e30)


# ---------------------------------------------------------------------------
# streamed forward (long S): gather each live k-block via the lattice plan
# ---------------------------------------------------------------------------


def _fa_stream_kernel(idx_ref, cnt_ref, q_ref, k_ref, v_ref, o_ref,
                      lse_ref, m_ref, l_ref, acc_ref, *, block_q: int,
                      block_k: int, causal: bool, scale: float, window,
                      max_live: int):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    s = pl.program_id(2)
    count = cnt_ref[qi]

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(s < count)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale      # [bq, d]
        kblk = k_ref[0].astype(jnp.float32)           # [bk, d]
        vblk = v_ref[0].astype(jnp.float32)
        kj = idx_ref[qi, s]
        sc = jax.lax.dot_general(q, kblk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        keep = lattice.tile_keep(qi, kj, block_q, block_k, causal, window)
        if keep is not None:
            sc = jnp.where(keep, sc, -1e30)
        m, l = m_ref[:, 0], l_ref[:, 0]
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        p = jnp.exp(sc - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, vblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new[:, None]
        l_ref[...] = l_new[:, None]
        acc_ref[...] = acc_new

    @pl.when(s == max_live - 1)
    def _finalize():
        l2 = l_ref[...]
        o_ref[0] = jnp.where(l2 > 0, acc_ref[...] / jnp.where(
            l2 > 0, l2, 1.0), 0.0).astype(o_ref.dtype)
        m1 = m_ref[...]
        lse_ref[0] = jnp.where(l2 > 0, m1 + jnp.log(
            jnp.where(l2 > 0, l2, 1.0)), 1e30)


def _flash_fwd_stream(qr, kr, vr, causal, block_q, block_k, window,
                      interpret):
    """[B*h, S, d] streamed forward over the lattice plan."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, S, d = qr.shape
    nq = S // block_q
    idx, counts = lattice.plan_q_live(S, block_q, block_k, causal, window)
    L = idx.shape[1]
    kern = functools.partial(_fa_stream_kernel, block_q=block_q,
                             block_k=block_k, causal=causal,
                             scale=1.0 / np.sqrt(d), window=window,
                             max_live=L)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(BH, nq, L),
        in_specs=[
            pl.BlockSpec((1, block_q, d),
                         lambda bh, qi, s, idx, cnt: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, qi, s, idx, cnt: (bh, idx[qi, s], 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, qi, s, idx, cnt: (bh, idx[qi, s], 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d),
                         lambda bh, qi, s, idx, cnt: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 1),
                         lambda bh, qi, s, idx, cnt: (bh, qi, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((BH, S, d), qr.dtype),
                   jax.ShapeDtypeStruct((BH, S, 1), jnp.float32)],
        interpret=bool(interpret),
        name="flash_fwd_stream",
    )(jnp.asarray(idx), jnp.asarray(counts), qr, kr, vr)


def _flash_call(q, k, v, causal, block_q, block_k, interpret,
                with_lse: bool = False, window=None, segment_ids=None,
                force_stream: bool = False):
    from jax.experimental import pallas as pl

    B, S, h, d = q.shape
    has_seg = segment_ids is not None
    if _kernel_refusal(S, d, block_q, block_k, has_seg) is not None:
        out, lse = _reference_fwd_with_lse(q, k, v, causal, window,
                                           segment_ids)
        return (out, lse) if with_lse else out
    block_q, block_k = _resolve_blocks(block_q, block_k, S, d)
    streamed = (force_stream or not lattice.resident_fits(S, d)) \
        and not has_seg
    heads = 1 if streamed else _heads_per_program(h, d)
    to_planes, from_planes, at = _planes(heads, B, S, h, d)
    qr, kr, vr = to_planes(q), to_planes(k), to_planes(v)

    if streamed:
        out, lse = _flash_fwd_stream(qr, kr, vr, causal, block_q, block_k,
                                     window, interpret)
        out = from_planes(out)
        lse = lse.reshape(B, h, S)
        return (out, lse) if with_lse else out
    seg, seg_spec = _seg_operand(segment_ids, S, h // heads)
    width = heads * d

    kernel = functools.partial(
        _fa_kernel, block_q=block_q, block_k=block_k, seq_len=S,
        causal=causal, scale=1.0 / np.sqrt(d), window=window,
        has_seg=has_seg, heads=heads)
    out, lse = pl.pallas_call(
        kernel,
        grid=(B * h // heads, S // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, width), at),
            pl.BlockSpec((1, S, width), lambda g, qi: at(g, 0)),
            pl.BlockSpec((1, S, width), lambda g, qi: at(g, 0)),
            seg_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, width), at),
            # lse as [B*h, S, 1]: trailing singleton keeps the block shape
            # legal under the (8, 128) TPU tiling rule for any block_q
            pl.BlockSpec((heads, block_q, 1), lambda g, qi: (g, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(qr.shape, q.dtype),
            jax.ShapeDtypeStruct((B * h, S, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
        **resident_compiler_params(interpret),
    )(qr, kr, vr, seg)
    out = from_planes(out)
    lse = lse.reshape(B, h, S)  # drops the singleton
    return (out, lse) if with_lse else out


# ---------------------------------------------------------------------------
# resident backward kernels
# ---------------------------------------------------------------------------


_NT = (((1,), (1,)), ((), ()))      # a · bᵀ: contract both minor dims
_NN = (((1,), (0,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))      # aᵀ · b


def _fa_bwd_kernel(q_ref, do_ref, k_ref, v_ref, lse_ref, delta_ref, seg_ref,
                   dq_ref, dk_ref, dv_ref, dq_acc, *, block_q: int,
                   block_k: int, seq_len: int, causal: bool, scale: float,
                   window, has_seg: bool = False,
                   delta_in_tile: bool = False, heads: int = 1):
    """The resident backward, one pass: grid (bh, k-block); Q/do/lse/Δ
    VMEM-resident, the q-loop walks the transposed lattice range, and dq
    gathers in a float32 ``[S, d]`` VMEM plane over a head's k-blocks —
    five products a tile where a dq pass beside a dk/dv pass makes seven.

    The score tile is held keys-major, sᵀ = k·qᵀ ``[bk, bq]``: lse and Δ
    are row vectors that broadcast along sublanes, and dv += pᵀ·do,
    dk += dsᵀ·q are plain ``[bk, bq] × [bq, d]`` products; dq += ds·k
    alone turns a (rounded) tile.  The MXU takes q, k, v, do as they
    arrive and p, ds rounded to that dtype; statistics, ``exp``, the ds
    arithmetic and the three accumulators are float32.

    ``delta_in_tile`` (one k-block spans the sequence, so a tile holds
    every key of its rows): Δ is the tile's own Σ_k p·dp, in float32 from
    the p and dp that ds is made of, and ``delta_ref`` is a placeholder.
    Δ = do·o from the ROUNDED output leaves Σ_k ds ≠ 0 by that rounding,
    which where keys share a large common part (a deep random-weight
    encoder) is most of dq's and dk's error: 0.10 against 0.03 relative
    on BERT-large's ``wq``/``wk`` gradients (my chip runs, PR 41).  With
    several k-blocks no tile sees a whole row, and Δ stays do·o, with that
    error where it arises (``test_deep_stack_gradients_by_k_blocks`` holds
    both; an exact Δ for every tiling needs a float32 output from the
    forward or a first pass over the row: PERF.md §7).

    ``heads`` > 1 (as in the forward): each head in turn takes the k and
    v tiles with the other heads' lanes zeroed, so k·qᵀ and v·doᵀ over all
    lanes are its own tiles and dq lands on its own lanes; dk and dv come
    out on every lane and are kept on its own."""
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    nq, nk = seq_len // block_q, seq_len // block_k
    k_all, v_all = k_ref[0], v_ref[0]                  # [bk, heads·d]
    lane_head = _head_lanes(heads, block_k, k_all.shape[-1])
    k_seg = (seg_ref[0, 0, pl.ds(pl.multiple_of(ki * block_k, block_k),
                                 block_k)] if has_seg else None)

    @pl.when(ki == 0)
    def _new_head():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q0, nq_eff = lattice.q_block_bounds(ki, block_q, block_k, nq, causal,
                                        window)
    zeros = jnp.zeros((block_k, k_all.shape[-1]), jnp.float32)
    dk, dv = None, None
    for a in range(heads):
        own = None if heads == 1 else lane_head == a
        kblk = k_all if heads == 1 else jnp.where(own, k_all, 0)
        vblk = v_all if heads == 1 else jnp.where(own, v_all, 0)

        def body(qi, carry, a=a, kblk=kblk, vblk=vblk):
            dk_acc, dv_acc = carry
            rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
            q, do = q_ref[0, rows, :], do_ref[0, rows, :]  # [bq, heads·d]
            st = jax.lax.dot_general(
                kblk, q, _NT, preferred_element_type=jnp.float32) * scale
            keep = lattice.tile_keep(
                qi, ki, block_q, block_k, causal, window,
                seg_ref[0, 0, rows] if has_seg else None, k_seg,
                transposed=True)
            pt = jnp.exp(st - lse_ref[a, :, rows])     # [bk, bq] − [1, bq]
            if keep is not None:
                pt = jnp.where(keep, pt, 0.0)
            dv_acc = dv_acc + jax.lax.dot_general(
                pt.astype(do.dtype), do, _NN,
                preferred_element_type=jnp.float32)
            dpt = jax.lax.dot_general(vblk, do, _NT,
                                      preferred_element_type=jnp.float32)
            delta = (jnp.sum(pt * dpt, axis=0, keepdims=True)
                     if delta_in_tile else delta_ref[a, :, rows])  # [1, bq]
            dst = (pt * (dpt - delta)).astype(q.dtype)
            dk_acc = dk_acc + jax.lax.dot_general(
                dst, q, _NN, preferred_element_type=jnp.float32)
            dq_acc[rows, :] += jax.lax.dot_general(
                dst, kblk, _TN, preferred_element_type=jnp.float32)
            return dk_acc, dv_acc

        dk_acc, dv_acc = jax.lax.fori_loop(q0, nq_eff, body, (zeros, zeros))
        dk = dk_acc if dk is None else jnp.where(own, dk_acc, dk)
        dv = dv_acc if dv is None else jnp.where(own, dv_acc, dv)
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)

    @pl.when(ki == nk - 1)
    def _head_done():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _flash_bwd_pallas(q, k, v, out, lse, do, causal, block_q, block_k,
                      window, interpret: bool = False, segment_ids=None):
    """Resident kernel backward: one Mosaic call gives dq, dk and dv,
    scores VMEM-resident, the tile from :func:`lattice.auto_flash_blocks`
    at the operands' dtype."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, h, d = q.shape
    block_q, block_k = _resolve_blocks(block_q, block_k, S, d,
                                       backward=True,
                                       itemsize=q.dtype.itemsize)
    heads = _heads_per_program(h, d)
    to_planes, from_planes, at = _planes(heads, B, S, h, d)
    qr, kr, vr, dor = to_planes(q), to_planes(k), to_planes(v), to_planes(do)
    width = heads * d
    # a head's statistics as one lane-dense float32 row, [B·h, 1, S]: 32 KiB
    # at S = 8,192 where [B·h, S, 1] is tiled (8, 128) to 4 MiB, in HBM and
    # in VMEM
    lse_r = lse.reshape(B * h, 1, S)
    plane = pl.BlockSpec((1, S, width), lambda g, ki: at(g, 0))
    stats = pl.BlockSpec((heads, 1, S), lambda g, ki: (g, 0, 0))
    delta_in_tile = block_k == S
    if delta_in_tile:
        # the kernel sums p·dp over the tile's keys, which are all of them
        delta_r, delta_spec = (jnp.zeros((1, 1, 1), jnp.float32),
                               pl.BlockSpec((1, 1, 1),
                                            lambda g, ki: (0, 0, 0)))
    else:
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)                        # [B, S, h]
        delta_r, delta_spec = (
            delta.transpose(0, 2, 1).reshape(B * h, 1, S), stats)
    seg, seg_spec = _seg_operand(segment_ids, S, h // heads)
    tile_k = pl.BlockSpec((1, block_k, width), at)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_kernel, block_q=block_q, block_k=block_k,
                          seq_len=S, causal=causal, scale=1.0 / np.sqrt(d),
                          window=window, has_seg=segment_ids is not None,
                          delta_in_tile=delta_in_tile, heads=heads),
        grid=(B * h // heads, S // block_k),
        in_specs=[plane, plane, tile_k, tile_k, stats, delta_spec, seg_spec],
        out_specs=[plane, tile_k, tile_k],
        out_shape=[jax.ShapeDtypeStruct(qr.shape, q.dtype),
                   jax.ShapeDtypeStruct(qr.shape, k.dtype),
                   jax.ShapeDtypeStruct(qr.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((S, width), jnp.float32)],
        interpret=interpret,
        name="flash_bwd",
        # dq gathers over a head's k-blocks: that grid axis runs in order
        **resident_compiler_params(interpret, ("parallel", "arbitrary")),
    )(qr, dor, kr, vr, lse_r, delta_r, seg)

    return from_planes(dq), from_planes(dk), from_planes(dv)


# ---------------------------------------------------------------------------
# streamed backward kernels (long S)
# ---------------------------------------------------------------------------


def _fa_bwd_dq_stream_kernel(idx_ref, cnt_ref, q_ref, do_ref, k_ref,
                             v_ref, lse_ref, delta_ref, dq_ref, acc_ref,
                             *, block_q: int, block_k: int, causal: bool,
                             scale: float, window):
    """Streamed dq: grid (bh, q-block, live-s); each step's K/V block is
    gathered by the prefetched lattice plan.  dq accumulates in VMEM
    scratch; the constant-over-s output index map flushes it at the
    q-row boundary (the block-sparse flat-walk write trick)."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    s = pl.program_id(2)
    count = cnt_ref[qi]

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(s < count)
    def _step():
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        kblk = k_ref[0].astype(jnp.float32)
        vblk = v_ref[0].astype(jnp.float32)
        lse = lse_ref[0, :, 0]
        delta = delta_ref[0, :, 0]
        kj = idx_ref[qi, s]
        sc = jax.lax.dot_general(q, kblk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        keep = lattice.tile_keep(qi, kj, block_q, block_k, causal, window)
        p = jnp.exp(sc - lse[:, None])
        if keep is not None:
            p = jnp.where(keep, p, 0.0)
        dp = jax.lax.dot_general(do, vblk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        acc_ref[...] += jax.lax.dot_general(
            ds, kblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _fa_bwd_dkv_stream_kernel(idx_ref, cnt_ref, q_ref, do_ref, k_ref,
                              v_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                              kacc_ref, vacc_ref, *, block_q: int,
                              block_k: int, causal: bool, scale: float,
                              window):
    """Streamed dk/dv: grid (bh, k-block, live-s) over the transposed
    plan; q/do/lse/Δ blocks gathered per step, dk/dv accumulate in
    scratch and flush at the k-column boundary."""
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    s = pl.program_id(2)
    count = cnt_ref[ki]

    @pl.when(s == 0)
    def _init():
        kacc_ref[...] = jnp.zeros_like(kacc_ref)
        vacc_ref[...] = jnp.zeros_like(vacc_ref)

    @pl.when(s < count)
    def _step():
        kblk = k_ref[0].astype(jnp.float32)
        vblk = v_ref[0].astype(jnp.float32)
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, :, 0]
        delta = delta_ref[0, :, 0]
        qi = idx_ref[ki, s]
        sc = jax.lax.dot_general(q, kblk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        keep = lattice.tile_keep(qi, ki, block_q, block_k, causal, window)
        p = jnp.exp(sc - lse[:, None])
        if keep is not None:
            p = jnp.where(keep, p, 0.0)
        vacc_ref[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, vblk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        kacc_ref[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    dk_ref[0] = kacc_ref[...].astype(dk_ref.dtype)
    dv_ref[0] = vacc_ref[...].astype(dv_ref.dtype)


def _flash_bwd_stream(q, k, v, out, lse, do, causal, block_q, block_k,
                      window, interpret: bool = False):
    """Streamed kernel backward — VMEM holds one tile's operands, HBM
    traffic follows the lattice's live count, S unbounded by residency."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, h, d = q.shape
    block_q, block_k = _resolve_blocks(block_q, block_k, S, d,
                                       backward=True)
    nq, nk = S // block_q, S // block_k
    qr = q.transpose(0, 2, 1, 3).reshape(B * h, S, d)
    kr = k.transpose(0, 2, 1, 3).reshape(B * h, S, d)
    vr = v.transpose(0, 2, 1, 3).reshape(B * h, S, d)
    dor = do.transpose(0, 2, 1, 3).reshape(B * h, S, d)
    lse_r = lse.reshape(B * h, S, 1)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    delta_r = delta.transpose(0, 2, 1).reshape(B * h, S, 1)
    scale = 1.0 / np.sqrt(d)

    idx, counts = lattice.plan_q_live(S, block_q, block_k, causal, window)
    L = idx.shape[1]
    dq_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * h, nq, L),
        in_specs=[
            pl.BlockSpec((1, block_q, d),
                         lambda bh, qi, s, ix, ct: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, d),
                         lambda bh, qi, s, ix, ct: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, qi, s, ix, ct: (bh, ix[qi, s], 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, qi, s, ix, ct: (bh, ix[qi, s], 0)),
            pl.BlockSpec((1, block_q, 1),
                         lambda bh, qi, s, ix, ct: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 1),
                         lambda bh, qi, s, ix, ct: (bh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda bh, qi, s, ix, ct: (bh, qi, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
    )
    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_stream_kernel, block_q=block_q,
                          block_k=block_k, causal=causal, scale=scale,
                          window=window),
        grid_spec=dq_spec,
        out_shape=jax.ShapeDtypeStruct((B * h, S, d), q.dtype),
        interpret=bool(interpret),
        name="flash_bwd_dq_stream",
    )(jnp.asarray(idx), jnp.asarray(counts), qr, dor, kr, vr, lse_r,
      delta_r)

    idx_k, counts_k = lattice.plan_k_live(S, block_q, block_k, causal,
                                          window)
    Lk = idx_k.shape[1]
    dkv_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * h, nk, Lk),
        in_specs=[
            pl.BlockSpec((1, block_q, d),
                         lambda bh, ki, s, ix, ct: (bh, ix[ki, s], 0)),
            pl.BlockSpec((1, block_q, d),
                         lambda bh, ki, s, ix, ct: (bh, ix[ki, s], 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, ki, s, ix, ct: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, ki, s, ix, ct: (bh, ki, 0)),
            pl.BlockSpec((1, block_q, 1),
                         lambda bh, ki, s, ix, ct: (bh, ix[ki, s], 0)),
            pl.BlockSpec((1, block_q, 1),
                         lambda bh, ki, s, ix, ct: (bh, ix[ki, s], 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d),
                         lambda bh, ki, s, ix, ct: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, ki, s, ix, ct: (bh, ki, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
    )
    dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_stream_kernel, block_q=block_q,
                          block_k=block_k, causal=causal, scale=scale,
                          window=window),
        grid_spec=dkv_spec,
        out_shape=[jax.ShapeDtypeStruct((B * h, S, d), k.dtype),
                   jax.ShapeDtypeStruct((B * h, S, d), v.dtype)],
        interpret=bool(interpret),
        name="flash_bwd_dkv_stream",
    )(jnp.asarray(idx_k), jnp.asarray(counts_k), qr, dor, kr, vr, lse_r,
      delta_r)

    back = lambda a: a.reshape(B, h, S, d).transpose(0, 2, 1, 3)
    return back(dq), back(dk), back(dv)


# ---------------------------------------------------------------------------
# custom_vjp wiring + public entry
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, seg, causal, block_q, block_k, window, impl, keep):
    return _flash_inner_fwd(q, k, v, seg, causal, block_q, block_k,
                            window, impl, keep)[0]


def _segments(seg, q):
    """The ``[B, S]`` segment ids, or None for the ``[B, 1]`` placeholder
    the custom_vjp carries when the caller passed none."""
    return seg if seg.ndim == 2 and seg.shape[1] == q.shape[1] else None


def _flash_inner_fwd(q, k, v, seg, causal, block_q, block_k, window, impl,
                     keep):
    segment_ids = _segments(seg, q)
    if impl == "reference":
        out, lse = _reference_fwd_with_lse(q, k, v, causal, window,
                                           segment_ids)
    else:
        out, lse = _flash_call(q, k, v, causal, block_q, block_k,
                               interpret=impl == "interpret", with_lse=True,
                               window=window, segment_ids=segment_ids)
    if keep:
        # named as the backward takes them: ``out [B, S, h, d]`` and the
        # dense ``lse [B, h, S]`` (at [1, 8192, 32, 128]: 64 MiB and
        # 1 MiB), not the kernel's ``[B·h, S, 1]`` statistics, which pad
        # to a lane a row (128 MiB).  A name is the identity outside
        # ``jax.checkpoint`` and lowers to nothing.
        out = checkpoint_name(out, RESIDUAL_NAMES[0])
        lse = checkpoint_name(lse, RESIDUAL_NAMES[1])
    return out, (q, k, v, seg, out, lse)


def _flash_inner_bwd(causal, block_q, block_k, window, impl, keep, res, do):
    """Backward of whatever the forward ran (``impl`` was fixed by
    :func:`flash_attention`): resident Pallas kernels while the planes
    fit VMEM, streamed kernels beyond, a jnp chunked scan for the
    reference.

    Uses the saved per-row log-sum-exp (no softmax re-normalization pass)
    and ``delta_i = Σ_d do_i·o_i`` so the softmax jacobian term needs no
    cross-block reduction."""
    q, k, v, seg, out, lse = res
    segment_ids = _segments(seg, q)
    B, S, h, d = q.shape
    dseg = np.zeros(seg.shape, dtype=jax.dtypes.float0)
    if impl != "reference":
        interpret = impl == "interpret"
        if lattice.resident_fits(S, d):
            dq, dk, dv = _flash_bwd_pallas(
                q, k, v, out, lse, do, causal, block_q, block_k, window,
                interpret=interpret, segment_ids=segment_ids)
        else:
            dq, dk, dv = _flash_bwd_stream(
                q, k, v, out, lse, do, causal, block_q, block_k, window,
                interpret=interpret)
        return dq, dk, dv, dseg
    _, bk = _resolve_blocks(block_q, block_k, S, d, backward=True)
    scale = 1.0 / np.sqrt(d)
    blk = min(bk if bk >= 1 else S, S)
    while blk > 1 and S % blk:  # shrink to a divisor (matches _flash_call)
        blk //= 2
    if blk < 64:
        blk = S  # degenerate fall-back: one chunk (== full recompute)
    nk = S // blk

    q32 = q.astype(jnp.float32)
    do32 = do.astype(jnp.float32)
    # delta: [B, h, S] — rowwise do·o
    delta = jnp.einsum("bqhd,bqhd->bhq", do32, out.astype(jnp.float32))

    k_chunks = k.reshape(B, nk, blk, h, d).transpose(1, 0, 2, 3, 4)
    v_chunks = v.reshape(B, nk, blk, h, d).transpose(1, 0, 2, 3, 4)
    q_pos = jnp.arange(S)

    def body(dq_acc, chunk):
        ki, kblk, vblk = chunk
        kb32 = kblk.astype(jnp.float32)
        s = jnp.einsum("bqhd,bkhd->bhqk", q32, kb32) * scale
        if causal or window is not None or segment_ids is not None:
            from ..masks import local_attention_mask

            k_pos = ki * blk + jnp.arange(blk)
            m = local_attention_mask(q_pos, k_pos, causal, window)[None,
                                                                   None]
            if segment_ids is not None:
                seg_m = (segment_ids[:, None, :, None]
                         == jax.lax.dynamic_slice_in_dim(
                             segment_ids, ki * blk, blk,
                             axis=1)[:, None, None, :])
                m = m & seg_m
            s = jnp.where(m, s, -1e30)
        p = jnp.exp(s - lse[..., None])  # [B, h, S, blk]
        dv_blk = jnp.einsum("bhqk,bqhd->bkhd", p, do32)
        dp = jnp.einsum("bqhd,bkhd->bhqk", do32, vblk.astype(jnp.float32))
        ds = p * (dp - delta[..., None])
        dq_acc = dq_acc + jnp.einsum("bhqk,bkhd->bqhd", ds, kb32) * scale
        dk_blk = jnp.einsum("bhqk,bqhd->bkhd", ds, q32) * scale
        return dq_acc, (dk_blk, dv_blk)

    dq0 = jnp.zeros((B, S, h, d), jnp.float32)
    dq, (dk_chunks, dv_chunks) = jax.lax.scan(
        body, dq0, (jnp.arange(nk), k_chunks, v_chunks))
    dk = dk_chunks.transpose(1, 0, 2, 3, 4).reshape(B, S, h, d)
    dv = dv_chunks.transpose(1, 0, 2, 3, 4).reshape(B, S, h, d)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            dseg)


_flash.defvjp(_flash_inner_fwd, _flash_inner_bwd)


def flash_route(S: int, d: int, block_q: int = 0, block_k: int = 0,
                has_seg: bool = False, interpret: bool | None = None):
    """``(impl, refusal)``: what :func:`flash_attention` runs for this
    shape on this platform, by the test it decides with: ``"kernel"`` (the
    compiled Pallas kernels), ``"interpret"`` (the kernels in the Pallas
    interpreter) or ``"reference"`` (``jax.numpy``), and why the kernels
    refused the shape when they did.  For a caller that has to know before
    a step is traced (a model's ``uses_flash_kernels``, which the engine's
    memory ledger asks)."""
    if reference_off_tpu(interpret):
        return "reference", None
    refusal = _kernel_refusal(S, d, block_q, block_k, has_seg)
    if refusal is not None:
        return "reference", refusal
    return ("interpret" if interpret else "kernel"), None


def mean_keys_scored(S: int, causal: bool, window=None) -> float:
    """Keys a query row scores, averaged over the ``S`` rows of the
    position mask (``ops/masks`` semantics: row ``i`` sees keys ``j <= i``
    when causal, and ``|i - j| < window``)."""
    i = np.arange(S, dtype=np.int64)
    reach = S if window is None else min(int(window), S)
    behind = np.minimum(i + 1, reach)
    ahead = 0 if causal else np.minimum(S - 1 - i, reach - 1)
    return float(np.mean(behind + ahead))


def keeps_residuals(S: int, h: int, d: int, causal: bool,
                    window=None) -> bool:
    """Whether a call's ``out`` and ``lse`` are worth holding through a
    rematerialized layer, from the shapes alone.  A layer's remat policy
    already holds its projections' outputs: a byte of one costs ``K``
    operations to recompute (2·K a bf16 element, ``K`` the contraction,
    which for the projections that feed and drain this call is ``h·d``).
    A byte of ``out`` costs ``2 × keys`` (4·keys·d operations for the
    2·d bytes of a row's head: the scores and the weighted sum; ``lse``
    rides along at 1/(64·d) of the bytes).  So the outputs are named for
    the policy to keep where they are the DEARER of the two to recompute,
    and strictly: at a tie they cost what the policy's own saves cost, and
    holding them buys nothing the memory would not buy elsewhere.

    Mistral-7B's row (S 8,192, causal, window 4,096, 32 × 128): 3,072
    keys a row, 6,144 operations a byte against 4,096: kept, 65 MiB a
    layer for a second ``flash_fwd`` of 4.1 ms.  BERT-large's (S 512, not
    causal, 16 × 64): 1,024 against 1,024: recomputed."""
    return 2 * mean_keys_scored(S, causal, window) > h * d


def flash_attention(q, k, v, causal: bool = True,
                    block_q: int = 0, block_k: int = 0,
                    window=None, segment_ids=None,
                    interpret: bool | None = None):
    """[B, S, h, d] attention, forward and backward.

    ``block_q``/``block_k`` 0 → the seq-length-aware table
    (:func:`lattice.auto_flash_blocks`; forward and backward resolve
    independently).  ``window`` = sliding-window reach (ops/masks
    semantics); k-blocks wholly outside the lattice are skipped.
    ``segment_ids [B, S]`` masks cross-segment pairs (packed sequences,
    padding) on the resident kernels and all reference paths.

    ``interpret`` follows :mod:`.select`: None → the compiled kernels on
    a TPU and the jnp reference elsewhere.  The choice is made HERE, once,
    for both passes; a shape the kernels refuse runs the reference and
    says so on a TPU."""
    B, S, h, d = q.shape
    seg = (segment_ids.astype(jnp.int32) if segment_ids is not None
           else jnp.zeros((B, 1), jnp.int32))
    block_q, block_k = int(block_q or 0), int(block_k or 0)
    impl, refusal = flash_route(S, d, block_q, block_k,
                                segment_ids is not None, interpret)
    if refusal is not None:
        shape_refused("flash_attention", tuple(q.shape), refusal)
    record_route("flash_attention", impl)
    keep = keeps_residuals(S, h, d, causal, window)
    record_residuals("flash_attention", keep)
    return _flash(q, k, v, seg, causal, block_q, block_k, window, impl,
                  keep)


def flash_attention_interpret(q, k, v, causal: bool = True,
                              block_q: int = 64, block_k: int = 64,
                              window=None, segment_ids=None,
                              stream: bool = False):
    """Interpreter-mode FORWARD kernel run (CPU numerics testing);
    ``stream=True`` forces the long-S gather kernels regardless of
    residency."""
    return _flash_call(q, k, v, causal, block_q, block_k, interpret=True,
                       window=window, segment_ids=segment_ids,
                       force_stream=stream)


def flash_attention_spmd(q, k, v, mesh, causal: bool = True,
                         block_q: int = 0, block_k: int = 0, window=None,
                         segment_ids=None):
    """:func:`flash_attention` on a mesh.  GSPMD cannot partition a Mosaic
    call, and jax lowers one only where EVERY mesh axis is manual
    ("Mosaic kernels cannot be automatically partitioned. Please wrap the
    call in a shard_map"), so the partitioning is explicit: a
    ``shard_map`` over all axes that are not manual already (an enclosing
    Ulysses, pipeline or ZeRO-3 region keeps its own), with batch rows
    split over the data-parallel axes and heads over ``tensor`` — both
    independent in attention, so no communication."""
    from ...parallel.mesh import AXIS_TENSOR, DP_AXES
    from ...utils.jax_compat import current_manual_axes, shard_map

    def local(ql, kl, vl, seg):
        return flash_attention(ql, kl, vl, causal, block_q=block_q,
                               block_k=block_k, window=window,
                               segment_ids=seg)

    if mesh is None:
        return local(q, k, v, segment_ids)
    manual = current_manual_axes()
    axes = set(mesh.axis_names) - manual
    if not axes:
        return local(q, k, v, segment_ids)
    P = jax.sharding.PartitionSpec
    batch = tuple(a for a in DP_AXES if a in axes) or None
    qkv = P(batch, None, AXIS_TENSOR if AXIS_TENSOR in axes else None, None)
    ctx = jax.sharding.get_abstract_mesh()
    return shard_map(
        local, mesh=mesh if ctx.empty else ctx,
        in_specs=(qkv, qkv, qkv,
                  None if segment_ids is None else P(batch, None)),
        out_specs=qkv, axis_names=axes, check_vma=False)(q, k, v,
                                                         segment_ids)
