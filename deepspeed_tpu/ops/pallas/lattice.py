"""Block skip lattice + block-size tables — shared by every attention kernel.

The causal triangle and the sliding window are BLOCK-structured masks:
at kernel-block granularity they define a boolean ``[nq, nk]`` lattice of
live tiles.  Before this module, :mod:`flash_attention` derived its
causal k-loop bounds inline and :mod:`block_sparse_attention` tril'd its
layout inline — two skip implementations that could (and did) drift.
Now there is ONE lattice:

* :func:`live_lattice` — the host-side ``[nq, nk]`` live-tile grid for
  (causal, window); block-sparse intersects its ``SparsityConfig``
  layout with it (:func:`apply_lattice`), flash walks it directly.
* :func:`kv_block_bounds` / :func:`q_block_bounds` — the traced
  contiguous [lo, hi) loop bounds the RESIDENT kernels use (causal and
  window lattices are banded, so a contiguous range is exact).
* :func:`plan_q_live` / :func:`plan_k_live` — padded live-index plans
  (row-major / column-major) that drive the STREAMED kernels' scalar-
  prefetched gather ``index_map``s, the same machinery as the
  block-sparse gather forward.
* :func:`tile_keep` — the in-kernel ``[bq, bk]`` token mask for one
  tile (causal edge + window band + segment equality), shared by the
  flash forward, both flash backwards, and the block-sparse tile update
  so masking cannot drift between passes.

Block-size selection (:func:`auto_flash_blocks`): the forward walks a
table keyed on the resident planes' elements; the backward takes the
largest tile whose VMEM plan (:func:`backward_plan_bytes`) fits the limit
its resident passes hand to Mosaic.
"""

from __future__ import annotations

import functools
from collections import OrderedDict
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .select import RESIDENT_VMEM_LIMIT_BYTES

#: PER-PLANE element bound (S·d of K, same for V) for VMEM-resident
#: kernels; K+V together occupy up to 2x this.  2M elems/plane = 8 MiB
#: bf16 — inside a v5e core's VMEM alongside q/acc scratch (one bound
#: for flash AND block-sparse so their dispatch cannot disagree about
#: what "fits").
RESIDENT_VMEM_ELEMS = 2 * 1024 * 1024


def resident_fits(S: int, d: int) -> bool:
    """Whether a head's K/V planes fit the resident-kernel VMEM budget."""
    return S * d <= RESIDENT_VMEM_ELEMS


# ---------------------------------------------------------------------------
# block-size tables
# ---------------------------------------------------------------------------

#: (min_S·d_elems_exclusive → (block_q, block_k)) forward table: the
#: q/score/acc tiles share VMEM with the resident K/V planes, whose
#: footprint is S·d, which is why the key is ELEMENTS not raw S (a d=128
#: model meets a boundary at half the S).  ``auto_flash_blocks`` walks
#: this largest-bound-first.  The last row at [32, 512, 16, 64] bf16, no
#: mask, on a v5e (PR 41, chip runs of the kernel alone, the device's
#: clock, a head a program): 0.65 ms at 512 x 512 (one tile a head), 0.98
#: at 256 x 512, 1.09 at 512 x 256, 1.30 at 256 x 256 and at 128 x 512,
#: 3.31 at 128 x 128; two heads a program at 512 x 512: 0.53
_FWD_BLOCKS: Tuple[Tuple[int, Tuple[int, int]], ...] = (
    (16384 * 64, (256, 256)),   # S·d > 1M elems
    (8192 * 64, (256, 512)),    # 512k < S·d <= 1M
    (0, (512, 512)),            # S·d <= 512k
)

#: backward tiles (block_q, block_k), largest first; the rule takes the
#: first whose plan fits.  Nothing larger is a candidate: at
#: [1, 8192, 32, 128] bf16, window 4,096, on a v5e the backward took
#: 7.68 ms at 512 x 512, 7.85 at 1024 x 512, 8.17 at 512 x 1024, 8.32 at
#: 512 x 256, 9.26 at 256 x 512 and 12.2 at 256 x 256 (PR 32, chip runs
#: of the kernel alone, the device's clock); at [32, 512, 16, 64] bf16,
#: no mask, 1.03 ms at 512 x 512, 1.31 at 512 x 256, 1.46 at 256 x 512,
#: 1.75 at 128 x 512, 2.05 at 256 x 256 and 3.83 at 128 x 128 (PR 41,
#: the same way)
_BWD_TILES: Tuple[Tuple[int, int], ...] = (
    (512, 512), (512, 256), (256, 512), (256, 256), (128, 256), (128, 128),
    (64, 64))

#: the streamed backward (S·d past residency; timed in no cell) keeps the
#: tile it has always run: its calls pass Mosaic no VMEM limit
_STREAM_BWD_BLOCKS = (128, 256)


def backward_plan_bytes(S: int, d: int, itemsize: int, block_q: int,
                        block_k: int) -> int:
    """VMEM the resident backward asks of Mosaic at this tile: what the
    pipeline holds (every operand double-buffered), the float32 dq
    accumulator, and one tile's float32 score planes and accumulators."""
    planes = 3 * S * d * itemsize          # q and do in, dq out
    stats = 2 * 8 * S * 4                  # lse, Δ: [1, S] float32 rows
    blocked = 4 * block_k * d * itemsize   # k and v in, dk and dv out
    acc = (S + 2 * block_k) * d * 4
    scores = 6 * block_q * block_k * 4     # s, keep, p, dp, ds + roundings
    return 2 * (planes + stats + blocked) + acc + scores


def fit_block(b: int, S: int) -> int:
    """Largest block <= ``b`` that divides S and keeps the (8, 128)
    sublane tiling legal (shared by forward/backward eligibility so the
    two dispatch sites cannot drift)."""
    b = min(b, S)
    while b >= 64 and (S % b or b % 8):
        b //= 2
    return b


def auto_flash_blocks(S: int, d: int, backward: bool = False,
                      itemsize: int = 2) -> Tuple[int, int]:
    """(block_q, block_k) for the flash kernels from what the call can
    see: S·d (the resident planes' footprint) and, for the backward, the
    operands' ``itemsize``.  Callers pass explicit sizes (or the tuning
    plane's ``kernels.flash_block_*`` overrides) as caps."""
    if backward:
        if not resident_fits(S, d):
            bq, bk = _STREAM_BWD_BLOCKS
            return fit_block(bq, S), fit_block(bk, S)
        for bq, bk in _BWD_TILES:
            bq, bk = fit_block(bq, S), fit_block(bk, S)
            if (backward_plan_bytes(S, d, itemsize, bq, bk)
                    <= RESIDENT_VMEM_LIMIT_BYTES):
                return bq, bk
        raise AssertionError(
            f"no backward tile fits {RESIDENT_VMEM_LIMIT_BYTES} bytes of "
            f"VMEM at S={S}, d={d}, itemsize={itemsize}")
    elems = S * max(d, 1)
    for min_elems, blocks in _FWD_BLOCKS:
        if elems > min_elems:  # the (0, ...) row matches any valid S·d
            return fit_block(blocks[0], S), fit_block(blocks[1], S)
    raise AssertionError(f"block table has no row for S·d = {elems}")


# ---------------------------------------------------------------------------
# the lattice itself
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=64)
def live_lattice(S: int, block_q: int, block_k: int, causal: bool,
                 window: Optional[int] = None) -> np.ndarray:
    """Host-side ``[nq, nk]`` bool — True where a (q-block, k-block) tile
    holds ANY unmasked (causal ∩ window) token pair.  This is the single
    source of truth for "which tiles exist": flash plans walk it,
    block-sparse intersects its layout with it."""
    nq, nk = S // block_q, S // block_k
    qi = np.arange(nq)
    kj = np.arange(nk)
    q_lo = qi[:, None] * block_q                   # first q pos of row
    q_hi = q_lo + block_q - 1                      # last q pos of row
    k_lo = kj[None, :] * block_k
    k_hi = k_lo + block_k - 1
    # a tile is live iff SOME (q, k) pair in it is unmasked; the q−k
    # values a tile can realize form the interval [q_lo−k_hi, q_hi−k_lo]
    live = np.ones((nq, nk), bool)
    if causal:
        live &= k_lo <= q_hi                       # ∃ pair with q−k ≥ 0
    if window is not None:
        live &= (q_lo - k_hi) < window             # ∃ pair with q−k < w
        if not causal:
            live &= (k_lo - q_hi) < window         # ∃ pair with k−q < w
    return live


def apply_lattice(layout: np.ndarray, causal: bool,
                  window: Optional[int] = None,
                  cb: int = 1) -> np.ndarray:
    """Intersect a ``[H, nb, nb]`` sparsity-cell layout with the causal/
    window lattice at CELL granularity — the block-sparse planner's skip
    source (replaces its inline tril).  ``window`` is TOKENS (the unit
    every other lattice function uses); ``cb`` is the cell size in
    tokens, so the cell lattice is computed over the token grid with
    cells as blocks (cb=1 keeps cells == tokens)."""
    lay = np.asarray(layout)
    H, nb, _ = lay.shape
    if not causal and window is None:
        return lay
    cb = max(int(cb), 1)
    lat = live_lattice(nb * cb, cb, cb, causal, window)
    return lay * lat[None].astype(lay.dtype)


def kv_block_bounds(qi, block_q: int, block_k: int, nk: int, causal: bool,
                    window: Optional[int] = None):
    """Traced [k0, nk_eff) k-block loop bounds for one q-block — the
    contiguous-range form of the lattice row (causal/window rows are
    banded so the range is exact).  The resident flash forward's walk."""
    if causal:
        nk_eff = (qi * block_q + block_q + block_k - 1) // block_k
        nk_eff = jnp.minimum(nk_eff, nk)
    else:
        nk_eff = nk
    k0 = 0
    if window is not None:
        k0 = jnp.maximum(qi * block_q - (window - 1), 0) // block_k
        if not causal:
            nk_eff = jnp.minimum(
                nk_eff,
                (qi * block_q + block_q - 1 + window + block_k - 1)
                // block_k)
    return k0, nk_eff


def q_block_bounds(ki, block_q: int, block_k: int, nq: int, causal: bool,
                   window: Optional[int] = None):
    """Traced [q0, nq_eff) q-block bounds for one k-block (the resident
    backward's transposed walk of the same lattice)."""
    q0 = (ki * block_k) // block_q if causal else 0
    nq_eff = nq
    if window is not None:
        nq_eff = jnp.minimum(
            nq, (ki * block_k + block_k - 1 + window + block_q - 1)
            // block_q)
        if not causal:
            q0 = jnp.maximum(ki * block_k - (window - 1), 0) // block_q
    return q0, nq_eff


# ---------------------------------------------------------------------------
# streamed-kernel plans (padded live-index lists over the lattice)
# ---------------------------------------------------------------------------

_PLAN_CACHE: OrderedDict = OrderedDict()
_PLAN_CACHE_MAX = 32


def _cached(key, build):
    hit = _PLAN_CACHE.get(key)
    if hit is not None:
        _PLAN_CACHE.move_to_end(key)
        return hit
    out = build()
    _PLAN_CACHE[key] = out
    while len(_PLAN_CACHE) > _PLAN_CACHE_MAX:
        _PLAN_CACHE.popitem(last=False)
    return out


def plan_q_live(S: int, block_q: int, block_k: int, causal: bool,
                window: Optional[int] = None
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Row-major plan: per q-block, the list of live k-block ids —
    ``(idx [nq, L] int32, counts [nq] int32)`` with dead slots padded by
    the last live id (consecutive identical indices elide the re-DMA,
    the block-sparse gather trick).  Drives the streamed forward and the
    streamed dq backward."""
    def build():
        lat = live_lattice(S, block_q, block_k, causal, window)
        nq = lat.shape[0]
        lists = [np.nonzero(lat[qi])[0] for qi in range(nq)]
        L = max((len(l) for l in lists), default=1)
        L = max(L, 1)
        idx = np.zeros((nq, L), np.int32)
        counts = np.zeros((nq,), np.int32)
        for qi, live in enumerate(lists):
            counts[qi] = len(live)
            if len(live):
                idx[qi, :len(live)] = live
                idx[qi, len(live):] = live[-1]
        return idx, counts
    return _cached((S, block_q, block_k, causal, window, "q"), build)


def plan_k_live(S: int, block_q: int, block_k: int, causal: bool,
                window: Optional[int] = None
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Column-major plan: per k-block, the live q-block ids — the
    streamed dk/dv backward's transposed walk."""
    def build():
        lat = live_lattice(S, block_q, block_k, causal, window)
        nk = lat.shape[1]
        lists = [np.nonzero(lat[:, kj])[0] for kj in range(nk)]
        L = max((len(l) for l in lists), default=1)
        L = max(L, 1)
        idx = np.zeros((nk, L), np.int32)
        counts = np.zeros((nk,), np.int32)
        for kj, live in enumerate(lists):
            counts[kj] = len(live)
            if len(live):
                idx[kj, :len(live)] = live
                idx[kj, len(live):] = live[-1]
        return idx, counts
    return _cached((S, block_q, block_k, causal, window, "k"), build)


# ---------------------------------------------------------------------------
# the in-kernel tile mask
# ---------------------------------------------------------------------------


def tile_keep(qi, kj, block_q: int, block_k: int, causal: bool,
              window: Optional[int] = None, q_seg=None, k_seg=None,
              transposed: bool = False):
    """``[bq, bk]`` bool keep mask for tile (qi, kj): causal edge ∩
    window band ∩ segment equality.  ``q_seg [bq]`` / ``k_seg [bk]`` are
    this tile's segment-id slices (packed sequences / padding); None
    skips the segment term.  ``transposed`` gives the same mask as
    ``[bk, bq]`` (the resident backward holds its score tile keys-major).
    Returns None when nothing masks (the caller skips the where())."""
    need_pos = causal or window is not None
    keep = None
    shape = (block_k, block_q) if transposed else (block_q, block_k)
    q_axis = 1 if transposed else 0
    if need_pos:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, shape, q_axis)
        k_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, shape, 1 - q_axis)
        if causal:
            keep = q_pos >= k_pos
        if window is not None:
            reach = ((q_pos - k_pos < window) if causal
                     else (q_pos - k_pos < window)
                     & (k_pos - q_pos < window))
            keep = reach if keep is None else keep & reach
    if q_seg is not None and k_seg is not None:
        seg = (k_seg[:, None] == q_seg[None, :] if transposed
               else q_seg[:, None] == k_seg[None, :])
        keep = seg if keep is None else keep & seg
    return keep
