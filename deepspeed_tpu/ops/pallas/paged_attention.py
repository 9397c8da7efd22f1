"""Paged decode attention — blocked-KV-cache kernel for inference v2.

Role parity: the reference FastGen ragged kernels
(``deepspeed/inference/v2/kernels/ragged_ops/`` — blocked KV cache with
linear/blocked attention over a block table [K], SURVEY §2.2 row "Inference
v2 kernels").  Sequences share one physical KV pool; a per-sequence block
table maps logical KV positions onto pool blocks, so memory is allocated in
``block_size`` pages instead of a padded ``[B, Smax]`` rectangle.

The walk.  The pool ``[num_blocks, block_size, kv_h, d]`` stays in HBM and
the kernel fetches from it itself: the grid is one step per sequence, and
inside a step a ``fori_loop`` with a dynamic trip count walks that row's
LIVE pages only — ``[k0, nk)`` with ``nk = ceil(length / block_size)`` and
``k0 = max(length − window, 0) // block_size`` — ``P`` pages a compute
step.  Page ids come from the scalar-prefetched block table; each live
page of a step is one ``make_async_copy`` of K (all its planes) and one
of V into slot ``s`` of a ``[2, P, block_size·kv_h, d]`` VMEM buffer, and
the next step's pages (the next row's first step at a row's end) are in
flight in slot ``1 − s`` while the current step is scored.  So grid steps
plus loop iterations are ``Σ_rows max(ceil(live_pages / P), 1)``, whatever
the table's width; a row's last step fetches only its live pages, and what
is left in the buffer from earlier steps is masked by position.

``P`` follows from the shapes (:func:`pages_per_step`): as many pages as
make ``_STEP_COLUMNS`` score columns (key rows: ``P·block_size·kv_h``), so
that a step's fetch and its softmax are of one size whatever the KV heads
(256 keys of 8 KV heads, 512 of 4), no fewer than ``_STEP_TOKENS`` keys, no
more than a row can have live (the table's width; under a window,
``ceil(window / block_size) + 1``: a window layer's every row is then ONE
step as wide as the window), and no more than fit ``_VMEM_BUDGET_BYTES``
(both buffers of K and V, the head mask and the float32 score
temporaries).  A step is scored over all ``P`` pages whatever is live, so
a larger ``P`` pays in a row's last step what it saves in steps: 512 keys
measured best for 4 KV heads at ~750 keys a row (PERF.md §6, PR 44).

The arithmetic.  A page is read as the matrix ``[block_size·kv_h, d]`` it
already is in memory (row ``t·kv_h + g`` is key ``t`` of kv head ``g``),
so a step holds ``C = P·block_size·kv_h`` key rows.  QKᵀ is ONE MXU dot
of all ``h`` query heads against all ``C`` rows in the cache dtype with
float32 accumulation; a constant additive mask keeps, for query head
``r``, the columns of its own kv head (``column % kv_h == r // n_rep``).
That spends ``kv_h``× the exponentials the result needs, and in exchange
K and V are never upcast, repeated per group or re-laid-out by head: the
kernel is bound by the cache it reads, not by the MXU or the VPU.  The
``1/sqrt(d)`` scale is applied to the float32 scores.  Running max, sum
and the ``[h, d]`` accumulator are float32 loop carries.  PV is a second
MXU dot with float32 accumulation whose left operand, the unnormalised
probabilities, is cast to the cache dtype first — the choice
:func:`paged_decode_reference` and the engine's prefill path make too.

K and V rows need not be equally wide (a model whose value head is
narrower than its key head): the V pool's last dim is the accumulator's
and the output's.  A K row wider than 128 lanes (192) lies in ``k_planes``
PLANES of 128 lanes, the last padded with zeros
(``kv_cache.lane_planes``): plane ``p`` of page ``n`` is page ``n +
p·plane_stride`` of the K pool, the planes fill the pool (``k_planes ·
plane_stride`` pages), and the kernel takes it as ``[planes, plane_stride,
rows, 128]``: ONE strided copy fetches all of a page's planes into
``[planes, P, rows, 128]`` of the K buffer (a copy a plane spent more of a
step starting and awaiting copies: PERF.md §6, PRs 40 and 44), and QKᵀ is
the sum over planes of a dot of the query's 128 lanes of that plane (the
query is padded with zeros to match; the scale stays that of the true
width).  A pool of one plane is taken as ``[pages, rows, 128]``.  Why
planes and not one 256-lane row: Mosaic pads an HBM operand's lanes to 128
and refuses the page-sized slice a DMA needs of anything else, and the
``[pages, block·kv_h, 256]`` view of a ``[…, kv_h = 4, 256]`` pool is, in
the chip's tiled layout, a COPY of the pool (2.7 GB a call at the serving
cell's size), where every 128-lane view is a bitcast.  The padding is read
from HBM like the keys (a quarter of a 192-wide K: what the kernel's
roofline share loses).

``v_in_k`` (a latent cache: ONE row a token that every query head reads,
whose leading ``v_in_k`` numbers are also its value): there is no V pool,
no V copy and no V buffer; PV is a dot of the probabilities against each
of the K buffer's leading planes, the results side by side in the
accumulator (``v_in_k`` whole planes where the kernel is compiled).  A
step scores ``_LATENT_STEP_TOKENS`` keys.  With
one KV head under 128 query heads each fetched byte meets ``h`` rows of
the MXU twice: at 576-wide rows the kernel sits on the chip's ridge and
not under the cache's stream.  ``scale``: the scores' scale where it is
not ``1/sqrt(d)`` of the row as cached (an absorbed query is as wide as
the latent row; the scale is that of the head it stands for).

Several query tokens a grid row (``q [R, T, h, d]``: a prefill chunk's
rows, ``T`` consecutive positions of ONE sequence through ONE table row,
``lengths[r]`` the LAST token's): the query block is the ``T·h`` rows of
the row's tokens, token-major, and so are the running max, the sum and the
accumulator; the walk is the last token's live pages (under a window, from
the first token's), a superset of every token's; and the column mask
compares against the query ROW's own length, ``lengths[r] − (T − 1) +
row // h``.  The row's tokens share each fetched page, the row's fixed work
and its half-empty last step, and a step holds half the keys
(:func:`pages_per_step`); ``T`` for a chunk is :func:`query_tokens_per_row`'s.
``T = 1`` (``q [R, h, d]``) is the same kernel to the instruction.

``sink`` (``[h]`` float32, one learned logit a query head): a column of
the softmax that takes mass and carries no value,
``p_j = exp(s_j) / (Σ exp(s_j') + exp(sink))``.  In the kernel it is where
the running max and sum START (``m = sink, l = 1``) in place of
``(−inf, 0)``; the walk, the mask and the dots are the same.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...utils.jax_compat import shard_map as _shard_map
from .select import reference_off_tpu, shape_refused


def paged_decode_reference(q, k_pool, v_pool, block_tables, lengths,
                           window=None, sink=None, k_planes=1,
                           plane_stride=0, v_in_k=0, scale=None):
    """Pure-jnp reference.  ``q [B, h, d]`` (or ``[B, T, h, d]``: ``T``
    consecutive tokens a row, ``lengths`` the last one's); K pool ``[M, bs, kv_h, w]`` in
    ``k_planes`` planes (plane ``p`` of page ``n`` at ``n +
    p·plane_stride``; ``k_planes·w >= d``: what lies beyond ``d`` is lane
    padding); V pool ``[N, bs, kv_h, dv]``; ``block_tables [B,
    max_blocks]``; ``lengths [B]``; ``window`` = sliding-window reach (only
    the last ``window`` cache entries); ``sink [h]`` = a logit a head
    beside the keys'; ``v_in_k``: V is the K row's leading ``v_in_k``
    numbers (``v_pool`` None); ``scale``: the scores', where not
    ``1/sqrt(d)``."""
    if q.ndim == 4:
        # token t of a row attends over lengths - (T - 1 - t) keys: a row a
        # token, through the row's table
        B, T = q.shape[:2]
        out = paged_decode_reference(
            q.reshape((B * T,) + q.shape[2:]), k_pool, v_pool,
            jnp.repeat(block_tables, T, axis=0),
            (lengths[:, None] - (T - 1) + jnp.arange(T)[None, :]).reshape(-1),
            window, sink, k_planes, plane_stride, v_in_k, scale)
        return out.reshape((B, T) + out.shape[1:])
    B, _, d = q.shape
    _, bs, kv_h, _ = k_pool.shape
    max_blocks = block_tables.shape[1]
    # gather each sequence's pages into a padded [B, max_blocks*bs, kv_h, d]
    k = jnp.concatenate([k_pool[block_tables + p * plane_stride]
                         for p in range(k_planes)], axis=-1)
    k = k[..., :d].reshape(B, max_blocks * bs, kv_h, d)
    v = k[..., :v_in_k] if v_in_k else \
        v_pool[block_tables].reshape(B, max_blocks * bs, kv_h, -1)
    n_rep = q.shape[1] // kv_h
    row = "bkhd"
    if kv_h == 1 and n_rep > 1:
        # every query head reads the one row: none is repeated (128 heads
        # of 576 numbers a key would be)
        k, v, row = k[:, :, 0], v[:, :, 0], "bkd"
    elif n_rep > 1:
        k = jnp.repeat(k, n_rep, axis=2)
        v = jnp.repeat(v, n_rep, axis=2)
    scale = scale or 1.0 / np.sqrt(d)
    s = jnp.einsum(f"bhd,{row}->bhk", q, k).astype(jnp.float32) * scale
    pos = jnp.arange(max_blocks * bs)[None, None, :]
    mask = pos < lengths[:, None, None]
    if window is not None:
        mask = mask & (pos >= lengths[:, None, None] - window)
    s = jnp.where(mask, s, -1e30)
    if sink is None:
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    else:
        beside = jnp.broadcast_to(sink.astype(jnp.float32)[None, :, None],
                                  s.shape[:2] + (1,))
        p = jax.nn.softmax(jnp.concatenate([s, beside], axis=-1),
                           axis=-1)[..., :-1].astype(q.dtype)
    return jnp.einsum(f"bhk,{row}->bhd", p, v)


#: score columns (key rows: ``P·block_size·kv_h``) per compute step: what
#: the fetch, the masks and the softmax of a step are sized by.  8 KV
#: heads' 256 keys, which the dense serving cell runs at 82% of its
#: roofline; with 4 KV heads and K in two planes a page is 16 KB a copy,
#: and on the v5e at that cell's shapes (PERF.md §6, PR 44) 256 / 512 /
#: 768 / 1,024 keys a step made 48.9 / 52.4 / 51.6 / 49.5% of the roofline
_STEP_COLUMNS = 2048
#: and no fewer keys than this (``P·block_size``): 16 KV heads keep 256
#: keys (4,096 columns, 89% of the roofline in its cell)
_STEP_TOKENS = 256
#: the same over a latent cache (``v_in_k``): one KV head's 256 keys are a
#: step of 256 score columns where 8 KV heads' are one of 2,048, and the
#: step's fixed work (the loop, the waits, the running max and sum, the
#: rescaling of a ``[h, 512]`` accumulator) is spread over a quarter of the
#: products.  Measured on the v5e at the serving cell's shapes (PERF.md §6,
#: PR 40): 35.9% of the kernel's roofline at 256, 43.1% at 512, 45.6% at
#: 1,024 (and 52.0% with a page's planes in one copy); a row's last step
#: is half empty on average, which at ~3,100 keys a row costs a sixth at
#: 1,024 and would cost a third at 2,048
_LATENT_STEP_TOKENS = 1024
#: what one step may hold in VMEM: two slots each of K and V pages, the
#: head mask (double-buffered by the pipeline) and three float32 ``[h, C]``
#: temporaries of the softmax (Mosaic's default scoped limit is 16 MiB)
_VMEM_BUDGET_BYTES = 8 * 1024 * 1024


def _step_pages(block_size: int, kv_h: int, h: int, d: int, itemsize: int,
                max_blocks: int, v_dim: int | None, window: int | None,
                q_tokens: int) -> tuple[int, int]:
    """(the pages a step of ``q_tokens`` tokens a row wants, the pages of
    it that ``_VMEM_BUDGET_BYTES`` holds), of :func:`pages_per_step`'s
    arguments."""
    rows = block_size * kv_h
    v_dim = d if v_dim is None else v_dim
    per_page = (2 * rows * (d + v_dim) * itemsize
                + 5 * q_tokens * h * rows * 4)
    keys = _LATENT_STEP_TOKENS if v_dim == 0 \
        else max(_STEP_TOKENS, _STEP_COLUMNS // kv_h)
    # several tokens a row: half the keys.  A step's fixed work is then
    # spread over T times the products, so the dead keys of a row's last
    # step weigh more than the steps: on the v5e, the latent cell's chunk
    # rows, 512 keys a step were the best of 128 / 256 / 512 / 1,024 at
    # every T from 2 to 16 (PERF.md §6, PR 46)
    keys //= min(q_tokens, 2)
    # a row under a window has live pages [k0, nk): the window's (from the
    # row's first token's to its last's) and the one it begins in the
    # middle of
    live = max_blocks if window is None else min(
        max_blocks, -(-(window + q_tokens - 1) // block_size) + 1)
    # the float32 accumulator (a latent row's value is no wider than it)
    budget = _VMEM_BUDGET_BYTES - q_tokens * h * (v_dim or d) * 4
    return max(1, min(keys // block_size, live)), budget // per_page


def pages_per_step(block_size: int, kv_h: int, h: int, d: int, itemsize: int,
                   max_blocks: int, v_dim: int | None = None,
                   window: int | None = None, q_tokens: int = 1) -> int:
    """``P``: pages fetched and scored per compute step, from the shapes
    alone (``d``: a K row as the pool holds it, all its planes; ``v_dim``:
    a V row, where it is another width; 0: V lies in the K row and has no
    buffer, a latent cache; ``window``: the layer's reach in keys;
    ``q_tokens``: the tokens a grid row holds, ``T``: the step's score
    temporaries and its accumulator are ``T·h`` rows)."""
    want, fit = _step_pages(block_size, kv_h, h, d, itemsize, max_blocks,
                            v_dim, window, q_tokens)
    return max(1, min(want, fit))


def query_tokens_per_row(chunk: int, block_size: int, kv_h: int, h: int,
                         d: int, itemsize: int, max_blocks: int,
                         v_dim: int | None = None,
                         window: int | None = None) -> int:
    """``T``: how many of a prefill chunk's ``chunk`` consecutive tokens
    share a grid row of the kernel (``q [R, T, h, d]``), from the shapes
    alone (:func:`pages_per_step`'s): the largest divisor of ``chunk``
    whose step (the ``[T·h, dv]`` float32 accumulator, the score
    temporaries of ``T·h`` rows and two slots of pages) fits
    ``_VMEM_BUDGET_BYTES`` at the pages it wants.  A row's tokens share
    each fetched page, the row's fixed work and its half-empty last step;
    4 at 128 heads over a 512-wide latent row, where 8 and 16 (with
    Mosaic's scoped limit raised for them) were within 1.5% (PERF.md §6,
    PR 46)."""
    for tokens in range(chunk, 1, -1):
        if chunk % tokens == 0:
            want, fit = _step_pages(block_size, kv_h, h, d, itemsize,
                                    max_blocks, v_dim, window, tokens)
            if fit >= want:
                return tokens
    return 1


def _paged_kernel(len_ref, table_ref, q_ref, head_mask_ref, *rest,
                  block_size: int, kv_h: int, scale: float, window=None,
                  sink: bool = False, k_planes: int = 1, v_in_k: int = 0,
                  q_tokens: int = 1):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # with a sink, its [h, 1] logits come before the pools
    sink_ref = rest[0] if sink else None
    if v_in_k:      # V lies in the K rows: no V pool, no V buffer
        k_hbm, o_ref, k_buf, sems, slot_ref = rest[int(sink):]
        v_hbm = v_buf = None
    else:
        k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, slot_ref = rest[int(sink):]
    b = pl.program_id(0)
    num_rows = pl.num_programs(0)
    # K in planes: the buffer is [2, planes, P, rows, w] and a page's
    # planes arrive in one copy; one plane: [2, P, rows, w]
    P = k_buf.shape[-3]
    # the query rows of a grid row: its T tokens' heads, token-major
    h = q_ref.shape[1]
    T = q_tokens
    C = P * block_size * kv_h

    def live_pages(row):
        """(first live page, number of live pages) of ``row``: from the
        first token's window to the last token's length."""
        length = len_ref[row]
        nk = jax.lax.div(length + block_size - 1, block_size)
        if window is None:
            return 0, nk
        k0 = jax.lax.div(jnp.maximum(length - (window + T - 1), 0),
                         block_size)
        return k0, nk - k0

    def page_copies(page, slot, j):
        if k_planes > 1:
            # the K pool comes as [planes, pages, rows, w]: ONE strided
            # copy fetches every plane of the page (a copy a plane spent
            # more of a step starting and awaiting copies: PERF.md §6,
            # PRs 40 and 44)
            k_copy = pltpu.make_async_copy(
                k_hbm.at[:, page], k_buf.at[slot, :, j], sems.at[0, slot])
        else:
            k_copy = pltpu.make_async_copy(
                k_hbm.at[page], k_buf.at[slot, j], sems.at[0, slot])
        if v_in_k:
            return (k_copy,)
        return (k_copy, pltpu.make_async_copy(
            v_hbm.at[page], v_buf.at[slot, j], sems.at[1, slot]))

    def step_pages(n_live, i):
        return jnp.clip(n_live - i * P, 0, P)

    def start_step(row, k0, n_live, i, slot):
        def start(j, carry):
            for copy in page_copies(table_ref[row, k0 + i * P + j], slot, j):
                copy.start()
            return carry

        jax.lax.fori_loop(0, step_pages(n_live, i), start, 0)

    def wait_step(n_live, i, slot):
        def wait(j, carry):
            # every page copy moves the same bytes, so any page's
            # descriptor waits for one of them
            for copy in page_copies(0, slot, j):
                copy.wait()
            return carry

        jax.lax.fori_loop(0, step_pages(n_live, i), wait, 0)

    k0, n_live = live_pages(b)
    length = len_ref[b]
    # a row of length 0 still takes one (empty) step, so that every row's
    # last step can start the next row's first
    steps = jnp.maximum(jax.lax.div(n_live + P - 1, P), 1)

    @pl.when(b == 0)
    def _first():
        # masked columns carry probability 0 into the PV dot, and 0 x what
        # an unwritten VMEM buffer holds may be NaN: after this, a slot only
        # ever holds zeros or pages some row owns
        values = k_buf if v_in_k else v_buf
        values[...] = jnp.zeros_like(values)
        slot_ref[0] = 0
        start_step(0, k0, n_live, 0, 0)

    slot0 = slot_ref[0]
    q = q_ref[0]                                   # [h, d], cache dtype
    column = jax.lax.broadcasted_iota(jnp.int32, (h, C), 1)
    # the keys a query row attends over: the grid row's, or with T tokens
    # a row ``[h, 1]``: row r is token r // heads, and the last has them all
    own = length if T == 1 else length - (T - 1) + jax.lax.div(
        jax.lax.broadcasted_iota(jnp.int32, (h, 1), 0), h // T)

    def step(i, carry):
        m_prev, l_prev, acc = carry
        slot = jax.lax.rem(slot0 + i, 2)
        last = i == steps - 1
        nxt_row = jnp.where(last, b + 1, b)

        @pl.when(nxt_row < num_rows)
        def _prefetch():
            nk0, nn = live_pages(nxt_row)
            start_step(nxt_row, nk0, nn, jnp.where(last, 0, i + 1), 1 - slot)

        wait_step(n_live, i, slot)
        w = k_buf.shape[-1]

        def k_plane(p):
            """Plane ``p`` of the step's pages as ``[C, w]``."""
            kp = k_buf[slot] if k_planes == 1 else k_buf[slot, p]
            return kp.reshape(C, w)

        def plane_scores(p):
            """q's lanes of plane ``p`` against that plane's pages."""
            qp = q if k_planes == 1 else q[:, p * w:(p + 1) * w]
            return jax.lax.dot_general(
                qp, k_plane(p), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

        s = plane_scores(0)
        for p in range(1, k_planes):
            s = s + plane_scores(p)
        s = s * scale + head_mask_ref[...]
        # column c of this step is position first + c // kv_h
        first = (k0 + i * P) * block_size
        keep = column < (own - first) * kv_h
        if window is not None:  # sliding window: only the cache tail
            keep = keep & (column >= (own - window - first) * kv_h)
        s = jnp.where(keep, s, -1e30)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        if v_in_k:
            # the row's leading numbers: whole planes, and the leading
            # lanes of one more where the interpreter runs narrow rows
            whole, part = divmod(v_in_k, w)
            values = [k_plane(p) for p in range(whole + bool(part))]
            if part:
                values[-1] = values[-1][:, :part]
        else:
            values = [v_buf[slot].reshape(C, v_buf.shape[-1])]
        pv = [jnp.dot(p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32) for v in values]
        pv = pv[0] if len(pv) == 1 else jnp.concatenate(pv, axis=1)
        return (m_new, l_prev * alpha + jnp.sum(p, axis=1, keepdims=True),
                acc * alpha + pv)

    if sink:    # the sink's column is where the running max and sum start
        start = (sink_ref[...], jnp.ones((h, 1), jnp.float32))
    else:
        start = (jnp.full((h, 1), -jnp.inf, jnp.float32),
                 jnp.zeros((h, 1), jnp.float32))
    _, l, acc = jax.lax.fori_loop(
        0, steps, step,
        start + (jnp.zeros((h, o_ref.shape[2]), jnp.float32),))
    slot_ref[0] = jax.lax.rem(slot0 + steps, 2)
    # a row of length 0 ran one step with every column masked
    o_ref[0] = jnp.where(own > 0, acc / l, 0.0).astype(o_ref.dtype)


def _refusal(h: int, kv_h: int, k_dim: int, v_dim: int,
             compiled: bool) -> str | None:
    """Why the kernel cannot take these shapes, or None.  ``k_dim`` and
    ``v_dim`` are the POOLS' last dims (a plane's width); where V lies in
    the K rows, ``v_dim`` is how many of a row's leading numbers it is."""
    if h % kv_h:
        return f"kv heads {kv_h} do not divide query heads {h}"
    if compiled and (k_dim % 128 or v_dim % 128):
        # Mosaic (jax 0.9.0) pads an HBM operand's lanes to 128 and then
        # refuses the page-sized slice of it a DMA needs
        return (f"head size {k_dim}/{v_dim} is not a multiple of 128 "
                f"lanes")
    return None


def paged_decode_impl(num_heads: int, kv_heads: int,
                      interpret: bool | None = None, k_dim: int = 128,
                      v_dim: int = 128) -> str:
    """Which path :func:`paged_decode_attention` takes for these shapes
    (``k_dim``, ``v_dim``: the pools' last dims): ``"pallas"``,
    ``"pallas_interpret"`` or ``"reference"`` — the serving engine records
    it (``last_attn_path``) from the same test the entry point decides by,
    so a head size the compiled kernel cannot take reads ``"reference"``
    there too, on a TPU as anywhere."""
    if reference_off_tpu(interpret) or _refusal(
            num_heads, kv_heads, k_dim, v_dim, compiled=not interpret):
        return "reference"
    return "pallas_interpret" if interpret else "pallas"


def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths,
                           interpret: bool | None = None, window=None,
                           sink=None, k_planes: int = 1,
                           plane_stride: int = 0, v_in_k: int = 0,
                           scale: float | None = None):
    """Queries ``q [B, h, d]``, a token a row (or ``[B, T, h, d]``: ``T``
    consecutive tokens of one sequence a row, ``lengths`` the LAST one's,
    token ``t`` attending over ``lengths − (T − 1 − t)`` keys → ``[B, T, h,
    dv]``) over a shared paged KV pool
    ``k [M, block_size, kv_h, w]``, ``v [N, block_size, kv_h, dv]``
    addressed by ``block_tables [B, max_blocks]`` with true ``lengths
    [B]`` → ``[B, h, dv]``.  A K row of ``d > 128`` lies in ``k_planes``
    planes of ``w = 128`` lanes, plane ``p`` of page ``n`` at page ``n +
    p·plane_stride`` of ``k`` (zeros beyond ``d``).  ``window``
    (sliding-window attention) is handled by the kernel's walk: it starts
    at the window's first page, so pages before the window are neither
    fetched nor scored.  ``sink [h]``: a logit a query head in the
    softmax's denominator.  A row of length 0 gives zeros.  ``v_in_k``:
    V is the leading ``v_in_k`` numbers of the K row and ``v_pool`` is
    None (a latent cache) → ``[B, h, v_in_k]``.  ``scale``: the scores',
    where it is not ``1/sqrt(d)``.  The pool is
    passed as it lies in memory: the ``[M, block_size·kv_h, w]`` view the
    kernel reads merges two adjacent dims and moves nothing."""
    h = q.shape[-2]
    kv_h, w = k_pool.shape[2], k_pool.shape[3]
    dv = v_in_k or v_pool.shape[-1]
    impl = paged_decode_impl(h, kv_h, interpret, w, dv)
    if impl == "reference":
        refusal = _refusal(h, kv_h, w, dv, compiled=not interpret)
        if refusal:
            shape_refused("paged_decode_attention",
                          (tuple(q.shape), tuple(k_pool.shape),
                           tuple(v_pool.shape) if v_pool is not None
                           else ("v_in_k", v_in_k)), refusal)
        return paged_decode_reference(q, k_pool, v_pool, block_tables,
                                      lengths, window, sink, k_planes,
                                      plane_stride, v_in_k, scale)
    return _paged_kernel_call(q, k_pool, v_pool, block_tables, lengths, sink,
                              interpret=bool(interpret), window=window,
                              k_planes=k_planes, plane_stride=plane_stride,
                              v_in_k=v_in_k, scale=scale)


@functools.partial(jax.jit, static_argnames=("interpret", "window",
                                             "k_planes", "plane_stride",
                                             "v_in_k", "scale"))
def _paged_kernel_call(q, k_pool, v_pool, block_tables, lengths, sink, *,
                       interpret: bool, window, k_planes: int,
                       plane_stride: int, v_in_k: int = 0,
                       scale: float | None = None):
    """The Mosaic call of :func:`paged_decode_attention`, once the path is
    decided.  A jitted function of its own so that a program which holds
    the kernel many times (a layer kind's unrolled layers) traces and
    lowers it once, and every further program of the process (a serving
    engine compiles one a page bucket) finds the kernel's trace cached:
    a kernel's body is ~70 ms of Python to trace and lower, and set-up
    pays it for every instance (PERF.md §6, PR 37)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # T tokens a row: their heads are the grid row's query rows, token-major
    T = q.shape[1] if q.ndim == 4 else 1
    B, d = q.shape[0], q.shape[-1]
    heads = q.shape[-2]
    h = T * heads
    q = q.reshape(B, h, d)
    M, block_size, kv_h, w = k_pool.shape
    dv = v_in_k or v_pool.shape[-1]
    if k_planes > 1 and k_planes * plane_stride != M:
        raise ValueError(f"a K pool of {M} pages is not {k_planes} "
                         f"planes of {plane_stride}")
    max_blocks = block_tables.shape[1]
    dk = k_planes * w
    if dk > d:      # the last plane's lane padding: zeros times zeros
        q = jnp.pad(q, ((0, 0), (0, 0), (0, dk - d)))
    P = pages_per_step(block_size, kv_h, heads, dk, k_pool.dtype.itemsize,
                       max_blocks, 0 if v_in_k else dv, window, T)
    rows = block_size * kv_h
    # K in planes: the pool as [planes, pages, rows, w], so that one
    # strided copy fetches a page's planes into [planes, P, rows, w]
    planes = (k_planes,) if k_planes > 1 else ()
    # query head r reads the columns of kv head r // n_rep
    head_mask = np.where(
        np.arange(P * rows)[None, :] % kv_h
        == np.arange(h)[:, None] % heads // (heads // kv_h), 0.0, -1e30
    ).astype(np.float32)
    kernel = functools.partial(_paged_kernel, block_size=block_size,
                               kv_h=kv_h, scale=scale or 1.0 / np.sqrt(d),
                               window=window, sink=sink is not None,
                               k_planes=k_planes, v_in_k=v_in_k, q_tokens=T)
    row = lambda b, lens, table: (b, 0, 0)
    whole = lambda b, lens, table: (0, 0)
    sink_spec, sink_arg = [], []
    if sink is not None:
        sink_spec = [pl.BlockSpec((h, 1), whole)]
        sink = sink.astype(jnp.float32)
        sink_arg = [(sink if T == 1 else jnp.tile(sink, T)).reshape(h, 1)]
    # a V pool of its own, with its buffer; none where V lies in K's rows
    v_spec, v_scratch, v_arg = [], [], []
    if not v_in_k:
        v_spec = [pl.BlockSpec(memory_space=pl.ANY)]
        v_scratch = [pltpu.VMEM((2, P, rows, dv), v_pool.dtype)]
        v_arg = [v_pool.reshape(v_pool.shape[0], rows, dv)]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, h, dk), row),
                pl.BlockSpec((h, P * rows), whole),
                *sink_spec,
                pl.BlockSpec(memory_space=pl.ANY),
                *v_spec,
            ],
            out_specs=pl.BlockSpec((1, h, dv), row),
            scratch_shapes=[
                pltpu.VMEM((2, *planes, P, rows, w), k_pool.dtype),
                *v_scratch,
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, h, dv), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(lengths.astype(jnp.int32), block_tables.astype(jnp.int32),
      q, jnp.asarray(head_mask), *sink_arg,
      k_pool.reshape(*planes, M // k_planes, rows, w), *v_arg)
    return out if T == 1 else out.reshape(B, T, heads, dv)


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
