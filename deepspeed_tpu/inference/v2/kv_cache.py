"""Blocked (paged) KV cache: the pool, its layout and its page allocator.

Reference: ``deepspeed/inference/v2/ragged/`` [K] — ``BlockedKVCache`` /
``KVCacheManager``: KV memory is a pool of fixed-size pages shared by all
sequences; each sequence owns a list of page ids (the block table), so HBM
is committed in page units as sequences grow instead of a padded
``[B, max_len]`` rectangle up front.

TPU-first: the pool is ONE device array per K/V with the layer dim stacked,
for each KIND of attention layer the model has (``adapters.AttentionKind``:
most models have one; a model that mixes full and windowed layers has a
pool for each, with their own head counts and row widths).  **How a cached
row lies in it is this module's alone**: :class:`KVLayout`, one a kind, is
the only code that indexes a pool array.

* An array is ``[layers·planes, pages, block_size, kv_heads, width]``: a row
  wider than 128 is cut into 128-lane PLANES (:func:`lane_planes`), plane
  ``p`` of layer ``l`` is block ``p·layers + l``, so a layer's page ``n`` is
  page ``l·pages + n`` of every plane's stretch of the flat view and one
  block table serves K's planes and V alike.  Page 0 is scratch.
* A kind that recycles the pages behind its window (``ring``) has a pool of
  RINGS, one a live sequence (:meth:`KVCacheConfig.with_rings`): logical
  page ``j`` of a sequence is page ``base + j % ring_blocks``.
* A LATENT kind (``v_in_k``) has a K pool and no V pool: the one row a token
  holds its value too.

A model whose sequences carry a recurrent STATE beside their keys
(``adapters.StateKind``: a state-space layer's state and its conv's tail,
a fixed size a sequence a layer whatever its length) has, for that, a pool
indexed by BATCH SLOT and not by page: :class:`StateLayout`, the only code
that indexes it.  Each of the kind's parts is one array ``[layers, 1 +
slots, …]``; slot 0 is scratch, for rows that are no sequence's, and
request ``r`` in batch slot ``s`` holds slot ``s + 1`` of every layer from
admission on.  The state pools lie in the same dict as the KV pools, under
the kind's name, and ride the same carry.

Inside the engine's programs a pool is a CARRIED BUFFER addressed by
``(layer, page)``: it rides the per-layer ``lax.scan`` as a carry beside the
activations and is written in place.  It is NOT scanned over like the
stacked weights: a layer sliced out of a scanned stack and handed to a
custom call (the paged kernel) is copied out, and the updated layer copied
back into a fresh stack: 62% of a serving cell's device time before PR 28
(PERF.md §6; PR 27 met the same copy on the expert stack).  Page bookkeeping
(free list, tables) is plain host Python — it never enters the compiled
program, which only ever sees int32 table arrays.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Iterable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class KVCacheConfig:
    num_blocks: int = 256          # pool pages (page 0 reserved as scratch)
    block_size: int = 16           # tokens per page
    max_seq_len: int = 2048        # per-sequence logical capacity
    #: Set by :meth:`with_rings` for a model that has attention kinds whose
    #: pages are recycled behind their window (``AttentionKind.ring``); a
    #: caller leaves them 0.  ``num_blocks`` stays the pages of TOKEN
    #: capacity: the pool of the kinds that keep every key.  A recycling
    #: kind has a pool of its own of ``num_rings`` rings of ``ring_blocks``
    #: pages (and page 0), one a sequence from its admission on.
    ring_blocks: int = 0
    num_rings: int = 0
    #: Set by :meth:`with_state` for a model with a ``StateKind``: the
    #: batch slots whose recurrent state the state pools hold; a caller
    #: leaves it 0.  What a scheduler reads to know that a sequence's cache
    #: is not all in its pages (no shared prefix, no seat given up and
    #: taken again elsewhere)
    state_slots: int = 0

    @property
    def max_blocks_per_seq(self) -> int:
        return -(-self.max_seq_len // self.block_size)

    @property
    def ring_pool_blocks(self) -> int:
        """Pages of a recycling kind's pool: page 0, then the rings."""
        return 1 + self.num_rings * self.ring_blocks

    def with_rings(self, kinds: Iterable[Any], slots: int,
                   prefill_chunk: int, row_tokens: int = 1
                   ) -> "KVCacheConfig":
        """This config with a ring a batch slot where a kind recycles: the
        widest window's pages and those a prefill chunk writes before it
        attends (a decode step's one page more is among them).
        ``row_tokens``: the consecutive tokens a decode row writes in a
        step (2 where the model drafts: the newest token at ``p`` and its
        draft at ``p + 1``).  Its last token reads the window from ``p +
        row_tokens − 1`` back while the first one's still lies there, so
        the ring holds ``window + row_tokens − 1`` keys wherever in a page
        they begin, ``ceil(that / block) + 1`` pages (the paged kernel's
        own count of such a row's live pages): no more than a chunk's
        share gives unless a chunk is a single page.  A rejected draft's
        key is overwritten by the next step's first row, at the same
        place: nothing is taken back by hand."""
        windows = [k.window for k in kinds if k.ring]
        if not windows:
            return self
        bs = self.block_size
        reach = max(windows)
        return dataclasses.replace(
            self, num_rings=slots,
            ring_blocks=max(-(-reach // bs) + max(prefill_chunk // bs, 1),
                            -(-(reach + row_tokens - 1) // bs) + 1))

    def with_state(self, state_kinds: Iterable[Any], slots: int
                   ) -> "KVCacheConfig":
        """This config with a state slot a batch slot where the model has
        a ``StateKind``."""
        if not tuple(state_kinds):
            return self
        return dataclasses.replace(self, state_slots=slots)

    def state_rows(self, rows: int, held: Iterable) -> Optional[np.ndarray]:
        """``[rows]``: the state slot of each row's request (``held``:
        ``(row, the request's batch slot)`` pairs), 0 (the scratch slot,
        which is no sequence's) elsewhere; None where the model has no
        recurrent state."""
        if not self.state_slots:
            return None
        slot = np.zeros((rows,), np.int32)
        for row, seat in held:
            slot[row] = 1 + seat if seat >= 0 else 0
        return slot

    def ring_bases(self, rows: int, held: Iterable) -> Optional[np.ndarray]:
        """``[rows]``: the first page of the ring each row's request holds
        (``held``: ``(row, the request's ring)`` pairs), 0 (the scratch
        page, which is no ring's) elsewhere; None where no kind recycles."""
        if not self.ring_blocks:
            return None
        base = np.zeros((rows,), np.int32)
        for row, ring in held:
            base[row] = 1 + ring * self.ring_blocks if ring >= 0 else 0
        return base

    def pages_recycled(self, first_page, pages) -> Optional[float]:
        """Of ``pages`` logical pages a sequence begun from ``first_page``
        on (arrays over sequences), those past a ring's length: each
        overwrote a page that fell out of the window.  None where no kind
        recycles."""
        if not self.ring_blocks:
            return None
        first_page, pages = np.asarray(first_page), np.asarray(pages)
        return float(np.clip(
            first_page + pages - np.maximum(first_page, self.ring_blocks),
            0, None).sum())


def lane_planes(d: int) -> tuple:
    """``(planes, width)``: how a cached row of ``d`` numbers lies in the
    pool.  A row of at most 128 is one plane as wide as itself.  A wider
    one (192) is cut into PLANES of 128 lanes, the last padded with zeros
    (192 → 2 x 128), each plane a stretch of all the layers' blocks in the
    pool's leading dim: the compiled paged kernel fetches whole 128-lane
    rows only, and a ``[…, kv_h, 256]`` array of few KV heads has a tiled
    layout on the chip whose ``[pages, block·kv_h, 256]`` view (the
    kernel's) is a copy of the pool and not a bitcast
    (``ops/pallas/paged_attention.py``).  A row under 128 is left as it is
    (padding 64 → 128 would double such a pool, and it runs the reference
    on the chip today: PERF.md §7)."""
    return (1, d) if d <= 128 else (-(-d // 128), 128)


def _page_matrices(array):
    """A pool array ``[blocks, N, bs, kv_h, w]`` as page matrices
    ``[blocks·N, bs·kv_h, w]``: the paged kernel's own view, a bitcast,
    which a chunk's pages are scattered into and gathered from."""
    blocks, pages, _, _, w = array.shape
    return array.reshape(blocks * pages, -1, w)


def _planes_of(rows, array):
    """``rows [..., d]`` as ``array`` holds them: the planes of its width,
    zeros beyond ``d`` in the last; as they are where one plane does."""
    d, w = rows.shape[-1], array.shape[-1]
    if d <= w:
        return [rows]
    n = -(-d // w)
    rows = jnp.pad(rows, [(0, 0)] * (rows.ndim - 1) + [(0, n * w - d)])
    return [rows[..., p * w:(p + 1) * w] for p in range(n)]


def _k_and_v(pool, kk, vv, fn):
    """``fn(array, rows)`` over K and V, or K alone where the kind's V lies
    in its K rows → the pool of the results."""
    rows = {"k": kk, "v": vv}
    return {name: fn(array, rows[name]) for name, array in pool.items()}


@dataclasses.dataclass(frozen=True)
class KVLayout:
    """How ONE attention kind's cached rows lie in its pool ``{"k": …,
    "v": …}`` (module docstring), and every access to it.  Under
    tensor-parallel serving a chunk's writes and gathers run on each chip's
    KV heads (``shard``: the engine's rule, told what each argument is), so
    they read the LOCAL head count off the arrays they are handed; the
    degree is asked only where shapes are stated (:meth:`kernel_shapes`)."""
    kind: Any               # adapters.AttentionKind
    cache: KVCacheConfig
    heads: int              # the model's query heads
    dtype: Any

    @property
    def chunks_through_kernel(self) -> bool:
        """Whether a prefill chunk's rows attend through the paged kernel
        (several tokens a grid row) and gather nothing: a latent kind's.
        With every query head on the one cached row the kernel is bound by
        its products either way, and gathered the float32 scores of 128
        heads over the bucket crossed HBM three times: 15 ms of a 43 ms
        step against the kernel's 7 (PERF.md §6, PRs 40 and 46).  Derived
        from the kind; no option."""
        return self.kind.v_in_k

    @property
    def gathers_bucket(self) -> bool:
        """Whether a chunk's rows gather a page bucket of their table: a
        ring gathers its window's pages and the chunk's instead."""
        return not (self.kind.ring or self.chunks_through_kernel)

    @property
    def pages(self) -> int:
        return (self.cache.ring_pool_blocks if self.kind.ring
                else self.cache.num_blocks)

    def block(self, p: int, l):
        """Plane ``p`` of layer ``l`` in a pool array's leading dim."""
        return p * self.kind.layers + l if p else l

    def keys_read(self, lengths: np.ndarray, chunk: int,
                  chunk_starts: Iterable[int]) -> float:
        """Keys a layer attends over through the paged kernel in a call:
        its decode rows' ``lengths`` (steps x rows), at most the window;
        of chunk rows that go through the kernel too, row ``t`` of each
        live chunk its ``start + t + 1`` keys."""
        read = float(np.minimum(lengths, self.kind.window or lengths).sum())
        if self.chunks_through_kernel:
            read += float(sum(chunk * start + chunk * (chunk + 1) // 2
                              for start in chunk_starts))
        return read

    def pages_in_use(self, scheduler: Any) -> int:
        """Pages live sequences hold: of a ring, what each has reached."""
        if self.kind.ring:
            return scheduler.ring_pages_in_use()
        return self.cache.num_blocks - 1 - scheduler.allocator.num_free

    def init_pool(self) -> Dict[str, jnp.ndarray]:
        """The zeroed pool: K's array, and V's unless it lies in K's."""
        def array(d):
            planes, width = lane_planes(d)
            return jnp.zeros(
                (self.kind.layers * planes, self.pages, self.cache.block_size,
                 self.kind.kv_heads, width), self.dtype)

        pool = {"k": array(self.kind.k_dim)}
        if not self.kind.v_in_k:
            pool["v"] = array(self.kind.v_dim)
        return pool

    def write_rows(self, pool, l, pages, offsets, kk, vv):
        """A decode step's rows ``kk``/``vv [B, kv_h, d]`` written at
        ``(l, pages[r], offsets[r])``: one scatter a plane."""
        def written(array, rows):
            for p, part in enumerate(_planes_of(rows, array)):
                array = array.at[self.block(p, l), pages, offsets].set(part)
            return array

        return _k_and_v(pool, kk, vv, written)

    def _pages_written(self, array, rows, l, pages):
        bs = self.cache.block_size
        rows = rows.reshape((rows.shape[0] // bs, bs) + rows.shape[1:])
        view = _page_matrices(array)
        for p, part in enumerate(_planes_of(rows, array)):
            view = view.at[pages + self.block(p, l) * array.shape[1]].set(
                part.reshape((-1,) + view.shape[1:]))
        return view.reshape(array.shape)

    def write_pages(self, pool, l, pages, kk, vv,
                    shard: Callable = lambda fn, *roles: fn):
        """A chunk's rows ``kk``/``vv [Bp·C, kv_h, d]`` written as whole
        pages at ``(l, pages[i])``, through the page matrices."""
        write = shard(self._pages_written, ("pool", "heads", "all", "all"),
                      "pool")
        return _k_and_v(pool, kk, vv, lambda array, rows: write(
            array, rows, jnp.asarray(l, jnp.int32), pages))

    def gather_pages(self, pool, l, pages) -> tuple:
        """Layer ``l``'s pages ``[Bp, n]`` back as rows ``[Bp, n·bs, kv_h,
        d]`` of K (and V), the padding lanes dropped: one gather a plane
        out of the carried buffer, never a layer sliced out first."""
        def gathered(array, d):
            view = _page_matrices(array)
            parts = [view[pages + self.block(p, l) * array.shape[1]]
                     for p in range(array.shape[0] // self.kind.layers)]
            rows = parts[0] if len(parts) == 1 else jnp.concatenate(
                parts, axis=-1)[..., :d]
            return rows.reshape(pages.shape[0],
                                pages.shape[1] * self.cache.block_size, -1, d)

        dims = {"k": self.kind.k_dim, "v": self.kind.v_dim}
        return tuple(gathered(array, dims[name])
                     for name, array in pool.items())

    def kernel_shapes(self, max_blocks: int, tp: int = 1) -> tuple:
        """What the paged kernel's rules (``pages_per_step``,
        ``query_tokens_per_row``) are given after their leading
        argument(s): page size, KV and query heads of a TP shard, a K row
        as held, item size, table width, a V row (0: in K's), window."""
        row = lambda d: int(np.prod(lane_planes(d)))
        return (self.cache.block_size, self.kind.kv_heads // tp,
                self.heads // tp, row(self.kind.k_dim),
                jnp.dtype(self.dtype).itemsize, max_blocks,
                0 if self.kind.v_in_k else row(self.kind.v_dim),
                self.kind.window)

    def kernel_operands(self, pool, l, tables) -> tuple:
        """Layer ``l`` as ``paged_decode_attention`` takes it: ``(k, v or
        None, tables, its keyword options, the (K, V) widths
        paged_decode_impl asks)``.  The kernel fetches pages from HBM by
        page id: it gets the whole pool's flat view ``[L·N, bs, kv_h, w]``
        (two adjacent major dims merged: a bitcast), and the layer's
        offset is folded into the tables it prefetches anyway.  Plane ``p``
        of a layer's K lies a whole plane (every layer's pages) further on
        than plane ``p - 1``; V is one plane, or K's leading numbers."""
        flat = {name: a.reshape((-1,) + a.shape[2:])
                for name, a in pool.items()}
        k_planes, pages = (pool["k"].shape[0] // self.kind.layers,
                           pool["k"].shape[1])
        v_in_k = self.kind.v_dim if self.kind.v_in_k else 0
        if not v_in_k and pool["v"].shape[0] != self.kind.layers:
            raise NotImplementedError(
                f"V rows of {self.kind.v_dim}: wider than one plane")
        return (flat["k"], flat.get("v"), tables + l * pages,
                dict(window=self.kind.window, k_planes=k_planes,
                     plane_stride=self.kind.layers * pages, v_in_k=v_in_k,
                     scale=self.kind.scale),
                (flat["k"].shape[-1], v_in_k or flat["v"].shape[-1]))

    def _in_ring(self, ring_base, logical):
        """``ring_base [R]`` (a row's ring's first page; 0: it holds none)
        and logical pages ``[R, n]`` → pages: ``j % ring_blocks`` of the
        ring; page 0 for no ring or a page before the sequence's first."""
        base = ring_base[:, None]
        return jnp.where((base > 0) & (logical >= 0),
                         base + logical % self.cache.ring_blocks, 0)

    def row_tables(self, tables, rings):
        """The tables a decode row walks: ``tables [B, max_blocks]``, or
        its ring (``rings [B]``: each row's ring's first page), repeated."""
        if not self.kind.ring:
            return tables
        return self._in_ring(rings, jnp.broadcast_to(
            jnp.arange(tables.shape[1])[None, :], tables.shape))

    def chunk_pages(self, tables, page_cursor, rings, chunk: int,
                    kb: int) -> tuple:
        """Where a round's prefill chunks (``Bp`` rows of ``chunk`` tokens
        from pages ``page_cursor [Bp]`` of their sequences on) write and
        read the pool under ``tables`` / ``rings`` and the page bucket
        ``kb``: ``(their own pages [Bp·C/bs], the pages gathered [Bp, n],
        the gathered keys' positions)``: ``[n·bs]`` where every row's are
        the same, ``[Bp, n·bs]`` a row's own (negative before its
        sequence's start: to be masked); None, None where none is."""
        bs = self.cache.block_size
        if self.kind.ring:
            # the window's pages before the chunk, then the chunk's:
            # logical numbers, negative before the sequence's start
            reach = -(-self.kind.window // bs)
            logical = (page_cursor[:, None] - reach
                       + jnp.arange(reach + chunk // bs)[None, :])
            kpos = (logical[:, :, None] * bs + jnp.arange(bs)[None, None, :]
                    ).reshape(logical.shape[0], -1)
            return (self._in_ring(rings, logical[:, reach:]).reshape(-1),
                    self._in_ring(rings, logical), kpos)
        pages = jax.vmap(lambda row, cur: jax.lax.dynamic_slice(
            row, (cur,), (chunk // bs,)))(tables, page_cursor)
        if self.chunks_through_kernel:
            return pages.reshape(-1), None, None
        # every key written so far lives in the first kb pages of a table
        return pages.reshape(-1), tables[:, :kb], jnp.arange(kb * bs)


@dataclasses.dataclass(frozen=True)
class StateLayout:
    """How ONE ``StateKind``'s parts lie in its pool ``{part: [layers, 1 +
    slots, …]}`` (module docstring), and every access to it.  A call's
    decode rows ARE the batch slots in order (row ``r`` is slot ``r + 1``),
    so their state is one contiguous stretch of a layer, read and written
    where it lies; a chunk's sequence is wherever its request sits."""
    kind: Any               # adapters.StateKind
    slots: int              # batch slots (the pool has one more: scratch)

    @property
    def bytes_per_slot(self) -> int:
        """What one sequence holds in ONE layer."""
        return sum(int(np.prod(shape)) * jnp.dtype(dtype).itemsize
                   for _, shape, dtype in self.kind.parts)

    @property
    def pool_bytes(self) -> int:
        return self.kind.layers * (1 + self.slots) * self.bytes_per_slot

    def init_pool(self) -> Dict[str, jnp.ndarray]:
        return {name: jnp.zeros((self.kind.layers, 1 + self.slots) + shape,
                                dtype)
                for name, shape, dtype in self.kind.parts}

    def decode_operands(self, pool, l) -> tuple:
        """Layer ``l``'s state of every batch slot in order, as a decode
        step's hook takes it (``adapters.ModelAdapterV2.mix_decode``) →
        (``{part: [slots, …]}``, the rows' values of the parts that are
        read and written back as values; ``{part: (the pool's array of the
        part, l, the rows' first slot)}`` for the kind's ``in_place``
        parts, which the hook moves where they lie: a kernel that reads
        each sequence's state once and writes it back in place, where in
        ``jax.numpy`` a layer's 407 MB crossed HBM seven times a step at
        the serving cell's shapes)."""
        return ({name: jax.lax.dynamic_index_in_dim(
                    array, l, 0, keepdims=False)[1:]
                 for name, array in pool.items()
                 if name not in self.kind.in_place},
                {name: (pool[name], l, 1) for name in self.kind.in_place})

    def decode_written(self, pool, l, values, arrays
                       ) -> Dict[str, jnp.ndarray]:
        """The pool after a decode step: ``values`` written over their
        stretch of layer ``l`` (slots 1 …), in place, and ``arrays``, the
        ``in_place`` parts' as the hook hands them back, in their place."""
        return {name: arrays[name] if name in arrays
                else jax.lax.dynamic_update_slice(
                    array, values[name][None].astype(array.dtype),
                    (l, 1) + (0,) * (array.ndim - 2))
                for name, array in pool.items()}

    def read_slots(self, pool, l, slots, fresh) -> Dict[str, jnp.ndarray]:
        """Layer ``l``'s state at ``slots [R]`` ``{part: [R, …]}``, zeros
        where ``fresh [R]``: a sequence's first chunk starts from nothing,
        whatever the slot's last owner left there.  A slice a row: a
        gather over ``(l, slots)`` is lowered on the chip to a pass over
        the WHOLE pool (two values of half its size, 1.14 GB each at the
        serving cell's shapes)."""
        def rows(array):
            got = jnp.concatenate([jax.lax.dynamic_slice(
                array, (l, slots[r]) + (0,) * (array.ndim - 2),
                (1, 1) + array.shape[2:])[0] for r in range(slots.shape[0])])
            keep = ~fresh.reshape((-1,) + (1,) * (got.ndim - 1))
            return jnp.where(keep, got, jnp.zeros_like(got))

        return {name: rows(array) for name, array in pool.items()}

    def write_slots(self, pool, l, slots, state) -> Dict[str, jnp.ndarray]:
        """``state {part: [R, …]}`` written at ``(l, slots[r])``, in place,
        a slice a row (rows that are no sequence's land on slot 0)."""
        def written(array, rows):
            for r in range(slots.shape[0]):
                array = jax.lax.dynamic_update_slice(
                    array, rows[r][None, None].astype(array.dtype),
                    (l, slots[r]) + (0,) * (array.ndim - 2))
            return array

        return {name: written(array, state[name])
                for name, array in pool.items()}


def state_layouts(adapter: Any, cache_config: KVCacheConfig
                  ) -> Dict[str, StateLayout]:
    """The layout of each of the adapter's state kinds, by its name."""
    return {kind.name: StateLayout(kind, cache_config.state_slots)
            for kind in adapter.state_kinds}


def kv_layouts(adapter: Any, cache_config: KVCacheConfig
               ) -> Dict[str, KVLayout]:
    """The layout of each of the adapter's attention kinds, by its name."""
    return {kind.name: KVLayout(kind, cache_config, adapter.num_heads,
                                adapter.dtype) for kind in adapter.kinds}


def init_kv_pool(adapter: Any, cache_config: KVCacheConfig
                 ) -> Dict[str, Dict[str, jnp.ndarray]]:
    """Zeroed pools, ``{kind: its layout's init_pool()}``: the attention
    kinds' and, in the same dict, the state kinds'."""
    layouts = dict(kv_layouts(adapter, cache_config),
                   **state_layouts(adapter, cache_config))
    return {name: layout.init_pool() for name, layout in layouts.items()}


def _transferred(layouts: Dict[str, KVLayout], pools) -> Dict[str, Any]:
    """The one pool of a model whose pages are transferred.  One of
    several kinds (full and window layers) keeps part of a sequence's
    cache in a ring that no block table names, and is not."""
    if len(layouts) != 1:
        raise NotImplementedError(
            f"KV page transfer of a model with {len(layouts)} KV pools "
            f"({sorted(layouts)})")
    if set(pools) - set(layouts):
        raise NotImplementedError(
            f"KV page transfer of a model with recurrent state "
            f"({sorted(set(pools) - set(layouts))}): a sequence's state "
            f"lies in its batch slot, not in its pages, and is not "
            f"transferred")
    return pools[next(iter(layouts))]


def page_arrays(layouts: Dict[str, KVLayout], pools, block: int
                ) -> List[np.ndarray]:
    """Page ``block`` on the host: its K planes over all layers
    ``[layers·planes, bs, kv_h, w]``, then V's unless they lie in K's."""
    pool = _transferred(layouts, pools)
    return [np.asarray(pool[name][:, block]) for name in sorted(pool)]


def write_page_arrays(layouts: Dict[str, KVLayout], pools,
                      blocks: List[int], pages: List[List[np.ndarray]]
                      ) -> None:
    """``pages`` (each as :func:`page_arrays` gave it) written at
    ``blocks``: one batched scatter an array — a functional ``.at[].set``
    a page would copy the whole multi-GB pool each time."""
    pool = _transferred(layouts, pools)
    idx = jnp.asarray(blocks)
    for i, name in enumerate(sorted(pool)):     # stacked on a new axis 1
        pool[name] = pool[name].at[:, idx].set(
            jnp.asarray(np.stack([page[i] for page in pages], axis=1)))


class BlockAllocator:
    """Free-list page allocator.  Page 0 is reserved: inactive batch slots
    point their whole table at it, so clamped kernel lookups always resolve
    to a valid page and dead slots scribble only on scratch."""

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (page 0 is reserved)")
        self.num_blocks = num_blocks
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        #: O(1) membership for the double-free check — the free list grew
        #: past linear-scan sizes once serving workloads started churning
        #: pages through the prefix cache
        self._free_set = set(self._free)

    @property
    def num_free(self) -> int:
        return len(self._free)

    def allocate(self, n: int) -> List[int]:
        if n > len(self._free):
            raise MemoryError(f"KV pool exhausted: want {n} pages, "
                              f"{len(self._free)} free")
        out = [self._free.pop() for _ in range(n)]
        self._free_set.difference_update(out)
        return out

    def check_owned(self, b: int) -> None:
        """Raise a descriptive error unless ``b`` is a currently-allocated
        page id.  The serving plane's refcounting is built on this
        invariant — a silent bad free there would corrupt a *shared*
        prefix page that other requests are still reading."""
        if not 0 < b < self.num_blocks:
            raise ValueError(
                f"free of out-of-range page id {b!r}: valid ids are "
                f"1..{self.num_blocks - 1} (page 0 is the reserved scratch "
                f"page and is never allocated or freed)")
        if b in self._free_set:
            raise ValueError(
                f"double free of page {b}: it is already on the free list "
                f"({len(self._free)} pages free of {self.num_blocks - 1}) — "
                f"the caller freed a block table twice or freed a table it "
                f"does not own")

    def free(self, blocks: List[int]) -> None:
        for b in blocks:
            self.check_owned(b)
            self._free.append(b)
            self._free_set.add(b)
