"""Blocked (paged) KV cache — pool + block allocator.

Reference: ``deepspeed/inference/v2/ragged/`` [K] — ``BlockedKVCache`` /
``KVCacheManager``: KV memory is a pool of fixed-size pages shared by all
sequences; each sequence owns a list of page ids (the block table), so HBM
is committed in page units as sequences grow instead of a padded
``[B, max_len]`` rectangle up front.

TPU-first: the pool is ONE device array per K/V with the layer dim stacked
(``[L, num_blocks, block_size, kv_h, d]``), for each KIND of attention
layer the model has (``adapters.AttentionKind``: most models have one; a
model that mixes full and windowed layers has a pool for each, with their
own head counts and row widths, and the windowed one's pages are recycled:
``KVCacheConfig.ring_blocks``).  Inside the engine's programs
it is a CARRIED BUFFER addressed by ``(layer, page)``: it rides the
per-layer ``lax.scan`` as a carry beside the activations (the scan's
``xs`` are a layer's parameters and its index), a layer's rows or pages
are scattered into it in place at ``(l, page)``, and attention reads it
through the flat view ``[L·num_blocks, ...]`` with ``l·num_blocks`` added
to the block tables.  It is NOT scanned over like the stacked weights: a
layer sliced out of a scanned stack and handed to a custom call (the paged
kernel) is copied out, and the updated layer copied back into a fresh
stack: 62% of a serving cell's device time before PR 28 (PERF.md §6; PR
27 met the same copy on the expert stack).  Outside the programs the
shape is what callers index: ``pool[kind]["k"][:, block]`` is one page's
planes over all that kind's layers (``serving/kv_transfer.py``).  Page
bookkeeping (free list, tables) is plain host Python — it never enters the
compiled program, which only ever sees int32 table arrays.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class KVCacheConfig:
    num_blocks: int = 256          # pool pages (page 0 reserved as scratch)
    block_size: int = 16           # tokens per page
    max_seq_len: int = 2048        # per-sequence logical capacity
    #: Set by the ENGINE for a model that has attention kinds whose pages
    #: are recycled behind their window (``AttentionKind.ring``); a caller
    #: leaves them 0.  ``num_blocks`` stays the pages of TOKEN capacity:
    #: the pool of the kinds that keep every key.  A recycling kind has a
    #: pool of its own of ``num_rings`` rings of ``ring_blocks`` pages (and
    #: page 0): a sequence is given one ring at admission, its logical page
    #: ``j`` is page ``j % ring_blocks`` of it, and what falls out of the
    #: window is overwritten there.
    ring_blocks: int = 0
    num_rings: int = 0

    @property
    def max_blocks_per_seq(self) -> int:
        return -(-self.max_seq_len // self.block_size)

    @property
    def ring_pool_blocks(self) -> int:
        """Pages of a recycling kind's pool: page 0, then the rings."""
        return 1 + self.num_rings * self.ring_blocks

    def ring_base(self, ring: int) -> int:
        """First page of ring ``ring``; 0 (the scratch page, which is no
        ring's) for a row that holds none."""
        return 1 + ring * self.ring_blocks if ring >= 0 else 0


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


def init_kv_pool(adapter: Any, cache_config: KVCacheConfig
                 ) -> Dict[str, Dict[str, jnp.ndarray]]:
    """Zeroed pools, one of K and V for each of the adapter's attention
    kinds: ``{kind: {"k": [layers·planes, pages, block_size, kv_heads,
    width], "v": […]}}`` with ``(planes, width) = lane_planes(k_dim)`` (and
    of ``v_dim``): plane ``p`` of layer ``l`` is block ``p·layers + l``, so
    that a layer's page ``n`` is page ``l·pages + n`` of every plane's
    stretch and one block table serves K's planes and V alike.
    ``pages`` is ``num_blocks`` for a kind that keeps every key and
    ``ring_pool_blocks`` for one that recycles.  A kind whose V lies in
    its K rows (``v_in_k``: a latent cache) has ``{"k"}`` alone."""
    pools = {}
    for kind in adapter.kinds:
        pages = (cache_config.ring_pool_blocks if kind.ring
                 else cache_config.num_blocks)

        def plane(d):
            planes, width = lane_planes(d)
            return jnp.zeros((kind.layers * planes, pages,
                              cache_config.block_size, kind.kv_heads, width),
                             adapter.dtype)

        pools[kind.name] = {"k": plane(kind.k_dim)}
        if not kind.v_in_k:
            pools[kind.name]["v"] = plane(kind.v_dim)
    return pools


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
