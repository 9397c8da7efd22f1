"""Blocked (paged) KV cache — pool + block allocator.

Reference: ``deepspeed/inference/v2/ragged/`` [K] — ``BlockedKVCache`` /
``KVCacheManager``: KV memory is a pool of fixed-size pages shared by all
sequences; each sequence owns a list of page ids (the block table), so HBM
is committed in page units as sequences grow instead of a padded
``[B, max_len]`` rectangle up front.

TPU-first: the pool is ONE device array per K/V with the layer dim stacked
(``[L, num_blocks, block_size, kv_h, d]``).  Inside the engine's programs
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
shape is what callers index: ``pool["k"][:, block]`` is one page's planes
over all layers (``serving/kv_transfer.py``).  Page bookkeeping (free
list, tables) is plain host Python — it never enters the compiled
program, which only ever sees int32 table arrays.
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

    @property
    def max_blocks_per_seq(self) -> int:
        return -(-self.max_seq_len // self.block_size)


def init_kv_pool(model_or_adapter: Any, cache_config: KVCacheConfig
                 ) -> Dict[str, jnp.ndarray]:
    """Zeroed pool sized from the model's (layers, kv-heads, head-dim).
    Accepts either a ``ModelAdapterV2`` (preferred — normalizes families
    without ``num_kv_heads``, e.g. OPT) or a raw model config."""
    c = model_or_adapter
    if hasattr(c, "kv_heads"):  # adapter protocol
        shape = (c.num_layers, cache_config.num_blocks,
                 cache_config.block_size, c.kv_heads, c.head_dim)
        return {"k": jnp.zeros(shape, c.dtype), "v": jnp.zeros(shape, c.dtype)}
    shape = (c.num_layers, cache_config.num_blocks, cache_config.block_size,
             c.num_kv_heads, c.hd)
    return {"k": jnp.zeros(shape, c.dtype), "v": jnp.zeros(shape, c.dtype)}


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
