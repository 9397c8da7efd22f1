"""ServingScheduler — the v2 ragged planner with prefix sharing,
refcounted pages, and preemptible decode slots.

Same planner surface as :class:`RaggedScheduler` (the engine drives it
through ``plan_step``/``chunk_done``/``decode_burst_done`` unchanged);
the deltas are exactly the serving-plane primitives:

* **Reservation** (`_reserve`): the prompt is matched against the prefix
  trie first; matched whole blocks are *acquired* (refcount++) instead
  of allocated, and the request's ``prefilled`` cursor starts past them
  — prefill recomputes nothing the pool already holds.  The reuse
  boundary is capped (a) strictly before the last prompt token (the
  final token must run so the first sampled token exists) and (b) so
  every remaining chunk start stays on a lattice where the engine's
  page-table ``dynamic_slice`` cannot clamp (see the engine's
  max_seq_len/prefill_chunk guard).
* **Release** (`_release`): refcount decrements; pages reaching zero
  that the trie still indexes enter the allocator's cached tier (LRU
  reclaimed) instead of the free list.
* **Indexing**: a request's full prompt pages are inserted into the trie
  the moment its prefill is COMMITTED (``chunk_done``) — concurrent
  requests in the same batch can already share them.  The engine commits
  a call a round after it dispatched it (``step_ahead``), so a twin
  admitted in between misses once.
* **Preemption** (`preempt`/`resume`): a RUNNING request can be bumped
  out of its decode slot; its pages stay referenced, its host state
  (generated tokens, prefill cursor) is untouched, so ``resume`` is just
  re-seating it in a free slot — decode continues from the same KV.
"""

from __future__ import annotations

from collections import deque
from typing import List, Optional

from ..inference.v2.kv_cache import KVCacheConfig
from ..inference.v2.scheduler import (RaggedScheduler, Request,
                                      RequestState)
from .prefix_cache import PrefixCache, RefcountedBlockAllocator


class ServingScheduler(RaggedScheduler):
    def __init__(self, cache_config: KVCacheConfig,
                 max_batch_slots: int = 8, prefill_chunk: int = 128,
                 prefill_batch: int = 1, prefix_sharing: bool = True,
                 max_cached_blocks: int = 0):
        self._max_cached_blocks = int(max_cached_blocks)
        super().__init__(cache_config, max_batch_slots, prefill_chunk,
                         prefill_batch)
        self.allocator: RefcountedBlockAllocator
        if prefix_sharing and cache_config.ring_blocks:
            # a shared prefix's pages hold the layers that keep every key;
            # the window layers' last keys lie in the ring of the sequence
            # that wrote them and are recycled there, so a prefix hit would
            # leave the window layers without their window
            from ..utils.logging import warn_once

            warn_once("serving/prefix_cache/window",
                      "prefix sharing is off for this model: its window "
                      "layers recycle their KV pages, and a shared prefix "
                      "could not give them their last keys back")
            prefix_sharing = False
        if prefix_sharing and cache_config.state_slots:
            # a prefix hit skips the prefill of the shared tokens, which
            # is what builds the state; no snapshot of the state at the
            # prefix's end exists to start from (ROADMAP R7).  (A model
            # that drafts sets the slots too: a page's last key of its
            # drafting layer was made with the token that FOLLOWED it in
            # the sequence that wrote it.)
            from ..utils.logging import warn_once

            warn_once("serving/prefix_cache/state",
                      "prefix sharing is off for this model: its layers "
                      "carry a recurrent state, which a shared prefix's "
                      "pages do not hold")
            prefix_sharing = False
        self.prefix = PrefixCache(self.allocator, cache_config.block_size,
                                  enabled=prefix_sharing)
        self.preemptions = 0

    def _make_allocator(self, num_blocks: int) -> RefcountedBlockAllocator:
        return RefcountedBlockAllocator(
            num_blocks, max_cached=self._max_cached_blocks)

    # -- prefix-shared reservation ----------------------------------------

    def _reuse_cap(self, prompt_len: int, matched_tokens: int) -> int:
        """Largest safe reuse boundary (tokens): block-aligned, at most
        ``matched_tokens``, strictly before the last prompt token, and
        placed so every later chunk start ``cap + k*chunk`` keeps
        ``start + chunk <= max_seq_len`` (the engine's dynamic_slice
        would silently clamp past that, retargeting KV writes onto the
        sequence's earlier pages)."""
        bs = self.cache.block_size
        cap = min(matched_tokens, ((prompt_len - 1) // bs) * bs)
        max_seq = self.cache.max_seq_len
        while cap > 0:
            last_start = cap + ((prompt_len - cap - 1) // self.chunk) \
                * self.chunk
            if last_start + self.chunk <= max_seq:
                break
            cap -= bs
        return max(cap, 0)

    def _shared_plan(self, prompt: List[int], max_new_tokens: int
                     ) -> tuple:
        """The ONE trie-match + reuse-cap + capacity accounting, shared
        by ``_reserve``, ``can_admit`` and ``adopt_reserve`` so their
        admission arithmetic can never diverge.  Read-only.  Returns
        ``(shared_blocks, fresh_needed, reused_tokens, available)`` —
        ``available`` already excludes the cached pages this very
        request would revive (fresh allocations may reclaim cached
        pages, but not the ones being re-acquired)."""
        bs = self.cache.block_size
        matched = self.prefix.match(prompt)
        reused = self._reuse_cap(len(prompt), len(matched) * bs)
        shared = matched[:reused // bs]
        need = -(-(len(prompt) + max_new_tokens) // bs)
        fresh = need - len(shared)
        cached_shared = sum(1 for b in shared
                            if self.allocator.is_cached(b))
        avail = (self.allocator.num_free
                 + self.allocator.num_cached - cached_shared)
        return shared, fresh, reused, avail

    def _reserve(self, req: Request) -> bool:
        shared, fresh, reused, avail = self._shared_plan(
            req.prompt, req.max_new_tokens)
        if fresh > avail:
            return False
        # the reservation is committing — only now is the mid-block
        # divergence a real CoW.  A page-blocked head retries _reserve
        # every plan_step; counting before the capacity check inflated
        # cow_events once per pump round.
        self.prefix.count_mid_block_divergence(req.prompt)
        self.prefix.acquire(shared)
        req.blocks = shared + self.allocator.allocate(fresh)
        req.prefilled = reused
        self.prefix.record_lookup(len(req.prompt), reused)
        return True

    def can_admit(self, prompt: List[int], max_new_tokens: int,
                  reserve_pages: int = 0,
                  ignore_slots: bool = False) -> bool:
        """Advisory capacity check for front-end admission control:
        would ``_reserve`` + a free slot succeed right now, leaving at
        least ``reserve_pages`` available afterwards?  Read-only.
        ``ignore_slots`` answers the pages-only question — the
        front-end uses it to tell slot-blocked (preemption helps) from
        page-blocked (it cannot: preempted KV stays resident)."""
        if not ignore_slots and self._free_slot() < 0:
            return False
        if self.cache.ring_blocks and not self._free_rings:
            return False
        _, fresh, _, avail = self._shared_plan(prompt, max_new_tokens)
        return fresh + max(reserve_pages, 0) <= avail

    def match_tokens(self, prompt: List[int]) -> int:
        """Prefix-affinity signal for the router: how many tokens of
        this prompt the local trie already holds (post-cap)."""
        matched = self.prefix.match(prompt)
        return self._reuse_cap(len(prompt), len(matched)
                               * self.cache.block_size)

    # -- release through refcounts ----------------------------------------

    def _release(self, req: Request) -> None:
        self.allocator.release(req.blocks, cache_fn=self.prefix.is_indexed)

    def admit_now(self, req: Request) -> bool:
        """Synchronously seat a just-added request, bypassing the FIFO
        ``waiting`` deque.  The front-end checks capacity (`can_admit`),
        preempts if needed, then calls this — deferring to the next
        ``plan_step``'s FIFO `_admit` would let a lower-class resume
        steal the very slot the preemption freed."""
        if req not in self.waiting:
            raise ValueError(f"admit_now: uid {req.uid} is not waiting")
        slot = self._free_slot()
        if slot < 0 or not self._claim(req):
            return False  # stays in waiting; _admit will retry in order
        self.waiting.remove(req)
        req.state = RequestState.PREFILL
        req.slot = slot
        self.slots[slot] = req
        self.prefilling.append(req)
        return True

    # -- class-aware SplitFuse interleave ----------------------------------

    def plan_step(self) -> tuple:
        """Prefill chunks are planned in priority order (stable within a
        class): an interactive prompt admitted behind N background
        prefills jumps the chunk lattice, which is what bounds its TTFT
        by a chunk, not by the whole background backlog."""
        if len(self.prefilling) > 1:
            self.prefilling = deque(
                sorted(self.prefilling, key=lambda r: r.priority))
        return super().plan_step()

    # -- trie indexing at prefill completion -------------------------------

    def chunk_done(self, chunk, first_token, eos_token_id=None) -> None:
        req = chunk.request
        super().chunk_done(chunk, first_token, eos_token_id)
        if chunk.is_last:
            # the full prompt's KV is now in the pool (the device call
            # returned before chunk_done runs) — index every full prompt
            # page; already-indexed chunks keep their shared page.  A
            # request finishing inside this very call (max_new=1/EOS)
            # has released its pages already — skip, nothing to index.
            if req.state is not RequestState.DONE:
                self.prefix.insert(req.prompt, req.blocks)

    # -- preemptible decode slots ------------------------------------------

    @property
    def seat_holds_state(self) -> bool:
        """Whether part of a sequence's cache lies in its batch slot and
        not in its pages (a recurrent state; a drafting engine's newest
        token, draft and length): a request that gives up its
        seat can then only start over (:meth:`preempt_release`), and its
        pages alone are not the sequence (no adoption)."""
        return bool(self.cache.state_slots)

    def _refuse_if_seat_holds_state(self, what: str) -> None:
        if self.seat_holds_state:
            raise NotImplementedError(
                f"{what} of a model with recurrent state (or one that "
                f"drafts): part of a sequence lies in its batch slot, not "
                f"in its pages, and no snapshot of it is kept (ROADMAP R7)")

    def unseat(self, req: Request) -> None:
        """:meth:`preempt` minus the SLO counters — the disaggregation
        plane's "hold the pages, free the slot" primitive: a prefill
        replica parks a just-prefilled request here while its KV pages
        stream out to a decode replica, then :meth:`cancel`\\ s it."""
        self._refuse_if_seat_holds_state("preemption that keeps the pages")
        if req.state is RequestState.PREFILL:
            self.prefilling.remove(req)
        elif req.state is not RequestState.RUNNING:
            raise ValueError(
                f"can only preempt RUNNING/PREFILL requests, uid "
                f"{req.uid} is {req.state.value}")
        self._vacate(req)
        req.state = RequestState.WAITING

    def preempt(self, req: Request) -> None:
        """Bump a RUNNING or PREFILL request out of its slot.  Pages
        stay referenced (all KV written so far is intact), generated
        tokens and the prefill cursor stay accepted; the caller
        re-queues the request and later calls :meth:`resume`, which
        continues decode — or the chunk lattice — exactly where it
        stopped."""
        self.unseat(req)
        self.preemptions += 1
        from ..telemetry import get_telemetry

        get_telemetry().inc_counter(
            "serving/preemptions",
            help="decode slots preempted for a higher latency class")

    def preempt_release(self, req: Request) -> int:
        """HBM-pressure preemption (ROADMAP 3e): bump the request AND
        release its KV pages back through the refcounts — trie-indexed
        prompt pages land in the cached-free LRU tier (immediately
        reclaimable, revivable), everything else returns to the free
        list.  The request object is RETIRED (state DONE): the caller
        re-queues its *handle* for a fresh admission, whose ``_reserve``
        re-matches the prefix trie and recomputes only what the cached
        tier no longer holds.  Returns the number of pages released."""
        if req.state is RequestState.PREFILL:
            self.prefilling.remove(req)
        elif req.state is not RequestState.RUNNING:
            raise ValueError(
                f"can only preempt RUNNING/PREFILL requests, uid "
                f"{req.uid} is {req.state.value}")
        released = len(req.blocks)
        self._give_back(req)
        self._vacate(req)
        req.state = RequestState.DONE
        self.preemptions += 1
        from ..telemetry import get_telemetry

        tel = get_telemetry()
        tel.inc_counter(
            "serving/preemptions",
            help="decode slots preempted for a higher latency class")
        tel.inc_counter(
            "serving/preempt_pages_released_total", v=released,
            help="KV pages released by HBM-pressure preemptions "
                 "(cached-free tier keeps trie-indexed prompt pages "
                 "revivable)")
        return released

    def resume(self, req: Request) -> bool:
        """Re-seat a preempted request in a free slot; decode (or the
        remaining prefill chunks) continue from the retained KV.  False
        if no slot is free."""
        if req.state is not RequestState.WAITING or not req.blocks:
            raise ValueError(
                f"resume expects a preempted request (WAITING with pages "
                f"reserved), uid {req.uid} is {req.state.value}")
        slot = self._free_slot()
        if slot < 0:
            return False
        req.slot = slot
        self.slots[slot] = req
        if req.prefilled < len(req.prompt):
            req.state = RequestState.PREFILL
            self.prefilling.append(req)
        else:
            req.state = RequestState.RUNNING
        return True

    # -- disaggregated prefill/decode adoption -----------------------------

    def prompt_pages(self, prompt_len: int) -> int:
        """Pages holding prompt KV (positions ``0..prompt_len-1``) —
        the page set a disaggregated transfer must cover.  The final
        page may be partial: decode's first write lands in it too, so
        it ships whole."""
        return -(-prompt_len // self.cache.block_size)

    def adopt_reserve(self, prompt: List[int], max_new_tokens: int
                      ) -> Optional[tuple]:
        """Decode-side phase 1 of KV-page adoption: reserve pages + a
        decode slot for a request whose prefill ran ELSEWHERE.  The
        prompt is matched against the local prefix trie first — shared
        pages already hold the right KV and are NOT re-transferred,
        which is what makes the paged prefix cache a cluster-wide tier.
        Returns ``(request, need)`` where ``need`` lists the
        prompt-page indices the transfer must fill, or ``None`` when no
        slot/pages are available (the caller re-queues).  The request
        parks WAITING in its slot (inert to the planner) until
        :meth:`adopt_commit` seats it RUNNING."""
        self.validate(prompt, max_new_tokens)
        self._refuse_if_seat_holds_state("KV adoption")
        if self.cache.ring_blocks:
            raise NotImplementedError(
                "KV adoption of a model whose window layers recycle their "
                "pages: a ring's pages are not transferred")
        slot = self._free_slot()
        if slot < 0:
            return None
        shared, fresh, reused, avail = self._shared_plan(prompt,
                                                         max_new_tokens)
        if fresh > avail:
            return None
        self.prefix.acquire(shared)
        req = Request(uid=self._uid, prompt=list(prompt),
                      max_new_tokens=int(max_new_tokens))
        self._uid += 1
        req.blocks = shared + self.allocator.allocate(fresh)
        req.prefilled = len(prompt)
        req.slot = slot
        self.slots[slot] = req
        self.prefix.record_lookup(len(prompt), reused)
        need = list(range(len(shared), self.prompt_pages(len(prompt))))
        return req, need

    def adopt_commit(self, req: Request, first_token: int,
                     eos_token_id: Optional[int] = None) -> None:
        """Phase 2: the transferred pages are in the pool — seat the
        request RUNNING with the prefill replica's sampled first token
        and index its prompt pages into the local trie (the next
        same-prefix adoption transfers nothing)."""
        if req.state is not RequestState.WAITING or req.slot < 0:
            raise ValueError(
                f"adopt_commit expects a reserved adoption (WAITING in "
                f"a slot), uid {req.uid} is {req.state.value}")
        req.state = RequestState.RUNNING
        req.generated.append(int(first_token))
        self._maybe_finish(req, int(first_token), eos_token_id)
        if req.state is not RequestState.DONE:
            self.prefix.insert(req.prompt, req.blocks)

    def adopt_abort(self, req: Request) -> None:
        """Transfer failed: give the reservation back (pages through
        refcounts, slot freed) — the caller re-routes the request."""
        if req.blocks:
            self._give_back(req)
        self._vacate(req)
        req.state = RequestState.DONE

    # -- introspection -----------------------------------------------------

    def telemetry_gauges(self) -> dict:
        # extends the base occupancy gauges, so the pool/prefix numbers
        # publish through the base scheduler's collect hook
        g = super().telemetry_gauges()
        g["serving/kv_pages_cached"] = float(self.allocator.num_cached)
        g["serving/kv_pages_free"] = float(self.allocator.num_free)
        g["serving/prefix_hit_rate"] = self.prefix.hit_rate
        return g
