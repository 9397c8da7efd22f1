"""Ragged request scheduler — continuous batching + chunked prefill.

Reference: ``deepspeed/inference/v2/ragged/ragged_manager.py`` +
``scheduling_utils`` [K] and the Dynamic SplitFuse policy (FastGen,
arXiv 2401.08671 [P]): long prompts are split into fixed-size chunks and
prefill work is interleaved with running decodes so every forward pass
carries a near-constant token count — which on TPU is exactly what keeps
ONE compiled program shape serving an arbitrary request mix.

Host-side only: states, block tables and the free list live in Python;
the device sees fixed-shape int32 arrays each step.
"""

from __future__ import annotations

import dataclasses
import enum
from collections import deque
from typing import Deque, List, Optional

import numpy as np

from ...telemetry import get_telemetry
from .kv_cache import BlockAllocator, KVCacheConfig


class RequestState(enum.Enum):
    WAITING = "waiting"
    PREFILL = "prefill"
    RUNNING = "running"
    DONE = "done"


@dataclasses.dataclass
class Request:
    uid: int
    prompt: List[int]
    max_new_tokens: int
    state: RequestState = RequestState.WAITING
    generated: List[int] = dataclasses.field(default_factory=list)
    blocks: List[int] = dataclasses.field(default_factory=list)
    prefilled: int = 0          # prompt tokens already written to the pool
    slot: int = -1              # decode batch slot while RUNNING
    #: its ring in the recycled pools (``KVCacheConfig.ring_blocks``), held
    #: from admission to release, through preemption too; -1: none
    ring: int = -1
    #: prefill-lattice priority (lower = sooner) — the serving plane maps
    #: latency classes here so an interactive prompt's chunks are not
    #: stuck behind a batch of background prefills; plain engine use
    #: leaves everything at 0 (pure FIFO)
    priority: int = 0
    #: (``blocks`` as they were, their padded table row):
    #: ``RaggedScheduler.table_row``'s
    table: Optional[tuple] = dataclasses.field(default=None, repr=False,
                                               compare=False)
    #: PLANNED beside committed (``RaggedScheduler.dispatched``): the prompt
    #: tokens and the generated tokens of calls that were dispatched for
    #: this request and are not committed yet.  ``prefilled`` and
    #: ``generated`` hold only what was fetched; the planner reads the sums
    #: below.  Both are 0 again when the request leaves its slot.
    ahead_prefilled: int = 0
    ahead_tokens: int = 0

    @property
    def length(self) -> int:
        return self.prefilled + len(self.generated)

    @property
    def planned_prefilled(self) -> int:
        """The prefill cursor once every dispatched call is committed."""
        return self.prefilled + self.ahead_prefilled

    @property
    def planned_tokens(self) -> int:
        """The tokens generated once every dispatched call is committed,
        unless one of them turns out to be the EOS; under a drafting
        engine AT LEAST these (a step yields one token or two, and a token
        a step is what is planned: a request's last call may then run past
        its budget, and its surplus is discarded as a burst's always
        was)."""
        return len(self.generated) + self.ahead_tokens

    @property
    def remaining_budget(self) -> int:
        """Generation tokens this request may still emit."""
        return max(self.max_new_tokens - len(self.generated), 0)

    def pages_needed(self, block_size: int) -> int:
        total = len(self.prompt) + self.max_new_tokens
        return -(-total // block_size)


@dataclasses.dataclass
class PrefillChunk:
    request: Request
    tokens: np.ndarray          # [chunk] int32, zero-padded
    start_pos: int              # first position this chunk covers
    n_valid: int                # true tokens in this chunk
    is_last: bool               # finishing chunk → sample first token


class RaggedScheduler:
    """Admission + step planning over a fixed decode-slot budget.

    Each :meth:`plan_step` returns at most one :class:`PrefillChunk` (the
    SplitFuse interleave unit) plus the current decode batch composition;
    the engine runs the corresponding compiled programs.
    """

    def __init__(self, cache_config: KVCacheConfig, max_batch_slots: int = 8,
                 prefill_chunk: int = 128, prefill_batch: int = 1):
        if prefill_chunk % cache_config.block_size:
            raise ValueError("prefill_chunk must be a multiple of block_size")
        self.cache = cache_config
        self.allocator = self._make_allocator(cache_config.num_blocks)
        self.chunk = prefill_chunk
        self.prefill_batch = max(1, prefill_batch)
        self.max_slots = max_batch_slots
        self.slots: List[Optional[Request]] = [None] * max_batch_slots
        self.waiting: Deque[Request] = deque()
        self.prefilling: Deque[Request] = deque()
        self._uid = 0
        #: rings of the recycled pools not held by a sequence (empty where
        #: the cache has none)
        self._free_rings: List[int] = list(
            range(cache_config.num_rings - 1, -1, -1)
            if cache_config.ring_blocks else ())
        # the occupancy gauges are worked out when the registry is read,
        # not in every plan; the hub holds the hook weakly
        get_telemetry().add_collect_hook(self._publish_gauges)

    def _make_allocator(self, num_blocks: int) -> BlockAllocator:
        """Subclass hook: the serving scheduler swaps in its refcounted
        allocator without constructing a discarded base one."""
        return BlockAllocator(num_blocks)

    # -- request surface ---------------------------------------------------

    def validate(self, prompt: List[int], max_new_tokens: int) -> None:
        """Reject malformed requests with an error naming the offending
        field.  The serving front-end forwards user input directly into
        this scheduler, so every invariant the planner relies on (a
        non-empty prompt, a positive generation budget, a pool that can
        ever hold the request) must be checked HERE, not discovered as a
        has_work spin or a zero-length chunk later."""
        if not prompt:
            raise ValueError("prompt: must be a non-empty token list")
        if max_new_tokens <= 0:
            raise ValueError(
                f"max_new_tokens: must be >= 1, got {max_new_tokens} "
                f"(a request that may generate nothing would occupy a "
                f"decode slot forever)")
        total = len(prompt) + max_new_tokens
        if total > self.cache.max_seq_len:
            raise ValueError(f"request of {total} tokens exceeds "
                             f"max_seq_len {self.cache.max_seq_len}")
        need = -(-total // self.cache.block_size)
        if need > self.cache.num_blocks - 1:  # page 0 reserved
            # reject now: _admit could never place it and generate() would
            # spin on has_work forever
            raise ValueError(
                f"request needs {need} pages but the pool only has "
                f"{self.cache.num_blocks - 1}")

    def add_request(self, prompt: List[int], max_new_tokens: int) -> Request:
        self.validate(prompt, max_new_tokens)
        req = Request(uid=self._uid, prompt=list(prompt),
                      max_new_tokens=max_new_tokens)
        self._uid += 1
        self.waiting.append(req)
        get_telemetry().inc_counter("inference/requests",
                                    help="requests admitted to the queue")
        return req

    @property
    def has_work(self) -> bool:
        return (bool(self.waiting) or bool(self.prefilling)
                or any(s is not None for s in self.slots))

    # -- planning ------------------------------------------------------------

    def _free_slot(self) -> int:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return -1

    def _reserve(self, req: Request) -> bool:
        """Reserve the request's full page budget; ``False`` defers
        admission.  Subclass hook: the serving scheduler overrides this
        to satisfy part of the reservation from shared prefix pages."""
        need = req.pages_needed(self.cache.block_size)
        if need > self.allocator.num_free:
            return False
        req.blocks = self.allocator.allocate(need)
        return True

    def _release(self, req: Request) -> None:
        """Return a finished/cancelled request's pages.  Subclass hook:
        the serving scheduler routes this through refcounts so shared
        prefix pages survive until their last holder lets go."""
        self.allocator.free(req.blocks)

    def _claim(self, req: Request) -> bool:
        """A request's whole reservation: its pages (``_reserve``) and,
        where the cache recycles window pages, a ring to recycle them in.
        ``False`` defers admission and takes nothing."""
        rings = bool(self.cache.ring_blocks)
        if (rings and not self._free_rings) or not self._reserve(req):
            return False
        if rings:
            req.ring = self._free_rings.pop()
        return True

    def _give_back(self, req: Request) -> None:
        """Undo :meth:`_claim`: pages through ``_release``, the ring to
        the free rings.  A call still in flight may hold a row of ``req``
        and write its pages (``max_pos`` keeps it inside them), and they
        may be handed out at once all the same: the call that writes the
        next owner's keys is dispatched later, the device runs calls in
        the order of dispatch, and nobody reads a key before writing it."""
        self._release(req)
        req.blocks = []
        if req.ring >= 0:
            self._free_rings.append(req.ring)
            req.ring = -1

    def _vacate(self, req: Request) -> None:
        """``req`` leaves its slot, and what was planned for it beyond
        what is committed is void: the calls in flight pass its rows and
        chunks over (the engine's ``_settle``), and if it resumes it is
        planned from what was fetched."""
        if req.slot >= 0:
            self.slots[req.slot] = None
            req.slot = -1
        req.ahead_prefilled = req.ahead_tokens = 0

    def _admit(self) -> None:
        """Move waiting → prefilling while a slot + enough pages exist.
        Pages for the FULL request (prompt + generation budget) are reserved
        at admission so a running sequence can never die of pool OOM
        mid-flight (the reference's conservative scheduling mode)."""
        while self.waiting:
            req = self.waiting[0]
            slot = self._free_slot()
            if slot < 0:
                return
            if not self._claim(req):
                return
            self.waiting.popleft()
            req.state = RequestState.PREFILL
            req.slot = slot
            self.slots[slot] = req
            self.prefilling.append(req)

    def telemetry_gauges(self) -> dict:
        """Scheduler occupancy numbers, published when the registry is
        read (:meth:`_publish_gauges`): queue depth, decode-slot
        occupancy, and KV-pool utilization (the pool is the 'cache' —
        utilization is pages committed to live sequences over the
        allocatable pool)."""
        occupied = sum(1 for s in self.slots if s is not None)
        allocatable = self.cache.num_blocks - 1  # page 0 reserved
        return {
            "inference/queue_depth": float(len(self.waiting)),
            "inference/prefilling": float(len(self.prefilling)),
            "inference/batch_occupancy": occupied / max(self.max_slots, 1),
            "inference/kv_pool_utilization":
                (allocatable - self.allocator.num_free) / max(allocatable, 1),
        }

    def _publish_gauges(self) -> None:
        """The registry's collect hook: it runs on the reader's thread,
        beside a round, and takes no lock; what it reads (queue lengths,
        the slots, the free list's length) is safe to read there."""
        tel = get_telemetry()
        if tel.enabled:
            for name, v in self.telemetry_gauges().items():
                tel.set_gauge(name, v)

    def plan_step(self) -> tuple:
        """→ (list[PrefillChunk] (≤ ``prefill_batch``, one chunk per
        distinct prefilling request), decode_requests) for the next call,
        planned from what has been DISPATCHED (:meth:`dispatched`), not
        from what has been committed: a request whose last chunk is in
        flight decodes, one whose budget ends in a call in flight does
        not.  Everything read here is settled once a call is dispatched,
        except an EOS: a row planned for a request that meanwhile ended
        is passed over when its call is committed."""
        self._admit()
        chunks: List[PrefillChunk] = []
        for req in self.prefilling:
            if len(chunks) == self.prefill_batch:
                break
            start = req.planned_prefilled
            if start >= len(req.prompt):
                continue            # its last chunk is in flight
            n_valid = min(self.chunk, len(req.prompt) - start)
            toks = np.zeros((self.chunk,), np.int32)
            toks[:n_valid] = req.prompt[start:start + n_valid]
            is_last = start + n_valid >= len(req.prompt)
            chunks.append(PrefillChunk(request=req, tokens=toks,
                                       start_pos=start, n_valid=n_valid,
                                       is_last=is_last))
        decode = [r for r in self.slots
                  if r is not None and self._decodes_next(r)]
        return chunks, decode

    @staticmethod
    def _decodes_next(req: Request) -> bool:
        """Whether ``req`` has a decode row in the next call: its prompt
        is in (or will be, once the calls in flight are committed) and its
        budget does not end in them."""
        if req.state is RequestState.PREFILL:
            if req.planned_prefilled < len(req.prompt):
                return False
        elif req.state is not RequestState.RUNNING:
            return False
        return req.planned_tokens < req.max_new_tokens

    def dispatched(self, chunks: List[PrefillChunk],
                   decode: List[Request], steps: int) -> None:
        """A call with this plan's ``chunks`` and ``steps`` decode steps
        for ``decode`` went out: advance what is PLANNED for each request
        (the one place that does), so that the next :meth:`plan_step`
        continues behind it before it is committed.  A driver that commits
        every plan before it makes the next (the synthetic engine) never
        calls this: what is planned is then what is committed, and the
        commits below leave it so."""
        for ch in chunks:
            ch.request.ahead_prefilled += ch.n_valid
            ch.request.ahead_tokens += ch.is_last
        for req in decode:
            req.ahead_tokens += min(steps,
                                    req.max_new_tokens - req.planned_tokens)

    # -- state transitions (called by the engine) ----------------------------

    def chunk_done(self, chunk: PrefillChunk, first_token: Optional[int],
                   eos_token_id: Optional[int] = None) -> None:
        req = chunk.request
        req.prefilled += chunk.n_valid
        req.ahead_prefilled = max(req.ahead_prefilled - chunk.n_valid, 0)
        if chunk.is_last:
            assert req.prefilled == len(req.prompt)
            self.prefilling.remove(req)
            req.state = RequestState.RUNNING
            if first_token is not None:
                req.generated.append(int(first_token))
                req.ahead_tokens = max(req.ahead_tokens - 1, 0)
                self._maybe_finish(req, int(first_token), eos_token_id)

    def decode_burst_done(self, requests: List[Request], tokens: np.ndarray,
                          eos_token_id: Optional[int] = None) -> int:
        """Accept an in-graph burst's ``[n_steps, B]`` token matrix: each
        request takes its slot's column until it finishes (EOS/budget);
        surplus tokens a done slot generated inside the burst are
        discarded.  A drafting engine's is ``[n_steps, B, 2]``, a step's
        one or two tokens side by side and −1 where it gave no second: a
        request's column is then what its steps emitted, in order, and a
        budget may end between a step's two tokens.  What was PLANNED for
        the call (:meth:`dispatched`) is a token a step, the least it
        yields.  Returns the number of accepted tokens."""
        accepted = 0
        tokens = np.asarray(tokens)
        steps = tokens.shape[0]
        if tokens.ndim == 3:
            columns = [[t for t in col if t >= 0] for col in
                       tokens.transpose(1, 0, 2).reshape(
                           tokens.shape[1], -1).tolist()]
        else:
            columns = tokens.T.tolist()             # [B][n_steps] ints
        for req in requests:
            if req.state is not RequestState.RUNNING:
                continue
            col = columns[req.slot][:max(req.remaining_budget, 1)]
            req.ahead_tokens = max(req.ahead_tokens - min(len(col), steps),
                                   0)
            if eos_token_id is not None and eos_token_id in col:
                col = col[:col.index(eos_token_id) + 1]
            req.generated.extend(col)
            accepted += len(col)
            self._maybe_finish(req, col[-1], eos_token_id)
        return accepted

    def _maybe_finish(self, req: Request, tok: int,
                      eos: Optional[int]) -> None:
        if (len(req.generated) >= req.max_new_tokens
                or (eos is not None and tok == eos)):
            req.state = RequestState.DONE
            self._give_back(req)
            self._vacate(req)
            get_telemetry().inc_counter(
                "inference/requests_done",
                help="requests finished (EOS or budget)")

    def cancel(self, req: Request) -> None:
        """Abort a request in any pre-DONE state: pages come back, the
        slot frees, and the planner never sees it again.  The serving
        front-end's ``cancel`` verb lands here."""
        if req.state is RequestState.DONE:
            return
        if req in self.waiting:
            self.waiting.remove(req)
        if req in self.prefilling:
            self.prefilling.remove(req)
        if req.blocks:
            self._give_back(req)
        self._vacate(req)
        req.state = RequestState.DONE
        get_telemetry().inc_counter(
            "inference/requests_cancelled",
            help="requests aborted before completion")

    def table_row(self, req: Request) -> np.ndarray:
        """The request's block table, padded to the table's width.  Built
        once for a reservation: ``blocks`` is assigned whole wherever it
        changes (admission, release, a resumed request), never edited, so
        the list's identity names the reservation."""
        made = req.table
        if made is None or made[0] is not req.blocks:
            row = np.zeros((self.cache.max_blocks_per_seq,), np.int32)
            row[:len(req.blocks)] = req.blocks
            made = req.table = (req.blocks, row)
        return made[1]

    def ring_pages_in_use(self) -> int:
        """Pages of a recycled pool that hold keys some window still
        reaches: a sequence's pages so far, at most its ring."""
        bs, ring = self.cache.block_size, self.cache.ring_blocks
        return sum(min(-(-r.length // bs), ring)
                   for r in self.slots if r is not None and r.ring >= 0)
