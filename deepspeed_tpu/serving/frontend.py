"""SLO-aware streaming front-end — submit / stream / cancel over
latency-class queues with admission control and preemption.

This is the layer that turns the v2 engine loop into a *service*:

* **submit(prompt, klass)** validates the request (the scheduler's
  field-naming validation runs at the front door), assigns it a
  latency class (``interactive`` / ``batch`` / ``background``), and
  queues it.  The returned :class:`ServingHandle` streams tokens as
  they are accepted (``stream()``), collects them (``result()``), or
  aborts (``cancel()``).
* **Admission control** drains class queues in strict priority order
  each pump: a request is admitted to its routed replica only when (a)
  the replica has a free decode slot and enough KV pages (prefix
  matches counted — a 90%-shared prompt is cheap to admit), (b) the
  replica's outstanding-token budget has room, (c) for non-interactive
  classes, admission leaves an interactive page reserve, and (d) the
  PR-7 memory ledger's HBM headroom (when it has device numbers) is
  above the configured floor — under memory pressure only interactive
  work is admitted.
* **Preemption**: when the interactive queue cannot place its head, a
  RUNNING background request is bumped out of its decode slot
  (``ServingScheduler.preempt`` — KV pages stay referenced, host state
  intact) and re-queued at the front of its class; it resumes in place
  later.  Interactive latency is bounded by a burst length, not by a
  background request's remaining budget.
* **Replica drain**: a replica that goes unhealthy (probe, device
  latch, watchdog trip) has its in-flight work re-queued onto healthy
  replicas.  Already-streamed tokens are not re-delivered: re-execution
  regenerates the sequence and delivery resumes past the high-water
  mark (exact for greedy decode; sampled streams may diverge at the
  splice point, which is recorded on the handle).

The front-end is driven either manually (``pump()`` — deterministic,
what the tests and an external event loop use) or by its own thread
(``start()``/``stop()``).  All mutable front-end state is guarded by
one re-entrant lock; token delivery to consumers goes through
per-handle thread-safe queues.  The clock is injectable, so SLO tests
measure TTFT distributions deterministically against a fake clock
advanced by the synthetic engine.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

from ..utils.logging import log_dist, warn_once
from .metrics import CLASSES, ServingMetrics
from .router import Replica, ReplicaRouter

_DONE = object()


class NoHealthyReplicaError(RuntimeError):
    """Every replica behind the front-end is dead (probe / device latch
    / watchdog) — pending work cannot make progress."""


@dataclasses.dataclass
class ServingParams:
    """Resolved front-end knobs (the ``serving.*`` config group maps
    onto this; tests construct it directly)."""

    #: per-replica admitted-but-unfinished token budget
    max_outstanding_tokens: int = 8192
    #: fraction of the allocatable pool kept free of batch/background
    #: reservations so interactive admission never waits on pages
    interactive_reserve_frac: float = 0.10
    #: admit only interactive work when the memory ledger reports HBM
    #: headroom below this fraction (0 disables the check)
    min_hbm_headroom_frac: float = 0.0
    #: allow interactive to preempt background decode slots
    preemption: bool = True
    #: router prefix-affinity threshold (tokens)
    affinity_min_tokens: int = 16
    #: sampling temperature for every decode dispatch (0 = greedy;
    #: greedy is what makes replica-death re-queue splice-exact)
    temperature: float = 0.0
    eos_token_id: Optional[int] = None
    #: per-handle stream bound (tokens): a consumer that stalls past
    #: this many unread tokens loses the OLDEST ones (drop-oldest), so
    #: the pump never blocks — size it to the longest generation whose
    #: full transcript must survive an unread buffer (``result()`` only
    #: returns what the buffer retained)
    stream_buffer: int = 4096
    #: interactive TTFT target (ms) — exported with the metrics so the
    #: bench/SLO gate reads the bound it asserts against
    interactive_ttft_slo_ms: float = 500.0
    #: under the HBM-headroom floor, preemption RELEASES the victim's
    #: KV pages back to the cached-free LRU tier (trie-indexed prompt
    #: pages stay revivable; re-admission recomputes the rest and the
    #: stream splices past the delivered high-water mark) instead of
    #: keeping them resident
    preempt_release_pages: bool = True


class ServingHandle:
    """One submitted request: stream / result / cancel surface."""

    def __init__(self, uid: int, prompt: List[int], max_new_tokens: int,
                 klass: str, submitted_at: float, frontend:
                 "ServingFrontend", stream_buffer: int):
        self.uid = uid
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.klass = klass
        self.submitted_at = submitted_at
        self.status = "queued"  # queued|running|done|cancelled|failed
        self.replica_id: Optional[int] = None
        self.request: Any = None          # live scheduler Request
        self.preempted = False
        self.pinned_replica: Optional[int] = None
        self.delivered = 0                # tokens pushed to the stream
        self.consumed = 0                 # tokens read off request
        self.dropped = 0                  # tokens evicted unread (full
                                          # buffer, stalled consumer)
        self.first_token_at: Optional[float] = None
        self.finished_at: Optional[float] = None
        self.admitted_at: Optional[float] = None
        self.error: Optional[BaseException] = None
        self.replays = 0                  # replica-death re-executions
        #: disaggregated serving: {"prefill_ms", "transfer_ms",
        #: "decode_ms"} TTFT attribution (None for colocated requests)
        self.ttft_breakdown: Optional[Dict[str, float]] = None
        #: distributed tracing (ISSUE 15): the propagated trace id and
        #: this process's lifecycle record for the request
        self.trace_id: Optional[str] = None
        self.record: Any = None
        self._frontend = frontend
        # a REAL bound: when a stalled consumer lets it fill, _push
        # drops the oldest undelivered token — the pump never blocks
        self._queue: "queue.Queue" = queue.Queue(
            maxsize=max(1, int(stream_buffer)))

    # -- consumer surface --------------------------------------------------

    def stream(self, timeout: Optional[float] = None) -> Iterator[int]:
        """Yield generated tokens as they arrive; raises the handle's
        error if the request failed.  With ``timeout`` per token."""
        while True:
            item = self._queue.get(timeout=timeout)
            if item is _DONE:
                if self.error is not None:
                    raise self.error
                return
            yield item

    def result(self, timeout: Optional[float] = None) -> List[int]:
        return list(self.stream(timeout=timeout))

    def drain(self) -> "tuple[List[int], bool]":
        """Non-blocking: every currently-buffered token plus a
        completion flag.  The replica-worker protocol's ``poll`` op
        reads the stream this way (a socket peer cannot park in
        :meth:`stream`)."""
        q = self._queue
        if not q.queue:     # nothing arrived: no lock taken
            return [], False
        with q.mutex:       # the whole buffer in one go, not a lock a token
            items = list(q.queue)
            q.queue.clear()
            q.not_full.notify_all()
        done = _DONE in items   # the completion mark: nothing follows it
        if done:
            items = items[:items.index(_DONE)]
        return [int(t) for t in items], done

    def next_event(self, timeout: Optional[float] = None) -> "tuple":
        """One stream event for push-style consumers (the SSE writer):
        ``("token", t)`` / ``("done", error)`` / ``("timeout", None)``
        when nothing arrived within ``timeout`` — the caller emits a
        heartbeat and retries, detecting dead sockets between tokens."""
        try:
            item = self._queue.get(timeout=timeout)
        except queue.Empty:
            return ("timeout", None)
        if item is _DONE:
            return ("done", self.error)
        return ("token", int(item))

    def cancel(self) -> None:
        self._frontend.cancel(self)

    @property
    def ttft_ms(self) -> Optional[float]:
        if self.first_token_at is None:
            return None
        return (self.first_token_at - self.submitted_at) * 1e3

    def _put_drop_oldest(self, item: Any) -> None:
        """Bounded stream, slow consumer: evict the oldest unread token
        so the pump never blocks (``dropped`` makes the loss visible —
        completion still lands even on a full buffer)."""
        while True:
            try:
                self._queue.put_nowait(item)
                return
            except queue.Full:
                try:
                    self._queue.get_nowait()
                    self.dropped += 1
                except queue.Empty:  # consumer drained it concurrently
                    pass

    def _push(self, tok: int) -> None:
        self._put_drop_oldest(tok)

    def _push_many(self, toks: List[int]) -> None:
        """:meth:`_push` for a round's tokens of one stream under one
        lock (a decode burst hands a stream 8 at once, 256 streams a
        round): the same bound, the oldest unread dropped."""
        q = self._queue
        with q.mutex:
            held, room = len(q.queue), q.maxsize
            unread = max(0, min(held + len(toks) - room, held))
            for _ in range(unread):
                q.queue.popleft()
            # a round longer than the whole buffer loses its own first
            unsent = max(0, len(toks) - room)
            self.dropped += unread + unsent
            q.queue.extend(toks[unsent:])
            q.unfinished_tasks += len(toks) - unsent
            q.not_empty.notify_all()

    def _finish(self, status: str,
                error: Optional[BaseException] = None) -> None:
        self.status = status
        self.error = error
        if self.record is not None:
            # the ONE terminal point both front-ends and the worker's
            # local pump share: close + commit the lifecycle record
            # (the ring decides sampled-or-anomalous)
            from .tracing import get_request_log

            self.record.finish(status, ttft_ms=self.ttft_ms, error=error,
                               breakdown=self.ttft_breakdown)
            get_request_log().commit(self.record)
        self._put_drop_oldest(_DONE)


class ServingFrontend:
    def __init__(self, replicas: List[Replica],
                 params: Optional[ServingParams] = None,
                 clock=time.monotonic):
        self.params = params or ServingParams()
        self.router = ReplicaRouter(
            replicas, affinity_min_tokens=self.params.affinity_min_tokens)
        self.clock = clock
        self.metrics = ServingMetrics()
        self._queues: Dict[str, List[ServingHandle]] = {
            c: [] for c in CLASSES}
        self._uid = 0
        self._lock = threading.RLock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._drained: set = set()  # replica ids already drained
        self._watchdogs: List[Any] = []  # for detach on close()
        self._round = 0  # pump round counter: probe-memo invalidation
        self._attach_recorder()
        # the percentile and queue gauges are worked out when the
        # registry is read, not at the end of every round; the hub holds
        # the hook weakly and carries it over its own reset()
        from ..telemetry import get_telemetry

        get_telemetry().add_collect_hook(self._publish_gauges)

    def _attach_recorder(self) -> None:
        """Every debug bundle gets a ``serving`` section."""
        try:
            from ..telemetry import get_flight_recorder

            rec = get_flight_recorder()
            if rec is not None:
                rec.register_context("serving", self.snapshot)
        except Exception as e:
            warn_once("serving/recorder",
                      f"flight-recorder attach failed ({e!r})")

    def attach_watchdog(self, watchdog: Any) -> None:
        """Replica health rides the existing hang watchdog: a trip means
        the process's device work is stuck, so every in-process replica
        drains (their queued work would blackhole otherwise)."""
        watchdog.add_trip_listener(self._on_watchdog_trip)
        self._watchdogs.append(watchdog)

    def close(self) -> None:
        """Stop the pump thread and detach from the process-global hooks
        (flight-recorder context provider, watchdog trip listeners).
        Without this, those hooks keep the front-end — and through it
        every replica's engine, model params, and KV pool — alive for
        the life of the process."""
        self.stop()
        from ..telemetry import get_telemetry

        tel = get_telemetry()
        if tel.enabled:
            # no round sets a gauge: the registry keeps this front-end's,
            # its engines' and their schedulers' last state from here
            tel.collect()
        tel.remove_collect_hook(self._publish_gauges)
        for wd in self._watchdogs:
            try:
                wd.remove_trip_listener(self._on_watchdog_trip)
            except Exception as e:
                warn_once("serving/watchdog-detach",
                          f"watchdog detach failed ({e!r})")
        self._watchdogs.clear()
        try:
            from ..telemetry import get_flight_recorder

            rec = get_flight_recorder()
            if rec is not None:
                rec.unregister_context("serving")
        except Exception as e:
            warn_once("serving/recorder-detach",
                      f"flight-recorder detach failed ({e!r})")

    def _on_watchdog_trip(self, reason: str, bundle: Optional[str]) -> None:
        # deliberately LOCKLESS: the trip fires precisely when a pump
        # thread may be wedged inside a device call while holding
        # self._lock — taking it here would deadlock the watchdog (and
        # every listener behind us, including the emergency snapshot).
        # mark_dead is a sticky one-shot attribute write on a replica
        # list that never mutates; the pump observes it at its next
        # health check.
        for r in self.router.replicas:
            if r.dead_reason is None:
                r.mark_dead(f"watchdog trip: {reason}")

    # -- request surface ---------------------------------------------------

    def submit(self, prompt: List[int], max_new_tokens: int = 64,
               klass: str = "interactive",
               trace_id: Optional[str] = None,
               sampled: Optional[bool] = None) -> ServingHandle:
        """``trace_id``/``sampled`` propagate the distributed trace
        context (ISSUE 15): the front door passes the minted/accepted
        id through; absent, one is minted here so every request is
        traceable.  ``sampled`` overrides the head-based decision (an
        upstream hop that knows the request is anomalous forces it)."""
        if klass not in CLASSES:
            raise ValueError(f"klass: unknown latency class {klass!r} "
                             f"(one of {', '.join(CLASSES)})")
        with self._lock:
            healthy = self.router.healthy()
            if not healthy:
                raise NoHealthyReplicaError(
                    "submit rejected: no healthy replica "
                    + "; ".join(f"replica{r.id}: {r.dead_reason}"
                                for r in self.router.replicas))
            # field-naming validation at the front door (the scheduler's
            # checks — empty prompt, max_new_tokens<=0, pool-impossible)
            healthy[0].scheduler.validate(list(prompt), max_new_tokens)
            if max_new_tokens >= self.params.stream_buffer:
                # the bounded buffer cannot hold the full generation: a
                # consumer that only reads after completion (the
                # submit -> run_until_idle -> result() pattern) will see
                # a truncated transcript (handle.dropped counts it)
                warn_once(
                    "serving/stream-buffer",
                    f"max_new_tokens {max_new_tokens} >= stream_buffer "
                    f"{self.params.stream_buffer}: an unread stream "
                    f"drops its oldest tokens")
            h = ServingHandle(self._uid, list(prompt), int(max_new_tokens),
                              klass, self.clock(), self,
                              self.params.stream_buffer)
            self._uid += 1
            from .tracing import get_request_log, mint_trace_id

            h.trace_id = trace_id or mint_trace_id()
            h.record = get_request_log().start(
                h.trace_id, h.uid, klass, len(prompt),
                int(max_new_tokens), sampled=sampled)
            h.record.event("submitted")
            self._queues[klass].append(h)
            self.metrics.inc("submitted")
            from ..telemetry import get_telemetry

            get_telemetry().inc_counter(
                f"serving/{klass}_submitted",
                help="requests submitted per latency class")
            return h

    def validate(self, prompt: List[int], max_new_tokens: int) -> None:
        """The scheduler's request validation, surfaced for the network
        front door: raises ``ValueError`` naming the offending field —
        the HTTP layer maps it to a 400 BEFORE anything is queued."""
        with self._lock:
            reps = self.router.replicas
            (self.router.healthy() or reps)[0].scheduler.validate(
                list(prompt), int(max_new_tokens))

    def queued_tokens(self, klass: str) -> int:
        """Admission-queue depth in TOKENS (prompt + generation budget)
        for one latency class — the front door's backpressure signal
        (429 + Retry-After when a class is over its token budget)."""
        with self._lock:
            return sum(len(h.prompt) + h.max_new_tokens
                       for h in self._queues.get(klass, ()))

    def healthy_count(self) -> int:
        """Replicas not marked dead (cheap — no probe RPCs): the
        ``/healthz`` answer."""
        return sum(1 for r in self.router.replicas
                   if r.dead_reason is None)

    def match_tokens(self, prompt: List[int]) -> int:
        """Best prefix-affinity score across replicas — the network
        router's placement signal (the worker protocol's ``match``)."""
        with self._lock:
            best = 0
            for r in self.router.replicas:
                sched = r.scheduler
                if hasattr(sched, "match_tokens"):
                    best = max(best, sched.match_tokens(list(prompt)))
            return best

    # -- disaggregated adoption (decode side) ------------------------------

    def adopt_begin(self, prompt: List[int], max_new_tokens: int,
                    klass: str = "interactive",
                    trace_id: Optional[str] = None,
                    sampled: Optional[bool] = None) -> "tuple":
        """Reserve pages + a slot for a request prefilled ELSEWHERE.
        Returns ``(handle, need)`` — ``need`` is the list of prompt-page
        indices the KV transfer must fill (trie-shared pages excluded)
        — or ``(None, None)`` when capacity is unavailable."""
        with self._lock:
            healthy = self.router.healthy()
            if not healthy:
                raise NoHealthyReplicaError(
                    "adopt rejected: no healthy replica")
            rep = healthy[0]
            got = rep.scheduler.adopt_reserve(list(prompt),
                                              int(max_new_tokens))
            if got is None:
                return None, None
            req, need = got
            h = ServingHandle(self._uid, list(prompt), int(max_new_tokens),
                              klass, self.clock(), self,
                              self.params.stream_buffer)
            self._uid += 1
            from .tracing import get_request_log, mint_trace_id

            h.trace_id = trace_id or mint_trace_id()
            h.record = get_request_log().start(
                h.trace_id, h.uid, klass, len(prompt),
                int(max_new_tokens), sampled=sampled)
            h.record.event("adopt_reserve", replica=rep.id,
                           need_pages=len(need))
            h.request = req
            h.status = "adopting"
            h.replica_id = rep.id
            h.pinned_replica = rep.id
            return h, need

    def adopt_commit(self, handle: ServingHandle, first_token: int,
                     inject_fn=None) -> None:
        """The transferred pages arrived (verified): write them into
        the pool (``inject_fn`` runs under the front-end lock — the
        pump must not step the engine mid-write) and seat the request
        RUNNING.  Token delivery flows through the normal pump."""
        with self._lock:
            rep = self._replica_by_id(handle.pinned_replica)
            if rep is None or not rep.healthy():
                raise NoHealthyReplicaError(
                    "adopt_commit: adopting replica died mid-transfer")
            if inject_fn is not None:
                inject_fn()
            rep.scheduler.adopt_commit(handle.request, int(first_token),
                                       self.params.eos_token_id)
            handle.status = "running"
            handle.admitted_at = self.clock()
            if handle.record is not None:
                handle.record.event("admitted", replica=rep.id,
                                    adopted=True)
            rep.active.append(handle)

    def adopt_abort(self, handle: ServingHandle,
                    error: Optional[BaseException] = None) -> None:
        """Transfer failed: release the reservation and fail the
        handle (the caller re-routes at ITS layer with a fresh one)."""
        with self._lock:
            rep = self._replica_by_id(handle.pinned_replica)
            if rep is not None and handle.request is not None:
                rep.scheduler.adopt_abort(handle.request)
            handle._finish("failed", error)

    def cancel(self, handle: ServingHandle) -> None:
        with self._lock:
            if handle.status == "queued":
                try:
                    self._queues[handle.klass].remove(handle)
                except ValueError:
                    pass
                if handle.request is not None:
                    # preempted: pages are still reserved on its replica
                    rep = self._replica_by_id(handle.pinned_replica)
                    if rep is not None:
                        rep.scheduler.cancel(handle.request)
                self.metrics.inc("cancelled")
                handle._finish("cancelled")
            elif handle.status == "running":
                rep = self._replica_by_id(handle.replica_id)
                if rep is not None:
                    rep.scheduler.cancel(handle.request)
                    if handle in rep.active:
                        rep.active.remove(handle)
                self.metrics.inc("cancelled")
                handle._finish("cancelled")
            elif handle.status == "adopting":
                # reserved for a KV transfer that no longer matters
                rep = self._replica_by_id(handle.pinned_replica)
                if rep is not None and handle.request is not None:
                    rep.scheduler.adopt_abort(handle.request)
                self.metrics.inc("cancelled")
                handle._finish("cancelled")

    # -- the pump ----------------------------------------------------------

    def pump(self) -> int:
        """One serving round: drain dead replicas, admit (with
        preemption), step every replica with work, deliver tokens.
        Returns tokens processed — 0 means idle.  The paged engine is
        stepped through ``step_ahead``: it dispatches this round's call
        behind the last round's, which is still running, then fetches and
        commits that one; the new call is on the device when this returns.
        So what a round delivers is what the call of the round BEFORE
        yielded (a token reaches its stream one round after its call
        ended, a request's slot is free for admission the round after the
        call that finished it was committed), and whatever is done to a
        request between two rounds (cancel, preemption) meets one call in
        flight that may hold a row of it: the engine passes that row over
        when it commits the call."""
        from ..telemetry import get_telemetry

        tel = get_telemetry()
        with self._lock:
            self._round += 1
            with tel.span("serving/pump", args={"round": self._round}):
                with tel.span("serving/admit") as sp:
                    # one health-probe evaluation per replica per round:
                    # every healthy() call below this reuses the memoized
                    # verdict
                    for r in self.router.replicas:
                        r.new_round(self._round)
                    queued = sum(len(q) for q in self._queues.values())
                    seated = 0
                    self._drain_dead()
                    healthy = bool(self.router.healthy())
                    if healthy:
                        seated = self._admit_all()
                        if self.params.preemption \
                                and self._queues["interactive"] \
                                and self._preempt_for_interactive():
                            seated += self._admit_all()
                    sp.set(queued=queued, admitted=seated)
                if not healthy:
                    # pump/start() mode has no caller to raise to (that is
                    # run_until_idle's job): fail pending handles so
                    # consumers parked in stream()/result() unblock instead
                    # of hanging forever
                    if any(self._queues.values()):
                        self._fail_pending_no_replica()
                    return 0
                n = 0
                for rep in self.router.healthy():
                    if rep.scheduler.has_work:
                        # an engine that can keep a call queued behind
                        # the running one does: its own fetch, commit and
                        # packing, delivery, the clients' reads and the
                        # next round's admissions then cost the device
                        # nothing (what a call yields is delivered a round
                        # after it ended)
                        step = getattr(rep.engine, "step_ahead",
                                       rep.engine.step)
                        n += step(temperature=self.params.temperature,
                                  eos_token_id=self.params.eos_token_id)
                    with tel.span("serving/deliver") as sp:
                        sp.set(tokens=self._deliver(rep, tel))
                    with tel.span("serving/ledger"):
                        rep.update_ledger()
                return n

    def _publish_gauges(self) -> None:
        """The registry's collect hook.  It runs on the reader's thread
        and takes no lock: a scrape must not wait for a round (the pump
        holds the front-end's lock through its device calls), and what
        it reads is safe to read beside the pump: queue lengths, the
        prefix counters, and sample windows that copy themselves
        (``LatencyTracker.percentile``)."""
        from ..telemetry import get_telemetry

        if not get_telemetry().enabled:
            return
        self.metrics.publish(
            {c: len(q) for c, q in self._queues.items()},
            self._aggregate_hit_rate(),
            moe_imbalance={r.id: imb for r in self.router.replicas
                           for imb in [r.moe_load_imbalance()]
                           if imb > 0.0} or None)

    def run_until_idle(self, max_rounds: int = 100_000) -> None:
        """Pump until no queued or in-flight work remains.  Raises
        :class:`NoHealthyReplicaError` if work is pending with every
        replica dead."""
        for _ in range(max_rounds):
            with self._lock:
                pending = (any(self._queues.values())
                           or any(r.active for r in self.router.replicas))
                if not pending:
                    return
                if not self.router.healthy():
                    # fail the pending handles BEFORE raising: other
                    # threads parked in stream()/result() would wait on
                    # queues that will never see _DONE otherwise
                    self._drain_dead()
                    self._fail_pending_no_replica()
                    raise NoHealthyReplicaError(
                        "pending serving work but no healthy replica")
            self.pump()
        raise RuntimeError(f"run_until_idle: no quiescence in "
                           f"{max_rounds} rounds")

    # -- background drive --------------------------------------------------

    def start(self, idle_sleep_s: float = 0.001) -> None:
        with self._lock:
            if self._thread is not None:
                return
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._serve_loop, args=(idle_sleep_s,),
                daemon=True, name="ds-serving-frontend")
            self._thread.start()

    def stop(self) -> None:
        with self._lock:
            t = self._thread
            self._thread = None
        if t is not None:
            self._stop.set()
            t.join(timeout=10.0)

    def _serve_loop(self, idle_sleep_s: float) -> None:
        log_dist("serving front-end loop started")
        while not self._stop.is_set():
            if self.pump() == 0:
                self._stop.wait(idle_sleep_s)

    # -- internals (lock held) ---------------------------------------------

    def _replica_by_id(self, rid: Optional[int]) -> Optional[Replica]:
        for r in self.router.replicas:
            if r.id == rid:
                return r
        return None

    def _aggregate_hit_rate(self) -> float:
        hits = looks = 0
        for r in self.router.replicas:
            p = getattr(r.scheduler, "prefix", None)
            if p is not None:
                hits += p.hit_tokens
                looks += p.lookup_tokens
        return hits / looks if looks else 0.0

    def _reset_for_replay(self, h: ServingHandle) -> None:
        """The dead engine's scheduler state is unreachable; the handle
        restarts from its prompt on a healthy replica, delivery resumes
        past the already-streamed high-water mark."""
        if h.record is not None:
            h.record.event("replayed", from_replica=h.pinned_replica,
                           delivered=h.delivered)
        h.request = None
        h.replica_id = None
        h.pinned_replica = None
        h.preempted = False
        h.consumed = 0
        h.replays += 1
        h.status = "queued"

    def _drain_dead(self) -> None:
        for rep in self.router.replicas:
            if rep.healthy() or rep.id in self._drained:
                continue
            self._drained.add(rep.id)
            moved = 0
            # preempted handles sit in the class queues (not rep.active)
            # but are still pinned to this replica's now-unreachable KV
            # pages — reset them in place so _try_admit restarts them on
            # a healthy replica instead of retrying the dead pin forever
            for q in self._queues.values():
                for h in q:
                    if h.request is not None and h.pinned_replica == rep.id:
                        self._reset_for_replay(h)
                        moved += 1
            # re-queue in-flight work at the class front, earliest
            # admission first (walk newest-first while inserting at 0)
            for h in reversed(rep.active):
                self._reset_for_replay(h)
                self._queues[h.klass].insert(0, h)
                moved += 1
            rep.active.clear()
            if moved:
                self.metrics.inc("requeued_replica_death", moved)
            log_dist(f"serving: replica{rep.id} drained "
                     f"({rep.dead_reason}); {moved} requests re-queued")

    def _fail_pending_no_replica(self) -> None:
        err = NoHealthyReplicaError(
            "all replicas dead: "
            + "; ".join(f"replica{r.id}: {r.dead_reason}"
                        for r in self.router.replicas))
        n = 0
        for q in self._queues.values():
            for h in q:
                self.metrics.inc("failed")
                h._finish("failed", err)
                n += 1
            q.clear()
        log_dist(f"serving: failed {n} pending requests — "
                 f"no healthy replica")

    def _headroom_degraded(self) -> bool:
        floor = self.params.min_hbm_headroom_frac
        if floor <= 0:
            return False
        from ..telemetry.memory import get_memory_ledger

        led = get_memory_ledger()
        if not led.enabled:
            return False
        hb = led.heartbeat_summary().get("hbm_headroom")
        return hb is not None and hb < floor

    def _admit_all(self) -> int:
        """Seat what can be seated, in class order; returns how many
        requests were (fresh admissions and resumed ones)."""
        seated = 0
        degraded = self._headroom_degraded()
        for klass in CLASSES:
            if degraded and klass != "interactive":
                if self._queues[klass]:
                    self.metrics.inc("admission_deferred_headroom")
                    from .metrics import count_admission_reject

                    count_admission_reject(self.metrics, "headroom")
                continue
            q = self._queues[klass]
            while q:
                if not self._try_admit(q[0]):
                    break  # FIFO within a class: no overtaking
                q.pop(0)
                seated += 1
            if q:
                # strict priority: a class that could not fully drain
                # blocks lower classes this round (no SLO inversion) —
                # unless nothing is seated anywhere: then only a
                # lower-class admission/resume can ever complete and
                # free the pages this head is waiting on, so blocking
                # them would deadlock the whole service
                if any(r.scheduler.has_work for r in self.router.healthy()):
                    break
        return seated

    def _reserve_pages(self, rep: Replica, klass: str) -> int:
        if klass == "interactive":
            return 0
        allocatable = rep.scheduler.cache.num_blocks - 1
        return int(self.params.interactive_reserve_frac * allocatable)

    def _try_admit(self, h: ServingHandle) -> bool:
        if h.request is not None:
            # preempted: pinned to the replica holding its KV pages
            rep = self._replica_by_id(h.pinned_replica)
            if rep is None or not rep.healthy():
                return False
            if not rep.scheduler.resume(h.request):
                if h.record is not None:
                    h.record.note_blocked_admission()
                return False
            h.status = "running"
            h.replica_id = rep.id
            if h.record is not None:
                h.record.event("resumed", replica=rep.id)
            rep.active.append(h)
            return True
        rejected = {"slots": 0, "pages": 0, "token_budget": 0}
        for rep in self.router.route_candidates(h.prompt):
            if (rep.outstanding_tokens() + len(h.prompt)
                    + h.max_new_tokens
                    > self.params.max_outstanding_tokens):
                rejected["token_budget"] += 1
                continue
            reserve = self._reserve_pages(rep, h.klass)
            if not rep.scheduler.can_admit(h.prompt, h.max_new_tokens,
                                           reserve_pages=reserve):
                # the pages-only re-check tells slot-blocked (more
                # workers help) from page-blocked (more HBM helps)
                if rep.scheduler.can_admit(h.prompt, h.max_new_tokens,
                                           reserve_pages=reserve,
                                           ignore_slots=True):
                    rejected["slots"] += 1
                else:
                    rejected["pages"] += 1
                continue
            h.request = rep.engine.put(h.prompt, h.max_new_tokens)
            h.request.priority = CLASSES.index(h.klass)
            rep.scheduler.admit_now(h.request)
            h.status = "running"
            h.replica_id = rep.id
            h.pinned_replica = rep.id
            h.admitted_at = self.clock()
            if h.record is not None:
                h.record.event("admitted", replica=rep.id)
            rep.active.append(h)
            self._queued_span(h)
            return True
        if h.record is not None:
            h.record.note_blocked_admission()
        if any(rejected.values()):
            from .metrics import count_admission_reject

            count_admission_reject(
                self.metrics,
                max(("slots", "pages", "token_budget"),
                    key=lambda r: rejected[r]))
        return False

    def _preempt_for_interactive(self) -> bool:
        """Free a decode slot for the interactive head by bumping a
        RUNNING background request; True when a preemption happened."""
        head = self._queues["interactive"][0]
        preempted = False
        # under the HBM-headroom floor the victim's pages are RELEASED
        # (cached-free tier), not retained — so preemption can help a
        # page-blocked head too, and HBM actually shrinks
        pressed = (self.params.preempt_release_pages
                   and self._headroom_degraded())
        for rep in self.router.healthy():
            if rep.scheduler.can_admit(head.prompt, head.max_new_tokens):
                return False  # admissible without preemption
        for rep in self.router.healthy():
            # a recurrent state lies in THIS replica's seats: its victim
            # starts over
            release = pressed or rep.scheduler.seat_holds_state
            if not release and not rep.scheduler.can_admit(
                    head.prompt, head.max_new_tokens, ignore_slots=True):
                # the head is page-blocked here, not slot-blocked:
                # retaining preemption keeps the victim's KV pages
                # resident, so bumping it cannot free what the head
                # needs — let the running work finish and release its
                # pages instead
                continue
            victims = [h for h in rep.active
                       if h.klass == "background" and h.request is not None
                       and h.request.slot >= 0
                       and h.request.state.value in ("running", "prefill")]
            if not victims:
                continue
            # bump the request expected to hold its slot longest: decode
            # with the most remaining budget first, else a prefill
            victim = max(victims, key=lambda h: h.request.remaining_budget)
            if victim.record is not None:
                victim.record.event("preempted", replica=rep.id,
                                    release=release)
            if release:
                pages = rep.scheduler.preempt_release(victim.request)
                rep.active.remove(victim)
                # the request object is retired with its pages: the
                # handle replays through a fresh admission, where the
                # prefix trie revives what the cached tier still holds
                # and delivery splices past the high-water mark
                self._reset_for_replay(victim)
                self._queues["background"].insert(0, victim)
                self.metrics.inc("preempt_pages_released", pages)
            else:
                rep.scheduler.preempt(victim.request)
                rep.active.remove(victim)
                victim.status = "queued"
                victim.preempted = True
                self._queues["background"].insert(0, victim)
            self.metrics.inc("preemptions")
            preempted = True
            break
        return preempted

    def _queued_span(self, h: ServingHandle) -> None:
        """A request's wait for a seat as a span of its trace id, between
        its record's own two stamps (``queue_wait_ms_p50.batch`` reads
        it).  A replayed request has waited twice and one without a
        record has no stamps: neither gives a span."""
        from ..telemetry import get_telemetry

        tel = get_telemetry()
        rec = h.record
        if tel.enabled and rec is not None and not h.replays:
            tel.tracer.add("serving/request/queued", rec.start_ts,
                           rec.admitted_ts,
                           {"trace_id": h.trace_id, "klass": h.klass})

    def _deliver(self, rep: Replica, tel: Any) -> int:
        """Push what the replica's requests generated since the last
        round onto their streams; returns the tokens pushed."""
        pushed = 0
        for h in list(rep.active):
            req = h.request
            # what the request generated since the last round, less what
            # a replayed request's stream was already given
            seen = len(req.generated)
            new = [int(t) for t in
                   req.generated[max(h.consumed, h.delivered):seen]]
            h.consumed = seen
            if new:
                if h.first_token_at is None:
                    h.first_token_at = self.clock()
                    self.metrics.record_ttft(h.klass, h.ttft_ms,
                                             ref=h.trace_id)
                    if h.record is not None:
                        h.record.event("first_token", replica=rep.id)
                h.delivered += len(new)
                pushed += len(new)
                if h.record is not None:
                    h.record.token(len(new))
                h._push_many(new)
            if req.state.value == "done" and h.status == "running":
                rep.active.remove(h)
                h.finished_at = self.clock()
                gen_s = (h.finished_at - (h.first_token_at
                                          or h.finished_at))
                self.metrics.record_completion(h.klass, h.delivered, gen_s)
                tel.inc_counter(
                    f"serving/{h.klass}_tokens", v=h.delivered,
                    help="generated tokens delivered per latency class")
                h._finish("done")
        return pushed

    # -- introspection -----------------------------------------------------

    #: bound on the snapshot lock wait — the flight recorder evaluates
    #: this provider inside dump(), and the watchdog dumps BEFORE firing
    #: trip listeners: exactly when a pump thread may be wedged in a
    #: device call while still holding self._lock.  A blocking acquire
    #: here would deadlock the watchdog thread — no bundle written,
    #: replicas never marked dead.  Sized to outlast a ROUTINE long
    #: device step (pump() holds the lock across engine.step), so a
    #: healthy-system dump waits for the full snapshot and only a
    #: genuine wedge degrades; on the watchdog-trip path the pump has
    #: already been stuck for hang_timeout_s, so the extra wait is
    #: noise.  (Class attribute: a test seam.)
    _snapshot_lock_timeout_s: float = 5.0

    def snapshot(self) -> Dict[str, Any]:
        if not self._lock.acquire(timeout=self._snapshot_lock_timeout_s):
            # mirror the lockless _on_watchdog_trip design: emit a
            # best-effort lock-free view instead of a bundle with no
            # serving section at all
            out = self._snapshot_best_effort()
            out["degraded"] = ("frontend lock held beyond "
                               f"{self._snapshot_lock_timeout_s}s (pump "
                               "wedged or in a long device call) — "
                               "lock-free best-effort reads")
            return out
        try:
            return self._snapshot_best_effort()
        finally:
            self._lock.release()

    def _snapshot_best_effort(self) -> Dict[str, Any]:
        """The one section list for BOTH snapshot branches (locked and
        lock-timeout fallback), so they cannot drift.  In the fallback
        the holder may be a LIVE pump in a long device call (not
        wedged), still mutating underneath us — so every section is
        guarded independently: a torn read (e.g. a metrics deque
        resized mid-sort) costs that one section, never the whole
        serving view.  Under the lock the guards never fire."""
        out: Dict[str, Any] = {}
        for build in (
                self.metrics.snapshot,
                lambda: {"queues": {c: len(q)
                                    for c, q in self._queues.items()}},
                lambda: {"queued_tokens":
                         {c: sum(len(h.prompt) + h.max_new_tokens
                                 for h in q)
                          for c, q in self._queues.items()}},
                lambda: {"router": self.router.snapshot()},
                lambda: {"prefix_hit_rate":
                         round(self._aggregate_hit_rate(), 4)},
                lambda: {"params": dataclasses.asdict(self.params)}):
            try:
                out.update(build())
            except Exception as e:
                out.setdefault("section_errors", []).append(repr(e))
        return out
