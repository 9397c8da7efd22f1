"""Multi-replica routing — load-aware, prefix-affine, health-fed.

N engine replicas (each a :class:`RaggedInferenceEngineV2` with its own
KV pool and :class:`ServingScheduler`, or a host-only
:class:`~.synthetic.SyntheticEngine` in tests/dry-runs) sit behind one
router.  Placement policy, in order:

1. **Health** — only healthy replicas are candidates.  A replica is
   unhealthy when (a) an operator / the front-end marked it dead, (b)
   its injected probe says so, or (c) the process-global
   device-unresponsive latch is set (the PR-7 bounded liveness probe
   tripped: the device runtime stopped answering, every in-process
   replica is gone with it).  The front-end additionally subscribes to the hang
   watchdog's trip edge.  A dead replica *drains*: the front-end
   re-queues its in-flight work onto healthy replicas instead of
   blackholing it.
2. **Prefix affinity** — prefer the replica whose prefix trie already
   holds the longest indexed prefix of this prompt (at least
   ``affinity_min_tokens`` worth, so one hot block doesn't pin
   everything to one replica).
3. **Least outstanding tokens** — among equals, the replica with the
   smallest admitted-but-unfinished token count (remaining prompt +
   remaining generation budget summed over its active requests).

Per-replica KV memory is attributed in the PR-7 memory ledger under
distinct ``kv_cache`` sub-keys (``serving/replica<i>/kv_pool`` from the
engine, ``serving/replica<i>/prefix_cache`` maintained here), so ``mem
top`` names serving memory and the ``memory_pressure`` health rule sees
prefix-cache growth.
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional

from ..telemetry.memory import get_memory_ledger
from ..telemetry.memory.ledger import device_unresponsive


class Replica:
    """One engine behind the router + its serving bookkeeping."""

    def __init__(self, engine: Any, replica_id: int,
                 probe: Optional[Callable[[], bool]] = None):
        self.engine = engine
        self.id = int(replica_id)
        self.scheduler = engine.scheduler
        #: handles admitted to this replica and not yet finished
        self.active: List[Any] = []
        self._probe = probe
        self._dead_reason: Optional[str] = None
        #: per-pump-round probe memo — one pump calls healthy() from
        #: half a dozen placement/drain/guard sites; an expensive probe
        #: (device RPC) must run once per round, not once per site
        self._probe_round = 0
        self._probe_seen = -1
        self._probe_ok = True
        #: bytes of ONE pool page across layers/K/V — for prefix-cache
        #: ledger attribution; 0 when the engine has no device pool
        pool = getattr(engine, "pool", None)
        if pool is not None:
            # the pools whose pages a block table names (a recycled
            # kind's rings are no prefix's)
            blocks = engine.cache_config.num_blocks
            self.block_nbytes = sum(
                int(a.nbytes) // blocks for kind in pool.values()
                for a in kind.values() if a.shape[1] == blocks)
        else:
            self.block_nbytes = 0

    # -- health ------------------------------------------------------------

    def new_round(self, gen: int) -> None:
        """Invalidate the probe memo (the front-end, once per pump)."""
        self._probe_round = gen

    def healthy(self) -> bool:
        if self._dead_reason is not None:
            return False
        # the latch is a process-global flag read — always checked fresh
        latch = device_unresponsive()
        if latch is not None:
            self._dead_reason = f"device unresponsive: {latch}"
            return False
        if self._probe is not None:
            if self._probe_seen != self._probe_round:
                self._probe_seen = self._probe_round
                self._probe_ok = self._run_probe()
            if not self._probe_ok:
                return False
        return True

    def _run_probe(self) -> bool:
        try:
            ok = bool(self._probe())
        except Exception as e:
            self._dead_reason = f"health probe raised: {e!r}"
            return False
        if not ok:
            self._dead_reason = "health probe reported dead"
        return ok

    def mark_dead(self, reason: str) -> None:
        self._dead_reason = str(reason)

    @property
    def dead_reason(self) -> Optional[str]:
        return self._dead_reason

    # -- load --------------------------------------------------------------

    def outstanding_tokens(self) -> int:
        total = 0
        for h in self.active:
            req = h.request
            if req is None:
                continue
            total += max(len(req.prompt) - req.prefilled, 0) \
                + req.remaining_budget
        return total

    def moe_load_imbalance(self) -> float:
        """Hot-expert signal from the engine: max/mean expert load of
        its recent decodes (1.0 = balanced router, 0.0 = no MoE data).
        Both the v2 engine and :class:`~.synthetic.SyntheticEngine`
        expose the same method; other engines read as 0.0."""
        fn = getattr(self.engine, "moe_load_imbalance", None)
        if fn is None:
            return 0.0
        try:
            return float(fn())
        except Exception:
            return 0.0

    def update_ledger(self) -> None:
        """Refresh this replica's prefix-cache attribution.  Marked
        ``transient``: cached pages live INSIDE the already-registered
        KV pool allocation, so counting them in the steady-state drift
        cross-check would double-count HBM — but ``mem top`` still shows
        reclaimable prefix memory per replica."""
        led = get_memory_ledger()
        if not led.enabled or self.block_nbytes <= 0:
            return
        alloc = getattr(self.scheduler, "allocator", None)
        cached = getattr(alloc, "num_cached", 0)
        led.register(
            "kv_cache", f"serving/replica{self.id}/prefix_cache",
            cached * self.block_nbytes, transient=True,
            tag=f"prefix-shared cached pages ({cached}) — reclaimable "
                f"subset of the replica's KV pool")

    def snapshot(self) -> dict:
        sched = self.scheduler
        out = {"id": self.id,
               "healthy": self._dead_reason is None,
               "active_requests": len(self.active),
               "outstanding_tokens": self.outstanding_tokens()}
        imb = self.moe_load_imbalance()
        if imb > 0.0:
            out["moe_load_imbalance"] = imb
            load = getattr(self.engine, "moe_expert_load", None)
            if load is None:
                stats = getattr(self.engine, "last_moe_stats", None) or {}
                out["moe_expert_load"] = stats.get("load")
            else:
                arr = load()
                out["moe_expert_load"] = (None if arr is None
                                          else list(map(float, arr)))
        if self._dead_reason:
            out["dead_reason"] = self._dead_reason
        if hasattr(sched, "prefix"):
            out["prefix"] = sched.prefix.stats()
            out["kv_pages_free"] = sched.allocator.num_free
            out["kv_pages_cached"] = sched.allocator.num_cached
            out["preemptions"] = sched.preemptions
        return out


class ReplicaRouter:
    """Least-outstanding-tokens with prefix affinity over healthy
    replicas."""

    def __init__(self, replicas: List[Replica],
                 affinity_min_tokens: int = 16,
                 moe_imbalance_weight: float = 0.25):
        if not replicas:
            raise ValueError("router needs at least one replica")
        self.replicas = list(replicas)
        self.affinity_min_tokens = int(affinity_min_tokens)
        #: hot-expert penalty: a replica whose recent decodes route
        #: max/mean = 2x (one expert doing double work — its MoE FLOPs
        #: are bottlenecked on the hot expert's capacity) scores like it
        #: carries ``1 + weight`` times its outstanding tokens.  0
        #: disables MoE-aware placement.
        self.moe_imbalance_weight = float(moe_imbalance_weight)

    def healthy(self) -> List[Replica]:
        return [r for r in self.replicas if r.healthy()]

    def route_candidates(self, prompt: List[int]) -> List[Replica]:
        """Healthy replicas in placement order (best first): max prefix
        affinity, then least *effective* load — outstanding tokens
        inflated by the replica's hot-expert imbalance (a skewed router
        bottlenecks on its hottest expert, so equal token counts are not
        equal work on a MoE replica) — then stable id."""
        def score(r: Replica):
            affinity = 0
            if hasattr(r.scheduler, "match_tokens"):
                m = r.scheduler.match_tokens(prompt)
                if m >= self.affinity_min_tokens:
                    affinity = m
            load = float(r.outstanding_tokens())
            imb = r.moe_load_imbalance() if self.moe_imbalance_weight else 0.0
            if imb > 1.0:
                load = (load + 1.0) * (
                    1.0 + self.moe_imbalance_weight * (imb - 1.0))
            return (-affinity, load, r.id)

        healthy = self.healthy()
        # one replica: nothing to order (its load is a walk over every
        # active request, in every round that has a request queued)
        return healthy if len(healthy) < 2 else sorted(healthy, key=score)

    def route(self, prompt: List[int]) -> Optional[Replica]:
        """Pick the replica for a fresh request; ``None`` when no
        replica is healthy."""
        candidates = self.route_candidates(prompt)
        return candidates[0] if candidates else None

    def snapshot(self) -> dict:
        return {"replicas": [r.snapshot() for r in self.replicas]}
