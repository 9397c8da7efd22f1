"""Page-releasing preemption under HBM pressure (ISSUE 14 satellite,
ROADMAP 3e): preempted requests release KV pages to the cached-free
LRU tier; re-admission recomputes via the prefix trie and the stream
splices exactly."""

from deepspeed_tpu.inference.v2 import KVCacheConfig
from deepspeed_tpu.serving import (Replica, ServingFrontend,
                                   ServingParams, SyntheticEngine,
                                   synthetic_token)


def make_frontend(num_blocks=12, slots=2, params=None):
    cc = KVCacheConfig(num_blocks=num_blocks, block_size=16,
                       max_seq_len=512)
    eng = SyntheticEngine(cc, max_batch_slots=slots, prefill_chunk=16,
                          prefill_batch=1, decode_burst=1)
    fe = ServingFrontend([Replica(eng, 0)], params=params
                         or ServingParams())
    return fe, eng


def test_pressure_preemption_releases_pages_and_replays_via_trie(
        monkeypatch):
    # pool: 11 allocatable pages.  Background: 33-token prompt (3
    # pages, 2 full -> trie-indexable) + 96 new = 9 pages total.
    fe, eng = make_frontend(num_blocks=12, slots=2)
    sched = fe.router.replicas[0].scheduler
    degraded = {"on": False}
    monkeypatch.setattr(fe, "_headroom_degraded",
                        lambda: degraded["on"])
    bg_prompt = list(range(2000, 2033))
    bg = fe.submit(bg_prompt, max_new_tokens=96, klass="background")
    for _ in range(8):
        fe.pump()
    assert bg.status == "running" and bg.delivered > 0
    streamed_before = bg.delivered
    # HBM pressure hits; an interactive request arrives that the pool
    # cannot hold alongside the background resident (page-blocked)
    degraded["on"] = True
    inter = fe.submit(list(range(100, 120)), max_new_tokens=30)
    fe.pump()
    # retaining preemption could never help a page-blocked head; the
    # release path frees real pages
    assert fe.metrics.counters["preemptions"] == 1
    assert fe.metrics.counters["preempt_pages_released"] > 0
    assert bg.status == "queued" and bg.request is None  # retired
    # the background PROMPT pages (trie-indexed at prefill completion)
    # are in the cached-free tier, revivable; generation pages freed
    assert sched.allocator.num_cached == 2  # 2 full prompt pages
    # (run_until_idle would spin: the deferred background stays queued
    # for as long as the pressure lasts — pump the interactive through)
    for _ in range(200):
        fe.pump()
        if inter.status == "done":
            break
    assert inter.status == "done"
    # pressure clears -> the background replays through a FRESH
    # admission whose _reserve re-matches the trie
    degraded["on"] = False
    fe.run_until_idle()
    assert bg.status == "done" and bg.replays == 1
    assert sched.prefix.revivals > 0  # recompute skipped cached pages
    # splice-exact: the full transcript, no duplicate and no gap past
    # the pre-preemption high-water mark
    assert streamed_before > 0
    assert bg.result(timeout=5) == [synthetic_token(bg_prompt, i)
                                    for i in range(96)]


def test_preemption_keeps_pages_when_not_degraded(monkeypatch):
    """Without HBM pressure the classic slot preemption still holds:
    pages stay resident, the victim resumes in place (no replay)."""
    fe, _ = make_frontend(num_blocks=64, slots=1)
    monkeypatch.setattr(fe, "_headroom_degraded", lambda: False)
    bg = fe.submit([1] * 20, max_new_tokens=64, klass="background")
    for _ in range(6):
        fe.pump()
    assert bg.status == "running"
    inter = fe.submit([2] * 20, max_new_tokens=4)
    fe.run_until_idle()
    assert inter.status == "done" and bg.status == "done"
    assert fe.metrics.counters["preemptions"] == 1
    assert fe.metrics.counters["preempt_pages_released"] == 0
    assert bg.replays == 0  # resumed from retained KV, not replayed


def test_release_preemption_disabled_by_param(monkeypatch):
    """preempt_release_pages=False: pressure preemption falls back to
    the retaining kind (slot-blocked only)."""
    degraded = {"on": False}
    fe, _ = make_frontend(
        num_blocks=64, slots=1,
        params=ServingParams(preempt_release_pages=False))
    monkeypatch.setattr(fe, "_headroom_degraded",
                        lambda: degraded["on"])
    bg = fe.submit([1] * 20, max_new_tokens=64, klass="background")
    for _ in range(6):
        fe.pump()
    assert bg.status == "running"
    degraded["on"] = True
    inter = fe.submit([2] * 20, max_new_tokens=4)
    for _ in range(200):
        fe.pump()
        if inter.status == "done":
            break
    assert inter.status == "done"
    assert fe.metrics.counters["preempt_pages_released"] == 0
    # degraded admission still deferred the background resume until
    # the pressure cleared
    degraded["on"] = False
    fe.run_until_idle()
    assert bg.status == "done" and bg.replays == 0


def test_only_a_replica_whose_seats_hold_state_starts_its_victim_over(
        monkeypatch):
    """A recurrent state lies in the seat, not in the pages, so a victim
    on such a replica is released and replays.  That is the replica's
    own: one visited after it, whose seats hold nothing, still keeps its
    victim's pages and resumes it in place."""
    engines = [SyntheticEngine(
        KVCacheConfig(num_blocks=64, block_size=16, max_seq_len=512,
                      state_slots=slots),
        max_batch_slots=1, prefill_chunk=16, prefill_batch=1,
        decode_burst=1) for slots in (1, 0)]
    fe = ServingFrontend([Replica(e, i) for i, e in enumerate(engines)])
    monkeypatch.setattr(fe, "_headroom_degraded", lambda: False)
    first, second = (r.scheduler for r in fe.router.replicas)
    assert first.seat_holds_state and not second.seat_holds_state
    # the first replica's one seat goes to a request that is no victim
    held = fe.submit([3] * 20, max_new_tokens=200)
    fe.pump()
    bg = fe.submit([1] * 20, max_new_tokens=64, klass="background")
    for _ in range(6):
        fe.pump()
    assert (held.replica_id, bg.replica_id) == (0, 1)
    assert bg.status == "running"
    inter = fe.submit([2] * 20, max_new_tokens=4)
    fe.pump()
    assert fe.metrics.counters["preemptions"] == 1
    assert fe.metrics.counters.get("preempt_pages_released", 0) == 0
    assert bg.preempted and bg.request is not None and bg.replays == 0
    fe.run_until_idle()
    assert inter.status == bg.status == "done" and bg.replays == 0


def test_a_victim_that_holds_recurrent_state_starts_over_and_serves_the_same():
    """Through the front end and a real engine whose layers carry a
    recurrent state (Falcon-H1 at a tiny size): the bumped background
    request gives up its seat AND its pages with no HBM pressure, replays
    from its first token in whatever seat it gets, and its stream is what
    it is when nothing interrupts it."""
    import jax

    from deepspeed_tpu import models
    from deepspeed_tpu.serving import build_serving_frontend

    model = models.FalconH1Model(models.FalconH1Config.tiny())
    fe = build_serving_frontend(
        model, model.init_params(jax.random.PRNGKey(3)),
        cache_config=KVCacheConfig(num_blocks=32, block_size=8,
                                   max_seq_len=64),
        max_batch_slots=1, prefill_chunk=16, prefill_batch=1)
    prompt = [(7 * i + 3) % 256 for i in range(21)]
    alone = fe.submit(prompt, max_new_tokens=24, klass="background")
    fe.run_until_idle()
    want = alone.result(timeout=5)
    assert len(want) == 24 and fe.metrics.counters.get("preemptions", 0) == 0
    bg = fe.submit(prompt, max_new_tokens=24, klass="background")
    for _ in range(40):
        fe.pump()
        if bg.delivered:
            break
    assert 0 < bg.delivered < 24
    inter = fe.submit([(5 * i + 1) % 256 for i in range(18)],
                      max_new_tokens=4)
    fe.run_until_idle()
    assert fe.metrics.counters["preemptions"] == 1
    assert fe.metrics.counters["preempt_pages_released"] > 0
    assert inter.status == bg.status == "done" and bg.replays == 1
    assert bg.result(timeout=5) == want
