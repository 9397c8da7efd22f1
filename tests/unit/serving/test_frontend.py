"""Front-end acceptance: latency classes, admission, preemption, SLOs.

Everything runs on synthetic replicas with an injectable clock — the
TTFT distributions below are DETERMINISTIC (the fake clock only
advances by the synthetic engine's per-chunk/per-burst costs), so the
SLO assertions are exact, not statistical.
"""

import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import KVCacheConfig
from deepspeed_tpu.serving import (FakeClock, NoHealthyReplicaError,
                                   Replica, ServingFrontend, ServingParams,
                                   SyntheticEngine, synthetic_token)


def make_frontend(replicas=1, slots=4, params=None, clock=None,
                  num_blocks=256, probes=None):
    clock = clock or FakeClock()
    cache = KVCacheConfig(num_blocks=num_blocks, block_size=16,
                          max_seq_len=512)
    reps = []
    for i in range(replicas):
        eng = SyntheticEngine(cache, max_batch_slots=slots,
                              prefill_chunk=64, prefill_batch=2,
                              decode_burst=4, clock=clock)
        probe = probes[i] if probes else None
        reps.append(Replica(eng, i, probe=probe))
    fe = ServingFrontend(reps, params=params or ServingParams(),
                         clock=clock)
    return fe, clock


def rng_prompt(rng, header, tail):
    return header + rng.randint(2, 29000, size=tail).tolist()


# ---------------------------------------------------------------------------
# submit / stream / cancel surface
# ---------------------------------------------------------------------------

def test_submit_validation_names_fields():
    fe, _ = make_frontend()
    with pytest.raises(ValueError, match="prompt"):
        fe.submit([], max_new_tokens=4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        fe.submit([1, 2, 3], max_new_tokens=0)
    with pytest.raises(ValueError, match="klass"):
        fe.submit([1, 2, 3], max_new_tokens=4, klass="premium")


def test_stream_yields_expected_tokens():
    fe, _ = make_frontend()
    prompt = [5, 6, 7, 8]
    h = fe.submit(prompt, max_new_tokens=6)
    fe.run_until_idle()
    want = [synthetic_token(prompt, i) for i in range(6)]
    assert h.result() == want
    assert h.status == "done"
    assert h.ttft_ms is not None and h.ttft_ms >= 0


def test_cancel_queued_and_running():
    # one slot, a long background request occupies it; the queued one
    # cancels instantly, the running one mid-generation
    fe, _ = make_frontend(slots=1)
    a = fe.submit([1] * 8, max_new_tokens=64, klass="background")
    b = fe.submit([2] * 8, max_new_tokens=64, klass="background")
    for _ in range(3):
        fe.pump()
    assert a.status == "running" and b.status == "queued"
    b.cancel()
    assert b.status == "cancelled"
    a.cancel()
    assert a.status == "cancelled"
    with pytest.raises(RuntimeError):
        raise a.error or RuntimeError("cancel leaves error unset")
    fe.run_until_idle()
    # every page reclaimable again
    alloc = fe.router.replicas[0].scheduler.allocator
    assert alloc.num_available == 255


def test_cancelled_stream_raises_nothing_and_ends():
    fe, _ = make_frontend()
    h = fe.submit([3] * 8, max_new_tokens=8)
    h.cancel()
    assert h.result() == []


# ---------------------------------------------------------------------------
# multi-tenant SLO acceptance (ISSUE 8 acceptance criterion)
# ---------------------------------------------------------------------------

def test_interactive_slo_holds_under_synthetic_overload():
    """Background floods one replica; interactive probes arrive
    throughout.  Interactive p99 TTFT stays under the bound while
    background TTFT degrades — never the reverse — and preemption (not
    luck) is what makes it true."""
    params = ServingParams(interactive_ttft_slo_ms=120.0,
                           interactive_reserve_frac=0.1)
    fe, clock = make_frontend(slots=2, params=params, num_blocks=512)
    rng = np.random.RandomState(0)
    header = rng.randint(2, 29000, size=128).tolist()

    background = [fe.submit(rng_prompt(rng, header, 16),
                            max_new_tokens=96, klass="background")
                  for _ in range(6)]
    for _ in range(4):
        fe.pump()
    interactive = []
    for _ in range(10):
        h = fe.submit(rng_prompt(rng, header, 8), max_new_tokens=8,
                      klass="interactive")
        interactive.append(h)
        while h.status in ("queued", "running"):
            fe.pump()
    fe.run_until_idle()

    assert all(h.status == "done" for h in interactive + background)
    m = fe.metrics
    inter_p99 = m.ttft["interactive"].percentile(99)
    bg_p99 = m.ttft["background"].percentile(99)
    assert inter_p99 <= params.interactive_ttft_slo_ms, \
        f"interactive p99 {inter_p99}ms blew the SLO"
    # background absorbed the degradation, not the reverse
    assert bg_p99 > inter_p99
    assert m.counters["preemptions"] >= 1
    # decode slots were actually contended the whole time
    assert m.ttft["background"].count == 6
    # every page comes back (preempted-and-resumed included)
    alloc = fe.router.replicas[0].scheduler.allocator
    assert alloc.num_available == 511


def test_ttft_ordering_interactive_before_background():
    """Submitted at the SAME instant, the interactive request gets its
    first token strictly before a background request submitted ahead
    of it (class queues, not arrival order, decide)."""
    fe, clock = make_frontend(slots=1)
    bg = fe.submit([9] * 48, max_new_tokens=32, klass="background")
    inter = fe.submit([8] * 48, max_new_tokens=4, klass="interactive")
    fe.run_until_idle()
    assert inter.first_token_at < bg.first_token_at
    assert inter.finished_at < bg.finished_at


def test_preempted_background_resumes_and_completes_exactly():
    """The preempted victim loses no tokens: its stream is the same
    sequence an uncontended run produces."""
    fe, _ = make_frontend(slots=1)
    bgp = [4] * 32
    bg = fe.submit(bgp, max_new_tokens=24, klass="background")
    for _ in range(6):
        fe.pump()
    inter = fe.submit([5] * 32, max_new_tokens=4, klass="interactive")
    fe.run_until_idle()
    assert fe.metrics.counters["preemptions"] >= 1
    assert bg.status == "done"
    assert bg.result() == [synthetic_token(bgp, i) for i in range(24)]
    assert inter.status == "done"


@pytest.mark.parametrize("pumps_before", [3, 4, 5, 6])
def test_preemption_under_a_decode_call_still_running(pumps_before):
    """The paged engine leaves a round's decode call running when the pump
    returns (``step_ahead``), and the victim of a preemption is among its
    rows: what the call yields for it is dropped when it is committed
    (the victim is no longer running), the victim decodes that position
    again when it resumes, and both requests are served the tokens an
    uncontended run serves."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import LlamaConfig, LlamaModel
    from deepspeed_tpu.serving import build_serving_frontend

    model = LlamaModel(LlamaConfig.tiny(num_layers=2, max_seq_len=64,
                                        dtype=jnp.float32))
    params = model.init_params(jax.random.PRNGKey(0))

    def build():
        return build_serving_frontend(
            model, params, replicas=1,
            cache_config=KVCacheConfig(num_blocks=64, block_size=4,
                                       max_seq_len=64),
            max_batch_slots=1, prefill_chunk=8, prefill_batch=1,
            decode_burst=2, serving_params=ServingParams())

    rng = np.random.RandomState(11)
    bgp, inp = (rng.randint(1, 512, size=n).tolist() for n in (11, 9))
    alone = []
    for prompt, new in ((bgp, 14), (inp, 4)):
        fe = build()
        h = fe.submit(prompt, max_new_tokens=new, klass="background")
        fe.run_until_idle()
        alone.append(h.result())
        fe.close()
    fe = build()
    bg = fe.submit(bgp, max_new_tokens=14, klass="background")
    for _ in range(pumps_before):
        fe.pump()
    assert fe.router.replicas[0].engine._inflight
    inter = fe.submit(inp, max_new_tokens=4, klass="interactive")
    fe.run_until_idle()
    assert fe.metrics.counters["preemptions"] == 1
    assert [bg.result(), inter.result()] == alone
    fe.close()


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------

def test_outstanding_token_budget_defers_admission():
    params = ServingParams(max_outstanding_tokens=200)
    fe, _ = make_frontend(params=params)
    a = fe.submit([1] * 64, max_new_tokens=64, klass="batch")   # 128 tok
    b = fe.submit([2] * 64, max_new_tokens=64, klass="batch")   # over
    fe.pump()
    assert a.status == "running"
    assert b.status == "queued"
    fe.run_until_idle()
    assert b.status == "done"


def test_interactive_page_reserve_blocks_background():
    # pool of 15 allocatable pages, reserve 20% (3): a background
    # request needing all the slack defers, interactive takes it
    params = ServingParams(interactive_reserve_frac=0.2)
    fe, _ = make_frontend(params=params, num_blocks=16)
    bg = fe.submit([1] * 112, max_new_tokens=96, klass="background")
    fe.pump()
    assert bg.status == "queued"  # 13 pages + 3 reserve > 15
    inter = fe.submit([2] * 112, max_new_tokens=96, klass="interactive")
    fe.pump()
    assert inter.status == "running"


def test_memory_headroom_degrades_to_interactive_only():
    from deepspeed_tpu.telemetry.memory import get_memory_ledger

    led = get_memory_ledger()
    led.configure(enabled=True)
    led._device_stats_fn = lambda: {"bytes_in_use": 9.7e9,
                                    "bytes_limit": 10e9,
                                    "peak_bytes_in_use": 9.8e9}
    led.step_sample()  # cache the reading the heartbeat summary reads
    params = ServingParams(min_hbm_headroom_frac=0.05)
    fe, _ = make_frontend(params=params)
    bg = fe.submit([1] * 8, max_new_tokens=4, klass="background")
    inter = fe.submit([2] * 8, max_new_tokens=4, klass="interactive")
    fe.pump()
    assert inter.status == "running"
    assert bg.status == "queued"  # headroom 0.02 < floor 0.05
    assert fe.metrics.counters["admission_deferred_headroom"] >= 1
    # pressure clears -> background admitted
    led._device_stats_fn = lambda: {"bytes_in_use": 2e9,
                                    "bytes_limit": 10e9,
                                    "peak_bytes_in_use": 2e9}
    led.step_sample()
    led._peak_hbm_bytes = 0.0  # headroom uses the rolling peak
    led.step_sample()
    fe.run_until_idle()
    assert bg.status == "done"


# ---------------------------------------------------------------------------
# no-healthy-replica behavior + snapshot
# ---------------------------------------------------------------------------

def test_submit_rejected_when_all_replicas_dead():
    fe, _ = make_frontend(replicas=2)
    for r in fe.router.replicas:
        r.mark_dead("test")
    with pytest.raises(NoHealthyReplicaError, match="replica0"):
        fe.submit([1, 2], max_new_tokens=2)


def test_run_until_idle_raises_with_pending_work_and_no_replicas():
    fe, _ = make_frontend()
    h = fe.submit([1] * 8, max_new_tokens=4)
    fe.router.replicas[0].mark_dead("test")
    with pytest.raises(NoHealthyReplicaError):
        fe.run_until_idle()
    # the handle fails too, so consumer threads parked in stream()/
    # result() unblock instead of waiting on a queue forever
    assert h.status == "failed"
    with pytest.raises(NoHealthyReplicaError):
        h.result()


def test_snapshot_has_serving_sections():
    fe, _ = make_frontend()
    fe.submit([1] * 8, max_new_tokens=4)
    fe.run_until_idle()
    snap = fe.snapshot()
    assert set(snap["queues"]) == {"interactive", "batch", "background"}
    assert snap["classes"]["interactive"]["completed"] == 1
    assert "router" in snap and snap["router"]["replicas"][0]["healthy"]
    assert "params" in snap


def test_snapshot_degrades_instead_of_deadlocking_when_lock_held():
    """REVIEW regression: snapshot() is a flight-recorder context
    provider, evaluated by the watchdog's dump() BEFORE trip listeners
    fire — exactly when a wedged pump thread may still hold the
    front-end lock.  It must time out into a best-effort lock-free
    view, never block the watchdog (no bundle, replicas never drained)."""
    import threading

    fe, _ = make_frontend()
    fe.submit([1] * 8, max_new_tokens=4)
    fe.run_until_idle()
    fe._snapshot_lock_timeout_s = 0.05
    held, release = threading.Event(), threading.Event()

    def wedged_pump():
        with fe._lock:
            held.set()
            release.wait(5.0)

    t = threading.Thread(target=wedged_pump, daemon=True)
    t.start()
    assert held.wait(5.0)
    try:
        snap = fe.snapshot()
    finally:
        release.set()
        t.join(5.0)
    assert "lock held" in snap["degraded"]
    # the best-effort view still carries the forensic sections
    assert snap["classes"]["interactive"]["completed"] == 1
    assert snap["router"]["replicas"][0]["healthy"]
    # uncontended: full snapshot, no degraded marker
    assert "degraded" not in fe.snapshot()


def test_degraded_snapshot_survives_torn_section():
    """The lock-timeout holder may be a LIVE pump (long device call,
    not wedged) still mutating state: a section raising on a torn read
    must cost that section only, not the whole serving view."""
    import threading

    fe, _ = make_frontend()
    fe._snapshot_lock_timeout_s = 0.05
    fe.metrics.snapshot = lambda: (_ for _ in ()).throw(
        RuntimeError("deque mutated during iteration"))
    held, release = threading.Event(), threading.Event()

    def busy_pump():
        with fe._lock:
            held.set()
            release.wait(5.0)

    t = threading.Thread(target=busy_pump, daemon=True)
    t.start()
    assert held.wait(5.0)
    try:
        snap = fe.snapshot()
    finally:
        release.set()
        t.join(5.0)
    assert "deque mutated" in snap["section_errors"][0]
    # the other sections survived
    assert set(snap["queues"]) == {"interactive", "batch", "background"}
    assert snap["router"]["replicas"][0]["healthy"]
    assert "params" in snap and "degraded" in snap


def test_stream_buffer_really_bounds_unread_tokens():
    """stream_buffer is a REAL bound: a consumer that never reads keeps
    only the newest tokens (drop-oldest) plus completion, and the pump
    never blocks on the stalled stream."""
    params = ServingParams(stream_buffer=4)
    fe, _ = make_frontend(params=params)
    prompt = [5, 6, 7, 8]
    h = fe.submit(prompt, max_new_tokens=12)
    fe.run_until_idle()        # consumer never reads while pumping
    assert h.status == "done"
    assert h.delivered == 12   # every token was pushed...
    want = [synthetic_token(prompt, i) for i in range(12)]
    # ...but the buffer retained only the newest 3: 4 slots, one
    # reclaimed by the completion sentinel — and the loss is VISIBLE
    assert h.result() == want[-3:]
    assert h.dropped == 9


@pytest.mark.parametrize("held, pushed, kept, dropped", [
    ([], [1, 2, 3], [1, 2, 3], 0),                 # room for the round
    ([1, 2, 3], [4, 5], [2, 3, 4, 5], 1),          # the oldest unread goes
    ([1, 2], [3, 4, 5, 6, 7, 8], [5, 6, 7, 8], 4),  # a round over the buffer
])
def test_a_rounds_tokens_are_pushed_and_drained_at_once(held, pushed, kept,
                                                        dropped):
    """A stream is handed a round's tokens under one lock (a decode burst
    gives a stream 8 at once, 256 streams a round) with the bound of the
    token-at-a-time path, and ``drain`` takes the whole buffer, the
    completion mark apart."""
    fe, _ = make_frontend(params=ServingParams(stream_buffer=4))
    h = fe.submit([5, 6, 7], max_new_tokens=4)
    for tok in held:
        h._push(tok)
    h._push_many(pushed)
    assert h.dropped == dropped
    assert h.drain() == (kept, False)
    assert h.drain() == ([], False)         # empty: no lock taken
    h._push_many([9])
    h._finish("done")
    assert h.drain() == ([9], True)
    fe.close()


def test_serving_metrics_published_to_telemetry():
    from deepspeed_tpu.telemetry import get_telemetry, parse_prometheus_text

    get_telemetry().configure(enabled=True, jsonl=False, prometheus=True)
    fe, _ = make_frontend()
    fe.submit([1] * 8, max_new_tokens=4)
    fe.run_until_idle()
    parsed = parse_prometheus_text(get_telemetry().prometheus_text())
    assert parsed["serving_interactive_submitted"] == 1
    assert "serving_interactive_ttft_p99_ms" in parsed
    assert "serving_prefix_hit_rate" in parsed
    # pool gauges ride the scheduler's plan_step publish path
    assert "serving_kv_pages_free" in parsed
    assert "serving_kv_pages_cached" in parsed


def test_pump_mode_fails_pending_when_all_replicas_die():
    """start()/pump() mode has no caller to raise to: pending handles
    must FAIL (unblocking consumers parked in stream()/result()), not
    hang forever."""
    fe, _ = make_frontend()
    h = fe.submit([1] * 8, max_new_tokens=4)
    fe.router.replicas[0].mark_dead("test")
    assert fe.pump() == 0
    assert h.status == "failed"
    with pytest.raises(NoHealthyReplicaError, match="replica0"):
        h.result()
    assert fe.metrics.counters["failed"] == 1


def test_page_blocked_interactive_does_not_preempt():
    """Preemption retains the victim's KV pages, so it can never help a
    PAGE-blocked head — preempting there used to livelock the service
    (victim bumped, pages never freed, strict priority blocks its
    resume forever)."""
    params = ServingParams(interactive_reserve_frac=0.0)
    fe, _ = make_frontend(slots=2, num_blocks=16, params=params)
    bgp = [1] * 112
    bg = fe.submit(bgp, max_new_tokens=96, klass="background")  # 13 pages
    for _ in range(3):
        fe.pump()
    assert bg.status == "running"
    # needs 13 fresh pages, only 2 free: page-blocked with a FREE slot
    inter = fe.submit([2] * 112, max_new_tokens=96, klass="interactive")
    fe.run_until_idle()
    assert fe.metrics.counters["preemptions"] == 0
    assert bg.status == "done" and inter.status == "done"
    assert bg.result() == [synthetic_token(bgp, i) for i in range(96)]


def test_preempted_victim_resumes_when_interactive_head_is_page_blocked():
    """A preempted victim holds its pages.  When the interactive head
    cannot admit (pages) and NOTHING is seated, strict priority must
    yield — only the victim's completion can free the pages the head
    is waiting on."""
    fe, _ = make_frontend(slots=1, num_blocks=32)
    bgp = [3] * 64
    bg = fe.submit(bgp, max_new_tokens=96, klass="background")  # 10 pages
    for _ in range(3):
        fe.pump()
    assert bg.status == "running"
    # slot-blocked (pages fine): legitimately preempts bg
    i1 = fe.submit([4] * 16, max_new_tokens=16, klass="interactive")
    fe.pump()
    assert fe.metrics.counters["preemptions"] == 1
    assert bg.status == "queued" and bg.preempted
    # queue a head too big for the pages left while bg's are held
    i2 = fe.submit([5] * 304, max_new_tokens=96, klass="interactive")
    fe.run_until_idle()
    assert all(h.status == "done" for h in (bg, i1, i2))
    assert bg.result() == [synthetic_token(bgp, i) for i in range(96)]


def test_close_detaches_recorder_and_watchdog():
    from deepspeed_tpu.telemetry import HangWatchdog, get_flight_recorder

    fe, _ = make_frontend()
    wd = HangWatchdog(hang_timeout_s=1e9)
    fe.attach_watchdog(wd)
    assert "serving" in get_flight_recorder()._context_providers
    assert wd._trip_listeners
    fe.close()
    assert "serving" not in get_flight_recorder()._context_providers
    assert not wd._trip_listeners
