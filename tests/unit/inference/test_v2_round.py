"""One program a round: the decode step that carries the round's prefill
chunks.  The logits every call samples from (a debug hook on ``_sample``),
row by row, against the plain float32 forward pass of each family: a dense
model whose sliding window is crossed, OLMoE, and the hybrid model with a
ring that turns and a sink; rounds with chunks beside decoding rows, rounds
with chunks alone (the decode rows dead), bursts.  And what ``_settle``
does with a chunk whose request was cancelled or preempted while the call
that wrote it was running.

A second call in flight (ISSUE 39): ``step_ahead`` dispatches a round's
call before it fetches the last one, so a round is planned from what was
dispatched and fed, on the device, the tokens the host has not seen.  Driven
to the end it serves what a loop of ``step`` serves; a request that ends,
is cancelled, preempted or moved while two calls are uncommitted keeps of
them what it was packed under and nothing else."""

import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import KVCacheConfig, build_engine_v2
from deepspeed_tpu.inference.v2 import engine_v2 as ev2
from deepspeed_tpu.inference.v2.scheduler import RequestState
from deepspeed_tpu.models import LlamaConfig, LlamaModel

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[3]))
from perfbench import manifest  # noqa: E402

PAGE, CHUNK = 4, 8


def _dense():
    """Three layers, GQA, a window of 8: every context below crosses it."""
    model = LlamaModel(LlamaConfig.tiny(
        num_layers=3, max_seq_len=128, dtype=jnp.float32, num_kv_heads=4,
        sliding_window=8))
    params = model.init_params(jax.random.PRNGKey(7))
    return model, params, lambda ids: model.forward(params, ids[None])[0]


def _olmoe():
    family = manifest.load_module("models", "olmoe")
    cfg = {"vocab_size": 512, "hidden_size": 128, "intermediate_size": 128,
           "num_hidden_layers": 2, "num_attention_heads": 4,
           "num_key_value_heads": 4, "max_position_embeddings": 256,
           "rope_theta": 10000, "rms_norm_eps": 1e-5,
           "tie_word_embeddings": False, "num_experts": 8,
           "num_experts_per_tok": 3, "norm_topk_prob": False,
           "run": {"dtype": "float32"}}
    model = family.build(cfg)
    params = model.init_params(jax.random.PRNGKey(3))
    return model, params, lambda ids: family.forward(params, cfg,
                                                     ids[None])[0]


def _hybrid():
    """[full + dense, window, window, full], window 8 with a sink, K 24 /
    V 16, sigmoid routing with a bias: ``test_v2_hybrid``'s model."""
    from test_v2_hybrid import FAMILY, TINY

    model = FAMILY.build(TINY)
    params = model.init_params(jax.random.PRNGKey(7))
    return model, params, lambda ids: FAMILY.forward(params, TINY,
                                                     ids[None])[0]


FAMILIES = {"dense_window": _dense, "olmoe": _olmoe, "hybrid_ring": _hybrid}


def _serve_with_logits(model, params, prompts, new, **engine_kw):
    """The requests served through ``step_ahead``; returns (requests,
    {(request index, token index): the logits row that token was sampled
    from}, [(chunks, rows decoding, steps) of every call])."""
    seen = []
    real = ev2._sample

    def spy(logits, temperature, key):
        jax.debug.callback(lambda l: seen.append(np.asarray(l)), logits,
                           ordered=True)
        return real(logits, temperature, key)

    mp = pytest.MonkeyPatch()
    mp.setattr(ev2, "_sample", spy)
    try:
        eng = build_engine_v2(
            model, params,
            cache_config=KVCacheConfig(num_blocks=96, block_size=PAGE,
                                       max_seq_len=128),
            prefill_chunk=CHUNK, **engine_kw)
        reqs = [eng.put(p, n) for p, n in zip(prompts, new)]
        index = {r.uid: i for i, r in enumerate(reqs)}
        rows, calls = {}, []
        while eng.scheduler.has_work:
            sent = eng._calls
            eng.step_ahead()
            if eng._calls == sent:
                continue                        # nothing to plan
            chunks, decode, burst = eng._inflight[-1][:3]
            calls.append((len(chunks), len(decode), burst))
            # a row's position is that of the token it is fed
            at = {row.request.uid: row.position - len(row.request.prompt) + 1
                  for row in decode}
            jax.effects_barrier()
            steps, seen[:] = list(seen), []
            assert len(steps) == burst
            lead = eng.prefill_batch if chunks else 0
            assert steps[0].shape[0] == lead + eng.max_slots
            for i, ch in enumerate(chunks):
                if ch.is_last:
                    rows[index[ch.request.uid], 0] = steps[0][i]
            for t, logits in enumerate(steps):
                for r, slot, _ in decode:
                    if at[r.uid] + t < r.max_new_tokens:
                        rows[index[r.uid], at[r.uid] + t] = logits[lead + slot]
        assert eng.settle() == 0
    finally:
        mp.undo()
    return reqs, rows, calls


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_round_of_chunks_and_decode_rows_samples_the_references_logits(
        family):
    """Four ragged requests over three slots: chunks ride beside decoding
    rows (two a step and one, at different depths of their prompts), the
    first round and a late one carry chunks alone, bursts run between;
    every token of every request was sampled from the logits of the
    float32 forward pass over what came before it."""
    model, params, forward = FAMILIES[family]()
    rng = np.random.RandomState(4)
    vocab = model.config.vocab_size
    lens, new = (5, 21, 30, 11), (22, 9, 6, 5)
    prompts = [rng.randint(1, vocab, size=n).tolist() for n in lens]
    with jax.default_matmul_precision("highest"):
        reqs, rows, calls = _serve_with_logits(
            model, params, prompts, new, max_batch_slots=3, prefill_batch=2,
            decode_burst=2)
        # the rounds this is about all happened
        assert any(c == 2 and d >= 1 for c, d, _ in calls)
        assert any(c == 1 and d >= 1 for c, d, _ in calls)
        assert any(c and not d for c, d, _ in calls)
        assert any(not c and b == 2 for c, _, b in calls)
        assert all(b == 1 for c, _, b in calls if c)
        for i, (req, prompt) in enumerate(zip(reqs, prompts)):
            assert len(req.generated) == new[i]
            ids = jnp.asarray(prompt + req.generated[:-1])
            want = np.asarray(forward(ids))[len(prompt) - 1:]
            got = np.stack([rows[i, j] for j in range(new[i])])
            assert np.abs(got - want).max() < 2e-4, (family, i)
            assert np.argmax(want, axis=-1).tolist() == req.generated


def test_a_chunk_whose_request_was_cancelled_in_flight_is_passed_over():
    """``step_ahead`` returns with the chunks' call running; a request
    cancelled before the next step is not handed its chunk (its pages have
    gone back), the chunk beside it is committed as ever."""
    model, params, _ = _dense()
    rng = np.random.RandomState(8)
    prompts = [rng.randint(1, 512, size=n).tolist() for n in (19, 6)]
    eng = build_engine_v2(
        model, params,
        cache_config=KVCacheConfig(num_blocks=48, block_size=PAGE,
                                   max_seq_len=128),
        max_batch_slots=2, prefill_chunk=CHUNK, prefill_batch=2,
        decode_burst=2)
    gone, stays = (eng.put(p, 4) for p in prompts)
    assert eng.step_ahead() == 0 and len(eng._inflight[-1].chunks) == 2
    eng.scheduler.cancel(gone)
    assert eng.step_ahead() == len(prompts[1])       # the other's chunk
    assert gone.prefilled == 0 and gone.state is RequestState.DONE
    assert len(stays.generated) == 1
    while eng.scheduler.has_work:
        eng.step()
    want = build_engine_v2(
        model, params,
        cache_config=KVCacheConfig(num_blocks=48, block_size=PAGE,
                                   max_seq_len=128),
        max_batch_slots=2, prefill_chunk=CHUNK).generate([prompts[1]], 4)[0]
    assert stays.generated == want
    assert eng.scheduler.allocator.num_free == 47


def test_a_chunk_whose_request_was_preempted_in_flight_is_prefilled_again():
    """Preempted between the rounds (the serving scheduler keeps its pages
    and its cursor), a request's chunk in flight is passed over by the
    commit; resumed later, it prefills that chunk again and serves the
    tokens it serves undisturbed.  Preempted AND resumed before the next
    step it is where the call left it, and the chunk counts."""
    from deepspeed_tpu.serving.scheduler import ServingScheduler

    model, params, _ = _dense()
    prompt = np.random.RandomState(12).randint(1, 512, size=21).tolist()

    def build():
        return build_engine_v2(
            model, params,
            cache_config=KVCacheConfig(num_blocks=48, block_size=PAGE,
                                       max_seq_len=128),
            max_batch_slots=2, prefill_chunk=CHUNK, decode_burst=2,
            scheduler_factory=ServingScheduler)

    want = build().generate([prompt], 5)[0]
    eng = build()
    req = eng.put(prompt, 5)
    assert eng.step_ahead() == 0                     # chunk 0, running
    assert eng.step_ahead() == CHUNK                 # chunk 1, running
    eng.scheduler.preempt(req)
    assert req.state is RequestState.WAITING
    assert eng.step_ahead() == 0 and req.prefilled == CHUNK   # passed over
    assert not eng._inflight                         # nothing to run
    assert eng.scheduler.resume(req)
    assert eng.step_ahead() == 0                     # chunk 1 again
    assert eng._inflight[-1].chunks[0].start_pos == CHUNK
    eng.scheduler.preempt(req)
    assert eng.scheduler.resume(req)                 # back before the step
    assert eng.step_ahead() == CHUNK and req.prefilled == 2 * CHUNK
    while eng.scheduler.has_work:
        eng.step()
    assert req.generated == want


# -- a second call in flight -------------------------------------------------

def _engine(model, params, slots, scheduler=None, burst=2):
    return build_engine_v2(
        model, params,
        cache_config=KVCacheConfig(num_blocks=96, block_size=PAGE,
                                   max_seq_len=128),
        max_batch_slots=slots, prefill_chunk=CHUNK, prefill_batch=2,
        decode_burst=burst, scheduler_factory=scheduler)


def _pages_out(eng):
    """Pages no request should hold once all are done (the prefix cache
    keeps indexed prompt pages revivable: they count as back)."""
    alloc = eng.scheduler.allocator
    return 95 - alloc.num_free - getattr(alloc, "num_cached", 0)


def _ahead(eng, **sampling):
    """``step_ahead`` to the end: never more than one call between rounds."""
    while eng.scheduler.has_work:
        eng.step_ahead(**sampling)
        assert len(eng._inflight) <= 1
    eng.settle()


def _looped(eng, **sampling):
    while eng.scheduler.has_work:
        eng.step(**sampling)
        assert not eng._inflight


@pytest.fixture
def hub():
    """The telemetry hub on, in memory; ``hub(name)`` reads a counter."""
    from deepspeed_tpu import telemetry

    tel = telemetry.get_telemetry()
    tel.reset()
    tel.configure(enabled=True, jsonl=False, prometheus=False)

    def read(name):
        metric = tel.registry.metrics().get(name)
        return 0.0 if metric is None else metric.value

    yield read
    tel.reset()


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_step_ahead_to_the_end_serves_what_a_loop_of_step_serves(family):
    """Six ragged requests over three slots, greedy: slots are re-seated,
    budgets end inside bursts, first tokens and decode tokens are fed back
    on the device; request for request the tokens are those of ``step`` in
    a loop, and every page comes back."""
    model, params, _ = FAMILIES[family]()
    rng = np.random.RandomState(5)
    vocab = model.config.vocab_size
    lens, new = (5, 21, 30, 11, 7, 9), (22, 9, 6, 5, 12, 1)
    prompts = [rng.randint(1, vocab, size=n).tolist() for n in lens]
    served = []
    for drive in (_looped, _ahead):
        eng = _engine(model, params, slots=3)
        reqs = [eng.put(p, n) for p, n in zip(prompts, new)]
        drive(eng)
        assert [len(r.generated) for r in reqs] == list(new)
        assert _pages_out(eng) == 0
        served.append(([r.generated for r in reqs], eng._calls))
    assert served[0][0] == served[1][0]
    # a freed slot is seen a round later: a call or two more, no fewer
    assert served[0][1] <= served[1][1] <= served[0][1] + len(prompts)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_sampled_tokens_are_the_loops_where_both_orders_make_the_same_calls(
        family):
    """As many slots as requests, all there from the start: no slot is
    re-seated, so planning from what was dispatched makes the very calls
    that planning from what was committed makes, each under the same key
    of the chain; at a temperature the sampled tokens are the same."""
    model, params, _ = FAMILIES[family]()
    rng = np.random.RandomState(6)
    vocab = model.config.vocab_size
    prompts = [rng.randint(1, vocab, size=n).tolist() for n in (5, 21, 12)]
    served = []
    for drive in (_looped, _ahead):
        eng = _engine(model, params, slots=3)
        eng._reseed(3)
        reqs = [eng.put(p, n) for p, n in zip(prompts, (9, 4, 7))]
        drive(eng, temperature=0.8)
        served.append(([r.generated for r in reqs], eng._calls))
    assert served[0] == served[1]
    greedy = _engine(model, params, slots=3).generate(prompts, 9)
    assert served[0][0][0] != greedy[0]           # it did sample


def test_a_request_that_ends_inside_a_call_keeps_nothing_of_the_next(hub):
    """With an EOS id a request can end inside call N after N + 1 went out
    with its row: that row is passed over and counted, the request's
    tokens end at the EOS as a loop of ``step`` ends them, and the row,
    whose burst runs past the budget, wrote nothing behind ``max_pos``."""
    model, params, _ = _dense()
    rng = np.random.RandomState(9)
    prompts = [rng.randint(1, 512, size=n).tolist() for n in (5, 13)]
    new = (10, 12)          # 5 + 10 = 15 positions: one left in page four
    plain = _engine(model, params, slots=2, burst=4).generate(prompts, 12)
    # the first request's sixth token: inside its second burst
    eos = plain[0][5]
    assert eos not in plain[0][:5]
    want = []
    for toks, n in zip(plain, new):
        toks = toks[:n]
        want.append(toks[:toks.index(eos) + 1] if eos in toks else toks)
    loop = _engine(model, params, slots=2, burst=4)
    reqs = [loop.put(p, n) for p, n in zip(prompts, new)]
    _looped(loop, eos_token_id=eos)
    assert [r.generated for r in reqs] == want

    calls = hub("inference/calls")               # the two loops': not ahead
    assert hub("inference/calls_dispatched_ahead") == 0
    eng = _engine(model, params, slots=2, burst=4)
    first, other = (eng.put(p, n) for p, n in zip(prompts, new))
    while first.state is not RequestState.DONE:
        blocks = list(first.blocks)
        eng.step_ahead(eos_token_id=eos)
    # the call behind the one that held the EOS carries the row still
    assert any(row.request is first for row in eng._inflight[-1].decode)
    _ahead(eng, eos_token_id=eos)
    assert [first.generated, other.generated] == want
    assert hub("inference/rows_overrun") >= 1
    assert hub("inference/calls_dispatched_ahead") \
        == hub("inference/calls") - calls - 1
    # position 15 of the request's pages: behind max_pos, never written
    assert len(blocks) == 4
    for name in ("k", "v"):
        page = np.asarray(eng.pool["kv"][name][:, blocks[-1]])
        assert page[:, :3].any() and not page[:, 3:].any()
    assert _pages_out(eng) == 0


def _serving_engine(model, params, slots=3):
    from deepspeed_tpu.serving.scheduler import ServingScheduler

    return _engine(model, params, slots, scheduler=ServingScheduler)


def _two_decoding(model, params, prompts, new):
    """An engine with the first two prompts' requests decoding and a call
    with a row of each in flight; (engine, requests)."""
    eng = _serving_engine(model, params)
    reqs = [eng.put(p, n) for p, n in zip(prompts[:2], new)]
    while not all(len(r.generated) >= 2 for r in reqs):
        eng.step_ahead()
    rows = {row.request.uid: row.slot for row in eng._inflight[-1].decode}
    assert rows == {reqs[0].uid: 0, reqs[1].uid: 1}
    return eng, reqs


@pytest.fixture(scope="module")
def alone():
    """Three prompts and what each is answered alone, greedy."""
    model, params, _ = _dense()
    rng = np.random.RandomState(14)
    prompts = [rng.randint(1, 512, size=n).tolist() for n in (6, 17, 10)]
    new = (14, 12, 8)
    want = [_serving_engine(model, params).generate([p], n)[0]
            for p, n in zip(prompts, new)]
    return model, params, prompts, new, want


def test_a_row_of_a_cancelled_request_is_passed_over(alone, hub):
    model, params, prompts, new, want = alone
    eng, (gone, stays) = _two_decoding(model, params, prompts, new)
    had = list(gone.generated)
    eng.scheduler.cancel(gone)
    late = eng.put(prompts[2], new[2])            # into the freed slot
    _ahead(eng)
    assert gone.generated == had == want[0][:len(had)]
    assert [stays.generated, late.generated] == want[1:]
    assert hub("inference/rows_overrun") == 1
    assert _pages_out(eng) == 0


def test_a_request_moved_to_another_slot_is_committed_where_it_was_packed(
        alone, hub):
    """Preempted with a row in flight in slot 0, another request seated in
    slot 0 at once, then resumed into slot 2: the row in flight is passed
    over (its request is not in the row's slot), the request decodes that
    position again from what was fetched, in its new slot, and the
    newcomer in slot 0 never receives its predecessor's token."""
    model, params, prompts, new, want = alone
    eng, (moved, stays) = _two_decoding(model, params, prompts, new)
    sched = eng.scheduler
    sched.preempt(moved)
    assert moved.ahead_tokens == 0 and moved.slot == -1
    late = eng.put(prompts[2], new[2])
    assert sched.admit_now(late) and late.slot == 0
    assert sched.resume(moved) and moved.slot == 2
    had = len(moved.generated)
    eng.step_ahead()        # a call with slot 2's row out; slot 0's passed
    assert len(moved.generated) == had and hub("inference/rows_overrun") == 1
    assert {row.slot for row in eng._inflight[-1].decode
            if row.request is moved} == {2}
    _ahead(eng)
    assert [moved.generated, stays.generated, late.generated] == want
    assert hub("inference/rows_overrun") == 1
    assert _pages_out(eng) == 0


def test_a_row_of_a_request_preempted_and_released_is_passed_over(alone, hub):
    """``preempt_release`` retires the request and frees its pages with a
    row of it in flight; its handle's fresh admission (the same prompt,
    here into the same slot and, the free list being a stack, the same
    pages) prefills again and is answered as if alone."""
    model, params, prompts, new, want = alone
    eng, (retired, stays) = _two_decoding(model, params, prompts, new)
    had = list(retired.generated)
    assert eng.scheduler.preempt_release(retired) == 5
    again = eng.put(prompts[0], new[0])
    _ahead(eng)
    assert retired.generated == had and retired.state is RequestState.DONE
    assert [again.generated, stays.generated] == want[:2]
    assert hub("inference/rows_overrun") == 1
    assert _pages_out(eng) == 0


def test_settle_leaves_nothing_in_flight_and_step_is_complete(alone):
    model, params, prompts, new, want = alone
    eng = _serving_engine(model, params)
    assert eng.settle() == 0 and not eng._inflight
    req = eng.put(prompts[1], new[1])
    assert eng.step_ahead() == 0 and len(eng._inflight) == 1
    assert eng.step_ahead() == CHUNK and len(eng._inflight) == 1
    # two chunks are out, one is committed; settle commits the other
    assert (req.prefilled, req.planned_prefilled) == (CHUNK, 2 * CHUNK)
    assert eng.settle() == CHUNK and not eng._inflight
    assert req.prefilled == req.planned_prefilled == 2 * CHUNK
    assert eng.settle() == 0
    seen = req.length
    while eng.scheduler.has_work:
        assert eng.step() > 0 and not eng._inflight
        assert req.length > seen                  # complete on return
        assert (req.ahead_prefilled, req.ahead_tokens) == (0, 0)
        seen = req.length
    assert req.generated == want[1]
