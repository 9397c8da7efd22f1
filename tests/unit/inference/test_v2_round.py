"""One program a round: the decode step that carries the round's prefill
chunks.  The logits every call samples from (a debug hook on ``_sample``),
row by row, against the plain float32 forward pass of each family: a dense
model whose sliding window is crossed, OLMoE, and the hybrid model with a
ring that turns and a sink; rounds with chunks beside decoding rows, rounds
with chunks alone (the decode rows dead), bursts.  And what ``_settle``
does with a chunk whose request was cancelled or preempted while the call
that wrote it was running."""

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
            eng.step_ahead()
            if eng._inflight is None:
                continue
            chunks, decode, burst = eng._inflight[:3]
            calls.append((len(chunks), len(decode), burst))
            at = {r.uid: len(r.generated) for r in decode}
            jax.effects_barrier()
            steps, seen[:] = list(seen), []
            assert len(steps) == burst
            lead = eng.prefill_batch if chunks else 0
            assert steps[0].shape[0] == lead + eng.max_slots
            for i, ch in enumerate(chunks):
                if ch.is_last:
                    rows[index[ch.request.uid], 0] = steps[0][i]
            for t, logits in enumerate(steps):
                for r in decode:
                    rows[index[r.uid], at[r.uid] + t] = logits[lead + r.slot]
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
    assert eng.step_ahead() == 0 and len(eng._inflight[0]) == 2
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
    assert eng._inflight is None                     # nothing to run
    assert eng.scheduler.resume(req)
    assert eng.step_ahead() == 0                     # chunk 1 again
    assert eng._inflight[0][0].start_pos == CHUNK
    eng.scheduler.preempt(req)
    assert eng.scheduler.resume(req)                 # back before the step
    assert eng.step_ahead() == CHUNK and req.prefilled == 2 * CHUNK
    while eng.scheduler.has_work:
        eng.step()
    assert req.generated == want
