"""A model whose layers are of several kinds served through the paged
engine: a leading full-attention dense layer, window layers with a sink
whose pages are recycled in a ring, a full layer, K and V rows of unequal
width, 2 and 4 KV heads, two rotary bases on part of a row, sigmoid
routing with a choice bias over experts of which a share is held.  The
logits the two programs sample from, prefill and then decoding through
both pools, against the plain float32 reference's full forward pass."""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import KVCacheConfig, build_engine_v2
from deepspeed_tpu.inference.v2 import engine_v2 as ev2

sys.path.insert(0, str(__import__("pathlib").Path(__file__).parents[3]))
from perfbench import manifest  # noqa: E402

FAMILY = manifest.load_module("models", "mimo_v2")

#: [full + dense, window, window, full], window 8, every mechanism live
TINY = dict(
    vocab_size=256, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_attention_heads=8, head_dim=24,
    v_head_dim=16, num_key_value_heads=2, swa_num_key_value_heads=4,
    sliding_window=8, partial_rotary_factor=0.334, rope_theta=1e7,
    swa_rope_theta=1e4, attention_value_scale=0.707, layernorm_epsilon=1e-5,
    published={"n_routed_experts": 32}, n_routed_experts=8, expert_rank=1,
    num_experts_per_tok=3, norm_topk_prob=True,
    hybrid_layer_pattern=[0, 1, 1, 0], moe_layer_freq=[0, 1, 1, 1],
    max_position_embeddings=256, run={"dtype": "float32"})
PAGE, CHUNK = 4, 8
PROMPT, NEW = 43, 40            # 83 positions: ten windows of 8


@pytest.fixture(scope="module")
def served():
    """One request through the engine with the logits every call sampled
    from: ``(ids [PROMPT + NEW], logits [NEW, V], engine, request)``; row
    ``i`` of the logits is what token ``PROMPT + i`` was the argmax of."""
    model = FAMILY.build(TINY)
    params = model.init_params(jax.random.PRNGKey(7))
    seen = []
    real = ev2._sample

    def spy(logits, temperature, key):
        jax.debug.callback(lambda l: seen.append(np.asarray(l)), logits,
                           ordered=True)
        return real(logits, temperature, key)

    mp = pytest.MonkeyPatch()
    mp.setattr(ev2, "_sample", spy)
    eng = build_engine_v2(
        model, params,
        cache_config=KVCacheConfig(num_blocks=64, block_size=PAGE,
                                   max_seq_len=128),
        max_batch_slots=2, prefill_chunk=CHUNK, prefill_batch=1,
        decode_burst=4)
    prompt = np.random.RandomState(3).randint(0, 256, size=PROMPT).tolist()
    req = eng.put(prompt, NEW)
    pages = []
    while eng.scheduler.has_work:
        eng.step()
        pages.append(eng.scheduler.ring_pages_in_use())
    jax.effects_barrier()
    mp.undo()
    chunks = -(-PROMPT // CHUNK)
    # a prefill call's logits are its last valid token's; the last chunk's
    # are the first sampled token's; then a row of slot 0 a decode step
    logits = [seen[chunks - 1][0]] + [l[0] for l in seen[chunks:]]
    ids = np.asarray(prompt + req.generated)
    return (ids, np.stack(logits[:NEW]), eng, params, max(pages))


def _reference(params, ids, **changed):
    cfg = dict(TINY, **changed)
    return np.asarray(FAMILY.forward(params, cfg, jnp.asarray(ids)[None])[0])


def test_served_logits_are_the_references(served):
    ids, logits, eng, params, _ = served
    assert len(ids) == PROMPT + NEW
    want = _reference(params, ids[:-1])[PROMPT - 1:]
    assert np.abs(logits - want).max() < 2e-4
    assert eng.last_attn_path == "reference"          # the CPU's path
    assert sorted(eng.pool) == ["full", "window"]


WRONG = {
    "window_off_by_one": dict(sliding_window=9),
    "value_scale_dropped": dict(attention_value_scale=1.0),
    "weights_not_normalised": dict(norm_topk_prob=False),
}


@pytest.mark.parametrize("name", sorted(WRONG))
def test_a_reference_with_one_key_changed_is_told_apart(served, name):
    ids, logits, _, params, _ = served
    wrong = _reference(params, ids[:-1], **WRONG[name])[PROMPT - 1:]
    assert np.abs(logits - wrong).max() > 5e-3, name


def _without(params, leaf):
    """The weights with every ``leaf`` (``sink`` or ``bias``) at what
    ignoring it computes: a sink that takes no mass, a bias of zero."""
    fill = -jnp.inf if leaf == "sink" else 0.0

    def walk(tree):
        if isinstance(tree, dict):
            return {k: (jnp.full_like(v, fill) if k == leaf else walk(v))
                    for k, v in tree.items()}
        if isinstance(tree, list):
            return [walk(v) for v in tree]
        return tree

    return walk(params)


@pytest.mark.parametrize("leaf", ["sink", "bias"])
def test_ignoring_the_sink_or_the_choice_bias_is_told_apart(served, leaf):
    ids, logits, _, params, _ = served
    wrong = _reference(_without(params, leaf), ids[:-1])[PROMPT - 1:]
    assert np.abs(logits - wrong).max() > 5e-3, leaf


def test_softmax_for_sigmoid_is_told_apart(served, monkeypatch):
    ids, logits, _, params, _ = served
    monkeypatch.setattr(jax.nn, "sigmoid",
                        lambda x: jax.nn.softmax(x, axis=-1))
    wrong = _reference(params, ids[:-1])[PROMPT - 1:]
    assert np.abs(logits - wrong).max() > 5e-3


def test_window_layers_hold_a_ring_and_no_more(served):
    """Ten windows long, the sequence never held more than its ring in the
    window layers' pool, and that pool has a ring a slot and page 0: the
    logits above are those of a cache that kept every key."""
    _, _, eng, _, most = served
    cc = eng.cache_config
    assert cc.ring_blocks == -(-8 // PAGE) + CHUNK // PAGE == 4
    assert most == cc.ring_blocks < -(-(PROMPT + NEW) // PAGE)
    assert eng.pool["window"]["k"].shape[:2] == (2, 1 + 2 * cc.ring_blocks)
    assert eng.pool["full"]["k"].shape[:2] == (2, 64)
    # K rows of 24 and V rows of 16 as they are (under one lane row)
    assert eng.pool["full"]["k"].shape[2:] == (PAGE, 2, 24)
    assert eng.pool["window"]["v"].shape[2:] == (PAGE, 4, 16)
    # every ring went back
    assert len(eng.scheduler._free_rings) == cc.num_rings == 2


def test_two_sequences_keep_to_their_own_rings():
    """Ragged batch, both slots live, a burst of 4: each sequence's tokens
    are the ones it generates alone."""
    model = FAMILY.build(TINY)
    params = model.init_params(jax.random.PRNGKey(11))
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, 256, size=n).tolist() for n in (9, 30)]

    def run(batch):
        eng = build_engine_v2(
            model, params,
            cache_config=KVCacheConfig(num_blocks=64, block_size=PAGE,
                                       max_seq_len=128),
            max_batch_slots=2, prefill_chunk=CHUNK, prefill_batch=2,
            decode_burst=4)
        return eng.generate(batch, max_new_tokens=24)

    together = run(prompts)
    assert together == [run([p])[0] for p in prompts]


def test_k_rows_wider_than_a_lane_row_are_served_from_two_planes():
    """K rows of 160 lie in two 128-lane planes (the published 192 do):
    the pool has a block a plane of a layer, prefill scatters and gathers
    both, the decode path reads both; the tokens are the reference's
    argmax all the way."""
    cfg = dict(TINY, head_dim=160, v_head_dim=32, partial_rotary_factor=0.4)
    model = FAMILY.build(cfg)
    params = model.init_params(jax.random.PRNGKey(2))
    eng = build_engine_v2(
        model, params,
        cache_config=KVCacheConfig(num_blocks=32, block_size=PAGE,
                                   max_seq_len=64),
        max_batch_slots=2, prefill_chunk=CHUNK, prefill_batch=2,
        decode_burst=4)
    assert eng.pool["full"]["k"].shape == (2 * 2, 32, PAGE, 2, 128)
    assert eng.pool["full"]["v"].shape == (2, 32, PAGE, 2, 32)
    assert eng.pool["window"]["k"].shape[0] == 2 * 2
    prompt = np.random.RandomState(9).randint(0, 256, size=21).tolist()
    (tokens,) = eng.generate([prompt], max_new_tokens=14)
    ids = np.asarray(prompt + tokens)
    logits = np.asarray(FAMILY.forward(params, cfg,
                                       jnp.asarray(ids[:-1])[None])[0])
    best = logits[len(prompt) - 1:]
    chosen = best[np.arange(len(tokens)), tokens]
    assert (best.max(axis=1) - chosen).max() < 1e-4
    # plane 1's lanes beyond 160 hold zeros, plane 0's are written
    k = np.asarray(eng.pool["full"]["k"])
    assert np.abs(k[0]).max() > 0 and not k[2:, ..., 32:].any()
    assert np.abs(k[2, ..., :32]).max() > 0


def test_a_refused_shape_on_the_chip_is_reported_and_counted(monkeypatch):
    """Where the compiled kernel refuses a pool's rows (OPT's heads of 64,
    no whole lane row) the engine says ``reference`` on a TPU as anywhere,
    and a counter advances for each layer traced that way."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.models import OPTConfig, OPTModel
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    monkeypatch.setattr(pa, "reference_off_tpu", lambda interpret: False)
    assert pa.paged_decode_impl(8, 8, None, 64, 64) == "reference"
    assert pa.paged_decode_impl(8, 8, None, 128, 128) == "pallas"
    monkeypatch.setattr(ev2.jax, "default_backend", lambda: "tpu")
    tel = telemetry.configure(enabled=True, jsonl=False, prometheus=False)
    try:
        model = OPTModel(OPTConfig.tiny(dtype=jnp.float32))
        eng = build_engine_v2(
            model, model.init_params(jax.random.PRNGKey(0)),
            cache_config=KVCacheConfig(num_blocks=16, block_size=4,
                                       max_seq_len=32),
            max_batch_slots=2, prefill_chunk=8, decode_burst=2)
        eng.generate([[3, 4, 5, 6, 7]], max_new_tokens=4)
        assert eng.last_attn_path == "reference"
        counted = tel.registry.metrics()[
            "inference/attn/reference_fallbacks"].value
        assert counted >= 1
    finally:
        telemetry.configure(enabled=False)
