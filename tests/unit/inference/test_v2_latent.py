"""A model of latent (MLA) attention served through the paged engine: one
cache row a token that every query head reads and that holds its own
value, absorbed queries, the output brought back from the latent space,
sandwich norms, a shared expert beside sigmoid-routed experts of which a
share is held.  The logits the programs sample from (prefill in chunks,
steps that carry chunks, bursts through the paged latent cache) against
the plain float32 reference's full forward pass, in the expanded form."""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import KVCacheConfig, build_engine_v2
from deepspeed_tpu.inference.v2 import engine_v2 as ev2
from deepspeed_tpu.ops.pallas.paged_attention import (
    paged_decode_attention, paged_decode_reference)

sys.path.insert(0, str(__import__("pathlib").Path(__file__).parents[3]))
from perfbench import manifest  # noqa: E402

FAMILY = manifest.load_module("models", "pangu_ultra_moe")

#: a dense layer, then two sparse ones; share 1 of 4 over 16 experts
TINY = dict(
    vocab_size=256, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_attention_heads=4, q_lora_rank=24,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_theta=25600000, rms_norm_eps=1e-5, num_hidden_layers=3,
    first_k_dense_replace=1, published={"n_routed_experts": 16},
    n_routed_experts=4, expert_rank=1, num_experts_per_tok=3,
    norm_topk_prob=True, routed_scaling_factor=2.5, n_shared_experts=1,
    sandwich_norm=True, max_position_embeddings=256,
    run={"dtype": "float32"})
PAGE, CHUNK = 4, 8
PROMPT, NEW = 43, 24
OTHER = 21                      # a second request, so steps carry chunks


def _weights(cfg, seed=7):
    """Seeded weights with the norms' weights drawn too (the program's
    are 1, under which a norm dropped would move little)."""
    params = FAMILY.build(cfg).init_params(jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def walk(tree):
        if isinstance(tree, dict):
            return {k: (jax.random.uniform(next(keys), v.shape, v.dtype,
                                           0.5, 1.5)
                        if k.endswith("norm") else walk(v))
                    for k, v in tree.items()}
        if isinstance(tree, list):
            return [walk(v) for v in tree]
        return tree

    return walk(params)


@pytest.fixture(scope="module")
def served():
    """Two requests through the engine, the second admitted while the
    first decodes (its chunks ride decode steps), with the logits every
    call sampled from: ``(ids of the first, its logits [NEW, V], engine,
    params)``."""
    model = FAMILY.build(TINY)
    params = _weights(TINY)
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
    rs = np.random.RandomState(3)
    prompt = rs.randint(0, 256, size=PROMPT).tolist()
    req = eng.put(prompt, NEW)
    calls = []
    late = None
    while eng.scheduler.has_work:
        if late is None and len(req.generated) >= 6:
            late = eng.put(rs.randint(0, 256, size=OTHER).tolist(), 5)
        before = len(seen)
        eng.step()
        jax.effects_barrier()
        calls.append((len(seen) - before, len(req.generated)))
    mp.undo()
    # the first request's logits: its last chunk's row 0, then slot 0 of
    # every decode step (the rows behind a step's chunk rows)
    chunks = -(-PROMPT // CHUNK)
    logits = [seen[chunks - 1][0]] + [l[-2] for l in seen[chunks:]]
    ids = np.asarray(prompt + req.generated)
    return ids, np.stack(logits[:NEW]), eng, params, (late, calls)


def _reference(params, ids, **changed):
    cfg = dict(TINY, **changed)
    return np.asarray(FAMILY.forward(params, cfg, jnp.asarray(ids)[None])[0])


def test_served_logits_are_the_references(served):
    ids, logits, eng, params, (late, calls) = served
    assert len(ids) == PROMPT + NEW and len(late.generated) == 5
    want = _reference(params, ids[:-1])[PROMPT - 1:]
    assert np.abs(logits - want).max() < 2e-4
    assert eng.last_attn_path == "reference"          # the CPU's path
    # one pool, of K alone: a row a token, its value inside it
    assert {k: sorted(v) for k, v in eng.pool.items()} == {"latent": ["k"]}
    assert eng.pool["latent"]["k"].shape == (3, 64, PAGE, 1, 32 + 8)
    # bursts and single steps (those that carried the late one's chunks)
    assert {n for n, _ in calls} >= {1, 4}


def test_the_late_request_is_the_references_too(served):
    _, _, _, params, (late, _) = served
    ids = np.asarray(late.prompt + late.generated)
    want = _reference(params, ids[:-1])[OTHER - 1:]
    assert want.argmax(-1).tolist() == late.generated


#: one published key changed
WRONG_KEYS = {
    "no_routed_scaling": dict(routed_scaling_factor=1.0),
    "weights_not_normalised": dict(norm_topk_prob=False),
    "shared_expert_dropped": dict(n_shared_experts=0),
    "another_share": dict(expert_rank=0),
}


@pytest.mark.parametrize("name", sorted(WRONG_KEYS))
def test_a_reference_with_one_key_changed_is_told_apart(served, name):
    ids, logits, _, params, _ = served
    wrong = _reference(params, ids[:-1], **WRONG_KEYS[name])[PROMPT - 1:]
    assert np.abs(logits - wrong).max() > 5e-3, name


@pytest.mark.parametrize("norm", ["post_attn_norm", "post_mlp_norm",
                                  "q_norm", "kv_norm"])
def test_a_norm_dropped_is_told_apart(served, norm, monkeypatch):
    ids, logits, _, params, _ = served
    real = FAMILY._norm
    monkeypatch.setattr(
        FAMILY, "_norm", lambda x, group, name, eps:
        x if name == norm else real(x, group, name, eps))
    wrong = _reference(params, ids[:-1])[PROMPT - 1:]
    assert np.abs(logits - wrong).max() > 5e-3, norm


def _shifted_values(c_kv, w_uv):
    """V taken from the wrong numbers of the row: one to the right."""
    return jnp.roll(c_kv, 1, axis=-1) @ w_uv


#: one function of the reference replaced by what a wrong path computes
WRONG_PARTS = {
    "softmax_for_sigmoid": (jax.nn, "sigmoid",
                            lambda x: jax.nn.softmax(x, axis=-1)),
    "scale_of_the_nope_part_alone": (
        FAMILY, "_score_scale",
        lambda cfg: 1.0 / jnp.sqrt(jnp.float32(cfg["qk_nope_head_dim"]))),
    # each head's key rotated for itself (at the head's own offset), and
    # not the one vector a token that every head shares
    "k_rope_rotated_per_head": (
        FAMILY, "_shared_key", lambda k_rope, head, theta: jnp.roll(
            FAMILY._rope(k_rope, theta), head, axis=-1)),
    "values_from_the_wrong_numbers": (FAMILY, "_values", _shifted_values),
}


@pytest.mark.parametrize("name", sorted(WRONG_PARTS))
def test_a_wrong_part_is_told_apart(served, name, monkeypatch):
    ids, logits, _, params, _ = served
    where, what, wrong_fn = WRONG_PARTS[name]
    monkeypatch.setattr(where, what, wrong_fn)
    wrong = _reference(params, ids[:-1])[PROMPT - 1:]
    assert np.abs(logits - wrong).max() > 5e-3, name


def test_the_shares_add_up_to_the_uncut_layer():
    """4 shares of 4 of 16 experts: the routed parts summed and the shared
    expert counted once are the uncut reference's ``y``, before
    ``N_post_mlp``."""
    whole_cfg = dict(TINY, n_routed_experts=16, expert_rank=0)
    whole = _weights(whole_cfg, seed=11)
    stacks = whole["layers"]
    h = jax.random.normal(jax.random.PRNGKey(5), (12, TINY["hidden_size"]))
    for layer in range(2):
        cut = lambda tree: jax.tree.map(lambda t: t[layer], tree)
        uncut = {"moe": dict(stacks["moe"], wg=stacks["moe"]["wg"][layer],
                             layer=layer), "shared": cut(stacks["shared"])}
        with jax.default_matmul_precision("highest"):
            want = np.asarray(FAMILY.ffn(h, uncut, whole_cfg))
        total = 0.0
        for rank in range(4):
            cfg = dict(TINY, expert_rank=rank)
            model = FAMILY.build(cfg)
            held = {n: stacks["moe"][n][:, 4 * rank:4 * rank + 4]
                    for n in ("w_gate", "w_up", "w_down")}
            lp = {"moe": {"wg": stacks["moe"]["wg"][layer]},
                  "shared": cut(stacks["shared"]), "expert_layer": layer}
            part = np.asarray(model.routed(lp, h, {"moe": held}))
            assert np.abs(part).max() > 1e-3      # every share has work
            total = total + part
            # and the reference of the share is the program's share
            ref_part = FAMILY.routed(
                h, dict(held, wg=lp["moe"]["wg"], layer=layer), cfg)
            assert np.abs(part - np.asarray(ref_part)).max() < 2e-5
        total = total + np.asarray(model.shared(lp, h))
        assert np.abs(total - want).max() < 2e-5


def _latent_pool(rs, layers, pages, bs, d, lengths, tables):
    """A K pool in planes with seeded rows where ``tables`` point, as the
    engine lays it out: ``[layers·planes, pages, bs, 1, w]``."""
    from deepspeed_tpu.inference.v2.kv_cache import lane_planes

    planes, w = lane_planes(d)
    pool = np.zeros((layers * planes, pages, bs, 1, w), np.float32)
    rows = rs.randn(layers, pages, bs, d).astype(np.float32)
    padded = np.zeros((layers, pages, bs, planes * w), np.float32)
    padded[..., :d] = rows
    for p in range(planes):
        pool[p * layers:(p + 1) * layers, :, :, 0] = \
            padded[..., p * w:(p + 1) * w]
    return pool, planes


@pytest.mark.parametrize("h, d, dv, bs", [(128, 576, 512, 16),
                                          (8, 40, 32, 4)])
def test_the_kernel_reads_v_from_ks_planes(h, d, dv, bs):
    """Interpret mode against ``paged_decode_reference``: a group of
    ``h`` query heads on the one KV head, K in planes (five at 576), V
    the row's leading numbers, ragged lengths, a dead slot; and the
    reference against attention written out."""
    rs = np.random.RandomState(0)
    layers, pages, max_blocks = 2, 24, 6
    lengths = np.asarray([bs * 5 + 3, 0, 1, bs * 2], np.int32)
    tables = np.zeros((4, max_blocks), np.int32)
    free = iter(rs.permutation(np.arange(1, pages)))
    for r, n in enumerate(lengths):
        for j in range(-(-int(n) // bs)):
            tables[r, j] = next(free)
    pool, planes = _latent_pool(rs, layers, pages, bs, d, lengths, tables)
    flat = jnp.asarray(pool.reshape((-1,) + pool.shape[2:]))
    q = jnp.asarray(rs.randn(4, h, d).astype(np.float32))
    layer, scale = 1, 0.11
    args = (q, flat, None, jnp.asarray(tables + layer * pages),
            jnp.asarray(lengths))
    kw = dict(k_planes=planes, plane_stride=layers * pages, v_in_k=dv,
              scale=scale)
    want = np.asarray(paged_decode_reference(*args, **kw))
    got = np.asarray(paged_decode_attention(*args, interpret=True, **kw))
    assert got.shape == (4, h, dv)
    live = lengths > 0
    assert np.abs(got - want)[live].max() < 2e-5
    assert not got[1].any()                       # the dead slot: zeros
    # written out for the longest row
    w = pool.shape[-1]
    n = int(lengths[0])
    rows = np.concatenate(
        [pool[p * layers + layer, tables[0], :, 0].reshape(-1, w)
         for p in range(planes)], axis=-1)[:n, :d]
    s = np.asarray(q[0]) @ rows.T * scale
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    assert np.abs(want[0] - p @ rows[:, :dv]).max() < 2e-5


def test_the_latent_kinds_counter_and_gauge():
    from deepspeed_tpu import telemetry

    # a hub another file of this worker left on has counted this file's
    # earlier tests: start from an empty registry
    telemetry.get_telemetry().reset()
    tel = telemetry.configure(enabled=True, jsonl=False, prometheus=False)
    try:
        model = FAMILY.build(TINY)
        eng = build_engine_v2(
            model, model.init_params(jax.random.PRNGKey(0)),
            cache_config=KVCacheConfig(num_blocks=64, block_size=PAGE,
                                       max_seq_len=128),
            max_batch_slots=2, prefill_chunk=CHUNK, prefill_batch=1,
            decode_burst=4)
        eng.put(list(range(1, 11)), 9)
        while eng.scheduler.has_work:
            eng.step()
        snap = tel.registry.snapshot()
        metrics = tel.registry.metrics()
        # what the paged kernel read a layer: 8 decode steps of one row at
        # lengths 11..18 (the first token comes from the prompt's last
        # chunk), and the two chunks' 8 rows each at start + t + 1 keys
        assert metrics["inference/attn/keys_read_latent"].value \
            == sum(range(11, 19)) + sum(range(1, 9)) + sum(range(9, 17))
        assert "inference/kv/pages_in_use/latent" in str(snap) \
            or "inference/kv/pages_in_use/latent" in metrics
        assert metrics["inference/attn/reference_fallbacks"].value == 0 \
            if "inference/attn/reference_fallbacks" in metrics else True
    finally:
        telemetry.configure(enabled=False)


def test_a_latent_page_is_transferred_as_k_alone():
    from deepspeed_tpu.serving import kv_transfer

    model = FAMILY.build(TINY)
    params = model.init_params(jax.random.PRNGKey(0))
    cache = KVCacheConfig(num_blocks=16, block_size=PAGE, max_seq_len=64)
    make = lambda: build_engine_v2(model, params, cache_config=cache,
                                   max_batch_slots=2, prefill_chunk=CHUNK,
                                   prefill_batch=1, decode_burst=2)
    src, dst = make(), make()
    prompt = list(range(3, 3 + 2 * PAGE))
    req = src.put(prompt, 4)
    src.step()                  # the prompt's one chunk: its two pages
    blocks = [int(b) for b in req.blocks[:2]]
    page = kv_transfer.page_payload(src, prompt, blocks, 0)
    plane = np.asarray(src.pool["latent"]["k"][:, blocks[0]])
    assert page["raw"] == plane.tobytes() and plane.any()
    kv_transfer.inject_pages(dst, [5], {0: page})
    assert sorted(dst.pool["latent"]) == ["k"]
    assert np.array_equal(np.asarray(dst.pool["latent"]["k"][:, 5]), plane)
