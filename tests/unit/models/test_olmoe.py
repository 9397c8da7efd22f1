"""``OlmoeModel`` against the plain reference of ``perfbench/models/olmoe.py``
at a tiny size, float32, seeded weights with every norm weight moved off 1:
one layer, the full forward, the loss and every gradient leaf; then prefill
and decoding through the paged cache of the v2 engine against the
reference's full forward pass, logits and not tokens."""

import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[3]))

from deepspeed_tpu.inference.v2 import KVCacheConfig
from deepspeed_tpu.inference.v2 import engine_v2
from deepspeed_tpu.inference.v2.adapters import (LlamaV2Adapter,
                                                 OlmoeV2Adapter, make_adapter)
from deepspeed_tpu.models import (LlamaConfig, LlamaModel, MixtralConfig,
                                  MixtralModel, OlmoeConfig, OlmoeModel)
from deepspeed_tpu.moe import DroplessMoE, MOELayer
from perfbench import manifest

REFERENCE = manifest.load_module("models", "olmoe")
#: the reference reads the published keys
CFG = {"vocab_size": 512, "hidden_size": 128, "intermediate_size": 128,
       "num_hidden_layers": 2, "num_attention_heads": 4,
       "num_key_value_heads": 4, "max_position_embeddings": 256,
       "rope_theta": 10000, "rms_norm_eps": 1e-5,
       "tie_word_embeddings": False, "num_experts": 8,
       "num_experts_per_tok": 3, "norm_topk_prob": False,
       "run": {"dtype": "float32"}}
#: float32 on both sides at ``highest``: the same sums in another order,
#: through two layers and a 128-wide head
LOGIT_TOL = 5e-5


@pytest.fixture(scope="module")
def model():
    return REFERENCE.build(CFG)


@pytest.fixture(scope="module")
def params(model):
    params = model.init_params(jax.random.PRNGKey(0))

    def off_one(path, leaf):
        name = jax.tree_util.keystr(path)
        if "norm" not in name:
            return leaf
        key = jax.random.fold_in(jax.random.PRNGKey(7), sum(map(ord, name)))
        return leaf * (1.0 + 0.2 * jax.random.normal(key, leaf.shape))

    return jax.tree_util.tree_map_with_path(off_one, params)


IDS = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0, CFG["vocab_size"])


def test_the_family_is_the_llama_backbone_with_its_own_routing(model, params):
    assert isinstance(model, MixtralModel) and isinstance(model, LlamaModel)
    assert isinstance(model._moe_layer, DroplessMoE)
    assert model.config.qk_norm and not LlamaConfig.tiny().qk_norm
    attn, moe = params["layers"]["attn"], params["layers"]["moe"]
    assert attn["q_norm"].shape == attn["k_norm"].shape == (2, 4 * 32)
    assert moe["wg"].shape == (2, 128, 8)
    assert moe["w_gate"].shape == moe["w_up"].shape == (2, 8, 128, 128)
    assert moe["w_down"].shape == (2, 8, 128, 128)
    specs = model.param_specs(params)
    assert jax.tree.structure(specs, is_leaf=lambda s: not isinstance(s, dict)
                              ) == jax.tree.structure(params)
    # Mixtral keeps its capacity gate; a dense Llama has no q/k norm weights
    assert isinstance(MixtralModel(MixtralConfig.tiny())._moe_layer, MOELayer)
    dense = LlamaModel(LlamaConfig.tiny()).init_params(jax.random.PRNGKey(0))
    assert "q_norm" not in dense["layers"]["attn"]
    # the family's own adapter keeps the three expert stacks out of what
    # the engine's layer scan slices, and nothing else; the tree itself
    # keeps Mixtral's layout
    adapter = make_adapter(model)
    assert type(adapter) is OlmoeV2Adapter
    scanned = adapter.layers(params)
    assert set(scanned["moe"]) == {"wg"} and scanned["moe"]["wg"] is moe["wg"]
    assert {k: v for k, v in scanned.items() if k != "moe"} == {
        k: v for k, v in params["layers"].items() if k != "moe"}
    assert set(moe) == {"wg", "w_gate", "w_up", "w_down"}


def test_one_layer(model, params):
    layer0 = jax.tree.map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 40, 128))
    with jax.default_matmul_precision("highest"):
        got, _ = model.decoder_layer(layer0, x)
        want = REFERENCE._layer(x[0], layer0, CFG)
    assert float(jnp.max(jnp.abs(got[0] - want))) < LOGIT_TOL


def test_forward_loss_and_every_gradient_leaf(model, params):
    batch = {"input_ids": IDS}
    with jax.default_matmul_precision("highest"):
        logits = model.forward(params, IDS)
        want = REFERENCE.forward(params, CFG, IDS)
        loss, grads = jax.value_and_grad(model.loss)(params, batch)
        want_loss, want_grads = jax.value_and_grad(
            lambda w: REFERENCE.loss(w, CFG, batch))(params)
    assert float(jnp.max(jnp.abs(logits - want))) < LOGIT_TOL
    assert abs(float(loss - want_loss)) < 1e-5
    errs = jax.tree.map(
        lambda g, w: float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w)),
        grads, want_grads)
    # float32 round-off through the backward pass, relative to each leaf
    for path, err in jax.tree_util.tree_leaves_with_path(errs):
        assert err < 1e-4, (jax.tree_util.keystr(path), err)


def test_the_router_loss_is_added_with_its_coefficient(params):
    import dataclasses

    with_aux = OlmoeModel(dataclasses.replace(REFERENCE.build(CFG).config,
                                              aux_loss_coef=0.01))
    batch = {"input_ids": IDS}
    plain = REFERENCE.build(CFG).loss(params, batch)
    # balanced routing gives k per layer: 0.01 x (2 layers x ~3)
    assert 0.03 < float(with_aux.loss(params, batch) - plain) < 0.2


def test_the_models_own_cache_decodes_like_its_forward(model, params):
    """``prefill`` / ``decode_step`` (the dense cache of the v1 engine)
    carry the q/k norm too."""
    prompt = IDS[:1, :20]
    with jax.default_matmul_precision("highest"):
        logits, cache = model.prefill(params, prompt, model.init_cache(1, 64))
        want = REFERENCE.forward(params, CFG, IDS[:1, :22])[0]
        assert float(jnp.max(jnp.abs(logits[0] - want[19]))) < LOGIT_TOL
        for t in (20, 21):
            logits, cache = model.decode_step(params, cache, IDS[:1, t])
            assert float(jnp.max(jnp.abs(logits[0] - want[t]))) < LOGIT_TOL


def test_prefill_then_decode_through_the_paged_cache(model, params,
                                                     monkeypatch):
    """Two requests of unequal lengths (three and one prefill chunks; one
    decode step beside a prefill, then a full burst) through the engine's
    two programs; every logit row a token was sampled from against the
    reference's full forward pass teacher-forced on what was served."""
    seen = []
    sample = engine_v2._sample

    def recording(logits, temperature, key):
        jax.debug.callback(lambda l: seen.append(np.asarray(l)), logits,
                           ordered=True)
        return sample(logits, temperature, key)

    monkeypatch.setattr(engine_v2, "_sample", recording)
    engine = engine_v2.build_engine_v2(
        model, params, KVCacheConfig(block_size=16, num_blocks=64,
                                     max_seq_len=256),
        max_batch_slots=4, prefill_chunk=32, prefill_batch=2, decode_burst=4)
    prompts = [np.asarray(IDS[0, :40]).tolist() + np.asarray(IDS[1, :37]).tolist(),
               np.asarray(IDS[1, :21]).tolist()]
    new = 9
    with jax.default_matmul_precision("highest"):
        served = engine.generate(prompts, max_new_tokens=new)
        jax.effects_barrier()
        assert engine.last_attn_path == "reference"      # off the TPU
        assert engine.last_moe_stats["drop_rate"] == 0.0
        for prompt, tokens in zip(prompts, served):
            assert len(tokens) == new
            ids = jnp.asarray([prompt + tokens[:-1]])
            want = np.asarray(REFERENCE.forward(params, CFG, ids)[0,
                                                                 len(prompt) - 1:])
            for i, token in enumerate(tokens):
                # the row this token was sampled from is among the recorded
                # ones (prefill rows and every slot of every decode step):
                # one of them is the reference's row, and its argmax is
                # the token served
                rows = [r for call in seen for r in call
                        if np.max(np.abs(r - want[i])) < LOGIT_TOL]
                assert rows, (i, token)
                assert int(np.argmax(want[i])) == token


def _tiny(family):
    if family == "olmoe":
        cfg = OlmoeConfig.tiny(dtype=jnp.float32, remat=False)
        return OlmoeModel(cfg), OlmoeV2Adapter
    if family == "mixtral":
        # capacity 2·T·2.0/4 = T slots an expert: nothing is dropped, so a
        # token does not depend on who shares its batch
        cfg = MixtralConfig.tiny(num_layers=2, dtype=jnp.float32,
                                 remat=False)
        return MixtralModel(cfg), LlamaV2Adapter
    cfg = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32, remat=False)
    return LlamaModel(cfg), LlamaV2Adapter


@pytest.mark.parametrize("family", ["olmoe", "mixtral", "dense_llama"])
def test_the_engine_serves_the_tokens_of_the_models_own_forward(family):
    """Through each family's adapter (OLMoE's hands the expert stacks over
    whole with the layer's index; Mixtral and a dense Llama keep
    ``LlamaV2Adapter``, whose scan slices the whole layers' tree as
    before): every served token is the argmax of the model's own forward
    pass over the prompt and what was served before it."""
    tiny, adapter_type = _tiny(family)
    weights = tiny.init_params(jax.random.PRNGKey(3))
    engine = engine_v2.build_engine_v2(
        tiny, weights, KVCacheConfig(block_size=16, num_blocks=32,
                                     max_seq_len=128),
        max_batch_slots=4, prefill_chunk=32, prefill_batch=2, decode_burst=4)
    assert type(engine.adapter) is adapter_type
    if adapter_type is LlamaV2Adapter:
        assert engine.adapter.layers(weights) is weights["layers"]
    prompts = [np.asarray(IDS[0, :37]).tolist(),
               np.asarray(IDS[1, :12]).tolist()]
    new = 6
    with jax.default_matmul_precision("highest"):
        served = engine.generate(prompts, max_new_tokens=new)
        for prompt, tokens in zip(prompts, served):
            assert len(tokens) == new
            logits = tiny.forward(weights,
                                  jnp.asarray([prompt + tokens[:-1]]))[0]
            want = np.argmax(np.asarray(logits[len(prompt) - 1:]), axis=-1)
            assert want.tolist() == tokens


def test_the_engine_counts_assignments_and_active_experts(model, params):
    """``inference/moe/assignments`` is rows x k a step of a call and
    ``inference/moe/experts_active`` the non-empty groups summed over
    layers and steps, from the stats every program threads out; nothing
    is dropped."""
    from deepspeed_tpu import telemetry

    tel = telemetry.configure(enabled=True, jsonl=False, prometheus=False)
    try:
        engine = engine_v2.build_engine_v2(
            model, params, KVCacheConfig(block_size=16, num_blocks=32,
                                         max_seq_len=128),
            max_batch_slots=4, prefill_chunk=32, prefill_batch=2,
            decode_burst=4)
        # one step that carries the chunks (2 x 32 rows beside the 4 slots'
        # rows, padding and idle rows route like any row), then one
        # four-step burst over the 4 slots
        def value(name):
            metric = tel.registry.metrics().get(name)
            return metric.value if metric is not None else 0.0

        # another test of this process may have counted already
        names = ("inference/moe/assignments", "inference/moe/experts_active",
                 "inference/moe/rows_computed")
        before = {name: value(name) for name in names}
        engine.generate([np.asarray(IDS[0, :20]).tolist()], max_new_tokens=5)
        k, layers, experts = 3, 2, 8
        assert value(names[0]) - before[names[0]] == 68 * k + 4 * (4 * k)
        active = value(names[1]) - before[names[1]]
        assert 5 * layers * k <= active <= 5 * layers * experts
        assert float(active).is_integer()
        # the rows the grouped matmuls multiplied: whole tiles, no fewer
        # than the assignments, and at most one part-filled tile a group
        rows = value(names[2]) - before[names[2]]
        tiles = engine.last_moe_tile_rows
        assert tiles == {"n_steps1": 64, "n_steps4": 8}
        assert 68 * k + 4 * (4 * k) <= rows <= (
            68 * k + experts * 64 + 4 * (4 * k + experts * 8))
        assert rows * layers % 8 == 0
        gauges = tel.registry.snapshot()["gauges"]
        assert {n: gauges[f"inference/moe/tile_rows/{n}"]["value"]
                for n in tiles} == tiles
        # a gauge of the router's last stats, worked out on a read
        assert tel.registry.snapshot()["gauges"][
            "inference/moe/drop_rate"]["value"] == 0.0
        assert engine.last_moe_stats["drop_rate"] == 0.0
        assert len(engine.last_moe_stats["load"]) == experts
    finally:
        telemetry.configure(enabled=False)


def test_gate_stats_that_do_not_fit_are_skipped_not_raised(model, params):
    """Telemetry must never kill a serving round: the packed stats are
    read by the layout the program recorded as it was traced; stats that
    do not fit it leave the router's signal (``last_moe_stats``) alone."""
    from deepspeed_tpu import telemetry

    engine = engine_v2.build_engine_v2(
        model, params, KVCacheConfig(block_size=16, num_blocks=32,
                                     max_seq_len=128),
        max_batch_slots=4, prefill_chunk=32, prefill_batch=2, decode_burst=4)
    engine.generate([np.asarray(IDS[0, :20]).tolist()], max_new_tokens=5)
    assert "moe/load" in dict(engine._moe_columns)
    width = sum(w for _, w in engine._moe_columns)
    telemetry.configure(enabled=False)
    signal = engine.last_moe_stats
    packed = np.zeros((2, width), np.float32)
    assert engine._ingest_moe_stats(packed[:, :-1]) is None     # too narrow
    assert engine._ingest_moe_stats(packed[0]) is None          # one row
    assert engine.last_moe_stats is signal
    engine._moe_columns = [
        (n, w) for n, w in engine._moe_columns
        if n != "moe/drop_rate"] + [("moe/other", 1)]
    assert "moe/other" in engine._ingest_moe_stats(packed)      # no entry
    assert engine.last_moe_stats is not signal
    assert engine.last_moe_stats["drop_rate"] == 0.0
