"""A pattern's entry names a layer's PART (PR 52): an attention kind with
whatever the family has behind it, a state kind whose mixer is a layer of
its own, or the FFN alone; each part's pool has the layers of its own part.
The six adapters that were there before serve, to the bit, the logits the
parent commit served (``tests/fixtures/serving/v2_parent_logits.npz``, made
by this file's ``served_logits`` run on the parent's tree): they changed by
deletion or not at all."""

import pathlib

import jax
import numpy as np
import pytest

from deepspeed_tpu import models
from deepspeed_tpu.inference.v2 import KVCacheConfig, build_engine_v2
from deepspeed_tpu.inference.v2 import adapters
from deepspeed_tpu.inference.v2 import engine_v2 as ev2

GOLDEN = pathlib.Path(__file__).parents[2] / "fixtures" / "serving" \
    / "v2_parent_logits.npz"

#: a model of each registered class, at its tiny size
FAMILIES = {
    "LlamaModel": lambda: models.LlamaModel(models.LlamaConfig.tiny(
        num_layers=2, sliding_window=24)),
    "MixtralModel": lambda: models.MixtralModel(models.MixtralConfig.tiny(
        num_layers=2)),
    "OlmoeModel": lambda: models.OlmoeModel(models.OlmoeConfig.tiny()),
    "OPTModel": lambda: models.OPTModel(models.OPTConfig.tiny(num_layers=2)),
    "MimoV2Model": lambda: models.MimoV2Model(models.MimoV2Config.tiny(
        held_experts=(2, 4))),
    "PanguUltraMoeModel": lambda: models.PanguUltraMoeModel(
        models.PanguUltraMoeConfig.tiny(held_experts=(2, 4))),
    "FalconH1Model": lambda: models.FalconH1Model(
        models.FalconH1Config.tiny()),
}


def served_logits(family: str) -> np.ndarray:
    """Every row of logits the engine's programs sampled from while two
    requests were served (chunked prefill, steps that carry chunks,
    bursts), in order, as one array."""
    model = FAMILIES[family]()
    params = model.init_params(jax.random.PRNGKey(4))
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
            model, params, KVCacheConfig(num_blocks=64, block_size=4,
                                         max_seq_len=128),
            max_batch_slots=3, prefill_chunk=8, prefill_batch=2,
            decode_burst=4)
        rng = np.random.default_rng(9)
        eng.put(rng.integers(0, 256, 29).tolist(), 12)
        for _ in range(4):
            eng.step()
        eng.put(rng.integers(0, 256, 13).tolist(), 6)
        while eng.scheduler.has_work:
            eng.step()
        jax.effects_barrier()
    finally:
        mp.undo()
    return np.concatenate(seen).astype(np.float32)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_adapters_that_were_there_serve_the_parents_logits(family):
    want = np.load(GOLDEN)[family]
    got = served_logits(family)
    assert got.shape == want.shape
    np.testing.assert_array_equal(got, want)


def test_the_registry_holds_the_six_and_the_family_of_one_part_layers():
    assert set(adapters._REGISTRY) == set(FAMILIES) | {
        "NemotronHModel", "SolarOpen2Model", "ExaoneMoeModel"}
    assert len({adapters._REGISTRY[f] for f in FAMILIES}) == 6
    # the hook of the FFN alone: the families whose FFN follows their
    # attention in the same layer state no such layer and have none
    for family in FAMILIES:
        ad = adapters.make_adapter(FAMILIES[family]())
        assert adapters.FFN not in ad.pattern.leading + ad.pattern.period
        with pytest.raises(NotImplementedError):
            ad.ffn_layer(None, None, None, None)


def test_a_published_layer_may_be_two_entries_of_the_pattern():
    """Solar-Open-2: a mixer and then the experts, each under its own norm:
    the pattern has twice the published layers and the state kind's pool
    the KDA layers alone (PR 57)."""
    solar = adapters.make_adapter(models.SolarOpen2Model(
        models.SolarOpen2Config.tiny()))
    assert solar.num_layers == 8
    assert solar.pattern == adapters.LayerPattern(
        (), ("kv", adapters.FFN) + ("delta", adapters.FFN) * 3, 2)
    kind, = solar.state_kinds
    assert (kind.name, kind.layers, kind.beside, kind.in_place) == (
        "delta", 6, None, ("delta", "conv"))
    assert dict((name, shape) for name, shape, _ in kind.parts) == {
        "delta": (4, 16, 16), "conv": (3 * 192,)}
    attention, = solar.kinds
    assert (attention.layers, attention.theta, attention.kv_heads) == (
        2, None, 2)


def test_a_state_kind_is_a_layer_of_its_own_or_rides_beside_attention():
    falcon = adapters.make_adapter(FAMILIES["FalconH1Model"]())
    kind, = falcon.state_kinds
    assert kind.beside == falcon.kinds[0].name == "kv"
    assert kind.name not in falcon.pattern.period
    assert kind.layers == falcon.kinds[0].layers == 2
    nemotron = adapters.make_adapter(models.NemotronHModel(
        models.NemotronHConfig.tiny()))
    kind, = nemotron.state_kinds
    assert kind.beside is None and kind.layers == 2
    assert nemotron.pattern == adapters.LayerPattern(
        (), ("ssm", adapters.FFN, "kv", adapters.FFN), 2)
    attention, = nemotron.kinds
    assert (attention.layers, attention.theta, attention.kv_heads) == (
        2, None, 2)
    # a pattern that repeats nothing is one period, unrolled
    once = adapters.make_adapter(models.NemotronHModel(
        models.NemotronHConfig.tiny(pattern="MEMEMEM*EME")))
    assert once.pattern.periods == 1 and len(once.pattern.period) == 11
    assert [k.layers for k in once.state_kinds + once.kinds] == [5, 1]


def test_the_layers_run_by_part_are_gauges_set_when_a_program_is_traced():
    from deepspeed_tpu import telemetry

    tel = telemetry.get_telemetry()
    # another file of this worker may have left counters in the registry
    tel.reset()
    tel.configure(enabled=True, jsonl=False, prometheus=False)
    try:
        model = models.NemotronHModel(models.NemotronHConfig.tiny())
        eng = build_engine_v2(
            model, model.init_params(jax.random.PRNGKey(0)),
            KVCacheConfig(num_blocks=32, block_size=4, max_seq_len=64),
            max_batch_slots=2, prefill_chunk=8, prefill_batch=1,
            decode_burst=2)
        assert eng.last_layers_by_part == {}
        eng.generate([[5, 6, 7, 8, 9]], 4)
        assert eng.last_layers_by_part == {"ssm": 2, "ffn": 4, "kv": 2}
        # worked out when the registry is read, as the other gauges of a
        # traced program are
        snap = tel.registry.snapshot()["gauges"]
        for part, layers in eng.last_layers_by_part.items():
            assert snap[f"inference/layers/{part}"]["value"] == layers
        # the counters mean what they meant, over the layers of the part:
        # a step moves the MIXER layers' states (two of the eight layers)
        counters = {m.name: m.value for m in tel.registry.metrics().values()
                    if getattr(m, "kind", "") == "counter"}
        slot = eng.state_layouts["ssm"].bytes_per_slot
        moved = counters["inference/ssm/state_bytes_read"]
        assert moved % (2 * slot) == 0 and moved > 0
        assert eng.state_layouts["ssm"].pool_bytes == 2 * 3 * slot
    finally:
        tel.reset()
