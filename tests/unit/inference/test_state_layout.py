"""The recurrent state a model carries beside its keys: ``StateLayout`` is
the only code that indexes its pool (``kv_cache.py``'s seam, as
``test_kv_layout.py`` holds it for the KV pools), what it reads and writes,
which adapters state a ``StateKind``, and what the serving plane refuses
for one that does: a shared prefix, a seat given up with the pages kept, a
page transfer, tensor-parallel serving."""

import ast
import logging
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu import models
from deepspeed_tpu.inference.v2 import KVCacheConfig, build_engine_v2
from deepspeed_tpu.inference.v2 import adapters, kv_cache
from deepspeed_tpu.inference.v2.adapters import StateKind
from deepspeed_tpu.inference.v2.kv_cache import StateLayout
from deepspeed_tpu.serving.scheduler import ServingScheduler

ROOT = pathlib.Path(__file__).resolve().parents[3]
KIND = StateKind("ssm", 3, (("ssm", (2, 4, 8), jnp.float32),
                            ("conv", (3, 16), jnp.bfloat16)),
                 in_place=("ssm",))


def _filled(layout):
    pool = layout.init_pool()
    return {name: jnp.arange(a.size, dtype=jnp.float32).reshape(a.shape
                                                                ).astype(a.dtype)
            for name, a in pool.items()}


def test_the_pool_is_a_slot_a_batch_slot_and_one_of_scratch():
    layout = StateLayout(KIND, 5)
    pool = layout.init_pool()
    assert {n: (a.shape, a.dtype) for n, a in pool.items()} == {
        "ssm": ((3, 6, 2, 4, 8), jnp.float32),
        "conv": ((3, 6, 3, 16), jnp.bfloat16)}
    assert layout.bytes_per_slot == 2 * 4 * 8 * 4 + 3 * 16 * 2
    assert layout.pool_bytes == 3 * 6 * layout.bytes_per_slot
    assert not any(bool(a.any()) for a in pool.values())


def test_decode_rows_get_values_of_one_layers_stretch_and_write_them_back():
    layout = StateLayout(StateKind("ssm", 3, KIND.parts, ()), 5)
    pool = _filled(layout)
    got, held = jax.jit(layout.decode_operands)(pool, 1)
    assert held == {}
    for name in pool:
        np.testing.assert_array_equal(got[name], pool[name][1, 1:])
    new = {name: -a.astype(jnp.float32) for name, a in got.items()}
    after = jax.jit(layout.decode_written)(pool, 1, new, {})
    for name in pool:
        assert after[name].dtype == pool[name].dtype
        np.testing.assert_array_equal(after[name][1, 1:],
                                      new[name].astype(pool[name].dtype))
        # the scratch slot and the other layers are as they were
        np.testing.assert_array_equal(after[name][1, 0], pool[name][1, 0])
        np.testing.assert_array_equal(after[name][0], pool[name][0])
        np.testing.assert_array_equal(after[name][2], pool[name][2])


def test_a_part_in_place_goes_out_as_the_pools_array_and_comes_back_as_one():
    layout = StateLayout(KIND, 5)
    pool = _filled(layout)
    values, held = layout.decode_operands(pool, 1)
    assert sorted(values) == ["conv"] and sorted(held) == ["ssm"]
    array, l, first = held["ssm"]
    assert array is pool["ssm"] and (l, first) == (1, 1)
    np.testing.assert_array_equal(values["conv"], pool["conv"][1, 1:])
    moved = array.at[l, first:].multiply(2.0)
    after = layout.decode_written(pool, 1, {"conv": values["conv"] + 1},
                                  {"ssm": moved})
    assert after["ssm"] is moved
    np.testing.assert_array_equal(after["conv"][1, 1:], values["conv"] + 1)
    np.testing.assert_array_equal(after["conv"][1, 0], pool["conv"][1, 0])
    np.testing.assert_array_equal(after["conv"][0], pool["conv"][0])


def test_a_kind_whose_every_part_is_in_place_hands_a_decode_step_no_values():
    """What all three families state (two since PR 58, Nemotron-H since
    PR 60: the conv's tail moved where it lies, as the state is): the hook
    gets every part as the pool's array, and every array it hands back is
    put in its place."""
    layout = StateLayout(StateKind("ssm", 3, KIND.parts, ("ssm", "conv")), 5)
    pool = _filled(layout)
    values, held = layout.decode_operands(pool, 2)
    assert values == {} and sorted(held) == ["conv", "ssm"]
    for name, (array, l, first) in held.items():
        assert array is pool[name] and (l, first) == (2, 1)
    moved = {name: array + 1 for name, (array, _, _) in held.items()}
    after = layout.decode_written(pool, 2, {}, moved)
    assert sorted(after) == ["conv", "ssm"]
    for name in pool:
        assert after[name] is moved[name]


@pytest.mark.parametrize("make, part, tail_in_place", [
    (lambda: models.FalconH1Model(models.FalconH1Config.tiny()), "ssm", True),
    (lambda: models.NemotronHModel(models.NemotronHConfig.tiny()), "ssm",
     True),
    (lambda: models.SolarOpen2Model(models.SolarOpen2Config.tiny()),
     "delta", True)], ids=["falcon-h1", "nemotron-h", "solar-open2"])
def test_which_families_move_the_tail_where_it_lies(make, part,
                                                    tail_in_place):
    """All three state both parts ``in_place`` (Nemotron-H since PR 60:
    the kernel moves its tails and the conv stays XLA's chain, the parent's
    arithmetic).  In all three the conv's tail lies time-major and FLAT a
    slot (no dimension of ``K − 1`` for the chip to tile)."""
    kind, = adapters.make_adapter(make()).state_kinds
    assert kind.name == part
    assert kind.in_place == ((part, "conv") if tail_in_place else (part,))
    shapes = {name: shape for name, shape, _ in kind.parts}
    assert sorted(shapes) == sorted([part, "conv"])
    assert len(shapes["conv"]) == 1


def test_a_chunks_slot_is_read_where_it_sits_and_from_zeros_where_fresh():
    layout = StateLayout(KIND, 5)
    pool = _filled(layout)
    slots, fresh = jnp.asarray([4, 2, 0]), jnp.asarray([False, True, False])
    got = jax.jit(layout.read_slots)(pool, 2, slots, fresh)
    for name in pool:
        np.testing.assert_array_equal(got[name][0], pool[name][2, 4])
        assert not bool(got[name][1].any())     # whatever slot 2 held
        np.testing.assert_array_equal(got[name][2], pool[name][2, 0])
    new = {name: jnp.full(a.shape, 7.0) for name, a in got.items()}
    after = jax.jit(layout.write_slots)(pool, 2, slots, new)
    for name in pool:
        written = np.asarray(after[name][2] == 7).reshape(6, -1).all(axis=1)
        assert written.tolist() == [True, False, True, False, True, False]
        np.testing.assert_array_equal(after[name][:2], pool[name][:2])


def test_the_state_pools_lie_beside_the_kv_pools_under_the_kinds_name():
    model = models.FalconH1Model(models.FalconH1Config.tiny())
    adapter = adapters.make_adapter(model)
    cache = KVCacheConfig(num_blocks=8, block_size=8, max_seq_len=64)
    assert cache.with_state((), 4) is cache and not cache.state_slots
    cache = cache.with_state(adapter.state_kinds, 4)
    assert cache.state_slots == 4
    pools = jax.eval_shape(lambda: kv_cache.init_kv_pool(adapter, cache))
    assert sorted(pools) == ["kv", "ssm"]
    assert sorted(pools["ssm"]) == ["conv", "ssm"]
    assert pools["ssm"]["ssm"].shape == (2, 5, 4, 16, 8)
    assert pools["ssm"]["conv"].shape == (2, 5, 3 * (32 + 2 * 32))
    # a row's slot is its request's batch slot plus one; 0 elsewhere
    assert cache.state_rows(4, [(0, 2), (3, 0), (1, -1)]).tolist() \
        == [3, 0, 0, 1]
    assert KVCacheConfig().state_rows(4, [(0, 2)]) is None


@pytest.mark.parametrize("cls, config", [
    (models.LlamaModel, models.LlamaConfig.tiny()),
    (models.MixtralModel, models.MixtralConfig.tiny()),
    (models.OlmoeModel, models.OlmoeConfig.tiny()),
    (models.OPTModel, models.OPTConfig.tiny()),
    (models.MimoV2Model, models.MimoV2Config.tiny()),
    (models.PanguUltraMoeModel, models.PanguUltraMoeConfig.tiny())],
    ids=lambda v: getattr(v, "__name__", ""))
def test_the_other_adapters_state_no_state_kind(cls, config):
    adapter = adapters.make_adapter(cls(config))
    assert adapter.state_kinds == ()
    for hook in ("mix_in", "mix_chunk", "mix_decode", "mix_out"):
        assert getattr(type(adapter), hook) \
            is getattr(adapters.ModelAdapterV2, hook)
    cache = KVCacheConfig(num_blocks=8, block_size=8, max_seq_len=64)
    assert kv_cache.state_layouts(adapter, cache) == {}
    assert sorted(jax.eval_shape(
        lambda: kv_cache.init_kv_pool(adapter, cache))) == sorted(
            k.name for k in adapter.kinds)


def test_the_falcon_adapter_states_what_a_sequence_holds_a_layer():
    model = models.FalconH1Model(models.FalconH1Config())
    kind, = adapters.make_adapter(model).state_kinds
    assert (kind.name, kind.layers) == ("ssm", 72)
    assert kind.parts == (("ssm", (32, 256, 128), jnp.float32),
                          ("conv", (3 * 5120,), jnp.bfloat16))
    assert kind.in_place == ("ssm", "conv")
    attention, = adapters.make_adapter(model).kinds
    assert (attention.kv_heads, attention.k_dim, attention.window,
            attention.ring) == (4, 128, None, False)


# -- the seam ----------------------------------------------------------------

ENGINE = ROOT / "deepspeed_tpu/inference/v2/engine_v2.py"
ACCESS = {"decode_operands", "decode_written", "read_slots", "write_slots"}


def test_only_the_state_layout_indexes_a_state_pool():
    """The engine reaches a state pool through its layout's four methods
    and nothing else: no subscript of a pool, no ``.at[…]`` update, no
    ``dynamic_slice`` of its own; the model and the adapter get a group's
    state, or for a part held in place the kernel's operands as the layout
    hands them out, and index no pool themselves."""
    tree = ast.parse(ENGINE.read_text())
    called = {n.func.attr for n in ast.walk(tree)
              if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)}
    assert ACCESS <= called
    assert not {"dynamic_slice", "dynamic_update_slice",
                "dynamic_index_in_dim"} & called
    for node in ast.walk(tree):
        if isinstance(node, ast.Subscript):
            base = ast.unparse(node.value)
            assert not re.search(r"\bpool\b", base) \
                and not base.endswith(".at"), ast.unparse(node)
    for path in ("deepspeed_tpu/models/falcon_h1.py",
                 "deepspeed_tpu/inference/v2/adapters.py"):
        text = (ROOT / path).read_text()
        names = {n.attr for n in ast.walk(ast.parse(text))
                 if isinstance(n, ast.Attribute)}
        assert not ACCESS & names and "state_slots" not in names, path
        assert not {"dynamic_slice", "dynamic_update_slice",
                    "dynamic_index_in_dim", "at"} & names, path
    # a request's slot is handed to the cache where the rows are packed
    handed = {id(n) for call in ast.walk(tree) if isinstance(call, ast.Call)
              and getattr(call.func, "attr", None) == "state_rows"
              for n in ast.walk(call)}
    assert len(handed) > 0


# -- what the serving plane refuses -------------------------------------------

def _scheduler(state_slots, **kw):
    cache = KVCacheConfig(num_blocks=32, block_size=8, max_seq_len=128,
                          state_slots=state_slots)
    return ServingScheduler(cache, max_batch_slots=2, prefill_chunk=16, **kw)


def test_prefix_sharing_is_off_and_logged_for_a_model_with_state():
    from deepspeed_tpu.utils import logging as ds_logging

    ds_logging._logged_once.discard("serving/prefix_cache/state")
    said = []
    handler = logging.Handler()
    handler.emit = lambda record: said.append(record.getMessage())
    ds_logging.logger.addHandler(handler)
    try:
        with_state = _scheduler(2)
        _scheduler(2)                       # once a process
    finally:
        ds_logging.logger.removeHandler(handler)
    assert [m for m in said if "prefix sharing is off" in m] == [
        "prefix sharing is off for this model: its layers carry a "
        "recurrent state, which a shared prefix's pages do not hold"]
    assert not with_state.prefix.enabled and with_state.seat_holds_state
    assert _scheduler(0).prefix.enabled
    assert not _scheduler(0).seat_holds_state
    # the same prompt twice reuses nothing
    prompt = list(range(40))
    first = with_state.add_request(prompt, 4)
    with_state.plan_step()
    for _ in range(3):
        chunks, _ = with_state.plan_step()
        for ch in chunks:
            with_state.chunk_done(ch, 1 if ch.is_last else None)
    again = with_state.add_request(prompt, 4)
    with_state.plan_step()
    assert first.prefilled == len(prompt) and again.prefilled == 0


def test_a_seat_is_not_given_up_with_the_pages_kept_and_nothing_is_adopted():
    sched = _scheduler(2)
    req = sched.add_request(list(range(20)), 4)
    sched.plan_step()
    with pytest.raises(NotImplementedError, match="recurrent state"):
        sched.preempt(req)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        sched.unseat(req)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        sched.adopt_reserve(list(range(20)), 4)
    # starting over is what is left: the pages go, the request is retired
    assert sched.preempt_release(req) == 3
    plain = _scheduler(0)
    req = plain.add_request(list(range(20)), 4)
    plain.plan_step()
    plain.preempt(req)
    assert plain.resume(req)


@pytest.fixture(scope="module")
def engine():
    model = models.FalconH1Model(models.FalconH1Config.tiny())
    return build_engine_v2(
        model, model.init_params(jax.random.PRNGKey(0)),
        cache_config=KVCacheConfig(num_blocks=32, block_size=8,
                                   max_seq_len=64),
        max_batch_slots=2, prefill_chunk=16)


def test_a_page_of_a_model_with_state_is_not_transferred(engine):
    from deepspeed_tpu.serving import kv_transfer

    with pytest.raises(NotImplementedError, match="recurrent state"):
        kv_transfer.page_payload(engine, [1, 2, 3], [1], 0)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        kv_cache.write_page_arrays(engine.layouts, engine.pool, [1], [])


def test_tensor_parallel_serving_of_a_model_with_state_is_refused():
    from deepspeed_tpu.parallel import MeshLayout
    from deepspeed_tpu.parallel.mesh import build_mesh

    layout = MeshLayout.infer(2, tp=2)
    mesh = build_mesh(layout, devices=jax.devices()[:2])
    model = models.FalconH1Model(models.FalconH1Config.tiny(), mesh=mesh)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        build_engine_v2(model, jax.eval_shape(
            model.init_params, jax.random.PRNGKey(0)), mesh=mesh)


def test_the_counters_and_gauges_exist_with_the_hub_on_and_cost_nothing_off(
        engine):
    from deepspeed_tpu import telemetry

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, size=n).tolist() for n in (20, 9)]
    off = telemetry.get_telemetry()
    # another file of this worker may have left the hub on (a traced
    # rehearsal of tests/perfbench_tests does): start from it off and empty
    off.reset()
    assert not off.enabled
    engine.generate(prompts, max_new_tokens=5)
    assert not any("ssm" in name for name in off.registry.metrics())
    tel = telemetry.configure(enabled=True, jsonl=False, prometheus=False)
    try:
        engine.generate(prompts, max_new_tokens=5)
        tel.registry.snapshot()
        got = {name: m.value for name, m in tel.registry.metrics().items()
               if "inference/ssm/" in name}
    finally:
        telemetry.configure(enabled=False)
    per_slot = engine.state_layouts["ssm"].bytes_per_slot
    assert got["inference/ssm/chunk_tokens"] == 29
    assert got["inference/ssm/chunks_from_zero"] == 2
    assert got["inference/ssm/decode_rows"] >= 8
    assert got["inference/ssm/state_bytes_read"] \
        == got["inference/ssm/state_bytes_written"] > 0
    assert got["inference/ssm/state_bytes_read"] % (2 * per_slot) == 0
    assert got["inference/ssm/state_bytes"] == 2 * 3 * per_slot
    assert "inference/ssm/slots_in_use" in got
