"""The serving program of a model that carries a recurrent state, compiled
for a described v5e with no chip (``test_tpu_compile.py``'s way, in a file
of its own so that it runs beside it): the state pool rides the layer scan
as the KV pools do, read and written in place at ``(layer, slot)``.  The
first version gathered a chunk's slots with ``pool[l, slots]``, which
XLA:TPU lowered to a pass over the WHOLE pool (two values of half its
size): what this file holds against."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu import models
from deepspeed_tpu.ops.pallas import paged_attention as pa
from deepspeed_tpu.ops.pallas import ssm_state_update as ssu

SLOTS, PAGES, LAYERS = 24, 512, 2


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def program(one_chip):
    """The step that carries chunks (chunk rows and decode rows beside
    each other) at the serving cell's widths, two layers and a small
    vocabulary: ``(engine, compiled text, memory analysis)``."""
    from deepspeed_tpu.inference.v2 import engine_v2 as ev2
    from deepspeed_tpu.inference.v2.kv_cache import KVCacheConfig

    model = models.FalconH1Model(models.FalconH1Config(
        num_layers=LAYERS, vocab_size=8192, max_seq_len=2048))
    cache = KVCacheConfig(num_blocks=PAGES, block_size=16, max_seq_len=2048)
    placed = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    arg = lambda shape, dt=jnp.int32: placed(jax.ShapeDtypeStruct(shape, dt))
    mp = pytest.MonkeyPatch()
    mp.setattr(pa, "reference_off_tpu", lambda interpret: False)
    mp.setattr(ssu, "reference_off_tpu", lambda interpret: False)
    real_pool = ev2.init_kv_pool
    mp.setattr(ev2, "init_kv_pool",
               lambda ad, cc: jax.eval_shape(lambda: real_pool(ad, cc)))
    try:
        shapes = jax.eval_shape(
            lambda key: jax.tree.map(lambda w: w.astype(jnp.bfloat16),
                                     model.init_params(key)),
            jax.random.PRNGKey(0))
        engine = ev2.RaggedInferenceEngineV2(model, shapes, cache,
                                             max_batch_slots=SLOTS)
        blocks, Bp = cache.max_blocks_per_seq, engine.prefill_batch
        rows = (arg((SLOTS,)), (arg((SLOTS,)), arg((SLOTS + Bp,))),
                arg((SLOTS,)), arg((SLOTS, blocks)), arg((SLOTS,)))
        chunks = (arg((Bp, engine.chunk)), arg((Bp, blocks)), arg((Bp,)),
                  arg((Bp,)), None)
        done = jax.jit(
            functools.partial(engine._decode_burst_fn, n_steps=1, kb=8),
            donate_argnums=(1,)).lower(
                placed(shapes), placed(engine.pool), *rows,
                arg((), jnp.float32),
                placed(jax.eval_shape(lambda: jax.random.PRNGKey(0))), None,
                chunks, (arg((SLOTS,)), arg((Bp,)))).compile()
        yield engine, done.as_text(), done.memory_analysis()
    finally:
        mp.undo()


def test_the_state_pool_is_carried_and_written_in_place_on_v5e(program):
    engine, text, memory = program
    assert engine.last_attn_path == "pallas"
    pools = engine.pool
    assert sorted(pools) == ["kv", "ssm"]
    state = pools["ssm"]["ssm"]
    assert state.shape == (LAYERS, SLOTS + 1, 32, 256, 128)
    # every pool is donated and comes back: nothing of their size is
    # planned beside them
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for pool in pools.values() for a in pool.values())
    assert memory.alias_size_in_bytes == held
    assert memory.temp_size_in_bytes < 0.25 * held
    # no instruction makes a value of the pool's size, or of half of it,
    # but the ones that pass the buffer on or write into it in place
    dims = lambda *shape: ",".join(str(n) for n in shape)
    whole = {dims(*state.shape), dims(*state.shape[:-2], 128, 128),
             dims(*state.shape[1:]), dims(SLOTS, *state.shape[2:])}
    passes_on = {"parameter", "bitcast", "get-tuple-element"}
    roots, computation = {}, None
    for line in text.splitlines():
        head = re.match(r"%?([\w.-]+) \(.*\{$", line)
        root = re.match(r"\s*ROOT %?[\w.-]+ = \S+ ([\w-]+)\(", line)
        if head:
            computation = head.group(1)
        elif root:
            roots[computation] = root.group(1)
    faults = []
    for name, shape, opcode, rest in re.findall(
            r"^\s*(?:ROOT )?%?([\w.-]+) = f32\[([\d,]+)\]\S* ([\w-]+)\((.*)$",
            text, re.M):
        if shape not in whole or opcode in passes_on \
                or opcode == "dynamic-update-slice" \
                or (opcode == "custom-call" and "ssm_state_update" in name):
            continue
        called = re.search(r"calls=%?([\w.-]+)", rest)
        if not (opcode == "fusion" and called
                and roots.get(called.group(1)) == "dynamic-update-slice"):
            faults.append(f"{opcode} {name} makes f32[{shape}]")
    assert faults == []
    # the decode rows' states are moved by the kernel, a call a layer, on
    # the pool itself (its result IS the pool: aliased)
    moved = re.findall(r"ssm_state_update[\w.]* = \(f32\[([\d,]+)\]", text)
    assert moved == [dims(*state.shape)], moved
    # a paged attention call a layer for the decode rows; the dense kind's
    # chunk rows gather their pages in XLA
    assert len(re.findall(r"paged_decode_attention[\w.]* = ", text)) == 1
