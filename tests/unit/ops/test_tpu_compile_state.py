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
from deepspeed_tpu.ops.pallas import conv_tail_update as ctu
from deepspeed_tpu.ops.pallas import paged_attention as pa
from deepspeed_tpu.ops.pallas import ssm_state_update as ssu

#: the serving cell's slots and layers: 97 rows a layer of a state pool.
#: (Of 25 rows in two layers the chip's compiler would rather tile the
#: tails' pool by its LAYERS and re-lay it out around the kernel that moves
#: it: not the cell's program.)
SLOTS, PAGES, LAYERS = 96, 512, 6


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
    mp.setattr(ctu, "reference_off_tpu", lambda interpret: False)
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
    # (the conv's tails lie in tiles of eight slots: seven rows padding)
    tail = pools["ssm"]["conv"]
    assert memory.alias_size_in_bytes == held + (-(SLOTS + 1) % 8) \
        * LAYERS * tail.shape[2] * tail.dtype.itemsize
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


def test_the_convs_tails_are_moved_where_they_lie_by_the_kernel(program):
    """PR 58: the decode rows' conv reads a slot's tail once and writes it
    back shifted in the pool itself (``conv_tail_update``, a call a layer of
    the scan, aliased); no instruction makes a value of the tails' pool nor
    of one layer of it.  This is where a relayout copy of the pool would
    show."""
    from test_tpu_compile_parts import _values_made

    engine, text, _ = program
    tail = engine.pool["ssm"]["conv"]
    assert tail.shape == (LAYERS, SLOTS + 1, 3 * 5120)
    dims = lambda *shape: ",".join(str(n) for n in shape)
    layer = {dims(*tail.shape), dims(*tail.shape[1:]),
             dims(SLOTS, tail.shape[2]), dims(SLOTS, 3, 5120),
             dims(SLOTS, 4, 5120)}
    assert _values_made(text, "bf16", layer, "conv_tail_update") == []
    calls = [line for line in text.splitlines()
             if re.search(r"conv_tail_update[\w.]* = \(", line)]
    assert len(calls) == 1 and f"(bf16[{dims(*tail.shape)}]" in calls[0]
    assert "output_to_operand_aliasing" in calls[0]


@pytest.mark.parametrize("shape, dtype", [
    ((6, 97, 15360), jnp.bfloat16), ((5, 129, 30720), jnp.bfloat16),
    ((3, 193, 73728), jnp.bfloat16), ((36, 193, 73728), jnp.bfloat16),
    ((72, 96, 15360), jnp.bfloat16), ((3, 33, 73728), jnp.bfloat16),
    ((72, 97, 15360), jnp.bfloat16), ((8, 193, 73728), jnp.bfloat16),
    ((4, 97, 15360), jnp.bfloat16), ((2, 129, 30720), jnp.bfloat16),
    ((3, 17, 73728), jnp.bfloat16), ((2, 25, 15360), jnp.bfloat16),
    ((8, 97, 15360), jnp.float32), ((3, 17, 1536), jnp.float32)],
    ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else "")
def test_the_rule_for_the_tails_pools_order_is_the_compilers(one_chip, shape,
                                                            dtype):
    """``conv_tail_update`` takes the pool only where the chip holds it
    row-major (``rows_on_sublanes``): the three serving cells' pools, and
    not a pool whose layers pad less than its slots.  The rule is the
    compiler's own choice for a donated argument of that shape."""
    compiled = jax.jit(lambda a: a.at[1, 2].add(1), donate_argnums=0).lower(
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)).compile()
    order = re.search(r"entry_computation_layout=\{\(\w+\[[\d,]+\]\{([\d,]+)",
                      compiled.as_text()).group(1)
    assert order in ("2,1,0", "2,0,1")
    assert ctu.rows_on_sublanes(*shape[:2], jnp.dtype(dtype).itemsize) \
        == (order == "2,1,0")
