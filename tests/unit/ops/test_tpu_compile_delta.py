"""The serving program of a model whose published layer is a token mixer
AND experts (``models/solar_open2.py``: a gated delta rule or a gated
attention, then the experts), compiled for a described v5e with no chip
(``test_tpu_compile_parts.py``'s way): the delta rule's state pool has the
KDA layers alone and rides the layer scan in place, moved by its own
kernel, and so does the conv's tail beside it (PR 58); the expert stacks
ride whole and are read at their layer by the grouped kernels.  No
instruction makes a value of a pool's or a stack's size, nor of one layer
of the tails' pool."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu import models
from deepspeed_tpu.ops.pallas import conv_tail_update as ctu
from deepspeed_tpu.ops.pallas import delta_state_update as dsu
from deepspeed_tpu.ops.pallas import moe_grouped_matmul as gm
from deepspeed_tpu.ops.pallas import paged_attention as pa
from test_tpu_compile_parts import _values_made, one_chip  # noqa: F401

#: the serving cell's slots: 193 rows a layer of a state pool.  (Of 17 the
#: chip's compiler would rather tile the tails' pool by its three LAYERS,
#: and of 24 it keeps the whole 10 MB pool in VMEM: neither is the cell's
#: program.)
SLOTS, PAGES, HELD = 192, 64, 8


@pytest.fixture(scope="module")
def program(one_chip):
    """The step that carries chunks at the serving cell's widths, one
    period of four published layers, 8 held experts of the router's 320
    and a small vocabulary: ``(engine, compiled text, memory analysis,
    the weights' shapes)``."""
    from deepspeed_tpu.inference.v2 import engine_v2 as ev2
    from deepspeed_tpu.inference.v2.kv_cache import KVCacheConfig

    model = models.SolarOpen2Model(models.SolarOpen2Config(
        num_layers=4, gqa_layers=(0,), vocab_size=8192, max_seq_len=2048,
        held_experts=(0, HELD)))
    cache = KVCacheConfig(num_blocks=PAGES, block_size=128, max_seq_len=2048)
    placed = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    arg = lambda shape, dt=jnp.int32: placed(jax.ShapeDtypeStruct(shape, dt))
    mp = pytest.MonkeyPatch()
    for module in (pa, dsu, ctu, gm):
        mp.setattr(module, "reference_off_tpu", lambda interpret: False)
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
        yield engine, done.as_text(), done.memory_analysis(), shapes
    finally:
        mp.undo()


def test_the_delta_pool_has_the_kda_layers_and_is_moved_in_place(program):
    engine, text, memory, _ = program
    assert engine.last_attn_path == "pallas"            # at 8 KV heads
    # a published layer is two of the engine's: a mixer's and the experts'
    assert engine.last_layers_by_part == {"delta": 3, "kv": 1, "ffn": 4}
    pools = engine.pool
    assert sorted(pools) == ["delta", "kv"]
    state = pools["delta"]["delta"]
    assert state.shape == (3, SLOTS + 1, 64, 128, 128)
    assert state.dtype == jnp.float32
    assert pools["delta"]["conv"].shape == (3, SLOTS + 1, 3 * 24576)
    assert pools["kv"]["k"].shape == (1, PAGES, 128, 8, 128)
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for pool in pools.values() for a in pool.values())
    # every pool aliased in and out; the conv's tails lie flat, in tiles
    # of eight slots by 128 channels (the last tile's seven rows padding)
    tail = pools["delta"]["conv"]
    assert memory.alias_size_in_bytes == held + (-(SLOTS + 1) % 8) \
        * 3 * tail.shape[2] * tail.dtype.itemsize
    dims = lambda *shape: ",".join(str(n) for n in shape)
    whole = {dims(*state.shape), dims(*state.shape[1:]),
             dims(SLOTS, *state.shape[2:])}
    assert _values_made(text, "f32", whole, "delta_state_update") == []
    # a kernel call a KDA layer on the pool itself (its result IS the pool:
    # aliased), and the one attention layer's paged kernel
    moved = re.findall(r"delta_state_update[\w.]* = \(f32\[([\d,]+)\]", text)
    assert moved == [dims(*state.shape)] * 3, moved
    assert len(re.findall(r"paged_decode_attention[\w.]* = ", text)) == 1
    assert "ssm_state_update" not in text


def test_the_convs_tails_are_moved_where_they_lie_by_a_call_a_layer(program):
    """PR 58: the decode rows' conv reads a slot's tail once and writes it
    back shifted in the pool itself; as values the tails of a layer were
    sliced out, concatenated with the rows, gathered a row and written
    back (``bf16[SLOTS,4,24576]`` and its neighbours).  This is where a
    relayout copy of the pool would show."""
    engine, text, _, _ = program
    tail = engine.pool["delta"]["conv"]
    dims = lambda *shape: ",".join(str(n) for n in shape)
    layer = {dims(*tail.shape), dims(*tail.shape[1:]),
             dims(SLOTS, tail.shape[2]), dims(SLOTS, 3, 24576),
             dims(SLOTS, 4, 24576), dims(SLOTS + 1, 3, 24576)}
    assert _values_made(text, "bf16", layer, "conv_tail_update") == []
    # a call a KDA layer on the pool itself (its result IS the pool)
    moved = re.findall(r"conv_tail_update[\w.]* = \(bf16\[([\d,]+)\]", text)
    assert moved == [dims(*tail.shape)] * 3, moved
    calls = [line for line in text.splitlines()
             if re.search(r"conv_tail_update[\w.]* = \(", line)]
    assert all("output_to_operand_aliasing" in line for line in calls)


def test_the_expert_stacks_are_read_where_they_lie(program):
    _, text, _, shapes = program
    up, down = shapes["moe"]["w_up"], shapes["moe"]["w_down"]
    assert up.shape == (4, HELD, 4096, 1280)
    assert down.shape == (4, HELD, 1280, 4096)
    dims = lambda *shape: ",".join(str(n) for n in shape)
    stacks = {dims(*a.shape[at:]) for a in (up, down) for at in (0, 1)} \
        | {dims(4 * HELD, *a.shape[2:]) for a in (up, down)}
    assert _values_made(text, "bf16", stacks, "moe_grouped_matmul") == []
    # the chunk rows and the decode rows of a layer go through the expert
    # kernels together: two calls an expert layer, on the whole stacks
    names = re.findall(r"(moe_grouped_matmul\w*?)(?:\.\d+)? = ", text)
    assert sorted(names) == ["moe_grouped_matmul"] * 4 \
        + ["moe_grouped_matmul_swiglu"] * 4, names
