"""The serving program of a model that DRAFTS (``models/exaone_moe.py``: a
multi-token-prediction layer behind the trunk), compiled for a described
v5e with no chip (``test_tpu_compile_parts.py``'s way) at the serving
cell's widths: two rows a sequence a step through the paged kernel as ONE
grid row of two tokens, in the window rings and in the full pool, the
drafting layer's keys one more layer of the full pool, both pools and the
sequences' lengths carried in place, the expert stacks (the trunk's and the
drafting layer's own) read where they lie."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu import models
from deepspeed_tpu.ops.pallas import moe_grouped_matmul as gm
from deepspeed_tpu.ops.pallas import paged_attention as pa
from test_tpu_compile_parts import _values_made, one_chip  # noqa: F401

#: the serving cell's slots, page and pool (a pool of a few MB the compiler
#: would keep in VMEM, which is not the cell's program); a small share and
#: vocabulary
SLOTS, PAGES, PAGE, HELD = 128, 32768, 16, 4


def _program(one_chip, n_steps, with_chunks):
    from deepspeed_tpu.inference.v2 import engine_v2 as ev2
    from deepspeed_tpu.inference.v2.kv_cache import KVCacheConfig

    model = models.ExaoneMoeModel(models.ExaoneMoeConfig(
        vocab_size=8192, max_seq_len=4096, held_experts=(0, HELD)))
    cache = KVCacheConfig(num_blocks=PAGES, block_size=PAGE, max_seq_len=4096)
    placed = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    arg = lambda shape, dt=jnp.int32: placed(jax.ShapeDtypeStruct(shape, dt))
    mp = pytest.MonkeyPatch()
    for module in (pa, gm):
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
        seq = {name: arg((SLOTS,)) for name in ("len", "tok", "draft")}
        chunks, kw = None, {}
        if with_chunks:
            chunks = (arg((Bp, engine.chunk)), arg((Bp, blocks)), arg((Bp,)),
                      arg((Bp,)), arg((Bp,)), arg((Bp,)), arg((Bp,)))
            kw["kb"] = 16
        done = jax.jit(
            functools.partial(engine._draft_burst_fn, n_steps=n_steps, **kw),
            donate_argnums=(1,)).lower(
                placed(shapes), placed(engine.pool), seq,
                arg((SLOTS,), jnp.bool_), arg((SLOTS, blocks)),
                arg((SLOTS,)), arg((), jnp.float32),
                placed(jax.eval_shape(lambda: jax.random.PRNGKey(0))),
                arg((SLOTS,)), chunks).compile()
        return engine, done.as_text(), done.memory_analysis(), shapes
    finally:
        mp.undo()


def _rooted_in_scatter(text, fusion):
    """Whether instruction ``fusion`` is a fusion whose root is a scatter
    into its first parameter."""
    called = re.search(rf"^\s*(?:ROOT )?%?{re.escape(fusion)} = .* fusion\("
                       rf".*calls=%?([\w.-]+)", text, re.M)
    if not called:
        return False
    body = text.split(f"%{called.group(1)} (", 1)[-1].split("\n}", 1)[0]
    return re.search(r"ROOT %?[\w.-]+ = \S+ scatter\(%?param_0", body) \
        is not None


@pytest.fixture(scope="module")
def step(one_chip):
    """The step that carries chunks: ``(engine, compiled text, memory
    analysis, the weights' shapes)``."""
    return _program(one_chip, 1, True)


def test_two_rows_a_sequence_are_one_grid_row_of_the_paged_kernel(step):
    engine, text, _, _ = step
    assert engine.last_attn_path == "pallas"
    assert engine.last_layers_by_part == {"full": 1, "window": 4, "mtp": 1}
    # every layer's decode rows: the trunk's five and the drafting layer's
    assert len(re.findall(r"paged_decode_attention[\w.]* = ", text)) == 6
    assert engine.last_attn_query_tokens == {"full/decode": 2,
                                             "window/decode": 2}
    # a ring of 16 pages (8 for the window, 8 for a chunk) holds the ten a
    # two-row step may touch
    assert engine.cache_config.ring_blocks == 16
    assert engine.last_attn_pages_per_step["window"] <= 10


def test_the_pools_are_carried_in_place_the_drafting_layers_keys_among_them(
        step):
    engine, text, memory, _ = step
    pools = engine.pool
    assert sorted(pools) == ["full", "window"]
    full, ring = pools["full"]["k"], pools["window"]["k"]
    assert full.shape == (2, PAGES, PAGE, 8, 128)       # layer 7's and mtp's
    assert ring.shape == (4, 1 + SLOTS * 16, PAGE, 8, 128)
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for pool in pools.values() for a in pool.values())
    assert memory.alias_size_in_bytes == held
    dims = lambda *shape: ",".join(str(n) for n in shape)
    whole = {dims(*a.shape) for a in (full, ring)} \
        | {dims(*a.shape[1:]) for a in (full, ring)} \
        | {dims(a.shape[0] * a.shape[1], *a.shape[2:]) for a in (full, ring)}
    # rows and pages are scattered into the carried pool where it lies
    # (a scatter, alone or as the root of its fusion, on the pool itself);
    # nothing else makes a value of a pool's size: no copy, no re-layout
    made = [m for m in _values_made(text, "bf16", whole,
                                    "paged_decode_attention")
            if not m.startswith("scatter ")
            and not _rooted_in_scatter(text, m.split()[1])]
    assert made == []


def test_the_expert_stacks_are_read_where_they_lie(step):
    _, text, _, shapes = step
    up = shapes["layers"]["moe"]["w_up"]
    own = shapes["mtp"]["layer"]["moe"]["w_up"]
    assert up.shape == (4, HELD, 6144, 2048)
    assert own.shape == (1, HELD, 6144, 2048)
    dims = lambda *shape: ",".join(str(n) for n in shape)
    stacks = {dims(*up.shape), dims(*up.shape[1:]),
              dims(4 * HELD, *up.shape[2:]), dims(4, HELD, 2048, 6144),
              dims(HELD, 2048, 6144), dims(4 * HELD, 2048, 6144)}
    assert _values_made(text, "bf16", stacks, "moe_grouped_matmul") == []
    # gate/up and down, a sparse layer: the trunk's four and the drafting
    # layer's
    assert len(re.findall(r"moe_grouped_matmul[\w.]* = ", text)) == 10


@pytest.mark.slow    # a second program's compile: the step above has its body
def test_the_burst_compiles_and_carries_the_lengths_on_the_device(one_chip):
    engine, text, memory, _ = _program(one_chip, 8, False)
    assert engine.last_attn_path == "pallas"
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for pool in engine.pool.values() for a in pool.values())
    assert memory.alias_size_in_bytes == held
    # ids [steps, slots, 2] and the lengths the call found come back
    assert re.search(r"s32\[8,128,2\]", text)
