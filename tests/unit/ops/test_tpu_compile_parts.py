"""The serving program of a model whose layers are ONE part alone
(``models/nemotron_h.py``: a Mamba-2 mixer, or attention, or LatentMoE
experts), compiled for a described v5e with no chip
(``test_tpu_compile_state.py``'s way): each part's pool has the layers of
its own part and rides the layer scan in place; the expert stacks ride
whole and are read at their layer by the grouped kernels.  No instruction
makes a value of a pool's or a stack's size; at the serving cell's slots
the conv's tails are moved where they lie too (PR 60)."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu import models
from deepspeed_tpu.ops.pallas import conv_tail_update as ctu
from deepspeed_tpu.ops.pallas import moe_grouped_matmul as gm
from deepspeed_tpu.ops.pallas import paged_attention as pa
from deepspeed_tpu.ops.pallas import ssm_state_update as ssu

SLOTS, PAGES, HELD = 16, 64, 16
#: two mixers, an attention layer, two expert layers
PATTERN = "ME*EM"
#: ``serve-longreason-nemotron3-super-l11``'s layers and batch slots
CELL_PATTERN, CELL_SLOTS = "MEMEMEM*EME", 128


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


def _compiled(one_chip, pattern, slots):
    """The step that carries chunks at the serving cell's widths, 16 held
    experts of the router's 512 and a small vocabulary, its layers
    ``pattern``: ``(engine, compiled text, memory analysis, the weights'
    shapes)``."""
    from deepspeed_tpu.inference.v2 import engine_v2 as ev2
    from deepspeed_tpu.inference.v2.kv_cache import KVCacheConfig

    model = models.NemotronHModel(models.NemotronHConfig(
        pattern=pattern, vocab_size=8192, max_seq_len=2048,
        held_experts=(0, HELD)))
    cache = KVCacheConfig(num_blocks=PAGES, block_size=128, max_seq_len=2048)
    placed = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    arg = lambda shape, dt=jnp.int32: placed(jax.ShapeDtypeStruct(shape, dt))
    mp = pytest.MonkeyPatch()
    for module in (pa, ssu, gm, ctu):
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
                                             max_batch_slots=slots)
        blocks, Bp = cache.max_blocks_per_seq, engine.prefill_batch
        rows = (arg((slots,)), (arg((slots,)), arg((slots + Bp,))),
                arg((slots,)), arg((slots, blocks)), arg((slots,)))
        chunks = (arg((Bp, engine.chunk)), arg((Bp, blocks)), arg((Bp,)),
                  arg((Bp,)), None)
        done = jax.jit(
            functools.partial(engine._decode_burst_fn, n_steps=1, kb=8),
            donate_argnums=(1,)).lower(
                placed(shapes), placed(engine.pool), *rows,
                arg((), jnp.float32),
                placed(jax.eval_shape(lambda: jax.random.PRNGKey(0))), None,
                chunks, (arg((slots,)), arg((Bp,)))).compile()
        return engine, done.as_text(), done.memory_analysis(), shapes
    finally:
        mp.undo()


@pytest.fixture(scope="module")
def program(one_chip):
    """Five layers at 16 slots (the tails' pool ``[2, 17, 30720]`` lies
    with its layers on the sublanes there: the conv runs its reference)."""
    return _compiled(one_chip, PATTERN, SLOTS)


@pytest.fixture(scope="module")
def cell_program(one_chip):
    """The serving cell's own eleven layers AT THE CELL'S SLOTS: the tails'
    pool ``[5, 129, 30720]`` is what the chip holds row-major and too large
    to be kept in VMEM, so the conv's kernel is what the cell runs."""
    return _compiled(one_chip, CELL_PATTERN, CELL_SLOTS)


def _values_made(text, dtype, shapes, kernel="ssm_state_update"):
    """The instructions that make a value of one of ``shapes`` (dims as
    text) but pass a buffer on or write into it in place (a call of
    ``kernel`` does: its result IS its aliased operand)."""
    passes_on = {"parameter", "bitcast", "get-tuple-element"}
    roots, computation = {}, None
    for line in text.splitlines():
        head = re.match(r"%?([\w.-]+) \(.*\{$", line)
        root = re.match(r"\s*ROOT %?[\w.-]+ = \S+ ([\w-]+)\(", line)
        if head:
            computation = head.group(1)
        elif root:
            roots[computation] = root.group(1)
    made = []
    for name, shape, opcode, rest in re.findall(
            r"^\s*(?:ROOT )?%?([\w.-]+) = " + dtype
            + r"\[([\d,]+)\]\S* ([\w-]+)\((.*)$", text, re.M):
        if shape not in shapes or opcode in passes_on \
                or opcode == "dynamic-update-slice" \
                or (opcode == "custom-call" and kernel in name):
            continue
        called = re.search(r"calls=%?([\w.-]+)", rest)
        if not (opcode == "fusion" and called
                and roots.get(called.group(1)) == "dynamic-update-slice"):
            made.append(f"{opcode} {name} makes {dtype}[{shape}]")
    return made


def test_each_parts_pool_has_its_own_layers_and_is_moved_in_place(program):
    engine, text, memory, _ = program
    assert engine.last_attn_path == "pallas"            # at 2 KV heads
    assert engine.last_layers_by_part == {"ssm": 2, "kv": 1, "ffn": 2}
    pools = engine.pool
    assert sorted(pools) == ["kv", "ssm"]
    state = pools["ssm"]["ssm"]
    # the mixer's layers alone; two heads of 64 a lane row
    assert state.shape == (2, SLOTS + 1, 64, 128, 128)
    assert pools["kv"]["k"].shape == (1, PAGES, 128, 2, 128)
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for pool in pools.values() for a in pool.values())
    assert memory.alias_size_in_bytes == held
    dims = lambda *shape: ",".join(str(n) for n in shape)
    whole = {dims(*state.shape), dims(*state.shape[1:]),
             dims(SLOTS, *state.shape[2:]),
             dims(*state.shape[:2], 128, 128, 64)}
    assert _values_made(text, "f32", whole) == []
    # a kernel call a mixer layer on the pool itself (its result IS the
    # pool: aliased), in the form that holds several heads a lane row
    moved = re.findall(r"ssm_state_update_lanes[\w.]* = \(f32\[([\d,]+)\]",
                       text)
    assert moved == [dims(*state.shape)] * 2, moved
    assert len(re.findall(r"paged_decode_attention[\w.]* = ", text)) == 1


def test_the_expert_stacks_are_read_where_they_lie(program):
    _, text, _, shapes = program
    up, down = shapes["moe"]["w_up"], shapes["moe"]["w_down"]
    assert up.shape == (2, HELD, 1024, 2688)
    assert down.shape == (2, HELD, 2688, 1024)
    dims = lambda *shape: ",".join(str(n) for n in shape)
    stacks = {dims(*a.shape[at:]) for a in (up, down) for at in (0, 1)} \
        | {dims(2 * HELD, *a.shape[2:]) for a in (up, down)}
    assert _values_made(text, "bf16", stacks) == []
    # the chunk rows and the decode rows of a layer go through the expert
    # kernels together: two calls an expert layer, on the whole stacks
    names = re.findall(r"(moe_grouped_matmul\w*?)(?:\.\d+)? = ", text)
    assert sorted(names) == ["moe_grouped_matmul"] * 2 \
        + ["moe_grouped_matmul_relu2"] * 2, names
    for name, operand in (("moe_grouped_matmul_relu2", up),
                          ("moe_grouped_matmul", down)):
        calls = [line for line in text.splitlines()
                 if re.search(rf"{name}(\.\d+)? = ", line)]
        flat = f"bf16[{dims(2 * HELD, *operand.shape[2:])}]"
        assert all(flat in line for line in calls), (name, flat)


def test_the_cells_step_moves_the_convs_tails_where_they_lie(cell_program):
    """PR 60: Nemotron-H's kind states the conv's tail ``in_place``, so at
    the cell's 129 slots a mixer layer's decode rows go through ONE
    ``conv_tail_update`` call whose first result IS the tails' pool
    (aliased) and whose second is the slots' tails as they lay, for XLA's
    conv chain (this family has the op move only: WHO computes the conv's
    output is what its check's tokens followed, ``PERF.md`` §6, PR 60, so
    the second result's shape is a pin, not a detail); what is left on the
    pool beside the five calls is a chunk
    row's slot written in place.  No ``copy``, no ``remat`` twin, nothing
    that makes a value of the pool's shape or of a layer of it: where the
    tail was a value, XLA kept three rematerialised copies of the pool a
    step (``dynamic_update_slice.*.remat_compressed``), and an XLA read of
    the pool beside the aliased call makes it copy the pool a layer."""
    engine, text, _, _ = cell_program
    tail = engine.pool["ssm"]["conv"]
    mixers, slots, conv_dim = CELL_PATTERN.count("M"), CELL_SLOTS, 10240
    assert tail.shape == (mixers, slots + 1, 3 * conv_dim)
    assert ctu.rows_on_sublanes(*tail.shape[:2], tail.dtype.itemsize)
    dims = lambda *shape: ",".join(str(n) for n in shape)
    held = {dims(*tail.shape), dims(*tail.shape[1:])}
    assert _values_made(text, "bf16", held, "conv_tail_update") == []
    calls = [line for line in text.splitlines()
             if re.search(r"conv_tail_update[\w.]* = \(", line)]
    assert len(calls) == mixers
    for line in calls:
        assert re.search(r"= \(bf16\[" + dims(*tail.shape) + r"\]\S*, "
                         r"bf16\[" + dims(*tail.shape[1:]) + r"\]", line)
        assert "output_to_operand_aliasing" in line
    on_the_pool = re.findall(
        r"^\s*%?([\w.-]+) = bf16\[" + dims(*tail.shape) + r"\]\S* ([\w-]+)\(",
        text, re.M)
    assert not [name for name, _ in on_the_pool if "remat" in name]
    assert not [name for name, op in on_the_pool
                if op in ("copy", "copy-start", "copy-done")]
    # a chunk row's slot a layer, written where it lies
    assert sum(op == "dynamic-update-slice" for _, op in on_the_pool) \
        == mixers * engine.prefill_batch
