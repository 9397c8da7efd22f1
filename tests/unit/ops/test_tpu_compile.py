"""Kernels of the serving path compiled for a described v5e — no chip
attached, nothing runs: what Mosaic or XLA:TPU refuses at real widths is
caught here and not on the chip.  The topology is described inside a
fixture (one process may hold libtpu; see the on-chip-measurement guide),
and every such compile lives in this one file."""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from deepspeed_tpu.ops.pallas import paged_attention as pa


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("rows, h, kv_h, dtype, window", [
    (32, 32, 8, jnp.bfloat16, 4096),     # Mistral-7B, the serving cell
    (32, 32, 8, jnp.bfloat16, None),
    (8, 8, 2, jnp.bfloat16, 4096),       # a tensor-parallel shard of it
    (8, 32, 32, jnp.bfloat16, None),     # no grouping (Llama-7B)
    (8, 32, 8, jnp.float32, 4096),
    (32, 16, 16, jnp.bfloat16, None),    # OLMoE-1B-7B: pages [16, 16, 128]
], ids=["mistral7b", "mistral7b_no_window", "tp4_shard", "mha", "float32",
        "olmoe_16_heads"])
def test_paged_decode_compiles_for_v5e(one_chip, rows, h, kv_h, dtype,
                                       window):
    """The compiled program is the Mosaic call alone: the pool reaches it
    through a bitcast, never a copy."""
    d, page, pages, max_blocks = 128, 16, 3200, 512

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def fn(q, k_pool, v_pool, tables, lengths):
        return pa.paged_decode_attention(q, k_pool, v_pool, tables, lengths,
                                         interpret=False, window=window)

    pool = arg((pages, page, kv_h, d), dtype)
    text = jax.jit(fn).lower(
        arg((rows, h, d), dtype), pool, pool,
        arg((rows, max_blocks), jnp.int32),
        arg((rows,), jnp.int32)).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    assert "paged_decode_attention" in text
    # "%name = <shape and layout> <opcode>(...": what holds the pool's shape
    made = re.findall(rf"= \w+\[{pages},\S* ([\w-]+)\(", text)
    assert made and set(made) <= {"parameter", "bitcast"}, made


#: the serving cells' models at their published widths, cut to two layers
#: (the pool's page shape is what is held here: ``[16, 8, 128]`` under a
#: 4,096-token window and ``[16, 16, 128]``), and each cell's context limit
_SERVING_CELLS = {
    "mistral7b": dict(vocab_size=32000, hidden_size=4096,
                      intermediate_size=14336, num_heads=32, num_kv_heads=8,
                      max_seq_len=8192, sliding_window=4096),
    "olmoe": dict(vocab_size=50304, hidden_size=2048, intermediate_size=1024,
                  num_heads=16, num_kv_heads=16, max_seq_len=4096,
                  num_experts=64, top_k=8, norm_topk_prob=False,
                  aux_loss_coef=0.0),
}
_POOL_LAYERS, _POOL_PAGES, _PAGE, _SLOTS = 2, 3200, 16, 32


def _kernel_body(fn, *args):
    """Of the one Pallas call ``fn`` traces: the shapes of the refs its
    body takes (operands as blocked, the output, the scratch buffers) and
    how many copies it starts and awaits and how many dots it makes, as
    written (a loop's body once).  What the Mosaic module is built from:
    the compiled text holds that module as bytecode."""
    def calls(jaxpr, name):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == name:
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub, name)

    call, = calls(jax.make_jaxpr(fn)(*args).jaxpr, "pallas_call")
    body = call.params["jaxpr"]
    return ([v.aval.shape for v in body.invars],
            {name: len(list(calls(body, name)))
             for name in ("dma_start", "dma_wait", "dot_general")})


@pytest.mark.parametrize("shape, refs, counts", [
    # Mistral-7B and OLMoE-1B-7B: one plane, P = 16, [M, rows, 128] pools
    # and [2, 16, rows, 128] buffers, a K and a V copy a page
    ("mistral7b", [(3200, 128, 128)] * 2 + [(2, 16, 128, 128)] * 2
     + [(32, 2048)], {"dma_start": 4, "dma_wait": 2, "dot_general": 2}),
    ("olmoe", [(3200, 256, 128)] * 2 + [(2, 16, 256, 128)] * 2
     + [(16, 4096)], {"dma_start": 4, "dma_wait": 2, "dot_general": 2}),
    # the latent cache: five planes in one copy a page, 1,024 keys a
    # step, no V pool and no V buffer; a dot a plane and four PV dots
    ("latent", [(5, 2050, 128, 128), (2, 5, 8, 128, 128), (128, 1024)],
     {"dma_start": 2, "dma_wait": 1, "dot_general": 9}),
])
def test_cells_of_one_plane_and_the_latent_cell_keep_their_kernel(
        shape, refs, counts):
    """The dense, OLMoE and latent cells' calls are built as they were
    before K's planes shared a descriptor and ``P`` followed the KV heads
    and the window: the same operand views, buffers, head mask, copies
    and dots (nothing is compiled here: the call's own jaxpr)."""
    arg = jax.ShapeDtypeStruct
    if shape == "latent":
        fn = lambda q, k, t, l: pa.paged_decode_attention(
            q, k, None, t, l, interpret=False, k_planes=5, plane_stride=2050,
            v_in_k=512, scale=192 ** -0.5)
        args = (arg((128, 128, 576), jnp.bfloat16),
                arg((5 * 2050, 128, 1, 128), jnp.bfloat16),
                arg((128, 128), jnp.int32), arg((128,), jnp.int32))
    else:
        h, kv_h, window = {"mistral7b": (32, 8, 4096),
                           "olmoe": (16, 16, None)}[shape]
        fn = lambda q, k, v, t, l: pa.paged_decode_attention(
            q, k, v, t, l, interpret=False, window=window)
        pool = arg((3200, 16, kv_h, 128), jnp.bfloat16)
        args = (arg((32, h, 128), jnp.bfloat16), pool, pool,
                arg((32, 512), jnp.int32), arg((32,), jnp.int32))
    got, got_counts = _kernel_body(fn, *args)
    for ref in set(refs):
        assert got.count(ref) == refs.count(ref), (ref, got)
    # lengths, table, q, head mask, pools, output, buffers, semaphores, slot
    assert len(got) == (9 if shape == "latent" else 11)
    assert got_counts == counts


#: the T = 1 calls' Mosaic modules (canonicalised, printed without
#: locations) as the parent of PR 46 built them: length and the leading 16
#: hex digits of the text's SHA-256.  A grid row of several query tokens is
#: the same kernel with ``T`` from the operand's shape, and a row a token
#: must stay the kernel the four serving cells were measured with
_T1_MODULES = {
    "mistral7b": (17209, "1964a50c8e9b802f"),
    "olmoe": (16348, "ecfc36e9b4b0546f"),
    "hybrid_full": (17364, "70c8acb562fc898c"),
    "hybrid_window": (18646, "975a9bec9064a21a"),
    "latent": (17914, "3782a5e87d6f7e48"),
}
#: keyword arguments, q, K pool, V pool, table width, a sink
_T1_CALLS = {
    "mistral7b": (dict(window=4096), (32, 32, 128), (3200, 16, 8, 128),
                  (3200, 16, 8, 128), 512, False),
    "olmoe": (dict(), (32, 16, 128), (3200, 16, 16, 128),
              (3200, 16, 16, 128), 256, False),
    "hybrid_full": (dict(k_planes=2, plane_stride=2 * 40960), (256, 64, 192),
                    (4 * 40960, 16, 4, 128), (2 * 40960, 16, 4, 128), 512,
                    False),
    "hybrid_window": (dict(k_planes=2, plane_stride=5 * 4097, window=128),
                      (256, 64, 192), (10 * 4097, 16, 8, 128),
                      (5 * 4097, 16, 8, 128), 512, True),
    "latent": (dict(k_planes=5, plane_stride=5 * 6144, v_in_k=512,
                    scale=192 ** -0.5), (128, 128, 576),
               (25 * 6144, 128, 1, 128), None, 128, False),
}


def _mosaic_module(monkeypatch, capsys, one_chip, kwargs, q, k, v, width,
                   sink, tokens=None):
    """The Mosaic module of the one paged call these shapes trace, as
    ``pallas_call(debug=True)`` prints it while lowering for the chip."""
    from jax.experimental import pallas as pl

    real = pl.pallas_call
    monkeypatch.setattr(pl, "pallas_call",
                        lambda *a, **kw: real(*a, **{**kw, "debug": True}))
    arg = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    rows = q[0] if tokens is None else q[0] // tokens
    q = q if tokens is None else (rows, tokens) + q[1:]
    jax.clear_caches()      # the call is a jitted function of its own
    capsys.readouterr()
    jax.jit(lambda q, k, v, t, l, s: pa.paged_decode_attention(
        q, k, v, t, l, interpret=False, sink=s, **kwargs)).lower(
            arg(q), arg(k), None if v is None else arg(v),
            arg((rows, width), jnp.int32), arg((rows,), jnp.int32),
            arg(q[-2:-1], jnp.float32) if sink else None)
    printed = capsys.readouterr().out
    return printed.split("The Mosaic module for pallas_call", 1)[1].split(
        "\n", 1)[1]


@pytest.mark.parametrize("call", sorted(_T1_CALLS))
def test_a_row_a_token_lowers_to_the_kernel_the_cells_were_measured_with(
        monkeypatch, capsys, one_chip, call):
    import hashlib

    text = _mosaic_module(monkeypatch, capsys, one_chip, *_T1_CALLS[call])
    assert (len(text), hashlib.sha256(text.encode()).hexdigest()[:16]) \
        == _T1_MODULES[call]


def test_several_tokens_a_row_is_another_module_of_the_same_kernel(
        monkeypatch, capsys, one_chip):
    """The latent chunk rows' call at the rule's ``T``: ``T·h`` query rows
    and their accumulator, the step the rule gives, one name."""
    shapes = (128, 1, 128, 640, 2, 128, 0)
    tokens = pa.query_tokens_per_row(128, *shapes)
    pages = pa.pages_per_step(*shapes, None, tokens)
    kwargs, q, k, v, width, sink = _T1_CALLS["latent"]
    text = _mosaic_module(monkeypatch, capsys, one_chip, kwargs,
                          (256,) + q[1:], k, v, width, sink, tokens=tokens)
    assert f"memref<1x{tokens * 128}x640xbf16" in text         # the queries
    assert f"memref<2x5x{pages}x128x128xbf16" in text          # K's slots
    assert f"vector<{tokens * 128}x512xf32>" in text           # accumulator


def _values_made(text):
    """``(name, dims, opcode, operands…)`` of every instruction of a
    compiled program's text whose value is one array (a tuple's shape
    starts with a parenthesis and is not matched)."""
    return re.findall(r"^\s*(?:ROOT )?%?([\w.-]+) = \w+\[([\d,]+)\]\S* "
                      r"([\w-]+)\((.*)$", text, re.M)


def pool_value_faults(text, layers, pages, page, kv_h, d):
    """What in a compiled program's text makes a value of the KV pool's
    shape, or of one layer of it, other than by passing the buffer on
    (``parameter``, ``bitcast``, ``get-tuple-element``; ``while`` and
    ``tuple`` have tuple shapes) or writing rows or pages into it in place
    (a ``scatter`` or ``dynamic-update-slice``, or a fusion whose root is
    one).  A ``copy``, a ``dynamic-slice`` of a layer and the update-slice
    that puts a layer back are all among them.  Empty for a program in
    which the pool is a carried buffer addressed by (layer, page)."""
    def dims(*shape):
        return ",".join(str(n) for n in shape)

    whole = {dims(layers, pages, page, kv_h, d),           # as it is held
             dims(layers * pages, page, kv_h, d),          # flat, 4-D
             dims(layers * pages, page * kv_h, d)}         # as the kernel reads
    layer = {dims(pages, page, kv_h, d), dims(1, pages, page, kv_h, d),
             dims(pages, page * kv_h, d)}
    passes_on = {"parameter", "bitcast", "get-tuple-element"}
    writes = {"scatter", "dynamic-update-slice"}
    made = _values_made(text)
    roots, computation = {}, None         # computation -> its root's opcode
    for line in text.splitlines():
        head = re.match(r"%?([\w.-]+) \(.*\{$", line)
        root = re.match(r"\s*ROOT %?[\w.-]+ = \S+ ([\w-]+)\(", line)
        if head:
            computation = head.group(1)
        elif root:
            roots[computation] = root.group(1)
    faults = []
    for name, shape, opcode, rest in made:
        if shape in layer:
            faults.append(f"{opcode} {name} makes one layer [{shape}]")
        elif shape in whole and opcode not in passes_on | writes:
            called = re.search(r"calls=%?([\w.-]+)", rest)
            if not (opcode == "fusion" and called
                    and roots.get(called.group(1)) in writes):
                faults.append(f"{opcode} {name} makes the pool [{shape}]")
    return faults


@pytest.fixture(scope="module",
                params=sorted(_SERVING_CELLS) + ["mistral7b_tp4"])
def serving_programs(request, topo, one_chip):
    """A cell's engine over shapes alone (no weight and no pool exists), and
    a function that compiles one of its programs for the described chip;
    ``_tp4``: for the four described chips, heads split over ``tensor``."""
    from jax.sharding import NamedSharding, PartitionSpec

    from deepspeed_tpu import models
    from deepspeed_tpu.inference.v2 import engine_v2 as ev2
    from deepspeed_tpu.inference.v2.kv_cache import KVCacheConfig
    from deepspeed_tpu.ops.pallas import moe_grouped_matmul as gm
    from deepspeed_tpu.parallel.mesh import (MeshLayout, build_mesh,
                                             strip_manual_axes)

    cell, _, tp = request.param.partition("_tp")
    tp = int(tp or 1)
    widths = _SERVING_CELLS[cell]
    mesh = build_mesh(MeshLayout(tp=tp), devices=topo.devices) \
        if tp > 1 else None
    family = (models.OlmoeConfig, models.OlmoeModel) \
        if "num_experts" in widths else (models.LlamaConfig, models.LlamaModel)
    model = family[1](family[0](num_layers=_POOL_LAYERS, remat=False,
                                **widths), mesh=mesh)
    cache = KVCacheConfig(num_blocks=_POOL_PAGES, block_size=_PAGE,
                          max_seq_len=widths["max_seq_len"])

    def placed(tree, spec=PartitionSpec()):
        """Shapes with their place: the one chip, or the mesh under
        ``spec`` (one for every leaf, or a tree of them)."""
        def at(a, where):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=where)

        if mesh is None:
            return jax.tree.map(lambda a: at(a, one_chip), tree)
        if isinstance(spec, PartitionSpec):
            spec = jax.tree.map(lambda _: spec, tree)
        return jax.tree.map(lambda a, s: at(a, NamedSharding(mesh, s)),
                            tree, spec)

    def arg(shape, dt=jnp.int32):
        return placed(jax.ShapeDtypeStruct(shape, dt))

    mp = pytest.MonkeyPatch()
    # the engine and both kernels ask the platform, which is the CPU here:
    # steer them onto the path they take on the chip, and build no pool
    mp.setattr(pa, "reference_off_tpu", lambda interpret: False)
    mp.setattr(gm, "reference_off_tpu", lambda interpret: False)
    real_pool = ev2.init_kv_pool
    mp.setattr(ev2, "init_kv_pool",
               lambda ad, cc: jax.eval_shape(lambda: real_pool(ad, cc)))
    # weights in the model's dtype, as the cells hold them (bf16)
    shapes = jax.eval_shape(
        lambda key: jax.tree.map(lambda w: w.astype(model.config.dtype),
                                 model.init_params(key)),
        jax.random.PRNGKey(0))
    engine = ev2.RaggedInferenceEngineV2(model, shapes, cache,
                                         max_batch_slots=_SLOTS)
    # given the mesh at birth the engine would place real weights on it
    engine.mesh, engine._tp = mesh, tp
    params = placed(shapes, jax.tree.map(
        lambda spec: strip_manual_axes(*spec), model.param_specs(shapes),
        is_leaf=lambda spec: isinstance(spec, PartitionSpec)))
    pool = placed(engine.pool,
                  PartitionSpec(None, None, None, "tensor", None))
    blocks = cache.max_blocks_per_seq
    common = (arg((), jnp.float32),
              placed(jax.eval_shape(lambda: jax.random.PRNGKey(0))))

    @functools.cache                   # three tests read a program's text
    def compiled_text(program):
        """``decode_burst_<n>``: the burst of n steps; ``chunks_<kb>``: the
        one-step program with a round's chunks riding in it under the page
        bucket kb (``deepest``: the whole table)."""
        kind, _, n = program.rpartition("_")
        rows = (arg((_SLOTS,)),
                (arg((_SLOTS,)), arg((_SLOTS + engine.prefill_batch,))),
                arg((_SLOTS,)), arg((_SLOTS, blocks)), arg((_SLOTS,)))
        if kind == "decode_burst":
            fn = functools.partial(engine._decode_burst_fn, n_steps=int(n))
            chunks = None
        else:
            fn = functools.partial(
                engine._decode_burst_fn, n_steps=1,
                kb=blocks if n == "deepest" else int(n))
            chunks = (arg((engine.prefill_batch, engine.chunk)),
                      arg((engine.prefill_batch, blocks)),
                      arg((engine.prefill_batch,)),
                      arg((engine.prefill_batch,)), None)
        return jax.jit(fn, donate_argnums=(1,)).lower(
            params, pool, *rows, *common, None, chunks).compile().as_text()

    yield engine, compiled_text
    mp.undo()


_PROGRAMS = ["decode_burst_1", "decode_burst_8", "chunks_8",
             "chunks_deepest"]


@pytest.mark.parametrize("program", _PROGRAMS)
def test_engine_programs_keep_the_pool_in_place_on_v5e(serving_programs,
                                                       program):
    """Both serving cells' programs at the cells' pool shapes: the pool is
    a carried buffer that is passed on, written by scatters that alias
    it, and read by the paged kernel through a bitcast.  No instruction
    copies it and none makes a value of one layer's shape (the parent's
    programs sliced a layer out for the kernel and put it back: 62% of the
    dense cell's device time, PERF.md PR 28).  The step that carries a
    round's chunks writes their pages and the decode rows into the same
    buffer and holds the paged kernel once, like the plain step; under
    ``tensor`` each chip does so on its own KV heads (the chunks' pages
    addressed under GSPMD had the whole pool re-laid out around every
    access: PERF.md §6, PR 37)."""
    engine, compiled_text = serving_programs
    ad = engine.adapter
    kv_h = ad.kv_heads // engine._tp          # what one chip holds
    text = compiled_text(program)
    assert 'custom_call_target="tpu_custom_call"' in text
    assert len(re.findall(r"paged_decode_attention[\w.]* = ", text)) == 1
    # what the engine says its kernel was built with: 256 keys a step in
    # both cells (8 and 16 KV heads); a TP shard's 2 KV heads get 1,024
    assert list(engine.last_attn_pages_per_step.values()) \
        == [64 if engine._tp > 1 else 16]
    assert pool_value_faults(text, _POOL_LAYERS, _POOL_PAGES, _PAGE, kv_h,
                             ad.head_dim) == []
    # a decode step's rows are scattered into the pool as it is held, a
    # chunk's whole pages into its page matrices, the kernel's own view:
    # K and V, each once a layer
    as_held = f"bf16[{_POOL_LAYERS},{_POOL_PAGES},{_PAGE},{kv_h},"
    matrices = f"bf16[{_POOL_LAYERS * _POOL_PAGES},{_PAGE * kv_h},"
    writes = lambda shape: len(re.findall(
        rf"= {re.escape(shape)}\S* scatter\(", text))
    assert writes(as_held) == 2
    assert writes(matrices) == (2 if program.startswith("chunks") else 0)


def _matmul_rows(text):
    """The leading dim of every matmul's result in a compiled program."""
    return [int(n) for n in re.findall(
        r"= \w+\[(\d+),[\d,]+\]\S* convolution\(", text)]


def test_a_step_with_chunks_reads_each_weight_once_on_v5e(serving_programs):
    """The chunks' rows and the decode rows go through a layer's matrices
    (and the head) TOGETHER: every weight matmul of the step has Bp·C + B
    rows, none has the chunks' rows or the decode rows alone (the two
    programs of a round each streamed the model for itself: PERF.md §6,
    PR 37).  The engine has no prefill program to compile."""
    engine, compiled_text = serving_programs
    chunk_rows = engine.prefill_batch * engine.chunk
    rows = _matmul_rows(compiled_text("chunks_8"))
    assert rows.count(chunk_rows + _SLOTS) >= 4    # q/k/v/o, MLP or router
    assert not {chunk_rows, _SLOTS} & set(rows)
    assert _SLOTS in _matmul_rows(compiled_text("decode_burst_1"))
    assert not hasattr(engine, "_prefill")
    assert not hasattr(engine, "_prefill_batch_fn")


def expert_value_faults(text, layers, experts, hidden, inner):
    """What in a compiled program's text makes a value of one layer's
    expert weights (``[E, H, I]`` or ``[E, I, H]``, with or without a
    leading 1: a ``dynamic-slice`` of the stack, the ``copy`` that makes it
    a custom call's operand), or makes the ``[L, E, …]`` stack or its flat
    ``[L·E, …]`` view other than by passing the buffer on.  Empty for a
    program whose grouped matmul reads a layer's experts where they lie."""
    def dims(*shape):
        return ",".join(str(n) for n in shape)

    tails = [(hidden, inner), (inner, hidden)]
    layer = {dims(*lead, experts, *tail)
             for tail in tails for lead in ((), (1,))}
    whole = {dims(*lead, *tail) for tail in tails
             for lead in ((layers, experts), (layers * experts,))}
    passes_on = {"parameter", "bitcast", "get-tuple-element"}
    return [f"{opcode} {name} makes "
            f"{'one layer' if shape in layer else 'the stack'} [{shape}]"
            for name, shape, opcode, _ in _values_made(text)
            if shape in layer or (shape in whole and opcode not in passes_on)]


def _mosaic_calls(text):
    """How often each of the two grouped kernels stands in the text."""
    return (len(re.findall(r"moe_grouped_matmul_swiglu[\w.]* = ", text)),
            len(re.findall(r"moe_grouped_matmul\.[\w.]* = |"
                           r"moe_grouped_matmul = ", text)))


@pytest.mark.parametrize("program", _PROGRAMS)
def test_engine_programs_read_the_experts_where_they_lie_on_v5e(
        serving_programs, program):
    """The sparse cell's programs at its widths (64 experts of
    ``[2048, 1024]``): the three expert stacks reach the two Mosaic calls
    through bitcasts alone, the layer's offset folded into the tile ->
    block map.  The parent's programs let the layer scan slice them, and
    XLA copied each slice for the custom call: nine faults in each of the
    four programs, a ``dynamic-slice`` and two more values of a layer's
    shape for each of the three leaves (on the chip
    ``dynamic-slice_bitcast_fusion`` x 3, 60% of the cell's device time,
    PERF.md PR 30).  The step that carries chunks holds each kernel once:
    their rows are routed with the decode rows.  A dense model's programs
    hold no expert kernel."""
    engine, compiled_text = serving_programs
    c = engine.config
    text = compiled_text(program)
    if not hasattr(c, "num_experts"):
        assert _mosaic_calls(text) == (0, 0)       # a dense FFN: no expert
        return
    assert expert_value_faults(text, _POOL_LAYERS, c.num_experts,
                               c.hidden_size, c.intermediate_size) == []
    assert _mosaic_calls(text) == (1, 1)


@pytest.mark.parametrize("rows", [32, 256], ids=["decode_step", "prefill_call"])
def test_grouped_expert_matmul_compiles_for_v5e_inside_a_layer_scan(
        one_chip, rows, monkeypatch):
    """The dropless expert layer at OLMoE-1B-7B's widths (64 experts of
    [2048, 1024], 8 a token), scanned over the stacked layers as the
    serving programs scan them (the router and the layer's index are the
    scan's ``xs``; the expert stacks stay whole): both Mosaic calls are
    there, once each, and nothing copies a layer's experts."""
    from deepspeed_tpu.moe import DroplessMoE
    from deepspeed_tpu.ops.pallas import moe_grouped_matmul as gm

    # the layer asks the platform, which is the CPU here: steer it onto
    # the path it takes on the chip
    monkeypatch.setattr(gm, "reference_off_tpu", lambda interpret: False)
    L, E, H, I, k = 8, 64, 2048, 1024, 8
    layer_fn = DroplessMoE(E, k)

    def arg(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def fn(x, wg, w_gate, w_up, w_down):
        experts = {"w_gate": w_gate, "w_up": w_up, "w_down": w_down}

        def one(x, xs):
            wg_l, l = xs
            y, _, _ = layer_fn(wg_l, experts, x[None], layer=l)
            return x + y[0], None

        return jax.lax.scan(one, x, (wg, jnp.arange(L, dtype=jnp.int32)))[0]

    text = jax.jit(fn).lower(
        arg((rows, H)), arg((L, H, E)), arg((L, E, H, I)), arg((L, E, H, I)),
        arg((L, E, I, H))).compile().as_text()
    assert _mosaic_calls(text) == (1, 1)
    assert expert_value_faults(text, L, E, H, I) == []


# -- a model of two kinds of attention layer (the hybrid serving cell) --------

#: MiMo-V2.5 at its published widths as the cell runs it: layer 0 and one
#: period, 16 of 256 experts held, an eighth of the vocabulary; the cell's
#: pool (40,960 pages of token capacity), slots and context limit
_HYBRID = dict(vocab_size=19072, held_experts=(0, 16), max_seq_len=8192)
_HYBRID_PAGES, _HYBRID_SLOTS = 40960, 256


@pytest.mark.parametrize("kv_h, window, sink", [(4, None, False),
                                                (8, 128, True)],
                         ids=["full_groups_of_16", "window_sink_groups_of_8"])
def test_paged_decode_with_k_planes_compiles_for_v5e(one_chip, kv_h, window,
                                                     sink):
    """K rows of 192 in two 128-lane planes, V rows of 128, 64 query heads:
    the Mosaic call alone, both pools through bitcasts (a ``[…, 4, 256]``
    K pool's ``[pages, 64, 256]`` view was a 2.7 GB copy of it)."""
    rows, h, page, pages, layers, max_blocks = 256, 64, 16, 4097, 2, 512

    def arg(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def fn(q, k_pool, v_pool, tables, lengths, logits):
        flat = lambda a: a.reshape((-1,) + a.shape[2:])
        return pa.paged_decode_attention(
            q, flat(k_pool), flat(v_pool), tables, lengths, interpret=False,
            window=window, sink=logits if sink else None, k_planes=2,
            plane_stride=layers * pages)

    args = (arg((rows, h, 192)), arg((2 * layers, pages, page, kv_h, 128)),
            arg((layers, pages, page, kv_h, 128)),
            arg((rows, max_blocks), jnp.int32), arg((rows,), jnp.int32),
            arg((h,), jnp.float32))
    # the walk the shapes give: 2,048 score columns of 4 KV heads are 32
    # pages a step; a window of 128 has 9 live pages at most.  K's planes
    # of a page arrive in ONE copy: [2 slots, 2 planes, P, 16·kv_h, 128]
    P = 9 if window else 32
    refs, counts = _kernel_body(fn, *args)
    assert (2, 2, P, page * kv_h, 128) in refs
    assert (2, P, page * kv_h, 128) in refs                   # V's buffer
    assert (2, layers * pages, page * kv_h, 128) in refs      # the K pool
    assert (h, P * page * kv_h) in refs                       # the head mask
    assert counts == {"dma_start": 4, "dma_wait": 2, "dot_general": 3}
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    assert "paged_decode_attention" in text
    made = re.findall(rf"= \w+\[(?:{2 * layers}|{layers}),{pages},\S* "
                      rf"([\w-]+)\(", text)
    assert made and set(made) <= {"parameter", "bitcast"}, made
    # the kernel's own views, [2 planes, layers·pages, rows, 128] of K and
    # [layers·pages, rows, 128] of V: bitcasts
    flat = re.findall(rf"= \w+\[(?:2,)?{layers * pages},\S* ([\w-]+)\(",
                      text)
    assert flat and set(flat) <= {"bitcast"}, flat


@pytest.fixture(scope="module")
def hybrid_programs(topo, one_chip):
    """The hybrid cell's engine over shapes alone and a function that
    compiles one of its programs for the described chip."""
    from deepspeed_tpu import models
    from deepspeed_tpu.inference.v2 import engine_v2 as ev2
    from deepspeed_tpu.inference.v2.kv_cache import KVCacheConfig
    from deepspeed_tpu.ops.pallas import moe_grouped_matmul as gm

    model = models.MimoV2Model(models.MimoV2Config(**_HYBRID))
    cache = KVCacheConfig(num_blocks=_HYBRID_PAGES, block_size=_PAGE,
                          max_seq_len=_HYBRID["max_seq_len"])
    placed = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    arg = lambda shape, dt=jnp.int32: placed(jax.ShapeDtypeStruct(shape, dt))
    mp = pytest.MonkeyPatch()
    mp.setattr(pa, "reference_off_tpu", lambda interpret: False)
    mp.setattr(gm, "reference_off_tpu", lambda interpret: False)
    real_pool = ev2.init_kv_pool
    mp.setattr(ev2, "init_kv_pool",
               lambda ad, cc: jax.eval_shape(lambda: real_pool(ad, cc)))
    shapes = jax.eval_shape(
        lambda key: jax.tree.map(lambda w: w.astype(model.config.dtype),
                                 model.init_params(key)),
        jax.random.PRNGKey(0))
    engine = ev2.RaggedInferenceEngineV2(model, shapes, cache,
                                         max_batch_slots=_HYBRID_SLOTS)
    blocks = cache.max_blocks_per_seq
    common = (arg((), jnp.float32),
              placed(jax.eval_shape(lambda: jax.random.PRNGKey(0))))

    @functools.cache
    def compiled(program):
        kind, _, n = program.rpartition("_")
        rows = (arg((_HYBRID_SLOTS,)),
                (arg((_HYBRID_SLOTS,)),
                 arg((_HYBRID_SLOTS + engine.prefill_batch,))),
                arg((_HYBRID_SLOTS,)), arg((_HYBRID_SLOTS, blocks)),
                arg((_HYBRID_SLOTS,)))
        if kind == "decode_burst":
            fn = functools.partial(engine._decode_burst_fn, n_steps=int(n))
            chunks = None
        else:                    # the one-step program with chunks riding
            fn = functools.partial(engine._decode_burst_fn, n_steps=1,
                                   kb=int(n))
            chunks = (arg((engine.prefill_batch, engine.chunk)),
                      arg((engine.prefill_batch, blocks)),
                      arg((engine.prefill_batch,)),
                      arg((engine.prefill_batch,)),
                      arg((engine.prefill_batch,)))
        done = jax.jit(fn, donate_argnums=(1,)).lower(
            placed(shapes), placed(engine.pool), *rows, *common,
            arg((_HYBRID_SLOTS,)), chunks).compile()
        return done.as_text(), done.memory_analysis()

    yield engine, compiled
    mp.undo()


@pytest.mark.parametrize("program", ["decode_burst_1", "decode_burst_8",
                                     "chunks_8", "chunks_64"])
def test_hybrid_programs_keep_both_pools_in_place_on_v5e(hybrid_programs,
                                                         program):
    """The hybrid cell's programs at its shapes (two full layers of 4 KV
    heads over 40,960 pages, five window layers of 8 over 256 rings of 16
    pages; K in two planes): each of the four pool arrays is passed on,
    written in place and read through a bitcast, seven Mosaic attention
    calls and a pair of grouped expert calls a sparse layer stand in a
    decode step (the one that carries chunks too: once a layer, not once
    a group of rows), nothing copies a layer's experts, and the program plans
    little beyond its arguments (the parent of the planes planned 2.7 GB:
    a copy of the full layers' K pool a call)."""
    engine, compiled = hybrid_programs
    cc = engine.cache_config
    assert (cc.ring_blocks, cc.num_rings) == (16, _HYBRID_SLOTS)
    text, memory = compiled(program)
    pools = {("full", "k"): (4, _HYBRID_PAGES, 4), ("full", "v"): (2,
             _HYBRID_PAGES, 4), ("window", "k"): (10, cc.ring_pool_blocks, 8),
             ("window", "v"): (5, cc.ring_pool_blocks, 8)}
    for (kind, name), (layers, pages, kv_h) in pools.items():
        assert engine.pool[kind][name].shape == (layers, pages, _PAGE, kv_h,
                                                 128)
        assert pool_value_faults(text, layers, pages, _PAGE, kv_h, 128) \
            == [], (kind, name)
        if name == "k":
            # the kernel reads K as [2 planes, a plane's pages, rows, 128]
            # (one copy fetches both planes of a page): a bitcast
            view = f"2,{layers // 2 * pages},{_PAGE * kv_h},128"
            made = [op for _, shape, op, _ in _values_made(text)
                    if shape == view]
            assert made and set(made) == {"bitcast"}, (kind, made)
    assert memory.temp_size_in_bytes < 0.5e9
    assert memory.alias_size_in_bytes == sum(
        2 * np.prod(a.shape) for pool in engine.pool.values()
        for a in pool.values())
    assert expert_value_faults(text, 6, 16, 4096, 2048) == []
    assert _mosaic_calls(text) == (6, 6)
    assert len(re.findall(r"paged_decode_attention[\w.]* = ", text)) == 7
    assert engine.last_attn_pages_per_step == {"full": 32, "window": 9}
    if program.startswith("chunks"):
        # 256 chunk rows and 256 decode rows through each weight together
        rows = _matmul_rows(text)
        assert rows.count(512) >= 7 and 256 not in rows


def test_paged_decode_over_a_latent_cache_compiles_for_v5e(one_chip):
    """128 query heads on ONE cached row of 576 numbers in five 128-lane
    planes, pages of 128 tokens, V the row's leading 512 (no V pool): the
    Mosaic call alone, the pool through bitcasts."""
    rows, h, page, pages, layers, max_blocks = 128, 128, 128, 1025, 2, 128

    def arg(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def fn(q, k_pool, tables, lengths):
        return pa.paged_decode_attention(
            q, k_pool.reshape((-1,) + k_pool.shape[2:]), None, tables,
            lengths, interpret=False, k_planes=5,
            plane_stride=layers * pages, v_in_k=512, scale=192 ** -0.5)

    done = jax.jit(fn).lower(
        arg((rows, h, 576)), arg((5 * layers, pages, page, 1, 128)),
        arg((rows, max_blocks), jnp.int32), arg((rows,), jnp.int32)).compile()
    text = done.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    assert "paged_decode_attention" in text
    assert done.output_shardings is not None
    made = re.findall(rf"= \w+\[{5 * layers},{pages},\S* ([\w-]+)\(", text)
    assert made and set(made) <= {"parameter", "bitcast"}, made
    # the kernel's own view, [planes, layers·pages, rows, 128]: a bitcast
    view = re.findall(rf"= \w+\[5,{layers * pages},\S* ([\w-]+)\(", text)
    assert view and set(view) <= {"bitcast"}, view
    # a V narrower than whole planes is refused in words, not compiled
    assert "128 lanes" in pa._refusal(h, 1, 128, 500, compiled=True)


#: the latent cell's model at its published widths, cut to a dense and two
#: sparse layers, with the cell's pages of 128 tokens
_LATENT = dict(vocab_size=19200, num_layers=3, first_k_dense=1,
               held_experts=(0, 8), max_seq_len=16384)
_LATENT_PAGE, _LATENT_PAGES, _LATENT_SLOTS = 128, 6144, 128


@pytest.fixture(scope="module")
def latent_programs(topo, one_chip):
    """The latent cell's engine over shapes alone and a function that
    compiles one of its programs for the described chip."""
    from deepspeed_tpu import models
    from deepspeed_tpu.inference.v2 import engine_v2 as ev2
    from deepspeed_tpu.inference.v2.kv_cache import KVCacheConfig
    from deepspeed_tpu.ops.pallas import moe_grouped_matmul as gm

    model = models.PanguUltraMoeModel(models.PanguUltraMoeConfig(**_LATENT))
    cache = KVCacheConfig(num_blocks=_LATENT_PAGES, block_size=_LATENT_PAGE,
                          max_seq_len=_LATENT["max_seq_len"])
    placed = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    arg = lambda shape, dt=jnp.int32: placed(jax.ShapeDtypeStruct(shape, dt))
    mp = pytest.MonkeyPatch()
    mp.setattr(pa, "reference_off_tpu", lambda interpret: False)
    mp.setattr(gm, "reference_off_tpu", lambda interpret: False)
    real_pool = ev2.init_kv_pool
    mp.setattr(ev2, "init_kv_pool",
               lambda ad, cc: jax.eval_shape(lambda: real_pool(ad, cc)))
    shapes = jax.eval_shape(
        lambda key: jax.tree.map(lambda w: w.astype(model.config.dtype),
                                 model.init_params(key)),
        jax.random.PRNGKey(0))
    engine = ev2.RaggedInferenceEngineV2(model, shapes, cache,
                                         max_batch_slots=_LATENT_SLOTS)
    blocks = cache.max_blocks_per_seq
    common = (arg((), jnp.float32),
              placed(jax.eval_shape(lambda: jax.random.PRNGKey(0))))

    @functools.cache
    def compiled(program):
        kind, _, n = program.rpartition("_")
        rows = (arg((_LATENT_SLOTS,)),
                (arg((_LATENT_SLOTS,)),
                 arg((_LATENT_SLOTS + engine.prefill_batch,))),
                arg((_LATENT_SLOTS,)), arg((_LATENT_SLOTS, blocks)),
                arg((_LATENT_SLOTS,)))
        if kind == "decode_burst":
            fn = functools.partial(engine._decode_burst_fn, n_steps=int(n))
            chunks = None
        else:                    # the one-step program with chunks riding
            fn = functools.partial(engine._decode_burst_fn, n_steps=1,
                                   kb=int(n))
            chunks = (arg((engine.prefill_batch, engine.chunk)),
                      arg((engine.prefill_batch, blocks)),
                      arg((engine.prefill_batch,)),
                      arg((engine.prefill_batch,)), None)
        done = jax.jit(fn, donate_argnums=(1,)).lower(
            placed(shapes), placed(engine.pool), *rows, *common, None,
            chunks).compile()
        return done.as_text(), done.memory_analysis()

    yield engine, compiled
    mp.undo()


@pytest.mark.parametrize("program", ["decode_burst_8", "chunks_128"])
def test_latent_programs_keep_the_one_pool_in_place_on_v5e(latent_programs,
                                                           program):
    """The latent cell's programs at its shapes (a row a token of 576
    numbers in five planes, 6,144 pages of 128 tokens, no V pool): the
    pool is passed on, written in place and read through a bitcast; a
    Mosaic attention call a layer for the decode rows and, in the step
    that carries chunks, one more for the chunk rows (four tokens a grid
    row, as the kernel's rule gives for these shapes: 64 rows for the 256
    tokens; no bucket of keys is gathered, no score matrix of 128 heads
    over 8,192 keys lies in memory); a pair of grouped expert calls in the
    sparse layer."""
    engine, compiled = latent_programs
    assert engine.last_attn_path in (None, "pallas")
    assert {k: sorted(v) for k, v in engine.pool.items()} == {"latent": ["k"]}
    plane = engine.pool["latent"]["k"]
    assert plane.shape == (15, _LATENT_PAGES, _LATENT_PAGE, 1, 128)
    text, memory = compiled(program)
    assert engine.last_attn_path == "pallas"
    assert pool_value_faults(text, 15, _LATENT_PAGES, _LATENT_PAGE, 1,
                             128) == []
    assert memory.alias_size_in_bytes == 2 * np.prod(plane.shape)
    assert memory.temp_size_in_bytes < 1.0e9
    assert expert_value_faults(text, 2, 8, 7680, 2048) == []
    assert _mosaic_calls(text) == (1, 1)
    calls = len(re.findall(r"paged_decode_attention[\w.]* = ", text))
    assert calls == (4 if program.startswith("chunks") else 2)
    shapes = (_LATENT_PAGE, 1, 128, 640, 2, 128, 0)
    assert engine.last_attn_pages_per_step["latent"] \
        == pa.pages_per_step(*shapes) == 8
    assert memory.temp_size_in_bytes < 0.7e9
    if program.startswith("chunks"):
        # the chunk rows' call: its own step, and the decode rows' entry
        # not overwritten by it
        tokens = pa.query_tokens_per_row(engine.chunk, *shapes)
        assert engine.last_attn_query_tokens == {"latent": tokens} \
            == {"latent": 4}
        assert engine.last_attn_pages_per_step == {
            "latent": 8,
            "latent/chunk": pa.pages_per_step(*shapes, None, tokens)} \
            == {"latent": 8, "latent/chunk": 4}
        grid_rows = engine.prefill_batch * engine.chunk // tokens
        made = re.findall(r"paged_decode_attention[\w.]* = bf16\[([\d,]+)\]",
                          text)
        assert sorted(made) == sorted(
            [f"{grid_rows},{tokens * 128},512", "128,128,512"] * 2), made
        # 256 chunk rows and 128 decode rows through each weight together
        rows = _matmul_rows(text)
        assert rows.count(384) >= 6 and 256 not in rows
        # no gathered keys, no scores over a bucket
        assert not re.search(r"\[2,16384,\d+\]|\[2,\d+,128,16384\]", text)
        assert engine._prefill_bucket([]) == 128      # one program, no bucket


def _plane_relayouts(text: str, B: int, S: int, h: int, d: int):
    """Names of the compiled program's instructions that make a new
    ``[.., S, .., d]`` plane of the attention operands by moving data:
    a ``copy`` or ``transpose``, alone, asynchronous or as a fusion."""
    shapes = (rf"\w+\[{B},{S},{h},{d}\]", rf"\w+\[{B * h},{S},{d}\]",
              rf"\w+\[{B},{h},{S},{d}\]")
    return [name for name, shape, opcode in re.findall(
        r"^\s*(?:ROOT )?%?([\w.\-]+) = (\S+) ([\w\-]+)\(", text, re.M)
        if any(re.match(s, shape) for s in shapes)
        and (opcode in ("copy", "transpose", "copy-done")
             or opcode == "fusion" and re.match(r"copy|transpose", name))]


@pytest.mark.parametrize(
    "B, S, h, d, causal, window, packed, parent_relayouts, stat_rows", [
        (1, 8192, 32, 128, True, 4096, False, 15, 2),   # the Mistral cells
        # BERT-large, padding: one k-block spans the sequence, so Δ is
        # summed in the tile and only lse comes in (PR 41)
        (32, 512, 16, 64, False, None, True, 9, 1),
    ], ids=["mistral7b_row", "bert_large_segments"])
def test_flash_vjp_compiles_for_v5e(one_chip, B, S, h, d, causal, window,
                                    packed, parent_relayouts, stat_rows):
    """``jax.vjp`` of ``flash_attention`` at a training cell's shape:
    Mosaic takes the backward's plan under ``RESIDENT_VMEM_LIMIT_BYTES``,
    every backward call is named ``flash_bwd*`` (what
    ``attention_share.train`` matches), the statistics reach it as
    lane-dense rows, and no more planes are re-laid out around the calls
    than in the lowering this backward replaced (PR 32 counted the
    parent's)."""
    import importlib

    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    x = jax.ShapeDtypeStruct((B, S, h, d), jnp.bfloat16, sharding=one_chip)
    seg = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=one_chip)

    def fn(q, k, v, do, segment_ids):
        out, vjp = jax.vjp(lambda q, k, v: fa.flash_attention(
            q, k, v, causal, window=window,
            segment_ids=segment_ids if packed else None, interpret=False),
            q, k, v)
        return (out,) + vjp(do)

    text = jax.jit(fn).lower(x, x, x, x, seg).compile().as_text()
    calls = re.findall(r'^\s*%?([\w.\-]+) = [^\n]*custom_call_target='
                       r'"tpu_custom_call"', text, re.M)
    backward = [c for c in calls if "flash_fwd" not in c]
    assert len(calls) == 2 and len(backward) == 1, calls
    assert "flash_bwd" in backward[0], calls
    # lse and Δ: [B·h, 1, S] rows, not [B·h, S, 1] columns on 128 lanes
    line = next(ln for ln in text.splitlines()
                if f"%{backward[0]} = " in ln)
    assert line.count(f"f32[{B * h},1,{S}]") == stat_rows, line[:400]
    assert f"f32[{B * h},{S},1]" not in line
    moved = _plane_relayouts(text, B, S, h, d)
    assert len(moved) <= parent_relayouts, moved


def test_mistral_row_resolves_to_the_tiles_it_was_timed_at():
    """The Mistral training cells' call (``S=8192, d=128``, bf16) keeps the
    tiles its kernels were timed at (PR 32): whatever row another shape
    adds to the tables, this one resolves as before and so lowers to the
    same kernels."""
    import importlib

    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    assert fa._resolve_blocks(0, 0, 8192, 128) == (256, 512)
    assert fa._resolve_blocks(0, 0, 8192, 128, backward=True,
                              itemsize=2) == (512, 512)
    assert fa.flash_route(8192, 128, interpret=False) == ("kernel", None)


def _computations(text: str):
    """A compiled program's text cut into its computations, name → body."""
    parts = re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \()", text)
    return {re.match(r"(?:ENTRY )?%([\w.\-]+)", p).group(1): p
            for p in parts if re.match(r"(?:ENTRY )?%[\w.\-]+ \(", p)}


def test_mistral_step_runs_the_flash_forward_once_a_layer_on_v5e(
        one_chip, monkeypatch):
    """``value_and_grad`` of a two-layer Mistral-7B-width loss at the
    training cells' row (1 x 8,192, bf16, remat, window 4,096): the op
    names its ``out`` and ``lse`` at this shape and the layer scan's policy
    holds them, so the program has ONE ``flash_fwd``, in the forward scan's
    body, and the backward scan's body holds ``flash_bwd`` and no forward
    (under the dots-only policy remat ran a second one there: 4.1 ms a
    layer, PR 32's trace).  What the scans stack for it is ``out`` as the
    kernel wrote it and the DENSE ``lse [B, h, S]``, 64 + 1 MiB a layer:
    no statistic with a minor axis of 1, which pads to a lane a row
    (128 MiB a layer)."""
    import importlib

    from deepspeed_tpu.models import LlamaConfig, LlamaModel

    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    # the op asks the platform, which is the CPU here: steer it onto the
    # path it takes on the chip
    monkeypatch.setattr(fa, "reference_off_tpu", lambda interpret: False)
    L, S, h, d = 2, 8192, 32, 128
    model = LlamaModel(LlamaConfig.mistral_7b(
        num_layers=L, vocab_size=2048, dtype=jnp.bfloat16,
        attn_impl="flash", remat=True))
    assert model.keeps_flash_residuals()
    placed = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    text = jax.jit(jax.value_and_grad(model.loss)).lower(
        placed(jax.eval_shape(model.init_params, jax.random.PRNGKey(0))),
        placed({"input_ids": jax.ShapeDtypeStruct((1, S), jnp.int32)})
    ).compile().as_text()
    holders = {
        name: sorted(re.sub(r"[.\d]+$", "", c) for c in re.findall(
            r'^\s*%?([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"',
            body, re.M))
        for name, body in _computations(text).items()}
    holders = {name: calls for name, calls in holders.items() if calls}
    assert sorted(holders.values()) == [["flash_bwd"], ["flash_fwd"]], holders
    bodies = set(re.findall(r"\bbody=%?([\w.\-]+)", text))
    assert set(holders) <= bodies, (holders, bodies)     # two scans' bodies

    forward, = (name for name, calls in holders.items()
                if calls == ["flash_fwd"])
    carried = re.search(
        rf"= \(([^\n]*?)\) while\([^\n]*body=%?{re.escape(forward)}\b", text)
    stacked = re.findall(rf"(\w+)\[{L},([\d,]+)\]\{{([\d,]+)",
                         carried.group(1))
    # the layer's saves, a row of the stack each: q and out, and lse
    assert sum(t == "bf16" and dims == f"1,{S},{h},{d}"
               for t, dims, _ in stacked) == 2, stacked
    statistics = [(dims, order) for t, dims, order in stacked
                  if t == "f32" and str(S) in dims.split(",")]
    assert [dims for dims, _ in statistics] == [f"1,{h},{S}"], stacked
    for dims, order in statistics:
        # the minor-most axis is the row's S positions, lane-dense
        minor = ([L] + [int(n) for n in dims.split(",")])[
            int(order.split(",")[0])]
        assert minor == S, (dims, order)


def _products_over(text: str, width: int):
    """Every matrix product (``convolution``) of a compiled program with an
    operand or a result ``width`` wide, as its ``op_name``."""
    shape = dict(re.findall(
        r"^\s*(?:ROOT )?%([\w.\-]+) = \(?\w+\[([\d,]*)\]", text, re.M))
    found = []
    for dims, a, b, op in re.findall(
            r"= \w+\[([\d,]*)\]\S* convolution\(%([\w.\-]+), %([\w.\-]+)\)"
            r'[^\n]*op_name="([^"]*)"', text):
        if any(str(width) in d.split(",")
               for d in (dims, shape.get(a, ""), shape.get(b, ""))):
            found.append(op)
    return found


@pytest.mark.parametrize("chips", [1, 4], ids=["one_chip", "data4_zero3"])
def test_mistral_step_multiplies_by_the_head_three_times_on_v5e(
        topo, one_chip, monkeypatch, chips):
    """The loss and gradient of a two-layer Mistral-7B at the training
    cells' row (8,192 tokens, bf16, remat, ``loss_tiles=8``, the whole
    32,000-token vocabulary), on one chip and as a ``data=4`` mesh under
    the ZeRO-3 sharder's specs: THREE products over the vocabulary (the
    tile's logits, ``dh``, ``dW``), all in the loss tail's one scan and
    none recomputed (a checkpointed tile made four, 11.4 ms a step, PR 49's
    trace); no float32 value over the vocabulary larger than one tile's
    logits but the head's and the embedding's own gradients; and on four chips ONE all-gather of
    the head (the compiler sinks it into the scan's body, its own choice)
    and no REDUCTION over ``[.., 32000]`` inside a scan's body (a ``dW``
    carried through the scan as one array was reduce-scattered every tile:
    3.2 ms each, eight a step; the replicas' partial sums are added once,
    after the scan)."""
    import importlib

    from deepspeed_tpu.models import LlamaConfig, LlamaModel

    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "reference_off_tpu", lambda interpret: False)
    L, S, H, V, tiles = 2, 8192, 4096, 32000, 8
    config = LlamaConfig.mistral_7b(num_layers=L, dtype=jnp.bfloat16,
                                    attn_impl="flash", remat=True,
                                    loss_tiles=tiles)
    assert (config.hidden_size, config.vocab_size) == (H, V)
    if chips == 1:
        model = LlamaModel(config)
        shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
        params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), shapes)
        ids = jax.ShapeDtypeStruct((1, S), jnp.int32, sharding=one_chip)
        constrain = lambda grads: grads
    else:
        from jax.sharding import NamedSharding, PartitionSpec

        from deepspeed_tpu.parallel import MeshLayout
        from deepspeed_tpu.parallel.mesh import DP_AXES, build_mesh
        from deepspeed_tpu.runtime.zero.config import DeepSpeedZeroConfig
        from deepspeed_tpu.runtime.zero.sharder import ZeroShardingPolicy
        from deepspeed_tpu.utils import groups

        layout = MeshLayout.infer(chips)
        mesh = groups.initialize_mesh(
            layout, build_mesh(layout, devices=topo.devices))
        model = LlamaModel(config, mesh=mesh)
        policy = ZeroShardingPolicy.from_config(
            mesh, DeepSpeedZeroConfig(stage=3))
        shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
        base = model.param_specs()
        params = jax.tree.map(
            lambda a, sharding: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                     sharding=sharding),
            shapes, policy.param_shardings(shapes, base))
        ids = jax.ShapeDtypeStruct(
            (chips, S), jnp.int32,
            sharding=NamedSharding(mesh, PartitionSpec(DP_AXES, None)))
        constrain = lambda grads: policy.apply_grad_constraints(grads, base)

    def step(params, batch):
        # as the engine's step: the compute copy is cast outside the
        # gradient, the gradients widened and put where the sharder says
        compute = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
        loss, grads = jax.value_and_grad(model.loss)(compute, batch)
        return loss, constrain(jax.tree.map(
            lambda g: g.astype(jnp.float32), grads))

    text = jax.jit(step).lower(
        params, {"input_ids": ids}).compile().as_text()

    products = _products_over(text, V)
    assert len(products) == 3, products
    assert not [op for op in products
                if "rematted_computation" in op or "transpose(" in op], products

    tile = (S // tiles) * V
    too_large = {dims for dims in re.findall(r"\bf32\[([\d,]+)\]", text)
                 if str(V) in dims.split(",")
                 and np.prod([int(n) for n in dims.split(",")]) > tile
                 and sorted(int(n) for n in dims.split(",")
                            if n != "1") != [H, V]}   # head and embedding
    assert not too_large, too_large

    if chips > 1:
        gathers = re.findall(
            rf"= bf16\[{H},{V}\]\S* all-gather(?:-start)?\(", text)
        assert len(gathers) == 1, gathers
        computations = _computations(text)
        for body in set(re.findall(r"\bbody=%?([\w.\-]+)", text)):
            inside = [line[:160] for line in computations[body].splitlines()
                      if f",{V}]" in line and re.search(
                          r"all-reduce|reduce-scatter", line)]
            assert not inside, (body, inside)


@pytest.mark.parametrize("masked", [False, True],
                         ids=["no_mask", "attention_mask"])
def test_bert_step_holds_the_flash_kernels_on_v5e(one_chip, monkeypatch,
                                                  masked):
    """``value_and_grad`` of a two-layer BERT-large's loss at the training
    cell's shape (32 x 512, bf16, remat, ``attn_impl="xla"`` passed as the
    cell's file passes it): the program holds the flash forward and the
    one-call backward, no array of the score matrix's shape, no per-head
    plane, and a segment operand only when the batch carries an
    ``attention_mask``."""
    import importlib

    from deepspeed_tpu.models.bert import BertConfig, BertModel

    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    # the op asks the platform, which is the CPU here: steer it onto the
    # path it takes on the chip
    monkeypatch.setattr(fa, "reference_off_tpu", lambda interpret: False)
    B, S = 32, 512
    model = BertModel(BertConfig(num_layers=2, dtype=jnp.bfloat16,
                                 remat=True, attn_impl="xla"))
    placed = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    ids = jax.ShapeDtypeStruct((B, S), jnp.int32)
    batch = {"input_ids": ids, "labels": ids}
    if masked:
        batch["attention_mask"] = ids
    text = jax.jit(jax.value_and_grad(model.loss)).lower(
        placed(jax.eval_shape(model.init_params, jax.random.PRNGKey(0))),
        placed(batch)).compile().as_text()
    calls = {name: line for name, line in re.findall(
        r'^\s*%?([\w.\-]+) = ([^\n]*custom_call_target="tpu_custom_call"'
        r'[^\n]*)', text, re.M)}
    heads = model.config.num_heads
    # forward, remat's recomputation of it, backward
    assert sorted(re.sub(r"[.\d]+$", "", c) for c in calls) == [
        "flash_bwd", "flash_fwd", "flash_fwd"], sorted(calls)
    assert f"[{B},{heads},{S},{S}]" not in text
    segments = f"s32[{B},1,{S}]"
    for name, line in calls.items():
        assert (segments in line) == masked, (name, line[:400])
        # two 64-wide heads a program, read from the operands as they lie
        assert f"bf16[{B},{S},{heads * 64}]" in line, (name, line[:400])
    # no transpose to or from per-head planes around the calls (the
    # one-head-a-program lowering made nine at this shape)
    assert _plane_relayouts(text, B, S, heads, 64) == []


#: the grouped expert kernels' Mosaic modules (printed without locations) at
#: the four sparse serving cells' shapes, and the state-space cell's state
#: update: length and the leading 16 hex digits of the text's SHA-256.
#: OLMoE's two and the state update are as the parent of PR 52 built them
#: (PR 52 gave the dropless layer a second FORM of expert, two matrices,
#: ``moe_grouped_matmul_relu2``, and the state update a form for several
#: heads a lane row: the cells that were measured with the gated kernels
#: and the one-head-a-row update keep them).  The three SHARES' are PR 53's:
#: their tile is the router's mean group's (``tile_rows_for``), not the one
#: of what can land on the share, which was 128 rows in all six
_GROUPED_MODULES = {
    "olmoe_chunks": ((4376, "ae8ef3c38f7e3fc6"), (3040, "9af48ac99cbab093")),
    "olmoe_burst": ((4352, "ffade58a0721036b"), (3026, "6615dec66a9caca7")),
    "hybrid_chunks": ((4328, "91ce1efff7b6399f"), (3032, "7c57ada04e1b3faa")),
    "hybrid_burst": ((4328, "dffce5f8d65d26c9"), (3032, "39faec49b5b50b68")),
    "latent_chunks": ((4328, "37e7bd9774d204aa"), (3032, "b26e9578274208bd")),
    "latent_burst": ((4320, "41b3458e1caa12ab"), (3026, "72af2b370eb319b6")),
    "nemotron_chunks": ((3146, "9023df1befcc7905"),
                        (3016, "6fadcfa9647e0e34")),
    "nemotron_burst": ((3146, "50fce319f41eec0e"),
                       (3016, "01aee7ea28fbdc63")),
}
#: a call's tokens, k, the router's experts, groups (the experts held),
#: whether they are a share, layers, the rows' width, I, the experts' form;
#: their tiles: 128 / 16 (OLMoE), 32 / 16 (hybrid, latent), 64 / 16
_GROUPED_CALLS = {
    "olmoe_chunks": (288, 8, 64, 64, False, 8, 2048, 1024, "swiglu"),
    "olmoe_burst": (32, 8, 64, 64, False, 8, 2048, 1024, "swiglu"),
    "hybrid_chunks": (512, 8, 256, 16, True, 6, 4096, 2048, "swiglu"),
    "hybrid_burst": (256, 8, 256, 16, True, 6, 4096, 2048, "swiglu"),
    "latent_chunks": (384, 8, 256, 8, True, 4, 7680, 2048, "swiglu"),
    "latent_burst": (128, 8, 256, 8, True, 4, 7680, 2048, "swiglu"),
    "nemotron_chunks": (384, 22, 512, 128, True, 5, 1024, 2688, "relu2"),
    "nemotron_burst": (128, 22, 512, 128, True, 5, 1024, 2688, "relu2"),
}
_STATE_UPDATE_MODULE = (30808, "67eabd975b90a8b4")


def _module_of(monkeypatch, capsys, fn, *args):
    """(length, digest) of the Mosaic module of the one Pallas call ``fn``
    traces, as ``pallas_call(debug=True)`` prints it."""
    import hashlib

    from jax.experimental import pallas as pl

    real = pl.pallas_call
    monkeypatch.setattr(pl, "pallas_call",
                        lambda *a, **kw: real(*a, **{**kw, "debug": True}))
    jax.clear_caches()      # the call is a jitted function of its own
    capsys.readouterr()
    jax.jit(fn).lower(*args)
    text = capsys.readouterr().out.split(
        "The Mosaic module for pallas_call", 1)[1].split("\n", 1)[1]
    return len(text), hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("call", sorted(_GROUPED_CALLS))
def test_the_gated_expert_kernels_are_the_ones_the_cells_were_measured_with(
        monkeypatch, capsys, one_chip, call):
    from deepspeed_tpu.moe.layer import EXPERT_FORMS
    from deepspeed_tpu.ops.pallas import moe_grouped_matmul as gm

    T, k, routed, E, share, L, H, I, form = _GROUPED_CALLS[call]
    # the tile as ``DroplessMoE`` asks for it: the router's mean group
    tm = gm.tile_rows_for(T * k, routed, jnp.bfloat16)
    arg = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    leaves, up_call = EXPERT_FORMS[form]

    def up(idx, x, layer, *weights):
        plan = gm.plan_groups(idx, E, tm, share=share)
        return getattr(gm, up_call)(gm.gather_rows(x, plan), *weights, layer,
                                    plan, interpret=False)

    def down(idx, x, w_down, layer):
        plan = gm.plan_groups(idx, E, tm, share=share)
        return gm.grouped_matmul(gm.gather_rows(x, plan), w_down, layer,
                                 plan, interpret=False)

    idx, layer = arg((T, k), jnp.int32), arg((), jnp.int32)
    got = (_module_of(monkeypatch, capsys, up, idx, arg((T, H)), layer,
                      *[arg((L, E, H, I))] * len(leaves)),
           _module_of(monkeypatch, capsys, down, idx, arg((T, I)),
                      arg((L, E, I, H)), layer))
    assert got == _GROUPED_MODULES[call]


def test_a_head_a_lane_row_is_the_update_the_state_space_cell_was_measured_with(
        monkeypatch, capsys, one_chip):
    from deepspeed_tpu.ops.pallas import ssm_state_update as ssu

    arg = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    f32 = jnp.float32
    got = _module_of(
        monkeypatch, capsys,
        lambda pool, layer, a, dx, b, c: ssu.ssm_state_update(
            pool, layer, 1, a=a, dx=dx, b=b, c=c, interpret=False),
        arg((6, 97, 32, 256, 128), f32), arg((), jnp.int32),
        arg((96, 32), f32), arg((96, 32, 128), f32), arg((96, 2, 256)),
        arg((96, 2, 256)))
    assert got == _STATE_UPDATE_MODULE


_DELTA_UPDATE_MODULE = (92841, "82c307519e46e67d")


def test_the_delta_rule_s_update_is_the_kernel_its_cell_was_measured_with(
        monkeypatch, capsys, one_chip):
    """The decode step's update of a gated delta rule's states at the
    serving cell's shapes (192 rows of 64 heads, 128 keys x 128 values,
    three layers of 193 slots): a (sequence, 32 heads) block a grid step,
    the pool aliased in and out."""
    from deepspeed_tpu.ops.pallas import delta_state_update as dsu

    f32 = jnp.float32
    arg = lambda shape, dt=f32: jax.ShapeDtypeStruct(shape, dt,
                                                     sharding=one_chip)
    got = _module_of(
        monkeypatch, capsys,
        lambda pool, layer, a, k, q, beta, v: dsu.delta_state_update(
            pool, layer, 1, a, k, q, beta, v, interpret=False),
        arg((3, 193, 64, 128, 128)), arg((), jnp.int32),
        arg((192, 64, 128)), arg((192, 64, 128)), arg((192, 64, 128)),
        arg((192, 64)), arg((192, 64, 128)))
    assert got == _DELTA_UPDATE_MODULE
