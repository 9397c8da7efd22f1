"""``kv_cache.KVLayout``: how a cached row lies in a pool, and the seam
that keeps every other module from knowing it.

The four pool shapes are the serving cells': one 128-wide plane under a
window (Mistral, OLMoE), a 192-wide K in two planes beside a 128-wide V,
kept whole and as rings (the hybrid cell's two kinds), and a 576-wide
latent row in five planes with no V pool."""

import ast
import functools
import importlib.util
import io
import pathlib
import re
import tokenize

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import kv_cache
from deepspeed_tpu.inference.v2.adapters import AttentionKind
from deepspeed_tpu.inference.v2.kv_cache import KVCacheConfig, KVLayout

ROOT = pathlib.Path(__file__).resolve().parents[3]
PAGE, PAGES, LAYERS, SLOTS, CHUNK = 4, 12, 3, 2, 8

#: name → (the kind, query heads)
KINDS = {
    "plane_window": (AttentionKind("kv", LAYERS, 8, 128, 128, window=6), 32),
    "planes_full": (AttentionKind("full", LAYERS, 4, 192, 128), 64),
    "planes_ring": (AttentionKind("window", LAYERS, 8, 192, 128, window=6,
                                  sink=True, ring=True), 64),
    "latent": (AttentionKind("latent", LAYERS, 1, 576, 512, v_in_k=True,
                             scale=192 ** -0.5), 128),
}


def make(name, dtype=jnp.float32):
    kind, heads = KINDS[name]
    cache = KVCacheConfig(num_blocks=PAGES, block_size=PAGE, max_seq_len=32
                          ).with_rings([kind], SLOTS, CHUNK)
    return KVLayout(kind, cache, heads, dtype)


def rows_for(layout, n, seed):
    """K and V rows ``[n, kv_h, d]`` (V None where it lies in K)."""
    kind, rng = layout.kind, np.random.RandomState(seed)
    kk = rng.standard_normal((n, kind.kv_heads, kind.k_dim)).astype("f4")
    vv = None if kind.v_in_k else rng.standard_normal(
        (n, kind.kv_heads, kind.v_dim)).astype("f4")
    return jnp.asarray(kk), None if vv is None else jnp.asarray(vv)


@pytest.fixture(params=sorted(KINDS))
def layout(request):
    return make(request.param)


def test_the_pool_is_made_as_the_kind_needs_it(layout):
    kind, pool = layout.kind, layout.init_pool()
    pages = 1 + SLOTS * layout.cache.ring_blocks if kind.ring else PAGES
    planes, width = kv_cache.lane_planes(kind.k_dim)
    assert sorted(pool) == (["k"] if kind.v_in_k else ["k", "v"])
    assert pool["k"].shape == (LAYERS * planes, pages, PAGE, kind.kv_heads,
                               width)
    if not kind.v_in_k:
        assert pool["v"].shape == (LAYERS, pages, PAGE, kind.kv_heads, 128)
    assert layout.pages == pages
    assert [layout.block(p, 2) for p in range(3)] == [2, LAYERS + 2,
                                                      2 * LAYERS + 2]


def test_rows_written_are_gathered_back_and_the_padding_is_zero(layout):
    kind = layout.kind
    kk, vv = rows_for(layout, 5, 0)
    pages = jnp.asarray([3, 3, 7, 1, 8])
    offsets = jnp.asarray([0, 2, 3, 1, 0])
    pool = layout.write_rows(layout.init_pool(), 1, pages, offsets, kk, vv)
    got = layout.gather_pages(pool, jnp.int32(1), jnp.asarray([[3, 7, 1, 8]]))
    assert len(got) == len(pool)
    at = [0 * PAGE + 0, 0 * PAGE + 2, 1 * PAGE + 3, 2 * PAGE + 1, 3 * PAGE]
    for rows, want, d in zip(got, (kk, vv), (kind.k_dim, kind.v_dim)):
        assert rows.shape == (1, 4 * PAGE, kind.kv_heads, d)
        np.testing.assert_array_equal(np.asarray(rows[0])[at], want)
        assert float(jnp.abs(rows).sum()) == pytest.approx(
            float(jnp.abs(want).sum()), rel=1e-5)   # nothing else is set
    # the other layers are untouched, and so are the lanes beyond a row
    planes, width = kv_cache.lane_planes(kind.k_dim)
    k = np.asarray(pool["k"])
    assert not k[[layout.block(p, l) for p in range(planes)
                  for l in (0, 2)]].any()
    if planes > 1:
        assert not k[layout.block(planes - 1, 1)][..., kind.k_dim % 128:].any()
        assert k[layout.block(planes - 1, 1)][..., :kind.k_dim % 128].any()


def test_pages_written_whole_equal_their_rows_written_one_by_one(layout):
    kk, vv = rows_for(layout, 2 * CHUNK, 1)
    pages = jnp.asarray([5, 2, 8, 1])            # two chunks of two pages
    whole = layout.write_pages(layout.init_pool(), jnp.int32(2), pages, kk,
                               vv)
    tokens = np.arange(2 * CHUNK)
    by_row = layout.write_rows(
        layout.init_pool(), 2, pages[tokens // PAGE],
        jnp.asarray(tokens % PAGE), kk, vv)
    for name in whole:
        np.testing.assert_array_equal(np.asarray(whole[name]),
                                      np.asarray(by_row[name]))
        assert np.asarray(whole[name]).any()
    # a shard's rule is handed what each argument is, once an array
    seen = []
    layout.write_pages(layout.init_pool(), 0, pages, kk, vv,
                       lambda fn, ins, out: seen.append((ins, out)) or fn)
    assert seen == [(("pool", "heads", "all", "all"), "pool")]


@functools.cache
def _t1_calls():
    spec = importlib.util.spec_from_file_location(
        "_tpu_compile_pins", ROOT / "tests/unit/ops/test_tpu_compile.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module._T1_CALLS


#: the cells' pools as ``_T1_CALLS`` pins their kernel calls: the kind,
#: query heads, pages of token capacity, page size, slots, chunk
CELLS = {
    "mistral7b": (AttentionKind("kv", 2, 8, 128, 128, window=4096), 32,
                  1600, 16, 32, 128),
    "olmoe": (AttentionKind("kv", 2, 16, 128, 128), 16, 1600, 16, 32, 128),
    "hybrid_full": (AttentionKind("full", 2, 4, 192, 128), 64, 40960, 16,
                    256, 128),
    "hybrid_window": (AttentionKind("window", 5, 8, 192, 128, window=128,
                                    sink=True, ring=True), 64, 40960, 16, 256,
                      128),
    "latent": (AttentionKind("latent", 5, 1, 576, 512, v_in_k=True,
                             scale=192 ** -0.5), 128, 6144, 128, 128, 128),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_kernels_operands_are_what_the_cells_calls_pin(cell):
    """At the cells' shapes, over shapes alone: the flat views, the
    options and the widths a layout hands the paged kernel are those of
    ``test_tpu_compile._T1_CALLS``, whose Mosaic modules are pinned."""
    kwargs, q, k_shape, v_shape, width, _ = _t1_calls()[cell]
    kind, heads, pages, page, slots, chunk = CELLS[cell]
    cache = KVCacheConfig(num_blocks=pages, block_size=page,
                          max_seq_len=width * page).with_rings(
                              [kind], slots, chunk)
    layout = KVLayout(kind, cache, heads, jnp.bfloat16)
    got = {}

    def operands(pool, tables):
        k, v, tables, got["options"], got["widths"] = layout.kernel_operands(
            pool, 1, tables)
        return k, v, tables

    k, v, tables = jax.eval_shape(
        operands, jax.eval_shape(layout.init_pool),
        jax.ShapeDtypeStruct((q[0], width), jnp.int32))
    assert k.shape == k_shape and k.dtype == jnp.bfloat16
    assert (v is None) == (v_shape is None)
    assert v is None or v.shape == v_shape
    assert tables.shape == (q[0], width)
    defaults = dict(window=None, k_planes=1, v_in_k=0, scale=None)
    options = dict(got["options"])
    if options["k_planes"] == 1:
        options.pop("plane_stride")     # one plane: no stride is taken
    assert options == {**defaults, **kwargs}
    assert got["widths"] == (128, kwargs.get("v_in_k", 128))
    assert q[1:] == (heads, kind.k_dim)
    # what the kernel's two rules are given: a TP shard's heads
    bs, kv_h, h, k_row, itemsize, blocks, v_row, window = \
        layout.kernel_shapes(width, 1)
    assert (bs, kv_h, h, itemsize, blocks, window) == (
        page, kind.kv_heads, heads, 2, width, kind.window)
    assert (k_row, v_row) == (k_shape[-1] * options["k_planes"],
                              0 if v_shape is None else 128)
    if kind.kv_heads % 4 == 0:
        assert layout.kernel_shapes(width, 4)[1:3] == (kind.kv_heads // 4,
                                                       heads // 4)


def test_a_layers_offset_is_folded_into_its_tables(layout):
    tables = jnp.asarray([[1, 2, 0], [4, 0, 0]], jnp.int32)
    _, _, layer_tables, options, _ = layout.kernel_operands(
        layout.init_pool(), 2, tables)
    np.testing.assert_array_equal(layer_tables, tables + 2 * layout.pages)
    assert options["plane_stride"] == LAYERS * layout.pages


def test_v_wider_than_a_plane_is_refused():
    kind = AttentionKind("kv", 1, 2, 192, 192)
    wide = KVLayout(kind, KVCacheConfig(num_blocks=4, block_size=PAGE), 2,
                    jnp.float32)
    with pytest.raises(NotImplementedError, match="wider than one plane"):
        wide.kernel_operands(wide.init_pool(), 0, jnp.zeros((1, 2), jnp.int32))


def test_a_rings_logical_page_lands_on_its_ring():
    ring = make("planes_ring")
    n = ring.cache.ring_blocks
    assert n == -(-6 // PAGE) + CHUNK // PAGE == 4
    assert ring.cache.num_rings == SLOTS and ring.pages == 1 + SLOTS * n
    bases = ring.cache.ring_bases(3, [(0, 1), (2, 0)])
    np.testing.assert_array_equal(bases, [1 + n, 0, 1])
    tables = jnp.zeros((3, 10), jnp.int32)      # a ring's rows ignore them
    walked = np.asarray(ring.row_tables(tables, jnp.asarray(bases)))
    for j in range(10):
        assert walked[:, j].tolist() == [1 + n + j % n, 0, 1 + j % n]
    # a kind that keeps every key walks its block table
    full = make("planes_full")
    assert full.row_tables(tables, None) is tables
    assert full.cache.ring_bases(3, []) is None
    # a chunk from page 1 on: the window's two pages before it, then its
    # own two; the page before the sequence's start is the scratch page
    written, attended, kpos = ring.chunk_pages(
        tables, jnp.asarray([1, 0, 6]), jnp.asarray(bases), CHUNK, 99)
    assert np.asarray(written).tolist() == [
        1 + n + 1, 1 + n + 2, 0, 0, 1 + 6 % n, 1 + 7 % n]
    assert np.asarray(attended).tolist() == [
        [0, 1 + n, 1 + n + 1, 1 + n + 2], [0, 0, 0, 0],
        [1 + 4 % n, 1 + 5 % n, 1 + 6 % n, 1 + 7 % n]]
    assert np.asarray(kpos)[0].tolist() == list(range(-PAGE, 3 * PAGE))
    assert np.asarray(kpos)[2, 0] == 4 * PAGE


@pytest.mark.parametrize("name", ["plane_window", "planes_full", "latent"])
def test_a_chunks_pages_under_its_table(name):
    lay = make(name)
    tables = jnp.asarray([[3, 5, 7, 9, 2, 4, 0, 0], [6, 8, 1, 0, 0, 0, 0, 0]])
    written, attended, kpos = lay.chunk_pages(
        tables, jnp.asarray([2, 0]), None, CHUNK, 4)
    assert np.asarray(written).tolist() == [7, 9, 6, 8]
    if lay.chunks_through_kernel:       # its rows walk their pages there
        assert attended is None and kpos is None and not lay.gathers_bucket
    else:
        assert lay.gathers_bucket
        np.testing.assert_array_equal(attended, tables[:, :4])
        assert np.asarray(kpos).tolist() == list(range(4 * PAGE))
    assert not make("planes_ring").gathers_bucket


def test_what_a_call_reads_and_recycles():
    lengths = np.asarray([[3, 9], [4, 10]])         # steps x rows
    assert make("planes_full").keys_read(lengths, CHUNK, [0, 8]) == 26.0
    assert make("plane_window").keys_read(lengths, CHUNK, [0, 8]) == 19.0
    riding = sum(8 * start + 8 * 9 // 2 for start in (0, 8))
    assert make("latent").keys_read(lengths, CHUNK, [0, 8]) == 26.0 + riding
    cache = make("planes_ring").cache               # rings of four pages
    assert cache.pages_recycled([0, 3, 6], [2, 2, 3]) == 0 + 1 + 3
    assert make("planes_full").cache.pages_recycled([9], [9]) is None

    class Sched:
        class allocator:
            num_free = 4

        @staticmethod
        def ring_pages_in_use():
            return 3

    assert make("planes_full").pages_in_use(Sched) == PAGES - 1 - 4
    assert make("planes_ring").pages_in_use(Sched) == 3


@pytest.mark.parametrize("name", ["planes_full", "latent"])
def test_a_page_exported_and_injected_elsewhere_reads_back(name):
    lay = make(name)
    kk, vv = rows_for(lay, 2 * PAGE, 3)
    layouts = {lay.kind.name: lay}
    src = {lay.kind.name: lay.write_pages(lay.init_pool(), 1,
                                          jnp.asarray([4, 6]), kk, vv)}
    pages = [kv_cache.page_arrays(layouts, src, b) for b in (4, 6)]
    planes = kv_cache.lane_planes(lay.kind.k_dim)[0]
    assert [p.shape for p in pages[0]] == [
        (LAYERS * planes, PAGE, lay.kind.kv_heads, 128)] + (
            [] if lay.kind.v_in_k else [(LAYERS, PAGE, lay.kind.kv_heads, 128)])
    dst = {lay.kind.name: lay.init_pool()}
    kv_cache.write_page_arrays(layouts, dst, [9, 2], pages)
    for got, want in zip(kv_cache.page_arrays(layouts, dst, 9), pages[0]):
        np.testing.assert_array_equal(got, want)
        assert want.any()
    a, b = (lay.gather_pages(pool[lay.kind.name], jnp.int32(1),
                             jnp.asarray([at]))
            for pool, at in ((src, [4, 6]), (dst, [9, 2])))
    for got, want in zip(b, a):
        np.testing.assert_array_equal(got, want)
    untouched = np.ones(PAGES, bool)
    untouched[[9, 2]] = False
    assert not np.asarray(dst[lay.kind.name]["k"])[:, untouched].any()


def test_a_model_of_two_kinds_is_not_transferred():
    layouts = {n: make(n) for n in ("planes_full", "planes_ring")}
    pools = {n: lay.init_pool() for n, lay in layouts.items()}
    with pytest.raises(NotImplementedError, match="2 KV pools"):
        kv_cache.page_arrays(layouts, pools, 1)
    with pytest.raises(NotImplementedError, match="2 KV pools"):
        kv_cache.write_page_arrays(layouts, pools, [1], [])


# -- the seam ----------------------------------------------------------------

ENGINE = ROOT / "deepspeed_tpu/inference/v2/engine_v2.py"
TRANSFER = ROOT / "deepspeed_tpu/serving/kv_transfer.py"
#: what only ``kv_cache.py`` may say: how a kind's rows lie in a pool …
LAYOUT_NAMES = {"v_in_k", "ring_blocks", "num_rings", "ring_pool_blocks",
                "lane_planes"}
#: … and the helpers that said it before, which nothing stands in for
OLD_HELPERS = {"_flat_pool", "_planes", "_scatter", "_ring_pages",
               "_kernel_shapes", "_ring_bases", "_token_pool"}


def _names(path):
    """The identifiers of a source file: comments and strings left out."""
    tokens = tokenize.generate_tokens(io.StringIO(path.read_text()).readline)
    return {t.string for t in tokens if t.type == tokenize.NAME}


def _pool_array_accesses(path):
    """Subscripts of a pool (``pool[…]``, ``engine.pool[…]``: the dict of
    pools by kind, ``pools[…]``, is no array's) and ``.at[…]`` updates."""
    found = []
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Subscript):
            base = ast.unparse(node.value)
            if re.search(r"\bpool\b", base) or base.endswith(".at"):
                found.append(f"{path.name}:{node.lineno}: {ast.unparse(node)}")
    return found


@pytest.mark.parametrize("path", [ENGINE, TRANSFER], ids=lambda p: p.name)
def test_only_the_cache_knows_how_a_pool_lies(path):
    assert _names(path) & (LAYOUT_NAMES | OLD_HELPERS) == set()
    assert _pool_array_accesses(path) == []


def test_the_engine_asks_a_kind_nothing_about_rings():
    """A ``.ring`` in the engine is a REQUEST's, the index the scheduler
    gave it, handed to the cache where the rows are packed."""
    tree = ast.parse(ENGINE.read_text())
    handed = {id(n) for call in ast.walk(tree)
              if isinstance(call, ast.Call)
              and getattr(call.func, "attr", None) == "ring_bases"
              for n in ast.walk(call)}
    rings = [n for n in ast.walk(tree)
             if isinstance(n, ast.Attribute) and n.attr == "ring"]
    assert rings and all(id(n) in handed for n in rings)
    assert "ring" not in {n.attr for n in ast.walk(ast.parse(
        TRANSFER.read_text())) if isinstance(n, ast.Attribute)}


def test_the_old_helpers_exist_nowhere():
    kept = {path.relative_to(ROOT).as_posix(): _names(path) & OLD_HELPERS
            for part in ("inference", "serving")
            for path in (ROOT / "deepspeed_tpu" / part).rglob("*.py")}
    assert len(kept) > 20
    assert {path: names for path, names in kept.items() if names} == {}
