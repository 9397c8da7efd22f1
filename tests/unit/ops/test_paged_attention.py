"""The paged decode kernel's walk (Pallas interpreter) against
``paged_decode_reference``: live pages only, several pages a compute step,
whatever the block table's width."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import paged_attention as pa
from deepspeed_tpu.ops.pallas.selfcheck import DECODE_TOL, _rel_err

H, D, PAGE, MAX_BLOCKS = 4, 8, 8, 6
#: pages per compute step in these tests: a step is 16 keys, the table 48
P = 2
STEP = P * PAGE


def _keys_a_step(monkeypatch, keys):
    """Shrink the rule's two numbers to ``keys`` a step whatever the KV
    heads (these tests' shapes are tiny beside 2,048 score columns)."""
    monkeypatch.setattr(pa, "_STEP_TOKENS", keys)
    monkeypatch.setattr(pa, "_STEP_COLUMNS", keys)


@pytest.fixture
def two_pages_a_step(monkeypatch):
    _keys_a_step(monkeypatch, STEP)
    assert pa.pages_per_step(PAGE, 1, H, D, 4, MAX_BLOCKS) == P
    assert pa.pages_per_step(PAGE, 4, H, D, 4, MAX_BLOCKS) == P


def _case(n_rep, dtype, lengths, seed=0, dead_slot=True):
    """Rows of ``lengths`` over a shuffled, non-contiguous table, and (last)
    a dead slot as the engine leaves one: length 1, every entry page 0."""
    rng = np.random.RandomState(seed)
    kv_h = H // n_rep
    B = len(lengths) + int(dead_slot)
    num_pages = B * MAX_BLOCKS + 1
    q = jnp.asarray(rng.standard_normal((B, H, D)), dtype)
    k_pool = jnp.asarray(rng.standard_normal((num_pages, PAGE, kv_h, D)),
                         dtype)
    v_pool = jnp.asarray(rng.standard_normal((num_pages, PAGE, kv_h, D)),
                         dtype)
    tables = rng.permutation(np.arange(1, num_pages)).reshape(
        B, MAX_BLOCKS).astype(np.int32)
    lengths = list(lengths)
    if dead_slot:
        tables[-1] = 0
        lengths.append(1)
    return (q, k_pool, v_pool, jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32))


#: nothing, one key, one page exactly, one compute step exactly, a step
#: and one key, the whole table
LENGTHS = (0, 1, PAGE, STEP, STEP + 1, MAX_BLOCKS * PAGE)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize(
    "window", [None, 5, STEP + PAGE + 3, 10 * STEP],
    ids=["no_window", "window_in_one_step", "window_spans_steps",
         "window_past_row"])
@pytest.mark.parametrize("n_rep", [1, 4])
def test_walk_matches_reference(two_pages_a_step, n_rep, window, dtype):
    q, k_pool, v_pool, tables, lengths = _case(n_rep, dtype, LENGTHS)
    got = pa.paged_decode_attention(q, k_pool, v_pool, tables, lengths,
                                    interpret=True, window=window)
    assert got.dtype == q.dtype
    with jax.default_matmul_precision("highest"):
        want = pa.paged_decode_reference(
            q.astype(jnp.float32), k_pool.astype(jnp.float32),
            v_pool.astype(jnp.float32), tables, lengths, window)
    # the reference's softmax over no live key is uniform, not zero
    want = jnp.where((lengths > 0)[:, None, None], want, 0.0)
    if dtype == jnp.float32:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
    else:
        assert float(_rel_err(got, want)) <= DECODE_TOL
    assert not np.any(np.asarray(got[0], np.float32))   # length 0: zeros


@pytest.mark.parametrize("pages_a_step", [P, None],
                         ids=["two_pages_a_step", "derived_pages_a_step"])
def test_ragged_rows_at_the_derived_step(monkeypatch, pages_a_step):
    """Many rows of every length up to the table, one after another (each
    row's last step fetches the next row's first), with two pages a step
    and with the step the shapes give (the whole table here)."""
    if pages_a_step:
        _keys_a_step(monkeypatch, pages_a_step * PAGE)
    lengths = list(range(0, MAX_BLOCKS * PAGE + 1, 5))
    q, k_pool, v_pool, tables, lengths = _case(2, jnp.float32, lengths,
                                               seed=1)
    for window in (None, 11):
        got = pa.paged_decode_attention(q, k_pool, v_pool, tables, lengths,
                                        interpret=True, window=window)
        want = pa.paged_decode_reference(q, k_pool, v_pool, tables, lengths,
                                         window)
        want = jnp.where((lengths > 0)[:, None, None], want, 0.0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5,
                                   err_msg=f"window={window}")


@pytest.mark.parametrize("window", [None, 5, STEP + PAGE + 3],
                         ids=["no_window", "window_in_one_step",
                              "window_spans_steps"])
def test_dead_pages_never_reach_the_math(two_pages_a_step, window):
    """NaN in every page a row does not own, every page past its length
    and every page wholly before its window: the output is finite and
    equals the reference over a clean pool."""
    lengths = (1, PAGE, STEP + 1, 3 * PAGE + 2, MAX_BLOCKS * PAGE)
    q, k_pool, v_pool, tables, lens = _case(4, jnp.float32, lengths, seed=2,
                                            dead_slot=False)
    want = pa.paged_decode_reference(q, k_pool, v_pool, tables, lens, window)
    tables = np.array(tables)
    live = np.zeros(k_pool.shape[0], bool)
    for b, n in enumerate(lengths):
        first = max(n - window, 0) // PAGE if window else 0
        last = -(-n // PAGE)
        live[tables[b, first:last]] = True
        tables[b, last:] = 0                    # page 0 is poisoned too
    assert 0 < live.sum() < live.size - 1
    poison = jnp.where(jnp.asarray(live)[:, None, None, None], 0.0, jnp.nan)
    got = pa.paged_decode_attention(q, k_pool + poison, v_pool + poison,
                                    jnp.asarray(tables), lens,
                                    interpret=True, window=window)
    assert np.all(np.isfinite(np.asarray(got)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def _pallas_grids(fn, *args):
    grids = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grids.append(tuple(eqn.params["grid_mapping"].grid))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return grids


def test_work_does_not_scale_with_the_table():
    """The lowered call's grid is the rows, for a table 8 pages wide and
    for one 512 wide: no axis of the table's width."""
    B, h, kv_h, d, page = 4, 8, 2, 128, 16
    grids = {}
    for max_blocks in (8, 512):
        args = (jnp.zeros((B, h, d), jnp.bfloat16),
                jnp.zeros((64, page, kv_h, d), jnp.bfloat16),
                jnp.zeros((64, page, kv_h, d), jnp.bfloat16),
                jnp.zeros((B, max_blocks), jnp.int32),
                jnp.ones((B,), jnp.int32))
        grids[max_blocks], = _pallas_grids(
            lambda *a: pa.paged_decode_attention(*a, interpret=True,
                                                 window=4096), *args)
        assert max_blocks not in grids[max_blocks]
    assert grids[8] == grids[512] == (B,)


#: several query tokens a grid row, by form: KV heads, window, a sink, K's
#: planes, V in K's leading numbers (and then the scores' own scale)
_FORMS = {
    # one row a token that every head reads: 12 numbers in two planes of
    # 8 (the last half padding), the leading 8 the value
    "latent": dict(kv_h=1, d=12, planes=2, v_in_k=8, scale=0.3),
    "grouped_query": dict(kv_h=2, d=D),
    "window_and_sink": dict(kv_h=2, d=D, window=11, sink=True),
}


@pytest.mark.parametrize("form", sorted(_FORMS))
@pytest.mark.parametrize("tokens", [1, 2, 8])
def test_several_tokens_a_row_attend_as_a_token_a_row(monkeypatch, tokens,
                                                      form):
    """``q [R, T, h, d]``: ``T`` consecutive tokens of one sequence a grid
    row, ``lengths`` the last one's, against the reference applied a token
    a row.  The first group of a sequence (``lengths`` = ``T``), groups
    whose tokens cross a page and a step boundary, lengths that end on
    and just past both, the whole table, and (last) a dead row as the
    engine leaves one: an all-zero table."""
    for name in ("_STEP_TOKENS", "_STEP_COLUMNS", "_LATENT_STEP_TOKENS"):
        monkeypatch.setattr(pa, name, 2 * STEP)
    f = dict(_FORMS[form])
    kv_h, d, planes = f.pop("kv_h"), f.pop("d"), f.pop("planes", 1)
    shapes = (PAGE, kv_h, H, planes * 8 if planes > 1 else d, 4, MAX_BLOCKS,
              0 if "v_in_k" in f else None, f.get("window"))
    # two pages a step for several tokens a row (four for one, where no
    # window has fewer live)
    assert pa.pages_per_step(*shapes, tokens) == (
        P if tokens > 1 else 3 if "window" in f else 4)
    T = tokens
    lengths = [T, T + 3, PAGE, PAGE + 1, STEP, STEP + 1, STEP + T - 1,
               2 * STEP + 3, MAX_BLOCKS * PAGE, T]
    lengths = [max(n, T) for n in lengths]
    R = len(lengths)
    rng = np.random.RandomState(T)
    num_pages = R * MAX_BLOCKS + 1
    q = jnp.asarray(rng.standard_normal((R, T, H, d)), jnp.float32)
    k_pool = jnp.asarray(rng.standard_normal(
        (planes * num_pages, PAGE, kv_h, 8 if planes > 1 else d)),
        jnp.float32)
    v_pool = None if "v_in_k" in f else jnp.asarray(
        rng.standard_normal((num_pages, PAGE, kv_h, D)), jnp.float32)
    tables = rng.permutation(np.arange(1, num_pages)).reshape(
        R, MAX_BLOCKS).astype(np.int32)
    tables[-1] = 0
    tables, lengths = jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)
    if f.pop("sink", False):
        f["sink"] = jnp.asarray(rng.standard_normal((H,)), jnp.float32)
    if planes > 1:
        f.update(k_planes=planes, plane_stride=num_pages)
    if T == 1:      # the one form a row a token has
        q = q[:, 0]
    got = pa.paged_decode_attention(q, k_pool, v_pool, tables, lengths,
                                    interpret=True, **f)
    assert got.shape == q.shape[:-1] + (f.get("v_in_k", D),)
    own = (lengths[:, None] - (T - 1) + jnp.arange(T)[None, :]).reshape(-1)
    want = pa.paged_decode_reference(
        q.reshape(R * T, H, d), k_pool, v_pool,
        jnp.repeat(tables, T, axis=0), own, **f)
    np.testing.assert_allclose(np.asarray(got).reshape(want.shape),
                               np.asarray(want), rtol=2e-5, atol=2e-5)
    # and the reference's own form of several tokens a row is that
    if T > 1:
        np.testing.assert_array_equal(
            np.asarray(pa.paged_decode_reference(
                q, k_pool, v_pool, tables, lengths, **f)).reshape(want.shape),
            np.asarray(want))


def test_a_token_before_its_sequence_gives_zeros():
    """A row whose ``lengths`` is under ``T``: the tokens that would lie
    before the sequence's first attend over nothing and give zeros, as a
    row of length 0 does; the others are not disturbed."""
    T, lengths = 4, [2, 0]
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.standard_normal((2, T, H, D)), jnp.float32)
    pool = jnp.asarray(rng.standard_normal((13, PAGE, 2, D)), jnp.float32)
    tables = jnp.asarray(np.arange(1, 13).reshape(2, 6), jnp.int32)
    got = pa.paged_decode_attention(q, pool, pool, tables,
                                    jnp.asarray(lengths, jnp.int32),
                                    interpret=True)
    want = pa.paged_decode_reference(q[0, 2:], pool, pool,
                                     jnp.repeat(tables[:1], 2, axis=0),
                                     jnp.asarray([1, 2], jnp.int32))
    np.testing.assert_allclose(np.asarray(got[0, 2:]), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert not np.any(np.asarray(got[0, :2])) and not np.any(
        np.asarray(got[1]))


@pytest.mark.parametrize("shape, want", [
    # Mistral-7B serving: 8 KV heads, 2,048 score columns = 256 keys a step
    ((16, 8, 32, 128, 2, 512), 16),
    # and under its window of 4,096 (257 live pages at most: no cap)
    ((16, 8, 32, 128, 2, 512, None, 4096), 16),
    # a table narrower than a step
    ((16, 8, 32, 128, 2, 4), 4),
    # OLMoE-1B-7B: 16 KV heads would be 128 keys; the floor of 256 holds
    ((16, 16, 16, 128, 2, 256), 16),
    # the hybrid model's FULL layers: 4 KV heads under 64, K held in 256
    # lanes, V 128: 2,048 columns are 512 keys
    ((16, 4, 64, 256, 2, 512, 128), 32),
    # its WINDOW layers: 8 KV heads, window 128: at most 128/16 + 1 live
    # pages a row, so a step is never built for more
    ((16, 8, 64, 256, 2, 512, 128, 128), 9),
    # a window that is no whole number of pages, and one beyond the table
    ((16, 8, 64, 256, 2, 512, 128, 100), 8),
    ((16, 8, 64, 256, 2, 6, 128, 128), 6),
    # a TP shard's two kv heads: 16 while a step was 256 keys whatever the
    # heads; 2,048 columns of 2 heads are 1,024 keys (no cell runs it)
    ((16, 2, 8, 128, 2, 512), 64),
    # pages of 64 keys
    ((64, 8, 32, 128, 2, 128), 4),
    # 32 kv heads of float32: the VMEM budget, not the step, bounds it
    ((16, 32, 32, 128, 4, 512), 6),
    ((512, 8, 32, 128, 2, 16), 1),
    # the latent cache (V in K's rows, 5 planes, pages of 128): its own
    # 1,024 keys a step
    ((128, 1, 128, 640, 2, 128, 0), 8),
    # its chunk rows, several tokens a grid row: half the keys a step,
    # and T·h rows of score temporaries and accumulator in the budget:
    # four tokens hold the four pages, eight hold one
    ((128, 1, 128, 640, 2, 128, 0, None, 2), 4),
    ((128, 1, 128, 640, 2, 128, 0, None, 4), 4),
    ((128, 1, 128, 640, 2, 128, 0, None, 8), 1),
    # Mistral's shape with eight tokens a row: half of 256 keys; under a
    # window of 90 keys a token has 7 live pages at most and eight
    # tokens' windows together 8; the hybrid window layers' 64 heads x 8
    # tokens are 512 score rows: the budget holds five pages
    ((16, 8, 32, 128, 2, 512, None, None, 8), 8),
    ((16, 8, 16, 128, 2, 512, None, 90, 1), 7),
    ((16, 8, 16, 128, 2, 512, None, 90, 8), 8),
    ((16, 8, 64, 256, 2, 512, 128, None, 8), 5),
], ids=["mistral", "mistral_window_4096", "narrow_table", "olmoe",
        "hybrid_full", "hybrid_window_128", "window_100", "window_past_table",
        "tp_shard", "pages_of_64", "vmem_bound", "one_page", "latent",
        "latent_2_tokens", "latent_4_tokens", "latent_8_tokens",
        "mistral_8_tokens", "window_90_1_token", "window_90_8_tokens",
        "hybrid_8_tokens"])
def test_pages_per_step_follows_the_shapes(shape, want):
    assert pa.pages_per_step(*shape) == want
    page, kv_h, h, d, itemsize, _, v_dim, _, tokens = \
        shape + (None,) * (8 - len(shape)) + (1,) * (len(shape) < 9)
    v_dim = d if v_dim is None else v_dim
    held = want * (2 * page * kv_h * (d + v_dim) * itemsize
                   + 5 * tokens * h * page * kv_h * 4) \
        + tokens * h * (v_dim or d) * 4
    assert want == 1 or held <= pa._VMEM_BUDGET_BYTES


@pytest.mark.parametrize("chunk, shape, want", [
    # the latent cell: 128 heads' accumulator and score rows: 4 tokens
    (128, (128, 1, 128, 640, 2, 128, 0), 4),
    # a divisor of the chunk, and the whole chunk where everything fits
    (96, (128, 1, 128, 640, 2, 128, 0), 4),
    (6, (128, 1, 128, 640, 2, 128, 0), 3),
    (16, (8, 1, 4, 16, 4, 8, 0), 16),
    # grouped-query shapes (no caller): Mistral's, the hybrid full layers'
    (128, (16, 8, 32, 128, 2, 512), 8),
    (128, (16, 4, 64, 256, 2, 512, 128), 4),
    # nothing fits beside a page of 512 x 8 KV heads: a token a row
    (128, (512, 8, 32, 128, 2, 16), 1),
], ids=["latent", "chunk_of_96", "chunk_of_6", "tiny", "mistral",
        "hybrid_full", "one_page"])
def test_query_tokens_per_row_follows_the_shapes(chunk, shape, want):
    assert pa.query_tokens_per_row(chunk, *shape) == want
    assert chunk % want == 0
    # the step it is built with holds the pages it wants
    full = shape + (None,) * (8 - len(shape))
    assert want == 1 or pa.pages_per_step(*full, want) \
        == pa._step_pages(*full, want)[0]
