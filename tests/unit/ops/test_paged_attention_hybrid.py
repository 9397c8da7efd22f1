"""The paged decode kernel (Pallas interpreter) where K and V rows differ
in width, the K pool carries lane padding, many query heads share a KV
head and the softmax has a sink, against ``paged_decode_reference`` and
against the sum written out."""

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import kv_cache
from deepspeed_tpu.ops.pallas import paged_attention as pa
from deepspeed_tpu.ops.pallas.selfcheck import DECODE_TOL, _rel_err

#: a table of 640 keys: wider than the 32 pages a step of the full kind
#: (4 KV heads) holds, so a row can take a second step
H, K_DIM, V_DIM, PAGE, MAX_BLOCKS = 64, 192, 128, 16, 40


def _case(kv_h, lengths, dtype=jnp.float32, seed=0, max_blocks=MAX_BLOCKS):
    """K rows of 192 held in two planes of 128 lanes
    (``kv_cache.lane_planes``), plane 1 a whole plane of pages after
    plane 0, V rows of 128, a shuffled table; the padding lanes hold zeros
    as the engine writes them."""
    rng = np.random.RandomState(seed)
    B = len(lengths)
    pages = B * max_blocks + 1
    assert kv_cache.lane_planes(K_DIM) == (2, 128)
    k = np.zeros((pages, PAGE, kv_h, 256), np.float32)
    k[..., :K_DIM] = rng.standard_normal((pages, PAGE, kv_h, K_DIM))
    k = np.concatenate([k[..., :128], k[..., 128:]], axis=0)
    v = rng.standard_normal((pages, PAGE, kv_h, V_DIM))
    tables = rng.permutation(np.arange(1, pages)).reshape(B, max_blocks)
    return (jnp.asarray(rng.standard_normal((B, H, K_DIM)), dtype),
            jnp.asarray(k, dtype), jnp.asarray(v, dtype),
            jnp.asarray(tables, jnp.int32), jnp.asarray(lengths, jnp.int32),
            jnp.asarray(rng.standard_normal(H), jnp.float32))


#: nothing, one key, a page, under the window, around it, a window that
#: straddles 9 pages (200 − 128 = 72: pages 4..12), a 512-key step exactly
#: and one key past it, and a row of three keys beside one of the whole
#: table
LENGTHS = (0, 1, 16, 100, 127, 128, 129, 200, 512, 513, 3, 640)
PAGES = len(LENGTHS) * MAX_BLOCKS + 1


def _pages_a_step(kv_h, window, dtype=jnp.float32, max_blocks=MAX_BLOCKS):
    return pa.pages_per_step(PAGE, kv_h, H, 256, jnp.dtype(dtype).itemsize,
                             max_blocks, V_DIM, window)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("with_sink", [False, True], ids=["plain", "sink"])
@pytest.mark.parametrize("window", [None, 128], ids=["full", "window128"])
@pytest.mark.parametrize("kv_h", [4, 8], ids=["groups_of_16", "groups_of_8"])
def test_kernel_matches_reference(kv_h, window, with_sink, dtype):
    q, k, v, tables, lengths, sink = _case(kv_h, LENGTHS, dtype)
    sink = sink if with_sink else None
    assert k.shape == (2 * PAGES, PAGE, kv_h, 128)
    # what the walk is built with here: a step of 2,048 score columns
    # (float32: 30 and 15 pages, the VMEM budget), the window's 9 live
    # pages at most
    full = 128 // kv_h if dtype == jnp.bfloat16 else 120 // kv_h
    assert _pages_a_step(kv_h, window, dtype) == (9 if window else full)
    assert pa.paged_decode_impl(H, kv_h, True, k.shape[-1], V_DIM) \
        == "pallas_interpret"
    got = pa.paged_decode_attention(q, k, v, tables, lengths, interpret=True,
                                    window=window, sink=sink, k_planes=2,
                                    plane_stride=PAGES)
    want = pa.paged_decode_reference(q, k, v, tables, lengths, window, sink,
                                     2, PAGES)
    assert got.shape == (len(LENGTHS), H, V_DIM)
    # a row of length 0: zeros from the kernel (the reference averages V)
    # bfloat16: the reference rounds the probabilities AND the output, the
    # kernel the output of a float32 sum: two roundings of 2^-8 apart
    tol = DECODE_TOL * (2 if dtype == jnp.bfloat16 else 1)
    assert float(_rel_err(got[1:], want[1:])) < tol
    assert not np.asarray(got[0], np.float32).any()        # length 0


@pytest.mark.parametrize("kv_h, window", [(4, None), (8, 128)],
                         ids=["full_groups_of_16", "window_groups_of_8"])
def test_a_table_narrower_than_a_step(kv_h, window):
    """Six pages a row where the shapes would give a step 32 and 9: the
    step is the table, and a row's one step fetches its live pages only."""
    lengths = (0, 1, 50, 95, 96)
    q, k, v, tables, lengths, sink = _case(kv_h, lengths, max_blocks=6)
    pages = k.shape[0] // 2
    assert _pages_a_step(kv_h, window, max_blocks=6) == 6
    got = pa.paged_decode_attention(q, k, v, tables, lengths, interpret=True,
                                    window=window, sink=sink, k_planes=2,
                                    plane_stride=pages)
    want = pa.paged_decode_reference(q, k, v, tables, lengths, window, sink,
                                     2, pages)
    assert float(_rel_err(got[1:], want[1:])) < DECODE_TOL
    assert not np.asarray(got[0]).any()


def test_planes_that_do_not_fill_the_pool_are_refused():
    q, k, v, tables, lengths, _ = _case(4, (5,), max_blocks=2)
    with pytest.raises(ValueError, match="planes of 2"):
        pa.paged_decode_attention(q, k, v, tables, lengths, interpret=True,
                                  k_planes=2, plane_stride=2)


def test_reference_is_the_sum_written_out():
    """One row by hand: scale 1/sqrt(192) of the TRUE width, keys
    ``i − j < window``, the sink in the denominator only."""
    kv_h, window = 4, 128
    q, k, v, tables, lengths, sink = _case(kv_h, (150,) + (0,) * 6)
    pages = k.shape[0] // 2
    got = np.asarray(pa.paged_decode_reference(q, k, v, tables, lengths,
                                               window, sink, 2, pages))[0]
    keys = np.concatenate([np.asarray(k)[np.asarray(tables[0]) + p * pages]
                           for p in (0, 1)], axis=-1).reshape(-1, kv_h, 256)
    vals = np.asarray(v)[np.asarray(tables[0])].reshape(-1, kv_h, V_DIM)
    for head in (0, 17, 63):
        g = head // (H // kv_h)
        live = np.arange(150 - window, 150)          # i = 149: j > 21
        s = keys[live, g, :K_DIM] @ np.asarray(q)[0, head] / np.sqrt(K_DIM)
        e = np.exp(s - s.max())
        p = e / (e.sum() + np.exp(float(sink[head]) - s.max()))
        np.testing.assert_allclose(got[head], p @ vals[live, g], rtol=2e-4,
                                   atol=2e-5)
    # and the sink does take mass
    plain = np.asarray(pa.paged_decode_reference(q, k, v, tables, lengths,
                                                 window, None, 2, pages))[0]
    assert np.abs(plain - got).max() > 1e-3


@pytest.mark.parametrize("k_dim, v_dim, want", [
    (128, 128, "pallas"), (192, 128, "reference"), (64, 64, "reference")])
def test_compiled_path_needs_whole_lane_rows(monkeypatch, k_dim, v_dim, want):
    """What the engine records: a pool whose rows are no whole number of
    128-lane rows runs the reference on the chip, and says so."""
    monkeypatch.setattr(pa, "reference_off_tpu", lambda interpret: False)
    assert pa.paged_decode_impl(64, 8, None, k_dim, v_dim) == want


def test_only_rows_wider_than_a_lane_row_are_cut_into_planes():
    assert [kv_cache.lane_planes(d) for d in (64, 128, 192, 256, 320)] \
        == [(1, 64), (1, 128), (2, 128), (2, 128), (3, 128)]
