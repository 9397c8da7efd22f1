"""Flash-kernel numerics parity shard (ISSUE 12).

Every dispatch rung of the reworked flash attention — resident kernel,
streamed (lattice-gather) kernel, both backward pairs, segments, windows
— against ``_reference_attention`` in interpret mode on CPU, with
tolerance tiers per dtype.  Plus the shared skip lattice against a
brute-force token-mask coarsening, and the block-size tables' contracts.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
lattice = importlib.import_module("deepspeed_tpu.ops.pallas.lattice")

#: (rtol, atol) per input dtype — bf16 inputs accumulate in fp32 inside
#: every kernel, so the budget covers the input rounding, not the math
TOL = {jnp.float32: (2e-5, 2e-5), jnp.bfloat16: (2e-2, 2e-2)}


def qkv(B=2, S=256, h=2, d=64, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, S, h, d) * 0.5).astype(dtype)
    return mk(), mk(), mk()


def segs(B, S):
    """Two packed segments per row, uneven split."""
    cut = S // 3
    return jnp.asarray(
        np.concatenate([np.zeros((B, cut)), np.ones((B, S - cut))],
                       axis=1), jnp.int32)


# ---------------------------------------------------------------------------
# lattice
# ---------------------------------------------------------------------------


def brute_lattice(S, bq, bk, causal, window):
    q = np.arange(S)[:, None]
    k = np.arange(S)[None, :]
    keep = np.ones((S, S), bool)
    if causal:
        keep &= q >= k
    if window is not None:
        keep &= (q - k < window) if causal else (np.abs(q - k) < window)
    return keep.reshape(S // bq, bq, S // bk, bk).any(axis=(1, 3))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("window", [None, 1, 63, 100])
def test_live_lattice_matches_token_mask_coarsening(causal, window):
    for S, bq, bk in ((256, 64, 64), (256, 64, 32), (512, 128, 64)):
        got = lattice.live_lattice(S, bq, bk, causal, window)
        want = brute_lattice(S, bq, bk, causal, window)
        np.testing.assert_array_equal(got, want)


def test_plans_walk_exactly_the_lattice():
    S, bq, bk = 512, 64, 64
    lat = lattice.live_lattice(S, bq, bk, True, 100)
    idx, counts = lattice.plan_q_live(S, bq, bk, True, 100)
    for qi in range(S // bq):
        live = set(np.nonzero(lat[qi])[0])
        assert set(idx[qi, :counts[qi]].tolist()) == live
    idx_k, counts_k = lattice.plan_k_live(S, bq, bk, True, 100)
    for kj in range(S // bk):
        live = set(np.nonzero(lat[:, kj])[0])
        assert set(idx_k[kj, :counts_k[kj]].tolist()) == live


def test_block_bounds_cover_the_lattice_rows():
    """The contiguous [k0, nk_eff) resident-kernel bounds must cover
    every live tile of the banded lattices (and nothing is live outside
    them) — the resident and streamed kernels must agree on skips."""
    S, bq, bk = 512, 64, 64
    for causal, window in ((True, None), (True, 100), (False, 100)):
        lat = lattice.live_lattice(S, bq, bk, causal, window)
        for qi in range(S // bq):
            k0, nk_eff = jax.tree.map(
                int, lattice.kv_block_bounds(qi, bq, bk, S // bk, causal,
                                             window))
            live = np.nonzero(lat[qi])[0]
            if len(live):
                assert k0 <= live.min() and live.max() < nk_eff
            assert not lat[qi, :k0].any()
            assert not lat[qi, nk_eff:].any()


@pytest.mark.parametrize("causal, window, packed", [
    (True, None, False), (True, 100, False), (False, 100, True),
    (False, None, True)])
def test_tile_keep_transposed_is_the_same_mask_keys_major(causal, window,
                                                          packed):
    """The backward holds its score tile ``[bk, bq]``: its mask is the
    forward's, turned."""
    bq, bk = 64, 128
    seg = np.asarray(segs(1, 512))[0]
    for qi, kj in ((0, 0), (3, 1), (5, 2), (7, 3)):
        q_seg = jnp.asarray(seg[qi * bq:(qi + 1) * bq]) if packed else None
        k_seg = jnp.asarray(seg[kj * bk:(kj + 1) * bk]) if packed else None
        plain = lattice.tile_keep(qi, kj, bq, bk, causal, window, q_seg,
                                  k_seg)
        turned = lattice.tile_keep(qi, kj, bq, bk, causal, window, q_seg,
                                   k_seg, transposed=True)
        assert turned.shape == (bk, bq)
        np.testing.assert_array_equal(np.asarray(plain).T,
                                      np.asarray(turned))


def test_auto_blocks_step_down_with_seq_length():
    assert lattice.auto_flash_blocks(2048, 64) == (512, 512)
    assert lattice.auto_flash_blocks(32768, 64) == (256, 256)
    bq_s, _ = lattice.auto_flash_blocks(2048, 64)
    bq_l, _ = lattice.auto_flash_blocks(32768, 64)
    assert bq_l <= bq_s
    # the backward's tile never grows with the resident planes
    tiles = [lattice.auto_flash_blocks(S, 128, backward=True, itemsize=4)
             for S in (1024, 4096, 16384)]
    assert all(a[0] * a[1] >= b[0] * b[1] for a, b in zip(tiles, tiles[1:]))


@pytest.mark.parametrize("S, d, itemsize", [
    (8192, 128, 2),      # the Mistral training cells
    (512, 64, 2),        # BERT-large
    (4096, 64, 4), (16384, 128, 2), (16384, 128, 4), (192, 64, 2)])
def test_auto_blocks_key_on_elements_not_raw_seq_length(S, d, itemsize):
    """The backward rule keys on what the resident passes hold: the
    chosen tile's plan (planes of S·d·itemsize, double-buffered, plus
    the tile's own float32 planes) fits the limit handed to Mosaic, no
    larger candidate does, and the tile divides S."""
    from deepspeed_tpu.ops.pallas.select import RESIDENT_VMEM_LIMIT_BYTES

    bq, bk = lattice.auto_flash_blocks(S, d, backward=True,
                                       itemsize=itemsize)
    assert S % bq == 0 and S % bk == 0
    assert lattice.backward_plan_bytes(S, d, itemsize, bq, bk) \
        <= RESIDENT_VMEM_LIMIT_BYTES
    for cq, ck in lattice._BWD_TILES:
        cq, ck = lattice.fit_block(cq, S), lattice.fit_block(ck, S)
        if cq * ck > bq * bk:
            assert lattice.backward_plan_bytes(S, d, itemsize, cq, ck) \
                > RESIDENT_VMEM_LIMIT_BYTES
    # wider operands never get a larger tile
    wide = lattice.auto_flash_blocks(S, d, backward=True, itemsize=4)
    assert wide[0] * wide[1] <= bq * bk


def test_apply_lattice_window_is_token_denominated():
    """apply_lattice takes TOKEN windows like every other lattice fn;
    the cell size converts — a cb=16 layout with a 32-token window keeps
    a ~2-cell band, not a 32-cell one (review finding)."""
    nb, cb = 8, 16
    layout = np.ones((1, nb, nb), np.int8)
    out = lattice.apply_lattice(layout, causal=True, window=32, cb=cb)
    # cell (i, j) live iff ∃ tokens q∈cell i, k∈cell j with 0<=q-k<32:
    # exactly the token lattice at block=cb
    want = lattice.live_lattice(nb * cb, cb, cb, True, 32)[None]
    np.testing.assert_array_equal(out.astype(bool), want)
    # row 7 reaches at most back to cell 4 (112-16·cb boundary), far
    # from the full 8-cell band a cell-unit window would keep
    assert out[0, 7, :5].sum() <= 2


def test_explicit_backward_blocks_capped_at_table():
    # an explicit block is a cap: the resolver never hands the resident
    # passes a tile larger than the rule's (whose plan fits the limit),
    # and honors a smaller one
    for S, d, itemsize in ((16384, 64, 2), (16384, 128, 4), (8192, 128, 2)):
        abq, abk = lattice.auto_flash_blocks(S, d, backward=True,
                                             itemsize=itemsize)
        bq, bk = fa._resolve_blocks(1024, 1024, S, d, backward=True,
                                    itemsize=itemsize)
        assert (bq, bk) == (abq, abk)
        assert fa._resolve_blocks(128, 256, S, d, backward=True,
                                  itemsize=itemsize) == (128, 256)


# ---------------------------------------------------------------------------
# forward parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, None), (False, None),
                                           (True, 100), (False, 100)])
def test_resident_fwd_matches_reference(dtype, causal, window):
    q, k, v = qkv(dtype=dtype)
    got = fa.flash_attention_interpret(q, k, v, causal, 64, 64,
                                       window=window)
    ref = fa._reference_attention(q, k, v, causal, window)
    rtol, atol = TOL[dtype]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=rtol, atol=atol)


@pytest.mark.parametrize("causal,window", [(True, None), (False, None),
                                           (True, 100)])
def test_streamed_fwd_matches_reference(causal, window):
    """The long-S gather kernel (force-streamed at test size) — the path
    S > RESIDENT_VMEM_ELEMS/d takes in production."""
    q, k, v = qkv()
    got = fa.flash_attention_interpret(q, k, v, causal, 64, 64,
                                       window=window, stream=True)
    ref = fa._reference_attention(q, k, v, causal, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_segment_fwd_matches_reference(causal):
    q, k, v = qkv()
    seg = segs(q.shape[0], q.shape[1])
    got = fa.flash_attention_interpret(q, k, v, causal, 64, 64,
                                       segment_ids=seg)
    ref = fa._reference_attention(q, k, v, causal, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# backward parity
# ---------------------------------------------------------------------------


def _ref_vjp(q, k, v, do, causal, window=None, seg=None):
    def f(q_, k_, v_):
        out, _ = fa._reference_fwd_with_lse(q_, k_, v_, causal, window,
                                            seg)
        return out
    _, vjp = jax.vjp(f, q, k, v)
    return vjp(do)


@pytest.mark.parametrize("causal,window", [(True, None), (False, None),
                                           (True, 100), (False, 100)])
def test_resident_bwd_matches_reference(causal, window):
    q, k, v = qkv()
    do = jnp.asarray(np.random.RandomState(7).randn(*q.shape), jnp.float32)
    out, lse = fa._reference_fwd_with_lse(q, k, v, causal, window)
    got = fa._flash_bwd_pallas(q, k, v, out, lse, do, causal, 64, 64,
                               window, interpret=True)
    want = _ref_vjp(q, k, v, do, causal, window)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("causal,window", [(True, None), (False, 100)])
def test_streamed_bwd_matches_reference(causal, window):
    q, k, v = qkv()
    do = jnp.asarray(np.random.RandomState(7).randn(*q.shape), jnp.float32)
    out, lse = fa._reference_fwd_with_lse(q, k, v, causal, window)
    got = fa._flash_bwd_stream(q, k, v, out, lse, do, causal, 64, 64,
                               window, interpret=True)
    want = _ref_vjp(q, k, v, do, causal, window)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-3, atol=2e-3)


#: dtype, S, d, block_q, block_k, causal, window, segments — what the
#: rule can pick and what the cells meet: tiles wider than tall and taller
#: than wide; a window whose edge falls inside a tile (100) and on a
#: tile's edge (128); S = 384, no multiple of the largest tile; (0, 0)
#: is the rule's own choice
_BWD_CASES = [
    (jnp.float32, 256, 64, 64, 128, True, 100, False),
    (jnp.float32, 256, 64, 128, 64, True, 128, False),
    (jnp.float32, 256, 64, 64, 128, False, 100, False),
    (jnp.float32, 256, 64, 128, 64, False, None, True),
    (jnp.float32, 384, 64, 128, 64, True, 64, True),
    (jnp.float32, 384, 128, 0, 0, True, 200, False),
    (jnp.bfloat16, 256, 128, 64, 128, True, 100, False),
    (jnp.bfloat16, 256, 128, 128, 64, True, 128, True),
    (jnp.bfloat16, 256, 64, 128, 64, False, 100, False),
    (jnp.bfloat16, 384, 64, 128, 128, True, None, False),
    (jnp.bfloat16, 384, 64, 0, 0, True, 200, True),
]


@pytest.mark.parametrize("dtype, S, d, bq, bk, causal, window, packed",
                         _BWD_CASES)
def test_resident_bwd_tiles_match_reference(dtype, S, d, bq, bk, causal,
                                            window, packed):
    """The resident backward at its operands' dtype (bf16 products with
    float32 statistics and accumulators; float32 stays float32) against
    the float32 reference's vjp of the same inputs."""
    q, k, v = qkv(B=1, S=S, d=d, dtype=dtype)
    seg = segs(1, S) if packed else None
    do = jnp.asarray(np.random.RandomState(7).randn(*q.shape)).astype(dtype)
    f32 = lambda a: a.astype(jnp.float32)
    out, lse = fa._reference_fwd_with_lse(f32(q), f32(k), f32(v), causal,
                                          window, seg)
    got = fa._flash_bwd_pallas(q, k, v, out.astype(dtype), lse, do, causal,
                               bq, bk, window, interpret=True,
                               segment_ids=seg)
    want = _ref_vjp(f32(q), f32(k), f32(v), f32(do), causal, window, seg)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    for g, w in zip(got, want):
        assert g.dtype == dtype
        err = np.abs(np.asarray(g, np.float32) - np.asarray(w)).max()
        assert err <= tol * np.abs(np.asarray(w)).max(), err


def test_segment_bwd_matches_reference():
    q, k, v = qkv()
    seg = segs(q.shape[0], q.shape[1])
    do = jnp.asarray(np.random.RandomState(7).randn(*q.shape), jnp.float32)
    out, lse = fa._reference_fwd_with_lse(q, k, v, True, None, seg)
    got = fa._flash_bwd_pallas(q, k, v, out, lse, do, True, 64, 64, None,
                               interpret=True, segment_ids=seg)
    want = _ref_vjp(q, k, v, do, True, None, seg)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-3, atol=2e-3)


def test_public_vjp_with_segments_on_cpu_path():
    """The custom_vjp plumbing: segment ids ride as a traced arg whose
    cotangent is float0 — grad through the public entry must work and
    match the reference (CPU reference route)."""
    q, k, v = qkv(B=1, S=96, h=2, d=32)
    seg = segs(1, 96)

    g = jax.grad(lambda q_: jnp.sum(
        fa.flash_attention(q_, k, v, True, segment_ids=seg) ** 2))(q)
    g_ref = jax.grad(lambda q_: jnp.sum(
        fa._reference_attention(q_, k, v, True,
                                segment_ids=seg) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# heads side by side on the lanes (d < 128) and Δ summed in the tile
# ---------------------------------------------------------------------------


def test_heads_per_program_fill_the_lanes_from_the_shape_alone():
    """Two 64-wide heads (four 32-wide) to a resident program, one from
    128 lanes up or where the heads do not pair off; the Mistral cells'
    ``h=32, d=128`` keeps one head a program and the planes it had."""
    assert fa._heads_per_program(16, 64) == 2       # BERT-large
    assert fa._heads_per_program(8, 32) == 4
    assert fa._heads_per_program(3, 64) == 1        # an odd head is left
    assert fa._heads_per_program(4, 96) == 1        # 96 divides no 128
    assert fa._heads_per_program(32, 128) == 1      # Mistral-7B
    assert fa._heads_per_program(8, 256) == 1


@pytest.mark.parametrize("B, S, h, d, causal, window, packed", [
    (2, 256, 4, 64, False, None, True),     # an encoder with padding
    (2, 256, 4, 64, True, None, False),
    (1, 256, 8, 32, True, 96, False),       # four heads a program
    (2, 128, 2, 64, False, None, False),    # one tile: Δ in the tile
    (1, 256, 3, 64, False, None, True),     # heads that do not pair off
], ids=["encoder_segments", "causal", "four_heads_window", "one_tile",
        "odd_heads"])
def test_heads_side_by_side_match_reference(B, S, h, d, causal, window,
                                            packed):
    """Forward and all three gradients of the resident kernels against the
    reference, with several heads sharing a program's 128 lanes (and one
    case where they cannot), over 128 x 128 tiles and over one tile."""
    rng = np.random.RandomState(5)
    mk = lambda: jnp.asarray(rng.randn(B, S, h, d) * 0.5, jnp.float32)
    q, k, v, do = mk(), mk(), mk(), mk()
    seg = (jnp.asarray(np.sort(rng.randint(0, 3, (B, S)), axis=1), jnp.int32)
           if packed else None)
    out, vjp = jax.vjp(lambda q, k, v: fa.flash_attention(
        q, k, v, causal, block_q=128, block_k=128, window=window,
        segment_ids=seg, interpret=True), q, k, v)
    ref, ref_vjp = jax.vjp(lambda q, k, v: fa._reference_attention(
        q, k, v, causal, window, seg), q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    for got, want in zip(vjp(do), ref_vjp(do)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# model routing (BERT padding-as-segments)
# ---------------------------------------------------------------------------


def _dense_attention(q, k, v, mesh, causal=False, segment_ids=None):
    """The attention BERT had beside the flash op until PR 41, written out
    here: einsum, float32 softmax over every key that is not a pad."""
    assert not causal
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) \
        / np.sqrt(q.shape[-1])
    if segment_ids is not None:
        s = jnp.where(segment_ids.astype(bool)[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.fixture
def through_interpreter(monkeypatch):
    """Every ``flash_attention`` call runs the kernels in the Pallas
    interpreter, as a test has to ask: the platform here is the CPU."""
    import functools

    monkeypatch.setattr(fa, "flash_attention", functools.partial(
        fa.flash_attention, interpret=True))


def _bert_batch(padded: bool, B=2, S=64):
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, 512, size=(B, S)))
    mask = np.ones((B, S), bool)
    if padded:
        mask[:, S - 10:] = False  # padded tail
    labels = np.where(mask & (rng.rand(B, S) < 0.3), np.asarray(ids), -100)
    batch = {"input_ids": ids, "labels": jnp.asarray(labels)}
    if padded:
        batch["attention_mask"] = jnp.asarray(mask)
    return batch, mask


@pytest.mark.parametrize("impl", ["reference", "interpret"])
@pytest.mark.parametrize("padded", [True, False], ids=["padded", "no_mask"])
def test_bert_flash_matches_xla_on_real_tokens(padded, impl, request,
                                               monkeypatch):
    """BERT's one attention path (the flash op; padding, when there is
    any, as segment ids) against the same model over an in-test dense
    einsum + softmax: logits on real-token rows (pad-query rows differ by
    design and are -100 in the loss) and the loss's gradients, with the
    reference the op runs off the TPU and with its kernels interpreted.
    ``attn_impl`` selects nothing: both values give the same model."""
    from deepspeed_tpu.models.bert import BertConfig, BertModel

    if impl == "interpret":
        request.getfixturevalue("through_interpreter")
    batch, mask = _bert_batch(padded)
    model = BertModel(BertConfig.tiny(dtype=jnp.float32))
    params = model.init_params(jax.random.PRNGKey(0))
    run = lambda m: (m.forward(params, batch["input_ids"],
                               batch.get("attention_mask")),
                     jax.grad(m.loss)(params, batch))
    logits, grads = run(model)
    logits_flash, _ = run(BertModel(BertConfig.tiny(dtype=jnp.float32,
                                                    attn_impl="flash")))
    np.testing.assert_array_equal(np.asarray(logits),
                                  np.asarray(logits_flash))

    monkeypatch.setattr(fa, "flash_attention_spmd", _dense_attention)
    logits_x, grads_x = run(model)
    np.testing.assert_allclose(
        np.asarray(logits)[mask], np.asarray(logits_x)[mask],
        rtol=2e-4, atol=2e-4)
    for (path, g), g_x in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                              jax.tree.leaves(grads_x)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(g_x), rtol=2e-3, atol=2e-5,
            err_msg=jax.tree_util.keystr(path))


def test_bert_all_ones_mask_gives_the_logits_of_no_mask():
    """A batch without ``attention_mask`` hands the op no segment ids; the
    same batch with a mask of ones takes the segment path and gives the
    same logits."""
    from deepspeed_tpu.models.bert import BertConfig, BertModel

    batch, _ = _bert_batch(padded=False)
    model = BertModel(BertConfig.tiny(dtype=jnp.float32))
    params = model.init_params(jax.random.PRNGKey(0))
    seen = []
    real = fa.flash_attention_spmd

    def spy(q, k, v, mesh, causal=True, segment_ids=None, **kw):
        seen.append(segment_ids is not None)
        return real(q, k, v, mesh, causal=causal, segment_ids=segment_ids,
                    **kw)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fa, "flash_attention_spmd", spy)
        plain = model.forward(params, batch["input_ids"])
        ones = model.forward(params, batch["input_ids"],
                             attention_mask=jnp.ones_like(batch["input_ids"]))
    assert seen == [False, True]          # one trace of the layer each
    np.testing.assert_allclose(np.asarray(ones), np.asarray(plain),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("k_blocks, limit", [(1, 0.055), (2, 0.13)])
def test_deep_stack_gradients_by_k_blocks(k_blocks, limit, monkeypatch):
    """A deep random-weight post-norm encoder in bf16 (24 layers; token
    representations collapse, so a row's keys share a large common part)
    against the same weights in float32: the relative error of the
    ``wq`` / ``wk`` gradients through the interpreted kernels.  With one
    k-block a tile the backward sums Δ = Σ_k p·dp itself
    (``delta_in_tile``: 0.033–0.040 here over two seeds, 0.027–0.035 in
    the BERT-large cell on the chip, whose limit is 0.07); with several it
    takes Δ = do·o from the bf16-ROUNDED output, and the residue Σ_k ds ≠ 0
    times the common key shows (0.075–0.099 here).  The second limit is
    that KNOWN gap (PERF.md §7, PR 41 (6)), held so that it does not grow
    unseen and so that a repair has a number to tighten."""
    from deepspeed_tpu.models.bert import BertConfig, BertModel

    S = 128
    cfg = dict(vocab_size=512, hidden_size=256, intermediate_size=1024,
               num_layers=24, num_heads=4, max_seq_len=S)
    batch, _ = _bert_batch(padded=False, S=S)
    model32 = BertModel(BertConfig(dtype=jnp.float32, **cfg))
    params = model32.init_params(jax.random.PRNGKey(0))
    want = jax.jit(jax.grad(model32.loss))(params, batch)["layers"]["attn"]

    real = fa.flash_attention
    monkeypatch.setattr(fa, "flash_attention", lambda *a, **kw: real(
        *a, **{**kw, "interpret": True, "block_k": S // k_blocks}))
    assert fa._resolve_blocks(0, S // k_blocks, S, 64,
                              backward=True)[1] == S // k_blocks
    model16 = BertModel(BertConfig(dtype=jnp.bfloat16, **cfg))
    got = jax.jit(jax.grad(model16.loss))(params, batch)["layers"]["attn"]
    for name in ("wq", "wk"):
        g, w = (np.asarray(t[name], np.float32) for t in (got, want))
        err = np.linalg.norm(g - w) / np.linalg.norm(w)
        assert err <= limit, (name, err)


@pytest.mark.parametrize("impl", ["reference", "interpret"])
def test_bert_step_counts_the_route_its_attention_took(impl, request):
    """Tracing a BERT training step counts the implementation the flash op
    chose, once a call site: off the TPU the reference and no kernel; with
    the interpreter asked for, that and no reference."""
    from deepspeed_tpu.models.bert import BertConfig, BertModel
    from deepspeed_tpu.telemetry import get_telemetry

    if impl == "interpret":
        request.getfixturevalue("through_interpreter")
    hub = get_telemetry()
    hub.reset()
    hub.configure(enabled=True, jsonl=False, prometheus=False)
    try:
        batch, _ = _bert_batch(padded=True)
        model = BertModel(BertConfig.tiny(dtype=jnp.float32))
        params = model.init_params(jax.random.PRNGKey(0))
        jax.jit(jax.value_and_grad(model.loss)).lower(params, batch)
        counts = {name.rsplit("/", 1)[1]: c["value"] for name, c in
                  hub.registry.snapshot()["counters"].items()
                  if name.startswith("ops/flash_attention/")}
    finally:
        hub.reset()
    # the layer scan traces its one call site once; at this shape (64 keys
    # a row against 8 heads of 16) the op leaves its outputs to remat
    assert counts == {f"{impl}_calls": 1.0, "residuals_recomputed": 1.0}


# ---------------------------------------------------------------------------
# paged decode (interpret) — consolidating the kernel-parity shard
# ---------------------------------------------------------------------------


def test_paged_decode_kernel_matches_reference_interpret():
    from deepspeed_tpu.ops.pallas.paged_attention import (
        paged_decode_attention, paged_decode_reference)

    rng = np.random.RandomState(3)
    B, h, d, bs, nblocks = 3, 2, 64, 16, 12
    q = jnp.asarray(rng.randn(B, h, d), jnp.float32)
    k_pool = jnp.asarray(rng.randn(nblocks, bs, h, d), jnp.float32)
    v_pool = jnp.asarray(rng.randn(nblocks, bs, h, d), jnp.float32)
    tables = jnp.asarray(
        rng.permutation(nblocks)[:B * 3].reshape(B, 3), jnp.int32)
    lengths = jnp.asarray([41, 16, 33], jnp.int32)
    got = paged_decode_attention(q, k_pool, v_pool, tables, lengths,
                                 interpret=True)
    ref = paged_decode_reference(q, k_pool, v_pool, tables, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
