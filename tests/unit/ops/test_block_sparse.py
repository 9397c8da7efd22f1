"""Block-sparse Pallas kernel vs the dense masked reference.

The kernel (interpret mode here; on-chip via bench --selfcheck) must
reproduce ``sparse_attention``'s dense masked numerics for every layout
family, including per-head layouts and causal masking, while executing
only live k-blocks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas.block_sparse_attention import (
    _plan, block_sparse_attention)
from deepspeed_tpu.ops.sparse_attention import (BigBirdSparsityConfig,
                                                BSLongformerSparsityConfig,
                                                FixedSparsityConfig,
                                                sparse_attention)


def _qkv(B=2, S=256, h=4, d=64, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(B, S, h, d).astype(np.float32))
                 for _ in range(3))


CASES = [
    ("fixed", lambda h: FixedSparsityConfig(
        num_heads=h, block=16, num_local_blocks=4), False),
    ("fixed_causal", lambda h: FixedSparsityConfig(
        num_heads=h, block=16, num_local_blocks=4,
        attention="unidirectional"), True),
    ("longformer", lambda h: BSLongformerSparsityConfig(
        num_heads=h, block=16), False),
    ("bigbird_perhead", lambda h: BigBirdSparsityConfig(
        num_heads=h, block=16, different_layout_per_head=True), False),
]


@pytest.mark.parametrize("name,make,causal", CASES,
                         ids=[c[0] for c in CASES])
def test_kernel_matches_dense_masked(name, make, causal):
    q, k, v = _qkv()
    cfg = make(q.shape[2])
    want = sparse_attention(q, k, v, cfg, causal=causal, impl="dense")
    got = block_sparse_attention(q, k, v, cfg, causal=causal,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_kernel_skips_dead_blocks():
    """The plan's live-block count is what the kernel executes — assert
    the sparsity is real (far below dense) for a windowed layout."""
    S, bq = 2048, 128
    cfg = BSLongformerSparsityConfig(num_heads=1, block=16,
                                     num_sliding_window_blocks=3)
    layout = cfg.make_layout(S)[None]
    idx, counts, cells = _plan(layout, S, bq, bq, 16, causal=False)
    nk = S // bq
    # the global row is legitimately dense; every other q-block skips
    assert (counts < nk).mean() > 0.9
    total_live = int(counts.sum())
    assert total_live < 0.4 * (S // bq) * nk  # real sparsity, not a mask


def test_gradients_flow_through_kernel():
    """custom_vjp: training through the sparse op uses the dense-masked
    backward and matches its gradients."""
    q, k, v = _qkv(B=1, S=128, h=2, d=64)
    cfg = FixedSparsityConfig(num_heads=2, block=16, num_local_blocks=2)

    def loss_kernel(q, k, v):
        return jnp.sum(block_sparse_attention(q, k, v, cfg, causal=True,
                                              interpret=True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(sparse_attention(q, k, v, cfg, causal=True,
                                        impl="dense") ** 2)

    g1 = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_fully_masked_rows_zero():
    """A layout leaving a q-block with no live cells must produce zeros
    (the dense path's explicit zeroing)."""
    class EmptyTail(FixedSparsityConfig):
        def _head_layout(self, seq_len, head):
            lay = super()._head_layout(seq_len, head)
            lay[-4:, :] = 0  # last 4 cell-rows attend nothing
            return lay

    q, k, v = _qkv(B=1, S=256, h=2)
    cfg = EmptyTail(num_heads=2, block=16, num_local_blocks=2,
                    num_global_blocks=0)
    got = block_sparse_attention(q, k, v, cfg, interpret=True)
    want = sparse_attention(q, k, v, cfg, impl="dense")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert np.all(np.asarray(got)[:, -64:] == 0)


@pytest.mark.parametrize("name,make,causal", CASES,
                         ids=[c[0] for c in CASES])
def test_sparse_backward_tiles_matches_dense_all_layouts(name, make, causal):
    """_sparse_bwd_tiles (called directly — the auto-select heuristic
    routes dense-ish layouts to the dense vjp) == the dense masked vjp
    for every layout family (incl. per-head and causal)."""
    from deepspeed_tpu.ops.pallas.block_sparse_attention import (
        _norm_layout, _sparse_bwd_tiles)

    q, k, v = _qkv(B=1, S=256, h=4)
    cfg = make(4)
    layout = _norm_layout(cfg.make_layout(256), 4)

    def loss_dense(q, k, v):
        return jnp.sum(sparse_attention(
            q, k, v, cfg, causal=causal, impl="dense") ** 3)

    out = sparse_attention(q, k, v, cfg, causal=causal, impl="dense")
    do = 3 * out ** 2  # d/dx of sum(x^3)
    g1 = _sparse_bwd_tiles(q, k, v, do, layout, cfg.block, causal, 128, 128)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg=f"d{nm} ({name})")


def test_sparse_backward_selected_for_local_layouts():
    """End-to-end: a pure local-window layout (max_live << nk) routes
    through the sparse backward and matches the dense vjp."""
    from deepspeed_tpu.ops.pallas.block_sparse_attention import _plan

    S = 1024
    cfg = BSLongformerSparsityConfig(num_heads=2, block=16,
                                     num_sliding_window_blocks=3,
                                     global_block_indices=())
    layout = cfg.make_layout(S)[None]
    idx, _, _ = _plan(layout, S, 128, 128, 16, causal=False)
    assert idx.shape[2] * 2 <= S // 128  # heuristic picks the sparse path

    q, k, v = _qkv(B=1, S=S, h=2)

    def loss_kernel(q, k, v):
        return jnp.sum(block_sparse_attention(
            q, k, v, cfg, block_q=128, block_k=128, interpret=True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(sparse_attention(q, k, v, cfg, impl="dense") ** 2)

    g1 = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_sparse_backward_fully_masked_rows_zero_grad():
    """q rows with no live cells produce zero output AND zero dq."""
    class EmptyTail(FixedSparsityConfig):
        def _head_layout(self, seq_len, head):
            lay = super()._head_layout(seq_len, head)
            lay[-4:, :] = 0
            return lay

    q, k, v = _qkv(B=1, S=256, h=2)
    cfg = EmptyTail(num_heads=2, block=16, num_local_blocks=2,
                    num_global_blocks=0)

    def loss(q, k, v):
        return jnp.sum(block_sparse_attention(q, k, v, cfg,
                                              interpret=True) ** 2)

    dq = jax.grad(loss)(q, k, v)
    assert np.all(np.asarray(dq)[:, -64:] == 0)


def test_gather_forward_matches_dense_reference():
    """The PRODUCTION gather kernel (_bs_fwd_gather — scalar-prefetched
    index_map DMA of live blocks) in interpret mode matches the dense
    masked reference; CI must exercise the path real TPUs run, not just
    the resident interpret kernel."""
    import importlib

    bsa = importlib.import_module(
        "deepspeed_tpu.ops.pallas.block_sparse_attention")
    from deepspeed_tpu.ops.sparse_attention import BigBirdSparsityConfig

    rng = np.random.default_rng(0)
    B, S, h, d = 2, 512, 4, 64
    q = jnp.asarray(rng.normal(size=(B, S, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, h, d)), jnp.float32)
    cfg = BigBirdSparsityConfig(num_heads=h, block=64)
    layout = bsa._norm_layout(cfg.make_layout(S), h)
    key = (layout.tobytes(), layout.shape, layout.dtype.str)
    bsa._LAYOUTS[key] = layout
    for causal in (False, True):
        ref = bsa._dense_reference(q, k, v, layout, cfg.block, causal)
        got, _ = bsa._bs_fwd_gather(q, k, v, key, causal, 128, 128,
                                    cfg.block, True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_bucketed_backward_matches_dense_global_rows(causal):
    """The per-row-count bucketed backward handles layouts WITH dense
    global rows (the case the padded form had to punt to the dense vjp):
    gradients match the dense masked reference exactly, per head."""
    import importlib

    bsa = importlib.import_module(
        "deepspeed_tpu.ops.pallas.block_sparse_attention")
    from deepspeed_tpu.ops.sparse_attention import BigBirdSparsityConfig

    rng = np.random.default_rng(3)
    B, S, h, d = 2, 1024, 4, 32
    q = jnp.asarray(rng.normal(size=(B, S, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, h, d)), jnp.float32)
    do = jnp.asarray(rng.normal(size=(B, S, h, d)), jnp.float32)
    cfg = BigBirdSparsityConfig(num_heads=h, block=32, num_global_blocks=2,
                                num_random_blocks=1,
                                num_sliding_window_blocks=3)
    layout = bsa._norm_layout(cfg.make_layout(S), h)
    # this layout has global rows/cols: max_live*2 > nk (the old gate's
    # dense-fallback territory) but overall live fraction is sparse
    idx, counts, _ = bsa._plan(layout, S, 64, 64, cfg.block, causal)
    assert idx.shape[2] * 2 > (S // 64)  # old gate would punt to dense
    assert counts.sum() / counts.size / (S // 64) <= 0.5  # yet sparse

    got = bsa._sparse_bwd_bucketed(q, k, v, do, layout, cfg.block, causal,
                                   64, 64)

    def loss(q_, k_, v_):
        return jnp.sum(bsa._dense_reference(q_, k_, v_, layout, cfg.block,
                                            causal) * do)

    want = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-4)


def test_bucketed_backward_selected_for_global_row_layouts():
    """_bs_bwd routes global-row layouts through the bucketed backward
    (they previously fell back to the dense vjp)."""
    import importlib
    from unittest import mock

    bsa = importlib.import_module(
        "deepspeed_tpu.ops.pallas.block_sparse_attention")
    from deepspeed_tpu.ops.sparse_attention import BigBirdSparsityConfig

    rng = np.random.default_rng(0)
    B, S, h, d = 1, 2048, 2, 32
    q = jnp.asarray(rng.normal(size=(B, S, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, h, d)), jnp.float32)
    cfg = BigBirdSparsityConfig(num_heads=h, block=32, num_global_blocks=2,
                                num_random_blocks=1,
                                num_sliding_window_blocks=3)
    layout = bsa._norm_layout(cfg.make_layout(S), h)
    key = (layout.tobytes(), layout.shape, layout.dtype.str)
    bsa._LAYOUTS[key] = layout

    with mock.patch.object(bsa, "_sparse_bwd_bucketed",
                           wraps=bsa._sparse_bwd_bucketed) as spy:
        def loss(q_, k_, v_):
            return jnp.sum(bsa._bs_attention(q_, k_, v_, key, True, 64, 64,
                                             cfg.block, True) ** 2)

        jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        assert spy.called


@pytest.mark.parametrize("name,make,causal", CASES,
                         ids=[c[0] for c in CASES])
def test_pallas_flat_backward_matches_dense_all_layouts(name, make, causal):
    """The flat-tile Pallas backward (_sparse_bwd_pallas, interpret mode
    here; on-chip via bench --selfcheck) == the dense masked vjp for
    every layout family — the kernel realization of the bucketed jnp
    backward's O(live) property, fed by forward-saved softmax stats."""
    import importlib

    bsa = importlib.import_module(
        "deepspeed_tpu.ops.pallas.block_sparse_attention")

    q, k, v = _qkv(B=1, S=256, h=4)
    cfg = make(4)
    layout = bsa._norm_layout(cfg.make_layout(256), 4)
    key = (layout.tobytes(), layout.shape, layout.dtype.str)
    bsa._LAYOUTS[key] = layout

    out, res = bsa._bs_fwd(q, k, v, key, causal, 64, 64, cfg.block, True)
    _, _, _, o_saved, lse = res
    do = 3 * out ** 2
    g1 = bsa._sparse_bwd_pallas(q, k, v, o_saved, lse, do, layout,
                                cfg.block, causal, 64, 64, interpret=True)

    def loss_dense(q, k, v):
        return jnp.sum(sparse_attention(
            q, k, v, cfg, causal=causal, impl="dense") ** 3)

    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg=f"d{nm} ({name})")


def test_cell_exact_fast_path_matches_dense():
    """cb == kernel block (qc == kc == 1): the production default after
    block auto-snap — _keep_tile's causality-only branch, forward AND
    flat-kernel backward, against the dense anchor."""
    import importlib

    bsa = importlib.import_module(
        "deepspeed_tpu.ops.pallas.block_sparse_attention")

    q, k, v = _qkv(B=1, S=512, h=2, d=64)
    cfg = BigBirdSparsityConfig(num_heads=2, block=128)
    for causal in (False, True):
        layout = bsa._norm_layout(cfg.make_layout(512), 2)
        key = (layout.tobytes(), layout.shape, layout.dtype.str)
        bsa._LAYOUTS[key] = layout
        out, res = bsa._bs_fwd(q, k, v, key, causal, 128, 128, cfg.block,
                               True)
        want = sparse_attention(q, k, v, cfg, causal=causal, impl="dense")
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        _, _, _, o_saved, lse = res
        do = 3 * out ** 2
        g1 = bsa._sparse_bwd_pallas(q, k, v, o_saved, lse, do, layout,
                                    cfg.block, causal, 128, 128,
                                    interpret=True)

        def loss_dense(q, k, v):
            return jnp.sum(sparse_attention(
                q, k, v, cfg, causal=causal, impl="dense") ** 3)

        g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b, nm in zip(g1, g2, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-4,
                                       err_msg=f"d{nm} causal={causal}")
