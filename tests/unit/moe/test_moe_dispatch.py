"""Sparse dispatch plane: index-form parity, Pallas interpret bit-parity,
crossover resolution, RTS determinism, gating fixtures (ISSUE 19)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe import MoE, MOELayer, TopKGate, top_k_gating
from deepspeed_tpu.moe.layer import swiglu_expert_fn
from deepspeed_tpu.moe.sharded_moe import GateMeta, top_k_gating_indices
from deepspeed_tpu.ops.pallas import moe_dispatch as md
from deepspeed_tpu.parallel import MeshLayout
from deepspeed_tpu.utils import groups

slow = pytest.mark.slow  # jit-heavy; smoke tier runs -m "not slow"


def _routing(T=64, E=4, C=24, k=2, seed=0):
    """A capacity-stressed routing decision in both forms."""
    logits = jnp.asarray(np.random.RandomState(seed).randn(T, E),
                         jnp.float32)
    gi, _, _ = top_k_gating_indices(logits, k, C)
    src_idx, flat_idx = md.routing_to_indices(
        gi.expert_idx, gi.slot, gi.keep, E, C)
    combine, dispatch, _, _ = top_k_gating(logits, k, C)
    return gi, src_idx, flat_idx, combine, dispatch


# ---------------------------------------------------------------------------
# index form vs dense [T,E,C] einsum
# ---------------------------------------------------------------------------

@slow
def test_sparse_dispatch_matches_dense_einsum():
    T, E, C, H = 64, 4, 24, 16
    gi, src_idx, _, combine, dispatch = _routing(T, E, C)
    tokens = jnp.asarray(np.random.RandomState(1).randn(T, H), jnp.float32)
    dense = jnp.einsum("tec,th->ech", dispatch.astype(jnp.float32), tokens)
    sparse = md.dispatch_reference(tokens, src_idx)
    np.testing.assert_allclose(np.asarray(sparse), np.asarray(dense),
                               rtol=1e-6, atol=1e-6)


@slow
def test_sparse_combine_matches_dense_einsum():
    T, E, C, H = 64, 4, 24, 16
    gi, _, flat_idx, combine, _ = _routing(T, E, C)
    expert_out = jnp.asarray(
        np.random.RandomState(2).randn(E, C, H), jnp.float32)
    dense = jnp.einsum("tec,ech->th", combine, expert_out)
    sparse = md.combine_reference(expert_out, flat_idx, gi.gate.T)
    np.testing.assert_allclose(np.asarray(sparse), np.asarray(dense),
                               rtol=1e-5, atol=1e-6)


def test_pallas_interpret_bit_parity():
    """Interpret-mode kernels are BIT-identical to the jnp reference —
    the parity harness the acceptance criteria name.  NOT slow-marked
    (seconds on CPU): tier 1 must trace the kernel body, so that an API
    the installed jax removed cannot hide behind the marker again."""
    T, E, C, H = 64, 4, 24, 16
    gi, src_idx, flat_idx, _, _ = _routing(T, E, C)
    tokens = jnp.asarray(np.random.RandomState(3).randn(T, H), jnp.float32)
    ref_in = md.dispatch_reference(tokens, src_idx)
    pal_in = md.pallas_dispatch(tokens, src_idx, interpret=True)
    assert (np.asarray(pal_in) == np.asarray(ref_in)).all()
    expert_out = ref_in * 1.5
    ref_y = md.combine_reference(expert_out, flat_idx, gi.gate.T)
    pal_y = md.pallas_combine(expert_out, flat_idx, gi.gate.T,
                              interpret=True)
    # the weighted sum picks up 1-ulp FMA rounding differences; the
    # routing itself (which row lands where) must agree exactly
    np.testing.assert_allclose(np.asarray(pal_y), np.asarray(ref_y),
                               rtol=3e-7, atol=1e-7)
    assert ((np.asarray(pal_y) == 0) == (np.asarray(ref_y) == 0)).all()


@slow
def test_pallas_interpret_gradients_match_reference():
    T, E, C, H = 32, 4, 12, 8
    gi, src_idx, flat_idx, _, _ = _routing(T, E, C)
    tokens = jnp.asarray(np.random.RandomState(4).randn(T, H), jnp.float32)

    def loss(fn):
        def f(t):
            buf = fn(t, src_idx)
            y = md.combine_reference(buf * 2.0, flat_idx, gi.gate.T)
            return jnp.sum(y ** 2)
        return f

    g_ref = jax.grad(loss(md.dispatch_reference))(tokens)
    g_pal = jax.grad(loss(
        lambda t, s: md.pallas_dispatch(t, s, interpret=True)))(tokens)
    np.testing.assert_allclose(np.asarray(g_pal), np.asarray(g_ref),
                               rtol=1e-6, atol=1e-6)

    def loss_c(fn):
        def f(eo, g):
            return jnp.sum(fn(eo, flat_idx, g) ** 2)
        return f

    eo = jnp.asarray(np.random.RandomState(5).randn(E, C, H), jnp.float32)
    ga, gb = jax.grad(loss_c(md.combine_reference), (0, 1))(eo, gi.gate.T)
    pa, pb = jax.grad(loss_c(
        lambda e, f, g: md.pallas_combine(e, f, g, interpret=True)),
        (0, 1))(eo, gi.gate.T)
    np.testing.assert_allclose(np.asarray(pa), np.asarray(ga),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(pb), np.asarray(gb),
                               rtol=1e-6, atol=1e-6)


@slow
def test_moe_layer_sparse_dense_forward_and_grad_parity():
    """Full MOELayer: sparse rung == dense rung, values AND gradients."""
    groups.reset_mesh()
    E, H, I, T = 4, 16, 32, 64
    rng = np.random.RandomState(7)
    wg = jnp.asarray(rng.randn(H, E), jnp.float32) * 0.1
    ew = {"w_gate": jnp.asarray(rng.randn(E, H, I), jnp.float32) * 0.1,
          "w_up": jnp.asarray(rng.randn(E, H, I), jnp.float32) * 0.1,
          "w_down": jnp.asarray(rng.randn(E, I, H), jnp.float32) * 0.1}
    x = jnp.asarray(rng.randn(2, T // 2, H), jnp.float32)

    def run(impl):
        gate = TopKGate(num_experts=E, k=2, capacity_factor=1.25,
                        min_capacity=4)
        layer = MOELayer(gate, swiglu_expert_fn, dispatch_impl=impl)

        def loss(wg, ew, x):
            y, l_aux, _ = layer(wg, ew, x)
            return jnp.sum(y ** 2) + l_aux

        val, grads = jax.value_and_grad(loss, (0, 1))(wg, ew, x)
        return val, grads

    vd, gd = run("dense")
    vs, gs = run("sparse")
    np.testing.assert_allclose(float(vs), float(vd), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(gs),
                    jax.tree_util.tree_leaves(gd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# crossover resolution
# ---------------------------------------------------------------------------

@slow
def test_choose_dispatch_impl_crossover():
    # small T·E·C: auto keeps the fused dense einsum
    assert md.choose_dispatch_impl("auto", 64, 4, 16) == "dense"
    # big volume off-TPU: jnp sparse rung
    big = md.choose_dispatch_impl("auto", 8192, 8, 2048)
    assert big == ("pallas" if jax.default_backend() == "tpu" else "sparse")
    # sharded meshes never get pallas_call (GSPMD owns the all-to-all)
    assert md.choose_dispatch_impl("auto", 8192, 8, 2048,
                                   sharded=True) == "sparse"
    assert md.choose_dispatch_impl("pallas", 64, 4, 16,
                                   sharded=True) == "sparse"
    # explicit picks are honored
    assert md.choose_dispatch_impl("dense", 8192, 8, 2048) == "dense"
    assert md.choose_dispatch_impl("sparse", 64, 4, 16) == "sparse"
    with pytest.raises(ValueError, match="unknown moe dispatch impl"):
        md.choose_dispatch_impl("tutel", 64, 4, 16)


@slow
def test_moe_layer_records_resolved_impl():
    groups.reset_mesh()
    gate = TopKGate(num_experts=4, k=1, capacity_factor=4.0, min_capacity=4)
    layer = MOELayer(gate, lambda p, x: x, dispatch_impl="auto")
    x = jnp.asarray(np.random.RandomState(0).randn(2, 8, 16), jnp.float32)
    wg = jnp.asarray(np.random.RandomState(1).randn(16, 4), jnp.float32)
    layer(wg, None, x)
    assert layer.last_impl == "dense"  # 16·4·16 is under the crossover


@slow
def test_dispatch_scratch_bytes_positive_and_monotone():
    a = md.dispatch_scratch_bytes(4, 16, 128)
    b = md.dispatch_scratch_bytes(8, 16, 128)
    assert 0 < a < b


# ---------------------------------------------------------------------------
# RTS + tutel satellites
# ---------------------------------------------------------------------------

@slow
def test_rts_deterministic_under_fixed_rng():
    logits = jnp.asarray(np.random.RandomState(9).randn(64, 4), jnp.float32)
    key = jax.random.PRNGKey(42)
    _, d1, _, _ = top_k_gating(logits, 1, 4, rts_rng=key)
    _, d2, _, _ = top_k_gating(logits, 1, 4, rts_rng=key)
    assert (np.asarray(d1) == np.asarray(d2)).all()


@slow
def test_rts_varies_across_seeds():
    logits = jnp.asarray(np.random.RandomState(9).randn(64, 4), jnp.float32)
    d = [np.asarray(top_k_gating(logits, 1, 4,
                                 rts_rng=jax.random.PRNGKey(s))[1])
         for s in range(6)]
    # tight capacity: the random priority order must change who survives
    assert any((a != d[0]).any() for a in d[1:])


@slow
def test_rts_changes_which_tokens_drop_not_how_many():
    logits = jnp.asarray(np.random.RandomState(9).randn(64, 4), jnp.float32)
    _, d_fifo, _, m_fifo = top_k_gating(logits, 1, 4)
    _, d_rts, _, m_rts = top_k_gating(logits, 1, 4,
                                      rts_rng=jax.random.PRNGKey(3))
    # overflow volume is a property of the routing, not the priority order
    np.testing.assert_allclose(float(m_rts["overflow_frac"]),
                               float(m_fifo["overflow_frac"]), atol=1e-6)
    assert np.asarray(d_rts).sum() == np.asarray(d_fifo).sum()


@slow
def test_use_tutel_raises_with_guidance():
    with pytest.raises(ValueError, match="Pallas"):
        MoE(hidden_size=16, num_experts=4, use_tutel=True)


# ---------------------------------------------------------------------------
# gating fixtures (satellite c) — hand-computed expectations
# ---------------------------------------------------------------------------

@slow
def test_gating_meta_matches_hand_computed_fixture():
    # tokens 0,1,2 -> expert 0; token 3 -> expert 1; capacity 2 drops
    # token 2 (arrival order)
    logits = jnp.asarray([[2.0, 0.0], [2.0, 0.0], [2.0, 0.0], [0.0, 2.0]],
                         jnp.float32)
    _, dispatch, _, meta = top_k_gating(logits, 1, 2)
    np.testing.assert_allclose(np.asarray(meta["load"]), [0.75, 0.25])
    np.testing.assert_allclose(np.asarray(meta["exp_counts"]), [3.0, 1.0])
    np.testing.assert_allclose(float(meta["overflow_frac"]), 0.25)
    np.testing.assert_allclose(float(meta["drop_rate"]), 0.25)
    sm = np.exp([2.0, 0.0]) / np.exp([2.0, 0.0]).sum()
    me = (3 * sm + sm[::-1]) / 4
    want_entropy = -np.sum(me * np.log(me))
    np.testing.assert_allclose(float(meta["entropy"]), want_entropy,
                               rtol=1e-5)
    # token 2's slot overflowed: its dispatch row is empty
    assert np.asarray(dispatch)[2].sum() == 0
    assert np.asarray(dispatch)[0].sum() == 1


@slow
def test_top2_renorm_when_second_choice_dropped_fixture():
    # opposite 1st choices, so both fit at capacity 1 — but each token's
    # 2nd choice queues behind the other's 1st and overflows.  Reference
    # order filters BEFORE renormalizing: survivors carry full weight 1.0
    logits = jnp.asarray([[3.0, 1.0], [1.0, 3.0]], jnp.float32)
    combine, dispatch, _, _ = top_k_gating(logits, 2, 1)
    d = np.asarray(dispatch.sum(axis=(1, 2)))
    np.testing.assert_array_equal(d, [1, 1])  # exactly the 1st choices
    w = np.asarray(combine.sum(axis=(1, 2)))
    np.testing.assert_allclose(w, 1.0, atol=1e-6)
    # ample capacity: no drops, per-route split is the softmax ratio
    sm = np.exp([3.0, 1.0]) / np.exp([3.0, 1.0]).sum()
    combine2, _, _, _ = top_k_gating(logits, 2, 2)
    np.testing.assert_allclose(np.asarray(combine2[0].sum(axis=1)), sm,
                               rtol=1e-5)


@slow
def test_gate_meta_array_shim():
    logits = jnp.asarray(np.random.RandomState(0).randn(16, 4), jnp.float32)
    _, _, _, meta = top_k_gating(logits, 1, 8)
    assert isinstance(meta, GateMeta)
    np.testing.assert_allclose(np.asarray(meta),
                               np.asarray(meta["exp_counts"]))
    assert np.asarray(meta, dtype=np.int32).dtype == np.int32


@slow
def test_moe_call_returns_full_meta():
    groups.reset_mesh()
    moe = MoE(hidden_size=16, num_experts=4, k=2, capacity_factor=4.0,
              use_rts=False)
    params = moe.init_params(jax.random.PRNGKey(0), intermediate_size=32)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 8, 16), jnp.float32)
    _, _, meta = moe(params, x)
    for key in ("exp_counts", "load", "entropy", "overflow_frac",
                "drop_rate", "l_aux"):
        assert key in meta
    # back-compat: third slot still coerces to exp_counts
    assert np.asarray(meta).sum() == 2 * 8


# ---------------------------------------------------------------------------
# capacity auto-pad round-trip on the real 8-device mesh (satellite a/c)
# ---------------------------------------------------------------------------

@slow
def test_capacity_auto_pads_to_expert_axis():
    groups.reset_mesh()
    mesh = groups.initialize_mesh(MeshLayout.infer(8, ep=4, dp=2))
    try:
        gate = TopKGate(num_experts=4, k=2, capacity_factor=1.0,
                        min_capacity=1, mesh=mesh)
        # raw formula: ceil(2*10*1.0/4) = 5 -> padded to 8 (next mult of 4)
        assert gate.capacity(10) == 8
        raw = TopKGate(num_experts=4, k=2, capacity_factor=1.0,
                       min_capacity=1, mesh=mesh, pad_to_ep=False)
        assert raw.capacity(10) == 5
        # already aligned: no change
        assert gate.capacity(16) == 8

        # round-trip: padded capacity keeps the expert-buffer constraint
        # shardable, so no ep_constraint_dropped counts are emitted
        from deepspeed_tpu.telemetry import get_telemetry

        reg = get_telemetry().registry
        before = reg.snapshot()["counters"].get(
            "moe/ep_constraint_dropped", {}).get("value", 0.0)
        layer = MOELayer(gate, swiglu_expert_fn, mesh=mesh,
                         dispatch_impl="sparse")
        rng = np.random.RandomState(0)
        wg = jnp.asarray(rng.randn(16, 4), jnp.float32)
        ew = {"w_gate": jnp.asarray(rng.randn(4, 16, 32), jnp.float32),
              "w_up": jnp.asarray(rng.randn(4, 16, 32), jnp.float32),
              "w_down": jnp.asarray(rng.randn(4, 32, 16), jnp.float32)}
        x = jnp.asarray(rng.randn(1, 10, 16), jnp.float32)
        y, _, _ = layer(wg, ew, x)
        assert y.shape == x.shape
        after = reg.snapshot()["counters"].get(
            "moe/ep_constraint_dropped", {}).get("value", 0.0)
        assert after == before  # expert/capacity dims stayed divisible
    finally:
        groups.reset_mesh()
