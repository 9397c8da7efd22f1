"""Fused one-pass Adam kernel vs the optax chain (ISSUE 12).

The parity contract the module documents: first step from a fresh state
is BIT-exact on both moments and ≤1 ulp on params vs the eager optax
chain; multi-step divergence is bounded by XLA FMA contraction (≤~1e-7
absolute).  Plus the grad-norm read kernel, the combined
unscale/clip/overflow multiplier, and the optax-state surgery that keeps
fused and non-fused checkpoints interchangeable.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

fo = importlib.import_module("deepspeed_tpu.ops.pallas.fused_optimizer")


def tree(seed=0):
    rng = np.random.RandomState(seed)
    params = {"w": jnp.asarray(rng.randn(300, 7), jnp.float32),
              "b": jnp.asarray(rng.randn(13), jnp.float32)}
    grads = jax.tree.map(
        lambda p: jnp.asarray(rng.randn(*p.shape), jnp.float32), params)
    return params, grads


def test_single_step_bit_parity_adam():
    params, grads = tree()
    tx = optax.adam(1e-3)
    st = tx.init(params)
    u, st1 = tx.update(grads, st, params)
    p_opt = optax.apply_updates(params, u)
    p_f, st_f = fo.apply_fused_adam(tx.init(params), params, grads, 1e-3,
                                    1.0, fo.FusedAdamConfig(),
                                    interpret=True)
    for k in params:
        np.testing.assert_array_equal(np.asarray(st1[0].mu[k]),
                                      np.asarray(st_f[0].mu[k]))
        np.testing.assert_array_equal(np.asarray(st1[0].nu[k]),
                                      np.asarray(st_f[0].nu[k]))
        # params: FMA contraction bounds the diff ABSOLUTELY (~1 ulp
        # of the contracted product's magnitude, not of the result)
        np.testing.assert_allclose(np.asarray(p_opt[k]),
                                   np.asarray(p_f[k]), rtol=0, atol=3e-7)
    assert int(st_f[0].count) == 1


def test_multi_step_parity_within_fma_contraction():
    params, grads = tree()
    tx = optax.adam(1e-3)
    st = tx.init(params)
    p_opt = params
    p_f, st_f = params, tx.init(params)
    for _ in range(3):
        u, st = tx.update(grads, st, p_opt)
        p_opt = optax.apply_updates(p_opt, u)
        p_f, st_f = fo.apply_fused_adam(st_f, p_f, grads, 1e-3, 1.0,
                                        fo.FusedAdamConfig(),
                                        interpret=True)
    for k in params:
        np.testing.assert_allclose(np.asarray(p_opt[k]),
                                   np.asarray(p_f[k]),
                                   rtol=0, atol=3e-7)
    assert int(st_f[0].count) == 3


def test_adamw_decoupled_decay_bit_parity():
    params, grads = tree(1)
    tx = optax.adamw(3e-4, weight_decay=0.01)
    u, _ = tx.update(grads, tx.init(params), params)
    p1 = optax.apply_updates(params, u)
    p2, _ = fo.apply_fused_adam(
        tx.init(params), params, grads, 3e-4, 1.0,
        fo.FusedAdamConfig(weight_decay=0.01, decoupled_wd=True),
        interpret=True)
    for k in params:
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p2[k]),
                                   rtol=0, atol=3e-7)


def test_additive_l2_decay_bit_parity():
    """optax chain(add_decayed_weights, adam) — decay enters the moments
    (how build_optimizer maps plain 'Adam' with weight_decay)."""
    params, grads = tree(2)
    tx = optax.chain(optax.add_decayed_weights(0.02), optax.adam(1e-3))
    u, _ = tx.update(grads, tx.init(params), params)
    p1 = optax.apply_updates(params, u)
    p2, _ = fo.apply_fused_adam(
        tx.init(params), params, grads, 1e-3, 1.0,
        fo.FusedAdamConfig(weight_decay=0.02, decoupled_wd=False),
        interpret=True)
    for k in params:
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p2[k]),
                                   rtol=0, atol=3e-7)


def test_sqsum_kernel_matches_global_grad_norm():
    from deepspeed_tpu.runtime.precision import global_grad_norm

    _, grads = tree(3)
    sq = fo.tree_sqsum(grads, interpret=True)
    np.testing.assert_allclose(float(jnp.sqrt(sq)),
                               float(global_grad_norm(grads)), rtol=1e-6)


def test_sqsum_flags_nonfinite_grads():
    """The engine's fused path derives overflow from the norm's
    finiteness — any single inf/nan grad element must poison it."""
    _, grads = tree(4)
    bad = {"w": grads["w"].at[0, 0].set(jnp.inf), "b": grads["b"]}
    assert not bool(jnp.isfinite(jnp.sqrt(fo.tree_sqsum(
        bad, interpret=True))))
    nan = {"w": grads["w"].at[0, 0].set(jnp.nan), "b": grads["b"]}
    assert not bool(jnp.isfinite(jnp.sqrt(fo.tree_sqsum(
        nan, interpret=True))))


def test_mult_folds_unscale_and_clip():
    """fused(g_scaled, mult=factor/scale) == optax chain fed the
    separately unscaled+clipped grads — the two per-element sweeps the
    fused path deletes."""
    params, grads = tree(5)
    scale, clip = 1024.0, 0.5
    scaled = jax.tree.map(lambda g: g * scale, grads)
    from deepspeed_tpu.runtime.precision import global_grad_norm

    gn = float(global_grad_norm(grads))
    factor = min(1.0, clip / (gn + 1e-6))
    tx = optax.adam(1e-3)
    p_a, _ = fo.apply_fused_adam(tx.init(params), params, scaled, 1e-3,
                                 factor / scale, fo.FusedAdamConfig(),
                                 interpret=True)
    # what the optax engine path feeds: the SCALED grads unscaled, then
    # clipped — two separate per-element sweeps
    pre = jax.tree.map(lambda s: (s / scale) * factor, scaled)
    u, _ = tx.update(pre, tx.init(params), params)
    p_b = optax.apply_updates(params, u)
    for k in params:
        np.testing.assert_allclose(np.asarray(p_a[k]), np.asarray(p_b[k]),
                                   rtol=1e-5, atol=3e-7)


def test_schedule_state_count_marches_with_fused_updates():
    """A schedule-built optax.adam carries ScaleByScheduleState — the
    fused path must keep its counter in lockstep so a mid-run fallback
    to the optax chain resumes at the right LR."""
    params, grads = tree(6)
    tx = optax.adam(lambda step: 1e-3)
    st = tx.init(params)
    p_f, st_f = fo.apply_fused_adam(st, params, grads, 1e-3, 1.0,
                                    fo.FusedAdamConfig(), interpret=True)
    assert int(st_f[0].count) == 1          # ScaleByAdamState
    assert int(st_f[1].count) == 1          # ScaleByScheduleState
    # layout unchanged: the optax chain accepts the fused state as-is
    u, st2 = tx.update(grads, st_f, p_f)
    assert int(st2[0].count) == 2 and int(st2[1].count) == 2


def test_find_adam_state_names_the_layout_on_mismatch():
    st = optax.sgd(1e-2).init({"w": jnp.zeros((3,))})
    with pytest.raises(ValueError, match="fused_adam"):
        fo.find_adam_state(st)


def test_padding_roundtrip_preserves_odd_shapes():
    """Leaves far from the (64, 128) tile — scalars, odd vectors — must
    round-trip the pad/unpad unchanged in shape and value."""
    params = {"s": jnp.float32(2.0).reshape(()),
              "v": jnp.asarray(np.arange(130, dtype=np.float32))}
    grads = jax.tree.map(jnp.ones_like, params)
    tx = optax.adam(1e-3)
    p_f, _ = fo.apply_fused_adam(tx.init(params), params, grads, 1e-3,
                                 1.0, fo.FusedAdamConfig(),
                                 interpret=True)
    u, _ = tx.update(grads, tx.init(params), params)
    p_o = optax.apply_updates(params, u)
    for k in params:
        assert p_f[k].shape == params[k].shape
        np.testing.assert_allclose(np.asarray(p_o[k]),
                                   np.asarray(p_f[k]), rtol=0, atol=3e-7)
