"""ZeRO++ paths: qgZ int8 gradient reduction + hpZ secondary partition."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.models import LlamaConfig, LlamaModel
from deepspeed_tpu.parallel import MeshLayout
from deepspeed_tpu.runtime.zero import qgz
from deepspeed_tpu.utils import groups
from deepspeed_tpu.utils.jax_compat import shard_map as _shard_map


def test_quantized_allreduce_close_to_exact(mesh8):
    rng = np.random.RandomState(0)
    world = 8
    g = jnp.asarray(rng.randn(world, 31, 9), jnp.float32)  # odd sizes → pad

    def f(g_local):
        return qgz.quantized_allreduce(g_local[0],
                                       ("expert", "data"))[None]

    out = jax.jit(_shard_map(
        f, mesh=mesh8, in_specs=(P(("expert", "data")),),
        out_specs=P(("expert", "data")), check_vma=False))(g)
    exact = np.asarray(g).mean(axis=0)
    got = np.asarray(out[0])
    # int8 with per-256 group scales: ~1% relative error budget
    err = np.abs(got - exact).max() / (np.abs(exact).max() + 1e-9)
    assert err < 0.02, err
    for w in range(1, world):
        np.testing.assert_array_equal(np.asarray(out[w]), got)


def test_wire_bytes_reduction():
    params = {"w": np.zeros((1024, 512))}
    q, f = qgz.wire_bytes(params)
    assert f == 8 * 1024 * 512
    assert f / q > 3.5  # ~4x minus scale overhead


def make_engine(mesh, zero_extra=None, seed=0):
    cfg = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32)
    model = LlamaModel(cfg, mesh=mesh)
    params = model.init_params(jax.random.PRNGKey(seed))
    zero = {"stage": 2, "stage3_param_persistence_threshold": 0}
    zero.update(zero_extra or {})
    ds = {"train_micro_batch_size_per_gpu": 8,
          "gradient_accumulation_steps": 1,
          "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
          "zero_optimization": zero}
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=ds, mesh=mesh)
    return engine


def test_qgz_training_matches_uncompressed(mesh8):
    ids = np.random.RandomState(0).randint(0, 512, size=(16, 32))
    b = {"input_ids": jnp.asarray(ids)}

    qeng = make_engine(mesh8, {"zero_quantized_gradients": True})
    assert qeng.qgz_enabled
    losses_q = [float(qeng.train_step(b)["loss"]) for _ in range(6)]

    groups.reset_mesh()
    mesh = groups.initialize_mesh(MeshLayout.infer(8, dp=8))
    base = make_engine(mesh)
    losses_b = [float(base.train_step(b)["loss"]) for _ in range(6)]

    assert losses_q[-1] < losses_q[0]
    # int8 grads track the fp32 trajectory closely
    np.testing.assert_allclose(losses_q, losses_b, rtol=0.05)


def test_quantized_reduce_scatter_close_to_exact(mesh8):
    """Stage-3 hop: each worker ends with ITS slice of the mean grad; wire
    is int8 (s8 all-to-all visible in HLO)."""
    rng = np.random.RandomState(0)
    world = 8
    g = jnp.asarray(rng.randn(world, 64, 24), jnp.float32)

    def f(g_local):
        # each worker reduces over dim 1 and keeps its own 64/8-row chunk
        return qgz.quantized_reduce_scatter(
            g_local[0], ("expert", "data"), 0)[None]

    fn = jax.jit(_shard_map(
        f, mesh=mesh8, in_specs=(P(("expert", "data")),),
        out_specs=P(("expert", "data")),
        check_vma=False))
    out = fn(g)                          # [8, 8, 24]: row w = worker w's chunk
    exact = np.asarray(g).mean(axis=0)   # [64, 24]
    got = np.asarray(out).reshape(64, 24)
    err = np.abs(got - exact).max() / (np.abs(exact).max() + 1e-9)
    assert err < 0.02, err
    hlo = fn.lower(g).compile().as_text()
    assert "s8" in hlo and "all-to-all" in hlo


def test_qgz_stage3_training_matches_uncompressed(mesh8):
    """Round 3: qgZ composes with ZeRO-3 — params enter the grad program
    sharded, grads leave via int8 reduce-scatter in the stage-3 layout."""
    ids = np.random.RandomState(0).randint(0, 512, size=(16, 32))
    b = {"input_ids": jnp.asarray(ids)}

    qeng = make_engine(mesh8, {"stage": 3,
                               "zero_quantized_gradients": True})
    assert qeng.qgz_enabled and qeng.policy.stage == 3
    losses_q = [float(qeng.train_step(b)["loss"]) for _ in range(6)]

    groups.reset_mesh()
    mesh = groups.initialize_mesh(MeshLayout.infer(8, dp=8))
    base = make_engine(mesh, {"stage": 3})
    losses_b = [float(base.train_step(b)["loss"]) for _ in range(6)]

    assert losses_q[-1] < losses_q[0]
    np.testing.assert_allclose(losses_q, losses_b, rtol=0.05)


def test_hpz_secondary_partition():
    """hpZ: params shard over the inner 'data' axis only (ICI-local
    gathers); optimizer state keeps the full-DP partition; numerics match
    plain stage 3."""
    groups.reset_mesh()
    mesh = groups.initialize_mesh(MeshLayout.infer(8, ep=2, dp=4))
    hp = make_engine(mesh, {"stage": 3, "zero_hpz_partition_size": 4})

    def axes_of(leaf):
        spec = leaf.sharding.spec
        used = set()
        for e in spec:
            if e is None:
                continue
            used.update(e if isinstance(e, tuple) else (e,))
        return used

    big_params = [p for p in jax.tree.leaves(hp.state.params)
                  if p.size >= 4096]
    assert big_params
    for p in big_params:
        assert "expert" not in axes_of(p), p.sharding
        assert "data" in axes_of(p), p.sharding
    big_opt = [s for s in jax.tree.leaves(hp.state.opt_state)
               if hasattr(s, "size") and s.size >= 4096]
    assert any("expert" in axes_of(s) for s in big_opt)

    ids = np.random.RandomState(0).randint(0, 512, size=(16, 32))
    b = {"input_ids": jnp.asarray(ids)}
    losses_hp = [float(hp.train_step(b)["loss"]) for _ in range(3)]

    groups.reset_mesh()
    mesh = groups.initialize_mesh(MeshLayout.infer(8, ep=2, dp=4))
    base = make_engine(mesh, {"stage": 3})
    losses_b = [float(base.train_step(b)["loss"]) for _ in range(3)]
    np.testing.assert_allclose(losses_hp, losses_b, rtol=2e-4, atol=2e-4)


def test_hpz_size_must_match_inner_axis():
    groups.reset_mesh()
    mesh = groups.initialize_mesh(MeshLayout.infer(8, ep=2, dp=4))
    with pytest.raises(ValueError):
        make_engine(mesh, {"stage": 3, "zero_hpz_partition_size": 3})


# ---------------------------------------------------------------------------
# qwZ — quantized-weight all-gather
# ---------------------------------------------------------------------------

def test_qwz_quantization_error_bounded():
    from deepspeed_tpu.runtime.zero.qwz import GROUP, make_qwz

    mesh = groups.initialize_mesh(MeshLayout.infer(8, dp=8))
    rng = np.random.RandomState(1)
    p = jnp.asarray(rng.randn(64, 512), jnp.float32)  # 512 % 256 == 0
    out = jax.jit(make_qwz(mesh))(p)
    # per-group bound: amax/127 over each 256-wide group
    g = np.asarray(p).reshape(64, 512 // GROUP, GROUP)
    bound = np.abs(g).max(-1, keepdims=True) / 127.0 + 1e-7
    err = np.abs(np.asarray(out).reshape(g.shape) - g)
    assert np.all(err <= bound * 0.5 + 1e-6)


def test_qwz_straight_through_gradient():
    from deepspeed_tpu.runtime.zero.qwz import make_qwz

    mesh = groups.initialize_mesh(MeshLayout.infer(8, dp=8))
    p = jnp.asarray(np.random.RandomState(2).randn(8, 256), jnp.float32)
    qwz = make_qwz(mesh)
    g = jax.grad(lambda x: jnp.sum(qwz(x) ** 2))(p)
    # STE: cotangent of sum(q(x)^2) is 2*q(x), passed through unchanged
    np.testing.assert_allclose(np.asarray(g), 2 * np.asarray(qwz(p)),
                               rtol=1e-5, atol=1e-5)


def test_qwz_stage3_training_close_to_exact(mesh8):
    """ZeRO-3 + qwZ trains within quantization tolerance of exact ZeRO-3."""
    ids = np.random.RandomState(3).randint(0, 512, size=(8, 32))
    batch = {"input_ids": jnp.asarray(ids)}

    def losses(extra):
        groups.reset_mesh()
        mesh = groups.initialize_mesh(MeshLayout.infer(8, dp=8))
        engine = make_engine(mesh, {"stage": 3, **extra})
        return [float(engine.train_step(batch)["loss"]) for _ in range(6)]

    exact = losses({})
    qw = losses({"zero_quantized_weights": True})
    assert qw[-1] < qw[0]  # converges
    for a, b in zip(exact, qw):
        assert abs(a - b) / (abs(a) + 1e-9) < 0.05, (exact, qw)


def test_qwz_allgather_rides_int8(mesh8):
    """The compiled stage-3 program gathers s8, not f32 — the whole point."""
    groups.reset_mesh()
    mesh = groups.initialize_mesh(MeshLayout.infer(8, dp=8))
    engine = make_engine(mesh, {"stage": 3, "zero_quantized_weights": True})
    ids = jnp.asarray(np.random.RandomState(4).randint(0, 512, size=(8, 32)))
    if engine._train_step_fn is None:
        engine.compile()
    hlo = engine._train_step_fn.lower(
        engine.state, {"input_ids": ids}).compile().as_text()
    gathers = [ln for ln in hlo.splitlines() if "all-gather" in ln]
    assert any("s8" in ln for ln in gathers), gathers[:5]


def test_qwz_preserves_tp_sharding(mesh8):
    """qwZ must not gather over the tensor axis: the int8 constraint keeps
    the model's TP split (only DP axes replicate)."""
    groups.reset_mesh()
    mesh = groups.initialize_mesh(MeshLayout.infer(8, dp=4, tp=2))
    engine = make_engine(mesh, {"stage": 3, "zero_quantized_weights": True})
    ids = jnp.asarray(np.random.RandomState(5).randint(0, 512, size=(8, 32)))
    if engine._train_step_fn is None:
        engine.compile()
    hlo = engine._train_step_fn.lower(
        engine.state, {"input_ids": ids}).compile().as_text()
    # int8 gathers exist, and no f32 all-gather moves a full wq-sized
    # (H x heads x hd = 128x8x16) tensor — TP keeps its half
    gathers = [ln for ln in hlo.splitlines() if "all-gather" in ln]
    assert any("s8" in ln for ln in gathers)
    loss = float(engine.train_step({"input_ids": ids})["loss"])
    assert np.isfinite(loss)
