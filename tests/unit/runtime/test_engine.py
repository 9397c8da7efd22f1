"""Engine tests, modeled on the reference strategy (SURVEY §4): tiny models,
few steps, ZeRO variants asserted against the stage-0 baseline trajectory."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as dst
from deepspeed_tpu.parallel import MeshLayout
from deepspeed_tpu.utils import groups

HIDDEN = 16


def make_problem(seed=0):
    """Tiny 2-layer MLP regression; returns (loss_fn, params, data)."""
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=(HIDDEN, 1)).astype(np.float32)
    x = rng.normal(size=(64, HIDDEN)).astype(np.float32)
    y = x @ w_true + 0.01 * rng.normal(size=(64, 1)).astype(np.float32)

    params = {
        "w1": jnp.asarray(rng.normal(size=(HIDDEN, HIDDEN)).astype(np.float32) * 0.3),
        "b1": jnp.zeros((HIDDEN,), jnp.float32),
        "w2": jnp.asarray(rng.normal(size=(HIDDEN, 1)).astype(np.float32) * 0.3),
    }

    def loss_fn(p, batch):
        bx, by = batch
        h = jnp.tanh(bx @ p["w1"] + p["b1"])
        pred = h @ p["w2"]
        return jnp.mean((pred - by) ** 2)

    return loss_fn, params, (jnp.asarray(x), jnp.asarray(y))


def base_config(**over):
    cfg = {
        "train_micro_batch_size_per_gpu": 8,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "gradient_clipping": 1.0,
        "steps_per_print": 0,
    }
    cfg.update(over)
    return cfg


def train(engine, data, steps=10):
    losses = []
    for _ in range(steps):
        m = engine.train_step(data)
        losses.append(float(m["loss"]))
    return losses


@pytest.fixture(autouse=True)
def _mesh():
    groups.initialize_mesh(MeshLayout.infer(8, dp=8))
    yield


def test_stage0_loss_decreases():
    loss_fn, params, data = make_problem()
    engine, _, _, _ = dst.initialize(model=loss_fn, model_parameters=params,
                                     config=base_config())
    losses = train(engine, data, steps=15)
    assert losses[-1] < losses[0] * 0.5


@pytest.mark.parametrize("stage", [1, 2, 3])
def test_zero_stage_matches_stage0(stage):
    """ZeRO sharding must not change numerics — the reference's keystone
    equivalence test (tests/unit/runtime/zero/test_zero.py pattern)."""
    loss_fn, params, data = make_problem()
    e0, _, _, _ = dst.initialize(model=loss_fn, model_parameters=params,
                                 config=base_config())
    ref_losses = train(e0, data, steps=8)

    loss_fn, params, data = make_problem()
    ez, _, _, _ = dst.initialize(
        model=loss_fn, model_parameters=params,
        config=base_config(zero_optimization={"stage": stage,
                                              "stage3_param_persistence_threshold": 0}))
    z_losses = train(ez, data, steps=8)
    np.testing.assert_allclose(z_losses, ref_losses, rtol=2e-4, atol=1e-5)

    # stage 3: params must actually be sharded over the dp axes
    if stage == 3:
        spec = ez.state.params["w1"].sharding.spec
        assert any(s is not None for s in spec)


def test_grad_accumulation_equivalence():
    """gas=4 over the same global batch == gas=1 (fp32 exact-ish)."""
    loss_fn, params, data = make_problem()
    e1, _, _, _ = dst.initialize(model=loss_fn, model_parameters=params,
                                 config=base_config())
    l1 = train(e1, data, steps=5)

    loss_fn, params, data = make_problem()
    e4, _, _, _ = dst.initialize(
        model=loss_fn, model_parameters=params,
        config=base_config(gradient_accumulation_steps=4))
    l4 = train(e4, data, steps=5)
    np.testing.assert_allclose(l4, l1, rtol=1e-4, atol=1e-6)


def test_compat_forward_backward_step_matches_train_step():
    loss_fn, params, data = make_problem()
    cfg = base_config(gradient_accumulation_steps=2)
    ea, _, _, _ = dst.initialize(model=loss_fn, model_parameters=params,
                                 config=cfg)
    lb = train(ea, data, steps=4)

    loss_fn, params, data = make_problem()
    ec, _, _, _ = dst.initialize(model=loss_fn, model_parameters=params,
                                 config=cfg)
    x, y = data
    compat_losses = []
    for _ in range(4):
        for half in range(2):  # two microbatches of 32 = half the batch
            mb = (x[half * 32:(half + 1) * 32], y[half * 32:(half + 1) * 32])
            loss = ec(mb)
            ec.backward(loss)
            ec.step()
        compat_losses.append(float(ec.last_metrics["loss"]))
    np.testing.assert_allclose(compat_losses, lb, rtol=1e-4, atol=1e-6)
    assert ec.global_steps == 4
    assert ec.micro_steps == 8


def test_fp16_loss_scaler_overflow_skips_step():
    loss_fn, params, data = make_problem()
    cfg = base_config(fp16={"enabled": True, "initial_scale_power": 4,
                            "hysteresis": 1})
    engine, _, _, _ = dst.initialize(model=loss_fn, model_parameters=params,
                                     config=cfg)
    engine.train_step(data)
    scale0 = engine.get_loss_scale()
    params_before = jax.tree.map(np.asarray, engine.state.params)

    bad = (jnp.full_like(data[0], jnp.inf), data[1])
    engine.train_step(bad)
    assert engine.overflow
    assert engine.skipped_steps == 1
    assert engine.get_loss_scale() == scale0 / 2
    params_after = jax.tree.map(np.asarray, engine.state.params)
    for a, b in zip(jax.tree.leaves(params_before), jax.tree.leaves(params_after)):
        np.testing.assert_array_equal(a, b)  # skipped step → params untouched


def test_bf16_training():
    loss_fn, params, data = make_problem()
    cfg = base_config(bf16={"enabled": True})
    engine, _, _, _ = dst.initialize(model=loss_fn, model_parameters=params,
                                     config=cfg)
    losses = train(engine, data, steps=10)
    assert losses[-1] < losses[0]
    # master weights stay fp32
    assert engine.state.params["w1"].dtype == jnp.float32


def test_scheduler_and_metrics_surface():
    loss_fn, params, data = make_problem()
    cfg = base_config(scheduler={"type": "WarmupLR",
                                 "params": {"warmup_min_lr": 0.0,
                                            "warmup_max_lr": 1e-2,
                                            "warmup_num_steps": 10}})
    engine, opt, _, sched = dst.initialize(model=loss_fn,
                                           model_parameters=params, config=cfg)
    engine.train_step(data)
    assert engine.get_global_grad_norm() is not None
    lr0 = engine.get_lr()[0]
    for _ in range(5):
        engine.train_step(data)
    assert engine.get_lr()[0] > lr0  # warming up
    assert sched.get_last_lr()[0] == pytest.approx(engine.get_lr()[0])


def test_checkpoint_roundtrip(tmp_path):
    loss_fn, params, data = make_problem()
    engine, _, _, _ = dst.initialize(model=loss_fn, model_parameters=params,
                                     config=base_config())
    train(engine, data, steps=3)
    tag_dir = engine.save_checkpoint(str(tmp_path))
    assert "global_step3" in tag_dir
    ref_params = jax.tree.map(np.asarray, engine.state.params)
    ref_next = float(engine.train_step(data)["loss"])

    loss_fn2, params2, _ = make_problem(seed=123)
    e2, _, _, _ = dst.initialize(model=loss_fn2, model_parameters=params2,
                                 config=base_config())
    path, _ = e2.load_checkpoint(str(tmp_path))
    assert path is not None
    for a, b in zip(jax.tree.leaves(ref_params),
                    jax.tree.leaves(jax.tree.map(np.asarray, e2.state.params))):
        np.testing.assert_array_equal(a, b)
    assert e2.global_steps == 3
    # trajectory continues identically
    assert float(e2.train_step(data)["loss"]) == pytest.approx(ref_next, rel=1e-5)


def test_checkpoint_reshard_across_stages(tmp_path):
    """Save under ZeRO-3 (sharded), load under stage 0 (replicated) — the
    universal-checkpoint capability, natively via orbax reshard-on-load."""
    loss_fn, params, data = make_problem()
    e3, _, _, _ = dst.initialize(
        model=loss_fn, model_parameters=params,
        config=base_config(zero_optimization={
            "stage": 3, "stage3_param_persistence_threshold": 0}))
    train(e3, data, steps=2)
    e3.save_checkpoint(str(tmp_path))
    ref = jax.tree.map(np.asarray, e3.state.params)

    loss_fn2, params2, _ = make_problem(seed=9)
    e0, _, _, _ = dst.initialize(model=loss_fn2, model_parameters=params2,
                                 config=base_config())
    e0.load_checkpoint(str(tmp_path))
    for a, b in zip(jax.tree.leaves(ref),
                    jax.tree.leaves(jax.tree.map(np.asarray, e0.state.params))):
        np.testing.assert_array_equal(a, b)


def test_forced_partial_boundary_caches_program():
    """A forced partial accumulation boundary compiles its own program
    once per distinct microbatch count and reuses it afterwards — the
    recompile-per-occurrence cliff (round-3 weak 7) is gone."""
    loss_fn, params, data = make_problem()
    eng, _, _, _ = dst.initialize(
        model=loss_fn, model_parameters=params,
        config=base_config(gradient_accumulation_steps=4))
    micro = jax.tree.map(lambda x: x[:8], data)

    def partial_step(n):
        for _ in range(n):
            eng.backward(eng.forward(micro))
        eng.set_gradient_accumulation_boundary(True)
        eng.step()
        eng.set_gradient_accumulation_boundary(False)

    partial_step(2)
    assert 2 in eng._partial_step_fns
    first = eng._partial_step_fns[2][0]
    assert first is not None
    partial_step(2)
    assert eng._partial_step_fns[2][0] is first  # reused, not rebuilt
    # the full-GAS program is untouched by partial stepping
    assert eng.gradient_accumulation_steps == 4
