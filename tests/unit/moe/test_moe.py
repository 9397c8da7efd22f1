"""MoE: gating invariants, layer numerics, Mixtral EP training."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe import MoE, MOELayer, TopKGate, top_k_gating
from deepspeed_tpu.parallel import MeshLayout
from deepspeed_tpu.utils import groups


def test_top1_gating_invariants():
    rng = np.random.RandomState(0)
    T, E, C = 64, 4, 32
    logits = jnp.asarray(rng.randn(T, E), jnp.float32)
    combine, dispatch, l_aux, meta = top_k_gating(logits, 1, C)
    assert combine.shape == (T, E, C) and dispatch.shape == (T, E, C)
    # each token goes to at most one (expert, slot); weight in (0, 1]
    per_token = dispatch.sum(axis=(1, 2))
    assert (np.asarray(per_token) <= 1).all()
    # no slot is double-booked
    per_slot = dispatch.sum(axis=0)
    assert (np.asarray(per_slot) <= 1).all()
    # dispatched tokens carry their full (renormalized=1.0 for k=1) gate mass
    w = np.asarray(combine.sum(axis=(1, 2)))
    d = np.asarray(per_token)
    np.testing.assert_allclose(w[d == 1], 1.0, atol=1e-6)
    assert float(l_aux) > 0


def test_top2_gating_capacity_drops():
    rng = np.random.RandomState(1)
    T, E = 32, 4
    logits = jnp.asarray(rng.randn(T, E), jnp.float32)
    tight = 4
    combine, dispatch, _, meta = top_k_gating(logits, 2, tight)
    per_slot = np.asarray(dispatch.sum(axis=0))
    assert (per_slot <= 1).all()
    assert dispatch.sum() <= E * tight  # capacity respected
    assert float(meta["drop_rate"]) > 0  # tight capacity must drop


def test_top2_combine_weights_renormalized():
    rng = np.random.RandomState(2)
    logits = jnp.asarray(rng.randn(16, 4), jnp.float32)
    combine, dispatch, _, _ = top_k_gating(logits, 2, 16)  # ample capacity
    # with no drops every token's combine weights sum to 1
    np.testing.assert_allclose(np.asarray(combine.sum(axis=(1, 2))), 1.0,
                               atol=1e-5)


def test_moe_layer_identity_expert_roundtrip():
    """With identity experts + ample capacity, MOELayer ≈ identity."""
    mesh = groups.initialize_mesh(MeshLayout.infer(8, ep=4, dp=2))
    T, H, E = 8, 16, 4
    gate = TopKGate(num_experts=E, k=1, capacity_factor=E * 1.0,
                    min_capacity=T)
    layer = MOELayer(gate, lambda p, x: x, mesh=mesh)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 4, H), jnp.float32)
    wg = jnp.asarray(rng.randn(H, E), jnp.float32)
    y, l_aux, meta = jax.jit(
        lambda wg, x: layer(wg, None, x))(wg, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), rtol=1e-5,
                               atol=1e-5)


def test_moe_wrapper_api():
    groups.initialize_mesh(MeshLayout.infer(8, ep=4, dp=2))
    moe = MoE(hidden_size=16, num_experts=4, ep_size=4, k=2,
              capacity_factor=4.0)
    params = moe.init_params(jax.random.PRNGKey(0), intermediate_size=32)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 8, 16), jnp.float32)
    y, l_aux, exp_counts = moe(params, x)
    assert y.shape == x.shape
    assert np.asarray(exp_counts).sum() == 2 * 8
    with pytest.raises(ValueError):
        MoE(hidden_size=16, num_experts=6, ep_size=4)


def test_mixtral_ep_training_matches_single_device():
    import deepspeed_tpu
    from deepspeed_tpu.models import MixtralConfig, MixtralModel

    cfg = MixtralConfig.tiny(num_layers=2, dtype=jnp.float32)
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(8, 32)))

    def run(mesh):
        model = MixtralModel(cfg, mesh=mesh)
        params = model.init_params(jax.random.PRNGKey(0))
        ds = {"train_micro_batch_size_per_gpu": 8,
              "gradient_accumulation_steps": 1,
              "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
              "zero_optimization": {"stage": 3}}
        engine, *_ = deepspeed_tpu.initialize(
            model=model, model_parameters=params, config=ds, mesh=mesh)
        return [float(engine.train_step({"input_ids": ids})["loss"])
                for _ in range(3)]

    sharded = run(groups.initialize_mesh(
        MeshLayout.infer(8, ep=2, dp=2, tp=2)))
    groups.reset_mesh()
    single = run(groups.initialize_mesh(MeshLayout.infer(1, dp=1)))
    np.testing.assert_allclose(sharded, single, rtol=3e-4, atol=3e-4)
    assert sharded[-1] < sharded[0]


def test_config_driven_expert_parallel_trains_like_ep1():
    """``moe.expert_parallel_size`` in the config alone (no mesh passed)
    sizes the expert axis, shards every expert-stacked parameter to
    exactly 1/ep of its bytes a device, publishes the gate's drop rate,
    and trains to the losses of ep=1."""
    import deepspeed_tpu
    from deepspeed_tpu.models import MixtralConfig, MixtralModel
    from deepspeed_tpu.telemetry import get_telemetry

    cfg = MixtralConfig.tiny(num_layers=2, max_seq_len=128)
    hub = get_telemetry()
    hub_was_enabled = hub.enabled

    def run(ep):
        groups.reset_mesh()
        ds = {"train_micro_batch_size_per_gpu": 1,
              "gradient_accumulation_steps": 1,
              "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
              "zero_optimization": {"stage": 1},
              "moe": {"expert_parallel_size": ep,
                      "dispatch_impl": "sparse"},
              "steps_per_print": 0,
              "telemetry": {"enabled": True, "jsonl": False,
                            "numerics": {"every": 1}}}
        engine, *_ = deepspeed_tpu.initialize(
            model=MixtralModel(cfg), config=ds)
        rng = np.random.default_rng(11)
        losses = [float(engine.train_step({"input_ids": rng.integers(
            1, cfg.vocab_size, size=(engine.train_batch_size,
                                     cfg.max_seq_len),
            dtype=np.int32)})["loss"]) for _ in range(6)]
        fracs = {name: np.prod(w.sharding.shard_shape(w.shape))
                 / np.prod(w.shape)
                 for name, w in engine.state.params["layers"]["moe"].items()
                 if name in ("w_gate", "w_up", "w_down")}
        gauges = hub.registry.snapshot().get("gauges", {})
        return dict(engine.mesh.shape), losses, fracs, gauges

    try:
        mesh, losses, fracs, gauges = run(4)
        ref_mesh, ref_losses, ref_fracs, _ = run(1)
    finally:
        groups.reset_mesh()
        if not hub_was_enabled:
            hub.configure(enabled=False)
    assert mesh["expert"] == 4 and mesh["data"] == 2, mesh
    assert ref_mesh["expert"] == 1 and ref_mesh["data"] == 8, ref_mesh
    # the expert axis carries the expert dim; ZeRO-1 leaves params
    # unsharded over data, so a device holds exactly 1/ep of each
    assert fracs and all(f == 1 / 4 for f in fracs.values()), fracs
    assert all(f == 1.0 for f in ref_fracs.values()), ref_fracs
    assert 0.0 <= float(gauges["moe/drop_rate"]["value"]) < 1.0
    np.testing.assert_allclose(losses, ref_losses, rtol=3e-3)


def test_moe_residual_path():
    groups.initialize_mesh(MeshLayout.infer(8, ep=4, dp=2))
    moe = MoE(hidden_size=16, num_experts=4, ep_size=4, k=1,
              capacity_factor=4.0, use_residual=True)
    params = moe.init_params(jax.random.PRNGKey(0), intermediate_size=32)
    assert "residual_mlp" in params and "coefficient" in params
    x = jnp.asarray(np.random.RandomState(0).randn(2, 8, 16), jnp.float32)
    y, l_aux, _ = moe(params, x)
    assert y.shape == x.shape and jnp.isfinite(y).all()
    # specs cover every leaf
    assert set(moe.param_specs()) == set(params)


def test_top2_drop_keeps_full_weight_on_survivor():
    """Reference order: capacity-dropped 2nd choice -> 1st keeps weight 1."""
    rng = np.random.RandomState(5)
    T, E = 16, 2
    logits = jnp.asarray(rng.randn(T, E), jnp.float32)
    # capacity 1: almost every 2nd choice drops
    combine, dispatch, _, _ = top_k_gating(logits, 2, 1)
    w = np.asarray(combine.sum(axis=(1, 2)))
    d = np.asarray(dispatch.sum(axis=(1, 2)))
    # tokens with exactly one surviving route carry full weight 1.0
    np.testing.assert_allclose(w[d == 1], 1.0, atol=1e-5)
