"""Llama model: forward/loss numerics and sharded training over the fake mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import LlamaConfig, LlamaModel
from deepspeed_tpu.parallel import MeshLayout
from deepspeed_tpu.utils import groups


def tiny(**kw):
    return LlamaConfig.tiny(num_layers=2, dtype=jnp.float32, **kw)


def make_batch(cfg, batch=4, seq=32, seed=0):
    rng = np.random.RandomState(seed)
    return {"input_ids": jnp.asarray(
        rng.randint(0, cfg.vocab_size, size=(batch, seq)))}


def test_forward_shapes_and_loss():
    cfg = tiny()
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    batch = make_batch(cfg)
    logits = model.forward(params, batch["input_ids"])
    assert logits.shape == (4, 32, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    loss = model.loss(params, batch)
    # random init → loss ≈ ln(V)
    assert abs(float(loss) - np.log(cfg.vocab_size)) < 1.0


def test_param_count_formula():
    cfg = tiny()
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    assert n == cfg.num_params()


def test_labels_with_ignore_index():
    cfg = tiny()
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    ids = make_batch(cfg)["input_ids"]
    labels = jnp.where(jnp.arange(32)[None, :] < 16, ids, -100)
    loss = model.loss(params, {"input_ids": ids, "labels": labels})
    assert jnp.isfinite(loss)


@pytest.mark.parametrize("layout_kw,stage", [
    (dict(dp=8), 3),                  # pure FSDP
    (dict(dp=2, tp=2, sp=2), 3),      # 3-way hybrid: ZeRO-3 × TP × Ulysses SP
    (dict(dp=4, tp=2), 1),            # ZeRO-1 × TP
])
def test_sharded_training_matches_single_device(layout_kw, stage):
    """Hybrid-sharded training (mesh) must track the unsharded trace."""
    import deepspeed_tpu

    cfg = tiny()
    batch = make_batch(cfg, batch=8, seq=32)

    def run(mesh, n_steps=3):
        model = LlamaModel(cfg, mesh=mesh)
        params = model.init_params(jax.random.PRNGKey(0))
        ds_cfg = {
            "train_micro_batch_size_per_gpu": 8,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": stage},
        }
        engine, *_ = deepspeed_tpu.initialize(
            model=model, model_parameters=params, config=ds_cfg, mesh=mesh)
        losses = [float(engine.train_step(batch)["loss"])
                  for _ in range(n_steps)]
        return losses

    layout = MeshLayout.infer(8, **layout_kw)
    mesh = groups.initialize_mesh(layout)
    sharded = run(mesh)
    groups.reset_mesh()

    single = groups.initialize_mesh(MeshLayout.infer(1, dp=1))
    baseline = run(single)
    np.testing.assert_allclose(sharded, baseline, rtol=2e-4, atol=2e-4)
    assert sharded[-1] < sharded[0]  # it actually learns


def test_pipeline_parallel_training_matches_single_device():
    """pp=2 × tp=2 × dp=2 dense Llama must track the unsharded trace (dense
    model: pipeline microbatching is numerically neutral)."""
    import deepspeed_tpu
    from deepspeed_tpu.utils import groups as g

    cfg = LlamaConfig.tiny(num_layers=4, dtype=jnp.float32)
    batch = make_batch(cfg, batch=8, seq=32)

    def run(mesh):
        model = LlamaModel(cfg, mesh=mesh)
        params = model.init_params(jax.random.PRNGKey(0))
        ds_cfg = {
            "train_micro_batch_size_per_gpu": 8,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1},
        }
        engine, *_ = deepspeed_tpu.initialize(
            model=model, model_parameters=params, config=ds_cfg, mesh=mesh)
        return [float(engine.train_step(batch)["loss"]) for _ in range(3)]

    sharded = run(g.initialize_mesh(MeshLayout.infer(8, pp=2, tp=2)))
    g.reset_mesh()
    single = run(g.initialize_mesh(MeshLayout.infer(1, dp=1)))
    np.testing.assert_allclose(sharded, single, rtol=2e-4, atol=2e-4)
