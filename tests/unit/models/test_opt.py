"""OPT decoder: forward/loss, sharded training, v1 cached generate."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import OPTConfig, OPTModel
from deepspeed_tpu.parallel import MeshLayout
from deepspeed_tpu.utils import groups


def _cfg(**kw):
    d = dict(num_layers=2, dtype=jnp.float32)
    d.update(kw)
    return OPTConfig.tiny(**d)


def test_opt_forward_and_param_count():
    cfg = _cfg()
    model = OPTModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(2, 16)))
    logits = model.forward(params, ids)
    assert logits.shape == (2, 16, cfg.vocab_size)
    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    assert n == cfg.num_params()
    loss = model.loss(params, {"input_ids": ids})
    assert abs(float(loss) - np.log(cfg.vocab_size)) < 1.2


def test_opt_trains_sharded_matches_single_device():
    cfg = _cfg()
    batch = {"input_ids": jnp.asarray(np.random.RandomState(1).randint(
        0, cfg.vocab_size, size=(8, 24)))}

    def run(mesh, n=3):
        model = OPTModel(cfg, mesh=mesh)
        params = model.init_params(jax.random.PRNGKey(0))
        engine, *_ = deepspeed_tpu.initialize(
            model=model, model_parameters=params, mesh=mesh,
            config={"train_micro_batch_size_per_gpu": 8,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": 3},
                    "steps_per_print": 0})
        return [float(engine.train_step(batch)["loss"]) for _ in range(n)]

    groups.reset_mesh()
    sharded = run(groups.initialize_mesh(MeshLayout.infer(8, dp=4, tp=2)))
    groups.reset_mesh()
    single = run(groups.initialize_mesh(MeshLayout.infer(1, dp=1)))
    for a, b in zip(sharded, single):
        assert abs(a - b) < 5e-3, (sharded, single)
    assert sharded[-1] < sharded[0]


def test_opt_cached_generate_matches_full_forward():
    """v1 engine greedy generate == step-by-step full-forward argmax
    (cache write positions + learned-position offset agree)."""
    from deepspeed_tpu.inference import init_inference

    cfg = _cfg(max_seq_len=64)
    model = OPTModel(cfg)
    params = model.init_params(jax.random.PRNGKey(2))
    prompt = np.random.RandomState(3).randint(1, 512, size=(1, 5)).tolist()
    eng = init_inference(model=model, model_params=params)
    got = np.asarray(eng.generate(jnp.asarray(prompt), max_new_tokens=6))[0]
    seq = list(prompt[0])
    for _ in range(6):
        logits = model.forward(params, jnp.asarray([seq]))
        seq.append(int(jnp.argmax(logits[0, -1])))
    np.testing.assert_array_equal(got, np.asarray(seq))


def test_opt_rejects_over_length():
    cfg = _cfg(max_seq_len=16)
    model = OPTModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    import pytest
    with pytest.raises(ValueError, match="max_seq_len"):
        model.forward(params, jnp.zeros((1, 32), jnp.int32))
