"""Inference engine: KV-cache decode must match full-forward decoding."""

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference import init_inference
from deepspeed_tpu.models import LlamaConfig, LlamaModel
from deepspeed_tpu.parallel import MeshLayout
from deepspeed_tpu.utils import groups


def greedy_reference(model, params, input_ids, n_new):
    """Re-run the full forward for every generated token (no cache)."""
    ids = input_ids
    for _ in range(n_new):
        logits = model.forward(params, ids)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(ids.dtype)
        ids = jnp.concatenate([ids, nxt[:, None]], axis=1)
    return ids


def test_cached_generate_matches_full_forward():
    groups.initialize_mesh(MeshLayout.infer(1, dp=1))
    cfg = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(2, 16)))

    engine = init_inference(model=model, model_params=params,
                            dtype=jnp.float32)
    out = engine.generate(ids, max_new_tokens=8)
    ref = greedy_reference(model, params, ids, 8)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_generate_sampling_shapes_and_determinism():
    groups.initialize_mesh(MeshLayout.infer(1, dp=1))
    cfg = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    engine = init_inference(model=model, model_params=params,
                            dtype=jnp.float32)
    ids = jnp.asarray(np.random.RandomState(1).randint(
        0, cfg.vocab_size, size=(2, 8)))
    a = engine.generate(ids, max_new_tokens=4, temperature=0.8, top_k=5,
                        seed=7)
    b = engine.generate(ids, max_new_tokens=4, temperature=0.8, top_k=5,
                        seed=7)
    assert a.shape == (2, 12)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_mixtral_cached_generate_matches_full_forward():
    from deepspeed_tpu.models import MixtralConfig, MixtralModel

    groups.initialize_mesh(MeshLayout.infer(1, dp=1))
    cfg = MixtralConfig.tiny(num_layers=2, dtype=jnp.float32)
    model = MixtralModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.RandomState(2).randint(
        0, cfg.vocab_size, size=(2, 8)))
    engine = init_inference(model=model, model_params=params,
                            dtype=jnp.float32)
    out = engine.generate(ids, max_new_tokens=4)
    ref = greedy_reference(model, params, ids, 4)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_flops_profiler():
    from deepspeed_tpu.profiling import FlopsProfiler, get_model_profile

    cfg = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    ids = jnp.zeros((2, 16), jnp.int32)
    prof = FlopsProfiler()
    result = prof.profile_fn(model.forward, params, ids, runs=1)
    assert result["flops"] > 0
    assert result["latency_s"] > 0
    flops, macs, nparams = get_model_profile(
        fn=model.forward, args=(params, ids), print_profile=False,
        as_string=False)
    assert flops > 0 and macs == flops / 2 and nparams == cfg.num_params()
