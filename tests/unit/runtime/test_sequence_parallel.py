"""Ulysses/ALST sequence parallelism: all-to-all numerics + tiled compute."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.parallel import MeshLayout
from deepspeed_tpu.runtime.sequence_parallel import (
    SequenceTiledCompute, TiledMLP, UlyssesSPAttentionHF,
    UlyssesSPDataLoaderAdapter, sequence_tiled_loss, ulysses_attention)
from deepspeed_tpu.sequence import DistributedAttention
from deepspeed_tpu.utils import groups


def softmax_attn(q, k, v):
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def make_qkv(B=4, S=32, h=8, d=16, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, S, h, d), jnp.float32)
    return mk(), mk(), mk()


def test_ulysses_attention_matches_direct():
    mesh = groups.initialize_mesh(MeshLayout.infer(8, dp=2, sp=2, tp=2))
    q, k, v = make_qkv()
    out = jax.jit(lambda q, k, v: ulysses_attention(
        softmax_attn, q, k, v, mesh=mesh))(q, k, v)
    ref = softmax_attn(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ulysses_sp1_passthrough():
    mesh = groups.initialize_mesh(MeshLayout.infer(8, dp=8))
    q, k, v = make_qkv()
    out = ulysses_attention(softmax_attn, q, k, v, mesh=mesh)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(softmax_attn(q, k, v)), rtol=1e-5)


def test_distributed_attention_legacy_api():
    mesh = groups.initialize_mesh(MeshLayout.infer(8, dp=4, sp=2))
    q, k, v = make_qkv()
    attn = DistributedAttention(softmax_attn)
    out = jax.jit(attn)(q, k, v)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(softmax_attn(q, k, v)),
                               rtol=1e-5, atol=1e-5)


def test_sequence_tiled_compute_matches_untiled():
    x = jnp.asarray(np.random.RandomState(0).randn(2, 64, 8), jnp.float32)
    fn = lambda t: jax.nn.gelu(t) * 2.0 + 1.0
    out = SequenceTiledCompute.apply(fn, x, tiles=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(fn(x)), rtol=1e-6)
    out2 = TiledMLP.apply(fn, x, tiles=8)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(fn(x)), rtol=1e-6)


def test_sequence_tiled_loss_matches_untiled():
    rng = np.random.RandomState(0)
    B, S, H, V = 2, 32, 16, 64
    hidden = jnp.asarray(rng.randn(B, S, H), jnp.float32)
    head = jnp.asarray(rng.randn(H, V).astype(np.float32) * 0.1)
    labels = jnp.asarray(rng.randint(0, V, size=(B, S)))
    labels = labels.at[:, -4:].set(-100)

    logits_fn = lambda h: jnp.einsum("bsH,HV->bsV", h, head)
    tiled = sequence_tiled_loss(logits_fn, hidden, labels, tiles=4)

    logits = logits_fn(hidden)
    valid = labels != -100
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, jnp.where(valid, labels, 0)[..., None],
                               axis=-1)[..., 0]
    ref = jnp.sum(jnp.where(valid, nll, 0.0)) / jnp.sum(valid)
    np.testing.assert_allclose(float(tiled), float(ref), rtol=1e-5)


def test_dataloader_adapter_slices_sequence():
    groups.initialize_mesh(MeshLayout.infer(8, dp=4, sp=2))
    batches = [{"input_ids": jnp.arange(2 * 16).reshape(2, 16)}]
    sliced = list(UlyssesSPDataLoaderAdapter(batches, sp_rank=1,
                                             sp_world_size=2))
    assert sliced[0]["input_ids"].shape == (2, 8)
    np.testing.assert_array_equal(np.asarray(sliced[0]["input_ids"][0]),
                                  np.arange(8, 16))


def test_register_with_transformers_returns_mpu():
    groups.initialize_mesh(MeshLayout.infer(8, dp=4, sp=2))
    mpu = UlyssesSPAttentionHF.register_with_transformers(
        model_name_or_path="x", sequence_parallel_size=2, max_length=256)
    assert mpu.get_sequence_parallel_world_size() == 2
    assert UlyssesSPAttentionHF.register_with_transformers(
        sequence_parallel_size=1) is None
    with pytest.raises(ValueError):
        UlyssesSPAttentionHF.register_with_transformers(
            sequence_parallel_size=4, max_length=256)


def test_llama_tiled_loss_matches_untiled():
    from deepspeed_tpu.models import LlamaConfig, LlamaModel

    ids = jnp.asarray(np.random.RandomState(0).randint(0, 512, size=(2, 32)))
    cfg = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32)
    m0 = LlamaModel(cfg)
    params = m0.init_params(jax.random.PRNGKey(0))
    ref = m0.loss(params, {"input_ids": ids})
    m1 = LlamaModel(LlamaConfig.tiny(num_layers=2, dtype=jnp.float32,
                                     loss_tiles=4))
    tiled = m1.loss(params, {"input_ids": ids})
    np.testing.assert_allclose(float(tiled), float(ref), rtol=1e-5)
