"""Ring attention: sequence-parallel numerics past the head-count limit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.parallel import MeshLayout
from deepspeed_tpu.runtime.sequence_parallel.ring import (_plain_attention,
                                                          ring_attention)
from deepspeed_tpu.utils import groups


def _qkv(B=2, S=64, h=2, d=16, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, S, h, d) * 0.3, jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("sp,causal", [(4, True), (4, False),
                                       (8, True), (2, True)])
def test_ring_matches_dense(sp, causal):
    """sp devices, only h=2 heads — BEYOND the Ulysses sp<=h limit for
    sp>2 — still bit-close to dense attention."""
    groups.reset_mesh()
    mesh = groups.initialize_mesh(MeshLayout.infer(8, sp=sp,
                                                   dp=8 // sp))
    q, k, v = _qkv()
    out = jax.jit(lambda a, b, c: ring_attention(a, b, c, causal=causal,
                                                 mesh=mesh))(q, k, v)
    want = _plain_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_gradients_match_dense():
    groups.reset_mesh()
    mesh = groups.initialize_mesh(MeshLayout.infer(8, sp=8, dp=1))
    q, k, v = _qkv(S=32, seed=1)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention(q, k, v, causal=True, mesh=mesh) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_plain_attention(q, k, v, True) ** 2)

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_dense):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_ring_seq_not_divisible_raises():
    groups.reset_mesh()
    mesh = groups.initialize_mesh(MeshLayout.infer(8, sp=8, dp=1))
    q, k, v = _qkv(S=60)
    with pytest.raises(ValueError, match="divisible"):
        ring_attention(q, k, v, mesh=mesh)


def test_ring_sp1_is_plain():
    groups.reset_mesh()
    mesh = groups.initialize_mesh(MeshLayout.infer(8, dp=8))
    q, k, v = _qkv(S=16)
    out = ring_attention(q, k, v, causal=True, mesh=mesh)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_plain_attention(q, k, v, True)),
                               rtol=1e-6)


def test_llama_ring_sp_beyond_head_count_matches_single_device():
    """End-to-end: Llama with attn_impl='ring' trains under sp=4 with only
    2 heads (Ulysses would need sp<=2) and tracks the unsharded trace."""
    import deepspeed_tpu
    from deepspeed_tpu.models import LlamaConfig, LlamaModel

    cfg = LlamaConfig.tiny(num_layers=2, num_heads=2, num_kv_heads=2,
                           dtype=jnp.float32, attn_impl="ring")
    rng = np.random.RandomState(2)
    batch = {"input_ids": jnp.asarray(
        rng.randint(0, cfg.vocab_size, size=(8, 32)))}

    def run(mesh, n_steps=3):
        model = LlamaModel(cfg, mesh=mesh)
        params = model.init_params(jax.random.PRNGKey(0))
        engine, *_ = deepspeed_tpu.initialize(
            model=model, model_parameters=params, mesh=mesh,
            config={"train_micro_batch_size_per_gpu": 8,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": 3},
                    "steps_per_print": 0})
        return [float(engine.train_step(batch)["loss"])
                for _ in range(n_steps)]

    groups.reset_mesh()
    ring_losses = run(groups.initialize_mesh(
        MeshLayout.infer(8, sp=4, dp=2)))
    groups.reset_mesh()
    single_losses = run(groups.initialize_mesh(MeshLayout.infer(1, dp=1)))
    for a, b in zip(ring_losses, single_losses):
        assert abs(a - b) < 5e-3, (ring_losses, single_losses)
    assert ring_losses[-1] < ring_losses[0]


def test_ring_gqa_rotates_kv_width():
    """GQA: K/V circulate at kv-head width; output matches dense with
    expanded heads."""
    groups.reset_mesh()
    mesh = groups.initialize_mesh(MeshLayout.infer(8, sp=4, dp=2))
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(2, 32, 8, 16) * .3, jnp.float32)
    k = jnp.asarray(rng.randn(2, 32, 2, 16) * .3, jnp.float32)  # kv_h=2
    v = jnp.asarray(rng.randn(2, 32, 2, 16) * .3, jnp.float32)
    out = jax.jit(lambda a, b, c: ring_attention(a, b, c, causal=True,
                                                 mesh=mesh))(q, k, v)
    want = _plain_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_config_meshless_gqa_forward():
    """attn_impl='ring' without a mesh falls back to local attention and
    must expand GQA KV heads (regression: mismatched-head einsum crash)."""
    from deepspeed_tpu.models import LlamaConfig, LlamaModel

    cfg = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32,
                           attn_impl="ring")  # tiny is GQA: 8 q / 4 kv heads
    model = LlamaModel(cfg)  # mesh=None
    params = model.init_params(jax.random.PRNGKey(0))
    logits = model.forward(params, jnp.asarray([[1, 2, 3, 4]]))
    assert logits.shape == (1, 4, cfg.vocab_size)
