"""Mistral-style sliding-window attention across train/prefill/decode/ring."""

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models import LlamaConfig, LlamaModel
from deepspeed_tpu.parallel import MeshLayout
from deepspeed_tpu.utils import groups


def _cfg(**kw):
    d = dict(num_layers=2, dtype=jnp.float32, sliding_window=8)
    d.update(kw)
    return LlamaConfig.tiny(**d)


def test_mistral_preset_shape():
    cfg = LlamaConfig.mistral_7b()
    assert cfg.sliding_window == 4096
    assert cfg.num_kv_heads == 8 and cfg.num_layers == 32


def test_window_limits_attention_reach():
    """Perturbing a token OUTSIDE the window must not change logits;
    inside the window it must."""
    cfg = _cfg()
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    ids = rng.randint(1, cfg.vocab_size, size=(1, 24))
    base = np.asarray(model.forward(params, jnp.asarray(ids)))
    # token 0 is outside position 20's window of 8 → no influence on pos 20
    ids_far = ids.copy()
    ids_far[0, 0] = (ids[0, 0] + 7) % cfg.vocab_size
    far = np.asarray(model.forward(params, jnp.asarray(ids_far)))
    np.testing.assert_allclose(base[0, 20], far[0, 20], rtol=1e-5, atol=1e-5)
    # token 15 IS inside position 20's window → logits move
    ids_near = ids.copy()
    ids_near[0, 15] = (ids[0, 15] + 7) % cfg.vocab_size
    near = np.asarray(model.forward(params, jnp.asarray(ids_near)))
    assert np.abs(near[0, 20] - base[0, 20]).max() > 1e-6


def test_windowed_generate_matches_full_forward():
    """v1 cached generate under a window == argmax over the windowed
    forward logits at each step (cache path and train path agree)."""
    from deepspeed_tpu.inference import init_inference

    cfg = _cfg(max_seq_len=64)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(1))
    prompt = np.random.RandomState(2).randint(1, 512, size=(1, 6)).tolist()
    eng = init_inference(model=model, model_params=params)
    got = np.asarray(eng.generate(jnp.asarray(prompt), max_new_tokens=6))[0]
    # step-by-step reference: full forward, next token = argmax of last pos
    seq = list(prompt[0])
    for _ in range(6):
        logits = model.forward(params, jnp.asarray([seq]))
        seq.append(int(jnp.argmax(logits[0, -1])))
    np.testing.assert_array_equal(got, np.asarray(seq))


def test_ring_window_matches_dense_window():
    from deepspeed_tpu.runtime.sequence_parallel.ring import (
        _plain_attention, ring_attention)

    groups.reset_mesh()
    mesh = groups.initialize_mesh(MeshLayout.infer(8, sp=4, dp=2))
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(2, 32, 2, 16) * .3, jnp.float32)
    k = jnp.asarray(rng.randn(2, 32, 2, 16) * .3, jnp.float32)
    v = jnp.asarray(rng.randn(2, 32, 2, 16) * .3, jnp.float32)
    out = jax.jit(lambda a, b, c: ring_attention(
        a, b, c, causal=True, mesh=mesh, window=5))(q, k, v)
    want = _plain_attention(q, k, v, True, window=5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_v2_windowed_ragged_matches_v1():
    """The paged v2 engine honors the sliding window: ragged greedy
    generate == the v1 (cached, windowed) engine per prompt."""
    from deepspeed_tpu.inference import init_inference
    from deepspeed_tpu.inference.v2 import KVCacheConfig, build_engine_v2

    cfg = _cfg(max_seq_len=64)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(4))
    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, 512, size=n).tolist() for n in (4, 13)]

    eng2 = build_engine_v2(
        model, params,
        cache_config=KVCacheConfig(num_blocks=64, block_size=4,
                                   max_seq_len=64),
        max_batch_slots=2, prefill_chunk=16)
    assert eng2.kinds["kv"].window == cfg.sliding_window
    got = eng2.generate(prompts, max_new_tokens=6)
    v1 = init_inference(model=model, model_params=params)
    for prompt, g in zip(prompts, got):
        want = np.asarray(v1.generate(
            jnp.asarray([prompt]), max_new_tokens=6))[0, len(prompt):]
        np.testing.assert_array_equal(np.asarray(g), want)


def test_flash_kernel_window_matches_reference():
    """Windowed flash (interpret mode) == windowed dense reference, and the
    windowed flash backward matches the dense gradient."""
    from deepspeed_tpu.ops.pallas.flash_attention import (
        _reference_attention, flash_attention, flash_attention_interpret)

    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(2, 128, 2, 16) * .3, jnp.float32)
    k = jnp.asarray(rng.randn(2, 128, 2, 16) * .3, jnp.float32)
    v = jnp.asarray(rng.randn(2, 128, 2, 16) * .3, jnp.float32)
    for W in (16, 50, 128):
        got = flash_attention_interpret(q, k, v, True, 64, 64, window=W)
        want = _reference_attention(q, k, v, True, window=W)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5, err_msg=f"W={W}")

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, 64, 64, 16) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_reference_attention(q, k, v, True, window=16) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_flash_blocks_shrink_to_divisor():
    """S divisible by 128 but not 512 still runs the kernel (blocks shrink
    to a divisor instead of falling to the dense path)."""
    from deepspeed_tpu.ops.pallas.flash_attention import (
        _reference_attention, flash_attention_interpret)

    rng = np.random.RandomState(11)
    S = 640  # > 512 and divisible by 128, not by 512 → halving must run
    q = jnp.asarray(rng.randn(1, S, 2, 16) * .3, jnp.float32)
    got = flash_attention_interpret(q, q, q, True, 512, 512)
    want = _reference_attention(q, q, q, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # non-8-aligned S can never satisfy the sublane rule → dense fallback
    # (must still be numerically correct)
    S2 = 321
    q2 = jnp.asarray(rng.randn(1, S2, 2, 16) * .3, jnp.float32)
    got2 = flash_attention_interpret(q2, q2, q2, True, 512, 512)
    np.testing.assert_allclose(np.asarray(got2),
                               np.asarray(_reference_attention(
                                   q2, q2, q2, True)),
                               rtol=2e-5, atol=2e-5)
