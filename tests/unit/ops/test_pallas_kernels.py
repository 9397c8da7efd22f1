"""Pallas kernels vs jnp reference numerics (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas.decode_attention import (_reference_decode,
                                                       decode_attention)
from deepspeed_tpu.ops.pallas.flash_attention import (
    _reference_attention, flash_attention, flash_attention_interpret)
from deepspeed_tpu.ops.pallas.quantizer import (dequantize_int8,
                                                quantize_int8)


def qkv(B=2, S=128, h=4, d=64, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, S, h, d), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_kernel_matches_reference(causal):
    q, k, v = qkv()
    out = flash_attention_interpret(q, k, v, causal=causal)
    ref = _reference_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_public_fn_has_gradient():
    q, k, v = qkv(B=1, S=32, h=2, d=16)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True) ** 2)

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    def ref_loss(q, k, v):
        return jnp.sum(_reference_attention(q, k, v, True) ** 2)

    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_decode_attention_kernel_matches_reference():
    rng = np.random.RandomState(1)
    B, Smax, h, d = 4, 256, 4, 64
    q = jnp.asarray(rng.randn(B, h, d), jnp.float32)
    kc = jnp.asarray(rng.randn(B, Smax, h, d), jnp.float32)
    vc = jnp.asarray(rng.randn(B, Smax, h, d), jnp.float32)
    lengths = jnp.asarray([256, 100, 7, 128], jnp.int32)
    out = decode_attention(q, kc, vc, lengths, block_k=64, interpret=True)
    ref = _reference_decode(q, kc, vc, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_quantize_roundtrip():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(512, 256) * 3, jnp.float32)
    q, s = quantize_int8(x, interpret=True)
    assert q.dtype == jnp.int8 and s.shape == (512,)
    back = dequantize_int8(q, s)
    # int8 symmetric quant: max error = scale/2 per element
    max_err = np.asarray(s).max() / 2 + 1e-6
    assert np.abs(np.asarray(back) - np.asarray(x)).max() <= max_err


def test_quantize_kernel_matches_reference_path():
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(128, 64), jnp.float32)
    qk, sk = quantize_int8(x, interpret=True)
    from deepspeed_tpu.ops.pallas.quantizer import _ref_quantize

    qr, sr = _ref_quantize(x)
    np.testing.assert_array_equal(np.asarray(qk), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=1e-6)


def test_quantize_zero_rows():
    x = jnp.zeros((8, 32), jnp.float32)
    q, s = quantize_int8(x, interpret=True)
    assert np.all(np.asarray(q) == 0)
    back = dequantize_int8(q, s)
    assert np.all(np.asarray(back) == 0)


def test_flash_backward_kernels_match_reference_all_modes():
    """The resident Pallas flash backward (``_fa_bwd_kernel``, interpret
    mode here; on the chip through ``chip_smoke.py``'s selfcheck) == the
    reference vjp for causal, non-causal, and windowed attention: the
    kernel must stay testable without a chip."""
    import importlib

    import numpy as np

    fa = importlib.import_module(
        "deepspeed_tpu.ops.pallas.flash_attention")

    rng = np.random.default_rng(0)
    B, S, h, d = 1, 256, 2, 64
    q = jnp.asarray(rng.normal(size=(B, S, h, d)) * 0.3, jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, h, d)) * 0.3, jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, h, d)) * 0.3, jnp.float32)
    do = jnp.asarray(rng.normal(size=(B, S, h, d)), jnp.float32)

    for causal, window in ((True, None), (False, None), (True, 128),
                           (False, 128)):
        out, lse = fa._reference_fwd_with_lse(q, k, v, causal, window)
        got = fa._flash_bwd_pallas(q, k, v, out, lse, do, causal, 64, 64,
                                   window, interpret=True)

        def f(q_, k_, v_):
            return fa._reference_fwd_with_lse(q_, k_, v_, causal,
                                              window)[0]

        _, vjp = jax.vjp(f, q, k, v)
        want = vjp(do)
        for a, b, nm in zip(got, want, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4,
                err_msg=f"d{nm} causal={causal} window={window}")
