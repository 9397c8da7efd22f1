"""The gated delta rule's chunk form (``models/delta_rule.scan_chunk``:
what prefill runs) against the recurrence token by token: several chunk
lengths, the state carried across chunks, padded positions, ``β`` up to 2
and the fastest decay the model's weights are drawn with (a channel that
falls by five and more a token, where ``exp(−G)`` alone would overflow
inside a block)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import delta_rule

F32 = jnp.float32


def _inputs(seed, R, T, n, d, fastest=1.6, beta_most=2.0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(k[0], (R, T, n, d), F32)) * d ** -0.5
    key = unit(jax.random.normal(k[1], (R, T, n, d), F32))
    v = jax.random.normal(k[2], (R, T, n, d), F32)
    g = -jax.random.uniform(k[3], (R, T, n, d), F32, 0.0, fastest)
    beta = jax.random.uniform(k[4], (R, T, n), F32, 0.0, beta_most)
    S = jax.random.normal(k[5], (R, n, d, d), F32)
    return q, key, v, g, beta, S


def _token_by_token(q, k, v, g, beta, S):
    """The recurrence itself: ``S' = Diag(exp g) S; S = S' + k ⊗ β (v −
    kᵀS'); o = Sᵀ q``."""
    def token(S, t):
        q_t, k_t, v_t, g_t, b_t = t
        S = jnp.exp(g_t)[..., None] * S
        u = jnp.einsum("rnk,rnkv->rnv", k_t, S)
        S = S + k_t[..., None] * (b_t[..., None] * (v_t - u))[:, :, None, :]
        return S, jnp.einsum("rnk,rnkv->rnv", q_t, S)

    S, o = jax.lax.scan(token, S, tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), S


@pytest.mark.parametrize("T", [1, 7, 16, 48, 128])
def test_the_chunk_form_is_the_recurrence(T):
    q, k, v, g, beta, S = _inputs(T, 2, T, 3, 16)
    with jax.default_matmul_precision("highest"):
        got_o, got_S = delta_rule.scan_chunk(q, k, v, g, beta, S, F32)
        want_o, want_S = _token_by_token(q, k, v, g, beta, S)
    np.testing.assert_allclose(got_o, want_o, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(got_S, want_S, rtol=2e-4, atol=2e-5)
    assert float(jnp.std(want_o)) > 0.05


def test_the_state_is_carried_across_chunks():
    q, k, v, g, beta, S = _inputs(3, 2, 96, 2, 16)
    with jax.default_matmul_precision("highest"):
        want_o, want_S = _token_by_token(q, k, v, g, beta, S)
        outs = []
        for lo in (0, 32, 64):
            cut = lambda x: x[:, lo:lo + 32]
            o, S = delta_rule.scan_chunk(cut(q), cut(k), cut(v), cut(g),
                                         cut(beta), S, F32)
            outs.append(o)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want_o, rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(S, want_S, rtol=2e-4, atol=2e-5)


def test_the_fastest_decay_neither_overflows_nor_loses_the_result():
    """Every channel falls by ``exp(−20)`` a token: ``exp(−G)`` over a
    block of 16 would be ``exp(320)``, past float32; the pairwise
    differences stay at or under 1."""
    q, k, v, g, beta, S = _inputs(4, 1, 64, 2, 16, beta_most=2.0)
    g = jnp.full_like(g, -20.0)
    with jax.default_matmul_precision("highest"):
        got_o, got_S = delta_rule.scan_chunk(q, k, v, g, beta, S, F32)
        want_o, want_S = _token_by_token(q, k, v, g, beta, S)
    assert bool(jnp.all(jnp.isfinite(got_o)) & jnp.all(jnp.isfinite(got_S)))
    np.testing.assert_allclose(got_o, want_o, rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(got_S, want_S, rtol=2e-4, atol=2e-6)
    assert float(jnp.max(jnp.abs(want_o))) > 1e-3


def _leaves(seed, dims, H):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {"conv_w": jax.random.normal(k[0], (dims.d_conv, 3 * dims.width),
                                        F32) / 2,
            "dt_bias": jax.random.normal(k[1], (dims.width,), F32),
            "A_log": jnp.log(jax.random.uniform(k[2], (dims.heads,), F32,
                                                1.0, 16.0)),
            "norm": jnp.ones((dims.d_head,), F32)}


def _rows(seed, dims, N):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {"qkv": jax.random.normal(k[0], (N, 3 * dims.width), F32),
            "f": jax.random.normal(k[1], (N, dims.width), F32),
            "beta": 2 * jax.random.normal(k[2], (N, dims.heads), F32),
            "gate": jax.random.normal(k[3], (N, dims.width), F32)}


@pytest.mark.parametrize("valid", [(24, 24), (24, 9), (5, 0)])
def test_padded_positions_move_no_state_and_a_decode_step_is_one_token(valid):
    """``valid < tokens``: the padded tail of a chunk moves neither the
    state nor the conv's tail; and the chunk form over a sequence's real
    tokens is the one-token update (the decode step's path, here its
    ``jax.numpy`` reference) applied token by token from the same state."""
    dims = delta_rule.DeltaDims(heads=3, d_head=16, d_conv=4)
    m, T, R = _leaves(0, dims, 32), 24, 2
    p = _rows(1, dims, R * T)
    state = {name: jax.random.normal(jax.random.PRNGKey(5), (R,) + shape,
                                     F32).astype(dt)
             for name, shape, dt in dims.state_parts(F32)}
    valid = jnp.asarray(valid)
    with jax.default_matmul_precision("highest"):
        o, new = delta_rule.chunk(dims, m, p, state, T, valid, F32)
        # the same rows, a token a call through the decode step
        at = lambda tree, t: jax.tree.map(
            lambda x: x.reshape(R, T, -1)[:, t], tree)
        held, outs = dict(state), []
        for t in range(T):
            live = (t < valid).astype(jnp.int32)
            y, arrays = delta_rule.decode(
                dims, m, at(p, t),
                {name: (part[None], 0, 0) for name, part in held.items()},
                live)
            held = {name: array[0] for name, array in arrays.items()}
            outs.append(y)
    for name in new:
        np.testing.assert_allclose(new[name], held[name], rtol=2e-4,
                                   atol=2e-5, err_msg=name)
    want = jnp.stack(outs, 1)                           # [R, T, width]
    real = np.arange(T)[None, :] < np.asarray(valid)[:, None]
    np.testing.assert_allclose(
        np.asarray(o).reshape(R, T, -1)[real], np.asarray(want)[real],
        rtol=2e-4, atol=2e-5)
    # a sequence with no real token keeps what it held, to the bit
    for r in np.flatnonzero(np.asarray(valid) == 0):
        for name in new:
            np.testing.assert_array_equal(new[name][r], state[name][r])
