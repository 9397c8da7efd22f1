"""The decode step's state update: the kernel in the Pallas interpreter
against its ``jax.numpy`` reference, which is the recurrence written out;
rows that are no sequence's move nothing; only the rows' stretch of the one
layer is touched."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas.ssm_state_update import (
    ssm_state_update, ssm_state_update_reference)


def _case(seed, L, slots, R, heads, G, N, P, dtype=jnp.bfloat16):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    pool = jax.random.normal(k[0], (L, slots, heads, N, P), jnp.float32)
    a = jnp.exp(-jax.random.uniform(k[1], (R, heads), jnp.float32, 0., 2.))
    dx = 0.1 * jax.random.normal(k[2], (R, heads, P), jnp.float32)
    b = jax.random.normal(k[3], (R, G, N), jnp.float32).astype(dtype)
    c = jax.random.normal(k[4], (R, G, N), jnp.float32).astype(dtype)
    return pool, a, dx, b, c


@pytest.mark.parametrize("shape", [(3, 6, 4, 4, 2, 16, 8),
                                   (2, 4, 3, 4, 2, 256, 128)],
                         ids=["tiny", "lanes"])
def test_the_kernel_is_the_recurrence(shape):
    pool, a, dx, b, c = _case(0, *shape)
    layer, first = 1, 1
    want_pool, want_y = ssm_state_update_reference(pool, layer, first, a, dx,
                                                   b, c)
    # by hand: S ← a S + B ⊗ dx, y = Σ_n S[n, :] C[n], head h of group h // k
    R, heads, P = dx.shape
    G = b.shape[1]
    for r in (0, R - 1):
        for h in (0, heads - 1):
            g = h // (heads // G)
            S = a[r, h] * pool[layer, first + r, h] \
                + b[r, g].astype(jnp.float32)[:, None] * dx[r, h][None, :]
            np.testing.assert_allclose(want_pool[layer, first + r, h], S,
                                       rtol=1e-6, atol=1e-6)
            np.testing.assert_allclose(
                want_y[r, h], (S * c[r, g].astype(jnp.float32)[:, None]
                               ).sum(0), rtol=1e-5, atol=1e-5)
    got_pool, got_y = jax.jit(
        lambda *args: ssm_state_update(*args, interpret=True))(
            pool, layer, first, a, dx, b, c)
    np.testing.assert_allclose(got_pool, want_pool, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got_y, want_y, rtol=1e-5, atol=1e-5)
    # the other layers, and the slots before and after the rows', are as
    # they were
    untouched = np.ones(pool.shape[:2], bool)
    untouched[layer, first:first + R] = False
    np.testing.assert_array_equal(np.asarray(got_pool)[untouched],
                                  np.asarray(pool)[untouched])


@pytest.mark.parametrize("interpret", [None, True], ids=["reference", "kernel"])
def test_a_row_that_is_no_sequences_moves_nothing(interpret):
    pool, a, dx, b, c = _case(1, 2, 5, 4, 4, 2, 16, 8)
    dead = jnp.asarray([False, True, False, True])[:, None]
    a = jnp.where(dead, 1.0, a)
    dx = jnp.where(dead[..., None], 0.0, dx)
    got, _ = ssm_state_update(pool, 0, 1, a, dx, b, c, interpret=interpret)
    np.testing.assert_array_equal(got[0, 2], pool[0, 2])
    np.testing.assert_array_equal(got[0, 4], pool[0, 4])
    assert float(abs(got[0, 1] - pool[0, 1]).max()) > 0


@pytest.mark.parametrize("interpret", [None, True], ids=["reference", "kernel"])
def test_several_heads_a_lane_row_is_the_same_recurrence(interpret):
    """Heads of 64 held two a lane row (``[heads / 2, N, 128]``) with the
    decay a LANE (``a [R, heads / 2, 128]``): the states and ``y`` are
    those of the one-head-a-row form on the same numbers."""
    L, slots, R, heads, G, N, P, pack = 2, 5, 3, 8, 2, 16, 64, 2
    pool, a, dx, b, c = _case(2, L, slots, R, heads, G, N, P)
    want_pool, want_y = ssm_state_update_reference(pool, 1, 1, a, dx, b, c)
    # heads 2i and 2i + 1 side by side on the lanes
    packed = lambda s: s.reshape(s.shape[:-3] + (heads // pack, pack, N, P)
                                 ).swapaxes(-3, -2).reshape(
                                     s.shape[:-3] + (heads // pack, N,
                                                     pack * P))
    rows = (R, heads // pack, pack * P)
    got_pool, got_y = jax.jit(lambda *args: ssm_state_update(
        *args, interpret=interpret))(
            packed(pool), 1, 1,
            jnp.broadcast_to(a[..., None], dx.shape).reshape(rows),
            dx.reshape(rows), b, c)
    np.testing.assert_allclose(got_pool, packed(want_pool), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(got_y.reshape(R, heads, P), want_y, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(got_pool[0], packed(pool)[0])
