"""The decode step's update of a gated delta rule's states: the kernel in
the Pallas interpreter against its ``jax.numpy`` reference, which is the
recurrence written out (decay a key channel, ``kᵀS``, the rank-1
correction, the read-out); rows that are no sequence's are left as they
lay; only the rows' stretch of the one layer is touched."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas.delta_state_update import (
    delta_state_update, delta_state_update_reference)


def _case(seed, L, slots, R, heads, dk, dv):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    pool = jax.random.normal(k[0], (L, slots, heads, dk, dv), jnp.float32)
    # a decay down to a fifth a token, β up to 2
    a = jnp.exp(-jax.random.uniform(k[1], (R, heads, dk), jnp.float32, 0.,
                                    1.6))
    key = unit(jax.random.normal(k[2], (R, heads, dk), jnp.float32))
    q = unit(jax.random.normal(k[3], (R, heads, dk), jnp.float32)) \
        * dk ** -0.5
    beta = jax.random.uniform(k[4], (R, heads), jnp.float32, 0., 2.)
    v = jax.random.normal(k[5], (R, heads, dv), jnp.float32)
    return pool, a, key, q, beta, v


@pytest.mark.parametrize("shape", [(3, 6, 4, 4, 16, 8),
                                   (2, 4, 3, 4, 128, 128),
                                   (2, 3, 2, 64, 16, 128)],
                         ids=["tiny", "published_head", "two_groups"])
def test_the_kernel_is_the_recurrence(shape):
    pool, a, k, q, beta, v = _case(0, *shape)
    layer, first = 1, 1
    want_pool, want_o = delta_state_update_reference(pool, layer, first, a,
                                                     k, q, beta, v)
    # by hand: S' = Diag(α) S; S ← S' + k ⊗ β (v − kᵀS'); o = Sᵀ q, which is
    # (I − β k kᵀ) Diag(α) S + β k vᵀ
    R, heads, dk = k.shape
    for r in (0, R - 1):
        for h in (0, heads - 1):
            S = a[r, h][:, None] * pool[layer, first + r, h]
            S = (jnp.eye(dk) - beta[r, h] * jnp.outer(k[r, h], k[r, h])) @ S \
                + beta[r, h] * jnp.outer(k[r, h], v[r, h])
            np.testing.assert_allclose(want_pool[layer, first + r, h], S,
                                       rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(want_o[r, h], q[r, h] @ S, rtol=1e-5,
                                       atol=1e-5)
    got_pool, got_o = jax.jit(
        lambda *args: delta_state_update(*args, interpret=True))(
            pool, layer, first, a, k, q, beta, v)
    np.testing.assert_allclose(got_pool, want_pool, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got_o, want_o, rtol=1e-5, atol=1e-5)
    # the other layers, and the slots before and after the rows', are as
    # they were
    untouched = np.ones(pool.shape[:2], bool)
    untouched[layer, first:first + R] = False
    np.testing.assert_array_equal(np.asarray(got_pool)[untouched],
                                  np.asarray(pool)[untouched])


@pytest.mark.parametrize("interpret", [None, True], ids=["reference", "kernel"])
def test_a_row_that_is_no_sequences_is_left_as_it_lay(interpret):
    pool, a, k, q, beta, v = _case(1, 2, 5, 4, 4, 16, 8)
    dead = jnp.asarray([False, True, False, True])
    a = jnp.where(dead[:, None, None], 1.0, a)
    k, q = (jnp.where(dead[:, None, None], 0.0, x) for x in (k, q))
    beta = jnp.where(dead[:, None], 0.0, beta)
    got, o = delta_state_update(pool, 0, 1, a, k, q, beta, v,
                                interpret=interpret)
    np.testing.assert_array_equal(got[0, 2], pool[0, 2])
    np.testing.assert_array_equal(got[0, 4], pool[0, 4])
    np.testing.assert_array_equal(o[1], 0 * o[1])
    assert float(abs(got[0, 1] - pool[0, 1]).max()) > 0


def test_beta_of_two_reflects_a_state_along_its_key():
    """``kda_allow_neg_eigval``: at ``β = 2`` and no decay ``I − β k kᵀ``
    is a reflection: the part of the state along ``k`` changes sign (an
    eigenvalue of −1) where ``v = 0``."""
    pool, a, k, q, beta, v = _case(2, 1, 2, 1, 2, 16, 8)
    got, _ = delta_state_update(pool, 0, 0, jnp.ones_like(a), k, q,
                                jnp.full_like(beta, 2.0), 0 * v,
                                interpret=True)
    along = lambda S: jnp.einsum("rhk,rhkv->rhv", k, S)
    np.testing.assert_allclose(along(got[0, :1]), -along(pool[0, :1]),
                               rtol=1e-5, atol=1e-5)
