"""Collective–compute overlap rings (comm/overlap.py, ISSUE 12).

Numerics of every ring decomposition against the monolithic lax
collective on the real 8-device CPU mesh, across chunk counts; plus the
forensics contract — every ring hop goes through the comm verbs, so the
CollectiveLedger census sees the ring.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.comm import overlap as ov
from deepspeed_tpu.comm.comm import comms_logger
from deepspeed_tpu.utils.jax_compat import shard_map


@pytest.fixture()
def mesh():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    return Mesh(np.array(devs[:8]), ("data",))


def data(m=64, k=32, n=16, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(m, k), jnp.float32),
            jnp.asarray(rng.randn(k, n), jnp.float32))


@pytest.mark.parametrize("chunks", [1, 2, 4])
def test_ring_all_gather_matches_tiled_gather(mesh, chunks):
    x, _ = data()
    f = jax.jit(shard_map(
        lambda x_: ov.ring_all_gather(x_, "data", 0, chunks),
        mesh=mesh, in_specs=(P("data"),), out_specs=P(),
        check_vma=False))
    np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(x))


@pytest.mark.parametrize("chunks", [1, 2, 4])
def test_all_gather_matmul_matches_gather_then_matmul(mesh, chunks):
    x, w = data()
    f = jax.jit(shard_map(
        lambda x_, w_: ov.all_gather_matmul(x_, w_, "data", chunks),
        mesh=mesh, in_specs=(P("data"), P()), out_specs=P(),
        check_vma=False))
    np.testing.assert_allclose(np.asarray(f(x, w)), np.asarray(x @ w),
                               rtol=2e-6, atol=1e-5)


@pytest.mark.parametrize("chunks", [1, 2, 4])
def test_ring_reduce_scatter_matches_psum_scatter(mesh, chunks):
    x, _ = data()

    def body(x_):
        r = jax.lax.axis_index("data").astype(jnp.float32)
        part = x_ * (r + 1.0)  # rank-distinct partials
        mine = ov.ring_reduce_scatter(part, "data", 0, chunks)
        ref = jax.lax.psum_scatter(  # dslint: disable=raw-collective
            part, "data", scatter_dimension=0, tiled=True)
        return mine, ref

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=(P(),),
                          out_specs=(P("data"), P("data")),
                          check_vma=False))
    mine, ref = f(x)
    np.testing.assert_allclose(np.asarray(mine), np.asarray(ref),
                               rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("chunks", [1, 2])
def test_matmul_reduce_scatter_matches_monolithic(mesh, chunks):
    x, w = data()

    def body(x_, w_):
        r = jax.lax.axis_index("data").astype(jnp.float32)
        part = x_ * (r + 1.0)
        mine = ov.matmul_reduce_scatter(part, w_, "data", chunks)
        ref = jax.lax.psum_scatter(  # dslint: disable=raw-collective
            jnp.dot(part, w_), "data", scatter_dimension=0, tiled=True)
        return mine, ref

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=(P(), P()),
                          out_specs=(P("data"), P("data")),
                          check_vma=False))
    mine, ref = f(x, w)
    np.testing.assert_allclose(np.asarray(mine), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


def test_chunk_mismatch_is_a_named_error(mesh):
    x = jnp.ones((24, 8), jnp.float32)  # shard rows 3: chunks=2 invalid
    f = shard_map(lambda x_: ov.ring_all_gather(x_, "data", 0, 2),
                  mesh=mesh, in_specs=(P("data"),), out_specs=P(),
                  check_vma=False)
    with pytest.raises(ValueError, match="overlap_chunks"):
        jax.jit(f)(x)


def test_census_sees_the_ring(mesh):
    """Every ring hop routes through dist.ppermute → the CollectiveLedger
    census chain records it (the dslint raw-collective contract): a
    W-device ring all-gather traces W-1 ppermute records per chunk."""
    from deepspeed_tpu.telemetry.collective_ledger import CollectiveLedger

    led = CollectiveLedger(max_entries=64, tail=64, enabled=True)
    old = comms_logger.ledger
    comms_logger.ledger = led
    try:
        x, _ = data()
        f = jax.jit(shard_map(
            lambda x_: ov.ring_all_gather(x_, "data", 0, 2),
            mesh=mesh, in_specs=(P("data"),), out_specs=P(),
            check_vma=False))
        f(x)  # trace-time census
    finally:
        comms_logger.ledger = old
    ops = [e["op"] for e in led.snapshot().get("tail", [])]
    assert ops.count("ppermute") == 2 * 7  # 2 chunks x (W-1) hops


def test_staging_bytes_accounting():
    assert ov.staging_bytes((1024, 16), jnp.float32, 4) == \
        1024 * 16 * 4 // 4
    assert ov.staging_bytes((10,), jnp.bfloat16, 1) == 20
