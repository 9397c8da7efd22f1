"""1-bit compressed gradient reduction: primitives + engine convergence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import LlamaConfig, LlamaModel
from deepspeed_tpu.ops import onebit
from deepspeed_tpu.parallel import MeshLayout
from deepspeed_tpu.utils import groups
from deepspeed_tpu.utils.jax_compat import shard_map as _shard_map


def test_pack_unpack_roundtrip():
    rng = np.random.RandomState(0)
    for n in (8, 64, 100, 1000):  # incl. non-multiple-of-8
        x = jnp.asarray(rng.randn(n), jnp.float32)
        packed = onebit.pack_signs(x)
        assert packed.dtype == jnp.uint8
        assert packed.size == (n + 7) // 8
        signs = onebit.unpack_signs(packed, n)
        np.testing.assert_array_equal(np.asarray(signs),
                                      np.where(np.asarray(x) >= 0, 1.0, -1.0))


def test_error_feedback_identity():
    """decompressed + residual == corrected input (nothing is lost)."""
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(4, 33), jnp.float32)
    packed, scale, dec = onebit.compress(x)
    residual = x - dec
    np.testing.assert_allclose(np.asarray(dec + residual), np.asarray(x),
                               rtol=1e-6, atol=1e-6)
    assert float(scale) == pytest.approx(float(jnp.mean(jnp.abs(x))), rel=1e-5)


def test_wire_bytes_reduction():
    params = {"a": np.zeros((256, 64)), "b": np.zeros((1000,))}
    compressed, full = onebit.wire_bytes(params)
    assert full == 4 * (256 * 64 + 1000)
    assert compressed < full / 30  # ~32x minus per-tensor scale overhead


def test_onebit_allreduce_matches_mean_of_decompressed(mesh8):
    """Inside shard_map: the reduction equals the mean of per-worker
    sign*scale estimates, and residuals carry the error."""
    from jax.sharding import PartitionSpec as P

    rng = np.random.RandomState(2)
    world = 8
    g = jnp.asarray(rng.randn(world, 16, 8), jnp.float32)
    r = jnp.zeros_like(g)

    def f(g_local, r_local):
        out, new_r = onebit.onebit_allreduce(
            g_local[0], r_local[0], ("expert", "data"))
        return out[None], new_r[None]

    out, new_r = _shard_map(
        f, mesh=mesh8, in_specs=(P(("expert", "data")),) * 2,
        out_specs=(P(("expert", "data")), P(("expert", "data"))),
        check_vma=False)(g, r)
    # expected: mean over workers of (±1 by g_w>=0) * mean|g_w|
    per = np.stack([np.where(np.asarray(g[w]) >= 0, 1.0, -1.0) *
                    np.abs(np.asarray(g[w])).mean() for w in range(world)])
    expected = per.mean(axis=0)
    got = np.asarray(out[0])
    np.testing.assert_allclose(got, expected, rtol=1e-4, atol=1e-5)
    # all workers agree
    for w in range(1, world):
        np.testing.assert_array_equal(np.asarray(out[w]), got)
    # residual = corrected - decompressed per worker
    np.testing.assert_allclose(np.asarray(new_r[0]),
                               np.asarray(g[0]) - per[0], rtol=1e-4,
                               atol=1e-5)


def make_engine(mesh, opt_type, freeze_step=None):
    cfg = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32)
    model = LlamaModel(cfg, mesh=mesh)
    params = model.init_params(jax.random.PRNGKey(0))
    opt_params = {"lr": 2e-3, "betas": [0.9, 0.999], "eps": 1e-8}
    if freeze_step is not None:
        opt_params["freeze_step"] = freeze_step
    ds = {"train_micro_batch_size_per_gpu": 8,
          "gradient_accumulation_steps": 1,
          "optimizer": {"type": opt_type, "params": opt_params},
          "zero_optimization": {"stage": 1}}
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=ds, mesh=mesh)
    return engine


def test_onebit_adam_converges(mesh8):
    """OnebitAdam with a warmup (freeze_step): warmup steps match Adam
    exactly, and the compressed phase keeps converging (error feedback)."""
    ids = np.random.RandomState(0).randint(0, 512, size=(16, 32))
    b = {"input_ids": jnp.asarray(ids)}
    n, warm = 10, 5

    one = make_engine(mesh8, "OnebitAdam", freeze_step=warm)
    assert one.onebit_enabled and one.onebit_freeze_step == warm
    losses_1bit = [float(one.train_step(b)["loss"]) for _ in range(n)]

    groups.reset_mesh()
    mesh = groups.initialize_mesh(MeshLayout.infer(8, dp=8))
    base = make_engine(mesh, "Adam")
    assert not base.onebit_enabled
    losses_base = [float(base.train_step(b)["loss"]) for _ in range(n)]

    # warmup phase is the SAME program as uncompressed Adam
    np.testing.assert_allclose(losses_1bit[:warm], losses_base[:warm],
                               rtol=1e-4, atol=1e-4)
    # compressed phase keeps making progress
    assert losses_1bit[-1] < losses_1bit[warm - 1]
    # and stays in the neighborhood of the uncompressed trajectory
    assert losses_1bit[-1] < 2.5 * losses_base[-1]
