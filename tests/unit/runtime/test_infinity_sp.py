"""Ladder config 5's composition: ZeRO-Infinity layer streaming × Ulysses
sequence parallelism (the north-star 70B configuration, BASELINE.md).

Reference parity: the fork's flagship ALST subsystem
(``deepspeed/runtime/sequence_parallel/ulysses_sp.py``) composed with
ZeRO-Infinity (``deepspeed/runtime/zero/stage3.py`` + ``swap_tensor/*``,
SURVEY §2.1).  SP shards the sequence axis of every activation while
streaming shards the LAYER axis across time — the interaction under test
is that the per-layer jitted programs keep the Ulysses all-to-all and the
seq-sharded home layout while params arrive from host planes.

Own file (not test_infinity.py): each trajectory-equality test builds two
full engines; packing more of them into one process trips the known
XLA-CPU collective-rendezvous starvation (tests/run_suite.sh header).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import LlamaConfig, LlamaModel
from deepspeed_tpu.ops.op_builder import CPUAdamBuilder
from deepspeed_tpu.parallel import MeshLayout
from deepspeed_tpu.utils import groups

pytestmark = [
    pytest.mark.slow,
    pytest.mark.skipif(not CPUAdamBuilder.is_compatible(),
                       reason="no g++ toolchain"),
]

DS = {"train_micro_batch_size_per_gpu": 8,
      "gradient_accumulation_steps": 1,
      "optimizer": {"type": "AdamW",
                    "params": {"lr": 1e-3, "betas": [0.9, 0.999],
                               "eps": 1e-8, "weight_decay": 0.0}}}


def _batch():
    return {"input_ids": jnp.asarray(
        np.random.RandomState(0).randint(0, 512, size=(8, 32)))}


def _build(layout_kwargs, streaming, loss_tiles=1):
    groups.reset_mesh()
    mesh = groups.initialize_mesh(MeshLayout.infer(8, **layout_kwargs))
    cfg = LlamaConfig.tiny(num_layers=4, dtype=jnp.float32,
                           loss_tiles=loss_tiles)
    model = LlamaModel(cfg, mesh=mesh)
    params = model.init_params(jax.random.PRNGKey(0))
    ds = dict(DS)
    ds["zero_optimization"] = (
        {"stage": 3, "offload_param": {"device": "cpu"}} if streaming
        else {"stage": 3})
    eng, *_ = deepspeed_tpu.initialize(model=model, model_parameters=params,
                                       config=ds, mesh=mesh)
    if streaming:
        assert eng.infinity is not None
    return eng


def _trajectory(eng, b, steps=3):
    return [float(eng.train_step(b)["loss"]) for _ in range(steps)]


def test_streaming_sp_matches_fused_zero3():
    """dp4 × sp2: layer streaming under a seq axis == fused ZeRO-3 on the
    same mesh — and the streamed per-layer program really contains the
    Ulysses all-to-all (it did not silently drop to replicated attention)."""
    b = _batch()
    eng = _build({"sp": 2}, streaming=True)
    losses_stream = _trajectory(eng, b)

    # proof the all-to-all runs INSIDE the streamed layer program, and
    # activations ride seq-sharded between the per-layer programs
    ev = eng.infinity.sp_program_evidence(b)
    assert ev["all_to_all_in_layer_program"], ev
    assert "seq" in ev["activation_spec"], ev

    eng2 = _build({"sp": 2}, streaming=False)
    losses_fused = _trajectory(eng2, b)
    np.testing.assert_allclose(losses_stream, losses_fused,
                               rtol=3e-4, atol=3e-4)
    assert losses_stream[-1] < losses_stream[0]


def test_streaming_sp_tp_matches_fused_zero3():
    """dp2 × sp2 × tp2 (the full config-5 shape minus scale): wire params
    land TP-sharded + seq-replicated while activations are seq-sharded."""
    b = _batch()
    eng = _build({"sp": 2, "tp": 2}, streaming=True)
    losses_stream = _trajectory(eng, b)

    sw = eng.infinity.swapper
    sw.prefetch(0)
    lp0 = sw.get_device(0)
    spec = lp0["attn"]["wq"].sharding.spec
    sw.release(0)
    assert "tensor" in str(spec), spec  # TP-sharded wire params
    assert "seq" not in str(spec), spec  # params replicated over seq

    eng2 = _build({"sp": 2, "tp": 2}, streaming=False)
    losses_fused = _trajectory(eng2, b)
    np.testing.assert_allclose(losses_stream, losses_fused,
                               rtol=3e-4, atol=3e-4)


def test_streaming_sp_tiled_loss_matches():
    """ALST's sequence-tiled loss under streaming: loss_tiles=4 chunks the
    head so [B,S,V] logits are never materialized; trajectory unchanged."""
    b = _batch()
    eng = _build({"sp": 2}, streaming=True, loss_tiles=4)
    tiled = _trajectory(eng, b, steps=2)
    eng2 = _build({"sp": 2}, streaming=True, loss_tiles=1)
    flat = _trajectory(eng2, b, steps=2)
    np.testing.assert_allclose(tiled, flat, rtol=2e-4, atol=2e-4)
