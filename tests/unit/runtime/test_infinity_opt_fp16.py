"""Infinity engine-pair tests that need their OWN process: the
pipelined-optimizer and fp16 trajectory-equality tests each build 2-6
full engines; co-hosting them with the SP composition tests trips the
known XLA-CPU collective-rendezvous starvation (tests/run_suite.sh
header).  Same helpers as test_infinity_sp.py."""

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


def _trajectory(eng, b, steps=3):
    return [float(eng.train_step(b)["loss"]) for _ in range(steps)]


def test_pipelined_optimizer_matches_serial(tmp_path, monkeypatch):
    """The pipelined optimizer swapper (worker-thread C++ Adam behind
    device compute — reference pipelined_optimizer_swapper.py) must be
    bit-equal in trajectory to the serialized update, on BOTH tiers, and
    must actually be the production default."""
    b = _batch()

    def build(serial, nvme):
        if serial:
            monkeypatch.setenv("DS_INFINITY_SERIAL_OPT", "1")
        else:
            monkeypatch.delenv("DS_INFINITY_SERIAL_OPT", raising=False)
        groups.reset_mesh()
        mesh = groups.initialize_mesh(MeshLayout.infer(8, sp=2))
        cfg = LlamaConfig.tiny(num_layers=4, dtype=jnp.float32)
        model = LlamaModel(cfg, mesh=mesh)
        params = model.init_params(jax.random.PRNGKey(0))
        entry = {"device": "nvme", "nvme_path": str(tmp_path / "nv"),
                 "buffer_count": 2} if nvme else {"device": "cpu"}
        ds = dict(DS)
        ds["zero_optimization"] = {"stage": 3, "offload_param": entry}
        eng, *_ = deepspeed_tpu.initialize(
            model=model, model_parameters=params, config=ds, mesh=mesh)
        return eng

    for nvme in (False, True):
        eng = build(serial=False, nvme=nvme)
        assert eng.infinity.swapper._pipe is not None  # default = pipelined
        piped = _trajectory(eng, b)
        eng = build(serial=True, nvme=nvme)
        assert eng.infinity.swapper._pipe is None
        serial = _trajectory(eng, b)
        np.testing.assert_allclose(piped, serial, rtol=1e-6, atol=1e-7)

    # gas=2 + clipping exercises the stash/apply_stashed pipelined pass
    def build_gas(serial):
        if serial:
            monkeypatch.setenv("DS_INFINITY_SERIAL_OPT", "1")
        else:
            monkeypatch.delenv("DS_INFINITY_SERIAL_OPT", raising=False)
        groups.reset_mesh()
        mesh = groups.initialize_mesh(MeshLayout.infer(8, sp=2))
        cfg = LlamaConfig.tiny(num_layers=4, dtype=jnp.float32)
        model = LlamaModel(cfg, mesh=mesh)
        params = model.init_params(jax.random.PRNGKey(0))
        ds = dict(DS)
        ds["gradient_accumulation_steps"] = 2
        ds["gradient_clipping"] = 0.5
        ds["zero_optimization"] = {"stage": 3,
                                   "offload_param": {"device": "cpu"}}
        eng, *_ = deepspeed_tpu.initialize(
            model=model, model_parameters=params, config=ds, mesh=mesh)
        return eng

    piped = _trajectory(build_gas(serial=False), b, steps=2)
    serial = _trajectory(build_gas(serial=True), b, steps=2)
    np.testing.assert_allclose(piped, serial, rtol=1e-6, atol=1e-7)



def test_fp16_streaming_matches_fused_and_skips_on_overflow():
    """fp16 loss scaling through layer streaming (the reference runs fp16
    Infinity): cotangents ride scaled through every per-layer vjp, host
    planes unscale before the C++ Adam, and the overflow vote precedes
    every update — trajectory == fused fp16 ZeRO-3; a poisoned resident
    param skips the step (global_steps AND the Adam counter hold) and
    backs the scaler off."""
    b = {"input_ids": jnp.asarray(
        np.random.RandomState(0).randint(0, 512, size=(8, 32)))}

    def build(streaming):
        groups.reset_mesh()
        mesh = groups.initialize_mesh(MeshLayout.infer(8))
        cfg = LlamaConfig.tiny(num_layers=3, dtype=jnp.float16)
        model = LlamaModel(cfg, mesh=mesh)
        params = model.init_params(jax.random.PRNGKey(0))
        zo = {"stage": 3}
        if streaming:
            zo["offload_param"] = {"device": "cpu"}
        eng, *_ = deepspeed_tpu.initialize(
            model=model, model_parameters=params, mesh=mesh,
            config={"train_micro_batch_size_per_gpu": 8,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "fp16": {"enabled": True, "initial_scale_power": 8,
                             "hysteresis": 1, "loss_scale_window": 2},
                    "zero_optimization": zo})
        return eng

    e1 = build(True)
    assert e1.infinity is not None and e1.infinity.fp16
    l1 = [float(e1.train_step(b)["loss"]) for _ in range(4)]
    e2 = build(False)
    l2 = [float(e2.train_step(b)["loss"]) for _ in range(4)]
    np.testing.assert_allclose(l1, l2, rtol=5e-3, atol=5e-3)
    assert l1[-1] < l1[0]

    # overflow skip: poison a resident master -> fp16 cast inf
    e3 = build(True)
    m0 = e3.train_step(b)
    scale0 = float(m0["loss_scale"])
    steps_before = e3.infinity.global_steps
    adam_before = e3.infinity.swapper.state_step
    engine_step_before = int(e3.state.step)
    poisoned = dict(e3.infinity.resident)
    poisoned["embed"] = e3.infinity.resident["embed"] * 1e38
    e3.infinity.resident = poisoned
    m = e3.train_step(b)
    assert bool(m["overflow"]) is True
    assert e3.infinity.global_steps == steps_before
    assert e3.infinity.swapper.state_step == adam_before
    assert int(e3.state.step) == engine_step_before
    assert float(e3.infinity.scale_state.scale) == scale0 / 2
