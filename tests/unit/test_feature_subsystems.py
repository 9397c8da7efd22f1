"""Feature subsystems: elasticity, autotuning, compression, launcher,
zero.Init/GatheredParameters, activation checkpointing, tp_model_init,
env report, zero_to_fp32."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.parallel import MeshLayout
from deepspeed_tpu.utils import groups


# ---------------------------------------------------------------- elasticity

def test_elasticity_envelope():
    from deepspeed_tpu.elasticity import compute_elastic_config

    cfg = {"elasticity": {"enabled": True, "max_train_batch_size": 100,
                          "micro_batch_sizes": [2, 4], "min_gpus": 1,
                          "max_gpus": 64}}
    elastic, batch = compute_elastic_config(cfg)
    assert batch <= 100 and elastic["valid_gpus"]
    # resolve for a concrete world
    elastic, batch, micro = compute_elastic_config(
        cfg, world_size=4, return_microbatch=True)
    assert batch % micro == 0


def test_elasticity_disabled_raises():
    from deepspeed_tpu.elasticity import compute_elastic_config
    from deepspeed_tpu.elasticity.elasticity import ElasticityError

    with pytest.raises(ElasticityError):
        compute_elastic_config({"elasticity": {"enabled": False}})


# ---------------------------------------------------------------- autotuning

def test_autotuner_picks_best():
    import deepspeed_tpu
    from deepspeed_tpu.autotuning import Autotuner
    from deepspeed_tpu.models import LlamaConfig, LlamaModel

    mesh = groups.initialize_mesh(MeshLayout.infer(8, dp=8))
    cfg = LlamaConfig.tiny(num_layers=1, dtype=jnp.float32)

    def engine_factory(ds_cfg):
        model = LlamaModel(cfg, mesh=mesh)
        params = model.init_params(jax.random.PRNGKey(0))
        engine, *_ = deepspeed_tpu.initialize(
            model=model, model_parameters=params, config=ds_cfg, mesh=mesh)
        return engine

    def batch_factory(ds_cfg):
        b = int(ds_cfg["train_micro_batch_size_per_gpu"])
        return {"input_ids": jnp.zeros((b, 32), jnp.int32)}

    base = {"train_micro_batch_size_per_gpu": 8,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 0}}
    tuner = Autotuner(engine_factory, batch_factory, base,
                      tuning_space={"zero_optimization.stage": [0, 3],
                                    "train_micro_batch_size_per_gpu": [8]},
                      timed_steps=1)
    result = tuner.tune()
    assert result["throughput"] > 0
    assert result["best_combo"]["train_micro_batch_size_per_gpu"] == 8
    assert len(result["records"]) == 2


# --------------------------------------------------------------- compression

def test_compression_fake_quant_and_prune():
    from deepspeed_tpu.compression import (fake_quantize, init_compression,
                                           redundancy_clean)

    x = jnp.asarray(np.random.RandomState(0).randn(16, 16), jnp.float32)
    q = fake_quantize(x, bits=8)
    assert float(jnp.abs(q - x).max()) < float(jnp.abs(x).max()) / 100
    # STE gradient is identity-shaped
    g = jax.grad(lambda t: jnp.sum(fake_quantize(t) * 2))(x)
    np.testing.assert_allclose(np.asarray(g), 2.0, atol=1e-5)

    ds_cfg = {"compression_training": {
        "weight_quantization": {"shared_parameters": {"enabled": True}},
        "sparse_pruning": {"shared_parameters": {"enabled": True,
                                                 "dense_ratio": 0.5}}}}

    class M:
        def loss(self, params, batch):
            return jnp.sum(params["w"] * batch)

        def forward(self, params, batch):
            return params["w"] * batch

    params = {"w": x}
    cm = init_compression(M(), ds_cfg)
    out = cm.forward(params, jnp.float32(1.0))
    assert float(jnp.mean(out == 0)) >= 0.45  # ~half pruned
    cleaned = redundancy_clean(params, ds_cfg)
    assert float(jnp.mean(cleaned["w"] == 0)) >= 0.45


# ------------------------------------------------------------------ launcher

def test_launcher_hostfile_parsing(tmp_path):
    from deepspeed_tpu.launcher.runner import filter_hosts, parse_hostfile

    hf = tmp_path / "hostfile"
    hf.write_text("worker-0 slots=4\nworker-1 slots=4\n# comment\nworker-2 slots=8\n")
    hosts = parse_hostfile(str(hf))
    assert hosts == {"worker-0": 4, "worker-1": 4, "worker-2": 8}
    kept = filter_hosts(hosts, include="worker-0@worker-2")
    assert set(kept) == {"worker-0", "worker-2"}
    kept = filter_hosts(hosts, exclude="worker-1")
    assert set(kept) == {"worker-0", "worker-2"}


def test_launcher_local_exec(tmp_path):
    from deepspeed_tpu.launcher.runner import main

    script = tmp_path / "train.py"
    out = tmp_path / "out.txt"
    script.write_text(
        "import os, pathlib\n"
        f"pathlib.Path({str(out)!r}).write_text("
        "os.environ['RANK'] + '/' + os.environ['WORLD_SIZE'])\n")
    rc = main(["--launcher", "local", str(script)])
    assert rc == 0
    assert out.read_text() == "0/1"


# ---------------------------------------------------- zero.Init / Gathered

def test_zero_init_materializes_sharded():
    import deepspeed_tpu

    mesh = groups.initialize_mesh(MeshLayout.infer(8, dp=8))

    def init_fn(rng):
        return {"w": jax.random.normal(rng, (64, 32)),
                "b": jnp.zeros((32,))}

    with deepspeed_tpu.zero.Init(config_dict_or_path={
            "zero_optimization": {
                "stage": 3,
                # below the default persistence threshold the policy would
                # (correctly) keep these small test arrays replicated
                "stage3_param_persistence_threshold": 0}}, mesh=mesh) as zinit:
        params = zinit.materialize(init_fn, jax.random.PRNGKey(0))
    # large leaf sharded over the 8-way dp axis
    w_shard = params["w"].sharding
    assert w_shard.shard_shape(params["w"].shape)[0] == 8


def test_gathered_parameters_roundtrip():
    from deepspeed_tpu.runtime.zero import GatheredParameters

    mesh = groups.initialize_mesh(MeshLayout.infer(8, dp=8))
    p = {"w": jax.device_put(jnp.ones((16, 4)))}
    with GatheredParameters(p, modifier_rank=0) as full:
        full["w"][:] = 7.0
    ctx = GatheredParameters(p, modifier_rank=0)
    with ctx as full:
        full["w"][:] = 7.0
    np.testing.assert_allclose(np.asarray(ctx.result["w"]), 7.0)


# ---------------------------------------------- activation checkpointing api

def test_activation_checkpointing_api():
    from deepspeed_tpu.runtime.activation_checkpointing import (checkpoint,
                                                                configure)

    configure(partition_activations=True)
    x = jnp.arange(8.0)
    y = checkpoint(lambda t: jnp.sum(jnp.sin(t) ** 2), x)
    np.testing.assert_allclose(float(y), float(jnp.sum(jnp.sin(x) ** 2)),
                               rtol=1e-6)
    g = jax.grad(lambda t: checkpoint(lambda u: jnp.sum(jnp.sin(u) ** 2), t))(x)
    assert g.shape == x.shape


# ------------------------------------------------------------- tp_model_init

def test_tp_model_init_binds_mesh():
    import deepspeed_tpu
    from deepspeed_tpu.models import LlamaConfig, LlamaModel

    groups.reset_mesh()
    model = LlamaModel(LlamaConfig.tiny(num_layers=1, dtype=jnp.float32))
    model = deepspeed_tpu.tp_model_init(model, tp_size=2)
    assert int(model.mesh.shape["tensor"]) == 2


# ----------------------------------------------------------------- ds_report

def test_env_report_runs():
    from deepspeed_tpu.env_report import cli_main

    cli_main()  # must not raise


# -------------------------------------------------------------- zero_to_fp32

def test_zero_to_fp32_export(tmp_path):
    import deepspeed_tpu
    from deepspeed_tpu.models import LlamaConfig, LlamaModel
    from deepspeed_tpu.utils.zero_to_fp32 import \
        get_fp32_state_dict_from_zero_checkpoint

    mesh = groups.initialize_mesh(MeshLayout.infer(8, dp=8))
    cfg = LlamaConfig.tiny(num_layers=1, dtype=jnp.float32)
    model = LlamaModel(cfg, mesh=mesh)
    params = model.init_params(jax.random.PRNGKey(0))
    ds = {"train_micro_batch_size_per_gpu": 4,
          "gradient_accumulation_steps": 1,
          "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
          "zero_optimization": {"stage": 3}}
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=ds, mesh=mesh)
    engine.save_checkpoint(str(tmp_path))
    assert os.path.exists(tmp_path / "zero_to_fp32.py")
    sd = get_fp32_state_dict_from_zero_checkpoint(str(tmp_path))
    assert any("embed" in k for k in sd)
    total = sum(v.size for v in sd.values())
    assert total == cfg.num_params()
    # consolidated 16-bit export
    sd16 = engine._zero3_consolidated_16bit_state_dict()
    assert jax.tree.leaves(sd16)[0].dtype == jnp.bfloat16


def test_compression_structured_row_and_head_pruning():
    """row_pruning zeroes whole output channels; head_pruning zeroes whole
    attention heads (name-matched attn leaves)."""
    import jax
    import numpy as np
    from deepspeed_tpu.compression import redundancy_clean

    rng = np.random.RandomState(0)
    params = {
        "layers": {
            "attn": {"wq": jnp.asarray(rng.randn(2, 16, 4, 8) * 0.1),
                     "wk": jnp.asarray(rng.randn(2, 16, 4, 8) * 0.1),
                     "wv": jnp.asarray(rng.randn(2, 16, 4, 8) * 0.1),
                     "wo": jnp.asarray(rng.randn(2, 4, 8, 16) * 0.1)},
            "mlp": {"w_up": jnp.asarray(rng.randn(2, 16, 32) * 0.1)},
        },
    }
    cfg = {"compression_training": {
        "row_pruning": {"shared_parameters": {"enabled": True,
                                              "dense_ratio": 0.5}},
        "head_pruning": {"shared_parameters": {"enabled": True,
                                               "dense_ratio": 0.5}}}}
    out = redundancy_clean(params, cfg)
    # head pruning: exactly 2 of 4 heads fully zero in wq (dim -2)
    wq = np.asarray(out["layers"]["attn"]["wq"])
    head_zero = (np.abs(wq).sum(axis=(0, 1, 3)) == 0)
    assert head_zero.sum() == 2
    # the surviving heads are untouched
    # row pruning: half the mlp output channels zeroed
    wu = np.asarray(out["layers"]["mlp"]["w_up"])
    col_zero = (np.abs(wu).sum(axis=(0, 1)) == 0)
    assert col_zero.sum() == 16
    # wo heads (dim -3) pruned too
    wo = np.asarray(out["layers"]["attn"]["wo"])
    assert (np.abs(wo).sum(axis=(0, 2, 3)) == 0).sum() == 2


def test_layer_reduction_and_distillation():
    import jax
    import numpy as np
    from deepspeed_tpu.compression import (apply_layer_reduction,
                                           knowledge_distillation_loss,
                                           student_initialize)

    teacher = {"embed": jnp.ones((4, 8)),
               "layers": {"w": jnp.arange(6, dtype=jnp.float32
                                          ).reshape(6, 1) * jnp.ones((6, 3))}}
    student = apply_layer_reduction(teacher, [0, 2, 4])
    assert student["layers"]["w"].shape == (3, 3)
    np.testing.assert_array_equal(np.asarray(student["layers"]["w"][:, 0]),
                                  [0, 2, 4])
    # student_initialize honors keep_number_layer spacing
    cfg = {"compression_training": {"layer_reduction": {
        "enabled": True, "keep_number_layer": 2}}}
    s2 = student_initialize(None, teacher, cfg)
    assert s2["layers"]["w"].shape[0] == 2

    # KD loss: equals CE at alpha=0, pure KL at alpha=1 (0 when t==s)
    logits = jnp.asarray(np.random.RandomState(1).randn(4, 10),
                         jnp.float32)
    labels = jnp.asarray([1, 2, 3, 4])
    kd_same = knowledge_distillation_loss(logits, logits, labels, alpha=1.0)
    assert abs(float(kd_same)) < 1e-5
    ce_only = knowledge_distillation_loss(logits, logits * 0, labels,
                                          alpha=0.0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    want_ce = -float(jnp.mean(jnp.take_along_axis(
        logp, labels[:, None], axis=1)))
    assert abs(float(ce_only) - want_ce) < 1e-5


def test_head_pruning_mask_consistent_across_qkvo():
    """One keep-mask per attention group: the SAME heads zero in all of
    wq/wk/wv/wo (per-leaf masks would leave half-pruned heads emitting
    their mean value through a surviving wo)."""
    import numpy as np
    from deepspeed_tpu.compression import redundancy_clean

    rng = np.random.RandomState(7)
    params = {"attn": {
        "wq": jnp.asarray(rng.randn(2, 16, 4, 8) * 0.1),
        "wk": jnp.asarray(rng.randn(2, 16, 4, 8) * 0.1),
        "wv": jnp.asarray(rng.randn(2, 16, 4, 8) * 0.1),
        "wo": jnp.asarray(rng.randn(2, 4, 8, 16) * 0.1)}}
    cfg = {"compression_training": {"head_pruning": {
        "shared_parameters": {"enabled": True, "dense_ratio": 0.5}}}}
    out = redundancy_clean(params, cfg)
    zq = np.abs(np.asarray(out["attn"]["wq"])).sum(axis=(0, 1, 3)) == 0
    zk = np.abs(np.asarray(out["attn"]["wk"])).sum(axis=(0, 1, 3)) == 0
    zv = np.abs(np.asarray(out["attn"]["wv"])).sum(axis=(0, 1, 3)) == 0
    zo = np.abs(np.asarray(out["attn"]["wo"])).sum(axis=(0, 2, 3)) == 0
    assert zq.sum() == 2
    np.testing.assert_array_equal(zq, zk)
    np.testing.assert_array_equal(zq, zv)
    np.testing.assert_array_equal(zq, zo)


def test_memory_and_nvtx_utils():
    from deepspeed_tpu.utils import (instrument_w_nvtx, memory_status,
                                     see_memory_usage)
    from deepspeed_tpu.utils.numa import get_numa_nodes, pin_to_numa_node

    s = memory_status()
    assert isinstance(s, dict)
    see_memory_usage("unit test", force=True)  # logs, must not raise

    calls = []

    @instrument_w_nvtx
    def hot(x):
        calls.append(x)
        return x + 1

    assert hot(1) == 2 and calls == [1]

    nodes = get_numa_nodes()
    assert 0 in nodes and len(nodes[0]) >= 1
    # pinning mutates process affinity + OMP env — restore so later tests
    # keep the whole machine
    before_aff = os.sched_getaffinity(0)
    before_omp = os.environ.get("OMP_NUM_THREADS")
    try:
        cores = pin_to_numa_node(0)
        assert len(cores) >= 1
    finally:
        os.sched_setaffinity(0, before_aff)
        if before_omp is None:
            os.environ.pop("OMP_NUM_THREADS", None)
        else:
            os.environ["OMP_NUM_THREADS"] = before_omp


def test_wall_clock_breakdown_logging():
    import deepspeed_tpu
    from deepspeed_tpu.models import LlamaConfig, LlamaModel
    from deepspeed_tpu.parallel import MeshLayout
    from deepspeed_tpu.utils import groups

    groups.reset_mesh()
    cfg = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32)
    mesh = groups.initialize_mesh(MeshLayout.infer(8, dp=8))
    model = LlamaModel(cfg, mesh=mesh)
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init_params(jax.random.PRNGKey(0)),
        mesh=mesh,
        config={"train_micro_batch_size_per_gpu": 8,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 0},
                "steps_per_print": 1, "wall_clock_breakdown": True})
    import numpy as np
    batch = {"input_ids": jnp.asarray(
        np.random.RandomState(0).randint(0, 512, size=(8, 16)))}
    import io
    import logging

    from deepspeed_tpu.utils.logging import logger as ds_logger

    stream = io.StringIO()
    handler = logging.StreamHandler(stream)
    ds_logger.addHandler(handler)
    try:
        engine.train_step(batch)
    finally:
        ds_logger.removeHandler(handler)
    out = stream.getvalue()
    assert "step_time=" in out and "samples/s=" in out


def test_activation_quantization_wired():
    """activation_quantization: init_compression arms the model's QuantAct
    hook; loss changes but training still converges, STE keeps gradients."""
    import deepspeed_tpu
    from deepspeed_tpu.compression import init_compression
    from deepspeed_tpu.compression.quantization import quantize_activation
    from deepspeed_tpu.models import LlamaConfig, LlamaModel
    from deepspeed_tpu.parallel import MeshLayout

    # primitive: 2-bit quantization leaves few distinct values, STE grad = 1
    x = jnp.asarray(np.linspace(-1, 1, 64), jnp.float32)
    q = quantize_activation(x, bits=2)
    assert len(np.unique(np.asarray(q).round(5))) <= 4
    g = jax.grad(lambda t: jnp.sum(quantize_activation(t, 2)))(x)
    np.testing.assert_allclose(np.asarray(g), 1.0, atol=1e-6)

    groups.reset_mesh()
    cfg = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32)
    mesh = groups.initialize_mesh(MeshLayout.infer(8, dp=8))
    model = LlamaModel(cfg, mesh=mesh)
    params = model.init_params(jax.random.PRNGKey(0))
    batch = {"input_ids": jnp.asarray(
        np.random.RandomState(0).randint(0, 512, size=(8, 32)))}
    plain_loss = float(model.loss(params, batch))

    cm = init_compression(model, {"compression_training": {
        "activation_quantization": {"shared_parameters": {
            "enabled": True, "bits": 4}}}})
    assert model.act_quant_bits == 4
    aq_loss = float(cm.loss(params, batch))
    assert aq_loss != plain_loss            # quantization is in the graph
    params_host = jax.device_get(params)    # engine donates the originals
    engine, *_ = deepspeed_tpu.initialize(
        model=cm, model_parameters=params, mesh=mesh,
        config={"train_micro_batch_size_per_gpu": 8,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 0}, "steps_per_print": 0})
    first = float(engine.train_step(batch)["loss"])
    for _ in range(6):
        last = float(engine.train_step(batch)["loss"])
    assert last < first
    # re-wrapping WITHOUT the config disarms the hook (no state leak)
    init_compression(model, {})
    assert model.act_quant_bits is None
    np.testing.assert_allclose(float(model.loss(params_host, batch)),
                               plain_loss, rtol=1e-6)


def test_model_based_tuner_finds_best_with_fewer_measurements():
    """ModelBasedTuner (reference ModelBasedTuner role): a synthetic
    throughput landscape with additive structure — the tuner must find the
    argmax while MEASURING fewer candidates than the 12-point grid."""
    from deepspeed_tpu.autotuning import ModelBasedTuner

    space = {"zero_optimization.stage": [0, 1, 2],
             "train_micro_batch_size_per_gpu": [1, 2, 4, 8]}
    # separable landscape: stage effect x batch effect, best at (1, 4)
    stage_gain = {0: 1.0, 1: 1.3, 2: 1.1}
    batch_gain = {1: 0.5, 2: 0.9, 4: 1.2, 8: 1.0}
    measured = []

    class FakeEngine:
        def __init__(self, cfg):
            self.cfg = cfg
            self.train_batch_size = 1

        def train_step(self, batch):
            s = self.cfg["zero_optimization"]["stage"]
            b = self.cfg["train_micro_batch_size_per_gpu"]
            measured.append((s, b))
            self._dt = 1.0 / (stage_gain[s] * batch_gain[b])
            import time as _t
            _t.sleep(self._dt * 1e-2)
            return {"loss": 0.0}

    tuner = ModelBasedTuner(lambda cfg: FakeEngine(cfg), lambda cfg: {},
                            {"zero_optimization": {"stage": 0},
                             "train_micro_batch_size_per_gpu": 1},
                            tuning_space=space, warmup_steps=0,
                            timed_steps=3, seed_measurements=4,
                            measure_budget=8)
    result = tuner.tune()
    assert result["best_combo"] == {"zero_optimization.stage": 1,
                                    "train_micro_batch_size_per_gpu": 4}
    n_measured = len({m for m in measured})
    assert n_measured < 12  # strictly fewer than the grid
    pruned = [r for r in result["records"] if r.get("pruned") == "perf_model"]
    assert pruned and all("predicted" in r for r in pruned)
