"""CLI parity (VERDICT r4 item 9): ``deepspeed --autotuning`` launcher
orchestration and the ``ds_to_universal`` checkpoint converter, both end
to end."""

import json
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu as dst
from deepspeed_tpu.parallel import MeshLayout
from deepspeed_tpu.utils import groups

_REPO = str(pathlib.Path(__file__).resolve().parents[3])


def _make_problem():
    rng = np.random.default_rng(0)
    w_true = rng.normal(size=(16, 1)).astype(np.float32)
    x = rng.normal(size=(64, 16)).astype(np.float32)
    y = (x @ w_true).astype(np.float32)
    params = {"w1": jnp.asarray(
        rng.normal(size=(16, 16)).astype(np.float32) * 0.3),
        "w2": jnp.asarray(rng.normal(size=(16, 1)).astype(np.float32))}

    def loss_fn(p, batch):
        bx, by = batch
        return jnp.mean((jnp.tanh(bx @ p["w1"]) @ p["w2"] - by) ** 2)

    return loss_fn, params, (jnp.asarray(x), jnp.asarray(y))


def test_autotuning_cli_tune_end_to_end(tmp_path):
    """`deepspeed --autotuning tune train.py`: the launcher runs one
    profiling subprocess per candidate (config override + result file via
    the env hooks the runtime honors), ranks measured throughput, and
    writes best_config.json + the full summary."""
    train_py = tmp_path / "train.py"
    train_py.write_text(textwrap.dedent(f"""
        import os, sys
        os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \\
            " --xla_force_host_platform_device_count=8"
        import jax
        jax.config.update("jax_platforms", "cpu")
        sys.path.insert(0, {_REPO!r})
        import numpy as np, jax.numpy as jnp
        import deepspeed_tpu as dst
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(64, 16)).astype(np.float32))
        y = jnp.asarray(rng.normal(size=(64, 1)).astype(np.float32))
        params = {{"w": jnp.asarray(
            rng.normal(size=(16, 1)).astype(np.float32))}}
        def loss_fn(p, b):
            bx, by = b
            return jnp.mean((bx @ p["w"] - by) ** 2)
        engine, _, _, _ = dst.initialize(
            model=loss_fn, model_parameters=params,
            config={{"train_micro_batch_size_per_gpu": 8,
                    "optimizer": {{"type": "Adam",
                                  "params": {{"lr": 1e-2}}}},
                    "zero_optimization": {{"stage": 0}}}})
        # the engine's env hook writes the result file mid-loop
        for _ in range(32):
            engine.train_step((x, y))
    """))

    from deepspeed_tpu.launcher.runner import main as launcher_main

    env_before = dict(os.environ)
    os.environ["DS_AUTOTUNING_SPACE"] = json.dumps(
        {"zero_optimization.stage": [0, 2]})
    os.environ["DS_AUTOTUNING_STEPS"] = "6"
    os.environ["DS_AUTOTUNING_JOB_TIMEOUT_S"] = "240"
    results = tmp_path / "results"
    try:
        rc = launcher_main(["--launcher", "local", "--autotuning", "tune",
                            "--autotuning_results", str(results),
                            str(train_py)])
    finally:
        os.environ.clear()
        os.environ.update(env_before)
    assert rc == 0
    best = json.load(open(results / "best_config.json"))
    assert best["zero_optimization.stage"] in (0, 2)
    summary = json.load(open(results / "autotuning_summary.json"))
    assert len(summary) == 2
    assert all(s["samples_per_sec"] is not None for s in summary)


def test_ds_to_universal_convert_and_load(tmp_path):
    """Save under dp8/ZeRO-2 → ds_to_universal → resume under dp4×tp2/
    ZeRO-3 via load_universal_checkpoint — step counter, fp32 weights AND
    Adam moments carry over, so the trajectory continues exactly."""
    from deepspeed_tpu.utils.ds_to_universal import main as ds2u_main

    loss_fn, params, data = _make_problem()
    groups.reset_mesh()
    groups.initialize_mesh(MeshLayout.infer(8, dp=8))
    cfg = {"train_micro_batch_size_per_gpu": 8,
           "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
           "zero_optimization": {"stage": 2}}
    e1, _, _, _ = dst.initialize(model=loss_fn, model_parameters=params,
                                 config=cfg)
    [float(e1.train_step(data)["loss"]) for _ in range(3)]
    e1.save_checkpoint(str(tmp_path / "ckpt"))
    ref_next = [float(e1.train_step(data)["loss"]) for _ in range(2)]

    rc = ds2u_main(["--input_folder", str(tmp_path / "ckpt"),
                    "--output_folder", str(tmp_path / "universal")])
    assert rc == 0
    meta = json.load(open(tmp_path / "universal"
                          / "universal_metadata.json"))
    assert meta["step"] == 3
    assert all(e["has_moments"] for e in meta["params"].values())
    # canonical layout on disk: per-param fp32 + moments
    assert (tmp_path / "universal" / "zero" / "w1" / "fp32.npy").exists()
    assert (tmp_path / "universal" / "zero" / "w1"
            / "exp_avg.npy").exists()

    loss_fn2, params2, _ = _make_problem()
    groups.reset_mesh()
    groups.initialize_mesh(MeshLayout.infer(8, dp=4, tp=2))
    cfg2 = {"train_micro_batch_size_per_gpu": 8,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
            "zero_optimization": {"stage": 3}}
    e2, _, _, _ = dst.initialize(model=loss_fn2, model_parameters=params2,
                                 config=cfg2)
    e2.load_universal_checkpoint(str(tmp_path / "universal"))
    got = [float(e2.train_step(data)["loss"]) for _ in range(2)]
    np.testing.assert_allclose(got, ref_next, rtol=3e-4, atol=1e-6)
