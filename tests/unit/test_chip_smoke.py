"""``chip_smoke.py`` on the CPU: its phases at ``LlamaConfig.tiny()`` (the
kernels through the Pallas interpreter), and its refusal to run — or to
print a result — without a TPU."""

import os
import pathlib
import subprocess
import sys

import jax.numpy as jnp
import pytest

from deepspeed_tpu.models import LlamaConfig

_REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]


@pytest.fixture(autouse=True)
def _scrub_process_globals():
    """The phases configure what an executable owns for its whole life —
    the telemetry hub, the compile tracker, the flight recorder's crash
    hooks.  Inside the suite, hand them back as they were."""
    yield
    from deepspeed_tpu.telemetry import (get_compile_tracker,
                                         get_flight_recorder, get_telemetry)

    get_telemetry().reset()
    get_flight_recorder().reset()
    get_flight_recorder().uninstall()
    tracker = get_compile_tracker()
    tracker.reset()
    tracker.enabled = False


@pytest.fixture(scope="module")
def smoke():
    sys.path.insert(0, str(_REPO_ROOT))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(_REPO_ROOT))
    return chip_smoke


def test_trainer_phase_at_tiny_size(smoke):
    out = smoke.run_trainer(LlamaConfig.tiny(), seq=128, devices=1)
    assert out["layers"] == 4 and out["batch"] == [1, 128]
    assert len(out["losses"]) == smoke.TRAIN_STEPS
    assert out["losses"][-1] < out["losses"][0]
    assert out["compiles_after_first_step"] == 0
    # no Mosaic call off the TPU: the flash entry ran its reference
    assert out["kernels"] == []


def test_trainer_phase_over_eight_devices(smoke):
    """§5's checks on the virtual mesh: ZeRO-3 over data=8, then
    tensor=2 x data=4.  Leaves above the persistence threshold are
    sharded at least 1/dp on all eight devices."""
    cfg = LlamaConfig.tiny(hidden_size=256, vocab_size=1024)
    out = smoke.run_trainer(cfg, seq=128, devices=8)
    assert out["mesh"] == {"data": 8} and out["batch"] == [8, 128]
    assert out["shard_fractions"].get("1/8", 0) > 0
    assert out["collectives"]["all-gather"] > 0
    tp = smoke.run_trainer(cfg, seq=128, devices=8, tensor_parallel=2)
    assert tp["mesh"] == {"data": 4, "tensor": 2}
    # tensor-parallel leaves are split over both axes
    assert tp["shard_fractions"].get("1/8", 0) > 0
    assert tp["losses"][-1] < tp["losses"][0]


def test_server_phase_at_tiny_size(smoke):
    out = smoke.run_server(
        LlamaConfig.tiny(sliding_window=96), prompt_lengths=(5, 40, 70, 130),
        new_tokens=8, prefill_chunk=32, decode_burst=4,
        reference_max_tokens=256)
    assert out["tokens_streamed"] == 4 * 8
    assert out["compiles_after_warmup"] == 0
    assert out["requests_checked_against_forward"] == 4
    assert out["worst_logit_gap"] <= smoke.SERVE_LOGIT_TOL
    # the engine reports the path the entry point chose: off the TPU that
    # is the reference, and the decode burst holds no Mosaic call
    assert out["attn_path"] == "reference" and out["kernels"] == []


def test_kernels_phase_through_the_interpreter(smoke, monkeypatch):
    from deepspeed_tpu.ops.pallas import lattice

    cfg = LlamaConfig.tiny(dtype=jnp.float32, sliding_window=96)
    monkeypatch.setattr(lattice, "RESIDENT_VMEM_ELEMS",
                        cfg.max_seq_len * cfg.hd)
    out = smoke.run_kernels(cfg, interpret=True)
    names = set(out["checks"])
    for family in ("flash_resident_fwd", "flash_resident_segments_dk",
                   "flash_streamed_dq", "decode_attention",
                   "paged_decode(window=96)", "fused_adam_param",
                   "tree_sqsum", "moe_dispatch_mismatched_elements",
                   "moe_combine", "quantizer_codes", "block_sparse_dv"):
        assert family in names, (family, sorted(names))
    # the latent cache's two calls: a token a row, a chunk's several
    assert [n for n in names if "latent" in n and "tokens a row" in n], names


def test_refuses_to_run_without_a_tpu():
    """``JAX_PLATFORMS=cpu python chip_smoke.py`` exits non-zero before
    building any model, names the platform it found, and prints no
    result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=_REPO_ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "not a TPU" in proc.stderr
    assert '"platform": "cpu"' in proc.stdout
    for line in proc.stdout.splitlines():
        assert not line.startswith("{"), line
    assert "initialize" not in proc.stdout      # no engine was built


def test_last_line_is_ok_and_device_and_nothing_else(smoke, monkeypatch,
                                                     capsys):
    """Whoever runs the smoke parses its last line only: exactly ``ok`` and
    ``device`` (platform, kind, count).  The phase facts ride the line
    before it, which ends with ``"claim": null``."""
    import json
    import types

    from deepspeed_tpu.utils import compile_cache

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(smoke, "device_gate", lambda: dict(device))
    monkeypatch.setattr(smoke, "CompileClock",
                        lambda: types.SimpleNamespace(seconds=0.0))
    monkeypatch.setattr(smoke, "timed_phase",
                        lambda clock, label, fn, *a, **k: {"phase": label})
    monkeypatch.setattr(compile_cache, "configure_compile_cache",
                        lambda: "/somewhere/.jax_cache")
    assert smoke.main() == 0
    *_, summary, last = capsys.readouterr().out.splitlines()
    assert json.loads(last) == {"ok": True, "device": device}
    assert list(json.loads(last)) == ["ok", "device"]
    assert summary.startswith("[chip_smoke] summary: ")
    assert summary.endswith('"claim": null}')
    facts = json.loads(summary.split("summary: ", 1)[1])
    assert set(facts["phases"]) == {"trainer", "server", "kernels"}


def test_no_option_skips_the_gate_or_shrinks_the_model(smoke):
    """The executable reads no argument and no environment variable."""
    src = (_REPO_ROOT / "chip_smoke.py").read_text()
    for needle in ("argparse", "sys.argv", "os.environ", "getenv"):
        assert needle not in src, needle


def test_a_dropped_engine_is_collected():
    """The phases run in one process on one chip, so an engine the caller
    drops must give its train state back: nothing process-global (the
    flight recorder's context providers) may keep it alive."""
    import gc
    import weakref

    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models import LlamaModel

    cfg = LlamaConfig.tiny(num_layers=1)
    model = LlamaModel(cfg)
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init_params(jax.random.PRNGKey(0)),
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                "telemetry": {"enabled": True, "jsonl": False,
                              "prometheus": False}})
    # initialize() lays its own mesh over the 8 virtual devices
    engine.train_step({"input_ids": jax.numpy.zeros((8, 32), "int32")})
    ref = weakref.ref(engine)
    del engine
    gc.collect()
    assert ref() is None
