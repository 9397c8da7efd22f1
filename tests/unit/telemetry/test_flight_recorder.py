"""Flight recorder (ISSUE 2 tentpole): bounded rings, bundle dump/reload
round trip and crash hooks."""

import os
import signal
import sys

import pytest

from deepspeed_tpu.telemetry import (FlightRecorder, StepRecord,
                                     get_telemetry, load_bundle)


def _rec(step, **over):
    kw = dict(step=step, step_time_ms=200.0, device_fenced=True,
              samples_per_sec=20.0, tokens_per_sec=2048.0, loss=1.0,
              grad_norm=0.5, lr=1e-3, loss_scale=1.0, overflow=False,
              skipped_steps=0, comm_bytes=4096, comm_ops=2)
    kw.update(over)
    return StepRecord(**kw)


def test_dump_reload_round_trip(tmp_path):
    hub = get_telemetry()
    hub.configure(enabled=True, jsonl=False, prometheus=False)
    with hub.span("engine/train_step", args={"step": 1}):
        pass
    hub.inc_counter("train/steps_total")

    fr = FlightRecorder(max_records=8, output_path=str(tmp_path))
    for s in range(1, 4):
        fr.record_step(_rec(s))
    fr.record_health({"kind": "loss_spike", "step": 2, "value": 7.0})
    fr.annotate("rendezvous", {"round": 0, "rank": 0})
    fr.register_context("heartbeat_ages",
                        lambda: {"node-b": {"age_s": 42.0, "left": False}})

    path = fr.dump("operator requested", extra={"note": "round trip"})
    assert path == fr.last_bundle_path and os.path.isdir(path)

    bundle = load_bundle(path)
    m = bundle["manifest"]
    assert m["reason"] == "operator requested"
    assert m["extra"]["note"] == "round trip"
    assert [s["step"] for s in m["steps"]] == [1, 2, 3]
    assert m["steps"][-1]["tokens_per_sec"] == 2048.0
    assert m["health_events"][0]["kind"] == "loss_spike"
    assert m["annotations"][0]["kind"] == "rendezvous"
    assert m["context"]["heartbeat_ages"]["node-b"]["age_s"] == 42.0
    assert "train_steps_total 1" in m["metrics_prom"]
    assert m["comm"]["total_bytes"] >= 0
    # side files: Chrome-trace slice, env snapshot, per-thread stacks
    assert any(e["name"] == "engine/train_step"
               for e in bundle["trace"]["traceEvents"])
    assert "jax" in bundle["env_report"]["versions"]
    assert "File" in bundle["stacks"]  # faulthandler stack frames


def test_ring_is_bounded_and_keeps_the_tail(tmp_path):
    fr = FlightRecorder(max_records=4, output_path=str(tmp_path))
    for s in range(10):
        fr.record_step(_rec(s))
    m = load_bundle(fr.dump("bounded"))["manifest"]
    assert [s["step"] for s in m["steps"]] == [6, 7, 8, 9]


def test_broken_context_provider_does_not_kill_the_dump(tmp_path):
    fr = FlightRecorder(output_path=str(tmp_path))
    fr.register_context("dead", lambda: 1 / 0)
    m = load_bundle(fr.dump("resilience"))["manifest"]
    assert "ZeroDivisionError" in m["context"]["dead"]["error"]


def test_excepthook_dumps_then_chains(tmp_path, capsys):
    fr = FlightRecorder(output_path=str(tmp_path))
    fr.install(signals=False, excepthook=True)
    try:
        try:
            raise RuntimeError("induced crash")
        except RuntimeError:
            sys.excepthook(*sys.exc_info())
    finally:
        fr.uninstall()
    assert fr.last_bundle_path is not None
    m = load_bundle(fr.last_bundle_path)["manifest"]
    assert "induced crash" in m["reason"]
    assert "induced crash" in m["extra"]["traceback"]
    # the previous excepthook ran after the dump (traceback on stderr)
    assert "induced crash" in capsys.readouterr().err


def test_signal_handlers_install_and_restore():
    fr = FlightRecorder()
    prev_term = signal.getsignal(signal.SIGTERM)
    fr.install(signals=True, excepthook=False)
    try:
        assert signal.getsignal(signal.SIGTERM) == fr._signal_handler
        assert signal.getsignal(signal.SIGABRT) == fr._signal_handler
    finally:
        fr.uninstall()
    assert signal.getsignal(signal.SIGTERM) == prev_term


def test_bundle_retention_prunes_to_newest_k(tmp_path):
    """Satellite (ISSUE 3): repeated dumps keep only the newest
    ``retain`` bundle dirs — a watchdog stuck in trip cycles cannot
    fill the disk."""
    fr = FlightRecorder(output_path=str(tmp_path), retain=3)
    dumped = [fr.dump(f"trip {i}") for i in range(6)]
    kept = sorted(d for d in os.listdir(tmp_path)
                  if d.startswith("bundle-"))
    assert len(kept) == 3
    # the newest three survived, oldest three are gone
    assert kept == sorted(os.path.basename(p) for p in dumped[-3:])
    assert fr.last_bundle_path == dumped[-1]
    assert os.path.isdir(fr.last_bundle_path)


def test_bundle_retention_disabled_keeps_all(tmp_path):
    fr = FlightRecorder(output_path=str(tmp_path), retain=0)
    for i in range(4):
        fr.dump(f"r{i}")
    assert len([d for d in os.listdir(tmp_path)
                if d.startswith("bundle-")]) == 4


def test_retention_configurable_via_config(tmp_path):
    """The ``telemetry.flight_recorder.retain_bundles`` knob reaches the
    configured global recorder through recorder_from_config."""
    from deepspeed_tpu.runtime.config import DeepSpeedConfig
    from deepspeed_tpu.telemetry.flight_recorder import recorder_from_config

    cfg = DeepSpeedConfig.model_validate({
        "train_batch_size": 8,
        "telemetry": {"enabled": True,
                      "flight_recorder": {"enabled": True,
                                          "output_path": str(tmp_path),
                                          "retain_bundles": 2}}})
    fr = recorder_from_config(cfg.telemetry)
    assert fr is not None and fr.retain == 2
    for i in range(4):
        fr.dump(f"r{i}")
    assert len([d for d in os.listdir(tmp_path)
                if d.startswith("bundle-")]) == 2
