"""Watchdog device-liveness probe — bounded time, annotated bundle."""

import json
import os
import time

from deepspeed_tpu.telemetry import FlightRecorder, HangWatchdog
from deepspeed_tpu.telemetry.memory import (device_unresponsive,
                                            probe_device_liveness)


def _hang_forever():
    time.sleep(3600)


def test_probe_alive_fast_path():
    out = probe_device_liveness(5.0, probe_fn=lambda: {"ok": True})
    assert out["alive"] is True
    assert out["detail"] == {"ok": True}
    assert device_unresponsive() is None


def test_probe_timeout_latches_unresponsive():
    t0 = time.monotonic()
    out = probe_device_liveness(0.2, probe_fn=_hang_forever)
    elapsed = time.monotonic() - t0
    assert elapsed < 5.0, "probe must be BOUNDED (thread + deadline)"
    assert out["alive"] is False and out.get("timed_out")
    assert "unresponsive" in out["detail"]
    # the latch: later device introspection skips the device entirely
    assert device_unresponsive() is not None
    from deepspeed_tpu.telemetry.memory import get_memory_ledger

    led = get_memory_ledger()
    led.configure(enabled=True)
    led._device_stats_fn = _hang_forever  # would hang if consulted
    assert led.device_stats() == {}
    from deepspeed_tpu.utils.memory import memory_status

    s = memory_status()  # must return host numbers without hanging
    assert "process_rss_GB" in s and "device_in_use_GB" not in s


def test_probe_error_is_responsive_but_unhealthy():
    def broken():
        raise RuntimeError("backend exploded")

    out = probe_device_liveness(5.0, probe_fn=broken)
    assert out["alive"] is False and not out.get("timed_out")
    # an ANSWERED error is not a hang — the latch stays clear
    assert device_unresponsive() is None


def test_watchdog_trip_with_hanging_backend_is_bounded(tmp_path):
    """Acceptance (ISSUE 7): a device runtime that never answers produces
    a fail-fast bundle with a device_unresponsive annotation instead of
    an unbounded hang."""
    clock = {"t": 0.0}
    recorder = FlightRecorder(output_path=str(tmp_path))
    wd = HangWatchdog(hang_timeout_s=10.0, action="log",
                      comm_liveness=False, clock=lambda: clock["t"],
                      recorder=recorder,
                      device_probe=True, device_probe_timeout_s=0.2)
    wd.device_probe_fn = _hang_forever  # a backend that never answers
    wd.notify_progress(1, 0.1)
    clock["t"] = 100.0  # way past the hang timeout
    t0 = time.monotonic()
    assert wd.check() is True
    elapsed = time.monotonic() - t0
    assert elapsed < 30.0, f"trip path must be bounded, took {elapsed:.1f}s"
    bundle = recorder.last_bundle_path
    assert bundle is not None
    with open(os.path.join(bundle, "bundle.json")) as fh:
        manifest = json.load(fh)
    assert "device unresponsive" in manifest["reason"]
    assert manifest["extra"]["device_unresponsive"] is True
    probe = manifest["extra"]["device_probe"]
    assert probe["alive"] is False and probe["timed_out"]
    # the memory_status context provider ran WITHOUT touching the dead
    # device (the latch was set before the dump)
    assert wd.trips == 1


def test_watchdog_answered_error_is_not_unresponsive(tmp_path):
    """A probe the runtime ANSWERS with an error is responsive-but-
    unhealthy: no device_unresponsive annotation, no dead-device
    headline — the operator must chase the real hang cause."""
    clock = {"t": 0.0}
    recorder = FlightRecorder(output_path=str(tmp_path))
    wd = HangWatchdog(hang_timeout_s=10.0, action="log",
                      comm_liveness=False, clock=lambda: clock["t"],
                      recorder=recorder,
                      device_probe=True, device_probe_timeout_s=5.0)

    def broken():
        raise RuntimeError("backend init error")

    wd.device_probe_fn = broken
    wd.notify_progress(1, 0.1)
    clock["t"] = 100.0
    assert wd.check() is True
    with open(os.path.join(recorder.last_bundle_path,
                           "bundle.json")) as fh:
        manifest = json.load(fh)
    assert "device_unresponsive" not in manifest["extra"]
    assert "device unresponsive" not in manifest["reason"]
    assert manifest["extra"]["device_probe"]["alive"] is False
    assert device_unresponsive() is None  # latch stays clear


def test_watchdog_probe_disabled_skips_probe(tmp_path):
    clock = {"t": 0.0}
    recorder = FlightRecorder(output_path=str(tmp_path))
    wd = HangWatchdog(hang_timeout_s=10.0, action="log",
                      comm_liveness=False, clock=lambda: clock["t"],
                      recorder=recorder, device_probe=False)
    wd.device_probe_fn = _hang_forever  # must never be called
    wd.notify_progress(1, 0.1)
    clock["t"] = 100.0
    assert wd.check() is True
    with open(os.path.join(recorder.last_bundle_path,
                           "bundle.json")) as fh:
        manifest = json.load(fh)
    assert "device_probe" not in manifest["extra"]


def test_heartbeat_payload_carries_memory_summary():
    from deepspeed_tpu.telemetry.memory import get_memory_ledger

    led = get_memory_ledger()
    led.configure(enabled=True)
    led._device_stats_fn = lambda: {
        "bytes_in_use": 8 << 30, "bytes_limit": 16 << 30,
        "peak_bytes_in_use": 12 << 30}
    led.step_sample()
    wd = HangWatchdog(hang_timeout_s=10.0, device_probe=False)
    payload = wd.heartbeat_payload()
    assert payload["hbm_frac"] == 0.5
    assert payload["hbm_headroom"] == 0.25
