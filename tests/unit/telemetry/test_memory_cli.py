"""`mem` CLI — show/top/diff exit codes; perf-check no-data skip."""

import json
import os

import pytest

from deepspeed_tpu.telemetry import FlightRecorder
from deepspeed_tpu.telemetry.cli import main as cli_main
from deepspeed_tpu.telemetry.memory import get_memory_ledger


def _dump_bundle(tmp_path, name, mutate=None):
    """One bundle whose manifest carries context.memory from the global
    ledger (the configure_memory_ledger(recorder=...) wiring)."""
    led = get_memory_ledger()
    led.configure(enabled=True)
    if mutate:
        mutate(led)
    recorder = FlightRecorder(output_path=str(tmp_path / name))
    recorder.register_context("memory", led.snapshot)
    return recorder.dump(f"cli test {name}")


def test_mem_show_reads_manifest_context(tmp_path, capsys):
    bundle = _dump_bundle(
        tmp_path, "a",
        mutate=lambda led: led.register("params", "p", 2 << 30))
    assert cli_main(["mem", "show", bundle]) == 0
    out = capsys.readouterr().out
    assert "params" in out and "2.0GiB" in out


def test_mem_show_prefers_memory_json(tmp_path, capsys):
    bundle = _dump_bundle(tmp_path, "a")
    with open(os.path.join(bundle, "memory.json"), "w") as fh:
        json.dump({"pools_hbm_bytes": {"kv_cache": 1 << 30},
                   "tracked_bytes": 1 << 30,
                   "host_rss_bytes": 3 << 30}, fh)
    assert cli_main(["mem", "show", bundle]) == 0
    out = capsys.readouterr().out
    assert "kv_cache" in out


def test_mem_top_lists_largest_arrays(tmp_path, capsys):
    bundle = _dump_bundle(tmp_path, "a")
    with open(os.path.join(bundle, "memory.json"), "w") as fh:
        json.dump({"live_census": {
            "count": 2, "total_bytes": 3000,
            "top": [{"nbytes": 2000, "shape": [10, 50], "dtype": "float32",
                     "pool": "params"},
                    {"nbytes": 1000, "shape": [500], "dtype": "int32",
                     "pool": "untracked"}]}}, fh)
    assert cli_main(["mem", "top", bundle]) == 0
    out = capsys.readouterr().out
    assert "10x50" in out and "pool=params" in out


def test_mem_top_without_census_fails_cleanly(tmp_path):
    bundle = _dump_bundle(tmp_path, "a")
    assert cli_main(["mem", "top", bundle]) == 2


def test_mem_diff_zero_then_three(tmp_path, capsys):
    """Acceptance: identical bundles diff clean (0); a pool that grew
    beyond the thresholds produces the leak verdict (3)."""
    a = _dump_bundle(
        tmp_path, "a",
        mutate=lambda led: led.register("snapshot", "t0", 1 << 30,
                                        space="host"))
    assert cli_main(["mem", "diff", a, a]) == 0
    assert "no leak detected" in capsys.readouterr().out

    b = _dump_bundle(
        tmp_path, "b",
        mutate=lambda led: led.register("snapshot", "t0", 3 << 30,
                                        space="host"))
    rc = cli_main(["mem", "diff", a, b])
    out = capsys.readouterr().out
    assert rc == 3
    assert "LEAK VERDICT" in out and "snapshot" in out


def test_mem_diff_small_growth_under_floor_is_clean(tmp_path, capsys):
    a = _dump_bundle(
        tmp_path, "a",
        mutate=lambda led: led.register("params", "p", 1 << 30))
    b = _dump_bundle(
        tmp_path, "b",
        mutate=lambda led: led.register("params", "p", (1 << 30) + (1 << 20)))
    assert cli_main(["mem", "diff", a, b]) == 0


def test_mem_diff_missing_memory_section(tmp_path):
    led = get_memory_ledger()
    led.enabled = False
    recorder = FlightRecorder(output_path=str(tmp_path / "bare"))
    bare = recorder.dump("no memory context")
    assert cli_main(["mem", "diff", bare, bare]) == 2


# ---------------------------------------------------------------------------
# perf check: a no-data artifact SKIPS with a named reason (ISSUE 7 sat.)
# ---------------------------------------------------------------------------

@pytest.fixture
def baseline_file(tmp_path):
    run = tmp_path / "run.json"
    run.write_text(json.dumps({
        "metric": "llama_110m_train_tokens_per_sec", "value": 35000.0,
        "mfu": 0.4, "step_time_p50_ms": 100.0, "goodput": 0.9,
        "peak_hbm_bytes": 8 << 30, "hbm_headroom_frac": 0.4}))
    base = tmp_path / "base.json"
    assert cli_main(["perf", "baseline", str(run),
                     "--out", str(base)]) == 0
    return run, base


def test_perf_check_skips_r05_style_empty_run(tmp_path, baseline_file,
                                              capsys):
    _, base = baseline_file
    capsys.readouterr()
    empty = tmp_path / "r05.json"
    # the EXACT r05 shape: value 0.0 + error, no sentinel metrics
    empty.write_text(json.dumps({
        "metric": "llama_110m_train_tokens_per_sec", "value": 0.0,
        "unit": "tokens/sec/chip", "vs_baseline": 0.0,
        "error": "jax.devices() unresponsive after 180s"}))
    rc = cli_main(["perf", "check", str(empty), "--baseline", str(base)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "SKIPPED" in out and "unresponsive" in out


def test_perf_check_skips_environment_failure_marker(tmp_path,
                                                     baseline_file, capsys):
    _, base = baseline_file
    capsys.readouterr()
    marked = tmp_path / "marked.json"
    marked.write_text(json.dumps({
        "metric": "llama_110m_train_tokens_per_sec", "value": 0.0,
        "error": "device probe failed", "environment_failure": True}))
    rc = cli_main(["perf", "check", str(marked), "--baseline", str(base)])
    assert rc == 0
    assert "environment failure" in capsys.readouterr().out


def test_perf_check_does_not_skip_bench_crash_lines(tmp_path,
                                                    baseline_file):
    """A CRASHED bench (code regression) also prints value 0 + error —
    but with a debug_bundle key and no marker.  That must stay a loud
    failure of the gate, never a skip."""
    _, base = baseline_file
    crash = tmp_path / "crash.json"
    crash.write_text(json.dumps({
        "metric": "llama_110m_train_tokens_per_sec", "value": 0.0,
        "error": "AssertionError: kernel numerics",
        "debug_bundle": "debug_bundles/bundle-x"}))
    assert cli_main(["perf", "check", str(crash),
                     "--baseline", str(base)]) == 2


def test_mem_show_memory_status_fallback_is_space_unknown(tmp_path,
                                                          capsys):
    """memory_status merges hbm+host per pool — the fallback must not
    render host-only pools (offload masters, snapshot buffers) in an
    HBM column."""
    bundle = tmp_path / "bundle-x"
    bundle.mkdir()
    (bundle / "bundle.json").write_text(json.dumps({
        "reason": "t", "context": {"memory_status": {
            "process_rss_GB": 1.0, "pool_snapshot_GB": 4.0}}}))
    assert cli_main(["mem", "show", str(bundle)]) == 0
    out = capsys.readouterr().out
    assert "merged" in out and "snapshot" in out and "4.0GiB" in out
    assert "hbm / host" not in out
    # and diff still verdicts on these space-unknown pools
    grown = tmp_path / "bundle-y"
    grown.mkdir()
    (grown / "bundle.json").write_text(json.dumps({
        "reason": "t", "context": {"memory_status": {
            "process_rss_GB": 1.0, "pool_snapshot_GB": 8.0}}}))
    assert cli_main(["mem", "diff", str(bundle), str(grown)]) == 3


def test_perf_check_still_errors_on_metricless_healthy_run(
        tmp_path, baseline_file):
    _, base = baseline_file
    weird = tmp_path / "weird.json"
    weird.write_text(json.dumps({"hello": "world"}))
    assert cli_main(["perf", "check", str(weird),
                     "--baseline", str(base)]) == 2


def test_perf_check_gates_memory_regression(tmp_path, baseline_file,
                                            capsys):
    """Acceptance: an injected HBM regression exits 3."""
    run, base = baseline_file
    capsys.readouterr()
    # same run passes
    assert cli_main(["perf", "check", str(run),
                     "--baseline", str(base)]) == 0
    capsys.readouterr()
    fat = tmp_path / "fat.json"
    fat.write_text(json.dumps({
        "metric": "llama_110m_train_tokens_per_sec", "value": 35000.0,
        "mfu": 0.4, "step_time_p50_ms": 100.0, "goodput": 0.9,
        # +4GiB peak (>10% and > the 64MiB floor), headroom collapsed
        "peak_hbm_bytes": 12 << 30, "hbm_headroom_frac": 0.1}))
    rc = cli_main(["perf", "check", str(fat), "--baseline", str(base)])
    out = capsys.readouterr().out
    assert rc == 3
    assert "peak_hbm_bytes" in out and "hbm_headroom_frac" in out
