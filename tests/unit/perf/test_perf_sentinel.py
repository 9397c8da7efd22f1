"""Perf-regression sentinel: baseline math + CLI exit codes."""

import json

import pytest

from deepspeed_tpu.telemetry.cli import main as cli_main
from deepspeed_tpu.telemetry.perf import (check_regression, extract_perf,
                                          load_baseline, load_run,
                                          parse_tolerances, save_baseline)

RUN = {"metric": "llama_110m_train_tokens_per_sec", "value": 35000.0,
       "unit": "tokens/sec/chip", "vs_baseline": 1.0, "mfu": 0.42,
       "step_time_p50_ms": 120.0, "compile_time_s": 30.0, "goodput": 0.95}


def _write(path, obj):
    path.write_text(json.dumps(obj))
    return str(path)


def test_extract_perf_from_bench_line():
    m = extract_perf(RUN)
    assert m["tokens_per_sec"] == 35000.0
    assert m["mfu"] == 0.42
    assert m["step_time_p50_ms"] == 120.0
    assert m["compile_time_s"] == 30.0
    assert m["goodput"] == 0.95


def test_load_run_unwraps_driver_artifact(tmp_path):
    p = _write(tmp_path / "BENCH_r99.json",
               {"n": 99, "rc": 0, "parsed": RUN})
    assert extract_perf(load_run(p))["tokens_per_sec"] == 35000.0


def test_baseline_round_trip(tmp_path):
    p = str(tmp_path / "base.json")
    save_baseline(p, RUN, source="test")
    base = load_baseline(p)
    assert base == extract_perf(RUN)


def test_check_clean_and_regressed():
    base = extract_perf(RUN)
    clean = check_regression(base, base)
    assert not clean["regressions"]
    slow = dict(base, tokens_per_sec=base["tokens_per_sec"] * 0.8,
                step_time_p50_ms=base["step_time_p50_ms"] * 1.3)
    bad = check_regression(slow, base)
    names = {r["metric"] for r in bad["regressions"]}
    assert names == {"tokens_per_sec", "step_time_p50_ms"}


def test_check_within_tolerance_passes():
    base = extract_perf(RUN)
    slightly = dict(base, tokens_per_sec=base["tokens_per_sec"] * 0.95)
    assert not check_regression(slightly, base)["regressions"]


def test_check_abs_floor_ignores_tiny_compile_growth():
    base = {"compile_time_s": 0.1}
    cur = {"compile_time_s": 0.5}  # 5x relative, but < 1s absolute
    assert not check_regression(cur, base)["regressions"]


def test_one_sided_metric_is_skipped_not_failed():
    res = check_regression({"mfu": 0.4}, {"mfu": 0.4, "goodput": 0.9})
    assert res["skipped"] == ["goodput"]
    assert not res["regressions"]


def test_parse_tolerances_rejects_unknown_metric():
    assert parse_tolerances(["mfu=0.05"]) == {"mfu": 0.05}
    with pytest.raises(ValueError):
        parse_tolerances(["typo_metric=0.1"])


# -- CLI exit-code contract (the acceptance criterion) ----------------------

def test_cli_baseline_then_check_same_run_exits_0(tmp_path, capsys):
    run = _write(tmp_path / "run.json", RUN)
    base = str(tmp_path / "base.json")
    assert cli_main(["perf", "baseline", run, "--out", base]) == 0
    assert cli_main(["perf", "check", run, "--baseline", base]) == 0
    out = capsys.readouterr().out
    assert "perf check passed" in out


def test_cli_check_exits_3_on_injected_regression(tmp_path, capsys):
    run = _write(tmp_path / "run.json", RUN)
    base = str(tmp_path / "base.json")
    assert cli_main(["perf", "baseline", run, "--out", base]) == 0
    regressed = dict(RUN, value=RUN["value"] * 0.7, goodput=0.5)
    bad = _write(tmp_path / "bad.json", regressed)
    assert cli_main(["perf", "check", bad, "--baseline", base]) == 3
    out = capsys.readouterr().out
    assert "REGRESSION" in out


def test_cli_check_custom_tolerance_widens_gate(tmp_path):
    run = _write(tmp_path / "run.json", RUN)
    base = str(tmp_path / "base.json")
    cli_main(["perf", "baseline", run, "--out", base])
    mild = _write(tmp_path / "mild.json",
                  dict(RUN, value=RUN["value"] * 0.75, goodput=0.95,
                       mfu=RUN["mfu"], step_time_p50_ms=RUN[
                           "step_time_p50_ms"], compile_time_s=RUN[
                           "compile_time_s"]))
    assert cli_main(["perf", "check", mild, "--baseline", base]) == 3
    assert cli_main(["perf", "check", mild, "--baseline", base,
                     "--tol", "tokens_per_sec=0.5"]) == 0


def test_cli_missing_baseline_exits_2(tmp_path):
    run = _write(tmp_path / "run.json", RUN)
    assert cli_main(["perf", "check", run,
                     "--baseline", str(tmp_path / "nope.json")]) == 2


def test_cli_show_prints_metrics(tmp_path, capsys):
    run = _write(tmp_path / "run.json", RUN)
    assert cli_main(["perf", "show", run]) == 0
    out = capsys.readouterr().out
    assert "tokens_per_sec: 35000" in out and "goodput: 0.95" in out


def test_cli_show_marks_environment_failure_as_skipped(tmp_path, capsys):
    """Satellite (ISSUE 13): an r05-style environment-failure artifact
    (value 0.0 + error, no debug_bundle) must render as an explicitly
    SKIPPED round in `perf show` — never as measured 0.0 values.  Only
    `check` used to understand the marker."""
    r05 = {"metric": "llama_110m_train_tokens_per_sec", "value": 0.0,
           "unit": "tokens/sec/chip", "vs_baseline": 0.0,
           "error": "jax.devices() unresponsive after 180s"}
    run = _write(tmp_path / "r05.json", r05)
    assert cli_main(["perf", "show", run]) == 0
    out = capsys.readouterr().out
    assert "SKIPPED round" in out
    assert "unresponsive after 180s" in out
    assert "tokens_per_sec: 0" not in out

    # the explicit marker shape (bench stamps environment_failure=True)
    # takes the same path even when placeholder metric fields ride along
    marked = {"metric": "llama_110m_train_tokens_per_sec", "value": 0.0,
              "environment_failure": True, "mfu": 0.0,
              "error": "device probe timed out"}
    run2 = _write(tmp_path / "marked.json", marked)
    assert cli_main(["perf", "show", run2]) == 0
    out = capsys.readouterr().out
    assert "SKIPPED round" in out and "device probe timed out" in out
    assert "mfu: 0" not in out

    # a CRASH artifact (debug_bundle present) stays a loud error — a
    # code regression must never read as an environment skip
    crash = {"metric": "llama_110m_train_tokens_per_sec", "value": 0.0,
             "error": "OOM", "debug_bundle": "/tmp/bundle-x"}
    run3 = _write(tmp_path / "crash.json", crash)
    assert cli_main(["perf", "show", run3]) == 2
