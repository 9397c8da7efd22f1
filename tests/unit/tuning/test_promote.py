"""Sentinel-gated promotion: the perf check decides, exit codes prove it."""

import json

import pytest

from deepspeed_tpu.tuning.promote import (PROMOTE_BLOCKED, PROMOTE_ERROR,
                                          PROMOTE_OK, promote_entry)
from deepspeed_tpu.tuning.store import BestConfigStore, store_key

KEY = store_key("fp1", "devices=1", "cpu", "jax0.4")


@pytest.fixture()
def store(tmp_path):
    st = BestConfigStore(str(tmp_path / "store.json"), fallback=None)
    st.put(KEY, {"overrides": {"train_micro_batch_size_per_gpu": 8},
                 "scores": {"tokens_per_sec": 36000.0},
                 "status": "candidate"})
    return st


def write_run(tmp_path, name, tps, mfu):
    p = tmp_path / name
    p.write_text(json.dumps({"metric": "llama_110m_train_tokens_per_sec",
                             "value": tps, "mfu": mfu}))
    return str(p)


@pytest.fixture()
def baseline(tmp_path):
    from deepspeed_tpu.telemetry.perf import save_baseline

    out = str(tmp_path / "base.json")
    save_baseline(out, {"metric": "llama_110m_train_tokens_per_sec",
                        "value": 35000.0, "mfu": 0.42}, source="test")
    return out


def test_forced_regression_blocks_with_exit_3(store, baseline, tmp_path):
    run = write_run(tmp_path, "regressed.json", 24000.0, 0.30)
    code, report = promote_entry(store, KEY, run, baseline)
    assert code == PROMOTE_BLOCKED == 3
    assert "PROMOTION BLOCKED" in report
    assert "REGRESSION" in report
    # the entry stays a candidate — initialize() must not pick it up
    assert store.get(KEY)["status"] == "candidate"
    reload = BestConfigStore(store.path, fallback=None)
    assert reload.get(KEY)["status"] == "candidate"


def test_clean_check_promotes_with_provenance(store, baseline, tmp_path):
    run = write_run(tmp_path, "good.json", 36500.0, 0.45)
    code, report = promote_entry(store, KEY, run, baseline)
    assert code == PROMOTE_OK == 0
    assert "PROMOTED" in report
    entry = BestConfigStore(store.path, fallback=None).get(KEY)
    assert entry["status"] == "promoted"
    prov = entry["provenance"]
    assert prov["promoted_utc"]
    assert "compared=2" in prov["perf_check"]
    assert len(prov["artifact_sha1"]) == 16  # hash of the run artifact


def test_tolerance_override_can_unblock(store, baseline, tmp_path):
    # 8% drop: default 10% tolerance passes, a tightened 5% blocks
    run = write_run(tmp_path, "slight.json", 32200.0, 0.42)
    code, _ = promote_entry(store, KEY, run, baseline,
                            tolerances={"tokens_per_sec": 0.05})
    assert code == PROMOTE_BLOCKED
    code, _ = promote_entry(store, KEY, run, baseline)
    assert code == PROMOTE_OK


def test_missing_entry_is_structural_error(store, baseline, tmp_path):
    run = write_run(tmp_path, "good.json", 36500.0, 0.45)
    other = store_key("other", "devices=1", "cpu", "jax0.4")
    code, report = promote_entry(store, other, run, baseline)
    assert code == PROMOTE_ERROR == 2
    assert "no store entry" in report


def test_metricless_artifact_is_structural_error(store, baseline, tmp_path):
    p = tmp_path / "empty.json"
    p.write_text(json.dumps({"something": 1}))
    code, report = promote_entry(store, KEY, str(p), baseline)
    assert code == PROMOTE_ERROR
    assert "no sentinel metrics" in report


def test_environment_failure_artifact_cannot_justify_promotion(
        store, baseline, tmp_path):
    p = tmp_path / "nodata.json"
    p.write_text(json.dumps({"metric": "llama_110m_train_tokens_per_sec",
                             "value": 0.0, "error": "no device answered",
                             "environment_failure": True}))
    code, report = promote_entry(store, KEY, str(p), baseline)
    assert code == PROMOTE_ERROR
    assert "environment failure" in report
    assert store.get(KEY)["status"] == "candidate"


def test_missing_baseline_is_structural_error(store, tmp_path):
    run = write_run(tmp_path, "good.json", 36500.0, 0.45)
    code, report = promote_entry(store, KEY, run,
                                 str(tmp_path / "nope.json"))
    assert code == PROMOTE_ERROR
    assert "telemetry perf baseline" in report
