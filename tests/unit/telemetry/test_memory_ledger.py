"""Memory ledger — pool accounting round-trip, sampling, status rewire."""

import sys

import numpy as np
import pytest

from deepspeed_tpu.telemetry.memory import (MemoryLedger, get_memory_ledger,
                                            tree_nbytes, unique_key)


@pytest.fixture
def ledger():
    return MemoryLedger(enabled=True, top_k=5)


def test_register_release_round_trip(ledger):
    ledger.register("params", "a", 1000)
    ledger.register("params", "b", 500, tag="second")
    ledger.register("optimizer", "opt", 3000)
    ledger.register("snapshot", "t0", 4096, space="host")
    assert ledger.pool_bytes() == {"params": 1500, "optimizer": 3000,
                                   "snapshot": 4096}
    assert ledger.pool_bytes(space="hbm") == {"params": 1500,
                                              "optimizer": 3000}
    assert ledger.pool_bytes(space="host") == {"snapshot": 4096}
    # re-register same key REPLACES (double-buffer pattern)
    ledger.register("params", "a", 2000)
    assert ledger.pool_bytes()["params"] == 2500
    ledger.release("params", "b")
    assert ledger.pool_bytes()["params"] == 2000
    # releasing a never-registered key is a no-op
    ledger.release("params", "nope")


def test_transient_excluded_from_steady_state(ledger):
    ledger.register("params", "p", 1000)
    ledger.register("grads", "g", 4000, transient=True)
    assert ledger.pool_bytes(include_transient=True)["grads"] == 4000
    assert "grads" not in ledger.pool_bytes(include_transient=False)
    assert ledger.tracked_bytes(space="hbm") == 1000  # steady-state


def test_register_tree_counts_bytes_and_indexes_shapes(ledger):
    tree = {"w": np.zeros((4, 8), np.float32),
            "b": np.zeros((8,), np.float32)}
    total = ledger.register_tree("kv_cache", "pool", tree)
    assert total == tree_nbytes(tree) == 4 * 8 * 4 + 8 * 4
    assert ledger.pool_bytes()["kv_cache"] == total
    # the shape index attributes a matching live array back to the pool
    assert ledger._shape_index[((4, 8), "float32")] == "kv_cache"


def test_disabled_ledger_is_inert():
    led = MemoryLedger(enabled=False)
    led.register("params", "a", 100)
    assert led.register_tree("params", "t", {"x": np.zeros(3)}) == 0
    led.record_io("h2d", 10)
    assert led.pool_bytes() == {}
    assert led.step_sample() == {}


def test_record_io_and_unknown_kind(ledger):
    ledger.record_io("h2d", 100)
    ledger.record_io("h2d", 50)
    ledger.record_io("disk_write", 7)
    assert ledger.io_totals()["h2d"] == 150
    assert ledger.io_totals()["disk_write"] == 7
    with pytest.raises(ValueError):
        ledger.record_io("sideways", 1)


def test_step_sample_with_fake_device_stats(ledger):
    ledger._device_stats_fn = lambda: {
        "bytes_in_use": 8 << 30, "bytes_limit": 16 << 30,
        "peak_bytes_in_use": 12 << 30}
    ledger.register("params", "p", 6 << 30)
    out = ledger.step_sample()
    assert out["peak_hbm_bytes"] == float(12 << 30)
    assert out["hbm_frac"] == 0.5
    assert out["hbm_headroom_frac"] == 0.25
    assert out["ledger_drift_bytes"] == float(2 << 30)
    assert out["host_rss_bytes"] > 0
    # high-water is rolling: a lower later peak never lowers it
    ledger._device_stats_fn = lambda: {
        "bytes_in_use": 4 << 30, "bytes_limit": 16 << 30,
        "peak_bytes_in_use": 5 << 30}
    assert ledger.step_sample()["peak_hbm_bytes"] == float(12 << 30)


def test_heartbeat_summary(ledger):
    ledger._device_stats_fn = lambda: {
        "bytes_in_use": 8 << 30, "bytes_limit": 16 << 30,
        "peak_bytes_in_use": 12 << 30}
    ledger.step_sample()
    hb = ledger.heartbeat_summary()
    assert hb["hbm_frac"] == 0.5
    assert hb["hbm_headroom"] == 0.25


def test_snapshot_attribution_and_entries(ledger):
    ledger.register("params", "p", 900)
    ledger.register("other", "misc", 100)
    snap = ledger.snapshot()
    assert snap["tracked_bytes"] == 1000
    # 'other' is not a NAMED pool — attribution counts the rest
    assert snap["attributed_frac"] == 0.9
    keys = {(e["pool"], e["key"]) for e in snap["entries"]}
    assert ("params", "p") in keys and ("other", "misc") in keys


def test_live_array_census_attributes_pools(ledger):
    import jax.numpy as jnp

    arr = jnp.zeros((13, 7), jnp.float32)
    ledger.register_tree("kv_cache", "pool", {"a": arr})
    # every live array, not the top few: earlier tests of the same
    # process may have left larger arrays alive than this one
    census = ledger.live_array_census(top_k=sys.maxsize)
    assert census["count"] >= 1
    mine = [e for e in census["top"]
            if tuple(e["shape"]) == (13, 7) and e["dtype"] == "float32"]
    assert mine and mine[0]["pool"] == "kv_cache"
    del arr


def test_status_matches_memory_status_and_has_pools(ledger, monkeypatch):
    # the global-ledger seam: utils.memory.memory_status reads the SAME
    # account this plane writes
    glob = get_memory_ledger()
    glob.configure(enabled=True)
    glob.register("params", "x", 2 << 30)
    from deepspeed_tpu.utils.memory import memory_status, see_memory_usage

    s, again = memory_status(), glob.status()
    # the same account: the same keys, and every pool equal.  The
    # process's RSS is read anew by each call and moves between two
    # reads on a busy host, so it is held to its presence alone
    assert s.keys() == again.keys()
    pools = [k for k in s if k.startswith("pool_")]
    assert pools and {k: s[k] for k in pools} == {k: again[k] for k in pools}
    assert s["pool_params_GB"] == pytest.approx(2.0)
    assert "process_rss_GB" in s
    see_memory_usage("memory plane unit test", force=True)  # must not raise


def test_status_cached_reuses_last_sample(ledger):
    """The engine assembles the StepRecord right after step_sample —
    status(cached=True) must not pay the memory_stats RPC again."""
    calls = []

    def stats():
        calls.append(1)
        return {"bytes_in_use": 1 << 30, "bytes_limit": 2 << 30,
                "peak_bytes_in_use": 1 << 30}

    ledger._device_stats_fn = stats
    ledger.step_sample()
    n = len(calls)
    s = ledger.status(cached=True)
    assert len(calls) == n, "cached status re-probed the device"
    assert s["device_in_use_GB"] == pytest.approx(1.0)
    assert "process_rss_GB" in s  # host side reused from the sample too


def test_heartbeat_summary_reads_only_cached_sample(ledger):
    """The heartbeat thread must NEVER make a fresh device call — a
    runtime that stopped answering before the first step_sample would
    hang the very heartbeat loop that reports the host alive."""
    calls = []
    ledger._device_stats_fn = lambda: calls.append(1) or {}
    assert ledger.heartbeat_summary() == {}
    assert not calls, "heartbeat_summary probed the device"


def test_unique_key_is_unique():
    assert unique_key("a") != unique_key("a")


def test_reset_clears_everything(ledger):
    ledger.register("params", "p", 10)
    ledger.record_io("d2h", 5)
    ledger.reset()
    assert ledger.pool_bytes() == {}
    assert sum(ledger.io_totals().values()) == 0
