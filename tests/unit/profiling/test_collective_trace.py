"""Seed-coverage for ``profiling/collective_trace`` + the new
execution-order census feed (ISSUE 5 satellite + ROADMAP item)."""

import gzip
import json
import os

from deepspeed_tpu.profiling.collective_trace import (feed_exec_census,
                                                      parse_trace,
                                                      parse_trace_events,
                                                      profile_collectives)
from deepspeed_tpu.telemetry.collective_ledger import (CollectiveLedger,
                                                       find_first_divergence)


def _write_trace(tmp_path, events, name="t.trace.json.gz"):
    os.makedirs(str(tmp_path), exist_ok=True)
    p = os.path.join(str(tmp_path), name)
    with gzip.open(p, "wt") as f:
        json.dump({"traceEvents": events}, f)
    return str(tmp_path)


DEVICE_META = {"ph": "M", "name": "process_name", "pid": 7,
               "args": {"name": "/device:TPU:0"}}
PY_META = {"ph": "M", "name": "process_name", "pid": 9,
           "args": {"name": "/host:python"}}


def _ev(name, ts, dur, pid=7):
    return {"ph": "X", "pid": pid, "name": name, "ts": ts, "dur": dur}


def test_parse_trace_empty_dir_returns_empty(tmp_path):
    assert parse_trace(str(tmp_path)) == {}
    assert parse_trace_events(str(tmp_path)) == []


def test_profile_collectives_empty_trace_fallback(tmp_path, caplog):
    # no collectives in the fn -> empty table + the one-shot warning,
    # never an exception (a backend that exports no device lanes)
    import jax.numpy as jnp

    table = profile_collectives(lambda x: x + 1, jnp.ones((4,)), iters=1,
                                trace_dir=str(tmp_path / "trace"))
    assert isinstance(table, dict)


def test_parse_trace_aggregates_device_lanes_only(tmp_path):
    trace = _write_trace(tmp_path, [
        DEVICE_META, PY_META,
        _ev("all-reduce.1", 100, 10),
        _ev("all-reduce.1", 200, 30),
        _ev("fusion.7", 150, 5),              # not a collective
        _ev("all-reduce.1", 50, 99, pid=9),   # python lane: excluded
    ])
    table = parse_trace(trace)
    assert set(table) == {"all-reduce.1"}
    assert table["all-reduce.1"]["count"] == 2
    assert table["all-reduce.1"]["total_us"] == 40.0
    assert table["all-reduce.1"]["mean_us"] == 20.0


def test_parse_trace_events_ordered_by_timestamp(tmp_path):
    trace = _write_trace(tmp_path, [
        DEVICE_META,
        _ev("reduce-scatter.2", 300, 8),
        _ev("all-gather.1", 100, 4),
        _ev("all-reduce.3", 200, 6),
    ])
    events = parse_trace_events(trace)
    assert [e["name"] for e in events] == [
        "all-gather.1", "all-reduce.3", "reduce-scatter.2"]
    assert [e["ts_us"] for e in events] == sorted(
        e["ts_us"] for e in events)


def test_feed_exec_census_ordered_and_cross_rank_comparable(tmp_path):
    # two "ranks" run the same program: same collective EXECUTION order,
    # different timings — the exec chains must agree anyway
    events = [DEVICE_META,
              _ev("all-gather.1", 100, 4),
              _ev("all-reduce.3", 200, 6),
              _ev("reduce-scatter.2", 300, 8)]
    t_a = _write_trace(tmp_path / "a", events)
    slower = [DEVICE_META,
              _ev("all-gather.1", 1100, 40),
              _ev("all-reduce.3", 1900, 60),
              _ev("reduce-scatter.2", 2700, 80)]
    t_b = _write_trace(tmp_path / "b", slower)
    led_a = CollectiveLedger(enabled=True)
    led_b = CollectiveLedger(enabled=True)
    assert feed_exec_census(t_a, ledger=led_a) == 3
    assert feed_exec_census(t_b, ledger=led_b) == 3
    # ordered: seq strictly increasing, timestamps non-decreasing
    tail_a = led_a.exec_tail()
    assert [e["seq"] for e in tail_a] == [1, 2, 3]
    ts = [e["ts_us"] for e in tail_a]
    assert ts == sorted(ts)
    assert all(e["src"] == "exec_trace" for e in tail_a)
    # cross-rank comparable: identical op sequence -> identical chain
    assert led_a.exec_tail_hash == led_b.exec_tail_hash
    # a rank that executed a DIFFERENT order forks the chain
    led_c = CollectiveLedger(enabled=True)
    reordered = [DEVICE_META,
                 _ev("all-reduce.3", 100, 6),
                 _ev("all-gather.1", 200, 4),
                 _ev("reduce-scatter.2", 300, 8)]
    feed_exec_census(_write_trace(tmp_path / "c", reordered),
                     ledger=led_c)
    assert led_c.exec_tail_hash != led_a.exec_tail_hash


def test_feed_exec_census_dedupes_device_lanes(tmp_path):
    # an 8-shard single-process mesh shows the same program on every
    # lane; only ONE lane must be replayed
    meta2 = {"ph": "M", "name": "process_name", "pid": 8,
             "args": {"name": "/device:TPU:1"}}
    trace = _write_trace(tmp_path, [
        DEVICE_META, meta2,
        _ev("all-reduce.1", 100, 4, pid=7),
        _ev("all-reduce.1", 101, 4, pid=8),
    ])
    led = CollectiveLedger(enabled=True)
    assert feed_exec_census(trace, ledger=led) == 1


def test_feed_exec_census_empty_trace_is_zero(tmp_path):
    led = CollectiveLedger(enabled=True)
    assert feed_exec_census(str(tmp_path), ledger=led) == 0
    assert led.exec_seq == 0


def test_find_first_divergence_over_trace_fed_exec_tails(tmp_path):
    # ISSUE 20 satellite: the offline desync analysis runs unchanged
    # over EXEC tails harvested from profiler ring dirs — three "ranks"
    # replay their captured device lanes, one executed a different
    # second collective
    good = [DEVICE_META,
            _ev("all-gather.1", 100, 4),
            _ev("all-reduce.3", 200, 6),
            _ev("reduce-scatter.2", 300, 8)]
    bad = [DEVICE_META,
           _ev("all-gather.1", 100, 4),
           _ev("collective-permute.9", 200, 6),  # wrong op at seq 2
           _ev("reduce-scatter.2", 300, 8)]
    tails = {}
    for node, events in (("pn0", good), ("pn1", bad), ("pn2", good)):
        led = CollectiveLedger(enabled=True)
        assert feed_exec_census(_write_trace(tmp_path / node, events),
                                ledger=led) == 3
        tails[node] = led.snapshot()["exec_tail"]
    report = find_first_divergence(tails)
    assert report["desync"] is True
    assert report["first_mismatch"]["seq"] == 2
    assert report["first_mismatch"]["divergent_ranks"] == ["pn1"]
    assert report["first_mismatch"]["signatures"]["pn1"] == \
        "collective-permute.9:0"
    assert report["lagging_rank"] is None  # all at seq 3
    assert report["overlap"] == [1, 3]


def test_trace_fed_exec_lane_never_forks_census_chain(tmp_path):
    # two ranks whose LIVE census chains agree must keep agreeing even
    # when only one of them feeds a profiler trace into the exec lane —
    # the lanes are hash-isolated by construction
    led_a = CollectiveLedger(enabled=True)
    led_b = CollectiveLedger(enabled=True)
    for led in (led_a, led_b):
        led.record("all_reduce", 4096)
        led.record("psum", 128)
    trace = _write_trace(tmp_path, [DEVICE_META,
                                    _ev("all-reduce.1", 100, 4),
                                    _ev("all-gather.2", 200, 4)])
    assert feed_exec_census(trace, ledger=led_a) == 2
    assert led_a.tail_hash == led_b.tail_hash      # census chain intact
    assert led_a.seq == led_b.seq == 2
    assert led_a.exec_seq == 2 and led_b.exec_seq == 0
    assert led_a.exec_tail_hash != led_b.exec_tail_hash
    # and the divergence analysis over the CENSUS tails stays clean
    report = find_first_divergence({"a": led_a.tail(), "b": led_b.tail()})
    assert report["desync"] is False
    assert report["first_mismatch"] is None


def test_exec_lane_rides_ledger_snapshot(tmp_path):
    led = CollectiveLedger(enabled=True)
    led.record("psum", 1024)  # census lane
    led.record_exec("all-reduce.1", 0, dur_us=12.5, ts_us=100.0,
                    source="exec_trace")
    snap = led.snapshot()
    assert snap["seq"] == 1
    assert snap["exec_seq"] == 1
    assert snap["exec_tail"][0]["op"] == "all-reduce.1"
    assert snap["exec_tail"][0]["dur_us"] == 12.5
    # exec entries never touch the census chain
    led2 = CollectiveLedger(enabled=True)
    led2.record("psum", 1024)
    assert led2.tail_hash == led.tail_hash
