"""Metric arithmetic on hand-made observations."""

import statistics

import pytest

from perfbench import arith, manifest


def test_window_rate_and_counts():
    rate = manifest.load_module("readers", "rate")
    obs = {"work": {"tokens": 5000}, "t_open": 10.0, "t_close": 20.0}
    assert rate.read(obs, {"of": "tokens"}) == 500.0
    inside = arith.in_window([(9.9, 0, 4), (10.0, 0, 4), (10.1, 0, 4),
                              (20.0, 0, 4), (20.1, 0, 4)], 10.0, 20.0)
    assert arith.delivered_tokens(inside) == 8


def test_percentile_and_spread():
    xs = [5, 1, 4, 2, 3]
    assert arith.percentile(xs, 50) == 3 and arith.percentile(xs, 100) == 5
    assert arith.percentile(xs, 90) == pytest.approx(4.6)
    runs = [104.72, 104.72, 106.83, 106.93, 110.28, 110.84]
    q1, _, q3 = statistics.quantiles(runs, n=4)
    assert arith.spread(runs) == pytest.approx(
        (q3 - q1) / statistics.median(runs))


def test_span_and_counter_readers():
    spans = [{"name": "inference/decode_burst", "dur_s": 0.8, "args": {"burst": 8}},
             {"name": "inference/decode_burst", "dur_s": 0.12, "args": {"burst": 1}},
             {"name": "inference/prefill", "dur_s": 0.05, "args": {"chunks": 2}}]
    obs = {"program_spans": spans, "t_open": 0.0, "t_close": 2.0,
           "program_counters": {"inference/decode_tokens": 270.0}}
    read = lambda name, args: manifest.load_module("readers", name).read(obs, args)
    assert read("span_ms", {"span": "inference/decode_burst", "per": "burst",
                            "q": 50}) == pytest.approx(110.0)
    assert read("counter_per_span", {"counter": "inference/decode_tokens",
                                     "span": "inference/decode_burst"}) == 135.0
    assert read("span_ms", {"span": "nothing/here", "q": 50}) is None
