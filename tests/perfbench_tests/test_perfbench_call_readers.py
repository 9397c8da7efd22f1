"""The readers ISSUE 38 brings: the gap between two program calls on the
device's clock and its parts by the span the host was in, on a trace made
by hand and on the recorded v5e trace; a ratio of two program counters;
the metric files against ``BENCHMARK.json``; and a traced CPU rehearsal, in
which the program's new counters agree with the ones it already kept."""

import pathlib
import sys

import jax
import pytest

from perfbench import manifest
from perfbench import trace_reduce as tr

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import rehearsal  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"
BENCH = manifest.load_benchmark()
SERVING = ["serve-batch-mistral7b", "serve-batch-olmoe-l8",
           "serve-reason-mimo-v25-l7"]
GAP = ["call_gap_ms_p50.batch", "call_gap_ms_mean.batch"]
PARTS = ["gap_fetch_ms_mean.batch", "gap_commit_ms_mean.batch",
         "gap_plan_pack_ms_mean.batch", "gap_dispatch_ms_mean.batch",
         "gap_outside_step_ms_mean.batch"]
RATIOS = ["live_row_share.batch", "chunk_row_share.batch",
          "calls_with_chunks_share.batch"]
SPANS = ["call_life_ms_p50.batch", "tracing_self_ms_p50.batch"]
NEW = GAP + PARTS + RATIOS + SPANS


def read(metric, obs):
    spec = manifest.load_json("metrics", metric)
    return manifest.load_module("readers", spec["reader"]).read(
        obs, spec.get("args", {}))


def _ev(a, b, name):
    """An event from ``a`` to ``b`` ms."""
    return tr.Event(a * 1e6, (b - a) * 1e6, name)


def by_hand():
    """Three executions of the engine's programs, 0-10, 14-30 and 36-50 ms
    of a 60 ms stretch; a key refill's program runs 31-32 between the last
    two: the gaps are 4 ms and 6 - 1 = 5 ms.  One host thread carries the
    rounds; another one a pump of its own that must not be read."""
    step = "jit_inference_v2_decode_burst_n_steps"
    modules = [_ev(0, 10, step + "1(11)"), _ev(14, 30, step + "8(12)"),
               _ev(31, 32, "jit__split_chain(13)"),
               _ev(36, 50, step + "1(11)")]
    ops = [_ev(a, b, f"%fusion.{i} = bf16[8]{{0}} fusion(bf16[8] %a)")
           for i, (a, b) in enumerate([(0, 10), (14, 30), (31, 32),
                                       (36, 50)])]
    pump = [
        _ev(0, 60, "bench/traced"),
        # the first gap, 10-14: the fetch's tail 10-10.5, commit to 11.5,
        # plan to 12, two packs to 12.5 and 13 (the second one also under
        # a span of the same name: counted once), dispatch 13-13.9, and
        # the launch under ``inference/observe``: 0.1 that no part names
        _ev(9.0, 15.5, "serving/pump"), _ev(9.2, 14.5, "inference/step"),
        _ev(9.3, 10.5, "inference/decode_burst"),
        _ev(9.3, 10.5, "inference/decode_burst/fetch"),
        _ev(10.5, 11.5, "inference/commit"), _ev(11.5, 12.0, "inference/plan"),
        _ev(12.0, 12.5, "inference/pack"), _ev(12.5, 13.0, "inference/pack"),
        _ev(12.6, 12.9, "inference/pack"),
        _ev(13.0, 13.9, "inference/decode_burst/dispatch"),
        _ev(13.9, 14.3, "inference/observe"),
        # the second gap, 30-36 less the refill's program 31-32: a round
        # ends outside the step 30-30.5 (delivery), the load generator to
        # 31; then 32-36: fetch to 32.4, commit to 33, plan and pack to
        # 33.5, a dispatch 33.5-36 that holds a refill of keys 33.6-35
        _ev(28.0, 30.5, "serving/pump"), _ev(28.0, 29.5, "inference/step"),
        _ev(29.5, 30.5, "serving/deliver"),
        _ev(30.5, 31.0, "bench/deliver"),
        _ev(31.0, 37.0, "serving/pump"), _ev(31.0, 36.5, "inference/step"),
        _ev(31.0, 32.4, "inference/decode_burst"),
        _ev(31.0, 32.4, "inference/decode_burst/fetch"),
        _ev(32.4, 33.0, "inference/commit"), _ev(33.0, 33.2, "inference/plan"),
        _ev(33.2, 33.5, "inference/pack"),
        _ev(33.5, 36.0, "inference/decode_burst/dispatch"),
        _ev(33.6, 35.0, "inference/keys"),
        _ev(36.0, 36.4, "inference/observe"),
    ]
    other = [_ev(10, 14, "serving/pump"), _ev(10, 14, "inference/commit")]
    dev = tr.DeviceTrace(ops=ops, async_ops=[], modules=modules)
    return tr.Trace({0: dev}, {"main": pump, "other": other}, 0.0, 60e6)


def test_the_gap_is_the_idle_time_between_two_of_the_engines_calls():
    obs = {"trace": by_hand()}
    assert read("call_gap_ms_mean.batch", obs) == pytest.approx(4.5)
    assert read("call_gap_ms_p50.batch", obs) == pytest.approx(4.5)
    spec = manifest.load_json("metrics", "call_gap_ms_p50.batch")
    reader = manifest.load_module("readers", spec["reader"])
    assert reader.read(obs, dict(spec["args"], q=0)) == pytest.approx(4.0)
    assert reader.read(obs, dict(spec["args"], q=100)) == pytest.approx(5.0)
    # the refill's program counted as a call of the engine's: three gaps
    assert reader.read(obs, {"pattern": "^jit_"}) == pytest.approx(3.0)
    assert reader.read(obs, {"pattern": "^jit_no_such"}) is None
    assert reader.read({"trace": None}, spec["args"]) is None
    assert reader.read({}, spec["args"]) is None


def test_the_parts_and_what_no_part_names_sum_to_the_gap():
    obs = {"trace": by_hand()}
    got = {m: read(m, obs) for m in PARTS}
    assert got == pytest.approx({
        "gap_fetch_ms_mean.batch": (0.5 + 0.4) / 2,
        "gap_commit_ms_mean.batch": (1.0 + 0.6) / 2,
        # two packs of which one lies under two spans: once; the refill of
        # keys belongs here and not to the dispatch it is nested in
        "gap_plan_pack_ms_mean.batch": (1.5 + 0.5 + 1.4) / 2,
        "gap_dispatch_ms_mean.batch": (0.9 + 1.1) / 2,
        "gap_outside_step_ms_mean.batch": (0.0 + 1.0) / 2})
    # the launch after the first dispatch: 0.1 ms under no named span
    assert read("call_gap_ms_mean.batch", obs) - sum(got.values()) \
        == pytest.approx(0.1 / 2)


def test_a_trace_without_the_pumps_thread_gives_the_gap_and_no_part():
    trace = by_hand()
    trace.host = {"other": [_ev(0, 60, "bench/traced")]}
    obs = {"trace": trace}
    assert read("call_gap_ms_mean.batch", obs) == pytest.approx(4.5)
    assert all(read(m, obs) is None for m in PARTS)


@pytest.mark.parametrize("metric", GAP + PARTS)
def test_the_recorded_trace_of_an_unnamed_program_gives_no_reading(metric):
    """PR 23's trace: its decode program is ``jit__unknown(<hash>)`` and
    its host plane holds no program span."""
    text = (DATA / "serve_l2_v5e_decode_step.xspace.txt").read_text()
    recorded = tr.from_profile_data(
        jax.profiler.ProfileData.from_text_proto(text))
    assert read(metric, {"trace": recorded}) is None


@pytest.mark.parametrize("counters, want", [
    ({"inference/rows_live": 30.0, "inference/rows_computed": 40.0}, 75.0),
    ({"inference/rows_live": 0.0, "inference/rows_computed": 40.0}, 0.0),
    ({"inference/rows_computed": 40.0}, None),
    ({"inference/rows_live": 30.0}, None),
    ({"inference/rows_live": 0.0, "inference/rows_computed": 0.0}, None),
    ({}, None)])
def test_a_ratio_needs_both_counters_and_a_whole_that_grew(counters, want):
    obs = {"program_counters": counters}
    assert read("live_row_share.batch", obs) == want
    assert read("live_row_share.batch", {}) is None


@pytest.mark.parametrize("metric", NEW)
def test_a_new_metric_is_an_entry_and_a_file_that_agree(metric):
    entry = manifest.named(BENCH["per_layer"], metric, "metric")
    spec = manifest.load_json("metrics", metric)
    assert {k: spec[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"}
    assert entry["workloads"] == SERVING
    assert entry["moves"] == "serve_tokens_per_s"
    assert entry["source"] == ("device_trace" if metric in GAP + PARTS
                               else "program_counter" if metric in RATIOS
                               else "program_span")
    assert in_one_block([m["name"] for m in BENCH["per_layer"]])


def in_one_block(names):
    """PR 38's twelve lie in one block, in their order, wherever in the
    list it is: a later PR appends its entries behind it."""
    at = names.index(NEW[0])
    return names[at:at + len(NEW)] == NEW


def test_a_later_metric_is_appended_and_the_block_holds():
    """What any PR that may add a metric does: its entry goes to the end
    of ``per_layer``.  The block is not held to the end of the list (it
    was until PR 51, and seven metrics waited without an entry)."""
    names = [m["name"] for m in BENCH["per_layer"]]
    assert in_one_block(names + ["a_later_metric.batch"])
    assert names[-len(NEW):] != NEW       # entries already lie behind it
    # an entry INSIDE the block, or the block out of order, is refused
    at = names.index(NEW[0])
    assert not in_one_block(names[:at + 3] + ["x.batch"] + names[at + 3:])
    assert not in_one_block(names[:at] + NEW[::-1] + names[at + len(NEW):])


AHEAD = "calls_ahead_share.batch"


def test_the_share_of_calls_dispatched_ahead_is_an_entry_and_a_file():
    """PR 39's counter over PR 38's: data only, entered by PR 51 for the
    five serving cells (every one drives the engine through
    ``step_ahead``)."""
    entry = manifest.named(BENCH["per_layer"], AHEAD, "metric")
    # (that file and entry agree is ``test_metric``'s, for every metric)
    spec = manifest.load_json("metrics", AHEAD)
    assert spec["reader"] == "counter_ratio_pct" and spec["args"] == {
        "part": "inference/calls_dispatched_ahead",
        "whole": "inference/calls"}
    assert (entry["layer"], entry["source"], entry["moves"]) == (
        "v2 engine", "program_counter", "serve_tokens_per_s")
    served = [w["name"] for w in BENCH["workloads"]
              if w["name"].startswith("serve-")]
    assert entry["workloads"] == served and len(served) == 5
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(AHEAD) > names.index(NEW[-1])


@pytest.mark.parametrize("counters, want", [
    ({"inference/calls_dispatched_ahead": 802.0, "inference/calls": 802.0},
     100.0),
    ({"inference/calls_dispatched_ahead": 0.0, "inference/calls": 40.0}, 0.0),
    ({"inference/calls": 40.0}, None),
    ({"inference/calls_dispatched_ahead": 3.0}, None),
    ({}, None)])
def test_calls_ahead_reads_both_counters_or_nothing(counters, want):
    assert read(AHEAD, {"program_counters": counters}) == want


def test_the_parts_name_spans_that_do_not_overlap():
    """What lets them sum: each span name is in one part's ``under``, and
    the one nested name is taken away from the part it is nested in."""
    specs = {m: manifest.load_json("metrics", m)["args"]["spans"]
             for m in PARTS}
    under = [n for s in specs.values() for n in s.get("under", ())]
    assert len(under) == len(set(under)) == 6
    assert specs["gap_dispatch_ms_mean.batch"]["less"] == ["inference/keys"]
    assert "inference/keys" in specs["gap_plan_pack_ms_mean.batch"]["under"]
    assert specs["gap_outside_step_ms_mean.batch"] == {
        "thread": "serving/pump", "outside": ["inference/step"]}
    assert {s["thread"] for s in specs.values()} == {"serving/pump"}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    line = rehearsal.rehearse(SERVING[0], seed=2**31 + 38, seconds=0.6,
                              trace=True,
                              tmp_path=tmp_path_factory.mktemp("scratch"))
    if "inference/calls" not in line["_obs"]["program_counters"]:
        # these files laid over a program from before ISSUE 38: the
        # readers find nothing to read, which the tests above hold
        assert not set(NEW) & set(line["metrics"])
        pytest.skip("the program keeps no counters of its calls")
    return line


def test_the_rehearsal_reads_the_counters_and_the_calls_records(traced):
    metrics = traced["metrics"]
    assert traced["correct"] and traced["failed"] == 0
    # no device in a CPU trace: the gap and its parts are left out
    assert not set(GAP + PARTS) & set(metrics)
    assert set(RATIOS + SPANS) <= set(metrics)
    assert 0 < metrics["live_row_share.batch"]["value"] <= 100
    assert 0 <= metrics["chunk_row_share.batch"]["value"] < 100
    assert 0 <= metrics["calls_with_chunks_share.batch"]["value"] <= 100
    assert metrics["call_life_ms_p50.batch"]["value"] > 0
    assert metrics["tracing_self_ms_p50.batch"]["value"] > 0


def test_the_new_counters_agree_with_the_ones_the_program_kept(traced):
    counters = traced["_obs"]["program_counters"]
    spans = traced["_obs"]["program_spans"]
    count = lambda name: sum(s["name"] == name for s in spans)
    assert counters["inference/rows_live"] == (
        counters["inference/decode_tokens"]
        + counters["inference/prefill_tokens"]) > 0
    assert counters["inference/rows_live"] \
        <= counters["inference/rows_computed"]
    # the window opens and closes between two rounds
    assert counters["inference/calls"] == count("inference/call") \
        == count("inference/decode_burst") == count("inference/commit") > 0
    run = traced["_obs"]["config"]["run"]
    defaults = traced["program_defaults"]
    calls = [s["args"] for s in spans if s["name"] == "inference/call"]
    chunk_rows = defaults["prefill_batch"] * defaults["prefill_chunk"]
    with_chunks = sum(c["kb"] is not None for c in calls)
    assert counters["inference/calls_with_chunks"] == with_chunks
    assert counters["inference/chunk_rows_computed"] \
        == with_chunks * chunk_rows
    assert counters["inference/rows_computed"] == sum(
        c["steps"] for c in calls) * run["max_batch_slots"] \
        + with_chunks * chunk_rows
    assert {c["steps"] for c in calls} <= {1, defaults["decode_burst"]}
    assert sum(c["accepted"] for c in calls) \
        == counters["inference/decode_tokens"]
    assert sum(c["chunk_tokens"] for c in calls) \
        == counters["inference/prefill_tokens"]
