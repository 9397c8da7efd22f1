"""The readers of the program's span tree and of the named programs on
the device's ``XLA Modules`` line, on spans and traces made by hand, and
on the recorded v5e trace, whose decode program has no name of its own."""

import pathlib

import jax
import pytest

from perfbench import manifest
from perfbench import trace_reduce as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"


def reader(name):
    return manifest.load_module("readers", name)


def span(name, ms, depth=None, parent=None, **args):
    if depth is not None:
        args["depth"] = depth
    if parent is not None:
        args["parent"] = parent
    return {"name": name, "dur_s": ms * 1e-3, "args": args}


def one_round(pump_ms, step_ms, prefill_ms=None, burst_ms=4.0):
    """A round's spans in the order they close, children first."""
    out = [span("serving/admit", 0.2, 1, "serving/pump"),
           span("inference/plan", 0.1, 2, "inference/step")]
    if prefill_ms is not None:
        out += [span("inference/prefill/fetch", prefill_ms - 0.5, 3,
                     "inference/prefill"),
                span("inference/prefill", prefill_ms, 2, "inference/step")]
    out += [span("inference/decode_burst/dispatch", 0.4, 3,
                 "inference/decode_burst"),
            span("inference/decode_burst", burst_ms, 2, "inference/step",
                 burst=8),
            span("inference/step", step_ms, 1, "serving/pump"),
            # a request's wait, stamped elsewhere, closes mid-round
            span("serving/request/queued", 700.0, trace_id="abc"),
            span("serving/deliver", 0.3, 1, "serving/pump"),
            span("serving/pump", pump_ms, 0)]
    return out


ENGINE = {"span": "inference/step", "q": 50,
          "minus": ["inference/prefill", "inference/decode_burst"]}
FRONT = {"span": "serving/pump", "minus": ["inference/step"], "q": 50}


def test_self_time_is_the_span_less_its_named_direct_children():
    obs = {"program_spans": one_round(10.0, 9.0, prefill_ms=3.0)}
    assert reader("span_self_ms").read(obs, FRONT) == pytest.approx(1.0)
    # 9 - 3 (prefill) - 4 (burst); the burst's own child is not a direct one
    assert reader("span_self_ms").read(obs, ENGINE) == pytest.approx(2.0)


def test_self_time_of_repeated_rounds_is_taken_round_by_round():
    spans = (one_round(10.0, 9.0, prefill_ms=3.0) + one_round(6.0, 5.5)
             + one_round(8.0, 7.0, prefill_ms=1.0))
    obs = {"program_spans": spans}
    # per round 1.0, 0.5, 1.0 and 2.0, 1.5, 2.0
    assert reader("span_self_ms").read(obs, FRONT) == pytest.approx(1.0)
    assert reader("span_self_ms").read(
        obs, dict(FRONT, q=0)) == pytest.approx(0.5)
    assert reader("span_self_ms").read(obs, ENGINE) == pytest.approx(2.0)
    assert reader("span_self_ms").read(
        obs, dict(ENGINE, q=0)) == pytest.approx(1.5)


def test_self_time_of_a_parent_with_no_such_child_is_its_whole_time():
    idle = [span("serving/admit", 0.2, 1, "serving/pump"),
            span("serving/pump", 0.5, 0)]
    obs = {"program_spans": one_round(10.0, 9.0) + idle}
    assert reader("span_self_ms").read(
        obs, dict(FRONT, q=0)) == pytest.approx(0.5)
    # a step called outside any pump (``generate()``) still counts, and a
    # same-named span under another parent is not taken for a child
    alone = [span("inference/decode_burst", 4.0, 1, "inference/step"),
             span("inference/step", 5.0, 0),
             span("inference/step", 2.0, 1, "elsewhere"),
             span("serving/pump", 3.0, 0)]
    obs = {"program_spans": alone}
    assert reader("span_self_ms").read(obs, ENGINE) == pytest.approx(1.5)
    assert reader("span_self_ms").read(obs, FRONT) == pytest.approx(3.0)


def test_a_program_without_the_tree_gives_no_reading():
    """The parent commit's program: two flat spans, no depth kept apart."""
    old = {"program_spans": [span("inference/prefill", 3.0, 0),
                             span("inference/decode_burst", 4.0, 0, burst=8)]}
    for args in (FRONT, ENGINE):
        assert reader("span_self_ms").read(old, args) is None
        assert reader("span_self_ms").read({}, args) is None
    queue = {"span": "serving/request/queued", "per": "none", "q": 50}
    assert reader("span_ms").read(old, queue) is None
    obs = {"program_spans": [span("serving/request/queued", ms, trace_id=i)
                             for i, ms in enumerate((5.0, 900.0, 40.0))]}
    # no span carries ``per``: each is divided by 1
    assert reader("span_ms").read(obs, queue) == pytest.approx(40.0)


# -- traces made by hand -------------------------------------------------------


def _ev(a, b, name):
    return tr.Event(float(a), float(b - a), name)


def made(modules, host=None):
    ops = [_ev(0, 40, "%fusion.1 = bf16[8]{0} fusion(bf16[8] %a)"),
           _ev(60, 80, "%fusion.2 = bf16[8]{0} fusion(bf16[8] %a)")]
    dev = tr.DeviceTrace(ops=ops, async_ops=[], modules=modules)
    return tr.Trace({0: dev}, host or {}, 0.0, 100.0)


def test_idle_time_is_split_by_the_span_the_host_was_in():
    # idle 40..60 and 80..100; the pump covers 30..50 and 90..95
    host = {"main": [_ev(0, 100, "bench/traced"), _ev(30, 50, "serving/pump"),
                     _ev(90, 95, "serving/pump"), _ev(52, 58, "bench/deliver")],
            "other": [_ev(45, 50, "serving/pump")]}
    obs = {"trace": made([], host)}
    read = reader("idle_under_span_pct").read
    assert read(obs, {"span": "serving/pump"}) == pytest.approx(15.0)
    assert read(obs, {"span": "bench/deliver"}) == pytest.approx(6.0)
    assert read(obs, {"span": "bench/traced"}) == pytest.approx(40.0) \
        == pytest.approx(100 * tr.idle_share(obs["trace"]))
    # a program that does not annotate, and a run with no device trace
    assert read(obs, {"span": "serving/admit"}) is None
    assert read({"trace": None}, {"span": "serving/pump"}) is None
    assert read({}, {"span": "serving/pump"}) is None


BURSTS = r"^jit_inference_v2_decode_burst_n_steps(\d+)"


def test_a_programs_step_time_is_its_execution_over_the_steps_it_names():
    modules = [_ev(0, 16e6, "jit_inference_v2_decode_burst_n_steps8(123)"),
               _ev(20e6, 23e6, "jit_inference_v2_decode_burst_n_steps1(456)"),
               _ev(30e6, 54e6, "jit_inference_v2_decode_burst_n_steps8(123)"),
               _ev(60e6, 61e6, "jit_inference_v2_prefill(789)"),
               _ev(70e6, 71e6, "jit__unstack(1)")]
    obs = {"trace": made(modules)}
    read = reader("module_step_ms").read
    # 16/8, 3/1, 24/8 ms
    assert read(obs, {"pattern": BURSTS, "q": 0}) == pytest.approx(2.0)
    assert read(obs, {"pattern": BURSTS, "q": 50}) == pytest.approx(3.0)
    # a pattern that captures no step count divides by one
    assert read(obs, {"pattern": r"^jit_inference_v2_prefill\b", "q": 50}
                ) == pytest.approx(1.0)
    assert read(obs, {"pattern": r"^jit_inference_v2_decode_burst_n_steps8",
                      "q": 0}) == pytest.approx(16.0)
    assert read(obs, {"pattern": "^jit_no_such", "q": 50}) is None
    assert read({"trace": None}, {"pattern": BURSTS, "q": 50}) is None


@pytest.fixture(scope="module")
def recorded() -> tr.Trace:
    text = (DATA / "serve_l2_v5e_decode_step.xspace.txt").read_text()
    return tr.from_profile_data(jax.profiler.ProfileData.from_text_proto(text))


@pytest.mark.parametrize("metric", ["decode_device_step_ms_p50.batch",
                                    "idle_in_pump_share.batch"])
def test_the_recorded_trace_of_the_parents_program_gives_no_reading(
        recorded, metric):
    """PR 23's trace: the decode program is ``jit__unknown(<hash>)`` and
    the host plane holds no program span.  That is the fault ISSUE 24
    removes, and what these metrics read from the parent commit: nothing,
    without raising."""
    names = [m.name for m in recorded.devices[0].modules]
    assert any(n.startswith("jit__unknown(") for n in names)
    spec = manifest.load_json("metrics", metric)
    assert reader(spec["reader"]).read({"trace": recorded},
                                       spec["args"]) is None


def test_the_new_metrics_read_the_names_the_program_gives():
    """The pattern in the metric file against the names ``tracked_jit``
    gives the serving programs: the one-step program, which carries a
    round's prefill chunks since PR 37, counts as one step and the burst
    as eight."""
    from deepspeed_tpu.telemetry.perf.compile_tracker import program_name

    burst = "jit_" + program_name("inference_v2/decode_burst", {"n_steps": 8})
    one = "jit_" + program_name("inference_v2/decode_burst", {"n_steps": 1})
    step = manifest.load_json("metrics", "decode_device_step_ms_p50.batch")
    read = reader(step["reader"]).read
    obs = {"trace": made([_ev(0, 32, burst + "(1)")])}
    assert read(obs, step["args"]) == pytest.approx(4e-6)
    obs = {"trace": made([_ev(0, 32, burst + "(1)"), _ev(60, 75, one + "(2)"),
                          _ev(75, 90, one + "(2)")])}
    assert read(obs, step["args"]) == pytest.approx(15e-6)


@pytest.mark.parametrize("metric, reader_name", [
    ("prefill_wall_share.batch", "span_share_pct"),
    ("prefill_device_share.batch", "module_share_pct")])
def test_the_two_prefill_metrics_are_gone(metric, reader_name):
    """Both read a prefill call of its own, which no round has had since
    PR 37 (``null`` on every ledger line since): entry, file and reader
    went with PR 51.  ``chunk_row_share.batch`` and
    ``calls_with_chunks_share.batch`` say what part of the work is
    prompts."""
    bench = manifest.load_benchmark()
    assert metric not in {m["name"] for m in bench["per_layer"]}
    assert not (manifest.BENCH_DIR / "metrics" / f"{metric}.json").exists()
    assert not (manifest.BENCH_DIR / "readers" / f"{reader_name}.py").exists()
    assert reader_name not in {
        manifest.load_json("metrics", m["name"])["reader"]
        for m in bench["per_layer"]}
    for name in ("chunk_row_share.batch", "calls_with_chunks_share.batch"):
        assert manifest.named(bench["per_layer"], name, "metric")
