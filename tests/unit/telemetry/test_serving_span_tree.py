"""The serving round's span tree: one primitive (``telemetry.span``), two
sinks (the hub's ring and the profiler's trace), one tree per round, a
request's queue wait as a span of its trace id, and names on the device."""

import ast
import functools
import gc
import glob
import logging
import pathlib
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.v2 import KVCacheConfig, engine_v2
from deepspeed_tpu.models import LlamaConfig, LlamaModel
from deepspeed_tpu.serving import ServingParams, build_serving_frontend
from deepspeed_tpu.telemetry import tracer as tracer_mod
from deepspeed_tpu.telemetry.perf import (CompileTracker,
                                          get_compile_tracker, tracked_jit)
from deepspeed_tpu.telemetry.perf.compile_tracker import program_name

#: name -> parent, as ISSUE 24 fixes them, but for the round's call: the
#: front-end drives the engine through ``step_ahead``, which dispatches a
#: step's ONE call (the decode step that carries the round's chunks, or a
#: burst) behind the previous step's and only then fetches that one (ISSUE
#: 39), so ``inference/decode_burst`` is the wait for the PREVIOUS call (its
#: fetch), after this step's dispatch; no call of its own prefills, so there
#: is no ``inference/prefill`` span.  Since ISSUE 38 no stretch of a round
#: lies outside a leaf: the sampling keys' refill (once in 256 calls, the
#: first among them) and the hub's own accounting, last in a step, have spans
TREE = {
    "serving/pump": None,
    "serving/admit": "serving/pump",
    "inference/step": "serving/pump",
    "inference/plan": "inference/step",
    "inference/pack": "inference/step",
    "inference/decode_burst": "inference/step",
    "inference/decode_burst/dispatch": "inference/step",
    "inference/keys": "inference/decode_burst/dispatch",
    "inference/decode_burst/fetch": "inference/decode_burst",
    "inference/commit": "inference/step",
    "inference/observe": "inference/step",
    "serving/deliver": "serving/pump",
    "serving/ledger": "serving/pump",
}


@pytest.fixture(scope="module")
def tiny_model():
    cfg = LlamaConfig.tiny(num_layers=2, max_seq_len=64, dtype=jnp.float32)
    model = LlamaModel(cfg)
    return model, model.init_params(jax.random.PRNGKey(0))


def make_frontend(tiny_model):
    model, params = tiny_model
    return build_serving_frontend(
        model, params, replicas=1,
        cache_config=KVCacheConfig(num_blocks=64, block_size=4,
                                   max_seq_len=64),
        max_batch_slots=2, prefill_chunk=8, prefill_batch=2,
        decode_burst=4, serving_params=ServingParams())


def serve_three(fe):
    """Three prompts over two slots: two chunks and one in a step, with and
    without a row decoding beside them, full bursts, one request queued."""
    rng = np.random.RandomState(7)
    handles = [fe.submit(rng.randint(1, 512, size=n).tolist(),
                         max_new_tokens=6, klass="batch")
               for n in (5, 12, 19)]
    fe.run_until_idle()
    return handles


@pytest.fixture(scope="module")
def served(tiny_model):
    """The scenario once, hub on: the ring's events, the counters, the
    handles with their records."""
    tel = telemetry.get_telemetry()
    tel.reset()
    tel.configure(enabled=True, jsonl=False, prometheus=False)
    fe = make_frontend(tiny_model)
    handles = serve_three(fe)
    # the start's spans (ISSUE 54) land in the hub's ring too while it is
    # on; they are no part of a round and are held apart here
    ring = tel.tracer.events()
    out = {"events": [e for e in ring if not e["name"].startswith("startup/")],
           "startup": [e for e in ring if e["name"].startswith("startup/")],
           "handles": handles,
           "counters": {m.name: m.value
                        for m in tel.registry.metrics().values()
                        if m.kind == "counter"}}
    fe.close()
    tel.reset()
    return out


def named(served, name):
    return [e for e in served["events"] if e["name"] == name]


def test_every_span_of_the_tree_is_there_under_its_parent(served):
    seen = {(e["name"], e["args"].get("parent")) for e in served["events"]
            if "depth" in e["args"]}
    assert seen == set(TREE.items())
    depth = {"serving/pump": 0}
    for name, parent in TREE.items():
        if parent is not None:
            depth[name] = depth[parent] + 1
    for e in served["events"]:
        if "depth" in e["args"]:
            parent = e["args"].get("parent")
            assert e["args"]["depth"] == (
                depth[parent] + 1 if parent else 0), e["name"]
    kinds = {e["args"]["kind"] for e in named(served, "inference/pack")}
    assert kinds == {"prefill", "decode"}
    # the hub was on while the front-end was built: its ring has the start
    assert {"startup/serving_frontend", "startup/engine_v2",
            "startup/place/pools", "startup/frontend"} <= {
                e["name"] for e in served["startup"]}


def test_a_round_with_chunks_is_one_call_left_running(tiny_model):
    """A mixed run (four prompts over two slots, answers long enough that
    later prompts come in beside decoding rows): the children of a step
    that carries chunks are, in order, the plan, the chunks' pack and the
    decode rows' pack, ONE dispatch, THEN the last call's wait and its one
    commit, and the hub's accounting behind them; and most prompt tokens
    are written by a call that also yields a decode token."""
    tel = telemetry.get_telemetry()
    tel.reset()
    tel.configure(enabled=True, jsonl=False, prometheus=False)
    fe = make_frontend(tiny_model)
    rng = np.random.RandomState(11)
    handles = [fe.submit(rng.randint(1, 512, size=n).tolist(),
                         max_new_tokens=new, klass="batch")
               for n, new in ((6, 30), (20, 12), (22, 9), (15, 5))]
    fe.run_until_idle()
    events = tel.tracer.events()
    counters = {m.name: m.value for m in tel.registry.metrics().values()
                if m.kind == "counter"}
    fe.close()
    tel.reset()
    assert [len(h.result()) for h in handles] == [30, 12, 9, 5]
    # events are recorded as they close: a step's children, then the step
    rounds, children = [], []
    for e in events:
        if e["name"] == "inference/step":
            rounds.append((e["args"], children))
            children = []
        elif e["args"].get("parent") == "inference/step":
            children.append((e["name"], e["args"].get("kind")))
    carried = [(args, kids) for args, kids in rounds if args["chunks"]]
    assert len(carried) >= 6
    was_running = False
    for args, kids in rounds:
        want = [("inference/plan", None)]
        if args["chunks"]:
            want.append(("inference/pack", "prefill"))
        if args["chunks"] or args["decoding"]:
            want += [("inference/pack", "decode"),
                     ("inference/decode_burst/dispatch", None)]
        if was_running:         # the call the step before left in flight
            want += [("inference/decode_burst", None),
                     ("inference/commit", None)]
        was_running = bool(args["chunks"] or args["decoding"])
        if len(want) > 1:       # something was committed or dispatched
            want.append(("inference/observe", None))
        assert kids == want, args
    beside = counters["inference/chunk_tokens_beside_decode"]
    assert counters["inference/prefill_tokens"] == 6 + 20 + 22 + 15
    # all but the first round's chunks (6 + 8 tokens: nothing decodes yet)
    # and the fourth prompt's, seated after every other request finished
    alone = [args for args, _ in rounds
             if args["chunks"] and not args["decoding"]]
    assert len(alone) == 1 + 2
    assert beside == counters["inference/prefill_tokens"] - (6 + 8) - 15


def test_children_lie_inside_their_parent_and_sum_to_no_more(served):
    tree = [e for e in served["events"] if "depth" in e["args"]]
    parents = [e for e in tree if e["name"] in set(TREE.values())]
    checked = 0
    for p in parents:
        lo, hi = p["ts"], p["ts"] + p["dur"]
        kids = [e for e in tree if e["args"].get("parent") == p["name"]
                and lo - 0.3 <= e["ts"] and e["ts"] + e["dur"] <= hi + 0.3]
        # stamps are rounded to a tenth of a microsecond
        assert sum(k["dur"] for k in kids) <= p["dur"] + 0.1 * len(kids) + 0.1
        checked += len(kids)
    # every span but the roots was found inside exactly one parent
    roots = len(named(served, "serving/pump"))
    assert checked == len(tree) - roots


def test_prefill_and_decode_spans_read_as_at_the_parent_commit(served):
    """The accepted metrics read these spans and counters: the token
    counters and the served tokens are what commit 6f933b4 gives for the
    same three requests, and every program call is one
    ``inference/decode_burst`` span: a step that carries chunks has
    ``burst`` 1 and counts the rows decoding beside them (none while the
    first prompts come in, and none beside the third request's three
    chunks: it was queued until the others had finished).  The order is
    ``step_ahead``'s: the burst in which the second request finishes is
    committed by the NEXT step, after that round's admissions, so the
    queued third request is seated a round later and one more burst
    (of the one request left decoding) runs before its prefill."""
    got = [{k: v for k, v in e["args"].items()
            if k not in ("depth", "parent", "call")}
           for e in named(served, "inference/decode_burst")]
    chunks = [s["args"]["chunks"] for s in named(served, "inference/step")]
    assert chunks == [2, 1, 0, 0, 1, 1, 1, 0, 0, 0]
    # ``burst`` is the call's STEPS; ``tokens`` (PR 59) what its rows
    # yielded: a token a row a step where the model does not draft
    assert got == [
        {"burst": burst, "batch": batch, "tokens": burst * batch}
        for burst, batch in [(1, 0), (1, 1), (4, 2), (4, 1), (1, 0), (1, 0),
                             (1, 0), (4, 1), (4, 1)]]
    assert not named(served, "inference/prefill")
    # one commit a program call
    assert len(named(served, "inference/commit")) == len(got)
    assert served["counters"]["inference/prefill_tokens"] == 36
    assert served["counters"]["inference/decode_tokens"] == 15
    # the second prompt's last four tokens rode the first one's decode step
    assert served["counters"]["inference/chunk_tokens_beside_decode"] == 4
    assert [h.result() for h in served["handles"]] == [
        [308, 305, 456, 28, 393, 183], [26, 26, 26, 26, 26, 310],
        [291, 259, 123, 399, 27, 224]]


def test_span_arguments_count_what_the_round_did(served):
    """Rounds, steps and admissions are the spans' own count and
    arguments: no counter repeats them."""
    assert not {"serving/pump_rounds", "serving/admitted",
                "inference/steps"} & set(served["counters"])
    assert sum(e["args"]["admitted"]
               for e in named(served, "serving/admit")) == 3
    assert sum(e["args"]["tokens"]
               for e in named(served, "serving/deliver")) == 18
    rounds = [e["args"]["round"] for e in named(served, "serving/pump")]
    assert rounds == list(range(rounds[0], rounds[0] + len(rounds)))
    steps = named(served, "inference/step")
    assert len(steps) == 10      # the last one commits and finds no work
    assert sum(s["args"]["chunks"] for s in steps) == 6
    assert max(s["args"]["decoding"] for s in steps) == 2


def test_each_request_has_one_queued_span_from_its_own_record(served):
    spans = named(served, "serving/request/queued")
    by_id = {e["args"]["trace_id"]: e for e in spans}
    assert len(spans) == len(by_id) == 3
    for h in served["handles"]:
        e, rec = by_id[h.trace_id], h.record
        assert e["args"] == {"trace_id": h.trace_id, "klass": "batch"}
        assert e["dur"] == pytest.approx(
            (rec.admitted_ts - rec.start_ts) * 1e6, abs=0.11)
        assert e["dur"] == pytest.approx(
            rec.to_dict()["queue_wait_ms"] * 1e3, abs=1.0)
    # two slots: the third request waited for one
    waits = sorted(e["dur"] for e in spans)
    assert waits[2] > 10 * waits[1]
    # the later phases stay on the record (TTFT, TPOT, ``breakdown``):
    # no metric reads them as spans, so none is emitted
    assert {e["name"] for e in served["events"]
            if e["name"].startswith("serving/request/")} == {
                "serving/request/queued"}


def test_hub_off_costs_one_shared_object_and_nothing_else(
        tiny_model, monkeypatch):
    tel = telemetry.get_telemetry()
    assert not tel.enabled
    assert tel.span("a") is tel.span("b", args={"x": 1}) \
        is telemetry.span("c") is tracer_mod.NOOP_SPAN
    with tel.span("a") as sp:
        sp.set(n=1)
    # a START has its spans with the hub off too (ISSUE 54: the start-up
    # record), so the front-end is built before spans are refused; a round
    # has none.  The compile tracker is off, as an operator's is (with it
    # on, a program's first call is one more span of the start: the test
    # below)
    monkeypatch.setattr(get_compile_tracker(), "enabled", False)
    fe = make_frontend(tiny_model)
    started = tel.startup.events()
    assert {"startup/serving_frontend", "startup/engine_v2",
            "startup/place/pools"} <= {e["name"] for e in started}

    def refuse(*a, **k):
        raise AssertionError("a span was built with the hub off")

    monkeypatch.setattr(tracer_mod, "_Span", refuse)
    monkeypatch.setattr(tracer_mod, "_trace_annotation", refuse)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)

    class NoLock:
        def __enter__(self):
            raise AssertionError("the tracer's lock was taken")

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(tel.tracer, "_lock", NoLock())
    monkeypatch.setattr(tel.startup, "_lock", NoLock())
    # the hub's own accounting is not run at all: no call's record, no
    # NumPy over the packed lengths, none of the router's counters
    for name in ("_observe", "_count_call", "_count_cache_traffic",
                 "_count_recycled", "_count_moe"):
        monkeypatch.setattr(engine_v2.RaggedInferenceEngineV2, name, refuse)
    handles = serve_three(fe)
    eng = fe.router.replicas[0].engine
    fe.close()
    assert all(len(h.result()) == 6 for h in handles)
    monkeypatch.undo()
    assert tel.tracer.events() == []
    assert tel.startup.events() == started
    assert not any(name.startswith(("serving/", "inference/"))
                   for name in tel.registry.metrics())
    # what a call costs with the hub off: its number
    assert eng._calls == 9 and not eng._inflight


def test_hub_off_tracker_on_a_round_after_the_first_calls_builds_no_span(
        tiny_model, monkeypatch):
    """The benchmark's untraced serving run: hub off, compile tracker on.
    Each program's first call is a span of the start-up record; once every
    program has run, a round builds no span and touches neither ring."""
    tel = telemetry.get_telemetry()
    assert not tel.enabled
    tracker = get_compile_tracker()
    monkeypatch.setattr(tracker, "enabled", True)
    fe = make_frontend(tiny_model)
    serve_three(fe)
    eng = fe.router.replicas[0].engine
    started = tel.startup.events()
    firsts = [e for e in started if e["name"] == "startup/first_call"]
    assert firsts and all(e["args"]["site"] == "inference_v2/decode_burst"
                          for e in firsts)
    # one a program: the (program, static) pairs are distinct
    assert len({e["args"]["program"] for e in firsts}) == len(firsts)

    def refuse(*a, **k):
        raise AssertionError("a span was built in a round")

    class NoLock:
        def __enter__(self):
            raise AssertionError("a ring's lock was taken")

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(tracer_mod, "_Span", refuse)
    monkeypatch.setattr(tel.tracer, "_lock", NoLock())
    monkeypatch.setattr(tel.startup, "_lock", NoLock())
    before = eng._calls
    handles = serve_three(fe)
    fe.close()
    assert all(len(h.result()) == 6 for h in handles)
    monkeypatch.undo()
    assert eng._calls > before and not eng._inflight
    assert tel.startup.events() == started and tel.tracer.events() == []


def test_a_calls_spans_in_two_rounds_carry_its_number(served):
    """A round's dispatch and the NEXT round's wait, fetch and commit are
    one call's: they share ``call``, the engine's count of calls."""
    by_call = {}
    for e in served["events"]:
        if "call" in e["args"] and e["name"] != "inference/call":
            by_call.setdefault(e["args"]["call"], []).append(e["name"])
    assert sorted(by_call) == list(range(1, 10))
    # the ring keeps spans as they close: the dispatch a round before the
    # fetch, its wait and the commit
    assert all(names == ["inference/decode_burst/dispatch",
                         "inference/decode_burst/fetch",
                         "inference/decode_burst", "inference/commit"]
               for names in by_call.values())
    steps = [i for i, e in enumerate(served["events"])
             if e["name"] == "inference/step"]
    which = lambda i: sum(s < i for s in steps)     # the step a span lies in
    for call in by_call:
        at = {e["name"]: which(i) for i, e in enumerate(served["events"])
              if e["args"].get("call") == call}
        assert at["inference/commit"] == at["inference/decode_burst"] \
            == at["inference/decode_burst/dispatch"] + 1
        assert at["inference/call"] == at["inference/commit"]


def test_a_step_in_its_new_order_has_no_hole_and_counts_calls_ahead(served):
    """Plan, packs and dispatch, then the previous call's wait and commit,
    then the accounting: a step's children follow each other with nothing
    between them (the median step spends under a tenth of its time outside
    every child), every committed call is one ``inference/decode_burst``
    span, and a call counts as dispatched ahead when the call before it
    was uncommitted: all nine but the first."""
    tree = [e for e in served["events"] if "depth" in e["args"]]
    outside = []
    for step in named(served, "inference/step"):
        lo, hi = step["ts"], step["ts"] + step["dur"]
        kids = sorted((e["ts"], e["ts"] + e["dur"]) for e in tree
                      if e["args"].get("parent") == "inference/step"
                      and lo - 0.3 <= e["ts"] and e["ts"] + e["dur"]
                      <= hi + 0.3)
        assert all(a[1] <= b[0] + 0.3 for a, b in zip(kids, kids[1:]))
        outside.append(1.0 - sum(b - a for a, b in kids) / step["dur"])
    assert sorted(outside)[len(outside) // 2] < 0.1, outside
    counters = served["counters"]
    assert counters["inference/calls"] == len(
        named(served, "inference/decode_burst")) == 9
    assert counters["inference/calls_dispatched_ahead"] == 8 \
        <= counters["inference/calls"]
    assert counters["inference/rows_overrun"] == 0


def test_every_program_call_has_one_record_of_what_was_committed(served):
    calls = named(served, "inference/call")
    waits = named(served, "inference/decode_burst")
    assert [c["args"]["call"] for c in calls] == list(range(1, 10))
    # stamped elsewhere: in no thread's tree
    assert all("depth" not in c["args"] and "parent" not in c["args"]
               for c in calls)
    got = [(c["args"]["steps"], c["args"]["decode_rows"]) for c in calls]
    assert got == [(w["args"]["burst"], w["args"]["batch"]) for w in waits]
    # what the scheduler committed: the prompts' 36 tokens, chunk by chunk
    # (5 + 8, 4, then 8 + 8 + 3 of the third prompt, seated late), and the
    # 15 decode tokens (a request's first token is a chunk's)
    assert [c["args"]["chunk_tokens"] for c in calls] == [
        13, 4, 0, 0, 8, 8, 3, 0, 0]
    assert [c["args"]["accepted"] for c in calls] == [
        0, 1, 8, 1, 0, 0, 0, 4, 1]
    assert [c["args"]["kb"] for c in calls] == [
        2, 4, None, None, 2, 4, 8, None, None]
    dispatch = {e["args"]["call"]: e
                for e in named(served, "inference/decode_burst/dispatch")}
    commit = {e["args"]["call"]: e
              for e in named(served, "inference/commit")}
    fetch = {e["args"]["call"]: e
             for e in named(served, "inference/decode_burst/fetch")}
    for c in calls:
        n = c["args"]["call"]
        # from the dispatch span's start to the commit's end
        assert c["ts"] == dispatch[n]["ts"]
        assert c["ts"] + c["dur"] == pytest.approx(
            commit[n]["ts"] + commit[n]["dur"], abs=0.21)
        assert c["args"]["wait_s"] == pytest.approx(
            fetch[n]["dur"] * 1e-6, abs=2e-7)


def test_rows_are_counted_where_they_are_decided(served):
    counters = served["counters"]
    calls = named(served, "inference/call")
    slots, chunk_rows = 2, 2 * 8           # B; prefill_batch x prefill_chunk
    assert counters["inference/calls"] == len(calls) == len(
        named(served, "inference/decode_burst")) == 9
    assert counters["inference/calls_with_chunks"] == sum(
        c["args"]["kb"] is not None for c in calls) == 5
    assert counters["inference/chunk_rows_computed"] == 5 * chunk_rows
    assert counters["inference/rows_computed"] == sum(
        c["args"]["steps"] for c in calls) * slots + 5 * chunk_rows
    # to the token what the program already counts over the same rounds
    assert counters["inference/rows_live"] == (
        counters["inference/decode_tokens"]
        + counters["inference/prefill_tokens"]) == 15 + 36
    assert counters["inference/rows_live"] \
        <= counters["inference/rows_computed"]
    # one accounting span a step that committed or dispatched a call
    assert len(named(served, "inference/observe")) == 10


def _moe_engine():
    from deepspeed_tpu.inference.v2 import build_engine_v2
    from deepspeed_tpu.models import MixtralConfig, MixtralModel

    cfg = MixtralConfig.tiny(num_layers=2, max_seq_len=64,
                             dtype=jnp.float32, num_experts=4, top_k=2)
    model = MixtralModel(cfg)
    return build_engine_v2(
        model, model.init_params(jax.random.PRNGKey(2)),
        cache_config=KVCacheConfig(num_blocks=64, block_size=4,
                                   max_seq_len=64),
        max_batch_slots=2, prefill_chunk=8)


def _expected_gauges(eng):
    """What a scrape must read, from the state the engine and its
    scheduler keep anyway."""
    want = dict(eng.scheduler.telemetry_gauges())
    want["inference/kv/pages_in_use/kv"] = float(
        eng.cache_config.num_blocks - 1 - eng.scheduler.allocator.num_free)
    want.update({f"inference/attn/pages_per_step/{kind}": float(pages)
                 for kind, pages in eng.last_attn_pages_per_step.items()})
    want.update({f"inference/attn/query_tokens_per_row/{kind}": float(tokens)
                 for kind, tokens in eng.last_attn_query_tokens.items()})
    stats = eng.last_moe_stats
    if stats:
        want.update({f"inference/moe/expert_load_e{e}": frac
                     for e, frac in enumerate(stats["load"])})
        want["inference/moe/load_imbalance"] = stats["imbalance"]
        want["inference/moe/drop_rate"] = stats["drop_rate"]
    return want


@pytest.mark.parametrize("family", ["scheduler", "pools", "router", "kernel"])
def test_a_rounds_gauges_are_read_from_a_scrape_and_set_by_no_round(
        tiny_model, family):
    """``inference/queue_depth`` and its three neighbours, the pools'
    ``pages_in_use`` and the router's per-expert load were set in every
    round; now the registry's readers work them out, with the values a
    round would have set."""
    tel = telemetry.get_telemetry()
    tel.configure(enabled=True, jsonl=False, prometheus=False)
    if family == "router":
        eng = _moe_engine()
    else:
        model, params = tiny_model
        eng = engine_v2.build_engine_v2(
            model, params, KVCacheConfig(num_blocks=64, block_size=4,
                                         max_seq_len=64),
            max_batch_slots=2, prefill_chunk=8, decode_burst=4)
    rng = np.random.RandomState(5)
    for n in (9, 6, 7):                     # two seated, one queued
        eng.put(rng.randint(1, 512, size=n).tolist(), max_new_tokens=6)
    for _ in range(3):
        eng.step_ahead()
    # off the TPU the reference attends, which walks no pages: nothing is
    # recorded; a program traced for the chip leaves its kernel's P
    assert eng.last_attn_path == "reference"
    assert eng.last_attn_pages_per_step == eng.last_attn_query_tokens == {}
    if family == "kernel":      # as a latent kind's two calls leave them
        eng.last_attn_pages_per_step = {"kv": 16, "kv/chunk": 4}
        eng.last_attn_query_tokens = {"kv": 4}
    names = {"scheduler": {"inference/queue_depth", "inference/prefilling",
                           "inference/batch_occupancy",
                           "inference/kv_pool_utilization"},
             "pools": {"inference/kv/pages_in_use/kv"},
             "kernel": {"inference/attn/pages_per_step/kv",
                        "inference/attn/pages_per_step/kv/chunk",
                        "inference/attn/query_tokens_per_row/kv"},
             "router": {f"inference/moe/expert_load_e{e}" for e in range(4)}
             | {"inference/moe/load_imbalance", "inference/moe/drop_rate"}
             }[family]
    gauges = lambda: {n for n, m in tel.registry.metrics().items()
                      if m.kind == "gauge"}
    assert not names & gauges()          # no round set them
    want = _expected_gauges(eng)
    assert names <= set(want) and want["inference/queue_depth"] == 1.0
    snap = tel.registry.snapshot()["gauges"]
    parsed = telemetry.parse_prometheus_text(tel.prometheus_text())
    for name in names:
        assert snap[name]["value"] == pytest.approx(want[name], abs=1e-12)
        assert parsed[telemetry.prom_name(name)] == pytest.approx(
            want[name], rel=1e-6)
        assert snap[name]["help"] or family == "scheduler"
    if family == "pools":
        assert want["inference/kv/pages_in_use/kv"] > 0
    if family == "router":
        assert sum(want[f"inference/moe/expert_load_e{e}"]
                   for e in range(4)) == pytest.approx(1.0, abs=1e-4)
    # the next scrape follows the state: everything finished and released
    while eng.scheduler.has_work:
        eng.step()
    after = tel.registry.snapshot()["gauges"]
    later = _expected_gauges(eng)
    assert all(after[n]["value"] == pytest.approx(later[n], abs=1e-12)
               for n in names)
    if family in ("scheduler", "pools"):
        assert all(later[n] == 0.0 for n in names)


def test_no_gauge_is_set_inside_a_round():
    """The engine and the v2 scheduler call ``set_gauge`` in their collect
    hooks and nowhere else: not in ``plan_step``, ``step_ahead``,
    ``_ingest_moe_stats`` or ``generate``."""
    root = pathlib.Path(deepspeed_tpu.__file__).parent / "inference" / "v2"
    for f in ("engine_v2.py", "scheduler.py"):
        where = set()
        for fn in ast.walk(ast.parse((root / f).read_text())):
            if isinstance(fn, ast.FunctionDef):
                where |= {fn.name for node in ast.walk(fn)
                          if isinstance(node, ast.Attribute)
                          and node.attr == "set_gauge"}
        assert where == {"_publish_gauges"}, f


@pytest.mark.filterwarnings("ignore:builtin type event_stats")
def test_spans_land_in_the_profilers_trace_nested_on_one_thread(
        tiny_model, tmp_path):
    tel = telemetry.get_telemetry()
    tel.configure(enabled=True, jsonl=False, prometheus=False)
    fe = make_frontend(tiny_model)
    serve_three(fe)                      # compiled before the trace opens
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("bench/pump"):
            serve_three(fe)
    finally:
        jax.profiler.stop_trace()
    fe.close()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    profile = jax.profiler.ProfileData.from_file(path[0])
    host = next(p for p in profile.planes if p.name == "/host:CPU")
    line = next(ln for ln in host.lines
                if any(e.name == "serving/pump" for e in ln.events))
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for e in line.events]
    names = {n for n, _, _ in events}
    # the keys were refilled before the trace opened: 256 calls' worth
    assert set(TREE) - {"inference/keys"} <= names
    assert not any(n.startswith("serving/request/") for n in names)
    outer = next(e for e in events if e[0] == "bench/pump")
    pumps = [e for e in events if e[0] == "serving/pump"]
    fetches = [e for e in events if e[0] == "inference/decode_burst/fetch"]
    assert fetches and all(
        any(p[1] <= f[1] and f[2] <= p[2] for p in pumps) for f in fetches)
    assert all(outer[1] <= p[1] and p[2] <= outer[2] for p in pumps)
    # arguments known when the span opens ride along as stats
    pump = next(e for e in line.events if e.name == "serving/pump")
    assert "round" in dict(pump.stats)


class _Engine:
    def burst(self, x, y, *, kb, n_steps):
        return x * y + kb + n_steps


@pytest.mark.parametrize("tracker", ["none", "off", "on"])
def test_tracked_jit_names_the_module_from_site_and_statics(tracker):
    trk = {"none": None, "off": CompileTracker(enabled=False),
           "on": CompileTracker(enabled=True)}[tracker]
    fn = tracked_jit(functools.partial(_Engine().burst, n_steps=8),
                     "inference_v2/decode_burst", tracker=trk,
                     static_context={"n_steps": 8},
                     static_argnames=("kb",), donate_argnums=(1,))
    x = jnp.ones((4,))
    assert fn.lower(x, x, kb=2).as_text().startswith(
        "module @jit_inference_v2_decode_burst_n_steps8 ")
    np.testing.assert_allclose(fn(x, x + 1, kb=2), 12.0)
    # a static argument outside the context stays outside the name: the
    # one-step program carries its chunks' page bucket that way
    plain = tracked_jit(_Engine().burst, "inference_v2/decode_burst",
                        tracker=trk, static_context={"n_steps": 1},
                        static_argnames=("kb", "n_steps"))
    assert "@jit_inference_v2_decode_burst_n_steps1 " in plain.lower(
        x, x, kb=1, n_steps=1).as_text()
    if trk is not None and trk.enabled:
        assert [e.site for e in trk.events()] == ["inference_v2/decode_burst"]
    assert "jit_" + program_name("inference_v2/decode_burst",
                                 {"n_steps": 1}) \
        == "jit_inference_v2_decode_burst_n_steps1"


def test_the_engines_own_programs_carry_their_names(tiny_model):
    fe = make_frontend(tiny_model)
    eng = fe.router.replicas[0].engine
    serve_three(fe)
    fe.close()
    assert sorted(eng._decode_jits) == [1, 4]
    pool = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        eng.pool)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    mb = eng.cache_config.max_blocks_per_seq
    args = (eng.params, pool, i32(2), (i32(2), i32(2 + eng.prefill_batch)),
            i32(2), i32(2, mb), i32(2), jnp.float32(0),
            jax.random.PRNGKey(0))
    text = eng._decode(4).lower(*args).as_text()
    assert text.startswith("module @jit_inference_v2_decode_burst_n_steps4 ")
    # the step that carries chunks is the one-step program by name, whatever
    # its page bucket; the engine compiles nothing else
    chunks = (i32(2, eng.chunk), i32(2, mb), i32(2), i32(2), None)
    for kb in (2, 4):
        text = eng._decode(1).lower(*args, None, chunks, kb=kb).as_text()
        assert text.startswith(
            "module @jit_inference_v2_decode_burst_n_steps1 ")
    assert not hasattr(eng, "_prefill")


def live_hooks(tel):
    """The hub's collect hooks whose owners are alive (an engine of an
    earlier test, kept by a fixture, may be among them)."""
    gc.collect()
    tel.collect()
    return list(tel._collect_hooks)


def test_gauges_are_worked_out_when_the_registry_is_read(tiny_model):
    tel = telemetry.get_telemetry()
    before = live_hooks(tel)
    tel.configure(enabled=True, jsonl=False, prometheus=False)
    fe = make_frontend(tiny_model)
    serve_three(fe)
    gauges = lambda: {n for n, m in tel.registry.metrics().items()
                      if m.kind == "gauge" and n.startswith("serving/")}
    derived = {"serving/batch_ttft_p50_ms", "serving/batch_ttft_p99_ms",
               "serving/batch_tpot_p50_ms", "serving/batch_queue_depth",
               "serving/interactive_ttft_p50_ms"}
    assert not derived & gauges()        # no round computed them
    parsed = telemetry.parse_prometheus_text(tel.prometheus_text())
    assert derived <= gauges()
    assert parsed["serving_batch_ttft_p50_ms"] > 0
    assert parsed["serving_batch_queue_depth"] == 0
    snap = tel.registry.snapshot()["gauges"]
    assert snap["serving/batch_tpot_p50_ms"]["value"] > 0
    # a closed front-end leaves no hook behind; its engine's and its
    # scheduler's are held weakly and go with them
    fe.close()
    assert len(live_hooks(tel)) == len(before) + 2
    del fe
    assert live_hooks(tel) == before


def test_a_failing_collect_hook_does_not_break_the_export():
    tel = telemetry.get_telemetry()
    before = live_hooks(tel)
    tel.configure(enabled=True, jsonl=False, prometheus=False)
    tel.inc_counter("t/ok")

    def bad():
        raise RuntimeError("source gone")

    tel.add_collect_hook(bad)
    assert "t_ok 1" in tel.prometheus_text()
    tel.remove_collect_hook(bad)
    tel.remove_collect_hook(bad)      # twice is harmless
    assert tel._collect_hooks == before


def test_a_hook_outlives_the_hubs_reset_and_not_its_owner():
    tel = telemetry.get_telemetry()
    before = live_hooks(tel)

    class Source:
        def publish(self):
            telemetry.get_telemetry().set_gauge("t/derived", 7.0)

    src = Source()
    tel.add_collect_hook(src.publish)
    tel.reset()                          # a new registry, the same hooks
    tel.configure(enabled=True, jsonl=False, prometheus=False)
    assert tel.registry.snapshot()["gauges"]["t/derived"]["value"] == 7.0
    del src                              # held weakly: no close() needed
    gc.collect()
    tel.registry.reset()
    assert "t_derived" not in tel.prometheus_text()
    assert tel._collect_hooks == before


def test_a_scrape_never_waits_for_the_round(tiny_model, monkeypatch):
    """The pump holds the front-end's lock through its device calls; a
    reader of the registry takes no part in that."""
    tel = telemetry.get_telemetry()
    fe = make_frontend(tiny_model)
    serve_three(fe)
    held, release = threading.Event(), threading.Event()

    def hold():
        with fe._lock:
            held.set()
            release.wait(30.0)

    holder = threading.Thread(target=hold)
    holder.start()
    try:
        assert held.wait(10.0)
        # hub off: the hook returns before it reads anything
        monkeypatch.setattr(fe.metrics, "publish", None)
        assert tel.prometheus_text() == ""
        monkeypatch.undo()
        tel.configure(enabled=True, jsonl=False, prometheus=False)
        t0 = time.perf_counter()
        parsed = telemetry.parse_prometheus_text(tel.prometheus_text())
        assert time.perf_counter() - t0 < 2.0    # the old hook: 5 s
        assert parsed["serving_batch_ttft_p50_ms"] > 0
    finally:
        release.set()
        holder.join()
        fe.close()


def test_scrapes_beside_a_running_pump_read_whole_windows(
        tiny_model, caplog):
    tel = telemetry.get_telemetry()
    tel.configure(enabled=True, jsonl=False, prometheus=False)
    fe = make_frontend(tiny_model)
    # a window that is full turns over with every sample, which is when
    # a copy beside the writer can be torn
    for trackers in (fe.metrics.ttft, fe.metrics.tpot):
        for t in trackers.values():
            t._samples = type(t._samples)(maxlen=4)
    rng = np.random.RandomState(3)
    fe.start()
    try:
        with caplog.at_level(logging.WARNING):
            handles, scrapes = [], 0
            for _ in range(4):
                handles += [fe.submit(rng.randint(1, 512, size=6).tolist(),
                                      max_new_tokens=3, klass="batch")
                            for _ in range(3)]
                while any(h.status in ("queued", "running") for h in handles):
                    parsed = telemetry.parse_prometheus_text(
                        tel.prometheus_text())
                    assert parsed["serving_batch_queue_depth"] >= 0
                    scrapes += 1
    finally:
        fe.close()
    assert scrapes > 0 and all(len(h.result()) == 3 for h in handles)
    assert not [r for r in caplog.records if "collect hook" in r.message]
    assert telemetry.parse_prometheus_text(
        tel.prometheus_text())["serving_batch_ttft_p50_ms"] > 0


def test_add_records_a_span_stamped_elsewhere_and_set_reaches_the_ring():
    tr = tracer_mod.SpanTracer()
    with tr.span("outer", {"round": 3}) as sp:
        with tr.span("inner"):
            pass
        sp.set(n=2)
    t0 = tr._t0
    tr.add("request/queued", t0 + 1.0, t0 + 1.5, {"trace_id": "abc"})
    inner, outer, added = tr.events()
    assert inner["args"] == {"depth": 1, "parent": "outer"}
    assert outer["args"] == {"round": 3, "n": 2, "depth": 0}
    assert added["args"] == {"trace_id": "abc"}
    assert (added["ts"], added["dur"]) == (1e6, 5e5)
    # an exception inside a span still closes and records it
    with pytest.raises(ValueError):
        with tr.span("raises"):
            raise ValueError
    assert tr.events()[-1]["name"] == "raises" and tr._stack() == []


def test_every_kernel_of_the_two_main_paths_is_named():
    """A ``pl.pallas_call`` without ``name=`` shows in a device trace as
    whatever wraps it (``closed_call.12``, ``shard_map.431``)."""
    root = pathlib.Path(deepspeed_tpu.__file__).parent / "ops" / "pallas"
    names = []
    for f in ("paged_attention.py", "flash_attention.py"):
        for node in ast.walk(ast.parse((root / f).read_text())):
            if isinstance(node, ast.Call) and ast.unparse(
                    node.func) == "pl.pallas_call":
                kw = {k.arg: k.value for k in node.keywords}
                assert "name" in kw, f"{f}:{node.lineno}"
                names.append(kw["name"].value)
    assert len(names) == len(set(names)) == 6
    assert {"paged_decode_attention", "flash_fwd",
            "flash_bwd"} <= set(names)
    # the gate looks for the same names in the lowered programs on the chip
    gate = (root.parents[2] / "chip_smoke.py").read_text()
    expected = ast.literal_eval(
        gate.split("FLASH_KERNELS = ")[1].split("\n")[0])
    paged = ast.literal_eval(gate.split("PAGED_KERNEL = ")[1].split("\n")[0])
    assert expected | {paged} <= set(names)
