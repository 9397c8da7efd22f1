"""The start-up record (ISSUE 54): the program's own spans under
``initialize()`` and ``build_serving_frontend()``, written by the one span
primitive whether the hub is on or off; each program's first call; and the
compile account of the whole process."""

import ast
import json
import logging
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.v2 import KVCacheConfig
from deepspeed_tpu.models import LlamaConfig, LlamaModel
from deepspeed_tpu.parallel import MeshLayout
from deepspeed_tpu.serving import ServingParams, build_serving_frontend
from deepspeed_tpu.telemetry import tracer as tracer_mod
from deepspeed_tpu.telemetry.perf import get_compile_tracker
from deepspeed_tpu.telemetry.perf.compile_tracker import CompileAccount
from deepspeed_tpu.utils import groups

sys.path.insert(0, str(__import__("pathlib").Path(__file__).parents[3]))
from perfbench import manifest  # noqa: E402

ROOT = __import__("pathlib").Path(__file__).parents[3]

#: name -> the parents it may lie under, as ISSUE 54 fixes them
#: (``startup/distributed`` and ``startup/dataloader`` are leaves the
#: builder added; a second ``startup/engine/optimizer`` is the optimizer
#: state's shapes; since PR 55 ``orbax.checkpoint`` is an import leaf of
#: its own, under the span that first needs it: the snapshots' or an
#: async checkpoint engine's)
INITIALIZE = {
    "startup/initialize": {None},
    "startup/import": {"startup/initialize", "startup/engine",
                       "startup/engine/resilience"},
    "startup/distributed": {"startup/initialize"},
    "startup/config": {"startup/initialize"},
    "startup/mesh": {"startup/initialize"},
    "startup/observability": {"startup/initialize"},
    "startup/engine": {"startup/initialize"},
    "startup/engine/optimizer": {"startup/engine"},
    "startup/place/shardings": {"startup/engine"},
    "startup/place/params": {"startup/engine"},
    "startup/place/opt_state": {"startup/engine"},
    "startup/engine/resilience": {"startup/engine"},
}
SERVING = {
    "startup/serving_frontend": {None},
    "startup/import": {"startup/serving_frontend"},
    "startup/engine_v2": {"startup/serving_frontend"},
    "startup/place/weights": {"startup/engine_v2"},
    "startup/place/pools": {"startup/engine_v2"},
    "startup/engine_v2/programs": {"startup/engine_v2"},
    "startup/frontend": {"startup/serving_frontend"},
}
#: no hole: an inner span's own time is under 5% of it, or under 50 ms
HOLE_SHARE, HOLE_S = 0.05, 0.050

HYBRID = dict(
    vocab_size=256, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_attention_heads=8, head_dim=24,
    v_head_dim=16, num_key_value_heads=2, swa_num_key_value_heads=4,
    sliding_window=8, partial_rotary_factor=0.334, rope_theta=1e7,
    swa_rope_theta=1e4, attention_value_scale=0.707, layernorm_epsilon=1e-5,
    published={"n_routed_experts": 32}, n_routed_experts=8, expert_rank=1,
    num_experts_per_tok=3, norm_topk_prob=True,
    hybrid_layer_pattern=[0, 1, 1, 0], moe_layer_freq=[0, 1, 1, 1],
    max_position_embeddings=256, run={"dtype": "float32"})


@pytest.fixture(autouse=True)
def clean_hub():
    tel = telemetry.get_telemetry()
    tel.reset()
    tracker = get_compile_tracker()
    was = tracker.enabled
    yield tel
    tracker.configure(enabled=was)
    tel.reset()


def tree_of(events, root):
    """The root's event and the nested events that lie in it."""
    top = [e for e in events if e["name"] == root
           and e["args"].get("depth") == 0]
    assert len(top) == 1
    top = top[0]
    inside = [e for e in events if "depth" in e["args"]
              and top["start"] <= e["start"] and e["end"] <= top["end"]]
    return top, inside


def check_tree(events, root, names):
    top, inside = tree_of(events, root)
    seen = {}
    for e in inside:
        seen.setdefault(e["name"], set()).add(e["args"].get("parent"))
    # every name of the contract is there, under a parent it may have
    assert set(seen) - {"startup/first_call"} == set(names), sorted(seen)
    for name, parents in seen.items():
        if name != "startup/first_call":
            assert parents <= names[name], (name, parents)
    # every span lies in its parent: the innermost span around it that is
    # one level up carries the name it gives as its parent
    for e in inside:
        if e is top:
            continue
        around = [p for p in inside if p is not e
                  and p["args"]["depth"] == e["args"]["depth"] - 1
                  and p["start"] <= e["start"] and e["end"] <= p["end"]]
        assert len(around) == 1, e["name"]
        assert around[0]["name"] == e["args"]["parent"], e["name"]
    # no hole: what an inner span's children leave of it
    for p in inside:
        kids = [e for e in inside if e is not p
                and e["args"]["depth"] == p["args"]["depth"] + 1
                and p["start"] <= e["start"] and e["end"] <= p["end"]]
        if not kids:
            continue
        own = (p["end"] - p["start"]) - sum(k["end"] - k["start"]
                                            for k in kids)
        assert own >= -1e-6
        assert own < HOLE_S or own < HOLE_SHARE * (p["end"] - p["start"]), (
            p["name"], own, p["end"] - p["start"])
    return top, inside


def first_calls(events):
    return [e for e in events if e["name"] == "startup/first_call"]


# -- initialize() -----------------------------------------------------------

def initialize_tiny(stage, **config):
    mesh = groups.initialize_mesh(MeshLayout.infer(jax.device_count()))
    cfg = LlamaConfig.tiny(num_layers=2, max_seq_len=32, dtype=jnp.float32)
    model = LlamaModel(cfg, mesh=mesh)
    params = model.init_params(jax.random.PRNGKey(0))
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, mesh=mesh, config=dict({
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": stage}}, **config))
    batch = {"input_ids": jnp.ones((jax.device_count(), 32), jnp.int32)}
    return engine, batch


@pytest.mark.parametrize("stage", [2, 3])
def test_initialize_has_the_tree_with_the_hub_off(stage, clean_hub, caplog):
    tel = clean_hub
    # the package's logger does not propagate: the handler goes on it
    logger = logging.getLogger("deepspeed_tpu")
    logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger="deepspeed_tpu"):
            engine, batch = initialize_tiny(stage)
    finally:
        logger.removeHandler(caplog.handler)
    assert not tel.enabled
    events = tel.startup.events()
    top, inside = check_tree(events, "startup/initialize", INITIALIZE)
    assert top["args"]["stage"] == stage
    assert top["args"]["world"] == jax.device_count()
    # the import that brought this very package in was stamped by the
    # caller and handed over: it starts the root
    entry = next(e for e in inside if e["args"].get("module")
                 == "deepspeed_tpu.runtime.entry")
    assert entry["start"] == top["start"] and entry["args"]["depth"] == 1
    modules = {e["args"]["module"] for e in inside
               if e["name"] == "startup/import"}
    assert {"deepspeed_tpu.resilience", "deepspeed_tpu.tuning",
            "deepspeed_tpu.monitor"} <= modules
    placed = [e for e in inside if e["name"].startswith("startup/place/")]
    assert all(e["args"]["fenced"] is False for e in placed
               if e["name"] != "startup/place/shardings")
    assert {e["args"]["of"] for e in placed
            if e["name"] == "startup/place/shardings"} == {"params",
                                                           "opt_state"}
    # the optimizer state's program was compiled while it was placed
    opt = next(e for e in placed if e["name"] == "startup/place/opt_state")
    assert opt["args"]["compile_s"] > 0 and opt["args"]["trace_s"] > 0
    # one line a start, at INFO
    lines = [r.getMessage() for r in caplog.records
             if "start-up" in r.getMessage()]
    assert len(lines) == 1 and "(startup/initialize)" in lines[0]
    assert "import" in lines[0] and "programs:" in lines[0]
    # the hub's own ring and registry stayed empty
    assert tel.tracer.events() == [] and not tel.registry.metrics()

    # the step program's first call: once, with the account's seconds
    assert first_calls(tel.startup.events()) == []
    engine.train_step(batch)
    calls = first_calls(tel.startup.events())
    assert [c["args"]["site"] for c in calls] == ["engine/train_step"]
    args = calls[0]["args"]
    assert args["trace_s"] > 0 and args["lower_s"] > 0 \
        and args["compile_s"] > 0 and args["cache_hit"] is False
    assert args["trace_s"] + args["lower_s"] + args["compile_s"] \
        <= calls[0]["end"] - calls[0]["start"]
    # a second step adds nothing to the record
    n = len(tel.startup.events())
    engine.train_step(batch)
    engine.train_step(batch)
    assert len(tel.startup.events()) == n


def test_initialize_with_the_hub_on_writes_both_rings_and_fences(clean_hub):
    tel = clean_hub
    engine, batch = initialize_tiny(2, telemetry={
        "enabled": True, "jsonl": False, "prometheus": False})
    assert tel.enabled
    record = tel.startup.events()
    check_tree(record, "startup/initialize", INITIALIZE)
    ring = [e for e in tel.tracer.events()
            if e["name"].startswith("startup/")]
    # every span that closed after the hub was switched on is in its ring
    # too, under the same name, depth and parent: recorded once each
    on = {(e["name"], e["args"].get("depth"), e["args"].get("parent"),
           e["dur"]) for e in ring}
    assert on <= {(e["name"], e["args"].get("depth"),
                   e["args"].get("parent"), e["dur"]) for e in record}
    assert {"startup/initialize", "startup/engine", "startup/place/params",
            "startup/place/opt_state"} <= {e["name"] for e in ring}
    assert not [e for e in tel.tracer.events()
                if e["name"].startswith("zero/")]
    placed = [e for e in record if e["name"] in ("startup/place/params",
                                                 "startup/place/opt_state")]
    assert placed and all(e["args"]["fenced"] is True for e in placed)
    # the engine's tracker is on: the tracked jits record their own first
    # calls (the optimizer's init inside its placement, then the step's)
    engine.train_step(batch)
    sites = [c["args"]["site"] for c in first_calls(tel.startup.events())]
    assert sites == ["engine/opt_init", "engine/train_step"]
    # the gauges are worked out when the registry is read, never at start
    assert not any(n.startswith("startup/") for n in tel.registry.metrics())
    snap = tel.registry.snapshot()
    gauges = {n: m for n, m in tel.registry.metrics().items()
              if n.startswith("startup/")}
    assert {"startup/total_s", "startup/import_s", "startup/place_s",
            "startup/engine_s"} <= set(gauges), sorted(snap)
    phases = sum(g.value for n, g in gauges.items()
                 if n != "startup/total_s")
    assert phases == pytest.approx(gauges["startup/total_s"].value)
    # tracer.reset() (what the serving runner calls at window open) leaves
    # the record; Telemetry.reset() empties it
    kept = tel.startup.events()
    tel.tracer.reset()
    assert tel.tracer.events() == [] and tel.startup.events() == kept
    assert kept[:len(record)] == record
    tel.reset()
    assert tel.startup.events() == []


def test_the_record_is_the_startup_context_of_a_bundle(clean_hub, tmp_path):
    tel = clean_hub
    initialize_tiny(2, telemetry={
        "enabled": True, "jsonl": False, "prometheus": False,
        "output_path": str(tmp_path),
        "flight_recorder": {"enabled": True, "install_handlers": False}})
    from deepspeed_tpu.telemetry import get_flight_recorder, load_bundle

    doc = load_bundle(get_flight_recorder().dump("test"))["manifest"]
    startup = doc["context"]["startup"]
    assert startup["roots"][0]["root"] == "startup/initialize"
    assert startup["roots"][0]["total_s"] == pytest.approx(
        sum(startup["roots"][0]["phases"].values()))
    assert {"trace_s", "lower_s", "compile_s", "cache_hits",
            "cache_misses"} <= set(startup["compile_account"])
    assert any(s["name"] == "startup/place/params" for s in startup["spans"])


# -- build_serving_frontend() -----------------------------------------------

def dense_model():
    cfg = LlamaConfig.tiny(num_layers=2, max_seq_len=64, dtype=jnp.float32)
    model = LlamaModel(cfg)
    return model, model.init_params(jax.random.PRNGKey(0))


def hybrid_model():
    model = manifest.load_module("models", "mimo_v2").build(HYBRID)
    return model, model.init_params(jax.random.PRNGKey(7))


@pytest.mark.parametrize("make", [dense_model, hybrid_model],
                         ids=["dense", "hybrid"])
def test_serving_frontend_has_the_tree_with_the_hub_off(make, clean_hub):
    tel = clean_hub
    get_compile_tracker().configure(enabled=True)
    model, params = make()
    fe = build_serving_frontend(
        model, params, replicas=2,
        cache_config=KVCacheConfig(num_blocks=64, block_size=4,
                                   max_seq_len=64),
        max_batch_slots=2, prefill_chunk=8, prefill_batch=1,
        decode_burst=4, serving_params=ServingParams())
    top, inside = check_tree(tel.startup.events(),
                             "startup/serving_frontend", SERVING)
    assert top["args"]["replicas"] == 2
    engines = [e for e in inside if e["name"] == "startup/engine_v2"]
    assert [e["args"]["replica"] for e in engines] == [0, 1]
    pools = [e for e in inside if e["name"] == "startup/place/pools"]
    assert len(pools) == 2
    # nothing was compiled for a program yet: the programs are callables
    assert first_calls(tel.startup.events()) == []

    def serve():
        rng = np.random.RandomState(7)
        handles = [fe.submit(rng.randint(1, 256, size=n).tolist(),
                             max_new_tokens=6, klass="batch")
                   for n in (5, 12, 19)]
        fe.run_until_idle()
        return handles

    serve()
    calls = first_calls(tel.startup.events())
    assert calls and all(c["args"]["site"] == "inference_v2/decode_burst"
                         and c["args"]["compile_s"] > 0 for c in calls)
    assert {c["args"]["static"]["n_steps"] for c in calls} == {1, 4}
    # a second pass over the same prompts: no event is added
    n = len(tel.startup.events())
    assert all(len(h.result()) == 6 for h in serve())
    assert len(tel.startup.events()) == n
    fe.close()
    assert tel.tracer.events() == []
    assert not any(name.startswith("startup/")
                   for name in tel.registry.metrics())


# -- the primitive ------------------------------------------------------------

def test_one_span_primitive(clean_hub):
    tel = clean_hub
    assert not tel.enabled
    span = tel.startup_span("startup/config", {"x": 1})
    assert type(span) is tracer_mod._Span
    assert type(telemetry.startup_span("startup/mesh")) is tracer_mod._Span
    assert isinstance(tel.startup, tracer_mod.SpanTracer)
    with tel.startup_span("startup/a") as outer:
        assert tel.startup.innermost() is outer
        with tel.startup_span("startup/b") as inner:
            assert tel.startup.innermost() is inner
        assert tel.startup.innermost() is outer
    assert tel.startup.innermost() is None
    a, b = {e["name"]: e for e in tel.startup.events()}["startup/a"], \
        {e["name"]: e for e in tel.startup.events()}["startup/b"]
    assert b["args"] == {"depth": 1, "parent": "startup/a"}
    assert a["args"] == {"depth": 0}
    # stamps are perf_counter() seconds, unrounded
    assert a["start"] == outer.start and a["end"] == outer.end
    assert a["start"] <= b["start"] <= b["end"] <= a["end"] \
        <= time.perf_counter()
    # another thread's spans nest on their own
    seen = []
    t = threading.Thread(target=lambda: seen.append(tel.startup.innermost()))
    with tel.startup_span("startup/c"):
        t.start()
        t.join()
    assert seen == [None]


def test_add_takes_a_parent_and_a_depth(clean_hub):
    tel = clean_hub
    tel.startup.add("startup/import", 1.0, 3.5, {"module": "m"},
                    parent="startup/initialize", depth=1)
    tel.tracer.add("serving/request/queued", 1.0, 2.0, {"trace_id": "t"})
    (e,) = tel.startup.events()
    assert e["args"] == {"module": "m", "parent": "startup/initialize",
                         "depth": 1}
    assert (e["start"], e["end"]) == (1.0, 3.5)
    (q,) = tel.tracer.events()
    assert q["args"] == {"trace_id": "t"}


def test_phases_add_up_to_the_root(clean_hub):
    mk = lambda name, start, end, depth, parent=None, **args: {
        "name": name, "tid": 1, "start": start, "end": end,
        "args": dict(args, depth=depth,
                     **({"parent": parent} if parent else {}))}
    R = "startup/initialize"
    events = [
        mk("startup/import", 0.0, 4.0, 1, R, module="a"),
        mk("startup/config", 4.0, 4.5, 1, R),
        mk("startup/place/params", 5.0, 6.0, 2, "startup/engine"),
        mk("startup/import", 6.0, 9.0, 2, "startup/engine", module="b"),
        mk("startup/engine", 4.5, 9.5, 1, R),
        mk(R, 0.0, 10.0, 0),
        {"name": "startup/first_call", "tid": 1, "start": 11.0, "end": 12.0,
         "args": {"depth": 0}},
    ]
    (root,) = tracer_mod.startup_phases(events)
    assert root["phases"] == {"other": 0.5, "import": 7.0, "config": 0.5,
                              "engine": 1.0, "place": 1.0}
    assert sum(root["phases"].values()) == root["total_s"] == 10.0
    assert root["largest_import"] == ("a", 4.0)


# -- the compile account ------------------------------------------------------

def test_the_account_adds_up_and_fills_the_open_span(clean_hub):
    tel = clean_hub
    account = get_compile_tracker().account
    assert account is get_compile_tracker().account
    account.reset()
    with tel.startup_span("startup/place/pools") as span:
        jax.jit(lambda x: jnp.sin(x) * 3 + x.sum())(jnp.ones((3, 5)))
    (event,) = [e for e in tel.startup.events()
                if e["name"] == "startup/place/pools"]
    for kind in ("trace_s", "lower_s", "compile_s"):
        assert event["args"][kind] > 0
    # a compile under no start-up span is the caller's
    jax.jit(lambda x: jnp.cos(x) - 2)(jnp.ones((7,)))
    stamped = account.events()
    sums = account.sums()
    for kind, total in account.totals.items():
        assert total == pytest.approx(
            sum(v for _, k, v in stamped if k == kind))
        assert sums[kind] == pytest.approx(total)
    assert account.totals["compile_s"] > event["args"]["compile_s"]
    assert account.totals["trace_s"] > event["args"]["trace_s"]
    assert sums["programs"] >= 2
    assert all(a <= b for (a, _, _), (b, _, _) in zip(stamped, stamped[1:]))
    # clipped to a stretch: what ended before the second program
    cut = span.end
    assert account.sums(until=cut)["compile_s"] == pytest.approx(
        event["args"]["compile_s"])


def test_nested_traces_are_counted_once():
    """JAX reports every jitted function traced, the inner ones of a
    program too: an outer trace is counted less what ended inside it."""
    account = CompileAccount()
    trace = "/jax/core/compile/jaxpr_trace_duration"
    real = time.perf_counter
    now = [100.0]
    time_mod = __import__("deepspeed_tpu.telemetry.perf.compile_tracker",
                          fromlist=["time"]).time
    mp = pytest.MonkeyPatch()
    mp.setattr(time_mod, "perf_counter", lambda: now[0])
    try:
        for at, seconds in ((100.2, 0.1), (100.5, 0.2),    # two inner
                            (101.0, 1.0),                  # their outer
                            (103.0, 0.5)):                 # a sibling
            now[0] = at
            account._on_duration(trace, seconds)
        now[0] = 103.5
        account._on_duration(
            "/jax/core/compile/backend_compile_duration", 0.25)
        account._on_event("/jax/compilation_cache/cache_hits")
        account._on_duration("/not/one/of/ours", 9.0)
    finally:
        mp.undo()
    assert real() > 0
    assert account.totals["trace_s"] == pytest.approx(1.5)
    assert account.totals["compile_s"] == 0.25
    assert account.totals["cache_hits"] == 1.0
    # a thread's run of traces is one stamped entry, at its last end
    assert account.events() == [(103.0, "trace_s", pytest.approx(1.5)),
                                (103.5, "compile_s", 0.25),
                                (103.5, "cache_hits", 1.0)]
    assert account.sums(until=103.0)["compile_s"] == 0.0


def test_one_listener_for_the_process():
    account = get_compile_tracker().account
    from jax._src import monitoring

    listeners = monitoring.get_event_duration_listeners()
    assert sum(getattr(fn, "__self__", None) is account
               for fn in listeners) == 1
    assert account.register() is account
    assert len(monitoring.get_event_duration_listeners()) == len(listeners)
# -- the package --------------------------------------------------------------

# -- the package ----------------------------------------------------------------

def test_the_package_import_is_stamped_and_loads_nothing_new():
    code = (
        "import sys, time\n"
        "t0 = time.perf_counter()\n"
        "import deepspeed_tpu\n"
        "t1 = time.perf_counter()\n"
        "assert not [m for m in sys.modules if m.startswith("
        "'deepspeed_tpu.telemetry') or m.startswith('deepspeed_tpu.runtime')"
        " or m.startswith('orbax')], 'a light use pays for nothing new'\n"
        "a, b = deepspeed_tpu._IMPORT_STAMPS\n"
        "assert t0 <= a <= b <= t1\n"
        "from deepspeed_tpu import telemetry\n"
        "(e,) = telemetry.get_telemetry().startup.events()\n"
        "assert e['name'] == 'startup/package_import'\n"
        "assert (e['start'], e['end']) == (a, b)\n"
        "assert 'depth' not in e['args']\n"
        "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True, timeout=300,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


# -- the checkpoint library -------------------------------------------------

#: a process of its own (this one may hold ``orbax.checkpoint`` from any
#: test before): ``initialize()`` of a tiny model under ``argv[1]``'s
#: configuration and one ``train_step``, then what the case asks
A_START = """
import json, sys, tempfile
import jax, jax.numpy as jnp, numpy as np
import deepspeed_tpu
from deepspeed_tpu.models import LlamaConfig, LlamaModel
from deepspeed_tpu.parallel import MeshLayout
from deepspeed_tpu.telemetry import get_telemetry
from deepspeed_tpu.utils import groups

case, scratch = sys.argv[1], tempfile.mkdtemp()
config = json.loads(sys.argv[2].replace("SCRATCH", scratch))
mesh = groups.initialize_mesh(MeshLayout.infer(jax.device_count()))
model = LlamaModel(LlamaConfig.tiny(num_layers=2, max_seq_len=32,
                                    dtype=jnp.float32), mesh=mesh)
engine, *_ = deepspeed_tpu.initialize(
    model=model, model_parameters=model.init_params(jax.random.PRNGKey(0)),
    mesh=mesh, config=dict({
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2}}, **config))
tel = get_telemetry()
# (the bare namespace ``google.cloud`` is made by a .pth file as the
# interpreter starts; what orbax's logging loads lies under it)
heavy = lambda: sorted(m for m in sys.modules
                       if m.startswith(("orbax", "google.cloud.")))
named = lambda events: [e for e in events if e["name"] == "startup/import"
                        and e["args"]["module"] == "orbax.checkpoint"]
(root,) = [e for e in tel.startup.events()
           if e["name"] == "startup/initialize"]
at_return = "orbax.checkpoint" in sys.modules
batch = {"input_ids": jnp.ones((jax.device_count(), 32), jnp.int32)}
engine.train_step(batch)

if case in ("nothing_saves", "first_save"):
    # a run that configured nothing that saves loads no checkpoint library
    assert not heavy(), heavy()
    assert not named(tel.startup.events()) and not named(tel.tracer.events())
if case == "first_save":
    # the first save pays the load and says so: one span of the hub (this
    # is no start), inside the save's own, and none for the second save
    saved = jax.device_get(engine.state.params)
    engine.save_checkpoint(scratch + "/ckpt")
    assert "orbax.checkpoint" in sys.modules
    (span,) = named(tel.tracer.events())
    assert span["args"]["parent"] == "checkpoint/save"
    assert not named(tel.startup.events())
    engine.train_step(batch)
    engine.save_checkpoint(scratch + "/ckpt")
    assert len(named(tel.tracer.events())) == 1
    path, _ = engine.load_checkpoint(scratch + "/ckpt", tag="global_step1")
    assert path is not None and engine.global_steps == 1
    for a, b in zip(jax.tree.leaves(saved),
                    jax.tree.leaves(jax.device_get(engine.state.params))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
if case in ("snapshots", "async_engine"):
    # a run that will save on a deadline has the library when
    # initialize() returns: a leaf of its own under the start's root
    assert at_return
    (leaf,) = named(tel.startup.events())
    assert leaf["tid"] == root["tid"] and "depth" in leaf["args"]
    assert root["start"] <= leaf["start"] and leaf["end"] <= root["end"]
    assert leaf["args"]["parent"] == sys.argv[3]
    (start,) = tel.startup_report()["roots"]
    assert start["largest_import"][0] == "orbax.checkpoint"
    # and the resilience package's own import stayed light beside it
    (light,) = [e for e in tel.startup.events()
                if e["args"].get("module") == "deepspeed_tpu.resilience"]
    assert light["end"] - light["start"] < leaf["end"] - leaf["start"]
print("ok")
"""


#: case -> (what it adds to the configuration, the leaf's parent span)
STARTS = {
    "nothing_saves": ({}, None),
    "first_save": ({"telemetry": {"enabled": True, "jsonl": False,
                                  "prometheus": False}}, None),
    "snapshots": ({"resilience": {"enabled": True,
                                  "snapshot_dir": "SCRATCH/snaps"}},
                  "startup/engine/resilience"),
    "async_engine": ({"checkpoint": {"checkpoint_engine":
                                     {"type": "async"}}}, "startup/engine"),
}


@pytest.mark.parametrize("case", STARTS)
def test_the_checkpoint_library_is_loaded_by_what_saves(case):
    config, parent = STARTS[case]
    out = subprocess.run(
        [sys.executable, "-c", A_START, case, json.dumps(config),
         str(parent)], cwd=ROOT, capture_output=True, text=True,
        timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0 and out.stdout.strip().endswith("ok"), \
        out.stderr[-4000:]


def _imported_with_the_module(tree):
    """``(line, module)`` of every import that runs as the module is
    imported: not those in a function's body, nor under ``if
    TYPE_CHECKING:``."""
    todo = list(tree.body)
    while todo:
        node = todo.pop()
        if isinstance(node, ast.Import):
            yield from ((node.lineno, a.name) for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            yield node.lineno, node.module or ""
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.Lambda)):
            continue
        elif isinstance(node, ast.If) and "TYPE_CHECKING" in ast.unparse(
                node.test):
            todo.extend(node.orelse)
        else:
            todo.extend(ast.iter_child_nodes(node))


def test_no_module_of_the_package_imports_orbax_as_it_is_imported():
    """The next top-level ``import orbax`` would cost every training start
    its 13 s again (PERF.md, PR 55) with no other test red: the library
    comes from ``checkpoint_engine.orbax_checkpoint()``, inside the
    function that needs it."""
    found = []
    for path in sorted((ROOT / "deepspeed_tpu").rglob("*.py")):
        tree = ast.parse(path.read_text(), filename=str(path))
        found += [f"{path.relative_to(ROOT)}:{line}: imports {module}"
                  for line, module in _imported_with_the_module(tree)
                  if module.split(".")[0] == "orbax"]
    assert not found, "\n".join(found)
    # the walk sees what it has to see
    seen = dict(_imported_with_the_module(ast.parse(
        "import os\ntry:\n    import orbax.checkpoint as ocp\n"
        "except ImportError:\n    ocp = None\n"
        "class A:\n    from orbax import checkpoint\n"
        "def f():\n    import orbax.checkpoint\n"
        "if TYPE_CHECKING:\n    import orbax\nelse:\n    import json\n")))
    assert seen == {1: "os", 3: "orbax.checkpoint", 7: "orbax", 13: "json"}
