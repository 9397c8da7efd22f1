"""Telemetry stack tests (ISSUE 1): metrics registry, exporters, span
tracer, MonitorMaster fan-out with the telemetry backend, and the
acceptance-criteria StepRecord round trip from a 2-step CPU train loop.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.telemetry import (MetricsRegistry, SpanTracer, StepRecord,
                                     get_telemetry, parse_prometheus_text,
                                     publish_step_record)


@pytest.fixture(autouse=True)
def _fresh_hub():
    get_telemetry().reset()
    yield
    get_telemetry().reset()


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_counter_and_gauge():
    reg = MetricsRegistry()
    c = reg.counter("swap/evictions", "help text")
    c.inc()
    c.inc(3)
    assert c.value == 4
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("train/loss")
    g.set(2.5)
    assert g.value == 2.5
    # get-or-create returns the same object; kind mismatch is an error
    assert reg.counter("swap/evictions") is c
    with pytest.raises(TypeError):
        reg.gauge("swap/evictions")


def test_histogram_bucketing():
    reg = MetricsRegistry()
    h = reg.histogram("t", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 0.9, 5.0, 50.0, 5000.0):
        h.observe(v)
    cum = h.bucket_counts()
    assert cum["1.0"] == 2          # 0.5, 0.9
    assert cum["10.0"] == 3         # + 5.0
    assert cum["100.0"] == 4        # + 50.0
    assert cum["+Inf"] == 5         # + 5000.0
    assert h.count == 5
    assert h.sum == pytest.approx(5056.4)
    # boundary lands in the bucket whose upper bound it equals (le=)
    h2 = reg.histogram("t2", buckets=(1.0, 10.0))
    h2.observe(10.0)
    assert h2.bucket_counts()["10.0"] == 1


def test_prometheus_exposition_parses_cleanly():
    reg = MetricsRegistry()
    reg.counter("comm/ops_total", "ops").inc(7)
    reg.gauge("train/tokens_per_sec").set(1234.5)
    reg.histogram("train/step_time_ms", buckets=(10.0, 100.0)).observe(42.0)
    text = reg.prometheus_text()
    assert "# TYPE comm_ops_total counter" in text
    assert "# TYPE train_step_time_ms histogram" in text
    parsed = parse_prometheus_text(text)  # raises on malformed lines
    assert parsed["comm_ops_total"] == 7
    assert parsed["train_tokens_per_sec"] == 1234.5
    assert parsed['train_step_time_ms_bucket{le="100.0"}'] == 1
    assert parsed['train_step_time_ms_bucket{le="+Inf"}'] == 1
    assert parsed["train_step_time_ms_count"] == 1
    assert parsed["train_step_time_ms_sum"] == 42.0


def test_jsonl_event_log(tmp_path):
    reg = MetricsRegistry()
    path = str(tmp_path / "events.jsonl")
    reg.attach_event_log(path)
    reg.emit_event("step", {"step": 1, "loss": 0.5})
    reg.emit_event("monitor", {"tag": "Train/loss", "value": 0.5, "step": 1})
    lines = [json.loads(ln) for ln in open(path).read().splitlines()]
    assert [e["kind"] for e in lines] == ["step", "monitor"]
    assert lines[0]["loss"] == 0.5
    assert all("ts" in e for e in lines)


def test_events_survive_a_reconfigure_on_another_thread(tmp_path):
    """Emitters that race another thread's re-attach (five in-process
    hosts share one hub) neither fail their training step on a closed
    file nor lose an event: each lands in the log attached when its
    emitter got there."""
    import threading

    reg = MetricsRegistry()
    paths = [str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")]
    for path in paths:  # both exist, however early the emitters finish
        reg.attach_event_log(path)
    n_threads, n_events, errors = 4, 1000, []

    def emit(t):
        try:
            for i in range(n_events):
                reg.emit_event("step", {"t": t, "i": i})
        except Exception as e:
            errors.append(e)

    threads = [threading.Thread(target=emit, args=(t,))
               for t in range(n_threads)]
    for th in threads:
        th.start()
    swaps = 0
    while any(th.is_alive() for th in threads):
        swaps += 1
        reg.attach_event_log(paths[swaps % 2])
    for th in threads:
        th.join()
    reg.detach_event_log()
    assert not errors, errors
    reg.emit_event("step", {"t": -1, "i": 0})  # detached: a no-op
    seen = [(e["t"], e["i"]) for p in paths
            for e in map(json.loads, open(p).read().splitlines())]
    assert sorted(seen) == [(t, i) for t in range(n_threads)
                            for i in range(n_events)]


def test_step_record_publish_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.attach_event_log(str(tmp_path / "e.jsonl"))
    rec = StepRecord(step=3, step_time_ms=12.5, device_fenced=True,
                     samples_per_sec=8.0, tokens_per_sec=1024.0, loss=1.25,
                     grad_norm=0.5, lr=1e-3, loss_scale=1.0, overflow=False,
                     skipped_steps=0, comm_bytes=4096, comm_ops=2,
                     memory={"device_in_use_GB": 0.1})
    publish_step_record(reg, rec)
    parsed = parse_prometheus_text(reg.prometheus_text())
    assert parsed["train_steps_total"] == 1
    assert parsed["train_tokens_per_sec"] == 1024.0
    assert parsed["comm_bytes_total"] == 4096
    assert parsed["memory_device_in_use_GB"] == 0.1
    ev = json.loads(open(tmp_path / "e.jsonl").read())
    assert ev["kind"] == "step" and ev["step_time_ms"] == 12.5


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------


def test_span_nesting_and_chrome_trace(tmp_path):
    tr = SpanTracer()
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    evs = tr.events()
    assert [e["name"] for e in evs] == ["inner", "outer"]  # close order
    inner = evs[0]
    assert inner["ph"] == "X" and inner["args"]["parent"] == "outer"
    assert inner["args"]["depth"] == 1
    path = tr.save_chrome_trace(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    assert {e["name"] for e in doc["traceEvents"]} == {"outer", "inner"}


def test_span_buffer_bounded():
    tr = SpanTracer(max_events=3)
    for i in range(5):
        with tr.span(f"s{i}"):
            pass
    assert len(tr.events()) == 3
    assert tr.dropped == 2


def test_disabled_hub_is_noop():
    hub = get_telemetry()
    assert not hub.enabled
    with hub.span("never"):
        pass
    hub.inc_counter("never")
    hub.set_gauge("never", 1.0)
    assert hub.tracer.events() == []
    assert hub.registry.metrics() == {}


# ---------------------------------------------------------------------------
# monitor fan-out
# ---------------------------------------------------------------------------


def _ds_config(tmp_path, **telemetry_over):
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    tel = {"enabled": True, "output_path": str(tmp_path), "job_name": "job",
           **telemetry_over}
    return DeepSpeedConfig.model_validate({
        "train_micro_batch_size_per_gpu": 1,
        "csv_monitor": {"enabled": True, "output_path": str(tmp_path),
                        "job_name": "job"},
        "telemetry": tel,
    })


def test_monitor_master_fans_out_to_telemetry_backend(tmp_path):
    from deepspeed_tpu.monitor.monitor import MonitorMaster

    cfg = _ds_config(tmp_path)
    master = MonitorMaster(cfg)
    assert master.enabled
    assert master.telemetry.enabled
    # csv + telemetry both enabled → both in the fan-out
    assert master.csv in master.backends
    assert master.telemetry in master.backends
    master.write_events([("Train/loss", 0.5, 1), ("Train/lr", 1e-3, 1)])
    # telemetry backend: gauges in the hub registry + jsonl monitor events
    hub = get_telemetry()
    parsed = parse_prometheus_text(hub.prometheus_text())
    assert parsed["Train_loss"] == 0.5
    events = [json.loads(ln) for ln in
              open(tmp_path / "job" / "events.jsonl").read().splitlines()]
    assert {e["tag"] for e in events} == {"Train/loss", "Train/lr"}


def test_csv_monitor_append_semantics(tmp_path):
    from deepspeed_tpu.monitor.monitor import CSVMonitor

    class Cfg:
        enabled = True
        output_path = str(tmp_path)
        job_name = "job"

    m1 = CSVMonitor(Cfg())
    m1.write_events([("a", 1.0, 1)])
    # a second monitor over the same path APPENDS (no truncation, one
    # header) — restart-safe accumulation
    m2 = CSVMonitor(Cfg())
    m2.write_events([("b", 2.0, 2)])
    rows = open(tmp_path / "job" / "metrics.csv").read().splitlines()
    assert rows[0] == "tag,value,step"
    assert rows[1:] == ["a,1.0,1", "b,2.0,2"]


# ---------------------------------------------------------------------------
# engine round trip (the acceptance criteria)
# ---------------------------------------------------------------------------


def _tiny_engine(tmp_path, extra_cfg=None, mesh_devices=1):
    import deepspeed_tpu as dst
    from deepspeed_tpu.parallel import MeshLayout
    from deepspeed_tpu.utils import groups

    mesh = groups.initialize_mesh(MeshLayout.infer(mesh_devices,
                                                   dp=mesh_devices))
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.normal(size=(8, 1)).astype(np.float32))}

    def loss_fn(p, batch):
        x, y = batch
        return jnp.mean((x @ p["w"] - y) ** 2)

    cfg = {
        "train_micro_batch_size_per_gpu": 4,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "steps_per_print": 0,
        "comms_logger": {"enabled": True},
        "telemetry": {"enabled": True, "output_path": str(tmp_path),
                      "job_name": "job"},
    }
    cfg.update(extra_cfg or {})
    engine, *_ = dst.initialize(model=loss_fn, model_parameters=params,
                                config=cfg, mesh=mesh)
    x = jnp.asarray(rng.normal(size=(4 * mesh_devices, 8)).astype(np.float32))
    y = jnp.zeros((4 * mesh_devices, 1), jnp.float32)
    return engine, (x, y)


def test_step_record_from_two_step_train_loop(tmp_path):
    """Acceptance: a 2-step CPU-backend train run with telemetry enabled
    writes a JSONL step record containing device-fenced step_time_ms,
    tokens_per_sec, comm_bytes, and memory stats — and a Prometheus dump
    of the same registry parses cleanly."""
    engine, data = _tiny_engine(tmp_path)
    for _ in range(2):
        engine.train_step(data)

    # in-memory records
    assert len(engine.step_records) == 2
    rec = engine.last_step_record
    assert rec.step == 2 and rec.device_fenced
    assert rec.step_time_ms > 0 and rec.tokens_per_sec > 0

    # JSONL step records carry every acceptance field
    lines = open(tmp_path / "job" / "events.jsonl").read().splitlines()
    steps = [json.loads(ln) for ln in lines
             if json.loads(ln)["kind"] == "step"]
    assert [s["step"] for s in steps] == [1, 2]
    for s in steps:
        assert s["device_fenced"] is True
        assert s["step_time_ms"] > 0
        assert s["tokens_per_sec"] > 0
        assert "comm_bytes" in s and s["comm_bytes"] >= 0
        assert "device_in_use_GB" in s["memory"] \
            or "host_available_GB" in s["memory"]

    # Prometheus exposition of the SAME registry parses cleanly
    hub = get_telemetry()
    parsed = parse_prometheus_text(hub.prometheus_text())
    assert parsed["train_steps_total"] == 2
    assert parsed["train_step_time_ms_count"] == 2
    assert parsed["train_loss"] == pytest.approx(float(
        engine.last_metrics["loss"]), rel=1e-5)
    out = hub.flush()
    assert os.path.exists(out["prometheus"])
    # the engine/train_step spans were captured too
    names = [e["name"] for e in hub.tracer.events()]
    assert names.count("engine/train_step") == 2


def test_autotuning_result_is_device_fenced(tmp_path, monkeypatch):
    """ADVICE round-5: with DS_AUTOTUNING_RESULT set the engine fences
    every step, so the reported samples/sec is device time."""
    result = str(tmp_path / "result.json")
    monkeypatch.setenv("DS_AUTOTUNING_RESULT", result)
    # > tput_timer.start_step (2 warmup steps are excluded from the rate)
    monkeypatch.setenv("DS_AUTOTUNING_STEPS", "4")
    engine, data = _tiny_engine(tmp_path)
    assert engine._autotuning_fence
    for _ in range(4):
        engine.train_step(data)
    out = json.load(open(result))
    assert out["steps"] == 4
    assert out["samples_per_sec"] > 0
    # every counted step carried a device fence
    assert all(r.device_fenced for r in engine.step_records)


# ---------------------------------------------------------------------------
# satellite fixes riding this PR
# ---------------------------------------------------------------------------


def test_autotuning_override_rejects_non_dict_node(monkeypatch):
    from deepspeed_tpu.runtime.entry import _resolve_config

    monkeypatch.setenv("DS_AUTOTUNING_CONFIG_OVERRIDE",
                       json.dumps({"optimizer.params.lr": 0.1}))
    with pytest.raises(ValueError, match=r"optimizer\.params\.lr.*optimizer"):
        _resolve_config({"optimizer": "adam",
                         "train_micro_batch_size_per_gpu": 1}, None)


def test_swapper_rejects_pipeline_with_one_buffer():
    from deepspeed_tpu.runtime.swap_tensor.partitioned_param_swapper import (
        PartitionedParamSwapper)

    with pytest.raises(ValueError, match="buffer_count"):
        PartitionedParamSwapper([{"w": np.zeros((4,), np.float32)}],
                                pipeline=True, buffer_count=1)


def test_evict_for_slot_raises_descriptive_error_when_all_pinned():
    """A fully-pinned LRU must raise a RuntimeError naming the cure, not a
    bare StopIteration (ADVICE round-5)."""
    from deepspeed_tpu.runtime.swap_tensor.partitioned_param_swapper import (
        PartitionedParamSwapper)

    sw = PartitionedParamSwapper.__new__(PartitionedParamSwapper)
    sw._free = []
    sw._dirty_writes = 0
    sw._lru = [0, 1]
    sw._pinned = {0, 1}
    sw.buffer_count = 2
    with pytest.raises(RuntimeError, match="buffer_count"):
        sw._evict_for_slot()


def test_scheduler_telemetry_gauges(tmp_path):
    from deepspeed_tpu.inference.v2 import KVCacheConfig
    from deepspeed_tpu.inference.v2.scheduler import RaggedScheduler

    get_telemetry().configure(enabled=True, jsonl=False, prometheus=False)
    sched = RaggedScheduler(KVCacheConfig(num_blocks=16, block_size=16,
                                          max_seq_len=128),
                            max_batch_slots=2, prefill_chunk=16)
    sched.add_request([1, 2, 3], max_new_tokens=4)
    sched.add_request([4, 5], max_new_tokens=4)
    sched.add_request([6], max_new_tokens=4)  # queues (2 slots)
    sched.plan_step()
    g = sched.telemetry_gauges()
    assert g["inference/queue_depth"] == 1.0
    assert g["inference/batch_occupancy"] == 1.0
    assert 0 < g["inference/kv_pool_utilization"] <= 1.0
    parsed = parse_prometheus_text(get_telemetry().prometheus_text())
    assert parsed["inference_requests"] == 3
    assert parsed["inference_queue_depth"] == 1.0
