"""Cost ledger (ISSUE 17): compile-time harvest provenance, roofline
verdicts against the peak table, headroom, and the tracker/recorder
wiring."""

import pytest

from deepspeed_tpu.profiling.flops_profiler import DevicePeak
from deepspeed_tpu.telemetry.anatomy import comm_bytes_from_hlo
from deepspeed_tpu.telemetry.anatomy.ledger import (CostLedger,
                                                    configure_cost_ledger,
                                                    get_cost_ledger)

V4 = DevicePeak(kind="v4", flops_per_s=275e12, hbm_bytes_per_s=1228e9,
                ici_bytes_per_s=300e9)


class FakeCompiled:
    """An AOT executable surface: cost model + HLO text + memory."""

    def __init__(self, cost=None, hlo="", mem=None, raise_cost=False):
        self._cost = cost
        self._hlo = hlo
        self._mem = mem
        self._raise = raise_cost

    def cost_analysis(self):
        if self._raise:
            raise NotImplementedError("no cost model on this backend")
        return self._cost

    def as_text(self):
        return self._hlo

    def memory_analysis(self):
        return self._mem


class FakeMem:
    argument_size_in_bytes = 4 * 2 ** 20
    output_size_in_bytes = 2 ** 20
    temp_size_in_bytes = 2 ** 20


def test_harvest_cost_model_is_measured():
    led = CostLedger(peak=V4)
    led.harvest("engine/train_step", 0, FakeCompiled(
        cost={"flops": 1e12, "bytes accessed": 1e9}))
    e = led.entry_for("engine/train_step")
    assert e["provenance"] == "measured"
    assert e["flops"] == 1e12
    assert e["hbm_bytes"] == 1e9
    assert e["arithmetic_intensity"] == 1000.0


def test_harvest_reads_a_real_executable():
    # the installed jax hands cost_analysis() back as one flat dict; the
    # ledger reads it as such (no per-version shapes)
    import jax
    import jax.numpy as jnp

    compiled = jax.jit(lambda a, b: a @ b).lower(
        jnp.ones((64, 64)), jnp.ones((64, 64))).compile()
    assert isinstance(compiled.cost_analysis(), dict)
    led = CostLedger(peak=V4)
    led.harvest("s", 1, compiled)
    e = led.entry_for("s")
    assert e["provenance"] == "measured"
    assert e["flops"] >= 2 * 64 ** 3


def test_degraded_backend_is_estimated_not_measured():
    # no cost model: the ledger falls back to memory-analysis bytes and
    # MUST say so — the CPU/degraded path never masquerades as measured
    led = CostLedger(peak=V4)
    led.harvest("s", 0, FakeCompiled(raise_cost=True, mem=FakeMem()))
    e = led.entry_for("s")
    assert e["provenance"] == "estimated"
    assert e["hbm_bytes"] == float(4 * 2 ** 20 + 2 ** 20 + 2 ** 20)


def test_roofline_verdicts():
    led = CostLedger(peak=V4)
    # AI far above critical intensity -> compute-bound
    c = led.record("a", 0, flops=1e15, hbm_bytes=1e9)
    assert c["verdict"] == "compute-bound"
    # AI far below -> hbm-bound
    h = led.record("b", 0, flops=1e9, hbm_bytes=1e12)
    assert h["verdict"] == "hbm-bound"
    # collective traffic dominating the wires -> comm-bound
    m = led.record("c", 0, flops=1e9, hbm_bytes=1e6, comm_bytes=1e12)
    assert m["verdict"] == "comm-bound"
    assert led.record("d", 0)["verdict"] == "unknown"


def test_predicted_time_is_max_of_components():
    led = CostLedger(peak=V4)
    e = led.record("s", 0, flops=275e12, hbm_bytes=1228e9,
                   comm_bytes=0.0)
    # flops and hbm both predict exactly 1s -> 1e6 us
    assert e["predicted_us"] == pytest.approx(1e6)
    bd = e["predicted_breakdown_us"]
    assert bd["compute"] == pytest.approx(1e6)
    assert bd["hbm"] == pytest.approx(1e6)


def test_headroom_semantics():
    led = CostLedger(peak=V4)
    led.record("s", 0, flops=275e12, hbm_bytes=1e9)  # predicts 1s
    # measured 2s -> half the time is unexplained stall
    assert led.headroom("s", 2e6) == pytest.approx(0.5)
    # measured at the roofline -> no headroom
    assert led.headroom("s", 1e6) == pytest.approx(0.0)
    # faster than predicted clamps at 0, never negative
    assert led.headroom("s", 0.5e6) == 0.0
    assert led.headroom("missing", 1e6) is None


def test_entry_for_prefers_latest_program():
    led = CostLedger(peak=V4)
    led.record("s", 0, flops=1e9, hbm_bytes=1e6)
    led.record("s", 3, flops=2e9, hbm_bytes=1e6)
    assert led.entry_for("s")["program"] == 3
    assert led.entry_for("s", 0)["flops"] == 1e9


def test_comm_bytes_from_hlo():
    hlo = """
    %ar = f32[1024,512]{1,0} all-reduce(f32[1024,512]{1,0} %p0)
    %ag = bf16[2048]{0} all-gather(bf16[1024]{0} %p1)
    %dot = f32[64,64]{1,0} dot(%a, %b)
    """
    # 1024*512*4 + 2048*2
    assert comm_bytes_from_hlo(hlo) == 1024 * 512 * 4 + 2048 * 2
    assert comm_bytes_from_hlo("%x = f32[8]{0} add(%a, %b)") == 0


def test_summary_top_and_roofline_top():
    led = CostLedger(peak=V4)
    led.record("small", 0, flops=1e9, hbm_bytes=1e6)
    led.record("big", 0, flops=1e15, hbm_bytes=1e9)
    s = led.summary(top_k=1)
    assert s["programs"] == 2
    assert s["top"][0]["site"] == "big"
    assert s["roofline_top"] == "compute-bound"


def test_configure_wires_tracker_and_recorder_once():
    from deepspeed_tpu.telemetry.flight_recorder import FlightRecorder
    from deepspeed_tpu.telemetry.perf.compile_tracker import CompileTracker

    trk = CompileTracker()
    trk.configure(enabled=True)
    rec = FlightRecorder()
    led = configure_cost_ledger(tracker=trk, recorder=rec)
    assert led is get_cost_ledger()
    n = len(trk._cost_harvesters)
    # idempotent: a second engine init must not double-harvest
    configure_cost_ledger(tracker=trk, recorder=rec)
    assert len(trk._cost_harvesters) == n
    led.record("s", 0, flops=1e12, hbm_bytes=1e9, provenance="measured")
    led.set_last_capture({"comm_fraction": 0.2, "events": [1, 2, 3]})
    ctx = rec._context_providers["anatomy"]()
    assert ctx["cost_ledger"]["programs"] >= 1
    assert ctx["last_capture"]["comm_fraction"] == 0.2
    # event lists never ride the bundle context
    assert "events" not in ctx["last_capture"]
    led.reset()
    assert led.entries() == []


def test_harvest_through_tracker_hook():
    from deepspeed_tpu.telemetry.perf.compile_tracker import CompileTracker

    trk = CompileTracker()
    trk.configure(enabled=True)
    led = CostLedger(peak=V4)
    trk.add_cost_harvester(led.harvest)
    trk.harvest_cost("engine/eval_loss", 0, FakeCompiled(
        cost={"flops": 5e12, "bytes accessed": 1e9}))
    assert led.entry_for("engine/eval_loss")["flops"] == 5e12
    # a harvester that raises is swallowed by the tracker (best-effort)
    trk.add_cost_harvester(lambda *a: (_ for _ in ()).throw(ValueError))
    trk.harvest_cost("engine/eval_loss", 1, FakeCompiled(
        cost={"flops": 1.0, "bytes accessed": 1.0}))
    assert led.entry_for("engine/eval_loss")["program"] == 1
