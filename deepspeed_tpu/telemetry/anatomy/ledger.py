"""Compile-time cost ledger — FLOPs / HBM bytes / collective bytes per
tracked program, harvested from the AOT executable's compiler cost model.

The CompileTracker holds the ``compiled`` handle exactly once, at
compile time — ``cost_analysis()`` there costs the steady state nothing
(the original flops_profiler re-derives costs with live module hooks on
every profiled step; this ledger is the zero-overhead XLA-native
replacement for tracked jit sites).

Each entry carries a roofline verdict against the device peak table
(:func:`~...profiling.flops_profiler.peak_for_device`):

* arithmetic intensity AI = flops / hbm_bytes
* predicted step time = max(flops/peak_flops, hbm/hbm_bw, comm/ici_bw)
* verdict = whichever component dominates (compute / hbm / comm bound)

Provenance is explicit: ``measured`` when the numbers came from the
compiler's cost model, ``estimated`` when the backend has no cost model
and the ledger fell back to analytic estimates (memory analysis + HLO
text scan).  The peak table's own source (``spec`` vs
``backend_default``) is recorded alongside — a CPU-backend roofline is
an estimate twice over and says so.
"""

from __future__ import annotations

import re
import threading
from typing import Any, Dict, List, Optional

from ...profiling.flops_profiler import DevicePeak, peak_for_device
from ..flight_recorder import get_flight_recorder

#: element sizes for HLO shape strings (collective comm-bytes scan)
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

#: HLO result shapes feeding a collective instruction, e.g.
#: ``%ar = f32[1024,512]{1,0} all-reduce(...)``
_COLLECTIVE_HLO_RE = re.compile(
    r"=\s*(?:\(?)([a-z0-9]+)\[([\d,]*)\][^=]*?\b"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)\b")


def _shape_bytes(dtype: str, dims: str) -> int:
    elems = 1
    for d in dims.split(","):
        if d.strip():
            elems *= int(d)
    return elems * _DTYPE_BYTES.get(dtype, 4)


def comm_bytes_from_hlo(hlo_text: str) -> int:
    """Total bytes moved by collective instructions, from the optimized
    HLO text — an analytic estimate (each collective counted once at its
    result shape; all-reduce ring traffic is ~2x this, but the roofline
    only needs the right order of magnitude)."""
    total = 0
    for m in _COLLECTIVE_HLO_RE.finditer(hlo_text):
        total += _shape_bytes(m.group(1), m.group(2))
    return total


def _cost_dict(compiled: Any) -> Dict[str, float]:
    return dict(compiled.cost_analysis() or {})


class CostLedger:
    """Per-program compile-time costs with roofline verdicts.

    Thread-safe; the global instance is wired into the CompileTracker by
    :func:`configure_cost_ledger` and read by the anatomy capture, the
    debug bundle, and the tuning tie-breaker.
    """

    def __init__(self, peak: Optional[DevicePeak] = None):
        self._lock = threading.Lock()
        self._entries: Dict[str, Dict[str, Any]] = {}
        self._peak = peak
        self._last_capture: Optional[Dict[str, Any]] = None

    # -- peaks -------------------------------------------------------------

    @property
    def peak(self) -> DevicePeak:
        if self._peak is None:
            self._peak = peak_for_device()
        return self._peak

    # -- harvest -----------------------------------------------------------

    def harvest(self, site: str, program: int, compiled: Any) -> None:
        """CompileTracker cost-harvester hook: pull the compiler cost
        model out of a fresh AOT executable.  Never raises (the tracker
        wraps it anyway); degrades to analytic estimates when the
        backend exposes no cost model."""
        flops = hbm = comm = 0.0
        provenance = "measured"
        try:
            cost = _cost_dict(compiled)
        except Exception:
            cost = {}
        flops = float(cost.get("flops", 0.0) or 0.0)
        hbm = float(cost.get("bytes accessed", 0.0) or 0.0)
        if flops <= 0.0 and hbm <= 0.0:
            provenance = "estimated"
            hbm = self._estimate_bytes(compiled)
        comm = self._comm_bytes(compiled)
        self.record(site, program, flops=flops, hbm_bytes=hbm,
                    comm_bytes=comm, provenance=provenance)

    def _estimate_bytes(self, compiled: Any) -> float:
        # no cost model: memory analysis still knows the buffer sizes
        # every step must at least touch once
        try:
            mem = compiled.memory_analysis()
            return float(
                getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "output_size_in_bytes", 0)
                + getattr(mem, "temp_size_in_bytes", 0))
        except Exception:
            return 0.0

    def _comm_bytes(self, compiled: Any) -> float:
        # cost models don't split out collective traffic — scan the
        # optimized HLO for collective result shapes instead
        try:
            return float(comm_bytes_from_hlo(compiled.as_text()))
        except Exception:
            return 0.0

    def record(self, site: str, program: int, flops: float = 0.0,
               hbm_bytes: float = 0.0, comm_bytes: float = 0.0,
               provenance: str = "estimated") -> Dict[str, Any]:
        """Record one program's costs (public so offline tools and tests
        can feed entries without an executable)."""
        peak = self.peak
        ai = flops / hbm_bytes if hbm_bytes > 0 else 0.0
        t_compute = flops / peak.flops_per_s if peak.flops_per_s else 0.0
        t_hbm = (hbm_bytes / peak.hbm_bytes_per_s
                 if peak.hbm_bytes_per_s else 0.0)
        t_comm = (comm_bytes / peak.ici_bytes_per_s
                  if peak.ici_bytes_per_s else 0.0)
        predicted_s = max(t_compute, t_hbm, t_comm)
        if predicted_s <= 0.0:
            verdict = "unknown"
        elif t_comm >= t_compute and t_comm >= t_hbm:
            verdict = "comm-bound"
        elif t_compute >= t_hbm:
            verdict = "compute-bound"
        else:
            verdict = "hbm-bound"
        entry = {
            "site": site, "program": int(program),
            "flops": float(flops), "hbm_bytes": float(hbm_bytes),
            "comm_bytes": float(comm_bytes),
            "arithmetic_intensity": round(ai, 3),
            "critical_intensity": round(peak.critical_intensity, 3),
            "predicted_us": round(predicted_s * 1e6, 3),
            "predicted_breakdown_us": {
                "compute": round(t_compute * 1e6, 3),
                "hbm": round(t_hbm * 1e6, 3),
                "comm": round(t_comm * 1e6, 3)},
            "verdict": verdict,
            "provenance": provenance,
            "peak": peak.to_dict(),
        }
        # profiler-plane calibration (ISSUE 20): when a fleet capture
        # has measured this device kind, ground the analytic prediction
        # in the persisted measured/modeled factors.  compute and hbm
        # share a factor — the trace cannot split them per-op.
        from ..profiler.calibration import calibration_scale

        f_comp = calibration_scale(peak.kind, "compute")
        f_comm = calibration_scale(peak.kind, "collective")
        if f_comp != 1.0 or f_comm != 1.0:
            cal_s = max(t_compute * f_comp, t_hbm * f_comp,
                        t_comm * f_comm)
            entry["calibrated_us"] = round(cal_s * 1e6, 3)
            entry["calibration"] = {"compute": round(f_comp, 4),
                                    "collective": round(f_comm, 4)}
        with self._lock:
            self._entries[f"{site}#{int(program)}"] = entry
        return entry

    # -- queries -----------------------------------------------------------

    def entries(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(e) for e in self._entries.values()]

    def entry_for(self, site: str, program: Optional[int] = None
                  ) -> Optional[Dict[str, Any]]:
        """Latest entry for a jit site (highest program id wins when the
        site recompiled), or the exact ``site#program`` when given."""
        with self._lock:
            if program is not None:
                e = self._entries.get(f"{site}#{int(program)}")
                return dict(e) if e else None
            best = None
            for e in self._entries.values():
                if e["site"] == site and (
                        best is None or e["program"] > best["program"]):
                    best = e
            return dict(best) if best else None

    def top(self, k: int = 5) -> List[Dict[str, Any]]:
        """The k costliest programs by predicted step time."""
        rows = self.entries()
        rows.sort(key=lambda e: -e["predicted_us"])
        return rows[:max(int(k), 0)]

    def summary(self, top_k: int = 5) -> Dict[str, Any]:
        rows = self.top(top_k)
        return {
            "programs": len(self.entries()),
            "peak": self.peak.to_dict(),
            "top": rows,
            "roofline_top": rows[0]["verdict"] if rows else None,
        }

    def headroom(self, site: str, measured_us: float,
                 program: Optional[int] = None) -> Optional[float]:
        """Roofline headroom for a site: ``1 - predicted/measured``.
        Near 0 means the program runs at its hardware limit; large
        positive means unexplained stall time.  None when the site is
        unknown or either time is non-positive."""
        e = self.entry_for(site, program)
        if not e or measured_us <= 0:
            return None
        # the measurement-grounded prediction wins once a fleet capture
        # has calibrated this device kind
        predicted = float(e.get("calibrated_us") or e["predicted_us"])
        if predicted <= 0:
            return None
        return round(1.0 - min(predicted / measured_us, 1.0), 4)

    # -- last anatomy capture (bundle/manifest surface) --------------------

    def set_last_capture(self, summary: Dict[str, Any]) -> None:
        with self._lock:
            self._last_capture = dict(summary)

    def last_capture(self) -> Optional[Dict[str, Any]]:
        with self._lock:
            return dict(self._last_capture) if self._last_capture else None

    def context(self) -> Dict[str, Any]:
        """Debug-bundle context provider payload (compact: no event
        lists, capped program table)."""
        cap = self.last_capture()
        if cap:
            cap.pop("events", None)
        return {"cost_ledger": self.summary(), "last_capture": cap}

    def reset(self) -> None:
        with self._lock:
            self._entries.clear()
            self._last_capture = None
            self._peak = None


_default = CostLedger()


def get_cost_ledger() -> CostLedger:
    return _default


def configure_cost_ledger(tracker: Any = None, recorder: Any = None
                          ) -> CostLedger:
    """Wire the global ledger into the compile tracker (harvest every
    AOT compile) and the flight recorder (``context.anatomy`` in every
    debug bundle)."""
    if tracker is not None:
        # registering twice would double-harvest; the tracker keeps the
        # callable identity, so guard by function identity
        if _default.harvest not in getattr(tracker, "_cost_harvesters", []):
            tracker.add_cost_harvester(_default.harvest)
    rec = recorder if recorder is not None else get_flight_recorder()
    rec.register_context("anatomy", _default.context)
    return _default
