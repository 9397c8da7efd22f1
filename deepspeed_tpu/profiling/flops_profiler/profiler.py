"""Flops profiler — XLA cost analysis instead of module hooks.

Reference: ``deepspeed/profiling/flops_profiler/profiler.py`` [K] —
``FlopsProfiler`` (module-hook MAC counting, per-module latency table at
``profile_step``) and standalone ``get_model_profile()``; engine config group
``flops_profiler.{enabled,profile_step,module_depth,top_modules,detailed,
output_file}`` (SURVEY §5.1).

TPU-first: a jitted function's exact FLOPs/bytes come from the COMPILER —
``jax.jit(fn).lower(...).compile().cost_analysis()`` — so no hook walking,
and the numbers are the post-fusion truth rather than an analytic estimate.
Wall-clock from timed replay gives achieved FLOP/s and MFU.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from ...utils.logging import log_dist, logger

#: per-chip peaks by device kind (spec sheets): dense bf16 FLOP/s, HBM
#: bandwidth (bytes/s), aggregate ICI/interconnect bandwidth (bytes/s).
#: Substring-matched against ``device_kind`` first-match-wins, so the
#: more specific tag ("v5p", "v6e") must precede its prefix ("v5", "v6").
PEAK_TABLE = (
    # (kind tag,   flops,   hbm B/s,  ici B/s)
    ("v6e",     918e12,  1640e9,  448e9),   # Trillium
    ("v6",      918e12,  1640e9,  448e9),
    ("v5p",     459e12,  2765e9,  600e9),
    ("v5e",     197e12,   819e9,  200e9),
    ("v5 lite", 197e12,   819e9,  200e9),
    ("v4",      275e12,  1228e9,  300e9),
    ("v3",      123e12,   900e9,  175e9),
    ("v2",       46e12,   700e9,   62e9),
)

#: published dense bf16 peak per chip by device kind (back-compat view
#: of PEAK_TABLE; ``peak_for_device`` is the lookup new code uses)
PEAK_BF16_BY_KIND = tuple((tag, flops) for tag, flops, _, _ in PEAK_TABLE)

#: (flops, hbm B/s, ici B/s) handed to a CPU run so the roofline
#: arithmetic has a denominator in the tests.  Not a device peak: the
#: result carries ``source="backend_default"``.  Accelerators get no such
#: default — a kind missing from PEAK_TABLE is an error.
CPU_PLACEHOLDER_PEAKS = (1e12, 50e9, 10e9)


@dataclasses.dataclass(frozen=True)
class DevicePeak:
    """One chip's roofline ceilings.  ``source`` is ``"spec"`` when the
    device kind matched the spec-sheet table, ``"backend_default"`` for
    the CPU placeholder."""

    kind: str
    flops_per_s: float
    hbm_bytes_per_s: float
    ici_bytes_per_s: float
    source: str = "spec"

    @property
    def critical_intensity(self) -> float:
        """FLOPs/byte above which this chip is compute-bound."""
        return self.flops_per_s / max(self.hbm_bytes_per_s, 1.0)

    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["critical_intensity"] = round(self.critical_intensity, 2)
        return d


def peak_for_device(device: Any = None) -> DevicePeak:
    """THE peak lookup — the single source the MFU math, the anatomy
    plane's roofline model, and any future bandwidth accounting share.
    Kind-matched against the spec table; an accelerator whose kind is not
    in it raises rather than borrowing another chip's numbers."""
    dev = device if device is not None else jax.devices()[0]
    kind = getattr(dev, "device_kind", "") or ""
    low = kind.lower()
    for tag, flops, hbm, ici in PEAK_TABLE:
        if tag in low:
            return DevicePeak(kind=kind, flops_per_s=flops,
                              hbm_bytes_per_s=hbm, ici_bytes_per_s=ici)
    backend = str(getattr(dev, "platform", None) or jax.default_backend())
    if backend != "cpu":
        raise ValueError(
            f"no entry in PEAK_TABLE for device_kind {kind!r} on platform "
            f"{backend!r}: add its published peaks (with their source) — "
            f"utilization against another chip's peak would be wrong "
            f"without saying so")
    flops, hbm, ici = CPU_PLACEHOLDER_PEAKS
    return DevicePeak(kind=kind or backend, flops_per_s=flops,
                      hbm_bytes_per_s=hbm, ici_bytes_per_s=ici,
                      source="backend_default")


def peak_flops_per_chip() -> float:
    """bf16 peak for THIS chip — ``peak_for_device().flops_per_s``, kept
    as the narrow helper the MFU call sites read."""
    return peak_for_device().flops_per_s


def _compiled_cost(fn: Callable, *args, **kwargs) -> Dict[str, float]:
    compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    return dict(compiled.cost_analysis() or {})


class FlopsProfiler:
    """Profile a jitted step function (or an engine's train step)."""

    def __init__(self, model: Any = None, ds_engine: Any = None):
        self.engine = ds_engine if ds_engine is not None else model
        self.profile: Dict[str, float] = {}

    # -- step-function profiling ------------------------------------------

    def profile_fn(self, fn: Callable, *args, runs: int = 3,
                   **kwargs) -> Dict[str, float]:
        costs = _compiled_cost(fn, *args, **kwargs)
        flops = float(costs.get("flops", 0.0))
        jitted = jax.jit(fn)
        out = jitted(*args, **kwargs)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(runs):
            out = jitted(*args, **kwargs)
        jax.block_until_ready(out)
        latency = (time.perf_counter() - t0) / runs
        backend = jax.default_backend()
        peak = peak_flops_per_chip()
        achieved = flops / latency if latency > 0 else 0.0
        self.profile = {
            "flops": flops,
            "bytes_accessed": float(costs.get("bytes accessed", 0.0)),
            "latency_s": latency,
            "achieved_flops_per_s": achieved,
            "mfu": achieved / (peak * jax.device_count()),
            "backend": backend,
        }
        return self.profile

    # -- engine hook surface (reference API names) ------------------------

    def start_profile(self, ignore_list=None) -> None:
        self._t0 = time.perf_counter()

    def stop_profile(self) -> None:
        self.profile.setdefault("latency_s", time.perf_counter() - self._t0)

    def get_total_flops(self, as_string: bool = False):
        v = self.profile.get("flops", 0.0)
        return _num_to_string(v, "FLOPs") if as_string else v

    def get_total_duration(self, as_string: bool = False):
        v = self.profile.get("latency_s", 0.0)
        return f"{v * 1e3:.2f} ms" if as_string else v

    def print_model_profile(self, profile_step: int = 1, module_depth: int = -1,
                            top_modules: int = 1, detailed: bool = True,
                            output_file: Optional[str] = None) -> None:
        lines = ["-" * 60, "DeepSpeed-TPU Flops Profiler",
                 "-" * 60]
        for k, v in self.profile.items():
            lines.append(f"{k:>24}: {v}")
        text = "\n".join(lines)
        if output_file:
            with open(output_file, "w") as f:
                f.write(text)
        else:
            log_dist(text)

    def end_profile(self) -> None:
        self.profile = {}


def profile_model_modules(model: Any, params: Any, batch: Any,
                          module_depth: int = -1, top_modules: int = 0,
                          runs: int = 3) -> Dict[str, Dict[str, float]]:
    """PER-MODULE flops/params/latency table (reference FlopsProfiler's
    ``module_depth``/``top_modules`` per-module breakdown, SURVEY §2.5).

    TPU-first: instead of module hooks, each piece of the model's
    layer-streamable protocol compiles separately and its cost comes from
    the COMPILER (``cost_analysis``) plus a timed on-device replay —
    "which layer burns the FLOPs" answered with post-fusion truth:

    * depth 1 — ``embed``, ``layers`` (one decoder layer × L), ``head``
    * depth 2 — inside one decoder layer, whatever the model's
      ``profile_submodules()`` exposes (attn/mlp for the Llama family)

    Returns ``{module: {flops, macs, params, latency_s, pct_latency,
    tflops_per_s, count}}``; ``latency_s`` is the per-call forward time,
    ``pct_latency`` weights by ``count`` (layers run L times per step).
    """
    needed = ("embed_fwd", "decoder_layer", "head_loss", "batch_labels")
    if not all(callable(getattr(model, m, None)) for m in needed):
        raise ValueError(
            "per-module profiling needs the layer-streamable protocol "
            f"(embed_fwd/decoder_layer/head_loss); {type(model).__name__} "
            "does not implement it")
    ids, _ = model.batch_labels(batch)
    L = int(model.config.num_layers)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    resident = {k: v for k, v in params.items() if k != "layers"}

    def timed(fn, *args) -> Tuple[float, float]:
        costs = _compiled_cost(fn, *args)
        jitted = jax.jit(fn)
        jax.block_until_ready(jitted(*args))  # compile + warm
        t0 = time.perf_counter()
        for _ in range(runs):
            out = jitted(*args)
        jax.block_until_ready(out)
        return float(costs.get("flops", 0.0)), \
            (time.perf_counter() - t0) / runs

    def n_params(tree) -> int:
        return sum(int(x.size) for x in jax.tree.leaves(tree))

    x = jax.jit(model.embed_fwd)(resident, ids)
    rows: Dict[str, Dict[str, float]] = {}

    def add(name, fn, args, count, params_of, depth):
        flops, lat = timed(fn, *args)
        rows[name] = {"flops": flops, "macs": flops / 2.0,
                      "params": n_params(params_of), "latency_s": lat,
                      "count": count, "depth": depth,
                      "tflops_per_s": (flops / lat / 1e12) if lat else 0.0}

    add("embed", model.embed_fwd, (resident, ids), 1,
        {k: v for k, v in resident.items() if k == "embed"}, 1)
    add("layers", lambda l, a: model.decoder_layer(l, a)[0], (lp, x), L,
        params["layers"], 1)
    add("head", model.head_loss, (resident, x, batch), 1,
        {k: v for k, v in resident.items() if k != "embed"}, 1)
    if (module_depth < 0 or module_depth >= 2) and callable(
            getattr(model, "profile_submodules", None)):
        for name, fn in model.profile_submodules().items():
            add(f"layers.{name}", fn, (lp, x), L,
                {}, 2)  # params attributed at depth 1
    total = sum(r["latency_s"] * r["count"] for r in rows.values()
                if r["depth"] == 1)
    for r in rows.values():
        r["pct_latency"] = 100.0 * r["latency_s"] * r["count"] / total \
            if total else 0.0
    if top_modules and top_modules > 0:
        keep = set()
        for d in (1, 2):
            at_d = sorted((n for n, r in rows.items() if r["depth"] == d),
                          key=lambda n: -rows[n]["pct_latency"])
            keep.update(at_d[:top_modules])
        rows = {n: r for n, r in rows.items() if n in keep}
    return rows


def format_module_table(rows: Dict[str, Dict[str, float]]) -> str:
    """Reference-style top-modules table."""
    lines = ["-" * 78,
             f"{'module':<16}{'params':>12}{'MACs':>14}{'fwd latency':>14}"
             f"{'% latency':>11}{'TFLOP/s':>10}",
             "-" * 78]
    for name, r in sorted(rows.items(),
                          key=lambda kv: (kv[1]['depth'],
                                          -kv[1]['pct_latency'])):
        pad = "  " if r["depth"] == 2 else ""
        cnt = f" x{int(r['count'])}" if r["count"] > 1 else ""
        lines.append(
            f"{pad + name + cnt:<16}"
            f"{_num_to_string(r['params'], ''):>12}"
            f"{_num_to_string(r['macs'], 'MACs'):>14}"
            f"{r['latency_s'] * 1e3:>11.2f} ms"
            f"{r['pct_latency']:>10.1f}%"
            f"{r['tflops_per_s']:>10.2f}")
    lines.append("-" * 78)
    return "\n".join(lines)


def _num_to_string(num: float, unit: str) -> str:
    for scale, prefix in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "K")):
        if num >= scale:
            return f"{num / scale:.2f} {prefix}{unit}"
    return f"{num:.2f} {unit}"


def get_model_profile(model: Any = None, input_shape: Tuple[int, ...] = None,
                      args: Tuple = (), kwargs: Optional[Dict] = None,
                      print_profile: bool = True, detailed: bool = True,
                      module_depth: int = -1, top_modules: int = 1,
                      warm_up: int = 1, as_string: bool = True,
                      output_file: Optional[str] = None,
                      ignore_modules=None,
                      fn: Optional[Callable] = None):
    """Standalone profile (reference ``get_model_profile`` shape).

    TPU adaptation: pass ``fn`` + ``args`` (a pure function and its inputs);
    ``model`` objects with ``.loss``/``.forward`` are profiled through that.
    Returns (flops, macs, params) like the reference — macs = flops/2.
    """
    if fn is None:
        if model is None:
            raise ValueError("need fn or model")
        fn = model.forward if hasattr(model, "forward") else model
    prof = FlopsProfiler()
    result = prof.profile_fn(fn, *args, **(kwargs or {}))
    params = 0
    if args:
        try:
            params = sum(int(x.size) for x in jax.tree.leaves(args[0]))
        except Exception:
            params = 0
    if print_profile:
        prof.print_model_profile(output_file=output_file)
    flops = result["flops"]
    macs = flops / 2
    if as_string:
        return (_num_to_string(flops, "FLOPs"), _num_to_string(macs, "MACs"),
                _num_to_string(params, ""))
    return flops, macs, params
