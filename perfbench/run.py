"""The benchmark's one command:

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process per run.  It refuses to run without a TPU holding the chips
the cell asks for, makes inputs and weights from ``--seed``, warms up the
cell's own shapes (set-up), measures for ``--seconds``, checks the
program against the configuration's plain reference, and prints as its
last line one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and, traced, ``breakdown``.  ``--trace 0`` gives
the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics.

Which cells, configurations, traffic mixes and metrics exist is data:
``BENCHMARK.json`` and the files it names (see ``manifest.py``).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up is counted from here

import argparse  # noqa: E402
import json      # noqa: E402
import os        # noqa: E402
import pathlib   # noqa: E402
import sys       # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from perfbench import harness, manifest, shapes, trace_reduce  # noqa: E402


def place_compile_cache() -> str:
    """JAX's persistent cache: where ``JAX_COMPILATION_CACHE_DIR`` says,
    else ``<checkout>/.jax_cache``: a fixed path, because the path is part
    of the cache's key.  Every program is kept, however fast it compiled,
    so that a second run compiles nothing."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    path = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_gate(chips: int) -> dict:
    """No accelerator, or fewer chips than the cell asks for: no result."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise SystemExit(
            f"perfbench: the cell needs {chips} TPU chip(s); JAX found "
            f"{len(devices)} x {devices[0].platform} "
            f"({devices[0].device_kind}): nothing was run")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def measure(bench: dict, cell: dict, obs: dict, trace: bool) -> dict:
    """Each of the cell's metrics through its own reader.  A reader that
    finds nothing to read returns None and the metric is left out."""
    group = "per_layer" if trace else "end_to_end"
    out = {}
    for metric in manifest.cell_metrics(bench, cell["name"], group):
        spec = manifest.load_json("metrics", metric["name"])
        reader = manifest.load_module("readers", spec["reader"])
        value = reader.read(obs, spec.get("args", {}))
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def result_line(bench: dict, cell: dict, obs: dict, device: dict,
                trace: bool) -> dict:
    device = dict(device, memory_peak_bytes=int(obs["memory_peak_bytes"]))
    line = {"correct": bool(obs["correct"]),
            "attempted": int(obs["attempted"]), "failed": int(obs["failed"]),
            "metrics": measure(bench, cell, obs, trace), "device": device}
    tr = obs.get("trace")
    if trace and tr is not None:
        device["busy_s"] = trace_reduce.busy_s(tr)
        device["window_s"] = tr.window_s
        line["breakdown"] = {"device_ops": trace_reduce.top_ops(tr),
                             "idle_gaps": trace_reduce.idle_gaps(tr)}
    # what the driver ignores and a reader of the log wants; last the
    # check, which holds every number compared and its limit
    line["compiles_in_window"] = obs["compiles_in_window"]
    line["window_s"] = obs["t_close"] - obs["t_open"]
    for key in ("rounds", "program_defaults", "losses", "memory", "setup_s"):
        if key in obs:
            line[key] = obs[key]
    line["check"] = obs.get("check")
    return line


def print_compared(check: dict) -> None:
    """Each number the check compared beside its limit, as the run's last
    lines on standard error: read off the runner's ``check``, whose
    ``tolerance`` is one limit for a serving cell's ``logit_gap`` and one
    a number for a training cell."""
    limits = (check or {}).get("tolerance", {})
    if not isinstance(limits, dict):
        limits = {"logit_gap": limits}
    for name, limit in limits.items():
        print(f"perfbench: {name} {check[name]!r} limit {limit!r}",
              file=sys.stderr, flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    bench, cell, config, traffic = manifest.load_cell(args.workload)
    device = device_gate(int(cell["chips"]))
    peaks = shapes.peaks(device["kind"])      # an unknown kind stops here
    place_compile_cache()
    scratch = ROOT / ".perfbench_scratch"
    scratch.mkdir(exist_ok=True)
    ctx = harness.Context(cell=cell, config=config, traffic=traffic,
                          seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), t_start=T_START,
                          scratch=str(scratch))
    runner = manifest.load_module("runners", config["run"]["runner"])
    obs = runner.run(ctx)
    obs.update(peaks=peaks, chips=int(cell["chips"]), config=config)
    if obs["compiles_in_window"]:
        raise SystemExit(f"perfbench: {obs['compiles_in_window']} program(s) "
                         f"compiled inside the measured window")
    line = result_line(bench, cell, obs, device, bool(args.trace))
    print_compared(line["check"])
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
