"""CPU rehearsal of a cell, for these tests only: the cell's own runner,
generator, readers and reference at the tiny sizes of ``tiny/<config>.json``
(merged over the real files), with no device gate.  It returns the result
line the command would print, minus every number that needs the chip: a
CPU trace has no accelerator plane, so the trace readers find nothing to
read and leave their metrics out.  The real command has no such path."""

from __future__ import annotations

import copy
import json
import pathlib
import time
from typing import Any, Dict

from perfbench import harness, manifest
from perfbench import run as bench_run

HERE = pathlib.Path(__file__).resolve().parent


def merged(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    out = copy.deepcopy(base)
    for key, value in over.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = merged(out[key], value)
        else:
            out[key] = value
    return out


def tiny_files(cell: Dict[str, Any]):
    with open(HERE / "tiny" / f"{cell['config']}.json") as f:
        tiny = json.load(f)
    return (merged(manifest.load_json("configs", cell["config"]),
                   tiny["config"]),
            merged(manifest.load_json("traffic", cell["traffic"]),
                   tiny["traffic"]))


def rehearse(workload: str, seed: int, seconds: float, trace: bool,
             tmp_path) -> Dict[str, Any]:
    import jax

    bench = manifest.load_benchmark()
    cell = dict(manifest.named(bench["workloads"], workload, "workload"))
    config, traffic = tiny_files(cell)
    ctx = harness.Context(cell=cell, config=config, traffic=traffic,
                          seed=seed, seconds=seconds, trace=trace,
                          t_start=time.perf_counter(), scratch=str(tmp_path))
    runner = manifest.load_module("runners", config["run"]["runner"])
    obs = runner.run(ctx)
    device = {"platform": jax.devices()[0].platform,
              "kind": jax.devices()[0].device_kind, "count": cell["chips"]}
    # no table of peaks for a CPU: no utilization is printed
    obs.update(peaks=None, chips=cell["chips"], config=config)
    line = bench_run.result_line(bench, cell, obs, device, trace)
    line["_obs"] = obs
    return line
