"""BENCHMARK.json and the files it names, found by name and never listed.

A cell names a configuration and a traffic mix; a metric names itself.
Each of those is a file of its own in a directory of ``perfbench/``, so a
later PR adds a cell or a metric by adding files and entries:

    configs/<config>.json            sizes as run, ``runner`` = who drives it
    models/<model_type>.py           the configuration's family: the program's
                                     model from those sizes, a trained token's
                                     operations, the plain float32 reference
    traffic/<traffic>.json           parameters, ``generator`` = who reads them
    metrics/<metric>.json            ``reader`` = who computes it, and ``args``
    generators/<generator>.py, runners/<runner>.py, readers/<reader>.py

A configuration of a family that is there adds one file of sizes; a new
family adds its module beside the others.

What admits a configuration is ``check_configuration`` in
``tests/perfbench_tests/test_perfbench_manifest.py``, which runs for every
entry of ``configs``.  It holds the family's ``train_flops_per_token / 6``
(the weights a trained token of the MODEL passes in the layers that are
run) between two ends worked out there from the file's published keys, as
a sum over the layers by what each holds:

    hybrid_override_pattern          one part a layer: M a mixer, * attention,
                                     E experts, - a dense FFN; as long as
                                     num_hidden_layers.  No such key:
                                     attention and an FFN in every layer
    first_k_dense_replace,           the layers whose FFN is the dense
    moe_layer_freq                   intermediate_size, not the experts
    hidden_size H                    attention 2 H^2 .. 5 H^2
    mamba_num_heads x mamba_head_dim a mixer 2.5 H D .. 4 H D
    intermediate_size                a dense FFN 2 H I .. 4 H I
    num_experts_per_tok              routed experts a token, each
    moe_intermediate_size (else        2 w I .. 4 w I, w = moe_latent_size
      intermediate_size),              where the file has it (then + 2 H w
    moe_latent_size                    for the projections), else H
    n_shared_experts (null = 0),     shared experts of 2 H S .. 4 H S
    moe_shared_expert_intermediate_size
    num_experts | num_local_experts  the router, H x the PUBLISHED count
      | n_routed_experts, published    (high end only)
    vocab_size                       embedding and head 2 H V (high end only)

Nothing here (or in any module under ``perfbench/``) names a cell, a
configuration or a metric.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import re
import sys
from typing import Any, Dict, List

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
NAME_RE = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")


def load_benchmark(root: pathlib.Path = ROOT) -> Dict[str, Any]:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def named(entries: List[Dict[str, Any]], name: str, what: str
          ) -> Dict[str, Any]:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"perfbench: no {what} named {name!r} in BENCHMARK.json "
                     f"(it has {[e['name'] for e in entries]})")


def load_json(directory: str, name: str) -> Dict[str, Any]:
    """``perfbench/<directory>/<name>.json``."""
    if not NAME_RE.fullmatch(name):
        raise SystemExit(f"perfbench: {name!r} is not a name")
    path = BENCH_DIR / directory / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"perfbench: {path.relative_to(ROOT)} is missing")
    with open(path) as f:
        return json.load(f)


def load_module(directory: str, name: str) -> Any:
    """``perfbench/<directory>/<name>.py`` imported by path: the file's
    name is data (it may hold ``-`` and ``.``), so no import statement
    and no registry ever lists it."""
    if not NAME_RE.fullmatch(name):
        raise SystemExit(f"perfbench: {name!r} is not a name")
    path = BENCH_DIR / directory / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"perfbench: {path.relative_to(ROOT)} is missing")
    modname = "perfbench_" + directory + "_" + re.sub(r"\W", "_", name)
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[modname] = module       # dataclasses look their module up
    try:
        spec.loader.exec_module(module)
    except BaseException:
        del sys.modules[modname]
        raise
    return module


def load_cell(workload: str):
    """(BENCHMARK.json, the cell, its configuration, its traffic mix)."""
    bench = load_benchmark()
    cell = named(bench["workloads"], workload, "workload")
    return (bench, cell, load_json("configs", cell["config"]),
            load_json("traffic", cell["traffic"]))


def cell_metrics(bench: Dict[str, Any], cell: str, group: str
                 ) -> List[Dict[str, Any]]:
    """The metrics of ``group`` (``end_to_end`` or ``per_layer``) that
    this cell reports: those that list it, and those that list no cell."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]
