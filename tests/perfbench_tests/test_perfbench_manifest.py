"""BENCHMARK.json against the contract it was written to, and the rule
that a later cell or metric arrives as new files only."""

import json
import pathlib
import re
import shutil

import pytest

from perfbench import harness, manifest
from perfbench import run as bench_run

BENCH = manifest.load_benchmark()
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024
    assert all(not w.startswith("/") and ".." not in w for w in BENCH["command"])
    for path in BENCH["paths"]:
        assert (manifest.ROOT / path).is_dir()
    # the full check with all 24 cells fits the driver's day
    runs = 2 + 14 * 24
    assert (runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200) <= 43200


@pytest.mark.parametrize("entry", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell(entry):
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert entry["chips"] in (1, 4) and len(entry["why"]) <= 200
    for key in ("name", "config", "traffic"):
        assert NAME.fullmatch(entry[key])
    assert entry["config"] in {c["name"] for c in BENCH["configs"]}
    traffic = manifest.load_json("traffic", entry["traffic"])
    assert (manifest.BENCH_DIR / "generators"
            / f"{traffic['generator']}.py").is_file()
    reported = {g: manifest.cell_metrics(BENCH, entry["name"], g)
                for g in ("end_to_end", "per_layer")}
    e2e = {m["name"] for m in reported["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2 and reported["per_layer"]


def test_cells_are_unique_and_four_chip_cells_are_within_quota():
    assert len(set(CELLS)) == len(CELLS)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["name"] in {w["config"] for w in BENCH["workloads"]}
    assert entry["source"].startswith("https://") and len(entry["reduced"]) <= 16
    assert any(entry["file"].startswith(p + "/") for p in BENCH["paths"])
    with open(manifest.ROOT / entry["file"]) as f:
        cfg = json.load(f)
    assert cfg["source"] == entry["source"]
    # the file says why each reduced key was changed, and no width is one
    assert set(cfg["reduced"]) == set(entry["reduced"])
    for key in entry["reduced"]:
        assert not re.search(r"hidden_size|intermediate|head_dim|_dim$|_rank$|"
                             r"experts_per_tok", key)
    # its family's module is found by the published ``model_type``: the
    # program's model, a trained token's operations, the plain reference
    family = manifest.load_module("models", cfg["model_type"])
    for name in ("build", "train_flops_per_token", "forward", "loss"):
        assert callable(getattr(family, name)), name
    text = pathlib.Path(family.__file__).read_text()
    reference = text.split("# -- the plain reference")[1]
    assert 'default_matmul_precision("highest")' in reference
    assert "deepspeed_tpu" not in reference
    # 6 operations per weight and trained token, and attention on top
    n = family.train_flops_per_token(cfg, cfg["run"].get("seq", 512)) / 6
    H, L, I = (cfg[k] for k in ("hidden_size", "num_hidden_layers",
                                "intermediate_size"))
    assert L * (2 * H * H + 2 * H * I) < n < L * (5 * H * H + 4 * H * I) \
        + 2 * H * cfg["vocab_size"]


def test_configuration_files_are_not_shared():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric(metric):
    end_to_end = metric in BENCH["end_to_end"]
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if end_to_end else {"layer", "moves"})
    assert keys <= set(metric) <= keys | {"workloads"}
    assert NAME.fullmatch(metric["name"]) and UNIT.fullmatch(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        moved = next(m for m in BENCH["end_to_end"]
                     if m["name"] == metric["moves"])
        # every cell that reports this metric reports the one it moves
        for cell in metric.get("workloads", CELLS):
            assert "workloads" not in moved or cell in moved["workloads"]
        assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200
    assert set(metric.get("workloads", [])) <= set(CELLS)
    if "roofline" in metric["name"] or "mfu" in metric["name"]:
        assert metric["unit"] == "%"
    # its own file agrees with the manifest and names a reader that exists
    spec = manifest.load_json("metrics", metric["name"])
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec.get(key) == metric.get(key)
    assert (manifest.BENCH_DIR / "readers" / f"{spec['reader']}.py").is_file()


def test_metric_names_are_unique_and_setup_is_there():
    names = [m["name"] for m in METRICS]
    assert len(set(names)) == len(names)
    setup = next(m for m in BENCH["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup and setup["bound"] <= 0.1


def test_files_under_paths_are_named_from_the_allowed_characters():
    for path in BENCH["paths"]:
        for f in (manifest.ROOT / path).rglob("*"):
            if "__pycache__" in f.parts or f.suffix == ".pyc":
                continue
            assert re.fullmatch(r"[A-Za-z0-9_.\-/]+",
                                str(f.relative_to(manifest.ROOT))), f


def test_harness_code_names_no_cell_configuration_or_metric():
    names = ({w["name"] for w in BENCH["workloads"]}
             | {c["name"] for c in BENCH["configs"]}
             | {w["traffic"] for w in BENCH["workloads"]}
             | {m["name"] for m in METRICS if m["name"] != "setup_s"})
    code = list(manifest.BENCH_DIR.rglob("*.py"))
    assert len(code) > 15
    for f in code:
        text = f.read_text()
        assert not [n for n in names if n in text], f
        # a model family's classes are named in its own module alone, and
        # no attribute of the program that starts with ``_`` is read
        # outside the one function that says why
        if f.parent.name != "models":
            assert not re.search(r"Llama|Bert|Mistral|model_type\W+==", text), f
        if f.name != "program.py":
            assert not re.search(r"\b(engine|frontend|router|model)\._[a-z]",
                                 text), f


def test_a_cell_added_as_new_files_only_is_found_by_name(tmp_path, monkeypatch):
    """What a later PR does: new files and new entries, no file edited."""
    bench_dir = tmp_path / "perfbench"
    shutil.copytree(manifest.BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {f: f.read_bytes() for f in bench_dir.rglob("*") if f.is_file()}
    (bench_dir / "configs" / "new-model.json").write_text(
        json.dumps({"model_type": "new-family", "run": {"runner": "echo"}}))
    (bench_dir / "models" / "new-family.py").write_text(
        "def train_flops_per_token(cfg, seq):\n    return 6.0 * seq\n")
    (bench_dir / "traffic" / "new-mix.json").write_text(
        json.dumps({"generator": "steady", "repeat_one_batch": True}))
    (bench_dir / "runners" / "echo.py").write_text(
        "def run(ctx):\n    return {'pages': 12, 'seen': ctx.cell['name']}\n")
    (bench_dir / "readers" / "pages.py").write_text(
        "def read(obs, args):\n    return obs['pages'] * args['scale']\n")
    (bench_dir / "metrics" / "pages.new.json").write_text(
        json.dumps({"reader": "pages", "args": {"scale": 0.5}}))
    (bench_dir / "metrics" / "nothing.new.json").write_text(
        json.dumps({"reader": "idle_pct"}))
    bench = json.loads(json.dumps(BENCH))
    cell = {"name": "new-cell", "config": "new-model", "traffic": "new-mix",
            "chips": 1, "why": "a later PR's cell"}
    bench["workloads"].append(cell)
    for name in ("pages.new", "nothing.new"):
        bench["per_layer"].append(
            {"name": name, "unit": "pages", "better": "lower",
             "source": "program_counter", "layer": "Kernels",
             "moves": "setup_s", "workloads": ["new-cell"]})
    monkeypatch.setattr(manifest, "BENCH_DIR", bench_dir)
    monkeypatch.setattr(manifest, "ROOT", tmp_path)
    config = manifest.load_json("configs", "new-model")
    runner = manifest.load_module("runners", config["run"]["runner"])
    obs = runner.run(type("Ctx", (), {"cell": cell}))
    assert obs["seen"] == "new-cell"
    ctx = harness.Context(cell=cell, config=config, traffic={}, seed=1,
                          seconds=1.0, trace=False, t_start=0.0, scratch="")
    assert ctx.family().train_flops_per_token(config, 7) == 42.0
    # the reader that finds nothing to read is left out of the line
    assert bench_run.measure(bench, cell, obs, trace=True) == {
        "pages.new": {"value": 6.0, "unit": "pages"}}
    assert all(f.read_bytes() == data for f, data in before.items())
