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


def check_configuration(bench, entry, cfg, family):
    """Everything one configuration is held to: its entry, its file, its
    family's module and the operations it counts for a trained token."""
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["name"] in {w["config"] for w in bench["workloads"]}
    assert entry["source"].startswith("https://") and len(entry["reduced"]) <= 16
    assert any(entry["file"].startswith(p + "/") for p in bench["paths"])
    assert cfg["source"] == entry["source"]
    # the file says why each reduced key was changed, and no width is one
    assert set(cfg["reduced"]) == set(entry["reduced"])
    for key in entry["reduced"]:
        assert not re.search(r"hidden_size|intermediate|head_dim|_dim$|_rank$|"
                             r"experts_per_tok", key)
    # its family's module is found by the published ``model_type``: the
    # program's model, a trained token's operations, the plain reference
    for name in ("build", "train_flops_per_token", "forward", "loss"):
        assert callable(getattr(family, name)), name
    text = pathlib.Path(family.__file__).read_text()
    reference = text.split("# -- the plain reference")[1]
    assert 'default_matmul_precision("highest")' in reference
    assert "deepspeed_tpu" not in reference
    # 6 operations per weight a trained token passes through, and attention
    # on top.  The bracket is worked out here from the published keys, not
    # by the family's module.  A sparse-expert family's keys: the width of
    # one expert is ``moe_intermediate_size`` where the config has it
    # (DeepSeek, Qwen-MoE) and ``intermediate_size`` where it has not
    # (OLMoE, Mixtral); a token runs ``num_experts_per_tok`` routed experts
    # and ``n_shared_experts`` shared ones of that width; the router is one
    # [H, E] matrix a layer, E being ``num_experts`` (OLMoE, Qwen-MoE),
    # ``num_local_experts`` (Mixtral) or ``n_routed_experts`` (DeepSeek).
    # A dense family has none of these keys: one FFN, no router.  Leading
    # dense layers of another width (``first_k_dense_replace``) are not
    # read: left to the PR that brings such a family.
    n = family.train_flops_per_token(cfg, cfg["run"].get("seq", 512)) / 6
    H, L, V = (cfg[k] for k in ("hidden_size", "num_hidden_layers",
                                "vocab_size"))
    I = cfg.get("moe_intermediate_size", cfg["intermediate_size"])
    m = cfg.get("num_experts_per_tok", 1) + cfg.get("n_shared_experts", 0)
    R = H * next((cfg[k] for k in ("num_experts", "num_local_experts",
                                   "n_routed_experts") if k in cfg), 0)
    assert L * (2 * H * H + 2 * m * H * I) < n \
        < L * (5 * H * H + 4 * m * H * I + R) + 2 * H * V


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration(entry):
    with open(manifest.ROOT / entry["file"]) as f:
        cfg = json.load(f)
    check_configuration(BENCH, entry, cfg,
                        manifest.load_module("models", cfg["model_type"]))


#: OLMoE-1B-7B-0125-Instruct's published keys (its ``config.json``), cut to
#: the eight layers that fit one chip, with a stub family whose operation
#: count runs ``_experts_counted`` experts a token (keys with ``_`` are the
#: test's own)
SPARSE = {
    "hidden_size": 2048, "intermediate_size": 1024, "num_hidden_layers": 8,
    "num_attention_heads": 16, "num_key_value_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "norm_topk_prob": False, "vocab_size": 50304,
    "max_position_embeddings": 4096, "model_type": "olmoe",
    "source": "https://huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct/"
              "blob/main/config.json",
    "reduced": {"num_hidden_layers": "16 -> 8"}, "run": {}}
SPARSE_FAMILY = '''
def build(cfg, mesh=None):
    raise NotImplementedError("a stub: only its operation count is read")


def train_flops_per_token(cfg, seq):
    H, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    experts = cfg["_experts_counted"] * 3 * H * cfg["intermediate_size"]
    weights = L * (4 * H * H + experts + H * cfg["_experts_stored"]) + H * V
    return 3.0 * (2 * weights + L * 2 * 2 * (seq + 1) / 2 * H)


# -- the plain reference
def forward(weights, cfg, ids):
    with jax.default_matmul_precision("highest"):
        raise NotImplementedError


def loss(weights, cfg, batch):
    raise NotImplementedError
'''


@pytest.mark.parametrize("counted, held", [(8, True), (64, False), (1, False)])
def test_a_sparse_expert_configuration_is_held_by_its_own_keys(
        counted, held, tmp_path):
    """The bracket follows the configuration's sparsity: the operations of
    the eight experts a token runs pass; those of all 64 (what the chip
    stores) and of one (``intermediate_size`` read as a dense FFN) fail."""
    import importlib.util

    (tmp_path / "olmoe.py").write_text(SPARSE_FAMILY)
    spec = importlib.util.spec_from_file_location("olmoe_stub",
                                                  tmp_path / "olmoe.py")
    family = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(family)
    entry = {"name": "olmoe-1b-7b-serve-l8", "source": SPARSE["source"],
             "file": "perfbench/configs/olmoe-1b-7b-serve-l8.json",
             "reduced": ["num_hidden_layers"], "why": "64 experts, 8 a token"}
    bench = dict(BENCH, workloads=BENCH["workloads"] + [
        {"name": "serve-batch-olmoe-l8", "config": entry["name"]}])
    cfg = dict(SPARSE, _experts_counted=counted, _experts_stored=64)
    if not held:
        with pytest.raises(AssertionError):
            check_configuration(bench, entry, cfg, family)
        return
    check_configuration(bench, entry, cfg, family)
    # the dense reading of the same keys (the bracket before PR 26) refuses
    # the honest count: that is what kept every sparse family out
    dense = {k: v for k, v in cfg.items()
             if k not in ("num_experts", "num_experts_per_tok")}
    with pytest.raises(AssertionError):
        check_configuration(bench, entry, dense, family)


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
