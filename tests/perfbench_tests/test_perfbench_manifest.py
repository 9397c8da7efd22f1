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


#: what one character of a published layer pattern says a layer holds
PATTERN_KEY = "hybrid_override_pattern"
PATTERN_PARTS = {"M": ("mixer",), "*": ("attention",), "E": ("experts",),
                 "-": ("ffn",)}
EXPERT_COUNT_KEYS = ("num_experts", "num_local_experts", "n_routed_experts")


def layer_parts(cfg):
    """For each layer that is run, the parts it holds, from published keys
    alone.  Where the file has ``hybrid_override_pattern`` a layer holds
    ONE part, by its character (``M`` a state-space mixer, ``*``
    attention, ``E`` experts, ``-`` a dense FFN), and the pattern is as
    long as the layers run: a file cut to fewer layers cuts the pattern
    with them.  Where it has none, every layer holds attention and an FFN,
    which is the experts where the file has an expert count and the dense
    ``intermediate_size`` where it has none, in the ``first_k_dense_replace``
    leading layers and in the layers ``moe_layer_freq`` marks 0."""
    layers = cfg["num_hidden_layers"]
    pattern = cfg.get(PATTERN_KEY)
    if pattern is not None:
        if len(pattern) != layers or set(pattern) - set(PATTERN_PARTS):
            raise ValueError(
                f"{PATTERN_KEY} {pattern!r} does not give each of the "
                f"{layers} layers of num_hidden_layers one of "
                f"{sorted(PATTERN_PARTS)}")
        return [PATTERN_PARTS[c] for c in pattern]
    sparse = any(k in cfg for k in EXPERT_COUNT_KEYS)
    freq = cfg.get("moe_layer_freq")
    dense = [not sparse or i < cfg.get("first_k_dense_replace", 0)
             or (isinstance(freq, list) and not freq[i])
             for i in range(layers)]
    return [("attention", "ffn" if d else "experts") for d in dense]


def token_weight_bracket(cfg):
    """(low, high): the weights a trained token of the MODEL passes
    through in the layers that are run (all ``num_experts_per_tok``
    experts, wherever a deployment holds them: the chip's share of a held
    subset is smaller and is not what is bracketed), summed over the
    layers by what each holds (``layer_parts``), from published keys:

    - attention: 2 H^2 .. 5 H^2 (grouped-query to latent projections);
    - a mixer: 2.5 H D .. 4 H D, D = ``mamba_num_heads`` x
      ``mamba_head_dim`` (in and out projections of 3 H D and the B, C
      and step columns on top);
    - a dense FFN: 2 H I .. 4 H I, I = ``intermediate_size`` (two or three
      matrices, up to a wider gate);
    - experts: ``num_experts_per_tok`` routed ones of 2 w I .. 4 w I each,
      I = ``moe_intermediate_size`` where the file has it, else
      ``intermediate_size``; w = ``moe_latent_size`` where the file has
      it, and then the two projections H -> w -> H, 2 H w, in both ends;
      else w = H.  ``n_shared_experts`` (``null`` reads as 0) shared ones
      of 2 H S .. 4 H S, S = ``moe_shared_expert_intermediate_size`` where
      the file has it, else I.  The router, in the high end only: H x the
      PUBLISHED expert count (``published.n_routed_experts`` where the
      file holds a reduced share of them);
    - embedding and head, in the high end only: 2 H V of the file's V."""
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    I = cfg.get("moe_intermediate_size", cfg["intermediate_size"])
    w = cfg.get("moe_latent_size", H)
    k = cfg.get("num_experts_per_tok", 1)
    shared = (cfg.get("n_shared_experts") or 0) * H * cfg.get(
        "moe_shared_expert_intermediate_size", I)
    published = dict(cfg, **cfg.get("published", {}))
    router = H * next((published[key] for key in EXPERT_COUNT_KEYS
                       if key in published), 0)
    latent = 2 * H * w if "moe_latent_size" in cfg else 0
    D = cfg.get("mamba_num_heads", 0) * cfg.get("mamba_head_dim", 0)
    ends = {"attention": (2 * H * H, 5 * H * H),
            "mixer": (2.5 * H * D, 4 * H * D),
            "ffn": (2 * H * cfg["intermediate_size"],
                    4 * H * cfg["intermediate_size"]),
            "experts": (2 * k * w * I + latent + 2 * shared,
                        4 * k * w * I + latent + 4 * shared + router)}
    held = [ends[part] for parts in layer_parts(cfg) for part in parts]
    return (sum(low for low, _ in held),
            sum(high for _, high in held) + 2 * H * V)


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
    # by the family's module, as a sum over the layers by what each holds
    # (``layer_parts``, ``token_weight_bracket``: key by key there).  The
    # low end is not strict: an expert of two matrices is a real expert.
    n = family.train_flops_per_token(cfg, cfg["run"].get("seq", 512)) / 6
    low, high = token_weight_bracket(cfg)
    assert low <= n < high, (low, n, high)


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
    family = _stub_family(tmp_path, SPARSE_FAMILY, "olmoe")
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


#: the published keys of a family the benchmark does not hold yet (the
#: catalog's row of a hybrid of three layer kinds with latent experts),
#: cut by hand to one period of its pattern and a quarter of its
#: vocabulary, with a stub family whose count is ``_weights_a_token``:
#: each layer is ONE of a Mamba-2 mixer, an attention or an expert layer
#: whose 22 routed experts of two matrices work at a 1,024-wide latent
LAYER_KINDS = {
    "hidden_size": 4096, "intermediate_size": 2688, "num_hidden_layers": 11,
    "hybrid_override_pattern": "MEMEMEM*EME", "num_attention_heads": 32,
    "num_key_value_heads": 2, "head_dim": 128, "mamba_num_heads": 128,
    "mamba_head_dim": 64, "n_groups": 8, "ssm_state_size": 128,
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376, "n_routed_experts": 128,
    "n_shared_experts": 1, "num_experts_per_tok": 22, "vocab_size": 32768,
    "published": {"num_hidden_layers": 88, "n_routed_experts": 512,
                  "vocab_size": 131072},
    "model_type": "layer_kinds_stub", "source": "https://example.org/config",
    "reduced": {"num_hidden_layers": "88 -> 11", "n_routed_experts": "512 -> "
                "128 held", "vocab_size": "a quarter",
                "hybrid_override_pattern": "its first period"},
    "run": {}}
LAYER_KINDS_FAMILY = '''
def build(cfg, mesh=None):
    raise NotImplementedError("a stub: only its operation count is read")


def train_flops_per_token(cfg, seq):
    return 6.0 * cfg["_weights_a_token"]


# -- the plain reference
def forward(weights, cfg, ids):
    with jax.default_matmul_precision("highest"):
        raise NotImplementedError


def loss(weights, cfg, batch):
    raise NotImplementedError
'''


def _layer_kinds_counts(cfg):
    """By hand, from the keys: a mixer layer, the attention layer, an
    expert layer outside its routed experts, one routed expert at width
    ``w``, and the head over the file's vocabulary."""
    H, D = cfg["hidden_size"], cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    mixer = (3 * H * D + 2 * H * cfg["n_groups"] * cfg["ssm_state_size"]
             + H * cfg["mamba_num_heads"])
    attention = 2 * H * cfg["head_dim"] * (cfg["num_attention_heads"]
                                            + cfg["num_key_value_heads"])
    beside = (2 * H * cfg["moe_shared_expert_intermediate_size"]
              + 2 * H * cfg["moe_latent_size"]
              + H * cfg["published"]["n_routed_experts"])
    expert = lambda w: 2 * w * cfg["moe_intermediate_size"]
    return mixer, attention, beside, expert, H * cfg["vocab_size"]


def _stub_family(tmp_path, text, name):
    import importlib.util

    (tmp_path / f"{name}.py").write_text(text)
    spec = importlib.util.spec_from_file_location(name + "_stub",
                                                  tmp_path / f"{name}.py")
    family = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(family)
    return family


@pytest.mark.parametrize("case", ["one_part_a_layer", "experts_at_hidden",
                                  "every_part_in_every_layer",
                                  "pattern_of_another_length"])
def test_a_configuration_is_read_by_the_kinds_of_its_layers(case, tmp_path):
    """The bracket is a sum over the layers by what the published pattern
    says each holds.  The honest count of one period (1.60 B a token)
    passes; the routed experts counted at the hidden width in place of
    the latent one (4 x their term) and every layer counted as holding a
    mixer, an attention AND experts fail; a pattern that is not as long as
    the layers run is an error that names the key."""
    cfg = dict(LAYER_KINDS)
    mixer, attention, beside, expert, head = _layer_kinds_counts(cfg)
    k, H, w = cfg["num_experts_per_tok"], cfg["hidden_size"], \
        cfg["moe_latent_size"]
    n = {"M": cfg["hybrid_override_pattern"].count("M"),
         "*": cfg["hybrid_override_pattern"].count("*"),
         "E": cfg["hybrid_override_pattern"].count("E")}
    honest = (n["M"] * mixer + n["*"] * attention
              + n["E"] * (beside + k * expert(w)) + head)
    assert (mixer, attention) == (109_576_192, 35_651_584)
    assert expert(w) == 5_505_024 and 1.59e9 < honest < 1.60e9
    cfg["_weights_a_token"] = {
        "one_part_a_layer": honest,
        "pattern_of_another_length": honest,
        "experts_at_hidden": honest + n["E"] * k * (expert(H) - expert(w)),
        "every_part_in_every_layer": cfg["num_hidden_layers"] * (
            mixer + attention + beside + k * expert(w)) + head}[case]
    family = _stub_family(tmp_path, LAYER_KINDS_FAMILY, "layer_kinds")
    entry = {"name": "layer-kinds-l11", "source": cfg["source"],
             "file": "perfbench/configs/layer-kinds-l11.json",
             "reduced": list(cfg["reduced"]), "why": "one part a layer"}
    bench = dict(BENCH, workloads=BENCH["workloads"] + [
        {"name": "serve-layer-kinds", "config": entry["name"]}])
    if case == "one_part_a_layer":
        check_configuration(bench, entry, cfg, family)
        low, high = token_weight_bracket(cfg)
        # the reading before this one (attention and 23 experts of [H, I]
        # in every layer) put the low end at 5.94 B: no honest count fits
        assert low < 1.4e9 and 11 * (2 * H * H + 2 * 23 * H * 2688) > 5.9e9
    elif case == "pattern_of_another_length":
        cfg["hybrid_override_pattern"] += "M"
        with pytest.raises(ValueError, match="hybrid_override_pattern"):
            check_configuration(bench, entry, cfg, family)
    else:
        with pytest.raises(AssertionError):
            check_configuration(bench, entry, cfg, family)


@pytest.mark.parametrize("keys, low, high", [
    # a dense decoder: attention and one FFN a layer, no router
    ({"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
      "vocab_size": 10}, 2 * (128 + 256), 2 * (320 + 512) + 160),
    # ``n_shared_experts: null`` reads as 0, and the router is H x the
    # PUBLISHED expert count where the file holds a share
    ({"hidden_size": 8, "intermediate_size": 16, "moe_intermediate_size": 4,
      "num_hidden_layers": 1, "vocab_size": 10, "n_routed_experts": 2,
      "n_shared_experts": None, "num_experts_per_tok": 2,
      "published": {"n_routed_experts": 32}},
     128 + 2 * 2 * 8 * 4, 320 + 4 * 2 * 8 * 4 + 8 * 32 + 160),
    # ``first_k_dense_replace`` leading layers take the dense width, and
    # ``moe_layer_freq`` marks a dense layer with 0
    ({"hidden_size": 8, "intermediate_size": 16, "moe_intermediate_size": 4,
      "num_hidden_layers": 2, "vocab_size": 10, "n_routed_experts": 4,
      "n_shared_experts": 1, "num_experts_per_tok": 2,
      "first_k_dense_replace": 1},
     2 * 128 + 256 + (2 * 2 + 2) * 32, 2 * 320 + 512 + (4 * 2 + 4) * 32
     + 32 + 160),
    ({"hidden_size": 8, "intermediate_size": 16, "moe_intermediate_size": 4,
      "num_hidden_layers": 2, "vocab_size": 10, "n_routed_experts": 4,
      "num_experts_per_tok": 2, "moe_layer_freq": [0, 1]},
     2 * 128 + 256 + 2 * 2 * 32, 2 * 320 + 512 + 4 * 2 * 32 + 32 + 160),
    # a pattern: a mixer alone, an attention alone, a dense FFN alone
    ({"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 3,
      "vocab_size": 10, "hybrid_override_pattern": "M*-",
      "mamba_num_heads": 4, "mamba_head_dim": 4},
     2.5 * 8 * 16 + 128 + 256, 4 * 8 * 16 + 320 + 512 + 160)],
    ids=["dense", "null_shared_and_published_router", "leading_dense_layers",
         "moe_layer_freq", "pattern_of_single_parts"])
def test_the_bracket_key_by_key(keys, low, high):
    assert token_weight_bracket(keys) == (low, high)


def test_a_pattern_names_its_key_where_a_character_is_unknown():
    cfg = dict(LAYER_KINDS, hybrid_override_pattern="MEMEMEM?EME")
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        layer_parts(cfg)


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
