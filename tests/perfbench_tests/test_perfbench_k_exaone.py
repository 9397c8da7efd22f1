"""The ``exaone_moe`` family's cell: a model that drafts for itself, served
two rows a sequence a step.  The configuration's file against the catalog's
row and the issue's cut; the cell's metrics; the reference's wrong variants
and the check's controls at a tiny size; the traffic's schedule replayed
against the harness's ramp; the cell's readers on counters of the
program's own.  (The program against the reference, the drafted stream
against the undrafted one and the accepting branch:
``tests/unit/inference/test_v2_draft.py``.)  Tiny widths, seeded,
float32."""

import json
import pathlib
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import k_exaone_control  # noqa: E402
import serving_control   # noqa: E402
# the traffic's own schedule replayed without a clock: a token a live
# sequence a step, which is what drafts from random weights give
from test_perfbench_solar_open2 import (  # noqa: E402
    _steps_until_every_slot_streams)

BENCH = manifest.load_benchmark()
CELL = manifest.named(BENCH["workloads"], "serve-specreason-k-exaone-l5",
                      "workload")
ENTRY = manifest.named(BENCH["configs"], CELL["config"], "configuration")
REAL = manifest.load_json("configs", CELL["config"])
FAMILY = manifest.load_module("models", REAL["model_type"])
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")
S, F = "sliding_attention", "full_attention"
REDUCED = {"num_hidden_layers": 5, "layer_types": [S, S, S, S, F],
           "mlp_layer_types": ["dense"] + ["sparse"] * 4,
           "sliding_windows": [128, 128, 128, 128, 0], "num_experts": 16,
           "vocab_size": 19200, "max_position_embeddings": 16384}
NEW = ["mtp_accept_share.batch", "mtp_rows_per_call.batch",
       "mtp_proj_share.batch"]

TINY = dict(
    vocab_size=256, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_attention_heads=8, num_key_value_heads=2,
    head_dim=16, sliding_window=8, rope_parameters={"rope_theta": 1e6},
    rms_norm_eps=1e-5, published={"num_experts": 32}, num_experts=8,
    expert_rank=1, num_experts_per_tok=3, norm_topk_prob=True,
    routed_scaling_factor=2.5, num_shared_experts=1,
    layer_types=[S, S, F, S, F], mlp_layer_types=["dense"] + ["sparse"] * 4,
    num_hidden_layers=5, num_nextn_predict_layers=1,
    max_position_embeddings=256, run={"dtype": "float32"})


def _weights(seed=7):
    return FAMILY.build(TINY).init_params(jax.random.PRNGKey(seed))


# -- the configuration's file and the cell ----------------------------------

@pytest.mark.skipif(not CATALOG.is_file(), reason="no catalog installed")
def test_the_file_keeps_every_published_key_but_the_reduced():
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "K-EXAONE-236B-A23B")
    assert row["source_url"] == REAL["source"] == ENTRY["source"]
    assert ENTRY["reduced"] == list(REDUCED)
    assert set(REAL["reduced"]) == set(REDUCED)
    for key, value in row["config"].items():
        assert REAL[key] == REDUCED.get(key, value), key
    # the lists cut with the layers: layer 0's entry and the period 4-7's
    for key in ("layer_types", "mlp_layer_types", "sliding_windows"):
        assert REAL[key] == [row["config"][key][l] for l in (0, 4, 5, 6, 7)]
    for key in ("num_hidden_layers", "num_experts", "vocab_size",
                "max_position_embeddings"):
        assert REAL["published"][key] == row["config"][key]
    # the prediction layer is NOT reduced
    assert REAL["num_nextn_predict_layers"] == 1 \
        == row["config"]["num_nextn_predict_layers"]
    assert "n_shared_experts" not in REAL


def test_the_cut_is_the_issues():
    assert (REAL["expert_rank"], REAL["num_experts_per_tok"]) == (0, 8)
    assert "8 chips share each layer" in REAL["deployment"]
    assert "1 of 49" in REAL["deployment"]
    for said in ("PRE-norm", "modeling_exaone4.py:295-313",
                 "modeling_exaone4.py:227", "arXiv:2412.19437",
                 "EMBEDDING's half first", "AFTER the final norm",
                 "choice bias", "8) times smaller", "num_shared_experts 1",
                 "0 <= i - j < 128", "accepts no draft"):
        assert any(said in line for line in REAL["assumed"]), said
    run = REAL["run"]
    assert (run["max_batch_slots"], run["kv_block_size"],
            run["kv_num_blocks"]) == (128, 16, 32768)
    assert set(run["program_defaults_not_passed"]) == {
        "_note", "prefill_chunk", "prefill_batch", "decode_burst"}
    # the traffic, to the number
    traffic = manifest.load_json("traffic", CELL["traffic"])
    assert CELL["traffic"] == "specreason-closed-loop" and CELL["chips"] == 1
    assert {k: v for k, v in traffic.items() if k != "_why"} == {
        "generator": "requests", "loop": "closed", "klass": "batch",
        "clients": 256, "requests_per_client": 8,
        "open_when_live_streams": 128,
        "prompt_tokens": {"median": 512, "sigma": 0.6, "min": 128,
                          "max": 2048},
        "new_tokens": {"median": 2048, "sigma": 0.5, "min": 512,
                       "max": 6144},
        "shared_prefix_share": 0.0, "order_seed": 20261059}
    assert traffic["clients"] == 2 * run["max_batch_slots"]
    check = run["check"]
    assert check["prompt_tokens"] == [96, 640, 3000]
    assert check["new_tokens"] == 36 and 0.0 < check["tolerance"] <= 0.1
    why = check["_why"]
    for said in ("The largest sound one", "e4m3", "rotary applied in the full",
                 "routed_scaling_factor read as 1"):
        assert said in why, said
    # what is resident, reckoned from the file's keys: the issue's 13.9 GB
    model = FAMILY.build(REAL)
    weights = sum(int(np.prod(a.shape)) * 2 for a in jax.tree.leaves(
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0))))
    full = 32768 * 16 * 2 * 8 * (128 + 128) * 2         # layer 7 and mtp
    rings = (1 + 128 * 16) * 16 * 4 * 8 * (128 + 128) * 2
    assert 9.08e9 < weights < 9.10e9
    assert 13.9e9 < weights + full + rings < 13.95e9
    # the longest sequence and a draft row fit a block table
    longest = traffic["prompt_tokens"]["max"] + traffic["new_tokens"]["max"]
    assert longest + 1 <= REAL["max_position_embeddings"]


def test_the_cell_reports_its_own_metrics_and_not_the_other_families():
    reported = {m["name"] for m in manifest.cell_metrics(
        BENCH, CELL["name"], "per_layer")}
    assert set(NEW) <= reported
    assert {"moe_expert_share.batch", "moe_expert_roofline.batch",
            "moe_experts_active_per_call.batch", "peak_hbm_gb.batch",
            "paged_attn_share.batch", "calls_ahead_share.batch",
            "tokens_per_decode_call.batch", "decode_step_ms_p50.batch",
            "compile_load_s.setup"} <= reported
    # the paged roofline counts num_hidden_layers layers of keys where
    # this model has two kinds and a sixth layer; the others are held to
    # one cell each by the tests of the families that brought them
    assert not reported & {
        "paged_attn_roofline.batch", "hybrid_attn_roofline.batch",
        "kv_window_recycled_per_call.batch",
        "moe_local_assignments_per_call.batch", "call_gap_ms_p50.batch",
        "ssm_share.batch", "mixer_share.batch", "delta_share.batch",
        "latent_attn_roofline.batch"}
    # the new entries are the manifest's last three, this cell's alone
    assert [m["name"] for m in BENCH["per_layer"][-3:]] == NEW
    for name in NEW:
        entry = manifest.named(BENCH["per_layer"], name, "metric")
        assert entry["workloads"] == [CELL["name"]]
        assert entry["moves"] == "serve_tokens_per_s"
        assert entry["layer"] == "Draft and verify"
    assert {"serve_tokens_per_s", "setup_s"} == {
        m["name"] for m in manifest.cell_metrics(BENCH, CELL["name"],
                                                 "end_to_end")}
    assert BENCH["workloads"][-1] == CELL and len(BENCH["workloads"]) >= 11


def test_a_trained_tokens_operations_count_the_prediction_layer():
    """6 operations a weight a trained token passes: the five layers, the
    head, and the prediction layer with its own pass through the head."""
    H, V = REAL["hidden_size"], REAL["vocab_size"]
    attn = 2 * H * 64 * 128 + 2 * H * 8 * 128
    sparse = 9 * 3 * H * 2048 + H * 128
    trunk = 5 * attn + 3 * H * 18432 + 4 * sparse + H * V
    mtp = 2 * H * H + attn + sparse + H * V
    assert 2.38e9 < trunk < 2.40e9 and 0.64e9 < mtp < 0.65e9
    got = FAMILY.train_flops_per_token(REAL, 1) / 6
    assert got == pytest.approx(trunk + mtp, rel=1e-3)
    off = dict(REAL, num_nextn_predict_layers=0)
    assert FAMILY.train_flops_per_token(off, 1) / 6 == pytest.approx(
        trunk, rel=1e-3)


# -- every wrong variant fails -------------------------------------------------

_ROUTING = FAMILY.routing


def _bias_ignored(h, wr, bias, cfg):
    return _ROUTING(h, wr, jnp.zeros_like(bias), cfg)


def _softmax_routing(h, wr, bias, cfg):
    score = jax.nn.softmax(h @ wr, axis=-1)
    biased = score + bias[None, :]
    top, _ = jax.lax.top_k(biased, cfg["num_experts_per_tok"])
    chosen = jnp.where(biased >= top[:, -1:], score, 0.0)
    chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return chosen * cfg["routed_scaling_factor"]


#: a function of the reference replaced, or a key of its configuration
WRONG = {
    "no_qk_norm": ({}, {"control_no_qk_norm": True}),
    "rotary_in_the_full_layers": ({}, {"control_rotary_in_full": True}),
    "scale_1_for_2.5": ({}, {"routed_scaling_factor": 1.0}),
    "softmax_for_sigmoid": ({"routing": _softmax_routing}, {}),
    "choice_bias_ignored": ({"routing": _bias_ignored}, {}),
    "weights_not_normalised": ({}, {"norm_topk_prob": False}),
    "one_expert_fewer": ({}, {"num_experts_per_tok": 2}),
    "window_off_by_one": ({}, {"sliding_window": 9}),
    "every_layer_full": ({}, {"layer_types": [F] * 5}),
}


@pytest.fixture(scope="module")
def programs_logits():
    """(weights with the choice bias drawn 30 times larger, so that
    ignoring it moves a choice in 60 tokens; ids; the program's logits,
    which are the reference's)."""
    params = _weights()
    for lp in [params["layers"]["moe"], params["mtp"]["layer"]["moe"]]:
        lp["bias"] = lp["bias"] * 30
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, 60), 0, 256)
    with jax.default_matmul_precision("highest"):
        got = FAMILY.build(TINY).forward(params, ids)
    assert float(jnp.max(jnp.abs(
        got - FAMILY.forward(params, TINY, ids)))) < 2e-4
    return params, ids, got


@pytest.mark.parametrize("wrong", sorted(WRONG))
def test_every_wrong_variant_fails(wrong, monkeypatch, programs_logits):
    """The program's logits lie within 2e-4 of the reference's and far
    from each wrong variant's."""
    params, ids, got = programs_logits
    replaced, keys = WRONG[wrong]
    for name, fn in replaced.items():
        monkeypatch.setattr(FAMILY, name, fn)
    other = FAMILY.forward(params, dict(TINY, **keys), ids)
    assert float(jnp.max(jnp.abs(got - other))) > 0.1 * float(jnp.std(got))


def test_the_controls_of_the_check_fail_it_at_the_tiny_size():
    """``k_exaone_control.py``'s wrong models through the runner's own
    ``_logit_gap``: greedy tokens of the reference with rotary in its full
    layers, with its routed sum unscaled, without its head norms and with
    its products in e4m3 sit under the reference's best; its own sit at
    it."""
    params = _weights()
    runner = manifest.load_module("runners", "serve")
    ctx = types.SimpleNamespace(family=lambda: FAMILY, config=TINY)
    prompt = np.random.default_rng(2).integers(0, 256, 40, dtype=np.int32)

    def gap(cfg, bits=None):
        tokens = serving_control.greedy_tokens(FAMILY, params, cfg, prompt,
                                               10, bits)
        return runner._logit_gap(
            ctx, types.SimpleNamespace(params=params),
            types.SimpleNamespace(
                request=types.SimpleNamespace(prompt=prompt), tokens=tokens))

    assert gap(TINY) == 0.0
    wrong = k_exaone_control.WRONG
    assert set(wrong) == {"rotary_in_full", "no_qk_norm", "scale_1"}
    for name in sorted(wrong):
        assert gap(wrong[name](TINY)) > 0.05, name
    assert gap(TINY, (4, 3)) > 0.05


# -- the cell's readers --------------------------------------------------------

def test_the_cells_counter_metrics_read_the_programs_own_counters():
    """Through the real engine at the tiny size with the hub on: a draft a
    committed row a step, none accepted twice, two rows a slot a step and
    a chunk's rows through the drafting layer; the readers turn them into
    the cell's two counter metrics."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.inference.v2 import KVCacheConfig, build_engine_v2

    tel = telemetry.configure(enabled=True, jsonl=False, prometheus=False)
    read_counters = lambda: {
        m.name: float(m.value) for m in tel.registry.metrics().values()
        if getattr(m, "kind", "") == "counter"}
    try:
        before = read_counters()    # the hub is the process's: count growth
        tel.tracer.reset()
        eng = build_engine_v2(
            FAMILY.build(TINY), _weights(),
            cache_config=KVCacheConfig(num_blocks=96, block_size=4,
                                       max_seq_len=128),
            max_batch_slots=2, prefill_chunk=8, prefill_batch=1,
            decode_burst=4)
        prompt = np.random.RandomState(3).randint(0, 256, size=19).tolist()
        out = eng.generate([prompt], 13)
        counters = {name: value - before.get(name, 0.0)
                    for name, value in read_counters().items()}
        spans = [{"name": e["name"], "dur_s": e["dur"] * 1e-6,
                  "args": e.get("args", {})} for e in tel.tracer.events()]
    finally:
        telemetry.configure(enabled=False)
    assert len(out[0]) == 13
    drafted, accepted = (counters["inference/mtp/drafted"],
                         counters["inference/mtp/accepted"])
    assert drafted >= 12 - accepted and accepted <= drafted
    assert counters["inference/mtp/keys_taken_back"] == drafted - accepted
    assert counters["inference/decode_tokens"] == 12
    calls = sum(s["name"] == "inference/commit" for s in spans)
    chunk_calls = 3
    steps = sum(s["args"]["burst"] for s in spans
                if s["name"] == "inference/decode_burst")
    assert counters["inference/mtp/rows"] == steps * 2 * 2 + chunk_calls * 8
    assert counters["inference/rows_computed"] == counters[
        "inference/mtp/rows"]
    # a burst span says its steps AND the tokens its rows yielded
    bursts = [s["args"] for s in spans
              if s["name"] == "inference/decode_burst" and s["args"]["batch"]]
    assert all(b["burst"] <= b["tokens"] <= 2 * b["burst"] for b in bursts)
    obs = {"program_counters": counters, "program_spans": spans}
    read = lambda name: manifest.load_module(
        "readers", manifest.load_json("metrics", name)["reader"]).read(
            obs, manifest.load_json("metrics", name)["args"])
    assert read("mtp_accept_share.batch") == pytest.approx(
        100.0 * accepted / drafted)
    assert read("mtp_rows_per_call.batch") == pytest.approx(
        counters["inference/mtp/rows"] / calls)
    # a program without the counters (the parent, the other families):
    # nothing to read, no error
    bare = {"program_counters": {}, "program_spans": spans, "trace": None}
    for name in NEW:
        spec = manifest.load_json("metrics", name)
        assert manifest.load_module("readers", spec["reader"]).read(
            bare, spec["args"]) is None


# -- the traffic against the harness's ramp ----------------------------------

@pytest.mark.parametrize("seed", [1, 2**31 + 11])
def test_every_slot_streams_well_inside_the_harness_s_ramp(seed):
    """``runners/serve.py:offer`` gives the ramp a fixed 120 s (ledger, PR
    56).  This traffic fills its 128 slots within 600 steps on every seed
    (the schedule is the order_seed's): at 40 ms a step with chunks, 24
    s."""
    gen = manifest.load_module("generators", "requests")
    traffic = gen.make(manifest.load_json("traffic", CELL["traffic"]), seed,
                       25.0, REAL["vocab_size"])
    steps, prompt_tokens = _steps_until_every_slot_streams(
        traffic, REAL["run"]["max_batch_slots"])
    assert steps is not None and steps <= 600, steps
    # the same schedule on both seeds: lengths are the order_seed's
    assert (steps, prompt_tokens) == _SCHEDULE, (steps, prompt_tokens)


#: (steps until all 128 slots stream, prompt tokens prefilled until then)
_SCHEDULE = (386, 89_289)
