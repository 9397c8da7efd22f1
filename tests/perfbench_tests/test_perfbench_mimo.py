"""What the hybrid configuration brings to the benchmark: its file against
the catalog's published keys, the byte and weight arithmetic of its cut,
its reference told apart from its own wrong variants, and the reader of
its kernel's roofline on a trace and counters made by hand."""

import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import hybrid_attn_shapes, manifest, moe_shapes
from perfbench import trace_reduce as tr

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import rehearsal  # noqa: E402

BENCH = manifest.load_benchmark()
CELL = manifest.named(BENCH["workloads"], "serve-reason-mimo-v25-l7",
                      "workload")
ENTRY = manifest.named(BENCH["configs"], CELL["config"], "configuration")
REAL = manifest.load_json("configs", CELL["config"])
FAMILY = manifest.load_module("models", REAL["model_type"])
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")

#: the source's keys as the catalog gives them (``config`` of the row
#: ``MiMo-V2.5``), without the two 48-entry lists: written out so that the
#: test holds where the catalog is not installed
PUBLISHED = {
    "attention_bias": False, "attention_chunk_size": 128,
    "attention_value_scale": 0.707,
    "attention_projection_layout": "fused_qkv",
    "add_full_attention_sink_bias": False,
    "add_swa_attention_sink_bias": True, "swa_num_key_value_heads": 8,
    "swa_num_attention_heads": 64, "swa_head_dim": 192,
    "swa_v_head_dim": 128, "head_dim": 192, "hidden_act": "silu",
    "hidden_size": 4096, "hybrid_block_size": None,
    "intermediate_size": 16384, "layernorm_epsilon": 1e-05,
    "max_position_embeddings": 1048576, "model_type": "mimo_v2",
    "moe_intermediate_size": 2048, "n_group": 1, "n_routed_experts": 256,
    "n_shared_experts": None, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "partial_rotary_factor": 0.334,
    "rope_scaling": {"rope_type": "default", "type": "default"},
    "rope_theta": 10000000, "routed_scaling_factor": None,
    "scoring_func": "sigmoid", "sliding_window": 128,
    "sliding_window_size": 128, "swa_rope_theta": 10000,
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 152576}
REDUCED = {"num_hidden_layers": 7, "hybrid_layer_pattern": [0, 1, 1, 1, 1, 1, 0],
           "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1], "n_routed_experts": 16,
           "vocab_size": 19072, "max_position_embeddings": 8192}


def test_the_file_keeps_every_published_key_but_the_reduced():
    assert set(ENTRY["reduced"]) == set(REAL["reduced"]) == set(REDUCED)
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert REAL[key] == REDUCED[key], key
        elif key == "n_shared_experts":
            assert REAL[key] == 0          # null in the source: `assumed`
        else:
            assert REAL[key] == value, key
    assert REAL["source"] == ENTRY["source"]
    stated = REAL["published"]
    assert (stated["n_routed_experts"], stated["num_hidden_layers"],
            stated["vocab_size"]) == (256, 48, 152576)
    assert stated["n_shared_experts"] is None
    assert any("n_shared_experts" in line for line in REAL["assumed"])
    assert "16 chips" in REAL["deployment"] and "8 rows" in REAL["deployment"]
    # the floors: a whole period and four layers after the leading dense
    # one, eight held experts, an eighth of the vocabulary
    assert REAL["hybrid_layer_pattern"][1:] == [1, 1, 1, 1, 1, 0]
    assert REAL["n_routed_experts"] >= 8
    assert REAL["vocab_size"] * 8 >= PUBLISHED["vocab_size"]


@pytest.mark.skipif(not CATALOG.is_file(), reason="no catalog installed")
def test_the_written_out_keys_are_the_catalogs():
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "MiMo-V2.5")
    assert row["source_url"] == REAL["source"]
    config = dict(row["config"])
    pattern, freq = config.pop("hybrid_layer_pattern"), config.pop(
        "moe_layer_freq")
    assert config == PUBLISHED
    # the layers that are run are published layers 0 and 6-11
    assert [pattern[0]] + pattern[6:12] == REAL["hybrid_layer_pattern"]
    assert [freq[0]] + freq[6:12] == REAL["moe_layer_freq"]


def test_the_cells_traffic_is_the_issues():
    traffic = manifest.load_json("traffic", CELL["traffic"])
    assert (traffic["generator"], traffic["loop"], traffic["klass"]) == (
        "requests", "closed", "batch")
    assert (traffic["clients"], traffic["requests_per_client"],
            traffic["open_when_live_streams"]) == (512, 8, 256)
    assert traffic["prompt_tokens"] == {"median": 256, "sigma": 0.6,
                                        "min": 64, "max": 1024}
    assert traffic["new_tokens"] == {"median": 1536, "sigma": 0.5,
                                     "min": 512, "max": 4096}
    assert traffic["shared_prefix_share"] == 0.0
    run = REAL["run"]
    assert traffic["clients"] == 2 * run["max_batch_slots"] == 512
    longest = traffic["prompt_tokens"]["max"] + traffic["new_tokens"]["max"]
    assert longest <= REAL["max_position_embeddings"]
    # nothing is refused: every client's longest request fits what may be
    # outstanding, and the pool holds every slot's
    assert run["max_outstanding_tokens"] >= traffic["clients"] * longest
    assert run["kv_num_blocks"] * run["kv_block_size"] \
        >= run["max_batch_slots"] * 2 * 1024


def test_weights_and_bytes_of_the_cut():
    H, V = 4096, 19072
    full = H * 64 * 192 + H * 4 * 192 + H * 4 * 128 + 64 * 128 * H
    window = H * 64 * 192 + H * 8 * 192 + H * 8 * 128 + 64 * 128 * H
    expert, dense, router = 3 * H * 2048, 3 * H * 16384, H * 256
    assert (full, window, expert, dense) == (89128960, 94371840, 25165824,
                                             201326592)
    held = (full + dense) + 5 * (window + router + 16 * expert) \
        + (full + router + 16 * expert) + 2 * V * H
    assert held == pytest.approx(3.43e9, rel=2e-3)
    # the program's weight tree is that many numbers (plus norms, sinks and
    # the choice bias)
    model = FAMILY.build(REAL)
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    stored = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    small = 15 * H + 5 * 64 + 6 * 256
    assert stored == held + small
    # a trained token of the MODEL: eight routed experts wherever they
    # live, inside the manifest test's bracket; the chip's share is not
    token = FAMILY.train_flops_per_token(REAL, 1) / 6
    routed = (full + dense) + 5 * (window + router + 8 * expert) \
        + (full + router + 8 * expert) + V * H
    assert token == pytest.approx(routed + 7 * 64 * 320 / 6 * 2, rel=1e-4)
    assert 1.174e9 < 2.14e9 < token < 2.15e9 < 2.623e9
    assert moe_shapes.expert_weight_bytes(REAL) == 2 * expert == 50331648
    # the KV pools as published widths count them
    per_key = hybrid_attn_shapes.bytes_per_key(REAL)
    assert per_key == {"full": 4 * 320 * 2.0, "window": 8 * 320 * 2.0}
    assert hybrid_attn_shapes.layers_of(REAL) == {"full": 2, "window": 5}
    run = REAL["run"]
    tokens = run["kv_num_blocks"] * run["kv_block_size"]
    assert tokens * 2 * per_key["full"] == pytest.approx(3.36e9, rel=2e-3)
    # a decode step of 256 rows at a context of 1,400
    step = hybrid_attn_shapes.decode_bytes(
        {"full": 256 * 1400.0, "window": 256 * 128.0}, REAL)
    assert step == pytest.approx(1.835e9 + 0.839e9, rel=1e-3)


def _tiny():
    cfg, _ = rehearsal.tiny_files(CELL)
    cfg["run"] = dict(cfg["run"], dtype="float32")
    return cfg


def test_the_reference_tells_its_wrong_variants_apart():
    """Same weights and ids: one published key changed moves the logits at
    positions past the window (and the window's edge only there)."""
    cfg = _tiny()
    weights = FAMILY.build(cfg).init_params(jax.random.PRNGKey(3))
    ids = jax.random.randint(jax.random.PRNGKey(4), (1, 48), 0,
                             cfg["vocab_size"])
    base = FAMILY.forward(weights, cfg, ids)
    window = cfg["sliding_window"]
    wider = FAMILY.forward(weights, dict(cfg, sliding_window=window + 1), ids)
    assert float(jnp.abs(base - wider)[:, :window].max()) < 1e-5
    assert float(jnp.abs(base - wider)[:, window:].max()) > 1e-3
    for key, value in (("attention_value_scale", 1.0),
                       ("norm_topk_prob", False),
                       ("partial_rotary_factor", 0.5),
                       ("swa_rope_theta", cfg["rope_theta"]),
                       ("expert_rank", 0)):
        other = FAMILY.forward(weights, dict(cfg, **{key: value}), ids)
        assert float(jnp.abs(base - other).max()) > 1e-3, key


def _kernel(name):
    return (f"%{name} = bf16[256,64,128]{{2,1,0:T(8,128)(2,1)}} custom-call("
            f"s32[256]{{0}} %lens, bf16[256,64,256]{{2,1,0}} %q), "
            f'custom_call_target="tpu_custom_call", operand_layout_constraints={{}}')


def _obs(**over):
    ops = [tr.Event(0.0, 4e6, _kernel("paged_decode_attention.3")),
           tr.Event(4e6, 6e6, _kernel("paged_decode_attention.4")),
           tr.Event(10e6, 5e6, _kernel("moe_grouped_matmul.1")),
           tr.Event(15e6, 5e6, "%fusion.1 = bf16[256,4096]{1,0} fusion(%p0)")]
    mods = [tr.Event(i * 1e7, 1e7, name) for i, name in enumerate(
        ["jit_inference_v2_prefill(3)",
         "jit_inference_v2_decode_burst_n_steps8(9)",
         "jit_inference_v2_decode_burst_n_steps1(7)"])]
    spans = ([{"name": "inference/decode_burst", "dur_s": 0.1,
               "args": {"burst": 8, "batch": 256}}] * 10
             + [{"name": "inference/decode_burst", "dur_s": 0.02,
                 "args": {"burst": 1, "batch": 256}}] * 10
             + [{"name": "inference/prefill", "dur_s": 0.02, "args": {}}] * 10
             + [{"name": "inference/commit", "dur_s": 1e-4, "args": {}}] * 30)
    obs = {"trace": tr.Trace(devices={0: tr.DeviceTrace(
               ops=ops, async_ops=[], modules=mods)}, host={}, t0_ns=0.0,
               t1_ns=1e9),
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
           "config": REAL, "program_spans": spans,
           # 90 decode steps in the window of 256 rows at 1,400 keys
           "program_counters": {
               "inference/attn/keys_read_full": 90 * 256 * 1400.0,
               "inference/attn/keys_read_window": 90 * 256 * 128.0,
               "inference/kv/window_pages_recycled": 90 * 16.0,
               "inference/moe/assignments": 90 * 128.0 + 10 * 128.0}}
    obs.update(over)
    return obs


def _read(metric, obs):
    spec = manifest.load_json("metrics", metric)
    return manifest.load_module("readers", spec["reader"]).read(
        obs, spec.get("args", {}))


def test_the_roofline_joins_the_counters_to_the_trace_by_decode_steps():
    # 9 traced decode steps of the window's 90: a tenth of the counters,
    # against the 10 ms of the two attention kernels (not the expert one)
    step = 256 * (1400 * 2 * 4 + 128 * 5 * 8) * 320 * 2
    assert _read("hybrid_attn_roofline.batch", _obs()) == pytest.approx(
        100.0 * 9 * step / 819e9 / 0.010)
    assert _read("kv_window_recycled_per_call.batch", _obs()) \
        == pytest.approx(90 * 16 / 30)
    assert _read("moe_local_assignments_per_call.batch", _obs()) \
        == pytest.approx(100 * 128 / 30)


@pytest.mark.parametrize("missing", ["trace", "counters", "peaks", "kernel",
                                     "spans", "one_counter"])
def test_a_program_without_the_pools_gives_nothing_to_read(missing):
    """The parent's program has no such counter: the reader returns
    nothing and does not raise."""
    obs = _obs()
    if missing == "kernel":
        obs["trace"].devices[0].ops[:] = obs["trace"].devices[0].ops[2:]
    elif missing == "spans":
        obs["program_spans"] = []
    elif missing == "counters":
        obs["program_counters"] = {"inference/decode_tokens": 5.0}
    elif missing == "one_counter":
        del obs["program_counters"]["inference/attn/keys_read_window"]
    else:
        obs[missing] = None
    assert _read("hybrid_attn_roofline.batch", obs) is None
    if missing in ("counters", "spans"):
        assert _read("kv_window_recycled_per_call.batch", obs) is None


def test_the_new_metrics_list_the_new_cell_alone():
    new = {"hybrid_attn_roofline.batch", "kv_window_recycled_per_call.batch",
           "moe_local_assignments_per_call.batch"}
    for metric in BENCH["per_layer"]:
        if metric["name"] in new:
            assert metric["workloads"] == [CELL["name"]]
            assert metric["moves"] == "serve_tokens_per_s"
    listed = {m["name"] for m in manifest.cell_metrics(
        BENCH, CELL["name"], "per_layer")}
    assert new <= listed and "paged_attn_roofline.batch" not in listed
    assert {"paged_attn_share.batch", "moe_expert_roofline.batch",
            "moe_expert_share.batch", "moe_experts_active_per_call.batch",
            "idle_in_pump_share.batch"} <= listed
    assert {m["name"] for m in manifest.cell_metrics(
        BENCH, CELL["name"], "end_to_end")} == {"serve_tokens_per_s",
                                                "setup_s"}
