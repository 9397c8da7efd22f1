"""What the latent-attention configuration brings to the benchmark: its
file against the catalog's published keys, the arithmetic of its cut, the
bytes and operations of a cached key by hand, its roofline reader on a
trace and counters made by hand, where its two metrics stand, and the
control of its check at the tiny size."""

import json
import pathlib
import sys
import types

import jax
import numpy as np
import pytest

from perfbench import latent_attn_shapes, manifest, moe_shapes
from perfbench import trace_reduce as tr

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import control  # noqa: E402
import rehearsal  # noqa: E402
import serving_control  # noqa: E402

BENCH = manifest.load_benchmark()
CELL = manifest.named(BENCH["workloads"], "serve-longreason-pangu-ultra-l5",
                      "workload")
ENTRY = manifest.named(BENCH["configs"], CELL["config"], "configuration")
REAL = manifest.load_json("configs", CELL["config"])
FAMILY = manifest.load_module("models", REAL["model_type"])
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")

#: the source's keys as the catalog gives them (``config`` of the row
#: ``openPangu-Ultra-MoE-718B``): written out so that the test holds where
#: the catalog is not installed
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 3, "hidden_act": "silu",
    "hidden_size": 7680, "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "pangu_ultra_moe",
    "moe_intermediate_size": 2048, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 25600000, "routed_scaling_factor": 2.5,
    "sandwich_norm": True, "tie_word_embeddings": False, "v_head_dim": 128,
    "vocab_size": 153600}
REDUCED = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
           "n_routed_experts": 8, "vocab_size": 19200,
           "max_position_embeddings": 16384}
NEW = ["latent_attn_roofline.batch", "latent_keys_read_per_call.batch"]


def test_the_file_keeps_every_published_key_but_the_reduced():
    assert set(ENTRY["reduced"]) == set(REAL["reduced"]) == set(REDUCED)
    for key, value in PUBLISHED.items():
        assert REAL[key] == REDUCED.get(key, value), key
    assert REAL["source"] == ENTRY["source"]
    assert REAL["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert "32 chips" in REAL["deployment"] and "8 rows" in REAL["deployment"]
    for said in ("sigmoid", "half-split", "sandwich_norm",
                 "multi-token-prediction", "8) times smaller"):
        assert any(said in line for line in REAL["assumed"]), said
    # the floors: four sparse layers after the leading dense one, eight
    # held experts, an eighth of the vocabulary
    assert REAL["num_hidden_layers"] - REAL["first_k_dense_replace"] >= 4
    assert REAL["n_routed_experts"] >= 8
    assert REAL["vocab_size"] * 8 >= PUBLISHED["vocab_size"]


@pytest.mark.skipif(not CATALOG.is_file(), reason="no catalog installed")
def test_the_written_out_keys_are_the_catalogs():
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "openPangu-Ultra-MoE-718B")
    assert row["source_url"] == REAL["source"]
    assert row["config"] == PUBLISHED


def test_the_cells_traffic_is_the_issues():
    traffic = manifest.load_json("traffic", CELL["traffic"])
    assert (traffic["generator"], traffic["loop"], traffic["klass"]) == (
        "requests", "closed", "batch")
    assert (traffic["clients"], traffic["requests_per_client"],
            traffic["open_when_live_streams"]) == (256, 8, 128)
    assert traffic["prompt_tokens"] == {"median": 2048, "sigma": 0.5,
                                        "min": 512, "max": 6144}
    assert traffic["new_tokens"] == {"median": 3072, "sigma": 0.5,
                                     "min": 1024, "max": 8192}
    assert traffic["shared_prefix_share"] == 0.0
    others = {manifest.load_json("traffic", w["traffic"]).get("order_seed")
              for w in BENCH["workloads"] if w["name"] != CELL["name"]}
    assert traffic["order_seed"] not in others
    run = REAL["run"]
    assert traffic["clients"] == 2 * run["max_batch_slots"] == 256
    longest = traffic["prompt_tokens"]["max"] + traffic["new_tokens"]["max"]
    assert longest <= REAL["max_position_embeddings"]
    assert run["max_outstanding_tokens"] >= traffic["clients"] * longest
    # the check's longest prompt is served inside the traffic's shapes,
    # is over 4,096 tokens and past a page bucket's edge (buckets are
    # powers of two of a chunk's pages)
    check = run["check"]
    assert 4096 < max(check["prompt_tokens"]) \
        <= traffic["prompt_tokens"]["max"] and check["new_tokens"] >= 36
    assert CELL["chips"] == 1


def test_weights_and_bytes_of_the_cut():
    H, V = 7680, 19200
    attention = (H * 1536 + 1536 * 128 * 192 + H * 576 + 512 * 128 * 256
                 + 128 * 128 * H)
    expert, dense, router = 3 * H * 2048, 3 * H * 18432, H * 256
    assert FAMILY.attention_weights(REAL) == attention == 196_575_232
    assert (expert, dense, router) == (47_185_920, 424_673_280, 1_966_080)
    sparse = attention + 8 * expert + expert + router
    assert sparse == pytest.approx(623.2e6, rel=1e-3)
    held = (attention + dense) + 4 * sparse + 2 * V * H
    assert held == pytest.approx(3.41e9, rel=2e-3)
    assert 2 * held == pytest.approx(6.82e9, rel=2e-3)
    # the program's weight tree is that many numbers (plus the norms)
    model = FAMILY.build(REAL)
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    stored = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    norms = 5 * (4 * H + 1536 + 512) + H
    assert stored == held + norms
    # a trained token of the MODEL: eight routed experts wherever they
    # live and the shared one, inside the manifest test's bracket
    token = FAMILY.train_flops_per_token(REAL, 1) / 6
    model_token = 4 * sparse + (attention + dense) + V * H
    assert model_token == pytest.approx(3.26e9, rel=2e-3)
    assert token == pytest.approx(model_token + 5 * 2 * 128 * 320 / 2,
                                  rel=1e-6)
    low = 5 * (2 * H * H + 2 * 9 * H * 2048)
    high = 5 * (5 * H * H + 4 * 9 * H * 2048 + H * 8) + 2 * H * V
    assert low == pytest.approx(2.01e9, rel=5e-3)
    assert high == pytest.approx(4.60e9, rel=5e-3)
    assert low < token < high
    assert moe_shapes.expert_weight_bytes(REAL) == 2 * expert
    # the cache: 786,432 tokens of 5 rows of 576 numbers; five 128-lane
    # planes as held
    run = REAL["run"]
    tokens = run["kv_num_blocks"] * run["kv_block_size"]
    assert tokens == 49152 * 16 == 786_432
    assert tokens * 5 * latent_attn_shapes.bytes_per_key(REAL) \
        == pytest.approx(4.53e9, rel=2e-3)
    from deepspeed_tpu.inference.v2 import KVCacheConfig
    from deepspeed_tpu.inference.v2.adapters import make_adapter
    from deepspeed_tpu.inference.v2.kv_cache import init_kv_pool

    adapter_pool = jax.eval_shape(lambda: init_kv_pool(
        make_adapter(model),
        KVCacheConfig(num_blocks=run["kv_num_blocks"],
                      block_size=run["kv_block_size"])))
    assert sorted(adapter_pool) == ["latent"]
    assert sorted(adapter_pool["latent"]) == ["k"]
    plane = adapter_pool["latent"]["k"]
    assert plane.shape == (5 * 5, 6144, 128, 1, 128)
    assert int(np.prod(plane.shape)) * 2 == pytest.approx(5.03e9, rel=2e-3)
    assert run["max_batch_slots"] == 128


def test_a_cached_keys_bytes_and_operations_by_hand():
    assert latent_attn_shapes.row_width(REAL) == 576
    assert latent_attn_shapes.bytes_per_key(REAL) == 1152.0
    assert latent_attn_shapes.flops_per_key(REAL) \
        == 128 * (2 * 576 + 2 * 512) == 278_528.0
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    # the ridge: 242 operations a byte against the chip's 240
    assert 278_528 / 1152 == pytest.approx(241.8, abs=0.1)
    keys = 128 * 3300.0
    by_bytes = keys * 5 * 1152 / 819e9
    by_flops = keys * 5 * 278_528 / 197e12
    assert by_flops > by_bytes
    assert latent_attn_shapes.decode_seconds(keys, REAL, peaks) \
        == pytest.approx(by_flops)
    # a chip with more arithmetic for its bandwidth: the byte bound
    slow_hbm = dict(peaks, hbm_bytes_per_s=400e9)
    assert latent_attn_shapes.decode_seconds(keys, REAL, slow_hbm) \
        == pytest.approx(keys * 5 * 1152 / 400e9)


def _kernel(name):
    return (f"%{name} = bf16[128,128,512]{{2,1,0:T(8,128)(2,1)}} custom-call("
            f"s32[128]{{0}} %lens, bf16[128,128,640]{{2,1,0}} %q), "
            f'custom_call_target="tpu_custom_call", operand_layout_constraints={{}}')


def _obs(**over):
    ops = [tr.Event(0.0, 4e6, _kernel("paged_decode_attention.3")),
           tr.Event(4e6, 6e6, _kernel("paged_decode_attention.4")),
           tr.Event(10e6, 5e6, _kernel("moe_grouped_matmul.1")),
           tr.Event(15e6, 5e6, "%fusion.1 = bf16[128,7680]{1,0} fusion(%p0)")]
    mods = [tr.Event(i * 1e7, 1e7, name) for i, name in enumerate(
        ["jit_inference_v2_decode_burst_n_steps8(9)",
         "jit_inference_v2_decode_burst_n_steps1(7)"])]
    spans = ([{"name": "inference/decode_burst", "dur_s": 0.1,
               "args": {"burst": 8, "batch": 128}}] * 10
             + [{"name": "inference/decode_burst", "dur_s": 0.02,
                 "args": {"burst": 1, "batch": 128}}] * 10
             + [{"name": "inference/commit", "dur_s": 1e-4, "args": {}}] * 20)
    obs = {"trace": tr.Trace(devices={0: tr.DeviceTrace(
               ops=ops, async_ops=[], modules=mods)}, host={}, t0_ns=0.0,
               t1_ns=1e9),
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
           "config": REAL, "program_spans": spans,
           # 90 decode steps in the window of 128 rows at 3,300 keys
           "program_counters": {
               "inference/attn/keys_read_latent": 90 * 128 * 3300.0}}
    obs.update(over)
    return obs


def _read(metric, obs):
    spec = manifest.load_json("metrics", metric)
    return manifest.load_module("readers", spec["reader"]).read(
        obs, spec.get("args", {}))


def test_the_roofline_joins_the_counter_to_the_trace_by_decode_steps():
    # 9 traced decode steps of the window's 90: a tenth of the counter,
    # against the 10 ms of the two attention kernels (not the expert one);
    # the operation bound is the larger at the published widths
    step = 128 * 3300 * 5 * 278_528 / 197e12
    assert _read("latent_attn_roofline.batch", _obs()) == pytest.approx(
        100.0 * 9 * step / 0.010)
    assert _read("latent_keys_read_per_call.batch", _obs()) \
        == pytest.approx(90 * 128 * 3300 / 20)


@pytest.mark.parametrize("missing", ["trace", "counters", "peaks", "kernel",
                                     "spans"])
def test_a_program_without_the_latent_cache_gives_nothing_to_read(missing):
    """The parent's program has no such counter: the reader returns
    nothing and does not raise."""
    obs = _obs()
    if missing == "kernel":
        obs["trace"].devices[0].ops[:] = obs["trace"].devices[0].ops[2:]
    elif missing == "spans":
        obs["program_spans"] = []
    elif missing == "counters":
        obs["program_counters"] = {"inference/decode_tokens": 5.0}
    else:
        obs[missing] = None
    assert _read("latent_attn_roofline.batch", obs) is None
    if missing in ("counters", "spans"):
        assert _read("latent_keys_read_per_call.batch", obs) is None


@pytest.mark.parametrize("metric", NEW)
def test_a_new_metric_is_a_file_and_an_entry_that_agree(metric):
    """The two metrics' files, readers and shapes came with the cell;
    their ``per_layer`` entries waited until PR 51 unpinned the list
    (``test_perfbench_call_readers.py`` held PR 38's twelve to its END):
    they list this cell alone and lie in one block."""
    spec = manifest.load_json("metrics", metric)
    assert spec["name"] == metric and spec["moves"] == "serve_tokens_per_s"
    assert (manifest.BENCH_DIR / "readers" / f"{spec['reader']}.py").is_file()
    assert spec["unit"] == ("%" if "roofline" in metric else "keys/call")
    assert spec["layer"] == ("Kernels" if "roofline" in metric
                             else "v2 engine")
    names = [m["name"] for m in BENCH["per_layer"]]
    listed = [m for m in BENCH["per_layer"] if m["name"] in NEW]
    assert all(entry["workloads"] == [CELL["name"]] for entry in listed)
    entry = manifest.named(listed, metric, "metric")
    assert {k: spec[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"}
    assert len(listed) == len(NEW)
    at = names.index(NEW[0])
    assert names[at:at + len(NEW)] == NEW


def test_the_cell_joins_the_serving_metrics_that_are_not_pinned():
    listed = {m["name"] for m in manifest.cell_metrics(
        BENCH, CELL["name"], "per_layer")}
    assert {"paged_attn_share.batch", "moe_expert_share.batch",
            "moe_expert_roofline.batch", "moe_experts_active_per_call.batch",
            "device_idle_share.batch", "peak_hbm_gb.batch",
            "tokens_per_decode_call.batch",
            "chunk_tokens_per_decode_call.batch",
            "decode_device_step_ms_p50.batch",
            "idle_in_pump_share.batch", "calls_ahead_share.batch",
            *NEW} <= listed
    # retired by PR 51, not this cell's, another kind of cache, or PR 38's
    # twelve, which stay the three older cells'
    assert not {"prefill_wall_share.batch", "prefill_device_share.batch",
                "paged_attn_roofline.batch", "hybrid_attn_roofline.batch",
                "call_gap_ms_p50.batch", "live_row_share.batch"} & listed
    assert {m["name"] for m in manifest.cell_metrics(
        BENCH, CELL["name"], "end_to_end")} == {"serve_tokens_per_s",
                                                "setup_s"}


def _tiny():
    cfg, _ = rehearsal.tiny_files(CELL)
    cfg["run"] = dict(cfg["run"], dtype="float32")
    return cfg


def test_the_reference_tells_its_published_keys_apart():
    """Same weights and ids: one published key changed moves the logits."""
    cfg = _tiny()
    weights = FAMILY.build(cfg).init_params(jax.random.PRNGKey(3))
    ids = jax.random.randint(jax.random.PRNGKey(4), (1, 40), 0,
                             cfg["vocab_size"])
    base = FAMILY.forward(weights, cfg, ids)
    for key, value in (("routed_scaling_factor", 1.0),
                       ("norm_topk_prob", False), ("n_shared_experts", 0),
                       ("sandwich_norm", False), ("rope_theta", 10000),
                       ("expert_rank", 0)):
        other = FAMILY.forward(weights, dict(cfg, **{key: value}), ids)
        assert float(abs(base - other).max()) > 1e-3, key


def test_the_e4m3_control_is_refused_at_the_tiny_size():
    """The check's own gap for greedy tokens of the reference itself is 0;
    for the reference with every product narrowed to e4m3 it is over the
    tiny check's limit, as on the chip it has to be over the cell's."""
    import jax.numpy as jnp

    from perfbench import harness

    cfg = rehearsal.tiny_files(CELL)[0]
    ctx = harness.Context(cell=CELL, config=cfg, traffic={}, seed=5,
                          seconds=0.0, trace=False, t_start=0.0, scratch="")
    model = FAMILY.build(cfg)
    weights = jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                           model.init_params(jax.random.PRNGKey(5)))
    runner = manifest.load_module("runners", cfg["run"]["runner"])
    prompt = np.random.default_rng(6).integers(0, cfg["vocab_size"], size=48,
                                               dtype=np.int32)

    def gap(bits):
        tokens = serving_control.greedy_tokens(FAMILY, weights, cfg, prompt,
                                               12, bits)
        return runner._logit_gap(
            ctx, types.SimpleNamespace(params=weights),
            types.SimpleNamespace(
                request=types.SimpleNamespace(prompt=prompt), tokens=tokens))

    assert gap(None) == 0.0
    assert gap(control.NARROWER["bfloat16"]) > 0.01
