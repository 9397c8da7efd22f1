"""What the sparse-expert configuration brings to the benchmark: its
reference's routing, the control of its serving check (a wrong expert
layer in the server's place comes out as not correct), its operation and
byte counts, and the readers of its per-layer metrics on traces and
counters made by hand."""

import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import harness, manifest, moe_shapes
from perfbench import trace_reduce as tr

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import rehearsal        # noqa: E402
import serving_control  # noqa: E402

BENCH = manifest.load_benchmark()
CELL = next(w for w in BENCH["workloads"]
            if manifest.load_json("configs", w["config"]).get("num_experts"))
REAL = manifest.load_json("configs", CELL["config"])
FAMILY = manifest.load_module("models", REAL["model_type"])
MOE_METRICS = [m["name"] for m in BENCH["per_layer"]
               if m["layer"] == "Expert layer"]
PATTERN = manifest.load_json("metrics", "moe_expert_share.batch")["args"]["pattern"]


def test_the_configuration_keeps_every_published_width():
    published = {"hidden_size": 2048, "intermediate_size": 1024,
                 "num_attention_heads": 16, "num_key_value_heads": 16,
                 "num_experts": 64, "num_experts_per_tok": 8,
                 "norm_topk_prob": False, "vocab_size": 50304,
                 "max_position_embeddings": 4096, "rope_theta": 10000,
                 "rms_norm_eps": 1e-05, "tie_word_embeddings": False,
                 "clip_qkv": None, "attention_bias": False,
                 "rope_scaling": None, "hidden_act": "silu",
                 "model_type": "olmoe"}
    assert {k: REAL[k] for k in published} == published
    assert REAL["num_hidden_layers"] == 8 and list(REAL["reduced"]) == [
        "num_hidden_layers"]
    assert REAL["assumed"] and REAL["deployment"]
    check = REAL["run"]["check"]
    assert max(check["prompt_tokens"]) + check["new_tokens"] \
        <= REAL["max_position_embeddings"]
    # the run group of the dense serving configuration: the same slots,
    # pages and traffic, no tuning argument passed
    dense = manifest.load_json("configs", "mistral-7b-serve-l16")["run"]
    for key in ("runner", "dtype", "attn_impl", "max_batch_slots",
                "kv_block_size", "kv_num_blocks", "max_outstanding_tokens"):
        assert REAL["run"][key] == dense[key], key
    assert set(REAL["run"]) == set(dense)
    assert {k: v for k, v in REAL["run"]["program_defaults_not_passed"].items()
            if k != "_note"} == {k: v for k, v in dense[
                "program_defaults_not_passed"].items() if k != "_note"}


def test_weights_and_bytes_at_the_published_widths():
    H, I, E, L = 2048, 1024, 64, 8
    assert moe_shapes.expert_weight_bytes(REAL) == 3 * H * I * 2 == 12582912
    assert moe_shapes.assignment_flops(REAL) == 6 * H * I
    # a decode step of 32 rows that touches 63 experts a layer: 6.3 GB
    assert 63 * L * moe_shapes.expert_weight_bytes(REAL) == pytest.approx(
        6.34e9, rel=1e-2)
    # the weights a trained token passes: eight experts, the router,
    # attention and the head (the embedding is a lookup), plus one key of
    # attention: inside the PR 26 bracket of 336-912 M
    weights = FAMILY.train_flops_per_token(REAL, 1) / 6
    assert weights == pytest.approx(
        L * (4 * H * H + 8 * 3 * H * I + H * E) + H * 50304 + L * 2 * H,
        rel=1e-6)
    assert weights == pytest.approx(641e6, rel=1e-3)
    all_stored = L * (4 * H * H + E * 3 * H * I + H * E) + 2 * H * 50304
    assert all_stored == pytest.approx(3.56e9, rel=5e-3)


def _tiny():
    cfg, _ = rehearsal.tiny_files(CELL)
    cfg["run"] = dict(cfg["run"], dtype="float32")
    return cfg


def test_the_references_routing_is_top_k_of_a_float32_softmax():
    cfg = _tiny()
    h = jax.random.normal(jax.random.PRNGKey(0), (12, cfg["hidden_size"]))
    wg = jax.random.normal(jax.random.PRNGKey(1), (
        cfg["hidden_size"], cfg["num_experts"])) / cfg["hidden_size"] ** 0.5
    got = np.asarray(FAMILY.routing(h, wg, cfg))
    p = np.asarray(jax.nn.softmax(h @ wg, axis=-1))
    k = cfg["num_experts_per_tok"]
    assert ((got > 0).sum(-1) == k).all()
    assert np.allclose(got[got > 0], p[got > 0])
    assert (got.sum(-1) < 1.0).all()                 # used as they are
    kept = np.sort(p, axis=-1)[:, -k:].sum(-1)
    assert np.allclose(got.sum(-1), kept, rtol=1e-5)
    renorm = np.asarray(FAMILY.routing(h, wg, dict(cfg, norm_topk_prob=True)))
    assert np.allclose(renorm.sum(-1), 1.0, rtol=1e-5)


@pytest.mark.parametrize("seed", [2**31 + 11, 5, 77])
def test_a_wrong_expert_layer_comes_out_as_not_correct(seed):
    """The control of the serving check at the tiny sizes: tokens of the
    reference with one expert a token fewer, and with the weights
    renormalised, are over the tolerance the configuration states; the
    reference's own tokens sit at 0."""
    cfg, _ = rehearsal.tiny_files(CELL)      # in the type it states: bf16
    ctx = harness.Context(cell=CELL, config=cfg, traffic={}, seed=seed,
                          seconds=0.0, trace=False, t_start=0.0, scratch="")
    got = serving_control.readings(ctx, prompts=[20, 70])
    assert got["tolerance"] == REAL["run"]["check"]["tolerance"]
    assert max(got["reference"]) == 0.0
    for wrong in serving_control.WRONG:
        assert max(got[wrong]) > got["tolerance"], (wrong, got)
    # the reference in e4m3 is read too.  The tolerance is set under its
    # smallest reading at the published widths on the chip (the config's
    # ``_why``); twelve tokens at these sizes are too few to hold it here
    # (0.42 / 0.067 / 0.063 on these seeds: over, by a rounding's width)
    assert all(0.0 <= g < 10.0 for g in got[serving_control.NARROWER])


# -- the readers -------------------------------------------------------------

def _kernel(name):
    return (f"%{name} = bf16[1280,1024]{{1,0:T(8,128)(2,1)}} custom-call("
            f"s32[80]{{0}} %tile_group, bf16[1280,2048]{{1,0}} %fusion.3), "
            f'custom_call_target="tpu_custom_call", operand_layout_constraints={{}}')


@pytest.mark.parametrize("name, counted", [
    ("moe_grouped_matmul.1", True), ("moe_grouped_matmul_swiglu.4", True),
    ("moe_grouped_matmul", True), ("paged_decode_attention.7", False),
    ("moe_gather.3", False), ("my_moe_grouped_matmul.1", False)])
def test_the_expert_kernels_are_counted_by_their_own_name(name, counted):
    for metric in MOE_METRICS:
        pattern = manifest.load_json("metrics", metric)["args"].get("pattern")
        if pattern is None:
            continue
        assert bool(re.search(pattern, _kernel(name))) == counted
        assert not re.search(pattern, _kernel(name).replace(
            "tpu_custom_call", "Sharding"))
    # a consumer names the kernel among its operands and is not the kernel
    consumer = ("%fusion.9 = f32[32,2048]{1,0} fusion(bf16[1280,2048]{1,0} "
                '%moe_grouped_matmul.1), kind=kLoop, custom_call_target="x"')
    assert not re.search(PATTERN, consumer)
    # and the attention metrics do not count the expert kernels
    for metric in ("paged_attn_share.batch", "paged_attn_roofline.batch"):
        other = manifest.load_json("metrics", metric)["args"]["pattern"]
        assert not re.search(other, _kernel("moe_grouped_matmul.1"))


def _trace(kernel_ms, modules):
    """One chip: ``kernel_ms`` of each expert kernel, beside another
    instruction, under the named program executions."""
    ops = [tr.Event(0.0, kernel_ms * 1e6, _kernel("moe_grouped_matmul_swiglu.1")),
           tr.Event(kernel_ms * 1e6, kernel_ms * 1e6, _kernel("moe_grouped_matmul.1")),
           tr.Event(2 * kernel_ms * 1e6, 2 * kernel_ms * 1e6,
                    "%fusion.1 = bf16[32,2048]{1,0} fusion(%p0), kind=kLoop")]
    mods = [tr.Event(i * 1e7, 1e7, name) for i, name in enumerate(modules)]
    dev = tr.DeviceTrace(ops=ops, async_ops=[], modules=mods)
    return tr.Trace(devices={0: dev}, host={}, t0_ns=0.0, t1_ns=1e9)


def _obs(**over):
    spans = ([{"name": "inference/prefill", "dur_s": 0.05, "args": {"chunks": 2}}]
             * 10 + [{"name": "inference/decode_burst", "dur_s": 0.3,
                      "args": {"burst": 8, "batch": 32}}] * 5
             + [{"name": "inference/decode_burst", "dur_s": 0.04,
                 "args": {"burst": 1, "batch": 32}}] * 10
             + [{"name": "inference/commit", "dur_s": 1e-4, "args": {}}] * 25)
    obs = {"trace": _trace(10.0, ["jit_inference_v2_prefill(123)",
                                  "jit_inference_v2_decode_burst_n_steps8(9)",
                                  "jit_inference_v2_decode_burst_n_steps1(7)",
                                  "jit_something_else(1)"]),
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
           "config": REAL, "program_spans": spans,
           # 60 steps in the window, 63 experts a layer and step
           "program_counters": {"inference/moe/experts_active": 60 * 8 * 63.0,
                                "inference/moe/assignments": 60 * 256.0}}
    obs.update(over)
    return obs


def _read(metric, obs):
    spec = manifest.load_json("metrics", metric)
    return manifest.load_module("readers", spec["reader"]).read(
        obs, spec.get("args", {}))


def test_the_roofline_joins_the_counter_to_the_trace_by_steps():
    # 10 traced steps (1 + 8 + 1) of the window's 60: a sixth of the
    # counter, 8 x 63 experts a step, against 20 ms of the two kernels
    want_s = 10 * 8 * 63 * 12582912 / 819e9
    assert _read("moe_expert_roofline.batch", _obs()) == pytest.approx(
        100.0 * want_s / 0.020)
    # compute-bound only where rows are many: the larger bound is taken
    crowded = _obs()
    crowded["program_counters"]["inference/moe/assignments"] = 60 * 4e6
    flops_s = 10 * 4e6 * 8 * 6 * 2048 * 1024 / 197e12
    assert flops_s > want_s
    assert _read("moe_expert_roofline.batch", crowded) == pytest.approx(
        100.0 * flops_s / 0.020)


def test_the_share_and_the_experts_a_call():
    obs = _obs()
    assert _read("moe_expert_share.batch", obs) == pytest.approx(50.0)
    assert _read("moe_experts_active_per_call.batch", obs) == pytest.approx(
        60 * 8 * 63.0 / 25)


@pytest.mark.parametrize("missing", ["trace", "program_counters", "peaks",
                                     "kernel", "spans"])
def test_a_program_without_the_layer_gives_nothing_to_read(missing):
    """The parent's program has no such kernel, counter or span: every
    reader returns nothing and none raises."""
    obs = _obs()
    if missing == "kernel":
        obs["trace"].devices[0].ops[:] = obs["trace"].devices[0].ops[2:]
    elif missing == "spans":
        obs["program_spans"] = []
    elif missing == "program_counters":
        obs["program_counters"] = {"inference/decode_tokens": 5.0}
    else:
        obs[missing] = None
    assert _read("moe_expert_roofline.batch", obs) is None
    if missing in ("trace", "kernel"):
        assert _read("moe_expert_share.batch", obs) is None
    if missing in ("program_counters", "spans"):
        assert _read("moe_experts_active_per_call.batch", obs) is None


def test_the_rehearsal_reads_the_programs_counters(tmp_path):
    """The cell at its tiny sizes, traced: the program's counters arrive
    through the telemetry registry, every assignment is computed, and the
    device metrics stay out of a CPU line."""
    line = rehearsal.rehearse(CELL["name"], seed=2**31 + 13, seconds=0.6,
                              trace=True, tmp_path=tmp_path)
    assert line["correct"] and line["failed"] == 0
    counters = line["_obs"]["program_counters"]
    cfg = line["_obs"]["config"]
    assert counters["inference/moe/assignments"] > 0
    per_call = line["metrics"]["moe_experts_active_per_call.batch"]["value"]
    # a call runs at least one step of every layer, and no more groups
    # than experts a layer and step
    assert cfg["num_hidden_layers"] <= per_call \
        <= 8 * cfg["num_hidden_layers"] * cfg["num_experts"]
    assert "moe_expert_roofline.batch" not in line["metrics"]
    assert "moe_expert_share.batch" not in line["metrics"]
    gauges = {m.name: m.value for m in
              __import__("deepspeed_tpu").telemetry.get_telemetry()
              .registry.metrics().values()}
    assert gauges["inference/moe/drop_rate"] == 0.0
