"""What the state-space configuration brings to the benchmark: its file
against the catalog's published keys, the arithmetic of its cut, the
recurrence's bytes and operations by hand, its roofline reader on a trace
and counters made by hand, where its four metrics stand, and at the tiny
size on the CPU: the engine against the plain reference (chunked prefill,
bursts, a reused slot, a chunk beside decode rows), the chunk form against
the recurrence, the reference against the published torch implementation,
and the controls of its check."""

import json
import pathlib
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest, ssm_shapes
from perfbench import trace_reduce as tr

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import control  # noqa: E402
import falcon_h1_control  # noqa: E402
import rehearsal  # noqa: E402
import serving_control  # noqa: E402

BENCH = manifest.load_benchmark()
CELL = manifest.named(BENCH["workloads"], "serve-chat-falcon-h1-l6",
                      "workload")
ENTRY = manifest.named(BENCH["configs"], CELL["config"], "configuration")
REAL = manifest.load_json("configs", CELL["config"])
FAMILY = manifest.load_module("models", REAL["model_type"])
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")

#: the source's keys as the catalog gives them (``config`` of the row
#: ``Falcon-H1-34B-Instruct``): written out so that the test holds where
#: the catalog is not installed
PUBLISHED = {
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804, "lm_head_multiplier": 0.0078125,
    "mamba_chunk_size": 128, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 128, "mamba_d_ssm": 4096, "mamba_d_state": 256,
    "mamba_expand": 2, "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "model_type": "falcon_h1", "num_attention_heads": 20,
    "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 100000000000,
    "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845, "tie_word_embeddings": False,
    "vocab_size": 261120}
REDUCED = {"num_hidden_layers": 6, "max_position_embeddings": 4096}
NEW = ["ssm_share.batch", "ssm_state_roofline.batch",
       "ssm_scan_roofline.batch", "ssm_state_gb_per_call.batch"]


# -- the configuration's file and the cell ----------------------------------

def test_the_file_keeps_every_published_key_but_the_reduced():
    assert set(ENTRY["reduced"]) == set(REAL["reduced"]) == set(REDUCED)
    for key, value in PUBLISHED.items():
        assert REAL[key] == REDUCED.get(key, value), key
    assert REAL["source"] == ENTRY["source"]
    assert REAL["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert "6 of the 72 layers" in REAL["deployment"] \
        and "34%" in REAL["deployment"]
    for said in ("float32", "A_log", "dt_bias", "rotate-half",
                 "time_step_limit", "folded"):
        assert any(said in line for line in REAL["assumed"]), said
    # no width, no head count and not the vocabulary is cut; the floor of
    # four layers of the one kind there is
    assert REAL["num_hidden_layers"] >= 4


@pytest.mark.parametrize("key", ["ssm_state_dtype", "control_state_held_in"])
def test_no_key_of_a_configuration_lowers_the_programs_state(key):
    """The state's type is the program's constant: the key the control of
    the check puts into the REFERENCE's configuration (and any other) is
    nothing ``build`` reads, so a file cannot halve the state's traffic
    and have the reference follow it down."""
    assert key not in REAL
    for cfg in (REAL, dict(REAL, **{key: "bfloat16"})):
        parts = {name: (shape, dtype) for name, shape, dtype
                 in FAMILY.build(cfg).state_parts()}
        assert parts["ssm"] == ((32, 256, 128), jnp.float32)


@pytest.mark.skipif(not CATALOG.is_file(), reason="no catalog installed")
def test_the_written_out_keys_are_the_catalogs():
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "Falcon-H1-34B-Instruct")
    assert row["source_url"] == REAL["source"]
    assert row["config"] == PUBLISHED


def test_the_cells_traffic_is_the_issues():
    traffic = manifest.load_json("traffic", CELL["traffic"])
    assert (traffic["generator"], traffic["loop"], traffic["klass"]) == (
        "requests", "closed", "batch")
    assert (traffic["clients"], traffic["requests_per_client"],
            traffic["open_when_live_streams"]) == (192, 8, 96)
    assert traffic["prompt_tokens"] == {"median": 512, "sigma": 0.6,
                                        "min": 64, "max": 2048}
    assert traffic["new_tokens"] == {"median": 352, "sigma": 0.45,
                                     "min": 96, "max": 1024}
    assert traffic["shared_prefix_share"] == 0.0
    others = {manifest.load_json("traffic", w["traffic"]).get("order_seed")
              for w in BENCH["workloads"] if w["name"] != CELL["name"]}
    assert traffic["order_seed"] not in others
    run = REAL["run"]
    assert traffic["clients"] == 2 * run["max_batch_slots"] == 192
    longest = traffic["prompt_tokens"]["max"] + traffic["new_tokens"]["max"]
    assert longest == 3072 <= REAL["max_position_embeddings"]
    assert run["max_outstanding_tokens"] >= traffic["clients"] * longest
    # the check's prompts: under a chunk, several chunks, and many chunks
    # with a partial last one, all inside the traffic's own shapes
    check = run["check"]
    chunk = run["program_defaults_not_passed"]["prefill_chunk"]
    assert min(check["prompt_tokens"]) < chunk
    assert max(check["prompt_tokens"]) % chunk \
        and max(check["prompt_tokens"]) <= traffic["prompt_tokens"]["max"]
    assert check["new_tokens"] >= 36
    assert CELL["chips"] == 1 and run["max_batch_slots"] >= 64


def test_weights_and_bytes_of_the_cut():
    H, V, I, L = 5120, 261120, 21504, 6
    attention = H * 2560 + 2 * H * 512 + 2560 * H
    mixer = H * 9248 + 4096 * H + 5120 * 4
    mlp = 3 * H * I
    assert (attention, mlp) == (31_457_280, 330_301_440)
    assert mixer == pytest.approx(68.35e6, rel=1e-3)
    assert FAMILY.layer_weights(REAL) == attention + mixer + mlp
    assert attention + mixer + mlp == pytest.approx(430.1e6, rel=1e-3)
    held = L * (attention + mixer + mlp) + 2 * V * H
    assert 2 * held == pytest.approx(10.51e9, rel=1e-3)
    # the program's weight tree is that many numbers (plus the vectors:
    # norms, the conv's bias, dt_bias, A_log, D)
    model = FAMILY.build(REAL)
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    stored = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    vectors = L * (2 * H + 5120 + 3 * 32 + 4096) + H
    assert stored == held + vectors
    # a trained token, inside the manifest test's bracket
    token = FAMILY.train_flops_per_token(REAL, 1) / 6
    assert L * 272.6e6 < token < L * 571.5e6 + 2.68e9
    assert token == pytest.approx(
        L * (attention + mixer + mlp) + V * H
        + L * (4 * 20 * 128 + 6 * 32 * 128 * 256) / 2, rel=1e-6)
    # the pools: paged KV and, beside it, a slot's state a layer
    run = REAL["run"]
    tokens = run["kv_num_blocks"] * run["kv_block_size"]
    assert tokens * L * 2 * 4 * 128 * 2 == pytest.approx(1.61e9, rel=2e-3)
    from deepspeed_tpu.inference.v2 import KVCacheConfig
    from deepspeed_tpu.inference.v2.adapters import make_adapter
    from deepspeed_tpu.inference.v2.kv_cache import init_kv_pool

    slots = run["max_batch_slots"]
    pools = jax.eval_shape(lambda: init_kv_pool(
        make_adapter(model),
        KVCacheConfig(num_blocks=run["kv_num_blocks"],
                      block_size=run["kv_block_size"]
                      ).with_state(make_adapter(model).state_kinds, slots)))
    assert sorted(pools) == ["kv", "ssm"]
    assert pools["kv"]["k"].shape == (6, 8192, 16, 4, 128)
    assert pools["ssm"]["ssm"].shape == (6, slots + 1, 32, 256, 128)
    assert pools["ssm"]["ssm"].dtype == jnp.float32
    assert pools["ssm"]["conv"].shape == (6, slots + 1, 3 * 5120)
    assert pools["ssm"]["conv"].dtype == jnp.bfloat16
    state = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                for a in pools["ssm"].values())
    assert state == pytest.approx(2.46e9, rel=2e-3) and state >= 1.6e9
    assert ssm_shapes.state_bytes(REAL) == 32 * 128 * 256 * 4 == 4_194_304


def test_the_recurrences_bytes_and_operations_by_hand():
    assert ssm_shapes.update_bytes(REAL) == 2 * 4_194_304
    # a token of a 128-token block: two groups' C·B over 64.5 tokens, and a
    # head's weighted sum, read-out and state increment
    want = 2 * 2 * 64.5 * 256 + 32 * (2 * 64.5 * 128 + 4 * 128 * 256)
    assert ssm_shapes.chunk_flops_per_token(REAL, 128) == want == 4_788_736
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    # a decode step of 96 live slots: 4.83 GB of state, 5.9 ms of HBM
    step = ssm_shapes.update_seconds(96, REAL, peaks)
    assert step == pytest.approx(96 * 6 * 8_388_608 / 819e9)
    assert step == pytest.approx(5.9e-3, rel=0.01)
    assert ssm_shapes.chunk_seconds(256, REAL, peaks, 128) \
        == pytest.approx(256 * 6 * want / 197e12)


# -- the four metrics that wait ----------------------------------------------

def _fusion(name, shape):
    return f"%{name} = {shape} fusion(f32[6,97,32,256,128]{{4,3,2,1,0}} %p)"


def _obs(**over):
    ops = [tr.Event(0.0, 6e6, _fusion("update.1", "f32[6,97,32,256,128]")),
           tr.Event(6e6, 2e6, _fusion("readout.1", "f32[96,32,128]")),
           tr.Event(8e6, 1e6, "%scan.1 = f32[2,2,16,128,128]{4,3,2,1,0} "
                              "fusion(%a, %b)"),
           tr.Event(9e6, 11e6, "%fusion.1 = bf16[96,5120]{1,0} fusion(%p0)")]
    mods = [tr.Event(i * 1e7, 1e7, name) for i, name in enumerate(
        ["jit_inference_v2_decode_burst_n_steps8(9)",
         "jit_inference_v2_decode_burst_n_steps1(7)"])]
    spans = ([{"name": "inference/decode_burst", "dur_s": 0.1,
               "args": {"burst": 8, "batch": 96}}] * 10
             + [{"name": "inference/decode_burst", "dur_s": 0.02,
                 "args": {"burst": 1, "batch": 96}}] * 10
             + [{"name": "inference/commit", "dur_s": 1e-4, "args": {}}] * 20)
    obs = {"trace": tr.Trace(devices={0: tr.DeviceTrace(
               ops=ops, async_ops=[], modules=mods)}, host={}, t0_ns=0.0,
               t1_ns=1e9),
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
           "config": REAL, "program_spans": spans,
           # the window's 90 decode steps of 90 live rows; ten calls with
           # 150 prompt tokens each; 4.87 GB of state a step
           "program_counters": {
               "inference/ssm/decode_rows": 90 * 90.0,
               "inference/ssm/chunk_tokens": 10 * 150.0,
               "inference/ssm/state_bytes_read": 90 * 96 * 6 * 4_225_024.0}}
    obs.update(over)
    return obs


def _read(metric, obs, **args):
    spec = manifest.load_json("metrics", metric)
    return manifest.load_module("readers", spec["reader"]).read(
        obs, dict(spec.get("args", {}), **args))


def test_the_rooflines_join_the_counters_to_the_trace_by_calls():
    # 9 traced decode steps of the window's 90: a tenth of the rows, their
    # bytes against the 8 ms of the two instructions that move the state
    update = r"f32\[6,97,32,256,128\]"
    least = ssm_shapes.update_seconds(0.1 * 90 * 90, REAL, _obs()["peaks"])
    assert _read("ssm_state_roofline.batch", _obs(), pattern=update) \
        == pytest.approx(100.0 * least / 0.008)
    # one traced call with chunks of the window's ten: 150 tokens against
    # the 1 ms of the block's own instruction
    least = ssm_shapes.chunk_seconds(150.0, REAL, _obs()["peaks"], 128)
    assert _read("ssm_scan_roofline.batch", _obs(), pattern=r"^%scan") \
        == pytest.approx(100.0 * least / 0.001)
    assert _read("ssm_state_gb_per_call.batch", _obs()) == pytest.approx(
        90 * 96 * 6 * 4_225_024 * 1e-9 / 20)
    assert _read("ssm_share.batch", _obs(), pattern=update + r"|^%scan") \
        == pytest.approx(100.0 * 9e6 / 20e6)


@pytest.mark.parametrize("missing", ["trace", "counters", "peaks", "ops",
                                     "spans"])
def test_a_program_without_the_state_gives_nothing_to_read(missing):
    """The parent's program has no such counter and no such instruction:
    the readers return nothing and do not raise."""
    obs = _obs()
    if missing == "ops":
        obs["trace"].devices[0].ops[:] = obs["trace"].devices[0].ops[3:]
    elif missing == "spans":
        obs["program_spans"] = []
    elif missing == "counters":
        obs["program_counters"] = {"inference/decode_tokens": 5.0}
    else:
        obs[missing] = None
    pattern = r"f32\[6,97,32,256,128\]"
    for metric in ("ssm_state_roofline.batch", "ssm_scan_roofline.batch"):
        assert _read(metric, obs, pattern=pattern) is None
    if missing in ("counters", "spans"):
        assert _read("ssm_state_gb_per_call.batch", obs) is None
    if missing in ("trace", "ops"):
        assert _read("ssm_share.batch", obs, pattern=pattern) is None


@pytest.mark.parametrize("metric", NEW)
def test_a_new_metric_is_a_file_and_an_entry_that_agree(metric):
    """The four metrics' files, readers and shapes came with the cell;
    their ``per_layer`` entries waited until PR 51 unpinned the list
    (``test_perfbench_call_readers.py`` held PR 38's twelve to its END):
    they list this cell alone and lie in one block."""
    spec = manifest.load_json("metrics", metric)
    assert spec["name"] == metric and spec["moves"] == "serve_tokens_per_s"
    assert (manifest.BENCH_DIR / "readers" / f"{spec['reader']}.py").is_file()
    assert spec["unit"] == ("GB/call" if "gb_per_call" in metric else "%")
    assert spec["layer"] == {"ssm_share.batch": "State-space mixer",
                             "ssm_state_gb_per_call.batch": "v2 engine"
                             }.get(metric, "Kernels")
    assert spec["source"] == ("program_counter" if "gb_per_call" in metric
                              else "device_trace")
    if "pattern" in spec["args"]:
        import re

        re.compile(spec["args"]["pattern"])
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
    assert listed == {name + ".batch" for name in (
        "tokens_per_decode_call", "decode_step_ms_p50", "paged_attn_share",
        "paged_attn_roofline", "device_idle_share", "peak_hbm_gb",
        "queue_wait_ms_p50", "frontend_host_ms_p50", "engine_host_ms_p50",
        "decode_dispatch_ms_p50", "decode_device_step_ms_p50",
        "idle_in_pump_share", "chunk_tokens_per_decode_call",
        "calls_ahead_share")} | set(NEW)
    assert {m["name"] for m in manifest.cell_metrics(
        BENCH, CELL["name"], "end_to_end")} == {"serve_tokens_per_s",
                                                "setup_s"}


# -- at the tiny size on the CPU ----------------------------------------------

def _tiny(**over):
    cfg, _ = rehearsal.tiny_files(CELL)
    cfg["run"] = dict(cfg["run"], dtype="float32")
    return dict(cfg, **over)


def _weights(cfg, seed=3):
    """Seeded random weights, every vector (``dt_bias``, ``A_log``, ``D``,
    the conv's bias, the norms) away from its initial constant, as the
    model's own ``init_params`` draws them."""
    return FAMILY.build(cfg).init_params(jax.random.PRNGKey(seed))


def _engine(cfg, weights, slots=3, chunk=16, page=8, burst=4):
    from deepspeed_tpu.inference.v2 import KVCacheConfig, build_engine_v2

    return build_engine_v2(
        FAMILY.build(cfg), weights,
        cache_config=KVCacheConfig(num_blocks=96, block_size=page,
                                   max_seq_len=128),
        max_batch_slots=slots, prefill_chunk=chunk, prefill_batch=2,
        decode_burst=burst)


def _gap(cfg, weights, prompt, tokens):
    """``runners/serve.py:_logit_gap`` by hand: how far under the
    reference's best logit the served tokens sit, at worst."""
    ids = jnp.asarray(np.concatenate([prompt, tokens[:-1]]), jnp.int32)
    logits = FAMILY.forward(weights, cfg, ids[None])[0][len(prompt) - 1:]
    chosen = logits[jnp.arange(len(tokens)), jnp.asarray(tokens)]
    return float(jnp.max(jnp.max(logits, axis=1) - chosen))


#: float32 on both sides: the engine's chunk form sums a block's products
#: in another order than the reference's token-by-token recurrence, and
#: logits of ~0.03 agree to ~1e-7; a served token may sit this far under
#: the reference's best before it counts as another token
TIE = 1e-5


@pytest.fixture(scope="module")
def tiny():
    cfg = _tiny()
    return cfg, _weights(cfg)


@pytest.mark.parametrize("length", [5, 15, 16, 17, 24, 33, 50])
def test_the_engine_agrees_with_the_reference_across_chunk_and_page_edges(
        tiny, length):
    """(a) Chunked prefill over several chunks of 16 with a partial last
    one, then decoding through bursts of 4, against the reference's full
    forward pass: prompt lengths on both sides of a chunk's edge (16) and
    a page's (8, 16, 24, 32)."""
    cfg, weights = tiny
    prompt = np.random.default_rng(length).integers(
        0, cfg["vocab_size"], size=length, dtype=np.int32)
    tokens = _engine(cfg, weights).generate([prompt.tolist()],
                                            max_new_tokens=11)[0]
    assert len(tokens) == 11
    assert _gap(cfg, weights, prompt, tokens) <= TIE


def _drive(engine, requests):
    """``requests``: (prompt, budget) pairs, admitted in order; steps the
    engine to the end and returns (each one's tokens, the slot each sat
    in, the (chunks, decode rows) of every call)."""
    calls, real = [], engine._dispatch

    def watched(tel, chunks, decode, *rest):
        calls.append((len(chunks), len(decode)))
        return real(tel, chunks, decode, *rest)

    engine._dispatch = watched
    reqs = [engine.put(p.tolist(), n) for p, n in requests]
    seats = {}
    while engine.scheduler.has_work:
        engine.step()
        for i, r in enumerate(reqs):
            if r.slot >= 0:
                seats[i] = r.slot
    return [r.generated for r in reqs], seats, calls


def test_a_reused_slot_leaks_no_state_and_a_chunk_rides_beside_decode_rows(
        tiny):
    """(b) Two sequences in two slots; the first finishes and the third
    takes its slot while the second is still there: the third's tokens are
    those it gets alone.  (c) Meanwhile calls carry a chunk row and decode
    rows together, and every sequence still gets its own tokens."""
    cfg, weights = tiny
    rng = np.random.default_rng(7)
    a, b, c = (rng.integers(0, cfg["vocab_size"], size=n, dtype=np.int32)
               for n in (9, 60, 37))
    served, seats, calls = _drive(_engine(cfg, weights, slots=2),
                                  [(a, 3), (b, 30), (c, 12)])
    assert seats[2] == seats[0] != seats[1]
    assert any(chunks and rows for chunks, rows in calls)
    for prompt, tokens, budget in ((a, served[0], 3), (b, served[1], 30),
                                   (c, served[2], 12)):
        alone = _engine(cfg, weights, slots=2).generate(
            [prompt.tolist()], max_new_tokens=budget)[0]
        assert tokens == alone
        assert _gap(cfg, weights, prompt, tokens) <= TIE


def test_the_reference_tells_its_published_keys_apart():
    """(d) Same weights and ids: one published key that changes the
    mathematics, flipped, moves the logits (the groups' count is flipped
    with the state's width, so that the same weights fit: four groups of
    8 in place of two of 16)."""
    cfg = _tiny()
    weights = _weights(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(4), (1, 40), 0,
                             cfg["vocab_size"])
    base = FAMILY.forward(weights, cfg, ids)
    scale = float(abs(base).max())
    dropped = list(cfg["ssm_multipliers"])
    dropped[2] = 1.0
    for over in ({"mamba_norm_before_gate": True}, {"key_multiplier": 1.0},
                 {"ssm_multipliers": dropped},
                 {"mamba_n_groups": 4, "mamba_d_state": 8},
                 {"mamba_conv_bias": False}, {"ssm_in_multiplier": 1.0},
                 {"attention_out_multiplier": 1.0},
                 {"mlp_multipliers": [1.0, cfg["mlp_multipliers"][1]]},
                 {"lm_head_multiplier": 1.0}):
        other = FAMILY.forward(weights, dict(cfg, **over), ids)
        assert float(abs(base - other).max()) > 1e-3 * scale, over
    # the rotary base moves them too, but little: under key_multiplier
    # 0.011 the scores are near 0 and attention is close to a plain mean
    other = FAMILY.forward(weights, dict(cfg, rope_theta=100.0), ids)
    assert 1e-5 * scale < float(abs(base - other).max()) < 1e-3 * scale


@pytest.mark.parametrize("valid, block", [(13, 13), (13, 16), (1, 5)])
def test_the_chunk_form_equals_the_recurrence(valid, block):
    """(e) One layer's mixer: the program's chunk form over a block of 13
    tokens (alone, and padded to 16) FROM A CARRIED-IN STATE, the state
    its own one-token form left after 7 tokens, against the reference's
    token-by-token recurrence over all 20 from zero; and the state going
    out against the one-token form's over the same tokens."""
    cfg = _tiny()
    model = FAMILY.build(cfg)
    weights = _weights(cfg, seed=11)
    lp = jax.tree.map(lambda w: w[1], weights["layers"])
    H, before = cfg["hidden_size"], 7
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(12), (before + valid, H))
    norm = lambda v: v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True)
                                       + cfg["rms_norm_eps"]) * lp["attn_norm"]
    with jax.default_matmul_precision("highest"):
        want = FAMILY.mixer(norm(x), lp["ssm"], cfg) \
            * cfg["ssm_out_multiplier"]
    state = model.zero_state(1)
    for t in range(before):                 # token by token: non-zero state
        out, state = model.mix(lp, x[t:t + 1], state, 1, jnp.ones((1,), int))
        np.testing.assert_allclose(out[0], want[t], atol=2e-6)
    assert float(abs(state["ssm"]).max()) > 1e-3
    rows = jnp.pad(x[before:], ((0, block - valid), (0, 0)), constant_values=3.)
    out, after = model.mix(lp, rows, state, block, jnp.full((1,), valid))
    np.testing.assert_allclose(out[:valid], want[before:], atol=2e-6)
    for t in range(before, before + valid):
        _, state = model.mix(lp, x[t:t + 1], state, 1, jnp.ones((1,), int))
    for part in ("ssm", "conv"):            # padding moved nothing
        np.testing.assert_allclose(after[part], state[part], atol=2e-6)
    # a row that is no sequence's leaves its state as it was
    _, same = model.mix(lp, rows[:1], state, 1, jnp.zeros((1,), int))
    for part in ("ssm", "conv"):
        np.testing.assert_array_equal(same[part], state[part])


def test_the_reference_is_the_published_torch_implementation():
    """(f) ``transformers``' own ``FalconH1ForCausalLM`` (torch, CPU, its
    ``torch_forward``) at the tiny size, every parameter drawn at random
    (``dt_bias``, ``A_log``, ``D``, the conv's bias and the norms among
    them), its weights read into this repo's tree by
    ``models/hf_import.py``: the reference's logits are its logits, so the
    reference is tied to the published implementation and not to a reading
    of it.  Both float32: they agree to ~1e-8 on logits of ~0.03."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from deepspeed_tpu.models import hf_import

    cfg = dict(_tiny(), mamba_chunk_size=16)
    own = ("source", "published", "reduced", "assumed", "deployment", "run",
           "model_type")
    hf_cfg = transformers.FalconH1Config(
        **{k: v for k, v in cfg.items() if k not in own})
    hf_cfg._attn_implementation = "eager"
    torch.manual_seed(0)
    model = transformers.FalconH1ForCausalLM(hf_cfg).eval()
    with torch.no_grad():
        for name, p in model.named_parameters():
            if p.dim() == 1:        # norms, biases, dt_bias, A_log, D
                p.add_(0.2 * torch.randn_like(p))
            else:
                p.copy_(torch.randn_like(p) / np.sqrt(p.shape[-1]))
    weights = hf_import.params_from_hf_falcon_h1_state_dict(
        model.state_dict(),
        hf_import.config_from_hf_falcon_h1(hf_cfg, dtype=jnp.float32))
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"],
                                            size=(2, 37))
    with torch.no_grad():
        want = model(torch.tensor(ids), logits_to_keep=0).logits.numpy()
    got = np.asarray(FAMILY.forward(weights, cfg, jnp.asarray(ids)))
    assert float(abs(want).max()) > 0.01
    np.testing.assert_allclose(got, want, atol=1e-6)
    # and so is the program's own forward pass over whole sequences
    served = FAMILY.build(cfg).forward(weights, jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(served), want, atol=1e-6)


@pytest.mark.parametrize("wrong", ["narrower_precision", "state_in_bfloat16",
                                   "multiplier_dropped"])
def test_the_controls_of_the_check_at_the_tiny_size(wrong):
    """The check's own gap for greedy tokens of the reference itself is 0;
    for the reference with every product narrowed to e4m3 it is not, as on
    the chip it has to be over the cell's limit.  The multiplier on ``C``
    read as 1 moves the logits by a hundredth of their scale (on the chip,
    over 261,120 words, that picks other tokens).  The state rounded to
    bfloat16 after every token IS another function but moves them 5e-5 of
    their scale, under what one rounding of an activation to bfloat16
    does: the comparison of tokens does not see it, here or on the chip
    (PERF.md §7)."""
    from perfbench import harness

    cfg = rehearsal.tiny_files(CELL)[0]
    ctx = harness.Context(cell=CELL, config=cfg, traffic={}, seed=5,
                          seconds=0.0, trace=False, t_start=0.0, scratch="")
    weights = jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                           _weights(cfg, seed=5))
    runner = manifest.load_module("runners", cfg["run"]["runner"])
    prompt = np.random.default_rng(6).integers(0, cfg["vocab_size"], size=48,
                                               dtype=np.int32)

    def gap(served_by, bits=None):
        tokens = serving_control.greedy_tokens(FAMILY, weights, served_by,
                                               prompt, 24, bits)
        return runner._logit_gap(
            ctx, types.SimpleNamespace(params=weights),
            types.SimpleNamespace(
                request=types.SimpleNamespace(prompt=prompt), tokens=tokens))

    assert gap(cfg) == 0.0
    if wrong == "narrower_precision":
        assert gap(cfg, control.NARROWER["bfloat16"]) > 1e-4
        return
    # over 256 words the best logit leads by a tenth of the logits' scale
    # and 24 tokens keep their argmax under either; what is held here is
    # that each is another function, and by how much
    ids = jnp.asarray(prompt)[None]
    base = FAMILY.forward(weights, cfg, ids)
    moved = float(abs(base - FAMILY.forward(
        weights, falcon_h1_control.WRONG[wrong](cfg), ids)).max())
    scale = float(abs(base).max())
    if wrong == "multiplier_dropped":
        assert moved > 3e-3 * scale
    else:
        assert 0.0 < moved < 3e-4 * scale
