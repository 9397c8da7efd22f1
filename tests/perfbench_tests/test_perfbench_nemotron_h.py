"""The ``nemotron_h`` family: a model whose layers are ONE part alone (a
Mamba-2 mixer, or attention, or LatentMoE experts).  The configuration's
file against the catalog's row; the program's ``forward`` and the engine
(prefill in chunks, steps that carry chunks, bursts) against the plain
float32 reference on the LOGITS they sample from; the four shares of an
expert layer against the uncut layer; every wrong variant the family
invites fails as it must; the new readers and shapes modules on a synthetic
trace.  Tiny widths, seeded, float32 unless said."""

import json
import pathlib
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import KVCacheConfig, build_engine_v2
from deepspeed_tpu.inference.v2 import engine_v2 as ev2
from perfbench import manifest
from perfbench import run as bench_run
from perfbench import latent_moe_shapes, mixer_shapes, trace_reduce

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import nemotron_h_control  # noqa: E402
import serving_control     # noqa: E402

BENCH = manifest.load_benchmark()
CELL = manifest.named(BENCH["workloads"],
                      "serve-longreason-nemotron3-super-l11", "workload")
ENTRY = manifest.named(BENCH["configs"], CELL["config"], "configuration")
REAL = manifest.load_json("configs", CELL["config"])
FAMILY = manifest.load_module("models", REAL["model_type"])
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")
REDUCED = {"num_hidden_layers": 11, "hybrid_override_pattern": "MEMEMEM*EME",
           "n_routed_experts": 128, "vocab_size": 32768,
           "max_position_embeddings": 16384}
NEW = ["latent_moe_roofline.batch", "mixer_state_roofline.batch",
       "mixer_scan_roofline.batch", "mixer_share.batch"]

#: two periods of mixer, experts, attention, experts; share 1 of 4 over 16
#: experts; two heads of 64 a lane row of the held state
TINY = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=8,
    hybrid_override_pattern="ME*EME*E", num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, mamba_num_heads=4, mamba_head_dim=64,
    ssm_state_size=16, n_groups=2, conv_kernel=4, chunk_size=16,
    moe_intermediate_size=48, moe_latent_size=32,
    moe_shared_expert_intermediate_size=96, n_routed_experts=4,
    published={"n_routed_experts": 16}, expert_rank=1, num_experts_per_tok=3,
    norm_topk_prob=True, routed_scaling_factor=5, n_shared_experts=1,
    norm_eps=1e-5, max_position_embeddings=256, run={"dtype": "float32"})
PAGE, CHUNK = 4, 8
PROMPT, NEW_TOKENS, OTHER = 43, 24, 21


def _weights(cfg=TINY, seed=7):
    """The program's seeded weights with a routed expert's down projection
    back at its full scale (the program draws it ``top_k / 2`` times
    smaller for the chip's check; here every routed expert must be
    seen)."""
    params = FAMILY.build(cfg).init_params(jax.random.PRNGKey(seed))
    params["moe"]["w_down"] = params["moe"]["w_down"] \
        * cfg["num_experts_per_tok"] / 2
    return params


# -- the configuration's file and the cell ----------------------------------

@pytest.mark.skipif(not CATALOG.is_file(), reason="no catalog installed")
def test_the_file_keeps_every_published_key_but_the_reduced():
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    assert row["source_url"] == REAL["source"] == ENTRY["source"]
    assert set(ENTRY["reduced"]) == set(REAL["reduced"]) == set(REDUCED)
    for key, value in row["config"].items():
        assert REAL[key] == REDUCED.get(key, value), key
    assert REAL["published"] == {k: row["config"][k] for k in REDUCED}
    assert row["config"]["hybrid_override_pattern"].startswith(
        REAL["hybrid_override_pattern"])


def test_the_cut_is_the_issues():
    assert (REAL["expert_rank"], REAL["num_experts_per_tok"]) == (0, 22)
    assert "4 chips share each layer" in REAL["deployment"]
    for said in ("rotary", "float32", "A_log", "dt_bias", "choice bias",
                 "11 times smaller", "multi-token-prediction",
                 "time_step_limit"):
        assert any(said in line for line in REAL["assumed"]), said
    run = REAL["run"]
    assert (run["max_batch_slots"], run["kv_block_size"],
            run["kv_num_blocks"]) == (128, 128, 6144)
    assert set(run["program_defaults_not_passed"]) == {
        "_note", "prefill_chunk", "prefill_batch", "decode_burst"}
    traffic = manifest.load_json("traffic", CELL["traffic"])
    # the latent cell's traffic, key for key, under an order_seed of its
    # own: ``test_perfbench_pangu.py`` holds the other to its cell alone
    assert CELL["traffic"] == "longreason-closed-loop-b" and CELL["chips"] == 1
    theirs = manifest.load_json("traffic", "longreason-closed-loop")
    assert {k: v for k, v in traffic.items()
            if k not in ("order_seed", "_why")} == {
        k: v for k, v in theirs.items() if k not in ("order_seed", "_why")}
    assert traffic["order_seed"] != theirs["order_seed"]
    assert traffic["clients"] == 2 * run["max_batch_slots"]
    # no check prompt longer than the traffic's longest: no program is
    # compiled that the window does not run
    check = run["check"]
    assert max(check["prompt_tokens"]) <= traffic["prompt_tokens"]["max"]
    assert min(check["prompt_tokens"]) < 128 < sorted(
        check["prompt_tokens"])[1]
    assert check["new_tokens"] == 36
    # what is resident, reckoned from the file's keys: the issue's 12.85 GB
    model = FAMILY.build(REAL)
    weights = sum(int(np.prod(a.shape)) * 2 for a in jax.tree.leaves(
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0))))
    state = 129 * 5 * sum(int(np.prod(shape)) * jnp.dtype(dt).itemsize
                          for _, shape, dt in model.state_parts())
    kv = 6144 * 128 * 2 * 128 * 2 * 2
    assert 12.8e9 < weights + state + kv < 12.9e9
    assert 9.29e9 < weights < 9.31e9 and 2.74e9 < state < 2.75e9


def test_the_cell_reports_its_own_metrics_and_not_the_other_families():
    reported = {m["name"] for m in manifest.cell_metrics(
        BENCH, CELL["name"], "per_layer")}
    assert set(NEW) <= reported
    assert {"moe_expert_share.batch", "moe_experts_active_per_call.batch",
            "peak_hbm_gb.batch", "paged_attn_share.batch"} <= reported
    # each of these would read 2 to 11 times too high, or find no key
    assert not reported & {
        "moe_expert_roofline.batch", "ssm_state_roofline.batch",
        "ssm_scan_roofline.batch", "ssm_share.batch",
        "paged_attn_roofline.batch"}
    # two that the issue asked for and that tests of the families that
    # brought them hold to their own cell alone (files this PR may not
    # edit): a `benchmark` PR's
    assert not reported & {"moe_local_assignments_per_call.batch",
                           "ssm_state_gb_per_call.batch"}
    for name in NEW:
        entry = manifest.named(BENCH["per_layer"], name, "metric")
        assert entry["workloads"] == [CELL["name"]]
    assert "serve_tokens_per_s" in {m["name"] for m in manifest.cell_metrics(
        BENCH, CELL["name"], "end_to_end")}


@pytest.mark.parametrize("key", ["ssm_state_dtype", "control_state_held_in",
                                 "control_state_dropped"])
def test_no_key_of_a_configuration_reaches_the_programs_state(key):
    assert key not in REAL
    for cfg in (REAL, dict(REAL, **{key: "bfloat16"})):
        parts = {name: (shape, dtype) for name, shape, dtype
                 in FAMILY.build(cfg).state_parts()}
        # two heads of 64 a lane row, float32
        assert parts["ssm"] == ((64, 128, 128), jnp.float32)


def test_a_trained_tokens_operations_are_the_parts_of_the_layers_run():
    weights = FAMILY.part_weights(REAL)
    assert weights["M"] == 109_576_192 + 10240 * 4
    assert weights["*"] == 35_651_584
    assert weights["E"] == 4096 * 512 + 2 * 4096 * 1024 \
        + 2 * 4096 * 5376 + 22 * 5_505_024
    n = FAMILY.train_flops_per_token(REAL, 512) / 6
    # the weights' 1.599 B and, on top, attention's products and the scan's
    assert 1.60e9 < n < 1.62e9


# -- the program against the reference ---------------------------------------

@pytest.mark.parametrize("held", [(4, 1), (16, 0)], ids=["share", "all"])
def test_the_programs_forward_is_the_reference(held):
    cfg = dict(TINY, n_routed_experts=held[0], expert_rank=held[1])
    params = _weights(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 45), 0, 256)
    with jax.default_matmul_precision("highest"):
        got = FAMILY.build(cfg).forward(params, ids)
    want = FAMILY.forward(params, cfg, ids)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5
    assert float(jnp.std(want)) > 0.5


@pytest.fixture(scope="module")
def served():
    """Two requests through the engine, the second admitted while the
    first decodes (its chunks ride decode steps), with the logits every
    call sampled from: ``(ids of the first, its logits [NEW, V], engine,
    params)``."""
    model = FAMILY.build(TINY)
    params = _weights()
    seen = []
    real = ev2._sample

    def spy(logits, temperature, key):
        jax.debug.callback(lambda l: seen.append(np.asarray(l)), logits,
                           ordered=True)
        return real(logits, temperature, key)

    mp = pytest.MonkeyPatch()
    mp.setattr(ev2, "_sample", spy)
    with jax.default_matmul_precision("highest"):
        eng = build_engine_v2(
            model, params, KVCacheConfig(num_blocks=64, block_size=PAGE,
                                         max_seq_len=128),
            max_batch_slots=3, prefill_chunk=CHUNK, prefill_batch=2,
            decode_burst=4)
        rng = np.random.default_rng(3)
        prompt = rng.integers(0, 256, PROMPT).tolist()
        first = eng.put(prompt, NEW_TOKENS)
        for _ in range(5):
            eng.step()
        eng.put(rng.integers(0, 256, OTHER).tolist(), 8)
        while eng.scheduler.has_work:
            eng.step()
    jax.effects_barrier()
    mp.undo()
    return prompt + first.generated, seen, first, eng, params


def test_prefill_then_decode_through_the_engine_is_the_reference(served):
    ids, seen, first, eng, params = served
    want = FAMILY.forward(params, TINY, jnp.asarray([ids[:-1]]))[0]
    want = np.asarray(want[PROMPT - 1:])
    assert len(first.generated) == NEW_TOKENS
    # greedy tokens: each is the reference's argmax
    np.testing.assert_array_equal(first.generated, want.argmax(axis=1))
    # and the logits they were sampled from are the reference's: every
    # token's row is among the rows some call sampled from
    rows = np.concatenate(seen)
    for t in range(NEW_TOKENS):
        nearest = np.abs(rows - want[t][None]).max(axis=1).min()
        assert nearest < 5e-5, (t, nearest)
    # each part's pool has the layers of its own part
    assert eng.pool["ssm"]["ssm"].shape == (2, 4, 2, 16, 128)
    assert eng.pool["kv"]["k"].shape[0] == 2
    assert eng.last_layers_by_part == {"ssm": 2, "kv": 2, "ffn": 4}
    assert [k.theta for k in eng.adapter.kinds] == [None]      # no rotary


def test_a_mixer_layer_indexed_by_the_models_layer_is_refused():
    """The state pool holds the mixers' layers alone: handed the model's
    layer as its place (what ``ls`` was before a layer was one part), the
    two mixers (the model's second and sixth layers) land on one layer's
    slots.  The engine as it is serves the reference's tokens under the
    same pattern."""
    cfg = dict(TINY, hybrid_override_pattern="EM*EEM*E")
    params = _weights(cfg)
    prompt = np.random.default_rng(3).integers(0, 256, PROMPT).tolist()
    real = ev2.RaggedInferenceEngineV2._layer_step

    def by_model_layer(self, params, lp, l, part, at, *rest, **kw):
        if part in self.state_layouts:
            at = l
        return real(self, params, lp, l, part, at, *rest, **kw)

    def serve():
        with jax.default_matmul_precision("highest"):
            eng = build_engine_v2(
                FAMILY.build(cfg), params,
                KVCacheConfig(num_blocks=64, block_size=PAGE,
                              max_seq_len=128),
                max_batch_slots=3, prefill_chunk=CHUNK, prefill_batch=2,
                decode_burst=4)
            return eng.generate([prompt], NEW_TOKENS)[0]

    sound = serve()
    want = FAMILY.forward(params, cfg, jnp.asarray([prompt + sound[:-1]]))[0]
    np.testing.assert_array_equal(
        sound, np.asarray(want[PROMPT - 1:]).argmax(axis=1))
    mp = pytest.MonkeyPatch()
    mp.setattr(ev2.RaggedInferenceEngineV2, "_layer_step", by_model_layer)
    try:
        assert serve() != sound
    finally:
        mp.undo()


# -- the shares of an expert layer -------------------------------------------

def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """``Part = W_↑ r + shared``: the shares' routed parts add up (``W_↑``
    is linear) and the shared expert is counted ONCE; in the program and
    in the reference alike."""
    whole = dict(TINY, n_routed_experts=16, expert_rank=0)
    params = _weights(whole)
    h = jax.random.normal(jax.random.PRNGKey(5), (19, 64))
    layer = 1
    cut = lambda name, w: w if name in FAMILY.WHOLE else w[layer]
    m = {name: cut(name, w) for name, w in params["moe"].items()}
    with jax.default_matmul_precision("highest"):
        want = FAMILY.experts(h, m, whole, layer)
        shared = FAMILY.experts(
            h, dict(m, w_up=m["w_up"] * 0, w_down=m["w_down"] * 0), whole,
            layer)
        parts_ref, parts_prog = [], []
        for rank in range(4):
            cfg = dict(TINY, expert_rank=rank)
            held = slice(4 * rank, 4 * rank + 4)
            share = dict(m, w_up=m["w_up"][:, held],
                         w_down=m["w_down"][:, held])
            parts_ref.append(FAMILY.experts(h, share, cfg, layer) - shared)
            model = FAMILY.build(cfg)
            lp = dict({n: w for n, w in share.items()
                       if n not in FAMILY.WHOLE},
                      pre_norm=jnp.ones((64,)), expert_layer=layer)
            # the program's layer takes x and norms it: under a weight of
            # 1 its own norm of h; what it adds to x is the Part
            x = h
            part = model.experts(lp, x, {n: share[n] for n in FAMILY.WHOLE}) - x
            normed = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                       + TINY["norm_eps"])
            parts_prog.append(part - FAMILY.experts(
                normed, dict(m, w_up=m["w_up"] * 0, w_down=m["w_down"] * 0),
                whole, layer))
        assert float(jnp.max(jnp.abs(sum(parts_ref) + shared - want))) < 2e-5
        whole_normed = FAMILY.experts(normed, m, whole, layer)
        shared_normed = FAMILY.experts(
            normed, dict(m, w_up=m["w_up"] * 0, w_down=m["w_down"] * 0),
            whole, layer)
        assert float(jnp.max(jnp.abs(
            sum(parts_prog) + shared_normed - whole_normed))) < 2e-5
    # the routed part is there to be seen, and one share is not the layer
    assert float(jnp.max(jnp.abs(want - shared))) > 0.5
    assert float(jnp.max(jnp.abs(parts_ref[0] + shared - want))) > 0.1


# -- every wrong variant fails -------------------------------------------------

def _softmax_routing(h, m, cfg):
    score = jax.nn.softmax(h @ m["wg"].astype(jnp.float32), axis=-1)
    biased = score + m["bias"].astype(jnp.float32)
    top, _ = jax.lax.top_k(biased, cfg["num_experts_per_tok"])
    chosen = jnp.where(biased >= top[:, -1:], score, 0.0)
    chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return chosen * cfg["routed_scaling_factor"]


_ROUTING = FAMILY.routing


def _bias_ignored(h, m, cfg):
    return _ROUTING(h, dict(m, bias=jnp.zeros_like(m["bias"])), cfg)


def _rotary(q, k):
    def rope(x):
        S, d = x.shape
        inv = 1.0 / (10000.0 ** (jnp.arange(0, d, 2) / d))
        ang = jnp.arange(S)[:, None] * inv[None, :]
        x1, x2 = x[:, : d // 2], x[:, d // 2:]
        return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                                x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)
    return rope(q), rope(k)


#: a function of the reference replaced, or a key of its configuration
WRONG = {
    "state_dropped": ({}, {"control_state_dropped": True}),
    "softmax_for_sigmoid": ({"routing": _softmax_routing}, {}),
    "choice_bias_ignored": ({"routing": _bias_ignored}, {}),
    "weights_not_normalised": ({}, {"norm_topk_prob": False}),
    "scale_dropped": ({}, {"routed_scaling_factor": 1}),
    "relu_for_relu2": ({"_relu2": jax.nn.relu}, {}),
    "experts_fed_h_truncated": (
        {"_latent": lambda h, m: h[:, :m["latent_down"].shape[1]]}, {}),
    "rotary_applied": ({"_positions": _rotary}, {}),
    "one_expert_fewer": ({}, {"num_experts_per_tok": 2}),
}


@pytest.mark.parametrize("wrong", sorted(WRONG))
def test_every_wrong_variant_fails(wrong, monkeypatch):
    """The program's logits lie within 2e-5 of the reference's
    (``test_the_programs_forward_is_the_reference``) and at least half their
    scale from each wrong variant's."""
    params = _weights()
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, 60), 0, 256)
    with jax.default_matmul_precision("highest"):
        got = FAMILY.build(TINY).forward(params, ids)
    replaced, keys = WRONG[wrong]
    for name, fn in replaced.items():
        monkeypatch.setattr(FAMILY, name, fn)
    monkeypatch.setattr(FAMILY, "PARTS", {
        "M": FAMILY.mixer, "*": FAMILY.attention, "E": FAMILY.experts})
    other = FAMILY.forward(params, dict(TINY, **keys), ids)
    assert float(jnp.max(jnp.abs(got - other))) > 0.5 * float(jnp.std(got))


def test_the_controls_of_the_check_fail_it_at_the_tiny_size():
    """``nemotron_h_control.py``'s wrong models through the runner's own
    ``_logit_gap``: greedy tokens of the reference with its state dropped
    with its products in e4m3 and with its routed sum dropped sit under
    the reference's best; its own sit at it."""
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
    assert gap(nemotron_h_control.WRONG["state_dropped"](TINY)) > 0.05
    assert gap(TINY, (4, 3)) > 0.05
    assert gap(nemotron_h_control.WRONG["routed_dropped"](TINY)) > 0.05
    assert set(nemotron_h_control.WRONG) == {
        "state_dropped", "one_expert_fewer", "scale_dropped",
        "routed_dropped"}


# -- the new shapes modules and readers --------------------------------------

def test_the_shapes_are_this_familys():
    # a routed expert: two [1024, 2688] matrices, a sixth of what the
    # gated count at the hidden width reads
    assert latent_moe_shapes.expert_weight_bytes(REAL) == 2 * 1024 * 2688 * 2
    assert latent_moe_shapes.assignment_flops(REAL) == 4 * 1024 * 2688
    assert latent_moe_shapes.expert_layers(REAL) == 5
    assert mixer_shapes.mixer_layers(REAL) == 5
    assert mixer_shapes.state_bytes(REAL) == 128 * 64 * 128 * 4
    assert mixer_shapes.update_bytes(REAL) == 2 * 4_194_304
    per_token = mixer_shapes.chunk_flops_per_token(REAL, 128)
    assert per_token == 8 * 2 * 64.5 * 128 + 128 * (2 * 64.5 * 64
                                                    + 4 * 128 * 64)


def _trace(ops, module="jit_inference_v2_decode_burst_n_steps1(1)",
           calls=10):
    """A synthetic traced stretch of one second: ``ops`` as (instruction
    text, seconds) on one chip, and ``calls`` executions of ``module``."""
    events, at = [], 0.0
    for text, seconds in ops:
        events.append(trace_reduce.Event(at, seconds * 1e9, text))
        at += seconds * 1e9
    modules = [trace_reduce.Event(i * 1e8, 9e7, module) for i in range(calls)]
    dev = trace_reduce.DeviceTrace(ops=events, modules=modules, async_ops=[])
    return trace_reduce.Trace(devices={0: dev}, host={}, t0_ns=0.0,
                              t1_ns=1e9)


MOSAIC = ' = (bf16[1]) custom-call(), custom_call_target="tpu_custom_call"'
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _obs(trace, counters, calls=100, steps=1):
    spans = [{"name": "inference/decode_burst", "dur_s": 0.03,
              "args": {"burst": steps}} for _ in range(calls)] \
        + [{"name": "inference/commit", "dur_s": 1e-4, "args": {}}
           for _ in range(calls)]
    return {"trace": trace, "program_counters": counters,
            "program_spans": spans, "peaks": PEAKS, "config": REAL,
            "memory_peak_bytes": 13.3e9}


def test_the_new_readers_on_a_synthetic_trace_stay_under_100():
    """At the counts the cell's traffic gives (640 active experts, 128
    decode rows and 256 chunk tokens a call of one step) and kernel times a
    little over each roofline's least, every share reads under 100%; under
    the other families' counts the same trace would read 2 to 11 times as
    much."""
    ops = [("%moe_grouped_matmul_relu2.1" + MOSAIC, 0.060),
           ("%moe_grouped_matmul.2" + MOSAIC, 0.050),
           ("%ssm_state_update_lanes.3" + MOSAIC, 0.070),
           ("%fusion.9 = f32[2,8,16,128,128]{4,3,2,1,0} fusion()", 0.004),
           ("%fusion.10 = bf16[2,128,8,16,64]{4,3,2,1,0} fusion()", 0.002),
           ("%fusion.11 = f32[4096,32768]{1,0} fusion()", 0.3)]
    counters = {"inference/moe/experts_active": 640.0 * 100,
                "inference/moe/assignments": 2112.0 * 100,
                "inference/ssm/decode_rows": 128.0 * 100,
                "inference/ssm/chunk_tokens": 256.0 * 100}
    obs = _obs(_trace(ops), counters)
    line = bench_run.measure(BENCH, CELL, obs, trace=True)
    got = {name: line[name]["value"] for name in NEW}
    # ten calls traced: 6,400 experts x 11.0 MB / 819 GB/s = 86.0 ms of 110
    assert got["latent_moe_roofline.batch"] == pytest.approx(
        100 * 6400 * 11_010_048 / 819e9 / 0.110, rel=1e-6)
    assert got["mixer_state_roofline.batch"] == pytest.approx(
        100 * 1280 * 5 * 8_388_608 / 819e9 / 0.070, rel=1e-6)
    assert got["mixer_scan_roofline.batch"] == pytest.approx(
        100 * 2560 * 5 * mixer_shapes.chunk_flops_per_token(REAL, 128)
        / 197e12 / 0.006, rel=1e-6)
    assert got["mixer_share.batch"] == pytest.approx(100 * 0.076 / 0.486)
    assert all(0 < v < 100 for v in got.values()), got
    assert line["moe_expert_share.batch"]["value"] == pytest.approx(
        100 * 0.110 / 0.486)
    # what the other families' modules would make of it: three [H, I]
    # matrices an expert, num_hidden_layers mixers
    from perfbench import moe_shapes
    assert moe_shapes.expert_weight_bytes(REAL) \
        == 6 * latent_moe_shapes.expert_weight_bytes(REAL)
    assert REAL["num_hidden_layers"] / mixer_shapes.mixer_layers(REAL) == 2.2


@pytest.mark.parametrize("lacking", ["trace", "counters", "kernel"])
def test_the_new_readers_find_nothing_to_read_where_the_program_lacks_it(
        lacking):
    """The parent's program has no such kernel and no such layer: the
    readers return nothing and do not raise, and the line leaves the
    metrics out."""
    ops = [("%fusion.11 = f32[4096,32768]{1,0} fusion()", 0.3)]
    obs = _obs(None if lacking == "trace" else _trace(
        ops if lacking == "kernel" else ops + [
            ("%moe_grouped_matmul.2" + MOSAIC, 0.05)]),
        {} if lacking != "kernel" else {
            "inference/moe/experts_active": 64.0,
            "inference/ssm/decode_rows": 12.0,
            "inference/ssm/chunk_tokens": 12.0})
    line = bench_run.measure(BENCH, CELL, obs, trace=True)
    assert not set(NEW) & set(line)
