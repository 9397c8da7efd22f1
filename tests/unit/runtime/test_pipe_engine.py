"""PipelineEngine + tied-layer gradients.

Reference anchor: ``deepspeed/runtime/pipe/engine.py`` tied-weight grad
all-reduce across owning stages [K].  Here tied layers share ONE param
leaf, so autodiff SUMS the use-site cotangents — the same reduction,
verified against a hand-built two-use-site model.
"""

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.parallel import MeshLayout
from deepspeed_tpu.runtime.pipe.module import (LayerSpec, PipelineModule,
                                               TiedLayerSpec)
from deepspeed_tpu.utils import groups
import pytest

pytestmark = pytest.mark.slow  # jit/engine-heavy; smoke tier runs -m "not slow"

def _tied_module(H=8, V=16):
    """embed → tanh mid-layer → unembed with the SAME weight (tied)."""

    def embed_init(rng):
        return {"w": jax.random.normal(rng, (V, H)) * 0.1}

    def embed_apply(p, x):      # x: [B] int ids → [B, H]
        return jnp.take(p["w"], x, axis=0)

    def mid_init(rng):
        return {"m": jax.random.normal(rng, (H, H)) * 0.5}

    def mid_apply(p, x):
        return jnp.tanh(x @ p["m"])

    def unembed_apply(p, x):    # reuses the tied embedding: [B, H] → [B, V]
        return x @ p["w"].T

    def loss_fn(logits, y):
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    return PipelineModule(
        layers=[
            TiedLayerSpec(init_fn=embed_init, apply_fn=embed_apply,
                          key="embed", name="embed"),
            LayerSpec(init_fn=mid_init, apply_fn=mid_apply, name="mid"),
            TiedLayerSpec(init_fn=embed_init, apply_fn=unembed_apply,
                          key="embed", name="unembed"),
        ],
        num_stages=2, loss_fn=loss_fn)


def _engine(module):
    mesh = groups.initialize_mesh(MeshLayout.infer(8, dp=8))
    engine, *_ = deepspeed_tpu.initialize(
        model=module, mesh=mesh,
        config={"train_micro_batch_size_per_gpu": 8,
                "optimizer": {"type": "SGD", "params": {"lr": 0.1}},
                "zero_optimization": {"stage": 0},
                "steps_per_print": 0})
    return engine


def test_tied_layer_single_leaf():
    """Tie groups materialize exactly one param leaf per key."""
    engine = _engine(_tied_module())
    assert list(engine.state.params["tied"].keys()) == ["embed"]
    # 3 specs but only 2 leaf groups: 1 tied + 1 regular
    assert len(engine.state.params["layers"]) == 1


def test_tied_gradient_is_sum_of_use_sites():
    """d(loss)/d(tied) == embed-site grad + unembed-site grad (the
    reference's cross-stage tied allreduce)."""
    module = _tied_module()
    engine = _engine(module)
    p = jax.device_get(engine.state.params)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randint(0, 16, size=(8,)))
    y = jnp.asarray(rng.randint(0, 16, size=(8,)))

    def loss_tied(tied_w, mid):
        h = jnp.take(tied_w, x, axis=0)
        h = jnp.tanh(h @ mid)
        logits = h @ tied_w.T
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    def loss_split(w_embed, w_unembed, mid):
        h = jnp.take(w_embed, x, axis=0)
        h = jnp.tanh(h @ mid)
        logits = h @ w_unembed.T
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    tied_w = p["tied"]["embed"]["w"]
    mid = p["layers"]["1"]["m"]
    g_tied = jax.grad(loss_tied)(tied_w, mid)
    g_embed = jax.grad(loss_split, argnums=0)(tied_w, tied_w, mid)
    g_unembed = jax.grad(loss_split, argnums=1)(tied_w, tied_w, mid)
    np.testing.assert_allclose(np.asarray(g_tied),
                               np.asarray(g_embed + g_unembed),
                               rtol=1e-5, atol=1e-6)

    # and the engine's own grad path agrees
    loss_fn = engine.loss_fn
    g_engine = jax.grad(lambda pp: loss_fn(pp, (x, y)))(p)
    np.testing.assert_allclose(np.asarray(g_engine["tied"]["embed"]["w"]),
                               np.asarray(g_tied), rtol=1e-5, atol=1e-6)


def test_pipeline_engine_train_batch_converges():
    module = _tied_module()
    engine = _engine(module)
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randint(0, 16, size=(8,)))
    batch = (x, x)  # learn identity mapping
    first = float(engine.train_batch(batch=batch))
    for _ in range(20):
        last = float(engine.train_batch(batch=batch))
    assert last < first


# ---------------------------------------------------------------------------
# 1F1B in the PRODUCTION path (VERDICT round-3 item 4): initialize() routes
# pp>1 engines through pipeline_train_1f1b when pipeline.schedule=1f1b
# (reference: runtime/pipe/engine.py TrainSchedule, SURVEY §3.5)
# ---------------------------------------------------------------------------

def _llama_pp(schedule, zero_stage=0, pp=2, steps=3, tp=1):
    from deepspeed_tpu.models import LlamaConfig, LlamaModel

    groups.reset_mesh()
    mesh = groups.initialize_mesh(
        MeshLayout.infer(8, pp=pp, tp=tp, dp=8 // (pp * tp)))
    cfg = LlamaConfig.tiny(num_layers=4, max_seq_len=32, dtype=jnp.float32,
                           pp_microbatches=4)
    model = LlamaModel(cfg, mesh=mesh)
    params = model.init_params(jax.random.PRNGKey(0))
    ds = {"train_micro_batch_size_per_gpu": 16,
          "gradient_accumulation_steps": 1,
          "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
          "zero_optimization": {"stage": zero_stage},
          "pipeline": {"stages": pp, "schedule": schedule}}
    engine, *_ = deepspeed_tpu.initialize(model=model,
                                          model_parameters=params,
                                          config=ds, mesh=mesh)
    b = {"input_ids": jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(16, 32)))}
    losses = [float(engine.train_step(b)["loss"]) for _ in range(steps)]
    return engine, losses


def test_engine_routes_1f1b_schedule():
    """pipeline.schedule=1f1b (the default) drives the real 1F1B tick scan
    — engine.last_pipe_stats proves the schedule built the program, and
    the trajectory matches the GPipe (autodiff) schedule."""
    eng_1f1b, losses_1f1b = _llama_pp("1f1b")
    assert eng_1f1b.last_pipe_stats is not None
    assert eng_1f1b.last_pipe_stats["schedule"] == "1f1b"
    # O(pp) stash, not O(M): the 1F1B memory bound
    assert eng_1f1b.last_pipe_stats["stash_depth"] == 2 * 2 - 1
    assert eng_1f1b.last_pipe_stats["gpipe_stash"] == 4

    eng_gpipe, losses_gpipe = _llama_pp("gpipe")
    assert eng_gpipe.last_pipe_stats is None  # 1F1B path NOT taken
    np.testing.assert_allclose(losses_1f1b, losses_gpipe,
                               rtol=2e-4, atol=2e-4)
    assert losses_1f1b[-1] < losses_1f1b[0]


def test_1f1b_under_tensor_axes_manual_tp():
    """1F1B x tp2 (VERDICT r4 item 6): the tensor axis joins the manual
    shard_map set and the model's Megatron column/row layer
    (decoder_layer_manual_tp, explicit _tp_copy/_tp_reduce collectives)
    runs the schedule — no GPipe fallback, trajectory == GPipe x tp2."""
    eng, losses = _llama_pp("1f1b", tp=2)
    assert eng.last_pipe_stats is not None
    assert eng.last_pipe_stats["schedule"] == "1f1b"
    assert eng.last_pipe_stats["manual_tp"] is True
    # untied head -> the vocab-parallel Megatron cross entropy runs
    # (lm_head column-sharded inside the manual region)
    assert eng.last_pipe_stats["vocab_parallel_head"] is True
    assert eng.last_pipe_stats["stash_depth"] == 2 * 2 - 1

    _, losses_gpipe = _llama_pp("gpipe", tp=2)
    np.testing.assert_allclose(losses, losses_gpipe, rtol=3e-4, atol=3e-4)
    assert losses[-1] < losses[0]


def test_1f1b_fp16_loss_scaling():
    """fp16 through 1F1B (VERDICT r4 item 10): the per-micro loss scales
    INSIDE the schedule, grads unscale outside, and the overflow vote is
    globally consistent (grads are one SPMD array).  Trajectory == fp16
    GPipe; an absurd initial scale overflows, SKIPS the step, and backs
    the scaler off — at which point training proceeds."""
    from deepspeed_tpu.models import LlamaConfig, LlamaModel

    def build(schedule, scale_power):
        groups.reset_mesh()
        mesh = groups.initialize_mesh(MeshLayout.infer(8, pp=2, dp=4))
        cfg = LlamaConfig.tiny(num_layers=4, max_seq_len=32,
                               dtype=jnp.float16, pp_microbatches=4)
        model = LlamaModel(cfg, mesh=mesh)
        params = model.init_params(jax.random.PRNGKey(0))
        eng, *_ = deepspeed_tpu.initialize(
            model=model, model_parameters=params, mesh=mesh,
            config={"train_micro_batch_size_per_gpu": 16,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "fp16": {"enabled": True,
                             "initial_scale_power": scale_power,
                             "loss_scale_window": 2, "hysteresis": 1},
                    "pipeline": {"stages": 2, "schedule": schedule}})
        return eng

    b = {"input_ids": jnp.asarray(np.random.RandomState(0).randint(
        0, 512, size=(16, 32)))}
    e1 = build("1f1b", 8)
    l1 = [float(e1.train_step(b)["loss"]) for _ in range(3)]
    # stats set at trace time proves the 1F1B program ran (no fp16 fallback)
    assert e1.last_pipe_stats is not None
    assert e1.last_pipe_stats["schedule"] == "1f1b"
    e2 = build("gpipe", 8)
    l2 = [float(e2.train_step(b)["loss"]) for _ in range(3)]
    np.testing.assert_allclose(l1, l2, rtol=3e-3, atol=3e-3)

    # overflow path: poison a master param so the fp16 cast is inf ->
    # overflow votes True on EVERY stage (one SPMD predicate), the step
    # skips (params untouched), and the scaler backs off
    e3 = build("1f1b", 8)
    e3.train_step(b)
    scale0 = float(e3.get_loss_scale())
    clean_embed = np.asarray(e3.state.params["embed"])
    poisoned = dict(e3.state.params)
    poisoned["embed"] = e3.state.params["embed"] * 1e38
    e3.state = e3.state._replace(params=poisoned)
    m = e3.train_step(b)
    assert bool(m["overflow"]) is True
    assert int(e3.skipped_steps) >= 1
    assert float(e3.get_loss_scale()) == scale0 / 2
    # skipped step left the (poisoned) params untouched
    np.testing.assert_allclose(np.asarray(e3.state.params["embed"]),
                               clean_embed * 1e38, rtol=1e-6)


@pytest.mark.parametrize("stage", [2, 3])
def test_1f1b_composes_with_zero(stage):
    """pipeline × ZeRO stage 2/3: the 1F1B schedule's grads feed the
    sharded optimizer states and the trajectory matches stage 0."""
    eng, losses = _llama_pp("1f1b", zero_stage=stage)
    assert eng.last_pipe_stats is not None
    _, losses0 = _llama_pp("1f1b", zero_stage=0)
    np.testing.assert_allclose(losses, losses0, rtol=2e-4, atol=2e-4)


def test_compat_pipeline_engine_runs_schedule_at_pp2():
    """The compat PipelineEngine executes the REAL ppermute fill/drain
    schedule when the mesh has pipe=2 — trajectory matches the pp=1
    sequential lowering of the same module."""
    groups.reset_mesh()
    module = _tied_module()
    mesh = groups.initialize_mesh(MeshLayout.infer(8, pp=2, dp=4))
    engine, *_ = deepspeed_tpu.initialize(
        model=module, mesh=mesh,
        config={"train_micro_batch_size_per_gpu": 8,
                "optimizer": {"type": "SGD", "params": {"lr": 0.1}},
                "zero_optimization": {"stage": 0},
                "pipeline": {"stages": 2, "num_micro_batches": 4},
                "steps_per_print": 0})
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randint(0, 16, size=(8,)))
    losses_pp = [float(engine.train_batch(batch=(x, x)))
                 for _ in range(5)]

    groups.reset_mesh()
    module2 = _tied_module()
    eng_seq = _engine(module2)
    losses_seq = [float(eng_seq.train_batch(batch=(x, x)))
                  for _ in range(5)]
    np.testing.assert_allclose(losses_pp, losses_seq, rtol=2e-4, atol=2e-5)


def _build_relayout_engine(pp, tp, stage, schedule="1f1b"):
    from deepspeed_tpu.models import LlamaConfig, LlamaModel

    groups.reset_mesh()
    mesh = groups.initialize_mesh(
        MeshLayout.infer(8, pp=pp, tp=tp, dp=8 // (pp * tp)))
    cfg = LlamaConfig.tiny(num_layers=4, max_seq_len=32,
                           dtype=jnp.float32, pp_microbatches=4)
    model = LlamaModel(cfg, mesh=mesh)
    params = model.init_params(jax.random.PRNGKey(0))
    eng, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, mesh=mesh,
        config={"train_micro_batch_size_per_gpu": 16,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": stage},
                "pipeline": {"stages": pp, "schedule": schedule}})
    return eng


def _relayout_batch():
    return {"input_ids": jnp.asarray(
        np.random.RandomState(3).randint(0, 512, size=(16, 32)))}


def test_universal_checkpoint_3d_relayout_to_pp_tp(tmp_path):
    """Universal-checkpoint 3D relayout (VERDICT r3 item 8, reference
    ``ds_to_universal`` role, SURVEY §5.4): save under dp8 ZeRO-3, resume
    under pp2 x tp2 x dp2 ZeRO-1 with the training trace continuing
    (orbax reshard-on-load owns the relayout).  The reverse direction is
    its own test — one process can't host too many mesh programs (the
    documented XLA-CPU limit, tests/run_suite.sh)."""
    b = _relayout_batch()
    src = _build_relayout_engine(pp=1, tp=1, stage=3)
    [float(src.train_step(b)["loss"]) for _ in range(2)]
    src.save_checkpoint(str(tmp_path / "a"))
    ref_next = float(src.train_step(b)["loss"])

    dst3d = _build_relayout_engine(pp=2, tp=2, stage=1)
    dst3d.load_checkpoint(str(tmp_path / "a"))
    got = float(dst3d.train_step(b)["loss"])
    np.testing.assert_allclose(got, ref_next, rtol=3e-4)


def test_universal_checkpoint_3d_relayout_to_dp(tmp_path):
    """Reverse 3D relayout: save under pp2 x tp2 x dp2 ZeRO-1, resume
    under dp8 ZeRO-3 — trace continues."""
    b = _relayout_batch()
    src = _build_relayout_engine(pp=2, tp=2, stage=1)
    [float(src.train_step(b)["loss"]) for _ in range(2)]
    src.save_checkpoint(str(tmp_path / "b"))
    ref_next = float(src.train_step(b)["loss"])

    back = _build_relayout_engine(pp=1, tp=1, stage=3)
    back.load_checkpoint(str(tmp_path / "b"))
    got = float(back.train_step(b)["loss"])
    np.testing.assert_allclose(got, ref_next, rtol=3e-4)
