"""Round-2 feature subsystems: linear/LoRA, sparse attention, autotuner
memory model, elastic agent v2, MiCS shard-size wiring."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from deepspeed_tpu.parallel import MeshLayout
from deepspeed_tpu.utils import groups


# ---------------------------------------------------------------------------
# linear / LoRA
# ---------------------------------------------------------------------------

def test_lora_linear_starts_as_base():
    from deepspeed_tpu.linear import LoRAConfig, OptimizedLinear

    lin = OptimizedLinear(32, 16, lora_config=LoRAConfig(lora_r=4),
                          dtype=jnp.float32)
    params = lin.init(jax.random.PRNGKey(0))
    assert "lora_a" in params and "lora_b" in params
    x = jnp.asarray(np.random.RandomState(0).randn(4, 32), jnp.float32)
    # B = 0 → adapter contributes nothing at init
    np.testing.assert_allclose(
        np.asarray(lin.apply(params, x)),
        np.asarray(x @ params["base"].astype(jnp.float32)),
        rtol=1e-5, atol=1e-5)


def test_lora_mask_freezes_base():
    from deepspeed_tpu.linear import (LoRAConfig, OptimizedLinear,
                                      lora_trainable_mask)

    lin = OptimizedLinear(16, 8, lora_config=LoRAConfig(lora_r=2),
                          dtype=jnp.float32)
    params = lin.init(jax.random.PRNGKey(1))
    mask = lora_trainable_mask(params)
    assert mask["lora_a"] and mask["lora_b"] and not mask["base"]

    tx = optax.masked(optax.sgd(0.1), mask)
    opt_state = tx.init(params)
    x = jnp.asarray(np.random.RandomState(2).randn(4, 16), jnp.float32)

    def loss(p):
        return jnp.sum(lin.apply(p, x) ** 2)

    g = jax.grad(loss)(params)
    updates, _ = tx.update(g, opt_state, params)
    new = optax.apply_updates(params, updates)
    np.testing.assert_array_equal(np.asarray(new["base"]),
                                  np.asarray(params["base"]))
    # at init B=0 blocks grad(A); B is the leaf that moves first
    assert not np.array_equal(np.asarray(new["lora_b"]),
                              np.asarray(params["lora_b"]))


def test_quantized_base_and_merge():
    from deepspeed_tpu.linear import (LoRAConfig, OptimizedLinear,
                                      QuantizationConfig, lora_merge)

    qc = QuantizationConfig(group_size=32)
    lin = OptimizedLinear(64, 32, lora_config=LoRAConfig(lora_r=4),
                          quantization_config=qc, dtype=jnp.float32)
    params = lin.init(jax.random.PRNGKey(3))
    assert params["base_q"].dtype == jnp.int8
    x = jnp.asarray(np.random.RandomState(4).randn(2, 64), jnp.float32)
    y = lin.apply(params, x)
    assert np.all(np.isfinite(np.asarray(y)))
    merged = lora_merge(params, LoRAConfig(lora_r=4), group_size=32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ merged),
                               rtol=1e-4, atol=1e-4)


def test_base_gradient_stopped():
    from deepspeed_tpu.linear import LoRAConfig, OptimizedLinear

    lin = OptimizedLinear(8, 8, lora_config=LoRAConfig(lora_r=2),
                          dtype=jnp.float32)
    params = lin.init(jax.random.PRNGKey(5))
    x = jnp.ones((2, 8), jnp.float32)
    g = jax.grad(lambda p: jnp.sum(lin.apply(p, x)))(params)
    np.testing.assert_array_equal(np.asarray(g["base"]), 0.0)
    assert np.abs(np.asarray(g["lora_b"])).sum() > 0  # grad(A)=0 while B=0


# ---------------------------------------------------------------------------
# sparse attention
# ---------------------------------------------------------------------------

def test_fixed_layout_and_mask_blocks():
    from deepspeed_tpu.ops.sparse_attention import (FixedSparsityConfig,
                                                    sparse_attention)

    cfg = FixedSparsityConfig(block=4, num_local_blocks=2,
                              num_global_blocks=1)
    lay = cfg.make_layout(32)
    assert lay.shape == (8, 8)
    assert lay[0, 1] == 1      # local window
    assert lay[0, 2] == 0 or lay[:, 2].all()  # outside window unless global
    # masked key blocks cannot influence the output
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 32, 2, 8), jnp.float32)
    k = jnp.asarray(rng.randn(1, 32, 2, 8), jnp.float32)
    v = jnp.asarray(rng.randn(1, 32, 2, 8), jnp.float32)
    out1 = sparse_attention(q, k, v, cfg)
    # perturb keys/values in a block masked for query block 0
    masked_kb = int(np.where(lay[0] == 0)[0][0])
    sl = slice(masked_kb * 4, masked_kb * 4 + 4)
    k2 = k.at[:, sl].set(99.0)
    v2 = v.at[:, sl].set(99.0)
    out2 = sparse_attention(q, k2, v2, cfg)
    np.testing.assert_allclose(np.asarray(out1[:, :4]),
                               np.asarray(out2[:, :4]), rtol=1e-5, atol=1e-5)


def test_bigbird_and_longformer_patterns():
    from deepspeed_tpu.ops.sparse_attention import (
        BigBirdSparsityConfig, BSLongformerSparsityConfig)

    bb = BigBirdSparsityConfig(block=4, num_random_blocks=1,
                               num_sliding_window_blocks=3,
                               num_global_blocks=1).make_layout(64)
    assert bb[0].all() and bb[:, 0].all()          # global first block
    assert np.diag(bb).all()                        # window includes self
    lf = BSLongformerSparsityConfig(
        block=4, num_sliding_window_blocks=3,
        global_block_indices=(0,)).make_layout(64)
    assert lf[:, 0].all() and lf[0].all()
    assert lf[8, 2] == 0                            # far off-window masked


def test_sparse_attention_causal_matches_dense_when_full():
    from deepspeed_tpu.ops.sparse_attention import (SparsityConfig,
                                                    sparse_attention)

    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(2, 16, 2, 8), jnp.float32)
    k = jnp.asarray(rng.randn(2, 16, 2, 8), jnp.float32)
    v = jnp.asarray(rng.randn(2, 16, 2, 8), jnp.float32)
    out = sparse_attention(q, k, v, SparsityConfig(block=4), causal=True)
    # dense causal reference
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(8)
    mask = np.tril(np.ones((16, 16), bool))
    s = np.where(mask[None, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("bhqk,bkhd->bqhd", p, v)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# autotuner memory model
# ---------------------------------------------------------------------------

def test_zero_memory_estimate_scales_with_stage():
    from deepspeed_tpu.autotuning.autotuner import zero_memory_estimate

    n, dp = 1_000_000, 8
    s0 = zero_memory_estimate(n, 0, dp)
    s1 = zero_memory_estimate(n, 1, dp)
    s2 = zero_memory_estimate(n, 2, dp)
    s3 = zero_memory_estimate(n, 3, dp)
    assert s0 > s1 > s2 > s3
    assert s0 == 16 * n
    off = zero_memory_estimate(n, 2, dp, offload_optimizer=True)
    assert off < s2


def test_autotuner_memory_prune_skips_without_compiling():
    from deepspeed_tpu.autotuning.autotuner import Autotuner

    calls = []

    def factory(cfg):
        calls.append(cfg["zero_optimization"]["stage"])
        raise RuntimeError("should only be called for surviving candidates")

    tuner = Autotuner(
        factory, lambda cfg: None,
        base_config={"train_micro_batch_size_per_gpu": 1,
                     "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}},
        tuning_space={"zero_optimization.stage": [0, 3]},
        model_params_count=10_000_000_000,  # 10B params
        hbm_bytes=16 * 2 ** 30, dp_size=1)  # 16 GiB chip, dp=1
    with pytest.raises(RuntimeError, match="no autotuning candidate"):
        tuner.tune()  # every candidate pruned or failed
    # stage 0 AND stage 3 at dp=1 both exceed 16 GiB → factory never called
    assert calls == []
    assert all(r.get("pruned") == "memory_model" for r in tuner.records)


# ---------------------------------------------------------------------------
# elastic agent
# ---------------------------------------------------------------------------

def test_elastic_agent_restarts_until_success(tmp_path):
    from deepspeed_tpu.elasticity.elastic_agent import launch_elastic

    attempts = []

    def flaky(restart_count, ckpt_dir):
        attempts.append(restart_count)
        if restart_count < 2:
            raise RuntimeError("simulated worker crash")
        return {"resumed_from": ckpt_dir, "restarts": restart_count}

    out = launch_elastic(flaky, max_restarts=3,
                         checkpoint_dir=str(tmp_path))
    assert out["restarts"] == 2
    assert attempts == [0, 1, 2]


def test_elastic_agent_gives_up():
    from deepspeed_tpu.elasticity.elastic_agent import launch_elastic

    def always_fails(restart_count, ckpt_dir):
        raise RuntimeError("permanent failure")

    with pytest.raises(RuntimeError, match="permanent"):
        launch_elastic(always_fails, max_restarts=2)


# ---------------------------------------------------------------------------
# MiCS shard-size wiring
# ---------------------------------------------------------------------------

def test_mics_factors_mesh_and_shards_subgroup():
    import deepspeed_tpu
    from deepspeed_tpu.models import LlamaConfig, LlamaModel

    groups.reset_mesh()
    cfg = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32)
    model_holder = {}

    class LateModel:
        """Model bound to the mesh initialize() builds from config."""

        def loss(self, p, b):
            return model_holder["m"].loss(p, b)

    # mesh=None → entry factors dp into data=mics(2) × expert(4)
    engine, *_ = deepspeed_tpu.initialize(
        model=LlamaModel(cfg),  # mesh-less model; constraints no-op
        model_parameters=LlamaModel(cfg).init_params(jax.random.PRNGKey(0)),
        config={"train_micro_batch_size_per_gpu": 8,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3, "mics_shard_size": 2,
                                      "stage3_param_persistence_threshold": 0},
                "steps_per_print": 0})
    assert dict(engine.mesh.shape)["data"] == 2
    assert dict(engine.mesh.shape)["expert"] == 4
    # params sharded over data(2) only → each shard spans 4 replicas
    big_leaf = engine.state.params["layers"]["mlp"]["w_gate"]
    spec = big_leaf.sharding.spec
    flat = [a for e in spec if e is not None
            for a in (e if isinstance(e, tuple) else (e,))]
    assert "data" in flat and "expert" not in flat
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 512, size=(8, 32)))
    m = engine.train_step({"input_ids": ids})
    assert np.isfinite(float(m["loss"]))


def test_mics_shard_size_must_divide():
    import deepspeed_tpu
    from deepspeed_tpu.models import LlamaConfig, LlamaModel

    groups.reset_mesh()
    cfg = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32)
    with pytest.raises(ValueError, match="divide"):
        deepspeed_tpu.initialize(
            model=LlamaModel(cfg),
            model_parameters=LlamaModel(cfg).init_params(
                jax.random.PRNGKey(0)),
            config={"train_micro_batch_size_per_gpu": 8,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": 3, "mics_shard_size": 3},
                    "steps_per_print": 0})


def test_elastic_agent_handles_sys_exit():
    """sys.exit(nonzero) from a supervised script counts as a failure to
    restart, not an agent crash; sys.exit(0) is success."""
    from deepspeed_tpu.elasticity.elastic_agent import launch_elastic

    attempts = []

    def exits_nonzero_then_ok(restart_count, ckpt_dir):
        attempts.append(restart_count)
        if restart_count < 1:
            raise SystemExit(1)
        raise SystemExit(0)

    launch_elastic(exits_nonzero_then_ok, max_restarts=2)
    assert attempts == [0, 1]


# ---------------------------------------------------------------------------
# AutoTP spec inference
# ---------------------------------------------------------------------------

def test_infer_tp_specs_name_patterns():
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.runtime.tensor_parallel import infer_tp_specs

    params = {
        "embed": jnp.zeros((512, 64)),
        "layers": {
            "attn": {"wq": jnp.zeros((2, 64, 8, 8)),
                     "wo": jnp.zeros((2, 8, 8, 64))},
            "mlp": {"w_up": jnp.zeros((2, 64, 256)),
                    "w_down": jnp.zeros((2, 256, 64)),
                    "norm": jnp.zeros((2, 64))},
        },
        "q_proj": jnp.zeros((64, 64)),      # HF spelling → column
        "down_proj": jnp.zeros((256, 64)),  # HF spelling → row
        "bias": jnp.zeros((64,)),
    }
    specs = infer_tp_specs(params)
    assert specs["embed"] == P()                               # replicated
    assert specs["layers"]["attn"]["wq"] == P(None, None, "tensor", None)
    assert specs["layers"]["attn"]["wo"] == P(None, "tensor", None, None)
    assert specs["layers"]["mlp"]["w_up"] == P(None, None, "tensor")
    assert specs["layers"]["mlp"]["w_down"] == P(None, "tensor", None)
    assert specs["layers"]["mlp"]["norm"] == P()
    assert specs["q_proj"] == P(None, "tensor")
    assert specs["down_proj"] == P("tensor", None)
    assert specs["bias"] == P()


def test_autotp_inferred_training_matches_single_device():
    """A spec-less model (bare loss over a dict pytree) trains under tp=2
    with inferred specs and tracks the unsharded trace."""
    import deepspeed_tpu

    rng = np.random.RandomState(4)
    W = {"q_proj": jnp.asarray(rng.randn(16, 16) * .3, jnp.float32),
         "out_proj": jnp.asarray(rng.randn(16, 16) * .3, jnp.float32),
         "head": jnp.asarray(rng.randn(16, 8) * .3, jnp.float32)}
    x = jnp.asarray(rng.randn(8, 16), jnp.float32)
    y = jnp.asarray(rng.randint(0, 8, size=(8,)))

    def loss_fn(p, batch):
        xb, yb = batch
        h = jnp.tanh(xb @ p["q_proj"])
        h = jnp.tanh(h @ p["out_proj"])
        logits = h @ p["head"]
        lp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(lp, yb[:, None], axis=1))

    def run(mesh):
        engine, *_ = deepspeed_tpu.initialize(
            model=loss_fn, model_parameters=jax.tree.map(jnp.copy, W),
            mesh=mesh,
            config={"train_micro_batch_size_per_gpu": 8,
                    "optimizer": {"type": "SGD", "params": {"lr": 0.1}},
                    "zero_optimization": {"stage": 0},
                    "steps_per_print": 0})
        return engine, [float(engine.train_step((x, y))["loss"])
                        for _ in range(4)]

    groups.reset_mesh()
    engine_tp, tp_losses = run(groups.initialize_mesh(
        MeshLayout.infer(8, tp=2, dp=4)))
    # inferred: q_proj column-sharded over tensor
    spec = engine_tp.state.params["q_proj"].sharding.spec
    flat = [a for e in spec if e is not None
            for a in (e if isinstance(e, tuple) else (e,))]
    assert "tensor" in flat
    groups.reset_mesh()
    _, single_losses = run(groups.initialize_mesh(MeshLayout.infer(1, dp=1)))
    for a, b in zip(tp_losses, single_losses):
        assert abs(a - b) < 1e-4, (tp_losses, single_losses)


def test_per_head_sparse_layouts():
    """different_layout_per_head: BigBird heads get distinct random blocks
    and attention applies the per-head masks."""
    from deepspeed_tpu.ops.sparse_attention import (BigBirdSparsityConfig,
                                                    sparse_attention)

    cfg = BigBirdSparsityConfig(num_heads=4, block=4, num_random_blocks=2,
                                num_sliding_window_blocks=1,
                                num_global_blocks=1,
                                different_layout_per_head=True)
    lay = cfg.make_layout(64)
    assert lay.shape == (4, 16, 16)
    # at least one pair of heads differs (random blocks per head)
    assert any(not np.array_equal(lay[0], lay[h]) for h in range(1, 4))
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(2, 64, 4, 8), jnp.float32)
    out = sparse_attention(q, q, q, cfg)
    assert out.shape == (2, 64, 4, 8)
    assert np.all(np.isfinite(np.asarray(out)))


def test_infer_tp_specs_matches_nested_and_dotted_paths():
    """Flax-style nesting ({'q_proj': {'kernel'}}) and dotted keys match;
    Fixed-pattern per-head layouts collapse to the shared 2-D form."""
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.ops.sparse_attention import FixedSparsityConfig
    from deepspeed_tpu.runtime.tensor_parallel import infer_tp_specs

    params = {"q_proj": {"kernel": jnp.zeros((64, 64)),
                         "bias": jnp.zeros((64,))},
              "self_attn.o_proj.weight": jnp.zeros((64, 64))}
    specs = infer_tp_specs(params)
    assert specs["q_proj"]["kernel"] == P(None, "tensor")
    assert specs["q_proj"]["bias"] == P()
    assert specs["self_attn.o_proj.weight"] == P("tensor", None)

    lay = FixedSparsityConfig(num_heads=8, block=4,
                              different_layout_per_head=True).make_layout(64)
    assert lay.ndim == 2  # identical heads collapse — no 8x mask memory
