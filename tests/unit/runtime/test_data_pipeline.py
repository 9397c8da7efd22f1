"""Data-efficiency pipeline: curriculum schedules/sampling + random-LTD."""

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.runtime.data_pipeline import (
    CurriculumScheduler, CurriculumSampler, DeepSpeedDataSampler,
    RandomLTDScheduler, random_ltd_apply)
from deepspeed_tpu.runtime.data_pipeline.data_sampler import truncate_batch


# ---------------------------------------------------------------------------
# schedules
# ---------------------------------------------------------------------------

def test_fixed_linear_schedule_monotone_and_quantized():
    s = CurriculumScheduler({
        "min_difficulty": 8, "max_difficulty": 64,
        "schedule_type": "fixed_linear",
        "schedule_config": {"total_curriculum_step": 100,
                            "difficulty_step": 8}})
    vals = [s.get_difficulty(t) for t in range(0, 140, 10)]
    assert vals[0] == 8 and vals[-1] == 64
    assert all(b >= a for a, b in zip(vals, vals[1:]))
    assert all(v % 8 == 0 for v in vals)


def test_fixed_root_reaches_max_faster_than_linear():
    common = dict(min_difficulty=0, max_difficulty=100,
                  schedule_config={"total_curriculum_step": 100,
                                   "difficulty_step": 1})
    lin = CurriculumScheduler({**common, "schedule_type": "fixed_linear"})
    root = CurriculumScheduler({**common, "schedule_type": "fixed_root"})
    assert root.get_difficulty(25) > lin.get_difficulty(25)


def test_fixed_discrete_schedule():
    s = CurriculumScheduler({
        "min_difficulty": 10, "max_difficulty": 40,
        "schedule_type": "fixed_discrete",
        "schedule_config": {"difficulty": [10, 20, 40],
                            "max_step": [5, 10, 10 ** 9]}})
    assert s.get_difficulty(3) == 10
    assert s.get_difficulty(7) == 20
    assert s.get_difficulty(100) == 40


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

def test_curriculum_sampler_pool_grows():
    diffs = np.arange(100)  # sample i has difficulty i
    s = CurriculumScheduler({
        "min_difficulty": 10, "max_difficulty": 100,
        "schedule_type": "fixed_linear",
        "schedule_config": {"total_curriculum_step": 50,
                            "difficulty_step": 10}})
    samp = CurriculumSampler(diffs, s, seed=7)
    early = samp.sample(step=0, batch_size=256)
    late = samp.sample(step=100, batch_size=256)
    assert early.max() <= 10          # only easy samples at step 0
    assert late.max() > 50            # full pool later
    # deterministic
    np.testing.assert_array_equal(early, samp.sample(0, 256))


def test_data_sampler_iterates_batches():
    data = [{"input_ids": np.full((8,), i)} for i in range(50)]
    ds = DeepSpeedDataSampler(
        data, difficulties=np.arange(50), batch_size=4,
        curriculum_config={
            "min_difficulty": 5, "max_difficulty": 50,
            "schedule_type": "fixed_linear",
            "schedule_config": {"total_curriculum_step": 10,
                                "difficulty_step": 5}})
    b = next(ds)
    assert b["input_ids"].shape == (4, 8)
    assert b["input_ids"].max() <= 5


def test_truncate_batch_seqlen_curriculum():
    batch = {"input_ids": np.ones((2, 64)), "labels": np.ones((2, 64)),
             "extra": np.ones((3,))}
    out = truncate_batch(batch, 16)
    assert out["input_ids"].shape == (2, 16)
    assert out["labels"].shape == (2, 16)
    assert out["extra"].shape == (3,)


# ---------------------------------------------------------------------------
# random-LTD
# ---------------------------------------------------------------------------

def test_random_ltd_identity_outside_subset():
    """Dropped tokens pass through bit-exact; kept tokens are processed."""
    B, S, H, keep = 2, 16, 8, 6
    x = jnp.asarray(np.random.RandomState(0).randn(B, S, H))
    layer = lambda t: t + 100.0
    out = random_ltd_apply(layer, x, keep, jax.random.PRNGKey(0))
    delta = np.asarray(out - x)
    changed = np.abs(delta).sum(-1) > 1.0
    assert changed.sum(axis=1).tolist() == [keep, keep]
    # unchanged rows are exactly identity
    assert np.all(delta[~changed] == 0)


def test_random_ltd_full_keep_is_layer():
    B, S, H = 2, 8, 4
    x = jnp.asarray(np.random.RandomState(1).randn(B, S, H))
    layer = lambda t: t * 2.0
    out = random_ltd_apply(layer, x, S, jax.random.PRNGKey(0))
    np.testing.assert_allclose(np.asarray(out), np.asarray(x) * 2.0)


def test_random_ltd_gradients_flow():
    B, S, H, keep = 2, 12, 4, 4
    x = jnp.asarray(np.random.RandomState(2).randn(B, S, H).astype(np.float32))
    w = jnp.ones((H,), jnp.float32)

    def loss(w):
        layer = lambda t: t * w
        return jnp.sum(random_ltd_apply(layer, x, keep, jax.random.PRNGKey(3)))

    g = jax.grad(loss)(w)
    assert np.all(np.isfinite(np.asarray(g)))
    assert np.abs(np.asarray(g)).sum() > 0


def test_random_ltd_scheduler_reference_schema():
    cfg = {"random_ltd_layer_id": [1, 2],
           "random_ltd_schedule": {
               "min_value": 128, "max_value": 512,
               "schedule_type": "fixed_linear",
               "schedule_config": {"require_steps": 100,
                                   "seq_per_step": 64}}}
    s = RandomLTDScheduler(cfg, seq_len=512)
    assert s.keep_count(0) == 128
    assert s.keep_count(100) == 512
    assert s.keep_count(50) % 64 == 0
    assert s.applies_to(1) and not s.applies_to(0)


def test_random_ltd_under_jit_static_keep():
    """keep is a static shape parameter — jit compiles per keep bucket."""
    B, S, H = 2, 16, 4
    x = jnp.asarray(np.random.RandomState(4).randn(B, S, H).astype(np.float32))

    import functools

    @functools.partial(jax.jit, static_argnums=(1,))
    def step(x, keep, rng):
        return random_ltd_apply(lambda t: t + 1.0, x, keep, rng)

    a = step(x, 8, jax.random.PRNGKey(0))
    b = step(x, 16, jax.random.PRNGKey(0))
    assert a.shape == b.shape == x.shape


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

def _mlm_data(vocab, n_samples=32, seq=64, seed=0):
    rng = np.random.RandomState(seed)
    data = []
    for _ in range(n_samples):
        ids = rng.randint(4, vocab, size=(seq,))
        labels = np.where(rng.rand(seq) < 0.15, ids, -100)
        data.append({"input_ids": ids, "labels": labels})
    return data


def test_curriculum_dataloader_wired_through_initialize():
    import deepspeed_tpu
    from deepspeed_tpu.models import BertConfig, BertModel
    from deepspeed_tpu.parallel import MeshLayout
    from deepspeed_tpu.utils import groups

    groups.reset_mesh()
    cfg = BertConfig.tiny(num_layers=2, max_seq_len=64, dtype=jnp.float32)
    mesh = groups.initialize_mesh(MeshLayout.infer(8, dp=8))
    model = BertModel(cfg, mesh=mesh)
    engine, _, dl, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init_params(jax.random.PRNGKey(0)),
        mesh=mesh, training_data=_mlm_data(cfg.vocab_size),
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 0},
                "steps_per_print": 0,
                "curriculum_learning": {
                    "enabled": True, "min_difficulty": 16,
                    "max_difficulty": 64,
                    "schedule_type": "fixed_linear",
                    "schedule_config": {"total_curriculum_step": 4,
                                        "difficulty_step": 16}}})
    it = iter(dl)
    first = next(it)
    assert first["input_ids"].shape[1] == 16      # truncated at step 0
    m = engine.train_step(first)
    assert np.isfinite(float(m["loss"]))
    engine.global_steps = 10                      # past the schedule
    late = next(it)
    assert late["input_ids"].shape[1] == 64       # full length restored


def test_random_ltd_wired_through_engine():
    """BERT + random_ltd config: buckets compile per keep count, training
    converges, and keep grows along the schedule."""
    import deepspeed_tpu
    from deepspeed_tpu.models import BertConfig, BertModel
    from deepspeed_tpu.parallel import MeshLayout
    from deepspeed_tpu.utils import groups

    groups.reset_mesh()
    cfg = BertConfig.tiny(num_layers=4, max_seq_len=32, dtype=jnp.float32)
    mesh = groups.initialize_mesh(MeshLayout.infer(8, dp=8))
    model = BertModel(cfg, mesh=mesh)
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init_params(jax.random.PRNGKey(0)),
        mesh=mesh,
        config={"train_micro_batch_size_per_gpu": 8,
                "optimizer": {"type": "AdamW", "params": {"lr": 2e-3}},
                "zero_optimization": {"stage": 0},
                "steps_per_print": 0,
                "data_efficiency": {
                    "enabled": True,
                    "data_routing": {"random_ltd": {
                        "enabled": True,
                        "random_ltd_layer_id": [1, 2],
                        "random_ltd_schedule": {
                            "min_value": 16, "max_value": 32,
                            "schedule_type": "fixed_linear",
                            "schedule_config": {"require_steps": 6,
                                                "seq_per_step": 8}}}}}})
    assert engine.module.ltd_layer_ids == (1, 2)
    rng = np.random.RandomState(1)
    ids = rng.randint(4, cfg.vocab_size, size=(8, 32))
    labels = np.where(rng.rand(8, 32) < 0.15, ids, -100)
    batch = {"input_ids": jnp.asarray(ids), "labels": jnp.asarray(labels)}
    first = float(engine.train_step(batch)["loss"])   # keep=16 bucket
    for _ in range(8):
        last = float(engine.train_step(batch)["loss"])
    assert last < first
    # schedule crossed 16 → 24 → full(32≡off): several compiled buckets
    assert len(engine._ltd_fns) >= 2
    assert -1 in engine._ltd_fns                      # full-keep bucket
    assert engine.module.ltd_keep is None             # LTD off at the end
