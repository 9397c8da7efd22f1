"""The control of the training cells' check (``control.py``) at the tiny
sizes: the reference computed one precision down, put in the program's
place, comes out as not correct through the runner's own comparison, and
leaves the real reference as it found it."""

import pathlib
import sys

import pytest

from perfbench import harness, manifest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import control    # noqa: E402
import rehearsal  # noqa: E402

BENCH = manifest.load_benchmark()
TRAINING = [w["name"] for w in BENCH["workloads"]
            if manifest.load_json("configs", w["config"])["run"]["runner"]
            == "train"]
SEEDS = [2**31 + 17, 29, 3_000_000_019]


def context(cell_name: str, seed: int) -> harness.Context:
    cell = manifest.named(BENCH["workloads"], cell_name, "workload")
    config, traffic = rehearsal.tiny_files(cell)
    return harness.Context(cell=cell, config=config, traffic=traffic,
                           seed=seed, seconds=0.0, trace=False, t_start=0.0,
                           scratch="")


#: PR 23's two configurations hold the one loss over all positions to
#: 0.01: a mean in which the roundings cancel, which the control passes
#: (PERF.md section 4).  It holds a dropped or misscaled term there.  A
#: configuration added since brings only limits that the control fails:
#: the loss group by group (``loss_group_rms_err``).
PASSED_BY_THE_CONTROL = {"bert-large-zero2": {"loss_abs_err"},
                         "mistral-7b-zero3-l4-dp4": {"loss_abs_err"}}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cell", TRAINING)
def test_the_control_comes_out_as_not_correct(cell, seed):
    ctx = context(cell, seed)
    sound = control.readings(ctx, control=False)
    narrow = control.readings(ctx, control=True)
    assert sound["ok"] is True, sound
    assert narrow["ok"] is False, narrow
    tol = ctx.config["run"]["check"]["tolerance"]
    assert sound["tolerance"] == narrow["tolerance"] == tol
    # every limit tells the two apart, with room on both sides
    for key in set(tol) - PASSED_BY_THE_CONTROL.get(ctx.cell["config"], set()):
        assert narrow[key] > tol[key] > sound[key], key
        assert narrow[key] > 3 * sound[key], key


def test_narrowing_ends_with_its_block():
    """A trace made inside ``narrowed`` must not serve the reference
    afterwards, nor one made before it serve the control."""
    import jax
    import jax.numpy as jnp

    x = jax.random.normal(jax.random.PRNGKey(1), (8, 64))
    w = jax.random.normal(jax.random.PRNGKey(2), (64, 16))
    plain = lambda: jnp.einsum("sh,hd->sd", x, w, precision="highest")
    before = plain()
    with control.narrowed(4, 3):
        inside = plain()
        grad = jax.jit(jax.grad(lambda w: jnp.sum(jnp.tanh(x @ w))))(w)
    assert bool(jnp.all(plain() == before))
    err = float(jnp.max(jnp.abs(inside - before)) / jnp.max(jnp.abs(before)))
    assert 0.005 < err < 0.2          # three mantissa bits: 2^-4 an operand
    true = jax.grad(lambda w: jnp.sum(jnp.tanh(x @ w)))(w)
    gerr = float(jnp.linalg.norm(grad - true) / jnp.linalg.norm(true))
    assert 0.005 < gerr < 0.3         # the backward products are narrowed too


def test_the_grouped_loss_holds_what_the_one_loss_held():
    """``grouped_loss_err`` is nothing for the reference itself, moves
    when one position is dropped from the loss, and passes its limit when
    the loss is misscaled by a hundredth: what the limit on the one loss
    over all positions was there for."""
    import jax
    import jax.numpy as jnp

    from perfbench import program

    ctx = context("train-mistral7b-zero3-l2", 11)
    cfg, ref = ctx.config, ctx.family()
    train = manifest.load_module("runners", "train")
    params = jax.jit(ref.build(cfg).init_params)(program.seed_key(ctx.seed))
    ids = jax.random.randint(jax.random.PRNGKey(3), (1, 96), 0,
                             cfg["vocab_size"])
    logits = ref.forward(params, cfg, ids)
    shifted = jnp.concatenate([ids[:, 1:], jnp.full_like(ids[:, :1], -100)], 1)
    assert float(jnp.sum(train.label_losses(logits, shifted)) / 95) == \
        pytest.approx(float(ref.loss(params, cfg, {"input_ids": ids})), rel=1e-6)

    class Program:
        """The reference as a program that takes labels, with a fault."""

        def __init__(self, drop=None, scale=1.0):
            self.drop, self.scale = drop, scale

        def loss(self, w, batch):
            labels = batch["labels"]
            if self.drop is not None:
                labels = labels.at[:, self.drop].set(-100)
            return self.scale * (jnp.sum(train.label_losses(logits, labels))
                                 / jnp.maximum(jnp.sum(labels != -100), 1))

    err = lambda p: train.grouped_loss_err(p, params, ids, logits)
    assert err(Program()) < 1e-5
    limit = cfg["run"]["check"]["tolerance"]["loss_group_rms_err"]
    assert err(Program(drop=17)) > 1e-3 and err(Program(scale=1.01)) > limit
