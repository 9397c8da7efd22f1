"""Runner for training cells: ``deepspeed_tpu.initialize()`` →
``engine.train_step`` on one repeated batch, steps back to back, each
fenced by ``jax.block_until_ready`` on the loss.

Before the engine exists, the program's model (its loss, logits and
gradients, in the configuration's precision and with its kernels) is held
against the configuration's plain float32 reference on a seeded sample.
"""

from __future__ import annotations

import math
from typing import Any, Dict

from perfbench import harness, program

#: the first loss of a random-weight model is ln(vocab) plus about half
#: the variance of its ~N(0, 1) logits
FIRST_LOSS_BAND = 1.0
#: positions to a group of the grouped loss: 32 groups in a 1,024-token sample
GROUP_POSITIONS = 32


def label_losses(logits: Any, labels: Any) -> Any:
    """``[B, S]``: the cross-entropy of ``labels[b, t]`` under
    ``logits[b, t]``, and 0 where the label is -100 (not counted)."""
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    return jnp.where(labels != -100, nll, 0.0)


def grouped_loss_err(plain: Any, params: Any, ids: Any, r_logits: Any
                     ) -> float:
    """The loss, group of positions by group: positions t with
    ``t % groups == g`` form group g (so each group passes through every
    tile of a tiled loss; ``GROUP_POSITIONS`` to a group), the program's ``loss`` is taken with every
    other label set to -100, the reference's is the mean over the same
    positions of ``label_losses`` of its logits, and the root mean square
    of the ``groups`` differences is returned.  The one loss over all
    positions is a mean in which the roundings cancel: its error is a
    signed noise around zero that varies tenfold from seed to seed and
    cannot tell a narrower precision from the program; a root mean square
    over groups does not cancel and is steady.  Next-token families only
    (the labels are the shifted inputs)."""
    import jax
    import jax.numpy as jnp

    labels = jnp.concatenate(
        [ids[:, 1:], jnp.full_like(ids[:, :1], -100)], axis=1)
    groups = max(ids.shape[1] // GROUP_POSITIONS, 1)
    member = jnp.arange(ids.shape[1]) % groups == jnp.arange(groups)[:, None]
    grouped = jnp.where(member[:, None, :], labels, -100)   # [groups, B, S]
    program_losses = jax.jit(lambda w, x, ls: jax.lax.map(
        lambda l: plain.loss(w, {"input_ids": x, "labels": l}), ls))(
            params, ids, grouped)
    reference_losses = jax.jit(lambda logits: jnp.sum(
        label_losses(logits, labels) * member[:, None, :], axis=(1, 2))
        / jnp.sum(grouped != -100, axis=(1, 2)))(r_logits)
    return float(jnp.sqrt(jnp.mean(
        (program_losses.astype(jnp.float32) - reference_losses) ** 2)))


def compare_with_reference(ctx: harness.Context, params: Any
                           ) -> Dict[str, float]:
    """Program against reference on ``run.check``'s sample: the largest
    logit difference over the largest reference logit, the loss
    difference (where ``check.tolerance`` has ``loss_group_rms_err``, group
    by group: ``grouped_loss_err``), and over every weight leaf the largest relative
    L2 error of its gradient (``leaf_errors``).  Every key of
    ``check.tolerance`` is a limit; a number without one is printed only.
    ``check.config_overrides`` changes
    keys of the configuration on BOTH sides for the check alone: a sliding
    window scaled down with the sample, so that the sample crosses it as
    the cell's rows do (a float32 reference with gradients at the cell's
    own 8192 tokens does not fit a chip).  The program's gradients wait in
    bfloat16 while the reference's are computed (both in float32 beside
    the weights do not fit one chip at 1.1 B parameters); that rounding is
    2^-9, far under the tolerance."""
    import jax
    import jax.numpy as jnp

    check = ctx.config["run"]["check"]
    cfg = dict(ctx.config, **check.get("config_overrides", {}))
    sample = ctx.generator().make(
        ctx.traffic, ctx.seed + 1, rows=check["rows"], seq=check["seq"],
        vocab_size=cfg["vocab_size"],
        mlm_label_share=cfg["run"].get("mlm_label_share", 0.0))
    sample = {k: jnp.asarray(v) for k, v in sample.items()}
    ref = ctx.family()
    plain = ref.build(cfg, mesh=None)                # one chip, no sharding

    p_logits = jax.jit(plain.forward)(params, sample["input_ids"])
    r_logits = jax.jit(lambda w, ids: ref.forward(w, cfg, ids))(
        params, sample["input_ids"])
    logit_err = float(jnp.max(jnp.abs(p_logits - r_logits))
                      / jnp.max(jnp.abs(r_logits)))
    grouped = ({"loss_group_rms_err": grouped_loss_err(
        plain, params, sample["input_ids"], r_logits)}
        if "loss_group_rms_err" in check["tolerance"] else {})
    del p_logits, r_logits

    p_loss, p_grads = jax.jit(jax.value_and_grad(plain.loss))(params, sample)
    p_grads = jax.tree.map(lambda g: g.astype(jnp.bfloat16), p_grads)
    r_loss, r_grads = jax.jit(jax.value_and_grad(
        lambda w, b: ref.loss(w, cfg, b)))(params, sample)

    def leaf_errors(a, b):
        # a leaf whose true gradient is (nearly) nothing, such as BERT's
        # key bias, is held to a hundredth of the largest leaf's norm
        norm = lambda x: jnp.linalg.norm(x.astype(jnp.float32).ravel())
        floor = 1e-2 * jnp.max(jnp.stack([norm(y) for y in jax.tree.leaves(b)]))
        return jax.tree.map(
            lambda x, y: norm(x.astype(jnp.float32) - y)
            / jnp.maximum(norm(y), floor), a, b)

    leaf_err = jax.jit(leaf_errors)(p_grads, r_grads)
    worst = max(jax.tree_util.tree_flatten_with_path(leaf_err)[0],
                key=lambda kv: float(kv[1]))
    out = {"logit_rel_err": logit_err,
           "loss_abs_err": abs(float(p_loss) - float(r_loss)),
           "grad_rel_err": float(worst[1]),
           "grad_worst_leaf": jax.tree_util.keystr(worst[0]),
           "reference_loss": float(r_loss), **grouped}
    tol = {k: v for k, v in check["tolerance"].items() if k[0] != "_"}
    out.update(tolerance=tol, ok=all(out[k] <= tol[k] for k in tol))
    return out


def run(ctx: harness.Context) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu

    cfg, run_ = ctx.config, ctx.config["run"]
    compiles = harness.CompileCounter()
    mesh, dp = program.mesh_for(run_, ctx.chips)
    model = ctx.family().build(cfg, mesh=mesh)
    params = jax.jit(model.init_params)(program.seed_key(ctx.seed))
    check = compare_with_reference(ctx, params)

    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=dict(run_["ds_config"]),
        mesh=mesh)
    del params
    rows = dp * int(run_["ds_config"]["train_micro_batch_size_per_gpu"])
    seq = int(run_["seq"])
    batch = {k: jnp.asarray(v) for k, v in ctx.generator().make(
        ctx.traffic, ctx.seed, rows=rows, seq=seq,
        vocab_size=cfg["vocab_size"],
        mlm_label_share=run_.get("mlm_label_share", 0.0)).items()}

    def step() -> float:
        with harness.span("bench/train_step"):
            metrics = engine.train_step(batch)
        with harness.span("bench/fence"):
            jax.block_until_ready(metrics["loss"])
        return metrics["loss"]

    losses = [float(step()) for _ in range(int(run_["warmup_steps"]))]
    # the step program's planned scratch (a read of the compile cache)
    plan = program.train_step_plan(engine, batch)
    compiled_before = compiles.count

    t_open = ctx.clock()
    tracer = harness.TailTracer(ctx, t_open)
    starts, ends, window_losses = [], [], []
    while not ends or ends[-1] - t_open < ctx.seconds:
        tracer.poll(ctx.clock())
        starts.append(ctx.clock())
        window_losses.append(step())
        ends.append(ctx.clock())
    trace = tracer.finish()
    in_use = program.memory_bytes()
    losses += [float(x) for x in window_losses]

    finite = [math.isfinite(x) for x in losses]
    first_ok = abs(losses[0] - math.log(cfg["vocab_size"])) <= FIRST_LOSS_BAND
    return {
        "t_open": t_open, "t_close": ends[-1],
        "setup_s": t_open - ctx.t_start,
        "attempted": len(losses), "failed": finite.count(False),
        "correct": bool(check["ok"] and all(finite) and first_ok
                        and losses[-1] < losses[0]),
        "check": check, "losses": [losses[0], losses[-1]],
        "compiles_in_window": compiles.count - compiled_before,
        "work": {"tokens": rows * seq * len(ends)},
        "steps": {"tokens": rows * seq,
                  "seconds": [b - a for a, b in zip(starts, ends)]},
        "flops_per_token": ctx.family().train_flops_per_token(cfg, seq),
        "memory_peak_bytes": program.window_peak_bytes(in_use, [plan]),
        "memory": {"in_use_in_window": in_use, "step_program": plan,
                   "process_peak_in_use":
                       program.memory_bytes("peak_bytes_in_use")},
        "trace": trace,
    }
