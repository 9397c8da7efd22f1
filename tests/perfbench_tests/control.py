"""The control of a training cell's check: a comparison that has been shown
to fail.

The control is the configuration's plain reference put in the program's
place and computed in the nearest precision below the one the
configuration states, the step that would tempt a later PR: an 8-bit
float (e4m3) for the bfloat16 compute that every training configuration
states.  Every matrix product of the reference, forward and backward,
gets its two operands (in the backward pass the cotangent and the kept
operand) rounded to that type after scaling the operand to the type's
range; sums, softmax and norms stay in float32, as in an fp8 matmul
path.  It goes through the runner's own
``compare_with_reference`` (the code that decides ``correct``), which
must come out as NOT ok.

    python3 tests/perfbench_tests/control.py --workload <cell> \
        --seeds <n> ... --control-seeds <n> ...

reads, in one process and at the cell's own size, the program's numbers
on ``--seeds`` and the control's on ``--control-seeds``, one JSON line
each: the two readings a limit is set from (PERF.md gives them beside
each limit).  No engine, no window.  ``test_perfbench_control.py`` keeps
the control as a test at the tiny sizes.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys
import types
from typing import Any, Dict

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perfbench import harness, manifest, program  # noqa: E402
from perfbench import run as bench_run            # noqa: E402

#: the nearest precision below the one a configuration's ``run.dtype``
#: states, as (exponent bits, mantissa bits): e4m3, the 8-bit float of fp8
#: matmul paths.  A configuration in another type brings its entry
NARROWER = {"bfloat16": (4, 3)}


@contextlib.contextmanager
def narrowed(exponent_bits: int, mantissa_bits: int):
    """While this is open, every ``dot_general`` that JAX traces rounds
    its operands to a float of that many bits first, and carries a
    gradient rule that rounds the cotangent too before the two backward
    products (an fp8 path's backward pass).  The rounding is
    ``lax.reduce_precision``, which XLA keeps: a cast to
    ``float8_e4m3fn`` and back is removed by the TPU compiler wherever
    both casts land in one fusion (measured, PR 26: under ``jit`` a
    [256, 256] operand came back unrounded), so a control made of casts is
    narrowed in some products and not in others.  ``jnp.einsum`` and ``@``
    are jitted functions whose traces JAX keeps, so the caches are emptied
    on the way in (or a trace from outside would be used, un-narrowed) and
    on the way out (or the real reference would get a narrowed one)."""
    import jax
    import jax.numpy as jnp

    primitive = jax.lax.dot_general_p
    bind = primitive.bind

    #: the format's largest number: (2 - 2^-m) * 2^(2^(e-1) - 1); 240
    largest = (2.0 - 2.0 ** -mantissa_bits) * 2.0 ** (
        2 ** (exponent_bits - 1) - 1)

    def rounded(x):
        # an 8-bit float spans five decades: unscaled, the backward
        # pass's cotangents all round to zero and the control fails for
        # nothing.  Scale each operand to the format's range first, as fp8
        # recipes do ("current scaling": one factor per tensor, from its
        # largest entry)
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / largest
        return jax.lax.reduce_precision(
            x / scale, exponent_bits, mantissa_bits) * scale

    def narrow_bind(lhs, rhs, **params):
        def forward_pass(a, b):
            a, b = rounded(a), rounded(b)
            return bind(a, b, **params), (a, b)

        def backward_pass(operands, g):
            # the two backward products are plain ones of rounded operands:
            # the rule that makes them must not itself be narrowed
            patched = vars(primitive).pop("bind", None)
            try:
                _, pullback = jax.vjp(lambda a, b: bind(a, b, **params),
                                      *operands)
                return pullback(rounded(g))
            finally:
                if patched is not None:
                    primitive.bind = patched

        product = jax.custom_vjp(lambda a, b: forward_pass(a, b)[0])
        product.defvjp(forward_pass, backward_pass)
        return product(lhs, rhs)

    jax.clear_caches()
    primitive.bind = narrow_bind
    try:
        # setting ``bind`` on the primitive's instance is not public API: a
        # JAX that no longer looks it up there would leave the control
        # un-narrowed, and sound.  Scaled so that 1.0625 is the format's
        # largest number, 1.0 falls between two of its numbers.
        with jax.ensure_compile_time_eval():   # entered under a trace too
            probe = jnp.array([[1.0, 1.0625]], jnp.float32)
            narrow = float((probe @ probe.T)[0, 0]) != 1.0 + 1.0625 ** 2
        if not narrow:
            raise RuntimeError("dot_general was not narrowed: the control "
                               "would measure the reference against itself")
        yield
    finally:
        del primitive.bind
        jax.clear_caches()


class NarrowedReference:
    """Stands where ``family.build(cfg)`` stands in the comparison: the
    program's ``forward(params, ids)`` and ``loss(params, batch)``, here
    the reference's own with narrowed products."""

    def __init__(self, family: Any, cfg: Dict[str, Any], bits: Any):
        self._family, self._cfg, self._bits = family, cfg, bits

    def forward(self, params, ids):
        with narrowed(*self._bits):
            return self._family.forward(params, self._cfg, ids)

    def loss(self, params, batch):
        import jax.numpy as jnp

        with narrowed(*self._bits):
            if "labels" not in batch or "loss_group_rms_err" not in \
                    self._cfg["run"]["check"]["tolerance"]:
                return self._family.loss(params, self._cfg, batch)
            # the grouped loss's batch form, which a next-token reference
            # does not take: -100 marks a position that is not counted
            logits = self._family.forward(params, self._cfg,
                                          batch["input_ids"])
            runner = manifest.load_module("runners", "train")
            return (jnp.sum(runner.label_losses(logits, batch["labels"]))
                    / jnp.sum(batch["labels"] != -100))


class ControlContext(harness.Context):
    """A run's context whose family builds the control as its program and
    keeps the real reference as its reference."""

    def family(self) -> Any:
        family = super().family()
        bits = NARROWER[self.config["run"]["dtype"]]
        return types.SimpleNamespace(
            build=lambda cfg, mesh=None: NarrowedReference(family, cfg, bits),
            forward=family.forward, loss=family.loss)


def readings(ctx: harness.Context, control: bool) -> Dict[str, Any]:
    """What ``compare_with_reference`` reads on this context's seed, with
    the program (``control`` false) or the control in the program's place."""
    import jax

    runner = manifest.load_module("runners", ctx.config["run"]["runner"])
    model = ctx.family().build(ctx.config, mesh=None)
    params = jax.jit(model.init_params)(program.seed_key(ctx.seed))
    if control:
        ctx = ControlContext(**vars(ctx))
    out = runner.compare_with_reference(ctx, params)
    return dict(out, seed=ctx.seed, control=control)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args()
    _, cell, config, traffic = manifest.load_cell(args.workload)
    bench_run.place_compile_cache()     # one seed compiles, the others load
    for control, seeds in ((False, args.seeds), (True, args.control_seeds)):
        for seed in seeds:
            ctx = harness.Context(cell=cell, config=config, traffic=traffic,
                                  seed=seed, seconds=0.0, trace=False,
                                  t_start=0.0, scratch="")
            print(json.dumps(readings(ctx, control)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
