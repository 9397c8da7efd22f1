"""The control of a serving cell's check: a comparison that has been shown
to fail.

The serving check holds the tokens the server emitted against the
configuration's plain reference (``runners/serve.py:_logit_gap``: how far
under the reference's best logit a served token sits, at worst).  The
control puts a WRONG model in the server's place, greedy tokens of the
reference itself computed wrongly, and runs them through that same
function: it must come out over the check's tolerance.  The wrong models:
the reference in the nearest precision below the one the configuration
states (``control.py``'s narrowed products: e4m3 for bfloat16), which is
what the tolerance is set against; and the mistakes a sparse-expert
serving path invites, the reference under a configuration that differs in
one published key: one expert a token fewer (``num_experts_per_tok - 1``:
an assignment dropped) and the weights renormalised (``norm_topk_prob``
flipped).

    python3 tests/perfbench_tests/serving_control.py --workload <cell> \
        --seeds <n> ... [--prompts 96 640]

reads each wrong model's gap at the cell's own widths, one JSON line a
seed, with no engine and no window.  ``test_perfbench_olmoe.py`` keeps the
control as a test at the tiny sizes.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys
import types
from typing import Any, Callable, Dict, List

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
for path in (str(HERE.parents[1]), str(HERE)):
    if path not in sys.path:
        sys.path.insert(0, path)

import control                                    # noqa: E402
from perfbench import harness, manifest, program  # noqa: E402
from perfbench import run as bench_run            # noqa: E402

#: one published key changed: what a wrong expert layer would compute
WRONG: Dict[str, Callable[[Dict[str, Any]], Dict[str, Any]]] = {
    "one_expert_fewer": lambda cfg: dict(
        cfg, num_experts_per_tok=cfg["num_experts_per_tok"] - 1),
    "renormalised": lambda cfg: dict(
        cfg, norm_topk_prob=not cfg["norm_topk_prob"]),
}


#: the reference itself, in the nearest precision below the configuration's
NARROWER = "narrower_precision"


def greedy_tokens(family: Any, weights: Any, cfg: Dict[str, Any],
                  prompt: np.ndarray, new: int, bits: Any = None
                  ) -> List[int]:
    """``new`` greedy tokens of ``family.forward`` under ``cfg`` after
    ``prompt``, every matrix product narrowed to ``bits`` (exponent,
    mantissa) where given.  One shape: the ids are padded to their final
    length, and a causal model's logits at a position do not see what
    follows it."""
    import jax
    import jax.numpy as jnp

    n = len(prompt)
    ids = np.zeros((n + new,), np.int32)
    ids[:n] = prompt
    # the program is traced at its first call, inside the narrowing
    with control.narrowed(*bits) if bits else contextlib.nullcontext():
        step = jax.jit(lambda w, i, at: jnp.argmax(
            family.forward(w, cfg, i[None])[0][at]))
        for i in range(new):
            ids[n + i] = int(step(weights, jnp.asarray(ids), n - 1 + i))
    return ids[n:].tolist()


def readings(ctx: harness.Context, prompts: List[int]) -> Dict[str, Any]:
    """The check's own gap for the reference's own tokens (0: the sound
    side) and for each wrong model's, on this context's seed."""
    import jax

    family = ctx.family()
    cfg, check = ctx.config, ctx.config["run"]["check"]
    model = family.build(cfg)
    weights = jax.jit(lambda key: jax.tree.map(
        lambda x: x.astype(model.config.dtype), model.init_params(key)))(
            program.seed_key(ctx.seed))
    runner = manifest.load_module("runners", cfg["run"]["runner"])
    rng = np.random.default_rng(ctx.seed + 1)
    out: Dict[str, Any] = {"seed": ctx.seed, "tolerance": check["tolerance"],
                           "prompts": prompts}
    samples = [rng.integers(0, cfg["vocab_size"], size=n, dtype=np.int32)
               for n in prompts]
    same = lambda c: c
    for name, wrong in dict(WRONG, reference=same, **{NARROWER: same}
                            ).items():
        bits = control.NARROWER[cfg["run"]["dtype"]] if name == NARROWER \
            else None
        gaps = []
        for prompt in samples:
            tokens = greedy_tokens(family, weights, wrong(cfg), prompt,
                                   check["new_tokens"], bits)
            gaps.append(runner._logit_gap(
                ctx, types.SimpleNamespace(params=weights),
                types.SimpleNamespace(
                    request=types.SimpleNamespace(prompt=prompt),
                    tokens=tokens)))
        out[name] = gaps
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--prompts", type=int, nargs="*", default=None)
    args = ap.parse_args()
    _, cell, config, traffic = manifest.load_cell(args.workload)
    bench_run.place_compile_cache()
    prompts = args.prompts or config["run"]["check"]["prompt_tokens"]
    for seed in args.seeds:
        ctx = harness.Context(cell=cell, config=config, traffic=traffic,
                              seed=seed, seconds=0.0, trace=False,
                              t_start=0.0, scratch="")
        print(json.dumps(readings(ctx, prompts)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
