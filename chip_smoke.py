"""chip_smoke.py — does the system still start, train, serve and match its
references on the TPU?  One process, one command, no options:

    python3 chip_smoke.py

It refuses to run without a TPU, then drives the two main paths at the
full width of Mistral-7B (``LlamaConfig.mistral_7b``; depth is the only
cut, weights are random from a seed):

1. trainer — ``deepspeed_tpu.initialize()`` (ZeRO-3, AdamW, bf16, flash
   attention, remat, tiled loss) → ``engine.train_step`` on one repeated
   batch of full-context sequences;
2. server — ``serving.build_serving_frontend()`` → the v2 engine's chunked
   prefill and paged decode burst, for requests from ~100 to ~6000 prompt
   tokens, checked token by token against the model's full forward pass;
3. kernels — every kernel in ``ops/pallas/`` compiled against its float32
   reference at this model's shapes (``ops/pallas/selfcheck.py``);
4. with more than one chip (the layout comes from ``jax.device_count()``),
   the trainer again over all of them: ZeRO-3 over ``data=N``, then
   ``tensor=2 × data=N/2``.

Any failed check raises, so the exit code is non-zero and no result line
is printed.  The line before the last is the summary: per phase, compile
and run seconds, peak HBM per device and the kernels found in the compiled
programs — facts about the run, not measurements of speed.  The last line
of standard output is the result, one JSON object with exactly the keys
``ok`` and ``device``, the device as JAX reports it:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The phases are functions of a model config: the CPU tests call them with
``LlamaConfig.tiny()``.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import math
import re
import sys
import time
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

#: layers the one-chip trainer holds: 2 layers + both 32000-row tables is
#: 0.70 B parameters, 16 B each in ZeRO-3 + AdamW (fp32 master, two
#: moments, fp32 grads) — the compiled step peaks at 15.1 of the chip's
#: 15.75 GiB; a third layer does not fit
TRAIN_LAYERS = 2
TRAIN_STEPS = 4
#: bf16 weights only: 4 layers + tables is 1.13 B parameters, 2.3 GB
SERVE_LAYERS = 4
#: layers trained over a four-chip host (1.13 B parameters: 4.5 GB of
#: state per chip at 1/4 each, the rest is activations of 8192-token rows)
MULTICHIP_LAYERS = 4
#: tokens decoded per request, and the prompt lengths of one round: two
#: shorter than a prefill chunk, several spanning many chunks, three past
#: the 4096-token window
NEW_TOKENS = 64
PROMPT_LENGTHS = (100, 300, 700, 1500, 2500, 4200, 5000, 6000)
PREFILL_CHUNK = 512
DECODE_BURST = 16
#: requests whose every token is compared with the model's full forward
#: pass (the dense reference holds [heads, S, S] scores: up to ~4.3k here)
REFERENCE_MAX_TOKENS = 4300
#: a served token's reference logit may sit this far under the reference
#: maximum.  Logits of a random-weight model are ~N(0, 1) with the top two
#: ~0.2 apart, so bf16 rounding between two correct bf16 paths flips an
#: argmax now and then (worst gap measured on the v5e, PR 21: 0.045); a
#: wrong page, position or mask picks a token ~4 under the maximum.
SERVE_LOGIT_TOL = 0.25
#: first loss of a random-weight model: ln(vocab) plus half the variance
#: of its ~N(0, 1) logits
FIRST_LOSS_BAND = 1.0
#: per-device bytes_in_use after a multi-chip run, largest over smallest
MEMORY_BALANCE_RATIO = 1.25

#: the names the kernels are given at their ``pl.pallas_call(name=...)``
FLASH_KERNELS = {"flash_fwd", "flash_bwd"}
PAGED_KERNEL = "paged_decode_attention"


# ---------------------------------------------------------------------------
# what the run records about itself
# ---------------------------------------------------------------------------


class CompileClock:
    """Sums what JAX reports spending on tracing, lowering and compiling
    (or reading from the persistent cache) for every jit in the process."""

    _DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  # wraps the persistent-cache lookup, so a hit's read time
                  # is in here too
                  "/jax/core/compile/backend_compile_duration")
    _CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_: Any) -> None:
        if event in self._DURATIONS:
            self.seconds += seconds
            self.programs += event == self._DURATIONS[-1]

    def _event(self, event: str, **_: Any) -> None:
        self.cache_hits += event == self._CACHE_HIT


def hbm_bytes(stat: str) -> List[int]:
    """One ``memory_stats()`` figure (``bytes_in_use``, or
    ``peak_bytes_in_use`` over the process lifetime) of every local device;
    0 where the backend keeps none."""
    return [int((d.memory_stats() or {}).get(stat, 0))
            for d in jax.local_devices()]


def kernel_names(lowered_text: str) -> List[str]:
    """Mosaic kernels in a lowered (StableHLO) program, by kernel name."""
    return sorted(set(re.findall(r'kernel_name = "([^"]+)"', lowered_text)))


def timed_phase(clock: CompileClock, label: str, fn, *args, **kwargs
                ) -> Dict[str, Any]:
    """Run one phase; add its compile/run split and the HBM high-water,
    then give the device memory back to the next phase."""
    t0, c0 = time.perf_counter(), clock.seconds
    p0, h0 = clock.programs, clock.cache_hits
    before = hbm_bytes("bytes_in_use")
    out = fn(*args, **kwargs)
    wall, compile_s = time.perf_counter() - t0, clock.seconds - c0
    out.update(compile_seconds=round(compile_s, 2),
               run_seconds=round(wall - compile_s, 2),
               programs=clock.programs - p0,
               programs_from_cache=clock.cache_hits - h0,
               hbm_in_use_before_bytes=before,
               peak_hbm_bytes=hbm_bytes("peak_bytes_in_use"))
    print(f"[chip_smoke] {label}: {json.dumps(out)}", flush=True)
    # the phase's engine sits in jit-closure reference cycles: collect it,
    # and the executables that pin its constants
    gc.collect()
    jax.clear_caches()
    return out


# ---------------------------------------------------------------------------
# phase 0: the device gate
# ---------------------------------------------------------------------------


def device_gate() -> Dict[str, Any]:
    """Refuse to continue on anything but a TPU; say what was found."""
    from importlib.metadata import version

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"[chip_smoke] device: {json.dumps(device)} jax={jax.__version__} "
          f"jaxlib={version('jaxlib')} libtpu={version('libtpu')}",
          flush=True)
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found platform {dev.platform!r} "
            f"({dev.device_kind}), not a TPU — nothing was run")
    return device


# ---------------------------------------------------------------------------
# phase 1: the trainer
# ---------------------------------------------------------------------------


def run_trainer(cfg: Any, seq: int, devices: int, steps: int = TRAIN_STEPS,
                tensor_parallel: int = 1) -> Dict[str, Any]:
    """``initialize()`` → ``steps`` × ``train_step`` on one repeated batch
    of one ``seq``-token row per data-parallel replica, over a mesh of the
    first ``devices`` devices."""
    import deepspeed_tpu
    from deepspeed_tpu.models import LlamaModel
    from deepspeed_tpu.parallel import MeshLayout
    from deepspeed_tpu.parallel.mesh import AXIS_DATA
    from deepspeed_tpu.utils import groups

    cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16, attn_impl="flash",
                              remat=True, loss_tiles=8)
    groups.reset_mesh()
    mesh = groups.initialize_mesh(
        MeshLayout.infer(devices, tp=tensor_parallel))
    world = int(mesh.devices.size)
    dp = int(mesh.shape[AXIS_DATA])
    model = LlamaModel(cfg, mesh=mesh)
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 3},
        "bf16": {"enabled": True},
        "steps_per_print": 0,
        # the compile tracker and StepRecords ride the telemetry hub;
        # nothing is written to disk
        "telemetry": {"enabled": True, "jsonl": False, "prometheus": False},
        # the run must not depend on a tuning store under ~/.cache
        "tuning": {"auto_apply": False},
    }
    if tensor_parallel > 1:
        config["tensor_parallel"] = {"autotp_size": tensor_parallel}
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init_params(jax.random.PRNGKey(0)),
        config=config, mesh=mesh)

    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, size=(dp, seq))
    batch = {"input_ids": jnp.asarray(ids, jnp.int32)}
    tracker = engine.compile_tracker
    losses, fence = [], {}
    compiles_after_first = None
    for step in range(steps):
        t0 = time.perf_counter()
        metrics = engine.train_step(batch)
        jax.block_until_ready(metrics["loss"])
        t1 = time.perf_counter()
        losses.append(float(metrics["loss"]))
        if step == steps - 1:
            # block_until_ready is the fence: the scalar fetch after it
            # has nothing left to wait for
            fence = {"block_until_ready_s": round(t1 - t0, 4),
                     "fetch_after_s": round(time.perf_counter() - t1, 6)}
        if step == 0:
            compiles_after_first = tracker.events_total
    recompiles = tracker.events_total - compiles_after_first

    step_fn = engine._train_step_fn
    kernels = kernel_names(step_fn.lower(engine.state, batch).as_text())
    executable, = step_fn.executables()
    hlo = executable.as_text()
    plan = executable.memory_analysis()
    n_params = sum(int(x.size) for x in jax.tree.leaves(engine.state.params))
    out = {
        "layers": cfg.num_layers, "params": n_params,
        "mesh": {a: int(s) for a, s in mesh.shape.items() if int(s) > 1},
        "batch": [dp, seq], "losses": [round(x, 4) for x in losses],
        "compiles_after_first_step": recompiles,
        "kernels": kernels, "fence": fence,
        "hbm_in_use_bytes": hbm_bytes("bytes_in_use"),
        # the compiler's per-device plan for the step: state in and out
        # (donated, so counted once) plus scratch
        "compiled_step_bytes": {
            "arguments": int(plan.argument_size_in_bytes),
            "temporaries": int(plan.temp_size_in_bytes)},
    }

    want = math.log(cfg.vocab_size)
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"trainer: non-finite loss in {losses}")
    if abs(losses[0] - want) > FIRST_LOSS_BAND:
        raise AssertionError(
            f"trainer: first loss {losses[0]:.3f} is not within "
            f"{FIRST_LOSS_BAND} of ln(vocab) = {want:.3f}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"trainer: loss did not fall: {losses}")
    if recompiles:
        raise AssertionError(
            f"trainer: {recompiles} compile event(s) after the first step: "
            f"{[e.site for e in tracker.events(last=recompiles)]}")
    if jax.default_backend() == "tpu" and not FLASH_KERNELS <= set(kernels):
        raise AssertionError(
            f"trainer: the lowered step lacks Mosaic flash kernels "
            f"{sorted(FLASH_KERNELS - set(kernels))} (found {kernels})")
    if world > 1:
        out.update(_check_sharded_state(engine, mesh, hlo))
    return out


def _check_sharded_state(engine: Any, mesh: Any, hlo: str) -> Dict[str, Any]:
    """Nothing may hide on the first chip: every parameter and optimizer
    leaf lives on all devices in equal shards, of at most 1/dp of its
    bytes (1/world where tensor parallelism splits it too) unless ZeRO-3
    keeps it whole as a small persisted parameter; the compiled step
    gathers and reduce-scatters; the devices hold about the same bytes."""
    from deepspeed_tpu.parallel.mesh import AXIS_DATA

    world = int(mesh.devices.size)
    dp = int(mesh.shape[AXIS_DATA])
    persisted_below = engine.policy.persistence_threshold
    fractions: Dict[str, int] = {}
    unsharded = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            (engine.state.params, engine.state.opt_state))[0]:
        if leaf.ndim == 0:
            continue            # step counters
        shards = leaf.addressable_shards
        devices = {s.device.id for s in shards}
        sizes = {int(s.data.nbytes) for s in shards}
        name = jax.tree_util.keystr(path)
        if len(devices) != world or len(sizes) != 1:
            raise AssertionError(
                f"multichip: {name} has shards of {sorted(sizes)} bytes on "
                f"devices {sorted(devices)}, not equal shards on {world}")
        denom = leaf.nbytes // sizes.pop()
        fractions[f"1/{denom}"] = fractions.get(f"1/{denom}", 0) + 1
        if denom < dp and leaf.size > persisted_below:
            unsharded.append(name)
    if unsharded:
        raise AssertionError(
            f"multichip: leaves not ZeRO-sharded over data={dp}: "
            f"{unsharded}")
    in_use = hbm_bytes("bytes_in_use")
    ratio = max(in_use) / max(min(in_use), 1)
    if ratio > MEMORY_BALANCE_RATIO:
        raise AssertionError(
            f"multichip: per-device bytes_in_use {in_use} differ by "
            f"{ratio:.2f}x (> {MEMORY_BALANCE_RATIO})")
    collectives = {op: len(re.findall(rf"\b{op}", hlo))
                   for op in ("all-gather", "reduce-scatter", "all-reduce")}
    # XLA's CPU backend leaves ZeRO's gradient reduction as all-reduce +
    # slice; the TPU compiler forms (all-)reduce-scatter
    needed = ["all-gather"] + (["reduce-scatter"]
                               if jax.default_backend() == "tpu" else [])
    if not all(collectives[op] for op in needed):
        raise AssertionError(
            f"multichip: the compiled step lacks {needed}: {collectives}")
    return {"shard_fractions": fractions,
            "memory_balance_ratio": round(ratio, 3),
            "collectives": collectives}


# ---------------------------------------------------------------------------
# phase 2: the server
# ---------------------------------------------------------------------------


def run_server(cfg: Any, prompt_lengths: Sequence[int] = PROMPT_LENGTHS,
               new_tokens: int = NEW_TOKENS,
               prefill_chunk: int = PREFILL_CHUNK,
               decode_burst: int = DECODE_BURST,
               reference_max_tokens: int = REFERENCE_MAX_TOKENS,
               ) -> Dict[str, Any]:
    """``build_serving_frontend()`` → a warm-up round and a checked round
    of the same request mix: every request streams ``new_tokens`` tokens
    and finishes, nothing compiles in the second round, and each served
    token is one the model's full forward pass also ranks (near) first."""
    from deepspeed_tpu.inference.v2 import KVCacheConfig
    from deepspeed_tpu.models import LlamaModel
    from deepspeed_tpu.serving import ServingParams, build_serving_frontend
    from deepspeed_tpu.telemetry.perf import configure_compile_tracker

    cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16, attn_impl="xla")
    model = LlamaModel(cfg)
    params = jax.jit(lambda key: jax.tree.map(
        lambda x: x.astype(cfg.dtype), model.init_params(key)))(
            jax.random.PRNGKey(1))
    tracker = configure_compile_tracker(enabled=True)
    slots = len(prompt_lengths)
    block = 16
    pages = sum(-(-(n + new_tokens) // block) for n in prompt_lengths)
    cache = KVCacheConfig(block_size=block, num_blocks=pages + 64,
                          max_seq_len=cfg.max_seq_len)
    frontend = build_serving_frontend(
        model, params, replicas=1, cache_config=cache,
        max_batch_slots=slots, prefill_chunk=prefill_chunk, prefill_batch=2,
        decode_burst=decode_burst,
        serving_params=ServingParams(
            max_outstanding_tokens=2 * sum(prompt_lengths) + slots * 1024))
    engine = frontend.router.replicas[0].engine

    def one_round(seed: int):
        rng = np.random.RandomState(seed)
        prompts = [rng.randint(0, cfg.vocab_size, size=n).tolist()
                   for n in prompt_lengths]
        handles = [frontend.submit(p, max_new_tokens=new_tokens)
                   for p in prompts]
        frontend.run_until_idle()
        streamed = [list(h.stream(timeout=60.0)) for h in handles]
        for h, toks in zip(handles, streamed):
            if h.status != "done" or len(toks) != new_tokens:
                raise AssertionError(
                    f"server: request {h.uid} ({len(h.prompt)} prompt "
                    f"tokens) ended {h.status!r} with {len(toks)} of "
                    f"{new_tokens} tokens")
            if not all(0 <= t < cfg.vocab_size for t in toks):
                raise AssertionError(
                    f"server: request {h.uid} streamed a token outside "
                    f"the vocabulary")
        return prompts, streamed

    one_round(seed=10)                    # warm-up: compiles every program
    compiled = tracker.events_total
    prompts, streamed = one_round(seed=11)
    recompiles = tracker.events_total - compiled

    decode = engine._decode(decode_burst)
    B, mb = engine.max_slots, cache.max_blocks_per_seq
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    kernels = kernel_names(decode.lower(
        engine.params, engine.pool, i32(B),
        (i32(B), i32(B + engine.prefill_batch)), i32(B), i32(B, mb), i32(B),
        jax.ShapeDtypeStruct((), jnp.float32), engine._key).as_text())

    # logit-level agreement with the plain forward pass, teacher-forced on
    # what the server emitted: prefill, then decoding through the cache
    forward = jax.jit(model.forward)
    worst, checked = 0.0, 0
    for prompt, toks in zip(prompts, streamed):
        if len(prompt) + new_tokens > reference_max_tokens:
            continue
        ids = jnp.asarray([prompt + toks[:-1]], jnp.int32)
        logits = forward(params, ids)[0, len(prompt) - 1:]   # [new, V]
        chosen = jnp.take_along_axis(
            logits, jnp.asarray(toks, jnp.int32)[:, None], axis=1)[:, 0]
        worst = max(worst, float(jnp.max(jnp.max(logits, axis=1) - chosen)))
        checked += 1
    out = {
        "layers": cfg.num_layers, "requests": slots,
        "prompt_tokens": list(prompt_lengths), "new_tokens": new_tokens,
        "tokens_streamed": sum(len(t) for t in streamed),
        "compiles_after_warmup": recompiles,
        "attn_path": engine.last_attn_path, "kernels": kernels,
        "requests_checked_against_forward": checked,
        "worst_logit_gap": round(worst, 4),
    }
    if recompiles:
        raise AssertionError(
            f"server: {recompiles} compile event(s) after the warm-up "
            f"round: {[e.site for e in tracker.events(last=recompiles)]}")
    if not checked:
        raise AssertionError("server: no request was short enough to check "
                             "against the forward pass")
    if not worst <= SERVE_LOGIT_TOL:
        raise AssertionError(
            f"server: a served token sits {worst:.3f} under the forward "
            f"pass's best logit (tolerance {SERVE_LOGIT_TOL})")
    if jax.default_backend() == "tpu" and (
            PAGED_KERNEL not in kernels
            or engine.last_attn_path != "pallas"):
        raise AssertionError(
            f"server: the decode burst does not run the paged Mosaic "
            f"kernel (path {engine.last_attn_path!r}, kernels {kernels})")
    frontend.close()
    return out


# ---------------------------------------------------------------------------
# phase 3: the kernels
# ---------------------------------------------------------------------------


def run_kernels(cfg: Any, interpret: bool = False) -> Dict[str, Any]:
    """Every Pallas kernel against its reference at this model's shapes
    (raises on the first family with a mismatch)."""
    from deepspeed_tpu.ops.pallas.selfcheck import KernelShapes, run_checks

    shapes = KernelShapes.for_model(cfg)
    results = run_checks(shapes, interpret=interpret)
    return {"shapes": {k: str(v) for k, v in
                       dataclasses.asdict(shapes).items()},
            "checks": {c.name: float(f"{c.error:.3g}") for c in results}}


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def main() -> int:
    device = device_gate()

    from deepspeed_tpu.models import LlamaConfig
    from deepspeed_tpu.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    clock = CompileClock()
    width = LlamaConfig.mistral_7b(dtype=jnp.bfloat16)
    seq = width.max_seq_len
    phases: Dict[str, Any] = {}
    n = device["count"]

    layers = lambda L: dataclasses.replace(width, num_layers=L)
    phases["trainer"] = timed_phase(
        clock, "trainer", run_trainer, layers(TRAIN_LAYERS), seq, devices=1)
    phases["server"] = timed_phase(clock, "server", run_server,
                                   layers(SERVE_LAYERS))
    phases["kernels"] = timed_phase(clock, "kernels", run_kernels, width)
    if n > 1:
        label = f"trainer_data{n}"
        phases[label] = timed_phase(
            clock, label, run_trainer, layers(MULTICHIP_LAYERS), seq,
            devices=n)
        if n % 2 == 0:
            label = f"trainer_tensor2_data{n // 2}"
            phases[label] = timed_phase(
                clock, label, run_trainer, layers(MULTICHIP_LAYERS), seq,
                devices=n, tensor_parallel=2)

    summary = {"compile_cache_dir": cache_dir,
               "compile_seconds": round(clock.seconds, 2),
               "phases": phases, "claim": None}
    print(f"[chip_smoke] summary: {json.dumps(summary)}", flush=True)
    # the result: whoever runs the smoke parses this line only, and takes
    # no key beside ``ok`` and the device of ``device_gate()``
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
