"""Benchmark: flagship Llama training throughput + MFU on the available chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.

Headline metric: training tokens/sec on the SAME ~110M-param Llama config as
round 1 (bf16, flash attention, fused single-program step) so ``vs_baseline``
is a ratio against the round-1 number of the pre-round chip record (git
history at ``4d1f3b8``: 35367.7 tok/s; BASELINE.json ``published`` is {} —
there is no driver-verified reference number, see BASELINE.md).

It runs on a TPU or not at all: with no chip it exits non-zero and prints
no metric.  ``ROADMAP.md`` S1/D1 replaces this file with a table of cells;
until then ``chip_smoke.py`` is the quick proof that the system starts.

Extras in the same JSON line:
- ``kernels_verified``  — ``ops/pallas/selfcheck.run_checks`` passed at
                          Mistral-7B shapes before the headline (a failed
                          check ends the run; ``--selfcheck`` runs it
                          standalone).
- ``mfu``               — achieved model FLOP/s over the chip's bf16 peak
                          (analytic 6N + attention FLOPs; remat recompute
                          and optimizer math excluded per MFU convention).
- ``peak_hbm_bytes``    — HBM high-water of the headline run
                          (``memory_stats().peak_bytes_in_use``); gated
                          by ``telemetry perf check`` (lower is better,
                          10% tolerance + 64 MiB absolute floor).
- ``hbm_headroom_frac`` — 1 - peak/limit: how much HBM the headline
                          config leaves free (higher is better; the
                          autotuning search budget).
- ``tuned_config_source`` — which best-known-config store entry the tuned
                          run applied (``<store path>::<key>``; "none" on
                          a store miss, "error: ..." when the tuned run
                          died).  The headline itself NEVER changes config
                          (cross-round comparability); the tuned run is a
                          separate engine build from the store entry.
- ``tuned_mfu``         — MFU of the tuned run; gated by ``telemetry perf
                          check`` so a bad promotion or stale seed gates
                          like a code regression.  ``tuned_vs_default_
                          mfu_delta`` is the same number minus the
                          headline ``mfu``.
- ``flash_speedup_s{2048,8192,32768}`` — Pallas flash attention
                          (fwd+bwd, causal) vs the XLA reference ladder
                          rung at that seq length (dense masked ref to
                          8k, chunked online-softmax scan at 32k).
                          Gated; the dispatch contract is >= 1.0 at
                          every benched length.
- ``block_sparse_speedup_s4096`` — block-sparse kernel vs its own dense
                          fallback at 4k; with choose_impl's crossover
                          auto-dispatch a sub-1.0 value is a dispatch
                          bug.  Gated (was variants-only before r05).
- ``fused_adam_hbm_gbps`` — the one-pass fused Adam kernel's effective
                          HBM GB/s over the same 7-floats/param
                          accounting as ``optax_adam_hbm_gbps``
                          (variants).  Gated; acceptance is fused >
                          optax.
- ``overlap_hiding_frac`` — share of the all-gather's serialized cost
                          the chunked-ppermute ring buries under the
                          matmul it feeds (variants.overlap carries the
                          raw timings).  Gated.
- ``variants``          — driver-ladder configs (BASELINE.md): BERT-large
                          ZeRO-2, llama3-8B-shaped ZeRO-3 slice, Mixtral
                          MoE on inference v2; plus the shape-tuned MFU
                          ceiling, v2 ragged serving, the block-sparse
                          kernel speedup, and the ZeRO-Offload overlap
                          breakdown.
"""

from __future__ import annotations

import functools
import gc
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

# round-1 headline of the pre-round chip record — the cross-round baseline
R01_TOKENS_PER_SEC = 35367.7

def peak_flops_per_chip() -> float:
    # single source of truth for the per-kind peak table
    from deepspeed_tpu.profiling.flops_profiler.profiler import (
        peak_flops_per_chip as _peak)

    return _peak()


def hbm_bytes() -> int:
    try:
        stats = jax.devices()[0].memory_stats() or {}
        return int(stats.get("bytes_limit", 0))
    except Exception:
        return 0


def free_hbm() -> None:
    """Collect + clear jit caches so a variant's HBM comes back even after
    an exception mid-build (an OOM'd variant must not poison the rest of
    the bench).  Callers must ``del`` their own references first — passing
    them here could never drop the caller's binding."""
    gc.collect()
    try:
        jax.clear_caches()
    except Exception:
        pass


def build_engine(cfg, batch, zero_stage=0, offload=False, bf16=True,
                 model_cls=None, gas=1, ds_extra=None):
    import deepspeed_tpu
    from deepspeed_tpu.models import LlamaModel
    from deepspeed_tpu.parallel import MeshLayout
    from deepspeed_tpu.utils import groups

    ds_extra = dict(ds_extra or {})
    ker = dict(ds_extra.get("kernels") or {})
    if ker.get("flash_attention") and hasattr(cfg, "attn_impl"):
        # the kernels.flash_attention config knob routes model attention
        # through the Pallas kernel family (same contract initialize()'s
        # tuned model_overrides use)
        import dataclasses as _dc

        repl = {"attn_impl": "flash"}
        if hasattr(cfg, "flash_block_q"):
            repl["flash_block_q"] = int(ker.get("flash_block_q", 0) or 0)
            repl["flash_block_k"] = int(ker.get("flash_block_k", 0) or 0)
        cfg = _dc.replace(cfg, **repl)

    layout = MeshLayout.infer(1, dp=1)
    mesh = groups.initialize_mesh(layout)
    model = (model_cls or LlamaModel)(cfg, mesh=mesh)
    params = model.init_params(jax.random.PRNGKey(0))
    zero: dict = {"stage": zero_stage}
    if offload:
        zero["offload_optimizer"] = {"device": "cpu"}
    ds_config = {
        "train_micro_batch_size_per_gpu": batch,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "zero_optimization": zero,
        "bf16": {"enabled": bf16},
        "steps_per_print": 0,
        # engine-side StepRecords are THE measured numbers (ISSUE 1: bench
        # reports what the engine logged, so artifacts and telemetry can
        # never disagree); in-memory only — no file exporters in a bench
        "telemetry": {"enabled": True, "jsonl": False, "prometheus": False},
        # bench engines pin their exact config: a promoted store entry
        # must not silently shift the headline across rounds (the tuned
        # variant applies its store entry's overrides explicitly)
        "tuning": {"auto_apply": False},
    }
    ds_config.update(ds_extra)
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=ds_config, mesh=mesh)
    return engine


def _sync(metrics) -> float:
    """Device fence: the last step's loss depends on every enqueued step
    through the state chain, so waiting for it waits for them all."""
    return float(jax.block_until_ready(metrics["loss"]))


def measure(engine, batch, seq, vocab, steps, segments=3,
            budget_s: float = 120.0, data=None):
    """Median-of-segments tokens/sec with a wall-clock budget: a slow
    config (e.g. optimizer offload) degrades to fewer steps
    instead of hanging the driver's bench run."""
    if data is None:
        ids = jnp.asarray(np.random.RandomState(0).randint(
            0, vocab, size=(batch, seq)))
        data = {"input_ids": ids}
    _sync(engine.train_step(data))  # compile + warmup
    # probe one step to right-size the per-segment step count
    t0 = time.perf_counter()
    _sync(engine.train_step(data))
    per_step = max(time.perf_counter() - t0, 1e-4)
    steps = max(1, min(steps, int(budget_s / (segments * per_step))))
    rates = []
    records = getattr(engine, "step_records", None)
    for _ in range(segments):
        # step-id marker, not a length index: the deque's maxlen eviction
        # would freeze a length-based cursor once it wraps
        mark = records[-1].step if records else 0
        t0 = time.perf_counter()
        for _ in range(steps):
            m = engine.train_step(data)
        _sync(m)
        wall = time.perf_counter() - t0
        segment = ([r for r in records if r.step > mark and r.device_fenced]
                   if records is not None else [])
        if segment:
            # the engine's OWN device-fenced StepRecords are the measured
            # numbers — the bench just aggregates them, so the emitted
            # metric line and the engine telemetry cannot disagree.
            # Cross-check against wall: record assembly/export overhead
            # is real run cost, so if the per-step device sum diverges
            # from wall by >5% the (cross-round-comparable, conservative)
            # wall number wins.
            dev_s = sum(r.step_time_ms for r in segment) / 1e3
            denom = dev_s if abs(wall - dev_s) <= 0.05 * wall else wall
            rates.append(batch * seq * len(segment) / max(denom, 1e-9))
        else:  # engine without telemetry: fall back to wall clock
            rates.append(batch * seq * steps / wall)
    return sorted(rates)[len(rates) // 2]


def _perf_extras(engine) -> dict:
    """Perf-sentinel fields for the BENCH line (telemetry/perf):
    step-time p50 from the engine's own device-fenced StepRecords,
    cumulative compile seconds from the compile tracker, and the run's
    goodput fraction — the metrics `telemetry perf check` gates on."""
    out: dict = {}
    try:
        recs = [r for r in getattr(engine, "step_records", [])
                if r.device_fenced]
        if recs:
            times = sorted(r.step_time_ms for r in recs)
            out["step_time_p50_ms"] = round(times[len(times) // 2], 2)
        from deepspeed_tpu.telemetry.perf import (get_compile_tracker,
                                                  get_goodput_ledger)

        trk = get_compile_tracker()
        if trk.enabled and trk.events_total:
            out["compile_time_s"] = round(trk.time_ms_total / 1e3, 3)
            out["compile_events"] = trk.events_total
            out["recompile_events"] = trk.recompiles_total
        gp = get_goodput_ledger()
        if gp.enabled and gp.total_seconds() > 0:
            out["goodput"] = round(gp.goodput(), 4)
        # memory plane (telemetry/memory): HBM high-water + headroom in
        # the baseline, so `telemetry perf check` gates memory
        # regressions the same way it gates throughput
        stats = jax.local_devices()[0].memory_stats() or {}
        peak = int(stats.get("peak_bytes_in_use", 0) or 0)
        limit = int(stats.get("bytes_limit", 0) or 0)
        if peak:
            out["peak_hbm_bytes"] = peak
        if peak and limit:
            out["hbm_headroom_frac"] = round(1.0 - peak / limit, 4)
    except Exception as e:
        out["perf_extras_error"] = str(e)[:120]
    return out


def step_flops(engine, batch, seq, vocab, cfg) -> float:
    """MODEL FLOPs per step — the analytic 6N + attention formula (the MFU
    convention: remat recompute and optimizer math don't count, so neither
    XLA cost_analysis (counts recompute) nor hardware counters apply)."""
    n_params = sum(int(x.size) for x in jax.tree.leaves(engine.state.params))
    per_tok = 6 * n_params + 12 * cfg.num_layers * seq * cfg.hidden_size
    return float(per_tok * batch * seq)


def selfcheck() -> None:
    """The on-chip kernel numerics gate: every Pallas kernel against its
    reference at Mistral-7B shapes (the one copy lives in
    ``deepspeed_tpu/ops/pallas/selfcheck.py``; raises on any mismatch)."""
    from deepspeed_tpu.models import LlamaConfig
    from deepspeed_tpu.ops.pallas.selfcheck import KernelShapes, run_checks

    run_checks(KernelShapes.for_model(
        LlamaConfig.mistral_7b(dtype=jnp.bfloat16)))


_T0 = time.time()

#: bench-wide wall budget: once exceeded, remaining variants SKIP (the
#: except path records it) so the driver always gets the complete JSON
#: line — a cold compile cache costs ~10 min for everything; the budget
#: bounds the emit at ~8 (warm runs finish everything in ~3.5).
_BUDGET_S = float(os.environ.get("DS_BENCH_BUDGET_S", "780"))


class _BudgetExceeded(RuntimeError):
    pass


def _budget_check() -> None:
    spent = time.time() - _T0
    if spent > _BUDGET_S:
        raise _BudgetExceeded(
            f"skipped: bench budget exceeded ({spent:.0f}s > {_BUDGET_S:.0f}s"
            f" — cold compile cache; warm reruns cover this variant)")


def _mark(name: str) -> None:
    """Section progress to stderr (driver logs) — finding the slow stage
    of a 10-minute bench without rerunning it piecewise."""
    print(f"[bench +{time.time() - _T0:7.1f}s] {name}", file=sys.stderr,
          flush=True)



def serve_v2_throughput(model, prompts, max_new: int, *,
                        cache_blocks: int = 512, max_seq_len: int = 1024,
                        decode_burst: int = 32) -> float:
    """Shared v2 serving measurement: build the ragged engine, warm up
    BOTH compiled programs (prefill batch + the full decode burst — an
    unwarmed burst would compile inside the measured run), then time one
    ragged generate."""
    from deepspeed_tpu.inference.v2 import KVCacheConfig, build_engine_v2
    from deepspeed_tpu.parallel import MeshLayout
    from deepspeed_tpu.utils import groups

    groups.reset_mesh()
    groups.initialize_mesh(MeshLayout.infer(1, dp=1))
    params = model.init_params(jax.random.PRNGKey(0))
    eng = build_engine_v2(
        model, params,
        cache_config=KVCacheConfig(num_blocks=cache_blocks, block_size=16,
                                   max_seq_len=max_seq_len),
        max_batch_slots=8, prefill_chunk=128, prefill_batch=4,
        decode_burst=decode_burst)
    # warm EVERY program the timed run will hit: both decode shapes AND
    # every prefill page-bucket the prompt mix reaches (bucketed prefill
    # compiles per power-of-two depth — a mid-run compile would land in
    # the measured window)
    eng.generate(prompts, max_new_tokens=max_new)
    eng.generate(prompts, max_new_tokens=max_new)
    tps = eng.last_throughput
    del eng, params
    free_hbm()
    return tps


def _bench_llama8b_infinity(batch: int = 2, seq: int = 2048) -> dict:
    """Full-depth Llama-3-8B ZeRO-Infinity measurement (see call site)."""
    import deepspeed_tpu
    from deepspeed_tpu.models import LlamaConfig, LlamaModel
    from deepspeed_tpu.ops.op_builder import CPUAdamBuilder

    if not CPUAdamBuilder.is_compatible():
        raise RuntimeError("no g++ toolchain for the fused C++ Adam")
    L = 32
    per_layer = (4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336
                 + 2 * 4096)
    with open("/proc/meminfo") as f:
        avail = {ln.split(":")[0]: int(ln.split()[1])
                 for ln in f}["MemAvailable"] * 1024
    # planes 14 B/param + fp16 source 2 B/param + 8G slack
    while L > 4 and avail < L * per_layer * 16 + 8e9:
        L -= 4  # degrade on small-RAM hosts; reported in the result
    cfg = LlamaConfig(vocab_size=32000, hidden_size=4096,
                      intermediate_size=14336, num_layers=L,
                      num_heads=32, num_kv_heads=8, max_seq_len=seq,
                      rope_theta=500000.0, dtype=jnp.bfloat16,
                      attn_impl="flash", remat=True, loss_tiles=8,
                      tie_embeddings=False)
    model = LlamaModel(cfg)  # single-chip streaming (mesh=None)

    # host-side param synthesis: throughput doesn't depend on values (the
    # MXU runs dense matmuls regardless), so the trunk is fp32 zeros —
    # calloc'd virtual pages, no RAM touched until the planes read them,
    # and no fp16 casts (numpy fp16 paths run ~170 MB/s, which would put
    # minutes into seeding an 8B tree).  jax init of an 8B tree would OOM
    # the 16G chip and crawl on host PRNG.
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    def synth(sd):
        n = int(np.prod(sd.shape))
        if n <= (1 << 26):  # resident leaves get real values (loss sanity)
            return (rng.random(n, dtype=np.float32) * 0.02).reshape(sd.shape)
        return np.zeros(sd.shape, np.float32)

    params = jax.tree.map(synth, shapes)
    _mark("8b: params synthesized")
    ds = {"train_micro_batch_size_per_gpu": batch,
          "gradient_accumulation_steps": 1,
          "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
          "zero_optimization": {"stage": 3,
                                "offload_param": {"device": "cpu"}},
          "bf16": {"enabled": True}, "steps_per_print": 0}
    # Plane seeding bypass: copying 43 GB of zeros through numpy's
    # single-core bf16 cast costs ~8 minutes and changes NOTHING the
    # bench measures (the trunk is zeros either way; planes are
    # zero-initialized).  The planes stay allocated at full depth and
    # every h2d/d2h moves real bytes; only the redundant zero-copy is
    # skipped.  The REAL fill path is exercised by test_infinity.py.
    from deepspeed_tpu.runtime.swap_tensor import (
        partitioned_param_swapper as _pps)

    _orig_fill = _pps.PartitionedParamSwapper._fill_planes
    _pps.PartitionedParamSwapper._fill_planes = \
        lambda self, planes, tree, zero_moments=True: None
    try:
        eng, *_ = deepspeed_tpu.initialize(model=model,
                                           model_parameters=params,
                                           config=ds)
    finally:
        _pps.PartitionedParamSwapper._fill_planes = _orig_fill
    _mark("8b: engine built (planes allocated, resident placed)")
    del params
    inf = eng.infinity
    sw = inf.swapper
    n_params = inf.total_param_count()

    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(batch, seq)))
    b = {"input_ids": ids}

    def block(t):
        """Device fence (``jax.block_until_ready`` waits for the device
        on this platform — checked on the v5e, PR 21)."""
        return jax.block_until_ready(t)

    times: dict = {}
    # ---- embed + warmup layer 0 (compiles layer_fwd) --------------------
    block(inf._fn("embed")(inf.resident, ids))  # compile + resident cast
    t0 = time.perf_counter()
    x = block(inf._fn("embed")(inf.resident, ids))
    times["embed_s"] = time.perf_counter() - t0
    _mark("8b: embed done")
    acts = {}
    t0 = time.perf_counter()
    lp = block(sw.get_device(0))
    acts[0] = x
    x, _aux = block(inf._fn("layer_fwd")(lp, x))
    sw.release(0)
    warm_fwd = time.perf_counter() - t0  # includes h2d AND compile
    _mark(f"8b: fwd warmup {warm_fwd:.1f}s")

    # ---- measured fwd layers (steady-state, no compile) -----------------
    k_fwd = 2
    h2d, fwd = [], []
    for i in range(1, 1 + k_fwd):
        t0 = time.perf_counter()
        lp = block(sw.get_device(i))
        h2d.append(time.perf_counter() - t0)
        acts[i] = x
        t0 = time.perf_counter()
        x, _aux = block(inf._fn("layer_fwd")(lp, x))
        fwd.append(time.perf_counter() - t0)
        sw.release(i)
    times["h2d_per_layer_s"] = sorted(h2d)[len(h2d) // 2]
    times["fwd_per_layer_s"] = sorted(fwd)[len(fwd) // 2]

    # ---- head loss + grad (resident) ------------------------------------
    block(inf._fn("head_grad")(inf.resident, x, b)[0])  # compile
    t0 = time.perf_counter()
    loss, (g_res, dx) = inf._fn("head_grad")(inf.resident, x, b)
    block(loss)
    times["head_s"] = time.perf_counter() - t0
    _mark("8b: head done")
    if not np.isfinite(float(loss)):
        raise RuntimeError(f"non-finite loss {float(loss)}")

    # ---- bwd: warmup (compile) + one measured layer ---------------------
    i = 1 + k_fwd - 1  # deepest measured layer, acts stashed
    t0 = time.perf_counter()
    lp = block(sw.get_device(i))
    dx2, dlp = inf._fn("layer_bwd")(lp, acts[i], dx)
    block(dx2)
    sw.release(i)
    warm_bwd = time.perf_counter() - t0
    _mark(f"8b: bwd warmup {warm_bwd:.1f}s")
    bwd_times = []
    dprev = dx2
    for j in range(i - 1, max(i - 3, -1), -1):
        lp = block(sw.get_device(j))  # h2d timed in fwd
        t0 = time.perf_counter()
        dprev, dlp = inf._fn("layer_bwd")(lp, acts[j], dprev)
        block(dprev)
        bwd_times.append(time.perf_counter() - t0)
        sw.release(j)
    times["bwd_per_layer_s"] = sorted(bwd_times)[len(bwd_times) // 2]
    # grad d2h timed as an explicit host fetch, then the fused C++ Adam
    # gets the ALREADY-FETCHED numpy tree so its timing is host-only
    # (np.asarray on the device tree again would re-pay the link)
    t0 = time.perf_counter()
    g_host = jax.tree.map(np.asarray, dlp)
    times["grad_d2h_per_layer_s"] = time.perf_counter() - t0
    sw.begin_step()
    sw.step_layer(i, g_host, lr=1e-4)  # first touch faults in m/v planes
    t0 = time.perf_counter()
    sw.step_layer(i, g_host, lr=1e-4)  # steady-state host Adam
    times["host_adam_per_layer_s"] = time.perf_counter() - t0
    times["d2h_adam_per_layer_s"] = (times["grad_d2h_per_layer_s"]
                                     + times["host_adam_per_layer_s"])

    # ---- pipelined update: the REAL overlapped bwd phase ----------------
    # (reference pipelined_optimizer_swapper role, VERDICT r4 item 2):
    # replay two full bwd+update layers through the production path —
    # h2d, vjp, then step_layer_async handing d2h+C++ Adam to the worker
    # while the next layer's h2d/vjp proceed.  The measured wall clock IS
    # the per-layer cost of the pipelined backward phase; the serial
    # composition of the same phases is the number it beats.
    k_pipe = 2
    assert sw._pipe is not None, "pipelined swapper must be the default"
    sw.drain_updates()
    t0 = time.perf_counter()
    dp_ = dx2
    for j in range(i, i - k_pipe, -1):
        lp_j = sw.get_device(j)
        dp_, dlp_j = inf._fn("layer_bwd")(lp_j, acts[j], dp_)
        sw.step_layer_async(j, dlp_j, lr=1e-4)
        sw.release(j)
    block(dp_)
    sw.drain_updates()
    pipe_wall = time.perf_counter() - t0
    serial_sum = k_pipe * (times["h2d_per_layer_s"]
                           + times["bwd_per_layer_s"]
                           + times["d2h_adam_per_layer_s"])
    times["pipelined_bwd_layer_s"] = pipe_wall / k_pipe
    times["serial_bwd_layer_s"] = serial_sum / k_pipe
    overlap_win = serial_sum / pipe_wall if pipe_wall > 0 else 1.0

    # ---- compose the full step ------------------------------------------
    # backward phase composes at the MEASURED pipelined per-layer cost
    # (d2h + host Adam overlap h2d + vjp of the next layer); forward is
    # unchanged (no update work to hide there)
    proj = (times["embed_s"] + times["head_s"]
            + L * (times["h2d_per_layer_s"] + times["fwd_per_layer_s"])
            + L * times["pipelined_bwd_layer_s"])
    result = {"layers": L, "params": int(n_params), "batch": batch,
              "seq": seq, "phases": {k: round(v, 3)
                                     for k, v in times.items()},
              "warmup_fwd_s": round(warm_fwd, 2),
              "warmup_bwd_s": round(warm_bwd, 2),
              "optimizer_overlap": {
                  "pipelined_bwd_layer_s": round(pipe_wall / k_pipe, 3),
                  "serial_bwd_layer_s": round(serial_sum / k_pipe, 3),
                  "overlap_win": round(overlap_win, 3),
                  "host_cores": os.cpu_count()}}
    peak = peak_flops_per_chip()
    remaining = _BUDGET_S - (time.time() - _T0)
    if proj < min(remaining - 30, 180):
        # the link can carry a real step — run the engine's actual
        # train_step end to end and use the measured number
        _sync(eng.train_step(b))  # warm (fills any remaining compiles)
        t0 = time.perf_counter()
        _sync(eng.train_step(b))
        step_s = time.perf_counter() - t0
        result["projected"] = False
    else:
        step_s = proj
        result["projected"] = True
        result["projection_note"] = (
            "host<->device link cannot carry a full streamed step inside "
            "the bench budget; step_s composes per-layer phases measured "
            "on the real chip at full depth (streaming is layer-linear; "
            "each phase includes one ~0.1s fence round-trip, so the "
            "composition is conservative).  The backward phase uses the "
            "MEASURED pipelined per-layer wall clock (worker-thread d2h+"
            "Adam overlapping the next layer's h2d+vjp), not the serial "
            "phase sum — see optimizer_overlap")
    tps = batch * seq / step_s
    result["step_s"] = round(step_s, 2)
    result["tokens_per_sec"] = round(tps, 3)
    result["mfu"] = round(6.0 * n_params * tps / peak, 5)
    # compute-only view: what the same step costs with the link excluded —
    # the upper bound a locally-attached host (PCIe/DMA) approaches.
    # With the pipelined optimizer the host Adam overlaps the device
    # backward, so the bwd phase costs max(vjp, adam) per layer, not the
    # sum; this box has os.cpu_count() core(s) for the OpenMP Adam, while
    # a TPU-VM host has ~100+ — host_adam/cores drops below the vjp time
    # there and the step becomes fwd+bwd-bound (the reference's
    # pipelined_optimizer_swapper steady state)
    compute_s = (times["embed_s"] + times["head_s"]
                 + L * (times["fwd_per_layer_s"]
                        + max(times["bwd_per_layer_s"],
                              times["host_adam_per_layer_s"])))
    result["compute_only_tokens_per_sec"] = round(batch * seq / compute_s, 1)
    result["compute_only_mfu"] = round(
        6.0 * n_params * (batch * seq / compute_s) / peak, 4)
    # the same law with the Adam spread over a TPU-VM-class host (96
    # cores): what THIS code does on real hardware, stated as arithmetic
    adam96 = times["host_adam_per_layer_s"] * os.cpu_count() / 96.0
    c96 = (times["embed_s"] + times["head_s"]
           + L * (times["fwd_per_layer_s"]
                  + max(times["bwd_per_layer_s"], adam96)))
    result["compute_only_96core_tokens_per_sec"] = round(
        batch * seq / c96, 1)
    result["compute_only_96core_mfu"] = round(
        6.0 * n_params * (batch * seq / c96) / peak, 4)
    del eng, inf, sw, acts
    free_hbm()
    return result


def _bench_offload_overlap_synthetic() -> dict:
    """Overlap proof where the LINK IS NOT the bottleneck (VERDICT r4
    item 7): device compute (real TPU matmul chains, async dispatch) vs
    the host fused C++ Adam (production ``_OptPipeline`` worker), with
    grads already host-resident so no host-device bytes move.  Serial = the
    two phases back to back (device fenced, then L sync updates);
    pipelined = the production ``step_layer_async`` interleaving — the
    wall clock approaches max(Σdev, Σadam) instead of the sum.  Sized so
    T_dev ≈ T_adam per layer (the regime where overlap matters most)."""
    from deepspeed_tpu.ops.op_builder import CPUAdamBuilder
    from deepspeed_tpu.runtime.swap_tensor.partitioned_param_swapper import (
        PartitionedParamSwapper)

    if not CPUAdamBuilder.is_compatible():
        raise RuntimeError("no g++ toolchain for the fused C++ Adam")
    L, n = 10, 6_000_000
    mk = lambda pipe: PartitionedParamSwapper(
        [{"w": np.zeros((n,), np.float32)} for _ in range(L)],
        wire_dtype=jnp.bfloat16, adam_hparams={"lr": 1e-3}, pipeline=pipe)
    g_host = {"w": (np.random.RandomState(0).rand(n) * 1e-3
                    ).astype(np.float32)}
    x = jnp.ones((1024, 1024), jnp.bfloat16)

    def fence(y):
        float(jnp.sum(y.ravel()[:1].astype(jnp.float32)))

    # calibrate: one layer's sync host Adam, then a device chain of
    # similar cost (K matmuls; 1024^3 MACs ≈ 11us each at peak — scale up)
    sw_s = mk(False)
    sw_s.begin_step()
    sw_s.step_layer(0, g_host)  # warm (faults planes in)
    t0 = time.perf_counter()
    sw_s.step_layer(0, g_host)
    t_adam = time.perf_counter() - t0

    def devchain(x, K):
        def body(c, _):
            return (c @ c) * jnp.bfloat16(1e-3) + c, None
        return jax.lax.scan(body, x, None, length=K)[0]

    # dispatch-free calibration: difference two chain lengths (a single
    # fenced call carries a fixed dispatch + fence cost that would shrink
    # the chain to ~zero real compute)
    def timed(K, reps=3):
        f = jax.jit(functools.partial(devchain, K=K))
        fence(f(x))
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fence(f(x))
            ts.append(time.perf_counter() - t0)
        return min(ts)
    per_mm = max((timed(512) - timed(64)) / 448, 2e-6)
    K = max(32, int(t_adam / per_mm))
    dc = jax.jit(functools.partial(devchain, K=K))
    fence(dc(x))
    t_dev = K * per_mm

    # serial: all device work (one fence), then L sync updates
    t0 = time.perf_counter()
    y = x
    for _ in range(L):
        y = dc(y)
    fence(y)
    for i in range(L):
        sw_s.step_layer(i, g_host)
    serial = time.perf_counter() - t0

    # pipelined: production async path — worker Adam behind device chains
    sw_p = mk(True)
    sw_p.begin_step()
    sw_p.step_layer_async(0, g_host)  # warm worker path
    sw_p.drain_updates()
    t0 = time.perf_counter()
    y = x
    for i in range(L):
        y = dc(y)
        sw_p.step_layer_async(i, g_host)
    fence(y)
    sw_p.drain_updates()
    piped = time.perf_counter() - t0
    win = serial / piped if piped > 0 else 1.0
    del sw_s, sw_p
    return {"layers": L, "plane_params": n,
            "t_adam_layer_s": round(t_adam, 4),
            "t_dev_layer_s": round(t_dev, 4),
            "serial_s": round(serial, 4), "pipelined_s": round(piped, 4),
            "overlap_win": round(win, 3)}


def _bench_infinity_sp_miniature() -> dict:
    """Ladder config 5's COMPOSITION, miniature, on the real chip: Llama
    trunk + Ulysses SP machinery (mesh-routed attention, SP dataloader
    adapter, sequence-tiled loss) + ZeRO-Infinity layer streaming, all in
    ONE run (VERDICT r4 item 1).

    One physical chip means the seq axis is size 1 — the all-to-all is a
    no-op here (``sp1_no_op: true`` in the result says so) — but every
    composed code path executes end-to-end on TPU: the streamed per-layer
    programs are the SAME jits the fake-8 dp2×sp2(×tp2) equality tests
    (tests/unit/runtime/test_infinity_sp.py) and the ``infinity_sp``
    dryrun layout prove correct at sp>1."""
    import deepspeed_tpu
    from deepspeed_tpu.models import LlamaConfig, LlamaModel
    from deepspeed_tpu.ops.op_builder import CPUAdamBuilder
    from deepspeed_tpu.parallel import MeshLayout
    from deepspeed_tpu.runtime.sequence_parallel.ulysses_sp import (
        UlyssesSPDataLoaderAdapter)
    from deepspeed_tpu.utils import groups

    if not CPUAdamBuilder.is_compatible():
        raise RuntimeError("no g++ toolchain for the fused C++ Adam")
    groups.reset_mesh()
    mesh = groups.initialize_mesh(MeshLayout.infer(1, sp=1))
    batch, seq = 4, 1024
    cfg = LlamaConfig(vocab_size=2048, hidden_size=256,
                      intermediate_size=688, num_layers=3, num_heads=8,
                      num_kv_heads=4, max_seq_len=seq, dtype=jnp.bfloat16,
                      attn_impl="flash", loss_tiles=4)
    model = LlamaModel(cfg, mesh=mesh)
    params = model.init_params(jax.random.PRNGKey(0))
    ds = {"train_micro_batch_size_per_gpu": batch,
          "gradient_accumulation_steps": 1,
          "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
          "zero_optimization": {"stage": 3,
                                "offload_param": {"device": "cpu"}},
          "bf16": {"enabled": True}, "steps_per_print": 0}
    eng, *_ = deepspeed_tpu.initialize(model=model, model_parameters=params,
                                       config=ds, mesh=mesh)
    assert eng.infinity is not None

    ids = np.random.RandomState(0).randint(0, cfg.vocab_size,
                                           size=(batch, seq))
    loader = UlyssesSPDataLoaderAdapter(
        [{"input_ids": jnp.asarray(ids)}] * 4)
    batches = list(loader)
    eng.train_step(batches[0])  # warm every per-layer program
    t0 = time.perf_counter()
    steps = 2
    for k in range(steps):
        m = eng.train_step(batches[(k + 1) % len(batches)])
    loss = float(m["loss"])  # fences the streamed tail
    dt = (time.perf_counter() - t0) / steps
    assert np.isfinite(loss)
    n_params = eng.infinity.total_param_count()
    del eng, params, batches, loader
    free_hbm()
    return {"tokens_per_sec": round(batch * seq / dt, 1),
            "step_s": round(dt, 3), "loss": round(loss, 4),
            "params": n_params, "layers": cfg.num_layers,
            "sp1_no_op": True, "loss_tiles": cfg.loss_tiles}


def _emit_crash_line(e: BaseException, reason: str = "bench unhandled "
                     "exception") -> str:
    """Crash path of the one-JSON-line contract (ISSUE 2): dump a flight-
    recorder debug bundle and record its path in the BENCH artifact so a
    dead bench leaves the operator a post-mortem, not just an exit code.
    Returns the bundle path ("" if even the dump failed)."""
    import traceback

    from deepspeed_tpu.telemetry import get_flight_recorder

    path = ""
    try:
        path = get_flight_recorder().dump(
            f"{reason}: {type(e).__name__}: {e}",
            extra={"traceback": traceback.format_exc()})
    except Exception:
        pass  # the JSON line below must go out regardless
    print(json.dumps({
        "metric": "llama_110m_train_tokens_per_sec",
        "value": 0.0, "unit": "tokens/sec/chip", "vs_baseline": 0.0,
        "error": f"{type(e).__name__}: {e}"[:300],
        "debug_bundle": path,
    }))
    sys.stdout.flush()
    return path


def main() -> None:
    try:
        _main()
    except SystemExit:
        raise
    except KeyboardInterrupt:
        raise
    except BaseException as e:
        _emit_crash_line(e)
        sys.exit(4)


def _main() -> None:
    from deepspeed_tpu.models import LlamaConfig

    from deepspeed_tpu.utils.compile_cache import configure_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        # a measurement path that finds no chip fails; it does not fall
        # back to the CPU and print a metric under a device's name
        raise SystemExit(f"bench.py: JAX found platform {dev.platform!r} "
                         f"({dev.device_kind}), not a TPU — nothing measured")
    configure_compile_cache()
    extras: dict = {}

    if "--selfcheck" in sys.argv:
        selfcheck()
        print(json.dumps({"kernels_verified": True}))
        return

    _mark("selfcheck")
    # -- kernel numerics gate: runs BEFORE the headline; a failed check
    # raises and ends the run -----------------------------------------------
    selfcheck()
    extras["kernels_verified"] = True

    _mark("headline")
    # -- headline: identical config to round 1 (comparable across rounds) --
    cfg = LlamaConfig(vocab_size=32000, hidden_size=768,
                      intermediate_size=2048, num_layers=12,
                      num_heads=12, num_kv_heads=12, max_seq_len=2048,
                      dtype=jnp.bfloat16, attn_impl="flash")
    batch, seq = 8, 2048
    engine = build_engine(cfg, batch)
    flops = step_flops(engine, batch, seq, cfg.vocab_size, cfg)
    engine.flops_per_step = flops  # StepRecords then carry TFLOPS/MFU too
    tps = measure(engine, batch, seq, cfg.vocab_size, steps=20)
    peak = peak_flops_per_chip()
    mfu = (flops * tps / (batch * seq)) / peak
    extras["mfu"] = round(mfu, 4)
    extras["device_kind"] = jax.devices()[0].device_kind
    extras.update(_perf_extras(engine))
    del engine
    free_hbm()  # engine sits in a jit-closure reference cycle

    _mark("tuned")
    # -- tuned: the best-known-config run (tuning/ — ISSUE 9) --------------
    # The headline above stays the round-1 config for cross-round
    # comparability; THIS run is what the store says the same model should
    # do on this chip — the seeded v5-lite entry (or whatever a search
    # promoted since).  ``tuned_mfu`` is a gated perf metric, so a bad
    # promotion or a stale seed shows up in `telemetry perf check`
    # exactly like a code regression, never as a hand-asserted number.
    try:
        _budget_check()
        import dataclasses

        from deepspeed_tpu.models import LlamaModel
        from deepspeed_tpu.parallel import MeshLayout
        from deepspeed_tpu.tuning import BestConfigStore, resolve_store_path
        from deepspeed_tpu.tuning.store import (current_device_kind,
                                                mesh_signature,
                                                model_fingerprint)
        from deepspeed_tpu.utils import groups

        fp = model_fingerprint(jax.eval_shape(
            LlamaModel(cfg).init_params, jax.random.PRNGKey(0)))
        tmesh = groups.initialize_mesh(MeshLayout.infer(1, dp=1))
        store = BestConfigStore(resolve_store_path())
        hit = store.lookup(fp, mesh_signature(tmesh), current_device_kind(),
                           promoted_only=True)
        if hit is None:
            extras["tuned_config_source"] = "none"
        else:
            key, entry = hit
            ov = entry.get("overrides", {})
            known = {f.name for f in dataclasses.fields(cfg)}
            tcfg = dataclasses.replace(
                cfg, **{k: v for k, v in entry.get(
                    "model_overrides", {}).items() if k in known})
            tmb = int(ov.get("train_micro_batch_size_per_gpu", batch))
            tgas = int(ov.get("gradient_accumulation_steps", 1))
            tstage = int(ov.get("zero_optimization.stage", 0))
            toff = str(ov.get("zero_optimization.offload_optimizer.device",
                              "none")) == "cpu"
            teng = build_engine(tcfg, tmb, zero_stage=tstage, offload=toff,
                                gas=tgas)
            # the engine steps on the GLOBAL batch (gas microbatches of
            # tmb rows) — feeding only tmb rows would silently measure
            # micro-batch tmb/gas, a config the store never claimed
            tglobal = tmb * tgas
            tflops = step_flops(teng, tglobal, seq, tcfg.vocab_size, tcfg)
            teng.flops_per_step = tflops
            ttps = measure(teng, tglobal, seq, tcfg.vocab_size, steps=10)
            tmfu = (tflops * ttps / (tglobal * seq)) / peak
            extras["tuned_config_source"] = f"{store.source_of(key)}::{key}"
            extras["tuned_mfu"] = round(tmfu, 4)
            extras["tuned_tokens_per_sec"] = round(ttps, 1)
            extras["tuned_vs_default_mfu_delta"] = round(tmfu - mfu, 4)
            if entry.get("stale_jax"):
                extras["tuned_stale_jax"] = entry["stale_jax"]
            del teng
            free_hbm()
    except Exception as e:  # the tuned run must never kill the headline line
        free_hbm()
        extras["tuned_config_source"] = "error: " + str(e)[:160]

    _mark("shape_tuned")
    # -- variant: max-fitting ZeRO-3 + remat, sized from live HBM ----------
    # shape choice is MFU-tuned: wide-short beats narrow-deep on the MXU
    # (measured on v5e: h2048/L10 = 48% MFU vs h1024/L24 = 31% at equal
    # fit) — the BASELINE.md north star is MFU, so the max-fitting config
    # maximizes it, not parameter count
    try:
        _budget_check()
        hbm = hbm_bytes()
        if hbm >= 80e9:      # ~3.5B for 95G chips (56G Adam states + acts)
            big = LlamaConfig(vocab_size=32000, hidden_size=4096,
                              intermediate_size=11008, num_layers=16,
                              num_heads=32, num_kv_heads=32, max_seq_len=2048,
                              dtype=jnp.bfloat16, attn_impl="flash",
                              remat=True)
            bbatch = 4
        elif hbm >= 30e9:    # ~1.2B for 32G chips (~19G states)
            big = LlamaConfig(vocab_size=32000, hidden_size=2048,
                              intermediate_size=5504, num_layers=24,
                              num_heads=16, num_kv_heads=16, max_seq_len=2048,
                              dtype=jnp.bfloat16, attn_impl="flash",
                              remat=True)
            bbatch = 4
        else:                # 637M wide-short fits 16G chips with states+acts
            big = LlamaConfig(vocab_size=32000, hidden_size=2048,
                              intermediate_size=5504, num_layers=10,
                              num_heads=16, num_kv_heads=16, max_seq_len=2048,
                              dtype=jnp.bfloat16, attn_impl="flash",
                              remat=True)
            bbatch = 4
        eng = build_engine(big, bbatch, zero_stage=3)
        btps = measure(eng, bbatch, seq, big.vocab_size, steps=10)
        bflops = step_flops(eng, bbatch, seq, big.vocab_size, big)
        # "shape_tuned": this config's aspect ratio was picked to maximize
        # MFU (VERDICT r2 weak #2) — the driver-ladder configs below are
        # the representative numbers; this one is the chip's ceiling
        extras["variants"] = {
            "zero3_remat_shape_tuned_tokens_per_sec": round(btps, 1),
            "zero3_remat_shape_tuned_mfu": round(
                (bflops * btps / (bbatch * seq)) / peak, 4),
        }
        del eng
        free_hbm()
    except Exception as e:  # a variant must never kill the headline line
        free_hbm()
        extras["variants"] = {"zero3_remat_shape_tuned_error": str(e)[:200]}

    _mark("bert_zero2")
    # -- driver ladder (BASELINE.md): BERT-large ZeRO-2 ---------------------
    try:
        _budget_check()
        from deepspeed_tpu.models.bert import BertConfig, BertModel

        bcfg = BertConfig.bert_large()  # true BERT-large, 335M
        bb, bs = 32, 512
        rng0 = np.random.RandomState(0)
        ids = jnp.asarray(rng0.randint(0, bcfg.vocab_size, size=(bb, bs)))
        labels = np.full((bb, bs), -100)
        mask_pos = rng0.rand(bb, bs) < 0.15  # MLM-style 15% masking
        labels[mask_pos] = np.asarray(ids)[mask_pos]
        bdata = {"input_ids": ids, "labels": jnp.asarray(labels)}
        eng = build_engine(bcfg, bb, zero_stage=2, model_cls=BertModel)
        btps = measure(eng, bb, bs, bcfg.vocab_size, steps=10,
                       budget_s=60.0, data=bdata)
        bflp = step_flops(eng, bb, bs, bcfg.vocab_size, bcfg)
        extras["variants"]["bert_large_zero2_tokens_per_sec"] = round(btps, 1)
        extras["variants"]["bert_zero2_mfu"] = round(
            (bflp * btps / (bb * bs)) / peak, 4)
        del eng, bdata, ids
        free_hbm()
    except Exception as e:
        free_hbm()
        extras.setdefault("variants", {})["bert_zero2_error"] = str(e)[:200]

    _mark("mixtral_v2")
    # -- driver ladder: Mixtral-shaped MoE serving on inference v2 ----------
    try:
        _budget_check()
        from deepspeed_tpu.models import MixtralConfig, MixtralModel

        # Mixtral aspect ratios (8 experts, top-2, GQA) scaled to the chip
        mcfg = MixtralConfig(vocab_size=32000, hidden_size=1024,
                             intermediate_size=3584, num_layers=8,
                             num_heads=16, num_kv_heads=8, max_seq_len=2048,
                             num_experts=8, top_k=2, dtype=jnp.bfloat16)
        prng = np.random.RandomState(2)
        mprompts = [prng.randint(1, mcfg.vocab_size, size=n).tolist()
                    for n in (40, 100, 200, 64, 128, 80, 300, 50)]
        extras["variants"]["mixtral_proxy_v2_tokens_per_sec"] = round(
            serve_v2_throughput(MixtralModel(mcfg), mprompts, 97), 1)
    except Exception as e:
        free_hbm()
        extras.setdefault("variants", {})[
            "mixtral_v2_error"] = str(e)[:200]

    _mark("moe_ep")
    # -- variant: expert-parallel training plane (ISSUE 19) ----------------
    # The Mixtral proxy TRAINED through the config-driven ep path (expert
    # mesh axis > 1 when the chip count allows; ep=1 reference alongside)
    # plus the index-form-vs-dense dispatch micro-bench.  Three figures go
    # top-level into the gated PERF_METRICS: moe_ep_tokens_per_sec,
    # moe_dispatch_speedup, moe_drop_rate.
    try:
        _budget_check()
        from deepspeed_tpu.moe.bench import run_moe_ep_bench

        mo = run_moe_ep_bench(dry_run=False, steps=4, warmup=2)
        extras.setdefault("variants", {})["moe_ep"] = mo
        for key in ("moe_ep_tokens_per_sec", "moe_dispatch_speedup",
                    "moe_drop_rate"):
            extras[key] = mo[key]
        free_hbm()
    except Exception as e:
        free_hbm()
        extras.setdefault("variants", {})["moe_ep_error"] = str(e)[:200]

    _mark("llama_v2")
    # -- variant: inference v2 ragged serving throughput -------------------
    # NOTE: each dispatch pays a fixed host cost — bursts amortize it;
    # tracked round-over-round for relative movement.
    try:
        _budget_check()
        from deepspeed_tpu.models import LlamaModel

        prng = np.random.RandomState(1)
        prompts = [prng.randint(1, cfg.vocab_size, size=n).tolist()
                   for n in (40, 100, 200, 350, 64, 128, 500, 80)]
        extras.setdefault("variants", {})[
            "inference_v2_ragged_tokens_per_sec"] = round(
                serve_v2_throughput(LlamaModel(cfg), prompts, 97), 1)
    except Exception as e:
        free_hbm()
        extras.setdefault("variants", {})[
            "inference_v2_error"] = str(e)[:200]

    _mark("serving")
    # -- variant: serving plane — SLO front-end + prefix cache over a real
    # engine replica.  Mixed-class workload with a shared 256-token header:
    # interactive p99 TTFT, prefix hit rate, and per-class tok/s land in
    # the gated baseline (`telemetry perf check` fails on regression).
    fe = None
    try:
        _budget_check()
        from deepspeed_tpu.inference.v2 import KVCacheConfig
        from deepspeed_tpu.models import LlamaModel
        from deepspeed_tpu.serving import (ServingParams,
                                           build_serving_frontend)
        from deepspeed_tpu.serving.cli import run_workload

        svcfg = LlamaConfig(vocab_size=8192, hidden_size=512,
                            intermediate_size=1408, num_layers=4,
                            num_heads=8, num_kv_heads=8, max_seq_len=1024,
                            dtype=jnp.bfloat16)
        fe = build_serving_frontend(
            LlamaModel(svcfg), replicas=1,
            cache_config=KVCacheConfig(num_blocks=512, block_size=16,
                                       max_seq_len=1024),
            max_batch_slots=8, prefill_chunk=128, prefill_batch=2,
            decode_burst=8,
            serving_params=ServingParams(interactive_reserve_frac=0.1))
        # warm both compiled programs + the prefill buckets OUTSIDE the
        # measured window (mid-run compile would land in the TTFT tail)
        run_workload(fe, time.monotonic, n_interactive=2, n_background=1,
                     header_len=256, interactive_new=8, background_new=16,
                     warm_rounds=2, seed=7)
        sv = run_workload(fe, time.monotonic, n_interactive=8,
                          n_background=4, header_len=256,
                          interactive_new=16, background_new=64, seed=0)
        extras["serving_p99_ttft_ms"] = sv["serving_p99_ttft_ms"]
        extras["prefix_hit_rate"] = sv["prefix_hit_rate"]
        extras["tok_s_interactive"] = sv["tok_s_interactive"]
        extras["tok_s_background"] = sv["tok_s_background"]
        extras.setdefault("variants", {})["serving"] = sv
    except Exception as e:
        extras.setdefault("variants", {})["serving_error"] = str(e)[:200]
    finally:
        if fe is not None:
            # detach the flight-recorder context provider — it holds the
            # front-end (and its engine + KV pool) alive otherwise, on
            # the error path too
            fe.close()
            fe = None
        free_hbm()

    _mark("serving_network")
    # -- variant: NETWORK serving plane — a real HTTP/SSE front door over
    # 2 replica worker PROCESSES (synthetic engines: this measures the
    # serving STACK — sockets, SSE writes, router RPCs, process hops —
    # not model math, so the numbers are stable across devices).
    # Sustained mixed-class QPS with shared tenant headers; p99 TTFT,
    # sustained QPS and the cross-tenant prefix hit rate land in the
    # gated baseline (`telemetry perf check` fails on regression).
    net_door = None
    net_fleet = []
    try:
        _budget_check()
        from deepspeed_tpu.launcher.serving_fleet import (
            launch_worker_fleet, shutdown_fleet)
        from deepspeed_tpu.serving import (FrontDoor, FrontDoorParams,
                                           NetworkFrontend, NetworkParams,
                                           ReplicaEndpoint)
        from deepspeed_tpu.serving.cli import run_network_workload

        net_fleet = launch_worker_fleet(2)
        net_eps = [ReplicaEndpoint(w.id, w.endpoint, role=w.role)
                   for w in net_fleet]
        net_door = FrontDoor(NetworkFrontend(net_eps, net=NetworkParams()),
                             params=FrontDoorParams())
        net_door.start()
        # warm the sockets + tenant headers outside the measured window
        run_network_workload(net_door.host, net_door.port,
                             duration_s=1.0, seed=7)
        nsv = run_network_workload(net_door.host, net_door.port,
                                   duration_s=4.0, seed=0)
        extras["serving_net_p99_ttft_ms"] = nsv["serving_net_p99_ttft_ms"]
        extras["serving_net_qps_sustained"] = \
            nsv["serving_net_qps_sustained"]
        extras["serving_net_prefix_hit_rate"] = \
            nsv["serving_net_prefix_hit_rate"]
        extras.setdefault("variants", {})["serving_network"] = nsv
    except Exception as e:
        extras.setdefault("variants", {})[
            "serving_network_error"] = str(e)[:200]
    finally:
        if net_door is not None:
            net_door.shutdown()
        if net_fleet:
            from deepspeed_tpu.launcher.serving_fleet import shutdown_fleet

            shutdown_fleet(net_fleet)
        free_hbm()

    _mark("block_sparse")
    # -- variant: block-sparse kernel speedup vs dense-masked (S=4096) ----
    try:
        _budget_check()
        from deepspeed_tpu.ops.pallas.block_sparse_attention import (
            block_sparse_attention)
        from deepspeed_tpu.ops.sparse_attention import (
            BigBirdSparsityConfig, sparse_attention)

        rng = np.random.RandomState(0)
        Sb, hb, db = 4096, 8, 64
        qs = jnp.asarray(rng.randn(1, Sb, hb, db)).astype(jnp.bfloat16)
        ks = jnp.asarray(rng.randn(1, Sb, hb, db)).astype(jnp.bfloat16)
        vs = jnp.asarray(rng.randn(1, Sb, hb, db)).astype(jnp.bfloat16)
        bb = BigBirdSparsityConfig(num_heads=hb, block=16,
                                   num_random_blocks=2,
                                   num_sliding_window_blocks=5,
                                   num_global_blocks=1)

        def _bench_attn(f, n=5, reps=10):
            # amortize dispatch: the per-call floor would otherwise
            # swamp sub-ms kernel differences — chain `reps`
            # applications inside ONE program via lax.scan (output feeds
            # back as v, so steps can't be elided)
            def chained(q, k, v):
                def body(c, _):
                    return (c[0], c[1], f(c[0], c[1], c[2]).astype(
                        c[2].dtype)), None
                (q_, k_, v_), _ = jax.lax.scan(body, (q, k, v), None,
                                               length=reps)
                return v_
            g = jax.jit(chained)
            o = g(qs, ks, vs)
            float(jnp.sum(o.astype(jnp.float32)))  # compile + fence
            t0 = time.perf_counter()
            for _ in range(n):
                o = g(qs, ks, vs)
            float(jnp.sum(o.astype(jnp.float32)))  # fence
            return (time.perf_counter() - t0) / (n * reps)

        t_dense = _bench_attn(jax.jit(
            lambda q, k, v: sparse_attention(q, k, v, bb, impl="dense")))
        t_sparse = _bench_attn(jax.jit(
            lambda q, k, v: block_sparse_attention(q, k, v, bb)))
        extras.setdefault("variants", {})["block_sparse_speedup_s4096"] = \
            round(t_dense / t_sparse, 2)
        # top-level: gated by telemetry perf check (PERF_METRICS) — with
        # choose_impl's crossover auto-dispatch a sub-1.0 value is a
        # dispatch regression, not a tuning note
        extras["block_sparse_speedup_s4096"] = round(t_dense / t_sparse, 2)
        # long-context comparison — the block-sparse kernels' real value
        # is where dense S² attention stops being viable.  Baseline is
        # dense causal FLASH (what you'd run without sparse support) at
        # S=8192 with a representative 64-cell BigBird; the gather kernel
        # also runs S=32k+ where both dense paths cannot.  (The cb=16
        # config above coarsens near-dense at kernel granularity and
        # auto-dispatch correctly picks the dense path — speedup ~1.0.)
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

        S8 = 8192
        q8 = jnp.asarray(rng.randn(1, S8, hb, db)).astype(jnp.bfloat16)
        k8 = jnp.asarray(rng.randn(1, S8, hb, db)).astype(jnp.bfloat16)
        v8 = jnp.asarray(rng.randn(1, S8, hb, db)).astype(jnp.bfloat16)
        bb64 = BigBirdSparsityConfig(num_heads=hb, block=64,
                                     num_random_blocks=1,
                                     num_sliding_window_blocks=3,
                                     num_global_blocks=1)

        def _bench_attn8(f, n=4, reps=10):
            def chained(q, k, v):
                def body(c, _):
                    return (c[0], c[1], f(c[0], c[1], c[2]).astype(
                        c[2].dtype)), None
                (a, b, v_), _ = jax.lax.scan(body, (q, k, v), None,
                                             length=reps)
                return v_
            g = jax.jit(chained)
            float(jnp.sum(g(q8, k8, v8).astype(jnp.float32)))
            t0 = time.perf_counter()
            for _ in range(n):
                o = g(q8, k8, v8)
            float(jnp.sum(o.astype(jnp.float32)))
            return (time.perf_counter() - t0) / (n * reps)

        t_flash8 = _bench_attn8(
            lambda q, k, v: flash_attention(q, k, v, True))
        t_sparse8 = _bench_attn8(
            lambda q, k, v: block_sparse_attention(q, k, v, bb64,
                                                   causal=True))
        extras["variants"]["block_sparse_vs_flash_s8192"] = \
            round(t_flash8 / t_sparse8, 2)
        del qs, ks, vs, q8, k8, v8
        free_hbm()

        # ---- TRAINING (fwd+bwd) — the Pallas flat-tile backward ------
        # (VERDICT r4 items 3+4): grad-vs-grad against the dense masked
        # vjp at S=4096, and a live-fraction sweep vs dense-causal FLASH
        # at S=8192 (what you'd run without sparse support).  Sweep
        # documents the crossover: wins scale as ~1/(1.4·live).
        def _bench_grad(f, q_, k_, v_, n=3, reps=6):
            # differentiate w.r.t. ALL of q/k/v and fold every grad into
            # the carry — a dq-only grad lets XLA dead-code-eliminate the
            # dk/dv backward kernels and the "training" number would be
            # fwd+dq only
            def chained(q, k, v):
                def body(c, _):
                    gq, gk, gv = jax.grad(
                        lambda a, b2, c2: jnp.sum(
                            f(a, b2, c2).astype(jnp.float32) ** 2),
                        argnums=(0, 1, 2))(*c)
                    return (c[0] * 0.5 + gq.astype(c[0].dtype) * 1e-6,
                            c[1] * 0.5 + gk.astype(c[1].dtype) * 1e-6,
                            c[2] * 0.5 + gv.astype(c[2].dtype) * 1e-6), None
                (q_2, _, _), _ = jax.lax.scan(body, (q, k, v), None,
                                              length=reps)
                return q_2
            g = jax.jit(chained)
            o = g(q_, k_, v_)
            float(jnp.sum(o[0, 0, 0, :1].astype(jnp.float32)))
            t0 = time.perf_counter()
            for _ in range(n):
                o = g(q_, k_, v_)
            float(jnp.sum(o[0, 0, 0, :1].astype(jnp.float32)))
            return (time.perf_counter() - t0) / (n * reps)

        from deepspeed_tpu.ops.sparse_attention import sparse_attention \
            as _sa

        B4, h4 = 2, 16
        q4 = jnp.asarray(rng.randn(B4, Sb, h4, db)).astype(jnp.bfloat16)
        k4 = jnp.asarray(rng.randn(B4, Sb, h4, db)).astype(jnp.bfloat16)
        v4 = jnp.asarray(rng.randn(B4, Sb, h4, db)).astype(jnp.bfloat16)
        bb128 = BigBirdSparsityConfig(num_heads=h4, block=128)
        ts_ = _bench_grad(lambda q, k, v: block_sparse_attention(
            q, k, v, bb128, causal=True), q4, k4, v4)
        td_ = _bench_grad(lambda q, k, v: _sa(
            q, k, v, bb128, impl="dense", causal=True), q4, k4, v4)
        extras["variants"]["block_sparse_train_speedup_s4096"] = \
            round(td_ / ts_, 2)
        del q4, k4, v4
        free_hbm()

        sweep = {}
        qs8 = jnp.asarray(rng.randn(1, S8, h4, db)).astype(jnp.bfloat16)
        ks8 = jnp.asarray(rng.randn(1, S8, h4, db)).astype(jnp.bfloat16)
        vs8 = jnp.asarray(rng.randn(1, S8, h4, db)).astype(jnp.bfloat16)
        t_fl8 = _bench_grad(lambda q, k, v: flash_attention(q, k, v, True),
                            qs8, ks8, vs8)
        from deepspeed_tpu.ops.pallas.block_sparse_attention import (
            _live_fraction, _norm_layout, _plan)

        for win in (3, 7, 15):
            _budget_check()
            cfg_w = BigBirdSparsityConfig(
                num_heads=h4, block=128, num_global_blocks=1,
                num_random_blocks=1, num_sliding_window_blocks=win)
            lay_w = _norm_layout(cfg_w.make_layout(S8), h4)
            _, cnt_w, _ = _plan(lay_w, S8, 128, 128, 128, True)
            lf = _live_fraction(cnt_w, S8, 128, 128, True)
            t_w = _bench_grad(lambda q, k, v, c=cfg_w:
                              block_sparse_attention(q, k, v, c,
                                                     causal=True),
                              qs8, ks8, vs8)
            sweep[f"win{win}"] = {"live": round(float(lf), 3),
                                  "vs_flash": round(t_fl8 / t_w, 2)}
        extras["variants"]["block_sparse_train_sweep_s8192"] = sweep
        del qs8, ks8, vs8
        free_hbm()
    except Exception as e:
        free_hbm()
        extras.setdefault("variants", {})[
            "block_sparse_error"] = str(e)[:200]

    _mark("flash_sweep")
    # -- variant: flash attention vs the XLA reference ladder, 2k–32k -----
    # (ISSUE 12 acceptance: the Pallas path must be >= 1.0x at EVERY
    # benched seq length, not just break even at 8k.)  Train-shaped
    # fwd+bwd timing; baseline is what the dispatch would run WITHOUT
    # the kernel: the dense masked reference where its O(S^2) logits fit
    # (2k/8k), the chunked online-softmax lax.scan beyond (32k).
    try:
        _budget_check()
        from deepspeed_tpu.ops.pallas.flash_attention import (
            _reference_attention, flash_attention)

        def _xla_chunked_attention(q, k, v, blk=512):
            """Best non-Pallas XLA form at long S: online-softmax scan
            over k-chunks (causal), O(S·blk) transients."""
            B, S, h, d = q.shape
            scale = 1.0 / np.sqrt(d)
            qt = (q.astype(jnp.float32) * scale).transpose(0, 2, 1, 3)
            kt = k.astype(jnp.float32).transpose(0, 2, 1, 3)
            vt = v.astype(jnp.float32).transpose(0, 2, 1, 3)
            nk = S // blk
            kc = kt.reshape(B, h, nk, blk, d).transpose(2, 0, 1, 3, 4)
            vc = vt.reshape(B, h, nk, blk, d).transpose(2, 0, 1, 3, 4)
            q_pos = jnp.arange(S)[:, None]

            def body(carry, chunk):
                m, l, acc = carry
                ki, kb, vb = chunk
                s = jnp.einsum("bhqd,bhkd->bhqk", qt, kb)
                k_pos = ki * blk + jnp.arange(blk)[None, :]
                s = jnp.where(q_pos >= k_pos, s, -1e30)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                p = jnp.exp(s - m_new[..., None])
                alpha = jnp.exp(m - m_new)
                l_new = l * alpha + jnp.sum(p, axis=-1)
                acc_new = (acc * alpha[..., None]
                           + jnp.einsum("bhqk,bhkd->bhqd", p, vb))
                return (m_new, l_new, acc_new), None

            m0 = jnp.full((B, h, S), -jnp.inf, jnp.float32)
            l0 = jnp.zeros((B, h, S), jnp.float32)
            a0 = jnp.zeros((B, h, S, d), jnp.float32)
            (m, l, acc), _ = jax.lax.scan(
                body, (m0, l0, a0), (jnp.arange(nk), kc, vc))
            out = acc / l[..., None]
            return out.transpose(0, 2, 1, 3).astype(q.dtype)

        def _bench_grad_fs(f, q_, k_, v_, n=3, reps=4):
            # self-contained copy of the block-sparse section's fwd+bwd
            # timer (that section failing must not take this gate down):
            # all of dq/dk/dv fold into the carry so no backward kernel
            # is dead-code-eliminated
            def chained(q, k, v):
                def body(c, _):
                    gq, gk, gv = jax.grad(
                        lambda a, b2, c2: jnp.sum(
                            f(a, b2, c2).astype(jnp.float32) ** 2),
                        argnums=(0, 1, 2))(*c)
                    return (c[0] * 0.5 + gq.astype(c[0].dtype) * 1e-6,
                            c[1] * 0.5 + gk.astype(c[1].dtype) * 1e-6,
                            c[2] * 0.5 + gv.astype(c[2].dtype) * 1e-6), None
                (q_2, _, _), _ = jax.lax.scan(body, (q, k, v), None,
                                              length=reps)
                return q_2
            g = jax.jit(chained)
            o = g(q_, k_, v_)
            float(jnp.sum(o[0, 0, 0, :1].astype(jnp.float32)))
            t0 = time.perf_counter()
            for _ in range(n):
                o = g(q_, k_, v_)
            float(jnp.sum(o[0, 0, 0, :1].astype(jnp.float32)))
            return (time.perf_counter() - t0) / (n * reps)

        rngf = np.random.RandomState(0)
        hf, df = 8, 64
        for Sf, Bf in ((2048, 4), (8192, 1), (32768, 1)):
            _budget_check()
            qf = jnp.asarray(rngf.randn(Bf, Sf, hf, df)).astype(
                jnp.bfloat16)
            kf = jnp.asarray(rngf.randn(Bf, Sf, hf, df)).astype(
                jnp.bfloat16)
            vf = jnp.asarray(rngf.randn(Bf, Sf, hf, df)).astype(
                jnp.bfloat16)
            if Sf <= 8192:
                baseline = lambda q, k, v: _reference_attention(
                    q, k, v, True)
            else:
                baseline = _xla_chunked_attention
            t_ref = _bench_grad_fs(baseline, qf, kf, vf)
            t_fl = _bench_grad_fs(
                lambda q, k, v: flash_attention(q, k, v, True),
                qf, kf, vf)
            key = f"flash_speedup_s{Sf}"
            extras[key] = round(t_ref / t_fl, 2)
            extras.setdefault("variants", {})[key] = extras[key]
            del qf, kf, vf
            free_hbm()
    except Exception as e:
        free_hbm()
        extras.setdefault("variants", {})["flash_sweep_error"] = \
            str(e)[:200]

    _mark("overlap")
    # -- variant: collective-compute overlap hiding fraction --------------
    # Ring-decomposed all-gather matmul (comm/overlap.py) vs the
    # monolithic gather-then-matmul: hiding_frac = the share of the
    # collective's serialized cost the ring buries under compute.
    try:
        _budget_check()
        from jax.sharding import Mesh, PartitionSpec as Psp

        from deepspeed_tpu.comm import overlap as _ovl
        from deepspeed_tpu.comm.comm import all_gather_in_graph
        from deepspeed_tpu.utils.jax_compat import shard_map as _shmap

        devs = jax.devices()
        if len(devs) >= 2:
            omesh = Mesh(np.array(devs), ("data",))
            M, K, N = 4096, 2048, 2048
            xo = jnp.asarray(np.random.RandomState(0).randn(
                M, K)).astype(jnp.bfloat16)
            wo = jnp.asarray(np.random.RandomState(1).randn(
                K, N)).astype(jnp.bfloat16)

            def _time_fn(fn, *args, n=8):
                o = fn(*args)
                float(jnp.sum(o[:1, :1].astype(jnp.float32)))
                t0 = time.perf_counter()
                for _ in range(n):
                    o = fn(*args)
                float(jnp.sum(o[:1, :1].astype(jnp.float32)))
                return (time.perf_counter() - t0) / n

            serial = jax.jit(_shmap(
                lambda x, w: jnp.dot(
                    all_gather_in_graph(x, "data", axis=0, tiled=True),
                    w, preferred_element_type=jnp.bfloat16),
                mesh=omesh, in_specs=(Psp("data"), Psp()),
                out_specs=Psp(), check_vma=False))
            ring = jax.jit(_shmap(
                lambda x, w: _ovl.all_gather_matmul(x, w, "data",
                                                    chunks=4),
                mesh=omesh, in_specs=(Psp("data"), Psp()),
                out_specs=Psp(), check_vma=False))
            mm_only = jax.jit(lambda x, w: jnp.dot(
                x, w, preferred_element_type=jnp.bfloat16))

            t_serial = _time_fn(serial, xo, wo)
            t_ring = _time_fn(ring, xo, wo)
            t_mm = _time_fn(mm_only, xo, wo)
            coll = max(t_serial - t_mm, 1e-9)
            hiding = max(0.0, min(1.0, (t_serial - t_ring) / coll))
            extras["overlap_hiding_frac"] = round(hiding, 3)
            extras.setdefault("variants", {})["overlap"] = {
                "t_serial_ms": round(t_serial * 1e3, 3),
                "t_ring_ms": round(t_ring * 1e3, 3),
                "t_matmul_ms": round(t_mm * 1e3, 3),
                "hiding_frac": round(hiding, 3),
                "chunks": 4,
            }
            del xo, wo
            free_hbm()
        else:
            extras.setdefault("variants", {})["overlap"] = {
                "skipped": "single device — no collective to hide"}
    except Exception as e:
        free_hbm()
        extras.setdefault("variants", {})["overlap_error"] = str(e)[:200]

    _mark("anatomy")
    # -- variant: step anatomy — trace-measured comm/compute split --------
    # One shared profiler session over a few fenced steps of the ring
    # all_gather_matmul (2+ devices; plain matmul fallback on one),
    # classified into compute / exposed-collective / overlapped /
    # host-sync buckets.  comm_fraction is sentinel-gated (lower is
    # better); the MEASURED overlap hiding backfills the analytic
    # overlap number when the ring variant couldn't run.
    try:
        _budget_check()
        from deepspeed_tpu.telemetry.anatomy import (capture_step_anatomy,
                                                     get_cost_ledger)

        devs = jax.devices()
        if len(devs) >= 2:
            from jax.sharding import Mesh, PartitionSpec as Psp

            from deepspeed_tpu.comm import overlap as _ovl
            from deepspeed_tpu.utils.jax_compat import shard_map as _shmap

            amesh = Mesh(np.array(devs), ("data",))
            afn = jax.jit(_shmap(
                lambda x, w: _ovl.all_gather_matmul(x, w, "data",
                                                    chunks=4),
                mesh=amesh, in_specs=(Psp("data"), Psp()),
                out_specs=Psp(), check_vma=False))
        else:
            afn = jax.jit(lambda x, w: jnp.dot(
                x, w, preferred_element_type=jnp.bfloat16))
        xa = jnp.asarray(np.random.RandomState(2).randn(
            2048, 2048)).astype(jnp.bfloat16)
        wa = jnp.asarray(np.random.RandomState(3).randn(
            2048, 2048)).astype(jnp.bfloat16)
        try:  # roofline join needs costs for the captured program
            get_cost_ledger().harvest("bench/anatomy_probe", 0,
                                      afn.lower(xa, wa).compile())
        except Exception:
            pass
        asum = capture_step_anatomy(afn, xa, wa, steps=3,
                                    site="bench/anatomy_probe")
        extras["comm_fraction"] = float(asum["comm_fraction"])
        if (asum.get("overlap_hiding_frac") is not None
                and "overlap_hiding_frac" not in extras):
            extras["overlap_hiding_frac"] = round(
                float(asum["overlap_hiding_frac"]), 3)
        roof = (asum.get("roofline") or [{}])[0]
        extras.setdefault("variants", {})["anatomy"] = {
            "window_us": asum.get("window_us"),
            "compute_us": asum.get("compute_us"),
            "coll_exposed_us": asum.get("coll_exposed_us"),
            "coll_overlapped_us": asum.get("coll_overlapped_us"),
            "host_sync_us": asum.get("host_sync_us"),
            "comm_fraction": asum.get("comm_fraction"),
            "overlap_hiding_frac": asum.get("overlap_hiding_frac"),
            "attributed_frac": asum.get("attributed_frac"),
            "roofline_verdict": roof.get("verdict"),
            "roofline_headroom": roof.get("headroom"),
            "devices": len(devs),
        }
        del xa, wa
        free_hbm()
    except Exception as e:
        free_hbm()
        extras.setdefault("variants", {})["anatomy_error"] = str(e)[:200]

    _mark("numerics")
    # -- variant: numerics probe overhead ---------------------------------
    # The plane's contract (ISSUE 18) is that the sampled probes-on step
    # variant costs (nearly) nothing: 8 scalars per probe folded into the
    # step's own output pytree, no host callbacks.  Measured here as the
    # fenced step-time delta of a probed value_and_grad vs the identical
    # un-probed program, and sentinel-gated (lower, 5% abs floor) so a
    # probe that starts forcing a host sync or breaking a fusion shows
    # up in the trajectory.
    try:
        _budget_check()
        from deepspeed_tpu.telemetry import numerics as _num

        NH, NB, NL = 512, 256, 4
        rs = np.random.RandomState(5)
        np_ = {f"w{i}": jnp.asarray(rs.randn(NH, NH) * 0.05).astype(
            jnp.bfloat16) for i in range(NL)}
        nx = jnp.asarray(rs.randn(NB, NH)).astype(jnp.bfloat16)

        def _nloss(p, x):
            h = x
            for i in range(NL):
                h = _num.probe(f"h{i}", jnp.tanh(h @ p[f"w{i}"]))
            return jnp.sum(jnp.square(h.astype(jnp.float32)))

        def _nstep_base(p, x):
            return jax.value_and_grad(_nloss)(p, x)

        def _nstep_probed(p, x):
            def lf(pp):
                mark = _num.scan_mark()
                loss = _nloss(pp, x)
                return loss, (_num.scan_drain(mark) or {})

            return jax.value_and_grad(lf, has_aux=True)(p)

        f_base = jax.jit(_nstep_base)
        f_prob = jax.jit(_nstep_probed)

        def _ntime(fn, probed, iters=20, reps=3):
            times = []
            for _ in range(reps + 1):  # first rep is the warmup/compile
                if probed:
                    coll = _num.Collector(probes=True, moe=False,
                                          tag="bench")
                    with _num.collecting(coll):
                        t0 = time.perf_counter()
                        for _i in range(iters):
                            out = fn(np_, nx)
                        jax.block_until_ready(out)
                        times.append(time.perf_counter() - t0)
                else:
                    t0 = time.perf_counter()
                    for _i in range(iters):
                        out = fn(np_, nx)
                    jax.block_until_ready(out)
                    times.append(time.perf_counter() - t0)
            return sorted(times[1:])[len(times[1:]) // 2]

        t_off = _ntime(f_base, probed=False)
        t_on = _ntime(f_prob, probed=True)
        frac = max(0.0, (t_on - t_off) / max(t_off, 1e-9))
        extras["numerics_overhead_frac"] = round(frac, 4)
        extras.setdefault("variants", {})["numerics"] = {
            "base_s_per_20": round(t_off, 5),
            "probed_s_per_20": round(t_on, 5),
            "overhead_frac": round(frac, 4),
            "probes": NL,
        }
        del np_, nx
        free_hbm()
    except Exception as e:
        free_hbm()
        extras.setdefault("variants", {})["numerics_error"] = str(e)[:200]

    _mark("profiler")
    # -- variant: fleet profiler duty-cycle overhead -----------------------
    # ISSUE 20's continuous mode ("always-on capture with a bounded
    # overhead budget") only earns its keep if the budget holds: the same
    # fenced step loop timed with the duty-cycled ProfilerPlane arming
    # real jax.profiler windows (capture + parse + census + calibration)
    # vs with no plane at all.  profiler_overhead_pct is sentinel-gated
    # (lower, 5pt abs floor).
    try:
        _budget_check()
        import shutil as _sh
        import tempfile as _tmp

        from deepspeed_tpu.telemetry.profiler import ProfilerPlane
        from deepspeed_tpu.telemetry.profiler.calibration import (
            default_calibration_path, get_calibration_store)

        PH, PB = 512, 256
        rs = np.random.RandomState(7)
        pw = jnp.asarray(rs.randn(PH, PH) * 0.05).astype(jnp.bfloat16)
        px = jnp.asarray(rs.randn(PB, PH)).astype(jnp.bfloat16)
        pfn = jax.jit(lambda w, x: jnp.sum(jnp.square(
            jnp.tanh(x @ w).astype(jnp.float32))))
        float(pfn(pw, px))  # warm the compile out of both timings

        def _ptime(plane, iters=60):
            t0 = time.perf_counter()
            out = None
            for i in range(iters):
                if plane is not None:
                    plane.on_step(i)
                out = pfn(pw, px)
            jax.block_until_ready(out)
            if plane is not None:
                plane.on_step(iters)  # close a still-open window
            return time.perf_counter() - t0

        t_off = min(_ptime(None), _ptime(None))
        pdir = _tmp.mkdtemp(prefix="bench_profiler_")
        # duty captures calibrate too — point the factor store at a
        # throwaway so the bench doesn't pollute the user's cache
        get_calibration_store(os.path.join(pdir, "calibration.json"))
        plane = ProfilerPlane("bench-duty", out_dir=pdir, ring=2,
                              duty_cycle_pct=10.0, duty_period_steps=20)
        plane.enable_duty_cycle()
        t_on = min(_ptime(plane), _ptime(plane))
        pct = max(0.0, (t_on - t_off) / max(t_off, 1e-9) * 100.0)
        extras["profiler_overhead_pct"] = round(pct, 2)
        extras.setdefault("variants", {})["profiler"] = {
            "base_s_per_60": round(t_off, 5),
            "duty_s_per_60": round(t_on, 5),
            "overhead_pct": round(pct, 2),
            "captures": plane._captures,
            "duty_cycle_pct": plane.duty_cycle_pct,
        }
        get_calibration_store(default_calibration_path())
        _sh.rmtree(pdir, ignore_errors=True)
        del pw, px
        free_hbm()
    except Exception as e:
        free_hbm()
        extras.setdefault("variants", {})["profiler_error"] = str(e)[:200]

    _mark("offload_overlap_synthetic")
    # -- overlap machinery proof with the link excluded -----------------
    try:
        _budget_check()
        extras.setdefault("variants", {})["offload_overlap_synthetic"] = \
            _bench_offload_overlap_synthetic()
        free_hbm()
    except Exception as e:
        free_hbm()
        extras.setdefault("variants", {})[
            "offload_overlap_synthetic_error"] = str(e)[:200]

    _mark("llama8b_proxy")
    # -- driver ladder: llama3-8B-shaped slice, ZeRO-3 on device -----------
    # 8B-true per-layer shape (h4096/i14336/GQA-8); L and vocab scale the
    # slice to what fp32 Adam states fit on this chip's HBM.
    try:
        _budget_check()
        hbm = hbm_bytes() or 16e9
        if hbm >= 80e9:
            attempts = [(24, 32000, 2)]
        elif hbm >= 30e9:
            attempts = [(8, 32000, 2)]
        else:  # 16G: fp32 Adam states cap the slice ~0.6B params
            attempts = [(2, 16384, 2), (1, 16384, 2)]
        last_err = None
        for L8, v8, b8 in attempts:
            try:
                l8cfg = LlamaConfig(vocab_size=v8, hidden_size=4096,
                                    intermediate_size=14336, num_layers=L8,
                                    num_heads=32, num_kv_heads=8,
                                    max_seq_len=2048, rope_theta=500000.0,
                                    dtype=jnp.bfloat16, attn_impl="flash",
                                    remat=True, loss_tiles=8,
                                    tie_embeddings=False)
                eng = build_engine(l8cfg, b8, zero_stage=3)
                otps = measure(eng, b8, 2048, l8cfg.vocab_size, steps=5,
                               segments=1, budget_s=45.0)
                oflops = step_flops(eng, b8, 2048, l8cfg.vocab_size, l8cfg)
                extras["variants"]["llama8b_proxy_zero3_tokens_per_sec"] = \
                    round(otps, 1)
                extras["variants"]["llama8b_proxy_zero3_mfu"] = round(
                    (oflops * otps / (b8 * 2048)) / peak, 4)
                extras["variants"]["llama8b_proxy_layers"] = L8
                del eng
                free_hbm()
                last_err = None
                break
            except Exception as e:
                eng = None  # drop the failed attempt's engine before retry
                free_hbm()
                last_err = e
        if last_err is not None:
            raise last_err
    except Exception as e:
        free_hbm()
        extras.setdefault("variants", {})[
            "llama8b_proxy_error"] = str(e)[:200]

    _mark("llama8b_infinity_full_depth")
    # -- north star: Llama-3-8B shapes at the REAL layer count (32) via
    # ZeRO-Infinity layer streaming (VERDICT r3 item 2).  The full trunk's
    # host planes (fp32 master + Adam moments + bf16 wire ≈ 14 B/param)
    # are ACTUALLY allocated and seeded — this is the real model, not a
    # 2-layer slice — and the phases of the real streamed step (wire h2d,
    # layer fwd, vjp, grad d2h + fused C++ Adam) are measured with the
    # engine's own compiled fns on the chip.  When the host↔device link
    # can carry a full step inside the budget the engine's real
    # train_step is timed; over a slow link the honest number is the
    # per-layer measured phases composed over all 32 layers (streaming is
    # layer-linear BY DESIGN — O(2 layers) device residency), reported
    # with projected=true + the link stats that explain it.
    # (vocab 32000 keeps the RESIDENT embed/head optimizer states inside
    # a 16G chip's HBM; every trunk shape is 8B-true.)
    try:
        _budget_check()
        extras.setdefault("variants", {})["llama8b_infinity"] = \
            _bench_llama8b_infinity()
        v = extras["variants"]["llama8b_infinity"]
        extras["variants"]["llama8b_infinity_mfu"] = v.get("mfu")
        extras["variants"]["llama8b_infinity_tokens_per_sec"] = \
            v.get("tokens_per_sec")
        extras["variants"]["llama8b_infinity_params"] = v.get("params")
    except Exception as e:
        free_hbm()
        extras.setdefault("variants", {})[
            "llama8b_infinity_error"] = str(e)[:300]

    _mark("infinity_sp_miniature")
    # -- ladder config 5's composition (Infinity × Ulysses SP) on-chip ----
    try:
        _budget_check()
        extras.setdefault("variants", {})["llama_infinity_sp"] = \
            _bench_infinity_sp_miniature()
        extras["variants"]["llama_infinity_sp_tokens_per_sec"] = \
            extras["variants"]["llama_infinity_sp"]["tokens_per_sec"]
    except Exception as e:
        free_hbm()
        extras.setdefault("variants", {})[
            "llama_infinity_sp_error"] = str(e)[:300]

    _mark("resnet_cifar")
    # -- driver ladder config 1: CIFAR ResNet-56, ZeRO-0 -------------------
    try:
        _budget_check()
        from deepspeed_tpu.models.resnet import ResNetConfig, ResNetModel

        rcfg = ResNetConfig.resnet56(dtype=jnp.bfloat16)
        rb = 128
        rng0 = np.random.RandomState(0)
        rdata = {
            "images": jnp.asarray(rng0.randn(
                rb, rcfg.image_size, rcfg.image_size, 3).astype(np.float32)),
            "labels": jnp.asarray(rng0.randint(0, rcfg.num_classes,
                                               size=(rb,))),
        }
        eng = build_engine(rcfg, rb, zero_stage=0, model_cls=ResNetModel)
        # measure() counts batch*seq tokens; seq=1 makes that images/sec,
        # with its median-of-segments noise rejection and budget logic
        ips = measure(eng, rb, 1, rcfg.num_classes, steps=20,
                      budget_s=45.0, data=rdata)
        extras["variants"]["resnet56_cifar_images_per_sec"] = round(ips, 1)
        del eng, rdata
        free_hbm()
    except Exception as e:
        free_hbm()
        extras.setdefault("variants", {})[
            "resnet_cifar_error"] = str(e)[:200]

    _mark("fused_adam_probe")
    # -- SURVEY row 30 evidence: a hand-fused Pallas Adam only matters if
    # XLA leaves update bandwidth on the table.  The probe times an
    # isolated optax adamw step over a 13.75M-param plane and reports
    # achieved HBM GB/s (7 fp32 passes/param) — read against the chip's
    # ~820 GB/s peak, it bounds what a custom kernel could win on a
    # component that is ~2%% of a training step.
    try:
        _budget_check()
        import optax

        n = 110_000_000 // 8  # one shard-sized param plane
        p = jnp.zeros((n,), jnp.float32)
        g = jnp.ones((n,), jnp.float32) * 1e-3
        tx = optax.adamw(1e-4)
        state = tx.init(p)

        @jax.jit
        def opt_step(p, g, state):
            u, state = tx.update(g, state, p)
            return optax.apply_updates(p, u), state

        p2, state = opt_step(p, g, state)  # compile
        float(jnp.sum(p2))
        # 200 chained steps between fences, so the number reflects the
        # kernel and not the fence
        t0 = time.perf_counter()
        for _ in range(200):
            p2, state = opt_step(p2, g, state)
        float(jnp.sum(p2))
        dt = (time.perf_counter() - t0) / 200
        # bytes moved: p r/w + g r + m r/w + v r/w = 7 floats/param
        gbps = 7 * 4 * n / dt / 1e9
        extras["variants"]["optax_adam_hbm_gbps"] = round(gbps, 1)

        # the one-pass fused kernel over the SAME plane + byte accounting
        # (ops/pallas/fused_optimizer.py): one read of g + one r/w of
        # p/m/v, no materialized updates tree — the effective GB/s over
        # the identical 7-floats/param logical traffic is the gated
        # fused_adam_hbm_gbps (acceptance: > optax_adam_hbm_gbps)
        from deepspeed_tpu.ops.pallas.fused_optimizer import (
            FusedAdamConfig, apply_fused_adam)

        fcfg = FusedAdamConfig(weight_decay=0.01, decoupled_wd=True)
        fstate = tx.init(p)

        @jax.jit
        def fused_step(p, g, state):
            return apply_fused_adam(state, p, g, 1e-4, 1.0, fcfg)

        p3, fstate = fused_step(p, g, fstate)  # compile
        float(jnp.sum(p3))
        t0 = time.perf_counter()
        for _ in range(200):
            p3, fstate = fused_step(p3, g, fstate)
        float(jnp.sum(p3))
        fdt = (time.perf_counter() - t0) / 200
        fgbps = 7 * 4 * n / fdt / 1e9
        extras["fused_adam_hbm_gbps"] = round(fgbps, 1)
        extras["variants"]["fused_adam_hbm_gbps"] = round(fgbps, 1)
        extras["variants"]["fused_vs_optax_adam"] = round(fgbps / gbps, 2)
        del p, g, p2, p3, state, fstate
        free_hbm()
    except Exception as e:
        free_hbm()
        extras.setdefault("variants", {})[
            "fused_adam_probe_error"] = str(e)[:200]

    _mark("infinity")
    # -- ZeRO-Infinity capacity: peak params/chip the tiering can hold -----
    # CAPACITY math, not a measured training run: a layer-streaming
    # step moves every layer's params over the host link, so the number
    # here is what the
    # cpu/nvme tiers can back: fp32 master + Adam moments (12 B/param)
    # stream from host/NVMe, bf16 residence is O(2 layers).  The suite's
    # test_infinity.py exercises the actual streaming path.
    try:
        import shutil

        with open("/proc/meminfo") as f:
            info = {ln.split(":")[0]: int(ln.split()[1]) for ln in f}
        host_free = info.get("MemAvailable", 0) * 1024
        # a tmpfs /tmp IS host RAM — counting it again would double-count
        with open("/proc/mounts") as f:
            tmp_is_tmpfs = any(
                ln.split()[1] == "/tmp" and ln.split()[0] == "tmpfs"
                for ln in f)
        nvme_free = 0 if tmp_is_tmpfs else shutil.disk_usage("/tmp").free
        # conservative: keep 20% headroom on each tier
        capacity = int(0.8 * (host_free + nvme_free) / 12)
        extras.setdefault("variants", {})[
            "infinity_peak_params_per_chip"] = capacity
    except Exception:
        pass

    # perf baseline for local tracking + the regression sentinel (the
    # cross-round ratio uses R01; `python -m deepspeed_tpu.telemetry
    # perf check --baseline .bench_baseline.json` gates later runs)
    hist = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        ".bench_baseline.json")
    try:
        from deepspeed_tpu.telemetry.perf import save_baseline

        save_baseline(hist, {"metric": "llama_110m_train_tokens_per_sec",
                             "value": tps, **extras},
                      source="bench.py headline")
    except Exception:
        try:  # the sentinel must never cost the bench its artifact line
            with open(hist, "w") as f:
                json.dump({"tokens_per_sec": tps, "mfu": extras["mfu"]}, f)
        except Exception:
            pass

    print(json.dumps({
        "metric": "llama_110m_train_tokens_per_sec",
        "value": round(tps, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(tps / R01_TOKENS_PER_SEC, 3),
        **extras,
    }))


if __name__ == "__main__":
    main()
