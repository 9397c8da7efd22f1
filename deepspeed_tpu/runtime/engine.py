"""DeepSpeedEngine — the training engine as ONE compiled XLA program per step.

Capability parity with the reference ``deepspeed/runtime/engine.py`` [K]
(~4k LoC): config-driven optimizer/ZeRO/precision assembly, gradient
accumulation, loss scaling + overflow skip, gradient clipping, LR scheduling,
throughput/monitor logging, and the public train-loop contract
``engine.backward(loss)`` / ``engine.step()`` /
``set_gradient_accumulation_boundary`` [L ACC-DS:264-281].

TPU-first architecture (SURVEY §7): instead of an eager module wrapper with
hooks, the engine compiles the whole optimizer step — microbatch scan (grad
accumulation), fp32 accumulation, overflow check, clip, optax update, ZeRO
sharding constraints — into a single ``jit`` with donated state.  GSPMD
inserts every collective the reference issues by hand (psum for DP, reduce-
scatter for stage 2, all-gather for stage 3).  The eager
``backward()``/``step()`` surface is a thin compat shim that buffers
microbatches and fires the compiled step at the accumulation boundary —
mandatory because separate host-side backward/step calls would break XLA
fusion.
"""

from __future__ import annotations

import collections
import contextlib
import os
import time
import weakref
from typing import Any, Callable, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec

from ..comm import comm as dist
from ..parallel.mesh import DP_AXES, MeshLayout
from ..utils import groups as groups_mod
from ..utils.logging import log_dist, logger
from ..utils.timer import SynchronizedWallClockTimer, ThroughputTimer
from .checkpoint_engine import make_checkpoint_engine
from .config import DeepSpeedConfig
from .lr_schedules import LRScheduler, Schedule, get_lr_schedule
from .optimizers import build_optimizer
from .precision import (DynamicLossScaler, LossScaleState, cast_tree,
                        clip_grads_by_global_norm, global_grad_norm,
                        has_overflow)
from .zero.sharder import ZeroShardingPolicy
from ..utils.jax_compat import shard_map as _shard_map
from ..telemetry import numerics, startup_span

LossFn = Callable[[Any, Any], jnp.ndarray]  # (params, batch) -> scalar loss


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jnp.ndarray  # i32 — optimizer steps taken (skips excluded)
    loss_scale: LossScaleState
    skipped_steps: jnp.ndarray  # i32
    # per-worker communication state: 1-bit error-feedback residuals
    # (leading dim = DP world, sharded over the DP axes); () when unused
    comm_state: Any = ()


class DeepSpeedEngine:
    """One engine = (loss_fn, params, config) compiled over the active mesh."""

    def __init__(self,
                 loss_fn: LossFn,
                 params: Any,
                 config: DeepSpeedConfig,
                 optimizer: Optional[optax.GradientTransformation] = None,
                 lr_schedule: Optional[Schedule] = None,
                 module: Any = None,
                 mesh=None):
        self.config = config
        self.loss_fn = loss_fn
        self.module = module
        self.mesh = mesh if mesh is not None else groups_mod.get_mesh()
        self.policy = ZeroShardingPolicy.from_config(self.mesh,
                                                     config.zero_optimization)
        # Model-provided TP/SP placement (reference analogue: AutoTP policy);
        # ZeRO DP sharding is composed on top by the policy.
        self.base_specs = (module.param_specs()
                          if callable(getattr(module, "param_specs", None))
                          else None)
        from ..parallel.mesh import AXIS_TENSOR

        if (self.base_specs is None
                and int(self.mesh.shape.get(AXIS_TENSOR, 1)) > 1):
            # AutoTP fallback: models without hand-authored specs get
            # name-pattern-inferred tensor placement (reference AutoTP for
            # arbitrary modules); GSPMD keeps any inference correct
            from .tensor_parallel import infer_tp_specs

            self.base_specs = infer_tp_specs(params)
            log_dist("AutoTP: inferred tensor-parallel specs from param "
                     "names (model provides no param_specs)")
        from .zero.config import OffloadDeviceEnum

        self.offload_enabled = (config.zero_optimization.offload_optimizer_device()
                                != OffloadDeviceEnum.none)
        if self.offload_enabled and optimizer is not None:
            # reference behavior [L ACC:2365-2367]: offload requires the DS
            # CPU optimizer unless zero_force_ds_cpu_optimizer is disabled
            if config.zero_force_ds_cpu_optimizer:
                raise ValueError(
                    "a client optimizer cannot be combined with "
                    "offload_optimizer; remove it or set "
                    "zero_force_ds_cpu_optimizer: false to acknowledge the "
                    "config-derived CPU optimizer will be used instead")
            logger.warning("offload_optimizer active: ignoring the client "
                           "optimizer, using the config-derived CPU optimizer")
            optimizer = None
        self.offload_opt = None  # built after state init (needs placed params)
        self.infinity = None     # ZeRO-Infinity layer-streaming executor
        self._infinity_requested = (
            config.zero_optimization.offload_param_device()
            != OffloadDeviceEnum.none)
        if self._infinity_requested:
            streamable = all(
                callable(getattr(module, m, None))
                for m in ("embed_fwd", "decoder_layer", "head_loss",
                          "batch_labels"))
            if not streamable:
                raise ValueError(
                    "offload_param requires a layer-streamable module "
                    "(embed_fwd/decoder_layer/head_loss protocol — see "
                    "runtime/swap_tensor/infinity_engine.py); "
                    f"{type(module).__name__} does not implement it")
            eff_mesh = mesh if mesh is not None else groups_mod.get_mesh()
            world = int(np.prod(list(eff_mesh.shape.values())))
            if world > 1 and getattr(module, "mesh", None) is None:
                raise ValueError(
                    "ZeRO-Infinity layer streaming on a multi-device mesh "
                    "requires the module to be built WITH that mesh (its "
                    "per-layer programs carry the sharding constraints); "
                    "pass mesh= to the model constructor")
            if int(eff_mesh.shape.get("pipe", 1)) > 1:
                raise NotImplementedError(
                    "layer streaming is itself layer-sequential; combine it "
                    "with dp/tp/sp axes, not pipe")

        self.compute_dtype = config.dtype()
        self.fp16_enabled = config.fp16.enabled is True
        self.bf16_enabled = config.bf16.enabled is True

        # --- pipeline schedule routing (reference TrainSchedule = 1F1B) --
        from ..parallel.mesh import AXIS_PIPE

        pp = int(self.mesh.shape.get(AXIS_PIPE, 1))
        self._pp_1f1b = (
            pp > 1
            and str(config.pipeline.schedule).lower() == "1f1b"
            and isinstance(params, dict) and "layers" in params
            and all(callable(getattr(module, m, None))
                    for m in ("embed_fwd", "decoder_layer", "head_loss",
                              "batch_labels")))
        self.last_pipe_stats = None  # set at trace time by _pp_1f1b_grads
        from ..parallel.mesh import AXIS_TENSOR as _AT

        fallback_reason = None
        compressed_comm = (
            config.zero_optimization.zero_quantized_gradients
            or config.zero_optimization.zero_quantized_weights
            or (config.optimizer is not None
                and "onebit" in config.optimizer.type.lower().replace("-",
                                                                      "")))
        self._pp_1f1b_manual_tp = False
        tp = int(self.mesh.shape.get(_AT, 1))
        if self._pp_1f1b and tp > 1:
            # XLA's SPMD partitioner CHECK-fails on the 1F1B partial-manual
            # shard_map combined with tensor-axis GSPMD constraints inside
            # (spmd_partitioner_util.cc partition-group mismatch, verified
            # on jax 0.9 CPU).  The workaround manualizes the TENSOR axis
            # too: the model supplies a Megatron column/row layer with
            # explicit collectives (decoder_layer_manual_tp), leaving no
            # tensor constraint inside the region.  Models without that
            # hook (or with a seq axis, whose constraints would hit the
            # same CHECK) fall back to GPipe-through-autodiff, which
            # partitions fine and computes identical gradients at a larger
            # activation footprint.
            from ..parallel.mesh import AXIS_SEQ as _AS

            cfg_m = getattr(module, "config", None)
            shards_ok = (
                cfg_m is not None
                and getattr(cfg_m, "num_heads", 0) > 0
                and getattr(cfg_m, "num_heads", 0) % tp == 0
                and getattr(cfg_m, "num_kv_heads", 0) > 0
                and getattr(cfg_m, "num_kv_heads", 0) % tp == 0
                and getattr(cfg_m, "intermediate_size", 0) > 0
                and getattr(cfg_m, "intermediate_size", 0) % tp == 0)
            if (callable(getattr(module, "decoder_layer_manual_tp", None))
                    and int(self.mesh.shape.get(_AS, 1)) == 1
                    and shards_ok):
                self._pp_1f1b_manual_tp = True
            else:
                fallback_reason = ("+ tensor parallelism trips an XLA "
                                   "partitioner limitation (and this "
                                   "module has no manual-TP layer hook)")
        if fallback_reason is None and self._pp_1f1b and compressed_comm:
            fallback_reason = ("does not compose with compressed-comm "
                              "paths (1-bit/qwZ/qgZ)")
        if fallback_reason is not None:
            log_dist(f"pipeline.schedule=1f1b {fallback_reason} — falling "
                     f"back to the GPipe (autodiff) schedule")
            self._pp_1f1b = False
        elif (pp > 1 and not self._pp_1f1b
              and str(config.pipeline.schedule).lower() == "1f1b"):
            log_dist("pipeline.schedule=1f1b needs the layer-streamable "
                     "module protocol (embed_fwd/decoder_layer/head_loss) "
                     "— running the module's own pipeline path instead")
        gas = config.gradient_accumulation_steps
        self.gradient_accumulation_steps = int(gas) if isinstance(gas, int) else 1
        self.micro_batch_size = config.train_micro_batch_size_per_gpu
        self.train_batch_size = config.train_batch_size

        # --- LR schedule -------------------------------------------------
        if lr_schedule is not None:
            self._schedule = lr_schedule
        elif config.scheduler is not None:
            params_d = dict(config.scheduler.params.model_dump())
            params_d.update(config.scheduler.params.model_extra or {})
            self._schedule = get_lr_schedule(config.scheduler.type, params_d)
        else:
            base_lr = 1e-3
            if config.optimizer is not None and not isinstance(
                    config.optimizer.params.lr, str):
                base_lr = float(config.optimizer.params.lr)
            self._schedule = lambda step: base_lr
        self.lr_scheduler = LRScheduler(self._schedule)

        # --- 1-bit compressed-gradient family (reference fp16/onebit [K]) -
        opt_name = (config.optimizer.type.lower().replace("_", "")
                    if config.optimizer is not None else "")
        self.onebit_enabled = opt_name in ("onebitadam", "onebitlamb",
                                           "zerooneadam")
        self.onebit_freeze_step = 0
        if self.onebit_enabled:
            # reference OnebitAdam `freeze_step` [K]: full-precision warmup
            # before compression kicks in (variance estimates settle first)
            extra = (config.optimizer.params.model_extra or {})
            self.onebit_freeze_step = int(extra.get("freeze_step", 0) or 0)
            if self.policy.stage >= 2:
                raise ValueError(
                    "1-bit optimizers compress the DP gradient allreduce; "
                    "ZeRO stage >= 2 reduce-scatters instead — use stage 0/1 "
                    "(reference has the same restriction)")
            if self.fp16_enabled:
                raise NotImplementedError(
                    "1-bit compression + fp16 loss scaling not supported; "
                    "use bf16/fp32")
            if self.mesh is not None and int(
                    self.mesh.shape.get("pipe", 1)) > 1:
                raise NotImplementedError("1-bit + pipeline parallelism "
                                          "not supported yet")
            if self.offload_enabled or self._infinity_requested:
                raise NotImplementedError(
                    "1-bit optimizers are not supported with optimizer/param "
                    "offload (the offload step would discard the error-"
                    "feedback residuals) — pick one")

        # --- ZeRO++ qwZ: int8 quantized-weight all-gather -----------------
        # (runtime/zero/qwz.py: sharded master → int8+scales → replicated
        # sharding constraint, so the GSPMD all-gather moves int8 bytes;
        # straight-through backward)
        self.qwz_enabled = bool(config.zero_optimization.zero_quantized_weights)
        if self.qwz_enabled and (self.offload_enabled
                                 or self._infinity_requested):
            raise NotImplementedError(
                "zero_quantized_weights + offload/infinity not supported "
                "(those paths own their own param movement)")
        self.qgz_enabled = bool(config.zero_optimization.zero_quantized_gradients)
        if self.qgz_enabled:
            if self.onebit_enabled:
                raise ValueError("zero_quantized_gradients and 1-bit "
                                 "optimizers are mutually exclusive "
                                 "compression schemes")
            if self.offload_enabled or self._infinity_requested:
                raise NotImplementedError(
                    "zero_quantized_gradients + offload not supported yet")
            if self.mesh is not None and int(
                    self.mesh.shape.get("pipe", 1)) > 1:
                raise NotImplementedError("qgZ + pipeline parallelism "
                                          "not supported yet")
            from .zero.qgz import wire_bytes as _qgz_bytes

            # params aren't placed yet; log after state init instead
            self._log_qgz_bytes = _qgz_bytes

        # --- optimizer ---------------------------------------------------
        with startup_span("startup/engine/optimizer"):
            self.optimizer = (optimizer if optimizer is not None
                              else build_optimizer(config, lr=self._schedule))
        clip = config.gradient_clipping
        self.gradient_clipping = 0.0 if isinstance(clip, str) else float(clip)

        # --- Pallas kernel plane (kernels.* config group) ----------------
        kcfg = config.kernels
        self.overlap_zero3 = bool(kcfg.overlap_collectives)
        self.overlap_chunks = max(int(kcfg.overlap_chunks), 1)
        self.fused_adam_enabled = False
        self._fused_adam_cfg = None
        if kcfg.fused_adam:
            fused_ok = (optimizer is None
                        and opt_name in ("adam", "fusedadam", "adamw",
                                         "deepspeedcpuadam")
                        and not (self.offload_enabled
                                 or self._infinity_requested
                                 or self.onebit_enabled or self._pp_1f1b))
            if (fused_ok and int(self.mesh.devices.size) > 1
                    and jax.default_backend() == "tpu"):
                # GSPMD cannot partition a Mosaic call ("Mosaic kernels
                # cannot be automatically partitioned"), and the update
                # runs on ZeRO-sharded leaves outside any shard_map (off
                # the TPU the same entry points run their jnp reference,
                # which partitions like any other XLA code)
                logger.warning(
                    "kernels.fused_adam requested on a "
                    f"{int(self.mesh.devices.size)}-chip mesh, where its "
                    "Pallas kernels cannot lower — keeping the optax chain")
                fused_ok = False
            elif not fused_ok:
                log_dist("kernels.fused_adam requested but the active "
                         "optimizer/path is not a config-built adam "
                         "family (or offload/1-bit/1F1B owns the update) "
                         "— keeping the optax chain")
            else:
                from ..ops.pallas.fused_optimizer import FusedAdamConfig

                op = config.optimizer.params if config.optimizer else None
                betas = getattr(op, "betas", [0.9, 0.999])
                if isinstance(betas, str):  # "auto"
                    betas = [0.9, 0.999]
                eps_v = getattr(op, "eps", 1e-8)
                wd_v = getattr(op, "weight_decay", 0.0)
                self._fused_adam_cfg = FusedAdamConfig(
                    b1=float(betas[0]), b2=float(betas[1]),
                    eps=1e-8 if isinstance(eps_v, str) else float(eps_v),
                    weight_decay=(0.0 if isinstance(wd_v, str)
                                  else float(wd_v)),
                    # build_optimizer maps adamw/cpu-adam to optax.adamw
                    # (decoupled decay); plain adam takes additive L2
                    decoupled_wd=opt_name in ("adamw", "deepspeedcpuadam"))
                self.fused_adam_enabled = True
                log_dist("kernels.fused_adam: one-pass fused Adam update "
                         f"active ({self._fused_adam_cfg})")

        # --- loss scaler (fp16 only; bf16/fp32 need none) ----------------
        # Scale cap 2^15: the loss cotangent enters the f16 subgraph as the
        # scale itself, and f16 max is 65504 — a 2^16 seed is inf before the
        # first multiply. (The dynamic grower may probe 2^16 and back off.)
        fp16 = config.fp16
        self.loss_scaler = (DynamicLossScaler.from_config(fp16)
                            if self.fp16_enabled else None)

        # --- unified telemetry (telemetry/) ------------------------------
        # (before state init so placement spans of the build are captured)
        from ..telemetry import configure_from_config, get_telemetry

        if config.telemetry.enabled:
            configure_from_config(config.telemetry)
        elif "enabled" in config.telemetry.model_fields_set:
            # an EXPLICIT {"telemetry": {"enabled": false}} turns the
            # process-global hub off (a defaulted-off config leaves a hub
            # another job enabled alone)
            get_telemetry().configure(enabled=False)
        self.telemetry = get_telemetry()
        self._telemetry_steps = bool(config.telemetry.enabled
                                     and config.telemetry.step_records)
        self._telemetry_fence = bool(config.telemetry.device_fence)
        #: recent per-step records (bench/autotuner read the SAME numbers
        #: the engine logged — they can never disagree)
        self.step_records: collections.deque = collections.deque(maxlen=512)
        #: last comms_logger exec_totals snapshot — StepRecords carry the
        #: per-step DELTA (the cumulative number is already comm_bytes)
        self._last_exec_totals = (0.0, 0.0)
        self.last_step_record = None
        #: analytic model FLOPs per optimizer step; callers that know the
        #: model shape set it so StepRecords carry TFLOPS/MFU
        self.flops_per_step = 0.0
        # ADVICE round-5: under `deepspeed --autotuning` candidate profiling
        # every step is fenced, so samples/sec ranks candidates by DEVICE
        # step time instead of host dispatch/queue backpressure
        self._autotuning_fence = bool(os.environ.get("DS_AUTOTUNING_RESULT"))

        # --- active diagnostics: flight recorder / watchdog / health ------
        # (telemetry/{flight_recorder,watchdog,health}.py — ISSUE 2)
        tcfg = config.telemetry
        self.flight_recorder = None
        self.watchdog = None
        self.health = None
        wd_cfg, h_cfg = tcfg.watchdog, tcfg.health
        from ..telemetry.flight_recorder import recorder_from_config

        self.flight_recorder = recorder_from_config(tcfg)
        if wd_cfg.enabled:
            from ..telemetry import HangWatchdog, set_watchdog

            self.watchdog = HangWatchdog(
                hang_timeout_s=wd_cfg.hang_timeout_s,
                poll_interval_s=wd_cfg.poll_interval_s,
                action=wd_cfg.action, comm_liveness=wd_cfg.comm_liveness,
                # None when the recorder is disabled — the watchdog then
                # trips WITHOUT writing bundles (the operator said no)
                recorder=self.flight_recorder,
                device_probe=wd_cfg.device_probe,
                device_probe_timeout_s=wd_cfg.device_probe_timeout_s,
                heartbeat_max_bytes=getattr(wd_cfg, "heartbeat_max_bytes",
                                            1024))
            # process-global handle: the elastic agent folds the
            # watchdog's heartbeat_payload into rendezvous heartbeats
            set_watchdog(self.watchdog)
            # start NOW, not after the first step: the most common hang
            # (a misconfigured mesh's first collective) happens INSIDE
            # the first train_step, before any progress notification
            self.watchdog.start()
        # collective ledger (telemetry/collective_ledger.py — ISSUE 3):
        # every comms-logger record feeds a monotonic per-rank ledger
        # whose tail hash rides elastic heartbeats (live desync) and
        # whose tail lands in every debug bundle (offline divergence)
        self.collective_ledger = None
        agg_cfg = tcfg.aggregation
        if agg_cfg.enabled and agg_cfg.ledger_enabled:
            from ..telemetry import configure_collective_ledger

            self.collective_ledger = configure_collective_ledger(
                max_entries=agg_cfg.ledger_max_entries,
                tail=agg_cfg.ledger_tail,
                exec_feed=agg_cfg.ledger_exec_feed,
                recorder=self.flight_recorder)
        if h_cfg.enabled and self._telemetry_steps:
            from ..telemetry import HealthMonitor

            self.health = HealthMonitor(
                window=h_cfg.window, min_points=h_cfg.min_points,
                loss_spike_zscore=h_cfg.loss_spike_zscore,
                grad_norm_ratio=h_cfg.grad_norm_ratio,
                loss_scale_floor=h_cfg.loss_scale_floor,
                consecutive_scale_drops=h_cfg.consecutive_scale_drops,
                throughput_frac=h_cfg.throughput_frac,
                compile_dominated_frac=h_cfg.compile_dominated_frac,
                recompile_storm_threshold=h_cfg.recompile_storm_threshold,
                control_plane=h_cfg.control_plane,
                memory_pressure_frac=tcfg.memory.pressure_frac,
                memory_pressure_steps=tcfg.memory.pressure_steps,
                host_leak_window=tcfg.memory.leak_window,
                host_leak_frac=tcfg.memory.leak_frac,
                numerics_underflow_frac=tcfg.numerics.underflow_frac,
                numerics_underflow_steps=tcfg.numerics.underflow_steps,
                numerics_layer_grad_ratio=tcfg.numerics.layer_grad_ratio,
                numerics_layer_grad_floor=tcfg.numerics.layer_grad_floor,
                numerics_entropy_floor=tcfg.numerics.entropy_floor,
                numerics_entropy_steps=tcfg.numerics.entropy_steps,
                registry=(self.telemetry.registry if self.telemetry.enabled
                          else None),
                recorder=self.flight_recorder)

        # --- performance observability plane (telemetry/perf — ISSUE 5) --
        # compile/recompile tracking over every engine jit site + the
        # goodput wall-clock ledger.  Configured BEFORE _init_state so
        # the build-time programs (optimizer init, bf16 wire cast, 1-bit
        # residuals) are in the compile table too.
        self.compile_tracker = None
        self.goodput = None
        self.cost_ledger = None
        self._last_anatomy = None
        self._anatomy_cfg = pcfg = tcfg.perf
        self._compile_dominated_frac = float(h_cfg.compile_dominated_frac)
        if pcfg.enabled and tcfg.enabled:
            from ..telemetry.perf import (configure_compile_tracker,
                                          configure_goodput_ledger)

            if pcfg.compile_tracker:
                self.compile_tracker = configure_compile_tracker(
                    enabled=True, max_events=pcfg.compile_max_events,
                    recorder=self.flight_recorder)
            if pcfg.goodput:
                self.goodput = configure_goodput_ledger(
                    enabled=True, window_s=pcfg.goodput_window_s,
                    recorder=self.flight_recorder)
            # anatomy plane (ISSUE 17): the cost ledger rides the
            # compile tracker — every AOT compile is harvested for
            # FLOPs/HBM/collective bytes + a roofline verdict at the
            # moment the executable exists, so the steady state pays
            # nothing
            if pcfg.anatomy and self.compile_tracker is not None:
                from ..telemetry.anatomy import configure_cost_ledger

                self.cost_ledger = configure_cost_ledger(
                    tracker=self.compile_tracker,
                    recorder=self.flight_recorder)

        # --- fleet profiler capture plane (telemetry/profiler — ISSUE 20) --
        # the plane is installed (or not) by initialize()/the serving
        # worker; the engine only holds the reference so train_step can
        # feed the step index (two attribute reads when no window is
        # armed) and stamps its anatomy site for the calibration join
        self._profiler_plane = None
        if tcfg.enabled and tcfg.profiler.enabled:
            from ..telemetry.profiler import get_profiler_plane

            self._profiler_plane = get_profiler_plane()
            if self._profiler_plane is not None:
                self._profiler_plane.site = self._anatomy_site()
                if tcfg.profiler.duty_cycle_pct > 0.0:
                    self._profiler_plane.enable_duty_cycle()

        # --- memory observability plane (telemetry/memory — ISSUE 7) -----
        # per-pool byte ledger fed by the allocation sites below
        # (_init_state placement, offload, swappers, KV pool, snapshots),
        # per-step HBM/RSS/swap-IO samples on StepRecords, and the OOM
        # catch around the step dispatch.  Configured BEFORE _init_state
        # so placement registers into a live ledger.
        self.memory_ledger = None
        mem_cfg = tcfg.memory
        if mem_cfg.enabled and (tcfg.enabled
                                or self.flight_recorder is not None):
            from ..telemetry.memory import configure_memory_ledger

            self.memory_ledger = configure_memory_ledger(
                enabled=True, top_k=mem_cfg.top_k,
                recorder=self.flight_recorder)
        self._mem_census_every = int(mem_cfg.live_census_every)

        # --- numerics observability plane (telemetry/numerics — ISSUE 18) --
        # in-graph tensor-health probes: sampled steps run a SEPARATE
        # jitted step variant whose trace carries the probe stats in an
        # aux output pytree (the base step's program is never touched —
        # probes off means today's exact jaxpr), and a non-finite loss
        # triggers the probes-on forensic re-run that NAMES the first
        # bad layer (see _run_nonfinite_forensics)
        ncfg = tcfg.numerics
        self._numerics_cfg = ncfg
        self._last_numerics: Optional[Dict[str, Any]] = None
        self._last_nonfinite_report = None
        self._numerics_step_fn = None
        self._moe_step_fn = None
        self._forensic_fwd_fn = None
        self._numerics_context: Optional[Dict[str, Any]] = None
        if self.flight_recorder is not None and (ncfg.enabled
                                                 or ncfg.moe_gauges):
            # every bundle carries the latest capture (the CLI's
            # `numerics show` fallback when no numerics.json exists).
            # Through a weak reference: the recorder is process-global,
            # and a provider closing over the engine would pin its whole
            # train state in HBM after the caller drops the engine
            me = weakref.ref(self)
            self.flight_recorder.register_context(
                "numerics",
                lambda: getattr(me(), "_numerics_context", None))

        # --- place state on the mesh, sharded per ZeRO stage -------------
        self.state = self._init_state(params)
        if self.qgz_enabled:
            q, f = self._log_qgz_bytes(self.state.params)
            log_dist(f"qgZ: DP grad reduction wire bytes {f/2**20:.1f} MiB "
                     f"→ {q/2**20:.1f} MiB per step ({f/q:.1f}× reduction)")

        # --- self-healing resilience plane (resilience/ — ISSUE 4) -------
        # snapshots + recovery policy + fault injection.  The injector is
        # independent of `resilience.enabled`: injecting faults WITHOUT
        # recovery is how you prove the failure actually breaks a run.
        self.snapshots = None
        self.resilience = None
        with startup_span("startup/import",
                          {"module": "deepspeed_tpu.resilience"}):
            # this tree holds no checkpoint library: orbax.checkpoint is
            # an import leaf of its own, opened only by what will save
            # (checkpoint_engine.orbax_checkpoint)
            from .. import resilience  # noqa: F401

        with startup_span("startup/engine/resilience"):
            self._init_resilience(config)
        # the checkpoint engine is made with the engine, not by the first
        # save: an async one loads orbax.checkpoint in its constructor
        # (seconds, cold), which a save that exists to return at once
        # must not meet; a sync one loads nothing until it saves or loads
        self._ckpt_engine = make_checkpoint_engine(config)
        self._train_step_fn = None  # compiled lazily (first call)
        #: forced-partial-boundary programs, keyed by microbatch count
        self._partial_step_fns: Dict[int, Any] = {}
        self._warmup_step_fn = None  # 1-bit warmup variant
        self._eval_loss_fn = None

        # --- random-LTD (data_efficiency.data_routing) --------------------
        # keep-count changes along a quantized schedule; each bucket gets
        # its own compiled step (the model reads ltd_keep at trace time)
        self._ltd_cfg = None
        self._ltd_sched = None
        self._ltd_fns: Dict[int, Any] = {}
        de = config.data_efficiency
        routing = (de.data_routing.get("random_ltd", {})
                   if de.enabled else {})
        if routing.get("enabled"):
            ids = tuple(routing.get("random_ltd_layer_id", []))
            if not hasattr(self.module, "ltd_keep"):
                logger.warning("random_ltd enabled but the model has no "
                               "ltd_keep support; ignoring")
            elif not ids:
                # explicit beats implicit: without layer ids the model
                # would silently never drop a token while the engine
                # compiles a redundant program per keep bucket
                logger.warning("random_ltd enabled but random_ltd_layer_id "
                               "is empty; ignoring (list the layers to "
                               "apply token dropping to)")
            else:
                self._ltd_cfg = dict(routing)
                self.module.ltd_layer_ids = ids

        # --- compat-mode bookkeeping -------------------------------------
        self._pending_batch: Any = None
        self._microbatch_buffer: List[Any] = []
        self._accumulation_boundary_forced: Optional[bool] = None
        self.global_steps = 0
        self.micro_steps = 0
        self.last_metrics: Dict[str, Any] = {}
        self._last_health_events: List[Any] = []
        self.timers = SynchronizedWallClockTimer()
        self.tput_timer = ThroughputTimer(
            batch_size=int(self.train_batch_size or 1))
        self.steps_per_print = config.steps_per_print
        self.monitor = None  # attached by monitor subsystem when configured

    def _init_resilience(self, config: DeepSpeedConfig) -> None:
        """The resilience plane of the constructor: the fault injector,
        and with ``resilience.enabled`` the snapshots and the policy."""
        from ..resilience.faults import FaultInjector

        self.fault_injector = FaultInjector.from_config(
            config.resilience, recorder=self.flight_recorder)
        rcfg = config.resilience
        if rcfg.enabled:
            from ..resilience import (RecoveryPolicy, SnapshotManager,
                                      SnapshotUnsupportedError,
                                      check_snapshot_support)

            try:
                check_snapshot_support(self)
            except SnapshotUnsupportedError as e:
                # degrade, don't die: the job still trains (and ordinary
                # checkpoints still cover it) — only the self-healing
                # rollback/resume loop is unavailable on this engine
                logger.warning(
                    f"resilience: snapshots DISABLED for this run — {e}")
                rcfg = None
        if rcfg is not None and rcfg.enabled:
            self.snapshots = SnapshotManager(
                self, rcfg, recorder=self.flight_recorder)
            self.resilience = RecoveryPolicy(
                self, self.snapshots, rcfg, recorder=self.flight_recorder)
            if self.watchdog is not None:
                # emergency-save-if-responsive on the trip edge (runs on
                # the watchdog thread BEFORE its raise/exit action)
                self.watchdog.add_trip_listener(
                    self.resilience.on_watchdog_trip)
            elif rcfg.emergency_save_on_trip:
                logger.warning(
                    "resilience: emergency_save_on_trip is set but the "
                    "hang watchdog is off — hangs will NOT trigger an "
                    "emergency snapshot (enable telemetry.watchdog)")
            # the policy checks the loss scalar itself, but every OTHER
            # rollback trigger arrives as a HealthMonitor event — which
            # only exists when telemetry step records are on
            inert = [k for k in rcfg.rollback_on
                     if k != "nan_loss" and self.health is None]
            if inert:
                logger.warning(
                    f"resilience: rollback_on includes {inert} but the "
                    f"health monitor is off (it needs telemetry.enabled "
                    f"+ step_records + health.enabled) — those triggers "
                    f"will never fire; only the direct NaN-loss check "
                    f"is active")
            log_dist(f"resilience: snapshots every "
                     f"{rcfg.snapshot_interval} steps -> "
                     f"{rcfg.snapshot_dir} (tiers: memory"
                     + (", disk" if rcfg.disk_tier else "")
                     + (", buddy" if rcfg.buddy_tier else "") + ")")

    # ------------------------------------------------------------------
    # state construction
    # ------------------------------------------------------------------

    def _jit(self, fn, site: str, static_context=None, **jit_kwargs):
        """``jax.jit`` through the compile tracker (telemetry/perf):
        every engine program gets a compile event with lower/compile
        timing, and a recompile of the same site records a structured
        cause diff.  ``static_context`` names the closure-baked statics
        (gas, 1-bit warmup flag, LTD keep bucket) so a recompile caused
        by one of THOSE is named, not just 'signature changed'.  With
        the tracker off this IS ``jax.jit``."""
        from ..telemetry.perf import tracked_jit

        return tracked_jit(fn, site=site, tracker=self.compile_tracker,
                           static_context=static_context, **jit_kwargs)

    def _init_state(self, params: Any) -> TrainState:
        if self._infinity_requested:
            # ZeRO-Infinity: trunk params NEVER touch the device whole —
            # the streaming executor owns them (host/NVMe tier); only the
            # small resident subtree (embed/norm/head) lives in self.state
            from .swap_tensor import LayerStreamingEngine

            self.infinity = LayerStreamingEngine(
                self.module, params, self.config, self._schedule,
                mesh=getattr(self.module, "mesh", None),
                base_specs=self.base_specs)
            scale_state = LossScaleState(jnp.float32(1.0), jnp.int32(0),
                                         jnp.int32(0))
            if self.memory_ledger is not None:
                # only the small resident subtree (embed/norm/head) lives
                # on device; the trunk is the swapper's host planes,
                # registered by PartitionedParamSwapper itself
                self.memory_ledger.register_tree(
                    "params", "infinity/resident_params",
                    self.infinity.resident,
                    tag="Infinity resident subtree (embed/norm/head)")
            return TrainState(params=self.infinity.resident, opt_state=(),
                              step=jnp.int32(0), loss_scale=scale_state,
                              skipped_steps=jnp.int32(0))
        # placement is fenced only while the hub is on: the spans then
        # measure the transfer, else the enqueue (device_put is async), and
        # say which (``fenced``)
        placed = {"stage": self.policy.stage,
                  "fenced": self.telemetry.enabled}
        with startup_span("startup/place/params", dict(placed, of="host")):
            params = jax.tree.map(jnp.asarray, params)
        param_shardings = self.policy.param_shardings(params, self.base_specs)
        with startup_span("startup/place/params", placed):
            params = jax.device_put(params, param_shardings)
            if self.telemetry.enabled:
                jax.block_until_ready(params)
        if self.memory_ledger is not None:
            # the ZeRO placement site IS the params allocation: register
            # the logical tree bytes (per-device residency is bytes/dp at
            # stage 3 — the drift cross-check compares against the local
            # device, so the snapshot records both views)
            self.memory_ledger.register_tree(
                "params", "engine/placed_params", params,
                tag=f"zero stage {self.policy.stage} placed model params")
            # stage >= 2 grads exist only INSIDE the compiled step in
            # their reduce-scattered layout — tracked as transient fp32
            # bytes so the breakdown names them without skewing the
            # steady-state drift metric
            grad_bytes = sum(
                int(np.prod(np.shape(p))) * 4
                for p in jax.tree.leaves(params))
            self.memory_ledger.register(
                "grads", "engine/step_grads", grad_bytes, transient=True,
                tag="fp32 grad accumulators (transient, inside-step)")
            # kernel scratch attribution (ISSUE 12): the Pallas planes
            # that live OUTSIDE the params/grads/optimizer pools get
            # named entries under collective_scratch so peak_hbm gating
            # and OOM forensics can point at them
            mc = getattr(self.module, "config", None)
            # keyed on the MODEL's route (the module says whether its
            # step holds the flash kernels: the signal that decides
            # whether they actually run), not the kernels.flash_attention
            # config knob — the knob only steers builders that construct
            # the model
            holds_flash = getattr(self.module, "uses_flash_kernels", None)
            if holds_flash is not None and holds_flash():
                heads = int(getattr(mc, "num_heads", 0) or 0)
                max_s = int(getattr(mc, "max_seq_len", 0) or 0)
                layers = int(getattr(mc, "num_layers", 1) or 1)
                rows = int(self.micro_batch_size or 0)
                if heads and max_s and rows:
                    # fwd lse + bwd delta, fp32 per (row, head, pos); one
                    # layer's planes live at a time under remat
                    elems = rows * heads * max_s
                    stats = elems * 4
                    nbytes, tag = 2 * stats, "lse/delta softmax stats"
                    kept = getattr(self.module, "keeps_flash_residuals",
                                   None)
                    if not getattr(mc, "remat", True):
                        nbytes *= layers
                    elif kept is not None and kept():
                        # the op names its outputs for the remat policy
                        # (its rule, asked through the module as the route
                        # is): every layer's out and lse are held from
                        # the forward pass to that layer's backward
                        out = elems * int(mc.hd) * np.dtype(
                            mc.dtype).itemsize
                        nbytes += layers * (out + stats)
                        tag += f" + out/lse held by remat x{layers} layers"
                    self.memory_ledger.register(
                        "collective_scratch", "engine/flash_softmax_stats",
                        nbytes, transient=True, tag=f"flash attention {tag}")
            if self.overlap_zero3 and self.policy.stage >= 3:
                from ..comm.overlap import staging_bytes

                dp_world = int(np.prod([self.mesh.shape[a]
                                        for a in DP_AXES]))
                ring_bytes = sum(
                    staging_bytes(np.shape(p),
                                  getattr(p, "dtype", jnp.float32),
                                  self.overlap_chunks) // max(dp_world, 1)
                    for p in jax.tree.leaves(params))
                self.memory_ledger.register(
                    "collective_scratch", "engine/overlap_ring_staging",
                    ring_bytes, transient=True,
                    tag=f"ZeRO-3 overlap ring payloads "
                        f"(chunks={self.overlap_chunks})")

        if self.offload_enabled:
            # optimizer states live on the HOST (ZeRO-Offload): fp32 master +
            # moments in numpy, updated by the fused C++ kernel
            from .zero.offload import CPUOffloadOptimizer

            opt_cfg = self.config.optimizer
            opt_name = (opt_cfg.type if opt_cfg is not None else "AdamW")
            # bf16 wire needs the C++ kernel's fused bf16 emit — Adam-only;
            # Lion/Adagrad offload stays on the fp32 wire
            wire_bf16 = (self.bf16_enabled and opt_name.lower()
                         in ("adam", "adamw", "cpu_adam"))
            with startup_span("startup/place/opt_state",
                              dict(placed, of="host")):
                self.offload_opt = CPUOffloadOptimizer(
                    params,
                    optimizer_name=opt_name,
                    optimizer_params=(dict(opt_cfg.params.model_dump())
                                      if opt_cfg is not None else {}),
                    schedule=self._schedule,
                    policy=self.policy, base_specs=self.base_specs,
                    wire_bf16=wire_bf16)
            opt_state = ()
            if wire_bf16:
                # bf16 wire: the device copy lives in bf16 (fp32 masters are
                # host-side) — halves HBM and h2d bytes, same compute as the
                # on-device bf16 path which casts fp32→bf16 every step
                params = self._jit(lambda t: cast_tree(t, jnp.bfloat16),
                                   "engine/bf16_wire_cast",
                                   out_shardings=param_shardings)(params)
        else:
            with startup_span("startup/engine/optimizer",
                              {"of": "state_shapes"}):
                opt_shapes = jax.eval_shape(self.optimizer.init, params)
            opt_shardings = self.policy.opt_state_shardings(
                opt_shapes, tx=self.optimizer, base_specs=self.base_specs)
            with startup_span("startup/place/opt_state", placed):
                opt_state = self._jit(self.optimizer.init, "engine/opt_init",
                                      out_shardings=opt_shardings)(params)
                if self.telemetry.enabled:
                    jax.block_until_ready(opt_state)
            if self.memory_ledger is not None:
                self.memory_ledger.register_tree(
                    "optimizer", "engine/opt_state", opt_state,
                    tag=f"optax state (zero stage {self.policy.stage})")

        scale_state = (self.loss_scaler.init_state() if self.loss_scaler
                       else LossScaleState(jnp.float32(1.0), jnp.int32(0),
                                           jnp.int32(0)))
        comm_state: Any = ()
        if self.onebit_enabled:
            # per-worker error-feedback residuals: [dp_world, *param_shape],
            # sharded over the DP axes so each worker owns exactly its own;
            # ONE compiled program materializes the whole pytree sharded
            from ..ops.onebit import init_residuals

            dp_world = int(np.prod([self.mesh.shape[a] for a in DP_AXES]))
            res_shardings = jax.tree.map(
                lambda _: NamedSharding(self.mesh, PartitionSpec(DP_AXES)),
                params)
            comm_state = self._jit(
                # dp_world is static by design: a mesh change rebuilds
                # the engine (fresh jit sites), never retraces this one
                lambda: init_residuals(params, dp_world),  # dslint: disable=recompile-hazard
                "engine/onebit_residuals",
                out_shardings=res_shardings)()
            if self.memory_ledger is not None:
                self.memory_ledger.register_tree(
                    "collective_scratch", "engine/onebit_residuals",
                    comm_state, tag="1-bit error-feedback residuals")
        return TrainState(params=params, opt_state=opt_state,
                          step=jnp.int32(0), loss_scale=scale_state,
                          skipped_steps=jnp.int32(0), comm_state=comm_state)

    def _state_shardings(self, state: TrainState) -> TrainState:
        def of(x):
            s = getattr(x, "sharding", None)
            return s if isinstance(s, NamedSharding) else NamedSharding(
                self.mesh, PartitionSpec())

        return jax.tree.map(of, state)

    def mesh_topology(self) -> Dict[str, Any]:
        """This engine's mesh topology — stamped into every snapshot
        manifest and compared by the reshard-on-restore guard (a
        snapshot taken on a different mesh re-lays onto THIS one, or
        fails with a MeshMismatchError naming both)."""
        from ..parallel.mesh import mesh_topology

        return mesh_topology(self.mesh)

    # ------------------------------------------------------------------
    # the compiled train step
    # ------------------------------------------------------------------

    def _pp_1f1b_grads(self, compute_params, batch, scale=None):
        """Grads + mean loss through the 1F1B schedule.

        Bridges the module's layer-streamable protocol (embed_fwd /
        decoder_layer / head_loss — the same contract Infinity streams
        through) onto ``pipeline_train_1f1b``'s (embed_fn, layer_fn,
        head_fn) surface; MoE aux loss rides the activation carry.
        Reference: ``runtime/pipe/engine.py`` TrainSchedule execution
        (SURVEY §3.5)."""
        from ..parallel.mesh import AXIS_PIPE
        from ..parallel.pipeline import pipeline_train_1f1b

        mod = self.module
        # host attribute, not a device value — no sync happens here
        aux_coef = float(getattr(mod, "aux_loss_coef", 0.0))  # dslint: disable=host-sync-hot-path
        gas = self.gradient_accumulation_steps
        pp = int(self.mesh.shape[AXIS_PIPE])
        rows = jax.tree.leaves(batch)[0].shape[0]
        m_pipe = int(getattr(getattr(mod, "config", None),
                             "pp_microbatches", 0) or pp)
        M = gas * m_pipe
        if rows % M:
            raise ValueError(
                f"batch rows {rows} not divisible by pipeline microbatches "
                f"{M} (gas {gas} × pp micro {m_pipe})")
        micro = jax.tree.map(
            lambda x: x.reshape((M, rows // M) + x.shape[1:]), batch)
        resident = {k: v for k, v in compute_params.items()
                    if k != "layers"}

        def embed_fn(ep, mb):
            ids, _ = mod.batch_labels(mb)
            return (mod.embed_fwd(ep, ids), jnp.float32(0.0))

        manual_tp = getattr(self, "_pp_1f1b_manual_tp", False)
        layer_impl = (mod.decoder_layer_manual_tp if manual_tp
                      else mod.decoder_layer)
        from ..parallel.mesh import AXIS_TENSOR as _ATg

        tp_now = int(self.mesh.shape.get(_ATg, 1))
        # the module declares which resident leaves its manual-TP head
        # reads; the TENSOR-SHARDED ones among them (from the module's own
        # param_specs — no key names hardcoded here) are the vocab-scale
        # leaves the split exists to keep off the replicated path
        head_keys = tuple(getattr(mod, "manual_tp_head_param_keys", ()))
        base = self.base_specs or {}

        def _tensor_dim(key):
            spec = base.get(key)
            if spec is None:
                return None
            ent = tuple(spec)
            for i, e in enumerate(ent):
                axes = e if isinstance(e, (tuple, list)) else (e,)
                if any(a == _ATg for a in axes if a):
                    return i
            return None

        sharded_head_keys = [k for k in head_keys
                             if k in resident and _tensor_dim(k) is not None]

        def _divides(key):
            dim = _tensor_dim(key)
            shape = np.shape(jax.tree.leaves(resident[key])[0])
            return shape[dim] % max(tp_now, 1) == 0

        vocab_parallel = (
            manual_tp
            and callable(getattr(mod, "head_loss_manual_tp", None))
            and not getattr(getattr(mod, "config", None), "tie_embeddings",
                            True)
            and bool(sharded_head_keys)
            and all(k in resident for k in head_keys)
            # shard_map hard-errors on non-divisible dims: a GPT-2-like
            # vocab (50257) must keep the replicated head, not crash
            and all(_divides(k) for k in sharded_head_keys))
        head_impl = (mod.head_loss_manual_tp if vocab_parallel
                     else mod.head_loss)

        def layer_fn(lp, act):
            x, aux = act
            nx, naux = layer_impl(lp, x)
            return (nx, aux + naux)

        def head_fn(hp, act, mb):
            x, aux = act
            loss = head_impl(hp, x, mb) + aux_coef * aux
            # fp16 loss scaling INSIDE the schedule: the 1/M cotangent
            # seed then carries the scale through every stage's fp16 vjp
            return loss * scale if scale is not None else loss

        manual_axes: tuple = ()
        trunk_specs = None
        head_specs = None
        if manual_tp:
            # tensor joins the manual set; the trunk in/out specs carry
            # the model's pipe+tensor placement (manual axes only — dp/
            # ZeRO placement on other dims stays with GSPMD outside)
            from jax.sharding import PartitionSpec as P

            from ..parallel.mesh import AXIS_PIPE as _AP
            from ..parallel.mesh import AXIS_TENSOR as _AT2
            manual_axes = (_AT2,)
            keep = {_AP, _AT2}

            def manual_only(spec):
                out = []
                for e in tuple(spec):
                    if isinstance(e, (tuple, list)):
                        kept = tuple(a for a in e if a in keep)
                        out.append(kept if kept else None)
                    else:
                        out.append(e if e in keep else None)
                return P(*out)

            trunk_specs = jax.tree.map(
                manual_only, mod.param_specs()["layers"],
                is_leaf=lambda s: isinstance(s, P))
            if vocab_parallel:
                # vocab-parallel head (Megatron parallel CE): the
                # module's tensor-sharded head leaves enter with their
                # OWN param_specs placement (manual axes only); the rest
                # stay replicated
                head_specs = {
                    k: (manual_only(base[k]) if k in sharded_head_keys
                        else jax.tree.map(lambda _: P(), resident[k]))
                    for k in head_keys}

        # under the vocab-parallel head each manual-region argument
        # carries ONLY what its role reads: the embed side drops lm_head
        # (embed_fwd never touches it), the head side drops embed
        # (head_loss_manual_tp reads final_norm + lm_head) — a redundant
        # replicated [V, H]-scale copy PLUS its fp32 zero-grad scan-carry
        # accumulator per device is the footprint at stake on each side
        embed_resident = resident
        head_resident = resident
        if vocab_parallel:
            embed_resident = {k: v for k, v in resident.items()
                              if k not in sharded_head_keys}
            head_resident = {k: v for k, v in resident.items()
                             if k in head_keys}

        loss, (g_trunk, g_emb, g_head), stats = pipeline_train_1f1b(
            layer_fn, compute_params["layers"], embed_fn, embed_resident,
            head_fn, head_resident, micro, self.mesh,
            manual_axes=manual_axes, trunk_specs=trunk_specs,
            head_specs=head_specs)
        self.last_pipe_stats = dict(stats, schedule="1f1b",
                                    manual_tp=manual_tp,
                                    vocab_parallel_head=vocab_parallel)
        grads = {}
        for k in set(g_emb) | set(g_head):
            if k in g_emb and k in g_head:
                grads[k] = jax.tree.map(jnp.add, g_emb[k], g_head[k])
            else:
                grads[k] = g_emb[k] if k in g_emb else g_head[k]
        grads["layers"] = g_trunk
        return grads, loss

    def _stage3_manual_infos(self, compute_params, label: str):
        """Per-leaf manual-sharding projections for the explicit stage-3
        shard_map branches (qgZ int8 comm, ring-overlap comm): how each
        param/grad leaf's DP axes project into the manual region.  One
        home so the two branches cannot drift."""
        policy = self.policy
        dp_set = set(DP_AXES)
        if tuple(policy.shard_axes) != tuple(DP_AXES):
            raise NotImplementedError(
                f"{label} + MiCS sub-group sharding not supported (the "
                f"manual reduce must cover every DP axis)")

        def _manual_proj(spec, shape):
            entries = list(spec) + [None] * (len(shape) - len(spec))
            man_entries, dims = [], []
            for i, e in enumerate(entries):
                axes = (e if isinstance(e, tuple)
                        else ((e,) if e is not None else ()))
                man = tuple(a for a in axes if a in dp_set)
                auto = tuple(a for a in axes if a not in dp_set)
                if man and auto:
                    raise NotImplementedError(
                        f"{label}: leaf mixes DP and model axes on one dim")
                man_entries.append(man if man else None)
                if man:
                    dims.append(i)
            if len(dims) > 1:
                raise NotImplementedError(f"{label}: multi-dim DP sharding")
            dim = dims[0] if dims else None
            return (PartitionSpec(*man_entries), dim,
                    man_entries[dim] if dim is not None else None)

        def _leaf_info(p, b):
            if b is not None:
                for e in tuple(b):
                    axes = (e if isinstance(e, tuple)
                            else ((e,) if e else ()))
                    if any(a in dp_set for a in axes):
                        raise NotImplementedError(
                            f"{label} does not support model params "
                            f"sharded over DP axes (expert-stacked MoE "
                            f"weights)")
            shape = np.shape(p)
            pin, pdim, paxes = _manual_proj(policy.param_spec(p, b), shape)
            gout, gdim, gaxes = _manual_proj(policy.grad_spec(p, b), shape)
            return {"pin": pin, "pdim": pdim, "paxes": paxes,
                    "gout": gout, "gdim": gdim, "gaxes": gaxes}

        if self.base_specs is None:
            info = jax.tree.map(lambda p: _leaf_info(p, None),
                                compute_params)
        else:
            info = jax.tree.map(_leaf_info, compute_params,
                                self.base_specs)
        pin_tree = jax.tree.map(lambda p, i: i["pin"], compute_params,
                                info)
        gout_tree = jax.tree.map(lambda p, i: i["gout"], compute_params,
                                 info)
        return info, pin_tree, gout_tree

    def _grad_core(self, onebit: Optional[bool] = None,
                   fused_prep: bool = False):
        """Shared microbatch-scan gradient computation: accumulation, loss
        (un)scaling, ZeRO grad constraints, overflow screen, clipping.  Used
        by BOTH the fused on-device step and the offload grad-only step so
        the two paths cannot drift.

        ``fused_prep=True`` (the kernels.fused_adam path): the separate
        unscale/clip HBM sweeps are SKIPPED — grads return still
        loss-scaled, the global grad-norm comes from ONE Pallas read
        (``tree_sqsum``), and everything the chain applied per element
        (unscale × clip × overflow-zero) folds into the single ``mult``
        scalar the fused update kernel consumes."""
        gas = self.gradient_accumulation_steps
        fp16 = self.fp16_enabled
        dtype = self.compute_dtype
        clip = self.gradient_clipping
        policy = self.policy
        loss_fn = self.loss_fn

        onebit = self.onebit_enabled if onebit is None else onebit
        qgz = self.qgz_enabled
        mesh = self.mesh

        def microbatch_scan(compute_params, micro, scale):
            """gas-scan of value_and_grad, fp32 accumulation.

            Numerics plane: when a collector is active AT TRACE TIME the
            loss closure brackets the forward with scan_mark/scan_drain
            and the per-micro probe stats exit value_and_grad via
            ``has_aux`` and the gas scan via its ``ys`` (folded over the
            gas axis after the scan closes).  When no collector is
            active this traces today's exact jaxpr — ``ys`` is None and
            value_and_grad has no aux."""
            coll = numerics.active()

            def grad_of_micro(mb):
                def scaled_loss(p):
                    loss = loss_fn(p, mb)
                    return (loss * scale / gas).astype(jnp.float32) if fp16 \
                        else loss / gas

                def scaled_loss_aux(p):
                    mark = numerics.scan_mark()
                    loss = loss_fn(p, mb)
                    aux = numerics.scan_drain(mark)
                    scaled = (loss * scale / gas).astype(jnp.float32) \
                        if fp16 else loss / gas
                    return scaled, (aux or {})

                if coll is None:
                    return jax.value_and_grad(scaled_loss)(compute_params), \
                        None
                (loss, aux), grads = jax.value_and_grad(
                    scaled_loss_aux, has_aux=True)(compute_params)
                return (loss, grads), (aux or None)

            def body(acc, mb):
                loss_acc, grads_acc = acc
                (loss, grads), ys = grad_of_micro(mb)
                grads_acc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), grads_acc, grads)
                return (loss_acc + loss.astype(jnp.float32), grads_acc), ys

            zero_grads = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), compute_params)
            totals, ys = jax.lax.scan(
                body, (jnp.float32(0.0), zero_grads), micro)
            numerics.scan_collect(ys, combine=True)
            return totals

        def compute(state: TrainState, batch):
            if self._ltd_cfg is not None and isinstance(batch, dict):
                # step rides as a per-row leaf (survives the gas reshape) so
                # the model's LTD token selection is fresh every step
                rows = jax.tree.leaves(batch)[0].shape[0]
                batch = {**batch,
                         "_step": jnp.full((rows,), state.step, jnp.int32)}
            compute_params = (cast_tree(state.params, dtype)
                              if dtype != jnp.float32 else state.params)
            if self.qwz_enabled:
                from .zero.qwz import qwz_compress_tree

                compute_params = qwz_compress_tree(
                    compute_params, mesh,
                    threshold=policy.persistence_threshold,
                    base_specs=self.base_specs)
            scale = state.loss_scale.scale

            if self._pp_1f1b and not (onebit or qgz or self.qwz_enabled):
                # 1F1B pipeline schedule (reference TrainSchedule): grads
                # come from the lockstep tick scan in parallel/pipeline.py
                # — O(pp) stashed activations per stage — instead of
                # autodiff through the module's GPipe forward.  The
                # pipeline microbatch count absorbs gas (both are "grads
                # summed over micros of the mean loss").  fp16: the
                # per-micro loss is scaled INSIDE the schedule (cotangents
                # ride scaled through the fp16 backward), unscaled here;
                # the overflow vote is globally consistent by construction
                # — grads are one logical SPMD array, so every stage
                # computes the same isfinite reduction (the reference
                # all-reduces a per-stage overflow flag to the same end).
                grads, mean_loss = self._pp_1f1b_grads(
                    compute_params, batch, scale=scale if fp16 else None)
                if fp16:
                    grads = jax.tree.map(lambda g: g / scale, grads)
                    mean_loss = mean_loss / scale
                grads = policy.apply_grad_constraints(grads,
                                                      self.base_specs)
                overflow = has_overflow(grads) if fp16 else jnp.bool_(False)
                grads = jax.tree.map(
                    lambda g: jnp.where(overflow, 0.0, g), grads)
                if clip > 0:
                    grads, grad_norm = clip_grads_by_global_norm(grads,
                                                                 clip)
                else:
                    grad_norm = global_grad_norm(grads)
                return (grads, mean_loss, overflow, grad_norm,
                        state.comm_state)

            # [global_batch, ...] -> [gas, global_batch/gas, ...]
            micro = jax.tree.map(
                lambda x: x.reshape((gas, x.shape[0] // gas) + x.shape[1:]),
                batch)

            if qgz and policy.stage >= 3:
                # qgZ under ZeRO-3 (round 3): params enter the partial-manual
                # shard_map in their stage-3 DP-SHARDED layout (no more
                # program-long replication), are all-gathered over DP inside,
                # and grads leave via a single-hop int8 reduce-scatter that
                # lands them directly in the stage-3 grad/opt-state layout —
                # the reference's qgZ lives inside stage3.py the same way
                # (SURVEY §2.1 ZeRO++ row).  Transient peak = params/tp
                # during the grad step (the fused path gathers per-layer;
                # layer-granular gather here is future work).
                from .zero.qgz import (quantized_allreduce,
                                       quantized_reduce_scatter)

                P = PartitionSpec
                info, pin_tree, gout_tree = self._stage3_manual_infos(
                    compute_params, "qgZ stage>=3")

                def local3(params_shards, micro_local):
                    def gather(p, i):
                        if i["pdim"] is None:
                            return p
                        return dist.all_gather_in_graph(
                            p, i["paxes"], axis=i["pdim"], tiled=True)
                    params_full = jax.tree.map(gather, params_shards, info)
                    # probe tracers cannot exit a shard_map body — probes
                    # become identities here (dispatch never samples this
                    # path; this is the trace-time guarantee)
                    with numerics.suppressed():
                        loss_sum, grads = microbatch_scan(params_full,
                                                          micro_local, scale)

                    def reduce(g, i):
                        if i["gdim"] is None:
                            return quantized_allreduce(g, DP_AXES)
                        return quantized_reduce_scatter(g, i["gaxes"],
                                                        i["gdim"])
                    grads = jax.tree.map(reduce, grads, info)
                    mean_loss = dist.pmean(loss_sum, DP_AXES)
                    return mean_loss, grads

                mean_loss, grads = _shard_map(
                    local3, mesh=mesh,
                    in_specs=(pin_tree, P(None, DP_AXES)),
                    out_specs=(P(), gout_tree),
                    axis_names=set(DP_AXES), check_vma=False)(
                        compute_params, micro)
                new_comm = state.comm_state
            elif (self.overlap_zero3 and policy.stage >= 3
                  and not (onebit or qgz or self.qwz_enabled)):
                # collective–compute overlap for stage 3 (kernels.
                # overlap_collectives): the same explicit shard_map shape
                # as the qgZ branch, but the param gather and grad reduce
                # are CHUNKED ppermute rings (comm/overlap.py) instead of
                # monolithic collectives — chunk i's compute runs while
                # chunk i+1 is in flight, where GSPMD's single all-gather
                # serializes against the first matmul it feeds.  Every
                # ring hop goes through the comm verbs, so the
                # CollectiveLedger census sees the ring.
                from ..comm import overlap as ovl

                P = PartitionSpec
                info, pin_tree, gout_tree = self._stage3_manual_infos(
                    compute_params, "overlap stage>=3")
                ring_chunks = self.overlap_chunks
                dp_world = int(np.prod([mesh.shape[a] for a in DP_AXES]))

                def _fit_chunks(dim_size: int) -> int:
                    c = min(ring_chunks, max(dim_size, 1))
                    while c > 1 and dim_size % c:
                        c -= 1
                    return c

                def local3o(params_shards, micro_local):
                    def gather(p, i):
                        if i["pdim"] is None:
                            return p
                        return ovl.ring_all_gather(
                            p, i["paxes"], axis=i["pdim"],
                            chunks=_fit_chunks(p.shape[i["pdim"]]))
                    params_full = jax.tree.map(gather, params_shards, info)
                    with numerics.suppressed():
                        loss_sum, grads = microbatch_scan(params_full,
                                                          micro_local, scale)

                    def reduce(g, i):
                        if i["gdim"] is None:
                            return dist.pmean(g, DP_AXES)
                        shard = g.shape[i["gdim"]] // dp_world
                        out = ovl.ring_reduce_scatter(
                            g, i["gaxes"], axis=i["gdim"],
                            chunks=_fit_chunks(shard))
                        return out / dp_world  # mean (matches pmean/qgZ)
                    grads = jax.tree.map(reduce, grads, info)
                    mean_loss = dist.pmean(loss_sum, DP_AXES)
                    return mean_loss, grads

                mean_loss, grads = _shard_map(
                    local3o, mesh=mesh,
                    in_specs=(pin_tree, P(None, DP_AXES)),
                    out_specs=(P(), gout_tree),
                    axis_names=set(DP_AXES), check_vma=False)(
                        compute_params, micro)
                new_comm = state.comm_state
            elif onebit or qgz:
                # compressed-comm path: per-worker LOCAL grads inside a
                # partial-manual shard_map over the DP axes (TP/SP stay
                # GSPMD-auto), then a compressed allreduce instead of psum —
                # 1-bit error-feedback signs or qgZ int8 2-hop (ZeRO++)
                from ..ops.onebit import onebit_reduce_tree
                from .zero.qgz import qgz_reduce_tree

                P = PartitionSpec

                def local(params_c, micro_local, residuals):
                    with numerics.suppressed():
                        loss_sum, grads = microbatch_scan(params_c,
                                                          micro_local, scale)
                    if onebit:
                        res = jax.tree.map(lambda r: jnp.squeeze(r, 0),
                                           residuals)
                        grads, new_res = onebit_reduce_tree(grads, res,
                                                            DP_AXES)
                        new_res = jax.tree.map(lambda r: r[None], new_res)
                    else:
                        grads = qgz_reduce_tree(grads, DP_AXES)
                        new_res = residuals
                    mean_loss = dist.pmean(loss_sum, DP_AXES)
                    return mean_loss, grads, new_res

                res_spec = P(DP_AXES) if onebit else P()
                mean_loss, grads, new_comm = _shard_map(
                    local, mesh=mesh,
                    in_specs=(P(), P(None, DP_AXES), res_spec),
                    out_specs=(P(), P(), res_spec),
                    axis_names=set(DP_AXES), check_vma=False)(
                        compute_params, micro, state.comm_state)
            else:
                loss_sum, grads = microbatch_scan(compute_params, micro,
                                                  scale)
                mean_loss = loss_sum
                new_comm = state.comm_state

            if fused_prep:
                # kernels.fused_adam: NO per-element unscale/clip sweeps.
                # One Pallas read of the (still-scaled) grads yields the
                # norm; overflow falls out of its finiteness (any non-
                # finite grad poisons the sum); unscale × clip × zero
                # collapse into the `mult` scalar the update kernel folds
                # into its single pass.
                from ..ops.pallas.fused_optimizer import tree_sqsum

                if fp16:
                    mean_loss = mean_loss / scale
                grads = policy.apply_grad_constraints(grads,
                                                      self.base_specs)
                raw_norm = jnp.sqrt(tree_sqsum(grads))  # scaled-grad norm
                overflow = ((~jnp.isfinite(raw_norm)) if fp16
                            else jnp.bool_(False))
                safe = jnp.where(jnp.isfinite(raw_norm), raw_norm, 0.0)
                grad_norm = safe / scale if fp16 else safe
                if clip > 0:
                    factor = jnp.minimum(1.0, clip / (grad_norm + 1e-6))
                else:
                    factor = jnp.float32(1.0)
                mult = jnp.where(overflow, 0.0, factor)
                if fp16:
                    mult = mult / scale
                return (grads, mean_loss, overflow, grad_norm, mult,
                        new_comm)

            if fp16:
                grads = jax.tree.map(lambda g: g / scale, grads)
                mean_loss = mean_loss / scale  # undo scaling; /gas already in

            # ZeRO stage >= 2: pin grads to their reduce-scattered layout.
            grads = policy.apply_grad_constraints(grads, self.base_specs)

            overflow = has_overflow(grads) if fp16 else jnp.bool_(False)
            grads = jax.tree.map(lambda g: jnp.where(overflow, 0.0, g), grads)

            if clip > 0:
                grads, grad_norm = clip_grads_by_global_norm(grads, clip)
            else:
                grad_norm = global_grad_norm(grads)
            return grads, mean_loss, overflow, grad_norm, new_comm

        return compute

    def _build_fused_train_step(self, onebit: Optional[bool] = None):
        """kernels.fused_adam step: the optax chain's update (moments →
        bias correction → direction → apply, each its own HBM sweep plus
        the separate unscale/clip sweeps in the core) is replaced by TWO
        Pallas passes over the ZeRO shard — the grad-norm read inside
        the fused-prep core and the one-pass update here."""
        from ..ops.pallas.fused_optimizer import apply_fused_adam

        fp16 = self.fp16_enabled
        schedule = self._schedule
        scaler = self.loss_scaler
        fused_cfg = self._fused_adam_cfg
        core = self._grad_core(onebit, fused_prep=True)

        def step_fn(state: TrainState, batch):
            (grads, mean_loss, overflow, grad_norm, mult,
             new_comm) = core(state, batch)
            lr = jnp.asarray(schedule(state.step), jnp.float32)
            new_params, new_opt_state = apply_fused_adam(
                state.opt_state, state.params, grads, lr, mult, fused_cfg)

            if fp16:
                keep = lambda new, old: jax.tree.map(
                    lambda n, o: jnp.where(overflow, o, n), new, old)
                new_params = keep(new_params, state.params)
                new_opt_state = keep(new_opt_state, state.opt_state)
                new_scale = scaler.update(state.loss_scale, overflow)
            else:
                new_scale = state.loss_scale

            new_state = TrainState(
                params=new_params, opt_state=new_opt_state,
                step=state.step + jnp.where(overflow, 0, 1),
                loss_scale=new_scale,
                skipped_steps=state.skipped_steps + jnp.where(overflow, 1,
                                                              0),
                comm_state=new_comm)
            metrics = {
                "loss": mean_loss,
                "grad_norm": grad_norm,
                "lr": lr,
                "loss_scale": state.loss_scale.scale,
                "overflow": overflow,
            }
            return new_state, metrics

        state_shardings = self._state_shardings(self.state)
        batch_sharding = NamedSharding(self.mesh, PartitionSpec(DP_AXES))
        onebit_now = self.onebit_enabled if onebit is None else bool(onebit)
        return self._jit(
            step_fn, "engine/train_step_fused",
            static_context={
                "gas": self.gradient_accumulation_steps,
                "onebit": onebit_now,
                "ltd_keep": getattr(self.module, "ltd_keep", None),
            },
            in_shardings=(state_shardings, batch_sharding),
            out_shardings=(state_shardings, None),
            donate_argnums=(0,))

    def _build_train_step(self, onebit: Optional[bool] = None,
                          numerics_mode: Optional[str] = None):
        """``numerics_mode`` selects the numerics-plane step variant:
        ``None`` is the base step (today's exact program), ``"numerics"``
        / ``"moe"`` are the sampled-capture variants traced at their OWN
        jit sites — turning the plane on never invalidates the base
        step's compile cache."""
        if self.fused_adam_enabled:
            return self._build_fused_train_step(onebit)
        fp16 = self.fp16_enabled
        schedule = self._schedule
        scaler = self.loss_scaler
        tx = self.optimizer
        core = self._grad_core(onebit)
        # forensic precondition: the probes-on re-run localizes the NaN
        # origin by replaying the forward on the params the bad loss came
        # from — but the state is donated, so the only copy left after
        # the step is new_params.  Guarding the update on a non-finite
        # loss keeps that copy equal to the pre-step params (fp16 already
        # does this via overflow-skip; fp32 would otherwise apply the NaN
        # grads and poison every layer, making the re-run blame layer 0).
        guard_nonfinite = (self._numerics_cfg.enabled
                           and self._numerics_cfg.forensic_on_nan)

        def step_fn(state: TrainState, batch):
            grads, mean_loss, overflow, grad_norm, new_comm = core(state,
                                                                   batch)

            updates, new_opt_state = tx.update(grads, state.opt_state,
                                               state.params)
            new_params = optax.apply_updates(state.params, updates)

            if fp16:
                keep = lambda new, old: jax.tree.map(
                    lambda n, o: jnp.where(overflow, o, n), new, old)
                new_params = keep(new_params, state.params)
                new_opt_state = keep(new_opt_state, state.opt_state)
                new_scale = scaler.update(state.loss_scale, overflow)
            else:
                new_scale = state.loss_scale
            if guard_nonfinite and not fp16:
                bad = ~jnp.isfinite(mean_loss)
                hold = lambda new, old: jax.tree.map(
                    lambda n, o: jnp.where(bad, o, n), new, old)
                new_params = hold(new_params, state.params)
                new_opt_state = hold(new_opt_state, state.opt_state)

            new_state = TrainState(
                params=new_params, opt_state=new_opt_state,
                step=state.step + jnp.where(overflow, 0, 1),
                loss_scale=new_scale,
                skipped_steps=state.skipped_steps + jnp.where(overflow, 1, 0),
                comm_state=new_comm)
            metrics = {
                "loss": mean_loss,
                "grad_norm": grad_norm,
                "lr": jnp.asarray(schedule(state.step), jnp.float32),
                "loss_scale": state.loss_scale.scale,
                "overflow": overflow,
            }
            coll = numerics.active()
            if coll is not None:
                # grad-path health sliced from THIS step's existing
                # pytrees (no extra forward): per-module grad norms, the
                # per-layer [L] norm vector, update/param ratios
                if coll.want_probes:
                    for k, v in numerics.grad_stats(
                            grads, updates, state.params).items():
                        coll.add(k, v)
                aux = coll.harvest()
                if aux:
                    metrics = dict(metrics, numerics=aux)
            return new_state, metrics

        state_shardings = self._state_shardings(self.state)
        batch_sharding = NamedSharding(self.mesh, PartitionSpec(DP_AXES))
        onebit_now = self.onebit_enabled if onebit is None else bool(onebit)
        site = ("engine/train_step" if numerics_mode is None
                else f"engine/train_step_{numerics_mode}")
        return self._jit(
            step_fn, site,
            # the documented recompile hazards, named so a recompile's
            # cause diff says WHICH boundary was crossed: tail-batch gas,
            # the 1-bit warmup edge, the active LTD keep bucket
            static_context={
                "gas": self.gradient_accumulation_steps,
                "onebit": onebit_now,
                "ltd_keep": getattr(self.module, "ltd_keep", None),
                **({"numerics": numerics_mode} if numerics_mode else {}),
            },
            in_shardings=(state_shardings, batch_sharding),
            out_shardings=(state_shardings, None),
            donate_argnums=(0,))

    def _build_grad_step(self):
        """Offload mode: the device program ends at clipped grads + metrics;
        the optimizer update happens on the host (C++ CPU Adam)."""
        fp16 = self.fp16_enabled
        schedule = self._schedule
        scaler = self.loss_scaler
        core = self._grad_core()
        policy = self.policy
        base_specs = self.base_specs

        wire_bf16 = (self.offload_opt is not None
                     and self.offload_opt.wire_bf16)

        def grad_fn(state: TrainState, batch):
            grads, mean_loss, overflow, grad_norm, _ = core(state, batch)
            # land grads in the host-partition (opt-state) layout: each
            # process's d2h pull is exactly its master slice — reduce-scatter
            # over DP instead of all-reduce whenever stage >= 1
            grads = policy.apply_offload_grad_constraints(grads, base_specs)
            if wire_bf16:
                # bf16 grad wire (reference sends fp16 grads to the CPU
                # optimizer): halves d2h bytes; accumulation stayed fp32
                grads = jax.tree.map(lambda g: g.astype(jnp.bfloat16), grads)
            new_scale = (scaler.update(state.loss_scale, overflow)
                         if fp16 else state.loss_scale)
            metrics = {
                "loss": mean_loss,
                "grad_norm": grad_norm,
                "lr": jnp.asarray(schedule(state.step), jnp.float32),
                "loss_scale": state.loss_scale.scale,
                "overflow": overflow,
            }
            return grads, metrics, new_scale

        state_shardings = self._state_shardings(self.state)
        batch_sharding = NamedSharding(self.mesh, PartitionSpec(DP_AXES))
        return self._jit(
            grad_fn, "engine/grad_step",
            static_context={"gas": self.gradient_accumulation_steps,
                            "wire_bf16": wire_bf16},
            in_shardings=(state_shardings, batch_sharding))

    def _first_call(self, site: str):
        """``startup/first_call`` around a step program's build and first
        call, where the compile tracker is off (a tracked jit records its
        own): the compile account then says in it what was traced, lowered
        and compiled or loaded."""
        if self.compile_tracker is not None:
            return contextlib.nullcontext()
        return startup_span("startup/first_call",
                            {"site": site, "program": 0})

    def _offload_train_step(self, batch) -> Dict[str, Any]:
        if self._train_step_fn is None:
            with self._first_call("engine/grad_step"):
                self._train_step_fn = self._build_grad_step()
                grads, metrics, new_scale = self._train_step_fn(self.state,
                                                                batch)
        else:
            grads, metrics, new_scale = self._train_step_fn(self.state,
                                                            batch)
        overflow = bool(metrics["overflow"]) if self.fp16_enabled else False
        st = self.state
        if overflow:
            self.state = st._replace(
                loss_scale=new_scale,
                skipped_steps=st.skipped_steps + 1)
        else:
            new_params = self.offload_opt.step(grads, int(st.step))
            self.state = st._replace(params=new_params, step=st.step + 1,
                                     loss_scale=new_scale)
        return metrics

    # ------------------------------------------------------------------
    # idiomatic API — one call per optimizer step
    # ------------------------------------------------------------------

    def _feed_batch(self, batch):
        """Assemble the GLOBAL batch under multi-controller execution.

        Single process: pass through (the jit's in_shardings place it).
        Multi-process (``jax.process_count() > 1``): host leaves are this
        process's LOCAL rows — the per-rank slice its dataloader produced,
        the reference's per-rank batch feeding — and are assembled into
        global dp-sharded arrays via
        ``jax.make_array_from_process_local_data``; leaves that are already
        global jax.Arrays pass through untouched."""
        if jax.process_count() == 1:
            return batch
        from ..parallel.mesh import global_feed

        sh = NamedSharding(self.mesh, PartitionSpec(DP_AXES))
        return jax.tree.map(lambda x: global_feed(x, sh), batch)

    def _dispatch_train_step(self, batch) -> Dict[str, Any]:
        """Route the (assembled, global) batch to the right compiled-step
        family and return its metrics."""
        if self.infinity is not None:
            metrics = self.infinity.train_step(batch)
            stepped = 0 if bool(metrics.get("overflow", False)) else 1
            self.state = self.state._replace(
                params=self.infinity.resident,
                step=self.state.step + stepped)
        elif self.offload_enabled:
            metrics = self._offload_train_step(batch)
        elif (self.onebit_enabled
              and self.global_steps < self.onebit_freeze_step):
            # 1-bit warmup phase: full-precision DP reduction until
            # freeze_step (reference OnebitAdam semantics)
            if self._warmup_step_fn is None:
                self._warmup_step_fn = self._build_train_step(onebit=False)
            self.state, metrics = self._warmup_step_fn(self.state, batch)
        elif self._ltd_cfg is not None:
            # random-LTD: pick this step's keep bucket, (re)use its program
            from .data_pipeline.random_ltd import RandomLTDScheduler

            seq = jax.tree.leaves(batch)[0].shape[1]
            if self._ltd_sched is None or seq > self._ltd_sched.seq_len:
                # rebuild on longer sequences: a curriculum-truncated FIRST
                # batch must not cap the keep schedule for the whole run
                self._ltd_sched = RandomLTDScheduler(self._ltd_cfg, seq)
            keep = min(self._ltd_sched.keep_count(self.global_steps), seq)
            self.module.ltd_keep = None if keep >= seq else keep
            key = keep if keep < seq else -1
            if key not in self._ltd_fns:
                self._ltd_fns[key] = self._build_train_step()
            self.state, metrics = self._ltd_fns[key](self.state, batch)
        else:
            fn, coll = self._select_numerics_step()
            if fn is not None:
                # sampled numerics capture: the variant's own jit site —
                # the base step's compile cache is untouched, and the
                # collector is active for the trace (and harmlessly for
                # every cached call after it)
                with numerics.collecting(coll):
                    self.state, metrics = fn(self.state, batch)
            elif self._train_step_fn is None:
                # the step program's first call: built, traced, lowered,
                # compiled or loaded, and run (a tracked jit has that span
                # of its own)
                with self._first_call("engine/train_step"):
                    self._train_step_fn = self._build_train_step()
                    self.state, metrics = self._train_step_fn(self.state,
                                                              batch)
            else:
                self.state, metrics = self._train_step_fn(self.state, batch)
        return metrics

    def _select_numerics_step(self):
        """(step_fn, collector) when the numerics plane samples THIS
        step, else (None, None).  Full captures need ``numerics.enabled``;
        with the plane off but ``moe_gauges`` on, a MoE model still gets
        its routing telemetry (satellite: gate stats are never discarded)
        through the lighter ``engine/train_step_moe`` variant.  Only the
        plain dispatch path samples — infinity/offload/1-bit-warmup/LTD
        keep their own programs probe-free."""
        ncfg = self._numerics_cfg
        every = int(ncfg.every)
        if self.fused_adam_enabled or every <= 0 \
                or (self.global_steps + 1) % every:
            return None, None
        if ncfg.enabled:
            if self._numerics_step_fn is None:
                self._numerics_step_fn = self._build_train_step(
                    numerics_mode="numerics")
            return self._numerics_step_fn, numerics.Collector(
                probes=True, moe=True, tag="sample")
        if ncfg.moe_gauges and getattr(self.module, "_moe_layer",
                                       None) is not None:
            if self._moe_step_fn is None:
                self._moe_step_fn = self._build_train_step(
                    numerics_mode="moe")
            return self._moe_step_fn, numerics.Collector(
                probes=False, moe=True, tag="moe")
        return None, None

    def _ingest_numerics_capture(self, named: Dict[str, Any]) -> None:
        """Host-side decode of a sampled capture: ``numerics/*`` and
        ``moe/*`` gauges, the summary staged for this step's
        ``StepRecord.extra['numerics']`` (the health rules' input), and
        the full per-probe table into the debug-bundle context."""
        try:
            decoded = numerics.decode(named)
        except Exception as e:  # telemetry must never kill the step
            logger.error(f"numerics: capture decode failed: {e!r}")
            return
        summary = numerics.summarize(decoded)
        first = numerics.first_nonfinite(decoded["probes"],
                                         decoded["order"])
        self._numerics_context = {
            "step": self.global_steps, "first_nonfinite": first,
            "summary": summary,
            **{k: decoded[k] for k in ("probes", "order", "grads",
                                       "update_ratio", "moe")}}
        extra = dict(summary)
        if first:
            extra["first_nonfinite"] = first
        self._last_numerics = extra
        for key in ("underflow_frac", "saturated_frac", "zero_frac",
                    "absmax", "nonfinite_total", "layer_grad_max"):
            if key in summary:
                self.telemetry.set_gauge(
                    f"numerics/{key}", float(summary[key]),  # dslint: disable=host-sync-hot-path — decode() already pulled the capture; these are host floats
                    help="worst-case probe stat of the last sampled "
                         "numerics capture")
        for src, name in (("gate_entropy", "moe/gate_entropy"),
                          ("moe_drop_rate", "moe/drop_rate"),
                          ("moe_overflow_frac", "moe/overflow_frac"),
                          ("moe_load_imbalance", "moe/load_imbalance")):
            if src in summary:
                self.telemetry.set_gauge(
                    name, float(summary[src]),  # dslint: disable=host-sync-hot-path — same: post-decode host floats
                    help="MoE gate telemetry from the last sampled step")

    def _numerics_forensic_capture(self, batch):
        """Probes-on loss forward on the failed ``(params, batch)`` —
        its own jit site, compiled only on the first failure ever."""
        if self._forensic_fwd_fn is None:
            loss_fn = self.loss_fn
            dtype = self.compute_dtype

            def fwd(params, b):
                p = (cast_tree(params, dtype)
                     if dtype != jnp.float32 else params)
                mark = numerics.scan_mark()
                loss = loss_fn(p, b)
                aux = numerics.scan_drain(mark)
                return loss, (aux or {})

            self._forensic_fwd_fn = self._jit(fwd,
                                              "engine/numerics_forensics")
        coll = numerics.Collector(probes=True, moe=True, tag="forensic")
        with numerics.collecting(coll):
            loss, aux = self._forensic_fwd_fn(self.state.params, batch)
        return loss, aux

    def _run_nonfinite_forensics(self, batch, loss_val: float) -> None:
        """Non-finite loss seen: re-run the forward with every probe on
        and localize the first bad tensor in program order.  The report
        is staged for the nan_loss health event and the resilience
        rollback annotation; the bundle gets ``numerics.json``."""
        try:
            _, aux = self._numerics_forensic_capture(batch)
            report = numerics.report_from_capture(
                aux, self.global_steps, loss_val,
                recorder=self.flight_recorder)
        except Exception as e:  # forensics must not mask the failure
            logger.error(f"numerics: forensic capture failed: {e!r}")
            return
        self._last_nonfinite_report = report
        self._numerics_context = report.report
        summary = dict(report.report.get("summary") or {})
        summary["forensic"] = 1.0
        if report.report.get("first_nonfinite"):
            summary["first_nonfinite"] = report.report["first_nonfinite"]
        self._last_numerics = summary
        logger.error(f"numerics: {report}")

    def train_step(self, batch) -> Dict[str, Any]:
        """Run ONE full optimizer step (fwd+bwd over all microbatches + update)
        as a single compiled program.  ``batch`` holds the full global batch
        (micro × gas × dp_world leading dim) — or, multi-process, this
        process's local rows (see :meth:`_feed_batch`)."""
        self.tput_timer.start()
        t_step0 = time.perf_counter()
        plane = self._profiler_plane
        if plane is not None:
            # fleet profiler window arm/disarm (ISSUE 20) — outside the
            # jitted program; two attribute reads when nothing is armed
            plane.on_step(self.global_steps)
        batch = self._feed_batch(batch)
        if self.snapshots is not None and self.snapshots.snapshots_taken == 0:
            # step-0 baseline: a failure inside the FIRST snapshot
            # interval must roll back to init, not give up for want of
            # any snapshot at all
            self.snapshots.take()
        if self.fault_injector is not None:
            # chaos harness: fire any fault scheduled for THIS step
            # (kill/stall/NaN-poison/corrupt-snapshot) before dispatch
            batch = self.fault_injector.apply(self.global_steps + 1, batch,
                                              engine=self)
        trk = self.compile_tracker
        if trk is not None:
            # marks for per-step compile attribution: whatever the
            # tracker records between here and the fence happened INSIDE
            # this step's wall time
            _c_ev0, _c_rc0 = trk.events_total, trk.recompiles_total
            _c_ms0 = trk.time_ms_total
        _stall0_s = (self.goodput.totals()["stall"]
                     if self.goodput is not None else 0.0)
        fenced = (self.config.wall_clock_breakdown
                  or self._autotuning_fence
                  or (self._telemetry_steps and self._telemetry_fence))
        try:
            with self.telemetry.span("engine/train_step",
                                     args={"step": self.global_steps}):
                metrics = self._dispatch_train_step(batch)
            # the sampled numerics aux rides the metrics pytree out of
            # the jitted step — peel it off before anything float()s or
            # iterates the metrics dict
            numerics_aux = (metrics.pop("numerics", None)
                            if isinstance(metrics, dict) else None)
            if fenced:
                # breakdown/autotuning/telemetry trade throughput for
                # truth (the reference inserts barriers the same way): a
                # scalar fetch is the only reliable fence, so timers and
                # StepRecords see DEVICE step time, not dispatch time —
                # and it is also where an async RESOURCE_EXHAUSTED from
                # this step's program surfaces
                float(metrics["loss"])  # dslint: disable=host-sync-hot-path — the fence IS the point
        except Exception as e:
            from ..telemetry.memory.oom import handle_oom, is_oom_error

            if self.memory_ledger is None or not is_oom_error(e):
                raise
            # OOM forensics: ledger breakdown + top live arrays into the
            # debug bundle (memory.json), re-raised as a descriptive
            # error naming the top pools instead of a raw XLA traceback
            raise handle_oom(e, recorder=self.flight_recorder,
                             step=self.global_steps) from e
        step_time_s = time.perf_counter() - t_step0
        compile_ms, compile_events, recompile_events = 0.0, 0, 0
        if trk is not None:
            compile_events = trk.events_total - _c_ev0
            recompile_events = trk.recompiles_total - _c_rc0
            compile_ms = trk.time_ms_total - _c_ms0
        #: this step spent most of its wall time in XLA lower/compile —
        #: excluded from the watchdog EWMA and the health throughput
        #: window (a first-step or rebucketing compile must not skew
        #: straggler ratios or trip a false throughput regression)
        compile_dominated = (
            compile_ms > 0.0
            and compile_ms >= self._compile_dominated_frac
            * step_time_s * 1e3)
        if self.goodput is not None:
            # any stall the watchdog charged DURING this step (a tripped
            # hang that later unblocked) is already accounted — charge
            # only the remainder, or the interval would count twice
            stalled_s = self.goodput.totals()["stall"] - _stall0_s
            self.goodput.add_step(max(step_time_s - stalled_s, 0.0),
                                  compile_ms / 1e3)
        self.tput_timer.stop(sync=False)
        from ..utils import debug as _debug

        if _debug.enabled():
            _debug.check_step(metrics)
        self.global_steps += 1
        result_path = os.environ.get("DS_AUTOTUNING_RESULT")
        if (result_path and self.global_steps
                == int(os.environ.get("DS_AUTOTUNING_STEPS", "8"))):
            # candidate profiling run under `deepspeed --autotuning`: every
            # step was fenced above (_autotuning_fence), so per-step
            # timings are device times; report and let the orchestrator
            # reap the process
            import json as _json

            float(metrics["loss"])  # drain any unfenced tail  # dslint: disable=host-sync-hot-path
            t = self.tput_timer
            tmp = result_path + ".tmp"
            with open(tmp, "w") as f:
                _json.dump({"samples_per_sec": t.samples_per_sec(),
                            "avg_step_time_s": t.avg_step_time(),
                            "steps": self.global_steps}, f)
            os.replace(tmp, result_path)  # atomic: no torn reads
        self.lr_scheduler.last_step = self.global_steps
        self.last_metrics = metrics
        if numerics_aux:
            # one device→host pull of a few hundred floats, sampled
            # steps only: decode, publish gauges, stage the summary for
            # this step's record and the bundle context
            self._ingest_numerics_capture(numerics_aux)
        if self._numerics_cfg.enabled and self._numerics_cfg.forensic_on_nan:
            try:
                _lv = float(metrics["loss"])  # dslint: disable=host-sync-hot-path — NaN triage needs the scalar
            except Exception:
                _lv = 0.0
            if not np.isfinite(_lv):
                # forensic capture BEFORE the record/health/resilience
                # consumers run, so the nan_loss event and the rollback
                # annotation can NAME the first bad layer
                self._run_nonfinite_forensics(batch, _lv)
        if self.watchdog is not None:
            # a completed step IS progress (the daemon started at build);
            # a compile-dominated step still notifies but contributes no
            # EWMA sample — its time was the compiler's, not the step's
            self.watchdog.notify_progress(
                self.global_steps,
                None if compile_dominated else step_time_s)
        if self._telemetry_steps:
            self._record_step_telemetry(
                batch, metrics, step_time_s, fenced,
                compile_ms=compile_ms, compile_events=compile_events,
                recompile_events=recompile_events)
        rolled_back = False
        if self.resilience is not None:
            # recovery policy: a NaN'd loss / scale collapse rolls the
            # engine back to the last good snapshot (the offending data
            # window is skipped — this batch is never refed); healthy
            # steps feed the snapshot cadence instead.  observe_step
            # pulls the loss scalar — resilience trades overlap for
            # catching the NaN before it ages another interval.
            rolled_back = self.resilience.observe_step(
                metrics, self._last_health_events)
            if rolled_back:
                metrics = dict(metrics, rolled_back=True)
            else:
                self.snapshots.maybe_snapshot()
        if not rolled_back and self.steps_per_print and self.global_steps \
                % int(self.steps_per_print) == 0:
            # printing requires the values; the pull is gated to the
            # steps_per_print cadence
            m = {k: float(v) for k, v in metrics.items()}  # dslint: disable=host-sync-hot-path
            line = (f"step={self.global_steps} loss={m['loss']:.4f} "
                    f"lr={m['lr']:.3e} grad_norm={m['grad_norm']:.3f} "
                    f"loss_scale={m['loss_scale']:.0f}")
            if self.config.wall_clock_breakdown:
                # fused-step engine: fwd/bwd/step are ONE program, so the
                # reference's per-phase split collapses to step wall time +
                # throughput (+ a memory line, the other half of the
                # reference's breakdown prints)
                from ..utils.memory import memory_status

                t = self.tput_timer
                mem = memory_status()
                line += (f" | step_time={t.avg_step_time() * 1e3:.1f}ms "
                         f"samples/s={t.samples_per_sec():.1f} "
                         f"hbm={mem.get('device_in_use_GB', 0):.2f}GB")
            log_dist(line)
        if self.monitor is not None and not rolled_back:
            # a rolled-back step's metrics are the FAILED step's (NaN
            # loss) while global_steps already points at the restored
            # step — logging them would stamp a NaN onto a healthy step
            self.monitor.write_events(
                [(f"Train/{k}", v, self.global_steps)
                 for k, v in metrics.items()
                 if k not in ("overflow", "rolled_back")])
        fp = self.config.flops_profiler
        if fp.enabled and self.global_steps == int(fp.profile_step):
            self._emit_module_profile(batch, fp)
        return metrics

    def _record_step_telemetry(self, batch, metrics: Dict[str, Any],
                               step_time_s: float, fenced: bool,
                               compile_ms: float = 0.0,
                               compile_events: int = 0,
                               recompile_events: int = 0) -> None:
        """Assemble + publish this step's :class:`~..telemetry.StepRecord`
        (the numbers are device-true when ``fenced``; the float() pulls
        below force the same sync anyway)."""
        from ..comm.comm import comms_logger
        from ..telemetry import StepRecord, collect_memory_stats

        leaves = [l for l in jax.tree.leaves(batch)
                  if getattr(l, "ndim", 0) >= 1]
        rows = int(leaves[0].shape[0]) if leaves else 0
        seq = (int(leaves[0].shape[1])
               if leaves and leaves[0].ndim >= 2 else 1)
        dt = max(step_time_s, 1e-9)
        tflops = mfu = 0.0
        # rate/TFLOPS/MFU fields only when the step was fenced: an
        # unfenced step_time is host DISPATCH time, and a rate derived
        # from it would overstate throughput by orders of magnitude
        if self.flops_per_step and fenced:
            tflops = self.flops_per_step / dt / 1e12
            try:
                from ..profiling.flops_profiler.profiler import (
                    peak_flops_per_chip)

                peak = float(peak_flops_per_chip())
                if peak > 0:
                    mfu = self.flops_per_step / dt / peak
            except Exception as e:  # unknown device kind — MFU stays None
                from ..utils.logging import debug_once

                debug_once("telemetry/mfu_peak",
                           f"peak-FLOPs lookup failed ({e!r}); "
                           f"StepRecord.mfu omitted")
        nan = float("nan")
        extra: Dict[str, Any] = {}
        if compile_events or compile_ms:
            # compile attribution (telemetry/perf): lets the health
            # monitor exclude compile-dominated steps from the
            # throughput window and operators see where step N's wall
            # time actually went
            extra["compile_ms"] = round(compile_ms, 3)
            extra["compile_events"] = int(compile_events)
            extra["recompile_events"] = int(recompile_events)
        if self.memory_ledger is not None:
            # per-step memory plane numbers ride extra (ISSUE 7):
            # peak_hbm_bytes / hbm_frac / host_rss_bytes / swap_io_bytes
            # (+ a live-array census every _mem_census_every steps) — the
            # health monitor's memory_pressure and host_memory_leak
            # rules read exactly these fields
            census = (self._mem_census_every > 0
                      and self.global_steps % self._mem_census_every
                      == 1 % self._mem_census_every)  # every=1 → each step
            extra.update(self.memory_ledger.step_sample(live_census=census))
        if self._last_anatomy is not None:
            # the capture's compact summary rides the NEXT step record
            # once (anatomy plane) — bundles and the rollup see where
            # the traced window's device time went
            extra["anatomy"] = self._last_anatomy
            self._last_anatomy = None
        if self._last_numerics is not None:
            # this step's sampled/forensic capture summary — the
            # underflow_creep / layer_grad_explosion / router_collapse
            # health rules read exactly these keys
            extra["numerics"] = self._last_numerics
            self._last_numerics = None
        if comms_logger.enabled and comms_logger.exec_counts:
            # THIS step's execution-probe activity: shard-normalized
            # cumulative totals (satellite: no more hand-dividing by
            # jax.local_device_count()), diffed against the previous
            # record's snapshot; clamped so a mid-run logger reset
            # can't go negative
            eops, ebytes = comms_logger.exec_totals(per_step=True)
            prev = self._last_exec_totals
            self._last_exec_totals = (eops, ebytes)
            extra["comm_exec_ops"] = max(0.0, eops - prev[0])
            extra["comm_exec_bytes"] = max(0.0, ebytes - prev[1])
        rec = StepRecord(
            step=self.global_steps,
            step_time_ms=step_time_s * 1e3,
            device_fenced=bool(fenced),
            samples_per_sec=rows / dt if fenced else 0.0,
            tokens_per_sec=rows * seq / dt if fenced else 0.0,
            # unfenced mode is the ASYNC-recording path (device_fence:
            # false buys back dispatch/execute overlap) — scalar pulls
            # would block on the step, so metric fields stay NaN there
            loss=float(metrics.get("loss", 0.0)) if fenced else nan,
            grad_norm=float(metrics.get("grad_norm", 0.0)) if fenced
            else nan,
            lr=float(metrics.get("lr", 0.0)) if fenced else nan,
            loss_scale=float(metrics.get("loss_scale", 1.0)) if fenced
            else nan,
            overflow=bool(metrics.get("overflow", False)) if fenced
            else False,
            skipped_steps=int(self.state.skipped_steps) if fenced else -1,
            comm_bytes=comms_logger.total_bytes(),
            comm_ops=comms_logger.total_ops(),
            tflops=tflops, mfu=mfu,
            # with the memory ledger on, reuse the device/host readings
            # step_sample just took (and its census already rode extra)
            # — the record must not pay memory_stats + procfs twice;
            # without it, the legacy path with its 16-step census
            memory=(self.memory_ledger.status(cached=True)
                    if self.memory_ledger is not None
                    else collect_memory_stats(
                        include_live_buffers=self.global_steps % 16 == 1)),
            extra=extra)
        self.last_step_record = rec
        self.step_records.append(rec)
        self.telemetry.record_step(rec)
        if self.flight_recorder is not None:
            self.flight_recorder.record_step(rec)
        if self.health is not None:
            events = self.health.observe(rec)
            self._last_health_events = events  # resilience policy input
            if events and self.monitor is not None:
                self.monitor.write_health_events(events)

    def _emit_module_profile(self, batch, fp) -> None:
        """One-shot per-module flops/latency table at ``profile_step``
        (reference FlopsProfiler behavior, SURVEY §2.5)."""
        try:
            from ..profiling.flops_profiler.profiler import (
                format_module_table, profile_model_modules)

            rows = profile_model_modules(
                self.module, self.state.params, batch,
                module_depth=int(fp.module_depth),
                top_modules=int(fp.top_modules) if not fp.detailed else 0)
            text = format_module_table(rows)
            if fp.output_file:
                with open(fp.output_file, "w") as f:
                    f.write(text + "\n")
            log_dist("flops profiler (per-module, step "
                     f"{self.global_steps}):\n{text}")
        except Exception as e:
            logger.warning(f"flops profiler: per-module table unavailable "
                           f"({e})")

    def eval_loss(self, batch) -> jnp.ndarray:
        batch = self._feed_batch(batch)
        if self.infinity is not None:
            return self.infinity.eval_loss(batch)
        if self._eval_loss_fn is None:
            dtype = self.compute_dtype

            def fwd(params, b):
                p = cast_tree(params, dtype) if dtype != jnp.float32 else params
                return self.loss_fn(p, b)

            self._eval_loss_fn = self._jit(fwd, "engine/eval_loss")
        return self._eval_loss_fn(self.state.params, batch)

    # ------------------------------------------------------------------
    # autotuning trial hook (tuning/ — ISSUE 9)
    # ------------------------------------------------------------------

    def trial_run(self, batch, warmup_steps: int = 1,
                  timed_steps: int = 3) -> Dict[str, Any]:
        """Run ``warmup_steps`` + ``timed_steps`` optimizer steps with a
        per-step device fence and return a telemetry-sourced summary for
        the tuning plane: tokens/sec and step-time p50 from this
        engine's OWN device-fenced StepRecords (falling back to the
        fenced wall clock when telemetry is off), MFU when
        ``flops_per_step`` is set, the window's compile cost from the
        compile tracker (already charged to the goodput ``compile``
        bucket by ``train_step``), and the memory ledger's per-step
        HBM numbers.  The per-step loss fetch is the fence: dispatch is
        asynchronous, and the loss exists only once the step has run."""
        warmup_steps = max(int(warmup_steps), 0)
        timed_steps = max(int(timed_steps), 1)
        trk = self.compile_tracker
        ev0 = trk.events_total if trk is not None else 0
        ms0 = trk.time_ms_total if trk is not None else 0.0
        for _ in range(warmup_steps):
            m = self.train_step(batch)
            float(m["loss"])  # warmup fence: compiles stay out of timing
        mark = (self.step_records[-1].step if self.step_records
                else self.global_steps)
        t0 = time.perf_counter()
        for _ in range(timed_steps):
            m = self.train_step(batch)
            float(m["loss"])  # the per-step fence IS the measurement
        wall_s = time.perf_counter() - t0
        leaves = [l for l in jax.tree.leaves(batch)
                  if getattr(l, "ndim", 0) >= 1]
        rows = int(leaves[0].shape[0]) if leaves else 0
        seq = (int(leaves[0].shape[1])
               if leaves and leaves[0].ndim >= 2 else 1)
        out: Dict[str, Any] = {"timed_steps": timed_steps,
                               "wall_s": wall_s}
        recs = [r for r in self.step_records
                if r.step > mark and r.device_fenced]
        if recs:
            times = sorted(r.step_time_ms for r in recs)
            tps = sorted(r.tokens_per_sec for r in recs)
            out["source"] = "telemetry"
            out["step_time_p50_ms"] = times[len(times) // 2]
            out["tokens_per_sec"] = tps[len(tps) // 2]
            sps = sorted(r.samples_per_sec for r in recs)
            out["samples_per_sec"] = sps[len(sps) // 2]
            mfus = sorted(r.mfu for r in recs if r.mfu)
            if mfus:
                out["mfu"] = mfus[len(mfus) // 2]
            mem = recs[-1].extra or {}
            for k in ("peak_hbm_bytes", "hbm_headroom_frac"):
                if k in mem:
                    out[k] = mem[k]
        else:
            dt = wall_s / timed_steps
            out["source"] = "wall_clock"
            out["step_time_p50_ms"] = dt * 1e3
            out["samples_per_sec"] = rows / max(dt, 1e-9)
            out["tokens_per_sec"] = rows * seq / max(dt, 1e-9)
        if trk is not None:
            out["compile_events"] = trk.events_total - ev0
            out["compile_s"] = (trk.time_ms_total - ms0) / 1e3
        if self.memory_ledger is not None and "peak_hbm_bytes" not in out:
            sample = self.memory_ledger.step_sample()
            for k in ("peak_hbm_bytes", "hbm_headroom_frac"):
                if k in sample:
                    out[k] = sample[k]
        if self.cost_ledger is not None and "step_time_p50_ms" in out:
            # roofline headroom (anatomy plane): 1 - predicted/measured
            # for the step program — the tuning tie-breaker (a config
            # near its roofline is fast BECAUSE of the hardware, not by
            # accident of an unexplained stall going quiet this trial)
            head = self.cost_ledger.headroom(
                self._anatomy_site(), out["step_time_p50_ms"] * 1e3)
            if head is not None:
                out["roofline_headroom"] = head
        return out

    def _anatomy_site(self) -> str:
        """The tracked jit site of the CURRENT step program (offload
        engines step through grad_step; everyone else the fused step)."""
        if self.cost_ledger is not None:
            for site in ("engine/train_step_fused", "engine/train_step",
                         "engine/grad_step"):
                if self.cost_ledger.entry_for(site):
                    return site
        return "engine/train_step_fused"

    def capture_anatomy(self, batch, steps: Optional[int] = None,
                        trace_dir: Optional[str] = None,
                        feed_census: Optional[bool] = None
                        ) -> Dict[str, Any]:
        """Step anatomy (ISSUE 17): trace ``steps`` fenced train steps
        under ONE shared profiler session and return the attribution
        summary — compute / exposed-collective / overlapped-collective /
        host-sync buckets, measured overlap hiding, and the roofline
        predicted-vs-measured join for this engine's step program.

        The exec-order census (when ``aggregation.ledger_exec_feed`` is
        on, or ``feed_census=True``) is fed from the SAME trace — one
        profiler window serves both consumers; nested sessions raise in
        jax, so this is the only safe composition.  The compact summary
        also lands on the next StepRecord's ``extra['anatomy']``, the
        ``anatomy/*`` gauges, and the debug-bundle context.
        """
        from ..telemetry.anatomy import capture_step_anatomy
        from ..telemetry.anatomy.ledger import get_cost_ledger

        cfg = self._anatomy_cfg
        n = int(steps if steps is not None
                else cfg.anatomy_capture_steps)
        if feed_census is None:
            feed_census = bool(getattr(
                self.config.telemetry.aggregation, "ledger_exec_feed",
                False))
        ledger = self.cost_ledger or get_cost_ledger()

        def _one(b):
            m = self.train_step(b)
            float(m["loss"])  # the per-step fence IS the window edge
            return m["loss"]

        summary = capture_step_anatomy(
            _one, batch, steps=n, trace_dir=trace_dir,
            site=self._anatomy_site(), ledger=ledger,
            top_k=int(cfg.anatomy_top_k), feed_census=feed_census)
        if not summary.get("deferred"):
            compact = {k: summary.get(k) for k in (
                "window_us", "steps", "compute_us", "coll_exposed_us",
                "coll_overlapped_us", "host_sync_us", "idle_us",
                "comm_fraction", "overlap_hiding_frac",
                "attributed_frac", "roofline_top")}
            self._last_anatomy = compact
            self.telemetry.set_gauge(
                "anatomy/comm_fraction",
                float(summary.get("comm_fraction") or 0.0),
                help="exposed-collective fraction of step wall time")
            if summary.get("overlap_hiding_frac") is not None:
                self.telemetry.set_gauge(
                    "anatomy/overlap_hiding_frac",
                    float(summary["overlap_hiding_frac"]),
                    help="collective time hidden under compute")
            self.telemetry.set_gauge(
                "anatomy/attributed_frac",
                float(summary.get("attributed_frac") or 0.0),
                help="fenced step time the trace explains")
        return summary

    # ------------------------------------------------------------------
    # DeepSpeed compat surface: forward / backward / step
    # ------------------------------------------------------------------

    def forward(self, batch):
        """Compat fwd: record the microbatch, return its loss (lazy array)."""
        self._pending_batch = batch
        return self.eval_loss(batch)

    __call__ = forward

    def backward(self, loss=None):
        """Compat bwd: queue the pending microbatch for the fused step.
        The actual gradient computation happens inside the compiled program
        fired by :meth:`step` at the accumulation boundary."""
        if self._pending_batch is None:
            raise RuntimeError("backward() called without a prior forward()")
        self._microbatch_buffer.append(self._pending_batch)
        self._pending_batch = None
        self.micro_steps += 1
        return loss

    def is_gradient_accumulation_boundary(self) -> bool:
        if self._accumulation_boundary_forced is not None:
            return self._accumulation_boundary_forced
        return len(self._microbatch_buffer) >= self.gradient_accumulation_steps

    def set_gradient_accumulation_boundary(self, is_boundary: bool) -> None:
        """[L ACC-DS:264-281] external override of the GAS boundary."""
        self._accumulation_boundary_forced = is_boundary

    def step(self):
        """Compat step: no-op until the accumulation boundary, then fire the
        compiled train step over the buffered microbatches."""
        if not self.is_gradient_accumulation_boundary():
            return
        if not self._microbatch_buffer:
            return
        buffered = self._microbatch_buffer
        self._microbatch_buffer = []
        n = len(buffered)
        batch = (buffered[0] if n == 1 else
                 jax.tree.map(lambda *xs: jnp.concatenate(xs), *buffered))
        if n == self.gradient_accumulation_steps:
            return self.train_step(batch)
        # partial accumulation (forced boundary): the program bakes GAS
        # in, so n needs its own — built once per distinct n and CACHED
        # (round-3 weak item 7: a workload that forces the same partial
        # boundary every epoch must not pay a recompile each time)
        logger.warning(f"stepping with {n} buffered microbatches "
                       f"(configured GAS={self.gradient_accumulation_steps})")
        saved_gas, saved_fn = self.gradient_accumulation_steps, self._train_step_fn
        saved_warm = self._warmup_step_fn
        saved_ltd = self._ltd_fns
        saved_inf_gas = self.infinity.gas if self.infinity is not None else None
        self.gradient_accumulation_steps = n
        if self.infinity is not None:
            self.infinity.gas = n  # the streaming executor baked its own
        # every GAS-baking program family gets a per-n cache entry —
        # warmup (1-bit) and LTD programs recompile per n too
        cached = self._partial_step_fns.get(n, (None, None, {}))
        self._train_step_fn, self._warmup_step_fn, self._ltd_fns = cached
        try:
            return self.train_step(batch)
        finally:
            self._partial_step_fns[n] = (self._train_step_fn,
                                         self._warmup_step_fn,
                                         self._ltd_fns)
            self.gradient_accumulation_steps = saved_gas
            if self.infinity is not None:
                self.infinity.gas = saved_inf_gas
            self._train_step_fn = saved_fn
            self._warmup_step_fn = saved_warm
            self._ltd_fns = saved_ltd

    # ------------------------------------------------------------------
    # introspection parity
    # ------------------------------------------------------------------

    def get_global_grad_norm(self) -> Optional[float]:
        if "grad_norm" not in self.last_metrics:
            return None
        return float(self.last_metrics["grad_norm"])

    def get_lr(self) -> List[float]:
        # state.step excludes overflow-skipped steps — it is the step the
        # compiled program actually fed to the schedule (global_steps counts
        # skips too and would drift ahead after any fp16 overflow).
        applied_step = int(self.state.step)
        self.lr_scheduler.last_step = applied_step
        return [float(self._schedule(applied_step))]

    def get_loss_scale(self) -> float:
        return float(self.state.loss_scale.scale)

    @property
    def overflow(self) -> bool:
        """fp16 skip signal of the LAST step [L ACC-DS:306-319]."""
        if "overflow" not in self.last_metrics:
            return False
        return bool(self.last_metrics["overflow"])

    @property
    def skipped_steps(self) -> int:
        return int(self.state.skipped_steps)

    def zero_grad(self) -> None:
        pass  # grads are step-local values in a functional engine

    def allreduce_gradients(self) -> None:
        pass  # GSPMD inserts DP grad reduction inside the compiled step

    def train(self, mode: bool = True):
        return self

    def eval(self):
        return self

    def compile(self, backend: Any = None,
                compile_kwargs: Optional[Dict[str, Any]] = None) -> None:
        """Compat [L ACC:2441-2446]: the reference exposes torch.compile
        here; on TPU every step is already an XLA program, so this just
        builds the train-step executable eagerly instead of on first call.
        ``backend``/``compile_kwargs`` accepted and ignored."""
        if (self._train_step_fn is None and not self.offload_enabled
                and self.infinity is None):
            self._train_step_fn = self._build_train_step()
        self.is_compiled = True

    def _zero3_consolidated_16bit_state_dict(
            self, exclude_frozen_parameters: bool = False):
        """Gather the (possibly ZeRO-3-sharded) params into replicated host
        bf16 arrays [L ACC:4042] — device_get assembles the logical array
        regardless of sharding."""
        return jax.tree.map(
            lambda p: np.asarray(jax.device_get(p)).astype(
                jnp.bfloat16 if jnp.issubdtype(p.dtype, jnp.floating)
                else p.dtype),
            self.state.params)

    # checkpointing implemented in runtime/checkpointing.py, attached by entry
    def save_checkpoint(self, save_dir, tag=None, client_state=None,
                        exclude_frozen_parameters=False):
        from .checkpointing import save_checkpoint as _save

        return _save(self, save_dir, tag=tag, client_state=client_state or {})

    def load_universal_checkpoint(self, universal_dir):
        """Resume from a ``ds_to_universal`` per-parameter directory at
        THIS engine's parallelism layout (reference --load_universal)."""
        from .checkpointing import load_universal_checkpoint

        return load_universal_checkpoint(self, universal_dir)

    def load_checkpoint(self, load_dir, tag=None, load_module_strict=True,
                        load_optimizer_states=True,
                        load_lr_scheduler_states=True,
                        load_module_only=False):
        from .checkpointing import load_checkpoint as _load

        return _load(self, load_dir, tag=tag,
                     load_optimizer_states=load_optimizer_states,
                     load_module_only=load_module_only)
