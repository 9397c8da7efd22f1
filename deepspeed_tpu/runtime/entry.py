"""deepspeed.initialize() — the public factory.

Signature parity with the reference ``deepspeed/__init__.py:initialize``
[L ACC:2358-2439]: returns ``(engine, optimizer, training_dataloader,
lr_scheduler)``; accepts ``config`` | ``config_params`` (dict, path, or
base64), ``model_parameters``, user ``optimizer`` / ``lr_scheduler``, and
``mpu``.  Routes to PipelineEngine when the model is a PipelineModule
(reference behavior), else DeepSpeedEngine.

TPU adaptation of the model argument: the reference takes a torch
``nn.Module`` whose loss the USER computes eagerly.  Here ``model`` is one of
  * a pure loss function ``loss_fn(params, batch) -> scalar``        (JAX-natural)
  * an object exposing ``.loss(params, batch)`` (e.g. our model wrappers)
  * a ``PipelineModule`` (pipeline-parallel path)
with ``model_parameters`` the parameter pytree (or an abstract init thunk —
see ``zero.Init``).
"""

from __future__ import annotations

import os
from typing import Any, Optional, Tuple

import jax

from ..parallel.mesh import MeshLayout
from ..telemetry import get_telemetry, startup_span
from ..utils import groups as groups_mod
from ..utils.logging import log_dist
from .config import DeepSpeedConfig
from .engine import DeepSpeedEngine


def _resolve_config(config, config_params) -> DeepSpeedConfig:
    payload = config if config is not None else config_params
    if payload is None:
        raise ValueError("deepspeed_tpu.initialize needs config or config_params")
    if isinstance(payload, DeepSpeedConfig):
        return payload
    if not isinstance(payload, dict):
        from .config import _load_config_payload

        payload = _load_config_payload(payload)
    override = os.environ.get("DS_AUTOTUNING_CONFIG_OVERRIDE")
    if override:
        # the launcher's --autotuning orchestration hands each candidate
        # run its dotted-key overrides through the environment (the
        # reference's exp-config rewrite, deepspeed/autotuning/)
        import json as _json

        payload = dict(payload)
        for dotted, value in _json.loads(override).items():
            node = payload
            parts = dotted.split(".")
            for p in parts[:-1]:
                cur = node.get(p)
                if cur is not None and not isinstance(cur, dict):
                    # a dotted path must traverse objects; walking through
                    # e.g. a string would die later in an opaque TypeError
                    # that aborts the whole candidate run
                    raise ValueError(
                        f"DS_AUTOTUNING_CONFIG_OVERRIDE key {dotted!r}: "
                        f"config node {p!r} holds the non-object value "
                        f"{cur!r} ({type(cur).__name__}) — cannot set a "
                        f"nested key under it")
                nxt = dict(cur or {})
                node[p] = nxt
                node = nxt
            node[parts[-1]] = value
    # batch sizes resolved below, once the parallel dims are known
    return DeepSpeedConfig.model_validate(payload)


def _apply_moe_config(cfg, model: Any, mesh: Any = None) -> None:
    """Push the ``moe.*`` config group onto the model's MOELayer/TopKGate.

    Models build their MoE block at construction time (before
    ``initialize`` sees the config), so the engine applies the dispatch /
    capacity knobs here.  Works for any model exposing ``_moe_layer``
    (MixtralModel) or ``moe_layer`` (the reference-shaped ``MoE`` block).
    """
    layer = getattr(model, "_moe_layer", None) or getattr(
        model, "moe_layer", None)
    if layer is None:
        return
    moe = cfg.moe
    if layer.mesh is None and mesh is not None:
        layer.mesh = mesh
    if layer.gate.mesh is None and mesh is not None:
        layer.gate.mesh = mesh
    if moe.dispatch_impl != "auto":
        layer.dispatch_impl = moe.dispatch_impl
    gate = layer.gate
    gate.pad_to_ep = bool(moe.pad_capacity_to_ep)
    if moe.use_rts:
        gate.use_rts = True
    if moe.capacity_factor and moe.capacity_factor > 0:
        gate.capacity_factor = float(moe.capacity_factor)
        gate.eval_capacity_factor = float(moe.capacity_factor)


def initialize(args: Any = None,
               model: Any = None,
               optimizer: Any = None,
               model_parameters: Any = None,
               training_data: Any = None,
               lr_scheduler: Any = None,
               distributed_port: Optional[int] = None,
               mpu: Any = None,
               dist_init_required: Optional[bool] = None,
               collate_fn: Any = None,
               config: Any = None,
               config_params: Any = None,
               mesh: Any = None,
               _entered: Optional[Tuple[float, float]] = None
               ) -> Tuple[DeepSpeedEngine, Any, Any, Any]:
    """The whole of it is the start-up record's root
    ``startup/initialize``, its phases the spans below (``PERF.md`` has
    the tree).  ``_entered`` is the package's: the two ``perf_counter()``
    stamps around its import of this module, which is the start's first
    phase and ran before a span could be opened."""
    with startup_span("startup/initialize") as root:
        if _entered is not None:
            root.start = _entered[0]
            get_telemetry().startup.add(
                "startup/import", *_entered, {"module": __name__},
                parent="startup/initialize", depth=1)
        return _initialize(
            root, model=model, optimizer=optimizer,
            model_parameters=model_parameters, training_data=training_data,
            lr_scheduler=lr_scheduler, mpu=mpu,
            dist_init_required=dist_init_required, collate_fn=collate_fn,
            config=config, config_params=config_params, mesh=mesh)


def _initialize(root, *, model, optimizer, model_parameters, training_data,
                lr_scheduler, mpu, dist_init_required, collate_fn, config,
                config_params, mesh) -> Tuple[DeepSpeedEngine, Any, Any, Any]:
    from .. import comm

    with startup_span("startup/distributed"):
        if dist_init_required is not False:
            comm.init_distributed()

    with startup_span("startup/config"):
        cfg = _resolve_config(config, config_params)

    # Build/adopt the mesh from the parallel dims in config (+ mpu hints).
    with startup_span("startup/mesh"):
        if mesh is None:
            tp = int(cfg.tensor_parallel.autotp_size or 1)
            sp = int(cfg.sequence_parallel.sp_size or 1)
            pp = int(cfg.pipeline.stages or 1)
            ep = int(cfg.moe.expert_parallel_size or 1)
            if mpu is not None and hasattr(mpu, "get_sequence_parallel_world_size"):
                sp = int(mpu.get_sequence_parallel_world_size())
            dp = None
            mics = int(cfg.zero_optimization.mics_shard_size or -1)
            if mics > 0 and ep > 1:
                # MiCS repurposes the expert axis as its replica axis — it
                # cannot coexist with a real expert-parallel degree
                raise ValueError(
                    f"moe.expert_parallel_size={ep} is incompatible with "
                    f"mics_shard_size={mics}: MiCS uses the expert mesh axis "
                    "as its replica axis; disable one of the two")
            if ep > 1:
                total_dp = jax.device_count() // (tp * pp * sp)
                if total_dp % ep:
                    raise ValueError(
                        f"moe.expert_parallel_size={ep} must divide the DP "
                        f"world {total_dp} (= world/(tp·pp·sp))")
                dp = total_dp // ep
            if mics > 0:
                # MiCS: factor the DP world into (data=shard-group,
                # expert=replica-groups) so the sharder's data-axis-only
                # sharding realizes the sub-group partition.  The expert axis
                # doubles as the replica axis — MoE EP and MiCS can't share it.
                total_dp = jax.device_count() // (tp * pp * sp)
                if total_dp % mics:
                    raise ValueError(
                        f"mics_shard_size={mics} must divide the DP world "
                        f"{total_dp}")
                dp, ep = mics, total_dp // mics
            layout = MeshLayout.infer(jax.device_count(), tp=tp, pp=pp, sp=sp,
                                      ep=ep, dp=dp)
            mesh = groups_mod.initialize_mesh(layout)
            world = jax.device_count()
        else:
            # an explicit mesh is authoritative for every parallel dim
            groups_mod.initialize_mesh(mesh=mesh)
            tp = int(mesh.shape.get("tensor", 1))
            sp = int(mesh.shape.get("seq", 1))
            pp = int(mesh.shape.get("pipe", 1))
            world = int(mesh.devices.size)

    # --- telemetry-driven autotuning (tuning/ — ISSUE 9) -----------------
    # consult the best-known-config store BEFORE resolve_batch_sizes:
    # resolution assigns the batch triple (pydantic marks assigned fields
    # as set), so the pinned-knob check must see the USER's fields only.
    # Promoted entries apply; pinned knobs always win; what happened is
    # stamped into every debug bundle (context.tuning) and readable via
    # tuning.autoapply for bench artifacts (tuned_config_source).
    with startup_span("startup/import", {"module": "deepspeed_tpu.tuning"}):
        from ..tuning.autoapply import (maybe_apply_tuned_config,
                                        reset_applied)

    with startup_span("startup/config"):
        if cfg.tuning.enabled and cfg.tuning.auto_apply:
            maybe_apply_tuned_config(cfg, model=model,
                                     model_parameters=model_parameters,
                                     mesh=mesh)
        else:
            # skipping the consult must also clear a PREVIOUS
            # initialize()'s hit — bundles/bench would otherwise report
            # that engine's tuned config for this untuned one
            reset_applied()

        cfg.resolve_batch_sizes(world_size=world, tp=tp, pp=pp, sp=sp)
        cfg.resolve_auto_precision()
        root.set(stage=int(cfg.zero_optimization.stage), world=world)

        if cfg.comms_logger.enabled:
            comm.comms_logger.configure(
                enabled=True, verbose=cfg.comms_logger.verbose,
                exec_counts=cfg.comms_logger.exec_counts)

    # the telemetry stack's own share of a start
    with startup_span("startup/observability"):
        if cfg.telemetry.enabled:
            # configure the hub BEFORE engine construction so state-placement /
            # compile spans of the build itself are captured
            from ..telemetry import configure_from_config

            configure_from_config(cfg.telemetry)

        # flight recorder BEFORE engine construction: a crash during state
        # placement / first compile still gets a debug bundle, and the
        # fatal-signal + unhandled-exception hooks cover the whole run
        from ..telemetry.flight_recorder import recorder_from_config

        recorder = recorder_from_config(cfg.telemetry)
        if recorder is not None:
            recorder.register_context("startup",
                                      get_telemetry().startup_report)
            if cfg.telemetry.flight_recorder.install_handlers:
                recorder.install()

        # cross-host observability plane (telemetry/{aggregator,
        # collective_ledger}.py): the ledger hooks into the comms logger
        # BEFORE engine construction so state-placement / first-compile
        # collectives are in the sequence; the publisher is the process-global
        # service the elastic agent's heartbeat loop drives
        if cfg.telemetry.aggregation.enabled:
            from ..telemetry.aggregator import publisher_from_config

            publisher = publisher_from_config(cfg.telemetry)
            # subprocess deployments: THIS (worker) process owns the recorder
            # and ledger, but the elastic agent heartbeats in its own process
            # where get_publisher() is None — so the worker services the
            # store itself through the endpoint the agent exported
            rdzv_endpoint = os.environ.get("DS_RDZV_ENDPOINT")
            if publisher is not None and rdzv_endpoint:
                publisher.start_daemon(rdzv_endpoint)
            if cfg.telemetry.aggregation.ledger_enabled:
                from ..telemetry import configure_collective_ledger

                configure_collective_ledger(
                    max_entries=cfg.telemetry.aggregation.ledger_max_entries,
                    tail=cfg.telemetry.aggregation.ledger_tail,
                    exec_feed=cfg.telemetry.aggregation.ledger_exec_feed,
                    recorder=recorder)
            # cross-process telemetry plane (telemetry/rollup.py): compact
            # StepRecords buffer in a bounded ring and ship to rank 0's
            # rollup on the publisher tick (with the registry snapshot)
            from ..telemetry import configure_step_stream

            configure_step_stream(
                enabled=(cfg.telemetry.aggregation.metrics_rollup
                         and cfg.telemetry.aggregation.step_stream),
                maxlen=cfg.telemetry.aggregation.step_stream_len)
            # fleet-synchronized profiler capture plane (telemetry/profiler):
            # the publisher tick polls the store for `telemetry profile`
            # commands, the engine feeds on_step, the window's device lanes
            # publish back through the store
            pcfg = cfg.telemetry.profiler
            if pcfg.enabled:
                from ..telemetry.profiler import configure_profiler_plane

                plane = configure_profiler_plane(
                    node_id=os.environ.get("DS_ELASTIC_NODE_ID",
                                           f"node-{os.getpid()}"),
                    out_dir=pcfg.out_dir or None,
                    ring=pcfg.ring, lead=pcfg.lead,
                    duty_cycle_pct=pcfg.duty_cycle_pct,
                    duty_period_steps=pcfg.duty_period_steps)
                if recorder is not None:
                    plane.register_bundle_context(recorder)
        else:
            # a previous initialize() may have enabled the stream — this
            # engine's config says no aggregation, so stop buffering
            from ..telemetry import configure_step_stream

            configure_step_stream(enabled=False)

    # --- MoE plane: push the moe.* group onto the model's MOELayer -------
    _apply_moe_config(cfg, model, mesh)

    # --- resolve the model into a loss_fn --------------------------------
    with startup_span("startup/import",
                      {"module": "deepspeed_tpu.runtime.pipe"}):
        from .pipe.module import PipelineModule  # noqa: avoid cycle at import time

    if isinstance(model, PipelineModule):
        with startup_span("startup/import",
                          {"module": "deepspeed_tpu.runtime.pipe.engine"}):
            from .pipe.engine import PipelineEngine

        with startup_span("startup/engine", {"engine": "PipelineEngine"}):
            engine = PipelineEngine(module=model, config=cfg, mesh=mesh,
                                    optimizer=optimizer,
                                    lr_schedule=lr_scheduler)
    else:
        if callable(getattr(model, "loss", None)):
            loss_fn = model.loss
            if model_parameters is None and hasattr(model, "init_params"):
                model_parameters = model.init_params(jax.random.PRNGKey(cfg.seed))
        elif callable(model):
            loss_fn = model
        else:
            raise TypeError(
                "model must be a loss function, an object with .loss(), or a "
                f"PipelineModule; got {type(model)}")
        if model_parameters is None:
            raise ValueError("model_parameters (a param pytree) is required")
        with startup_span("startup/engine", {"engine": "DeepSpeedEngine"}):
            engine = DeepSpeedEngine(loss_fn=loss_fn, params=model_parameters,
                                     config=cfg, optimizer=optimizer,
                                     lr_schedule=lr_scheduler
                                     if callable(lr_scheduler) else None,
                                     module=model, mesh=mesh)

    # --- monitor ----------------------------------------------------------
    with startup_span("startup/import", {"module": "deepspeed_tpu.monitor"}):
        from ..monitor.monitor import MonitorMaster

    with startup_span("startup/observability"):
        monitor = MonitorMaster(cfg)
        if monitor.enabled:
            engine.monitor = monitor

    if cfg.hybrid_engine.enabled:
        with startup_span("startup/engine",
                          {"engine": "DeepSpeedHybridEngine"}):
            from .hybrid_engine import DeepSpeedHybridEngine

            engine = DeepSpeedHybridEngine(
                engine, max_out_tokens=cfg.hybrid_engine.max_out_tokens)

    dataloader = None
    if training_data is not None:
        with startup_span("startup/dataloader"):
            from .dataloader import DeepSpeedDataLoader

            dataloader = DeepSpeedDataLoader(
                training_data, batch_size=int(cfg.train_batch_size),
                mesh=mesh, collate_fn=collate_fn, shuffle=True, seed=cfg.seed)
            # seqlen curriculum: legacy top-level group or the data_efficiency
            # nested form — both feed the same scheduler
            cl = dict(cfg.curriculum_learning or {})
            if not cl.get("enabled"):
                cl = dict(cfg.data_efficiency.data_sampling.get(
                    "curriculum_learning", {})) if cfg.data_efficiency.enabled \
                    else {}
            if cl.get("enabled"):
                from .data_pipeline import CurriculumScheduler
                from .data_pipeline.data_sampler import CurriculumDataLoader

                sched = CurriculumScheduler(cl)
                engine.curriculum_scheduler = sched
                dataloader = CurriculumDataLoader(
                    dataloader, sched, lambda: engine.global_steps)
                log_dist(f"curriculum learning: seqlen "
                         f"{sched.min}→{sched.max} over "
                         f"{getattr(sched, 'total', '?')} steps")

    # --- resilience plane (resilience/ — ISSUE 4) -------------------------
    # wired LAST so resume-from-snapshot sees the fully-assembled engine
    # (and the dataloader's cursor hook is registered before any restore)
    if getattr(engine, "resilience", None) is not None:
        # (a restarted worker's restore from its snapshot lies here)
        with startup_span("startup/engine/resilience"):
            if dataloader is not None:
                dl = dataloader  # bind the (possibly curriculum-wrapped) loader
                inner = getattr(dl, "loader", dl)
                # sample-progress anchor: steps*tb alone under-counts any
                # run whose global batch already changed once (an earlier
                # reshape), so progress ACCUMULATES from the last restored
                # position instead of being re-derived from the current tb
                base = {"samples": 0, "steps": 0}

                def _capture_cursor(eng=engine, inner=inner, base=base):
                    # position in SAMPLES, not steps: a snapshot resumed on
                    # a different world (different global batch) converts
                    # back without double-consuming any window
                    tb = int(eng.train_batch_size or 0)
                    consumed = base["samples"] \
                        + (int(eng.global_steps) - base["steps"]) * tb
                    return {"epoch": int(getattr(inner, "_epoch", 0)),
                            "consumed_samples": consumed,
                            "train_batch_size": tb}

                def _restore_cursor(p, eng=engine, inner=inner, base=base):
                    inner._epoch = int(p.get("epoch", 0))
                    origin_tb = int(p.get("train_batch_size", 0) or 0)
                    consumed = int(p.get("consumed_samples", -1))
                    if consumed < 0:
                        return
                    # every step from here on consumes THIS engine's tb
                    base["samples"], base["steps"] = \
                        consumed, int(eng.global_steps)
                    if (origin_tb
                            and origin_tb != int(eng.train_batch_size or 0)
                            and hasattr(inner, "resume_from_samples")):
                        # mesh reshape changed the global batch: re-point
                        # the cursor at the absolute sample position
                        inner.resume_from_samples(consumed)

                engine.snapshots.register_meta(
                    "data_sampler", _capture_cursor, restore=_restore_cursor)
            if cfg.resilience.buddy_tier and os.environ.get("DS_RDZV_ENDPOINT"):
                # tier 2 from the WORKER process: the sealed ring + buddy
                # slot live in the store, so a plain client suffices even
                # when the elastic agent heartbeats in a different process
                from ..elasticity.rendezvous import (ElasticRendezvous,
                                                     RendezvousClient)

                engine.snapshots.attach_rendezvous(ElasticRendezvous(
                    RendezvousClient(os.environ["DS_RDZV_ENDPOINT"]),
                    node_id=os.environ.get("DS_ELASTIC_NODE_ID",
                                           f"node-{os.getpid()}")))
            # elastic restart path: the agent exported DS_ELASTIC_RESTART_COUNT;
            # a restarted worker resumes from the policy-chosen newest VALID
            # snapshot (checksum-gated, tier fallback)
            engine.resilience.resume_if_restarted()

    log_dist(f"deepspeed_tpu.initialize: stage={cfg.zero_optimization.stage} "
             f"dtype={cfg.dtype().__name__} mesh={dict(mesh.shape)} "
             f"batch={cfg.train_batch_size}(micro={cfg.train_micro_batch_size_per_gpu}"
             f"×gas={cfg.gradient_accumulation_steps})")
    return engine, engine.optimizer, dataloader, engine.lr_scheduler
