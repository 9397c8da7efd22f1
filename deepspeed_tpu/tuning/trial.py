"""Trial runners — run one candidate for a few steps, score from telemetry.

The old autotuner timed ``time.time()`` around unfenced dispatches, which
measures host queueing, not the device.  Here every
timed step is device-fenced (the loss scalar fetch IS the fence) and the
score comes from the engine's own device-fenced StepRecords when the
candidate engine runs with telemetry — the same numbers the bench and
the perf sentinel read, so a tune can never disagree with them.  Compile
cost is read from the compile tracker (and the engine already charges it
to the goodput ``compile`` bucket, so a tune's compiles never trip the
``throughput_regression`` health rule), and the memory ledger supplies
``peak_hbm_bytes`` / ``hbm_headroom_frac`` per candidate.

A candidate that OOMs is caught via ``is_oom_error`` and recorded as
*infeasible* with its memory breakdown — a data point for the calibrated
memory model, never a crash of the search.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

from ..utils.logging import debug_once, logger
from .space import apply_overrides, split_overrides


@dataclass
class TrialResult:
    candidate: Dict[str, Any]
    feasible: bool = True
    #: score metrics (tokens_per_sec / samples_per_sec / mfu / ...)
    metrics: Dict[str, float] = field(default_factory=dict)
    #: how the score was measured: "telemetry" (device-fenced
    #: StepRecords) or "wall_clock" (fenced loop timing fallback)
    source: str = "wall_clock"
    timed_steps: int = 0
    oom: bool = False
    pruned: Optional[str] = None
    error: Optional[str] = None
    #: per-pool HBM breakdown at failure/completion (memory ledger)
    memory: Dict[str, Any] = field(default_factory=dict)
    compile_s: float = 0.0
    compile_events: int = 0

    def score(self, metric: str) -> Optional[float]:
        v = self.metrics.get(metric)
        return None if v is None else float(v)

    def to_record(self) -> Dict[str, Any]:
        rec: Dict[str, Any] = {"candidate": dict(self.candidate),
                               "feasible": self.feasible,
                               "source": self.source,
                               "timed_steps": self.timed_steps}
        if self.metrics:
            rec["metrics"] = {k: round(float(v), 4)
                              for k, v in self.metrics.items()}
        if self.compile_events:
            rec["compile_s"] = round(self.compile_s, 3)
            rec["compile_events"] = self.compile_events
        if self.pruned:
            rec["pruned"] = self.pruned
        if self.oom:
            rec["oom"] = True
        if self.error:
            rec["error"] = self.error[:300]
        if self.memory:
            rec["memory"] = self.memory
        return rec


class TrialRunner:
    """Interface: ``run(candidate, timed_steps) -> TrialResult``."""

    def run(self, candidate: Dict[str, Any],
            timed_steps: int = 3) -> TrialResult:
        raise NotImplementedError


class EngineTrialRunner(TrialRunner):
    """Build a candidate engine in-process and measure a few steps.

    ``engine_factory(config_dict, model_overrides) -> engine`` and
    ``batch_factory(config_dict) -> batch`` own model/params/mesh so the
    runner stays generic (the legacy one-arg ``engine_factory(config)``
    shape is accepted too).  A factory that declares a ``candidate=``
    keyword additionally receives the full candidate dict — the only way
    to read ``tuning.*`` harness knobs (donation, mesh layout), which
    never enter the DS config.  Engines that expose the ``trial_run``
    hook (DeepSpeedEngine) are measured through it — telemetry-sourced
    numbers; anything else falls back to a fenced wall-clock loop.
    """

    def __init__(self, engine_factory: Callable[..., Any],
                 batch_factory: Callable[[Dict[str, Any]], Any],
                 base_config: Dict[str, Any],
                 warmup_steps: int = 1,
                 memory_model: Optional[Any] = None,
                 teardown: Optional[Callable[[Any], None]] = None):
        self.engine_factory = engine_factory
        self.batch_factory = batch_factory
        self.base_config = dict(base_config)
        self.warmup_steps = max(int(warmup_steps), 0)
        self.memory_model = memory_model
        self.teardown = teardown

    # -- plumbing ----------------------------------------------------------

    def _build(self, candidate: Dict[str, Any]):
        config_over, model_over = split_overrides(candidate)
        # tuning.* keys are search-harness knobs (donation, mesh layout),
        # not DS-config keys the engine validates — factories that care
        # declare a ``candidate=`` keyword and get the full dict
        config_over = {k: v for k, v in config_over.items()
                       if not k.startswith("tuning.")}
        cfg = apply_overrides(self.base_config, config_over)
        shape = self._factory_positional()
        kwargs = ({"candidate": dict(candidate)}
                  if shape["takes_candidate"] else {})
        # the second positional is treated as the model_overrides slot
        # only when it is REQUIRED, is *args, or is NAMED for the role —
        # an unrelated optional second positional (cfg, model_cls=None)
        # must never silently receive the overrides dict
        overrides_slot = (shape["required"] >= 2 or shape["varargs"]
                          or shape["second_name"] in ("model_overrides",
                                                      "model_over",
                                                      "overrides"))
        if model_over:
            if not overrides_slot:
                raise ValueError(
                    f"candidate carries model overrides {model_over} but "
                    f"the engine factory takes only (config) — give it a "
                    f"(config, model_overrides) signature")
            engine = self.engine_factory(cfg, model_over, **kwargs)
        elif shape["required"] >= 2:
            engine = self.engine_factory(cfg, {}, **kwargs)
        else:
            # legacy one-arg factory — a factory with an OPTIONAL second
            # positional (e.g. (cfg, model_cls=...)) keeps its default
            engine = self.engine_factory(cfg, **kwargs)
        return engine, cfg

    def _factory_positional(self) -> Dict[str, Any]:
        """Shape of the engine factory's signature: ``required``
        positional count, the ``second_name`` of its second positional
        (None when absent), ``varargs``, and whether it ``takes_candidate``
        as a keyword.  Unknown signatures count as legacy one-arg."""
        import inspect

        shape: Dict[str, Any] = {"required": 1, "second_name": None,
                                 "varargs": False, "takes_candidate": False}
        try:
            sig = inspect.signature(self.engine_factory)
        except (TypeError, ValueError):
            return shape  # builtins/partials without signatures
        shape["required"] = 0
        capacity = 0
        for p in sig.parameters.values():
            if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD):
                capacity += 1
                if capacity == 2:
                    shape["second_name"] = p.name
                if p.default is p.empty:
                    shape["required"] += 1
            elif p.kind is p.VAR_POSITIONAL:
                shape["varargs"] = True
            if p.name == "candidate" and (
                    p.kind is p.KEYWORD_ONLY
                    or (p.kind is p.POSITIONAL_OR_KEYWORD and capacity > 2)):
                # keyword-only, or a 3rd+ positional — never one of the
                # two slots (config, model_overrides) we fill positionally
                shape["takes_candidate"] = True
        return shape

    @staticmethod
    def _fence(metrics: Any) -> None:
        """Per-step device fence: fetch the loss scalar, which exists
        only once the step has run."""
        if isinstance(metrics, dict) and "loss" in metrics:
            float(metrics["loss"])

    def _memory_breakdown(self) -> Dict[str, Any]:
        try:
            from ..telemetry.memory import get_memory_ledger

            led = get_memory_ledger()
            if not led.enabled:
                return {}
            out: Dict[str, Any] = {"pools_hbm": led.pool_bytes(space="hbm")}
            dev = led.device_stats()
            if dev:
                out["device"] = dev
            return out
        except Exception as e:
            logger.debug(f"tuning: memory breakdown unavailable ({e!r})")
            return {}

    def _calibrate(self, candidate: Dict[str, Any]) -> None:
        if self.memory_model is None:
            return
        try:
            from ..telemetry.memory import get_memory_ledger

            led = get_memory_ledger()
            if not led.enabled:
                return
            pools = led.pool_bytes(space="hbm", include_transient=True)
            measured = sum(pools.get(p, 0)
                           for p in ("params", "grads", "optimizer"))
            self.memory_model.calibrate(candidate, measured)
        except Exception as e:
            logger.debug(f"tuning: ledger calibration skipped ({e!r})")

    # -- the trial ---------------------------------------------------------

    def run(self, candidate: Dict[str, Any],
            timed_steps: int = 3) -> TrialResult:
        from ..telemetry.memory.oom import is_oom_error
        from ..telemetry.perf import get_compile_tracker

        timed_steps = max(int(timed_steps), 1)
        trk = get_compile_tracker()
        ev0, ms0 = trk.events_total, trk.time_ms_total
        engine = None
        try:
            engine, cfg = self._build(candidate)
            batch = self.batch_factory(cfg)
            if callable(getattr(engine, "trial_run", None)):
                summary = engine.trial_run(batch,
                                           warmup_steps=self.warmup_steps,
                                           timed_steps=timed_steps)
                # v is not None, NOT truthiness: hbm_headroom_frac=0.0
                # ("no headroom") is exactly the value analysis needs
                metrics = {k: float(v) for k, v in summary.items()
                           if k in ("tokens_per_sec", "samples_per_sec",
                                    "mfu", "step_time_p50_ms",
                                    "peak_hbm_bytes", "hbm_headroom_frac",
                                    "roofline_headroom")
                           and v is not None}
                source = str(summary.get("source", "telemetry"))
            else:  # legacy/fake engines: fenced wall-clock loop
                m = None
                for _ in range(self.warmup_steps):
                    m = engine.train_step(batch)
                if m is not None:
                    self._fence(m)
                t0 = time.perf_counter()
                for _ in range(timed_steps):
                    m = engine.train_step(batch)
                    self._fence(m)  # per-step fence: device time, not queue
                dt = (time.perf_counter() - t0) / timed_steps
                samples = float(getattr(engine, "train_batch_size", 0) or 1)
                # tokens_per_sec must exist on this path too — it is the
                # default score metric, and a search over wall-clock
                # engines would otherwise find "no feasible candidate";
                # rows×seq from the batch when it has array leaves, else
                # seq degenerates to 1 (tokens == samples)
                rows, seq = samples, 1.0
                try:
                    import jax

                    leaves = [l for l in jax.tree.leaves(batch)
                              if getattr(l, "ndim", 0) >= 1]
                    if leaves:
                        rows = float(leaves[0].shape[0])
                        if leaves[0].ndim >= 2:
                            seq = float(leaves[0].shape[1])
                except Exception as e:
                    debug_once("tuning/wallclock_batch_shape",
                               f"batch shape unreadable ({e!r}); tokens "
                               f"degrade to samples")
                metrics = {"samples_per_sec": samples / max(dt, 1e-9),
                           "tokens_per_sec": rows * seq / max(dt, 1e-9),
                           "step_time_p50_ms": dt * 1e3}
                source = "wall_clock"
            self._calibrate(candidate)
            result = TrialResult(candidate=dict(candidate), feasible=True,
                                 metrics=metrics, source=source,
                                 timed_steps=timed_steps,
                                 memory=self._memory_breakdown())
        except Exception as e:
            if is_oom_error(e):
                result = TrialResult(candidate=dict(candidate),
                                     feasible=False, oom=True,
                                     error=str(e),
                                     memory=self._memory_breakdown())
            else:
                logger.warning(f"tuning trial {candidate} failed: {e}")
                result = TrialResult(candidate=dict(candidate),
                                     feasible=False, error=str(e))
        finally:
            if engine is not None and self.teardown is not None:
                self.teardown(engine)
        result.compile_events = trk.events_total - ev0
        result.compile_s = (trk.time_ms_total - ms0) / 1e3
        return result


class SyntheticTrialRunner(TrialRunner):
    """Deterministic cost-model runner for tests and the CLI smoke.

    ``cost_model(candidate) -> {metric: value, ...}``; raise from it (or
    return ``{"oom": True}``) to simulate an infeasible candidate.  Every
    ``run`` is counted so tests can assert pruning really skipped work.
    """

    def __init__(self, cost_model: Callable[[Dict[str, Any]],
                                            Dict[str, float]],
                 memory_model: Optional[Any] = None):
        self.cost_model = cost_model
        self.memory_model = memory_model
        self.calls: List[Dict[str, Any]] = []

    def run(self, candidate: Dict[str, Any],
            timed_steps: int = 3) -> TrialResult:
        from ..telemetry.memory.oom import is_oom_error

        self.calls.append(dict(candidate))
        try:
            out = dict(self.cost_model(candidate))
        except Exception as e:
            if is_oom_error(e):
                return TrialResult(candidate=dict(candidate), feasible=False,
                                   oom=True, error=str(e),
                                   memory={"pools_hbm": {}})
            return TrialResult(candidate=dict(candidate), feasible=False,
                               error=str(e))
        if out.pop("oom", False):
            return TrialResult(candidate=dict(candidate), feasible=False,
                               oom=True, error="synthetic OOM",
                               memory={"pools_hbm": {}})
        measured = out.pop("measured_state_bytes", None)
        if measured and self.memory_model is not None:
            self.memory_model.calibrate(candidate, int(measured))
        return TrialResult(candidate=dict(candidate), feasible=True,
                           metrics={k: float(v) for k, v in out.items()},
                           source="synthetic", timed_steps=int(timed_steps))
