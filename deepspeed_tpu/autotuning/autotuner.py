"""Autotuner — the reference API shape, now a shim over ``tuning/``.

Reference: ``deepspeed/autotuning/`` [K] — ``Autotuner`` +
``GridSearchTuner/RandomTuner/ModelBasedTuner`` launch short profiling jobs
over ``zero_optimization.stage`` / micro-batch / offload and pick the best
throughput config (SURVEY §2.5).

TPU-first: no subprocess launches — each candidate is one jit compile + a
few timed steps IN PROCESS.  Since ISSUE 9 the measurement itself lives in
the autotuning plane (``deepspeed_tpu/tuning/``): trials are DEVICE-FENCED
per timed step (the loss-scalar fetch is the fence — ``time.time()``
around unfenced dispatches measures host queueing, not the device),
scored from the engine's own StepRecords when telemetry is on, and pruned
through the ledger-calibrated memory model.  This module keeps the
reference entry points (``Autotuner``/``ModelBasedTuner``/``autotune``,
the ``DS_AUTOTUNING_*`` env flows, the emitted best-config JSON shape) as
thin shims over that plane.
"""

from __future__ import annotations

import itertools
import json
from typing import Any, Callable, Dict, List, Optional, Sequence

from ..utils.logging import log_dist, logger

DEFAULT_TUNING_SPACE = {
    "zero_optimization.stage": [0, 1, 2, 3],
    "train_micro_batch_size_per_gpu": [1, 2, 4, 8],
}

#: reference's offload dimension (enabled by passing this as tuning_space
#: or merging it in; kept out of the default so fast tunes stay fast)
OFFLOAD_TUNING_SPACE = {
    **DEFAULT_TUNING_SPACE,
    "zero_optimization.offload_optimizer.device": ["none", "cpu"],
}


def zero_memory_estimate(n_params: int, stage: int, dp: int,
                         offload_optimizer: bool = False,
                         dtype_bytes: int = 2) -> int:
    """Device bytes/chip for model+optimizer state under a ZeRO stage —
    the reference ModelBasedTuner's memory model (params bf16 2N, grads
    2N, fp32 master+Adam moments 12N, sharded per stage; activations not
    included — the XLA OOM check catches those)."""
    params = dtype_bytes * n_params
    grads = dtype_bytes * n_params
    opt = 12 * n_params  # fp32 master + m + v
    if offload_optimizer:
        opt = 0
    if stage >= 1:
        opt //= dp
    if stage >= 2:
        grads //= dp
    if stage >= 3:
        params //= dp
    return params + grads + opt


class Autotuner:
    def __init__(self, engine_factory: Callable[[Dict[str, Any]], Any],
                 batch_factory: Callable[[Dict[str, Any]], Any],
                 base_config: Dict[str, Any],
                 tuning_space: Optional[Dict[str, List[Any]]] = None,
                 metric: str = "throughput", warmup_steps: int = 1,
                 timed_steps: int = 3, model_params_count: int = 0,
                 hbm_bytes: int = 0, dp_size: int = 1):
        """``engine_factory(config_dict) -> engine`` builds a fresh engine;
        ``batch_factory(config_dict) -> batch`` supplies a matching global
        batch.  Factories own model/params so the tuner stays generic.

        ``model_params_count`` + ``hbm_bytes`` (both optional) switch on
        the memory model: candidates whose estimated state footprint
        exceeds HBM are pruned WITHOUT compiling them (the reference
        ModelBasedTuner's OOM pre-screen); 0 for either disables it."""
        self.engine_factory = engine_factory
        self.batch_factory = batch_factory
        self.base_config = base_config
        self.space = tuning_space or DEFAULT_TUNING_SPACE
        self.metric = metric
        self.warmup_steps = warmup_steps
        self.timed_steps = timed_steps
        self.model_params_count = int(model_params_count)
        self.hbm_bytes = int(hbm_bytes)
        self.dp_size = max(int(dp_size), 1)
        self.records: List[Dict[str, Any]] = []
        self._mm = None  # one shared memory model — calibrations persist

    def _apply(self, cfg: Dict[str, Any], dotted: str, value: Any) -> None:
        node = cfg
        parts = dotted.split(".")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = value

    def _candidates(self):
        keys = list(self.space.keys())
        for combo in itertools.product(*(self.space[k] for k in keys)):
            cfg = json.loads(json.dumps(self.base_config))
            for k, v in zip(keys, combo):
                self._apply(cfg, k, v)
            yield dict(zip(keys, combo)), cfg

    def _memory_model(self):
        """The plane's calibrated memory model at legacy semantics:
        margin 0, scale 1 until a trial calibrates it.  ONE instance per
        tuner — every trial's calibration sharpens later prune calls."""
        from ..tuning.memory_model import CalibratedMemoryModel

        if self._mm is None:
            self._mm = CalibratedMemoryModel(
                params_count=self.model_params_count,
                hbm_limit_bytes=self.hbm_bytes, dp_size=self.dp_size,
                base_config=self.base_config, margin_frac=0.0)
        return self._mm

    def _memory_prune(self, combo: Dict[str, Any]) -> bool:
        """True → skip without compiling (estimated state exceeds HBM)."""
        if not (self.model_params_count and self.hbm_bytes):
            return False
        return self._memory_model().prune_reason(combo) is not None

    def _runner(self, base_config: Optional[Dict[str, Any]] = None):
        from ..tuning.trial import EngineTrialRunner

        return EngineTrialRunner(
            self.engine_factory, self.batch_factory,
            base_config if base_config is not None else self.base_config,
            warmup_steps=self.warmup_steps,
            memory_model=self._memory_model()
            if self.model_params_count else None)

    def _measure(self, combo: Dict[str, Any]) -> Optional[float]:
        """One candidate's samples/sec through the tuning plane's trial
        runner: every timed step is DEVICE-FENCED (loss-scalar fetch),
        and engines exposing the ``trial_run`` hook are scored from
        their own StepRecords.  The COMBO (not a pre-merged config) is
        what runs, so ledger calibration sees the candidate's real ZeRO
        stage instead of the base config's."""
        result = self._runner().run(combo, timed_steps=self.timed_steps)
        if not result.feasible:
            logger.warning(
                f"autotuning candidate failed: {result.error}"
                + (" (OOM)" if result.oom else ""))
            return None
        rate = result.score(self.metric if self.metric in result.metrics
                            else "samples_per_sec")
        if rate is None:
            rate = result.score("tokens_per_sec")
        return rate

    def tune(self) -> Dict[str, Any]:
        """Grid search through the tuning plane (``tuning.SearchEngine``
        + ``GridStrategy``), mapped back to the reference result shape
        ``{"best_config", "best_combo", "throughput", "records"}``."""
        from ..tuning.search import GridStrategy, SearchEngine
        from ..tuning.space import CandidateSpace, Dimension

        space = CandidateSpace()
        for name, values in self.space.items():
            space.register(Dimension(name, list(values)))
        metric = (self.metric if self.metric != "throughput"
                  else "samples_per_sec")
        eng = SearchEngine(
            self._runner(), space,
            strategy=GridStrategy(timed_steps=self.timed_steps),
            metric=metric,
            memory_model=self._memory_model()
            if (self.model_params_count and self.hbm_bytes) else None)
        result = eng.search()
        for rec in result.records:
            combo = rec.get("candidate")
            if combo is None:
                continue
            if rec.get("pruned"):
                self.records.append({"combo": combo, "throughput": None,
                                     "pruned": rec["pruned"]})
            else:
                rate = (rec.get("metrics") or {}).get(
                    metric, (rec.get("metrics") or {}).get(
                        "samples_per_sec"))
                self.records.append({"combo": combo, "throughput": rate})
        if result.best is None:
            raise RuntimeError("no autotuning candidate succeeded")
        combo = result.best.candidate
        best_rate = result.best.score(metric) or 0.0
        cfg = json.loads(json.dumps(self.base_config))
        for k, v in combo.items():
            self._apply(cfg, k, v)
        log_dist(f"autotuning best: {combo} at {best_rate:.1f} samples/s")
        return {"best_config": cfg, "best_combo": combo,
                "throughput": best_rate, "records": self.records}

    def write_best(self, path: str) -> None:
        result = self.tune()
        with open(path, "w") as f:
            json.dump(result["best_config"], f, indent=2)


class ModelBasedTuner(Autotuner):
    """Reference ``ModelBasedTuner`` role (SURVEY §2.5, VERDICT r2 missing
    #7): instead of timing the full grid, measure a small SEED set, fit a
    performance model, and spend the remaining measurement budget only on
    the top-predicted candidates.

    The model is additive in log-throughput over the tuning dimensions
    (``log T ≈ base + Σ_dim effect[dim=value]``, one-hot least squares) —
    the same structure the reference fits over micro-batch/stage curves.
    Memory-model pruning applies before anything is measured."""

    def __init__(self, *args, seed_measurements: int = 3,
                 measure_budget: int = 6, **kwargs):
        super().__init__(*args, **kwargs)
        self.seed_measurements = max(2, int(seed_measurements))
        self.measure_budget = max(self.seed_measurements + 1,
                                  int(measure_budget))

    # -- the performance model --------------------------------------------

    @staticmethod
    def _design_row(combo: Dict[str, Any], levels: Dict[str, List[Any]]):
        import numpy as np

        row = [1.0]
        for k, vals in levels.items():
            onehot = [0.0] * len(vals)
            onehot[vals.index(combo[k])] = 1.0
            row.extend(onehot)
        return np.asarray(row)

    def _fit_predict(self, measured, candidates):
        """measured: [(combo, throughput)] → predicted throughput for every
        candidate combo (same additive-log model for all)."""
        import numpy as np

        levels = {k: list(self.space[k]) for k in self.space}
        X = np.stack([self._design_row(c, levels) for c, _ in measured])
        y = np.log([max(t, 1e-9) for _, t in measured])
        coef, *_ = np.linalg.lstsq(X, y, rcond=None)
        return [float(np.exp(self._design_row(c, levels) @ coef))
                for c in candidates]

    def _seed_combos(self, combos):
        """Greedy level cover: every (dimension, level) pair must appear in
        at least one seed, else that level's effect is unidentifiable and
        the model can never rank untried configs containing it."""
        uncovered = {(k, v) for k in self.space for v in self.space[k]}
        idx: List[int] = []
        while uncovered:
            best_i, best_gain = None, -1
            for i, (combo, _) in enumerate(combos):
                if i in idx:
                    continue
                gain = sum((k, combo[k]) in uncovered for k in combo)
                if gain > best_gain:
                    best_i, best_gain = i, gain
            if best_i is None or best_gain <= 0:
                break  # remaining levels were memory-pruned away entirely
            idx.append(best_i)
            uncovered -= {(k, combos[best_i][0][k])
                          for k in combos[best_i][0]}
        # top up to the requested seed count with evenly spaced extras
        step = max(1, len(combos) // max(self.seed_measurements, 1))
        for i in range(0, len(combos), step):
            if len(idx) >= self.seed_measurements:
                break
            if i not in idx:
                idx.append(i)
        return sorted(idx)

    def tune(self) -> Dict[str, Any]:
        all_cands = [(combo, cfg) for combo, cfg in self._candidates()
                     if not self._memory_prune(combo)]
        if not all_cands:
            raise RuntimeError("memory model pruned every candidate")
        measured: List = []

        def run(i: int) -> None:
            combo, cfg = all_cands[i]
            rate = self._measure(combo)
            self.records.append({"combo": combo, "throughput": rate})
            log_dist(f"autotuning(model) {combo} -> "
                     f"{'FAIL' if rate is None else f'{rate:.1f} samples/s'}")
            if rate is not None:
                measured.append((combo, rate, cfg))

        seen = set()
        for i in self._seed_combos(all_cands):
            seen.add(i)
            run(i)
        if not measured:
            raise RuntimeError("no autotuning seed candidate succeeded")

        remaining = [i for i in range(len(all_cands)) if i not in seen]
        if remaining:
            preds = self._fit_predict([(c, t) for c, t, _ in measured],
                                      [all_cands[i][0] for i in remaining])
            ranked = sorted(zip(preds, remaining), reverse=True)
            n_extra = max(0, self.measure_budget - len(seen))
            for _, i in ranked[:n_extra]:
                seen.add(i)
                run(i)
            for pred, i in ranked[n_extra:]:
                self.records.append({"combo": all_cands[i][0],
                                     "throughput": None,
                                     "pruned": "perf_model",
                                     "predicted": pred})

        combo, rate, cfg = max(measured, key=lambda m: m[1])
        log_dist(f"autotuning(model) best: {combo} at {rate:.1f} samples/s "
                 f"({len([r for r in self.records if 'pruned' not in r])} "
                 f"of {len(all_cands)} candidates measured)")
        return {"best_config": cfg, "best_combo": combo, "throughput": rate,
                "records": self.records}


def autotune(engine_factory, batch_factory, base_config,
             tuning_space=None, model_based: bool = False) -> Dict[str, Any]:
    cls = ModelBasedTuner if model_based else Autotuner
    return cls(engine_factory, batch_factory, base_config,
               tuning_space).tune()
