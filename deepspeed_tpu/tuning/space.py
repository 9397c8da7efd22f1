"""Candidate space — the pluggable dimension registry.

A *dimension* is one tunable knob: a name, the values to try, and where
the knob lives — most are dotted DS-config keys (applied into the config
dict the engine factory receives), some are *model* knobs (``model.*``
prefixed: remat policy, attention impl — applied by the caller that owns
model construction, since the engine never rebuilds the user's model),
and donation/mesh knobs ride the same dotted convention under their
subsystem groups.

A *candidate* is a plain ``{dimension_name: value}`` dict; its store
form is the same dict (dotted keys ARE the override format the
best-known-config store persists and ``initialize()`` re-applies).
"""

from __future__ import annotations

import itertools
import json
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence

#: overrides under this prefix target the MODEL config (remat policy,
#: attention impl), not the DS config — ``initialize()`` cannot apply
#: them (it never rebuilds the caller's model); bench/search harnesses
#: that own model construction do.
MODEL_KEY_PREFIX = "model."


@dataclass
class Dimension:
    """One tunable knob.

    ``name`` is the dotted override key (``train_micro_batch_size_per_gpu``,
    ``zero_optimization.stage``, ``model.remat``).  ``values`` is the
    candidate list in search order.  ``feasible`` (optional) rejects a
    value given the partial candidate built so far — cheap structural
    constraints (gas must divide batch) belong here, memory constraints
    belong to the calibrated memory model."""

    name: str
    values: Sequence[Any]
    description: str = ""
    feasible: Optional[Callable[[Any, Dict[str, Any]], bool]] = None

    def __post_init__(self) -> None:
        if not self.values:
            raise ValueError(f"dimension {self.name!r}: empty value list")


@dataclass
class CandidateSpace:
    """Ordered registry of dimensions with candidate enumeration."""

    dimensions: List[Dimension] = field(default_factory=list)

    def register(self, dim: Dimension) -> "CandidateSpace":
        if any(d.name == dim.name for d in self.dimensions):
            raise ValueError(f"dimension {dim.name!r} already registered")
        self.dimensions.append(dim)
        return self

    def remove(self, name: str) -> "CandidateSpace":
        self.dimensions = [d for d in self.dimensions if d.name != name]
        return self

    def names(self) -> List[str]:
        return [d.name for d in self.dimensions]

    def __len__(self) -> int:
        n = 1
        for d in self.dimensions:
            n *= len(d.values)
        return n

    def candidates(self) -> Iterator[Dict[str, Any]]:
        """Enumerate the full cross product, dropping combos any
        dimension's ``feasible`` hook rejects."""
        names = [d.name for d in self.dimensions]
        for combo in itertools.product(*(d.values for d in self.dimensions)):
            cand = dict(zip(names, combo))
            ok = True
            for d in self.dimensions:
                if d.feasible is not None and not d.feasible(cand[d.name],
                                                             cand):
                    ok = False
                    break
            if ok:
                yield cand


def split_overrides(candidate: Dict[str, Any]
                    ) -> tuple[Dict[str, Any], Dict[str, Any]]:
    """Split a candidate into (ds-config overrides, model overrides) —
    the latter with the ``model.`` prefix stripped."""
    config = {k: v for k, v in candidate.items()
              if not k.startswith(MODEL_KEY_PREFIX)}
    model = {k[len(MODEL_KEY_PREFIX):]: v for k, v in candidate.items()
             if k.startswith(MODEL_KEY_PREFIX)}
    return config, model


def apply_overrides(base_config: Dict[str, Any],
                    overrides: Dict[str, Any]) -> Dict[str, Any]:
    """Deep-copy ``base_config`` and set each dotted key (the same
    traversal contract as ``DS_AUTOTUNING_CONFIG_OVERRIDE``); ``model.*``
    keys are rejected — route them through :func:`split_overrides`."""
    cfg = json.loads(json.dumps(base_config))
    for dotted, value in overrides.items():
        if dotted.startswith(MODEL_KEY_PREFIX):
            raise ValueError(
                f"override {dotted!r} targets the model config — apply it "
                f"where the model is constructed (split_overrides)")
        node = cfg
        parts = dotted.split(".")
        for p in parts[:-1]:
            cur = node.get(p)
            if cur is not None and not isinstance(cur, dict):
                raise ValueError(
                    f"override key {dotted!r}: config node {p!r} holds the "
                    f"non-object value {cur!r} — cannot set a nested key "
                    f"under it")
            node = node.setdefault(p, {})
        node[parts[-1]] = value
    return cfg


def apply_calibration(store: Any = None,
                      device_kind: Optional[str] = None) -> float:
    """Ground the measured-once Pallas crossover constants in fleet
    profiler measurement (ISSUE 20).

    ROADMAP carries the debt explicitly: every PR-12 crossover threshold
    is a constant measured once on one host.  Once a ``telemetry
    profile`` capture has persisted a per-device-kind ``compute`` factor
    (measured/modeled ratio), the MoE dense/sparse dispatch crossover
    scales by ``1/factor`` — a device measured 2x slower than modeled on
    compute flips to the sparse path at half the T·E·C volume.  Returns
    the scale applied (1.0 when no calibration exists)."""
    from ..telemetry.profiler.calibration import get_calibration_store

    store = store or get_calibration_store()
    if device_kind is None:
        try:
            import jax

            d = jax.devices()[0]
            device_kind = (getattr(d, "device_kind", "")
                           or getattr(d, "platform", "") or "unknown")
        except Exception:
            device_kind = "unknown"
    try:
        factor = float(store.factor(device_kind, "compute"))
    except Exception:
        factor = 1.0
    scale = 1.0 / factor if factor > 0 else 1.0
    scale = min(max(scale, 0.25), 4.0)
    from ..ops.pallas.moe_dispatch import set_crossover_scale

    set_crossover_scale(scale)
    return scale


def default_space(max_micro_batch: int = 16,
                  include_offload: bool = False,
                  include_zero_stage: bool = True,
                  mesh_layouts: Optional[Sequence[str]] = None,
                  include_kernels: bool = True,
                  include_moe: bool = False,
                  moe_ep_degrees: Sequence[int] = (1, 2, 4),
                  ) -> CandidateSpace:
    """The stock search space: micro-batch × grad-accumulation × remat ×
    donation (× ZeRO stage, × offload, × mesh layout when asked) × the
    Pallas kernel plane (attention impl × flash block sizes × fused
    optimizer × collective overlap — every kernel is a searchable
    dimension, so the store picks winners per (model, mesh,
    device_kind) instead of a global default guessing).

    ``mesh_layouts`` entries are opaque layout names the trial harness
    interprets (an engine rebuild on a different mesh); omitted on
    single-chip searches where there is only one layout."""
    micro = [b for b in (1, 2, 4, 8, 16, 32) if b <= max_micro_batch]
    space = CandidateSpace()
    space.register(Dimension(
        "train_micro_batch_size_per_gpu", micro,
        description="per-chip micro batch (activation footprint vs MXU "
                    "utilization)"))
    space.register(Dimension(
        "gradient_accumulation_steps", [1, 2, 4],
        description="microbatch scan length at fixed global batch"))
    space.register(Dimension(
        "model.remat", [True, False],
        description="activation rematerialization (jax.checkpoint) — "
                    "recompute vs stash"))
    space.register(Dimension(
        "tuning.donate_state", [True],
        description="donate TrainState buffers into the step program "
                    "(off only for debugging aliasing)"))
    if include_zero_stage:
        space.register(Dimension(
            "zero_optimization.stage", [0, 1, 2, 3],
            description="ZeRO partitioning stage (reference tuning_space "
                        "dimension)"))
    if include_offload:
        space.register(Dimension(
            "zero_optimization.offload_optimizer.device", ["none", "cpu"],
            description="host-offloaded optimizer states (reference "
                        "offload dimension)"))
    if mesh_layouts:
        space.register(Dimension(
            "tuning.mesh_layout", list(mesh_layouts),
            description="mesh/sharding layout name the trial harness "
                        "realizes (dp/tp/sp split)"))
    if include_kernels:
        flash_on = lambda v, cand: (
            v == 0 or cand.get("model.attn_impl") == "flash")
        space.register(Dimension(
            "model.attn_impl", ["xla", "flash"],
            description="attention kernel: XLA einsum+softmax vs the "
                        "Pallas flash family (ops/pallas/"
                        "flash_attention.py dispatch ladder) for the "
                        "Llama family; selects nothing for BERT, whose "
                        "attention always goes through the flash op"))
        space.register(Dimension(
            "model.flash_block_q", [0, 256, 512],
            description="flash q-block (0 = seq-length auto table)",
            feasible=flash_on))
        space.register(Dimension(
            "model.flash_block_k", [0, 256, 512],
            description="flash k-block (0 = seq-length auto table)",
            feasible=flash_on))
        space.register(Dimension(
            "kernels.fused_adam", [False, True],
            description="one-pass fused Pallas Adam over ZeRO shards vs "
                        "the optax chain (ops/pallas/fused_optimizer.py)"))
        space.register(Dimension(
            "kernels.overlap_collectives", [False, True],
            description="ZeRO-3 chunked-ring collective overlap "
                        "(comm/overlap.py) vs monolithic GSPMD "
                        "collectives",
            feasible=lambda v, cand: (not v) or cand.get(
                "zero_optimization.stage", 3) >= 3))
        space.register(Dimension(
            "kernels.overlap_chunks", [2, 4, 8],
            description="ring payloads per shard (finer pipelining vs "
                        "per-hop latency)",
            feasible=lambda v, cand: cand.get(
                "kernels.overlap_collectives", False) or v == 4))
    if include_moe:
        # the expert-parallel plane (ISSUE 19): ep degree × capacity
        # slack × dispatch rung.  ep rides the DS config (engine rebuilds
        # the mesh); capacity factor and dispatch impl are model knobs
        # (the MoE block is built with the model).
        space.register(Dimension(
            "moe.expert_parallel_size", list(moe_ep_degrees),
            description="expert mesh axis degree (experts sharded "
                        "ep-ways; ZeRO composes over (expert, data))"))
        space.register(Dimension(
            "model.capacity_factor", [1.0, 1.25, 2.0],
            description="expert capacity slack: FLOPs/memory per step vs "
                        "token drop rate under routing skew"))
        space.register(Dimension(
            "model.moe_dispatch_impl", ["auto", "dense", "sparse"],
            description="token dispatch rung: fused dense einsum vs "
                        "index-form gathers (ops/pallas/moe_dispatch.py; "
                        "'pallas' is picked by auto on unsharded TPU)"))
    return space
