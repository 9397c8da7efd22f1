"""Activation checkpointing — the reference API over ``jax.checkpoint``.

Reference: ``deepspeed/runtime/activation_checkpointing/checkpointing.py``
[K] — drop-in ``checkpoint(function, *args)`` with extras: partitioned
activations across TP ranks, CPU checkpointing, contiguous memory, RNG-state
tracking (SURVEY §2.1).

TPU-first mapping: ``jax.checkpoint`` (remat) subsumes the hook machinery;
the extras become remat POLICIES —
* ``partition_activations`` → saveables carry their sharding, so saved
  residuals are already partitioned (GSPMD; nothing to do)
* ``cpu_checkpointing`` → ``jax.checkpoint`` with ``offload`` policy
  (``save_and_offload_only_these_names`` / host memory kind)
* RNG tracking → functional PRNG keys thread explicitly; nothing to track.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax



@dataclasses.dataclass
class CheckpointConfig:
    partition_activations: bool = False
    cpu_checkpointing: bool = False
    contiguous_memory_optimization: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False


_CONFIG = CheckpointConfig()


def configure(mpu_: Any = None, deepspeed_config: Any = None,
              partition_activations: Optional[bool] = None,
              contiguous_checkpointing: Optional[bool] = None,
              num_checkpoints: Optional[int] = None,
              checkpoint_in_cpu: Optional[bool] = None,
              synchronize: Optional[bool] = None,
              profile: Optional[bool] = None) -> None:
    """Reference ``configure`` signature; updates the module-level policy."""
    global _CONFIG
    if deepspeed_config is not None:
        ac = getattr(deepspeed_config, "activation_checkpointing", None)
        if ac is not None:
            _CONFIG = CheckpointConfig(
                partition_activations=ac.partition_activations,
                cpu_checkpointing=ac.cpu_checkpointing,
                contiguous_memory_optimization=ac.contiguous_memory_optimization,
                number_checkpoints=ac.number_checkpoints,
                synchronize_checkpoint_boundary=ac.synchronize_checkpoint_boundary,
                profile=ac.profile)
    for key, val in dict(partition_activations=partition_activations,
                         contiguous_memory_optimization=contiguous_checkpointing,
                         number_checkpoints=num_checkpoints,
                         cpu_checkpointing=checkpoint_in_cpu,
                         synchronize_checkpoint_boundary=synchronize,
                         profile=profile).items():
        if val is not None:
            setattr(_CONFIG, key, val)


def remat_policy():
    """What a rematerialized layer body holds for its backward, written
    here once for every model's layer scan and for :func:`checkpoint`: the
    outputs of its matrix products (``dots_with_no_batch_dims_saveable``)
    AND the values an op has named as dearer to recompute than those: the
    flash attention call's ``out`` and ``lse``, where the op's own rule
    (``flash_attention.keeps_residuals``, from the call's shapes) names
    them.  An attention call is a Mosaic custom call, not a ``dot_general``,
    so under the dots policy alone remat runs its forward kernel a second
    time inside the backward."""
    from ...ops.pallas.flash_attention import RESIDUAL_NAMES

    cp = jax.checkpoint_policies
    return cp.save_from_both_policies(
        cp.dots_with_no_batch_dims_saveable,
        cp.save_only_these_names(*RESIDUAL_NAMES))


def _policy():
    if _CONFIG.cpu_checkpointing:
        return jax.checkpoint_policies.save_and_offload_only_these_names(
            names_which_can_be_saved=[], names_which_can_be_offloaded=[],
            offload_src="device", offload_dst="pinned_host")
    return remat_policy()


def checkpoint(function: Callable, *args: Any) -> Any:
    """Reference drop-in: checkpoint ``function(*args)`` under the configured
    policy and run it immediately."""
    return jax.checkpoint(function, policy=_policy())(*args)


def checkpoint_wrapped(function: Callable) -> Callable:
    """Return the remat-wrapped function (for scan bodies etc.)."""
    return jax.checkpoint(function, policy=_policy())
