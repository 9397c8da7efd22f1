"""Kernel-or-reference selection shared by every Pallas entry point.

Each entry point takes ``interpret``: ``None`` leaves the choice to the
platform (compiled kernel on a TPU, the ``jax.numpy`` reference anywhere
else), ``False`` demands the compiled kernel, ``True`` runs the kernel in
the Pallas interpreter (what the CPU tests ask for).  A shape the kernel
cannot take still runs the reference, but on a TPU that is said once
where the decision is made, so a run on the chip cannot quietly measure
the reference.
"""

from __future__ import annotations

from typing import Any, Optional

import jax

from ...utils.logging import warn_once

#: scoped-VMEM ceiling handed to Mosaic by the kernels that keep whole
#: per-head planes resident (flash/block-sparse resident passes, the MoE
#: row gather's output window).  Mosaic's default is 16 MiB; a v5e core
#: has 128 MiB, and the resident flash backward at S·d = 1M elements
#: (bf16, 512 x 512 tiles) allocates 16.75 MiB (the compiler's own
#: figure, PR 32: compiled for a described v5e under a 4 MiB limit,
#: jax 0.9.0 / libtpu 0.0.34; ``lattice.backward_plan_bytes`` counts
#: 24.5 MiB there).  It is also the budget that rule picks tiles under.
RESIDENT_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def reference_off_tpu(interpret: Optional[bool]) -> bool:
    """The caller left the choice open and there is no TPU to compile for."""
    return interpret is None and jax.default_backend() != "tpu"


def shape_refused(kernel: str, shape: Any, reason: str) -> None:
    """Record that ``kernel`` runs its reference for ``shape``.  Silent off
    the TPU, where the reference is the expected path anyway."""
    if jax.default_backend() == "tpu":
        warn_once(
            f"pallas/{kernel}/{shape}",
            f"{kernel}: shape {shape} runs the jax.numpy reference on the "
            f"TPU, not the Pallas kernel — {reason}")


def record_route(op: str, impl: str) -> None:
    """Count, when a call site is traced, which implementation an entry
    point chose, under the op's own name: ``ops/<op>/kernel_calls``,
    ``ops/<op>/interpret_calls`` or ``ops/<op>/reference_calls`` on the
    telemetry hub (a no-op while the hub is off).  Every caller of the op
    counts here, a training step as a self-check; a reference call
    counted on a TPU is a fall-back that :func:`shape_refused` has
    explained.  The counters make a route visible without reading the
    compiled program; on the chip the program's device operations are the
    witness."""
    from ...telemetry import get_telemetry

    get_telemetry().inc_counter(
        f"ops/{op}/{impl}_calls",
        help=f"call sites traced at which {op} runs its {impl} "
             f"implementation (kernel: compiled Pallas; interpret: the "
             f"Pallas interpreter; reference: jax.numpy)")


def record_residuals(op: str, kept: bool) -> None:
    """Count, beside :func:`record_route` and as it does, which way an
    op's rule for its backward's residuals went at a traced call site:
    ``ops/<op>/residuals_kept`` (named for a remat policy to hold) or
    ``ops/<op>/residuals_recomputed`` (left to remat, which runs the
    forward again)."""
    from ...telemetry import get_telemetry

    way = "kept" if kept else "recomputed"
    get_telemetry().inc_counter(
        f"ops/{op}/residuals_{way}",
        help=f"call sites traced at which {op}'s outputs are {way} for "
             f"its backward under a layer's remat policy (the op's rule, "
             f"from the call's shapes)")


def record_head_loss(one_pass: bool) -> None:
    """Count, as :func:`record_residuals` does for an op's residuals, which
    form of the tiled output-head loss a traced call built
    (``runtime/sequence_parallel/ulysses_sp.py:sequence_tiled_loss``):
    ``ops/head_loss/one_pass`` (the gradient computed in the pass that
    computes the loss: three products over the vocabulary a step) or
    ``ops/head_loss/recomputed`` (each tile's logits computed again in the
    backward: four)."""
    from ...telemetry import get_telemetry

    form = "one_pass" if one_pass else "recomputed"
    get_telemetry().inc_counter(
        f"ops/head_loss/{form}",
        help=f"traced calls of the tiled head loss built in its {form} "
             f"form (chosen from the head's dtype)")


def resident_compiler_params(interpret: bool, dimension_semantics=None):
    """``compiler_params`` for a kernel holding resident planes (empty in
    the interpreter, which has no VMEM to limit and walks its grid in
    order).  ``dimension_semantics`` names the grid axes a kernel carries
    state across (``"arbitrary"``)."""
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=RESIDENT_VMEM_LIMIT_BYTES,
        dimension_semantics=dimension_semantics)}
