"""Sparse MoE token dispatch/combine — the expert-parallel data plane.

Role parity: DeepSpeed's MoE dispatch is an explicit ``_AllToAll`` around a
dense einsum (``deepspeed/moe/sharded_moe.py`` [K], GShard arXiv 2006.16668);
the dense one-hot formulation costs O(T·E·C·H) FLOPs and materialises a
``[T, E, C]`` mask whose useful content is k·T entries.  This module lowers
the gating decision to INDEX form and moves tokens with gathers instead:

* dispatch: ``src_idx [E, C]`` — which token fills slot c of expert e
  (``EMPTY_SLOT`` for unfilled slots).  ``expert_in[e, c] = tokens[src]``
  is a pure row gather, O(E·C·H) traffic and exactly the dense einsum's
  result bit-for-bit (each slot has at most one contributing token, so the
  dense reduction degenerates to a copy).
* combine: ``flat_idx [T, K]`` into the flattened ``[E·C, H]`` expert
  output (``E·C`` addresses a zero pad row for dropped assignments) plus
  renormalized ``gates [T, K]`` — ``y[t] = Σ_k gates[t,k]·out[flat_idx[t,k]]``,
  O(k·T·H) instead of O(T·E·C·H).

Three rungs share these index semantics:

* ``*_reference`` — jnp ``take``-based, fully differentiable (``take``'s
  transpose is the scatter-add), GSPMD-friendly: this is what runs under an
  expert-sharded mesh, where the gather IS the all-to-all boundary.
* ``pallas_dispatch`` / ``pallas_combine`` — one Pallas row-gather kernel
  riding ``PrefetchScalarGridSpec``: the index array is scalar-prefetched
  to SMEM and drives one DMA per row from the HBM-resident token /
  expert-output matrix into the output window (combine leaves its
  K-way weighted sum to XLA).  Forward-only, with a ``custom_vjp`` whose
  backward is the jnp reference (indices are routing decisions — integer,
  non-differentiable — so both paths share one backward).
* ``choose_dispatch_impl`` — the auto crossover: tiny T·E·C keeps the dense
  einsum (fusion beats bookkeeping), sharded meshes keep the jnp sparse
  path (``pallas_call`` does not self-partition under GSPMD), TPU +
  unsharded goes to the kernels.

Scratch accounting: the dispatch buffers ``[E, C, H]`` (+ pad rows) are
transient per-step bytes registered in the memory ledger under
``collective_scratch`` by the calling ``MOELayer``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .select import reference_off_tpu, shape_refused

#: src_idx value marking an unfilled expert slot
EMPTY_SLOT = -1

#: auto crossover: dense einsum below this T·E·C volume (the [T,E,C] mask
#: is small enough that XLA's fused einsum beats gather bookkeeping)
DENSE_CROSSOVER_TEC = 1 << 16

#: fleet-profiler calibration multiplier on the crossover (ISSUE 20):
#: a measured compute factor > 1 means the device runs the dense einsum
#: slower than modeled, so the sparse path wins earlier (scale < 1)
_CROSSOVER_SCALE = 1.0


def set_crossover_scale(scale: float) -> None:
    """Scale the measured-once dense/sparse crossover by a calibration
    factor (``tuning.space.apply_calibration`` drives this from the
    persisted fleet-profiler factors).  Clamped to [0.25, 4] — a wild
    capture must not flip every dispatch decision."""
    global _CROSSOVER_SCALE
    _CROSSOVER_SCALE = min(max(float(scale), 0.25), 4.0)


def dense_crossover_tec() -> int:
    """The calibrated T·E·C crossover the auto impl compares against."""
    return max(int(DENSE_CROSSOVER_TEC * _CROSSOVER_SCALE), 1)


# ---------------------------------------------------------------------------
# index construction (shared by every sparse rung)
# ---------------------------------------------------------------------------

def routing_to_indices(expert_idx: jnp.ndarray, slot: jnp.ndarray,
                       keep: jnp.ndarray, num_experts: int, capacity: int
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-choice routing ``(expert_idx [K,T], slot [K,T], keep [K,T])`` →
    ``(src_idx [E, C], flat_idx [T, K])``.

    ``src_idx[e, c]`` is the token id filling slot ``c`` of expert ``e``
    (``EMPTY_SLOT`` if none); ``flat_idx[t, k]`` indexes the flattened
    ``[E·C + 1, H]`` expert output, with ``E·C`` = the zero pad row for
    dropped assignments.  Kept ``(e, c)`` pairs are unique by construction
    (slot = cumulative position within the expert), so the scatter has no
    collisions.
    """
    E, C = num_experts, capacity
    K, T = expert_idx.shape
    tid = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (K, T))
    flat_ec = jnp.where(keep, expert_idx * C + slot, E * C).astype(jnp.int32)
    src = jnp.full((E * C + 1,), EMPTY_SLOT, jnp.int32)
    src = src.at[flat_ec.reshape(-1)].set(tid.reshape(-1), mode="drop")
    src_idx = src[: E * C].reshape(E, C)
    flat_idx = flat_ec.T  # [T, K]
    return jax.lax.stop_gradient(src_idx), jax.lax.stop_gradient(flat_idx)


# ---------------------------------------------------------------------------
# jnp reference rung (differentiable; runs under GSPMD meshes)
# ---------------------------------------------------------------------------

def dispatch_reference(tokens: jnp.ndarray, src_idx: jnp.ndarray
                       ) -> jnp.ndarray:
    """``tokens [T, H]`` gathered into ``[E, C, H]`` expert buffers; empty
    slots come out zero.

    Deliberately clamp-and-mask instead of gathering from a ``[T+1, H]``
    zero-padded copy: the pad row makes the gather operand's leading dim
    indivisible by the mesh axes, and XLA's SPMD partitioner mishandles
    the unevenly-padded gather (wrong rows on non-zero shards).  Clamped
    in-bounds indices keep the operand evenly shardable.
    """
    T, H = tokens.shape
    E, C = src_idx.shape
    idx = jnp.clip(src_idx, 0, T - 1)
    out = jnp.take(tokens, idx.reshape(-1), axis=0).reshape(E, C, H)
    return out * (src_idx >= 0)[..., None].astype(tokens.dtype)


def combine_reference(expert_out: jnp.ndarray, flat_idx: jnp.ndarray,
                      gates: jnp.ndarray) -> jnp.ndarray:
    """``expert_out [E, C, H]`` + ``flat_idx/gates [T, K]`` →
    ``y [T, H] = Σ_k gates[t,k] · expert_out.flat[flat_idx[t,k]]``.

    Same clamp-and-mask scheme as :func:`dispatch_reference` (dropped
    assignments address ``E·C``, which is masked out) so the gather
    operand stays evenly shardable under GSPMD.
    """
    E, C, H = expert_out.shape
    flat = expert_out.reshape(E * C, H)
    valid = flat_idx < E * C
    idx = jnp.clip(flat_idx, 0, E * C - 1)
    picked = jnp.take(flat, idx.reshape(-1), axis=0)  # [T*K, H]
    picked = picked.reshape(*flat_idx.shape, H)
    w = jnp.where(valid, gates, 0.0)[..., None].astype(expert_out.dtype)
    return jnp.sum(w * picked, axis=1)


# ---------------------------------------------------------------------------
# pallas kernel (forward) — one index-driven row gather serves both verbs
# ---------------------------------------------------------------------------

#: rows gathered per grid step (the output window is [_GATHER_ROWS, H])
_GATHER_ROWS = 128
_LANES = 128


def _gather_kernel(idx_ref, src_ref, out_ref, sem):
    """grid=(G, M/BM): fill one ``[1, BM, W/128, 128]`` output window with
    rows of the HBM-resident source, one DMA per row at the
    scalar-prefetched index; a negative index leaves a zero row.  Nothing
    but the window lives in VMEM, so T and E·C are unbounded.

    Two refusals of Mosaic (jax 0.9.0) shape the layout.  Rows are 32-bit
    words (:func:`_to_words`): a single-row dynamic slice of a packed
    dtype fails with "cannot statically prove that index in dimension 0
    is a multiple of 8".  And a row is a ``[W/128, 128]`` slab indexed on
    a LEADING dim: one row of a 2-D ``[N, W]`` array cuts the (8, 128)
    tiling ("Slice shape along dimension 0 must be aligned to tiling
    (8), but is 1")."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    g = pl.program_id(0)
    BM = out_ref.shape[1]
    base = pl.program_id(1) * BM

    def row_copy(r, idx):
        return pltpu.make_async_copy(src_ref.at[idx], out_ref.at[0, r], sem)

    def start(r, carry):
        idx = idx_ref[g, base + r]

        @pl.when(idx >= 0)
        def _():
            row_copy(r, idx).start()

        @pl.when(idx < 0)
        def _():
            out_ref[0, r] = jnp.zeros(out_ref.shape[2:], out_ref.dtype)

        return carry

    jax.lax.fori_loop(0, BM, start, 0)

    def wait(r, carry):
        idx = idx_ref[g, base + r]

        @pl.when(idx >= 0)
        def _():
            # every row copy moves the same bytes on the one semaphore,
            # so waiting once per started copy drains them all
            row_copy(r, idx).wait()

        return carry

    jax.lax.fori_loop(0, BM, wait, 0)


def _to_words(x: jnp.ndarray) -> jnp.ndarray:
    """``[N, H]`` of a 1/2/4-byte dtype → ``[N, H·itemsize/4]`` uint32."""
    pack = 4 // x.dtype.itemsize
    if pack == 1:
        return jax.lax.bitcast_convert_type(x, jnp.uint32)
    N, H = x.shape
    return jax.lax.bitcast_convert_type(x.reshape(N, H // pack, pack),
                                        jnp.uint32)


def _from_words(w: jnp.ndarray, dtype) -> jnp.ndarray:
    out = jax.lax.bitcast_convert_type(w, dtype)
    return out.reshape(*w.shape[:-1], -1) if out.ndim > w.ndim else out


def gather_refusal(H: int, dtype) -> Optional[str]:
    """Why the gather kernel cannot move rows of this width (None when it
    can): a row must be a whole number of 128-lane 32-bit vectors."""
    bits = H * jnp.dtype(dtype).itemsize * 8
    if jnp.dtype(dtype).itemsize > 4 or bits % (32 * _LANES):
        return (f"rows of {H} x {jnp.dtype(dtype).name} are not a multiple "
                f"of 128 32-bit lanes")
    return None


def _gather_rows(src: jnp.ndarray, idx: jnp.ndarray, interpret: bool
                 ) -> jnp.ndarray:
    """``src [N, H]``, ``idx [G, M]`` → ``[G, M, H]`` with
    ``out[g, m] = src[idx[g, m]]`` (zeros where ``idx < 0``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    G, M = idx.shape
    words = _to_words(src)
    N, W = words.shape
    # interpret-mode tests use rows narrower than a lane vector
    lanes = _LANES if W % _LANES == 0 else W
    slab = (W // lanes, lanes)
    BM = min(_GATHER_ROWS, M)
    Mp = -(-M // BM) * BM
    idx_p = jnp.pad(idx.astype(jnp.int32), ((0, 0), (0, Mp - M)),
                    constant_values=EMPTY_SLOT)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G, Mp // BM),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, BM) + slab,
                               lambda g, m, idx: (g, m, 0, 0)),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G, Mp) + slab, jnp.uint32),
        interpret=interpret,
    )(idx_p, words.reshape((N,) + slab))
    return _from_words(out[:, :M].reshape(G, M, W), src.dtype)


def _pallas_combine_fwd(expert_out: jnp.ndarray, flat_idx: jnp.ndarray,
                        gates: jnp.ndarray, interpret: bool) -> jnp.ndarray:
    """The gather kernel picks each token's K expert rows; the weighted
    sum over K is left to XLA, which fuses it into one pass."""
    E, C, H = expert_out.shape
    valid = flat_idx < E * C
    picked = _gather_rows(expert_out.reshape(E * C, H),
                          jnp.where(valid, flat_idx, EMPTY_SLOT).T,
                          interpret)                       # [K, T, H]
    w = jnp.where(valid, gates, 0.0).T[..., None].astype(expert_out.dtype)
    return jnp.sum(w * picked, axis=0)


# -- custom_vjp wrappers: pallas forward, jnp-reference backward -----------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _pallas_dispatch(tokens, src_idx, interpret):
    return _gather_rows(tokens, src_idx, interpret)


def _pallas_dispatch_vjp_fwd(tokens, src_idx, interpret):
    return _gather_rows(tokens, src_idx, interpret), \
        (tokens.shape, src_idx)


def _pallas_dispatch_vjp_bwd(interpret, res, g):
    (T, H), src_idx = res
    # transpose of the gather: scatter-add each slot's cotangent back to
    # its source token (empty slots route to the dropped pad row)
    idx = jnp.where(src_idx >= 0, src_idx, T).reshape(-1)
    d_tokens = jnp.zeros((T + 1, H), g.dtype)
    d_tokens = d_tokens.at[idx].add(g.reshape(-1, H))[:T]
    return d_tokens, None


_pallas_dispatch.defvjp(_pallas_dispatch_vjp_fwd, _pallas_dispatch_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _pallas_combine(expert_out, flat_idx, gates, interpret):
    return _pallas_combine_fwd(expert_out, flat_idx, gates, interpret)


def _pallas_combine_vjp_fwd(expert_out, flat_idx, gates, interpret):
    y = _pallas_combine_fwd(expert_out, flat_idx, gates, interpret)
    return y, (expert_out, flat_idx, gates)


def _pallas_combine_vjp_bwd(interpret, res, g):
    expert_out, flat_idx, gates, = res
    E, C, H = expert_out.shape
    T, K = flat_idx.shape
    flat = jnp.concatenate(
        [expert_out.reshape(E * C, H),
         jnp.zeros((1, H), expert_out.dtype)], axis=0)
    picked = jnp.take(flat, flat_idx.reshape(-1), axis=0).reshape(T, K, H)
    d_gates = jnp.einsum("th,tkh->tk", g.astype(jnp.float32),
                         picked.astype(jnp.float32)).astype(gates.dtype)
    weighted = gates[..., None].astype(g.dtype) * g[:, None, :]  # [T,K,H]
    d_flat = jnp.zeros((E * C + 1, H), g.dtype)
    d_flat = d_flat.at[flat_idx.reshape(-1)].add(weighted.reshape(-1, H))
    d_eo = d_flat[: E * C].reshape(E, C, H)
    return d_eo, None, d_gates


_pallas_combine.defvjp(_pallas_combine_vjp_fwd, _pallas_combine_vjp_bwd)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def _runs_reference(kernel: str, rows: jnp.ndarray,
                    interpret: Optional[bool]) -> bool:
    """:mod:`.select`'s choice for a gather over ``rows [..., H]``: the
    reference off the TPU, and (said once) for a row width the compiled
    kernel refuses — the interpreter takes any width."""
    if reference_off_tpu(interpret):
        return True
    refusal = (None if interpret
               else gather_refusal(rows.shape[-1], rows.dtype))
    if refusal is not None:
        shape_refused(kernel, tuple(rows.shape), refusal)
    return refusal is not None


def pallas_dispatch(tokens: jnp.ndarray, src_idx: jnp.ndarray,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Pallas token dispatch: ``tokens [T, H]`` + ``src_idx [E, C]`` →
    ``[E, C, H]``.  ``interpret`` follows :mod:`.select`; a row width the
    gather kernel refuses runs :func:`dispatch_reference`."""
    if _runs_reference("moe_dispatch", tokens, interpret):
        return dispatch_reference(tokens, src_idx)
    return _pallas_dispatch(tokens, src_idx, bool(interpret))


def pallas_combine(expert_out: jnp.ndarray, flat_idx: jnp.ndarray,
                   gates: jnp.ndarray,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """Pallas token combine: ``expert_out [E, C, H]`` + ``flat_idx/gates
    [T, K]`` → ``y [T, H]``.  Selection mirrors :func:`pallas_dispatch`."""
    if _runs_reference("moe_combine", expert_out, interpret):
        return combine_reference(expert_out, flat_idx, gates)
    return _pallas_combine(expert_out, flat_idx, gates, bool(interpret))


def dispatch_scratch_bytes(num_experts: int, capacity: int, hidden: int,
                           dtype=jnp.float32, k: int = 2) -> int:
    """Analytic transient bytes of the sparse dispatch plane (expert in/out
    buffers + pad rows + index arrays) for the memory ledger's
    ``collective_scratch`` pool."""
    itemsize = jnp.dtype(dtype).itemsize
    buffers = 2 * num_experts * capacity * hidden * itemsize  # in + out
    pad = 2 * hidden * itemsize
    indices = (num_experts * capacity + 1) * 4 + 2 * k * 4
    return int(buffers + pad + indices)


def choose_dispatch_impl(impl: str, num_tokens: int, num_experts: int,
                         capacity: int, sharded: bool = False) -> str:
    """Resolve a requested dispatch impl (``auto``/``dense``/``sparse``/
    ``pallas``) to a concrete one.

    ``auto``: small T·E·C keeps the fused dense einsum; expert-sharded
    meshes take the jnp sparse path (``pallas_call`` does not partition
    itself under GSPMD — the gather is the all-to-all boundary and belongs
    to the compiler); unsharded TPU gets the kernels.
    """
    if impl not in ("auto", "dense", "sparse", "pallas"):
        raise ValueError(
            f"unknown moe dispatch impl {impl!r} "
            "(expected auto|dense|sparse|pallas)")
    if impl != "auto":
        if impl == "pallas" and sharded:
            return "sparse"
        return impl
    if num_tokens * num_experts * capacity <= dense_crossover_tec():
        return "dense"
    if sharded or jax.default_backend() != "tpu":
        return "sparse"
    return "pallas"
