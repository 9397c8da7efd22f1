"""Ulysses/ALST sequence parallelism, TPU-native.

Reference behavior (``runtime/sequence_parallel/ulysses_sp.py``
[L ACC:2398-2437], arXiv 2309.14509 / 2506.13996 [P]): activations ride
sequence-sharded everywhere EXCEPT attention; at the attention boundary an
all-to-all converts seq-sharding → head-sharding (full sequence, h/sp heads
per rank), attention runs locally, and a second all-to-all converts back.
Plus: a dataloader adapter handing each SP rank its sequence slice, and
tiled compute (MLP / logits+loss chunked over the sequence) so activation
memory is O(tile), not O(N).

TPU-first: the all-to-alls are ``jax.lax.all_to_all`` over the ``seq`` mesh
axis inside ``shard_map`` — an ICI-native collective XLA schedules directly.
This replaces both the reference's torch-dist all-to-all AND the
GSPMD-constraint formulation (which trips XLA's "involuntary full
rematerialization" on the seq↔head reshard); tiled compute is
``lax.scan`` + ``jax.checkpoint`` over sequence chunks, and the tiled loss
a scan with a gradient rule of its own (:func:`sequence_tiled_loss`).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ...comm.comm import all_to_all_in_graph
from ...ops.pallas.select import record_head_loss
from ...parallel.mesh import AXIS_SEQ, AXIS_TENSOR, DP_AXES
from ...utils import groups as groups_mod
from ...utils.jax_compat import shard_map as _shard_map

P = PartitionSpec


def ulysses_attention(attn_fn: Callable[[jnp.ndarray, jnp.ndarray, jnp.ndarray],
                                        jnp.ndarray],
                      q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      mesh: Optional[Mesh] = None) -> jnp.ndarray:
    """All-to-all seq↔heads around ``attn_fn`` (the Ulysses core).

    ``q,k,v``: global ``[B, S, h, d]`` arrays, sequence-sharded over the
    ``seq`` axis (and heads over ``tensor`` if TP is active).  ``attn_fn``
    receives per-device blocks with the FULL sequence and ``h/(sp·tp)`` heads
    and must be position-exact (RoPE etc. happen inside it on global
    positions).  Falls back to a direct call when the seq axis is 1.
    """
    mesh = mesh if mesh is not None else groups_mod.get_mesh()
    sp = int(mesh.shape.get(AXIS_SEQ, 1))
    if sp == 1:
        # still shard heads over tensor via ordinary GSPMD; no seq comm needed
        return attn_fn(q, k, v)

    # Manualize ONLY the seq axis: batch/head sharding stays with GSPMD, and
    # the partial-manual form composes under an enclosing pipeline shard_map
    # (whose context mesh must be reused — a concrete Mesh would mismatch).
    ctx = jax.sharding.get_abstract_mesh()
    sm_mesh = mesh if ctx.empty else ctx
    spec = P(None, AXIS_SEQ, None, None)

    def inner(ql, kl, vl):
        # local [B, S/sp, h, d] → [B, S, h/sp, d]
        ql = all_to_all_in_graph(ql, AXIS_SEQ, split_axis=2,
                                 concat_axis=1, tiled=True)
        kl = all_to_all_in_graph(kl, AXIS_SEQ, split_axis=2,
                                 concat_axis=1, tiled=True)
        vl = all_to_all_in_graph(vl, AXIS_SEQ, split_axis=2,
                                 concat_axis=1, tiled=True)
        ol = attn_fn(ql, kl, vl)
        # back: [B, S, h/sp, d] → [B, S/sp, h, d]
        return all_to_all_in_graph(ol, AXIS_SEQ, split_axis=1,
                                   concat_axis=2, tiled=True)

    return _shard_map(inner, mesh=sm_mesh,
                         in_specs=(spec, spec, spec),
                         out_specs=spec, axis_names={AXIS_SEQ},
                         check_vma=False)(q, k, v)


# ----------------------------------------------------------------------
# tiled compute (ALST memory reducers)
# ----------------------------------------------------------------------

class SequenceTiledCompute:
    """Chunk a seq-wise function through ``lax.scan`` + remat.

    Reference: ``SequenceTiledCompute`` autograd fn [L ACC signature];
    activation memory becomes O(S/tiles) — the ALST enabler for multi-M-token
    sequences.
    """

    @staticmethod
    def apply(fn: Callable[[jnp.ndarray], jnp.ndarray], x: jnp.ndarray,
              tiles: int, seq_axis: int = 1) -> jnp.ndarray:
        if tiles <= 1:
            return fn(x)
        S = x.shape[seq_axis]
        if S % tiles:
            raise ValueError(f"seq len {S} not divisible by tiles={tiles}")
        xs = jnp.moveaxis(
            x.reshape(x.shape[:seq_axis] + (tiles, S // tiles)
                      + x.shape[seq_axis + 1:]), seq_axis, 0)

        def body(_, xt):
            return None, jax.checkpoint(fn)(xt)

        _, ys = jax.lax.scan(body, None, xs)
        ys = jnp.moveaxis(ys, 0, seq_axis)
        return ys.reshape(x.shape[:seq_axis] + (S,) + ys.shape[seq_axis + 2:])


class TiledMLP:
    """Seq-tiled pointwise MLP application (reference ``TiledMLP`` [L]).

    Valid for any token-wise fn (an MLP block, a norm+MLP residual…)."""

    @staticmethod
    def apply(mlp_fn: Callable[[jnp.ndarray], jnp.ndarray], x: jnp.ndarray,
              tiles: int) -> jnp.ndarray:
        return SequenceTiledCompute.apply(mlp_fn, x, tiles, seq_axis=1)


def _seq_tiles(x: jnp.ndarray, tiles: int) -> jnp.ndarray:
    """``[B, S, ...] → [tiles, B, S / tiles, ...]``, a scan's ``xs``."""
    B, S = x.shape[:2]
    return jnp.moveaxis(x.reshape((B, tiles, S // tiles) + x.shape[2:]), 1, 0)


def _tile_nll(h: jnp.ndarray, head: jnp.ndarray, lab: jnp.ndarray):
    """One tile's mathematics, written once for every form of the tiled
    loss: the product with the head, widened to float32, and its
    log-softmax.  Returns ``(Σ nll over the valid labels, logp, valid,
    safe labels)``; a caller that wants the sum alone leaves the rest to
    dead-code elimination."""
    logits = jnp.einsum("bsH,HV->bsV", h, head).astype(jnp.float32)
    valid = lab != -100
    safe = jnp.where(valid, lab, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.where(valid, nll, 0.0)), logp, valid, safe


def _mean_over_tiles(tile_sum, hidden, labels, count, tiles):
    """``Σ_tiles tile_sum(h, lab) / max(count, 1)``: the scan that holds
    one tile's logits at a time and nothing of a gradient."""
    total, _ = jax.lax.scan(
        lambda acc, xs: (acc + tile_sum(*xs), None), jnp.float32(0.0),
        (_seq_tiles(hidden, tiles), _seq_tiles(labels, tiles)))
    return total / jnp.maximum(count, 1)


def _recomputing_loss(hidden, head, labels, count, tiles):
    """The form left to autodiff: each tile under ``jax.checkpoint``, so
    the backward scan multiplies the tile by the head a second time to get
    its logits back (four products over the vocabulary a step)."""
    return _mean_over_tiles(
        jax.checkpoint(lambda h, lab: _tile_nll(h, head, lab)[0]),
        hidden, labels, count, tiles)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _one_pass_loss(hidden, head, labels, count, tiles):
    """The form with a gradient rule of its own.  Called where nothing is
    differentiated it is the plain tile scan."""
    return _mean_over_tiles(lambda h, lab: _tile_nll(h, head, lab)[0],
                            hidden, labels, count, tiles)


def _one_pass_fwd(hidden, head, labels, count, tiles):
    """The loss AND its gradient in one scan over the tiles: a tile's
    ``dlogits = (softmax − onehot) · valid / max(count, 1)`` is formed in
    float32 from the logits the loss was just computed from, rounded to
    the compute dtype where autodiff's cotangent is (the transpose of the
    widening), and multiplied out at once: ``dh`` into the tile's place of
    a ``[B, S, H]`` residual, ``dW`` summed over the tiles in float32 and
    rounded to the head's dtype once, after the last.  The backward then
    holds no product."""
    H, V = head.shape
    dtype = jnp.result_type(hidden.dtype, head.dtype)
    scale = 1.0 / jnp.maximum(count, 1).astype(jnp.float32)

    def body(carry, xs):
        total, dW = carry
        h, lab = xs
        nll, logp, valid, safe = _tile_nll(h, head, lab)
        onehot = safe[..., None] == jnp.arange(V, dtype=safe.dtype)
        dlogits = ((jnp.exp(logp) - onehot)
                   * (valid * scale)[..., None]).astype(dtype)
        dh = jnp.einsum("bsV,HV->bsH", dlogits, head)
        dW = dW + jnp.einsum("bsH,bsV->HV", h, dlogits,
                             preferred_element_type=jnp.float32)
        return (total + nll, dW), dh.astype(hidden.dtype)

    (total, dW), dh = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.zeros((H, V), jnp.float32)),
        (_seq_tiles(hidden, tiles), _seq_tiles(labels, tiles)))
    dh = jnp.moveaxis(dh, 0, 1).reshape(hidden.shape)
    return total / jnp.maximum(count, 1), (dh, dW.astype(head.dtype))


def _one_pass_bwd(tiles, residuals, g):
    """``g`` times what the forward left; labels and count get nothing."""
    return tuple((g * r).astype(r.dtype) for r in residuals) + (None, None)


_one_pass_loss.defvjp(_one_pass_fwd, _one_pass_bwd)


def sequence_tiled_loss(hidden: jnp.ndarray, head: jnp.ndarray,
                        labels: jnp.ndarray, tiles: int,
                        groups: int = 1) -> jnp.ndarray:
    """Tiled final-projection + cross-entropy (never materializes the full
    ``[B, S, V]`` logits — the dominant activation at large vocab).

    ``hidden [B, S, H]`` times ``head [H, V]`` (both in the compute dtype),
    ``tiles`` sequence tiles at a time (one tile where ``S % tiles``);
    labels use the HF ``-100`` ignore convention.  Returns the MEAN
    negative log-likelihood over the valid labels, a float32 scalar (0
    where no label is valid).

    Differentiated, the loss computes its gradient in the pass that
    computes the loss (:func:`_one_pass_fwd`: three products over the
    vocabulary a step, the backward none), where a tile left to
    ``jax.checkpoint`` recomputes its logits in the backward (four).  The
    form follows the head's dtype, which is all that decides it: bfloat16
    and float32 take the one-pass form; a float16 head (or any other) keeps
    the recomputing one, because its loss scale has to reach ``dlogits`` before
    they are rounded (the scaled cotangent keeps small gradients out of
    float16's subnormals) and the one-pass form applies the incoming
    cotangent after the rounding.  Which form a traced call built is
    counted: ``ops/head_loss/one_pass`` / ``ops/head_loss/recomputed``.

    ``groups`` (the caller's count of data-parallel replicas; ignored where
    it does not divide ``B``) keeps the head's gradient as one partial sum
    a group of batch rows until the scan is over.  A contraction over a
    batch that GSPMD has sharded, inside the scan, is a reduce-scatter of
    the whole ``[H, V]`` every tile; the groups' axis is sharded as the
    batch is, so each replica sums its own tiles and the replicas are
    added once a step.

    Keep ``jax.checkpoint`` off this function's callers: a remat around the
    loss tail would run the whole one-pass forward, gradient products and
    all, a second time.
    """
    B, S, H = hidden.shape
    if tiles <= 1 or S % tiles:
        tiles = 1
    one_pass = head.dtype in (jnp.bfloat16, jnp.float32)
    record_head_loss(one_pass)
    count = jnp.sum((labels != -100).astype(jnp.int32))
    form = _one_pass_loss if one_pass else _recomputing_loss
    if groups <= 1 or B % groups:
        return form(hidden, head, labels, count, tiles)
    # each group's share of the mean; vmap sums the head's cotangent (it is
    # not mapped) over the groups' axis, after the scan
    shares = jax.vmap(lambda h, lab: form(h, head, lab, count, tiles))(
        hidden.reshape(groups, B // groups, S, H),
        labels.reshape(groups, B // groups, S))
    return jnp.sum(shares)


# ----------------------------------------------------------------------
# dataloader adapter + registration API (reference signatures)
# ----------------------------------------------------------------------

class UlyssesSPDataLoaderAdapter:
    """Hand each SP rank its sequence slice of every batch
    [L ACC:2431-2437 signature parity].

    In the single-controller GSPMD world the engine consumes GLOBAL batches,
    so slicing is only needed in multi-process (one process per host) runs:
    each process slices for its own sp_rank and the global array is assembled
    with ``jax.make_array_from_process_local_data`` by the dataloader.
    """

    def __init__(self, dl: Any, sp_rank: Optional[int] = None,
                 sp_group: Any = None, sp_world_size: Optional[int] = None,
                 device: Any = None):
        self.dl = dl
        grp = sp_group if sp_group is not None else (
            groups_mod.get_sequence_parallel_group())
        self.sp_world_size = (int(sp_world_size) if sp_world_size is not None
                              else grp.size)
        self.sp_rank = (int(sp_rank) if sp_rank is not None
                        else grp.rank_of_process())
        self.device = device

    def _slice(self, x):
        if not hasattr(x, "ndim") or x.ndim < 2:
            return x
        S = x.shape[1]
        if S % self.sp_world_size:
            raise ValueError(
                f"sequence length {S} not divisible by sp={self.sp_world_size}")
        chunk = S // self.sp_world_size
        return x[:, self.sp_rank * chunk:(self.sp_rank + 1) * chunk]

    def __iter__(self) -> Iterator[Any]:
        for batch in self.dl:
            yield jax.tree.map(self._slice, batch)

    def __len__(self) -> int:
        return len(self.dl)


class UlyssesSPAttentionHF:
    """Registration façade with the reference's classmethod signature
    [L ACC:2409-2430].

    The reference monkey-patches HF *torch* attention; TPU-native models get
    Ulysses via :func:`ulysses_attention` / mesh constraints instead, so this
    classmethod's job reduces to (1) validating the geometry and (2) handing
    back an ``mpu`` whose group getters accelerate/HF consume.
    """

    @classmethod
    def register_with_transformers(cls, model_name_or_path: Any = None,
                                   core_attn_implementation: str = "sdpa",
                                   sequence_parallel_size: int = 1,
                                   max_length: Optional[int] = None,
                                   micro_batch_size: int = 1,
                                   seq_length_is_variable: bool = True,
                                   **_kwargs: Any):
        if sequence_parallel_size == 1:
            return None
        mesh = groups_mod.get_mesh()
        sp = int(mesh.shape.get(AXIS_SEQ, 1))
        if sp != sequence_parallel_size:
            raise ValueError(
                f"mesh seq axis is {sp}, requested sp={sequence_parallel_size};"
                " build the mesh with the matching MeshLayout first")
        if max_length and max_length % sp:
            raise ValueError(f"max_length {max_length} not divisible by sp={sp}")

        class _MPU:
            @staticmethod
            def get_sequence_parallel_group():
                return groups_mod.get_sequence_parallel_group()

            @staticmethod
            def get_sequence_parallel_world_size():
                return groups_mod.get_sequence_parallel_world_size()

            @staticmethod
            def get_sequence_parallel_rank():
                return groups_mod.get_sequence_parallel_rank()

        return _MPU()
