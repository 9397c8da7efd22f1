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
``lax.scan`` + ``jax.checkpoint`` over sequence chunks.
"""

from __future__ import annotations

from typing import Any, Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ...comm.comm import all_to_all_in_graph
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


def sequence_tiled_loss(logits_fn: Callable[[jnp.ndarray], jnp.ndarray],
                        hidden: jnp.ndarray, labels: jnp.ndarray,
                        tiles: int) -> jnp.ndarray:
    """Tiled final-projection + cross-entropy (never materializes the full
    ``[B, S, V]`` logits — the dominant activation at large vocab).

    Returns (sum_nll, valid_count) reduced over all positions; labels use the
    HF ``-100`` ignore convention.
    """
    B, S, H = hidden.shape
    if tiles <= 1 or S % tiles:
        tiles = 1
    hs = jnp.moveaxis(hidden.reshape(B, tiles, S // tiles, H), 1, 0)
    ls = jnp.moveaxis(labels.reshape(B, tiles, S // tiles), 1, 0)

    def body(acc, xs):
        h, lab = xs

        def chunk_nll(h):
            logits = logits_fn(h).astype(jnp.float32)
            valid = lab != -100
            safe = jnp.where(valid, lab, 0)
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
            return (jnp.sum(jnp.where(valid, nll, 0.0)),
                    jnp.sum(valid.astype(jnp.int32)))

        nll_sum, count = jax.checkpoint(chunk_nll)(h)
        return (acc[0] + nll_sum, acc[1] + count), None

    (total, count), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.int32(0)), (hs, ls))
    return total / jnp.maximum(count, 1)


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
