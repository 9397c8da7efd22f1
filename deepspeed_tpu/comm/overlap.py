"""Collective–compute overlap — chunked ring decompositions of
all-gather / reduce-scatter that XLA can hide behind the matmuls they
feed.

SNIPPETS.md [1]'s GSPMD pattern hands XLA the collectives automatically,
but a monolithic ``all-gather`` on the tensor or DP axis SERIALIZES
against the matmul that consumes it: nothing computes until the last
byte lands.  Decomposed into a ``ppermute`` ring at chunk granularity,
every step's transfer is independent of every other step's compute, so
the scheduler runs chunk *i*'s matmul while chunk *i+1* is in flight —
the classic Megatron/TE overlapped-GEMM recipe, built TPU-side from the
ICI-native collective-permute.

Everything routes through the :mod:`deepspeed_tpu.comm.comm` verbs
(``dist.ppermute`` / ``dist.axis_index``), so the CollectiveLedger
census sees every ring hop and the desync detector can compare them
across ranks — a raw ``jax.lax.ppermute`` here would be invisible to
forensics (and ``dslint``'s raw-collective rule rejects it).

All functions run INSIDE ``shard_map`` over manual mesh axes:

* :func:`ring_all_gather` — chunked AG (ZeRO-3 param gather).
* :func:`ring_reduce_scatter` — chunked RS (ZeRO-3 grad reduce).
* :func:`all_gather_matmul` — AG ∘ matmul with per-step compute
  (``[m_loc, K] @ [K, N] → [W·m_loc, N]``), the latency-hidden form.
* :func:`matmul_reduce_scatter` — matmul ∘ RS, the mirrored epilogue.

``chunks`` (the ``kernels.overlap_chunks`` tuning dimension) splits each
shard into that many ring payloads: more chunks → finer pipelining but
more per-hop latency; the PR-9 search plane owns the pick per (model,
mesh, device_kind).
"""

from __future__ import annotations

from typing import Any, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import comm as dist

AxisName = Union[str, Tuple[str, ...]]


def _axes_tuple(axes: AxisName) -> Tuple[str, ...]:
    return axes if isinstance(axes, tuple) else (axes,)


def _world(axes: AxisName) -> int:
    w = 1
    for a in _axes_tuple(axes):
        w *= int(jax.lax.axis_size(a))
    return w


def _linear_index(axes: AxisName):
    """Row-major linear index over (possibly several) manual axes —
    matches how ``PartitionSpec((a, b))`` linearizes shards."""
    idx = jnp.int32(0)
    for a in _axes_tuple(axes):
        idx = idx * int(jax.lax.axis_size(a)) + dist.axis_index(a)
    return idx


def _ring_perm(world: int) -> list:
    return [(i, (i + 1) % world) for i in range(world)]


def _split_chunks(x, chunks: int, axis: int):
    if chunks <= 1:
        return [x]
    n = x.shape[axis]
    if n % chunks:
        raise ValueError(
            f"overlap chunks={chunks} must divide the shard dim {n} "
            f"(axis {axis}) — pick a divisor (kernels.overlap_chunks)")
    return [jax.lax.slice_in_dim(x, c * (n // chunks), (c + 1) * (n // chunks),
                                 axis=axis) for c in range(chunks)]


def ring_all_gather(x, axes: AxisName, axis: int = 0, chunks: int = 1):
    """Chunked ring all-gather of ``x`` (this rank's shard) over manual
    ``axes`` → the concatenation ordered by rank along ``axis``.

    Equivalent to ``lax.all_gather(tiled=True)`` but emitted as W−1
    ``ppermute`` hops per chunk, so a consumer of shard *r* can start
    the moment hop |me−r| lands instead of after the full gather."""
    world = _world(axes)
    if world == 1:
        return x
    me = _linear_index(axes)
    perm = _ring_perm(world)
    shard = x.shape[axis]
    out_shape = list(x.shape)
    out_shape[axis] = shard * world
    pieces = _split_chunks(x, chunks, axis)
    sub = shard // len(pieces)
    out = jnp.zeros(tuple(out_shape), x.dtype)
    for ci, piece in enumerate(pieces):
        buf = piece
        for step in range(world):
            src = (me - step) % world          # whose shard buf holds now
            start = src * shard + ci * sub
            out = jax.lax.dynamic_update_slice_in_dim(out, buf, start,
                                                      axis=axis)
            if step + 1 < world:
                buf = dist.ppermute(buf, perm, axes)
    return out


def ring_reduce_scatter(x, axes: AxisName, axis: int = 0,
                        chunks: int = 1):
    """Chunked ring reduce-scatter: every rank holds a full partial ``x``;
    returns this rank's SUM-reduced shard along ``axis`` (the
    ``lax.psum_scatter(tiled=True)`` contract)."""
    world = _world(axes)
    if world == 1:
        return x
    me = _linear_index(axes)
    perm = _ring_perm(world)
    n = x.shape[axis]
    if n % world:
        raise ValueError(f"reduce_scatter dim {n} not divisible by "
                         f"group size {world}")
    shard = n // world

    def block(b, ci=0, sub=None, nsub=1):
        start = b * shard + ci * (shard // nsub)
        size = shard // nsub
        return jax.lax.dynamic_slice_in_dim(x, start, size, axis=axis)

    outs = []
    for ci in range(max(chunks, 1)):
        nsub = max(chunks, 1)
        if shard % nsub:
            raise ValueError(
                f"overlap chunks={chunks} must divide the output shard "
                f"dim {shard} (kernels.overlap_chunks)")
        # start at block (me + W - 1); after W-1 add-and-forward hops the
        # accumulator sitting at rank me covers block me with every
        # rank's contribution
        acc = block((me + world - 1) % world, ci, None, nsub)
        for step in range(1, world):
            acc = dist.ppermute(acc, perm, axes)
            acc = acc + block((me + world - 1 - step) % world, ci, None,
                              nsub)
        outs.append(acc)
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=axis)


def all_gather_matmul(x, w, axes: AxisName, chunks: int = 1,
                      precision=None):
    """Latency-hidden ``all_gather(x) @ w``: ``x [m_loc, K]`` is this
    rank's row shard, ``w [K, N]`` is resident — each ring step matmuls
    the chunk it holds while the next hop is in flight, writing its rows
    of the ``[W·m_loc, N]`` result.  Output rows are ordered by rank
    (the ``all_gather(tiled=True) @ w`` contract)."""
    world = _world(axes)
    if world == 1:
        return jnp.dot(x, w, precision=precision,
                       preferred_element_type=x.dtype)
    me = _linear_index(axes)
    perm = _ring_perm(world)
    m_loc = x.shape[0]
    out = jnp.zeros((m_loc * world, w.shape[1]),
                    jnp.result_type(x.dtype, w.dtype))
    pieces = _split_chunks(x, chunks, 0)
    sub = m_loc // len(pieces)
    for ci, piece in enumerate(pieces):
        buf = piece
        for step in range(world):
            src = (me - step) % world
            y = jnp.dot(buf, w, precision=precision,
                        preferred_element_type=out.dtype)
            out = jax.lax.dynamic_update_slice_in_dim(
                out, y, src * m_loc + ci * sub, axis=0)
            if step + 1 < world:
                buf = dist.ppermute(buf, perm, axes)
    return out


def matmul_reduce_scatter(x, w, axes: AxisName, chunks: int = 1,
                          precision=None):
    """Latency-hidden ``psum_scatter(x @ w)``: ``x [m, K_loc]`` carries
    this rank's K shard (a partial product), output is this rank's row
    shard of the reduced ``[m, N]``.  The per-block matmul runs INSIDE
    the ring loop — block *b*'s dot is independent of block *b−1*'s hop,
    so the scheduler overlaps them (a single monolithic dot before the
    scatter would serialize)."""
    world = _world(axes)
    out_dtype = jnp.result_type(x.dtype, w.dtype)
    if world == 1:
        return jnp.dot(x, w, precision=precision,
                       preferred_element_type=out_dtype)
    me = _linear_index(axes)
    perm = _ring_perm(world)
    m = x.shape[0]
    if m % world:
        raise ValueError(f"matmul_reduce_scatter rows {m} not divisible "
                         f"by group size {world}")
    shard = m // world
    nsub = max(int(chunks), 1)
    if shard % nsub:
        raise ValueError(
            f"overlap chunks={chunks} must divide the output shard dim "
            f"{shard} (kernels.overlap_chunks)")
    sub = shard // nsub

    def partial_y(b, ci):
        rows = jax.lax.dynamic_slice_in_dim(x, b * shard + ci * sub, sub,
                                            axis=0)
        return jnp.dot(rows, w, precision=precision,
                       preferred_element_type=out_dtype)

    outs = []
    for ci in range(nsub):
        acc = partial_y((me + world - 1) % world, ci)
        for step in range(1, world):
            acc = dist.ppermute(acc, perm, axes)
            acc = acc + partial_y((me + world - 1 - step) % world, ci)
        outs.append(acc)
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)


def staging_bytes(shape: Sequence[int], dtype: Any, chunks: int) -> int:
    """Bytes of ring staging buffers a decomposed collective keeps in
    flight (one chunk payload + the assembled output slot) — what the
    engine registers under the ledger's ``collective_scratch`` pool so
    ``peak_hbm_bytes`` gating and OOM forensics name the ring."""
    total = int(np.prod(list(shape))) * jnp.dtype(dtype).itemsize
    return total // max(int(chunks), 1)
