"""qgZ — ZeRO++ quantized gradient reduction (arXiv 2306.10209 [P]).

Role parity: the ``zero_quantized_gradients`` path inside the reference's
``zero/stage3.py`` + ``csrc/quantization`` kernels [K]: gradients cross the
wire as int8 + group scales instead of fp32/bf16, cutting DP-reduction
bytes ~4× (the win the paper targets for cross-node DCN links; on TPU the
same scheme relieves DCN in multi-slice meshes and ICI at large dp).

Scheme (the paper's 2-hop, all-to-all based reduce):

    1. each worker splits its local grad into ``world`` chunks, int8-
       quantizes each (group-wise scales), ``all_to_all``s them — after
       this hop worker w holds every worker's quantized chunk w;
    2. dequantize + sum locally → worker w owns the reduced chunk w;
    3. quantize the reduced chunk, ``all_gather``, dequantize → replicated
       mean gradient.

Wire bytes/worker ≈ 2n·int8 (+ scales) vs 8n for fp32 ring RS+AG → ~4×.
Runs inside the engine's partial-manual ``shard_map`` over the DP axes
(same harness as the 1-bit path); quantization reuses the int8 math of
``ops/pallas/quantizer.py`` (jnp form — inside shard_map the arrays are
small per-device blocks and XLA fuses the (de)quant into the collective
schedule).
"""

from __future__ import annotations

from typing import Any, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from ...comm.comm import all_gather_in_graph, all_to_all_in_graph

GROUP = 256  # quantization group size (scale granularity)


def _quant_groups(flat: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[n] fp32 (n % GROUP == 0) → (int8 [n], scales f32 [n/GROUP])."""
    g = flat.reshape(-1, GROUP)
    amax = jnp.max(jnp.abs(g), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    return q.reshape(-1), scale[:, 0]


def _dequant_groups(q: jnp.ndarray, scales: jnp.ndarray) -> jnp.ndarray:
    return (q.reshape(-1, GROUP).astype(jnp.float32)
            * scales[:, None]).reshape(-1)


def quantized_allreduce(g: jnp.ndarray, axis_names: Sequence[str]
                        ) -> jnp.ndarray:
    """Mean-allreduce of one tensor with int8 wire format (inside
    shard_map; ``g`` is this worker's local gradient)."""
    names = tuple(axis_names)
    world = 1
    for ax in names:
        world *= jax.lax.axis_size(ax)
    if world == 1:
        return g

    flat = g.reshape(-1).astype(jnp.float32)
    n = flat.size
    pad = -n % (world * GROUP)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    chunks = flat.reshape(world, -1)          # [W, c]

    # hop 1: quantize chunks, all-to-all so worker w collects chunk w
    q, s = jax.vmap(_quant_groups)(chunks)    # [W, c] int8, [W, c/G] f32
    q = all_to_all_in_graph(q[:, None], names, split_axis=0, concat_axis=1,
                            tiled=False)      # [1, W, c]
    s = all_to_all_in_graph(s[:, None], names, split_axis=0, concat_axis=1,
                            tiled=False)
    partial = jax.vmap(_dequant_groups)(q[0], s[0])   # [W, c] f32
    reduced = jnp.sum(partial, axis=0) / world        # [c] — my chunk, meaned

    # hop 2: quantize the reduced chunk, all-gather, dequantize
    q2, s2 = _quant_groups(reduced)
    q2 = all_gather_in_graph(q2, names, tiled=False)  # [W, c] (stacked axes
    s2 = all_gather_in_graph(s2, names, tiled=False)  # collapse to W)
    q2 = q2.reshape(world, -1)
    s2 = s2.reshape(world, -1)
    out = jax.vmap(_dequant_groups)(q2, s2).reshape(-1)
    if pad:
        out = out[:n]
    return out.reshape(g.shape).astype(g.dtype)


def quantized_reduce_scatter(g: jnp.ndarray, axis_names: Sequence[str],
                             dim: int) -> jnp.ndarray:
    """int8 single-hop reduce-scatter of one tensor along ``dim`` — the
    stage-3 form of qgZ: each worker ends up holding only ITS slice of the
    mean gradient (matching the ZeRO-3 grad/opt-state layout), so hop 2
    (all-gather) never happens and wire bytes drop to ~1×int8 vs 4×fp32.

    Inside shard_map; ``g`` is this worker's full local gradient."""
    names = tuple(axis_names)
    world = 1
    for ax in names:
        world *= jax.lax.axis_size(ax)
    if world == 1:
        return g

    gm = jnp.moveaxis(g, dim, 0).astype(jnp.float32)
    per = gm.shape[0] // world
    rest = int(np.prod(gm.shape[1:])) if gm.ndim > 1 else 1
    n = per * rest
    flat = gm.reshape(world, n)
    pad = -n % GROUP
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))

    q, s = jax.vmap(_quant_groups)(flat)      # [W, n'] int8, [W, n'/G] f32
    q = all_to_all_in_graph(q[:, None], names, split_axis=0, concat_axis=1,
                            tiled=False)       # [1, W, n']
    s = all_to_all_in_graph(s[:, None], names, split_axis=0, concat_axis=1,
                            tiled=False)
    partial = jax.vmap(_dequant_groups)(q[0], s[0])   # [W, n'] f32
    red = jnp.sum(partial, axis=0) / world
    if pad:
        red = red[:n]
    out = red.reshape((per,) + tuple(gm.shape[1:]))
    return jnp.moveaxis(out, 0, dim).astype(g.dtype)


def qgz_reduce_tree(grads: Any, axis_names: Sequence[str]) -> Any:
    return jax.tree.map(lambda g: quantized_allreduce(g, axis_names), grads)


def wire_bytes(params: Any) -> Tuple[int, int]:
    """(quantized, fp32) DP-reduction bytes per worker — int8 payload plus
    fp32 group scales for both hops, vs fp32 reduce-scatter + all-gather."""
    n = sum(int(np.prod(np.shape(p))) for p in jax.tree.leaves(params))
    quant = 2 * (n + 4 * (n // GROUP))
    return quant, 8 * n
