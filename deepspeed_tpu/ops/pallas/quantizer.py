"""Int8 blockwise quantizer — ZeRO++-style compressed collectives.

Role parity: ``csrc/quantization/`` [K] — symmetric int8 (de)quantization
with per-row scales, used to compress the weights all-gather (qwZ) and
gradient reduce (qgZ) (arXiv 2306.10209 [P]).

The op is memory-bound and simple enough that XLA fuses the jnp reference
to a single pass; the Pallas kernel exists for fusion with surrounding
collective-permute steps and as the building block for quantized
collectives.  Both paths share numerics and are cross-checked in tests.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from .select import reference_off_tpu, shape_refused

#: fp32 bytes one grid step may hold per plane: the kernel works on the
#: block upcast to fp32 and Pallas double-buffers it, under Mosaic's
#: default 16 MiB of scoped VMEM
_BLOCK_BYTES = 2 * 1024 * 1024
#: int8 packs 32 rows to a sublane tile — the row-block granule
_ROW_GRANULE = 32


def _ref_quantize(x2d):
    amax = jnp.max(jnp.abs(x2d), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(x2d.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale[:, 0]


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[:].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[:] = q.astype(jnp.int8)
    s_ref[:] = scale


def quantize_int8(x: jnp.ndarray, block_rows: int = 256,
                  interpret: bool | None = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-row int8 quantization of a 2D ``[R, C]`` array →
    ``(int8 [R, C], scales f32 [R])``.  Higher-rank inputs are flattened to
    rows of the last dim.  ``block_rows`` is an upper bound: wide rows
    shrink it so a block stays inside ``_BLOCK_BYTES``."""
    from jax.experimental import pallas as pl

    shape = x.shape
    x2d = x.reshape(-1, shape[-1])
    R, C = x2d.shape

    def reference():
        q, s = _ref_quantize(x2d)
        return q.reshape(shape), s.reshape(shape[:-1])

    if reference_off_tpu(interpret):
        return reference()
    fit = max(_BLOCK_BYTES // (4 * C) // _ROW_GRANULE * _ROW_GRANULE,
              _ROW_GRANULE)
    block_rows = min(block_rows, fit, R)
    if R % block_rows:
        shape_refused("quantize_int8", (R, C),
                      f"{block_rows}-row blocks do not divide R={R}")
        return reference()
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(R // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, C), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block_rows, C), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((R, C), jnp.int8),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32)],
        interpret=interpret,
    )(x2d)
    return q.reshape(shape), s[:, 0].reshape(shape[:-1])


def dequantize_int8(q: jnp.ndarray, scales: jnp.ndarray,
                    dtype=jnp.float32) -> jnp.ndarray:
    """Inverse of :func:`quantize_int8`."""
    return (q.astype(jnp.float32) * scales[..., None]).astype(dtype)
