"""Bytes and operations of paged decode attention over a LATENT cache,
from the published keys.

Beside ``shapes.py`` for the same reason as ``moe_shapes.py`` and
``hybrid_attn_shapes.py``: the numerators of a kernel's utilization, kept
where no PR that claims a gain can change them.  ``shapes.
paged_attention_bytes`` and ``hybrid_attn_shapes`` count K and V rows by KV
head; a latent (MLA) cache holds ONE row a token a layer, the compressed
vector ``kv_lora_rank`` and the rotary part ``qk_rope_head_dim``, which
every one of the ``num_attention_heads`` query heads reads, and whose
leading ``kv_lora_rank`` numbers are also the value (the absorbed form).
So a cached key is few bytes and many operations: each head scores the
whole row and sums the compressed part.  At the published widths
(512 + 64, 128 heads) that is 1,152 bytes against 278,528 operations,
242 operations a byte: the v5e's own ratio (197 TFLOP/s over 819 GB/s =
240).  Neither bound is the kernel's by a margin, so its roofline is the
LARGER of the two.  The widths are the PUBLISHED ones: a program that
holds the 576 numbers in five 128-lane planes reads (and multiplies)
more than is counted, and its share reads lower for it.
"""

from __future__ import annotations

from typing import Any, Dict


def row_width(cfg: Dict[str, Any]) -> int:
    """Numbers of ONE cached position in ONE layer."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def bytes_per_key(cfg: Dict[str, Any], bytes_per_element: int = 2) -> float:
    """Bytes of ONE cached position in ONE layer: the one row."""
    return float(row_width(cfg) * bytes_per_element)


def flops_per_key(cfg: Dict[str, Any]) -> float:
    """Operations ONE decoding row spends on ONE cached position in ONE
    layer: every query head's score over the whole row and its sum over
    the compressed part, two operations a number."""
    return float(cfg["num_attention_heads"]
                 * (2 * row_width(cfg) + 2 * cfg["kv_lora_rank"]))


def decode_seconds(keys_read: float, cfg: Dict[str, Any],
                   peaks: Dict[str, float]) -> float:
    """The least time the chip could take for all the layers' paged
    kernels, given the keys ONE layer attends over (summed over the rows
    the kernel serves and their steps: the program's ``inference/attn/
    keys_read_latent``): the larger of the cache's stream and the
    products."""
    keys = keys_read * cfg["num_hidden_layers"]
    return max(keys * bytes_per_key(cfg) / peaks["hbm_bytes_per_s"],
               keys * flops_per_key(cfg) / peaks["bf16_flops_per_s"])
