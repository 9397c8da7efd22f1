"""Operations and bytes from shapes, and the table of peaks.

These are the numerators of every utilization the benchmark prints; they
live under ``perfbench/`` so that no PR that claims a gain can change them.
What depends on a model family's layer (a trained token's operations) is
in ``models/<model_type>.py``; what depends on a kernel's job is here.
Keys of a configuration are those of its published ``config.json``.
"""

from __future__ import annotations

import json
import pathlib
from typing import Any, Dict

_PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """One chip's published peaks, by ``device_kind``; unknown kinds raise."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind.startswith("_") or device_kind not in table:
        raise SystemExit(
            f"perfbench: no peaks for device kind {device_kind!r} in "
            f"{_PEAKS.name}: add its published row with its source")
    return table[device_kind]


def attended_keys(seq: int, causal: bool, window: int | None) -> float:
    """Mean number of keys a query attends in a ``seq``-token row."""
    if not causal:
        return float(seq)
    if not window or window >= seq:
        return (seq + 1) / 2
    # queries 0..window-1 see i+1 keys, the rest see ``window``
    return (window * (window + 1) / 2 + (seq - window) * window) / seq


def paged_attention_bytes(context_tokens: float, cfg: Dict[str, Any],
                          bytes_per_element: int = 2) -> float:
    """Bytes of cached keys and values one decode step of ONE layer must
    read for rows whose contexts (each capped at the sliding window) sum
    to ``context_tokens``."""
    d = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    return (context_tokens * cfg["num_key_value_heads"] * d * 2
            * bytes_per_element)
