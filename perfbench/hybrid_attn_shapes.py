"""Bytes the paged decode kernel must read of a model whose attention
layers are of two kinds, from the published keys.

Beside ``shapes.py`` for the same reason as ``moe_shapes.py``: the
numerator of a kernel's utilization, kept where no PR that claims a gain
can change it.  ``shapes.paged_attention_bytes`` counts one KV head count,
K and V rows of one width and the config's ``sliding_window`` as a cap on
every layer; here a layer is, by ``hybrid_layer_pattern``, a FULL layer
(``num_key_value_heads`` heads, K rows of ``head_dim``, V rows of
``v_head_dim``, every key so far) or a WINDOW layer (the ``swa_*`` keys,
at most ``sliding_window`` keys).  The widths are the PUBLISHED ones: a
program that holds a 192-wide K row in 256 lanes reads more than is
counted, and its share reads lower for it.
"""

from __future__ import annotations

from typing import Any, Dict


def layers_of(cfg: Dict[str, Any]) -> Dict[str, int]:
    """How many of the layers that are run are of each kind."""
    window = sum(1 for w in cfg["hybrid_layer_pattern"] if w)
    return {"full": len(cfg["hybrid_layer_pattern"]) - window,
            "window": window}


def bytes_per_key(cfg: Dict[str, Any], bytes_per_element: int = 2
                  ) -> Dict[str, float]:
    """Bytes of ONE cached position in ONE layer of each kind: every KV
    head's K row and V row."""
    return {
        "full": float(cfg["num_key_value_heads"]
                      * (cfg["head_dim"] + cfg["v_head_dim"])
                      * bytes_per_element),
        "window": float(cfg["swa_num_key_value_heads"]
                        * (cfg["swa_head_dim"] + cfg["swa_v_head_dim"])
                        * bytes_per_element)}


def decode_bytes(keys_read: Dict[str, float], cfg: Dict[str, Any]) -> float:
    """Bytes all the layers' paged kernels must read, given for each kind
    the keys ONE layer of it attends over (summed over decoding rows and
    decode steps: the program's ``inference/attn/keys_read_<kind>``)."""
    layers, per_key = layers_of(cfg), bytes_per_key(cfg)
    return sum(keys_read[kind] * layers[kind] * per_key[kind]
               for kind in layers)
