"""Mixture-of-Experts with expert parallelism.

Reference: ``deepspeed/moe/`` [K] — ``layer.py:MoE``, ``sharded_moe.py``
(TopKGate, MOELayer, all-to-all token dispatch), ``experts.py``.
"""

from .layer import DroplessMoE, MoE
from .sharded_moe import (GateIndices, GateMeta, MOELayer, TopKGate,
                          top_k_gating, top_k_gating_indices, top_k_routing)

__all__ = ["MoE", "DroplessMoE", "MOELayer", "TopKGate", "top_k_gating",
           "top_k_gating_indices", "top_k_routing", "GateIndices", "GateMeta"]
