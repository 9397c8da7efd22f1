"""Operations and bytes of a sparse-expert layer, from the published keys.

Beside ``shapes.py`` for the same reason: these are the numerators of the
expert kernels' utilization, kept where no PR that claims a gain can
change them.  One expert is three ``[H, I]`` matrices (gate, up, down), I
being ``moe_intermediate_size`` where the config has it and
``intermediate_size`` where it has not (OLMoE, Mixtral).
"""

from __future__ import annotations

from typing import Any, Dict


def expert_weight_bytes(cfg: Dict[str, Any], bytes_per_element: int = 2
                        ) -> float:
    """Bytes of ONE expert's weights in ONE layer: what a grouped matmul
    must read for every expert that has at least one row."""
    inner = cfg.get("moe_intermediate_size", cfg["intermediate_size"])
    return 3.0 * cfg["hidden_size"] * inner * bytes_per_element


def assignment_flops(cfg: Dict[str, Any]) -> float:
    """Operations of ONE token-to-expert assignment in ONE layer: a row
    through the three matrices, two operations a weight."""
    inner = cfg.get("moe_intermediate_size", cfg["intermediate_size"])
    return 2.0 * 3.0 * cfg["hidden_size"] * inner
