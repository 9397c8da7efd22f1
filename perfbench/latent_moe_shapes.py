"""Operations and bytes of a LatentMoE expert layer, from the published
keys.

Beside ``moe_shapes.py`` for the same reason: these are the numerators of
the expert kernels' utilization, kept where no PR that claims a gain can
change them.  One routed expert is TWO matrices (up ``[w, I]``, down ``[I,
w]``) at the latent's width ``w = moe_latent_size``, I being
``moe_intermediate_size``: a third of what ``moe_shapes`` counts for a
gated expert at the hidden width at these sizes times a quarter.  The
expert layers are the ``E`` of ``hybrid_override_pattern``: a layer of
this family is one part alone, so ``num_hidden_layers`` is not their
count.  The router, the latent projections and the shared expert are
dense products like any other and are not counted here.
"""

from __future__ import annotations

from typing import Any, Dict


def expert_layers(cfg: Dict[str, Any]) -> int:
    """The layers that are run and hold experts."""
    return cfg["hybrid_override_pattern"].count("E")


def expert_weight_bytes(cfg: Dict[str, Any], bytes_per_element: int = 2
                        ) -> float:
    """Bytes of ONE routed expert's weights in ONE layer: what the grouped
    matmuls must read for every expert that has at least one row."""
    return (2.0 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]
            * bytes_per_element)


def assignment_flops(cfg: Dict[str, Any]) -> float:
    """Operations of ONE token-to-expert assignment in ONE layer: a row
    through the two matrices, two operations a weight."""
    return 2.0 * 2.0 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]
