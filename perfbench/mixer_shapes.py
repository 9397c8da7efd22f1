"""Operations and bytes of a Mamba-2 mixer's recurrence where the mixer is
a LAYER OF ITS OWN, from the published keys of that family
(``mamba_num_heads``, ``mamba_head_dim``, ``ssm_state_size``, ``n_groups``;
the mixer layers are the ``M`` of ``hybrid_override_pattern``, not
``num_hidden_layers``).

Beside ``ssm_shapes.py``, which reads another family's key names and a
mixer in every layer, for the same reason: these are the numerators of
the recurrence's utilization, kept where no PR that claims a gain can
change them.  The count is the WORK's, whatever implements it: a decode
step's update must read a sequence's state and write it back; a prefill
block must do the products of the chunk form.  The projections around the
recurrence are matmuls like any other and are not counted here.
"""

from __future__ import annotations

from typing import Any, Dict


def mixer_layers(cfg: Dict[str, Any]) -> int:
    """The layers that are run and hold a mixer."""
    return cfg["hybrid_override_pattern"].count("M")


def state_bytes(cfg: Dict[str, Any], bytes_per_element: int = 4) -> float:
    """Bytes of ONE sequence's state in ONE mixer layer: ``[heads,
    head_dim, state]``, float32 as the configurations here hold it."""
    return float(cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
                 * cfg["ssm_state_size"] * bytes_per_element)


def update_bytes(cfg: Dict[str, Any]) -> float:
    """Bytes one decode step's update of ONE live sequence must move in
    ONE mixer layer: its state read and written back.  The token's own
    ``x``, ``B``, ``C`` and the conv's tail are a hundredth of that and
    are left out: the share can only read low by them."""
    return 2.0 * state_bytes(cfg)


def chunk_flops_per_token(cfg: Dict[str, Any], block: int) -> float:
    """Operations of the chunk form for ONE token of a block of ``block``
    tokens in ONE mixer layer, two a multiply-add: a group's ``C_t·B_s``
    over a causal mean of ``(block + 1) / 2`` earlier tokens; a head's
    weighted sum of their ``x_s``; its read-out of the carried-in state;
    its part of the state going out."""
    heads, P, N = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                   cfg["ssm_state_size"])
    seen = (block + 1) / 2.0
    return (cfg["n_groups"] * 2.0 * seen * N
            + heads * (2.0 * seen * P + 2.0 * N * P + 2.0 * P * N))


def update_seconds(rows: float, cfg: Dict[str, Any],
                   peaks: Dict[str, float]) -> float:
    """The least time ``rows`` one-token updates (live sequences x decode
    steps) take in every mixer layer: memory-bound by construction (six
    operations a state element against eight bytes)."""
    return (rows * mixer_layers(cfg) * update_bytes(cfg)
            / peaks["hbm_bytes_per_s"])


def chunk_seconds(tokens: float, cfg: Dict[str, Any],
                  peaks: Dict[str, float], block: int) -> float:
    """The least time the chunk form takes for ``tokens`` prompt tokens in
    every mixer layer, in blocks of ``block``: bound by its products."""
    return (tokens * mixer_layers(cfg) * chunk_flops_per_token(cfg, block)
            / peaks["bf16_flops_per_s"])
