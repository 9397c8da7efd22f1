"""Operations and bytes of a state-space (Mamba-2) mixer's recurrence, from
the published keys.

Beside ``shapes.py`` for the same reason: these are the numerators of the
recurrence's utilization, kept where no PR that claims a gain can change
them.  The count is the WORK's, whatever implements it: a decode step's
update must read a sequence's state and write it back; a prefill block
must do the products of the chunk form.  The projections around the
recurrence are matmuls like any other and are not counted here.
"""

from __future__ import annotations

from typing import Any, Dict


def state_bytes(cfg: Dict[str, Any], bytes_per_element: int = 4) -> float:
    """Bytes of ONE sequence's state in ONE layer: ``[heads, d_head,
    d_state]``, float32 as the configurations here hold it."""
    return float(cfg["mamba_n_heads"] * cfg["mamba_d_head"]
                 * cfg["mamba_d_state"] * bytes_per_element)


def update_bytes(cfg: Dict[str, Any]) -> float:
    """Bytes one decode step's update of ONE live sequence must move in
    ONE layer: its state read and written back.  The token's own ``x``,
    ``B``, ``C`` and the conv's tail are a thousandth of that and are left
    out: the share can only read low by them."""
    return 2.0 * state_bytes(cfg)


def chunk_flops_per_token(cfg: Dict[str, Any], block: int) -> float:
    """Operations of the chunk form for ONE token of a block of ``block``
    tokens in ONE layer, two a multiply-add: a group's ``C_t·B_s`` over a
    causal mean of ``(block + 1) / 2`` earlier tokens; a head's weighted
    sum of their ``x_s``; its read-out of the carried-in state; its part
    of the state going out."""
    heads, P, N = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                   cfg["mamba_d_state"])
    seen = (block + 1) / 2.0
    return (cfg["mamba_n_groups"] * 2.0 * seen * N
            + heads * (2.0 * seen * P + 2.0 * N * P + 2.0 * P * N))


def update_seconds(rows: float, cfg: Dict[str, Any],
                   peaks: Dict[str, float]) -> float:
    """The least time ``rows`` one-token updates (live sequences x decode
    steps) take in every layer: memory-bound by construction (six
    operations a state element against eight bytes)."""
    return (rows * cfg["num_hidden_layers"] * update_bytes(cfg)
            / peaks["hbm_bytes_per_s"])


def chunk_seconds(tokens: float, cfg: Dict[str, Any],
                  peaks: Dict[str, float], block: int) -> float:
    """The least time the chunk form takes for ``tokens`` prompt tokens in
    every layer, in blocks of ``block``: bound by its products."""
    return (tokens * cfg["num_hidden_layers"]
            * chunk_flops_per_token(cfg, block) / peaks["bf16_flops_per_s"])
