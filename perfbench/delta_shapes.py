"""Operations and bytes of a gated delta rule's recurrence (a decay a key
channel: KDA), from the published keys of the family that has one
(``linear_attn_config``: ``num_heads`` heads whose keys and values are
``head_dim`` numbers each; the layers that hold it are those NOT named in
``gqa_layers``).

Beside ``mixer_shapes.py`` and ``ssm_shapes.py``, which read the Mamba-2
families' keys, for the same reason: these are the numerators of the
recurrence's utilization, kept where no PR that claims a gain can change
them.  The count is the WORK's, whatever implements it: a decode step's
update must read a sequence's state and write it back; a prefill block
must do the products of the chunk form.  The projections, the convs and the
gates around the recurrence are counted nowhere here.
"""

from __future__ import annotations

from typing import Any, Dict


def delta_layers(cfg: Dict[str, Any]) -> int:
    """The layers that are run and hold the recurrence."""
    return cfg["num_hidden_layers"] - len(cfg["gqa_layers"])


def state_bytes(cfg: Dict[str, Any], bytes_per_element: int = 4) -> float:
    """Bytes of ONE sequence's state in ONE such layer: ``[heads, head_dim
    keys, head_dim values]``, float32 as the configurations here hold it."""
    linear = cfg["linear_attn_config"]
    return float(linear["num_heads"] * linear["head_dim"] ** 2
                 * bytes_per_element)


def update_bytes(cfg: Dict[str, Any]) -> float:
    """Bytes one decode step's update of ONE live sequence must move in
    ONE such layer: its state read and written back.  The token's own
    ``q``, ``k``, ``v``, decay and the conv's tail are a twentieth of that
    and are left out: the share can only read low by them."""
    return 2.0 * state_bytes(cfg)


def chunk_flops_per_token(cfg: Dict[str, Any], block: int) -> float:
    """Operations of the chunk form for ONE token of a block of ``block``
    tokens in ONE such layer, two a multiply-add, a head: the two pairwise
    products over the key channels (``k_t·k_s`` and ``q_t·k_s`` under
    their decays) with a causal mean of ``(block + 1) / 2`` earlier tokens;
    the triangular system applied to the token's correction and the
    weighted sum of the corrections (each over that mean, a value's
    numbers wide); its two read-outs of the carried-in state (``k`` and
    ``q``) and its part of the state going out."""
    linear = cfg["linear_attn_config"]
    heads, d = linear["num_heads"], linear["head_dim"]
    seen = (block + 1) / 2.0
    return heads * (2 * 2.0 * seen * d + 2 * 2.0 * seen * d
                    + 3 * 2.0 * d * d)


def update_seconds(rows: float, cfg: Dict[str, Any],
                   peaks: Dict[str, float]) -> float:
    """The least time ``rows`` one-token updates (live sequences x decode
    steps) take in every such layer: memory-bound by construction (seven
    operations a state element against eight bytes)."""
    return (rows * delta_layers(cfg) * update_bytes(cfg)
            / peaks["hbm_bytes_per_s"])


def chunk_seconds(tokens: float, cfg: Dict[str, Any],
                  peaks: Dict[str, float], block: int) -> float:
    """The least time the chunk form takes for ``tokens`` prompt tokens in
    every such layer, in blocks of ``block``: bound by its products."""
    return (tokens * delta_layers(cfg) * chunk_flops_per_token(cfg, block)
            / peaks["bf16_flops_per_s"])
