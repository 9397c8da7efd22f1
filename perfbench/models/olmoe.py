"""The ``olmoe`` family (``model_type`` of the published config): how the
program builds it, what one trained token costs, and its plain reference.

The reference is float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``: no kernel, no cache, no sort,
no capacity, no sharding.  It follows the published model (Muennighoff et
al., "OLMoE: Open Mixture-of-Experts Language Models", arXiv 2409.02060,
and ``modeling_olmoe.py`` of the source config): a pre-norm decoder whose
layer is

    h  = RMSNorm(x)
    x += Wo · Attn(rope(qnorm(Wq h)), rope(knorm(Wk h)), Wv h)
    h' = RMSNorm(x);  p = softmax_float32(h' Wg)  over all experts
    x += Σ_{e in top_k(p)} p_e · W_down,e (silu(W_gate,e h') ⊙ W_up,e h')

``qnorm`` / ``knorm`` are RMSNorms with their own weights over the WHOLE
projection (``heads·d`` wide), before the split into heads and before
rotary (rotate-half form); attention is causal over all earlier keys; the
``top_k`` weights are used as they are (``norm_topk_prob`` false: they sum
to well under 1) or divided by their sum where the config says so; an
untied output head; the loss is the mean cross-entropy of token t+1.
Every assignment is computed whatever else the batch holds: each expert
runs over ALL tokens and a mask of the router's choice weighs its output.

Departures from ``modeling_olmoe.py``, each on purpose: the router product
runs in float32 like everything else here (there: in the weights' type,
only the softmax is float32); ``clip_qkv`` is null in the source and is not
implemented; the load-balancing loss (``router_aux_loss_coef``) is a
training regulariser that is no part of ``loss`` here, and the program is
built with its coefficient at 0 to match.

Independent of ``deepspeed_tpu/models``: it shares only the layout of the
weight tree (``embed [V,H]``; ``layers.attn.wq/wk/wv [L,H,heads,d]``, ``wo
[L,heads,d,H]``, ``q_norm``/``k_norm [L,heads·d]``; ``layers.moe.wg
[L,H,E]``, ``w_gate``/``w_up [L,E,H,I]``, ``w_down [L,E,I,H]``;
``attn_norm``/``mlp_norm [L,H]``; ``final_norm [H]``; ``lm_head [H,V]``).
The weights come as the cell holds them (bfloat16 in serving) and are
widened to float32 as they are used (exact: every bfloat16 is a float32),
a layer's attention at once and its experts one at a time (64 experts
widened at once are 1.6 GB beside the server); attention runs one head at
a time, so a 3,000-token request needs one ``[S, S]`` score matrix and one
``[S, I]`` activation at a time.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from perfbench import shapes

F32 = jnp.float32


# -- the program's model -----------------------------------------------------

def build(cfg: Dict[str, Any], mesh: Any = None) -> Any:
    from deepspeed_tpu.models import OlmoeConfig, OlmoeModel

    run = cfg["run"]
    return OlmoeModel(OlmoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        num_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"], aux_loss_coef=0.0,
        dtype=getattr(jnp, run["dtype"]),
        attn_impl=run.get("attn_impl", "xla"),
        remat=run.get("remat", False),
        loss_tiles=run.get("loss_tiles", 1)), mesh=mesh)


# -- operations --------------------------------------------------------------

def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward plus backward (twice the forward), matmuls and attention
    products only: the ``num_experts_per_tok`` experts a token runs (not
    the ``num_experts`` the chip stores) and the router's ``[H, E]``."""
    H, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    I, E, k = (cfg["intermediate_size"], cfg["num_experts"],
               cfg["num_experts_per_tok"])
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = H // heads
    weights = L * (H * heads * d + 2 * H * kv * d + heads * d * H
                   + k * 3 * H * I + H * E) + H * V
    keys = shapes.attended_keys(seq, True, None)
    return 3.0 * (2 * weights + L * 2 * 2 * keys * heads * d)  # QK^T and PV


# -- the plain reference -----------------------------------------------------

def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [S, heads, d]: rotate-half rotary embedding at positions 0..S-1."""
    S, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]        # [S, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def routing(h, wg, cfg):
    """h [S, H] → the weight of every expert for every token ``[S, E]``:
    the softmax over all experts where it is among the token's
    ``num_experts_per_tok`` largest, 0 elsewhere."""
    p = jax.nn.softmax(h @ wg, axis=-1)
    top, _ = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    chosen = jnp.where(p >= top[:, -1:], p, 0.0)
    if cfg["norm_topk_prob"]:
        chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return chosen


def moe(h, lp, cfg):
    """h [S, H] → [S, H]: every expert over all tokens, one at a time."""
    weight = routing(h, lp["wg"].astype(F32), cfg)                # [S, E]

    def one_expert(y, ew):
        w_gate, w_up, w_down, col = (a.astype(F32) for a in ew)
        out = (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down
        return y + col[:, None] * out, None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                        (lp["w_gate"], lp["w_up"], lp["w_down"], weight.T))
    return y


def _layer(x, lp, cfg):
    """One row: x [S, H] float32 → [S, H]."""
    S = x.shape[0]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    a = jax.tree.map(lambda w: w.astype(F32), lp["attn"])
    norm = lambda name: lp[name].astype(F32)
    heads, d = a["wq"].shape[-2:]
    kv = a["wk"].shape[-2]
    h = _rms_norm(x, norm("attn_norm"), eps)
    q = _rms_norm(jnp.einsum("sH,Hhd->shd", h, a["wq"]).reshape(S, heads * d),
                  a["q_norm"], eps).reshape(S, heads, d)
    k = _rms_norm(jnp.einsum("sH,Hhd->shd", h, a["wk"]).reshape(S, kv * d),
                  a["k_norm"], eps).reshape(S, kv, d)
    q, k = _rope(q, theta), _rope(k, theta)
    v = jnp.einsum("sH,Hhd->shd", h, a["wv"])
    k, v = (jnp.repeat(t, heads // kv, axis=1) for t in (k, v))
    seen = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]

    def one_head(qkv):                       # each [S, d]
        qh, kh, vh = qkv
        scores = qh @ kh.T / jnp.sqrt(F32(d))
        return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1) @ vh

    attn = jax.lax.map(one_head, tuple(t.swapaxes(0, 1) for t in (q, k, v)))
    x = x + jnp.einsum("hqd,hdH->qH", attn, a["wo"])
    return x + moe(_rms_norm(x, norm("mlp_norm"), eps), lp["moe"], cfg)


def forward(weights: Dict[str, Any], cfg: Dict[str, Any], ids) -> jnp.ndarray:
    """Token ids ``[B, S]`` → logits ``[B, S, V]`` in float32."""
    def one(x, lp):      # the layers are stacked on their leading axis
        return jax.lax.map(lambda row: _layer(row, lp, cfg), x), None

    with jax.default_matmul_precision("highest"):
        x = weights["embed"][ids].astype(F32)
        x, _ = jax.lax.scan(one, x, weights["layers"])
        x = _rms_norm(x, weights["final_norm"].astype(F32),
                      cfg["rms_norm_eps"])
        return x @ weights["lm_head"].astype(F32)


def loss(weights: Dict[str, Any], cfg: Dict[str, Any],
         batch: Dict[str, Any]) -> jnp.ndarray:
    """Mean next-token cross-entropy of ``batch["input_ids"] [B, S]``."""
    ids = batch["input_ids"]
    logp = jax.nn.log_softmax(forward(weights, cfg, ids)[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll)
