"""The ``mistral`` family (``model_type`` of the published config): how
the program builds it, what one trained token costs, and its plain
reference.

The reference is float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``: no kernel, no cache, no
remat, no tiled loss, no sharding.  It follows the published model (Jiang
et al., "Mistral 7B", arXiv 2310.06825, and ``modeling_mistral.py`` of the
source config): pre-norm decoder of RMSNorm, grouped-query attention with
rotary positions (rotate-half form) and a causal sliding window (query i
sees keys j with 0 <= i - j < window), SwiGLU feed-forward, untied output
head; the loss is the mean cross-entropy of token t+1 given tokens up to
t.  Independent of ``deepspeed_tpu/models``: it shares only the layout of
the weight tree (``embed [V,H]``; ``layers.attn.wq [L,H,heads,d]``,
``wk``/``wv`` ``[L,H,kv_heads,d]``, ``wo [L,heads,d,H]``;
``layers.mlp.w_gate``/``w_up`` ``[L,H,I]``, ``w_down [L,I,H]``;
``attn_norm``/``mlp_norm [L,H]``; ``final_norm [H]``; ``lm_head [H,V]``).

The weights come as the cell holds them: float32 master weights in
training, bfloat16 in serving.  Each layer's weights are widened to
float32 as the layer runs (exact: every bfloat16 is a float32; sixteen
layers widened at once would not fit beside the server on one chip), and
attention runs one head at a time, so that a 4,200-token request's score
matrix is 71 MB and not 2.3 GB.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from perfbench import shapes

F32 = jnp.float32


# -- the program's model -----------------------------------------------------

def build(cfg: Dict[str, Any], mesh: Any = None) -> Any:
    from deepspeed_tpu.models import LlamaConfig, LlamaModel

    run = cfg["run"]
    return LlamaModel(LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim"),
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=cfg["rope_theta"], rms_norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        sliding_window=cfg.get("sliding_window"),
        dtype=getattr(jnp, run["dtype"]),
        attn_impl=run.get("attn_impl", "xla"),
        remat=run.get("remat", False),
        loss_tiles=run.get("loss_tiles", 1)), mesh=mesh)


# -- operations --------------------------------------------------------------

def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward plus backward (twice the forward), matmuls and attention
    products only, recomputation not counted."""
    H, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    I, V = cfg["intermediate_size"], cfg["vocab_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or H // heads
    weights = L * (H * heads * d + 2 * H * kv * d + heads * d * H
                   + 3 * H * I) + H * V
    keys = shapes.attended_keys(seq, True, cfg.get("sliding_window"))
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


def _layer(x, lp, cfg):
    """One row: x [S, H] float32 → [S, H]."""
    S = x.shape[0]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = lp["attn"]["wq"].shape[-1]
    h = _rms_norm(x, lp["attn_norm"], cfg["rms_norm_eps"])
    q = _rope(jnp.einsum("sH,Hhd->shd", h, lp["attn"]["wq"]), cfg["rope_theta"])
    k = _rope(jnp.einsum("sH,Hhd->shd", h, lp["attn"]["wk"]), cfg["rope_theta"])
    v = jnp.einsum("sH,Hhd->shd", h, lp["attn"]["wv"])
    k, v = (jnp.repeat(t, heads // kv, axis=1) for t in (k, v))
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    seen = (j <= i)
    if cfg.get("sliding_window"):
        seen &= (i - j) < cfg["sliding_window"]

    def one_head(qkv):                       # each [S, d]
        qh, kh, vh = qkv
        scores = qh @ kh.T / jnp.sqrt(F32(d))
        return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1) @ vh

    attn = jax.lax.map(one_head, tuple(t.swapaxes(0, 1) for t in (q, k, v)))
    x = x + jnp.einsum("hqd,hdH->qH", attn, lp["attn"]["wo"])
    h = _rms_norm(x, lp["mlp_norm"], cfg["rms_norm_eps"])
    gate = jax.nn.silu(h @ lp["mlp"]["w_gate"]) * (h @ lp["mlp"]["w_up"])
    return x + gate @ lp["mlp"]["w_down"]


def forward(weights: Dict[str, Any], cfg: Dict[str, Any], ids) -> jnp.ndarray:
    """Token ids ``[B, S]`` → logits ``[B, S, V]`` in float32."""
    def one(x, lp):      # the layers are stacked on their leading axis
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        return jax.vmap(lambda row: _layer(row, lp, cfg))(x), None

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
