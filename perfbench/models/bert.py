"""The ``bert`` family (``model_type`` of the published config): how the
program builds it, what one trained token costs, and its plain reference.

The reference is BERT's masked-LM training step: forward pass, loss and
(through ``jax.grad`` of that loss) gradients, in float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``: no kernel, no remat, no
sharding.

Follows the published model (Devlin et al., "BERT", arXiv 1810.04805, and
``modeling_bert.py`` of the source config): word + position + segment
embeddings through LayerNorm; post-norm encoder layers of multi-head
attention over every position and a GELU (erf form) feed-forward, each
followed by residual + LayerNorm; the MLM head is dense + GELU + LayerNorm,
then the word-embedding matrix transposed plus a bias; the loss is the
mean cross-entropy over the positions whose label is not -100.
Independent of ``deepspeed_tpu/models``: it shares only the layout of the
weight tree (``embed.{word,position,token_type,ln_w,ln_b}``;
``layers.attn.{wq,wk,wv [L,H,heads,d]; wo [L,heads,d,H]; bq,bk,bv,bo}``;
``layers.mlp.{w_in,b_in,w_out,b_out}``; ``layers.{attn,mlp}_ln_{w,b}``;
``mlm.{w,b,ln_w,ln_b,bias}``).  Departures: every segment id is 0 and no
position is padding, as in the benchmark's batches; dropout is off (the
trainer runs without it too).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from perfbench import shapes

F32 = jnp.float32


# -- the program's model -----------------------------------------------------

def build(cfg: Dict[str, Any], mesh: Any = None) -> Any:
    from deepspeed_tpu.models.bert import BertConfig, BertModel

    run = cfg["run"]
    return BertModel(BertConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        max_seq_len=cfg["max_position_embeddings"],
        type_vocab_size=cfg["type_vocab_size"],
        layer_norm_eps=cfg["layer_norm_eps"],
        dtype=getattr(jnp, run["dtype"]),
        attn_impl=run.get("attn_impl", "xla"),
        remat=run.get("remat", True)), mesh=mesh)


# -- operations --------------------------------------------------------------

def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward plus backward (twice the forward), matmuls and attention
    products only, recomputation not counted: the encoder layers, then the
    MLM head (dense, then the tied decoder)."""
    H, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    I, V = cfg["intermediate_size"], cfg["vocab_size"]
    heads = cfg["num_attention_heads"]
    weights = L * (4 * H * H + 2 * H * I) + H * H + H * V
    keys = shapes.attended_keys(seq, False, None)
    return 3.0 * (2 * weights + L * 2 * 2 * keys * H)      # QK^T and PV


# -- the plain reference -----------------------------------------------------


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _layer(x, lp, eps):
    a = lp["attn"]
    d = a["wq"].shape[-1]
    q = jnp.einsum("bsH,Hhd->bshd", x, a["wq"]) + a["bq"]
    k = jnp.einsum("bsH,Hhd->bshd", x, a["wk"]) + a["bk"]
    v = jnp.einsum("bsH,Hhd->bshd", x, a["wv"]) + a["bv"]
    probs = jax.nn.softmax(
        jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(d)), axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    out = jnp.einsum("bqhd,hdH->bqH", ctx, a["wo"]) + a["bo"]
    x = _layer_norm(x + out, lp["attn_ln_w"], lp["attn_ln_b"], eps)
    m = lp["mlp"]
    h = jax.nn.gelu(x @ m["w_in"] + m["b_in"], approximate=False)
    return _layer_norm(x + h @ m["w_out"] + m["b_out"],
                       lp["mlp_ln_w"], lp["mlp_ln_b"], eps)


def forward(weights: Dict[str, Any], cfg: Dict[str, Any], ids) -> jnp.ndarray:
    """Token ids ``[B, S]`` → MLM logits ``[B, S, V]`` in float32."""
    eps = cfg["layer_norm_eps"]
    w = jax.tree.map(lambda a: a.astype(F32), weights)
    with jax.default_matmul_precision("highest"):
        e = w["embed"]
        S = ids.shape[1]
        x = e["word"][ids] + e["position"][None, :S] + e["token_type"][0]
        x = _layer_norm(x, e["ln_w"], e["ln_b"], eps)
        # the layers are stacked on their leading axis
        x, _ = jax.lax.scan(lambda x, lp: (_layer(x, lp, eps), None),
                            x, w["layers"])
        m = w["mlm"]
        h = jax.nn.gelu(x @ m["w"] + m["b"], approximate=False)
        h = _layer_norm(h, m["ln_w"], m["ln_b"], eps)
        return h @ e["word"].T + m["bias"]


def loss(weights: Dict[str, Any], cfg: Dict[str, Any],
         batch: Dict[str, Any]) -> jnp.ndarray:
    """Mean cross-entropy over the positions labelled (label != -100)."""
    labels = batch["labels"]
    logp = jax.nn.log_softmax(forward(weights, cfg, batch["input_ids"]), -1)
    picked = jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    labelled = labels != -100
    return -jnp.sum(jnp.where(labelled, picked, 0.0)) / jnp.sum(labelled)
