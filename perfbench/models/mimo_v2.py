"""The ``mimo_v2`` family (``model_type`` of the published config): how the
program builds it, what one trained token costs, and its plain reference.

The reference is float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``: no kernel, no cache, no sort,
no capacity, no sharding.  It follows the language model of the published
config (``XiaomiMiMo/MiMo-V2.5``, ``config.json``; no vision or audio
tower, no MTP module): a pre-norm RMSNorm decoder with an untied head
whose layer ``l`` is, by ``hybrid_layer_pattern[l]`` and
``moe_layer_freq[l]``,

    h  = RMSNorm(x)
    q  = rope(Wq h) [64 heads x 192]   k = rope(Wk h) [Hkv x 192]
    v  = attention_value_scale · Wv h  [Hkv x 128]
    s_ij = q_i·k_j / sqrt(192)           for j <= i
      pattern 0, full:    Hkv = num_key_value_heads, theta = rope_theta,
                          p = softmax(s)
      pattern 1, window:  Hkv = swa_num_key_value_heads, theta =
                          swa_rope_theta, only i - j < sliding_window, and
                          p_ij = exp(s_ij) / (Σ_j' exp(s_ij') + exp(b_h)):
                          b_h a learned sink logit a query head, which
                          takes mass and carries no value
    x += Wo · (p v)
    h' = RMSNorm(x)
      freq 0, dense:      x += W_down (silu(W_gate h') ⊙ W_up h')
      freq 1, sparse:     σ = sigmoid(h' Wr) over the router's width; the
                          num_experts_per_tok largest of σ + c are chosen
                          (c: a bias an expert, for the choice only);
                          weights σ_e / Σ_chosen σ; x += Σ_{e chosen AND
                          held} w_e · Expert_e(h')

``rope`` is the half-split rotary embedding on the first
``int(head_dim · partial_rotary_factor)`` numbers of a row; the rest pass
through.  **The share**: the file's ``n_routed_experts`` experts are held
here, numbers ``expert_rank · n_routed_experts`` onward of the
``published.n_routed_experts`` the router scores; what the others would
add is left out, here as in the program (model-configs guide §4), and the
partial result is what goes on to the next layer.

Read by inference (``assumed`` in the configuration's file):
``attention_value_scale`` multiplies V; ``attention_chunk_size`` is not
read; the window's convention; the rotary form; ``n_shared_experts`` null
means none.

Independent of ``deepspeed_tpu/models``: it shares only the layout of the
weight tree.  ``leading`` is a list of single layers ``{attn_norm, mlp_norm
[H], attn: {wq [H,64,192], wk [H,Hkv,192], wv [H,Hkv,128], wo [64,128,H],
sink [64] in a window layer}, mlp: {w_gate, w_up [H,I], w_down [I,H]} or
moe: {wg [H,R], bias [R], w_gate, w_up [1,E,H,I], w_down [1,E,I,H]}}``;
``layers`` holds, over the layers that follow them, ``attn_norm``/
``mlp_norm [n, H]`` and one stack for each kind that occurs: ``full`` and
``window`` (the attention leaves with a leading layer axis), ``mlp``,
``moe`` (``wg [n,H,R]``, ``bias [n,R]``, ``w_gate``/``w_up [n,E,H,I]``,
``w_down [n,E,I,H]``); ``embed [V,H]``, ``final_norm [H]``, ``lm_head
[H,V]``.  Which layers lead is the program's choice (as many whole periods
as possible follow them) and is read off the tree: ``len(leading)``.

The weights come as the cell holds them (bfloat16 in serving) and are
widened to float32 as they are used (exact), an attention layer's at once,
its experts one at a time, the dense layer's FFN in column blocks of an
expert's width (the sum over blocks is the same sum); attention runs one
head at a time, so a 3,018-token request needs one ``[S, S]`` score matrix
at a time.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from perfbench import shapes

F32 = jnp.float32


def rotary_dim(cfg: Dict[str, Any]) -> int:
    return int(cfg["head_dim"] * cfg["partial_rotary_factor"])


# -- the program's model -----------------------------------------------------

def build(cfg: Dict[str, Any], mesh: Any = None) -> Any:
    from deepspeed_tpu.models import MimoV2Config, MimoV2Model

    held = cfg["n_routed_experts"]
    return MimoV2Model(MimoV2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
        v_head_dim=cfg["v_head_dim"],
        num_kv_heads=cfg["num_key_value_heads"],
        swa_num_kv_heads=cfg["swa_num_key_value_heads"],
        sliding_window=cfg["sliding_window"], rotary_dim=rotary_dim(cfg),
        rope_theta=float(cfg["rope_theta"]),
        swa_rope_theta=float(cfg["swa_rope_theta"]),
        value_scale=cfg["attention_value_scale"],
        rms_norm_eps=cfg["layernorm_epsilon"],
        num_experts=cfg["published"]["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        held_experts=(cfg["expert_rank"] * held, held),
        attention_pattern=tuple(cfg["hybrid_layer_pattern"]),
        moe_pattern=tuple(cfg["moe_layer_freq"]),
        max_seq_len=cfg["max_position_embeddings"],
        dtype=getattr(jnp, cfg["run"]["dtype"])), mesh=mesh)


# -- operations --------------------------------------------------------------

def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward plus backward (twice the forward), matmuls and attention
    products only, of the MODEL's token through the layers that are run:
    the ``num_experts_per_tok`` experts it is routed to wherever they live
    (not the share of them one chip computes) and the router's whole
    width."""
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    heads, dk, dv = (cfg["num_attention_heads"], cfg["head_dim"],
                     cfg["v_head_dim"])
    routed = cfg["published"]["n_routed_experts"]
    weights, attention = H * V, 0.0
    for window, sparse in zip(cfg["hybrid_layer_pattern"],
                              cfg["moe_layer_freq"]):
        kv = cfg["swa_num_key_value_heads" if window
                 else "num_key_value_heads"]
        weights += H * heads * dk + H * kv * (dk + dv) + heads * dv * H
        weights += (cfg["num_experts_per_tok"] * 3 * H
                    * cfg["moe_intermediate_size"] + H * routed) if sparse \
            else 3 * H * cfg["intermediate_size"]
        keys = shapes.attended_keys(
            seq, True, cfg["sliding_window"] if window else None)
        attention += 2 * keys * heads * (dk + dv)          # QK^T and PV
    return 3.0 * (2 * weights + attention)


# -- the plain reference -----------------------------------------------------

def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta, rot):
    """x [S, heads, d]: half-split rotary embedding at positions 0..S-1 on
    the first ``rot`` numbers of a row."""
    S = x.shape[0]
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]      # [S, rot/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : rot // 2], x[..., rot // 2: rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], -1)


def routing(h, wr, bias, cfg):
    """h [S, H] → the weight of every one of the router's experts for
    every token ``[S, R]``: sigmoid scores, the ``num_experts_per_tok``
    largest of score + bias kept, divided by their sum where
    ``norm_topk_prob``; 0 elsewhere."""
    score = jax.nn.sigmoid(h @ wr)
    biased = score + bias[None, :]
    top, _ = jax.lax.top_k(biased, cfg["num_experts_per_tok"])
    chosen = jnp.where(biased >= top[:, -1:], score, 0.0)
    if cfg["norm_topk_prob"]:
        chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return chosen


def _swiglu_sum(h, read, n, weight):
    """Σ_e weight[e] · down_e (silu(gate_e h) ⊙ up_e h) over ``n`` SwiGLU
    blocks, one at a time: ``read(e)`` gives block ``e``'s three matrices
    (widened there, so one block is float32 at a time), ``weight [n, S]``."""
    def one(y, e):
        g, u, d = (a.astype(F32) for a in read(e))
        return y + weight[e][:, None] * ((jax.nn.silu(h @ g) * (h @ u)) @ d), \
            None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(n))
    return y


def moe(h, m, cfg):
    """h [S, H] → the held experts' part of the layer ``[S, H]``.  ``m``:
    the router's ``wg``, ``bias`` of this layer and the expert leaves as
    they are held: ``[E, …]``, or a stack ``[n, E, …]`` with ``layer``,
    read an expert at a time where it lies."""
    weight = routing(h, m["wg"].astype(F32), m["bias"].astype(F32), cfg)
    stacked = m["w_gate"].ndim == 4
    at = (lambda w, e: w[m.get("layer", 0), e]) if stacked \
        else (lambda w, e: w[e])
    held = m["w_gate"].shape[-3]
    first = cfg["expert_rank"] * held
    return _swiglu_sum(
        h, lambda e: [at(m[name], e) for name in ("w_gate", "w_up", "w_down")],
        held, weight[:, first:first + held].T)


def dense(h, m, cfg):
    """The dense SwiGLU in column blocks of an expert's width (the sum
    over blocks is the same sum)."""
    H, I = m["w_gate"].shape
    block = min(cfg["moe_intermediate_size"], I)
    cols = lambda w, e: jax.lax.dynamic_slice_in_dim(w, e * block, block, 1)
    return _swiglu_sum(
        h, lambda e: [cols(m["w_gate"], e), cols(m["w_up"], e),
                      jax.lax.dynamic_slice_in_dim(m["w_down"], e * block,
                                                   block, 0)],
        I // block, jnp.ones((I // block, h.shape[0]), F32))


def _layer(x, lp, window, cfg):
    """One row through one layer: x [S, H] float32 → [S, H]; ``lp`` one
    layer's leaves (a sparse layer's experts as :func:`moe` takes them)."""
    S = x.shape[0]
    eps = cfg["layernorm_epsilon"]
    a = jax.tree.map(lambda w: w.astype(F32), lp["attn"])
    heads, kv = a["wq"].shape[-2], a["wk"].shape[-2]
    theta = float(cfg["swa_rope_theta" if window else "rope_theta"])
    h = _rms_norm(x, lp["attn_norm"].astype(F32), eps)
    rot = rotary_dim(cfg)
    q = _rope(jnp.einsum("sH,Hhd->shd", h, a["wq"]), theta, rot)
    k = _rope(jnp.einsum("sH,Hhd->shd", h, a["wk"]), theta, rot)
    v = cfg["attention_value_scale"] * jnp.einsum("sH,Hhd->shd", h, a["wv"])
    k, v = (jnp.repeat(t, heads // kv, axis=1) for t in (k, v))
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    seen = j <= i
    if window:
        seen = seen & (i - j < cfg["sliding_window"])
    sink = a["sink"] if "sink" in a else jnp.full((heads,), -jnp.inf, F32)

    def one_head(qkvb):                      # [S, 192] x2, [S, 128], []
        qh, kh, vh, b = qkvb
        s = jnp.where(seen, qh @ kh.T / jnp.sqrt(F32(qh.shape[-1])),
                      -jnp.inf)
        top = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), b)
        e = jnp.exp(s - top)
        return (e / (jnp.sum(e, axis=-1, keepdims=True)
                     + jnp.exp(b - top))) @ vh

    attn = jax.lax.map(one_head, (q.swapaxes(0, 1), k.swapaxes(0, 1),
                                  v.swapaxes(0, 1), sink))
    x = x + jnp.einsum("hqd,hdH->qH", attn, a["wo"])
    h2 = _rms_norm(x, lp["mlp_norm"].astype(F32), eps)
    return x + (moe(h2, lp["moe"], cfg) if "moe" in lp
                else dense(h2, lp["mlp"], cfg))


def layers_of(weights: Dict[str, Any], cfg: Dict[str, Any]):
    """Every layer that is run, in order: ``(window?, its leaves)``; the
    leading ones as they lie, the others cut out of their kinds' stacks."""
    lead = len(weights["leading"])
    out = [(bool(w), lp) for w, lp in
           zip(cfg["hybrid_layer_pattern"], weights["leading"])]
    stacks = weights["layers"]
    at = {"full": 0, "window": 0, "mlp": 0, "moe": 0}
    for n, (w, sparse) in enumerate(zip(cfg["hybrid_layer_pattern"][lead:],
                                        cfg["moe_layer_freq"][lead:])):
        attn, ffn = ("window" if w else "full"), ("moe" if sparse else "mlp")
        group = stacks[ffn]
        if sparse:      # the expert stacks whole: read where they lie
            group = dict(group, wg=group["wg"][at[ffn]],
                         bias=group["bias"][at[ffn]], layer=at[ffn])
        else:
            group = jax.tree.map(lambda t: t[at[ffn]], group)
        out.append((bool(w), {
            "attn_norm": stacks["attn_norm"][n],
            "mlp_norm": stacks["mlp_norm"][n],
            "attn": jax.tree.map(lambda t: t[at[attn]], stacks[attn]),
            ffn: group}))
        at[attn] += 1
        at[ffn] += 1
    return out


def forward(weights: Dict[str, Any], cfg: Dict[str, Any], ids) -> jnp.ndarray:
    """Token ids ``[B, S]`` → logits ``[B, S, V]`` in float32."""
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][ids].astype(F32)
        for window, lp in layers_of(weights, cfg):
            # a layer's weights are widened when the layer before it is
            # done, and not all seven at the program's start (2.4 GB
            # beside the server): the barrier ties them to its input
            lp, x = jax.lax.optimization_barrier((lp, x))
            x = jax.lax.map(lambda row: _layer(row, lp, window, cfg), x)
        x = _rms_norm(x, weights["final_norm"].astype(F32),
                      cfg["layernorm_epsilon"])
        return x @ weights["lm_head"].astype(F32)


def loss(weights: Dict[str, Any], cfg: Dict[str, Any],
         batch: Dict[str, Any]) -> jnp.ndarray:
    """Mean next-token cross-entropy of ``batch["input_ids"] [B, S]``."""
    ids = batch["input_ids"]
    logp = jax.nn.log_softmax(forward(weights, cfg, ids)[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll)
