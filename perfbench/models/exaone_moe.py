"""The ``exaone_moe`` family (``model_type`` of the published config): how
the program builds it, what one trained token costs, and its plain
reference: the trunk's, and the multi-token-prediction layer's.

The reference is float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``: no kernel, no cache, no sort,
no capacity, no sharding, no drafting.  It follows the published config of
``LGAI-EXAONE/K-EXAONE-236B-A23B`` (``config.json``): a pre-norm RMSNorm
decoder with an untied head whose layer ``l`` is, by ``layer_types[l]`` and
``mlp_layer_types[l]``,

    h  = RMSNorm(x)
    q  = Wq h [64 heads x 128]     k = Wk h, v = Wv h [8 heads x 128]
    q  = RMSNorm_128(q), k = RMSNorm_128(k)   a head, one weight of 128
                                              shared by the heads
      sliding_attention:  q, k = rope(q), rope(k): half-split over the
                          whole head, base rope_parameters.rope_theta;
                          keys j with 0 <= i - j < sliding_window
      full_attention:     NO rotary; every key j <= i
    x += Wo softmax(q k^T / sqrt(128)) v
    h' = RMSNorm(x)
      dense:   x += W_down (silu(W_gate h') * W_up h')
      sparse:  s = sigmoid(h' Wr) over the router's width; the
               num_experts_per_tok largest of s + c are chosen (c: a bias
               an expert, for the choice only); weights s_e / sum_chosen s
               x routed_scaling_factor;
               x += sum_{e chosen AND held} w_e Expert_e(h') + Shared(h')

and after the last layer ``u = RMSNorm(x)``, logits ``W_head u``.

**The prediction layer** (``num_nextn_predict_layers`` 1; the form of the
convention whose key names the config uses, DeepSeek-V3's, arXiv:2412.19437
section 2.2): with ``t_{i+1}`` the token that follows position ``i``,

    z_i = M [ RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(u_i) ]    M: [2H, H]
    y_i = Layer(z_i)      a full_attention sparse layer as above over the
                          z's, at positions i, weights of its own
    draft logits_i = W_head RMSNorm_m(y_i)                  of token i + 2

embedding and head the trunk's (:func:`draft_logits`).

**The share**: the file's ``num_experts`` experts are held here, numbers
``expert_rank * num_experts`` onward of the ``published.num_experts`` the
router scores; what the others would add is left out, here as in the
program (model-configs guide section 4); the shared expert is whole on
every chip.

Read by inference (``assumed`` in the configuration's file): the pre-norm
placement; the prediction layer's form, the order of the halves under
``M``, ``u`` taken after the final norm, its FFN sparse; the window's
convention; ``num_shared_experts`` experts of ``moe_intermediate_size``.

Two keys that only a control of the check sets, and only the reference
reads (``tests/perfbench_tests/k_exaone_control.py``):
``control_rotary_in_full`` (the window layers' rotary in the full layers
too) and ``control_no_qk_norm`` (Q and K as projected).

Independent of ``deepspeed_tpu/models``: it shares only the layout of the
weight tree.  ``leading`` is a list of single layers ``{attn_norm, mlp_norm
[H], attn: {wq [H,64,128], wk, wv [H,8,128], wo [64,128,H], q_norm, k_norm
[128]}, mlp: {w_gate, w_up [H,I], w_down [I,H]} or moe: {wg [H,R], bias
[R], w_gate, w_up [1,E,H,I], w_down [1,E,I,H]} with shared: {w_gate, w_up
[H,S], w_down [S,H]}}``; ``layers`` holds, over the layers that follow
them, ``attn_norm`` / ``mlp_norm [n, H]`` and one stack for each kind that
occurs: ``full`` and ``window`` (the attention leaves with a leading layer
axis), ``mlp``, ``moe`` and ``shared``; ``embed [V,H]``, ``final_norm
[H]``, ``lm_head [H,V]``; ``mtp: {enorm, hnorm, norm [H], proj [2H,H],
layer: a single sparse full-attention layer}``.  Which layers lead is the
program's choice and is read off the tree: ``len(leading)``.

The weights come as the cell holds them (bfloat16 in serving) and are
widened to float32 as they are used (exact), an attention layer's at once,
its experts one at a time, the dense layer's FFN in column blocks of an
expert's width; attention runs one head at a time, so a 3,036-token
request needs one ``[S, S]`` score matrix at a time.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from perfbench import shapes

F32 = jnp.float32


def _is_window(cfg: Dict[str, Any], l: int) -> bool:
    return cfg["layer_types"][l] == "sliding_attention"


def _is_sparse(cfg: Dict[str, Any], l: int) -> bool:
    return cfg["mlp_layer_types"][l] == "sparse"


# -- the program's model -----------------------------------------------------

def build(cfg: Dict[str, Any], mesh: Any = None) -> Any:
    from deepspeed_tpu.models import ExaoneMoeConfig, ExaoneMoeModel

    held = cfg["num_experts"]
    layers = range(cfg["num_hidden_layers"])
    return ExaoneMoeModel(ExaoneMoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        sliding_window=cfg["sliding_window"],
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        rms_norm_eps=cfg["rms_norm_eps"],
        num_experts=cfg["published"]["num_experts"],
        top_k=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        num_shared_experts=cfg["num_shared_experts"],
        held_experts=(cfg["expert_rank"] * held, held),
        attention_pattern=tuple(int(_is_window(cfg, l)) for l in layers),
        moe_pattern=tuple(int(_is_sparse(cfg, l)) for l in layers),
        num_nextn_predict_layers=cfg["num_nextn_predict_layers"],
        max_seq_len=cfg["max_position_embeddings"],
        dtype=getattr(jnp, cfg["run"]["dtype"])), mesh=mesh)


# -- operations --------------------------------------------------------------

def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward plus backward (twice the forward), matmuls and attention
    products only, of the MODEL's token through the layers that are run,
    the prediction layer among them (a trained token passes it, and the
    head a second time behind it): the ``num_experts_per_tok`` experts it
    is routed to wherever they live, the shared ones and the router's
    whole width."""
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    attn = 2 * H * heads * d + 2 * H * kv * d
    sparse = ((cfg["num_experts_per_tok"] + cfg["num_shared_experts"]) * 3
              * H * cfg["moe_intermediate_size"]
              + H * cfg["published"]["num_experts"])
    weights, attention = H * V, 0.0
    for l in range(cfg["num_hidden_layers"]):
        weights += attn + (sparse if _is_sparse(cfg, l)
                           else 3 * H * cfg["intermediate_size"])
        keys = shapes.attended_keys(
            seq, True, cfg["sliding_window"] if _is_window(cfg, l) else None)
        attention += 2 * keys * heads * 2 * d               # QK^T and PV
    for _ in range(cfg["num_nextn_predict_layers"]):
        weights += 2 * H * H + attn + sparse + H * V
        attention += 2 * shapes.attended_keys(seq, True, None) * heads * 2 * d
    return 3.0 * (2 * weights + attention)


# -- the plain reference -----------------------------------------------------

def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [S, heads, d]: half-split rotary embedding at positions 0..S-1
    over the whole row."""
    S, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]      # [S, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def routing(h, wr, bias, cfg):
    """h [S, H] → the weight of every one of the router's experts for
    every token ``[S, R]``: sigmoid scores, the ``num_experts_per_tok``
    largest of score + bias kept, divided by their sum where
    ``norm_topk_prob``, times ``routed_scaling_factor``; 0 elsewhere."""
    score = jax.nn.sigmoid(h @ wr)
    biased = score + bias[None, :]
    top, _ = jax.lax.top_k(biased, cfg["num_experts_per_tok"])
    chosen = jnp.where(biased >= top[:, -1:], score, 0.0)
    if cfg["norm_topk_prob"]:
        chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return chosen * cfg["routed_scaling_factor"]


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
    """h [S, H] → the held experts' part of the routed sum ``[S, H]``.
    ``m``: the router's ``wg``, ``bias`` of this layer and the expert
    leaves as a stack ``[n, E, …]`` with ``layer``, read an expert at a
    time where it lies."""
    weight = routing(h, m["wg"].astype(F32), m["bias"].astype(F32), cfg)
    at = lambda w, e: w[m.get("layer", 0), e]
    held = m["w_gate"].shape[-3]
    first = cfg["expert_rank"] * held
    return _swiglu_sum(
        h, lambda e: [at(m[name], e) for name in ("w_gate", "w_up", "w_down")],
        held, weight[:, first:first + held].T)


def dense(h, m, cfg):
    """A dense SwiGLU (the leading layer's FFN, a shared expert) in column
    blocks of an expert's width (the sum over blocks is the same sum)."""
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
    eps = cfg["rms_norm_eps"]
    a = jax.tree.map(lambda w: w.astype(F32), lp["attn"])
    heads, kv = a["wq"].shape[-2], a["wk"].shape[-2]
    h = _rms_norm(x, lp["attn_norm"].astype(F32), eps)
    q = jnp.einsum("sH,Hhd->shd", h, a["wq"])
    k = jnp.einsum("sH,Hhd->shd", h, a["wk"])
    v = jnp.einsum("sH,Hhd->shd", h, a["wv"])
    if not cfg.get("control_no_qk_norm"):
        q = _rms_norm(q, a["q_norm"], eps)
        k = _rms_norm(k, a["k_norm"], eps)
    # a full layer has no rotary
    if window or cfg.get("control_rotary_in_full"):
        theta = float(cfg["rope_parameters"]["rope_theta"])
        q, k = _rope(q, theta), _rope(k, theta)
    k, v = (jnp.repeat(t, heads // kv, axis=1) for t in (k, v))
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    seen = j <= i
    if window:
        seen = seen & (i - j < cfg["sliding_window"])

    def one_head(qkv):                       # [S, 128] x3
        qh, kh, vh = qkv
        s = jnp.where(seen, qh @ kh.T / jnp.sqrt(F32(qh.shape[-1])),
                      -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ vh

    attn = jax.lax.map(one_head, (q.swapaxes(0, 1), k.swapaxes(0, 1),
                                  v.swapaxes(0, 1)))
    x = x + jnp.einsum("hqd,hdH->qH", attn, a["wo"])
    h2 = _rms_norm(x, lp["mlp_norm"].astype(F32), eps)
    if "moe" not in lp:
        return x + dense(h2, lp["mlp"], cfg)
    y = moe(h2, lp["moe"], cfg)
    if "shared" in lp:
        y = y + dense(h2, lp["shared"], cfg)
    return x + y


def layers_of(weights: Dict[str, Any], cfg: Dict[str, Any]):
    """Every trunk layer that is run, in order: ``(window?, its leaves)``;
    the leading ones as they lie, the others cut out of their kinds'
    stacks."""
    lead = len(weights["leading"])
    out = [(_is_window(cfg, l), lp)
           for l, lp in enumerate(weights["leading"])]
    stacks = weights["layers"]
    at = {"full": 0, "window": 0, "mlp": 0, "moe": 0}
    for n, l in enumerate(range(lead, cfg["num_hidden_layers"])):
        attn = "window" if _is_window(cfg, l) else "full"
        ffn = "moe" if _is_sparse(cfg, l) else "mlp"
        lp = {"attn_norm": stacks["attn_norm"][n],
              "mlp_norm": stacks["mlp_norm"][n],
              "attn": jax.tree.map(lambda t: t[at[attn]], stacks[attn])}
        if ffn == "moe":    # the expert stacks whole: read where they lie
            lp["moe"] = dict(stacks["moe"], wg=stacks["moe"]["wg"][at[ffn]],
                             bias=stacks["moe"]["bias"][at[ffn]],
                             layer=at[ffn])
            if "shared" in stacks:
                lp["shared"] = jax.tree.map(lambda t: t[at[ffn]],
                                            stacks["shared"])
        else:
            lp["mlp"] = jax.tree.map(lambda t: t[at[ffn]], stacks["mlp"])
        out.append((_is_window(cfg, l), lp))
        at[attn] += 1
        at[ffn] += 1
    return out


def hidden(weights: Dict[str, Any], cfg: Dict[str, Any], ids) -> jnp.ndarray:
    """Token ids ``[B, S]`` → ``u [B, S, H]`` in float32: the trunk's
    output after the final norm, what the head and the prediction layer
    read."""
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][ids].astype(F32)
        for window, lp in layers_of(weights, cfg):
            # a layer's weights are widened when the layer before it is
            # done, and not all at the program's start: the barrier ties
            # them to its input
            lp, x = jax.lax.optimization_barrier((lp, x))
            x = jax.lax.map(lambda row: _layer(row, lp, window, cfg), x)
        return _rms_norm(x, weights["final_norm"].astype(F32),
                         cfg["rms_norm_eps"])


def forward(weights: Dict[str, Any], cfg: Dict[str, Any], ids) -> jnp.ndarray:
    """Token ids ``[B, S]`` → logits ``[B, S, V]`` in float32."""
    with jax.default_matmul_precision("highest"):
        return hidden(weights, cfg, ids) @ weights["lm_head"].astype(F32)


def draft_logits(weights: Dict[str, Any], cfg: Dict[str, Any], u,
                 follows) -> jnp.ndarray:
    """The prediction layer: ``u [B, S, H]`` (:func:`hidden`'s, positions
    0 .. S − 1) and the token that FOLLOWS each position ``follows [B,
    S]`` → float32 logits ``[B, S, V]``: row ``i`` scores token ``i + 2``."""
    with jax.default_matmul_precision("highest"):
        m, eps = weights["mtp"], cfg["rms_norm_eps"]
        e = weights["embed"][follows].astype(F32)
        z = jnp.concatenate(
            [_rms_norm(e, m["enorm"].astype(F32), eps),
             _rms_norm(u.astype(F32), m["hnorm"].astype(F32), eps)],
            axis=-1) @ m["proj"].astype(F32)
        y = jax.lax.map(lambda row: _layer(row, m["layer"], False, cfg), z)
        return _rms_norm(y, m["norm"].astype(F32), eps) \
            @ weights["lm_head"].astype(F32)


def loss(weights: Dict[str, Any], cfg: Dict[str, Any],
         batch: Dict[str, Any]) -> jnp.ndarray:
    """Mean next-token cross-entropy of ``batch["input_ids"] [B, S]``."""
    ids = batch["input_ids"]
    logp = jax.nn.log_softmax(forward(weights, cfg, ids)[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll)
