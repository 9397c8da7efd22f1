"""The ``pangu_ultra_moe`` family (``model_type`` of the published config):
how the program builds it, what one trained token costs, and its plain
reference.

The reference is float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``: no kernel, no cache, no
absorption, no sort, no capacity, no sharding.  It follows the language
model of the published config (``FreedomIntelligence/
openPangu-Ultra-MoE-718B``, ``config.json``: the DeepSeek-V3 line's keys
and ``sandwich_norm``) in the EXPANDED form, a head at a time.  ``N`` is
RMSNorm with a learned weight and eps ``rms_norm_eps``; layer ``l``:

    h   = N_in(x)
    c_q = N_q(h W_dq) [q_lora_rank]      q = c_q W_uq -> 128 heads of
                                             [q_nope 128 | q_rope 64]
    [c_kv | k_rope] = h W_dkv [512 | 64]   c_kv <- N_kv(c_kv)
    k_nope = c_kv W_uk, v = c_kv W_uv      a head, 128 each
    q_rope <- rot(q_rope) a head;  k_rope <- rot(k_rope): ONE a token,
                                             which every head shares
    s_ij = q_i·[k_nope | k_rope]_j / sqrt(128 + 64)   for j <= i
    x  += N_post_attn(concat_heads(softmax(s) v) W_o)
    h'  = N_pre_mlp(x)
      l < first_k_dense_replace:  y = W_down (silu(W_gate h') ⊙ W_up h')
      else:  σ = sigmoid(h' Wr) over the router's width; the
             num_experts_per_tok largest σ are chosen; weights
             σ_e / Σ_chosen σ (norm_topk_prob) × routed_scaling_factor;
             y = Σ_{e chosen AND held} w_e · Expert_e(h')
                 + n_shared_experts shared experts, unscaled
    x  += N_post_mlp(y)

then the final RMSNorm and the untied head.

Departures from the published description, each under ``assumed`` in the
configuration's file: the multi-token-prediction module
(``num_nextn_predict_layers`` 1) is not built; ``rot`` is the half-split
rotary embedding (the family's checkpoints interleave the pairs: the same
function under a fixed permutation of ``W_uq``'s and ``W_dkv``'s rotary
columns, which random weights do not tell apart); the published
``kv_b_proj`` ``[512 -> 128 x (128 | 128)]`` is held as its two halves
``w_uk`` and ``w_uv``; the router has no choice bias and no groups (the
config has no ``topk_method``, no ``n_group``); the four norms' places
are the sandwich norm's published reading (a norm on a sub-layer's output
as well as on its input: arXiv:2505.04519).  **The share**: the file's
``n_routed_experts`` experts are held here, numbers ``expert_rank ·
n_routed_experts`` onward of the ``published.n_routed_experts`` the router
scores; what the others would add is left out, here as in the program
(model-configs guide §4); the shared expert is whole; ``N_post_mlp`` is
applied to that partial sum, which is what goes on to the next layer.

Independent of ``deepspeed_tpu/models``: it shares only the layout of the
weight tree.  ``leading`` is a list of the dense layers ``{attn_norm,
post_attn_norm, mlp_norm, post_mlp_norm [H], attn: {w_dq [H,1536], q_norm
[1536], w_uq [1536,128,192], w_dkv [H,576], kv_norm [512], w_uk, w_uv
[512,128,128], wo [128,128,H]}, mlp: {w_gate, w_up [H,I], w_down [I,H]}}``;
``layers`` holds the sparse layers' leaves stacked ``[n, …]``: the norms,
``attn``, ``moe: {wg [n,H,R], w_gate, w_up [n,E,H,I], w_down [n,E,I,H]}``
and ``shared: {w_gate, w_up [n,H,I], w_down [n,I,H]}``; ``embed [V,H]``,
``final_norm [H]``, ``lm_head [H,V]``.

The weights come as the cell holds them (bfloat16 in serving) and are
widened to float32 as they are used (exact): a head's slices inside the
loop over heads, an expert at a time, the dense FFN in column blocks of an
expert's width (the sum over blocks is the same sum), so that a
4,500-token request fits beside the server: one ``[S, S]`` score matrix
and one head's keys and values at a time.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


# -- the program's model -----------------------------------------------------

def build(cfg: Dict[str, Any], mesh: Any = None) -> Any:
    from deepspeed_tpu.models import PanguUltraMoeConfig, PanguUltraMoeModel

    if not cfg["sandwich_norm"]:
        raise SystemExit("perfbench: the program's layers are sandwich-"
                         "normed; sandwich_norm false is another model")
    held = cfg["n_routed_experts"]
    return PanguUltraMoeModel(PanguUltraMoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=cfg["rms_norm_eps"],
        num_layers=cfg["num_hidden_layers"],
        first_k_dense=cfg["first_k_dense_replace"],
        num_experts=cfg["published"]["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        n_shared_experts=cfg["n_shared_experts"],
        held_experts=(cfg["expert_rank"] * held, held),
        max_seq_len=cfg["max_position_embeddings"],
        dtype=getattr(jnp, cfg["run"]["dtype"])), mesh=mesh)


# -- operations --------------------------------------------------------------

def attention_weights(cfg: Dict[str, Any]) -> int:
    """The numbers of one layer's attention matrices."""
    H, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (H * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * qk
            + H * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            + cfg["kv_lora_rank"] * h * (cfg["qk_nope_head_dim"]
                                         + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * H)


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward plus backward (twice the forward), matmuls and attention
    products only, of the MODEL's token through the layers that are run,
    in the expanded form a trainer runs: the ``num_experts_per_tok``
    experts it is routed to wherever they live (not the share of them one
    chip computes), the shared ones, and the router's whole width."""
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    h = cfg["num_attention_heads"]
    expert = 3 * H * cfg["moe_intermediate_size"]
    sparse = ((cfg["num_experts_per_tok"] + cfg["n_shared_experts"]) * expert
              + H * cfg["published"]["n_routed_experts"])
    dense = 3 * H * cfg["intermediate_size"]
    L, lead = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    weights = (H * V + L * attention_weights(cfg)
               + lead * dense + (L - lead) * sparse)
    keys = (seq + 1) / 2.0                          # causal: a mean query's
    attention = L * 2 * keys * h * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])                        # QK^T and PV
    return 3.0 * (2 * weights + attention)


# -- the plain reference -----------------------------------------------------

def _norm(x, group, name, eps):
    """RMSNorm of ``x`` under the weight ``group[name]``."""
    w = group[name].astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [S, d]: half-split rotary embedding at positions 0..S-1."""
    S, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]        # [S, d/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[:, : d // 2], x[:, d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _score_scale(cfg):
    return 1.0 / jnp.sqrt(F32(cfg["qk_nope_head_dim"]
                              + cfg["qk_rope_head_dim"]))


def _shared_key(k_rope, head, theta):
    """The rotary part of every head's key: the ONE vector a token,
    rotated once (``head`` is not read)."""
    del head
    return _rope(k_rope, theta)


def _values(c_kv, w_uv):
    """One head's values from the normed compressed vector."""
    return c_kv @ w_uv


def routing(h, wr, cfg):
    """h [S, H] → the weight of every one of the router's experts for
    every token ``[S, R]``: sigmoid scores, the ``num_experts_per_tok``
    largest kept, divided by their sum where ``norm_topk_prob``, times
    ``routed_scaling_factor``; 0 elsewhere."""
    score = jax.nn.sigmoid(h @ wr)
    top, _ = jax.lax.top_k(score, cfg["num_experts_per_tok"])
    chosen = jnp.where(score >= top[:, -1:], score, 0.0)
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


def _in_blocks(h, m, block):
    """A dense SwiGLU in column blocks of ``block`` (the sum over blocks
    is the same sum)."""
    I = m["w_gate"].shape[1]
    block = min(block, I)
    cols = lambda w, e: jax.lax.dynamic_slice_in_dim(w, e * block, block, 1)
    return _swiglu_sum(
        h, lambda e: [cols(m["w_gate"], e), cols(m["w_up"], e),
                      jax.lax.dynamic_slice_in_dim(m["w_down"], e * block,
                                                   block, 0)],
        I // block, jnp.ones((I // block, h.shape[0]), F32))


def routed(h, m, cfg):
    """h [S, H] → the held experts' part of the layer ``[S, H]``.  ``m``:
    the router's ``wg`` of this layer and the expert leaves as they are
    held: ``[E, …]``, or a stack ``[n, E, …]`` with ``layer``, read an
    expert at a time where it lies."""
    weight = routing(h, m["wg"].astype(F32), cfg)
    stacked = m["w_gate"].ndim == 4
    at = (lambda w, e: w[m.get("layer", 0), e]) if stacked \
        else (lambda w, e: w[e])
    held = m["w_gate"].shape[-3]
    first = cfg["expert_rank"] * held
    return _swiglu_sum(
        h, lambda e: [at(m[name], e) for name in ("w_gate", "w_up", "w_down")],
        held, weight[:, first:first + held].T)


def ffn(h, lp, cfg):
    """h [S, H] (normed) → ``y [S, H]`` before ``N_post_mlp``: the dense
    FFN, or the held experts' part and the shared experts whole."""
    block = cfg["moe_intermediate_size"]
    if "mlp" in lp:
        return _in_blocks(h, lp["mlp"], block)
    y = routed(h, lp["moe"], cfg)
    if cfg["n_shared_experts"]:
        y = y + _in_blocks(h, lp["shared"], block)
    return y


def _layer(x, lp, cfg):
    """One row through one layer: x [S, H] float32 → [S, H]; ``lp`` one
    layer's leaves (a sparse layer's experts as :func:`routed` takes
    them)."""
    S = x.shape[0]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    nope = cfg["qk_nope_head_dim"]
    a = lp["attn"]
    h = _norm(x, lp, "attn_norm", eps)
    c_q = _norm(h @ a["w_dq"].astype(F32), a, "q_norm", eps)
    kv = h @ a["w_dkv"].astype(F32)
    rank = a["kv_norm"].shape[-1]
    c_kv, k_rope = _norm(kv[:, :rank], a, "kv_norm", eps), kv[:, rank:]
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    heads = a["w_uq"].shape[-2]

    def one_head(n):
        # this head's slices, widened here: one head is float32 at a time
        q = c_q @ a["w_uq"][:, n].astype(F32)                   # [S, 192]
        q = jnp.concatenate([q[:, :nope], _rope(q[:, nope:], theta)], -1)
        k = jnp.concatenate([c_kv @ a["w_uk"][:, n].astype(F32),
                             _shared_key(k_rope, n, theta)], -1)
        v = _values(c_kv, a["w_uv"][:, n].astype(F32))          # [S, 128]
        s = jnp.where(j <= i, q @ k.T * _score_scale(cfg), -jnp.inf)
        return (jax.nn.softmax(s, axis=-1) @ v) @ a["wo"][n].astype(F32)

    out = jax.lax.fori_loop(0, heads, lambda n, acc: acc + one_head(n),
                            jnp.zeros_like(x))
    x = x + (_norm(out, lp, "post_attn_norm", eps) if cfg["sandwich_norm"]
             else out)
    y = ffn(_norm(x, lp, "mlp_norm", eps), lp, cfg)
    return x + (_norm(y, lp, "post_mlp_norm", eps) if cfg["sandwich_norm"]
                else y)


def layers_of(weights: Dict[str, Any], cfg: Dict[str, Any]):
    """Every layer that is run, in order: the leading dense ones as they
    lie, the sparse ones cut out of their stacks, the expert stacks left
    whole and read where they lie."""
    out = list(weights["leading"])
    stacks = weights["layers"]
    cut = lambda tree, l: jax.tree.map(lambda t: t[l], tree)
    for l in range(cfg["num_hidden_layers"] - len(out)):
        lp = {name: cut(group, l) for name, group in stacks.items()
              if name != "moe"}
        lp["moe"] = dict(stacks["moe"], wg=stacks["moe"]["wg"][l], layer=l)
        out.append(lp)
    return out


def forward(weights: Dict[str, Any], cfg: Dict[str, Any], ids) -> jnp.ndarray:
    """Token ids ``[B, S]`` → logits ``[B, S, V]`` in float32."""
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][ids].astype(F32)
        for lp in layers_of(weights, cfg):
            # a layer's weights are read when the layer before it is done
            # (the barrier ties them to its input), not all at the start
            lp, x = jax.lax.optimization_barrier((lp, x))
            x = jax.lax.map(lambda row: _layer(row, lp, cfg), x)
        x = _norm(x, weights, "final_norm", cfg["rms_norm_eps"])
        return x @ weights["lm_head"].astype(F32)


def loss(weights: Dict[str, Any], cfg: Dict[str, Any],
         batch: Dict[str, Any]) -> jnp.ndarray:
    """Mean next-token cross-entropy of ``batch["input_ids"] [B, S]``."""
    ids = batch["input_ids"]
    logp = jax.nn.log_softmax(forward(weights, cfg, ids)[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll)
