"""The ``falcon_h1`` family (``model_type`` of the published config): how
the program builds it, what one trained token costs, and its plain
reference.

The reference is float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``: no kernel, no cache, no chunk
form, no state pool.  It follows the published config
(``tiiuae/Falcon-H1-34B-Instruct``, ``config.json``) and the published
torch implementation (``transformers``' ``FalconH1DecoderLayer.forward``,
``FalconH1Mixer.torch_forward``, ``compute_mup_vector``), which
``tests/perfbench_tests/test_perfbench_falcon_h1.py`` holds it against.
``N`` is RMSNorm with a learned weight and eps ``rms_norm_eps``; layer
``l`` of a sequence ``x [S, H]``:

    x   = E[id] · embedding_multiplier                       (before layer 0)
    u   = N_in(x)                              both branches read u
    attention, a head at a time (head n reads KV head n // (heads/kv)):
      q = (u · attention_in_multiplier) W_q,  v = (u · a_in) W_v
      k = (u · attention_in_multiplier) W_k · key_multiplier
      rotate-half rotary on q and k over the whole head, base rope_theta
      o_attn = concat_n(softmax_causal(q kᵀ / sqrt(head_dim)) v) W_o
               · attention_out_multiplier
    mixer (Mamba-2), d_ssm = mamba_n_heads · mamba_d_head:
      p = ((u · ssm_in_multiplier) W_in) ⊙ µ,   µ = ssm_multipliers laid over
          [z d_ssm | xs d_ssm | B groups·state | C groups·state | dt heads]
      xBC = [xs | B | C];  xBC_t ← silu(Σ_j w_j ⊙ xBC_{t−(K−1)+j} + b),
          zeros before the sequence's first token (b where mamba_conv_bias)
      Δ_t = softplus(dt_t + dt_bias) a head,  A = −exp(A_log)
      TOKEN BY TOKEN, from S = 0, head h with its group's B and C:
          S ← exp(Δ_t A) S + Δ_t · xs_t B_tᵀ        [d_head, d_state]
          y_t = S C_t + D xs_t
      g = N_groups(y ⊙ silu(z))   (mamba_norm_before_gate false; where
          true: N_groups(y) ⊙ silu(z)); N over each group of d_ssm/groups
      o_ssm = g W_out · ssm_out_multiplier
    x  += o_attn + o_ssm
    n   = N_ff(x)
    x  += ((n W_up) ⊙ silu((n W_gate) · mlp_multipliers[0])) W_down
          · mlp_multipliers[1]

then the final RMSNorm and ``logits = x W_head · lm_head_multiplier``,
untied.  The mixer is the RECURRENCE itself (a ``lax.scan`` over time), so
that the program's chunk form and its one-token update are held against
something that is neither.  Its state is float32, as the program's is
(a constant there, ``FalconH1Model.state_parts``: no key of a
configuration lowers it).  ``control_state_held_in`` is no configuration's
key and ``build`` does not read it: the control of the check that holds
the REFERENCE's state in bfloat16
(``tests/perfbench_tests/falcon_h1_control.py``) puts it into the
reference's ``cfg``, and the state is rounded to it after every token.

Departures from the published description, each under ``assumed`` in the
configuration's file: the weights are random; ``A_log``, ``dt_bias``,
``D``, the conv's bias and every norm's weight are drawn away from their
initial constants.

Independent of ``deepspeed_tpu/models``: it shares only the layout of the
weight tree, stacked ``[L, …]``: ``layers: {attn_norm, mlp_norm [L, H],
attn: {wq [L, H, h, d], wk, wv [L, H, kv, d], wo [L, h, d, H]}, ssm:
{in_proj [L, H, P], conv_w [L, K, conv_dim], conv_b [L, conv_dim],
dt_bias, A_log, D [L, heads], norm [L, d_ssm], out_proj [L, d_ssm, H]},
mlp: {w_gate, w_up [L, H, I], w_down [L, I, H]}}``, ``embed [V, H]``,
``final_norm [H]``, ``lm_head [H, V]``.

The weights come as the cell holds them (bfloat16 in serving) and are
widened to float32 as they are used (exact): a layer at a time (a scan
over the stack), a head's slices inside the loop over heads, the MLP and
the head in column blocks (the sum over blocks is the same sum; the head's
blocks are written into the one ``[S, V]`` result where they belong), so
that a 1,100-token request over the whole 261,120-word vocabulary fits
beside the server (an 1,800-token one does not: its ``[S, V]`` float32
logits alone are 1.92 GB beside 14.58 GB resident).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


# -- the program's model -----------------------------------------------------

def build(cfg: Dict[str, Any], mesh: Any = None) -> Any:
    from deepspeed_tpu.models import FalconH1Config, FalconH1Model

    for key, want in (("mamba_rms_norm", True), ("mamba_conv_bias", True),
                      ("mamba_proj_bias", False), ("attention_bias", False),
                      ("mlp_bias", False), ("projectors_bias", False),
                      ("tie_word_embeddings", False), ("rope_scaling", None),
                      ("attn_layer_indices", None)):
        if cfg.get(key, want) != want:
            raise SystemExit(f"perfbench: the program's layer has {key} "
                             f"{want!r}; {cfg[key]!r} is another model")
    if cfg["mamba_d_ssm"] != cfg["mamba_n_heads"] * cfg["mamba_d_head"]:
        raise SystemExit("perfbench: mamba_d_ssm is not heads x head size")
    return FalconH1Model(FalconH1Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_theta"]), rms_norm_eps=cfg["rms_norm_eps"],
        mamba_n_heads=cfg["mamba_n_heads"], mamba_d_head=cfg["mamba_d_head"],
        mamba_d_state=cfg["mamba_d_state"],
        mamba_n_groups=cfg["mamba_n_groups"],
        mamba_d_conv=cfg["mamba_d_conv"],
        mamba_chunk_size=cfg["mamba_chunk_size"],
        mamba_norm_before_gate=cfg["mamba_norm_before_gate"],
        embedding_multiplier=cfg["embedding_multiplier"],
        lm_head_multiplier=cfg["lm_head_multiplier"],
        attention_in_multiplier=cfg["attention_in_multiplier"],
        attention_out_multiplier=cfg["attention_out_multiplier"],
        key_multiplier=cfg["key_multiplier"],
        ssm_in_multiplier=cfg["ssm_in_multiplier"],
        ssm_out_multiplier=cfg["ssm_out_multiplier"],
        ssm_multipliers=tuple(cfg["ssm_multipliers"]),
        mlp_multipliers=tuple(cfg["mlp_multipliers"]),
        max_seq_len=cfg["max_position_embeddings"],
        dtype=getattr(jnp, cfg["run"]["dtype"])), mesh=mesh)


# -- operations --------------------------------------------------------------

def layer_weights(cfg: Dict[str, Any]) -> int:
    """The numbers of one layer's matrices: attention, the mixer's two
    projections and its conv, the MLP."""
    H, d = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d_ssm = cfg["mamba_d_ssm"]
    conv_dim = d_ssm + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    proj = d_ssm + conv_dim + cfg["mamba_n_heads"]
    return (H * h * d + 2 * H * kv * d + h * d * H
            + H * proj + d_ssm * H + conv_dim * cfg["mamba_d_conv"]
            + 3 * H * cfg["intermediate_size"])


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward plus backward (twice the forward): two operations a weight a
    token through the layers that are run and the head; attention's QKᵀ and
    PV over a causal mean query's keys; the recurrence's own operations a
    token (decay, outer product and read-out over ``[heads, d_head,
    d_state]``: six a state element)."""
    H, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    keys = (seq + 1) / 2.0
    attention = 2 * 2 * keys * cfg["num_attention_heads"] * cfg["head_dim"]
    scan = 6.0 * cfg["mamba_n_heads"] * cfg["mamba_d_head"] \
        * cfg["mamba_d_state"]
    return 3.0 * (2 * (H * V + L * layer_weights(cfg))
                  + L * (attention + scan))


# -- the plain reference -----------------------------------------------------

def _norm(x, w, eps):
    """RMSNorm of ``x`` over its last axis under the weight ``w``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(x, theta):
    """x [S, d]: rotate-half rotary embedding at positions 0..S-1."""
    S, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]        # [S, d/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[:, : d // 2], x[:, d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(n: int, most: int) -> int:
    """The largest divisor of ``n`` that is at most ``most``."""
    return next(b for b in range(min(n, most), 0, -1) if n % b == 0)


def attention(u, a, cfg):
    """u [S, H] (normed) → ``o_attn [S, H]`` before its multiplier: a head
    at a time, this head's slices widened here."""
    S = u.shape[0]
    theta = float(cfg["rope_theta"])
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    u = u * cfg["attention_in_multiplier"]
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]

    def one_head(n):
        g = n // (heads // kv)
        q = _rope(u @ a["wq"][:, n].astype(F32), theta)
        k = _rope((u @ a["wk"][:, g].astype(F32)) * cfg["key_multiplier"],
                  theta)
        v = u @ a["wv"][:, g].astype(F32)
        s = jnp.where(j <= i, q @ k.T / jnp.sqrt(F32(cfg["head_dim"])),
                      -jnp.inf)
        return (jax.nn.softmax(s, axis=-1) @ v) @ a["wo"][n].astype(F32)

    return jax.lax.fori_loop(0, heads, lambda n, acc: acc + one_head(n),
                             jnp.zeros_like(u))


def mup_vector(cfg):
    """``µ``: the five ``ssm_multipliers`` over ``in_proj``'s outputs."""
    d_ssm, bc = cfg["mamba_d_ssm"], cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    widths = (d_ssm, d_ssm, bc, bc, cfg["mamba_n_heads"])
    return jnp.concatenate([jnp.full((w,), m, F32) for w, m
                            in zip(widths, cfg["ssm_multipliers"])])


def mixer(u, m, cfg):
    """u [S, H] (normed) → ``o_ssm [S, H]`` before its multiplier: the
    recurrence token by token from a zero state."""
    S = u.shape[0]
    heads, P, N, G, K = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                         cfg["mamba_d_state"], cfg["mamba_n_groups"],
                         cfg["mamba_d_conv"])
    d_ssm, bc = cfg["mamba_d_ssm"], G * N
    held = getattr(jnp, cfg.get("control_state_held_in", "float32"))
    p = ((u * cfg["ssm_in_multiplier"]) @ m["in_proj"].astype(F32)) \
        * mup_vector(cfg)
    z, xbc, dt = (p[:, :d_ssm], p[:, d_ssm:2 * d_ssm + 2 * bc],
                  p[:, 2 * d_ssm + 2 * bc:])
    # output t sums inputs t-(K-1) … t, zeros before the first token
    padded = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    conv = sum(padded[j:j + S] * m["conv_w"][j].astype(F32) for j in range(K))
    if cfg.get("mamba_conv_bias", True):
        conv = conv + m["conv_b"].astype(F32)
    conv = jax.nn.silu(conv)
    xs = conv[:, :d_ssm].reshape(S, heads, P)
    # head h reads its group's B and C
    B = jnp.repeat(conv[:, d_ssm:d_ssm + bc].reshape(S, G, N), heads // G, 1)
    C = jnp.repeat(conv[:, d_ssm + bc:].reshape(S, G, N), heads // G, 1)
    delta = jax.nn.softplus(dt + m["dt_bias"].astype(F32))        # [S, heads]
    A = -jnp.exp(m["A_log"].astype(F32))

    def token(state, t):
        x_t, B_t, C_t, d_t = t
        state = jnp.exp(d_t * A)[:, None, None] * state \
            + (d_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        state = state.astype(held).astype(F32)
        return state, jnp.sum(state * C_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((heads, P, N), F32),
                        (xs, B, C, delta))
    y = (y + m["D"].astype(F32)[:, None] * xs).reshape(S, G, d_ssm // G)
    gate = jax.nn.silu(z).reshape(S, G, d_ssm // G)
    weight = m["norm"].reshape(G, d_ssm // G)
    eps = cfg["rms_norm_eps"]
    g = _norm(y, weight, eps) * gate if cfg["mamba_norm_before_gate"] \
        else _norm(y * gate, weight, eps)
    return g.reshape(S, d_ssm) @ m["out_proj"].astype(F32)


def mlp(n, m, cfg):
    """n [S, H] (normed) → the MLP's result before its second multiplier,
    in column blocks, one widened at a time."""
    I = m["w_gate"].shape[1]
    block = _block(I, 2048)
    cols = lambda w, e, axis: jax.lax.dynamic_slice_in_dim(
        w, e * block, block, axis).astype(F32)

    def one(y, e):
        gate = jax.nn.silu((n @ cols(m["w_gate"], e, 1))
                           * cfg["mlp_multipliers"][0])
        return y + ((n @ cols(m["w_up"], e, 1)) * gate) \
            @ cols(m["w_down"], e, 0), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(n), jnp.arange(I // block))
    return y


def _layer(x, lp, cfg):
    """One sequence through one layer: x [S, H] float32 → [S, H]."""
    eps = cfg["rms_norm_eps"]
    u = _norm(x, lp["attn_norm"], eps)
    x = x + attention(u, lp["attn"], cfg) * cfg["attention_out_multiplier"] \
        + mixer(u, lp["ssm"], cfg) * cfg["ssm_out_multiplier"]
    n = _norm(x, lp["mlp_norm"], eps)
    return x + mlp(n, lp["mlp"], cfg) * cfg["mlp_multipliers"][1]


def head(x, weights, cfg):
    """x [S, H] → logits [S, V]: the head in column blocks, each written
    into the one result where it belongs."""
    x = _norm(x, weights["final_norm"], cfg["rms_norm_eps"])
    w = weights["lm_head"]
    V = w.shape[1]
    block = _block(V, 16384)

    def one(e, out):
        cols = jax.lax.dynamic_slice_in_dim(w, e * block, block, 1)
        return jax.lax.dynamic_update_slice_in_dim(
            out, (x @ cols.astype(F32)) * cfg["lm_head_multiplier"],
            e * block, 1)

    return jax.lax.fori_loop(0, V // block, one,
                             jnp.zeros((x.shape[0], V), F32))


def forward(weights: Dict[str, Any], cfg: Dict[str, Any], ids) -> jnp.ndarray:
    """Token ids ``[B, S]`` → logits ``[B, S, V]`` in float32."""
    with jax.default_matmul_precision("highest"):
        def one(row):
            x = weights["embed"][row].astype(F32) * cfg["embedding_multiplier"]
            # a layer's weights are cut out of the stack and widened when
            # the layer before it is done
            x, _ = jax.lax.scan(lambda x, lp: (_layer(x, lp, cfg), None), x,
                                weights["layers"])
            return head(x, weights, cfg)

        if ids.shape[0] == 1:           # no second copy of a [S, V] result
            return one(ids[0])[None]
        return jax.lax.map(one, ids)


def loss(weights: Dict[str, Any], cfg: Dict[str, Any],
         batch: Dict[str, Any]) -> jnp.ndarray:
    """Mean next-token cross-entropy of ``batch["input_ids"] [B, S]``."""
    ids = batch["input_ids"]
    logp = jax.nn.log_softmax(forward(weights, cfg, ids)[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll)
