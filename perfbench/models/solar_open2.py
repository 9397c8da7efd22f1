"""The ``solar_open2`` family (``model_type`` of the published config): how
the program builds it, what one trained token costs, and its plain
reference.

The reference is float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``: no kernel, no cache, no chunk
form, no state pool, no sorted expert layout.  It follows the published
config (``upstage/Solar-Open2-250B``, ``config.json``) and the catalog's
description of the family.  ``N`` is RMSNorm with a learned weight and eps
``rms_norm_eps``.  EVERY LAYER IS A TOKEN MIXER AND THEN EXPERTS, each under
a norm of its own: layer ``l`` of a sequence ``x [S, H]`` is ``x +=
Mixer(N(x))``, ``x += Experts(N'(x))`` with ``h`` the part's normed input
and

    the mixer of a layer NOT in gqa_layers, a gated delta rule with a decay
    a key channel (KDA), n = linear_attn_config.num_heads heads of
    d = linear_attn_config.head_dim, K = short_conv_kernel_size:
      q^, k^, v^ = h W_q, h W_k, h W_v          (n d each)
      x_t ← silu(Σ_j w_j ⊙ x_{t−(K−1)+j}) on each of the three, a conv of
          its own each, zeros before the sequence's first token, no bias
      a head at a time: q = q^/‖q^‖ · d^(−1/2),  k = k^/‖k^‖,  v = v^
          (‖x‖ = sqrt(Σ x² + 1e-6))
      g = −exp(A_log) · softplus((h W_f↓) W_f↑ + dt_bias)   a KEY CHANNEL
          (A_log a head, dt_bias a channel; the bottleneck d wide:
          kda_use_full_proj false)
      β = 2 · sigmoid(h W_β) a head           (kda_allow_neg_eigval true)
      TOKEN BY TOKEN, from S = 0  [d keys, d values] a head:
          S ← exp(g_t)[:, None] ⊙ S
          S ← S + k_t ⊗ β_t (v_t − k_tᵀ S)
          o_t = Sᵀ q_t
      Mixer = [N_d(o) ⊙ sigmoid((h W_g↓) W_g↑)] W_o,  N_d over a head's d
          under one weight [d] the heads share
    the mixer of a layer in gqa_layers, a head at a time (head n reads KV
    head n // (heads / kv)):
      q = h W_q, k = h W_k, v = h W_v;  NO rotary (use_rope false)
      a = softmax_causal(q kᵀ / sqrt(head_dim)) v
      Mixer = concat_n(a ⊙ sigmoid(h W_γ)) W_o     (use_gqa_gate: the gate
          elementwise over heads x head_dim, from h by a matrix of its own)
    the experts (every layer; first_k_dense_replace 0):
      s = sigmoid(h W_r) over the PUBLISHED expert count, float32
      chosen = the num_experts_per_tok largest of s + b (b a choice bias)
      g_e = routed_scaling_factor · s_e / Σ_chosen s   (norm_topk_prob)
      Experts = Σ_e g_e · (silu(h W1_e) ⊙ (h W3_e)) W2_e
                + (silu(h V1) ⊙ (h V3)) V2      (one shared expert of
                n_shared_experts · moe_intermediate_size)

then the final RMSNorm and ``logits = x W_head``, untied.  Where the file
holds a SHARE of the experts (``n_routed_experts`` under
``published.n_routed_experts``: experts ``expert_rank · held`` onwards),
the routed sum is over the held experts alone, one at a time where they
lie, and the rest of the layer is whole: the shares' parts add up to the
uncut layer's with the shared expert counted once.

The delta rule is the RECURRENCE itself (a ``lax.scan`` over time), so that
the program's chunk form and its one-token kernel are held against
something that is neither.  Keys that are no configuration's and that
``build`` does not read, set by the controls of the check alone
(``tests/perfbench_tests/solar_open2_control.py``): ``control_state_dropped``
(the recurrence forgets: every token from a zero state),
``control_beta_not_doubled`` (``β = sigmoid``), ``control_decay_a_head``
(a channel's decay replaced by the mean over its head's channels),
``control_gate_dropped`` (the attention's output gate left out).

Departures from the published description, each under ``assumed`` in the
configuration's file: the weights are random; ``A_log``, ``dt_bias``, the
choice bias and every norm's weight are drawn away from their initial
constants; a routed expert's ``W2`` is drawn ``num_experts_per_tok / 2``
times smaller; the bottlenecks' rank, the form of the attention's gate and
the router's scoring are the family's conventions, not keys.

Independent of ``deepspeed_tpu/models``: it shares only the layout of the
weight tree, a stack a PART: ``attn: {pre_norm [A, H], wq [A, H, h, d], wk,
wv [A, H, kv, d], w_gate [A, H, h, d], wo [A, h, d, H]}``, ``delta:
{pre_norm [D, H], in_proj [D, H, 3 n d] (q | k | v), conv_w [D, K, 3 n d],
f_down [D, H, d], f_up [D, d, n d], dt_bias [D, n d], A_log [D, n], w_beta
[D, H, n], g_down [D, H, d], g_up [D, d, n d], norm [D, d], out_proj [D, n
d, H]}``, ``moe: {pre_norm [L, H], wg [L, H, experts], bias [L, experts],
w_gate, w_up [L, held, H, I], w_down [L, held, I, H], shared: {w_gate, w_up
[L, H, S], w_down [L, S, H]}}``, ``embed [V, H]``, ``final_norm [H]``,
``lm_head [H, V]``; a mixer is its kind's next.

The weights come as the cell holds them (bfloat16 in serving) and are
widened to float32 as they are used (exact): a layer at a time, a head's
slices inside the loop over heads, an expert at a time and the head in
column blocks, so that a 2,000-token request fits beside the server.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: the leaves read where they lie in their stack, an expert at a time
WHOLE = ("w_gate", "w_up", "w_down")


# -- the program's model -----------------------------------------------------

def build(cfg: Dict[str, Any], mesh: Any = None) -> Any:
    from deepspeed_tpu.models import SolarOpen2Config, SolarOpen2Model

    for key, want in (("use_rope", False), ("use_gqa_gate", True),
                      ("kda_use_full_proj", False),
                      ("kda_allow_neg_eigval", True),
                      ("first_k_dense_replace", 0),
                      ("tie_word_embeddings", False)):
        if cfg.get(key, want) != want:
            raise SystemExit(f"perfbench: the program's layer has {key} "
                             f"{want!r}; {cfg[key]!r} is another model")
    linear = cfg["linear_attn_config"]
    held = cfg["n_routed_experts"]
    published = cfg.get("published", {}).get("n_routed_experts", held)
    return SolarOpen2Model(SolarOpen2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        gqa_layers=tuple(cfg["gqa_layers"]),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        linear_num_heads=linear["num_heads"],
        linear_head_dim=linear["head_dim"],
        conv_kernel=linear["short_conv_kernel_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=published, top_k=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        held_experts=(cfg.get("expert_rank", 0) * held, held),
        rms_norm_eps=cfg["rms_norm_eps"],
        max_seq_len=cfg["max_position_embeddings"],
        dtype=getattr(jnp, cfg["run"]["dtype"])), mesh=mesh)


# -- operations --------------------------------------------------------------

def part_weights(cfg: Dict[str, Any]) -> Dict[str, int]:
    """The numbers of the matrices a token of the MODEL passes in one part
    of each kind: KDA's four projections, its two bottlenecks, ``W_β`` and
    the three convs; attention's four and its gate; the experts' router at
    its published width, shared expert and ``num_experts_per_tok`` routed
    experts of three matrices, wherever they live."""
    H, d = cfg["hidden_size"], cfg["head_dim"]
    linear = cfg["linear_attn_config"]
    n, dl = linear["num_heads"], linear["head_dim"]
    I = cfg["moe_intermediate_size"]
    experts = cfg.get("published", {}).get("n_routed_experts",
                                           cfg["n_routed_experts"])
    return {
        "delta": 4 * H * n * dl + 2 * (H * dl + dl * n * dl) + H * n
        + 3 * n * dl * linear["short_conv_kernel_size"],
        "attn": H * d * (3 * cfg["num_attention_heads"]
                         + 2 * cfg["num_key_value_heads"]),
        "experts": H * experts + 3 * H * I * (cfg["n_shared_experts"]
                                              + cfg["num_experts_per_tok"])}


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward plus backward (twice the forward): two operations a weight a
    token through the parts that are run and the head; attention's QKᵀ and
    PV over a causal mean query's keys in the ``gqa_layers``; the
    recurrence's own operations a token in the others (decay, ``kᵀS``, the
    rank-1 correction and the read-out: seven a state element)."""
    layers = cfg["num_hidden_layers"]
    attn = len(cfg["gqa_layers"])
    weights = part_weights(cfg)
    linear = cfg["linear_attn_config"]
    keys = (seq + 1) / 2.0
    attention = 2 * 2 * keys * cfg["num_attention_heads"] * cfg["head_dim"]
    rule = 7.0 * linear["num_heads"] * linear["head_dim"] ** 2
    return 3.0 * (2 * (cfg["hidden_size"] * cfg["vocab_size"]
                       + attn * weights["attn"]
                       + (layers - attn) * weights["delta"]
                       + layers * weights["experts"])
                  + attn * attention + (layers - attn) * rule)


# -- the plain reference -----------------------------------------------------

def _norm(x, w, eps):
    """RMSNorm of ``x`` over its last axis under the weight ``w``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def _block(n: int, most: int) -> int:
    """The largest divisor of ``n`` that is at most ``most``."""
    return next(b for b in range(min(n, most), 0, -1) if n % b == 0)


def _unit(x):
    """``x`` over its L2 norm along the last axis."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def attention(h, a, cfg):
    """h [S, H] (normed) → the gated attention's Mixer [S, H]: a head at a
    time, this head's slices widened here."""
    S = h.shape[0]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    gated = 0.0 if cfg.get("control_gate_dropped") else 1.0

    def one_head(n):
        g = n // (heads // kv)
        q, k = h @ a["wq"][:, n].astype(F32), h @ a["wk"][:, g].astype(F32)
        v = h @ a["wv"][:, g].astype(F32)
        s = jnp.where(j <= i, q @ k.T / jnp.sqrt(F32(cfg["head_dim"])),
                      -jnp.inf)
        gate = jax.nn.sigmoid(h @ a["w_gate"][:, n].astype(F32))
        out = jax.nn.softmax(s, axis=-1) @ v
        return (out * (gated * gate + 1.0 - gated)) @ a["wo"][n].astype(F32)

    return jax.lax.fori_loop(0, heads, lambda n, acc: acc + one_head(n),
                             jnp.zeros_like(h))


def delta(h, m, cfg):
    """h [S, H] (normed) → the delta rule's Mixer [S, H]: the recurrence
    token by token from a zero state."""
    S = h.shape[0]
    linear = cfg["linear_attn_config"]
    n, d, K = (linear["num_heads"], linear["head_dim"],
               linear["short_conv_kernel_size"])
    D = n * d
    keeps = 0.0 if cfg.get("control_state_dropped") else 1.0
    doubled = 1.0 if cfg.get("control_beta_not_doubled") else 2.0
    streams = h @ m["in_proj"].astype(F32)
    # output t sums inputs t-(K-1) … t, zeros before the first token
    padded = jnp.pad(streams, ((K - 1, 0), (0, 0)))
    conv = jax.nn.silu(sum(padded[j:j + S] * m["conv_w"][j].astype(F32)
                           for j in range(K)))
    q, k, v = (conv[:, i * D:(i + 1) * D].reshape(S, n, d) for i in range(3))
    q, k = _unit(q) / jnp.sqrt(F32(d)), _unit(k)
    f = (h @ m["f_down"].astype(F32)) @ m["f_up"].astype(F32)
    g = -jnp.exp(m["A_log"].astype(F32))[:, None] * jax.nn.softplus(
        f + m["dt_bias"].astype(F32)).reshape(S, n, d)
    if cfg.get("control_decay_a_head"):
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    beta = doubled * jax.nn.sigmoid(h @ m["w_beta"].astype(F32))  # [S, n]

    def token(state, t):
        q_t, k_t, v_t, g_t, b_t = t
        state = keeps * jnp.exp(g_t)[:, :, None] * state  # [n, d keys, d]
        u = jnp.sum(k_t[:, :, None] * state, axis=1)
        state = state + k_t[:, :, None] * (b_t[:, None] * (v_t - u)
                                           )[:, None, :]
        return state, jnp.sum(q_t[:, :, None] * state, axis=1)

    _, o = jax.lax.scan(token, jnp.zeros((n, d, d), F32),
                        (q, k, v, g, beta))
    gate = jax.nn.sigmoid((h @ m["g_down"].astype(F32))
                          @ m["g_up"].astype(F32))
    o = _norm(o, m["norm"], cfg["rms_norm_eps"]).reshape(S, D) * gate
    return o @ m["out_proj"].astype(F32)


def routing(h, m, cfg):
    """h [S, H] → the weight of every one of the router's experts for
    every token ``[S, R]``: sigmoid scores; the ``num_experts_per_tok``
    largest of score + bias chosen; the chosen scores divided by their sum
    where ``norm_topk_prob``, times ``routed_scaling_factor``; 0
    elsewhere."""
    score = jax.nn.sigmoid(h @ m["wg"].astype(F32))
    biased = score + m["bias"].astype(F32)
    top, _ = jax.lax.top_k(biased, cfg["num_experts_per_tok"])
    chosen = jnp.where(biased >= top[:, -1:], score, 0.0)
    if cfg["norm_topk_prob"]:
        chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return chosen * cfg["routed_scaling_factor"]


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate.astype(F32)) * (h @ w_up.astype(F32))) \
        @ w_down.astype(F32)


def experts(h, m, cfg, layer):
    """h [S, H] (normed) → the Experts [S, H]: the held experts one at a
    time where they lie (``w_gate`` / ``w_up`` / ``w_down`` are the whole
    stacks ``[L, held, …]``, this layer the ``layer``-th), then the shared
    expert."""
    held = m["w_up"].shape[1]
    first = cfg.get("expert_rank", 0) * held
    weight = routing(h, m, cfg)[:, first:first + held]            # [S, held]

    def one(y, e):
        out = _swiglu(h, *(m[name][layer, e] for name in WHOLE))
        return y + weight[:, e][:, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(held))
    shared = m["shared"]
    return y + _swiglu(h, shared["w_gate"], shared["w_up"], shared["w_down"])


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
            out, x @ cols.astype(F32), e * block, 1)

    return jax.lax.fori_loop(0, V // block, one,
                             jnp.zeros((x.shape[0], V), F32))


def forward(weights: Dict[str, Any], cfg: Dict[str, Any], ids) -> jnp.ndarray:
    """Token ids ``[B, S]`` → logits ``[B, S, V]`` in float32."""
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        def one(row):
            x = weights["embed"][row].astype(F32)
            at = {"attn": 0, "delta": 0}
            for l in range(cfg["num_hidden_layers"]):
                # the layer's leaves are cut out of its kind's stack
                kind = "attn" if l in cfg["gqa_layers"] else "delta"
                lp = jax.tree.map(lambda w: w[at[kind]], weights[kind])
                at[kind] += 1
                mixer = attention if kind == "attn" else delta
                x = x + mixer(_norm(x, lp["pre_norm"], eps), lp, cfg)
                moe = weights["moe"]
                lp = dict(jax.tree.map(
                    lambda w: w[l],
                    {n: w for n, w in moe.items() if n not in WHOLE}),
                    **{n: moe[n] for n in WHOLE})
                x = x + experts(_norm(x, lp["pre_norm"], eps), lp, cfg, l)
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
