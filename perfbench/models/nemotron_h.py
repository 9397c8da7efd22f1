"""The ``nemotron_h`` family (``model_type`` of the published config): how
the program builds it, what one trained token costs, and its plain
reference.

The reference is float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``: no kernel, no cache, no chunk
form, no state pool, no sorted expert layout.  It follows the published
config (``nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16``, ``config.json``)
and the catalog's description of the family.  ``N`` is RMSNorm with a
learned weight and eps ``norm_eps``.  EVERY LAYER IS ONE PART ALONE, named
by its character of ``hybrid_override_pattern``; layer ``l`` of a sequence
``x [S, H]`` is ``x += Part(N(x))`` with ``h = N(x)`` and

    M, a Mamba-2 mixer, D = mamba_num_heads · mamba_head_dim:
      [z D | xBC D + 2·n_groups·ssm_state_size | dt mamba_num_heads] = h W_in
      xBC_t ← silu(Σ_j w_j ⊙ xBC_{t−(K−1)+j} + b),  K = conv_kernel, zeros
          before the sequence's first token (b where use_conv_bias)
      xBC = [xs | B | C];  head n reads group n // (heads / n_groups)
      Δ_t = softplus(dt_t + dt_bias) a head,  A = −exp(A_log)
      TOKEN BY TOKEN, from S = 0:
          S ← exp(Δ_t A) S + Δ_t · xs_t B_tᵀ        [head_dim, state]
          y_t = S C_t + D xs_t
      g = N_groups(y ⊙ silu(z))   gate first; one statistic a group of
          D / n_groups
      Part = g W_out
    *, attention, a head at a time (head n reads KV head n // (heads/kv)):
      q = h W_q, k = h W_k, v = h W_v;  NO rotary
      Part = concat_n(softmax_causal(q kᵀ / sqrt(head_dim)) v) W_o
    E, LatentMoE:
      s = sigmoid(h W_r) over the PUBLISHED expert count, float32
      chosen = the num_experts_per_tok largest of s + b (b a choice bias;
          n_group 1: no group limit)
      g_e = routed_scaling_factor · s_e / Σ_chosen s   (norm_topk_prob)
      u = h W_↓ ∈ R^w,  w = moe_latent_size
      r = Σ_e g_e · relu(u W1_e)² W2_e,   W1_e [w, I], W2_e [I, w]
      Part = r W_↑ + relu(h V1)² V2        (the shared expert reads h)

then the final RMSNorm and ``logits = x W_head``, untied.  Where the file
holds a SHARE of the experts (``n_routed_experts`` under
``published.n_routed_experts``: experts ``expert_rank · held`` onwards),
``r`` sums the held experts alone, one at a time where they lie, and the
rest of the layer is whole: ``W_↑`` is linear, so the shares' parts add up
to the uncut layer's with the shared expert counted once.

The mixer is the RECURRENCE itself (a ``lax.scan`` over time), so that the
program's chunk form and its one-token update are held against something
that is neither.  Two keys that are no configuration's and that ``build``
does not read, set by the controls of the check alone
(``tests/perfbench_tests/nemotron_h_control.py``):
``control_state_dropped`` (the recurrence forgets: ``S ← Δ_t xs_t B_tᵀ``)
and ``control_state_held_in`` (the state rounded to that type after every
token).

Departures from the published description, each under ``assumed`` in the
configuration's file: the weights are random; ``A_log``, ``dt_bias``,
``D``, the conv's bias, the choice bias and every norm's weight are drawn
away from their initial constants; a routed expert's ``W2`` is drawn
``num_experts_per_tok / 2`` times smaller; no rotary; the multi-token
prediction module is not built.

Independent of ``deepspeed_tpu/models``: it shares only the layout of the
weight tree, a stack a PART: ``mixer: {pre_norm [M, H], in_proj [M, H, P],
conv_w [M, K, conv_dim], conv_b [M, conv_dim], dt_bias, A_log, D [M,
heads], norm [M, D], out_proj [M, D, H]}``, ``attn: {pre_norm [A, H], wq
[A, H, h, d], wk, wv [A, H, kv, d], wo [A, h, d, H]}``, ``moe: {pre_norm
[E, H], wg [E, H, experts], bias [E, experts], latent_down [E, H, w],
latent_up [E, w, H], w_up [E, held, w, I], w_down [E, held, I, w],
shared_up [E, H, S], shared_down [E, S, H]}``, ``embed [V, H]``,
``final_norm [H]``, ``lm_head [H, V]``; a layer is its part's next.

The weights come as the cell holds them (bfloat16 in serving) and are
widened to float32 as they are used (exact): a layer at a time, a head's
slices inside the loop over heads, an expert at a time, the shared expert
and the head in column blocks (the sum over blocks is the same sum), so
that a 3,000-token request fits beside the server.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: a pattern's characters → the stack a layer of that part lies in
STACKS = {"M": "mixer", "*": "attn", "E": "moe"}
#: the leaves read where they lie in their stack, an expert at a time
WHOLE = ("w_up", "w_down")


# -- the program's model -----------------------------------------------------

def build(cfg: Dict[str, Any], mesh: Any = None) -> Any:
    from deepspeed_tpu.models import NemotronHConfig, NemotronHModel

    for key, want in (("use_conv_bias", True), ("use_bias", False),
                      ("mamba_proj_bias", False), ("attention_bias", False),
                      ("mlp_bias", False), ("tie_word_embeddings", False),
                      ("mlp_hidden_act", "relu2"), ("n_group", 1),
                      ("mamba_hidden_act", "silu"), ("n_shared_experts", 1),
                      ("sliding_window", None)):
        if cfg.get(key, want) != want:
            raise SystemExit(f"perfbench: the program's layer has {key} "
                             f"{want!r}; {cfg[key]!r} is another model")
    if len(cfg["hybrid_override_pattern"]) != cfg["num_hidden_layers"]:
        raise SystemExit("perfbench: hybrid_override_pattern is not as long "
                         "as num_hidden_layers")
    held = cfg["n_routed_experts"]
    published = cfg.get("published", {}).get("n_routed_experts", held)
    return NemotronHModel(NemotronHConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        pattern=cfg["hybrid_override_pattern"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        mamba_num_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"],
        ssm_state_size=cfg["ssm_state_size"], n_groups=cfg["n_groups"],
        conv_kernel=cfg["conv_kernel"], chunk_size=cfg["chunk_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        moe_latent_size=cfg["moe_latent_size"],
        moe_shared_expert_intermediate_size=cfg[
            "moe_shared_expert_intermediate_size"],
        num_experts=published, top_k=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        held_experts=(cfg.get("expert_rank", 0) * held, held),
        norm_eps=cfg["norm_eps"], max_seq_len=cfg["max_position_embeddings"],
        dtype=getattr(jnp, cfg["run"]["dtype"])), mesh=mesh)


# -- operations --------------------------------------------------------------

def part_weights(cfg: Dict[str, Any]) -> Dict[str, int]:
    """The numbers of the matrices a token of the MODEL passes in one layer
    of each part: a mixer's two projections and its conv; attention's four;
    an expert layer's router at its published width, latent projections,
    shared expert and ``num_experts_per_tok`` routed experts of two
    matrices at the latent's width, wherever they live."""
    H, d = cfg["hidden_size"], cfg["head_dim"]
    D = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    conv_dim = D + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    w, I = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    experts = cfg.get("published", {}).get("n_routed_experts",
                                           cfg["n_routed_experts"])
    return {
        "M": H * (D + conv_dim + cfg["mamba_num_heads"]) + D * H
        + conv_dim * cfg["conv_kernel"],
        "*": 2 * H * d * (cfg["num_attention_heads"]
                          + cfg["num_key_value_heads"]),
        "E": H * experts + 2 * H * w
        + 2 * H * cfg["moe_shared_expert_intermediate_size"]
        * cfg["n_shared_experts"] + cfg["num_experts_per_tok"] * 2 * w * I}


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward plus backward (twice the forward): two operations a weight a
    token through the layers that are run, each by its one part, and the
    head; attention's QKᵀ and PV over a causal mean query's keys in the
    attention layers; the recurrence's own operations a token in the mixer
    layers (decay, outer product and read-out: six a state element)."""
    pattern = cfg["hybrid_override_pattern"]
    weights = part_weights(cfg)
    keys = (seq + 1) / 2.0
    attention = 2 * 2 * keys * cfg["num_attention_heads"] * cfg["head_dim"]
    scan = 6.0 * cfg["mamba_num_heads"] * cfg["mamba_head_dim"] \
        * cfg["ssm_state_size"]
    return 3.0 * (2 * (cfg["hidden_size"] * cfg["vocab_size"]
                       + sum(weights[part] for part in pattern))
                  + pattern.count("*") * attention
                  + pattern.count("M") * scan)


# -- the plain reference -----------------------------------------------------

def _norm(x, w, eps):
    """RMSNorm of ``x`` over its last axis under the weight ``w``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def _block(n: int, most: int) -> int:
    """The largest divisor of ``n`` that is at most ``most``."""
    return next(b for b in range(min(n, most), 0, -1) if n % b == 0)


def _positions(q, k):
    """What the family does to queries and keys by position: nothing."""
    return q, k


def attention(h, a, cfg, layer=None):
    """h [S, H] (normed) → the attention's Part [S, H]: a head at a time,
    this head's slices widened here."""
    del layer       # its leaves are the layer's own
    S = h.shape[0]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]

    def one_head(n):
        g = n // (heads // kv)
        q, k = _positions(h @ a["wq"][:, n].astype(F32),
                          h @ a["wk"][:, g].astype(F32))
        v = h @ a["wv"][:, g].astype(F32)
        s = jnp.where(j <= i, q @ k.T / jnp.sqrt(F32(cfg["head_dim"])),
                      -jnp.inf)
        return (jax.nn.softmax(s, axis=-1) @ v) @ a["wo"][n].astype(F32)

    return jax.lax.fori_loop(0, heads, lambda n, acc: acc + one_head(n),
                             jnp.zeros_like(h))


def mixer(h, m, cfg, layer=None):
    """h [S, H] (normed) → the mixer's Part [S, H]: the recurrence token by
    token from a zero state."""
    del layer       # its leaves are the layer's own
    S = h.shape[0]
    heads, P, N, G, K = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                         cfg["ssm_state_size"], cfg["n_groups"],
                         cfg["conv_kernel"])
    D, bc = heads * P, G * N
    held = getattr(jnp, cfg.get("control_state_held_in", "float32"))
    keeps = 0.0 if cfg.get("control_state_dropped") else 1.0
    p = h @ m["in_proj"].astype(F32)
    z, xbc, dt = p[:, :D], p[:, D:2 * D + 2 * bc], p[:, 2 * D + 2 * bc:]
    # output t sums inputs t-(K-1) … t, zeros before the first token
    padded = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    conv = sum(padded[j:j + S] * m["conv_w"][j].astype(F32) for j in range(K))
    if cfg.get("use_conv_bias", True):
        conv = conv + m["conv_b"].astype(F32)
    conv = jax.nn.silu(conv)
    xs = conv[:, :D].reshape(S, heads, P)
    # head n reads its group's B and C
    B = jnp.repeat(conv[:, D:D + bc].reshape(S, G, N), heads // G, 1)
    C = jnp.repeat(conv[:, D + bc:].reshape(S, G, N), heads // G, 1)
    delta = jax.nn.softplus(dt + m["dt_bias"].astype(F32))        # [S, heads]
    A = -jnp.exp(m["A_log"].astype(F32))

    def token(state, t):
        x_t, B_t, C_t, d_t = t
        state = keeps * jnp.exp(d_t * A)[:, None, None] * state \
            + (d_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        state = state.astype(held).astype(F32)
        return state, jnp.sum(state * C_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((heads, P, N), F32),
                        (xs, B, C, delta))
    y = (y + m["D"].astype(F32)[:, None] * xs).reshape(S, G, D // G)
    gate = jax.nn.silu(z).reshape(S, G, D // G)
    g = _norm(y * gate, m["norm"].reshape(G, D // G), cfg["norm_eps"])
    return g.reshape(S, D) @ m["out_proj"].astype(F32)


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


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _latent(h, m):
    """What the routed experts read: the latent ``u = h W_↓``."""
    return h @ m["latent_down"].astype(F32)


def experts(h, m, cfg, layer):
    """h [S, H] (normed) → the expert layer's Part [S, H]: the held
    experts one at a time where they lie (``w_up`` / ``w_down`` are the
    whole stacks ``[E layers, held, …]``, this layer the ``layer``-th), in
    the latent; the shared expert at the hidden width in column blocks."""
    held = m["w_up"].shape[1]
    first = cfg.get("expert_rank", 0) * held
    weight = routing(h, m, cfg)[:, first:first + held]            # [S, held]
    u = _latent(h, m)

    def one(r, e):
        out = _relu2(u @ m["w_up"][layer, e].astype(F32)) \
            @ m["w_down"][layer, e].astype(F32)
        return r + weight[:, e][:, None] * out, None

    r, _ = jax.lax.scan(one, jnp.zeros_like(u), jnp.arange(held))
    S_ = m["shared_up"].shape[1]
    block = _block(S_, 2048)
    cols = lambda w, e, axis: jax.lax.dynamic_slice_in_dim(
        w, e * block, block, axis).astype(F32)

    def shared(y, e):
        return y + _relu2(h @ cols(m["shared_up"], e, 1)) \
            @ cols(m["shared_down"], e, 0), None

    y, _ = jax.lax.scan(shared, r @ m["latent_up"].astype(F32),
                        jnp.arange(S_ // block))
    return y


PARTS = {"M": mixer, "*": attention, "E": experts}


def head(x, weights, cfg):
    """x [S, H] → logits [S, V]: the head in column blocks, each written
    into the one result where it belongs."""
    x = _norm(x, weights["final_norm"], cfg["norm_eps"])
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
    with jax.default_matmul_precision("highest"):
        def one(row):
            x = weights["embed"][row].astype(F32)
            at = dict.fromkeys(STACKS, 0)
            for part in cfg["hybrid_override_pattern"]:
                # the layer's leaves are cut out of its part's stack
                lp = {name: w if name in WHOLE else w[at[part]]
                      for name, w in weights[STACKS[part]].items()}
                x = x + PARTS[part](_norm(x, lp["pre_norm"], cfg["norm_eps"]),
                                    lp, cfg, at[part])
                at[part] += 1
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
