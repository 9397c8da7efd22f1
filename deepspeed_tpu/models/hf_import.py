"""HF checkpoint import — map Hugging Face weights into the model zoo
(Llama, Mistral, Mixtral, OPT, BERT, Falcon-H1).

Capability anchor: reference users bring HF torch models directly
(``deepspeed.initialize(model=hf_model)``); this build's engine consumes
functional param pytrees instead, so checkpoint-level import is the parity
surface (SURVEY §7 hard-part 4: "HF-model story without torch").

The mapping is layout-only — HF stores ``[out, in]`` projection matrices
per layer; this zoo stores stacked ``[L, in, heads, head_dim]`` tensors so
``lax.scan`` consumes one leaf per weight.  RoPE conventions agree (both
use the GPT-NeoX half-split rotation), so no permutation is needed beyond
the reshape/transpose.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax.numpy as jnp
import numpy as np

from .llama import LlamaConfig


def _to_np(t: Any) -> np.ndarray:
    """torch tensor / np array → fp32 numpy without importing torch here."""
    if hasattr(t, "detach"):
        t = t.detach().float().cpu().numpy()
    return np.asarray(t, np.float32)


def _getter(hf_config: Any):
    """Uniform key access over an HF config object or a config.json dict."""
    return (hf_config.get if isinstance(hf_config, dict)
            else lambda k, d=None: getattr(hf_config, k, d))


def _load(model_name_or_path: str, config_fn, params_fn, model_cls=None,
          **config_overrides):
    """Shared load pipeline: AutoConfig → zoo config → from_pretrained →
    state-dict mapping.  ``transformers`` (torch CPU) handles safetensors
    and sharded bins uniformly."""
    from transformers import AutoConfig, AutoModelForCausalLM

    hf_cfg = AutoConfig.from_pretrained(model_name_or_path)
    config = config_fn(hf_cfg, **config_overrides)
    model = (model_cls or AutoModelForCausalLM).from_pretrained(
        model_name_or_path)
    try:
        params = params_fn(model.state_dict(), config)
    finally:
        del model
    return config, params


def config_from_hf(hf_config: Any, **overrides) -> LlamaConfig:
    """Build a :class:`LlamaConfig` from an HF ``LlamaConfig`` object or a
    plain dict (``config.json`` contents)."""
    get = _getter(hf_config)
    d = dict(
        vocab_size=int(get("vocab_size")),
        hidden_size=int(get("hidden_size")),
        intermediate_size=int(get("intermediate_size")),
        num_layers=int(get("num_hidden_layers")),
        num_heads=int(get("num_attention_heads")),
        num_kv_heads=int(get("num_key_value_heads",
                             get("num_attention_heads"))),
        max_seq_len=int(get("max_position_embeddings", 4096)),
        rope_theta=float(get("rope_theta", 10000.0)),
        rms_norm_eps=float(get("rms_norm_eps", 1e-5)),
        tie_embeddings=bool(get("tie_word_embeddings", False)),
    )
    hd = get("head_dim")
    if hd is not None and int(hd) != d["hidden_size"] // d["num_heads"]:
        d["head_dim"] = int(hd)
    sw = get("sliding_window")
    if sw is not None:
        d["sliding_window"] = int(sw)
    d.update(overrides)
    return LlamaConfig(**d)


def params_from_hf_state_dict(state_dict: Dict[str, Any],
                              config: LlamaConfig) -> Dict[str, Any]:
    """HF ``LlamaForCausalLM`` state dict → this zoo's stacked param pytree."""
    c = config
    H, L = c.hidden_size, c.num_layers
    nh, nkv, hd = c.num_heads, c.num_kv_heads, c.hd

    def w(name):
        key = f"model.layers.{{i}}.{name}.weight"
        return [_to_np(state_dict[key.format(i=i)]) for i in range(L)]

    # HF proj weights are [out, in]; ours are [in, ...out-structured]
    wq = np.stack([m.T.reshape(H, nh, hd) for m in w("self_attn.q_proj")])
    wk = np.stack([m.T.reshape(H, nkv, hd) for m in w("self_attn.k_proj")])
    wv = np.stack([m.T.reshape(H, nkv, hd) for m in w("self_attn.v_proj")])
    wo = np.stack([m.T.reshape(nh, hd, H) for m in w("self_attn.o_proj")])
    w_gate = np.stack([m.T for m in w("mlp.gate_proj")])
    w_up = np.stack([m.T for m in w("mlp.up_proj")])
    w_down = np.stack([m.T for m in w("mlp.down_proj")])
    attn_norm = np.stack(w("input_layernorm"))
    mlp_norm = np.stack(w("post_attention_layernorm"))

    params = {
        "embed": _to_np(state_dict["model.embed_tokens.weight"]),
        "layers": {
            "attn": {"wq": jnp.asarray(wq), "wk": jnp.asarray(wk),
                     "wv": jnp.asarray(wv), "wo": jnp.asarray(wo)},
            "mlp": {"w_gate": jnp.asarray(w_gate),
                    "w_up": jnp.asarray(w_up),
                    "w_down": jnp.asarray(w_down)},
            "attn_norm": jnp.asarray(attn_norm),
            "mlp_norm": jnp.asarray(mlp_norm),
        },
        "final_norm": jnp.asarray(_to_np(state_dict["model.norm.weight"])),
    }
    params["embed"] = jnp.asarray(params["embed"])
    if not c.tie_embeddings:
        key = ("lm_head.weight" if "lm_head.weight" in state_dict
               else "model.embed_tokens.weight")
        params["lm_head"] = jnp.asarray(_to_np(state_dict[key]).T)
    return params


def load_hf_llama(model_name_or_path: str, **config_overrides
                  ) -> Tuple[LlamaConfig, Dict[str, Any]]:
    """Load an HF Llama checkpoint directory into (config, params).

    Uses ``transformers`` (torch CPU) for robust format handling —
    safetensors and sharded bins both resolve through ``from_pretrained``.
    """
    return _load(model_name_or_path, config_from_hf,
                 params_from_hf_state_dict, **config_overrides)


# ---------------------------------------------------------------------------
# Mistral — same layout as Llama (HF MistralForCausalLM shares the module
# names), plus the sliding-window config key
# ---------------------------------------------------------------------------

def load_hf_mistral(model_name_or_path: str, **config_overrides
                    ) -> Tuple[LlamaConfig, Dict[str, Any]]:
    """HF Mistral checkpoint → (LlamaConfig-with-window, params).  The zoo
    serves Mistral through :class:`LlamaModel` (sliding_window set)."""
    return _load(model_name_or_path, config_from_hf,
                 params_from_hf_state_dict, **config_overrides)


# ---------------------------------------------------------------------------
# Mixtral — Llama attention + block-sparse MoE experts
# ---------------------------------------------------------------------------

def config_from_hf_mixtral(hf_config: Any, **overrides):
    from .mixtral import MixtralConfig

    get = _getter(hf_config)
    d = dict(
        vocab_size=int(get("vocab_size")),
        hidden_size=int(get("hidden_size")),
        intermediate_size=int(get("intermediate_size")),
        num_layers=int(get("num_hidden_layers")),
        num_heads=int(get("num_attention_heads")),
        num_kv_heads=int(get("num_key_value_heads",
                             get("num_attention_heads"))),
        max_seq_len=int(get("max_position_embeddings", 4096)),
        rope_theta=float(get("rope_theta", 10000.0)),
        rms_norm_eps=float(get("rms_norm_eps", 1e-5)),
        tie_embeddings=bool(get("tie_word_embeddings", False)),
        num_experts=int(get("num_local_experts", 8)),
        top_k=int(get("num_experts_per_tok", 2)),
    )
    d.update(overrides)
    return MixtralConfig(**d)


def params_from_hf_mixtral_state_dict(state_dict: Dict[str, Any],
                                      config: Any) -> Dict[str, Any]:
    """HF ``MixtralForCausalLM`` state dict → stacked params: the dense
    Llama attention mapping plus ``moe`` (router + expert-stacked FFN;
    HF per-expert w1/w3/w2 = gate/up/down, each ``[I, H]``/``[H, I]``)."""
    c = config
    H, L, E = c.hidden_size, c.num_layers, c.num_experts
    nh, nkv, hd = c.num_heads, c.num_kv_heads, c.hd

    def w(name):
        key = f"model.layers.{{i}}.{name}.weight"
        return [_to_np(state_dict[key.format(i=i)]) for i in range(L)]

    wq = np.stack([m.T.reshape(H, nh, hd) for m in w("self_attn.q_proj")])
    wk = np.stack([m.T.reshape(H, nkv, hd) for m in w("self_attn.k_proj")])
    wv = np.stack([m.T.reshape(H, nkv, hd) for m in w("self_attn.v_proj")])
    wo = np.stack([m.T.reshape(nh, hd, H) for m in w("self_attn.o_proj")])
    wg = np.stack([m.T for m in w("block_sparse_moe.gate")])  # [L, H, E]

    def experts(proj):
        out = []
        for i in range(L):
            per = [_to_np(state_dict[
                f"model.layers.{i}.block_sparse_moe.experts.{e}."
                f"{proj}.weight"]).T for e in range(E)]
            out.append(np.stack(per))
        return np.stack(out)

    params = {
        "embed": jnp.asarray(_to_np(state_dict["model.embed_tokens.weight"])),
        "layers": {
            "attn": {"wq": jnp.asarray(wq), "wk": jnp.asarray(wk),
                     "wv": jnp.asarray(wv), "wo": jnp.asarray(wo)},
            "moe": {
                "wg": jnp.asarray(wg),
                "w_gate": jnp.asarray(experts("w1")),  # [L, E, H, I]
                "w_up": jnp.asarray(experts("w3")),    # [L, E, H, I]
                "w_down": jnp.asarray(experts("w2")),  # [L, E, I, H]
            },
            "attn_norm": jnp.asarray(np.stack(w("input_layernorm"))),
            "mlp_norm": jnp.asarray(np.stack(w("post_attention_layernorm"))),
        },
        "final_norm": jnp.asarray(_to_np(state_dict["model.norm.weight"])),
    }
    if not c.tie_embeddings:
        key = ("lm_head.weight" if "lm_head.weight" in state_dict
               else "model.embed_tokens.weight")
        params["lm_head"] = jnp.asarray(_to_np(state_dict[key]).T)
    return params


def load_hf_mixtral(model_name_or_path: str, **config_overrides):
    return _load(model_name_or_path, config_from_hf_mixtral,
                 params_from_hf_mixtral_state_dict, **config_overrides)


# ---------------------------------------------------------------------------
# OPT — pre-LN decoder with learned positions (HF offset-2 table maps 1:1)
# ---------------------------------------------------------------------------

def config_from_hf_opt(hf_config: Any, **overrides):
    from .opt import OPTConfig

    get = _getter(hf_config)
    if get("do_layer_norm_before", True) is False:
        raise NotImplementedError(
            "this OPT implementation is pre-LN; post-LN variants "
            "(do_layer_norm_before=false, e.g. opt-350m) are not supported")
    proj = get("word_embed_proj_dim")
    if proj is not None and int(proj) != int(get("hidden_size")):
        raise NotImplementedError(
            f"word_embed_proj_dim {proj} != hidden_size "
            f"{get('hidden_size')} (project_in/out variants like opt-350m "
            "are not supported)")
    d = dict(
        vocab_size=int(get("vocab_size")),
        hidden_size=int(get("hidden_size")),
        ffn_dim=int(get("ffn_dim")),
        num_layers=int(get("num_hidden_layers")),
        num_heads=int(get("num_attention_heads")),
        max_seq_len=int(get("max_position_embeddings", 2048)),
    )
    d.update(overrides)
    return OPTConfig(**d)


def params_from_hf_opt_state_dict(state_dict: Dict[str, Any],
                                  config: Any) -> Dict[str, Any]:
    """HF ``OPTForCausalLM`` state dict → stacked params.  HF's learned
    position table already carries the legacy offset-2 rows, matching this
    zoo's ``POSITION_OFFSET`` layout row-for-row."""
    c = config
    H, L = c.hidden_size, c.num_layers
    nh, hd = c.num_heads, c.hd
    pre = "model.decoder."

    def w(name):
        return [_to_np(state_dict[f"{pre}layers.{i}.{name}.weight"])
                for i in range(L)]

    def b(name):
        return [_to_np(state_dict[f"{pre}layers.{i}.{name}.bias"])
                for i in range(L)]

    return {
        "embed": jnp.asarray(_to_np(state_dict[pre + "embed_tokens.weight"])),
        "pos_embed": jnp.asarray(
            _to_np(state_dict[pre + "embed_positions.weight"])),
        "layers": {
            "attn": {
                "wq": jnp.asarray(np.stack(
                    [m.T.reshape(H, nh, hd) for m in w("self_attn.q_proj")])),
                "wk": jnp.asarray(np.stack(
                    [m.T.reshape(H, nh, hd) for m in w("self_attn.k_proj")])),
                "wv": jnp.asarray(np.stack(
                    [m.T.reshape(H, nh, hd) for m in w("self_attn.v_proj")])),
                "wo": jnp.asarray(np.stack(
                    [m.T.reshape(nh, hd, H)
                     for m in w("self_attn.out_proj")])),
                "bq": jnp.asarray(np.stack(
                    [v.reshape(nh, hd) for v in b("self_attn.q_proj")])),
                "bk": jnp.asarray(np.stack(
                    [v.reshape(nh, hd) for v in b("self_attn.k_proj")])),
                "bv": jnp.asarray(np.stack(
                    [v.reshape(nh, hd) for v in b("self_attn.v_proj")])),
                "bo": jnp.asarray(np.stack(b("self_attn.out_proj"))),
            },
            "mlp": {
                "w_in": jnp.asarray(np.stack([m.T for m in w("fc1")])),
                "b_in": jnp.asarray(np.stack(b("fc1"))),
                "w_out": jnp.asarray(np.stack([m.T for m in w("fc2")])),
                "b_out": jnp.asarray(np.stack(b("fc2"))),
            },
            "attn_ln_w": jnp.asarray(np.stack(w("self_attn_layer_norm"))),
            "attn_ln_b": jnp.asarray(np.stack(b("self_attn_layer_norm"))),
            "mlp_ln_w": jnp.asarray(np.stack(w("final_layer_norm"))),
            "mlp_ln_b": jnp.asarray(np.stack(b("final_layer_norm"))),
        },
        "final_ln_w": jnp.asarray(
            _to_np(state_dict[pre + "final_layer_norm.weight"])),
        "final_ln_b": jnp.asarray(
            _to_np(state_dict[pre + "final_layer_norm.bias"])),
    }


def load_hf_opt(model_name_or_path: str, **config_overrides):
    return _load(model_name_or_path, config_from_hf_opt,
                 params_from_hf_opt_state_dict, **config_overrides)


# ---------------------------------------------------------------------------
# BERT — post-LN encoder + tied MLM head
# ---------------------------------------------------------------------------

def config_from_hf_bert(hf_config: Any, **overrides):
    from .bert import BertConfig

    get = _getter(hf_config)
    d = dict(
        vocab_size=int(get("vocab_size")),
        hidden_size=int(get("hidden_size")),
        intermediate_size=int(get("intermediate_size")),
        num_layers=int(get("num_hidden_layers")),
        num_heads=int(get("num_attention_heads")),
        max_seq_len=int(get("max_position_embeddings", 512)),
        type_vocab_size=int(get("type_vocab_size", 2)),
        layer_norm_eps=float(get("layer_norm_eps", 1e-12)),
    )
    d.update(overrides)
    return BertConfig(**d)


def params_from_hf_bert_state_dict(state_dict: Dict[str, Any],
                                   config: Any) -> Dict[str, Any]:
    """HF ``BertForMaskedLM`` state dict → stacked params (post-LN:
    ``attention.output.LayerNorm``/``output.LayerNorm`` land on the
    post-residual norms; the MLM decoder is tied to the word embedding,
    with its standalone bias imported)."""
    c = config
    H, L = c.hidden_size, c.num_layers
    nh, hd = c.num_heads, c.hd
    enc = "bert.encoder.layer.{i}."

    def w(name):
        return [_to_np(state_dict[(enc + name + ".weight").format(i=i)])
                for i in range(L)]

    def b(name):
        return [_to_np(state_dict[(enc + name + ".bias").format(i=i)])
                for i in range(L)]

    emb = "bert.embeddings."
    return {
        "embed": {
            "word": jnp.asarray(
                _to_np(state_dict[emb + "word_embeddings.weight"])),
            "position": jnp.asarray(
                _to_np(state_dict[emb + "position_embeddings.weight"])),
            "token_type": jnp.asarray(
                _to_np(state_dict[emb + "token_type_embeddings.weight"])),
            "ln_w": jnp.asarray(_to_np(state_dict[emb + "LayerNorm.weight"])),
            "ln_b": jnp.asarray(_to_np(state_dict[emb + "LayerNorm.bias"])),
        },
        "layers": {
            "attn": {
                "wq": jnp.asarray(np.stack(
                    [m.T.reshape(H, nh, hd)
                     for m in w("attention.self.query")])),
                "wk": jnp.asarray(np.stack(
                    [m.T.reshape(H, nh, hd)
                     for m in w("attention.self.key")])),
                "wv": jnp.asarray(np.stack(
                    [m.T.reshape(H, nh, hd)
                     for m in w("attention.self.value")])),
                "wo": jnp.asarray(np.stack(
                    [m.T.reshape(nh, hd, H)
                     for m in w("attention.output.dense")])),
                "bq": jnp.asarray(np.stack(
                    [v.reshape(nh, hd) for v in b("attention.self.query")])),
                "bk": jnp.asarray(np.stack(
                    [v.reshape(nh, hd) for v in b("attention.self.key")])),
                "bv": jnp.asarray(np.stack(
                    [v.reshape(nh, hd) for v in b("attention.self.value")])),
                "bo": jnp.asarray(np.stack(b("attention.output.dense"))),
            },
            "mlp": {
                "w_in": jnp.asarray(np.stack(
                    [m.T for m in w("intermediate.dense")])),
                "b_in": jnp.asarray(np.stack(b("intermediate.dense"))),
                "w_out": jnp.asarray(np.stack(
                    [m.T for m in w("output.dense")])),
                "b_out": jnp.asarray(np.stack(b("output.dense"))),
            },
            "attn_ln_w": jnp.asarray(np.stack(
                w("attention.output.LayerNorm"))),
            "attn_ln_b": jnp.asarray(np.stack(
                b("attention.output.LayerNorm"))),
            "mlp_ln_w": jnp.asarray(np.stack(w("output.LayerNorm"))),
            "mlp_ln_b": jnp.asarray(np.stack(b("output.LayerNorm"))),
        },
        "mlm": {
            "w": jnp.asarray(_to_np(
                state_dict["cls.predictions.transform.dense.weight"]).T),
            "b": jnp.asarray(_to_np(
                state_dict["cls.predictions.transform.dense.bias"])),
            "ln_w": jnp.asarray(_to_np(
                state_dict["cls.predictions.transform.LayerNorm.weight"])),
            "ln_b": jnp.asarray(_to_np(
                state_dict["cls.predictions.transform.LayerNorm.bias"])),
            "bias": jnp.asarray(_to_np(state_dict["cls.predictions.bias"])),
        },
    }


def load_hf_bert(model_name_or_path: str, **config_overrides):
    from transformers import BertForMaskedLM

    return _load(model_name_or_path, config_from_hf_bert,
                 params_from_hf_bert_state_dict, model_cls=BertForMaskedLM,
                 **config_overrides)


# ---------------------------------------------------------------------------
# Falcon-H1 — a Mamba-2 mixer beside attention in every layer (layout only:
# the µP multipliers stay the config's, none is folded into a weight)
# ---------------------------------------------------------------------------

def config_from_hf_falcon_h1(hf_config: Any, **overrides):
    from .falcon_h1 import FalconH1Config

    get = _getter(hf_config)
    for key, want in (("mamba_rms_norm", True), ("mamba_conv_bias", True),
                      ("mamba_proj_bias", False), ("attention_bias", False),
                      ("mlp_bias", False), ("projectors_bias", False),
                      ("tie_word_embeddings", False)):
        if bool(get(key, want)) != want:
            raise NotImplementedError(
                f"this Falcon-H1 implementation has {key}={want}")
    heads, d_head = int(get("mamba_n_heads")), int(get("mamba_d_head"))
    if get("mamba_d_ssm") is not None and int(get("mamba_d_ssm")) \
            != heads * d_head:
        raise NotImplementedError("mamba_d_ssm is not heads x head size")
    d = dict(
        vocab_size=int(get("vocab_size")),
        hidden_size=int(get("hidden_size")),
        intermediate_size=int(get("intermediate_size")),
        num_layers=int(get("num_hidden_layers")),
        num_heads=int(get("num_attention_heads")),
        num_kv_heads=int(get("num_key_value_heads")),
        head_dim=int(get("head_dim") or int(get("hidden_size"))
                     // int(get("num_attention_heads"))),
        rope_theta=float(get("rope_theta")),
        rms_norm_eps=float(get("rms_norm_eps")),
        mamba_n_heads=heads, mamba_d_head=d_head,
        mamba_d_state=int(get("mamba_d_state")),
        mamba_n_groups=int(get("mamba_n_groups")),
        mamba_d_conv=int(get("mamba_d_conv")),
        mamba_chunk_size=int(get("mamba_chunk_size")),
        mamba_norm_before_gate=bool(get("mamba_norm_before_gate")),
        ssm_multipliers=tuple(float(m) for m in get("ssm_multipliers")),
        mlp_multipliers=tuple(float(m) for m in get("mlp_multipliers")),
        max_seq_len=int(get("max_position_embeddings")),
    )
    d.update({key: float(get(key)) for key in (
        "embedding_multiplier", "lm_head_multiplier",
        "attention_in_multiplier", "attention_out_multiplier",
        "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier")})
    d.update(overrides)
    return FalconH1Config(**d)


def params_from_hf_falcon_h1_state_dict(state_dict: Dict[str, Any],
                                        config: Any) -> Dict[str, Any]:
    """``FalconH1ForCausalLM.state_dict()`` → this zoo's stacked tree.
    HF's ``in_proj`` rows are ``[z | xs | B | C | dt]`` as here; its conv
    weight ``[conv_dim, 1, K]`` is held ``[K, conv_dim]``."""
    c = config
    h, kv, d = c.num_heads, c.num_kv_heads, c.head_dim

    def stack(name, fn=lambda w: w):
        return jnp.asarray(np.stack([
            fn(_to_np(state_dict[f"model.layers.{l}.{name}"]))
            for l in range(c.num_layers)]))

    heads_in = lambda n: (lambda w: w.T.reshape(c.hidden_size, n, d))
    return {
        "embed": jnp.asarray(_to_np(state_dict["model.embed_tokens.weight"])),
        "layers": {
            "attn_norm": stack("input_layernorm.weight"),
            "mlp_norm": stack("pre_ff_layernorm.weight"),
            "attn": {
                "wq": stack("self_attn.q_proj.weight", heads_in(h)),
                "wk": stack("self_attn.k_proj.weight", heads_in(kv)),
                "wv": stack("self_attn.v_proj.weight", heads_in(kv)),
                "wo": stack("self_attn.o_proj.weight",
                            lambda w: w.T.reshape(h, d, c.hidden_size))},
            "ssm": {
                "in_proj": stack("mamba.in_proj.weight", lambda w: w.T),
                "conv_w": stack("mamba.conv1d.weight",
                                lambda w: w[:, 0, :].T),
                "conv_b": stack("mamba.conv1d.bias"),
                "dt_bias": stack("mamba.dt_bias"),
                "A_log": stack("mamba.A_log"),
                "D": stack("mamba.D"),
                "norm": stack("mamba.norm.weight"),
                "out_proj": stack("mamba.out_proj.weight", lambda w: w.T)},
            "mlp": {
                "w_gate": stack("feed_forward.gate_proj.weight",
                                lambda w: w.T),
                "w_up": stack("feed_forward.up_proj.weight", lambda w: w.T),
                "w_down": stack("feed_forward.down_proj.weight",
                                lambda w: w.T)}},
        "final_norm": jnp.asarray(
            _to_np(state_dict["model.final_layernorm.weight"])),
        "lm_head": jnp.asarray(_to_np(state_dict["lm_head.weight"]).T),
    }


def load_hf_falcon_h1(model_name_or_path: str, **config_overrides):
    return _load(model_name_or_path, config_from_hf_falcon_h1,
                 params_from_hf_falcon_h1_state_dict, **config_overrides)
