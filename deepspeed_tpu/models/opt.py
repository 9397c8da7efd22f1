"""OPT-family decoder — completes the reference inference-v2 model list.

Capability anchor: ``deepspeed/inference/v2/model_implementations/opt/``
[K] ships OPT alongside llama/mistral/mixtral; this zoo mirrors that
coverage (llama + mistral preset + mixtral already exist).

Architecture deltas vs Llama (all expressed in the same functional
grammar): learned absolute position embeddings (HF OPT offsets them by 2
— kept for checkpoint compatibility), LayerNorm (with bias) instead of
RMSNorm, biased attention/MLP projections, ReLU MLP, pre-LN blocks with
a final layer norm, tied lm head.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..parallel.mesh import AXIS_SEQ, AXIS_TENSOR, DP_AXES
from ..runtime.activation_checkpointing import remat_policy
from .bert import _layer_norm
from .llama import _attention

P = PartitionSpec

#: HF OPT reserves positions 0/1 (pad/bos legacy) — positions start here
POSITION_OFFSET = 2


@dataclasses.dataclass(frozen=True)
class OPTConfig:
    vocab_size: int = 50272
    hidden_size: int = 768
    ffn_dim: int = 3072
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 2048
    layer_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True

    @property
    def hd(self) -> int:
        return self.hidden_size // self.num_heads

    @classmethod
    def tiny(cls, **kw) -> "OPTConfig":
        d = dict(vocab_size=512, hidden_size=128, ffn_dim=256,
                 num_layers=4, num_heads=8, max_seq_len=128)
        d.update(kw)
        return cls(**d)

    @classmethod
    def opt_1_3b(cls, **kw) -> "OPTConfig":
        d = dict(hidden_size=2048, ffn_dim=8192, num_layers=24,
                 num_heads=32)
        d.update(kw)
        return cls(**d)

    def num_params(self) -> int:
        H, F, V, L = (self.hidden_size, self.ffn_dim, self.vocab_size,
                      self.num_layers)
        per_layer = 4 * H * H + 4 * H + 2 * H * F + F + H + 4 * H
        return (V + self.max_seq_len + POSITION_OFFSET) * H + \
            L * per_layer + 2 * H


class OPTModel:
    """Functional OPT: tied-embedding causal LM."""

    aux_loss_coef: float = 0.0

    def __init__(self, config: OPTConfig, mesh: Optional[Mesh] = None):
        self.config = config
        self.mesh = mesh

    # ------------------------------------------------------------------

    def init_params(self, rng: jax.Array) -> Dict[str, Any]:
        c = self.config
        H, F, V, L = c.hidden_size, c.ffn_dim, c.vocab_size, c.num_layers
        nh, hd = c.num_heads, c.hd
        k = iter(jax.random.split(rng, 12))

        def normal(key, shape, fan_in):
            return (jax.random.normal(key, shape, jnp.float32)
                    * (1.0 / np.sqrt(fan_in))).astype(jnp.float32)

        return {
            "embed": normal(next(k), (V, H), H),
            "pos_embed": normal(
                next(k), (c.max_seq_len + POSITION_OFFSET, H), H),
            "layers": {
                "attn": {
                    "wq": normal(next(k), (L, H, nh, hd), H),
                    "wk": normal(next(k), (L, H, nh, hd), H),
                    "wv": normal(next(k), (L, H, nh, hd), H),
                    "wo": normal(next(k), (L, nh, hd, H), H),
                    "bq": jnp.zeros((L, nh, hd), jnp.float32),
                    "bk": jnp.zeros((L, nh, hd), jnp.float32),
                    "bv": jnp.zeros((L, nh, hd), jnp.float32),
                    "bo": jnp.zeros((L, H), jnp.float32),
                },
                "mlp": {
                    "w_in": normal(next(k), (L, H, F), H),
                    "b_in": jnp.zeros((L, F), jnp.float32),
                    "w_out": normal(next(k), (L, F, H), F),
                    "b_out": jnp.zeros((L, H), jnp.float32),
                },
                "attn_ln_w": jnp.ones((L, H), jnp.float32),
                "attn_ln_b": jnp.zeros((L, H), jnp.float32),
                "mlp_ln_w": jnp.ones((L, H), jnp.float32),
                "mlp_ln_b": jnp.zeros((L, H), jnp.float32),
            },
            "final_ln_w": jnp.ones((H,), jnp.float32),
            "final_ln_b": jnp.zeros((H,), jnp.float32),
        }

    def param_specs(self, params: Optional[Any] = None) -> Dict[str, Any]:
        t = AXIS_TENSOR
        return {
            "embed": P(None, None),
            "pos_embed": P(None, None),
            "layers": {
                "attn": {
                    "wq": P(None, None, t, None), "wk": P(None, None, t, None),
                    "wv": P(None, None, t, None), "wo": P(None, t, None, None),
                    "bq": P(None, t, None), "bk": P(None, t, None),
                    "bv": P(None, t, None), "bo": P(None, None),
                },
                "mlp": {
                    "w_in": P(None, None, t), "b_in": P(None, t),
                    "w_out": P(None, t, None), "b_out": P(None, None),
                },
                "attn_ln_w": P(None, None), "attn_ln_b": P(None, None),
                "mlp_ln_w": P(None, None), "mlp_ln_b": P(None, None),
            },
            "final_ln_w": P(None), "final_ln_b": P(None),
        }

    # ------------------------------------------------------------------

    def _constrain(self, x: jnp.ndarray, *spec) -> jnp.ndarray:
        if self.mesh is None:
            return x
        from ..parallel.mesh import strip_manual_axes

        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, strip_manual_axes(*spec)))

    def _attn_block(self, lp: Any, x: jnp.ndarray, mask) -> jnp.ndarray:
        c = self.config
        dt = c.dtype
        h = _layer_norm(x, lp["attn_ln_w"].astype(dt),
                        lp["attn_ln_b"].astype(dt), c.layer_norm_eps)
        q = jnp.einsum("bsH,Hhd->bshd", h, lp["attn"]["wq"].astype(dt)) \
            + lp["attn"]["bq"].astype(dt)
        kk = jnp.einsum("bsH,Hhd->bshd", h, lp["attn"]["wk"].astype(dt)) \
            + lp["attn"]["bk"].astype(dt)
        vv = jnp.einsum("bsH,Hhd->bshd", h, lp["attn"]["wv"].astype(dt)) \
            + lp["attn"]["bv"].astype(dt)
        q = self._constrain(q, DP_AXES, AXIS_SEQ, AXIS_TENSOR, None)
        kk = self._constrain(kk, DP_AXES, AXIS_SEQ, AXIS_TENSOR, None)
        vv = self._constrain(vv, DP_AXES, AXIS_SEQ, AXIS_TENSOR, None)
        attn = _attention(q, kk, vv, mask)
        out = jnp.einsum("bshd,hdH->bsH", attn, lp["attn"]["wo"].astype(dt)) \
            + lp["attn"]["bo"].astype(dt)
        return x + out

    def _mlp_block(self, lp: Any, x: jnp.ndarray) -> jnp.ndarray:
        c = self.config
        dt = c.dtype
        h = _layer_norm(x, lp["mlp_ln_w"].astype(dt),
                        lp["mlp_ln_b"].astype(dt), c.layer_norm_eps)
        from ..compression.quantization import maybe_quantize_activation

        h = jnp.einsum("bsH,HF->bsF", h, lp["mlp"]["w_in"].astype(dt)) \
            + lp["mlp"]["b_in"].astype(dt)
        h = maybe_quantize_activation(self, jax.nn.relu(h))
        h = self._constrain(h, DP_AXES, AXIS_SEQ, AXIS_TENSOR)
        h = jnp.einsum("bsF,FH->bsH", h, lp["mlp"]["w_out"].astype(dt)) \
            + lp["mlp"]["b_out"].astype(dt)
        return x + h

    def _check_len(self, S: int) -> None:
        # learned positions have a hard table bound; an OOB jnp.take fills
        # NaN silently, so fail loudly at trace time instead
        if S > self.config.max_seq_len:
            raise ValueError(
                f"sequence length {S} exceeds max_seq_len "
                f"{self.config.max_seq_len} (learned position table)")

    def _trunk(self, params: Any, input_ids: jnp.ndarray,
               positions: jnp.ndarray, mask) -> jnp.ndarray:
        c = self.config
        dt = c.dtype
        x = (jnp.take(params["embed"].astype(dt), input_ids, axis=0)
             + jnp.take(params["pos_embed"].astype(dt),
                        positions + POSITION_OFFSET, axis=0))
        x = self._constrain(x, DP_AXES, AXIS_SEQ, None)

        def layer(carry, lp):
            x = self._attn_block(lp, carry, mask)
            return self._mlp_block(lp, x), None

        body = layer
        if c.remat:
            body = jax.checkpoint(layer, policy=remat_policy())
        x, _ = jax.lax.scan(lambda carry, lp: body(carry, lp), x,
                            params["layers"])
        return _layer_norm(x, params["final_ln_w"].astype(dt),
                           params["final_ln_b"].astype(dt), c.layer_norm_eps)

    def forward(self, params: Any, input_ids: jnp.ndarray) -> jnp.ndarray:
        """[B, S] ids → [B, S, V] logits (fp32; tied lm head)."""
        B, S = input_ids.shape
        self._check_len(S)
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        mask = jnp.tril(jnp.ones((S, S), bool))[None, None]
        x = self._trunk(params, input_ids, positions, mask)
        logits = jnp.einsum("bsH,VH->bsV", x,
                            params["embed"].astype(self.config.dtype))
        return logits.astype(jnp.float32)

    __call__ = forward

    def loss(self, params: Any, batch: Any) -> jnp.ndarray:
        from .llama import LlamaModel, masked_cross_entropy

        input_ids, labels = LlamaModel.batch_labels(batch)
        return masked_cross_entropy(self.forward(params, input_ids), labels)

    # ------------------------------------------------------------------
    # v1 inference (init_cache/prefill/decode_step contract)
    # ------------------------------------------------------------------

    def init_cache(self, batch_size: int, max_len: int) -> Dict[str, Any]:
        c = self.config
        shape = (c.num_layers, batch_size, max_len, c.num_heads, c.hd)
        return {"k": jnp.zeros(shape, c.dtype), "v": jnp.zeros(shape, c.dtype),
                "lengths": jnp.zeros((batch_size,), jnp.int32)}

    def prefill(self, params: Any, input_ids: jnp.ndarray,
                cache: Dict[str, Any]) -> Tuple[jnp.ndarray, Dict[str, Any]]:
        c = self.config
        dt = c.dtype
        B, S = input_ids.shape
        self._check_len(S)
        max_len = cache["k"].shape[2]
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        mask = jnp.tril(jnp.ones((S, S), bool))[None, None]
        x = (jnp.take(params["embed"].astype(dt), input_ids, axis=0)
             + jnp.take(params["pos_embed"].astype(dt),
                        positions + POSITION_OFFSET, axis=0))

        def layer(carry, lp):
            x, = carry
            h = _layer_norm(x, lp["attn_ln_w"].astype(dt),
                            lp["attn_ln_b"].astype(dt), c.layer_norm_eps)
            q = jnp.einsum("bsH,Hhd->bshd", h, lp["attn"]["wq"].astype(dt)) \
                + lp["attn"]["bq"].astype(dt)
            kk = jnp.einsum("bsH,Hhd->bshd", h, lp["attn"]["wk"].astype(dt)) \
                + lp["attn"]["bk"].astype(dt)
            vv = jnp.einsum("bsH,Hhd->bshd", h, lp["attn"]["wv"].astype(dt)) \
                + lp["attn"]["bv"].astype(dt)
            attn = _attention(q, kk, vv, mask)
            out = jnp.einsum("bshd,hdH->bsH", attn,
                             lp["attn"]["wo"].astype(dt)) \
                + lp["attn"]["bo"].astype(dt)
            x = self._mlp_block(lp, x + out)
            pad = max_len - S
            k_entry = jnp.pad(kk, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v_entry = jnp.pad(vv, ((0, 0), (0, pad), (0, 0), (0, 0)))
            return (x,), (k_entry, v_entry)

        (x,), (ks, vs) = jax.lax.scan(layer, (x,), params["layers"])
        x = _layer_norm(x, params["final_ln_w"].astype(dt),
                        params["final_ln_b"].astype(dt), c.layer_norm_eps)
        logits = jnp.einsum("bH,VH->bV", x[:, -1], params["embed"].astype(dt))
        return logits.astype(jnp.float32), {
            "k": ks, "v": vs, "lengths": jnp.full((B,), S, jnp.int32)}

    def decode_step(self, params: Any, cache: Dict[str, Any],
                    tokens: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
        from ..ops.pallas.decode_attention import decode_attention

        c = self.config
        dt = c.dtype
        B = tokens.shape[0]
        lengths = cache["lengths"]
        # clamp: generation past the table emits the last position's
        # embedding rather than NaN (the engine sizes the cache, so this
        # only triggers when a caller over-generates deliberately)
        pos_idx = jnp.minimum(lengths + POSITION_OFFSET,
                              params["pos_embed"].shape[0] - 1)
        x = (jnp.take(params["embed"].astype(dt), tokens, axis=0)
             + jnp.take(params["pos_embed"].astype(dt), pos_idx, axis=0))

        def layer(carry, xs):
            x, = carry
            lp, k_cache, v_cache = xs
            h = _layer_norm(x, lp["attn_ln_w"].astype(dt),
                            lp["attn_ln_b"].astype(dt), c.layer_norm_eps)
            q = jnp.einsum("bH,Hhd->bhd", h, lp["attn"]["wq"].astype(dt)) \
                + lp["attn"]["bq"].astype(dt)
            kk = jnp.einsum("bH,Hhd->bhd", h, lp["attn"]["wk"].astype(dt)) \
                + lp["attn"]["bk"].astype(dt)
            vv = jnp.einsum("bH,Hhd->bhd", h, lp["attn"]["wv"].astype(dt)) \
                + lp["attn"]["bv"].astype(dt)
            k_cache = k_cache.at[jnp.arange(B), lengths].set(kk)
            v_cache = v_cache.at[jnp.arange(B), lengths].set(vv)
            attn = decode_attention(q, k_cache, v_cache, lengths + 1)
            out = jnp.einsum("bhd,hdH->bH", attn,
                             lp["attn"]["wo"].astype(dt)) \
                + lp["attn"]["bo"].astype(dt)
            x = x + out
            h = _layer_norm(x, lp["mlp_ln_w"].astype(dt),
                            lp["mlp_ln_b"].astype(dt), c.layer_norm_eps)
            h = jax.nn.relu(h @ lp["mlp"]["w_in"].astype(dt)
                            + lp["mlp"]["b_in"].astype(dt))
            x = x + h @ lp["mlp"]["w_out"].astype(dt) \
                + lp["mlp"]["b_out"].astype(dt)
            return (x,), (k_cache, v_cache)

        (x,), (ks, vs) = jax.lax.scan(
            layer, (x,), (params["layers"], cache["k"], cache["v"]))
        x = _layer_norm(x, params["final_ln_w"].astype(dt),
                        params["final_ln_b"].astype(dt), c.layer_norm_eps)
        logits = jnp.einsum("bH,VH->bV", x, params["embed"].astype(dt))
        return logits.astype(jnp.float32), {
            "k": ks, "v": vs, "lengths": lengths + 1}
