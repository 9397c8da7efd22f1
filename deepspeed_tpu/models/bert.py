"""BERT-family encoder — driver config-ladder rung 2 (ZeRO-1/2).

Capability anchor: the reference's canonical ZeRO-1/2 showcase is
BERT-large pretraining (``tests/model/BingBertSquad`` convergence suite +
the FusedLamb large-batch BERT path [K], SURVEY §4/§2.2); the driver
ladder names "BERT-large (ZeRO-1/2 over ICI)" as config 2 [D BASELINE.md].

TPU-first, same design grammar as ``llama.py``:

* stacked per-layer params + ``lax.scan`` — one compiled encoder block;
* bidirectional (no causal mask) attention through the flash op
  (``ops/pallas/flash_attention``), which chooses its implementation from
  platform and shape: the Pallas kernels on a TPU, the einsum + float32
  softmax reference elsewhere or for a shape the kernels refuse.  Padding
  rides in as segment ids, and only when the batch carries a mask.
  Measured on a v5e at BERT-large's ``[32, 512, 16, 64]`` bf16 (PERF.md
  §6, PR 41): the kernels' forward 0.53 ms and backward 1.03 ms a layer
  where the einsum + softmax over materialised scores took 1.74 ms and
  4.5 ms; 33.4k → 41.4k tokens/s in the training cell;
* masked-LM loss with -100 ignore positions (HF convention), so HF-style
  data pipelines feed it unchanged;
* TP/ZeRO placement via ``param_specs`` exactly like the decoder models.
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
from ..telemetry import numerics

P = PartitionSpec


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 1024          # BERT-large defaults
    intermediate_size: int = 4096
    num_layers: int = 24
    num_heads: int = 16
    max_seq_len: int = 512
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12
    dtype: Any = jnp.bfloat16
    remat: bool = True
    #: ACCEPTED AND IGNORED: it selects nothing.  The encoder has one
    #: attention path (``flash_attention_spmd``), and the op decides what
    #: runs from what it can observe.  The argument stays only because the
    #: benchmark's builder (``perfbench/models/bert.py``) and saved
    #: HF-import dicts still pass it; ROADMAP D5 has the line that deletes
    #: it with the benchmark's next change
    attn_impl: str = "xla"

    @property
    def hd(self) -> int:
        return self.hidden_size // self.num_heads

    @classmethod
    def tiny(cls, **kw) -> "BertConfig":
        d = dict(vocab_size=512, hidden_size=128, intermediate_size=256,
                 num_layers=4, num_heads=8, max_seq_len=128)
        d.update(kw)
        return cls(**d)

    @classmethod
    def bert_large(cls, **kw) -> "BertConfig":
        return cls(**kw)

    def num_params(self) -> int:
        H, I, V, L = (self.hidden_size, self.intermediate_size,
                      self.vocab_size, self.num_layers)
        per_layer = 4 * H * H + 4 * H + 2 * H * I + I + H + 4 * H
        embeds = (V + self.max_seq_len + self.type_vocab_size) * H + 2 * H
        return embeds + L * per_layer + H * H + 3 * H + V  # MLM head


def _layer_norm(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                eps: float) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps)).astype(dt) * w + b


class BertModel:
    """Functional MLM encoder: pure forward, params as a plain pytree."""

    aux_loss_coef: float = 0.0

    def __init__(self, config: BertConfig, mesh: Optional[Mesh] = None):
        self.config = config
        self.mesh = mesh
        #: random-LTD state, assigned by the engine from the
        #: ``data_efficiency.data_routing.random_ltd`` config: middle
        #: layers process ``ltd_keep`` randomly-selected tokens (None →
        #: off).  BERT's learned ABSOLUTE position embeddings are added at
        #: embedding time, so gathering tokens is exact — no RoPE
        #: re-indexing problem (why the reference's random-LTD showcase is
        #: BERT/GPT2-era models, arXiv 2211.11586)
        self.ltd_keep: Optional[int] = None
        self.ltd_layer_ids: tuple = ()

    # ------------------------------------------------------------------

    def init_params(self, rng: jax.Array) -> Dict[str, Any]:
        c = self.config
        H, I, V, L = (c.hidden_size, c.intermediate_size, c.vocab_size,
                      c.num_layers)
        nh, hd = c.num_heads, c.hd
        k = iter(jax.random.split(rng, 16))

        def normal(key, shape, fan_in):
            return (jax.random.normal(key, shape, jnp.float32)
                    * (1.0 / np.sqrt(fan_in))).astype(jnp.float32)

        return {
            "embed": {
                "word": normal(next(k), (V, H), H),
                "position": normal(next(k), (c.max_seq_len, H), H),
                "token_type": normal(next(k), (c.type_vocab_size, H), H),
                "ln_w": jnp.ones((H,), jnp.float32),
                "ln_b": jnp.zeros((H,), jnp.float32),
            },
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
                    "w_in": normal(next(k), (L, H, I), H),
                    "b_in": jnp.zeros((L, I), jnp.float32),
                    "w_out": normal(next(k), (L, I, H), I),
                    "b_out": jnp.zeros((L, H), jnp.float32),
                },
                "attn_ln_w": jnp.ones((L, H), jnp.float32),
                "attn_ln_b": jnp.zeros((L, H), jnp.float32),
                "mlp_ln_w": jnp.ones((L, H), jnp.float32),
                "mlp_ln_b": jnp.zeros((L, H), jnp.float32),
            },
            "mlm": {  # prediction-head transform; decoder ties to word embed
                "w": normal(next(k), (H, H), H),
                "b": jnp.zeros((H,), jnp.float32),
                "ln_w": jnp.ones((H,), jnp.float32),
                "ln_b": jnp.zeros((H,), jnp.float32),
                "bias": jnp.zeros((V,), jnp.float32),
            },
        }

    def param_specs(self, params: Optional[Any] = None) -> Dict[str, Any]:
        t = AXIS_TENSOR
        return {
            "embed": {"word": P(None, None), "position": P(None, None),
                      "token_type": P(None, None),
                      "ln_w": P(None), "ln_b": P(None)},
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
            "mlm": {"w": P(None, None), "b": P(None), "ln_w": P(None),
                    "ln_b": P(None), "bias": P(None)},
        }

    # ------------------------------------------------------------------

    def uses_flash_kernels(self) -> bool:
        """Whether a step of this model holds the Pallas flash kernels:
        attention always goes through the flash op, so this is the op's
        own test (``flash_route``) for the model's shape on this platform
        (for a batch without a mask: padding is the batch's, not the
        model's).  The engine's memory ledger asks before a step is
        traced."""
        from ..ops.pallas.flash_attention import flash_route

        c = self.config
        return flash_route(c.max_seq_len, c.hd)[0] == "kernel"

    def keeps_flash_residuals(self) -> bool:
        """Whether the flash op names its ``out`` and ``lse`` for the layer
        scan's remat policy to hold: the op's own rule
        (``keeps_residuals``) at the shape a device's call has, its heads
        split over ``tensor``.  The engine's memory ledger asks, beside
        :meth:`uses_flash_kernels`."""
        from ..ops.pallas.flash_attention import keeps_residuals

        c = self.config
        split = (1 if self.mesh is None
                 else int(self.mesh.shape.get(AXIS_TENSOR, 1)))
        return keeps_residuals(c.max_seq_len, c.num_heads // split, c.hd,
                               False, None)

    def _constrain(self, x: jnp.ndarray, *spec) -> jnp.ndarray:
        if self.mesh is None:
            return x
        from ..parallel.mesh import strip_manual_axes

        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, strip_manual_axes(*spec)))

    def encoder_layer(self, lp: Any, x: jnp.ndarray,
                      pad_mask: Optional[jnp.ndarray]) -> jnp.ndarray:
        """One post-LN encoder block ``[B, S, H] → [B, S, H]``;
        ``pad_mask [B, S]`` True at real tokens, None for a batch with no
        padding (the kernel then compares no segments)."""
        c = self.config
        dt = c.dtype
        q = jnp.einsum("bsH,Hhd->bshd", x, lp["attn"]["wq"].astype(dt)) \
            + lp["attn"]["bq"].astype(dt)
        kk = jnp.einsum("bsH,Hhd->bshd", x, lp["attn"]["wk"].astype(dt)) \
            + lp["attn"]["bk"].astype(dt)
        vv = jnp.einsum("bsH,Hhd->bshd", x, lp["attn"]["wv"].astype(dt)) \
            + lp["attn"]["bv"].astype(dt)
        q = self._constrain(q, DP_AXES, AXIS_SEQ, AXIS_TENSOR, None)
        kk = self._constrain(kk, DP_AXES, AXIS_SEQ, AXIS_TENSOR, None)
        vv = self._constrain(vv, DP_AXES, AXIS_SEQ, AXIS_TENSOR, None)
        # padding rides as segment ids: real tokens are segment 1, pads
        # segment 0, so cross-segment pairs mask out in the op.  (A pad
        # QUERY then attends only pads, where a key-only mask would let it
        # see real keys: those rows are -100 in the loss, and the parity
        # test compares real rows only.)
        from ..ops.pallas.flash_attention import flash_attention_spmd

        attn = flash_attention_spmd(
            q, kk, vv, self.mesh, causal=False,
            segment_ids=(None if pad_mask is None
                         else pad_mask.astype(jnp.int32)))
        out = numerics.probe(
            "attn_out",
            jnp.einsum("bshd,hdH->bsH", attn, lp["attn"]["wo"].astype(dt))
            + lp["attn"]["bo"].astype(dt))
        x = numerics.probe(
            "resid_attn",
            _layer_norm(x + out, lp["attn_ln_w"].astype(dt),
                        lp["attn_ln_b"].astype(dt), c.layer_norm_eps))

        h = jnp.einsum("bsH,HI->bsI", x, lp["mlp"]["w_in"].astype(dt)) \
            + lp["mlp"]["b_in"].astype(dt)
        from ..compression.quantization import maybe_quantize_activation

        h = maybe_quantize_activation(self, jax.nn.gelu(h, approximate=False))
        h = self._constrain(h, DP_AXES, AXIS_SEQ, AXIS_TENSOR)
        h = numerics.probe(
            "mlp_out",
            jnp.einsum("bsI,IH->bsH", h, lp["mlp"]["w_out"].astype(dt))
            + lp["mlp"]["b_out"].astype(dt))
        x = numerics.probe(
            "resid_ffn",
            _layer_norm(x + h, lp["mlp_ln_w"].astype(dt),
                        lp["mlp_ln_b"].astype(dt), c.layer_norm_eps))
        return self._constrain(x, DP_AXES, AXIS_SEQ, None)

    def forward(self, params: Any, input_ids: jnp.ndarray,
                attention_mask: Optional[jnp.ndarray] = None,
                token_type_ids: Optional[jnp.ndarray] = None,
                ltd_step: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        """[B, S] ids → [B, S, V] MLM logits (fp32)."""
        c = self.config
        dt = c.dtype
        B, S = input_ids.shape
        # no mask stays None down to the attention call: a plane of ones
        # would have the kernel compare segments for every score tile
        if attention_mask is not None:
            attention_mask = attention_mask.astype(bool)
        if token_type_ids is None:
            token_type_ids = jnp.zeros((B, S), jnp.int32)
        e = params["embed"]
        x = (jnp.take(e["word"].astype(dt), input_ids, axis=0)
             + e["position"].astype(dt)[None, :S]
             + jnp.take(e["token_type"].astype(dt), token_type_ids, axis=0))
        x = _layer_norm(x, e["ln_w"].astype(dt), e["ln_b"].astype(dt),
                        c.layer_norm_eps)
        x = numerics.probe("embed",
                           self._constrain(x, DP_AXES, AXIS_SEQ, None))

        keep = self.ltd_keep
        ltd_on = (keep is not None and 0 < keep < S
                  and len(self.ltd_layer_ids) > 0)
        if ltd_on:
            from ..runtime.data_pipeline.random_ltd import random_ltd_apply

            # selection rng: content + step keyed (the engine threads the
            # step in as the ``_step`` batch leaf) — a revisited sample
            # drops a FRESH token subset each epoch, matching the
            # reference's per-step selection
            base_rng = jax.random.fold_in(
                jax.random.PRNGKey(17),
                jnp.sum(input_ids).astype(jnp.uint32))
            if ltd_step is not None:
                base_rng = jax.random.fold_in(
                    base_rng, ltd_step.reshape(-1)[0].astype(jnp.uint32))
            is_ltd = jnp.asarray([i in self.ltd_layer_ids
                                  for i in range(c.num_layers)])

            def ltd_layer(lp, x, rng):
                return random_ltd_apply(
                    # called with the gathered mask, or with ``sub`` alone
                    # when the batch has none
                    lambda sub, sub_mask=None: self.encoder_layer(
                        lp, sub, sub_mask),
                    x, keep, rng, mask=attention_mask)

            def layer(carry, xs):
                x, i = carry
                lp, flag = xs
                nx = jax.lax.cond(
                    flag,
                    lambda: ltd_layer(lp, x, jax.random.fold_in(base_rng, i)),
                    lambda: self.encoder_layer(lp, x, attention_mask))
                return (nx, i + 1), None

            body = layer
            if c.remat:
                body = jax.checkpoint(layer, policy=remat_policy())
            # numerics probes stay OFF through the LTD trunk: the
            # per-layer lax.cond routing would trap their stat tracers
            # inside branch scopes
            with numerics.suppressed():
                (x, _), _ = jax.lax.scan(body, (x, jnp.int32(0)),
                                         (params["layers"], is_ltd))
        else:
            def layer(carry, lp):
                mark = numerics.scan_mark()
                x = self.encoder_layer(lp, carry, attention_mask)
                return x, numerics.scan_drain(mark)

            body = layer
            if c.remat:
                body = jax.checkpoint(layer, policy=remat_policy())
            x, ys = jax.lax.scan(lambda carry, lp: body(carry, lp), x,
                                 params["layers"])
            numerics.scan_collect(ys)

        m = params["mlm"]
        h = jax.nn.gelu(jnp.einsum("bsH,HG->bsG", x, m["w"].astype(dt))
                        + m["b"].astype(dt), approximate=False)
        h = _layer_norm(h, m["ln_w"].astype(dt), m["ln_b"].astype(dt),
                        c.layer_norm_eps)
        logits = (jnp.einsum("bsH,VH->bsV", h, e["word"].astype(dt))
                  + m["bias"])
        return numerics.probe("mlm_logits", logits.astype(jnp.float32))

    __call__ = forward

    def loss(self, params: Any, batch: Any) -> jnp.ndarray:
        """Masked-LM cross entropy; ``batch = {"input_ids", "labels"[, "
        attention_mask", "token_type_ids"]}`` with -100 = not masked."""
        input_ids = batch["input_ids"]
        labels = batch["labels"]
        logits = self.forward(params, input_ids,
                              batch.get("attention_mask"),
                              batch.get("token_type_ids"),
                              ltd_step=batch.get("_step"))
        from .llama import masked_cross_entropy

        return masked_cross_entropy(logits, labels)
