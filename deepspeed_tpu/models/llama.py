"""Llama-family decoder — the flagship train/bench model, TPU-first.

Capability anchor: the reference trains HF torch Llama through its engine and
ships llama model implementations for inference
(``deepspeed/inference/v2/model_implementations/llama_v2/`` [K]); the driver
ladder names Llama-3-8B (ZeRO-3) and Llama-3-70B (Infinity + Ulysses SP) as
headline configs [D BASELINE.json].

TPU-first design, none of which mirrors the reference's torch modules:

* **Stacked-layer params + ``lax.scan``** — one compiled layer body regardless
  of depth: compile time O(1) in num_layers, and the layout pipeline/layer-
  streaming (ZeRO-Infinity) needs is the native one.
* **GSPMD Ulysses** — sequence parallelism is expressed as sharding
  constraints: activations ride sequence-sharded ``[B, S/sp, H]`` everywhere
  except attention, where Q/K/V are constrained to head-sharded
  ``[B, S, h/(sp·tp), D]``; XLA inserts the all-to-alls the reference issues
  by hand in ``ulysses_sp.py`` (SURVEY §5.7).
* **Tensor parallelism** — Megatron-style column/row sharding is a
  PartitionSpec on the weights (``tensor`` axis) + the same activation
  constraints; no module surgery (reference: ``module_inject/auto_tp.py``).
* **Remat** — ``jax.checkpoint`` on the layer body under the package's
  one policy (``activation_checkpointing.remat_policy``: the matrix
  products' outputs and what an op names, as the flash call its ``out``
  and ``lse``) ≈ reference ``activation_checkpointing`` with partitioned
  activations for free (saved residuals inherit their shardings).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..parallel.mesh import AXIS_PIPE, AXIS_SEQ, AXIS_TENSOR, DP_AXES
from ..runtime.activation_checkpointing import remat_policy
from ..telemetry import numerics

P = PartitionSpec


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: Optional[int] = None
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    tie_embeddings: bool = False
    #: Mistral-style sliding-window attention: each token attends at most
    #: this many previous positions (None → full causal).  Training and
    #: prefill mask by window; decode masks the cache tail (a rolling
    #: window KV cache is a serving optimization for a later round).
    sliding_window: Optional[int] = None
    #: OLMoE's q/k norm: an RMSNorm with its own weight
    #: (``layers.attn.q_norm`` / ``k_norm [L, heads·d]``) over the WHOLE q
    #: and k projection, before the split into heads and before rotary.
    #: Read by :func:`apply_qk_norm`, which every attention path calls: the
    #: model's own forward, its dense-cache decode and the v2 adapter.
    qk_norm: bool = False
    dtype: Any = jnp.bfloat16
    remat: bool = True
    #: >1 → chunk final projection+loss over the sequence so the [B,S,V]
    #: logits are never materialized (ALST sequence-tiled loss)
    loss_tiles: int = 1
    #: pipeline microbatch count (0 → pipe axis size); used when the mesh has
    #: a pipe axis > 1
    pp_microbatches: int = 0
    #: virtual stages per pipe rank (>1 → interleaved schedule: bubble
    #: shrinks by this factor; num_layers must divide by pp*pp_interleave)
    pp_interleave: int = 1
    #: "flash" → Pallas online-softmax kernel (TPU; falls back to XLA off-TPU),
    #: "xla" → einsum+softmax left to the XLA fuser
    attn_impl: str = "xla"
    #: flash kernel block sizes; 0 = the seq-length-aware table
    #: (ops/pallas/lattice.auto_flash_blocks) — surfaced so the tuning
    #: plane's kernels.flash_block_* dimensions reach the kernel
    flash_block_q: int = 0
    flash_block_k: int = 0

    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim is not None else (
            self.hidden_size // self.num_heads)

    # ------------------------------------------------------------------
    # presets (sizes follow the public Llama/Llama-3 configs)
    # ------------------------------------------------------------------

    @classmethod
    def tiny(cls, **kw) -> "LlamaConfig":
        """Test/CI model — small enough for an 8-device CPU mesh."""
        d = dict(vocab_size=512, hidden_size=128, intermediate_size=352,
                 num_layers=4, num_heads=8, num_kv_heads=4, max_seq_len=256)
        d.update(kw)
        return cls(**d)

    @classmethod
    def mistral_7b(cls, **kw) -> "LlamaConfig":
        """Mistral-7B: Llama architecture + GQA + sliding-window attention
        (the reference ships a mistral implementation in
        ``inference/v2/model_implementations`` [K])."""
        d = dict(vocab_size=32000, hidden_size=4096, intermediate_size=14336,
                 num_layers=32, num_heads=32, num_kv_heads=8,
                 max_seq_len=8192, rope_theta=10000.0, sliding_window=4096)
        d.update(kw)
        return cls(**d)

    @classmethod
    def llama3_8b(cls, **kw) -> "LlamaConfig":
        d = dict(vocab_size=128256, hidden_size=4096, intermediate_size=14336,
                 num_layers=32, num_heads=32, num_kv_heads=8,
                 max_seq_len=8192, rope_theta=500000.0)
        d.update(kw)
        return cls(**d)

    @classmethod
    def llama3_70b(cls, **kw) -> "LlamaConfig":
        d = dict(vocab_size=128256, hidden_size=8192, intermediate_size=28672,
                 num_layers=80, num_heads=64, num_kv_heads=8,
                 max_seq_len=8192, rope_theta=500000.0)
        d.update(kw)
        return cls(**d)

    def num_params(self) -> int:
        H, I, V, L = (self.hidden_size, self.intermediate_size,
                      self.vocab_size, self.num_layers)
        hd, nh, nkv = self.hd, self.num_heads, self.num_kv_heads
        per_layer = (H * nh * hd + 2 * H * nkv * hd + nh * hd * H  # attn
                     + 3 * H * I  # swiglu (gate, up, down)
                     + 2 * H)  # norms
        head = H if self.tie_embeddings else H + H * V
        return V * H + L * per_layer + head


@jax.custom_vjp
def _tp_copy(x: jnp.ndarray) -> jnp.ndarray:
    """Megatron's *f* operator for the manual-TP layer: identity forward,
    psum over the (manual) ``tensor`` axis in backward — the input of a
    column-parallel linear is used by every rank, so its cotangent is the
    cross-rank sum."""
    return x


def _tp_copy_fwd(x):
    return x, None


def _tp_copy_bwd(_, g):
    from ..comm.comm import psum
    from ..parallel.mesh import AXIS_TENSOR

    return (psum(g, AXIS_TENSOR),)


_tp_copy.defvjp(_tp_copy_fwd, _tp_copy_bwd)


@jax.custom_vjp
def _tp_reduce(x: jnp.ndarray) -> jnp.ndarray:
    """Megatron's *g* operator: psum over the manual ``tensor`` axis in
    forward, IDENTITY backward (the psum output is replicated, so its
    cotangent is already the full value on every rank).  Explicit because
    ``lax.psum``'s autodiff transpose under ``check_vma=False`` shard_map
    is another psum — which would scale row-parallel cotangents by tp."""
    from ..comm.comm import psum
    from ..parallel.mesh import AXIS_TENSOR

    return psum(x, AXIS_TENSOR)


def _tp_reduce_fwd(x):
    return _tp_reduce(x), None


def _tp_reduce_bwd(_, g):
    return (g,)


_tp_reduce.defvjp(_tp_reduce_fwd, _tp_reduce_bwd)


@jax.custom_vjp
def _tp_max(x: jnp.ndarray) -> jnp.ndarray:
    """Cross-rank max over the manual ``tensor`` axis with a ZERO
    backward — used only for the log-sum-exp shift, whose derivative
    w.r.t. the shift is identically 0 (``lax.pmax`` has no autodiff rule
    at all, so the no-op cotangent must be spelled out)."""
    from ..comm.comm import pmax
    from ..parallel.mesh import AXIS_TENSOR

    return pmax(x, AXIS_TENSOR)


def _tp_max_fwd(x):
    return _tp_max(x), None


def _tp_max_bwd(_, g):
    return (jnp.zeros_like(g),)


_tp_max.defvjp(_tp_max_fwd, _tp_max_bwd)


def _rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dt) * w


def apply_qk_norm(c: LlamaConfig, attn: Any, q: jnp.ndarray, k: jnp.ndarray
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``c.qk_norm`` applied to projections ``[..., heads, d]`` (identity
    where the config has none): each is flattened to ``[..., heads·d]``,
    normalised as one vector and split again; rotary comes after."""
    if not c.qk_norm:
        return q, k

    def normed(x, w):
        flat = x.reshape(*x.shape[:-2], x.shape[-2] * x.shape[-1])
        return _rms_norm(flat, w.astype(x.dtype), c.rms_norm_eps
                         ).reshape(x.shape)

    return normed(q, attn["q_norm"]), normed(k, attn["k_norm"])


def _rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary embedding on [..., S, h, D] with positions [..., S]."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, D/2]
    cos = jnp.cos(angles)[..., :, None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def masked_cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray
                         ) -> jnp.ndarray:
    """Token-mean CE with -100 ignore positions (HF convention) — the one
    home of the loss tail shared by every LM in the zoo."""
    valid = labels != -100
    safe = jnp.where(valid, labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.where(valid, nll, 0.0)) / jnp.maximum(
        jnp.sum(valid), 1)


def _attention(q, k, v, mask):
    """Reference attention: fp32 softmax; [B, S, h, D] layout.

    Swapped for the Pallas flash kernel on TPU via ops.attention once the
    kernel path lands (SURVEY §7 phase 11) — the caller controls that.
    """
    scale = 1.0 / np.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


class LlamaModel:
    """Functional model: params are a plain pytree, forward is pure.

    ``mesh=None`` (single device) skips all sharding constraints; with a mesh,
    the constraints express ZeRO/TP/SP placement and GSPMD inserts the
    collectives.
    """

    #: weight on the router load-balancing aux loss (dense model: no-op)
    aux_loss_coef: float = 0.0

    def __init__(self, config: LlamaConfig, mesh: Optional[Mesh] = None):
        self.config = config
        self.mesh = mesh

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------

    def init_params(self, rng: jax.Array) -> Dict[str, Any]:
        c = self.config
        H, I, V, L = c.hidden_size, c.intermediate_size, c.vocab_size, c.num_layers
        hd, nh, nkv = c.hd, c.num_heads, c.num_kv_heads
        k = iter(jax.random.split(rng, 9))

        def normal(key, shape, fan_in):
            return (jax.random.normal(key, shape, jnp.float32)
                    * (1.0 / np.sqrt(fan_in))).astype(jnp.float32)

        params = {
            "embed": normal(next(k), (V, H), H),
            "layers": {
                "attn": {
                    "wq": normal(next(k), (L, H, nh, hd), H),
                    "wk": normal(next(k), (L, H, nkv, hd), H),
                    "wv": normal(next(k), (L, H, nkv, hd), H),
                    "wo": normal(next(k), (L, nh, hd, H), nh * hd),
                },
                "mlp": {
                    "w_gate": normal(next(k), (L, H, I), H),
                    "w_up": normal(next(k), (L, H, I), H),
                    "w_down": normal(next(k), (L, I, H), I),
                },
                "attn_norm": jnp.ones((L, H), jnp.float32),
                "mlp_norm": jnp.ones((L, H), jnp.float32),
            },
            "final_norm": jnp.ones((H,), jnp.float32),
        }
        if c.qk_norm:
            params["layers"]["attn"]["q_norm"] = jnp.ones((L, nh * hd),
                                                          jnp.float32)
            params["layers"]["attn"]["k_norm"] = jnp.ones((L, nkv * hd),
                                                          jnp.float32)
        if not c.tie_embeddings:
            params["lm_head"] = normal(next(k), (H, V), H)
        return params

    # ------------------------------------------------------------------
    # partition specs (composed with ZeRO by the engine's sharding policy)
    # ------------------------------------------------------------------

    def param_specs(self, params: Optional[Any] = None) -> Dict[str, Any]:
        """Megatron-style TP specs on the ``tensor`` axis; the layer-stack
        dim shards over ``pipe`` when pipeline parallelism is active; DP/ZeRO
        axes are layered on top by ``ZeroShardingPolicy.compose`` (reference
        analogue: AutoTP column/row policy, ``module_inject/auto_tp.py`` [K])."""
        t = AXIS_TENSOR
        pipe = (AXIS_PIPE if self.mesh is not None
                and int(self.mesh.shape.get(AXIS_PIPE, 1)) > 1 else None)
        specs = {
            "embed": P(None, None),  # vocab gather stays local; H replicated
            "layers": {
                "attn": {
                    "wq": P(pipe, None, t, None),   # column (head) split
                    "wk": P(pipe, None, t, None),
                    "wv": P(pipe, None, t, None),
                    "wo": P(pipe, t, None, None),   # row split
                },
                "mlp": {
                    "w_gate": P(pipe, None, t),     # column split
                    "w_up": P(pipe, None, t),
                    "w_down": P(pipe, t, None),     # row split
                },
                "attn_norm": P(pipe, None),
                "mlp_norm": P(pipe, None),
            },
            "final_norm": P(None),
        }
        if self.config.qk_norm:
            specs["layers"]["attn"]["q_norm"] = P(pipe, None)
            specs["layers"]["attn"]["k_norm"] = P(pipe, None)
        if not self.config.tie_embeddings:
            specs["lm_head"] = P(None, t)  # vocab-sharded output projection
        return specs

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------

    def uses_flash_kernels(self) -> bool:
        """Whether a step of this model holds the Pallas flash kernels:
        the option sends attention to the flash op AND the op, by its own
        test (``flash_route``), runs the kernels for the model's shape on
        this platform.  The engine's memory ledger asks before a step is
        traced."""
        c = self.config
        if c.attn_impl != "flash":
            return False
        from ..ops.pallas.flash_attention import flash_route

        return flash_route(c.max_seq_len, c.hd, c.flash_block_q,
                           c.flash_block_k)[0] == "kernel"

    def keeps_flash_residuals(self) -> bool:
        """Whether the flash op names its ``out`` and ``lse`` for the layer
        scan's remat policy to hold: the op's own rule
        (``keeps_residuals``) at the shape a device's call has, its heads
        split over ``tensor`` and, through Ulysses, ``seq``.  The engine's
        memory ledger asks, beside :meth:`uses_flash_kernels`."""
        from ..ops.pallas.flash_attention import keeps_residuals

        c = self.config
        split = 1 if self.mesh is None else (
            int(self.mesh.shape.get(AXIS_TENSOR, 1))
            * int(self.mesh.shape.get(AXIS_SEQ, 1)))
        return keeps_residuals(c.max_seq_len, c.num_heads // split, c.hd,
                               True, c.sliding_window)

    def _constrain(self, x: jnp.ndarray, *spec) -> jnp.ndarray:
        if self.mesh is None:
            return x
        from ..parallel.mesh import strip_manual_axes

        stripped = strip_manual_axes(*spec)
        if not jax.sharding.get_abstract_mesh().empty:
            # inside a (partial-manual) shard_map / set_mesh scope: a bare
            # PartitionSpec binds to the CONTEXT mesh — a concrete-mesh
            # NamedSharding would fail the context-consistency check
            return jax.lax.with_sharding_constraint(x, stripped)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, stripped))

    def decoder_layer(self, lp: Any, x: jnp.ndarray
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """ONE decoder layer ``[B, S, H] → ([B, S, H], aux_loss)`` — the unit
        of the scan in :meth:`_forward_trunk` AND the unit of ZeRO-Infinity
        layer streaming (``runtime/swap_tensor``), where each layer's params
        arrive from host/NVMe just ahead of use."""
        c = self.config
        out = self._attn_block(lp, x)
        # back to the sequence-sharded home layout
        x = numerics.probe(
            "resid_attn", self._constrain(x + out, DP_AXES, AXIS_SEQ, None))

        h = _rms_norm(x, lp["mlp_norm"].astype(c.dtype), c.rms_norm_eps)
        ffn_out, l_aux = self._ffn(h, lp)
        x = numerics.probe(
            "resid_ffn",
            self._constrain(x + ffn_out, DP_AXES, AXIS_SEQ, None))
        return x, l_aux

    def _attn_block(self, lp: Any, x: jnp.ndarray) -> jnp.ndarray:
        """Attention half of one decoder layer (its norm + QKV + attention
        + output proj, WITHOUT the residual) — separately callable so the
        per-module flops profiler can attribute cost at module_depth 2."""
        from ..runtime.sequence_parallel.ulysses_sp import ulysses_attention

        c = self.config
        n_rep = c.num_heads // c.num_kv_heads
        # the ring branch below is taken only with a mesh; every other path
        # (incl. ring-configured but mesh-less) needs GQA-expanded KV
        ring_active = c.attn_impl == "ring" and self.mesh is not None

        def apply_rope_qk(q, kk):
            """Global-position RoPE on q/k — ONE home for position handling
            (used by the local attn body AND the ring branch)."""
            S = q.shape[1]
            positions = jnp.arange(S)[None, :]
            return (_rope(q, positions, c.rope_theta),
                    _rope(kk, positions, c.rope_theta))

        def attn_fn(q, kk, vv):
            """Position-exact attention on [b, S, h_local, d] blocks — runs
            under shard_map with the FULL sequence after the Ulysses
            all-to-all (heads local), or directly when unsharded."""
            q, kk = apply_rope_qk(q, kk)
            S = q.shape[1]
            W = c.sliding_window
            if c.attn_impl == "flash":
                from ..ops.pallas.flash_attention import flash_attention_spmd

                # window rides into the kernel: k-blocks wholly outside the
                # window are skipped, so windowed work is O(S·W), not O(S²)
                return flash_attention_spmd(q, kk, vv, self.mesh, True,
                                            block_q=c.flash_block_q,
                                            block_k=c.flash_block_k,
                                            window=W)
            from ..ops.masks import local_attention_mask

            pos = jnp.arange(S)
            mask = local_attention_mask(pos, pos, causal=True, window=W)
            return _attention(q, kk, vv, mask[None, None])

        h = _rms_norm(x, lp["attn_norm"].astype(c.dtype), c.rms_norm_eps)
        q = jnp.einsum("bsH,Hhd->bshd", h, lp["attn"]["wq"].astype(c.dtype))
        kk = jnp.einsum("bsH,Hhd->bshd", h, lp["attn"]["wk"].astype(c.dtype))
        vv = jnp.einsum("bsH,Hhd->bshd", h, lp["attn"]["wv"].astype(c.dtype))
        q, kk = apply_qk_norm(c, lp["attn"], q, kk)
        if n_rep > 1 and not ring_active:
            # GQA: repeat KV heads so every Ulysses rank holds a slice;
            # the ring path rotates kv-width blocks and expands per-visit
            kk = jnp.repeat(kk, n_rep, axis=2)
            vv = jnp.repeat(vv, n_rep, axis=2)
        # probe sites live OUTSIDE the attention branch below: the
        # ulysses path runs attn_fn under shard_map and the ring path
        # rotates inside collectives — a probe in there would register a
        # tracer that cannot escape the manual region
        q = numerics.probe(
            "attn_q", self._constrain(q, DP_AXES, AXIS_SEQ, AXIS_TENSOR,
                                      None))
        kk = self._constrain(kk, DP_AXES, AXIS_SEQ, AXIS_TENSOR, None)
        vv = self._constrain(vv, DP_AXES, AXIS_SEQ, AXIS_TENSOR, None)
        if ring_active:
            # ring SP: sequence stays sharded THROUGH attention (no
            # head-count bound, unlike Ulysses) — RoPE on global positions
            # first, then KV blocks rotate over the seq axis
            from ..runtime.sequence_parallel.ring import ring_attention

            q, kk = apply_rope_qk(q, kk)
            attn = ring_attention(q, kk, vv, causal=True, mesh=self.mesh,
                                  window=c.sliding_window)
        elif self.mesh is not None:
            attn = ulysses_attention(attn_fn, q, kk, vv, mesh=self.mesh)
        else:
            attn = attn_fn(q, kk, vv)
        attn = numerics.probe("attn_ctx", attn)
        return numerics.probe(
            "attn_out", jnp.einsum("bshd,hdH->bsH", attn,
                                   lp["attn"]["wo"].astype(c.dtype)))

    def decoder_layer_manual_tp(self, lp: Any, x: jnp.ndarray
                                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """ONE decoder layer on LOCAL tensor shards under a MANUAL
        ``tensor`` axis — the 1F1B × TP path.

        Why it exists: the 1F1B schedule is a pipe-manual ``shard_map``,
        and tensor-axis GSPMD constraints INSIDE a partial-manual region
        trip an XLA partitioner CHECK (spmd_partitioner_util.cc; see the
        engine's routing note).  Manualizing the tensor axis too removes
        every in-region constraint: this method is the Megatron
        column/row pattern (reference ``megatron/mpu`` semantics via
        AutoTP specs, SURVEY §2.1 #25) with explicit collectives —
        ``_tp_copy`` (identity fwd / psum bwd: Megatron's *f*) before the
        column-parallel projections, ``psum`` (Megatron's *g*) after the
        row-parallel ones.

        ``lp`` leaves are the per-rank shards ``param_specs`` dictates:
        wq/wk/wv ``[H, h/tp, d]``, wo ``[h/tp, d, H]``, w_gate/w_up
        ``[H, I/tp]``, w_down ``[I/tp, H]``, norms replicated.  ``x`` is
        the full ``[B, S, H]`` activation (replicated over tensor)."""
        c = self.config
        n_rep = c.num_heads // c.num_kv_heads
        if c.qk_norm:
            raise NotImplementedError(
                "qk_norm spans the whole projection; the manual-TP layer "
                "holds a slice of the heads")

        h = _rms_norm(x, lp["attn_norm"].astype(c.dtype), c.rms_norm_eps)
        h = _tp_copy(h)
        q = jnp.einsum("bsH,Hhd->bshd", h, lp["attn"]["wq"].astype(c.dtype))
        kk = jnp.einsum("bsH,Hhd->bshd", h, lp["attn"]["wk"].astype(c.dtype))
        vv = jnp.einsum("bsH,Hhd->bshd", h, lp["attn"]["wv"].astype(c.dtype))
        if n_rep > 1:
            kk = jnp.repeat(kk, n_rep, axis=2)
            vv = jnp.repeat(vv, n_rep, axis=2)
        S = q.shape[1]
        positions = jnp.arange(S)[None, :]
        q = _rope(q, positions, c.rope_theta)
        kk = _rope(kk, positions, c.rope_theta)
        W = c.sliding_window
        if c.attn_impl == "flash":
            from ..ops.pallas.flash_attention import flash_attention_spmd

            attn = flash_attention_spmd(q, kk, vv, self.mesh, True,
                                        block_q=c.flash_block_q,
                                        block_k=c.flash_block_k, window=W)
        else:
            from ..ops.masks import local_attention_mask

            pos = jnp.arange(S)
            mask = local_attention_mask(pos, pos, causal=True, window=W)
            attn = _attention(q, kk, vv, mask[None, None])
        out = jnp.einsum("bshd,hdH->bsH", attn,
                         lp["attn"]["wo"].astype(c.dtype))
        x = x + _tp_reduce(out)

        h2 = _rms_norm(x, lp["mlp_norm"].astype(c.dtype), c.rms_norm_eps)
        h2 = _tp_copy(h2)
        gate = jnp.einsum("bsH,HI->bsI", h2,
                          lp["mlp"]["w_gate"].astype(c.dtype))
        up = jnp.einsum("bsH,HI->bsI", h2, lp["mlp"]["w_up"].astype(c.dtype))
        down = jnp.einsum("bsI,IH->bsH", jax.nn.silu(gate) * up,
                          lp["mlp"]["w_down"].astype(c.dtype))
        x = x + _tp_reduce(down)
        return x, jnp.float32(0.0)

    def profile_submodules(self) -> Dict[str, Any]:
        """Depth-2 module pieces for the flops profiler: name →
        ``fn(lp, x)`` over one decoder layer's params + activations."""
        c = self.config

        def mlp(lp, x):
            h = _rms_norm(x, lp["mlp_norm"].astype(c.dtype), c.rms_norm_eps)
            return self._ffn(h, lp)[0]

        return {"attn": self._attn_block, "mlp": mlp}

    def embed_fwd(self, params: Any, input_ids: jnp.ndarray) -> jnp.ndarray:
        """[B, S] ids → embedded activations in the home layout."""
        c = self.config
        x = jnp.take(params["embed"].astype(c.dtype), input_ids, axis=0)
        # activations ride batch-sharded + sequence-sharded (Ulysses home
        # layout; a 1-sized seq axis makes this a no-op)
        return self._constrain(x, DP_AXES, AXIS_SEQ, None)

    def _forward_trunk(self, params: Any, input_ids: jnp.ndarray
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """[B, S] token ids → (final-norm hidden [B, S, H], aux loss)."""
        c = self.config
        x = numerics.probe("embed", self.embed_fwd(params, input_ids))

        def layer(carry, lp):
            x, aux = carry
            # numerics bracket: the body's probe stats exit the scan as
            # its ys (stacked [L, ...] per-layer) — None when the plane
            # is off, which leaves today's jaxpr untouched
            mark = numerics.scan_mark()
            x, l_aux = self.decoder_layer(lp, x)
            return (x, aux + l_aux), numerics.scan_drain(mark)

        body = layer
        if c.remat:
            body = jax.checkpoint(layer, policy=remat_policy())

        pp = (int(self.mesh.shape.get(AXIS_PIPE, 1))
              if self.mesh is not None else 1)
        if pp > 1:
            from ..parallel.pipeline import pipeline_apply

            B, S = input_ids.shape
            M = c.pp_microbatches or pp
            if B % M:
                raise ValueError(
                    f"batch {B} not divisible by pipeline microbatches {M}")
            if c.num_layers % pp:
                raise ValueError(
                    f"num_layers {c.num_layers} not divisible by pp={pp}")
            micro = (x.reshape(M, B // M, S, -1),
                     jnp.zeros((M,), jnp.float32))

            def pipe_layer(lp, act):
                (nx, naux), _ = body(act, lp)
                return (nx, naux)

            out_x, out_aux = pipeline_apply(pipe_layer, params["layers"],
                                            micro, self.mesh,
                                            virtual_stages=c.pp_interleave)
            x = out_x.reshape(B, S, -1)
            aux = out_aux.mean()
        else:
            (x, aux), ys = jax.lax.scan(lambda carry, lp: body(carry, lp),
                                        (x, jnp.float32(0.0)),
                                        params["layers"])
            numerics.scan_collect(ys)

        x = numerics.probe(
            "final_norm",
            _rms_norm(x, params["final_norm"].astype(c.dtype),
                      c.rms_norm_eps))
        return x, aux

    def _ffn(self, h: jnp.ndarray, lp: Any) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Dense SwiGLU FFN; Mixtral overrides with the MoE block.  Returns
        (output, aux_loss)."""
        c = self.config
        gate = jnp.einsum("bsH,HI->bsI", h, lp["mlp"]["w_gate"].astype(c.dtype))
        up = jnp.einsum("bsH,HI->bsI", h, lp["mlp"]["w_up"].astype(c.dtype))
        from ..compression.quantization import maybe_quantize_activation

        act = maybe_quantize_activation(self, jax.nn.silu(gate) * up)
        act = self._constrain(act, DP_AXES, AXIS_SEQ, AXIS_TENSOR)
        down = jnp.einsum("bsI,IH->bsH", act,
                          lp["mlp"]["w_down"].astype(c.dtype))
        return numerics.probe("mlp_out", down), jnp.float32(0.0)

    def _head(self, params: Any) -> jnp.ndarray:
        return (params["embed"].T if self.config.tie_embeddings
                else params["lm_head"])

    # ------------------------------------------------------------------
    # KV-cache inference path (consumed by deepspeed_tpu.inference)
    # ------------------------------------------------------------------

    def init_cache(self, batch_size: int, max_len: int) -> Dict[str, Any]:
        """Decode cache: stores ``num_kv_heads`` heads only — GQA groups are
        expanded inside the decode kernel, keeping the cache-HBM footprint at
        the GQA size (4× smaller for llama3-8b, 8× for 70b)."""
        c = self.config
        shape = (c.num_layers, batch_size, max_len, c.num_kv_heads, c.hd)
        return {"k": jnp.zeros(shape, c.dtype), "v": jnp.zeros(shape, c.dtype),
                "lengths": jnp.zeros((batch_size,), jnp.int32)}

    def prefill(self, params: Any, input_ids: jnp.ndarray,
                cache: Dict[str, Any]) -> Tuple[jnp.ndarray, Dict[str, Any]]:
        """Process the prompt [B, S]; returns (last-token logits [B, V],
        filled cache)."""
        c = self.config
        B, S = input_ids.shape
        max_len = cache["k"].shape[2]
        n_rep = c.num_heads // c.num_kv_heads
        from ..ops.masks import local_attention_mask

        x = jnp.take(params["embed"].astype(c.dtype), input_ids, axis=0)
        positions = jnp.arange(S)[None, :]
        pos = jnp.arange(S)
        causal = local_attention_mask(pos, pos, causal=True,
                                      window=c.sliding_window)[None, None]

        def layer(carry, lp):
            x, = carry
            h = _rms_norm(x, lp["attn_norm"].astype(c.dtype), c.rms_norm_eps)
            q = jnp.einsum("bsH,Hhd->bshd", h, lp["attn"]["wq"].astype(c.dtype))
            kk = jnp.einsum("bsH,Hhd->bshd", h, lp["attn"]["wk"].astype(c.dtype))
            vv = jnp.einsum("bsH,Hhd->bshd", h, lp["attn"]["wv"].astype(c.dtype))
            q, kk = apply_qk_norm(c, lp["attn"], q, kk)
            q = _rope(q, positions, c.rope_theta)
            kk = _rope(kk, positions, c.rope_theta)
            # cache keeps the GQA (kv-head) layout; expand only for compute
            kk_full = jnp.repeat(kk, n_rep, axis=2) if n_rep > 1 else kk
            vv_full = jnp.repeat(vv, n_rep, axis=2) if n_rep > 1 else vv
            attn = _attention(q, kk_full, vv_full, causal)
            out = jnp.einsum("bshd,hdH->bsH", attn,
                             lp["attn"]["wo"].astype(c.dtype))
            x = x + out
            h = _rms_norm(x, lp["mlp_norm"].astype(c.dtype), c.rms_norm_eps)
            ffn_out, _ = self._ffn(h, lp)
            x = x + ffn_out
            pad = max_len - S
            k_entry = jnp.pad(kk, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v_entry = jnp.pad(vv, ((0, 0), (0, pad), (0, 0), (0, 0)))
            return (x,), (k_entry, v_entry)

        (x,), (ks, vs) = jax.lax.scan(layer, (x,), params["layers"])
        x = _rms_norm(x, params["final_norm"].astype(c.dtype), c.rms_norm_eps)
        logits = jnp.einsum("bH,HV->bV", x[:, -1],
                            self._head(params).astype(c.dtype))
        cache = {"k": ks, "v": vs,
                 "lengths": jnp.full((B,), S, jnp.int32)}
        return logits.astype(jnp.float32), cache

    def decode_step(self, params: Any, cache: Dict[str, Any],
                    tokens: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
        """One generation step: tokens [B] → (logits [B, V], updated cache)."""
        from ..ops.pallas.decode_attention import decode_attention

        c = self.config
        B = tokens.shape[0]
        n_rep = c.num_heads // c.num_kv_heads
        lengths = cache["lengths"]
        x = jnp.take(params["embed"].astype(c.dtype), tokens, axis=0)  # [B,H]
        pos = lengths[:, None]  # [B,1] next position per sequence

        def layer(carry, xs):
            x, = carry
            lp, k_cache, v_cache = xs
            h = _rms_norm(x, lp["attn_norm"].astype(c.dtype), c.rms_norm_eps)
            q = jnp.einsum("bH,Hhd->bhd", h, lp["attn"]["wq"].astype(c.dtype))
            kk = jnp.einsum("bH,Hhd->bhd", h, lp["attn"]["wk"].astype(c.dtype))
            vv = jnp.einsum("bH,Hhd->bhd", h, lp["attn"]["wv"].astype(c.dtype))
            q, kk = apply_qk_norm(c, lp["attn"], q, kk)
            q = _rope(q[:, None], pos, c.rope_theta)[:, 0]
            kk = _rope(kk[:, None], pos, c.rope_theta)[:, 0]
            # cache stays in kv-head layout; the kernel expands GQA groups
            k_cache = k_cache.at[jnp.arange(B), lengths].set(kk)
            v_cache = v_cache.at[jnp.arange(B), lengths].set(vv)
            attn = decode_attention(q, k_cache, v_cache, lengths + 1,
                                    window=c.sliding_window)
            out = jnp.einsum("bhd,hdH->bH", attn,
                             lp["attn"]["wo"].astype(c.dtype))
            x = x + out
            h = _rms_norm(x, lp["mlp_norm"].astype(c.dtype), c.rms_norm_eps)
            ffn_out, _ = self._ffn(h[:, None, :], lp)
            x = x + ffn_out[:, 0, :]
            return (x,), (k_cache, v_cache)

        (x,), (ks, vs) = jax.lax.scan(
            layer, (x,), (params["layers"], cache["k"], cache["v"]))
        x = _rms_norm(x, params["final_norm"].astype(c.dtype), c.rms_norm_eps)
        logits = jnp.einsum("bH,HV->bV", x,
                            self._head(params).astype(c.dtype))
        new_cache = {"k": ks, "v": vs, "lengths": lengths + 1}
        return logits.astype(jnp.float32), new_cache

    def forward(self, params: Any, input_ids: jnp.ndarray) -> jnp.ndarray:
        """[B, S] token ids → [B, S, V] logits (fp32)."""
        x, _ = self._forward_trunk(params, input_ids)
        logits = jnp.einsum("bsH,HV->bsV", x,
                            self._head(params).astype(self.config.dtype))
        return logits.astype(jnp.float32)

    __call__ = forward

    # ------------------------------------------------------------------
    # loss
    # ------------------------------------------------------------------

    @staticmethod
    def batch_labels(batch: Any) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(input_ids, labels) from either batch form (labels default to
        shifted inputs; -100 = ignore, HF convention)."""
        if isinstance(batch, dict):
            input_ids = batch["input_ids"]
            labels = batch.get("labels")
        else:
            input_ids, labels = batch, None
        if labels is None:
            labels = jnp.concatenate(
                [input_ids[:, 1:], jnp.full_like(input_ids[:, :1], -100)], axis=1)
        return input_ids, labels

    def _ce_from_hidden(self, params: Any, hidden: jnp.ndarray,
                        labels: jnp.ndarray) -> jnp.ndarray:
        """Cross entropy from final-norm'd hidden states."""
        c = self.config
        head = self._head(params).astype(c.dtype)
        if c.loss_tiles > 1:
            from ..runtime.sequence_parallel.ulysses_sp import \
                sequence_tiled_loss

            # one partial head gradient a data-parallel replica, added once
            replicas = 1 if self.mesh is None else int(np.prod(
                [self.mesh.shape.get(a, 1) for a in DP_AXES]))
            return sequence_tiled_loss(hidden, head, labels, c.loss_tiles,
                                       groups=replicas)
        logits = jnp.einsum("bsH,HV->bsV", hidden, head)
        return masked_cross_entropy(logits, labels)

    def head_loss(self, params: Any, x: jnp.ndarray, batch: Any
                  ) -> jnp.ndarray:
        """Loss tail for layer streaming: post-last-layer activations →
        final norm → CE.  ``params`` needs only the resident leaves
        (final_norm + embed/lm_head)."""
        c = self.config
        _, labels = self.batch_labels(batch)
        hidden = _rms_norm(x, params["final_norm"].astype(c.dtype),
                           c.rms_norm_eps)
        return self._ce_from_hidden(params, hidden, labels)

    #: resident leaves head_loss_manual_tp reads — the engine narrows the
    #: manual-region head argument to exactly these (a module reading more
    #: must extend this, or the key goes missing inside the shard_map)
    manual_tp_head_param_keys = ("final_norm", "lm_head")

    def head_loss_manual_tp(self, params: Any, x: jnp.ndarray, batch: Any
                            ) -> jnp.ndarray:
        """Vocab-parallel loss tail for the manual-TP 1F1B region:
        ``params["lm_head"]`` is this rank's COLUMN shard ``[H, V/tp]``
        (Megatron parallel cross entropy) — local logits, cross-rank
        max-shifted log-sum-exp and gold-logit gather via explicit
        collectives, so no rank ever materializes (or differentiates)
        the full-vocab projection.  Numerics match
        :func:`masked_cross_entropy` on the gathered logits."""
        from ..parallel.mesh import AXIS_TENSOR

        c = self.config
        _, labels = self.batch_labels(batch)
        hidden = _rms_norm(x, params["final_norm"].astype(c.dtype),
                           c.rms_norm_eps)
        W = params["lm_head"].astype(c.dtype)          # [H, V/tp] local
        vshard = W.shape[-1]
        rank = jax.lax.axis_index(AXIS_TENSOR)

        def chunk_nll(hid, lab):
            """(Σ nll over valid, valid count) for one sequence chunk."""
            logits = jnp.einsum("bsH,HV->bsV", _tp_copy(hid),
                                W).astype(jnp.float32)
            valid = lab != -100
            # max-shift across shards; zero-grad (d lse/dm is 0)
            m = _tp_max(jnp.max(logits, axis=-1))       # [B, s]
            se = jnp.sum(jnp.exp(logits - m[..., None]), axis=-1)
            lse = jnp.log(_tp_reduce(se)) + m
            # gold logit lives on exactly one shard
            off = jnp.where(valid, lab, 0) - rank * vshard
            in_shard = (off >= 0) & (off < vshard)
            gold_loc = jnp.take_along_axis(
                logits, jnp.clip(off, 0, vshard - 1)[..., None],
                -1)[..., 0]
            gold = _tp_reduce(jnp.where(in_shard, gold_loc, 0.0))
            nll = lse - gold
            return (jnp.sum(jnp.where(valid, nll, 0.0)),
                    jnp.sum(valid).astype(jnp.int32))

        T = c.loss_tiles
        if T > 1 and hidden.shape[1] % T == 0:
            # ALST sequence tiling, vocab-parallel flavor: each tile's
            # [B, S/T, V/tp] logits live only inside its (rematerialized)
            # scan step — the same memory bound head_loss gets from
            # sequence_tiled_loss
            B, S, H = hidden.shape
            hs = jnp.moveaxis(hidden.reshape(B, T, S // T, H), 1, 0)
            ls = jnp.moveaxis(labels.reshape(B, T, S // T), 1, 0)

            def body(carry, xs):
                tot, cnt = carry
                t, n = jax.checkpoint(chunk_nll)(xs[0], xs[1])
                return (tot + t, cnt + n), None

            (tot, cnt), _ = jax.lax.scan(
                body, (jnp.float32(0.0), jnp.int32(0)), (hs, ls))
        else:
            tot, cnt = chunk_nll(hidden, labels)
        return tot / jnp.maximum(cnt, 1)

    def loss(self, params: Any, batch: Any) -> jnp.ndarray:
        """Next-token cross entropy.  ``batch`` is ``{"input_ids": [B, S]}``
        (labels = shifted inputs) or ``{"input_ids", "labels"}`` with -100
        ignore positions (HF convention)."""
        input_ids, labels = self.batch_labels(batch)
        hidden, aux = self._forward_trunk(params, input_ids)
        ce = self._ce_from_hidden(params, hidden, labels)
        return ce + self.aux_loss_coef * aux
