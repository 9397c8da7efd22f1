"""Every kernel in ``ops/pallas/`` against its reference, at a model's shapes.

``chip_smoke.py`` runs this compiled on the TPU at the widths of the model
it trains and serves (``KernelShapes.for_model``); the CPU tests run the
same checks at a tiny size through the Pallas interpreter
(``interpret=True``).  A kernel that disagrees with its reference, returns
a non-finite value or does not compile fails the run: nothing here catches
an exception or substitutes a reference.

References are float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")`` on the same (rounded) inputs,
so what is measured is the kernel's own error.  Attention references are
evaluated a slab of query rows at a time — the ``[h, S, S]`` scores of a
whole 8k context do not fit beside the kernel's operands — and at the
streamed length on a sample of slabs against the whole context.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import lattice


class Check(NamedTuple):
    name: str
    error: float
    tolerance: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.error) and self.error <= self.tolerance)


#: Errors are max |got - want| over max |want|.  Attention and MoE outputs
#: leave the kernels rounded to the input dtype (bf16 keeps 8 mantissa
#: bits: 2^-9 = 2e-3 per element) after float32 accumulation over
#: bf16-pass MXU products; an indexing, masking or VMEM bug moves a result
#: by O(1) of its scale.  Measured on the v5e at Mistral-7B shapes
#: (PR 21): forward 2.1e-3..2.6e-3, gradients 4.2e-3..7.1e-3, MoE
#: combine 5.0e-3 — the tolerance leaves about 3x over the worst of them
#: (PR 32, the one-pass flash backward on bf16 operands: resident
#: gradients 4.2e-3..5.5e-3).
ATTENTION_TOL = 2e-2
#: one query row a sequence.  The padded-cache kernel does float32 VPU
#: arithmetic, so only the output rounding is left: measured 6.5e-4.  The
#: paged kernel's products run on the MXU with float32 accumulation, its
#: probabilities rounded to the cache dtype for PV: measured 1.44e-3
#: (PR 25) — 3.5x margin, and well under a wrong page or length (O(1))
DECODE_TOL = 5e-3


@dataclasses.dataclass(frozen=True)
class KernelShapes:
    """The shapes the checks run at — a model's widths plus the sizes the
    serving and optimizer kernels meet beside it."""

    heads: int
    kv_heads: int
    head_dim: int
    hidden: int
    ffn: int
    dtype: Any
    #: resident flash / block-sparse length (the model's context)
    seq: int
    window: Optional[int]
    #: a length whose K/V planes exceed ``lattice.RESIDENT_VMEM_ELEMS``
    stream_seq: int
    #: rows per reference slab at ``seq`` and at ``stream_seq``
    slab: int
    stream_slab: int
    #: decode batch, padded cache length, page size
    slots: int
    cache_len: int
    page: int
    #: fused-Adam leaf (rows of ``hidden``)
    adam_rows: int
    #: MoE plane: tokens routed top-k over experts of width ``hidden``
    moe_tokens: int
    moe_experts: int
    moe_top_k: int
    #: block-sparse cell size
    sparse_cell: int

    @classmethod
    def for_model(cls, cfg: Any) -> "KernelShapes":
        """A ``LlamaConfig``'s widths; MoE at Mixtral-8x7B's routing
        (8 experts, top-2) over the same hidden size."""
        seq = int(cfg.max_seq_len)
        stream = seq
        while lattice.resident_fits(stream, cfg.hd):
            stream *= 2
        return cls(heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
                   head_dim=cfg.hd, hidden=cfg.hidden_size,
                   ffn=cfg.intermediate_size, dtype=cfg.dtype, seq=seq,
                   window=cfg.sliding_window, stream_seq=stream,
                   slab=min(512, seq), stream_slab=min(128, seq), slots=8,
                   cache_len=seq, page=16,
                   adam_rows=cfg.vocab_size, moe_tokens=2048,
                   moe_experts=8, moe_top_k=2, sparse_cell=128)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _rel_err(got, want):
    """max |got - want| over max |want| (a device scalar; ``Check`` takes
    its ``float``)."""
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    return jnp.max(jnp.abs(got - want)) / (jnp.max(jnp.abs(want)) + 1e-6)


def _normal(rng: np.random.RandomState, shape, dtype, scale=1.0):
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32)
                       * scale).astype(dtype)


def _mod(name: str):
    # resolved at call time so a test can swap a kernel for a broken one
    return importlib.import_module(f"{__package__}.{name}")


def _reference_rows(q_rows, k, v, mask_rows):
    """float32 attention of a slab of query rows against the whole context.
    ``q_rows [B, R, h, d]``, ``k``/``v [B, T, h, d]``, ``mask_rows``
    broadcastable to ``[B, h, R, T]``; rows with no live key come out 0."""
    with jax.default_matmul_precision("highest"):
        scale = 1.0 / np.sqrt(q_rows.shape[-1])
        s = jnp.einsum("bqhd,bkhd->bhqk", q_rows.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        s = jnp.where(mask_rows, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(jnp.any(mask_rows, axis=-1, keepdims=True), p, 0.0)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))


def _reference_slabs(q, k, v, starts: Sequence[int], slab: int, mask_fn):
    """Reference output ``[B, len(starts)·slab, h, d]`` for the query slabs
    beginning at ``starts``; ``mask_fn(start)`` gives that slab's mask."""

    @jax.checkpoint
    def one(start):
        rows = jax.lax.dynamic_slice_in_dim(q, start, slab, axis=1)
        return _reference_rows(rows, k, v, mask_fn(start))

    outs = jax.lax.map(one, jnp.asarray(starts, jnp.int32))
    B, _, h, d = q.shape
    return jnp.moveaxis(outs, 0, 1).reshape(B, len(starts) * slab, h, d)


def _gather_slabs(x, starts: Sequence[int], slab: int):
    return jnp.concatenate([x[:, s:s + slab] for s in starts], axis=1)


def _attention_checks(name: str, kernel_fn, q, k, v, starts, slab, mask_fn
                      ) -> List[Check]:
    """Forward and backward of ``kernel_fn(q, k, v)`` against the slab
    reference, with a random cotangent that is zero outside the slabs (so
    dk/dv compare over the whole context)."""
    rng = np.random.RandomState(7)
    B, S, h, d = q.shape
    cot_rows = _normal(rng, (B, len(starts) * slab, h, d), q.dtype)
    cot = jnp.zeros(q.shape, q.dtype)
    for i, s in enumerate(starts):
        cot = cot.at[:, s:s + slab].set(cot_rows[:, i * slab:(i + 1) * slab])

    got, vjp = jax.vjp(kernel_fn, q, k, v)
    g_dq, g_dk, g_dv = vjp(cot)
    want, ref_vjp = jax.vjp(
        lambda q, k, v: _reference_slabs(q, k, v, starts, slab, mask_fn),
        q, k, v)
    w_dq, w_dk, w_dv = ref_vjp(cot_rows.astype(jnp.float32))
    errors = {
        "fwd": _rel_err(_gather_slabs(got, starts, slab), want),
        "dq": _rel_err(_gather_slabs(g_dq, starts, slab),
                       _gather_slabs(w_dq, starts, slab)),
        "dk": _rel_err(g_dk, w_dk),
        "dv": _rel_err(g_dv, w_dv),
    }
    return [Check(f"{name}_{k}", float(e), ATTENTION_TOL)
            for k, e in errors.items()]


def _position_mask(S: int, slab: int, causal: bool, window, segment_ids):
    from ..masks import local_attention_mask

    def mask_fn(start):
        m = local_attention_mask(start + jnp.arange(slab), jnp.arange(S),
                                 causal, window)[None, None]
        if segment_ids is not None:
            q_seg = jax.lax.dynamic_slice_in_dim(segment_ids, start, slab,
                                                 axis=1)
            m = m & (q_seg[:, None, :, None]
                     == segment_ids[:, None, None, :])
        return m

    return mask_fn


# ---------------------------------------------------------------------------
# the checks
# ---------------------------------------------------------------------------


def check_flash(s: KernelShapes, interpret: bool) -> List[Check]:
    """Resident kernels at the model's context: causal + window, with and
    without packed segments; every query row compared."""
    fa = _mod("flash_attention")
    rng = np.random.RandomState(0)
    shape = (1, s.seq, s.heads, s.head_dim)
    q, k, v = (_normal(rng, shape, s.dtype) for _ in range(3))
    starts = list(range(0, s.seq, s.slab))
    # three packed documents of unequal length
    bounds = np.array([0, s.seq // 3 + 5, (3 * s.seq) // 4, s.seq])
    seg = jnp.asarray(np.searchsorted(bounds, np.arange(s.seq),
                                      side="right")[None, :].astype(np.int32))
    out: List[Check] = []
    for tag, segment_ids in (("flash_resident", None),
                             ("flash_resident_segments", seg)):
        out += _attention_checks(
            tag,
            lambda q, k, v: fa.flash_attention(
                q, k, v, True, window=s.window, segment_ids=segment_ids,
                interpret=interpret),
            q, k, v, starts, s.slab,
            _position_mask(s.seq, s.slab, True, s.window, segment_ids))
    return out


def check_flash_streamed(s: KernelShapes, interpret: bool) -> List[Check]:
    """Streamed kernels past VMEM residency; first, middle (straddling a
    block edge) and last slab of query rows against the whole context."""
    if lattice.resident_fits(s.stream_seq, s.head_dim):
        raise ValueError(
            f"stream_seq={s.stream_seq} x d={s.head_dim} fits "
            f"lattice.RESIDENT_VMEM_ELEMS: this would check the resident "
            f"kernels twice")
    fa = _mod("flash_attention")
    rng = np.random.RandomState(1)
    S = s.stream_seq
    shape = (1, S, s.heads, s.head_dim)
    q, k, v = (_normal(rng, shape, s.dtype) for _ in range(3))
    starts = [0, S // 2 - s.stream_slab // 2, S - s.stream_slab]
    return _attention_checks(
        "flash_streamed",
        lambda q, k, v: fa.flash_attention(q, k, v, True, window=s.window,
                                           interpret=interpret),
        q, k, v, starts, s.stream_slab,
        _position_mask(S, s.stream_slab, True, s.window, None))


def _decode_lengths(s: KernelShapes) -> jnp.ndarray:
    """Ragged lengths: one token, a partial page, either side of the
    window, the full cache."""
    w = s.window or s.cache_len // 2
    picks = [1, s.page + 3, w // 2, w, w + 1, s.cache_len - s.page - 1,
             s.cache_len - 1, s.cache_len]
    return jnp.asarray(np.resize(np.clip(picks, 1, s.cache_len),
                                 s.slots).astype(np.int32))


def check_decode(s: KernelShapes, interpret: bool) -> List[Check]:
    da = _mod("decode_attention")
    rng = np.random.RandomState(2)
    q = _normal(rng, (s.slots, s.heads, s.head_dim), s.dtype)
    cache = (s.slots, s.cache_len, s.kv_heads, s.head_dim)
    kc, vc = _normal(rng, cache, s.dtype), _normal(rng, cache, s.dtype)
    lengths = _decode_lengths(s)
    got = da.decode_attention(q, kc, vc, lengths, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        want = da._reference_decode(q.astype(jnp.float32),
                                    kc.astype(jnp.float32),
                                    vc.astype(jnp.float32), lengths)
    return [Check("decode_attention", float(_rel_err(got, want)),
                  DECODE_TOL)]


def _paged_lengths(s: KernelShapes, step: int) -> jnp.ndarray:
    """The decode lengths, then as many rows in the serving regime: far
    shorter than the table, ending inside one of the kernel's compute
    steps of ``step`` keys, on its edge and one key past it."""
    picks = [step // 2 + 3, step - 1, step, step + 1, 2 * step + s.page // 2,
             3 * step + s.page + 5, 5 * step - 3, s.page]
    short = np.resize(np.clip(picks, 1, s.cache_len), s.slots)
    return jnp.concatenate([_decode_lengths(s),
                            jnp.asarray(short.astype(np.int32))])


def check_paged(s: KernelShapes, interpret: bool) -> List[Check]:
    """Paged decode through a shuffled block table, with the model's
    window and without."""
    pa = _mod("paged_attention")
    rng = np.random.RandomState(3)
    max_blocks = s.cache_len // s.page
    rows = 2 * s.slots
    num_pages = rows * max_blocks + 1         # page 0 stays scratch
    q = _normal(rng, (rows, s.heads, s.head_dim), s.dtype)
    pool = (num_pages, s.page, s.kv_heads, s.head_dim)
    k_pool, v_pool = _normal(rng, pool, s.dtype), _normal(rng, pool, s.dtype)
    tables = jnp.asarray(rng.permutation(np.arange(1, num_pages)).reshape(
        rows, max_blocks).astype(np.int32))
    lengths = _paged_lengths(s, s.page * pa.pages_per_step(
        s.page, s.kv_heads, s.heads, s.head_dim,
        jnp.dtype(s.dtype).itemsize, max_blocks))
    out = []
    for window in dict.fromkeys((None, s.window)):
        got = pa.paged_decode_attention(q, k_pool, v_pool, tables, lengths,
                                        interpret=interpret, window=window)
        with jax.default_matmul_precision("highest"):
            want = pa.paged_decode_reference(
                q.astype(jnp.float32), k_pool.astype(jnp.float32),
                v_pool.astype(jnp.float32), tables, lengths, window)
        out.append(Check(f"paged_decode(window={window})",
                         float(_rel_err(got, want)), DECODE_TOL))
    return out


def check_paged_hybrid(s: KernelShapes, interpret: bool) -> List[Check]:
    """Paged decode where K and V rows differ in width and many query
    heads share a KV head, at the widths of the model that has them (64
    query heads, K rows of 192 in two 128-lane planes, V rows of 128): a
    full layer's 4 KV heads, and a window layer's 8 with its window of 128
    and a sink."""
    pa = _mod("paged_attention")
    rng = np.random.RandomState(11)
    heads, k_dim, v_dim, window = 64, 192, 128, 128
    max_blocks = max(s.cache_len // s.page, 2 * window // s.page)
    rows = s.slots
    pages = rows * max_blocks + 1
    q = _normal(rng, (rows, heads, k_dim), s.dtype)
    sink = _normal(rng, (heads,), jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, pages)).reshape(
        rows, max_blocks).astype(np.int32))
    top = max_blocks * s.page
    # under the window, on it, a window that straddles nine pages, the
    # whole table, one key past a 512-key step (the full kind's)
    lengths = jnp.asarray(np.resize(np.clip(
        [window - 1, window, window + 4 * s.page + 8, top, 3, 513, top // 2,
         s.page], 1, top), rows).astype(np.int32))
    out = []
    for kv_heads, reach, logits in ((4, None, None), (8, window, sink)):
        k = _normal(rng, (pages, s.page, kv_heads, 256), s.dtype)
        k = k.at[..., k_dim:].set(0)          # the last plane's padding
        k_pool = jnp.concatenate([k[..., :128], k[..., 128:]], axis=0)
        v_pool = _normal(rng, (pages, s.page, kv_heads, v_dim), s.dtype)
        got = pa.paged_decode_attention(
            q, k_pool, v_pool, tables, lengths, interpret=interpret,
            window=reach, sink=logits, k_planes=2, plane_stride=pages)
        with jax.default_matmul_precision("highest"):
            want = pa.paged_decode_reference(
                q.astype(jnp.float32), k_pool.astype(jnp.float32),
                v_pool.astype(jnp.float32), tables, lengths, reach, logits,
                2, pages)
        out.append(Check(
            f"paged_decode(k192_v128, kv_heads={kv_heads}, window={reach}, "
            f"sink={logits is not None})", float(_rel_err(got, want)),
            DECODE_TOL))
    return out


def check_paged_latent(s: KernelShapes, interpret: bool) -> List[Check]:
    """Paged decode over a latent cache at the widths of the model that
    has one: 128 query heads on ONE cached row a token of 512 + 64 numbers
    in five 128-lane planes, whose leading 512 are also the value, pages
    of 128 tokens, the scores' scale that of the 192-wide head: a token a
    row as its decode rows are, and as many tokens a row as the kernel's
    rule gives a prefill chunk of one page."""
    pa = _mod("paged_attention")
    rng = np.random.RandomState(13)
    heads, k_dim, v_dim, page, planes = 128, 576, 512, 128, 5
    max_blocks = max(s.cache_len // page, 6)
    rows = s.slots
    pages = rows * max_blocks + 1
    tables = jnp.asarray(rng.permutation(np.arange(1, pages)).reshape(
        rows, max_blocks).astype(np.int32))
    top = max_blocks * page
    k = _normal(rng, (pages, page, 1, planes * 128), s.dtype)
    k = k.at[..., k_dim:].set(0)              # the last plane's padding
    k_pool = jnp.concatenate([k[..., p * 128:(p + 1) * 128]
                              for p in range(planes)], axis=0)
    kw = dict(k_planes=planes, plane_stride=pages, v_in_k=v_dim,
              scale=192 ** -0.5)

    def case(what, q_shape, lengths, shortest):
        q = _normal(rng, q_shape, s.dtype)
        lengths = jnp.asarray(np.resize(np.clip(lengths, shortest, top),
                                        rows).astype(np.int32))
        got = pa.paged_decode_attention(q, k_pool, None, tables, lengths,
                                        interpret=interpret, **kw)
        with jax.default_matmul_precision("highest"):
            want = pa.paged_decode_reference(
                q.astype(jnp.float32), k_pool.astype(jnp.float32), None,
                tables, lengths, **kw)
        # a dead slot gives zeros
        live = (lengths > 0).reshape((rows,) + (1,) * (q.ndim - 1))
        return Check(f"paged_decode(latent k576 v=k[:512], {what})",
                     float(_rel_err(jnp.where(live, got, 0),
                                    jnp.where(live, want, 0))), DECODE_TOL)

    # a chunk's rows: T consecutive tokens a row, lengths the last one's,
    # from a sequence's first group to the table's end
    tokens = pa.query_tokens_per_row(
        page, page, 1, heads, planes * 128, jnp.dtype(s.dtype).itemsize,
        max_blocks, 0)
    return [
        case("128 heads on 1", (rows, heads, k_dim),
             [page - 1, page, page + 1, top, 3, 2 * page + 5, top // 2, 0],
             0),
        case(f"{tokens} tokens a row", (rows, tokens, heads, k_dim),
             [tokens, page - 1, page, page + 1, page + tokens - 1, top,
              2 * page + 5, top // 2], tokens)]


def check_fused_adam(s: KernelShapes, interpret: bool) -> List[Check]:
    """One-pass Adam and the grad-norm read on one large fp32 leaf.  The
    kernel and the reference run the same fp32 formula; they may differ by
    FMA contraction only."""
    fo = _mod("fused_optimizer")
    rng = np.random.RandomState(4)
    shape = (s.adam_rows, s.hidden)
    p = _normal(rng, shape, jnp.float32, 0.02)
    g = _normal(rng, shape, jnp.float32, 1e-3)
    m = _normal(rng, shape, jnp.float32, 1e-3)
    v = jnp.square(_normal(rng, shape, jnp.float32, 1e-3))
    cfg = fo.FusedAdamConfig(weight_decay=0.01)

    # one program, as in the engine's step: eagerly, every pad, reshape and
    # reference intermediate of this 0.5 GB plane would stay live at once
    @jax.jit
    def errors(p, g, m, v):
        count = jnp.int32(3)
        want = fo.reference_adam_tree(p, g, m, v, count, 1e-3, 0.5, cfg)
        got = fo.fused_adam_tree(p, g, m, v, count, 1e-3, 0.5, cfg,
                                 interpret=interpret)
        sq_want = jnp.sum(jnp.square(g))
        sq_got = fo.tree_sqsum({"g": g}, interpret=interpret)
        return ([_rel_err(a, b) for a, b in zip(got, want)],
                jnp.abs(sq_got - sq_want) / sq_want)

    adam, sqsum = errors(p, g, m, v)
    out = [Check(f"fused_adam_{n}", float(e), 1e-5)
           for n, e in zip(("param", "mu", "nu"), adam)]
    # the two sums add 1e8 fp32 terms in different orders
    out.append(Check("tree_sqsum", float(sqsum), 1e-4))
    return out


def check_moe(s: KernelShapes, interpret: bool) -> List[Check]:
    """Dispatch/combine row gathers under a capacity-stressed top-k
    routing.  Dispatch only moves rows, so it must agree exactly."""
    from ...moe.sharded_moe import top_k_gating_indices

    md = _mod("moe_dispatch")
    rng = np.random.RandomState(5)
    T, E, K, H = s.moe_tokens, s.moe_experts, s.moe_top_k, s.hidden
    C = T * K // E                      # capacity factor 1: some drops
    logits = _normal(rng, (T, E), jnp.float32)
    gi, _, _ = top_k_gating_indices(logits, K, C)
    src_idx, flat_idx = md.routing_to_indices(gi.expert_idx, gi.slot,
                                              gi.keep, E, C)
    tokens = _normal(rng, (T, H), s.dtype)
    want_in = md.dispatch_reference(tokens, src_idx)
    got_in = md.pallas_dispatch(tokens, src_idx, interpret=interpret)
    expert_out = _normal(rng, (E, C, H), s.dtype)
    gates = gi.gate.T
    got_y = md.pallas_combine(expert_out, flat_idx, gates,
                              interpret=interpret)
    with jax.default_matmul_precision("highest"):
        want_y = md.combine_reference(expert_out.astype(jnp.float32),
                                      flat_idx, gates)
    mismatched = float(jnp.sum(got_in != want_in))
    return [Check("moe_dispatch_mismatched_elements", mismatched, 0.0),
            Check("moe_combine", float(_rel_err(got_y, want_y)),
                  ATTENTION_TOL)]


def check_moe_grouped(s: KernelShapes, interpret: bool) -> List[Check]:
    """The dropless expert layer (rows sorted by expert, the two grouped
    matmuls, the weighted sum back) against each expert run over all
    tokens in float32.  Experts of OLMoE's width, or the model's FFN where
    that is narrower; routing skewed so that groups differ in size.  The
    weights are a stack of two layers read at layer 1, as a serving
    program reads them: the layer's offset is part of what is checked."""
    gm = _mod("moe_grouped_matmul")
    rng = np.random.RandomState(8)
    T, E, K, H = s.moe_tokens, s.moe_experts, s.moe_top_k, s.hidden
    inner = min(s.ffn, 1024)
    logits = _normal(rng, (T, E), jnp.float32) + jnp.linspace(1.0, 0.0, E)
    gates, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), K)
    x = _normal(rng, (T, H), s.dtype)
    layer = 1
    stack_gate, stack_up = (_normal(rng, (2, E, H, inner), s.dtype, H ** -0.5)
                            for _ in range(2))
    stack_down = _normal(rng, (2, E, inner, H), s.dtype, inner ** -0.5)
    plan = gm.plan_groups(idx, E, gm.tile_rows_for(T * K, E, s.dtype))
    act = gm.grouped_swiglu(gm.gather_rows(x, plan), stack_gate, stack_up,
                            layer, plan, interpret=interpret)
    got = gm.combine_rows(gm.grouped_matmul(act, stack_down, layer, plan,
                                            interpret=interpret), plan, gates)
    w_gate, w_up, w_down = (w[layer] for w in
                            (stack_gate, stack_up, stack_down))

    xe = x.astype(jnp.float32)

    def one_expert(y, e):
        out = (jax.nn.silu(xe @ w_gate[e].astype(jnp.float32))
               * (xe @ w_up[e].astype(jnp.float32))
               ) @ w_down[e].astype(jnp.float32)
        weight = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=1)
        return y + weight[:, None] * out, None

    with jax.default_matmul_precision("highest"):
        want, _ = jax.lax.scan(one_expert, jnp.zeros((T, H), jnp.float32),
                               jnp.arange(E))
    return [Check("moe_grouped_matmul", float(_rel_err(got, want)),
                  ATTENTION_TOL)]


def check_moe_share(s: KernelShapes, interpret: bool) -> List[Check]:
    """The grouped expert matmul over a SHARE of the router's experts (16
    held of 256, 8 a token: a decode step's 256 rows put ~8 on each held
    expert and 15 assignments of 16 land elsewhere), so that most of the
    plan's static tiles are empty, at the tile the layer gives a share (the
    router's mean group's: 16 rows here); experts of width 2048 over the
    model's hidden size, or its FFN where that is narrower.  Against each
    held expert run over all tokens in float32."""
    gm = _mod("moe_grouped_matmul")
    rng = np.random.RandomState(12)
    T, routed, held, K, H = 256, 256, 16, 8, s.hidden
    inner = min(s.ffn, 2048)
    score = jax.nn.sigmoid(_normal(rng, (T, routed), jnp.float32))
    gates, idx = jax.lax.top_k(score, K)
    first = 32
    local = idx - first
    here = (local >= 0) & (local < held)
    gates = jnp.where(here, gates, 0.0)
    x = _normal(rng, (T, H), s.dtype)
    layer = 1
    stack_gate, stack_up = (_normal(rng, (2, held, H, inner), s.dtype,
                                    H ** -0.5) for _ in range(2))
    stack_down = _normal(rng, (2, held, inner, H), s.dtype, inner ** -0.5)
    plan = gm.plan_groups(local, held, gm.tile_rows_for(
        T * K, routed, s.dtype), share=True)
    act = gm.grouped_swiglu(gm.gather_rows(x, plan), stack_gate, stack_up,
                            layer, plan, interpret=interpret)
    got = gm.combine_rows(gm.grouped_matmul(act, stack_down, layer, plan,
                                            interpret=interpret), plan, gates)
    xe = x.astype(jnp.float32)

    def one_expert(y, e):
        out = (jax.nn.silu(xe @ stack_gate[layer, e].astype(jnp.float32))
               * (xe @ stack_up[layer, e].astype(jnp.float32))
               ) @ stack_down[layer, e].astype(jnp.float32)
        weight = jnp.sum(jnp.where(local == e, gates, 0.0), axis=1)
        return y + weight[:, None] * out, None

    with jax.default_matmul_precision("highest"):
        want, _ = jax.lax.scan(one_expert, jnp.zeros((T, H), jnp.float32),
                               jnp.arange(held))
    tiles = int(plan.tile_group.shape[0])
    return [Check(f"moe_grouped_matmul(share 16 of 256, "
                  f"{int(plan.num_tiles[0])} of {tiles} tiles in use)",
                  float(_rel_err(got, want)), ATTENTION_TOL)]


def check_moe_latent(s: KernelShapes, interpret: bool) -> List[Check]:
    """Experts of TWO matrices with ``relu(.)²`` between, at a latent's
    width and not the model's (1,024 wide, inner 2,688: a LatentMoE
    layer's), over a share of the router's experts (16 held of 512, 22 a
    token, at the router's tile: 32 rows for its mean group of 11):
    ``grouped_relu2`` then the plain grouped matmul, against each held
    expert run over all tokens in float32."""
    gm = _mod("moe_grouped_matmul")
    rng = np.random.RandomState(13)
    T, routed, held, K, W, inner = 256, 512, 16, 22, 1024, 2688
    score = jax.nn.sigmoid(_normal(rng, (T, routed), jnp.float32))
    gates, idx = jax.lax.top_k(score, K)
    local = idx - 64
    gates = jnp.where((local >= 0) & (local < held), gates, 0.0)
    u = _normal(rng, (T, W), s.dtype)
    layer = 1
    stack_up = _normal(rng, (2, held, W, inner), s.dtype, W ** -0.5)
    stack_down = _normal(rng, (2, held, inner, W), s.dtype, inner ** -0.5)
    plan = gm.plan_groups(local, held, gm.tile_rows_for(
        T * K, routed, s.dtype), share=True)
    act = gm.grouped_relu2(gm.gather_rows(u, plan), stack_up, layer, plan,
                           interpret=interpret)
    got = gm.combine_rows(gm.grouped_matmul(act, stack_down, layer, plan,
                                            interpret=interpret), plan, gates)
    ue = u.astype(jnp.float32)

    def one_expert(y, e):
        out = jnp.square(jax.nn.relu(
            ue @ stack_up[layer, e].astype(jnp.float32))
        ) @ stack_down[layer, e].astype(jnp.float32)
        weight = jnp.sum(jnp.where(local == e, gates, 0.0), axis=1)
        return y + weight[:, None] * out, None

    with jax.default_matmul_precision("highest"):
        want, _ = jax.lax.scan(one_expert, jnp.zeros((T, W), jnp.float32),
                               jnp.arange(held))
    return [Check("moe_grouped_matmul_relu2(share 16 of 512 at a latent of "
                  "1024)", float(_rel_err(got, want)), ATTENTION_TOL)]


def _ssm_update_checks(name: str, s: KernelShapes, interpret: bool,
                       heads: int, groups: int, state: int, lanes: int,
                       decay_a_lane: bool) -> List[Check]:
    """A decode step of the recurrence on ``slots`` sequences in a pool of
    three layers ``[…, heads, state, lanes]``, in place, against the
    reference: the pool after, and ``y``.  Kernel and reference run the
    same float32 formula on the VPU; they may differ by FMA contraction
    and the order of the read-out's sum."""
    ssu = _mod("ssm_state_update")
    rng = np.random.RandomState(12)
    rows = s.slots
    pool = _normal(rng, (3, rows + 2, heads, state, lanes), jnp.float32)
    a = jnp.exp(-jnp.abs(_normal(
        rng, (rows, heads) + ((lanes,) if decay_a_lane else ()),
        jnp.float32)))
    dx = _normal(rng, (rows, heads, lanes), jnp.float32, 0.1)
    b = _normal(rng, (rows, groups, state), s.dtype)
    c = _normal(rng, (rows, groups, state), s.dtype)

    @jax.jit
    def errors(pool, a, dx, b, c):
        want = ssu.ssm_state_update_reference(pool, 1, 1, a, dx, b, c)
        # the kernel writes the pool it is given: hand it a copy, after
        # the reference has read the original
        got = ssu.ssm_state_update(pool + 0.0, 1, 1, a, dx, b, c,
                                   interpret=interpret)
        return [_rel_err(g, w) for g, w in zip(got, want)]

    state_err, y_err = errors(pool, a, dx, b, c)
    return [Check(f"{name}_state", float(state_err), 1e-5),
            Check(f"{name}_y", float(y_err), 1e-4)]


def check_ssm_state_update(s: KernelShapes, interpret: bool) -> List[Check]:
    """At a published mixer's widths (32 heads of 128 in 2 groups, state
    256), a head a lane row and the decay a scalar a head."""
    return _ssm_update_checks("ssm_state_update", s, interpret, 32, 2, 256,
                              128, False)


def check_ssm_state_update_lanes(s: KernelShapes, interpret: bool
                                 ) -> List[Check]:
    """Where a lane row holds TWO heads of 64 (128 heads in 8 groups, state
    128: held ``[64, 128, 128]``) and the decay is a value a lane: the
    kernel's second form."""
    return _ssm_update_checks("ssm_state_update_lanes", s, interpret, 64, 8,
                              128, 128, True)


def check_delta_state_update(s: KernelShapes, interpret: bool) -> List[Check]:
    """The decode step's update of a gated delta rule's states IN PLACE in
    their pool, at a published layer's widths (64 heads of 128 keys x 128
    values, a decay a key channel), against the ``jax.numpy`` reference:
    the pool after, and ``o``.  ``β`` up to 2 and a decay down to a fifth a
    token; kernel and reference run the same float32 formula on the VPU
    (the kernel folds ``β`` into ``k`` and ``v`` first)."""
    dsu = _mod("delta_state_update")
    rng = np.random.RandomState(13)
    rows, heads, d = s.slots, 64, 128
    pool = _normal(rng, (3, rows + 2, heads, d, d), jnp.float32)
    a = jnp.exp(-1.6 * jnp.abs(_normal(rng, (rows, heads, d), jnp.float32)))
    k, q = (_normal(rng, (rows, heads, d), jnp.float32, d ** -0.5)
            for _ in range(2))
    beta = 2.0 * jax.nn.sigmoid(_normal(rng, (rows, heads), jnp.float32))
    v = _normal(rng, (rows, heads, d), jnp.float32)

    @jax.jit
    def errors(pool, a, k, q, beta, v):
        want = dsu.delta_state_update_reference(pool, 1, 1, a, k, q, beta, v)
        # the kernel writes the pool it is given: hand it a copy, after
        # the reference has read the original
        got = dsu.delta_state_update(pool + 0.0, 1, 1, a, k, q, beta, v,
                                     interpret=interpret)
        return [_rel_err(g, w) for g, w in zip(got, want)]

    state_err, o_err = errors(pool, a, k, q, beta, v)
    return [Check("delta_state_update_state", float(state_err), 1e-5),
            Check("delta_state_update_o", float(o_err), 1e-4)]


def check_conv_tail_update(s: KernelShapes, interpret: bool) -> List[Check]:
    """The decode step's conv over the rows' held tails IN PLACE in their
    pool against the ``jax.numpy`` reference run in float32.  Twice: at the
    widest published conv (three streams of 8,192 channels, four taps, no
    bias; a serving step's 192 rows at slots 1 …): the pool after, which is
    copies (a dead row's as it lay) and so exact, and the conv's output,
    which the kernel rounds once to the rows' type; and as Nemotron-H's
    mixers call it, with no taps, at their ``[5, 129, 30720]`` tails and 128
    rows of 10,240 channels: the pool after and the rows' tails handed back
    as they lay, both copies and exact.  A quarter of the rows dead; the
    interpreter walks ``slots`` rows."""
    ctu = _mod("conv_tail_update")
    rng = np.random.RandomState(14)
    taps, checks = 4, []
    for name, layers, rows, spare, channels, conv, tol in (
            ("conv_tail_update", 3, 192, 2, 3 * 8192, True, DECODE_TOL),
            ("conv_tail_update_tails", 5, 128, 1, 10240, False, 0.0)):
        rows = s.slots if interpret else rows
        pool = _normal(rng, (layers, rows + spare, (taps - 1) * channels),
                       s.dtype)
        x = _normal(rng, (rows, channels), s.dtype)
        w = _normal(rng, (taps, channels), s.dtype, 0.5) if conv else None
        valid = jnp.asarray(rng.rand(rows) < 0.75, jnp.int32)

        @jax.jit
        def errors(pool, x, w, valid):
            want = ctu.conv_tail_update_reference(
                pool.astype(jnp.float32), 1, 1, x.astype(jnp.float32), w,
                None, valid)
            # the kernel writes the pool it is given: hand it a copy,
            # after the reference has read the original
            got = ctu.conv_tail_update(pool + 0, 1, 1, x, w, None, valid,
                                       interpret=interpret)
            return [_rel_err(g, wanted) for g, wanted in zip(got, want)]

        pool_err, out_err = errors(pool, x, w, valid)
        checks += [Check(f"{name}_pool", float(pool_err), 0.0),
                   Check(f"{name}_out", float(out_err), tol)]
    return checks


def check_quantizer(s: KernelShapes, interpret: bool) -> List[Check]:
    qz = _mod("quantizer")
    rng = np.random.RandomState(6)
    x = _normal(rng, (s.hidden, s.ffn), jnp.float32)
    codes, scales = qz.quantize_int8(x, interpret=interpret)
    ref_codes, _ = qz._ref_quantize(x)
    code_err = float(jnp.max(jnp.abs(codes.astype(jnp.int32)
                                     - ref_codes.astype(jnp.int32))))
    roundtrip = float(jnp.max(jnp.abs(qz.dequantize_int8(codes, scales)
                                      - x)))
    # |err| <= scale/2 per row, and scales are max|row| / 127
    bound = float(jnp.max(jnp.abs(x))) / 127.0
    # a value on a rounding boundary may land one code away
    return [Check("quantizer_codes", code_err, 1.0),
            Check("quantizer_roundtrip", roundtrip, bound * 1.01)]


def check_block_sparse(s: KernelShapes, interpret: bool) -> List[Check]:
    """Block-sparse forward and backward under a sliding-window layout
    with one global block (banded rows plus a dense column)."""
    from ..sparse_attention import (BSLongformerSparsityConfig,
                                    block_layout_to_token_mask)

    bsa = _mod("block_sparse_attention")
    rng = np.random.RandomState(8)
    shape = (1, s.seq, s.heads, s.head_dim)
    q, k, v = (_normal(rng, shape, s.dtype) for _ in range(3))
    cfg = BSLongformerSparsityConfig(
        num_heads=s.heads, block=s.sparse_cell, num_sliding_window_blocks=5,
        global_block_indices=(0,))
    mask = block_layout_to_token_mask(cfg.make_layout(s.seq), s.sparse_cell,
                                      causal=True)

    def mask_fn(start):
        return jax.lax.dynamic_slice_in_dim(mask, start, s.slab,
                                            axis=0)[None, None]

    return _attention_checks(
        "block_sparse",
        lambda q, k, v: bsa.block_sparse_attention(
            q, k, v, cfg, causal=True, interpret=interpret),
        q, k, v, list(range(0, s.seq, s.slab)), s.slab, mask_fn)


CHECKS = (check_flash, check_flash_streamed, check_decode, check_paged,
          check_paged_hybrid, check_paged_latent, check_fused_adam, check_moe,
          check_moe_grouped,
          check_moe_share, check_moe_latent, check_ssm_state_update,
          check_ssm_state_update_lanes, check_delta_state_update,
          check_conv_tail_update, check_quantizer,
          check_block_sparse)


def run_checks(shapes: KernelShapes, interpret: bool = False
               ) -> List[Check]:
    """Every check, in order; raises ``AssertionError`` naming each
    result that is non-finite or out of tolerance."""
    results: List[Check] = []
    for check in CHECKS:
        results += check(shapes, interpret)
    bad = [c for c in results if not c.ok]
    if bad:
        raise AssertionError(
            "kernel selfcheck FAILED: "
            + "; ".join(f"{c.name}: error {c.error:.3g} > tolerance "
                        f"{c.tolerance:.3g}" for c in bad))
    return results
