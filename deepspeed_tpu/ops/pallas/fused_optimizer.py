"""One-pass fused sharded optimizer — Pallas Adam + grad-norm kernels.

Role parity: the reference's fused CUDA Adam (``csrc/adam`` +
``ops/adam/fused_adam.py`` [K]) — multi-tensor apply collapsed into one
HBM sweep.  The optax chain the engine compiles costs 3–4 separate
sweeps over every gradient/param/moment plane per step (unscale sweep,
clip sweep, two moment updates, an ``updates`` tree materialized, then
``apply_updates``) — BENCH_r04 measured the isolated optax adamw update
at ``optax_adam_hbm_gbps = 352.9`` against the chip's ~820 GB/s peak.
The fused form is two passes total over the ZeRO shard:

1. :func:`tree_sqsum` — ONE read of the (still loss-scaled) grads
   producing the global grad-norm partial; the caller reduces it over
   the data-parallel group (comm verbs / GSPMD) and folds unscale +
   clip + overflow-zero into a single per-element multiplier.
2. :func:`fused_adam_tree` — ONE read of grads + params + moments and
   one write of params + moments: ``g·mult`` (unscale/clip applied on
   the fly), both Adam moments, bias correction, weight decay, and the
   param update, with ``input_output_aliases`` donating p/m/v in place.

Numerics mirror ``optax.scale_by_adam`` op-for-op — same formula, same
operation order.  Against the EAGER optax chain the first step from a
fresh state is bit-exact on the moments and ≤1 ulp on params; beyond
that the only divergence is XLA FMA contraction (``a·b + c`` fused into
one rounding where eager optax takes two — measured ≤1.2e-7 absolute on
params over 3 steps, and the engine's optax path is itself jitted so it
contracts the same way).  The parity tests in
``tests/unit/ops/test_fused_optimizer.py`` lock exactly this contract,
so an engine can flip ``kernels.fused_adam`` on without perturbing a
loss curve.
``interpret=True`` lowers the same kernels through the Pallas
interpreter, keeping parity testable without a chip; with ``interpret``
left open the entry points follow :mod:`.select` — compiled kernel on a
TPU, :func:`reference_adam_tree` / a plain ``jnp.sum`` elsewhere.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .select import reference_off_tpu

#: kernel tile: (rows, 128) fp32 — rows per grid step.  64 rows × 128
#: lanes × 4 B = 32 KiB per plane per step; 7 resident planes ≈ 224 KiB,
#: comfortably double-buffered in VMEM.
_LANES = 128
_ROWS = 64
#: the grad-norm read moves one plane, so its tile is larger: each grid
#: step leaves an (8, 128) partial (the smallest fp32 block Mosaic
#: stores), 1/128 of what it read
_SQ_ROWS = 1024


class FusedAdamConfig(NamedTuple):
    """Static hyperparameters (baked into the kernel at trace time)."""

    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0
    #: True → AdamW (decay added to the update direction, the optax
    #: ``adamw`` chain); False with weight_decay>0 → additive L2 (decay
    #: folded into the grads BEFORE the moments, the optax
    #: ``add_decayed_weights → adam`` chain)
    decoupled_wd: bool = True


def _pad_flat(x: jnp.ndarray, rows: int = _ROWS
              ) -> Tuple[jnp.ndarray, int]:
    """Flatten to [k·rows, 128] tileable form, zero-padded."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    chunk = rows * _LANES
    padded = -(-n // chunk) * chunk
    if padded != n:
        flat = jnp.concatenate(
            [flat, jnp.zeros((padded - n,), flat.dtype)])
    return flat.reshape(padded // _LANES, _LANES), n


# ---------------------------------------------------------------------------
# pass 1: grad-norm partials (one read per grad element)
# ---------------------------------------------------------------------------


def _sqsum_kernel(g_ref, out_ref):
    g = g_ref[...].astype(jnp.float32)
    # fold the tile's sublane groups onto one (8, 128) vreg-shaped partial
    # (VPU adds only; the cross-lane reduce happens once, outside)
    out_ref[0] = jnp.sum((g * g).reshape(-1, 8, _LANES), axis=0)


def leaf_sqsum(g: jnp.ndarray, interpret: Optional[bool] = None
               ) -> jnp.ndarray:
    """Σ g² of one leaf via the Pallas reduction kernel — one HBM read,
    per-tile partials summed in the surrounding graph."""
    from jax.experimental import pallas as pl

    if reference_off_tpu(interpret):
        g32 = g.astype(jnp.float32)
        return jnp.sum(g32 * g32)
    rows2d, _ = _pad_flat(g, _SQ_ROWS)
    steps = rows2d.shape[0] // _SQ_ROWS
    partials = pl.pallas_call(
        _sqsum_kernel,
        grid=(steps,),
        in_specs=[pl.BlockSpec((_SQ_ROWS, _LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, 8, _LANES), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((steps, 8, _LANES), jnp.float32),
        interpret=bool(interpret),
    )(rows2d)
    return jnp.sum(partials)


def tree_sqsum(grads: Any, interpret: Optional[bool] = None) -> jnp.ndarray:
    """Global Σ g² over a gradient tree (the grad-norm² partial for THIS
    shard; under GSPMD the sum over logical arrays already spans the
    mesh — multi-controller callers psum the result over the existing
    comm verbs)."""
    leaves = jax.tree.leaves(grads)
    if not leaves:
        return jnp.float32(0.0)
    return jnp.sum(jnp.stack([leaf_sqsum(g, interpret) for g in leaves]))


# ---------------------------------------------------------------------------
# pass 2: the fused update (one read of g/p/m/v, one write of p/m/v)
# ---------------------------------------------------------------------------


def _adam_kernel(sc_ref, p_ref, g_ref, m_ref, v_ref, po_ref, mo_ref,
                 vo_ref, *, b1: float, b2: float, eps: float, wd: float,
                 decoupled_wd: bool):
    """Mirrors ``optax.scale_by_adam``'s update op-for-op (same formula,
    same operation ORDER — the bit-parity contract).  ``sc_ref`` (SMEM)
    carries the traced scalars: [lr, mult, bc1, bc2]."""
    lr = sc_ref[0, 0]
    mult = sc_ref[0, 1]
    bc1 = sc_ref[0, 2]
    bc2 = sc_ref[0, 3]
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32) * mult
    if wd and not decoupled_wd:
        # optax chain(add_decayed_weights, adam): decay enters the moments
        g = g + wd * p
    m = m_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    m_new = (1.0 - b1) * g + b1 * m          # otu.tree_update_moment
    v_new = (1.0 - b2) * (g * g) + b2 * v    # ..._per_elem_norm
    mu_hat = m_new / bc1                     # tree_bias_correction
    nu_hat = v_new / bc2
    direction = mu_hat / (jnp.sqrt(nu_hat) + eps)
    if wd and decoupled_wd:
        # optax adamw: chain(scale_by_adam, add_decayed_weights, -lr)
        direction = direction + wd * p
    po_ref[...] = (p + (-lr) * direction).astype(po_ref.dtype)
    mo_ref[...] = m_new.astype(mo_ref.dtype)
    vo_ref[...] = v_new.astype(vo_ref.dtype)


def fused_adam_leaf(p, g, m, v, lr, mult, bc1, bc2,
                    cfg: FusedAdamConfig,
                    interpret: Optional[bool] = None):
    """One leaf through the fused kernel → (p_new, m_new, v_new); the
    kernel always (compiled unless ``interpret``) — the reference choice
    is :func:`fused_adam_tree`'s."""
    from jax.experimental import pallas as pl

    shape, dtype = p.shape, p.dtype
    p2, n = _pad_flat(p)
    g2, _ = _pad_flat(g)
    m2, _ = _pad_flat(m)
    v2, _ = _pad_flat(v)
    steps = p2.shape[0] // _ROWS
    scalars = jnp.stack([jnp.asarray(lr, jnp.float32),
                         jnp.asarray(mult, jnp.float32),
                         jnp.asarray(bc1, jnp.float32),
                         jnp.asarray(bc2, jnp.float32)]).reshape(1, 4)
    kern = functools.partial(_adam_kernel, b1=cfg.b1, b2=cfg.b2,
                             eps=cfg.eps, wd=cfg.weight_decay,
                             decoupled_wd=cfg.decoupled_wd)
    kwargs = {}
    if not interpret:
        # donate p/m/v into their outputs — the in-place contract that
        # makes this ONE read + ONE write per element (the interpreter
        # doesn't support aliasing)
        kwargs["input_output_aliases"] = {1: 0, 3: 1, 4: 2}
    plane = lambda i: (i, 0)
    from jax.experimental.pallas import tpu as pltpu

    p_new, m_new, v_new = pl.pallas_call(
        kern,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((1, 4), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((_ROWS, _LANES), plane),
            pl.BlockSpec((_ROWS, _LANES), plane),
            pl.BlockSpec((_ROWS, _LANES), plane),
            pl.BlockSpec((_ROWS, _LANES), plane),
        ],
        out_specs=[pl.BlockSpec((_ROWS, _LANES), plane)] * 3,
        out_shape=[jax.ShapeDtypeStruct(p2.shape, dtype),
                   jax.ShapeDtypeStruct(p2.shape, m.dtype),
                   jax.ShapeDtypeStruct(p2.shape, v.dtype)],
        interpret=bool(interpret),
        **kwargs,
    )(scalars, p2, g2, m2, v2)
    unpad = lambda x2, dt: x2.reshape(-1)[:n].reshape(shape).astype(dt)
    return (unpad(p_new, dtype), unpad(m_new, m.dtype),
            unpad(v_new, v.dtype))


def fused_adam_tree(params: Any, grads: Any, mu: Any, nu: Any,
                    count_inc, lr, mult=1.0,
                    cfg: FusedAdamConfig = FusedAdamConfig(),
                    interpret: Optional[bool] = None):
    """Whole-tree fused update → (params', mu', nu').

    ``count_inc`` is the POST-increment step (optax
    ``safe_int32_increment(count)``); ``mult`` is the combined
    per-element gradient multiplier (loss-scale unscale × clip factor ×
    overflow zero) the engine folds in so no separate unscale/clip
    sweeps exist."""
    if reference_off_tpu(interpret):
        return reference_adam_tree(params, grads, mu, nu, count_inc, lr,
                                   mult, cfg)
    # bias corrections once per step (optax: 1 - decay**count_inc)
    cf = count_inc
    bc1 = 1.0 - jnp.asarray(cfg.b1, jnp.float32) ** cf
    bc2 = 1.0 - jnp.asarray(cfg.b2, jnp.float32) ** cf
    flat_p, treedef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(mu)
    flat_v = jax.tree.leaves(nu)
    out_p, out_m, out_v = [], [], []
    for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v):
        pn, mn, vn = fused_adam_leaf(p, g, m, v, lr, mult, bc1, bc2, cfg,
                                     interpret)
        out_p.append(pn)
        out_m.append(mn)
        out_v.append(vn)
    return (jax.tree.unflatten(treedef, out_p),
            jax.tree.unflatten(treedef, out_m),
            jax.tree.unflatten(treedef, out_v))


# ---------------------------------------------------------------------------
# optax-state surgery (the engine keeps optax's state LAYOUT so
# checkpoints, ZeRO sharding specs, and the non-fused path interchange)
# ---------------------------------------------------------------------------


def find_adam_state(opt_state) -> Tuple[Tuple[int, ...], Any]:
    """Locate the ``ScaleByAdamState`` inside an optax chain's state —
    recursing through nested plain tuples, since a chain-of-chains
    (``chain(add_decayed_weights, adam)``) nests the inner chain's state
    → (index path, state).  Raises with the observed layout when the
    chain carries none (the engine gates fused mode on adam-family
    optimizers, so this is a config bug worth naming)."""
    def walk(st, path):
        if hasattr(st, "mu") and hasattr(st, "nu") and hasattr(st,
                                                               "count"):
            return path, st
        if isinstance(st, tuple) and not hasattr(st, "_fields"):
            for i, sub in enumerate(st):
                hit = walk(sub, path + (i,))
                if hit is not None:
                    return hit
        return None

    hit = walk(opt_state, ())
    if hit is None:
        states = (opt_state if isinstance(opt_state, tuple)
                  else (opt_state,))
        raise ValueError(
            f"no ScaleByAdamState in optimizer state (got "
            f"{[type(s).__name__ for s in states]}) — kernels.fused_adam "
            f"requires an adam/adamw-family optimizer")
    return hit


def replace_adam_state(opt_state, path: Tuple[int, ...], new_state):
    if not path:
        return new_state
    if isinstance(opt_state, tuple) and not hasattr(opt_state, "_fields"):
        i = path[0]
        return (opt_state[:i]
                + (replace_adam_state(opt_state[i], path[1:], new_state),)
                + opt_state[i + 1:])
    return new_state


def apply_fused_adam(opt_state, params, grads, lr, mult,
                     cfg: FusedAdamConfig,
                     interpret: Optional[bool] = None):
    """The engine's step-time entry: optax-shaped ``opt_state`` in,
    (params', opt_state') out — two fused passes instead of the chain's
    3–4 sweeps.  Callers that skipped the separate unscale/clip sweeps
    pass their combined multiplier as ``mult``."""
    import optax

    path, adam = find_adam_state(opt_state)
    count_inc = optax.safe_int32_increment(adam.count)
    new_params, new_mu, new_nu = fused_adam_tree(
        params, grads, adam.mu, adam.nu, count_inc, lr, mult, cfg,
        interpret)
    new_adam = type(adam)(count=count_inc, mu=new_mu, nu=new_nu)
    new_state = replace_adam_state(opt_state, path, new_adam)

    def bump(st, p):
        # keep counter-only states (ScaleByScheduleState from a
        # schedule-built lr) marching so fused/non-fused checkpoints and
        # a mid-run fallback to the optax chain stay interchangeable
        if p == path:
            return st  # the adam state, already replaced
        if (hasattr(st, "_fields")
                and getattr(st, "_fields", ()) == ("count",)):
            return type(st)(count=optax.safe_int32_increment(st.count))
        if isinstance(st, tuple) and not hasattr(st, "_fields"):
            return tuple(bump(s, p + (i,)) for i, s in enumerate(st))
        return st

    return new_params, bump(new_state, ())


# ---------------------------------------------------------------------------
# jnp reference (the anchor the kernel parity tests lock against)
# ---------------------------------------------------------------------------


def reference_adam_tree(params, grads, mu, nu, count_inc, lr, mult=1.0,
                        cfg: FusedAdamConfig = FusedAdamConfig()):
    """Pure-jnp mirror of the kernel math (itself mirroring optax) —
    the second anchor in the three-way parity test: optax chain ==
    this == the Pallas kernel."""
    b1, b2, eps, wd = cfg.b1, cfg.b2, cfg.eps, cfg.weight_decay
    bc1 = 1.0 - jnp.asarray(b1, jnp.float32) ** count_inc
    bc2 = 1.0 - jnp.asarray(b2, jnp.float32) ** count_inc

    def leaf(p, g, m, v):
        p32 = p.astype(jnp.float32)
        g = g.astype(jnp.float32) * mult
        if wd and not cfg.decoupled_wd:
            g = g + wd * p32
        m_new = (1.0 - b1) * g + b1 * m.astype(jnp.float32)
        v_new = (1.0 - b2) * (g * g) + b2 * v.astype(jnp.float32)
        direction = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
        if wd and cfg.decoupled_wd:
            direction = direction + wd * p32
        return ((p32 + (-lr) * direction).astype(p.dtype),
                m_new.astype(m.dtype), v_new.astype(v.dtype))

    trees = [jax.tree.map(lambda *xs, i=i: leaf(*xs)[i], params, grads,
                          mu, nu) for i in range(3)]
    return tuple(trees)
