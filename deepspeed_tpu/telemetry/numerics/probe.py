"""In-graph probe tags + the trace-time collector.

The contract that makes the plane free when off: :func:`probe` is an
IDENTITY — ``probe("resid", x)`` returns ``x`` itself (the same Python
object, not a copy) unless a collector is active *at trace time*.  The
enable decision is host-side module state read while JAX traces, never
a traced value — so a model instrumented with probes compiles to the
bitwise-same jaxpr as the uninstrumented model when the plane is off,
and turning the plane ON builds a SEPARATE program at its own jit site
(``engine/train_step_numerics``) instead of recompiling the base step.

Collection rides the step's output pytree: every probe folds its tensor
into an 8-scalar stat vector (:func:`~.stats.tensor_stats`) registered
on the active :class:`Collector`; the engine harvests the collector
into a tiny ``{name: array}`` dict returned next to the metrics — zero
host callbacks, one device→host transfer of a few hundred floats on
sampled steps only.

Two transform boundaries need an explicit bracket, because a probe's
stat tracer must EXIT the scope it was created in:

* ``lax.scan`` over stacked layers (the decoder trunk) and over
  gradient-accumulation microbatches: the body wraps itself in
  :func:`scan_mark` / :func:`scan_drain` — drain pops the body's own
  entries into an index-keyed dict returned as the body's scan ``ys``
  (names ride the dict KEYS, which are static pytree structure, so
  ``lax.scan`` stacks the values to ``[n, ...]`` and the names survive
  for free) — and :func:`scan_collect` re-registers the stacked result
  after the scan closes.  Draining inside the body keeps re-traces
  (``jax.checkpoint``, linearize) balanced: each trace pops exactly
  what it pushed.  When no collector is active every bracket call
  returns ``None`` and the body's ``ys`` stays ``None`` — today's
  jaxpr.
* ``value_and_grad``: the engine's loss closure drains the forward's
  entries and returns them via ``has_aux`` (see ``_grad_core``).

Regions that can NEVER carry a probe out (``shard_map`` bodies,
``lax.cond`` branches) suppress collection with :func:`suppressed` —
probes inside become identities for that region only.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .stats import STAT_FIELDS, stats_to_dict, tensor_stats

#: entry-name prefixes that are NOT probe stat vectors
MOE_PREFIX = "moe/"
GRAD_PREFIX = "grad/"
UPDATE_PREFIX = "update_ratio/"
#: key order prefix width: "0007:" — keeps sorted(dict) == program
#: order through jit/scan pytree round-trips (which sort dict keys)
_SEQ_W = 4


def _key(i: int, name: str) -> str:
    return f"{i:0{_SEQ_W}d}:{name}"


def _split_key(key: str) -> Tuple[int, str]:
    head, sep, rest = key.partition(":")
    if sep and head.isdigit():
        return int(head), rest
    return 1 << 30, key


class Collector:
    """One sampled (or forensic) capture: trace-time registry of
    ``(name, tracer)`` entries in program order."""

    def __init__(self, probes: bool = True, moe: bool = True,
                 tag: str = "sample"):
        self.want_probes = bool(probes)
        self.want_moe = bool(moe)
        self.tag = tag
        self.entries: List[Tuple[str, Any]] = []
        self._seq = 0  # monotonic across harvests — order survives resets

    def add(self, name: str, value: Any) -> None:
        self.entries.append((name, value))

    def harvest(self, reset: bool = True) -> Dict[str, Any]:
        """Entries → index-keyed ``{"0003:name": array}`` dict.  The
        index prefix makes SORTED key order equal program order — jit
        and scan rebuild dict pytrees key-sorted, so insertion order
        alone would not survive the round trip."""
        out: Dict[str, Any] = {}
        for name, value in self.entries:
            out[_key(self._seq, name)] = value
            self._seq += 1
        if reset:
            self.entries = []
        return out


# active collector is process-global but guarded: the engine activates
# it only around the traced call, and tests scrub it via reset()
_lock = threading.Lock()
_active: Optional[Collector] = None


class collecting:
    """``with collecting(coll): step_fn(...)`` — activates ``coll`` for
    the duration of the trace happening inside the block."""

    def __init__(self, collector: Optional[Collector]):
        self.collector = collector
        self._prev: Optional[Collector] = None

    def __enter__(self) -> Optional[Collector]:
        global _active
        with _lock:
            self._prev = _active
            _active = self.collector
        return self.collector

    def __exit__(self, *exc) -> None:
        global _active
        with _lock:
            _active = self._prev


class suppressed(collecting):
    """``with suppressed(): ...`` — probes become identities inside the
    block.  Used around regions whose tracers cannot legally escape
    (``shard_map`` bodies, ``lax.cond`` branches such as random-LTD's
    per-layer routing)."""

    def __init__(self) -> None:
        super().__init__(None)


def active() -> Optional[Collector]:
    return _active


def reset() -> None:
    """Test isolation: drop any active collector."""
    global _active
    with _lock:
        _active = None


# -- the tags models call ---------------------------------------------------

def probe(name: str, x: Any) -> Any:
    """Tag ``x`` for tensor-health stats.  Identity (returns ``x``
    itself) unless a probing collector is active at trace time."""
    c = _active
    if c is None or not c.want_probes:
        return x
    c.add(name, tensor_stats(x))
    return x


def moe_stats(meta: Dict[str, Any]) -> None:
    """Record gate statistics from a ``top_k_gating`` meta dict.  No-op
    without an active moe-accepting collector — callers never branch."""
    c = _active
    if c is None or not c.want_moe:
        return
    for key in ("load", "entropy", "drop_rate", "overflow_frac",
                "assignments", "experts_active", "assignments_routed",
                "rows_computed"):
        if key in meta:
            c.add(MOE_PREFIX + key, meta[key])


# -- scan bracket (stacked-layer models, gas microbatch scans) --------------

def scan_mark() -> Optional[int]:
    """Top of a scanned body (or a ``value_and_grad`` loss closure):
    remember how many entries exist so the matching :func:`scan_drain`
    pops only this region's additions."""
    c = _active
    if c is None:
        return None
    return len(c.entries)


def scan_drain(mark: Optional[int]) -> Optional[Dict[str, Any]]:
    """Bottom of the region: pop the entries added since ``mark`` and
    return them as an index-keyed dict — the body's scan ``ys`` (or the
    loss closure's ``has_aux`` aux).  Names ride the dict keys, so the
    structure is self-describing through any pytree transform."""
    c = _active
    if c is None or mark is None:
        return None
    popped = c.entries[mark:]
    del c.entries[mark:]
    if not popped:
        return None
    return {_key(i, name): v for i, (name, v) in enumerate(popped)}


def combine_stats(stacked: Any, name: str):
    """Fold the leading axis of a stacked stat array with field-aware
    reductions (gas-microbatch folding): counts sum, extrema take
    min/max, fractions and rms combine size-weighted.  Non-probe
    entries (moe/grad) just take the mean."""
    import jax.numpy as jnp

    is_vec = (getattr(stacked, "ndim", 0) >= 1
              and stacked.shape[-1] == len(STAT_FIELDS)
              and not name.startswith((MOE_PREFIX, GRAD_PREFIX,
                                       UPDATE_PREFIX)))
    if not is_vec:
        return jnp.mean(stacked, axis=0)
    f = {fld: i for i, fld in enumerate(STAT_FIELDS)}
    size = stacked[..., f["size"]]
    tot = jnp.maximum(jnp.sum(size, axis=0), 1.0)

    def wmean(idx):
        return jnp.sum(stacked[..., idx] * size, axis=0) / tot

    mn = stacked[..., f["min_nonzero"]]
    mn = jnp.min(jnp.where(mn > 0.0, mn, jnp.inf), axis=0)
    return jnp.stack([
        jnp.sum(stacked[..., f["nonfinite"]], axis=0),
        jnp.max(stacked[..., f["absmax"]], axis=0),
        jnp.where(jnp.isfinite(mn), mn, 0.0),
        jnp.sqrt(jnp.sum(jnp.square(stacked[..., f["rms"]]) * size, axis=0)
                 / tot),
        wmean(f["zero_frac"]),
        wmean(f["subnormal_frac"]),
        wmean(f["saturated_frac"]),
        jnp.sum(size, axis=0),
    ], axis=-1)


def scan_collect(ys: Optional[Dict[str, Any]],
                 combine: bool = False) -> None:
    """After the scan closes: re-register the stacked per-iteration
    values (each leaf now ``[n, ...]``).  ``combine=True`` folds the
    stacked axis with :func:`combine_stats` (the gas-microbatch fold);
    ``combine=False`` keeps it (the per-layer axis the forensics
    bisect on)."""
    c = _active
    if c is None or not ys:
        return
    for key in sorted(ys, key=_split_key):
        _, name = _split_key(key)
        value = ys[key]
        c.add(name, combine_stats(value, name) if combine else value)


# -- grad-path helpers (engine step_fn) -------------------------------------

def grad_stats(grads: Any, updates: Any, params: Any) -> Dict[str, Any]:
    """Per-top-level-module grad norms + update/param ratios, sliced
    from the step's existing pytrees (no extra forward).  A stacked
    ``layers`` module additionally yields a per-layer ``[L]`` grad-norm
    vector — the series ``layer_grad_explosion`` bisects on."""
    import jax
    import jax.numpy as jnp

    def _sq(tree, axes_from: int = 0):
        leaves = jax.tree_util.tree_leaves(tree)
        tot = jnp.float32(0.0)
        for lf in leaves:
            lf32 = lf.astype(jnp.float32)
            if axes_from:
                tot = tot + jnp.sum(jnp.square(lf32),
                                    axis=tuple(range(axes_from, lf32.ndim)))
            else:
                tot = tot + jnp.sum(jnp.square(lf32))
        return tot

    out: Dict[str, Any] = {}
    if isinstance(grads, dict):
        for key, sub in grads.items():
            out[GRAD_PREFIX + key] = jnp.sqrt(_sq(sub))
            if key == "layers":
                # leaves are [L, ...]: reduce every axis but the first
                out[GRAD_PREFIX + "per_layer"] = jnp.sqrt(_sq(sub, 1))
        if isinstance(updates, dict) and isinstance(params, dict):
            for key in grads:
                if key in updates and key in params:
                    un = jnp.sqrt(_sq(updates[key]))
                    pn = jnp.sqrt(_sq(params[key]))
                    out[UPDATE_PREFIX + key] = un / jnp.maximum(pn, 1e-12)
    else:
        out[GRAD_PREFIX + "all"] = jnp.sqrt(_sq(grads))
    return out


# -- host-side decode -------------------------------------------------------

def decode(named: Dict[str, Any]) -> Dict[str, Any]:
    """The harvested ``{"0003:name": device array}`` dict → a
    JSON-ready summary::

        {"probes": {flat_name: {field: float}},   # program order
         "order":  [flat_name, ...],
         "grads":  {module: float, "per_layer": [...]},
         "update_ratio": {module: float},
         "moe":    {stat: float or [..] list}}

    Probe entries with a leading layer axis (``[L, 8]``, the scanned
    decoder trunk) expand layer-major — ``layer00/attn_out``,
    ``layer00/mlp_out``, ``layer01/...`` — so "first nonfinite in
    program order" is a plain list walk.
    """
    probes: Dict[str, Dict[str, float]] = {}
    order: List[str] = []
    grads: Dict[str, Any] = {}
    ratios: Dict[str, Any] = {}
    moe: Dict[str, Any] = {}

    def _scalarize(v):
        a = np.asarray(v, dtype=np.float64)
        return float(a) if a.ndim == 0 else a.tolist()

    items = sorted(named.items(), key=lambda kv: _split_key(kv[0]))
    nfields = len(STAT_FIELDS)
    stacked = [(name, np.asarray(v)) for k, v in items
               for name in [_split_key(k)[1]]
               if not name.startswith((MOE_PREFIX, GRAD_PREFIX,
                                       UPDATE_PREFIX))
               and np.asarray(v).ndim == 2
               and np.asarray(v).shape[-1] == nfields]
    stacked_done = False
    for key, value in items:
        name = _split_key(key)[1]
        if name.startswith(MOE_PREFIX):
            moe[name[len(MOE_PREFIX):]] = _scalarize(value)
        elif name.startswith(GRAD_PREFIX):
            grads[name[len(GRAD_PREFIX):]] = _scalarize(value)
        elif name.startswith(UPDATE_PREFIX):
            ratios[name[len(UPDATE_PREFIX):]] = _scalarize(value)
        else:
            arr = np.asarray(value)
            if arr.shape == (nfields,):
                probes[name] = stats_to_dict(arr)
                order.append(name)
            elif arr.ndim == 2 and arr.shape[-1] == nfields:
                # the scanned-layer block: expand ONCE, layer-major, at
                # the position of its first member
                if stacked_done:
                    continue
                num_layers = max(a.shape[0] for _, a in stacked)
                for li in range(num_layers):
                    for n, a in stacked:
                        if li < a.shape[0]:
                            flat = f"layer{li:02d}/{n}"
                            probes[flat] = stats_to_dict(a[li])
                            order.append(flat)
                stacked_done = True
            else:  # unknown shape: keep raw rather than drop
                moe[name] = _scalarize(value)
    return {"probes": probes, "order": order, "grads": grads,
            "update_ratio": ratios, "moe": moe}


def summarize(decoded: Dict[str, Any]) -> Dict[str, float]:
    """Worst-case scalars for gauges/health from a decoded capture."""
    probes = decoded.get("probes", {})
    out = {
        "nonfinite_total": sum(p.get("nonfinite", 0.0)
                               for p in probes.values()),
        "absmax": max((p.get("absmax", 0.0) for p in probes.values()),
                      default=0.0),
        "underflow_frac": max((p.get("subnormal_frac", 0.0)
                               for p in probes.values()), default=0.0),
        "saturated_frac": max((p.get("saturated_frac", 0.0)
                               for p in probes.values()), default=0.0),
        "zero_frac": max((p.get("zero_frac", 0.0)
                          for p in probes.values()), default=0.0),
        "probe_count": float(len(probes)),
    }
    per_layer = decoded.get("grads", {}).get("per_layer")
    if isinstance(per_layer, list) and per_layer:
        finite = [g for g in per_layer if np.isfinite(g)]
        out["layer_grad_max"] = float(max(per_layer))
        out["layer_grad_median"] = float(np.median(finite)) if finite else 0.0
        out["layer_grad_argmax"] = float(int(np.argmax(per_layer)))
    moe = decoded.get("moe", {})

    def _mean(v):
        arr = np.asarray(v, dtype=np.float64)
        return float(arr.mean()) if arr.size else 0.0

    if "entropy" in moe:
        out["gate_entropy"] = _mean(moe["entropy"])
        load_arr = np.asarray(moe.get("load", []), dtype=np.float64)
        n_expert = load_arr.shape[-1] if load_arr.ndim else 0
        if n_expert > 1:
            # fraction of uniform (ln E): 1.0 = perfectly balanced
            # router, → 0 = collapse; E-independent, so the
            # router_collapse floor means the same thing at E=4 and E=64
            out["gate_entropy_frac"] = float(
                out["gate_entropy"] / np.log(n_expert))
    if "drop_rate" in moe:
        out["moe_drop_rate"] = _mean(moe["drop_rate"])
    if "overflow_frac" in moe:
        out["moe_overflow_frac"] = _mean(moe["overflow_frac"])
    if "load" in moe:
        # load is expert-load fractions [E] (or [L, E]): the max/mean
        # imbalance ratio is the one-number hot-expert signal
        arr = np.asarray(moe["load"], dtype=np.float64)
        if arr.size:
            flat = arr.reshape(-1, arr.shape[-1]) if arr.ndim > 1 \
                else arr[None]
            means = flat.mean(axis=1)
            ratio = np.where(means > 0, flat.max(axis=1) / np.maximum(
                means, 1e-12), 0.0)
            out["moe_load_imbalance"] = float(ratio.max())
    return out
