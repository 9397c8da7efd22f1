"""Checkpoint save/load.

Capability parity with the reference engine checkpointing + checkpoint-engine
backends (SURVEY §5.4): ``engine.save_checkpoint(dir, tag?)`` writes
``<dir>/<tag=global_step{N}>/`` plus a ``latest`` tag file
[L HF-DS:492, ACC:3665-3669]; ``engine.load_checkpoint`` restores
module+optimizer+scheduler+client state; resume tolerates a DIFFERENT
mesh/world size (the reference needs the separate universal-checkpoint
pipeline for that — orbax gives reshard-on-load natively, which is exactly
SURVEY §5.4's TPU mapping).

Layout per tag directory:
    state/            orbax sharded pytree (params, opt_state, step, scaler)
    client_state.json user + engine bookkeeping (global_steps, skipped, …)

``orbax.checkpoint`` is not imported with this module: a save or a load
gets it from ``checkpoint_engine.orbax_checkpoint()``, whose docstring
says who pays the seconds of its import and when (the first save or load
of a run that configured nothing that saves; the start of one that did).
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.logging import log_dist, logger
from .checkpoint_engine import (join_inflight_save, orbax_checkpoint,
                                save_entry)

LATEST_FILE = "latest"


def _tag_for(engine, tag: Optional[str]) -> str:
    return tag if tag is not None else f"global_step{engine.global_steps}"


def _side_save(saver, path: str, tree: Any) -> None:
    """A tree saved beside the main state (Infinity trunk, offload
    moments): entered into orbax one thread at a time, like the
    checkpoint engines' own saves."""
    with save_entry(path):
        saver.save(path, tree, force=True)


def _globalize_tree(tree, mesh):
    """Multi-controller: re-place host-local (single-device) leaves —
    eager scalars like ``state.step`` or a restored optax ``count`` — as
    mesh-replicated global arrays (same values on every process by the
    SPMD contract).  Orbax cannot serialize host-local arrays in a
    multi-host setting, and a committed single-device leaf poisons any
    jit that also takes global arguments.  Jit-produced leaves already
    carry global shardings and pass through."""
    from ..parallel.mesh import global_put, replicated

    rep = replicated(mesh)

    def fix(x):
        if (isinstance(x, jax.Array) and x.is_fully_addressable
                and len(x.sharding.device_set) == 1):
            return global_put(np.asarray(x), rep)
        return x

    return jax.tree.map(fix, tree)


def _globalize_state(engine):
    if jax.process_count() == 1 or getattr(engine, "mesh", None) is None:
        return
    engine.state = _globalize_tree(engine.state, engine.mesh)
    infinity = getattr(engine, "infinity", None)
    if infinity is not None:
        infinity.res_opt_state = _globalize_tree(infinity.res_opt_state,
                                                 engine.mesh)


def save_checkpoint(engine, save_dir: str, tag: Optional[str] = None,
                    client_state: Optional[Dict[str, Any]] = None) -> str:
    from ..telemetry import get_telemetry

    tel = get_telemetry()
    with tel.span("checkpoint/save", args={"dir": save_dir}):
        path = _save_checkpoint_impl(engine, save_dir, tag, client_state)
    tel.inc_counter("checkpoint/saves", help="engine checkpoint saves")
    return path


def _save_checkpoint_impl(engine, save_dir: str, tag: Optional[str],
                          client_state: Optional[Dict[str, Any]]) -> str:
    ocp = orbax_checkpoint()
    _globalize_state(engine)
    tag = _tag_for(engine, tag)
    ckpt_dir = os.path.abspath(os.path.join(save_dir, tag))
    os.makedirs(ckpt_dir, exist_ok=True)

    # main state goes through the configured backend: sync, or async
    # (orbax AsyncCheckpointer — returns after the device→host snapshot,
    # writes behind training; the reference's decoupled engine role).
    # The `latest` durability marker is a commit callback so an async save
    # that dies mid-write never leaves `latest` naming a torn checkpoint.
    ceng = engine._ckpt_engine

    def _write_latest():
        with open(os.path.join(save_dir, LATEST_FILE), "w") as fh:
            fh.write(tag)

    ceng.save(engine.state, os.path.join(ckpt_dir, "state"),
              commit_fn=_write_latest)

    with ocp.StandardCheckpointer() as saver:
        infinity = getattr(engine, "infinity", None)
        if infinity is not None:
            # ZeRO-Infinity: the trunk lives in the swapper (host/NVMe) —
            # persist fp32 masters + Adam moments ONE LAYER AT A TIME so the
            # nvme tier's O(buffer_count) host-memory bound survives the save
            sw = infinity.swapper
            for i in range(sw.L):
                _side_save(
                    saver, os.path.join(ckpt_dir, "infinity_trunk",
                                        f"layer_{i:05d}"),
                    {"master": sw.layer_master_tree(i),
                     "moments": sw.layer_moments(i)})
            _side_save(saver,
                       os.path.join(ckpt_dir, "infinity_resident_opt"),
                       infinity.res_opt_state)
        if getattr(engine, "offload_opt", None) is not None:
            # ZeRO-Offload: moments live host-side in the C++ optimizer;
            # the attribute set varies per optimizer (Adam: both moments,
            # Adagrad: sq only, Lion: avg only)
            moments = {k: list(v) for k, v in
                       engine.offload_opt.state_dict_arrays().items()
                       if k != "step"}
            _side_save(saver, os.path.join(ckpt_dir, "offload_state"),
                       moments)

    # sync the scheduler to the APPLIED step (excludes fp16 overflow skips;
    # the per-step fast path tracks global_steps to avoid a device sync)
    engine.lr_scheduler.last_step = int(engine.state.step)
    meta = {
        "global_steps": engine.global_steps,
        "micro_steps": engine.micro_steps,
        "offload_step": (engine.offload_opt.opt.state_step
                         if getattr(engine, "offload_opt", None) else 0),
        "infinity_step": (engine.infinity.swapper.state_step
                          if getattr(engine, "infinity", None) else 0),
        "lr_scheduler": engine.lr_scheduler.state_dict(),
        "client_state": client_state or {},
        "ds_config_stage": engine.config.zero_optimization.stage,
    }
    with open(os.path.join(ckpt_dir, "client_state.json"), "w") as fh:
        json.dump(meta, fh, default=str)

    # reference ships zero_to_fp32.py into the checkpoint dir
    # [L trainer.py:4218]; the `latest` tag file was written by the
    # checkpoint engine's commit (deferred past durability when async)
    try:
        import shutil

        from ..utils import zero_to_fp32 as z2f

        shutil.copy(z2f.__file__, os.path.join(save_dir, "zero_to_fp32.py"))
    except Exception as e:
        # non-fatal convenience copy: broad on purpose — __file__ can be
        # None (frozen/zipapp) raising TypeError, and NOTHING here may
        # fail the real checkpoint that was just written
        from ..utils.logging import debug_once

        debug_once("checkpoint/zero_to_fp32_copy",
                   f"zero_to_fp32.py convenience copy skipped ({e!r})")
    log_dist(f"saved checkpoint {ckpt_dir}")
    return ckpt_dir


def _resolve_tag(load_dir: str, tag: Optional[str]) -> Optional[str]:
    if tag is not None:
        return tag
    latest = os.path.join(load_dir, LATEST_FILE)
    if os.path.exists(latest):
        with open(latest) as fh:
            return fh.read().strip()
    # fall back to newest global_step* dir (reference glob [L HF-DS:492])
    candidates = [d for d in os.listdir(load_dir)
                  if d.startswith("global_step")
                  and os.path.isdir(os.path.join(load_dir, d))]
    if not candidates:
        return None
    return max(candidates, key=lambda d: int(d.replace("global_step", "") or 0))


def load_checkpoint(engine, load_dir: str, tag: Optional[str] = None,
                    load_optimizer_states: bool = True,
                    load_module_only: bool = False
                    ) -> Tuple[Optional[str], Optional[Dict[str, Any]]]:
    from ..telemetry import get_telemetry

    tel = get_telemetry()
    with tel.span("checkpoint/load", args={"dir": load_dir}):
        out = _load_checkpoint_impl(engine, load_dir, tag,
                                    load_optimizer_states, load_module_only)
    if out[0] is not None:
        tel.inc_counter("checkpoint/loads", help="engine checkpoint loads")
    return out


def _load_checkpoint_impl(engine, load_dir: str, tag: Optional[str],
                          load_optimizer_states: bool,
                          load_module_only: bool
                          ) -> Tuple[Optional[str], Optional[Dict[str, Any]]]:
    tag = _resolve_tag(load_dir, tag)
    if tag is None:
        logger.warning(f"no checkpoint found under {load_dir}")
        return None, None
    ocp = orbax_checkpoint()
    ckpt_dir = os.path.abspath(os.path.join(load_dir, tag))
    # join any in-flight async save before reading (it may be this tag)
    # — including one dispatched by a DIFFERENT engine instance (a fresh
    # engine resuming a tag its predecessor is still flushing; waiting
    # only on our own engine leaves that torn-read race to GC timing)
    engine._ckpt_engine.wait()
    join_inflight_save(ckpt_dir)
    _globalize_state(engine)  # restore targets must be globally shardable

    # Restore INTO the engine's current sharded layout: orbax reshards on
    # load, so a checkpoint written on a different mesh/world restores
    # correctly (the reference's universal-checkpoint capability).
    def abstract(x):
        return jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                    sharding=getattr(x, "sharding", None))

    params_only = load_module_only or not load_optimizer_states
    state_path = os.path.join(ckpt_dir, "state")
    with ocp.StandardCheckpointer() as loader:
        if params_only:
            # Build the non-params target from the SAVED metadata so a
            # module-only load works against a DIFFERENT optimizer than the
            # one that saved (reference: load_module_only skips optimizer
            # state [K]); only the params subtree binds to engine shardings.
            meta = loader.metadata(state_path).item_metadata.tree
            target = jax.tree.map(
                lambda am: jax.ShapeDtypeStruct(tuple(am.shape), am.dtype),
                meta)
            target["params"] = jax.tree.map(abstract, engine.state.params)
            restored = loader.restore(state_path, target)
            engine.state = engine.state._replace(params=restored["params"])
        else:
            target = jax.tree.map(abstract, engine.state)
            engine.state = loader.restore(state_path, target)

    infinity = getattr(engine, "infinity", None)
    if infinity is not None:
        trunk_path = os.path.join(ckpt_dir, "infinity_trunk")
        if os.path.exists(trunk_path):
            sw = infinity.swapper
            with ocp.StandardCheckpointer() as loader:
                for i in range(sw.L):  # layer-at-a-time, like the save
                    lp = os.path.join(trunk_path, f"layer_{i:05d}")
                    meta_tree = loader.metadata(lp).item_metadata.tree
                    target = jax.tree.map(
                        lambda am: jax.ShapeDtypeStruct(tuple(am.shape),
                                                        am.dtype),
                        meta_tree)
                    entry = loader.restore(lp, target)
                    sw.load_layer(
                        i, entry["master"],
                        entry["moments"] if not params_only else None)
            if not params_only:
                opt_path = os.path.join(ckpt_dir, "infinity_resident_opt")
                if os.path.exists(opt_path):
                    with ocp.StandardCheckpointer() as loader:
                        target = jax.tree.map(abstract,
                                              infinity.res_opt_state)
                        infinity.res_opt_state = loader.restore(opt_path,
                                                                target)
        # resident params were restored into engine.state above
        infinity.resident = engine.state.params

    offload = getattr(engine, "offload_opt", None)
    if offload is not None:
        restored_master = False
        offload_path = os.path.join(ckpt_dir, "offload_state")
        if os.path.exists(offload_path) and not params_only:
            with ocp.StandardCheckpointer() as loader:
                target = {k: [jax.ShapeDtypeStruct(a.shape, a.dtype)
                              for a in v]
                          for k, v in offload.state_dict_arrays().items()
                          if k != "step"}
                # legacy checkpoints (pre-round-3) carry no 'master' entry;
                # probe the saved tree instead of masking restore errors
                saved_keys = set(
                    loader.metadata(offload_path).item_metadata.tree)
                if "master" not in saved_keys:
                    target.pop("master", None)
                    log_dist("offload restore: legacy checkpoint without "
                             "fp32 masters — moments restored, masters "
                             "reseeded from device params (exact only for "
                             "an fp32 wire)")
                restored_off = loader.restore(offload_path, target)
            restored_master = offload.load_state_arrays(restored_off)
        if not restored_master:
            # legacy/params-only checkpoint: re-seed host fp32 master slices
            # from the restored device params (exact only for an fp32 wire)
            offload.reseed_masters(engine.state.params)

    meta_path = os.path.join(ckpt_dir, "client_state.json")
    client_state: Dict[str, Any] = {}
    if os.path.exists(meta_path):
        with open(meta_path) as fh:
            meta = json.load(fh)
        engine.global_steps = int(meta.get("global_steps", 0))
        engine.micro_steps = int(meta.get("micro_steps", 0))
        if offload is not None and not params_only:
            offload.opt.state_step = int(meta.get("offload_step", 0))
        if infinity is not None and not params_only:
            infinity.swapper.state_step = int(meta.get("infinity_step", 0))
            infinity.global_steps = int(meta.get("global_steps", 0))
        if meta.get("lr_scheduler"):
            engine.lr_scheduler.load_state_dict(meta["lr_scheduler"])
        client_state = meta.get("client_state", {})
    log_dist(f"loaded checkpoint {ckpt_dir}")
    return ckpt_dir, client_state


def load_universal_checkpoint(engine, universal_dir: str) -> None:
    """Load a ``ds_to_universal`` directory into the engine under ANY mesh.

    Reference: the ``--load_universal`` path of ``deepspeed/runtime/
    engine.py`` consuming ``checkpoint/ds_to_universal.py`` output (SURVEY
    §5.4).  Each per-param fp32 file lands via ``jax.device_put`` onto the
    TARGET state's sharding (the resharding the reference does with its
    pattern-matched slice merges falls out of GSPMD placement); Adam
    moments fill the matching ``mu``/``nu`` leaves of the optax state by
    path suffix, and the step counter resumes.
    """
    import json as _json

    meta_path = os.path.join(universal_dir, "universal_metadata.json")
    with open(meta_path) as f:
        meta = _json.load(f)
    zero_dir = os.path.join(universal_dir, "zero")

    def _load(key: str, name: str) -> np.ndarray:
        return np.load(os.path.join(zero_dir, key, name + ".npy"))

    def _put(arr: np.ndarray, like):
        arr = arr.astype(like.dtype)
        sh = getattr(like, "sharding", None)
        return jax.device_put(arr, sh) if sh is not None else jnp.asarray(
            arr)

    from ..utils.zero_to_fp32 import path_key

    flat, treedef = jax.tree_util.tree_flatten_with_path(
        engine.state.params)
    new_leaves = []
    for path, leaf in flat:
        key = path_key(path)
        if key not in meta["params"]:
            raise KeyError(
                f"universal checkpoint has no parameter '{key}' "
                f"(has: {sorted(meta['params'])[:8]}…)")
        new_leaves.append(_put(_load(key, "fp32"), leaf))
    params = jax.tree_util.tree_unflatten(treedef, new_leaves)

    oflat, otreedef = jax.tree_util.tree_flatten_with_path(
        engine.state.opt_state)
    new_opt = []
    for path, leaf in oflat:
        parts = path_key(path).split("/")
        repl = None
        for field, fname in (("mu", "exp_avg"), ("nu", "exp_avg_sq")):
            if field in parts:
                suffix = "/".join(parts[parts.index(field) + 1:])
                entry = meta["params"].get(suffix)
                if entry and entry.get("has_moments") and tuple(
                        entry["shape"]) == tuple(np.shape(leaf)):
                    repl = _put(_load(suffix, fname), leaf)
        if repl is None and "count" in parts and np.ndim(leaf) == 0:
            # optax's bias-correction step counter — without it the
            # resumed Adam re-warms from step 0 and the trajectory drifts
            repl = jnp.asarray(int(meta["step"]), leaf.dtype)
        new_opt.append(repl if repl is not None else leaf)
    opt_state = jax.tree_util.tree_unflatten(otreedef, new_opt)

    engine.state = engine.state._replace(
        params=params, opt_state=opt_state,
        step=jnp.asarray(int(meta["step"]), jnp.int32))
    engine.global_steps = int(meta["step"])
    log_dist(f"loaded universal checkpoint {universal_dir} "
             f"(step {meta['step']})")
