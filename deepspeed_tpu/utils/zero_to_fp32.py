"""zero_to_fp32 — consolidate a sharded checkpoint into plain fp32 arrays.

Reference: ``deepspeed/utils/zero_to_fp32.py`` [K] — the offline tool shipped
INTO every checkpoint dir that merges ZeRO shards into a single fp32
state_dict [L trainer.py:4218].  Orbax stores logical (unsharded) arrays, so
"consolidation" here is a restore-without-mesh + dtype cast — resumable from
ANY source mesh layout (the universal-checkpoint capability, SURVEY §5.4).
"""

from __future__ import annotations

import argparse
import os
import pickle
from typing import Any, Dict, Optional

import jax
import numpy as np


def path_key(path) -> str:
    """Canonical '/'-joined key for a pytree path (GetAttrKey / DictKey /
    SequenceKey all covered) — ONE implementation shared by every
    checkpoint-export tool so converter and loader can never disagree."""
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def resolve_tag(checkpoint_dir: str, tag: Optional[str]) -> str:
    """'latest' file, else newest global_step* dir (shared by every
    offline checkpoint tool)."""
    if tag is not None:
        return tag
    latest = os.path.join(checkpoint_dir, "latest")
    if os.path.exists(latest):
        with open(latest) as f:
            return f.read().strip()
    candidates = sorted(d for d in os.listdir(checkpoint_dir)
                        if d.startswith("global_step"))
    if not candidates:
        raise FileNotFoundError(
            f"no global_step* checkpoint under {checkpoint_dir}")
    return candidates[-1]


def restore_saved_state(checkpoint_dir: str, tag: Optional[str] = None):
    """Mesh-free host restore of a saved engine TrainState; returns
    (state, tag)."""
    import orbax.checkpoint as ocp

    tag = resolve_tag(checkpoint_dir, tag)
    state_path = os.path.join(checkpoint_dir, tag, "state")
    with ocp.StandardCheckpointer() as loader:
        meta = loader.metadata(state_path).item_metadata.tree
        target = jax.tree.map(
            lambda am: jax.ShapeDtypeStruct(tuple(am.shape), am.dtype), meta)
        return loader.restore(state_path, target), tag


def get_fp32_state_dict_from_zero_checkpoint(
        checkpoint_dir: str, tag: Optional[str] = None) -> Dict[str, Any]:
    """Load the params subtree of a saved engine state as host fp32 numpy,
    flattened to {'/'-joined path: array}."""
    restored, _ = restore_saved_state(checkpoint_dir, tag)
    params = restored["params"] if isinstance(restored, dict) else restored.params
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        flat[path_key(path)] = np.asarray(jax.device_get(leaf),
                                          dtype=np.float32)
    return flat


def convert_zero_checkpoint_to_fp32_state_dict(
        checkpoint_dir: str, output_file: str,
        tag: Optional[str] = None) -> None:
    sd = get_fp32_state_dict_from_zero_checkpoint(checkpoint_dir, tag)
    with open(output_file, "wb") as f:
        pickle.dump(sd, f)
    total = sum(v.size for v in sd.values())
    print(f"saved {len(sd)} tensors / {total:,} params to {output_file}")


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("checkpoint_dir")
    p.add_argument("output_file")
    p.add_argument("--tag", default=None)
    a = p.parse_args()
    convert_zero_checkpoint_to_fp32_state_dict(a.checkpoint_dir,
                                               a.output_file, tag=a.tag)


if __name__ == "__main__":
    main()
