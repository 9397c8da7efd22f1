"""Two thin adapters over the installed jax (0.9.0, pinned in
``pyproject.toml``).  Nothing here branches on a version: code that needs
another jax changes the pin.
"""

from __future__ import annotations

from typing import Any, Optional, Set

import jax


def shard_map(f, *, mesh, in_specs, out_specs,
              axis_names: Optional[Set[Any]] = None,
              check_vma: bool = False):
    """``jax.shard_map`` with this codebase's defaults: replication
    checking off (the manual regions here use collectives whose
    replication jax cannot infer), and ``axis_names=None`` meaning every
    mesh axis is manual."""
    kw = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
              check_vma=check_vma)
    if axis_names is not None:
        kw["axis_names"] = set(axis_names)
    return jax.shard_map(f, **kw)


def current_manual_axes() -> Set[Any]:
    """Mesh axes that are MANUAL at the current trace point (we are inside
    a ``shard_map`` over them); empty outside any mesh context."""
    return set(jax.sharding.get_abstract_mesh().manual_axes)
