"""Device-mesh construction — the TPU-native substrate for every parallelism mode.

The reference builds process groups per parallel dimension (DP/TP/PP/EP/SP) out
of global ranks (``deepspeed/utils/groups.py``, ``deepspeed/runtime/pipe/
topology.py:ProcessTopology`` [K]).  On TPU the idiomatic equivalent is ONE
``jax.sharding.Mesh`` whose named axes are the parallel dimensions; XLA/GSPMD
inserts collectives along those axes from sharding annotations, so "creating a
subgroup" reduces to naming an axis (or tuple of axes) in a PartitionSpec.

Axis layout (outer → inner, inner axes land on ICI-adjacent chips):

    pipe    pipeline-parallel stages        (reference: pp)
    expert  expert-parallel factor of DP    (reference: ep,  divides DP)
    data    pure data-parallel replicas     (reference: dp / ep)
    seq     sequence (context) parallel     (reference: Ulysses/ALST sp)
    tensor  tensor-model parallel           (reference: tp / AutoTP)

The full data-parallel degree (what the reference calls ``dp_world_size`` and
what ZeRO shards over) is ``expert × data``; GSPMD lets a PartitionSpec name
the flattened tuple ``("expert", "data")`` so ZeRO sharding composes with MoE
for free.  Batch math (reference ``runtime/config.py``):

    train_batch_size = micro_batch × grad_accum × (world // (tp·pp·sp))
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

AXIS_PIPE = "pipe"
AXIS_EXPERT = "expert"
AXIS_DATA = "data"
AXIS_SEQ = "seq"
AXIS_TENSOR = "tensor"

#: outer → inner; tensor innermost = most-communicating axis on fastest ICI.
MESH_AXIS_ORDER: Tuple[str, ...] = (AXIS_PIPE, AXIS_EXPERT, AXIS_DATA, AXIS_SEQ, AXIS_TENSOR)

#: Axes that together form the reference's data-parallel world (ZeRO shard axes).
DP_AXES: Tuple[str, ...] = (AXIS_EXPERT, AXIS_DATA)


@dataclasses.dataclass(frozen=True)
class MeshLayout:
    """Sizes of every parallel dimension. ``dp`` is the pure-data factor."""

    pp: int = 1
    ep: int = 1
    dp: int = 1
    sp: int = 1
    tp: int = 1

    @property
    def world_size(self) -> int:
        return self.pp * self.ep * self.dp * self.sp * self.tp

    @property
    def dp_world_size(self) -> int:
        """Reference dp_world_size = what ZeRO partitions over (= ep × dp)."""
        return self.ep * self.dp

    @property
    def axis_sizes(self) -> Dict[str, int]:
        return {
            AXIS_PIPE: self.pp,
            AXIS_EXPERT: self.ep,
            AXIS_DATA: self.dp,
            AXIS_SEQ: self.sp,
            AXIS_TENSOR: self.tp,
        }

    @classmethod
    def infer(
        cls,
        world_size: Optional[int] = None,
        *,
        tp: int = 1,
        pp: int = 1,
        sp: int = 1,
        ep: int = 1,
        dp: Optional[int] = None,
    ) -> "MeshLayout":
        """Fill in ``dp`` so the product matches ``world_size`` (device count)."""
        if world_size is None:
            world_size = jax.device_count()
        denom = tp * pp * sp * ep
        if dp is None:
            if world_size % denom:
                raise ValueError(
                    f"world_size={world_size} not divisible by tp*pp*sp*ep={denom}")
            dp = world_size // denom
        layout = cls(pp=pp, ep=ep, dp=dp, sp=sp, tp=tp)
        if layout.world_size != world_size:
            raise ValueError(
                f"mesh {layout.axis_sizes} has size {layout.world_size}, "
                f"need {world_size}")
        return layout


def mesh_topology(mesh: Mesh) -> Dict[str, object]:
    """JSON-able description of a mesh's topology — stamped into every
    snapshot manifest (resilience reshard-on-restore keys its
    compatibility check on this) and into reshape annotations.

    ``host_coverage`` records whether a single process can see the whole
    state ("full": single-controller, device_get returns global arrays)
    or only its own shards ("partial": multi-controller — a snapshot
    taken there cannot serve a different shape without every origin
    host's shards).
    """
    devs = np.asarray(mesh.devices).ravel()
    kind = str(getattr(devs[0], "device_kind", "unknown")) if len(devs) \
        else "unknown"
    procs = int(jax.process_count())
    return {
        "axes": {str(a): int(s) for a, s in mesh.shape.items()},
        "world_size": int(devs.size),
        "device_kind": kind,
        "num_processes": procs,
        "process_index": int(jax.process_index()),
        "host_coverage": "full" if procs == 1 else "partial",
    }


def build_mesh(layout: Optional[MeshLayout] = None,
               devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build the global Mesh with the canonical axis order.

    Uses ``mesh_utils.create_device_mesh`` so axis adjacency maps onto physical
    ICI topology on real TPU slices; host (CPU) device sets have no topology
    to exploit and are reshaped in enumeration order.  On a TPU a failure
    of the topology-aware assignment is raised, not papered over: a
    reshape there would train on a mesh whose "adjacent" chips are not.
    """
    layout = layout or MeshLayout.infer()
    if devices is None:
        devices = jax.devices()
        # A single-device layout on a multi-device host is an explicit ask
        # (tests/bench baselines); any other undercount stays a hard error so
        # misconfigured layouts don't silently train on a device subset.
        if layout.world_size == 1 and len(devices) > 1:
            devices = devices[:1]
    devices = list(devices)
    if len(devices) != layout.world_size:
        raise ValueError(f"{len(devices)} devices != layout world {layout.world_size}")
    shape = tuple(layout.axis_sizes[a] for a in MESH_AXIS_ORDER)
    if devices[0].platform == "tpu":
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    else:
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, MESH_AXIS_ORDER)


def single_device_mesh(device: Optional[jax.Device] = None) -> Mesh:
    device = device or jax.devices()[0]
    return Mesh(np.asarray([device]).reshape((1, 1, 1, 1, 1)), MESH_AXIS_ORDER)


def batch_sharding(mesh: Mesh, sp_shard_sequence: bool = False) -> NamedSharding:
    """Sharding for a [batch, seq, ...] input batch.

    Batch dim shards over the full DP world; the sequence dim additionally
    shards over ``seq`` when sequence parallelism is active (reference:
    UlyssesSPDataLoaderAdapter slices the sequence per SP rank).
    """
    if sp_shard_sequence:
        return NamedSharding(mesh, PartitionSpec(DP_AXES, AXIS_SEQ))
    return NamedSharding(mesh, PartitionSpec(DP_AXES))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def global_put(value, sharding: NamedSharding):
    """``device_put`` that also works when ``sharding`` spans processes.

    Multi-controller JAX cannot ``device_put`` host data onto devices other
    processes own; ``make_array_from_callback`` sidesteps that — every
    process materializes only its ADDRESSABLE shards (the callback is
    called per local device with that device's global index), and the
    result is one global array.  Each process must pass the same logical
    ``value`` (the usual SPMD contract).  Single-process: plain
    ``device_put`` (same semantics, fewer host copies)."""
    if jax.process_count() == 1:
        return jax.device_put(value, sharding)
    arr = np.asarray(value)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx])


def global_feed(value, sharding: NamedSharding):
    """Host batch leaf → global array under ``sharding`` (THE batch-feeding
    helper — engine, streaming executor, and dataloader all route here).

    * global ``jax.Array``s pass through untouched;
    * single-process: plain ``device_put``;
    * multi-process + sharded spec: ``value`` is this process's LOCAL rows
      (the per-rank slice its dataloader produced — the reference's
      per-rank batch feeding) and
      ``make_array_from_process_local_data`` assembles the global array;
    * multi-process + replicated spec: ``value`` is the full (identical)
      array on every process — :func:`global_put` semantics.
    """
    if isinstance(value, jax.Array) and not value.is_fully_addressable:
        return value
    if jax.process_count() == 1:
        return jax.device_put(value, sharding)
    if sharding.is_fully_replicated:
        return global_put(value, sharding)
    return jax.make_array_from_process_local_data(
        sharding, np.asarray(value))


def strip_manual_axes(*entries) -> PartitionSpec:
    """PartitionSpec from ``entries`` minus any axis that is currently
    MANUAL (i.e. we are inside a ``shard_map`` over it).

    Model code places activations with ``with_sharding_constraint``; under a
    partial-manual ``shard_map`` (1-bit grad reduction, pipeline loop) a
    constraint naming a manual axis is illegal — that axis's sharding is
    already the per-device block structure.  Dropping it preserves the
    constraint for the still-GSPMD axes (tensor/seq) and is a no-op
    otherwise.
    """
    from ..utils.jax_compat import current_manual_axes

    manual = current_manual_axes()
    if not manual:
        return PartitionSpec(*entries)
    out = []
    for e in entries:
        if e is None:
            out.append(None)
        elif isinstance(e, tuple):
            kept = tuple(a for a in e if a not in manual)
            out.append(kept if kept else None)
        else:
            out.append(None if e in manual else e)
    return PartitionSpec(*out)


class ProcessTopology:
    """Coordinate ↔ rank bookkeeping over named axes.

    Mirrors the reference ``deepspeed/runtime/pipe/topology.py:ProcessTopology``
    (axes/dims ctor, ``get_rank(**coords)``, ``get_coord(rank)``,
    ``get_axis_comm_lists``) so launcher/debug tooling can reason about global
    ranks even though GSPMD itself never needs explicit rank math.
    """

    def __init__(self, axes: Sequence[str], dims: Sequence[int]):
        if len(axes) != len(dims):
            raise ValueError("axes and dims must have equal length")
        self.axes = list(axes)
        self.dims = list(dims)

    @classmethod
    def from_layout(cls, layout: MeshLayout) -> "ProcessTopology":
        return cls(list(MESH_AXIS_ORDER), [layout.axis_sizes[a] for a in MESH_AXIS_ORDER])

    def world_size(self) -> int:
        return int(np.prod(self.dims))

    def get_dim(self, axis: str) -> int:
        return self.dims[self.axes.index(axis)]

    def get_rank(self, **coords: int) -> int:
        missing = set(self.axes) - set(coords)
        if missing:
            raise ValueError(f"missing coordinates for axes {sorted(missing)}")
        rank = 0
        for axis, dim in zip(self.axes, self.dims):
            c = coords[axis]
            if not 0 <= c < dim:
                raise ValueError(f"coord {axis}={c} out of range [0,{dim})")
            rank = rank * dim + c
        return rank

    def get_coord(self, rank: int) -> Dict[str, int]:
        coords: Dict[str, int] = {}
        for axis, dim in zip(reversed(self.axes), reversed(self.dims)):
            coords[axis] = rank % dim
            rank //= dim
        return {a: coords[a] for a in self.axes}

    def get_axis_comm_lists(self, axis: str) -> List[List[int]]:
        """All rank-groups that vary only along ``axis`` (= the reference's
        per-axis process groups, e.g. all TP groups)."""
        other_axes = [a for a in self.axes if a != axis]
        other_dims = [self.get_dim(a) for a in other_axes]
        lists = []
        for other_coords in itertools.product(*(range(d) for d in other_dims)):
            fixed = dict(zip(other_axes, other_coords))
            lists.append([self.get_rank(**{axis: i, **fixed})
                          for i in range(self.get_dim(axis))])
        return lists
