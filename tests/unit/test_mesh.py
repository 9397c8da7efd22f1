import jax
import numpy as np
import pytest

from deepspeed_tpu.parallel import (DP_AXES, MESH_AXIS_ORDER, MeshLayout,
                                    ProcessTopology, batch_sharding,
                                    build_mesh)


def test_layout_infer_dp():
    layout = MeshLayout.infer(8, tp=2, sp=2)
    assert layout.dp == 2 and layout.world_size == 8
    assert layout.dp_world_size == 2


def test_layout_infer_rejects_indivisible():
    with pytest.raises(ValueError):
        MeshLayout.infer(8, tp=3)


def test_layout_ep_factors_dp():
    layout = MeshLayout.infer(8, ep=2)
    assert layout.ep == 2 and layout.dp == 4
    assert layout.dp_world_size == 8  # ZeRO still shards over all 8


def test_build_mesh_axes():
    mesh = build_mesh(MeshLayout.infer(8, tp=2, pp=2))
    assert mesh.axis_names == MESH_AXIS_ORDER
    assert mesh.shape["tensor"] == 2 and mesh.shape["pipe"] == 2
    assert mesh.shape["data"] == 2
    assert mesh.devices.size == 8


def test_batch_sharding_spec():
    mesh = build_mesh(MeshLayout.infer(8, sp=2))
    s = batch_sharding(mesh, sp_shard_sequence=True)
    assert s.spec == jax.sharding.PartitionSpec(DP_AXES, "seq")


def test_topology_roundtrip():
    topo = ProcessTopology(["pipe", "data", "tensor"], [2, 2, 2])
    for rank in range(topo.world_size()):
        coords = topo.get_coord(rank)
        assert topo.get_rank(**coords) == rank


def test_topology_comm_lists():
    topo = ProcessTopology(["pipe", "data"], [2, 4])
    dp_groups = topo.get_axis_comm_lists("data")
    assert len(dp_groups) == 2
    assert dp_groups[0] == [0, 1, 2, 3]
    assert dp_groups[1] == [4, 5, 6, 7]


def test_tpu_topology_failure_is_raised_not_reshaped(monkeypatch):
    """On a TPU a failed topology-aware assignment must surface: a plain
    reshape would build a mesh whose adjacent chips are not adjacent."""
    import pytest
    from jax.experimental import mesh_utils

    from deepspeed_tpu.parallel.mesh import MeshLayout, build_mesh

    class FakeTpu:
        platform = "tpu"

    def refuse(shape, devices):
        raise ValueError("no assignment for this topology")

    monkeypatch.setattr(mesh_utils, "create_device_mesh", refuse)
    with pytest.raises(ValueError, match="topology"):
        build_mesh(MeshLayout.infer(4, tp=2), [FakeTpu() for _ in range(4)])
