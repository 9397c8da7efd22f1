"""Kernels of the serving path compiled for a described v5e — no chip
attached, nothing runs: what Mosaic or XLA:TPU refuses at real widths is
caught here and not on the chip.  The topology is described inside a
fixture (one process may hold libtpu; see the on-chip-measurement guide),
and every such compile lives in this one file."""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deepspeed_tpu.ops.pallas import paged_attention as pa


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("rows, h, kv_h, dtype, window", [
    (32, 32, 8, jnp.bfloat16, 4096),     # Mistral-7B, the serving cell
    (32, 32, 8, jnp.bfloat16, None),
    (8, 8, 2, jnp.bfloat16, 4096),       # a tensor-parallel shard of it
    (8, 32, 32, jnp.bfloat16, None),     # no grouping (Llama-7B)
    (8, 32, 8, jnp.float32, 4096),
], ids=["mistral7b", "mistral7b_no_window", "tp4_shard", "mha", "float32"])
def test_paged_decode_compiles_for_v5e(one_chip, rows, h, kv_h, dtype,
                                       window):
    """The compiled program is the Mosaic call alone: the pool reaches it
    through a bitcast, never a copy."""
    d, page, pages, max_blocks = 128, 16, 3200, 512

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def fn(q, k_pool, v_pool, tables, lengths):
        return pa.paged_decode_attention(q, k_pool, v_pool, tables, lengths,
                                         interpret=False, window=window)

    pool = arg((pages, page, kv_h, d), dtype)
    text = jax.jit(fn).lower(
        arg((rows, h, d), dtype), pool, pool,
        arg((rows, max_blocks), jnp.int32),
        arg((rows,), jnp.int32)).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    assert "paged_decode_attention" in text
    # "%name = <shape and layout> <opcode>(...": what holds the pool's shape
    made = re.findall(rf"= \w+\[{pages},\S* ([\w-]+)\(", text)
    assert made and set(made) <= {"parameter", "bitcast"}, made
