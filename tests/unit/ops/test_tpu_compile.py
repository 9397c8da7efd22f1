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
    (32, 16, 16, jnp.bfloat16, None),    # OLMoE-1B-7B: pages [16, 16, 128]
], ids=["mistral7b", "mistral7b_no_window", "tp4_shard", "mha", "float32",
        "olmoe_16_heads"])
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


@pytest.mark.parametrize("rows", [32, 256], ids=["decode_step", "prefill_call"])
def test_grouped_expert_matmul_compiles_for_v5e_inside_a_layer_scan(
        one_chip, rows, monkeypatch):
    """The dropless expert layer at OLMoE-1B-7B's widths (64 experts of
    [2048, 1024], 8 a token), scanned over the stacked layers as the
    serving programs scan them: both Mosaic calls are there, once each."""
    from deepspeed_tpu.moe import DroplessMoE
    from deepspeed_tpu.ops.pallas import moe_grouped_matmul as gm

    # the layer asks the platform, which is the CPU here: steer it onto
    # the path it takes on the chip
    monkeypatch.setattr(gm, "reference_off_tpu", lambda interpret: False)
    L, E, H, I, k = 8, 64, 2048, 1024, 8
    layer_fn = DroplessMoE(E, k)

    def arg(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def fn(x, wg, w_gate, w_up, w_down):
        def one(x, lp):
            wg_l, experts = lp
            y, _, _ = layer_fn(wg_l, experts, x[None])
            return x + y[0], None

        return jax.lax.scan(one, x, (wg, {"w_gate": w_gate, "w_up": w_up,
                                          "w_down": w_down}))[0]

    text = jax.jit(fn).lower(
        arg((rows, H)), arg((L, H, E)), arg((L, E, H, I)), arg((L, E, H, I)),
        arg((L, E, I, H))).compile().as_text()
    assert len(re.findall(r"moe_grouped_matmul_swiglu[\w.]* = ", text)) == 1
    assert len(re.findall(r"moe_grouped_matmul\.[\w.]* = |"
                          r"moe_grouped_matmul = ", text)) == 1
