"""The paged decode kernel at group size 1 and the widths of a 16-head
multi-head model: 16 query = 16 KV heads of 128, pages ``[16, 16, 128]``
(each page a ``[256, 128]`` matrix), against ``paged_decode_reference``
in the Pallas interpreter; and the engine saying which path ran."""

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import paged_attention as pa
from deepspeed_tpu.ops.pallas.selfcheck import DECODE_TOL, _rel_err

HEADS, D, PAGE, MAX_BLOCKS = 16, 128, 16, 8


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_sixteen_heads_of_their_own_keys(dtype):
    rng = np.random.RandomState(0)
    lengths = [0, 1, PAGE, 3 * PAGE + 5, MAX_BLOCKS * PAGE, 1]
    B = len(lengths)
    pages = B * MAX_BLOCKS + 1
    q = jnp.asarray(rng.standard_normal((B, HEADS, D)), dtype)
    k_pool, v_pool = (jnp.asarray(
        rng.standard_normal((pages, PAGE, HEADS, D)), dtype) for _ in "kv")
    tables = rng.permutation(np.arange(1, pages)).reshape(
        B, MAX_BLOCKS).astype(np.int32)
    tables[-1] = 0                  # a dead slot as the engine leaves one
    tables, lengths = jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)
    assert pa.paged_decode_impl(HEADS, HEADS, interpret=True) \
        == "pallas_interpret"
    got = pa.paged_decode_attention(q, k_pool, v_pool, tables, lengths,
                                    interpret=True)
    want = pa.paged_decode_reference(
        q.astype(jnp.float32), k_pool.astype(jnp.float32),
        v_pool.astype(jnp.float32), tables, lengths, None)
    assert got.shape == (B, HEADS, D) and got.dtype == dtype
    assert float(_rel_err(got[1:], want[1:])) < DECODE_TOL
    assert float(jnp.max(jnp.abs(got[0]))) == 0.0      # a row of length 0
    # group size 1: head h reads kv head h and no other
    other = v_pool.at[:, :, 3].set(0.0)
    moved = pa.paged_decode_attention(q, k_pool, other, tables, lengths,
                                      interpret=True)
    changed = np.asarray(jnp.any(moved != got, axis=(0, 2)))
    assert changed.tolist() == [h == 3 for h in range(HEADS)]


def test_the_engine_says_which_path_ran():
    import jax

    from deepspeed_tpu.inference.v2 import KVCacheConfig
    from deepspeed_tpu.inference.v2.engine_v2 import build_engine_v2
    from deepspeed_tpu.models import OlmoeConfig, OlmoeModel

    model = OlmoeModel(OlmoeConfig.tiny(num_heads=16, num_kv_heads=16,
                                        hidden_size=256, dtype=jnp.float32))
    engine = build_engine_v2(
        model, model.init_params(jax.random.PRNGKey(0)),
        KVCacheConfig(block_size=16, num_blocks=16, max_seq_len=64),
        max_batch_slots=2, prefill_chunk=16)
    assert engine.last_attn_path is None                 # nothing traced yet
    engine.generate([[1, 2, 3, 4, 5]], max_new_tokens=3)
    assert engine.pool["kv"]["k"].shape == (2, 16, 16, 16, 16)
    # off the TPU the entry point runs its reference, and the engine's
    # record is the entry point's own test
    assert engine.last_attn_path == pa.paged_decode_impl(16, 16) == "reference"
