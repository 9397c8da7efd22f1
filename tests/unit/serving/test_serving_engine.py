"""Real-engine serving acceptance: prefix sharing is a memory/latency
feature, NOT a numerics change — shared-header outputs are identical to
the unshared engine's, pages are shared while live and reclaimed after.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (KVCacheConfig, RequestState,
                                        build_engine_v2)
from deepspeed_tpu.models import LlamaConfig, LlamaModel
from deepspeed_tpu.serving import (ServingParams, ServingScheduler,
                                   build_serving_frontend)


@pytest.fixture(scope="module")
def tiny_model():
    # fp32 so greedy argmax cannot diverge on bf16 rounding ties
    cfg = LlamaConfig.tiny(num_layers=2, max_seq_len=64, dtype=jnp.float32)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return model, params


def _unshared_generate(model, params, prompts, n_new):
    eng = build_engine_v2(
        model, params,
        cache_config=KVCacheConfig(num_blocks=64, block_size=4,
                                   max_seq_len=64),
        max_batch_slots=4, prefill_chunk=8)
    return eng.generate(prompts, max_new_tokens=n_new)


def test_prefix_sharing_bitwise_identical_and_reclaimed(tiny_model):
    """ISSUE 8 acceptance: two prompts with a shared header allocate the
    header pages once (refcount 2), produce exactly the unshared
    engine's tokens, and every page is reclaimable after completion."""
    model, params = tiny_model
    rng = np.random.RandomState(5)
    header = rng.randint(1, 512, size=16).tolist()  # 4 full pages (bs=4)
    prompts = [header + rng.randint(1, 512, size=3).tolist(),
               header + rng.randint(1, 512, size=5).tolist()]
    want = _unshared_generate(model, params, prompts, 6)

    fe = build_serving_frontend(
        model, params, replicas=1,
        cache_config=KVCacheConfig(num_blocks=64, block_size=4,
                                   max_seq_len=64),
        max_batch_slots=4, prefill_chunk=8, prefill_batch=2,
        decode_burst=4, serving_params=ServingParams())
    sched = fe.router.replicas[0].scheduler
    assert isinstance(sched, ServingScheduler)

    h1 = fe.submit(prompts[0], max_new_tokens=6)
    # drive h1 through prefill: its last chunk indexes the header pages
    # in the trie (shareable the moment the KV content exists)
    while h1.request is None or h1.request.prefilled < len(prompts[0]):
        fe.pump()
    h2 = fe.submit(prompts[1], max_new_tokens=6)
    while h2.request is None or h2.request.prefilled < 16:
        fe.pump()
    r1, r2 = h1.request, h2.request
    # header pages allocated ONCE: both tables share them, refcount 2
    assert r2.blocks[:4] == r1.blocks[:4]
    assert all(sched.allocator.refcount(b) == 2 for b in r1.blocks[:4])
    assert sched.prefix.hit_tokens == 16

    fe.run_until_idle()
    # outputs identical to the unshared path
    assert h1.result() == want[0]
    assert h2.result() == want[1]
    # refcounts dropped to zero; header pages sit in the reclaimable
    # cached tier; the whole pool is available again
    assert all(sched.allocator.refcount(b) == 0 for b in r1.blocks[:4])
    # 5 cached pages: the 4 shared header pages + prompt 2's own full
    # tail block (21 tokens = 5 full pages, all trie-indexed)
    assert sched.allocator.num_cached == 5
    assert sched.allocator.num_available == 63
    # flushing the prefix cache returns them to the plain free list
    sched.prefix.drop_all()
    assert sched.allocator.num_free == 63


def test_prefix_revival_across_sequential_requests(tiny_model):
    """The second request arrives AFTER the first completed: the header
    KV is revived from the cached tier (never recomputed) and the
    output still matches the unshared engine."""
    model, params = tiny_model
    rng = np.random.RandomState(6)
    header = rng.randint(1, 512, size=16).tolist()
    prompts = [header + [7, 8], header + [9, 10, 11]]
    want = _unshared_generate(model, params, prompts, 5)

    fe = build_serving_frontend(
        model, params, replicas=1,
        cache_config=KVCacheConfig(num_blocks=64, block_size=4,
                                   max_seq_len=64),
        max_batch_slots=2, prefill_chunk=8, decode_burst=4)
    sched = fe.router.replicas[0].scheduler
    h1 = fe.submit(prompts[0], max_new_tokens=5)
    fe.run_until_idle()
    assert h1.result() == want[0]
    assert sched.allocator.num_cached == 4
    h2 = fe.submit(prompts[1], max_new_tokens=5)
    fe.run_until_idle()
    assert h2.result() == want[1]
    assert sched.prefix.revivals == 4
    assert h2.request.prefilled >= 16 or h2.request.state \
        is RequestState.DONE


def test_replica_kv_pools_attributed_in_memory_ledger(tiny_model):
    """ISSUE 8 satellite: per-replica KV pools and the prefix cache get
    DISTINCT kv_cache sub-keys in the PR-7 memory ledger."""
    from deepspeed_tpu.telemetry.memory import get_memory_ledger

    model, params = tiny_model
    led = get_memory_ledger()
    led.configure(enabled=True)
    fe = build_serving_frontend(
        model, params, replicas=2,
        cache_config=KVCacheConfig(num_blocks=64, block_size=4,
                                   max_seq_len=64),
        max_batch_slots=2, prefill_chunk=8)
    keys = {e["key"]: e for e in led.entries() if e["pool"] == "kv_cache"}
    assert "serving/replica0/kv_pool" in keys
    assert "serving/replica1/kv_pool" in keys
    assert keys["serving/replica0/kv_pool"]["nbytes"] > 0
    # run a header workload so the prefix cache holds pages, then the
    # per-replica prefix entry appears with real bytes
    header = list(range(1, 17))
    h = fe.submit(header + [5, 6], max_new_tokens=3)
    fe.run_until_idle()
    keys = {e["key"]: e for e in led.entries() if e["pool"] == "kv_cache"}
    pc_key = f"serving/replica{h.replica_id}/prefix_cache"
    assert pc_key in keys
    assert keys[pc_key]["nbytes"] > 0
    assert keys[pc_key]["transient"] is True  # subset of the pool bytes
    # `mem top`-style pool totals see the serving plane
    assert led.pool_bytes()["kv_cache"] > 0
