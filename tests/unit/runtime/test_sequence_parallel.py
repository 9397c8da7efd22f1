"""Ulysses/ALST sequence parallelism: all-to-all numerics + tiled compute."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.parallel import MeshLayout
from deepspeed_tpu.runtime.sequence_parallel import (
    SequenceTiledCompute, TiledMLP, UlyssesSPAttentionHF,
    UlyssesSPDataLoaderAdapter, sequence_tiled_loss, ulysses_attention)
from deepspeed_tpu.sequence import DistributedAttention
from deepspeed_tpu.utils import groups


def softmax_attn(q, k, v):
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def make_qkv(B=4, S=32, h=8, d=16, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, S, h, d), jnp.float32)
    return mk(), mk(), mk()


def test_ulysses_attention_matches_direct():
    mesh = groups.initialize_mesh(MeshLayout.infer(8, dp=2, sp=2, tp=2))
    q, k, v = make_qkv()
    out = jax.jit(lambda q, k, v: ulysses_attention(
        softmax_attn, q, k, v, mesh=mesh))(q, k, v)
    ref = softmax_attn(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ulysses_sp1_passthrough():
    mesh = groups.initialize_mesh(MeshLayout.infer(8, dp=8))
    q, k, v = make_qkv()
    out = ulysses_attention(softmax_attn, q, k, v, mesh=mesh)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(softmax_attn(q, k, v)), rtol=1e-5)


def test_distributed_attention_legacy_api():
    mesh = groups.initialize_mesh(MeshLayout.infer(8, dp=4, sp=2))
    q, k, v = make_qkv()
    attn = DistributedAttention(softmax_attn)
    out = jax.jit(attn)(q, k, v)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(softmax_attn(q, k, v)),
                               rtol=1e-5, atol=1e-5)


def test_sequence_tiled_compute_matches_untiled():
    x = jnp.asarray(np.random.RandomState(0).randn(2, 64, 8), jnp.float32)
    fn = lambda t: jax.nn.gelu(t) * 2.0 + 1.0
    out = SequenceTiledCompute.apply(fn, x, tiles=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(fn(x)), rtol=1e-6)
    out2 = TiledMLP.apply(fn, x, tiles=8)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(fn(x)), rtol=1e-6)


def _head_inputs(dtype=jnp.float32, B=2, S=32, H=16, V=64, seed=0):
    rng = np.random.RandomState(seed)
    hidden = jnp.asarray(rng.randn(B, S, H), jnp.float32).astype(dtype)
    head = jnp.asarray(rng.randn(H, V) * 0.3, jnp.float32).astype(dtype)
    labels = jnp.asarray(rng.randint(0, V, size=(B, S)))
    return hidden, head, labels.at[:, -4:].set(-100)


def _untiled(hidden, head, labels):
    from deepspeed_tpu.models.llama import masked_cross_entropy

    return masked_cross_entropy(
        jnp.einsum("bsH,HV->bsV", hidden, head), labels)


def _rel(a, b):
    a, b = (np.asarray(x, np.float64) for x in (a, b))
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _head_loss_counts(fn, *args):
    """``ops/head_loss/*`` after one trace of ``fn(*args)``."""
    from deepspeed_tpu.telemetry import get_telemetry

    hub = get_telemetry()
    hub.reset()
    hub.configure(enabled=True, jsonl=False, prometheus=False)
    try:
        jax.make_jaxpr(lambda *a: fn(*a))(*args)  # a trace of its own
        return {name.rsplit("/", 1)[1]: c["value"] for name, c in
                hub.registry.snapshot()["counters"].items()
                if name.startswith("ops/head_loss/")}
    finally:
        hub.reset()


def test_sequence_tiled_loss_matches_untiled():
    hidden, head, labels = _head_inputs()
    tiled = sequence_tiled_loss(hidden, head, labels, tiles=4)
    np.testing.assert_allclose(float(tiled),
                               float(_untiled(hidden, head, labels)),
                               rtol=1e-5)


def _labels_case(case, labels, S, tiles):
    if case == "a_tile_all_ignored":
        return labels.at[:, :S // tiles].set(-100)
    if case == "count_0":
        return jnp.full_like(labels, -100)
    return labels


@pytest.mark.parametrize("case", ["ignored_tail", "a_tile_all_ignored",
                                  "count_0", "ragged_S", "cotangent_2.5",
                                  "groups_2"])
@pytest.mark.parametrize("tiles", [2, 4, 8])
def test_one_pass_loss_and_gradients_match_untiled_float32(tiles, case):
    """The loss and both gradients of the form that computes its gradient
    in the loss's own pass, against ``masked_cross_entropy`` left to
    autodiff: with ignored labels, a tile that holds none that counts, no
    label at all (loss and gradients 0), a sequence the tiles do not
    divide (one tile), a cotangent other than 1, and the batch cut into
    groups whose head gradients are added after the scan."""
    S = 30 if case == "ragged_S" else 32
    hidden, head, labels = _head_inputs(S=S)
    labels = _labels_case(case, labels, S, tiles)
    weight = 2.5 if case == "cotangent_2.5" else 1.0
    groups_ = 2 if case == "groups_2" else 1

    def tiled(h, w):
        return weight * sequence_tiled_loss(h, w, labels, tiles,
                                            groups=groups_)

    got, (dh, dw) = jax.value_and_grad(tiled, (0, 1))(hidden, head)
    ref, (rh, rw) = jax.value_and_grad(
        lambda h, w: weight * _untiled(h, w, labels), (0, 1))(hidden, head)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(dh), np.asarray(rh), rtol=1e-4,
                               atol=1e-7)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(rw), rtol=1e-4,
                               atol=1e-7)
    if case == "count_0":
        assert float(got) == 0.0 and not np.asarray(dh).any() \
            and not np.asarray(dw).any()
    assert _head_loss_counts(tiled, hidden, head) == {"one_pass": 1.0}


@pytest.mark.parametrize("tiles", [2, 4, 8])
def test_one_pass_bfloat16_is_no_further_from_float32_than_recomputing(tiles):
    """bfloat16 operands: the one-pass form's loss and gradients against
    the float32 untiled reference err no more than the recomputing form's
    on the same inputs (the same products in the same dtypes; ``dW`` summed
    over the tiles in float32 where the scan's transpose sums in
    bfloat16)."""
    from deepspeed_tpu.runtime.sequence_parallel.ulysses_sp import \
        _recomputing_loss

    hidden, head, labels = _head_inputs(jnp.bfloat16, S=64, H=32, V=128)
    count = jnp.sum(labels != -100)
    ref, ref_grads = jax.value_and_grad(
        lambda h, w: _untiled(h, w, labels), (0, 1))(
            hidden.astype(jnp.float32), head.astype(jnp.float32))
    one, one_grads = jax.value_and_grad(
        lambda h, w: sequence_tiled_loss(h, w, labels, tiles), (0, 1))(
            hidden, head)
    old, old_grads = jax.value_and_grad(
        lambda h, w: _recomputing_loss(h, w, labels, count, tiles), (0, 1))(
            hidden, head)
    assert abs(float(one) - float(ref)) <= abs(float(old) - float(ref)) + 1e-6
    for mine, theirs, exact in zip(one_grads, old_grads, ref_grads):
        assert mine.dtype == jnp.bfloat16
        assert _rel(mine, exact) <= _rel(theirs, exact) * 1.02 + 1e-6
        assert _rel(mine, exact) < 0.02


def test_float16_head_keeps_the_recomputing_form():
    """A float16 head's loss scale has to reach ``dlogits`` before the
    rounding, so it is left to autodiff and ``jax.checkpoint``: the same
    loss and gradients as the untiled float16 loss, four products in the
    gradient, and the counter says which form was built."""
    hidden, head, labels = _head_inputs(jnp.float16)
    scale = 1024.0

    def tiled(h, w):
        return scale * sequence_tiled_loss(h, w, labels, 4)

    got, grads = jax.value_and_grad(tiled, (0, 1))(hidden, head)
    ref, ref_grads = jax.value_and_grad(
        lambda h, w: scale * _untiled(h, w, labels), (0, 1))(hidden, head)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-3)
    for mine, theirs in zip(grads, ref_grads):
        assert mine.dtype == jnp.float16
        assert _rel(mine, theirs) < 5e-3
    assert _head_loss_counts(tiled, hidden, head) == {"recomputed": 1.0}
    assert len(_vocabulary_products(
        jax.make_jaxpr(jax.grad(tiled, (0, 1)))(hidden, head).jaxpr,
        head.shape[1])) == 4


def _vocabulary_products(jaxpr, V, inside_scan=False, found=None):
    """Every ``dot_general`` of a jaxpr (its sub-jaxprs too) with an operand
    or a result ``V`` wide, as ``(inside a scan?, operand shapes, result
    shape)``."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            shapes = [tuple(v.aval.shape) for v in eqn.invars + eqn.outvars]
            if any(V in shape for shape in shapes):
                found.append((inside_scan, shapes[:2], shapes[2]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _vocabulary_products(
                sub, V, inside_scan or eqn.primitive.name == "scan", found)
    return found


def _scans(jaxpr, found=None):
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _scans(sub, found)
    return found


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_gradient_holds_three_products_over_the_vocabulary(dtype):
    """The gradient's jaxpr: ONE scan (the rule's forward), whose body
    holds the three products the mathematics needs (logits, ``dh``,
    ``dW``), and no scan nor product for the backward.  A ``jax.checkpoint``
    put around the loss tail would run that forward twice and show six
    here.  The undifferentiated call holds the logits' product alone and
    no ``[H, V]`` value but the head; either trace ticks its counter
    once."""
    B, S, H, V, tiles = 2, 32, 16, 64, 4
    hidden, head, labels = _head_inputs(dtype, B, S, H, V)

    def loss(h, w):
        return sequence_tiled_loss(h, w, labels, tiles)

    grad = jax.make_jaxpr(jax.grad(loss, (0, 1)))(hidden, head).jaxpr
    products = _vocabulary_products(grad, V)
    rows = (B, S // tiles)
    assert sorted(result for _, _, result in products) == sorted(
        [rows + (V,), rows + (H,), (H, V)]), products
    assert all(inside for inside, _, _ in products), products
    assert len(_scans(grad)) == 1
    # dW is summed over the tiles in float32 whatever the operands are
    carried = [v.aval for v in _scans(grad)[0].outvars
               if tuple(v.aval.shape) == (H, V)]
    assert [a.dtype for a in carried] == [jnp.float32]

    plain = jax.make_jaxpr(loss)(hidden, head).jaxpr
    assert [result for _, _, result in _vocabulary_products(plain, V)] == [
        rows + (V,)]
    assert not [v for scan in _scans(plain) for v in scan.outvars
                if tuple(v.aval.shape) == (H, V)]

    assert _head_loss_counts(jax.grad(loss, (0, 1)), hidden, head) == {
        "one_pass": 1.0}
    assert _head_loss_counts(loss, hidden, head) == {"one_pass": 1.0}


def test_llama_loss_tail_runs_its_one_pass_forward_once():
    """The model's own gradient under ``remat=True``: remat wraps the layer
    body only, so the loss tail's three products over the vocabulary appear
    once (a checkpoint around the tail would double them)."""
    from deepspeed_tpu.models import LlamaConfig, LlamaModel

    cfg = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32, remat=True,
                           loss_tiles=4, vocab_size=384)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(2, 32)))
    jaxpr = jax.make_jaxpr(jax.grad(model.loss))(
        params, {"input_ids": ids}).jaxpr
    # the embedding's gather is no dot_general; the head's are all there is
    assert len(_vocabulary_products(jaxpr, cfg.vocab_size)) == 3


@pytest.mark.parametrize("layout", [{"dp": 8}, {"dp": 4, "sp": 2}],
                         ids=["dp8", "dp4_sp2"])
def test_llama_tiled_loss_on_a_data_mesh_keeps_a_head_gradient_a_replica(
        layout):
    """On a mesh the model hands the tiled loss its count of data-parallel
    replicas: the same loss and gradients as the untiled loss with no
    mesh, and the one scan carries the head's gradient with a leading axis
    of replicas (each sums its own tiles; they are added after the scan,
    once, where a carry of ``[H, V]`` alone is reduced across the replicas
    every tile)."""
    from deepspeed_tpu.models import LlamaConfig, LlamaModel

    kw = dict(num_layers=2, dtype=jnp.float32, vocab_size=384)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 384, size=(8, 32)))
    plain = LlamaModel(LlamaConfig.tiny(**kw))
    params = plain.init_params(jax.random.PRNGKey(0))
    ref, ref_grads = jax.value_and_grad(plain.loss)(params,
                                                    {"input_ids": ids})
    mesh = groups.initialize_mesh(MeshLayout.infer(8, **layout))
    meshed = LlamaModel(LlamaConfig.tiny(loss_tiles=4, **kw), mesh=mesh)
    got, grads = jax.jit(jax.value_and_grad(meshed.loss))(
        params, {"input_ids": ids})
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)
    for mine, theirs in zip(jax.tree.leaves(grads),
                            jax.tree.leaves(ref_grads)):
        assert _rel(mine, theirs) < 1e-4
    jaxpr = jax.make_jaxpr(jax.grad(meshed.loss))(
        params, {"input_ids": ids}).jaxpr
    H, V = params["lm_head"].shape
    carried = [tuple(v.aval.shape) for scan in _scans(jaxpr)
               for v in scan.outvars if tuple(v.aval.shape)[-2:] == (H, V)]
    assert carried == [(layout["dp"], H, V)], carried


def test_dataloader_adapter_slices_sequence():
    groups.initialize_mesh(MeshLayout.infer(8, dp=4, sp=2))
    batches = [{"input_ids": jnp.arange(2 * 16).reshape(2, 16)}]
    sliced = list(UlyssesSPDataLoaderAdapter(batches, sp_rank=1,
                                             sp_world_size=2))
    assert sliced[0]["input_ids"].shape == (2, 8)
    np.testing.assert_array_equal(np.asarray(sliced[0]["input_ids"][0]),
                                  np.arange(8, 16))


def test_register_with_transformers_returns_mpu():
    groups.initialize_mesh(MeshLayout.infer(8, dp=4, sp=2))
    mpu = UlyssesSPAttentionHF.register_with_transformers(
        model_name_or_path="x", sequence_parallel_size=2, max_length=256)
    assert mpu.get_sequence_parallel_world_size() == 2
    assert UlyssesSPAttentionHF.register_with_transformers(
        sequence_parallel_size=1) is None
    with pytest.raises(ValueError):
        UlyssesSPAttentionHF.register_with_transformers(
            sequence_parallel_size=4, max_length=256)


def test_llama_tiled_loss_matches_untiled():
    from deepspeed_tpu.models import LlamaConfig, LlamaModel

    ids = jnp.asarray(np.random.RandomState(0).randint(0, 512, size=(2, 32)))
    cfg = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32)
    m0 = LlamaModel(cfg)
    params = m0.init_params(jax.random.PRNGKey(0))
    ref = m0.loss(params, {"input_ids": ids})
    m1 = LlamaModel(LlamaConfig.tiny(num_layers=2, dtype=jnp.float32,
                                     loss_tiles=4))
    tiled = m1.loss(params, {"input_ids": ids})
    np.testing.assert_allclose(float(tiled), float(ref), rtol=1e-5)
