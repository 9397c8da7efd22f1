"""Dropless top-k routing and the grouped expert layer against the plain
reference of ``perfbench/models/olmoe.py`` (one expert at a time over all
tokens, no sort, no capacity), and the control: under a skewed router the
capacity gate drops assignments and the dropless layer does not."""

import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[3]))

from deepspeed_tpu.moe import DroplessMoE, MOELayer, TopKGate, top_k_routing
from deepspeed_tpu.moe.layer import swiglu_expert_fn
from perfbench import manifest

REFERENCE = manifest.load_module("models", "olmoe")
T, H, I, E = 48, 64, 96, 16


def _params(seed=0, skew=0.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    wg = jax.random.normal(ks[0], (H, E)) / np.sqrt(H)
    if skew:
        # every row's router logits lean the same way: experts 0..7 win
        wg = wg * 0.05
    experts = {"w_gate": jax.random.normal(ks[1], (E, H, I)) / np.sqrt(H),
               "w_up": jax.random.normal(ks[2], (E, H, I)) / np.sqrt(H),
               "w_down": jax.random.normal(ks[3], (E, I, H)) / np.sqrt(I)}
    x = jax.random.normal(ks[4], (T, H))
    if skew:
        # one coordinate, the same in every row, that the first eight
        # experts' router columns read
        wg = wg.at[0, :8].add(jnp.linspace(2.0, 1.0, 8))
        x = x.at[:, 0].set(skew)
    return wg, experts, x


def _reference(wg, experts, x, k, renormalize=False):
    cfg = {"num_experts_per_tok": k, "norm_topk_prob": renormalize}
    with jax.default_matmul_precision("highest"):
        return REFERENCE.moe(x, dict(experts, wg=wg), cfg)


@pytest.mark.parametrize("k", [1, 2, 8])
@pytest.mark.parametrize("renormalize", [False, True])
def test_routing_keeps_every_assignment_with_its_softmax_weight(k, renormalize):
    wg, _, x = _params()
    idx, weights, meta = top_k_routing(wg, x, k, renormalize)
    assert idx.shape == weights.shape == (T, k)
    assert idx.dtype == jnp.int32 and weights.dtype == jnp.float32
    probs = jax.nn.softmax(
        jnp.dot(x, wg, precision="highest").astype(jnp.float32), axis=-1)
    want, want_idx = jax.lax.top_k(probs, k)
    assert (np.asarray(idx) == np.asarray(want_idx)).all()
    if renormalize:
        want = want / want.sum(-1, keepdims=True)
        np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-5)
    elif k < E:
        assert float(weights.sum(-1).max()) < 1.0     # used as they are
    np.testing.assert_allclose(np.asarray(weights), np.asarray(want),
                               rtol=2e-3)
    assert float(meta["drop_rate"]) == 0.0 == float(meta["overflow_frac"])
    assert float(meta["assignments"]) == T * k
    assert float(meta["exp_counts"].sum()) == T * k
    assert float(meta["experts_active"]) == float((meta["exp_counts"] > 0).sum())


def test_bfloat16_rows_are_routed_in_float32():
    wg, _, x = _params()
    _, weights, _ = top_k_routing(wg.astype(jnp.bfloat16),
                                  x.astype(jnp.bfloat16), 8)
    assert weights.dtype == jnp.float32


@pytest.mark.parametrize("k, renormalize", [(8, False), (8, True), (2, False)])
def test_layer_equals_the_plain_reference(k, renormalize):
    """float32, ``highest``: the same products summed in another order."""
    wg, experts, x = _params(1)
    layer = DroplessMoE(E, k, renormalize=renormalize)
    with jax.default_matmul_precision("highest"):
        y, l_aux, meta = layer(wg, experts, x[None])
    want = _reference(wg, experts, x, k, renormalize)
    assert float(jnp.max(jnp.abs(y[0] - want))) < 2e-5
    assert float(meta["drop_rate"]) == 0.0 and float(l_aux) > 0.0


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("several_devices", [False, True],
                         ids=["one_device", "mesh_of_two"])
def test_the_stacked_layers_weights_and_an_index_are_that_layers_call(
        layer, several_devices):
    """``layer=l`` with the ``[3, E, …]`` stacks (what a serving program
    hands over, ``l`` traced) against ``layer=None`` with layer ``l``'s own
    leaves (what a scan over the layers hands over): bit for bit, and the
    router's stats too.  Under a mesh the reference indexes the stack."""
    from jax.sharding import Mesh

    wg, _, x = _params(4)
    stacks = jax.tree.map(lambda *ws: jnp.stack(ws),
                          *(_params(seed)[1] for seed in (5, 6, 7)))
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("data",)) \
        if several_devices else None
    moe = DroplessMoE(E, 8, mesh=mesh)
    y, l_aux, meta = jax.jit(
        lambda l: moe(wg, stacks, x[None], layer=l))(jnp.int32(layer))
    one = jax.tree.map(lambda w: w[layer], stacks)
    want, want_aux, want_meta = jax.jit(lambda: moe(wg, one, x[None]))()
    assert bool(jnp.all(y == want)) and float(l_aux) == float(want_aux)
    assert bool(jnp.all(meta["exp_counts"] == want_meta["exp_counts"]))
    other = jax.tree.map(lambda w: w[(layer + 1) % 3], stacks)
    assert not bool(jnp.all(y == moe(wg, other, x[None])[0]))


def test_under_a_skewed_router_the_capacity_gate_drops_and_this_does_not():
    """The control: every row picks the same experts.  At k = 2, which the
    capacity gate can run, its capacity ``ceil(2·48·2.0/16) = 12`` slots an
    expert holds a quarter of the 48 rows that want each of the two
    experts: the dropless layer equals the reference, the gate does not."""
    wg, experts, x = _params(2, skew=4.0)
    idx, _, meta = top_k_routing(wg, x, 8)
    assert set(np.asarray(idx).reshape(-1).tolist()) == set(range(8))
    assert float(meta["experts_active"]) == 8.0
    with jax.default_matmul_precision("highest"):
        for k in (8, 2):
            y, _, _ = DroplessMoE(E, k)(wg, experts, x[None])
            want = _reference(wg, experts, x, k)
            assert float(jnp.max(jnp.abs(y[0] - want))) < 2e-5
        gate = TopKGate(num_experts=E, k=2, capacity_factor=2.0,
                        eval_capacity_factor=2.0, min_capacity=4)
        assert gate.capacity(T) == 12
        y, _, gate_meta = MOELayer(gate, swiglu_expert_fn)(wg, experts, x[None])
        # GShard renormalises the kept weights, so hold it to the
        # renormalised reference: what is left is the dropping alone
        want = _reference(wg, experts, x, 2, renormalize=True)
    assert float(gate_meta["drop_rate"]) >= 0.7
    dropped_rows = np.asarray(jnp.max(jnp.abs(y[0] - want), axis=-1) > 1e-3)
    assert dropped_rows.sum() >= T - 12


def test_top_8_is_beyond_the_capacity_gate():
    wg, experts, x = _params()
    gate = TopKGate(num_experts=E, k=8, capacity_factor=2.0)
    with pytest.raises(ValueError, match="k must be 1 or 2"):
        MOELayer(gate, swiglu_expert_fn)(wg, experts, x[None])


def test_a_mesh_of_several_devices_takes_the_reference_path():
    from jax.sharding import Mesh

    wg, experts, x = _params(3)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("data",))
    with jax.default_matmul_precision("highest"):
        y, _, _ = jax.jit(DroplessMoE(E, 8, mesh=mesh))(wg, experts, x[None])
    assert float(jnp.max(jnp.abs(y[0] - _reference(wg, experts, x, 8)))) < 2e-5
