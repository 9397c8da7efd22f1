"""The dropless expert layout and its grouped matmul: the plan's
invariants for any routing, the Pallas kernels (interpreter) and the
``ragged_dot`` reference against one expert at a time over all tokens, and
the kernels' gradients, which run through the reference.  The weights are
a stack of layers plus a layer's index: every layer of a stack of three
gives the bits of that layer as a stack of one."""

import functools
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import moe_grouped_matmul as gm

T, K, E, H, I = 24, 4, 8, 128, 256


def _routings():
    spread = jax.lax.top_k(jax.random.normal(jax.random.PRNGKey(0), (T, E)),
                           K)[1]
    same = jnp.tile(jnp.arange(2, 2 + K)[None], (T, 1))   # four experts only
    one_heavy = spread.at[:, 0].set(5)
    return {"spread": spread, "all_rows_the_same_experts": same,
            "one_heavy_expert": one_heavy}


ROUTINGS = _routings()


def _weights(seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (T, H)),
            jax.random.normal(ks[1], (E, H, I)) / np.sqrt(H),
            jax.random.normal(ks[2], (E, H, I)) / np.sqrt(H),
            jax.random.normal(ks[3], (E, I, H)) / np.sqrt(I),
            jax.random.uniform(ks[4], (T, K)))


def _stacks_of_three():
    """``w_gate, w_up [3, E, H, I]``, ``w_down [3, E, I, H]``: three layers
    of different weights."""
    return [jnp.stack(ws) for ws in zip(*(_weights(seed)[1:4]
                                          for seed in (5, 6, 7)))]


def _layer(plan, x, w_gate, w_up, w_down, gates, interpret, layer=None):
    """``layer=None``: one layer's ``[E, K, N]`` weights, made the stack of
    one that is the kernels' only form; else ``[L, E, K, N]`` stacks."""
    if layer is None:
        w_gate, w_up, w_down, layer = w_gate[None], w_up[None], w_down[None], 0
    rows = gm.gather_rows(x, plan)
    act = gm.grouped_swiglu(rows, w_gate, w_up, layer, plan,
                            interpret=interpret)
    out = gm.grouped_matmul(act, w_down, layer, plan, interpret=interpret)
    return gm.combine_rows(out, plan, gates)


def _one_expert_at_a_time(idx, x, w_gate, w_up, w_down, gates):
    y = jnp.zeros((T, H))
    for e in range(E):
        out = (jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e])) @ w_down[e]
        y += jnp.sum(jnp.where(idx == e, gates, 0.0), axis=1)[:, None] * out
    return y


@pytest.mark.parametrize("routing", ROUTINGS)
@pytest.mark.parametrize("tile_rows", [8, 16, 32])
def test_every_assignment_has_a_row_of_its_own_experts_tile(routing, tile_rows):
    idx = ROUTINGS[routing]
    plan = jax.tree.map(np.asarray, gm.plan_groups(idx, E, tile_rows))
    tiles = T * K // tile_rows + min(E, T * K)
    assert plan.tile_group.shape == (tiles,)
    assert plan.row_valid.shape == (tiles * tile_rows,)
    n = int(plan.num_tiles[0])
    assert n == sum(-(-c // tile_rows) for c in plan.group_sizes) <= tiles
    assert plan.group_sizes.sum() == T * K and plan.row_valid.sum() == T * K
    # nothing dropped: the T*k destinations are distinct, valid rows that
    # hold the assignment's own token, in a tile of the assignment's expert
    dest = plan.dest.reshape(-1)
    assert len(set(dest.tolist())) == T * K
    assert plan.row_valid[dest].all()
    assert (plan.row_token[dest] == np.repeat(np.arange(T), K)).all()
    assert (plan.tile_group[dest // tile_rows]
            == np.asarray(idx).reshape(-1)).all()
    # tiles in use are sorted by expert; the unused tail repeats the last
    assert (np.diff(plan.tile_group) >= 0).all()
    assert (plan.tile_group[n:] == plan.tile_group[n - 1]).all()
    assert not plan.row_valid[n * tile_rows:].any()


@pytest.mark.parametrize("routing", ROUTINGS)
@pytest.mark.parametrize("interpret", [True, None],
                         ids=["pallas_interpret", "reference"])
def test_layer_equals_one_expert_at_a_time(routing, interpret):
    """float32 at ``highest``: sums of the same products in another order."""
    idx = ROUTINGS[routing]
    plan = gm.plan_groups(idx, E, gm.tile_rows_for(T * K, E, jnp.float32))
    args = _weights()
    with jax.default_matmul_precision("highest"):
        got = _layer(plan, *args, interpret=interpret)
        want = _one_expert_at_a_time(idx, *args)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("interpret", [True, None],
                         ids=["pallas_interpret", "reference"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16_rows_float32_stack"])
def test_a_layer_of_a_stack_of_three_is_that_layer_bit_for_bit(layer, interpret,
                                                               dtype):
    """The same blocks in the same order, so the same sums: layer ``l`` of
    ``[3, E, K, N]`` read in place, with ``l`` traced as a scan's index is,
    against that layer alone as a stack of one.  With bf16 rows over the
    float32 stack the layer is cast, not the stack."""
    idx = ROUTINGS["one_heavy_expert"]
    plan = gm.plan_groups(idx, E, 16)
    x, *_, gates = _weights(4)
    x = x.astype(dtype)
    stacks = _stacks_of_three()
    got = jax.jit(lambda l: _layer(plan, x, *stacks, gates, interpret,
                                   layer=l))(jnp.int32(layer))
    want = _layer(plan, x, *(w[layer] for w in stacks), gates, interpret)
    assert got.dtype == want.dtype
    assert bool(jnp.all(got == want))
    others = [_layer(plan, x, *(w[l] for w in stacks), gates, interpret)
              for l in range(3) if l != layer]
    assert not any(bool(jnp.all(got == other)) for other in others)


@pytest.mark.parametrize("layer", [0, 2])
def test_gradients_reach_the_layer_of_the_stack_and_no_other(layer):
    idx = ROUTINGS["spread"]
    plan = gm.plan_groups(idx, E, 16)
    x, *_, gates = _weights(4)
    stacks = _stacks_of_three()
    loss = lambda *w, l=None: jnp.sum(
        _layer(plan, x, *w, gates, True, layer=l) ** 2)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(functools.partial(loss, l=jnp.int32(layer)),
                       argnums=(0, 1, 2))(*stacks)
        want = jax.grad(loss, argnums=(0, 1, 2))(*(w[layer] for w in stacks))
    for g, w in zip(got, want):
        assert g.shape == (3,) + w.shape
        assert bool(jnp.all(g[layer] == w))
        assert float(jnp.abs(g).sum()) == float(jnp.abs(g[layer]).sum())


def test_gradients_of_the_kernel_path_are_the_references():
    """The kernels carry no backward kernel: their ``custom_vjp`` runs the
    reference's.  Every operand's gradient against the plain layer's."""
    idx = ROUTINGS["spread"]
    plan = gm.plan_groups(idx, E, 16)
    args = _weights(2)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda *a: jnp.sum(_layer(plan, *a, interpret=True) ** 2),
                       argnums=(0, 1, 2, 3, 4))(*args)
        want = jax.grad(lambda *a: jnp.sum(_one_expert_at_a_time(idx, *a) ** 2),
                        argnums=(0, 1, 2, 3, 4))(*args)
    for g, w in zip(got, want):
        assert float(jnp.max(jnp.abs(g - w))) < 1e-4 * float(jnp.max(jnp.abs(w)))


def test_bfloat16_rows_accumulate_in_float32():
    idx = ROUTINGS["spread"]
    plan = gm.plan_groups(idx, E, 16)
    x, w_gate, w_up, w_down, gates = _weights(3)
    bf = lambda a: a.astype(jnp.bfloat16)
    got = _layer(plan, bf(x), bf(w_gate), bf(w_up), bf(w_down), gates, True)
    with jax.default_matmul_precision("highest"):
        want = _one_expert_at_a_time(idx, *(bf(a).astype(jnp.float32) for a in
                                            (x, w_gate, w_up, w_down)), gates)
    # two bf16 roundings (the activation, the output) of ~2^-8 each
    assert float(jnp.max(jnp.abs(got - want))) < 0.03 * float(jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("assignments, experts, dtype, rows", [
    (32 * 8, 64, jnp.bfloat16, 16),      # a decode step of the 8-of-64 model
    (256 * 8, 64, jnp.bfloat16, 64),     # a prefill call
    (8192 * 2, 8, jnp.bfloat16, 128),    # a training row of an 8x7B
    (4, 8, jnp.float32, 8),
    # a SHARE of the router's experts is given the router's mean group
    # too (rows x k over the experts it chooses among), whatever part of
    # them the chip holds: the serving cells' steps with chunks and bursts
    (384 * 22, 512, jnp.bfloat16, 64),   # 22 of 512, 128 held: 16.5 a group
    (128 * 22, 512, jnp.bfloat16, 16),
    (512 * 8, 256, jnp.bfloat16, 32),    # 8 of 256, 16 held: 16 a group
    (256 * 8, 256, jnp.bfloat16, 16),
    (384 * 8, 256, jnp.bfloat16, 32),    # 8 of 256, 8 held: 12 a group
    (128 * 8, 256, jnp.bfloat16, 16)])
def test_tile_rows_follow_the_mean_group(assignments, experts, dtype, rows):
    assert gm.tile_rows_for(assignments, experts, dtype) == rows


def test_every_kernel_is_named_for_the_device_trace():
    source = pathlib.Path(gm.__file__).read_text()
    assert len(re.findall(r"\bpl\.pallas_call\(", source)) == 1
    assert "interpret=interpret, name=name," in source
    names = re.findall(r'_differentiable\(\w+, "(\w+)"', source)
    assert sorted(names) == ["moe_grouped_matmul", "moe_grouped_matmul_relu2",
                             "moe_grouped_matmul_swiglu"]


# -- an expert of two matrices (a LatentMoE expert) ---------------------------

W = 64      # the latent's width: the rows the experts multiply, not H


def _latent_weights(seed=11, layers=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (T, W)),
            jax.random.normal(ks[1], (layers, E, W, I)) / np.sqrt(W),
            jax.random.normal(ks[2], (layers, E, I, W)) / np.sqrt(I),
            jax.random.uniform(ks[3], (T, K)))


def _two_matrix_experts(idx, u, w_up, w_down, gates):
    y = jnp.zeros((T, W))
    for e in range(E):
        out = jnp.square(jax.nn.relu(u @ w_up[e])) @ w_down[e]
        y += jnp.sum(jnp.where(idx == e, gates, 0.0), axis=1)[:, None] * out
    return y


@pytest.mark.parametrize("routing", ROUTINGS)
@pytest.mark.parametrize("interpret", [True, None],
                         ids=["pallas_interpret", "ragged_dot"])
def test_the_relu2_kernel_is_one_expert_at_a_time(routing, interpret):
    """The up projection with ``relu(.)²`` as a grouped kernel (interpreter)
    and as ``ragged_dot``, then the plain down kernel, against every expert
    over all tokens; layer 1 of a stack of three, read where it lies."""
    idx = ROUTINGS[routing]
    plan = gm.plan_groups(idx, E, gm.tile_rows_for(T * K, E, jnp.float32))
    u, w_up, w_down, gates = _latent_weights()
    with jax.default_matmul_precision("highest"):
        act = jax.jit(lambda l: gm.grouped_relu2(
            gm.gather_rows(u, plan), w_up, l, plan, interpret=interpret))(
                jnp.int32(1))
        # (rows of unused tiles are undefined)
        assert float(jnp.min(jnp.where(plan.row_valid[:, None], act, 0))) \
            >= 0.0
        out = gm.grouped_matmul(act, w_down, 1, plan, interpret=interpret)
        got = gm.combine_rows(out, plan, gates)
        want = _two_matrix_experts(idx, u, w_up[1], w_down[1], gates)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5
    other = _two_matrix_experts(idx, u, w_up[0], w_down[0], gates)
    assert float(jnp.max(jnp.abs(got - other))) > 0.1


def test_the_relu2_kernels_interpreter_and_ragged_dot_agree_to_the_bit():
    idx = ROUTINGS["one_heavy_expert"]
    plan = gm.plan_groups(idx, E, 16)
    u, w_up, _, _ = _latent_weights(12)
    rows = gm.gather_rows(u, plan)
    kernel = gm.grouped_relu2(rows, w_up, 2, plan, interpret=True)
    plain = gm.grouped_relu2(rows, w_up, 2, plan, interpret=None)
    used = np.asarray(plan.row_valid)
    np.testing.assert_allclose(np.asarray(kernel)[used],
                               np.asarray(plain)[used], rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("held", [None, (2, 4)], ids=["all", "share"])
def test_the_dropless_layer_takes_the_experts_form_and_their_rows(held):
    """``form="relu2"`` with ``rows`` apart from what the router reads: the
    router scores ``x [.., H]``, the experts multiply ``rows [.., w]``, and
    ``y`` is ``w`` wide; a share computes its experts' part."""
    from deepspeed_tpu.moe.layer import DroplessMoE
    from deepspeed_tpu.moe.sharded_moe import top_k_routing

    u, w_up, w_down, _ = _latent_weights(13, layers=1)
    x = jax.random.normal(jax.random.PRNGKey(14), (T, H))
    wg = jax.random.normal(jax.random.PRNGKey(15), (H, E)) / np.sqrt(H)
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(16), (E,))
    first, count = held or (0, E)
    experts = {"w_up": w_up[0, first:first + count],
               "w_down": w_down[0, first:first + count]}
    layer = DroplessMoE(E, K, renormalize=True, scoring="sigmoid", held=held,
                        form="relu2")
    with jax.default_matmul_precision("highest"):
        y, _, meta = layer(wg, experts, x[None], choice_bias=bias,
                           rows=u[None])
        idx, gates, _ = top_k_routing(wg, x, K, True, "sigmoid", bias)
        here = (idx >= first) & (idx < first + count)
        want = _two_matrix_experts(
            jnp.where(here, idx, -1), u,
            jnp.zeros_like(w_up[0]).at[first:first + count].set(
                experts["w_up"]),
            jnp.zeros_like(w_down[0]).at[first:first + count].set(
                experts["w_down"]), gates)
    assert y.shape == (1, T, W)
    assert float(jnp.max(jnp.abs(y[0] - want))) < 2e-5
    assert float(meta["assignments"]) == float(jnp.sum(here))
    with pytest.raises(ValueError, match="form"):
        DroplessMoE(E, K, form="gelu")
