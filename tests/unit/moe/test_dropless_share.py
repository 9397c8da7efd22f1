"""The expert layer that is told which experts it holds (expert
parallelism's share: ``DroplessMoE(held=(first, count))``) and the router
of the sigmoid line (``top_k_routing(scoring="sigmoid", choice_bias=…)``),
against the plain reference of ``perfbench/models/mimo_v2.py`` and against
the uncut layer: the parts of all shares add up to the whole."""

import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[3]))

from deepspeed_tpu.moe import DroplessMoE, top_k_routing
from deepspeed_tpu.ops.pallas import moe_grouped_matmul as gm
from perfbench import manifest

REFERENCE = manifest.load_module("models", "mimo_v2")
T, H, I, E, K = 40, 64, 48, 32, 8
SHARES, HELD = 4, 8


def _layer(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return {"wg": jax.random.normal(ks[0], (H, E)) / np.sqrt(H),
            "bias": 0.3 * jax.random.normal(ks[1], (E,)),
            "w_gate": jax.random.normal(ks[2], (E, H, I)) / np.sqrt(H),
            "w_up": jax.random.normal(ks[3], (E, H, I)) / np.sqrt(H),
            "w_down": jax.random.normal(ks[4], (E, I, H)) / np.sqrt(I)
            }, jax.random.normal(ks[5], (T, H))


def _cfg(**over):
    return dict({"num_experts_per_tok": K, "norm_topk_prob": True,
                 "expert_rank": 0}, **over)


def _share(m, rank, count=HELD):
    cut = lambda w: w[rank * count:(rank + 1) * count]
    return dict(m, w_gate=cut(m["w_gate"]), w_up=cut(m["w_up"]),
                w_down=cut(m["w_down"]))


def _served(m, x, held, **kw):
    layer = DroplessMoE(E, K, renormalize=True, scoring="sigmoid", held=held,
                        **kw)
    experts = {n: m[n] for n in ("w_gate", "w_up", "w_down")}
    with jax.default_matmul_precision("highest"):
        y, _, meta = layer(m["wg"], experts, x[None], choice_bias=m["bias"])
    return y[0], meta


@pytest.mark.parametrize("renormalize", [True, False])
def test_sigmoid_routing_with_a_choice_bias(renormalize):
    m, x = _layer()
    idx, weights, meta = top_k_routing(m["wg"], x, K, renormalize,
                                       "sigmoid", m["bias"])
    score = np.asarray(jax.nn.sigmoid(x @ m["wg"]), np.float64)
    want = np.argsort(-(score + np.asarray(m["bias"])), axis=1)[:, :K]
    assert (np.sort(np.asarray(idx), 1) == np.sort(want, 1)).all()
    # the bias moves the choice (it differs from the scores' own top k
    # somewhere) and not the weights
    assert (np.sort(np.argsort(-score, 1)[:, :K], 1) != np.sort(want, 1)).any()
    chosen = np.take_along_axis(score, np.asarray(idx), 1)
    if renormalize:
        chosen = chosen / chosen.sum(1, keepdims=True)
    np.testing.assert_allclose(np.asarray(weights), chosen, rtol=2e-5)
    assert float(meta["assignments"]) == T * K
    # the reference's routing is the same function, densely
    dense = np.asarray(REFERENCE.routing(
        x, m["wg"], m["bias"], _cfg(norm_topk_prob=renormalize)))
    np.testing.assert_allclose(
        np.take_along_axis(dense, np.asarray(idx), 1), np.asarray(weights),
        rtol=2e-5)
    assert ((dense > 0).sum(1) == K).all()


def test_softmax_routing_is_what_it_was():
    """No scoring argument, no bias: OLMoE's router, to the bit."""
    m, x = _layer(3)
    idx, weights, _ = top_k_routing(m["wg"], x, K)
    probs = jax.nn.softmax(jnp.einsum(
        "th,he->te", x, m["wg"], preferred_element_type=jnp.float32), -1)
    w, i = jax.lax.top_k(probs, K)
    assert (np.asarray(i) == np.asarray(idx)).all()
    assert (np.asarray(w) == np.asarray(weights)).all()
    with pytest.raises(ValueError, match="scoring"):
        top_k_routing(m["wg"], x, K, scoring="tanh")


def test_the_shares_parts_add_up_to_the_uncut_layer():
    """Four shares of 8 of 32 experts: each returns its own experts' part,
    the reference given the same share agrees with each, and the four
    parts add up to the layer with every expert held (program and
    reference)."""
    m, x = _layer(1)
    whole, meta = _served(m, x, None)
    with jax.default_matmul_precision("highest"):
        ref_whole = REFERENCE.moe(x, m, _cfg())
    np.testing.assert_allclose(np.asarray(whole), np.asarray(ref_whole),
                               atol=2e-5)
    assert float(meta["assignments"]) == T * K
    assert "assignments_routed" not in meta
    parts, here, active = [], 0.0, 0.0
    for rank in range(SHARES):
        part, meta = _served(_share(m, rank), x, (rank * HELD, HELD))
        with jax.default_matmul_precision("highest"):
            ref = REFERENCE.moe(x, _share(m, rank), _cfg(expert_rank=rank))
        np.testing.assert_allclose(np.asarray(part), np.asarray(ref),
                                   atol=2e-5)
        assert float(meta["assignments_routed"]) == T * K
        assert float(meta["experts_active"]) <= HELD
        here += float(meta["assignments"])
        active += float(meta["experts_active"])
        parts.append(part)
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               atol=4e-5)
    # every assignment is computed on exactly one share
    assert here == T * K
    assert float(np.abs(np.asarray(parts[0])).max()) > 1e-2


def test_a_share_nothing_lands_on_returns_zeros():
    """A choice bias that sends every token elsewhere: no row here, one
    tile of zeros in use, a zero part and no NaN."""
    m, x = _layer(2)
    m = dict(m, bias=m["bias"].at[:HELD].set(-10.0))
    part, meta = _served(_share(m, 0), x, (0, HELD))
    assert float(meta["assignments"]) == 0.0
    assert float(meta["experts_active"]) == 0.0
    assert not np.asarray(part).any()


@pytest.mark.parametrize("count, k", [(8, 3), (2, 3), (16, 8)])
def test_a_shares_plan_is_sized_for_what_can_land_on_it(count, k):
    """The static tiles: a token's k choices are distinct experts, so at
    most min(k, count) of them land on ``count`` held ones; rows of other
    experts' assignments point at row 0 and no group counts them."""
    rng = np.random.RandomState(0)
    idx = np.stack([rng.permutation(32)[:k] for _ in range(T)]) - 4
    plan = gm.plan_groups(jnp.asarray(idx, jnp.int32), count, 8, share=True)
    here = (idx >= 0) & (idx < count)
    assert plan.tile_group.shape[0] == T * min(k, count) // 8 + min(
        count, T * min(k, count))
    assert int(plan.group_sizes.sum()) == here.sum()
    assert (np.asarray(plan.dest)[~here] == 0).all()
    assert int(plan.num_tiles[0]) >= 1
    # every assignment that landed has a row of its own, holding its token
    dest = np.asarray(plan.dest)[here]
    assert len(set(dest.tolist())) == here.sum()
    tokens = np.broadcast_to(np.arange(T)[:, None], idx.shape)[here]
    assert (np.asarray(plan.row_token)[dest] == tokens).all()
    assert np.asarray(plan.row_valid)[dest].all()


def test_held_must_be_a_share_of_the_routers_experts():
    with pytest.raises(ValueError, match="no share"):
        DroplessMoE(32, 8, held=(28, 8))


def _one_expert_at_a_time(m, x, idx, weights, first, count):
    """Each held expert run over all tokens, weighted by the tokens'
    weights for it: float32, no plan, no tiles."""
    y = jnp.zeros_like(x)
    for e in range(count):
        out = (jax.nn.silu(x @ m["w_gate"][e]) * (x @ m["w_up"][e])
               ) @ m["w_down"][e]
        y = y + jnp.sum(jnp.where(idx == first + e, weights, 0.0),
                        axis=1)[:, None] * out
    return y


# the tiles a share is given at the serving cells' shapes (64, 32, 16: the
# router's mean group of 32, 16, 8), each where EVERY choice of every
# token lands on the held experts, which is what the static tiles are
# sized for, and where none does
@pytest.mark.parametrize("lands", ["every_choice", "none"])
@pytest.mark.parametrize("tokens, tile", [(128, 64), (64, 32), (32, 16)])
def test_a_share_at_the_routers_tile_computes_whatever_lands(tokens, tile,
                                                             lands):
    experts, k, first, count = 16, 4, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(tile), 6)
    m = {"wg": jax.random.normal(ks[0], (H, experts)) / np.sqrt(H),
         "w_gate": jax.random.normal(ks[1], (count, H, I)) / np.sqrt(H),
         "w_up": jax.random.normal(ks[2], (count, H, I)) / np.sqrt(H),
         "w_down": jax.random.normal(ks[3], (count, I, H)) / np.sqrt(I)}
    x = jax.random.normal(ks[4], (tokens, H))
    # a sigmoid score lies in (0, 1): a bias of 10 decides the choice
    held = (jnp.arange(experts) >= first) & (jnp.arange(experts)
                                             < first + count)
    bias = jnp.where(held, 10.0, 0.0) * (1 if lands == "every_choice" else -1)
    layer = DroplessMoE(experts, k, renormalize=True, scoring="sigmoid",
                        held=(first, count))
    with jax.default_matmul_precision("highest"):
        y, _, meta = layer(m["wg"], {n: m[n] for n in
                                     ("w_gate", "w_up", "w_down")},
                           x[None], choice_bias=bias)
        idx, weights, _ = top_k_routing(m["wg"], x, k, True, "sigmoid", bias)
        want = _one_expert_at_a_time(m, x, idx, weights, first, count)
    landed = tokens * k if lands == "every_choice" else 0
    assert float(meta["assignments"]) == landed
    assert float(meta["assignments_routed"]) == tokens * k
    # the tile is the router's (tokens x k over ITS experts), not the one
    # of what can land on the share (tokens x k over the 8 held: twice it)
    assert layer.last_tile_rows == tile == gm.tile_rows_for(
        tokens * k, experts, x.dtype)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want), atol=3e-5)
    assert bool(np.asarray(y).any()) == (landed > 0)

    local = idx - first
    plan = gm.plan_groups(local, count, tile, share=True)
    tiles = plan.tile_group.shape[0]
    assert tiles == tokens * k // tile + count
    assert 1 <= int(plan.num_tiles[0]) <= tiles
    assert float(meta["rows_computed"]) == int(plan.num_tiles[0]) * tile
    assert landed <= float(meta["rows_computed"]) or landed == 0
    here = np.asarray((local >= 0) & (local < count))
    assert here.sum() == landed == int(plan.group_sizes.sum())
    # every assignment that landed has a valid row of its own, in a tile
    # in use, that holds its token; the others point at row 0
    dest = np.asarray(plan.dest)
    assert (dest[~here] == 0).all()
    assert len(set(dest[here].tolist())) == landed
    assert (dest[here] < int(plan.num_tiles[0]) * tile).all()
    assert np.asarray(plan.row_valid)[dest[here]].all()
    assert (np.asarray(plan.row_token)[dest[here]]
            == np.broadcast_to(np.arange(tokens)[:, None], here.shape)[here]
            ).all()
    # and the kernels themselves (interpreted) on that plan
    here_w = jnp.where(jnp.asarray(here), weights, 0.0)
    stack = {n: m[n][None] for n in ("w_gate", "w_up", "w_down")}
    with jax.default_matmul_precision("highest"):
        act = gm.grouped_swiglu(gm.gather_rows(x, plan), stack["w_gate"],
                                stack["w_up"], 0, plan, interpret=True)
        got = gm.combine_rows(gm.grouped_matmul(
            act, stack["w_down"], 0, plan, interpret=True), plan, here_w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)
