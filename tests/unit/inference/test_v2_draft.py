"""A model with a multi-token-prediction layer served through the paged
engine: a decode step of TWO rows a sequence (the newest token and its
draft) that yields one token or two, the drafting layer behind the trunk in
the same program, its keys one more layer of the full kind's pool, lengths
carried on the device.  Window layers with rotary and rings, full layers
with none, Q and K normed a head, a scaled router over a held share, a
shared expert.

Held here: the logits both rows are sampled from and the drafts' logits
against the plain float32 reference's full forward pass; the drafted stream
against the undrafted one token for token; the accepting branch on weights
made for it; what a rejected draft leaves in the caches."""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import KVCacheConfig, build_engine_v2
from deepspeed_tpu.inference.v2 import engine_v2 as ev2
from deepspeed_tpu.inference.v2.scheduler import RequestState

sys.path.insert(0, str(__import__("pathlib").Path(__file__).parents[3]))
from perfbench import manifest  # noqa: E402

FAMILY = manifest.load_module("models", "exaone_moe")

S, F = "sliding_attention", "full_attention"
#: [window + dense, (window, full) x 2] and the prediction layer; window 8;
#: share 1 of 4 of the router's 32 experts
TINY = dict(
    vocab_size=256, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_attention_heads=8, num_key_value_heads=2,
    head_dim=16, sliding_window=8, rope_parameters={"rope_theta": 1e6},
    rms_norm_eps=1e-5, published={"num_experts": 32}, num_experts=8,
    expert_rank=1, num_experts_per_tok=3, norm_topk_prob=True,
    routed_scaling_factor=2.5, num_shared_experts=1,
    layer_types=[S, S, F, S, F], mlp_layer_types=["dense"] + ["sparse"] * 4,
    num_hidden_layers=5, num_nextn_predict_layers=1,
    max_position_embeddings=256, run={"dtype": "float32"})
PAGE, CHUNK = 4, 8
PROMPT, NEW = 43, 40            # 83 positions: ten windows of 8
UNDRAFTED = dict(TINY, num_nextn_predict_layers=0)


_ENGINES = {}


def _engine(cfg, params, slots=2, burst=4, prefill_batch=1, spied=False):
    """An engine of these shapes serving ``params``: built once a shape
    (its programs take the weights as an argument, so an idle engine
    serves other weights of the same shapes without compiling anew);
    ``spied``: one of its own, whose programs a fixture traces its spies
    into."""
    key = (cfg["num_nextn_predict_layers"], slots, burst, prefill_batch,
           spied)
    if key not in _ENGINES:
        _ENGINES[key] = build_engine_v2(
            FAMILY.build(cfg), params,
            cache_config=KVCacheConfig(num_blocks=96, block_size=PAGE,
                                       max_seq_len=128),
            max_batch_slots=slots, prefill_chunk=CHUNK,
            prefill_batch=prefill_batch, decode_burst=burst)
    eng = _ENGINES[key]
    assert not eng.scheduler.has_work and not eng._inflight
    assert eng.scheduler.allocator.num_free == 95
    eng.params = params
    return eng


@pytest.fixture(scope="module")
def weights():
    return FAMILY.build(TINY).init_params(jax.random.PRNGKey(7))


@pytest.fixture(scope="module")
def served(weights):
    """One request through the drafting engine with every call's logits:
    ``(ids, steps, first_draft, engine)``; ``steps`` is a list, a decode
    step each, of ``(position of the step's first row, trunk logits [2,
    V], draft logits [2, V], tokens emitted)``."""
    trunk, drafts, emitted = [], [], []
    real = ev2._sample

    def spy(logits, temperature, key):
        jax.debug.callback(lambda l: trunk.append(np.asarray(l)), logits,
                           ordered=True)
        return real(logits, temperature, key)

    mp = pytest.MonkeyPatch()
    mp.setattr(ev2, "_sample", spy)
    eng = _engine(TINY, weights, spied=True)
    real_draft = eng.adapter.draft_logits

    def draft_spy(params, y):
        out = real_draft(params, y)
        jax.debug.callback(lambda l: drafts.append(np.asarray(l)), out,
                           ordered=True)
        return out

    mp.setattr(eng.adapter, "draft_logits", draft_spy)
    real_done = eng.scheduler.decode_burst_done

    def done_spy(requests, tokens, eos=None):
        if requests:    # a call whose decode rows were all dead: nothing
            emitted.extend(np.asarray(tokens)[:, 0].tolist())   # slot 0
        return real_done(requests, tokens, eos)

    mp.setattr(eng.scheduler, "decode_burst_done", done_spy)
    prompt = np.random.RandomState(3).randint(0, 256, size=PROMPT).tolist()
    req = eng.put(prompt, NEW)
    while eng.scheduler.has_work:
        eng.step()
    jax.effects_barrier()
    mp.undo()
    chunks = -(-PROMPT // CHUNK)
    # a call with a chunk: the chunk's last row in front of the 2 x slots
    # decode rows; the prompt's last chunk samples the first token
    steps, pos = [], PROMPT
    assert len(trunk) - chunks == len(drafts) - chunks == len(emitted)
    for logits, dlogits, pair in zip(trunk[chunks:], drafts[chunks:],
                                     emitted):
        n = 1 + (pair[1] >= 0)
        steps.append((pos, logits[-4:-2], dlogits[-4:-2], n))
        pos += n
    return (np.asarray(prompt + req.generated), steps,
            (trunk[chunks - 1][0], drafts[chunks - 1][0]), eng)


def _reference(weights, ids, **changed):
    """(trunk logits [S, V], draft logits [S − 1, V]) of the plain
    reference over ``ids``."""
    cfg = dict(TINY, **changed)
    ids = jnp.asarray(ids)[None]
    u = FAMILY.hidden(weights, cfg, ids)
    return (np.asarray(FAMILY.forward(weights, cfg, ids)[0]),
            np.asarray(FAMILY.draft_logits(weights, cfg, u[:, :-1],
                                           ids[:, 1:])[0]))


def test_the_models_forward_passes_are_the_references(weights):
    model = FAMILY.build(TINY)
    ids = np.random.RandomState(5).randint(0, 256, size=(1, 37))
    want, want_draft = _reference(weights, ids[0])
    got = np.asarray(model.forward(weights, jnp.asarray(ids))[0])
    got_draft = np.asarray(model.draft_forward(weights, jnp.asarray(ids))[0])
    assert np.abs(got - want).max() < 2e-4
    assert np.abs(got_draft - want_draft).max() < 2e-4


def test_served_logits_and_drafts_are_the_references(served, weights):
    """Chunked prefill, then drafting decode through both pools: each
    step's FIRST row is the trunk's logits at the newest token's position
    and the drafting layer's behind it, to float32 rounding (both sides
    float32; the program sums in another order)."""
    ids, steps, (first, first_draft), eng = served
    assert len(ids) == PROMPT + NEW
    want, want_draft = _reference(weights, ids)
    assert np.abs(first - want[PROMPT - 1]).max() < 2e-4
    assert np.abs(first_draft - want_draft[PROMPT - 1]).max() < 2e-4
    seen = 0
    for pos, logits, drafts, n in steps:
        if pos >= len(ids) - 1:
            break       # past the budget: surplus
        assert np.abs(logits[0] - want[pos]).max() < 2e-4, pos
        assert np.abs(drafts[0] - want_draft[pos]).max() < 2e-4, pos
        seen += 1
    assert seen >= NEW // 2
    assert eng.last_attn_path == "reference"          # the CPU's path
    assert sorted(eng.pool) == ["full", "window"]
    # the drafting layer's keys: one more layer of the full kind's pool
    assert eng.pool["full"]["k"].shape[0] == 3
    assert eng.last_layers_by_part == {"full": 2, "window": 3, "mtp": 1}


def test_after_a_rejection_at_a_pages_and_a_rings_edge_the_reads_are_sound(
        served, weights):
    """A rejected draft's key lies at ``p + 1`` in every layer; the next
    step's first row overwrites it.  The steps whose draft row began a
    page, and those whose page began the ring again (4 pages here: the
    window's 2 and a chunk's 2), read what the reference reads."""
    ids, steps, _, eng = served
    want, _ = _reference(weights, ids)
    ring = eng.cache_config.ring_blocks
    assert ring == 4
    at_page = [s for s in steps if (s[0] + 1) % PAGE == 0 and s[3] == 1]
    at_ring = [s for s in at_page if ((s[0] + 1) // PAGE) % ring == 0]
    assert len(at_page) >= 4 and len(at_ring) >= 2
    for pos, logits, _, _ in at_page:
        if pos + 1 < len(ids) - 1:
            # the step AFTER the rejection: its first row is at pos + 1
            after = next(s for s in steps if s[0] == pos + 1)
            assert np.abs(after[1][0] - want[pos + 1]).max() < 2e-4


WRONG = {
    "no_qk_norm": dict(control_no_qk_norm=True),
    "rotary_in_the_full_layers": dict(control_rotary_in_full=True),
    "scale_1_for_2.5": dict(routed_scaling_factor=1.0),
    "window_off_by_one": dict(sliding_window=9),
}


@pytest.mark.parametrize("name", sorted(WRONG))
def test_a_reference_with_one_thing_changed_is_told_apart(served, weights,
                                                          name):
    ids, steps, _, _ = served
    wrong, _ = _reference(weights, ids, **WRONG[name])
    worst = max(np.abs(logits[0] - wrong[pos]).max()
                for pos, logits, _, _ in steps if pos < len(ids) - 1)
    assert worst > 5e-3, name


def test_the_halves_under_m_swapped_are_told_apart(served, weights):
    ids, steps, _, _ = served
    H = TINY["hidden_size"]
    proj = weights["mtp"]["proj"]
    swapped = dict(weights, mtp=dict(
        weights["mtp"], proj=jnp.concatenate([proj[H:], proj[:H]])))
    _, wrong = _reference(swapped, ids)
    worst = max(np.abs(drafts[0] - wrong[pos]).max()
                for pos, _, drafts, _ in steps if pos < len(ids) - 1)
    assert worst > 5e-3


def test_an_engine_that_accepted_every_draft_would_leave_the_stream(served):
    """The second row is computed on the DRAFT: where the draft is not the
    trunk's own token its sample is no token of the stream."""
    ids, steps, _, _ = served
    off = sum(int(np.argmax(logits[1])) != ids[pos + 2]
              for pos, logits, _, n in steps if pos + 2 < len(ids))
    assert off > len(steps) // 2


# -- the stream is the trunk's -----------------------------------------------

PROMPTS = [np.random.RandomState(11 + i).randint(0, 256, size=n).tolist()
           for i, n in enumerate((5, 19, 33))]


@pytest.mark.parametrize("burst", [1, 8])
def test_the_drafted_stream_is_the_undrafted_one(weights, burst):
    """Three requests through two slots (the third takes a freed seat),
    drafted in bursts of 1 and of 8, against the undrafted engine's (whose
    greedy stream does not depend on its burst)."""
    drafted = _engine(TINY, weights, burst=burst).generate(PROMPTS, 14)
    assert drafted == _engine(UNDRAFTED, weights).generate(PROMPTS, 14)
    assert all(len(o) == 14 for o in drafted)


def test_a_request_cancelled_with_a_call_in_flight_takes_nothing_with_it(
        weights):
    out = {}
    for name, cfg in (("drafted", TINY), ("undrafted", UNDRAFTED)):
        eng = _engine(cfg, weights)     # two slots: the third waits
        reqs = [eng.put(p, 20) for p in PROMPTS]
        rounds = 0
        while eng.scheduler.has_work:
            eng.step_ahead()
            rounds += 1
            if rounds == 6:     # two decoding, a call in flight
                assert reqs[1].state is RequestState.RUNNING
                eng.scheduler.cancel(reqs[1])
        eng.settle()
        out[name] = [r.generated for r in (reqs[0], reqs[2])]
        assert len(reqs[1].generated) < 20
    assert out["drafted"] == out["undrafted"]
    assert all(len(o) == 20 for o in out["drafted"])


def test_at_a_temperature_no_draft_is_accepted(weights):
    """Above 0 the engine samples one token a step and accepts no draft:
    the distribution is one-token sampling's (said in the program's
    docstring), here on weights whose every draft WOULD be accepted under
    greedy."""
    params, _, _ = _echo_weights(weights)
    eng = _engine(TINY, params, slots=2, burst=4)
    seen = []
    real = eng.scheduler.decode_burst_done
    eng.scheduler.decode_burst_done = lambda r, t, e=None: (
        seen.append(np.asarray(t)), real(r, t, e))[1]
    try:
        req = eng.put(PROMPTS[0], 10)
        while eng.scheduler.has_work:
            eng.step(temperature=0.7)
    finally:
        del eng.scheduler.decode_burst_done
    assert len(req.generated) == 10
    assert all((t[:, :, 1] < 0).all() for t in seen)


# -- the accepting branch, on weights made for it ----------------------------

def _zeroed(tree, names):
    if isinstance(tree, dict):
        return {k: (jnp.zeros_like(v) if k in names else _zeroed(v, names))
                for k, v in tree.items()}
    if isinstance(tree, list):
        return [_zeroed(v, names) for v in tree]
    return tree


def _echo_weights(weights, bend=None):
    """Weights on which the next token depends on the newest alone: every
    trunk layer's output projections are zero (the stream is the
    embedding), and the prediction layer passes the following token's
    embedding through (``M = [I; 0]``, its own layer's outputs zero), so
    its draft is the trunk's next token.  ``bend``: a weight ``[H]`` of the
    prediction layer's last norm that is not 1, which makes SOME tokens'
    drafts wrong.  Returns (weights, the trunk's next-token table ``[V]``,
    the drafts' table ``[V]``: the draft of the token after ``t``)."""
    H = TINY["hidden_size"]
    params = _zeroed(weights, ("wo", "w_down"))
    mtp = dict(params["mtp"], proj=jnp.concatenate(
        [jnp.eye(H), jnp.zeros((H, H))]))
    if bend is not None:
        mtp["norm"] = bend
    params = dict(params, mtp=mtp)
    norm = lambda x: x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + TINY["rms_norm_eps"])
    e = norm(params["embed"])
    nxt = jnp.argmax(e @ params["lm_head"], axis=-1)
    draft = jnp.argmax((norm(e) * mtp["norm"]) @ params["lm_head"], axis=-1)
    return params, np.asarray(nxt), np.asarray(draft)


def _serve_counting(cfg, params, prompt, new, eos=None):
    """→ (generated, per step the tokens slot 0 emitted, the request's
    pages, the engine)."""
    eng = _engine(cfg, params, slots=2, burst=4)
    counts = []
    real = eng.scheduler.decode_burst_done

    def spy(requests, tokens, e=None):
        tokens = np.asarray(tokens)
        if requests:    # a call whose decode rows were all dead: nothing
            counts.extend((1 + (tokens[:, 0, 1] >= 0)).tolist()
                          if tokens.ndim == 3 else [1] * len(tokens))
        return real(requests, tokens, e)

    eng.scheduler.decode_burst_done = spy
    try:
        req = eng.put(prompt, new)
        eng.step()
        blocks, ring = list(req.blocks), req.ring
        while eng.scheduler.has_work:
            eng.step(eos_token_id=eos)
    finally:
        del eng.scheduler.decode_burst_done
    return req.generated, counts, (blocks, ring), eng


def _keys(eng, kind, layer, blocks, ring, n):
    """The first ``n`` positions' K rows of ``layer`` of a kind's pool, as
    a request with these pages and this ring holds them (a ring: only what
    its last pages still hold is compared, by the caller)."""
    layout = eng.layouts[kind]
    pages = -(-n // PAGE)
    if layout.kind.ring:
        table = np.asarray(layout._in_ring(
            jnp.asarray([1 + ring * eng.cache_config.ring_blocks]),
            jnp.arange(pages)[None]))
    else:
        table = np.asarray(blocks[:pages])[None]
    k, _ = layout.gather_pages(eng.pool[kind], layer, jnp.asarray(table))
    return np.asarray(k[0, :n])


@pytest.mark.parametrize("new", [12, 13])
def test_every_draft_accepted_gives_two_tokens_a_step(weights, new):
    """Budgets that end on a step's FIRST token (12: the prompt's first
    token, five steps of two and one whose second token is surplus) and on
    its second (13)."""
    params, nxt, draft = _echo_weights(weights)
    assert (nxt == draft).all()
    prompt = PROMPTS[1]
    got, counts, (blocks, ring), eng = _serve_counting(TINY, params, prompt,
                                                       new)
    want, plain_counts, (blocks0, ring0), eng0 = _serve_counting(
        UNDRAFTED, params, prompt, new)
    assert got == want and len(got) == new
    stream = [nxt[prompt[-1]]]
    for _ in range(new - 1):
        stream.append(nxt[stream[-1]])
    assert got == stream
    # two tokens a step while the budget lasts: half the steps
    used = -(-(new - 1) // 2)
    assert counts[:used] == [2] * used
    assert sum(plain_counts[:new - 1]) == new - 1
    if new == 12:
        return
    # lengths and keys: what one-token serving holds, in every trunk layer
    # (the last token is never run) and in the drafting layer's
    n = len(prompt) + new - 1
    for layer in range(2):
        np.testing.assert_allclose(
            _keys(eng, "full", layer, blocks, ring, n),
            _keys(eng0, "full", layer, blocks0, ring0, n), atol=1e-6)
    model = FAMILY.build(TINY)
    ids = jnp.asarray(prompt + got)
    _, k, _ = jax.jit(lambda w: model.qkv(
        w["mtp"]["layer"],
        model.draft_in(w, model.trunk(w, ids)[:-1], ids[1:]),
        jnp.arange(len(ids) - 1), "full"))(params)
    np.testing.assert_allclose(_keys(eng, "full", 2, blocks, ring, n - 1),
                               np.asarray(k)[:n - 1], atol=1e-5)
    # the window layers' rings: the last window's keys
    held = eng.cache_config.ring_blocks * PAGE
    lo = max(n - TINY["sliding_window"], 0)
    for layer in range(3):
        a = _keys(eng, "window", layer, blocks, ring, n)
        b = _keys(eng0, "window", layer, blocks0, ring0, n)
        assert n - lo <= held
        np.testing.assert_allclose(a[lo:], b[lo:], atol=1e-6)


def test_an_eos_as_a_steps_second_token_ends_the_request_there(weights):
    params, nxt, _ = _echo_weights(weights)
    prompt = PROMPTS[1]
    stream = [nxt[prompt[-1]]]
    for _ in range(20):
        stream.append(nxt[stream[-1]])
    # the first token, then steps of two: stream[2] is a step's second
    eos = int(stream[2])
    first = stream.index(eos)
    got, counts, _, _ = _serve_counting(TINY, params, prompt, 20, eos=eos)
    want, _, _, _ = _serve_counting(UNDRAFTED, params, prompt, 20, eos=eos)
    assert got == want == [int(t) for t in stream[:first + 1]]


def test_tokens_that_break_the_draft_give_one_token_steps_among_twos(weights):
    """A prediction layer whose last norm is bent drafts some tokens
    wrongly: a step after such a token emits one token, the others two,
    and the stream is still the trunk's."""
    bend = 1.0 + 2.0 * jax.random.uniform(jax.random.PRNGKey(2),
                                          (TINY["hidden_size"],))
    params, nxt, draft = _echo_weights(weights, bend=bend)
    breaks = nxt != draft
    assert 0.05 < breaks.mean() < 0.95
    # a prompt whose stream meets both kinds of token
    for seed in range(64):
        prompt = np.random.RandomState(seed).randint(0, 256, size=9).tolist()
        stream = [int(nxt[prompt[-1]])]
        for _ in range(30):
            stream.append(int(nxt[stream[-1]]))
        # the draft of stream[i + 1] comes from stream[i]
        broken = [bool(breaks[t]) for t in stream]
        if 3 < sum(broken[:20]) < 17:
            break
    got, counts, _, _ = _serve_counting(TINY, params, prompt, 24)
    want, _, _, _ = _serve_counting(UNDRAFTED, params, prompt, 24)
    assert got == want == stream[:24]
    # the engine's steps against the tables: a step at token i emits
    # stream[i + 1], and stream[i + 2] too iff the draft made from
    # stream[i] (the token before) holds
    i, expected = 0, []
    while i < 23:
        n = 1 if breaks[stream[i]] else 2
        expected.append(n)
        i += n
    assert counts[:len(expected) - 1] == expected[:-1]
    assert 1 in expected and 2 in expected


# -- the share ----------------------------------------------------------------

def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(weights):
    """Four shares of 8 of the router's 32 experts, the shared expert
    counted once: the uncut layer."""
    from deepspeed_tpu.models import ExaoneMoeConfig, ExaoneMoeModel

    base = FAMILY.build(TINY).config
    import dataclasses

    whole = ExaoneMoeModel(dataclasses.replace(base, held_experts=None))
    params = whole.init_params(jax.random.PRNGKey(3))
    lp = params["mtp"]["layer"]         # a single sparse layer
    h = jax.random.normal(jax.random.PRNGKey(4), (12, TINY["hidden_size"]))
    want = whole.ffn(lp, h)
    shared = whole._swiglu(lp["shared"], h)
    total = shared
    for rank in range(4):
        share = ExaoneMoeModel(dataclasses.replace(
            base, held_experts=(8 * rank, 8)))
        cut = dict(lp, moe={n: (w[:, 8 * rank:8 * rank + 8]
                                if n in ("w_gate", "w_up", "w_down") else w)
                            for n, w in lp["moe"].items()})
        total = total + share.ffn(cut, h) - shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5)
    assert isinstance(whole.config, ExaoneMoeConfig)


# -- the ring ------------------------------------------------------------------

def test_a_ring_is_sized_for_a_two_row_step():
    """A window of 8 in pages of 4 under chunks of ONE page: two pages for
    the window and one for the chunk hold a one-row step's keys; the two
    rows of a drafting step reach a key further and may begin a fourth.
    The cell's own ring (window 128, pages of 16, chunks of 128) has 16
    either way."""
    kinds = lambda window: [type("K", (), {"ring": True,
                                           "window": window})()]
    ring = lambda rows, chunk, page, window: KVCacheConfig(
        num_blocks=96, block_size=page).with_rings(
            kinds(window), 2, chunk, row_tokens=rows).ring_blocks
    assert (ring(1, PAGE, PAGE, 8), ring(2, PAGE, PAGE, 8)) == (3, 4)
    assert (ring(1, CHUNK, PAGE, 8), ring(2, CHUNK, PAGE, 8)) == (4, 4)
    assert (ring(1, 128, 16, 128), ring(2, 128, 16, 128)) == (16, 16)


@pytest.mark.slow
def test_a_ring_of_single_page_chunks_serves_the_undrafted_stream(weights):
    """The same rule through the engine (two engines of their own shapes:
    not tier 1)."""
    rings = {}
    for name, cfg in (("drafted", TINY), ("undrafted", UNDRAFTED)):
        eng = build_engine_v2(
            FAMILY.build(cfg), weights,
            cache_config=KVCacheConfig(num_blocks=96, block_size=PAGE,
                                       max_seq_len=128),
            max_batch_slots=2, prefill_chunk=PAGE, prefill_batch=1)
        rings[name] = eng.cache_config.ring_blocks
        out = eng.generate([PROMPTS[2]], 30)
        rings[name + "/tokens"] = out
    assert (rings["drafted"], rings["undrafted"]) == (4, 3)
    assert rings["drafted/tokens"] == rings["undrafted/tokens"]
