"""The traffic generators and the loop that plays them: every seed offers
the same work, and a closed loop keeps its clients outstanding."""

import types

import numpy as np
import pytest

from perfbench import manifest

requests = manifest.load_module("generators", "requests")
steady = manifest.load_module("generators", "steady")
serve = manifest.load_module("runners", "serve")

CLOSED = manifest.load_json("traffic", "batch-closed-loop")
SEEDS = (7, 2**31 + 11)      # one past what 32 signed bits hold


def lengths(traffic):
    return ([len(r.prompt) for r in traffic.requests],
            [r.new_tokens for r in traffic.requests])


def test_the_same_seed_gives_the_same_inputs():
    a, b = (requests.make(CLOSED, 2**31 + 11, 30.0, 32000) for _ in range(2))
    assert all(x.client == y.client and np.array_equal(x.prompt, y.prompt)
               and x.new_tokens == y.new_tokens
               for x, y in zip(a.requests, b.requests))


def test_quantile_grid_follows_the_file():
    spec = {"median": 512, "sigma": 1.0, "min": 32, "max": 4096}
    grid = requests.quantile_grid(spec, 101)
    assert grid[50] == 512 and grid.min() >= 32 and grid.max() <= 4096
    assert list(grid) == sorted(grid)
    prompts, answers = lengths(requests.make(CLOSED, 1, 30.0, 32000))
    n = len(prompts)
    assert sorted(prompts) == list(requests.quantile_grid(
        CLOSED["prompt_tokens"], n))


def test_first_round_starts_in_the_steady_state():
    a, b = (requests.make(CLOSED, s, 30.0, 32000) for s in SEEDS)
    assert lengths(a) == lengths(b)              # the seed changes no length
    n = len(a.requests)
    grid = requests.quantile_grid(CLOSED["new_tokens"], n)
    answers = lengths(a)[1]
    # after each client's first request the answers are the grid's own
    assert not set(answers[64:]) - set(grid)
    # a uniform share of each first answer: what is left of a request
    # that was under way when the stream started
    assert [r.client for r in a.requests[:64]] == list(range(64))
    assert min(answers[:64]) >= 2
    assert sum(answers[:64]) == pytest.approx(
        0.5 * (sum(grid) - sum(answers[64:])), rel=0.1)
    assert len(set(answers[:64])) > 48


def test_closed_loop_traffic_and_steady_batch():
    params = manifest.load_json("traffic", "batch-closed-loop")
    a, b = (requests.make(params, s, 51.0, 32000) for s in SEEDS)
    assert a.clients == 64 and a.open_when_live_streams == 32
    # the file fixes who sends which lengths; the seed makes the ids
    assert lengths(a) == lengths(b)
    assert not np.array_equal(a.requests[0].prompt, b.requests[0].prompt)
    c = requests.make(dict(params, order_seed=params["order_seed"] + 1), 7,
                      51.0, 32000)
    assert sorted(lengths(c)[0]) == sorted(lengths(a)[0])
    assert lengths(c)[0] != lengths(a)[0]
    assert {r.client for r in a.requests} == set(range(64))
    s = manifest.load_json("traffic", "train-steady")
    x, y = (steady.make(s, seed, rows=4, seq=64, vocab_size=1000,
                        mlm_label_share=0.15) for seed in SEEDS)
    assert x["input_ids"].shape == (4, 64) and x["input_ids"].max() < 1000
    assert not np.array_equal(x["input_ids"], y["input_ids"])
    labelled = x["labels"] != -100
    assert 0.05 < labelled.mean() < 0.3
    assert np.array_equal(x["labels"][labelled], x["input_ids"][labelled])


# -- the loop, against a front-end made of nothing ---------------------------


class FakeClock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


class FakeSession:
    """Streams a token per pump to each of at most ``slots`` requests; a
    pump takes ``pump_s`` on the fake clock."""

    def __init__(self, clock, slots, pump_s):
        self.clock, self.slots, self.pump_s = clock, slots, pump_s
        self.streams, self.live, self.outstanding = [], [], []

    def submit(self, request, klass):
        s = serve.Stream(request)
        s.submitted = self.clock()
        s.index = len(self.streams)
        self.streams.append(s)
        self.live.append(s)
        return s

    def pump(self):
        self.outstanding.append(len(self.live))
        self.clock.now += self.pump_s
        for s in self.live[:self.slots]:
            s.first = s.first if s.first is not None else self.clock()
            s.tokens.append(1)
            if len(s.tokens) >= s.request.new_tokens:
                s.finished = self.clock()
        self.live = [s for s in self.live if s.finished is None]
        return 1

    def streaming(self):
        return sum(s.first is not None for s in self.live)


def _ctx(clock):
    return types.SimpleNamespace(clock=clock)


def test_closed_loop_keeps_every_client_outstanding():
    params = dict(manifest.load_json("traffic", "batch-closed-loop"),
                  new_tokens={"median": 6, "sigma": 0.3, "min": 3, "max": 12})
    traffic = requests.make(params, 3, 5.0, 1000)
    clock = FakeClock()
    session = FakeSession(clock, slots=32, pump_s=0.05)
    bounds = serve.offer(_ctx(clock), session, traffic, seconds=5.0)
    assert bounds["t_close"] - bounds["t_open"] >= 5.0
    # every pump saw all 64 clients with a request out
    assert set(session.outstanding) == {64}
    # a client that ran out of its 8 requests started over: the same
    # lengths, new token ids (never a prompt the prefix cache has seen)
    assert len(session.streams) > 64 * 8
    prompts = [s.request.prompt.tobytes() for s in session.streams]
    assert len(set(prompts)) == len(prompts)
    first, ninth = [s.request for s in session.streams
                    if s.request.client == 0][0:9:8]
    assert len(first.prompt) == len(ninth.prompt)
    assert first.new_tokens == ninth.new_tokens
