"""Closed-loop request traffic from a file of parameters.

Every seed offers the same work.  Lengths are a fixed quantile grid of
the file's distributions (the i-th of n lengths is the (i + 1/2)/n
quantile, clipped to the file's limits), so the multiset of prompt and
answer lengths does not depend on the seed.  ``order_seed`` in the file
fixes which client sends which lengths in which order (with the order
drawn from ``--seed``, one seed repeated to 0.2% on the chip and two seeds
differed by 2.8%: the seed was changing the work; PERF.md, PR 23), and
``--seed`` makes the token ids.  Each client sends its next request when
its last one finished.

Every client's FIRST answer is cut to a share (k + 1/2)/clients of its
length, k dealt to the clients by ``order_seed``: a request already under
way when the stream starts, as in the stationary state, where what is left
of a running answer is uniform over its length.  With whole first answers
every slot starts one at the same moment, nothing finishes for the
shortest answer's length (11 s on the chip at 128 tokens) and no prefill
runs until then, so a window's first seconds were a sixth faster than its
steady state (340 against 292 tokens/s; PERF.md, PR 23).

Parameters (``traffic/<name>.json``):
  klass            latency class handed to the front-end
  prompt_tokens    {"median", "sigma", "min", "max"}   log-normal
  new_tokens       the same, for the answer
  clients, requests_per_client, open_when_live_streams, order_seed
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


@dataclasses.dataclass(eq=False)
class Request:
    client: int             # who sends it
    prompt: np.ndarray      # int32 token ids
    new_tokens: int


@dataclasses.dataclass(eq=False)
class Traffic:
    klass: str
    requests: List[Request]          # by client, in the order it sends them
    clients: int
    open_when_live_streams: int      # the window opens at this many live streams
    seed: int
    vocab_size: int

    def again(self, request: Request, round_: int) -> Request:
        """A client that has sent all its requests starts over with the
        same lengths and NEW token ids: the same prompt twice would be
        served from the prefix cache, which is other work (and a prefill
        shape the warm-up never compiled)."""
        rng = np.random.default_rng([self.seed, request.client, round_,
                                     len(request.prompt)])
        return Request(request.client,
                       rng.integers(0, self.vocab_size,
                                    size=len(request.prompt), dtype=np.int32),
                       request.new_tokens)


def quantile_grid(spec: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` whole lengths: the (i + 1/2)/n quantiles of the log-normal
    ``spec``, clipped to ``[min, max]``."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    lengths = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    return np.clip(np.rint(lengths), spec["min"], spec["max"]).astype(int)


def make(params: Dict[str, Any], seed: int, seconds: float,
         vocab_size: int) -> Traffic:
    rng = np.random.default_rng(int(seed))
    order = np.random.default_rng(int(params["order_seed"]))
    clients = int(params["clients"])
    n = clients * int(params["requests_per_client"])
    prompts = order.permutation(quantile_grid(params["prompt_tokens"], n))
    answers = order.permutation(quantile_grid(params["new_tokens"], n))
    # the first round is under way already: a uniform share of each answer
    share = (order.permutation(clients) + 0.5) / clients
    answers[:clients] = np.maximum(
        2, np.rint(answers[:clients] * share)).astype(int)
    requests = [Request(i % clients,
                        rng.integers(0, vocab_size, size=int(p),
                                     dtype=np.int32), int(a))
                for i, (p, a) in enumerate(zip(prompts, answers))]
    return Traffic(params["klass"], requests, clients=clients,
                   open_when_live_streams=int(
                       params["open_when_live_streams"]),
                   seed=int(seed), vocab_size=int(vocab_size))
