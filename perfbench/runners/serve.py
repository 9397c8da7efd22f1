"""Runner for serving cells: ``serving.build_serving_frontend()`` driven
from one thread that plays every client: each sends its next request when
its last one finished, then ``pump()``, read every stream, stamp what
arrived.

``pump()`` holds the front-end's lock across the device call, so a second
thread could not submit any sooner than this loop does.  Only the
program's public surface is used: ``build_serving_frontend``, ``submit``,
``pump``, the handles' ``drain``, the compile tracker's harvest hook and,
in a traced run, the telemetry registry.  The program's tuning arguments
are not passed: its defaults run, and the result line says what they are.
"""

from __future__ import annotations

import dataclasses
import inspect
from typing import Any, Dict, List, Optional

import numpy as np

from perfbench import arith, harness, program


@dataclasses.dataclass(eq=False)
class Stream:
    """One request as its client sees it."""
    request: Any                    # generators' Request
    handle: Any = None
    submitted: Optional[float] = None
    first: Optional[float] = None
    finished: Optional[float] = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    failed: bool = False
    index: int = -1


class Session:
    """A front-end with its engine, and the clients' side of every
    request sent through it."""

    def __init__(self, ctx: harness.Context, trace_program: bool):
        import jax

        from deepspeed_tpu.inference.v2 import KVCacheConfig
        from deepspeed_tpu.serving import (ServingParams,
                                           build_serving_frontend)

        self.ctx = ctx
        cfg, run_ = ctx.config, ctx.config["run"]
        # every program the server compiles, with its planned scratch,
        # which the memory peak needs
        self.harvest = program.PlanHarvest()
        self.tel = None
        if trace_program:
            # the program's own spans and counters, kept in memory
            from deepspeed_tpu import telemetry

            self.tel = telemetry.configure(enabled=True, jsonl=False,
                                           prometheus=False)
        model = ctx.family().build(cfg)
        dtype = model.config.dtype
        # one jitted call from the seed, in the type they are served in
        self.params = jax.jit(lambda key: jax.tree.map(
            lambda x: x.astype(dtype), model.init_params(key)))(
                program.seed_key(ctx.seed))
        cache = KVCacheConfig(block_size=int(run_["kv_block_size"]),
                              num_blocks=int(run_["kv_num_blocks"]),
                              max_seq_len=cfg["max_position_embeddings"])
        self.frontend = build_serving_frontend(
            model, self.params, replicas=1, cache_config=cache,
            max_batch_slots=int(run_["max_batch_slots"]),
            serving_params=ServingParams(
                max_outstanding_tokens=int(run_["max_outstanding_tokens"])))
        # the tuning arguments this runner leaves to the program
        self.defaults = {
            name: p.default for name, p in
            inspect.signature(build_serving_frontend).parameters.items()
            if name in run_.get("program_defaults_not_passed", {})}
        self.streams: List[Stream] = []
        self.live: List[Stream] = []
        #: (time, stream index, tokens that arrived then)
        self.deliveries: List[tuple] = []
        #: one record per pump that did work
        self.pumps: List[Dict[str, float]] = []
        self._seen_spans = 0

    # -- the clients' side ---------------------------------------------------

    def submit(self, request: Any, klass: str) -> Stream:
        s = Stream(request)
        with harness.span("bench/submit"):
            s.handle = self.frontend.submit(
                request.prompt.tolist(), max_new_tokens=request.new_tokens,
                klass=klass)
        s.submitted = self.ctx.clock()
        s.index = len(self.streams)
        self.streams.append(s)
        self.live.append(s)
        return s

    def _decoding_context(self) -> tuple:
        """(rows decoding, their cached tokens each capped at the sliding
        window and summed): what the next decode step reads."""
        window = self.ctx.config.get("sliding_window") or float("inf")
        rows = [min(len(s.request.prompt) + len(s.tokens), window)
                for s in self.live if s.first is not None]
        return len(rows), sum(rows)

    def pump(self) -> int:
        """One serving round, then every client reads its stream."""
        decoding, ctx_tokens = self._decoding_context()
        t0 = self.ctx.clock()
        with harness.span("bench/pump"):
            n = self.frontend.pump()
        now = self.ctx.clock()
        with harness.span("bench/deliver"):
            for s in list(self.live):
                got, done = s.handle.drain()
                if got:
                    if s.first is None:
                        s.first = now
                    s.tokens += got
                    self.deliveries.append((now, s.index, len(got)))
                if done:
                    s.finished = now
                    s.failed = s.handle.status != "done"
                    self.live.remove(s)
        if n:
            record = {"t0": t0, "t1": now, "tokens": n, "decoding": decoding,
                      "context_tokens": ctx_tokens}
            if self.tel is not None:
                # the program's spans since the last pump: its decode steps
                events = self.tel.tracer.events()
                record["decode_steps"] = sum(
                    e["args"].get("burst", 0)
                    for e in events[self._seen_spans:]
                    if e["name"] == "inference/decode_burst")
                self._seen_spans = len(events)
            self.pumps.append(record)
        return n

    def run_until_idle(self) -> None:
        while self.live:
            self.pump()

    def streaming(self) -> int:
        return sum(s.first is not None for s in self.live)

    def close(self) -> None:
        self.frontend.close()


def _logit_gap(ctx: harness.Context, session: Session, s: Stream) -> float:
    """How far under the reference's best logit the served tokens sit, at
    worst: the reference's full forward pass over prompt + answer, teacher
    forced on what the server emitted (prefill, then decoding through the
    paged cache)."""
    import jax
    import jax.numpy as jnp

    ref = ctx.family()
    n = len(s.request.prompt)
    ids = jnp.asarray(np.concatenate([s.request.prompt, s.tokens[:-1]]),
                      jnp.int32)
    logits = jax.jit(lambda w, i: ref.forward(w, ctx.config, i[None])[0])(
        session.params, ids)[n - 1:]
    chosen = logits[jnp.arange(len(s.tokens)), jnp.asarray(s.tokens)]
    return float(jnp.max(jnp.max(logits, axis=1) - chosen))


def warm_up_and_check(ctx: harness.Context, session: Session, traffic: Any
                      ) -> Dict[str, Any]:
    """Compile every shape this traffic uses (each prefill page bucket up
    to the longest prompt, the decode burst of 1 that runs beside a
    prefill and the full burst), and hold what was served against the
    reference.  A check prompt longer than the traffic's longest (one that
    crosses the sliding window) goes last, and the programs compiled for
    it alone are not the window's: their plans are dropped."""
    gen = ctx.generator()
    check = ctx.config["run"]["check"]
    rng = np.random.default_rng(ctx.seed + 1)
    vocab = ctx.config["vocab_size"]
    make = lambda n, new: gen.Request(
        0, rng.integers(0, vocab, size=n, dtype=np.int32), new)
    longest = max(len(r.prompt) for r in traffic.requests)
    inside = [n for n in check["prompt_tokens"] if n <= longest]
    beyond = [n for n in check["prompt_tokens"] if n > longest]
    checked = [session.submit(make(n, check["new_tokens"]), traffic.klass)
               for n in inside]
    while any(s.first is None for s in checked):
        session.pump()
    session.submit(make(longest, 2), traffic.klass)
    session.run_until_idle()
    of_the_window = len(session.harvest.plans)
    checked += [session.submit(make(n, check["new_tokens"]), traffic.klass)
                for n in beyond]
    session.run_until_idle()
    del session.harvest.plans[of_the_window:]
    gaps = [_logit_gap(ctx, session, s) for s in checked]
    return {"logit_gap": max(gaps), "logit_gaps": gaps,
            "tolerance": check["tolerance"], "requests": len(gaps),
            "ok": all(len(s.tokens) == s.request.new_tokens and not s.failed
                      for s in checked) and max(gaps) <= check["tolerance"]}


def offer(ctx: harness.Context, session: Session, traffic: Any,
          seconds: float, on_open=None) -> Dict[str, Any]:
    """Play ``traffic`` against the session; returns the window's bounds.
    Every client sends its next request when its last one finished; the
    window opens when enough streams are live, and everything before that
    is the ramp.  ``on_open(t_open)`` is called once when the window opens
    and may return a tracer, which is then polled before every pump."""
    clock = ctx.clock
    t_stream = clock()
    per_client: Dict[int, List[Any]] = {}
    for r in traffic.requests:
        per_client.setdefault(r.client, []).append(r)
    owner: Dict[int, Stream] = {}
    sent: Dict[int, int] = {}
    t_open = tracer = None
    while True:
        for client, queue in per_client.items():
            last = owner.get(client)
            if last is None or last.finished is not None:
                # a client that has sent all its requests starts over
                n = sent.get(client, 0)
                sent[client] = n + 1
                request = queue[n % len(queue)]
                if n >= len(queue):
                    request = traffic.again(request, n // len(queue))
                owner[client] = session.submit(request, traffic.klass)
        if tracer is not None:
            tracer.poll(clock())
        session.pump()
        now = clock()
        if t_open is None:
            if session.streaming() >= traffic.open_when_live_streams:
                t_open = now
                if on_open is not None:
                    tracer = on_open(t_open)
            elif now - t_stream > 120.0:
                raise SystemExit(
                    f"perfbench: only {session.streaming()} streams live "
                    f"after 120 s of ramp")
        elif now - t_open >= seconds:
            return {"t_open": t_open, "t_close": now, "tracer": tracer}


def run(ctx: harness.Context) -> Dict[str, Any]:
    compiles = harness.CompileCounter()
    session = Session(ctx, trace_program=ctx.trace)
    traffic = ctx.generator().make(ctx.traffic, ctx.seed, ctx.seconds,
                                   ctx.config["vocab_size"])
    check = warm_up_and_check(ctx, session, traffic)
    first = len(session.streams)
    session.deliveries.clear()
    session.pumps.clear()
    compiled_before = compiles.count
    opened: Dict[str, float] = {}

    def on_open(t_open: float):
        # the ramp is set-up the traffic needs: everything before the
        # window opens counts as set-up
        opened["setup_s"] = t_open - ctx.t_start
        opened["compiled"] = compiles.count
        if session.tel is not None:
            session.tel.tracer.reset()
            session._seen_spans = 0
            opened["counters"] = _counters(session.tel)
        return harness.TailTracer(ctx, t_open)

    bounds = offer(ctx, session, traffic, ctx.seconds, on_open)
    tracer = bounds["tracer"]
    trace = tracer.finish()
    t_open, t_close = bounds["t_open"], bounds["t_close"]
    in_use = program.memory_bytes()
    plans = session.harvest.plans
    streams = session.streams[first:]
    inside = [s for s in streams if t_open <= s.submitted <= t_close]
    spans, counters = [], {}
    if session.tel is not None:
        spans = [{"name": e["name"], "dur_s": e["dur"] * 1e-6,
                  "args": e.get("args", {})}
                 for e in session.tel.tracer.events()]
        after = _counters(session.tel)
        counters = {k: after[k] - opened["counters"].get(k, 0.0)
                    for k in after}
    out = {
        "t_open": t_open, "t_close": t_close, "setup_s": opened["setup_s"],
        "attempted": len(inside), "failed": sum(s.failed for s in streams),
        "correct": bool(check["ok"] and not any(s.failed for s in streams)),
        "check": check,
        "compiles_in_window": compiles.count - opened["compiled"],
        "compiled_during_ramp": opened["compiled"] - compiled_before,
        "deliveries": session.deliveries,
        "pumps": [p for p in session.pumps if t_open < p["t1"] <= t_close],
        "program_spans": spans, "program_counters": counters,
        "program_defaults": session.defaults,
        "memory_peak_bytes": program.window_peak_bytes(in_use, plans),
        "memory": {"in_use_in_window": in_use, "programs": len(plans),
                   "largest_program": max(
                       plans, key=lambda p: p["beyond_arguments"]),
                   "process_peak_in_use":
                       program.memory_bytes("peak_bytes_in_use")},
        "trace": trace, "traced": (tracer.t0, tracer.t1),
    }
    out["work"] = {"tokens": arith.delivered_tokens(
        arith.in_window(session.deliveries, t_open, t_close))}
    # one program call a round: an untraced run's count of the window's
    # calls (the program's own counter ``inference/calls`` needs the hub on)
    out["rounds"] = len(out["pumps"])
    session.close()
    return out


def _counters(tel: Any) -> Dict[str, float]:
    """Every counter the program keeps, by name."""
    out = {}
    for metric in tel.registry.metrics().values():
        if getattr(metric, "kind", "") == "counter":
            out[metric.name] = float(metric.value)
    return out
