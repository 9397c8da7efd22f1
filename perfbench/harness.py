"""What every runner shares: the run's context, the compile counter, the
profiler session around the traced part of the window."""

from __future__ import annotations

import contextlib
import dataclasses
import os
import shutil
import time
from typing import Any, Callable, Dict, Optional

from . import manifest, trace_reduce

#: how much of the window's end a ``--trace 1`` run traces: a trace of the
#: whole window would be hundreds of megabytes, and a few seconds hold
#: tens of steps
TRACE_SECONDS = 5.0
#: the host span that bounds the traced part; every share is over it
TRACED_SPAN = "bench/traced"


@dataclasses.dataclass
class Context:
    cell: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    #: process start on ``clock``: ``setup_s`` runs from here to window open
    t_start: float
    #: where a run may write (trace files): inside the checkout
    scratch: str
    clock: Callable[[], float] = time.perf_counter

    @property
    def chips(self) -> int:
        return int(self.cell["chips"])

    def generator(self) -> Any:
        return manifest.load_module("generators", self.traffic["generator"])

    def family(self) -> Any:
        """The configuration's model family: the program's model, a
        trained token's operations, the plain reference."""
        return manifest.load_module("models", self.config["model_type"])


class CompileCounter:
    """Counts what JAX compiles (or loads from its persistent cache): the
    window must see none."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, seconds: float, **_: Any) -> None:
        self.count += event == self._EVENT


class TailTracer:
    """Traces the last ``TRACE_SECONDS`` of the window.  ``poll(now)`` is
    called between steps; ``finish()`` after the window closed returns the
    reduced :class:`trace_reduce.Trace` (None if tracing was off)."""

    def __init__(self, ctx: Context, t_open: float):
        self.on = ctx.trace
        self.dir = os.path.join(ctx.scratch, f"trace_{os.getpid()}")
        self.t_begin = t_open + max(ctx.seconds - TRACE_SECONDS, 0.0)
        self._stack: Optional[contextlib.ExitStack] = None
        self._clock = ctx.clock
        #: the traced stretch on the run's clock (it starts and ends
        #: between two steps of the runner's loop)
        self.t0 = self.t1 = None

    def poll(self, now: float) -> None:
        if not self.on or self._stack is not None or now < self.t_begin:
            return
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # the Python tracer alone
        options.host_tracer_level = 2        # writes ~70k events a second
        options.enable_hlo_proto = False
        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self._stack = contextlib.ExitStack()
        self._stack.enter_context(jax.profiler.TraceAnnotation(TRACED_SPAN))
        self.t0 = self._clock()

    def finish(self) -> Optional[trace_reduce.Trace]:
        if self._stack is None:
            return None
        import jax

        self.t1 = self._clock()
        self._stack.close()
        jax.profiler.stop_trace()
        try:
            trace = trace_reduce.load(self.dir, TRACED_SPAN)
            # no accelerator plane (a CPU rehearsal): no device number
            return trace if trace.devices else None
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def span(name: str):
    """A host span in the profiler's own trace (free when no trace runs):
    the benchmark's marks around its calls into the program."""
    import jax

    return jax.profiler.TraceAnnotation(name)
