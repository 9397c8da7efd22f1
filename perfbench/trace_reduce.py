"""From a ``jax.profiler`` trace to numbers: the one reduction every PR uses.

What a TPU trace looks like (looked at by hand, PR 23, "TPU v5 lite",
jax 0.9.0): one plane ``/device:TPU:<n>`` per chip with the lines
``XLA Modules`` (one event per program execution, named
``jit_<fn>(<hash>)``), ``XLA Ops`` (one event per executed HLO instruction,
named by the instruction's whole text, ``%name = shape opcode(...)``; a
``while`` or ``call`` event CONTAINS the events of its body) and
``Async XLA Ops`` (``*-start`` to ``*-done`` of asynchronous copies and
collectives); one plane ``/host:CPU`` with a line per host thread, where
``jax.profiler.TraceAnnotation`` spans land on the thread that made them.
All planes share one clock, nanoseconds from the start of the session.

Nothing here imports the program under test.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
#: instructions that only contain other instructions' events
CONTAINERS = {"while", "conditional", "call"}
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute")
_HEAD = re.compile(r"%?(?P<name>[^\s=]+) = (?P<rest>.*)", re.S)
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"([a-z]+[0-9]*\[[0-9,]*\])")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')

Interval = Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class Event:
    start_ns: float
    dur_ns: float
    name: str

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class DeviceTrace:
    ops: List[Event]
    async_ops: List[Event]
    modules: List[Event]


@dataclasses.dataclass
class Trace:
    """One traced window: per chip its events, and the host's spans by
    thread.  ``t0_ns``/``t1_ns`` bound the window every share is over."""
    devices: Dict[int, DeviceTrace]
    host: Dict[str, List[Event]]
    t0_ns: float
    t1_ns: float

    @property
    def window_s(self) -> float:
        return (self.t1_ns - self.t0_ns) * 1e-9


# ---------------------------------------------------------------------------
# reading
# ---------------------------------------------------------------------------


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def from_profile_data(profile, window_span: Optional[str] = None) -> Trace:
    """``jax.profiler.ProfileData`` → :class:`Trace`.  With ``window_span``
    the window is the (first) host span of that name and every event is
    clipped to it; without, the window is the extent of the device
    events."""
    devices: Dict[int, DeviceTrace] = {}
    host: Dict[str, List[Event]] = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: [Event(e.start_ns, e.duration_ns, e.name)
                               for e in ln.events] for ln in plane.lines}
            devices[int(m.group(1))] = DeviceTrace(
                ops=lines.get(OPS_LINE, []),
                async_ops=lines.get(ASYNC_LINE, []),
                modules=lines.get(MODULES_LINE, []))
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                host[ln.name] = [Event(e.start_ns, e.duration_ns, e.name)
                                 for e in ln.events]
    window = None
    if window_span is not None:
        window = next((e for evs in host.values() for e in evs
                       if e.name == window_span), None)
        if window is None:
            raise ValueError(f"no host span named {window_span!r} in trace")
        t0, t1 = window.start_ns, window.end_ns
    else:
        every = [e for d in devices.values() for e in d.ops + d.modules]
        if not every:
            raise ValueError("the trace holds no device event")
        t0 = min(e.start_ns for e in every)
        t1 = max(e.end_ns for e in every)
    clip = lambda evs: [c for c in (_clip(e, t0, t1) for e in evs) if c]
    return Trace(
        devices={i: DeviceTrace(clip(d.ops), clip(d.async_ops),
                                clip(d.modules))
                 for i, d in devices.items()},
        host={k: clip(v) for k, v in host.items()}, t0_ns=t0, t1_ns=t1)


def load(trace_dir: str, window_span: Optional[str] = None) -> Trace:
    import jax

    profile = jax.profiler.ProfileData.from_file(find_xplane(trace_dir))
    return from_profile_data(profile, window_span)


def _clip(e: Event, t0: float, t1: float) -> Optional[Event]:
    a, b = max(e.start_ns, t0), min(e.end_ns, t1)
    if b < a or e.end_ns < t0 or e.start_ns > t1:
        return None
    return Event(a, b - a, e.name)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The part of union ``a`` that union ``b`` does not cover."""
    out: List[Interval] = []
    b = list(b)
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def spans(events: Iterable[Event]) -> List[Interval]:
    return [(e.start_ns, e.end_ns) for e in events]


# ---------------------------------------------------------------------------
# HLO instruction text
# ---------------------------------------------------------------------------


def opcode(text: str) -> str:
    """``%x = bf16[8]{0} fusion(...)`` → ``fusion``; a custom call gives
    its target (``tpu_custom_call`` is a Mosaic, i.e. Pallas, kernel)."""
    head = _HEAD.match(text)
    if not head:
        return ""
    m = _OPCODE.search(" " + head.group("rest"))
    code = m.group(1) if m else ""
    if code == "custom-call":
        target = _TARGET.search(text)
        return target.group(1) if target else code
    return code


def label(text: str) -> str:
    """A short stable name for the breakdown: instruction, result shape,
    opcode: ``closed_call.12:bf16[8,32,128]:tpu_custom_call``."""
    head = _HEAD.match(text)
    if not head:
        return text[:80]
    shape = _SHAPE.search(head.group("rest"))
    return ":".join([head.group("name"), shape.group(1) if shape else "",
                     opcode(text)])


def is_collective(text: str) -> bool:
    head = _HEAD.match(text)
    return bool(COLLECTIVE.search(
        (head.group("name") + " " + opcode(text)) if head else text))


def leaves(ops: Sequence[Event]) -> List[Event]:
    """Executed instructions that do work themselves: not the ``while`` /
    ``call`` / ``conditional`` events that only span their bodies."""
    return [e for e in ops if opcode(e.name) not in CONTAINERS]


# ---------------------------------------------------------------------------
# the numbers
# ---------------------------------------------------------------------------


def busy_intervals(dev: DeviceTrace) -> List[Interval]:
    """When an operation ran on this chip: the union of its instruction
    events (containers included: their bodies fill them) and of the
    collectives in flight."""
    return union(spans(dev.ops) + spans(
        e for e in dev.async_ops if is_collective(e.name)))


def busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    if not trace.devices:
        return 0.0
    return sum(total(busy_intervals(d)) for d in trace.devices.values()
               ) * 1e-9 / len(trace.devices)


def idle_share(trace: Trace) -> float:
    return 1.0 - busy_s(trace) / trace.window_s


def matching_s(trace: Trace, pattern: str) -> float:
    """Seconds (averaged over the chips) in leaf instructions whose text
    matches ``pattern``; overlapping events count once."""
    rx = re.compile(pattern)
    if not trace.devices:
        return 0.0
    return sum(total(union(spans(e for e in leaves(d.ops)
                                 if rx.search(e.name))))
               for d in trace.devices.values()) * 1e-9 / len(trace.devices)


def collective_s(trace: Trace) -> Tuple[float, float]:
    """(seconds a collective was running or in flight, seconds of that
    with no other instruction running on the same chip), averaged over
    the chips."""
    if not trace.devices:
        return 0.0, 0.0
    all_s = exposed_s = 0.0
    for d in trace.devices.values():
        ops = leaves(d.ops)
        coll = union(spans(e for e in ops + d.async_ops
                           if is_collective(e.name)))
        compute = union(spans(e for e in ops if not is_collective(e.name)))
        all_s += total(coll)
        exposed_s += total(subtract(coll, compute))
    n = len(trace.devices)
    return all_s * 1e-9 / n, exposed_s * 1e-9 / n


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The ``n`` instructions that took most device time on the first
    chip, by label, leaf events only: ``[[label, seconds], ...]``."""
    if not trace.devices:
        return []
    sums: Dict[str, float] = {}
    for e in leaves(trace.devices[min(trace.devices)].ops):
        key = label(e.name)
        sums[key] = sums.get(key, 0.0) + e.dur_ns * 1e-9
    return [[k, v] for k, v in
            sorted(sums.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, n: int = 10, marker: str = "bench/"
              ) -> List[List]:
    """The ``n`` longest gaps in which nothing ran on the first chip, each
    named by what the host was doing at its middle: the outermost and the
    innermost span covering that moment on the thread that carries the
    benchmark's own ``marker`` spans; ``unattributed`` if none does."""
    if not trace.devices:
        return []
    busy = busy_intervals(trace.devices[min(trace.devices)])
    gaps = subtract([(trace.t0_ns, trace.t1_ns)], busy)
    thread = max(trace.host.values(), default=[],
                 key=lambda evs: sum(e.name.startswith(marker)
                                     for e in evs))
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (a + b) / 2
        # a span over (nearly) the whole window says nothing about a gap
        cover = sorted((e for e in thread
                        if e.start_ns <= mid <= e.end_ns
                        and 0 < e.dur_ns < 0.98 * (trace.t1_ns - trace.t0_ns)),
                       key=lambda e: -e.dur_ns)
        if not cover:
            what = "unattributed"
        elif len(cover) == 1:
            what = cover[0].name
        else:
            what = f"{cover[0].name}>{cover[-1].name}"
        out.append([what[:120], (b - a) * 1e-9])
    return out
