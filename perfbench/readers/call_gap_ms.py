"""The gap between two program calls on the device's clock, and what the
host was doing in it.

On the first chip's ``XLA Modules`` line, between the end of one execution
whose name matches ``pattern`` and the start of the next one inside the
traced stretch: the time in which no instruction ran (another program's
instructions in between, a refill of sampling keys, are not idle time).
With ``q`` a percentile over those gaps, without it their mean, in ms.

With ``spans`` only a part of each gap is counted, by what the host was in:
the gap's overlap with the profiler's copies of the program's spans on the
thread that carries the spans named ``thread``.  ``under`` is a list of
span names (a moment under two of them counts once), ``less`` takes the
moments under those names away again (a span nested in one of ``under``),
``outside`` counts the moments under NONE of its names.  Parts whose spans
do not overlap sum, with what no part names, to the whole gap's mean.

Nothing joins a gap to a span by identifier: the profiler's copies carry
names and times only.  Where no two executions match, or a part is asked
for and no thread carries ``thread`` spans (a program that does not
annotate, a CPU rehearsal), there is nothing to read."""

import re

from perfbench import arith, trace_reduce


def idle_between_calls(tr, pattern):
    """For each pair of neighbouring matching executions on the first
    chip: the intervals between them in which no instruction ran."""
    dev = tr.devices[min(tr.devices)]
    rx = re.compile(pattern)
    calls = sorted((e for e in dev.modules if rx.search(e.name)),
                   key=lambda e: e.start_ns)
    busy = trace_reduce.busy_intervals(dev)
    return [trace_reduce.subtract([(a.end_ns, b.start_ns)], busy)
            for a, b in zip(calls, calls[1:]) if b.start_ns > a.end_ns]


def covered(tr, spec):
    """The intervals of the stretch that ``spec`` selects on its thread,
    or None where no thread carries its ``thread`` spans."""
    thread = max(tr.host.values(), default=[],
                 key=lambda evs: sum(e.name == spec["thread"] for e in evs))
    if not any(e.name == spec["thread"] for e in thread):
        return None
    named = lambda names: trace_reduce.union(trace_reduce.spans(
        e for e in thread if e.name in names))
    if "outside" in spec:
        return trace_reduce.subtract([(tr.t0_ns, tr.t1_ns)],
                                     named(spec["outside"]))
    return trace_reduce.subtract(named(spec["under"]),
                                 named(spec.get("less", ())))


def read(obs, args):
    tr = obs.get("trace")
    if tr is None or not tr.devices:
        return None
    gaps = idle_between_calls(tr, args["pattern"])
    if not gaps:
        return None
    if "spans" in args:
        part = covered(tr, args["spans"])
        if part is None:
            return None
        # idle and under the part = idle less the part of it outside
        outside = trace_reduce.subtract([(tr.t0_ns, tr.t1_ns)], part)
        gaps = [trace_reduce.subtract(idle, outside) for idle in gaps]
    values = [trace_reduce.total(idle) * 1e-6 for idle in gaps]
    if "q" in args:
        return arith.percentile(values, args["q"])
    return sum(values) / len(values)
