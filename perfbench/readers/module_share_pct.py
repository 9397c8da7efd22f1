"""Device time of the programs whose name on the first chip's ``XLA
Modules`` line matches ``pattern``, as a percentage of the time that chip
was busy in the traced stretch.  None where no program matches: a program
that has no name of its own cannot be told from the others."""

import re

from perfbench import trace_reduce


def read(obs, args):
    tr = obs.get("trace")
    if tr is None or not tr.devices:
        return None
    dev = tr.devices[min(tr.devices)]
    rx = re.compile(args["pattern"])
    matching = trace_reduce.union(trace_reduce.spans(
        e for e in dev.modules if rx.search(e.name)))
    busy = trace_reduce.total(trace_reduce.busy_intervals(dev))
    if not matching or busy <= 0:
        return None
    return 100.0 * trace_reduce.total(matching) / busy
