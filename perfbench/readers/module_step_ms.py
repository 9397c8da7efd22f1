"""A percentile of the device time of one step of the programs whose name
on the first chip's ``XLA Modules`` line matches ``pattern``: each
execution's duration over the number of steps the pattern's first group
captures from its name (``..._n_steps(\\d+)`` on a program named
``..._n_steps8(<hash>)``: eight steps a call; a pattern with no group
divides by one), in ms.  The device's own clock: no host time is in it."""

import re

from perfbench import arith


def read(obs, args):
    tr = obs.get("trace")
    if tr is None or not tr.devices:
        return None
    rx = re.compile(args["pattern"])
    values = []
    for e in tr.devices[min(tr.devices)].modules:
        m = rx.search(e.name)
        if m:
            values.append(e.dur_ns * 1e-6 / (int(m.group(1)) if rx.groups
                                             else 1))
    return arith.percentile(values, args["q"]) if values else None
