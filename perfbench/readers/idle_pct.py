"""Share of the traced window in which no operation ran on the device
(mean over the chips used), in percent."""

from perfbench import trace_reduce


def read(obs, args):
    tr = obs.get("trace")
    return None if tr is None else 100.0 * trace_reduce.idle_share(tr)
