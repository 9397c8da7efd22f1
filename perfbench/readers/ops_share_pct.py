"""Device time of the instructions whose text matches ``pattern``, as a
percentage of ``of``: the device's ``busy`` time or the traced ``window``."""

from perfbench import trace_reduce


def read(obs, args):
    tr = obs.get("trace")
    if tr is None:
        return None
    base = trace_reduce.busy_s(tr) if args["of"] == "busy" else tr.window_s
    return 100.0 * trace_reduce.matching_s(tr, args["pattern"]) / base
