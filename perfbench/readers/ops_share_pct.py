"""Device time of the instructions whose text matches ``pattern``, as a
percentage of ``of``: the device's ``busy`` time or the traced ``window``.
Where no instruction matches there is nothing to read: a kernel that was
renamed leaves its metric out of the line, which a cell that lists the
metric is refused for, and does not read as a share that fell to 0."""

from perfbench import trace_reduce


def read(obs, args):
    tr = obs.get("trace")
    if tr is None:
        return None
    matching = trace_reduce.matching_s(tr, args["pattern"])
    if matching <= 0:
        return None
    base = trace_reduce.busy_s(tr) if args["of"] == "busy" else tr.window_s
    return 100.0 * matching / base
