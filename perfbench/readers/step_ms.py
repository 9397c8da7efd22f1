"""A percentile of the fenced step times inside the window, in ms."""

from perfbench import arith


def read(obs, args):
    steps = obs.get("steps")
    if not steps or not steps["seconds"]:
        return None
    return 1e3 * arith.percentile(steps["seconds"], args["q"])
