"""``counter_per_span`` times ``scale``: a program counter's growth over
the window divided by the number of the program's spans named ``span`` in
it, in the metric's unit (bytes counted, gigabytes printed)."""


def read(obs, args):
    calls = sum(s["name"] == args["span"]
                for s in obs.get("program_spans", ()))
    grown = (obs.get("program_counters") or {}).get(args["counter"])
    if not calls or grown is None:
        return None
    return grown * args["scale"] / calls
