"""A program counter's growth over the window divided by the number of
the program's spans named ``span`` in it: tokens per call."""


def read(obs, args):
    calls = sum(s["name"] == args["span"]
                for s in obs.get("program_spans", ()))
    grown = obs.get("program_counters", {}).get(args["counter"])
    return grown / calls if calls and grown is not None else None
