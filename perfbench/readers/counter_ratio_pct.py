"""One program counter's growth over the window as a percentage of
another's: live rows of computed rows.  Nothing to read where either
counter is missing (a program that does not keep it) or the second did
not grow."""


def read(obs, args):
    counters = obs.get("program_counters", {})
    part, whole = counters.get(args["part"]), counters.get(args["whole"])
    if part is None or not whole:
        return None
    return 100.0 * part / whole
