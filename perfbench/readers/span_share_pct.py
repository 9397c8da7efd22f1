"""Wall time inside the program's spans named ``span``, as a percentage
of the window (the spans are read from the program's own tracer, which
the traced run switches on)."""


def read(obs, args):
    spans = [s for s in obs.get("program_spans", ())
             if s["name"] == args["span"]]
    if not spans:
        return None
    return 100.0 * sum(s["dur_s"] for s in spans) / (
        obs["t_close"] - obs["t_open"])
