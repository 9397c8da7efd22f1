"""Share of the traced window in which a collective ran or was in flight
(``which`` = ``all``), or only the part of that with no other instruction
running on the same chip (``exposed``); mean over the chips, percent."""

from perfbench import trace_reduce


def read(obs, args):
    tr = obs.get("trace")
    if tr is None:
        return None
    every, exposed = trace_reduce.collective_s(tr)
    return 100.0 * (exposed if args["which"] == "exposed" else every
                    ) / tr.window_s
