"""Share of the traced stretch in which nothing ran on the first chip
WHILE the host was inside a span named ``span`` (the program's spans are
in the profiler's trace, on the device's clock), in percent.  Against the
chip's whole idle share it splits the idle time into what the program's
own round holds and what its caller does between rounds.  None where the
trace has no such host span (a program that does not annotate)."""

from perfbench import trace_reduce


def read(obs, args):
    tr = obs.get("trace")
    if tr is None or not tr.devices:
        return None
    under = trace_reduce.union(trace_reduce.spans(
        e for events in tr.host.values() for e in events
        if e.name == args["span"]))
    if not under:
        return None
    window = [(tr.t0_ns, tr.t1_ns)]
    idle = trace_reduce.subtract(
        window, trace_reduce.busy_intervals(tr.devices[min(tr.devices)]))
    # idle and under the span = idle less the part of it outside the span
    outside = trace_reduce.subtract(window, under)
    inside = trace_reduce.subtract(idle, outside)
    return 100.0 * trace_reduce.total(inside) / (tr.t1_ns - tr.t0_ns)
