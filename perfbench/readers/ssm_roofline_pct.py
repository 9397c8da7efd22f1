"""A state-space mixer's recurrence against its roofline: the least time
the chip could take for the work the program's counter reports
(``ssm_shapes``: ``"bound": "update"``, the bytes of the decode steps'
state updates against the HBM peak; ``"chunk"``, the operations of the
prefill blocks against the MXU peak), over the device time of the
instructions that do it (``pattern``), in the traced stretch.

A counter grows over the whole window and the trace covers its last
seconds, so the two are joined by calls, as ``moe_roofline_pct`` joins
them by steps: the counter's growth over the steps the program's spans
report for the window (``steps_of_span``: a span's name and the argument
that holds its steps), times the steps of the program executions on the
first chip's ``XLA Modules`` line in the stretch (``steps_of_module``:
patterns whose first group captures the steps from the program's name).
``only_steps`` keeps the calls of that many steps on both sides (1: the
calls that carry chunks).  A program without the counter (one with no such
layer), or a trace in which no instruction matches, gives nothing to
read."""

import re

from perfbench import ssm_shapes, trace_reduce


def read(obs, args):
    tr = obs.get("trace")
    counters = obs.get("program_counters") or {}
    work = counters.get(args["counter"])
    if tr is None or not tr.devices or not obs.get("peaks") or not work:
        return None
    only = args.get("only_steps")
    kept = lambda steps: only is None or steps == only
    op_s = trace_reduce.matching_s(tr, args["pattern"])
    of_span = args["steps_of_span"]
    window = [s["args"].get(of_span[s["name"]], 0)
              for s in obs.get("program_spans", ()) if s["name"] in of_span]
    steps_window = sum(n for n in window if kept(n))
    patterns = [re.compile(p) for p in args["steps_of_module"]]
    steps_traced = 0
    for e in tr.devices[min(tr.devices)].modules:
        for rx in patterns:
            m = rx.search(e.name)
            if m and kept(int(m.group(1))):
                steps_traced += int(m.group(1))
    if op_s <= 0 or not steps_window or not steps_traced:
        return None
    cfg, peaks = obs["config"], obs["peaks"]
    traced = work * steps_traced / steps_window
    if args["bound"] == "update":
        least = ssm_shapes.update_seconds(traced, cfg, peaks)
    else:
        least = ssm_shapes.chunk_seconds(traced, cfg, peaks, args["block"])
    return 100.0 * least / op_s
