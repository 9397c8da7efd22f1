"""The paged decode-attention kernel over a latent cache against its
roofline: the least time the chip could take for the rows it must read and
the products it must make (``latent_attn_shapes.decode_seconds``: the
larger of a byte bound and an operation bound; at the published widths the
two lie within a percent of each other), over the kernel's device time, in
the traced stretch.

The program counts the keys one layer attends over through the kernel,
summed over decoding rows and decode steps and over the chunk rows, which
the kernel serves too, a row a token (``keys_read``: the counter's name).  A counter
grows over the whole window and the trace covers its last seconds, so the
two are joined by steps as ``hybrid_attn_roofline_pct`` joins its own: the
counter's growth over the decode steps the program's spans report for the
window (``steps_of_span``), times the decode steps of the program
executions on the first chip's ``XLA Modules`` line in the stretch
(``steps_of_module``).  A program without the counter gives nothing to
read."""

import re

from perfbench import latent_attn_shapes, trace_reduce


def read(obs, args):
    tr = obs.get("trace")
    keys_read = obs.get("program_counters", {}).get(args["keys_read"])
    if tr is None or not tr.devices or not obs.get("peaks") or not keys_read:
        return None
    kernel_s = trace_reduce.matching_s(tr, args["pattern"])
    of_span = args["steps_of_span"]
    steps_window = sum(
        s["args"].get(of_span[s["name"]], 0) if of_span[s["name"]] else 1
        for s in obs.get("program_spans", ()) if s["name"] in of_span)
    patterns = [re.compile(p) for p in args["steps_of_module"]]
    steps_traced = 0
    for e in tr.devices[min(tr.devices)].modules:
        for rx in patterns:
            m = rx.search(e.name)
            if m:
                steps_traced += int(m.group(1)) if rx.groups else 1
    if kernel_s <= 0 or not steps_window or not steps_traced:
        return None
    needed_s = latent_attn_shapes.decode_seconds(
        keys_read * steps_traced / steps_window, obs["config"], obs["peaks"])
    return 100.0 * needed_s / kernel_s
