"""The paged decode-attention kernel of a model with full and window
layers against the memory roofline: the bytes of live keys and values it
must read over the chip's HBM bandwidth, over the kernel's device time, in
the traced stretch.

Bytes: the program counts, for each kind of layer, the keys one layer of it
attends over, summed over decoding rows and decode steps (``keys_read``:
kind → counter; a full layer a row's whole length, a window layer at most
the window).  ``hybrid_attn_shapes.decode_bytes`` turns them into bytes by
the published head counts and widths and the layers of each kind.  A
counter grows over the whole window and the trace covers its last seconds,
so the two are joined by steps as ``moe_roofline_pct`` joins its own: the
counters' growth over the decode steps the program's spans report for the
window (``steps_of_span``), times the decode steps of the program
executions on the first chip's ``XLA Modules`` line in the stretch
(``steps_of_module``).  Memory-bound by construction (about one FLOP per
byte of cache).  A program without the counters gives nothing to read."""

import re

from perfbench import hybrid_attn_shapes, trace_reduce


def read(obs, args):
    tr = obs.get("trace")
    counters = obs.get("program_counters", {})
    keys_read = {kind: counters.get(name)
                 for kind, name in args["keys_read"].items()}
    if tr is None or not tr.devices or not obs.get("peaks") \
            or not all(keys_read.values()):
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
    needed = hybrid_attn_shapes.decode_bytes(keys_read, obs["config"]) \
        * steps_traced / steps_window
    return 100.0 * needed / obs["peaks"]["hbm_bytes_per_s"] / kernel_s
