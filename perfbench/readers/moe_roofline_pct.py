"""The grouped expert matmul against its roofline: the least time the chip
could take for the expert weights it must read (and the operations it must
do), over the kernels' device time, in the traced stretch.

Bytes: an expert's weights (``moe_shapes.expert_weight_bytes``) cross HBM
once for every (layer, step) in which the expert has at least one row; the
program counts those in the counter ``experts_active`` (non-empty groups,
summed over layers and steps).  A counter grows over the whole window and
the trace covers its last seconds, so the two are joined by steps: the
counter's growth over the steps the program's spans report for the window
(``steps_of_span``: a span's name and the argument that holds its steps,
one where null), times the steps of the program executions on the first
chip's ``XLA Modules`` line in the stretch (``steps_of_module``: patterns
whose first group, if any, captures the steps from the program's name).
Operations likewise from ``assignments`` (rows x k a step) times the
layers.  Memory-bound at serving widths by a wide margin: a decode step's
256 rows of an 8-of-64 model are 0.016 ms of MXU against 0.97 ms of
weights a layer, a 256-token prefill call's 0.13 against 0.98; the larger
of the two bounds is taken all the same.  Rows padded up to whole tiles,
the gathered activations and the routing are not counted: the share can
only read low by them.  A program without the counters (one that has no
such layer) gives nothing to read."""

import re

from perfbench import moe_shapes, trace_reduce


def read(obs, args):
    tr = obs.get("trace")
    counters = obs.get("program_counters", {})
    active = counters.get(args["experts_active"])
    if tr is None or not tr.devices or not obs.get("peaks") or not active:
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
    cfg, peaks = obs["config"], obs["peaks"]
    share = steps_traced / steps_window
    bytes_s = (active * share * moe_shapes.expert_weight_bytes(cfg)
               / peaks["hbm_bytes_per_s"])
    flops_s = (counters.get(args["assignments"], 0.0) * share
               * cfg["num_hidden_layers"] * moe_shapes.assignment_flops(cfg)
               / peaks["bf16_flops_per_s"])
    return 100.0 * max(bytes_s, flops_s) / kernel_s
