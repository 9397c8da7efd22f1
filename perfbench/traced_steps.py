"""A counter that grows over the whole window against kernel time from the
trace of its last seconds: the two are joined by STEPS.

What ``moe_roofline_pct`` and ``ssm_roofline_pct`` each do for themselves,
in one place for the readers that came after them: the counter's growth
over the steps the program's spans report for the window
(``steps_of_span``: a span's name and the argument that holds its steps),
times the steps of the program executions on the first chip's ``XLA
Modules`` line in the stretch (``steps_of_module``: patterns whose first
group captures the steps from the program's name).  ``only_steps`` keeps
the calls of that many steps on both sides (1: the calls that carry
chunks)."""

from __future__ import annotations

import re
from typing import Any, Dict, Optional, Tuple

from perfbench import trace_reduce


def kernel_seconds_and_share(obs: Dict[str, Any], args: Dict[str, Any]
                             ) -> Optional[Tuple[float, float]]:
    """(device seconds of the instructions matching ``args["pattern"]`` in
    the traced stretch, the share of the window's steps that the stretch
    holds), or None where there is no device trace, no peak, no matching
    instruction or no step on either side."""
    tr = obs.get("trace")
    if tr is None or not tr.devices or not obs.get("peaks"):
        return None
    only = args.get("only_steps")
    kept = lambda steps: only is None or steps == only
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
    seconds = trace_reduce.matching_s(tr, args["pattern"])
    if seconds <= 0 or not steps_window or not steps_traced:
        return None
    return seconds, steps_traced / steps_window
