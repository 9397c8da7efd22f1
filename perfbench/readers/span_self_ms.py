"""A percentile of what the program's spans named ``span`` took themselves:
each one's duration less its direct children named in ``minus``, in ms.

``obs["program_spans"]`` keeps the spans in the order they closed, each
with its depth and its parent's name, so children come before their
parent: a span's direct children are the spans one level deeper, under
its name, that closed since the last span of its own depth or above.
Spans that carry no depth (a request's phases, stamped elsewhere) belong
to no tree and are passed over."""

from perfbench import arith


def read(obs, args):
    minus = set(args["minus"])
    values = []
    pending = {}        # depth -> seconds of ``minus`` children closed there
    for s in obs.get("program_spans", ()):
        depth = s["args"].get("depth")
        if depth is None:
            continue
        children = pending.pop(depth + 1, 0.0)
        if s["name"] == args["span"]:
            values.append(1e3 * (s["dur_s"] - children))
        if s["name"] in minus and s["args"].get("parent") == args["span"]:
            pending[depth] = pending.get(depth, 0.0) + s["dur_s"]
    return arith.percentile(values, args["q"]) if values else None
