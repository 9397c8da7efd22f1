"""A gated delta rule's recurrence against its roofline:
``mixer_roofline_pct``'s reading with that family's count
(``delta_shapes``: its key names, its layers counted from ``gqa_layers``).
The least time the chip could take for the work the program's counter
reports (``"bound": "update"``, the bytes of the decode steps' state
updates against the HBM peak; ``"chunk"``, the operations of the prefill
blocks against the MXU peak), over the device time of the instructions
that do it (``pattern``), in the traced stretch; the counter is joined to
the stretch by steps (``traced_steps``; ``only_steps`` 1: the calls that
carry chunks).  A program without the counter (one with no such layer, or
none yet), or a trace in which no instruction matches, gives nothing to
read."""

from perfbench import delta_shapes, traced_steps


def read(obs, args):
    work = (obs.get("program_counters") or {}).get(args["counter"])
    joined = work and traced_steps.kernel_seconds_and_share(obs, args)
    if not joined:
        return None
    op_s, share = joined
    cfg, peaks = obs["config"], obs["peaks"]
    if args["bound"] == "update":
        least = delta_shapes.update_seconds(work * share, cfg, peaks)
    else:
        least = delta_shapes.chunk_seconds(work * share, cfg, peaks,
                                           args["block"])
    return 100.0 * least / op_s
