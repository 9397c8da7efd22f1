"""The grouped matmuls of a LatentMoE layer (experts of two matrices at the
latent's width) against their roofline: the least time the chip could take
for the expert weights it must read (and the operations it must do), over
the kernels' device time, in the traced stretch.  ``moe_roofline_pct``'s
reading with this family's count (``latent_moe_shapes``: two ``[w, I]``
matrices an expert, the expert layers counted from the pattern).

Bytes: an expert's weights cross HBM once for every (layer, step) in which
the expert has at least one row; the program counts those in the counter
``experts_active`` (non-empty groups of the experts held here, summed over
the expert layers and steps), joined to the stretch by steps
(``traced_steps``).  Operations likewise from ``assignments`` (the
assignments computed here, a layer's mean, x steps) times the expert
layers.  The larger of the two bounds is taken.  Rows padded up to whole
tiles, the gathered activations and the routing are not counted: the share
can only read low by them.  A program without the counters (one that has
no such layer, or none yet) gives nothing to read."""

from perfbench import latent_moe_shapes, traced_steps


def read(obs, args):
    counters = obs.get("program_counters") or {}
    active = counters.get(args["experts_active"])
    joined = active and traced_steps.kernel_seconds_and_share(obs, args)
    if not joined:
        return None
    kernel_s, share = joined
    cfg, peaks = obs["config"], obs["peaks"]
    bytes_s = (active * share * latent_moe_shapes.expert_weight_bytes(cfg)
               / peaks["hbm_bytes_per_s"])
    flops_s = (counters.get(args["assignments"], 0.0) * share
               * latent_moe_shapes.expert_layers(cfg)
               * latent_moe_shapes.assignment_flops(cfg)
               / peaks["bf16_flops_per_s"])
    return 100.0 * max(bytes_s, flops_s) / kernel_s
