"""Model FLOP/s utilization: operations the forward and backward passes
require per token (``train_flops_per_token`` of the family: no recomputation
counted) times tokens per second of stepping, over chips times the
chip's published bf16 peak."""


def read(obs, args):
    steps = obs.get("steps")
    if not steps or not steps["seconds"] or not obs.get("peaks"):
        return None
    rate = steps["tokens"] * len(steps["seconds"]) / sum(steps["seconds"])
    peak = obs["peaks"]["bf16_flops_per_s"]
    return 100.0 * rate * obs["flops_per_token"] / (
        obs["chips"] * peak)
