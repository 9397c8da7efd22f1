"""The paged decode-attention kernel against the memory roofline: the
bytes of live keys and values it must read over the chip's HBM bandwidth,
over the kernel's device time, in the traced stretch.

Bytes: for every pump inside the stretch, ``shapes.paged_attention_bytes``
of the contexts of the rows then decoding (each capped at the sliding
window, and grown by half a burst: a row gains one token a step), times
the decode steps the program's spans report for that pump, times the
layers.  Memory-bound by construction: one query row per sequence does
about one FLOP per byte of cache, against a critical intensity of ~240
FLOP/byte on a v5e."""

from perfbench import shapes, trace_reduce


def read(obs, args):
    tr = obs.get("trace")
    if tr is None or not obs.get("peaks"):
        return None
    kernel_s = trace_reduce.matching_s(tr, args["pattern"])
    t0, t1 = obs["traced"]
    pumps = [p for p in obs.get("pumps", ())
             if p["t0"] >= t0 and p["t1"] <= t1 and p.get("decode_steps")]
    if kernel_s <= 0 or not pumps:
        return None
    cfg = obs["config"]
    needed = sum(
        shapes.paged_attention_bytes(
            p["context_tokens"] + p["decoding"] * p["decode_steps"] / 2, cfg)
        * p["decode_steps"] * cfg["num_hidden_layers"] for p in pumps)
    bandwidth = obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * needed / bandwidth / kernel_s
