"""The programs of the families that state no drafting layer hold nothing
of drafting (PR 59): ``jax.make_jaxpr`` of the decode burst and of the step
that carries chunks, for one dense family, two that carry a recurrent
state (a Mamba-2 mixer's and a delta rule's) and one with routed experts
(whose gate's stats the program packs), is
the text the PARENT commit's tree gives (``tests/fixtures/serving/
v2_program_texts.json``: sha256 and length of each, made by running this
file on that tree: ``python tests/unit/inference/
test_v2_programs_unchanged.py <tree> --write``).  A PR that changes those
programs on purpose writes the file anew and says so."""

import functools
import hashlib
import json
import pathlib
import re
import sys

import jax
import numpy as np
import pytest

GOLDEN = pathlib.Path(__file__).parents[2] / "fixtures" / "serving" \
    / "v2_program_texts.json"

FAMILIES = {
    "LlamaModel": lambda models: models.LlamaModel(models.LlamaConfig.tiny(
        num_layers=2, sliding_window=24)),
    "FalconH1Model": lambda models: models.FalconH1Model(
        models.FalconH1Config.tiny()),
    "OlmoeModel": lambda models: models.OlmoeModel(models.OlmoeConfig.tiny()),
    # PR 60 changed Nemotron-H's decode step alone (its conv's tail moved
    # where it lies): Falcon-H1's texts above and the delta rule's, pinned
    # here in bfloat16, are the parent's
    "SolarOpen2Model": lambda models: models.SolarOpen2Model(
        models.SolarOpen2Config.tiny(dtype="bfloat16")),
}
PROGRAMS = {"burst": 4, "step_with_chunks": 1}


def program_text(family: str, program: str) -> str:
    """The jaxpr of one of the engine's two programs at a tiny size, as
    text, with what differs from run to run (addresses) taken out."""
    from deepspeed_tpu import models
    from deepspeed_tpu.inference.v2 import KVCacheConfig, build_engine_v2

    model = FAMILIES[family](models)
    params = model.init_params(jax.random.PRNGKey(4))
    B, Bp, C = 3, 2, 8
    eng = build_engine_v2(
        model, params, KVCacheConfig(num_blocks=64, block_size=4,
                                     max_seq_len=128),
        max_batch_slots=B, prefill_chunk=C, prefill_batch=Bp,
        decode_burst=PROGRAMS["burst"])
    mb = eng.cache_config.max_blocks_per_seq
    i32 = lambda *shape: np.zeros(shape, np.int32)
    state = bool(eng.state_layouts)
    chunks = slots = None
    kw = {}
    if program == "step_with_chunks":
        chunks = (i32(Bp, C), i32(Bp, mb), i32(Bp), i32(Bp), None)
        kw["kb"] = mb
    if state:
        slots = (i32(B), i32(Bp) if chunks is not None else None)
    text = str(jax.make_jaxpr(functools.partial(
        eng._decode_burst_fn, n_steps=PROGRAMS[program], **kw))(
            params, eng.pool, i32(B), (i32(B), i32(B + Bp)), i32(B),
            i32(B, mb), i32(B), np.float32(0.0), jax.random.PRNGKey(0), None,
            chunks, slots))
    return re.sub(r"0x[0-9a-f]+", "0x", text)


def _digest(text: str) -> dict:
    return {"sha256": hashlib.sha256(text.encode()).hexdigest(),
            "length": len(text)}


@pytest.mark.parametrize("program", sorted(PROGRAMS))
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_program_is_the_parents_text(family, program):
    want = json.loads(GOLDEN.read_text())[f"{family}/{program}"]
    assert _digest(program_text(family, program)) == want


if __name__ == "__main__":
    # python <this file> <tree> [--write]: the digests of <tree>'s programs
    sys.path.insert(0, str(pathlib.Path(sys.argv[1]).resolve()))
    out = {f"{family}/{program}": _digest(program_text(family, program))
           for family in sorted(FAMILIES) for program in sorted(PROGRAMS)}
    if "--write" in sys.argv:
        GOLDEN.write_text(json.dumps(out, indent=1) + "\n")
    print(json.dumps(out, indent=1))
