"""The reduction from a profiler trace to numbers, on a small recorded
trace and on a hand-made one whose answers can be worked out on paper."""

import pathlib
import re

import jax
import pytest

from perfbench import manifest
from perfbench import trace_reduce as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def recorded() -> tr.Trace:
    """3.8 ms of a real trace (my chip run, PR 23: 2-layer Mistral-7B
    widths served on one "TPU v5 lite", one single-step decode program with
    its neighbours), cut from the .xplane.pb into the text form of the same
    message."""
    text = (DATA / "serve_l2_v5e_decode_step.xspace.txt").read_text()
    return tr.from_profile_data(jax.profiler.ProfileData.from_text_proto(text))


def test_recorded_trace_has_the_lines_the_reduction_reads(recorded):
    dev = recorded.devices[0]
    assert (len(dev.ops), len(dev.async_ops), len(dev.modules)) == (135, 18, 3)
    assert [m.name.split("(")[0] for m in dev.modules] == [
        "jit__threefry_split", "jit__unstack", "jit__unknown"]


def test_recorded_busy_time_is_the_union_of_the_operations(recorded):
    busy = tr.busy_s(recorded)
    assert busy == pytest.approx(0.002782163, rel=1e-6)
    # operations run inside program executions: never busier than those
    modules = sum(m.dur_ns for m in recorded.devices[0].modules) * 1e-9
    assert busy <= modules * 1.001
    assert tr.idle_share(recorded) == pytest.approx(
        1 - busy / recorded.window_s)
    assert 0.2 < tr.idle_share(recorded) < 0.3


def test_recorded_kernel_time_by_name(recorded):
    # two layers, one step: the paged Mosaic kernel ran twice
    kernel = [e for e in recorded.devices[0].ops
              if tr.opcode(e.name) == "tpu_custom_call"]
    assert len(kernel) == 2
    assert tr.matching_s(recorded, "tpu_custom_call") == pytest.approx(
        sum(e.dur_ns for e in kernel) * 1e-9) == pytest.approx(
            0.000586996, rel=1e-6)
    top = tr.top_ops(recorded, 3)
    assert top[0][0] == "closed_call.12:bf16[8,32,128]:tpu_custom_call"
    assert [round(s, 6) for _, s in top] == sorted(
        (round(s, 6) for _, s in top), reverse=True)
    # the decode program of this trace, on the modules line
    assert recorded.devices[0].modules[-1].dur_ns * 1e-9 == pytest.approx(
        0.002786661)


def test_recorded_while_bodies_are_not_counted_twice(recorded):
    ops = recorded.devices[0].ops
    containers = [e for e in ops if tr.opcode(e.name) in tr.CONTAINERS]
    assert containers, "the decode program scans its layers in a while"
    leaves = tr.leaves(ops)
    assert len(leaves) == len(ops) - len(containers)
    # every leaf summed is at most the busy union plus overlaps: no more
    assert sum(e.dur_ns for e in leaves) * 1e-9 <= tr.busy_s(recorded) * 1.05


# -- kernels by name ---------------------------------------------------------

#: recordings with the names PR 24 gave the kernels (my chip runs, PR 26, one
#: "TPU v5 lite"): 21 instructions around one ``paged_decode_attention`` call
#: of a one-step decode program of the serving cell, with ``%fusion.133``
#: that takes the kernel's result as an operand; and 21 around one
#: ``flash_fwd`` call of the two-layer training step
NAMED = {"paged": ("serve_l16_v5e_paged_named.xspace.txt",
                   "paged_decode_attention.7", 153542.5),
         "flash": ("train_l2_v5e_flash_named.xspace.txt",
                   "flash_fwd.21", 4061065.0)}
KERNEL_METRICS = {"paged_attn_share.batch": "paged",
                  "paged_attn_roofline.batch": "paged",
                  "attention_share.train": "flash"}


@pytest.fixture(scope="module")
def named():
    return {key: tr.from_profile_data(jax.profiler.ProfileData.from_text_proto(
        (DATA / name).read_text())) for key, (name, _, _) in NAMED.items()}


@pytest.mark.parametrize("metric", KERNEL_METRICS)
def test_a_kernel_metric_counts_its_kernel_and_nothing_else(named, metric):
    pattern = manifest.load_json("metrics", metric)["args"]["pattern"]
    own = KERNEL_METRICS[metric]
    _, kernel, kernel_ns = NAMED[own]
    ops = named[own].devices[0].ops
    hit = [e for e in ops if re.search(pattern, e.name)]
    assert [tr.label(e.name).split(":")[0] for e in hit] == [kernel]
    assert tr.opcode(hit[0].name) == "tpu_custom_call"
    assert tr.matching_s(named[own], pattern) == pytest.approx(
        hit[0].dur_ns * 1e-9) == pytest.approx(kernel_ns * 1e-9, rel=1e-4)
    # the other family's kernel in the same program would not be counted
    other = next(k for k in NAMED if k != own)
    assert tr.matching_s(named[other], pattern) == 0.0
    assert tr.matching_s(named[other], "tpu_custom_call") > 0.0


def test_a_kernels_consumer_names_it_and_is_not_counted(named):
    """An instruction's text names its operands too: the fusion after the
    paged kernel holds ``%paged_decode_attention.7``.  The name alone
    would count it; the pattern, anchored at the start, does not."""
    pattern = manifest.load_json(
        "metrics", "paged_attn_share.batch")["args"]["pattern"]
    ops = named["paged"].devices[0].ops
    consumers = [e for e in ops if "%paged_decode_attention.7" in e.name
                 and not e.name.startswith("%paged_decode_attention.7 = ")]
    assert [tr.label(e.name) for e in consumers] == ["fusion.133:f32[32]:fusion"]
    assert not re.search(pattern, consumers[0].name)
    kernel_s = tr.matching_s(named["paged"], pattern)
    assert kernel_s == pytest.approx(NAMED["paged"][2] * 1e-9, rel=1e-4)
    assert tr.matching_s(named["paged"], "paged_decode_attention") == \
        pytest.approx(kernel_s + consumers[0].dur_ns * 1e-9)


def _kernel_text(name: str) -> str:
    return (f"%{name} = bf16[32,32,128]{{2,1,0:T(8,128)(2,1)}} custom-call("
            f"bf16[32,32,128]{{2,1,0}} %fusion.1), "
            f'custom_call_target="tpu_custom_call", operand_layout_constraints={{}}')


def _families_matching(text: str):
    return {own for metric, own in KERNEL_METRICS.items() if re.search(
        manifest.load_json("metrics", metric)["args"]["pattern"], text)}


@pytest.mark.parametrize("name, family", [
    ("paged_decode_attention.7", "paged"), ("paged_decode_attention", "paged"),
    ("flash_fwd.2", "flash"), ("flash_bwd_dq.1", "flash"),
    ("flash_bwd_dkv.13", "flash"), ("flash_bwd.4", "flash"),
    ("flash_fwd.clone.1", "flash"), ("moe_gather.3", None),
    ("closed_call.12", None), ("my_flash_fwd.1", None)])
def test_kernel_patterns_against_instruction_names(name, family):
    """The names the ledger's ``breakdown`` shows, the forms a later PR
    could give them (backward kernels merged into one, XLA's ``.clone``),
    one that a second kernel family would bring, and the unnamed kernel
    of PR 23's trace."""
    text = _kernel_text(name)
    for metric, own in KERNEL_METRICS.items():
        pattern = manifest.load_json("metrics", metric)["args"]["pattern"]
        assert bool(re.search(pattern, text)) == (own == family), metric
        assert bool(re.search(pattern, text[1:])) == (own == family)  # no "%"
        # the same name on another kind of instruction is not the kernel
        assert not re.search(pattern, text.replace("tpu_custom_call", "Sharding"))


#: the program's files whose kernels the kernel metrics count, and each
#: one's family
PALLAS = pathlib.Path(__file__).resolve().parents[2] / "deepspeed_tpu/ops/pallas"
KERNEL_FILES = {"flash_attention.py": "flash", "paged_attention.py": "paged"}


def _kernel_names(file: str):
    """(number of ``pl.pallas_call`` sites, their ``name=`` arguments)."""
    source = (PALLAS / file).read_text()
    return (len(re.findall(r"\bpl\.pallas_call\(", source)),
            re.findall(r'^\s+name="(\w+)",?\s*$', source, re.M))


@pytest.mark.parametrize("file, name", [
    (file, name) for file in KERNEL_FILES for name in _kernel_names(file)[1]])
def test_every_kernel_the_program_names_is_counted_once(file, name):
    """The patterns against the ``name=`` of every ``pl.pallas_call`` in
    the program's own files, read from the source (the streaming variants
    too, which no cell runs yet): one family's metrics count it, as XLA
    writes it with and without a number.  A kernel renamed out of its
    family fails here, before a traced run reads a share that fell."""
    for text in (_kernel_text(name), _kernel_text(f"{name}.17")):
        assert _families_matching(text) == {KERNEL_FILES[file]}, text


@pytest.mark.parametrize("file", KERNEL_FILES)
def test_every_kernel_of_a_counted_file_has_a_name(file):
    calls, names = _kernel_names(file)
    assert calls == len(names) == len(set(names)) > 0


def test_a_share_of_nothing_is_not_read_as_zero(named):
    """``ops_share_pct`` with no instruction to count returns nothing, so
    the metric is left out of the line and not printed as 0."""
    read = manifest.load_module("readers", "ops_share_pct").read
    spec = manifest.load_json("metrics", "attention_share.train")
    args = spec["args"]
    assert read({"trace": named["flash"]}, args) > 0
    assert read({"trace": named["paged"]}, args) is None
    assert read({"trace": None}, args) is None


# -- a trace made by hand ----------------------------------------------------

AG = ("%all-gather-start.3 = (bf16[1024]{0}, bf16[4096]{0}) "
      "all-gather-start(bf16[1024]{0} %p), dimensions={0}")
RS = "%reduce-scatter.7 = bf16[256]{0} reduce-scatter(bf16[1024]{0} %g)"


def _ev(a, b, name):
    return tr.Event(float(a), float(b - a), name)


@pytest.fixture
def made() -> tr.Trace:
    ops = [
        _ev(0, 18, "%while.1 = (s32[]{:T(128)}) while((s32[]) %t), body=%b"),
        _ev(0, 10, "%fusion.1 = bf16[8,128]{1,0:T(8,128)} fusion(bf16[8] %a)"),
        _ev(12, 18, '%k.2 = bf16[8,128]{1,0} custom-call(bf16[8] %a), '
                    'custom_call_target="tpu_custom_call"'),
        _ev(30, 40, RS),
    ]
    dev = tr.DeviceTrace(ops=ops, async_ops=[_ev(5, 25, AG)], modules=[])
    host = {"main": [_ev(0, 50, "bench/traced"), _ev(24, 31, "bench/pump"),
                     _ev(26, 29, "np.asarray(jax.Array)")],
            "other": [_ev(0, 50, "unrelated")]}
    return tr.Trace({0: dev}, host, 0.0, 50.0)


def test_busy_union_and_idle_share(made):
    # [0, 25] (operations, then the gather still in flight) and [30, 40]
    assert tr.busy_intervals(made.devices[0]) == [(0.0, 25.0), (30.0, 40.0)]
    assert tr.busy_s(made) == pytest.approx(35e-9)
    assert tr.idle_share(made) == pytest.approx(1 - 35 / 50)


def test_collective_time_and_the_exposed_part(made):
    every, exposed = tr.collective_s(made)
    # gather in flight 5..25, reduce-scatter 30..40
    assert every == pytest.approx(30e-9)
    # compute covers 5..10 and 12..18 of the gather; the reduce-scatter
    # runs alone: (20 - 11) + 10
    assert exposed == pytest.approx(19e-9)


def test_kernel_time_by_pattern_skips_containers(made):
    assert tr.matching_s(made, "tpu_custom_call") == pytest.approx(6e-9)
    assert tr.matching_s(made, "fusion|while") == pytest.approx(10e-9)
    labels = [name for name, _ in tr.top_ops(made)]
    assert "k.2:bf16[8,128]:tpu_custom_call" in labels
    assert not any(name.startswith("while") for name in labels)


def test_idle_gaps_are_named_by_what_the_host_was_doing(made):
    gaps = tr.idle_gaps(made, 2)
    assert gaps[0][1] == pytest.approx(10e-9)          # 40..50: nothing covers it
    assert gaps[0][0] == "unattributed"
    assert gaps[1] == ["bench/pump>np.asarray(jax.Array)",
                       pytest.approx(5e-9)]            # 25..30


def test_instruction_text(made):
    assert tr.opcode(AG) == "all-gather-start" and tr.is_collective(AG)
    assert tr.is_collective(RS) and not tr.is_collective(made.devices[0].ops[1].name)
    assert tr.opcode("%f.1 = (u32[1]{0:T(128)}, u32[1]{0:T(128)}) "
                     "fusion(u32[2]{0:T(128)} %key.1), kind=kLoop") == "fusion"
    assert tr.label("%copy.70 = bf16[16,3200,16,8,128]{4,3,2,1,0:T(8,128)(2,1)} "
                    "copy(bf16[16,3200,16,8,128]{4,3,2,1,0} %x)"
                    ) == "copy.70:bf16[16,3200,16,8,128]:copy"


def test_interval_arithmetic():
    assert tr.union([(3, 5), (0, 1), (4, 8), (8, 9)]) == [(0, 1), (3, 9)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr.total(tr.subtract([(0, 10)], [])) == 10
