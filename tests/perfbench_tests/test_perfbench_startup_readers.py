"""The nine metrics under ``setup_s`` (ISSUE 54): the reader of the
program's start-up record and compile account on a record made by hand,
on a program that keeps none, and in the CPU rehearsal's traced line of a
training and a serving cell."""

import json
import pathlib
import sys

import pytest

from perfbench import manifest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import rehearsal  # noqa: E402

BENCH = manifest.load_benchmark()
NINE = {"before_entry_s.setup": "before_entry", "entry_s.setup": "entry",
        "after_entry_s.setup": "after_entry", "import_s.setup": "import",
        "placement_s.setup": "placement",
        "first_calls_s.setup": "first_calls",
        "trace_lower_s.setup": "trace_lower",
        "compile_load_s.setup": "compile_load",
        "cache_misses.setup": "cache_misses"}
READER = manifest.load_module("readers", "startup_s")
PINNED = "serve-chat-falcon-h1-l6"


def span(name, start, end, depth=None, **args):
    if depth is not None:
        args["depth"] = depth
    return {"name": name, "start": start, "end": end, "args": args}


class Account:
    """A compile account made by hand: ``(stamp, kind, value)``."""

    def __init__(self, events):
        self.kept = events

    def sums(self, since, until):
        out = dict.fromkeys(("trace_s", "lower_s", "compile_s",
                             "cache_misses", "cache_hits"), 0.0)
        for stamp, kind, value in self.kept:
            if since < stamp <= until:
                out[kind] += value
        return out


#: a start from 100.0 (the harness's first line) to a window that opens at
#: 160.0; the root runs 120.0-135.0; the package was imported at 101.0
RECORD = [
    span("startup/package_import", 101.0, 101.5),
    span("startup/import", 120.0, 126.0, 1, module="entry"),
    span("startup/config", 126.0, 126.5, 1),
    span("startup/place/shardings", 127.0, 127.25, 2),
    span("startup/place/params", 127.25, 129.25, 2),
    span("startup/first_call", 129.5, 130.0, 3, site="engine/opt_init"),
    span("startup/place/opt_state", 129.25, 130.25, 2),
    span("startup/import", 130.25, 133.25, 2, module="resilience"),
    span("startup/engine", 126.5, 134.0, 1),
    span("startup/initialize", 120.0, 135.0, 0, stage=3),
    span("startup/first_call", 136.0, 141.0, 0, site="engine/train_step"),
    # an import outside any root is no part of the entry point's imports
    span("startup/import", 142.0, 143.0, 0, module="late"),
    # after the window opened: not set-up
    span("startup/first_call", 161.0, 163.0, 0, site="late"),
    span("startup/place/pools", 170.0, 171.0, 0),
    # a root of an earlier start, before this run's first line
    span("startup/initialize", 10.0, 20.0, 0),
]
ACCOUNT = Account([
    (50.0, "compile_s", 9.0),                       # before the run
    (110.0, "trace_s", 1.5), (110.5, "lower_s", 0.5),
    (112.0, "compile_s", 2.0), (112.0, "cache_misses", 1.0),
    (140.0, "trace_s", 2.5), (140.5, "lower_s", 1.0),
    (141.0, "compile_s", 0.75), (141.0, "cache_hits", 1.0),
    (162.0, "compile_s", 4.0), (162.0, "cache_misses", 1.0),   # too late
])
OBS = {"t_open": 160.0, "setup_s": 60.0}


def test_the_parts_of_a_record_made_by_hand():
    got = READER.parts(RECORD, ACCOUNT, 100.0, 160.0)
    assert got == {
        "before_entry": 20.0, "entry": 15.0, "after_entry": 25.0,
        "import": 0.5 + 6.0 + 3.0,
        "placement": 0.25 + 2.0 + 1.0,
        "first_calls": 0.5 + 5.0,
        "trace_lower": 1.5 + 0.5 + 2.5 + 1.0,
        "compile_load": 2.75, "cache_misses": 1.0}
    assert got["before_entry"] + got["entry"] + got["after_entry"] \
        == OBS["setup_s"]
    assert got["import"] <= got["entry"] + 0.5
    assert got["first_calls"] <= got["entry"] + got["after_entry"]


def test_a_span_across_an_end_of_the_set_up_is_clipped():
    record = [span("startup/serving_frontend", 95.0, 130.0, 0),
              span("startup/first_call", 158.0, 164.0, 0)]
    got = READER.parts(record, Account([]), 100.0, 160.0)
    assert (got["before_entry"], got["entry"], got["after_entry"]) \
        == (0.0, 30.0, 30.0)
    assert got["first_calls"] == 2.0
    # two roots: what lies between them is the harness's, after the entry
    record.append(span("startup/serving_frontend", 140.0, 150.0, 0))
    got = READER.parts(record, Account([]), 100.0, 160.0)
    assert (got["before_entry"], got["entry"], got["after_entry"]) \
        == (0.0, 40.0, 20.0)


def test_no_root_in_the_window_reads_nothing():
    assert READER.parts([e for e in RECORD if e["start"] > 135.0
                         or e["start"] < 100.0], ACCOUNT, 100.0, 160.0) is None


@pytest.mark.parametrize("metric", sorted(NINE))
def test_each_metric_reads_its_part(metric, monkeypatch):
    spec = manifest.load_json("metrics", metric)
    assert spec["reader"] == "startup_s"
    assert spec["args"] == {"part": NINE[metric]}
    entry = manifest.named(BENCH["per_layer"], metric, "metric")
    assert entry["moves"] == "setup_s" and entry["better"] == "lower"
    assert entry["layer"] == "Entry / config"
    # every cell but the one whose list of metrics a test of its family
    # pins (``test_perfbench_falcon_h1.py:317``, a file this PR may not
    # edit): the ``benchmark`` PR that unpins it appends the cell
    assert entry["workloads"] == [w["name"] for w in BENCH["workloads"]
                                  if w["name"] != PINNED]
    monkeypatch.setattr(READER, "_sources", lambda: (RECORD, ACCOUNT))
    want = READER.parts(RECORD, ACCOUNT, 100.0, 160.0)[NINE[metric]]
    assert READER.read(OBS, spec["args"]) == want
    # a runner that observed no set-up: nothing to hold the record against
    assert READER.read({"t_open": 160.0}, spec["args"]) is None


@pytest.mark.parametrize("metric", sorted(NINE))
def test_a_program_without_the_record_reads_none(metric, monkeypatch):
    """The parent commit's program: its hub has no ``startup`` and its
    tracker no ``account``.  Nothing is read and nothing raises."""
    import deepspeed_tpu.telemetry as tel

    class OldHub:
        pass

    spec = manifest.load_json("metrics", metric)
    monkeypatch.setattr(tel, "get_telemetry", lambda: OldHub())
    assert READER.read(OBS, spec["args"]) is None
    monkeypatch.undo()
    monkeypatch.setattr(tel, "get_compile_tracker", lambda: OldHub())
    assert READER.read(OBS, spec["args"]) is None


def test_the_nine_are_the_last_entries_and_the_manifest_stays_small():
    assert [m["name"] for m in BENCH["per_layer"][-9:]] == list(NINE)
    assert len(json.dumps(BENCH)) < 64 * 1024
    moved = [m["name"] for m in BENCH["per_layer"] if m["moves"] == "setup_s"]
    assert moved == list(NINE)


@pytest.mark.parametrize("kind", ["train", "serve"])
def test_the_rehearsals_traced_line_holds_all_nine(kind, tmp_path):
    cell = next(w["name"] for w in BENCH["workloads"]
                if w["name"].startswith(kind))
    line = rehearsal.rehearse(cell, seed=2**31 + 54, seconds=0.6, trace=True,
                              tmp_path=tmp_path)
    got = {name: line["metrics"][name]["value"] for name in NINE}
    assert all(v >= 0.0 for v in got.values())
    setup = line["setup_s"]
    assert got["before_entry_s.setup"] + got["entry_s.setup"] \
        + got["after_entry_s.setup"] == pytest.approx(setup, abs=1e-3)
    assert got["entry_s.setup"] > 0 and got["after_entry_s.setup"] > 0
    # the rehearsal's clock starts after the imports: the package's own
    # lies before it and counts nothing; the entry point's leaves are in
    assert got["import_s.setup"] <= got["entry_s.setup"]
    assert got["placement_s.setup"] > 0
    assert 0 < got["first_calls_s.setup"] \
        <= got["entry_s.setup"] + got["after_entry_s.setup"]
    assert got["trace_lower_s.setup"] > 0
    assert got["compile_load_s.setup"] > 0
    assert got["trace_lower_s.setup"] + got["compile_load_s.setup"] < setup
    # the suite runs without a persistent cache: nothing is written to one
    assert got["cache_misses.setup"] == 0.0
    units = {name: line["metrics"][name]["unit"] for name in NINE}
    assert set(units.values()) == {"s", "programs"}
