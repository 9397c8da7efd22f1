"""Every cell end to end on the CPU at tiny sizes (``rehearsal.py``): the
program against each configuration's plain reference, the shape of the
result line, and the real command's refusal to run without a TPU."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

from perfbench import manifest
from perfbench import run as bench_run

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import rehearsal  # noqa: E402

BENCH = manifest.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
#: every key the driver reads, and nothing the contract forbids
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}
#: what only the chip can say: these never come out of a CPU run
DEVICE_ONLY = ("idle", "roofline", "mfu", "attn_share", "collective",
               "attention_share")


@pytest.fixture(scope="module")
def lines(tmp_path_factory):
    cache = {}

    def run(cell, trace):
        key = (cell, trace)
        if key not in cache:
            cache[key] = rehearsal.rehearse(
                cell, seed=2**31 + 11, seconds=0.6, trace=trace,
                tmp_path=tmp_path_factory.mktemp("scratch"))
        return cache[key]

    return run


@pytest.mark.parametrize("cell", CELLS)
def test_program_agrees_with_the_plain_reference(cell, lines):
    line = lines(cell, False)
    assert line["correct"] is True, line["check"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["compiles_in_window"] == 0
    check = line["check"]
    if "logit_gap" in check:
        # served tokens through the paged cache, against the reference's
        # full forward pass: at float32 tiny sizes they are its argmax
        assert check["requests"] == 3 and check["logit_gap"] <= check["tolerance"]
    else:
        # logits, loss and every gradient leaf.  bfloat16 compute against
        # a float32 reference: 2^-8 per rounding, a few of them in a row
        assert check["logit_rel_err"] < 0.03
        assert check["loss_abs_err"] < 0.01
        assert check["grad_rel_err"] < 0.06


@pytest.mark.parametrize("cell", CELLS)
def test_end_to_end_line(cell, lines):
    line = lines(cell, False)
    assert LINE_KEYS <= set(line)
    assert DEVICE_KEYS <= set(line["device"])
    want = {m["name"] for m in manifest.cell_metrics(BENCH, cell, "end_to_end")}
    assert set(line["metrics"]) == want
    for name, metric in line["metrics"].items():
        assert set(metric) == {"value", "unit"} and metric["value"] > 0, name
    json.dumps({k: v for k, v in line.items() if k != "_obs"})


@pytest.mark.parametrize("cell", CELLS)
def test_the_line_ends_with_the_check_and_a_serving_line_counts_its_rounds(
        cell, lines, capsys):
    """The check, with every number compared and its limit, is the line's
    last key, and the same numbers are the run's last lines on standard
    error; a serving line says how many rounds (one program call each) its
    window held, which an untraced run could not say before PR 51."""
    line = {k: v for k, v in lines(cell, False).items() if k != "_obs"}
    assert list(line)[-1] == "check"
    check = line["check"]
    limits = check["tolerance"]
    if not isinstance(limits, dict):
        limits = {"logit_gap": limits}
    assert limits and all(check[name] <= limit
                          for name, limit in limits.items())
    bench_run.print_compared(check)
    err = capsys.readouterr().err.strip().splitlines()
    assert err == [f"perfbench: {name} {check[name]!r} limit {limit!r}"
                   for name, limit in limits.items()]
    obs = lines(cell, False)["_obs"]
    if "deliveries" not in obs:
        assert "rounds" not in line         # a training cell: whole steps
        return
    # the window is the offered one: every delivery in it is counted
    assert line["window_s"] == obs["t_close"] - obs["t_open"]
    assert line["rounds"] == len(obs["pumps"]) > 0
    assert line["rounds"] >= len({d[0] for d in obs["deliveries"]
                                  if obs["t_open"] < d[0] <= obs["t_close"]})
    assert line["metrics"]["serve_tokens_per_s"]["value"] == pytest.approx(
        sum(n for t, _, n in obs["deliveries"]
            if obs["t_open"] < t <= obs["t_close"]) / line["window_s"])


@pytest.mark.parametrize("cell", CELLS)
def test_traced_line_prints_no_device_metric_on_the_cpu(cell, lines):
    line = lines(cell, True)
    want = {m["name"] for m in manifest.cell_metrics(BENCH, cell, "per_layer")}
    assert set(line["metrics"]) < want
    assert line["metrics"], "host-clock and program metrics are still read"
    for name in line["metrics"]:
        assert not any(word in name for word in DEVICE_ONLY), name
    assert "breakdown" not in line and "busy_s" not in line["device"]


def test_the_check_crosses_the_sliding_window(lines):
    """A wrong window mask must not print ``correct``: each Mistral cell's
    check sample is longer than the window it is checked under, at the
    real sizes as at the tiny ones."""
    import rehearsal as r

    seen = 0
    for cell in BENCH["workloads"]:
        real = manifest.load_json("configs", cell["config"])
        for cfg in (real, r.tiny_files(cell)[0]):
            check = cfg["run"]["check"]
            window = check.get("config_overrides", {}).get(
                "sliding_window", cfg.get("sliding_window"))
            if window is None:
                continue
            seen += 1
            longest = (check["seq"] if "seq" in check else
                       max(check["prompt_tokens"]) + check["new_tokens"])
            assert longest > window + 16, cell["name"]
            assert longest <= cfg["max_position_embeddings"]
    assert seen >= 6          # three Mistral cells, real and tiny
    # the prompt beyond the traffic's longest is served and checked, and
    # the programs compiled for it alone are not counted in the peak
    serving = next(w["name"] for w in BENCH["workloads"]
                   if "logit_gaps" in lines(w["name"], False)["check"])
    check = lines(serving, False)["check"]
    assert check["requests"] == 3 and max(check["logit_gaps"]) == check["logit_gap"]


def test_a_wrong_window_fails_the_check():
    """The reference itself tells windows apart: the same weights and ids
    under a window of 40 and of 41 differ beyond the serving tolerance at
    the positions past the window, and nowhere before it."""
    import jax
    import jax.numpy as jnp

    family = manifest.load_module("models", "mistral")
    cell = next(w for w in BENCH["workloads"]
                if manifest.load_json("configs", w["config"]).get("sliding_window"))
    cfg = dict(rehearsal.tiny_files(cell)[0], sliding_window=40)
    cfg["run"] = dict(cfg["run"], dtype="float32")
    weights = family.build(cfg).init_params(jax.random.PRNGKey(3))
    ids = jax.random.randint(jax.random.PRNGKey(4), (1, 96), 0, cfg["vocab_size"])
    a = family.forward(weights, cfg, ids)
    b = family.forward(weights, dict(cfg, sliding_window=41), ids)
    assert float(jnp.max(jnp.abs(a[:, :40] - b[:, :40]))) < 1e-5
    assert float(jnp.max(jnp.abs(a[:, 41:] - b[:, 41:]))) > 1e-3


def test_serving_defaults_are_the_programs_own(lines):
    cell = next(w["name"] for w in BENCH["workloads"]
                if "program_defaults" in lines(w["name"], False))
    import inspect

    from deepspeed_tpu.serving import build_serving_frontend

    sig = inspect.signature(build_serving_frontend).parameters
    assert lines(cell, False)["program_defaults"] == {
        k: sig[k].default
        for k in ("prefill_chunk", "prefill_batch", "decode_burst")}


def test_the_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", CELLS[0],
         "--seed", str(2**31 + 11), "--seconds", "1", "--trace", "0"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode != 0
    assert "nothing was run" in out.stderr
    assert not out.stdout.strip()


def test_the_command_names_a_missing_cell():
    out = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "no-such-cell",
         "--seed", "1", "--seconds", "1"], cwd=manifest.ROOT,
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and "no workload named" in out.stderr
    assert not out.stdout.strip()
