#!/bin/bash
# Full-suite runner: one fresh pytest process per shard.
#
# Why sharded: a single-process run of all ~260 tests reliably dies with
# a SIGABRT inside the XLA CPU runtime after ~240 heavy jit tests.
# Root-caused via an LD_PRELOAD SIGABRT backtrace (no gdb in the image):
#   absl LogMessage::Fail <- xla::internal::AwaitAndLogIfStuck
#   (rendezvous.cc) <- cpu::AllReduceThunk::Execute <- Eigen WorkerLoop
# i.e. a CPU-collective RENDEZVOUS TIMEOUT: late in a long run the 8
# virtual devices' collective participants stop being co-scheduled on
# the shared Eigen pool, the all-reduce rendezvous never completes, and
# XLA LOG(FATAL)s.  Sharding gives each slice a fresh XLA client/pool,
# which sidesteps the starvation entirely (and is how CI tiers anyway).
#
# Usage: tests/run_suite.sh [extra pytest args...]
set -u
cd "$(dirname "$0")/.."

SHARDS=(
  "tests/unit/inference"
  "tests/unit/launcher tests/unit/models"
  "tests/unit/moe tests/unit/ops tests/unit/parallel"
  "tests/unit/runtime --ignore=tests/unit/runtime/test_infinity.py --ignore=tests/unit/runtime/test_infinity_sp.py --ignore=tests/unit/runtime/test_infinity_opt_fp16.py --ignore=tests/unit/runtime/test_pipe_engine.py"
  "tests/unit/runtime/test_infinity.py"
  "tests/unit/runtime/test_infinity_sp.py"
  "tests/unit/runtime/test_infinity_opt_fp16.py"
  "tests/unit/runtime/test_pipe_engine.py"
  "tests/unit/monitor"
  "tests/unit/analysis"
  "tests/unit/telemetry --ignore=tests/unit/telemetry/test_memory_ledger.py --ignore=tests/unit/telemetry/test_memory_oom.py --ignore=tests/unit/telemetry/test_memory_health.py --ignore=tests/unit/telemetry/test_memory_cli.py --ignore=tests/unit/telemetry/test_memory_watchdog.py --ignore=tests/unit/telemetry/test_numerics_stats.py --ignore=tests/unit/telemetry/test_numerics_engine.py --ignore=tests/unit/telemetry/test_numerics_cli.py"
  "tests/unit/telemetry/test_memory_ledger.py tests/unit/telemetry/test_memory_oom.py tests/unit/telemetry/test_memory_health.py tests/unit/telemetry/test_memory_cli.py tests/unit/telemetry/test_memory_watchdog.py"
  "tests/unit/telemetry/test_numerics_stats.py tests/unit/telemetry/test_numerics_engine.py tests/unit/telemetry/test_numerics_cli.py"
  "tests/unit/resilience"
  "tests/unit/elasticity"
  "tests/unit/serving"
  "tests/unit/tuning"
  "tests/unit/perf"
  "tests/unit/profiling"
  "tests/unit/anatomy"
  "tests/unit/test_comm.py tests/unit/test_elastic_rendezvous.py tests/unit/test_mesh.py tests/unit/test_overlap.py"
  "tests/unit/multiprocess --ignore=tests/unit/multiprocess/test_chaos_control_plane.py --ignore=tests/unit/multiprocess/test_serving_network.py --ignore=tests/unit/multiprocess/test_autoscale.py"
  "tests/unit/multiprocess/test_chaos_control_plane.py -m chaos"
  "tests/unit/multiprocess/test_serving_network.py -m chaos"
  "tests/unit/multiprocess/test_autoscale.py -m chaos"
  "tests/unit/test_feature_round2.py tests/unit/test_feature_subsystems.py"
  "tests/unit/test_chip_smoke.py tests/unit/test_compile_cache.py"
)

total_pass=0
fail=0
for shard in "${SHARDS[@]}"; do
  echo "=== shard: $shard"
  log=$(mktemp)
  python -m pytest $shard -q "$@" >"$log" 2>&1
  rc=$?  # the real exit code — a silent SIGABRT has no text to grep
  tail -2 "$log"
  n=$(grep -oE '[0-9]+ passed' "$log" | grep -oE '[0-9]+' | head -1)
  total_pass=$((total_pass + ${n:-0}))
  if [ $rc -ne 0 ]; then
    echo "=== shard FAILED (exit $rc)"
    fail=1
  fi
  rm -f "$log"
done
# Operator-CLI smoke (ISSUE 3): a freshly generated debug bundle must
# summarize cleanly through `python -m deepspeed_tpu.telemetry`.
echo "=== CLI smoke: telemetry summary"
smoke_dir=$(mktemp -d)
bundle=$(python - "$smoke_dir" <<'PYEOF'
import sys
from deepspeed_tpu.telemetry import FlightRecorder

fr = FlightRecorder(output_path=sys.argv[1])
fr.annotate("cli_smoke", {"ok": True})
fr.record_step({"step": 1, "step_time_ms": 1.0, "loss": 0.5})
print(fr.dump("run_suite CLI smoke"))
PYEOF
)
bundle=$(echo "$bundle" | tail -1)
if python -m deepspeed_tpu.telemetry summary "$bundle" >/dev/null; then
  echo "=== CLI smoke passed"
else
  echo "=== CLI smoke FAILED"
  fail=1
fi
rm -rf "$smoke_dir"

# Live-cluster-view smoke (ISSUE 13): three in-process "hosts" publish
# their registry snapshots through a rendezvous store; `telemetry top
# --once` (the real module CLI, in a subprocess) must exit 0 and render
# every live node from the rollup — no bundles collected.
echo "=== CLI smoke: telemetry top --once"
if python - <<'PYEOF'
import subprocess
import sys

from deepspeed_tpu.elasticity.rendezvous import (RendezvousClient,
                                                 RendezvousServer)
from deepspeed_tpu.telemetry import (StepRecord, configure_step_stream,
                                     get_telemetry, push_node_telemetry)

srv = RendezvousServer()
try:
    c = RendezvousClient(srv.endpoint)
    tel = get_telemetry()
    tel.configure(enabled=True, jsonl=False, prometheus=False)
    configure_step_stream(enabled=True)
    for node, step in (("host-a", 4), ("host-b", 6), ("host-c", 5)):
        tel.record_step(StepRecord(
            step=step, step_time_ms=12.0, device_fenced=True,
            samples_per_sec=1.0, tokens_per_sec=100.0, loss=0.5,
            grad_norm=0.0, lr=0.1, loss_scale=1.0, overflow=False,
            skipped_steps=0, comm_bytes=0, comm_ops=0))
        push_node_telemetry(c, node)
        c.hb(f"rdzv/hb/{node}")
    out = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.telemetry", "top", "--once",
         "--endpoint", srv.endpoint, "--peers", "host-a,host-b,host-c"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    for node in ("host-a", "host-b", "host-c"):
        assert node in out.stdout, out.stdout
    assert "LIVE" in out.stdout, out.stdout
finally:
    srv.shutdown()
print("top --once rendered all 3 hosts")
PYEOF
then
  echo "=== top smoke passed"
else
  echo "=== top smoke FAILED"
  fail=1
fi

# Fault-injection smoke (ISSUE 4): an env-var fault must drive the WHOLE
# recovery loop — NaN injected, rollback taken, recovery counter moves.
echo "=== fault-injection smoke: env-driven NaN -> rollback"
smoke_dir=$(mktemp -d)
if DS_FAULTS="nan_loss@3" JAX_PLATFORMS=cpu python - "$smoke_dir" <<'PYEOF'
import sys

import jax.numpy as jnp
import numpy as np

import deepspeed_tpu as dst
from deepspeed_tpu.parallel import MeshLayout
from deepspeed_tpu.utils import groups

out = sys.argv[1]
mesh = groups.initialize_mesh(MeshLayout.infer(1, dp=1))
rng = np.random.default_rng(0)
params = {"w": jnp.asarray(rng.normal(size=(8, 1)).astype(np.float32))}
cfg = {"train_micro_batch_size_per_gpu": 4,
       "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
       "steps_per_print": 0,
       "telemetry": {"enabled": True, "output_path": out, "job_name": "smoke",
                     "flight_recorder": {"install_handlers": False}},
       "resilience": {"enabled": True, "snapshot_interval": 1,
                      "snapshot_dir": out + "/snaps", "flush_engine": "sync",
                      "backoff_base_s": 0.0}}
engine, *_ = dst.initialize(model=lambda p, b: jnp.mean((b[0] @ p["w"] - b[1]) ** 2),
                            model_parameters=params, config=cfg, mesh=mesh)
i = 0
while engine.global_steps < 5:
    x = jnp.asarray(np.random.default_rng(i).normal(size=(4, 8)).astype(np.float32))
    engine.train_step((x, jnp.zeros((4, 1), jnp.float32)))
    i += 1
from deepspeed_tpu.telemetry import get_telemetry, parse_prometheus_text

parsed = parse_prometheus_text(get_telemetry().prometheus_text())
assert parsed["resilience_faults_injected_total"] >= 1, parsed
assert parsed["resilience_rollbacks_total"] >= 1, parsed
assert float(engine.last_metrics["loss"]) == float(engine.last_metrics["loss"])  # finite again
print("fault smoke: rollback recovered, counters:",
      {k: v for k, v in parsed.items() if k.startswith("resilience")})
PYEOF
then
  echo "=== fault smoke passed"
else
  echo "=== fault smoke FAILED"
  fail=1
fi
# the snapshot CLI must read the smoke run's artifacts cleanly — and
# the offline reshard pre-check (ISSUE 10) must answer "can I resume
# this on 3 hosts?" without starting an engine (exit 0: the smoke run's
# full-coverage 1-device snapshot reshards onto any world)
if python -m deepspeed_tpu.resilience ls "$smoke_dir/snaps" >/dev/null \
   && python -m deepspeed_tpu.resilience verify "$smoke_dir/snaps" >/dev/null \
   && python -m deepspeed_tpu.resilience verify "$smoke_dir/snaps" \
        --target-mesh 3 >/dev/null \
   && python -m deepspeed_tpu.resilience faults \
        | grep -q "sigstop_hang"; then
  echo "=== resilience CLI smoke passed"
else
  echo "=== resilience CLI smoke FAILED"
  fail=1
fi
rm -rf "$smoke_dir"

# Memory-plane CLI smoke (ISSUE 7): a ledger-carrying bundle must `mem
# show` cleanly and `mem diff` against itself must exit 0 (and a grown
# pool must verdict-exit 3 — the scriptable leak gate).
echo "=== mem CLI smoke: show / diff exit codes"
smoke_dir=$(mktemp -d)
mem_ok=1
bundles=$(python - "$smoke_dir" <<'PYEOF'
import sys
from deepspeed_tpu.telemetry import FlightRecorder
from deepspeed_tpu.telemetry.memory import get_memory_ledger

led = get_memory_ledger()
led.configure(enabled=True)
led.register("params", "p", 2 << 30)
fr = FlightRecorder(output_path=sys.argv[1])
fr.register_context("memory", led.snapshot)
a = fr.dump("mem smoke A")
led.register("snapshot", "t0", 4 << 30, space="host")
b = fr.dump("mem smoke B")
print(a)
print(b)
PYEOF
)
bundle_a=$(echo "$bundles" | tail -2 | head -1)
bundle_b=$(echo "$bundles" | tail -1)
python -m deepspeed_tpu.telemetry mem show "$bundle_a" >/dev/null || mem_ok=0
python -m deepspeed_tpu.telemetry mem diff "$bundle_a" "$bundle_a" \
    >/dev/null || mem_ok=0
python -m deepspeed_tpu.telemetry mem diff "$bundle_a" "$bundle_b" >/dev/null
[ $? -eq 3 ] || mem_ok=0
if [ $mem_ok -eq 1 ]; then
  echo "=== mem CLI smoke passed"
else
  echo "=== mem CLI smoke FAILED"
  fail=1
fi
rm -rf "$smoke_dir"

# Serving CLI smoke (ISSUE 8): the dry-run bench (real scheduler +
# prefix cache + front-end on synthetic replicas, zero device work)
# must emit the gated serving metrics cleanly.
echo "=== serving CLI smoke: bench --dry-run"
serving_line=$(JAX_PLATFORMS=cpu python -m deepspeed_tpu.serving bench \
    --dry-run --interactive 4 --background 2 2>/dev/null | tail -1)
if echo "$serving_line" | python -c '
import json, sys

line = json.loads(sys.stdin.read())
for key in ("serving_p99_ttft_ms", "prefix_hit_rate",
            "tok_s_interactive", "tok_s_background"):
    assert key in line, key
assert line["requests_completed"] == line["requests_submitted"] == 6, line
'; then
  echo "=== serving CLI smoke passed"
else
  echo "=== serving CLI smoke FAILED"
  fail=1
fi

# Front-door CLI smoke (ISSUE 14): `serve --dry-run` must boot the
# HTTP/SSE front door over synthetic replicas, answer its own health
# probe, and shut down cleanly — one parseable JSON line, exit 0.
echo "=== front-door CLI smoke: serve --dry-run"
frontdoor_line=$(JAX_PLATFORMS=cpu python -m deepspeed_tpu.serving serve \
    --dry-run 2>/dev/null | tail -1)
if echo "$frontdoor_line" | python -c '
import json, sys

line = json.loads(sys.stdin.read())
assert line["ok"] is True, line
assert line["healthz"]["healthy_replicas"] >= 1, line
'; then
  echo "=== front-door smoke passed"
else
  echo "=== front-door smoke FAILED"
  fail=1
fi

# Request-trace CLI smoke (ISSUE 15): a dry-run request pushed through
# the rollup transport must assemble into a timeline (`serving trace
# <id>` exit 0); an unknown id must exit 3, not crash.
echo "=== serving trace smoke: assembled timeline / unknown id"
trace_ok=1
JAX_PLATFORMS=cpu python - <<'PYEOF' || trace_ok=0
import subprocess
import sys

from deepspeed_tpu.elasticity.rendezvous import (RendezvousClient,
                                                 RendezvousServer)
from deepspeed_tpu.inference.v2 import KVCacheConfig
from deepspeed_tpu.serving import (Replica, ServingFrontend,
                                   SyntheticEngine, get_request_log)
from deepspeed_tpu.telemetry import get_telemetry, push_node_telemetry

srv = RendezvousServer()
try:
    c = RendezvousClient(srv.endpoint)
    get_telemetry().configure(enabled=True, jsonl=False, prometheus=False)
    get_request_log().reset()
    cc = KVCacheConfig(num_blocks=64, block_size=16, max_seq_len=256)
    fe = ServingFrontend([Replica(SyntheticEngine(cc), 0)])
    h = fe.submit([1, 2, 3, 4], max_new_tokens=6,
                  trace_id="smoke-trace-01")
    fe.run_until_idle()
    assert h.status == "done", h.status
    push_node_telemetry(c, "door")
    out = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.serving", "trace",
         "smoke-trace-01", "--endpoint", srv.endpoint],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "smoke-trace-01" in out.stdout, out.stdout
    assert "admitted" in out.stdout, out.stdout
    unknown = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.serving", "trace",
         "no-such-trace", "--endpoint", srv.endpoint],
        capture_output=True, text=True, timeout=120)
    assert unknown.returncode == 3, (unknown.returncode,
                                     unknown.stdout + unknown.stderr)
finally:
    srv.shutdown()
print("serving trace smoke: timeline assembled, unknown id exits 3")
PYEOF
if [ $trace_ok -eq 1 ]; then
  echo "=== serving trace smoke passed"
else
  echo "=== serving trace smoke FAILED"
  fail=1
fi

# Replay smoke (ISSUE 16): `serving bench --replay` must re-issue the
# checked-in diurnal access log against an ephemeral real fleet and
# emit a parseable fidelity report carrying the sentinel-gated keys
# (including the SLO burn figure the perf baseline gates).
echo "=== serving replay smoke: bench --replay (diurnal fixture)"
replay_line=$(JAX_PLATFORMS=cpu python -m deepspeed_tpu.serving bench \
    --replay tests/fixtures/serving/diurnal_access.log --speed 20 \
    --max-requests 40 2>/dev/null | tail -1)
if echo "$replay_line" | python -c '
import json, sys

line = json.loads(sys.stdin.read())
assert line["replayed"] == 40, line
assert not line["aborted"], line
for key in ("recorded", "achieved", "diff", "within_tolerance",
            "serving_net_qps_sustained", "serving_slo_burn_rate_p99"):
    assert key in line, key
assert line["achieved"]["failed"] == 0, line["achieved"]
'; then
  echo "=== serving replay smoke passed"
else
  echo "=== serving replay smoke FAILED"
  fail=1
fi

# Step-anatomy CLI smoke (ISSUE 17): a dry-run capture (tiny probe,
# one fenced step, real profiler session) must classify its own trace
# and `anatomy show` must render the bucket table + roofline join.
echo "=== anatomy CLI smoke: capture --dry-run / show"
smoke_dir=$(mktemp -d)
anatomy_ok=1
JAX_PLATFORMS=cpu python -m deepspeed_tpu.telemetry anatomy capture \
    --dry-run --out "$smoke_dir/anat" >/dev/null || anatomy_ok=0
python -m deepspeed_tpu.telemetry anatomy show "$smoke_dir/anat" \
    | grep -q "comm_fraction" || anatomy_ok=0
if [ $anatomy_ok -eq 1 ]; then
  echo "=== anatomy CLI smoke passed"
else
  echo "=== anatomy CLI smoke FAILED"
  fail=1
fi
rm -rf "$smoke_dir"

# Fleet-profiler smoke (ISSUE 20): ONE `telemetry profile capture`
# against a real 2-process CPU gang on the production path must merge
# both ranks' device lanes into cluster_trace.json and write the
# measured-vs-modeled calibration report — the operator loop end to end.
echo "=== fleet profiler smoke: telemetry profile capture (2-proc gang)"
smoke_dir=$(mktemp -d)
if JAX_PLATFORMS=cpu python - "$smoke_dir" <<'PYEOF'
import json
import os
import signal
import subprocess
import sys

from deepspeed_tpu.elasticity.rendezvous import RendezvousServer

out = sys.argv[1]
repo = os.getcwd()
worker = os.path.join(repo, "tests/unit/multiprocess/worker_profiler_gang.py")
srv = RendezvousServer()
procs = []
try:
    for node in ("sm0", "sm1"):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env.update({"DS_RDZV_ENDPOINT": srv.endpoint,
                    "DS_ELASTIC_NODE_ID": node,
                    "DS_CALIBRATION_PATH": f"{out}/cal_{node}.json",
                    "T_REPO": repo, "T_OUT": out, "T_DEADLINE_S": "120",
                    "JAX_PLATFORMS": "cpu",
                    "PYTHONPATH": repo + os.pathsep
                    + env.get("PYTHONPATH", "")})
        procs.append(subprocess.Popen(
            [sys.executable, worker], env=env,
            stdout=open(f"{out}/{node}.log", "w"),
            stderr=subprocess.STDOUT, start_new_session=True))
    cli = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.telemetry", "profile",
         "capture", "--endpoint", srv.endpoint, "--steps", "2",
         "--lead", "2", "--nodes", "sm0,sm1",
         "--out", f"{out}/archive", "--timeout", "150"],
        env={**os.environ, "DS_CALIBRATION_PATH": f"{out}/cal_cli.json",
             "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=240)
    assert cli.returncode == 0, cli.stdout + cli.stderr
    trace = json.load(open(f"{out}/archive/cluster_trace.json"))
    hosts = trace["metadata"]["hosts"]
    for node in ("sm0", "sm1"):
        assert hosts[f"{node} (device)"]["events"] > 0, hosts
    rep = json.load(open(f"{out}/archive/calibration_report.json"))
    for node in ("sm0", "sm1"):
        assert rep["nodes"][node]["measured_step_ms"] > 0, rep
    assert "factors[" in cli.stdout, cli.stdout
finally:
    for p in procs:
        try:
            os.killpg(os.getpgid(p.pid), signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
    srv.shutdown()
print("fleet profiler smoke: both lanes merged, roofline calibrated")
PYEOF
then
  echo "=== fleet profiler smoke passed"
else
  echo "=== fleet profiler smoke FAILED"
  fail=1
fi
rm -rf "$smoke_dir"

# Perf-sentinel smoke (ISSUE 5): baseline-then-check on the same run
# must exit 0; a forced-regression fixture must exit 3.
echo "=== perf sentinel smoke: baseline / check exit codes"
smoke_dir=$(mktemp -d)
cat > "$smoke_dir/run.json" <<'EOF'
{"metric": "llama_110m_train_tokens_per_sec", "value": 35000.0,
 "unit": "tokens/sec/chip", "mfu": 0.42, "step_time_p50_ms": 120.0,
 "compile_time_s": 30.0, "goodput": 0.95}
EOF
cat > "$smoke_dir/regressed.json" <<'EOF'
{"metric": "llama_110m_train_tokens_per_sec", "value": 24000.0,
 "unit": "tokens/sec/chip", "mfu": 0.42, "step_time_p50_ms": 240.0,
 "compile_time_s": 30.0, "goodput": 0.95}
EOF
perf_ok=1
python -m deepspeed_tpu.telemetry perf baseline "$smoke_dir/run.json" \
    --out "$smoke_dir/base.json" >/dev/null || perf_ok=0
python -m deepspeed_tpu.telemetry perf check "$smoke_dir/run.json" \
    --baseline "$smoke_dir/base.json" >/dev/null || perf_ok=0
python -m deepspeed_tpu.telemetry perf check "$smoke_dir/regressed.json" \
    --baseline "$smoke_dir/base.json" >/dev/null
[ $? -eq 3 ] || perf_ok=0
if [ $perf_ok -eq 1 ]; then
  echo "=== perf sentinel smoke passed"
else
  echo "=== perf sentinel smoke FAILED"
  fail=1
fi
rm -rf "$smoke_dir"

# Tuning CLI smoke (ISSUE 9): the deterministic synthetic search must
# find the planted optimum, round-trip through show, and apply its
# overrides onto a base ds_config (the whole search → store → apply
# loop on CPU, no device work).
echo "=== tuning CLI smoke: search / show / apply round-trip"
smoke_dir=$(mktemp -d)
tuning_ok=1
tstore="$smoke_dir/store.json"
python -m deepspeed_tpu.tuning search --synthetic --store "$tstore" \
    >"$smoke_dir/search.json" || tuning_ok=0
tkey=$(python -c '
import json, sys

doc = json.load(open(sys.argv[1]))
assert doc["best"]["train_micro_batch_size_per_gpu"] == 8, doc["best"]
assert doc["best"]["zero_optimization.stage"] == 3, doc["best"]
print(doc["key"])
' "$smoke_dir/search.json") || tuning_ok=0
python -m deepspeed_tpu.tuning show --store "$tstore" --key "$tkey" \
    >/dev/null || tuning_ok=0
echo '{"optimizer": {"type": "AdamW"}}' > "$smoke_dir/ds_config.json"
python -m deepspeed_tpu.tuning apply --store "$tstore" --key "$tkey" \
    --config "$smoke_dir/ds_config.json" | python -c '
import json, sys

merged = json.load(sys.stdin)
assert merged["train_micro_batch_size_per_gpu"] == 8, merged
assert merged["zero_optimization"]["stage"] == 3, merged
assert merged["optimizer"]["type"] == "AdamW", merged
' || tuning_ok=0
# unknown key must be the structural-error exit, not a crash
python -m deepspeed_tpu.tuning show --store "$tstore" --key "no|such|key|x" \
    >/dev/null 2>&1
[ $? -eq 2 ] || tuning_ok=0
if [ $tuning_ok -eq 1 ]; then
  echo "=== tuning CLI smoke passed"
else
  echo "=== tuning CLI smoke FAILED"
  fail=1
fi
rm -rf "$smoke_dir"

# Static-analysis gate (ISSUE 6): dslint must run clean against the
# checked-in baseline — any NEW finding (untracked jit, raw collective,
# recompile hazard, host sync, silent except) fails the suite with the
# same exit-3 convention as the perf sentinel.
echo "=== dslint gate: analysis lint"
if python -m deepspeed_tpu.analysis lint; then
  echo "=== dslint gate passed"
else
  echo "=== dslint gate FAILED (new findings — fix, suppress, or baseline)"
  fail=1
fi
# Thread-safety smoke, UNscoped: the baseline already absorbs the
# reviewed findings (each with a written justification), and the audit
# demonstrably covers worker threads outside telemetry/resilience too
# (the swap_tensor _OptPipeline entry) — anything new gates.
echo "=== dslint races smoke"
if python -m deepspeed_tpu.analysis races; then
  echo "=== dslint races smoke passed"
else
  echo "=== dslint races smoke FAILED"
  fail=1
fi

echo "=== total passed: $total_pass; fail=$fail"
exit $fail
