"""Operator CLI — ``python -m deepspeed_tpu.telemetry <cmd>``.

The read side of the observability plane, for humans at 3am:

* ``collect``  — pull a cluster archive from a LIVE rendezvous store
  (or a shared-filesystem drop dir): request fresh bundles from every
  host, assemble one ``cluster-<utc>/`` archive + manifest.
* ``summary``  — one bundle OR one cluster archive: reason, last N
  steps, health events, slowest spans, desync verdict.
* ``diff``     — two hosts' bundles: step skew, comm-census deltas,
  ledger seq delta (the "which host is behind, doing what" question).
* ``desync``   — offline collective-divergence analysis over an
  archive's ledger tails; names the lagging rank and the first
  mismatched collective.  Exit code 3 when a desync is found (script-
  able), 0 when clean.
* ``perf``     — the perf-regression sentinel (``telemetry/perf``):
  ``perf show`` prints a run's sentinel metrics, ``perf baseline``
  stores them, ``perf check`` compares a run against the stored
  baseline and exits 3 on regression beyond tolerance — the gate that
  turns BENCH_r*.json from a log into a trajectory.  A no-data artifact
  (an r05-style environment failure) is *skipped with a named reason*,
  never a silent pass or a crash.
* ``mem``      — the memory plane (``telemetry/memory``): ``mem show``
  one bundle's pool breakdown, ``mem top`` its largest live arrays,
  ``mem diff`` two bundles with a leak verdict (exit 3).
* ``top``      — the LIVE cluster view (``telemetry/rollup.py``):
  per-node step / step-time EWMA / goodput / hbm / heartbeat age /
  store-outage counters rendered straight from the rendezvous store's
  rollup publications — no bundle collection, no engine.  ``--once``
  prints one frame and exits 0 (scriptable); default refreshes.

Every command except ``collect``/``top`` works on plain directories —
no store, no JAX device needed beyond what importing the package costs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional

from .aggregator import (CLUSTER_MANIFEST, CLUSTER_TRACE,
                         build_cluster_manifest, collect_cluster_archive,
                         collect_cluster_archive_fs, load_host_manifests)
from .collective_ledger import (find_first_divergence,
                                format_divergence_report)
from .flight_recorder import BUNDLE_MANIFEST, BUNDLE_TRACE


def _fail(msg: str) -> int:
    print(f"error: {msg}", file=sys.stderr)
    return 2


def _is_bundle(path: str) -> bool:
    return os.path.exists(os.path.join(path, BUNDLE_MANIFEST))


def _is_archive(path: str) -> bool:
    return (os.path.exists(os.path.join(path, CLUSTER_MANIFEST))
            or os.path.isdir(os.path.join(path, "hosts")))


def _resolve_bundle(path: str) -> Optional[str]:
    """Accept a bundle dir, or a dir holding exactly one ``bundle-*``
    (a host dir inside an archive, or a one-trip dump dir)."""
    if _is_bundle(path):
        return path
    if os.path.isdir(path):
        cands = sorted(d for d in os.listdir(path)
                       if _is_bundle(os.path.join(path, d)))
        if cands:
            return os.path.join(path, cands[-1])  # newest by name stamp
    return None


def _load_manifest(bundle: str) -> Dict[str, Any]:
    with open(os.path.join(bundle, BUNDLE_MANIFEST)) as fh:
        return json.load(fh)


def _slowest_spans(bundle: str, n: int = 5) -> List[Dict[str, Any]]:
    p = os.path.join(bundle, BUNDLE_TRACE)
    if not os.path.exists(p):
        return []
    try:
        with open(p) as fh:
            events = json.load(fh).get("traceEvents", [])
    except (OSError, ValueError):
        return []
    spans = [e for e in events if isinstance(e.get("dur"), (int, float))]
    spans.sort(key=lambda e: -e["dur"])
    return spans[:n]


# ---------------------------------------------------------------------------
# summary
# ---------------------------------------------------------------------------

def _print_bundle_summary(bundle: str, last_n: int) -> None:
    m = _load_manifest(bundle)
    print(f"bundle: {bundle}")
    print(f"  reason: {m.get('reason')}")
    print(f"  host: {m.get('host')}  pid: {m.get('pid')}  "
          f"time: {m.get('time_utc')}")
    steps = m.get("steps") or []
    print(f"  steps recorded: {len(steps)}")
    for s in steps[-last_n:]:
        print(f"    step {s.get('step')}: loss={s.get('loss')} "
              f"step_time_ms={s.get('step_time_ms')} "
              f"tokens/s={s.get('tokens_per_sec')}")
    health = m.get("health_events") or []
    print(f"  health events: {len(health)}")
    for h in health[-last_n:]:
        print(f"    {h.get('kind')}@step {h.get('step')}: "
              f"{h.get('message', '')}")
    led = (m.get("context") or {}).get("collective_ledger")
    if isinstance(led, dict):
        print(f"  collective ledger: seq {led.get('seq')} "
              f"tail_hash {led.get('tail_hash')} "
              f"(tail of {len(led.get('tail') or [])})")
        if led.get("exec_seq"):
            print(f"  exec-order census: seq {led.get('exec_seq')} "
                  f"tail_hash {led.get('exec_tail_hash')}")
    mem = (m.get("context") or {}).get("memory")
    if isinstance(mem, dict):
        from .memory.oom import _fmt_bytes, top_pools_of

        dev = mem.get("device") or {}
        line = "  memory:"
        if dev.get("bytes_limit"):
            line += (f" hbm {_fmt_bytes(dev.get('bytes_in_use', 0))}/"
                     f"{_fmt_bytes(dev['bytes_limit'])}")
        if mem.get("host_rss_bytes") is not None:
            line += f" rss {_fmt_bytes(mem['host_rss_bytes'])}"
        if mem.get("tracked_bytes"):
            line += f" tracked {_fmt_bytes(mem['tracked_bytes'])}"
        top = top_pools_of(mem)
        if top:
            line += " — top: " + ", ".join(
                f"{p}={_fmt_bytes(n)}" for p, n in top)
        print(line)
        if mem.get("device_unresponsive"):
            print(f"    DEVICE UNRESPONSIVE: {mem['device_unresponsive']}")
    gp = (m.get("context") or {}).get("goodput")
    if isinstance(gp, dict):
        buckets = gp.get("buckets_s") or {}
        budget = "  ".join(f"{k}={v:.1f}s" for k, v in sorted(
            buckets.items()) if v)
        print(f"  goodput: {gp.get('goodput')} "
              f"(rolling {gp.get('rolling_goodput')})"
              + (f" — {budget}" if budget else ""))
    ct = (m.get("context") or {}).get("compile_programs")
    if isinstance(ct, dict):
        print(f"  compiles: {ct.get('events_total')} events "
              f"({ct.get('recompiles_total')} recompiles, "
              f"{float(ct.get('time_ms_total') or 0) / 1e3:.1f}s)")
        for site, progs in sorted((ct.get("sites") or {}).items()):
            for p in progs:
                if p.get("kind") != "recompile":
                    continue
                from .perf.compile_tracker import format_cause

                causes = "; ".join(
                    format_cause(c) for c in (p.get("causes") or [])[:3])
                print(f"    RECOMPILE {site} #{p.get('program')}: "
                      f"{causes or 'unknown cause'}")
    spans = _slowest_spans(bundle)
    if spans:
        print("  slowest spans:")
        for e in spans:
            print(f"    {e.get('name')}: {e['dur'] / 1e3:.3f} ms")
    ann = m.get("annotations") or []
    if ann:
        print(f"  annotations: {len(ann)} "
              f"(last: {ann[-1].get('kind')})")


def _print_archive_summary(archive: str, last_n: int) -> int:
    mp = os.path.join(archive, CLUSTER_MANIFEST)
    if os.path.exists(mp):
        with open(mp) as fh:
            cm = json.load(fh)
    else:  # hand-assembled archive (shared-FS copy) — compute in memory;
        # summary is a READ command and must work on a read-only mount
        cm = build_cluster_manifest(archive, persist=False)
    print(f"cluster archive: {archive}")
    print(f"  created: {cm.get('created_utc')}  "
          f"hosts: {len(cm.get('hosts') or {})}  "
          f"missing: {cm.get('missing_hosts') or 'none'}")
    ct_path = os.path.join(archive, CLUSTER_TRACE)
    if os.path.exists(ct_path):
        try:
            with open(ct_path) as fh:
                hosts_meta = (json.load(fh).get("metadata")
                              or {}).get("hosts") or {}
            aligned = sum(1 for h in hosts_meta.values()
                          if h.get("aligned"))
            print(f"  merged trace: {CLUSTER_TRACE} "
                  f"({len(hosts_meta)} lanes, {aligned} clock-aligned)")
        except (OSError, ValueError):
            print(f"  merged trace: {CLUSTER_TRACE} (unreadable)")
    partials = cm.get("partials") or {}
    for node in cm.get("missing_hosts") or []:
        p = partials.get(node)
        if p:
            live = p.get("liveness") or {}
            print(f"  [{node}] PARTIAL only (watchdog trip "
                  f"#{p.get('trips')}): step {live.get('step')} "
                  f"coll_seq {live.get('coll_seq')} — see "
                  f"hosts/{node}/partial.json")
    print(f"  step skew across hosts: {cm.get('step_skew')}")
    if cm.get("goodput_min") is not None:
        print(f"  cluster goodput: min {cm.get('goodput_min')} "
              f"mean {round(cm.get('goodput_mean'), 4)}")
    for node, h in sorted((cm.get("hosts") or {}).items()):
        gp = (f" goodput {h.get('goodput')}"
              if h.get("goodput") is not None else "")
        mem = h.get("memory") or {}
        mm = (f" hbm {mem['hbm_frac']:.0%}"
              if mem.get("hbm_frac") is not None else "")
        print(f"  [{node}] step {h.get('last_step')} "
              f"ledger_seq {h.get('ledger_seq')} "
              f"comm_ops {h.get('comm_ops')}{gp}{mm} — {h.get('reason')}")
        if mem.get("device_unresponsive"):
            print(f"    [{node}] DEVICE UNRESPONSIVE: "
                  f"{mem['device_unresponsive']}")
    deltas = cm.get("comm_census_delta") or {}
    skewed = {op: d for op, d in deltas.items() if d.get("delta")}
    if skewed:
        print("  comm census deltas (op: max-min call count):")
        for op, d in sorted(skewed.items()):
            print(f"    {op}: {d['delta']} {d['per_host']}")
    print("  desync analysis:")
    for line in (cm.get("desync_report") or "").splitlines():
        print(f"    {line}")
    hosts_dir = os.path.join(archive, "hosts")
    if os.path.isdir(hosts_dir):
        for node in sorted(os.listdir(hosts_dir)):
            b = _resolve_bundle(os.path.join(hosts_dir, node))
            if b:
                print()
                _print_bundle_summary(b, last_n)
    return 0


def cmd_summary(args: argparse.Namespace) -> int:
    path = args.path
    if _is_archive(path):
        return _print_archive_summary(path, args.steps)
    bundle = _resolve_bundle(path)
    if bundle is None:
        return _fail(f"{path}: neither a debug bundle nor a cluster archive")
    _print_bundle_summary(bundle, args.steps)
    return 0


# ---------------------------------------------------------------------------
# diff
# ---------------------------------------------------------------------------

def cmd_diff(args: argparse.Namespace) -> int:
    a, b = _resolve_bundle(args.a), _resolve_bundle(args.b)
    if a is None or b is None:
        return _fail("diff needs two debug bundle directories")
    ma, mb = _load_manifest(a), _load_manifest(b)

    def last_step(m):
        steps = m.get("steps") or []
        return steps[-1].get("step") if steps else None

    la, lb = last_step(ma), last_step(mb)
    print(f"A: {a}\n   reason: {ma.get('reason')}  last step: {la}")
    print(f"B: {b}\n   reason: {mb.get('reason')}  last step: {lb}")
    if isinstance(la, (int, float)) and isinstance(lb, (int, float)):
        print(f"step skew (A-B): {la - lb}")
    ca = (ma.get("comm") or {}).get("summary") or {}
    cb = (mb.get("comm") or {}).get("summary") or {}
    ops = sorted(set(ca) | set(cb))
    if ops:
        print("comm census (op: A count / B count / delta):")
        for op in ops:
            na = float((ca.get(op) or {}).get("count", 0))
            nb = float((cb.get(op) or {}).get("count", 0))
            print(f"  {op}: {na:g} / {nb:g} / {na - nb:+g}")
    la_led = (ma.get("context") or {}).get("collective_ledger") or {}
    lb_led = (mb.get("context") or {}).get("collective_ledger") or {}
    if la_led or lb_led:
        print(f"collective ledger: A seq {la_led.get('seq')} "
              f"hash {la_led.get('tail_hash')} | "
              f"B seq {lb_led.get('seq')} hash {lb_led.get('tail_hash')}")
        tails = {}
        if la_led.get("tail"):
            tails["A"] = la_led["tail"]
        if lb_led.get("tail"):
            tails["B"] = lb_led["tail"]
        if len(tails) == 2:
            print(format_divergence_report(find_first_divergence(tails)))
    return 0


# ---------------------------------------------------------------------------
# desync
# ---------------------------------------------------------------------------

def cmd_desync(args: argparse.Namespace) -> int:
    if not _is_archive(args.archive):
        return _fail(f"{args.archive}: not a cluster archive")
    manifests = load_host_manifests(args.archive)
    if not manifests:
        return _fail(f"{args.archive}: no host bundles found")
    # same filter as the cluster manifest (aggregator._ledger_tails):
    # a host whose bundle has NO ledger context (ledger off / pre-ledger
    # bundle) must not enter the analysis as an empty ledger — it would
    # read as "lagging by everything".  A PRESENT-but-empty tail is real
    # data ("this host never issued a collective") and stays in.
    tails = {}
    no_ledger = []
    for node, m in manifests.items():
        tail = ((m.get("context") or {}).get("collective_ledger") or {}) \
            .get("tail")
        if isinstance(tail, list):
            tails[node] = tail
        else:
            no_ledger.append(node)
    if no_ledger:
        print(f"(no ledger data from: {', '.join(sorted(no_ledger))} — "
              f"excluded from the analysis)")
    report = find_first_divergence(tails)
    print(format_divergence_report(report))
    return 3 if report.get("desync") else 0


# ---------------------------------------------------------------------------
# collect
# ---------------------------------------------------------------------------

def cmd_collect(args: argparse.Namespace) -> int:
    if args.shared_fs:
        archive = collect_cluster_archive_fs(args.shared_fs,
                                             out_dir=args.out)
        print(archive)
        return 0
    if not args.endpoint:
        return _fail("collect needs --endpoint host:port (live store) "
                     "or --shared-fs <dir>")
    from ..elasticity.rendezvous import RendezvousClient

    client = RendezvousClient(args.endpoint)
    peers = ([p for p in args.peers.split(",") if p]
             if args.peers else None)
    try:
        archive = collect_cluster_archive(
            client, peer_ids=peers, out_dir=args.out,
            timeout_s=args.timeout, request=not args.no_request)
    except (ValueError, ConnectionError, OSError) as e:
        return _fail(str(e))
    print(archive)
    return 0


# ---------------------------------------------------------------------------
# top — the live cluster view (ISSUE 13)
# ---------------------------------------------------------------------------

def _render_serving_rows(client: Any, silent_after_s: float = 30.0
                         ) -> str:
    """The serving-worker table for ``top --serving`` (ISSUE 15
    satellite): registered workers (``serving/srv/*``), endpoint
    health from heartbeat age, live load from the rollup-labeled
    gauges each worker publishes.  Everything is already in the store
    — this just renders it."""
    from .aggregator import _heartbeat_view
    from .rollup import collect_rollup

    # lazy: the serving plane is optional at `top` time
    from ..serving.worker import SRV_PREFIX

    regs: Dict[str, Dict[str, Any]] = {}
    for key in sorted(client.keys(SRV_PREFIX)):
        v = client.get(key)
        if isinstance(v, dict):
            regs[key[len(SRV_PREFIX):]] = v

    def _slo_block() -> str:
        # the front door publishes serving/slo_* gauges through the
        # same rollup (ISSUE 16) — collect over every publisher, not
        # just registered workers, or the door's lane is invisible
        from ..serving.slo import render_slo_table, slo_rows_from_rollup

        pub = sorted(k.rsplit("/", 1)[1]
                     for k in client.keys("telemetry/metrics/"))
        if not pub:
            return ""
        rows = slo_rows_from_rollup(collect_rollup(client, pub))
        return render_slo_table(rows) if rows else ""

    if not regs:
        slo = _slo_block()
        return ("serving workers: none registered"
                + ("\n\n" + slo if slo else ""))
    ids = sorted(regs)
    rollup = collect_rollup(client, ids)
    hb = _heartbeat_view(client, ids)
    lines = [f"{'WORKER':<14} {'ROLE':<8} {'ENDPOINT':<22} "
             f"{'ACTIVE':>6} {'QUEUED':>6} {'TOK/S':>8} {'REQS':>7} "
             f"{'HB_AGE':>7} {'STATE':<8}"]

    def g(doc, name):
        snap = (doc or {}).get("snapshot") or {}
        m = (snap.get("gauges") or {}).get(name)
        return None if m is None else float(m.get("value", 0.0))

    def c(doc, name):
        snap = (doc or {}).get("snapshot") or {}
        m = (snap.get("counters") or {}).get(name)
        return None if m is None else float(m.get("value", 0.0))

    from .rollup import _fmt

    for wid in ids:
        reg = regs[wid]
        doc = rollup.node_doc(wid)
        age = (hb.get(wid) or {}).get("age_s")
        state = ("SILENT" if age is None or age > silent_after_s
                 else "LIVE")
        reqs = (c(doc, "serving/worker_requests_total")
                or c(doc, "serving/worker_prefills_total"))
        lines.append(
            f"{wid:<14} {str(reg.get('role', '?')):<8} "
            f"{str(reg.get('endpoint', '?')):<22} "
            f"{_fmt(g(doc, 'serving/worker_active'), '{:.0f}'):>6} "
            f"{_fmt(g(doc, 'serving/worker_queued'), '{:.0f}'):>6} "
            f"{_fmt(g(doc, 'serving/worker_tok_s'), '{:.1f}'):>8} "
            f"{_fmt(reqs, '{:.0f}'):>7} "
            f"{_fmt(age, '{:.1f}'):>7} "
            f"{state:<8}")
    slo = _slo_block()
    if slo:
        lines.append("")
        lines.append(slo)
    return "\n".join(lines)


def _render_top_frame(client: Any, peers: Optional[List[str]],
                      endpoint: str, silent_after_s: float = 30.0,
                      serving: bool = False) -> str:
    from .aggregator import _heartbeat_view, sealed_members
    from .rollup import collect_rollup, render_top

    peer_ids = peers or sealed_members(client)
    if not peer_ids:
        # no sealed round yet: fall back to whoever has published
        # telemetry (a gang mid-formation is still worth watching)
        peer_ids = sorted(k.rsplit("/", 1)[1]
                          for k in client.keys("telemetry/metrics/"))
    if not peer_ids and not serving:
        raise ValueError("no peers: store has no sealed round and no "
                         "telemetry publications (pass --peers)")
    frame = ""
    if peer_ids:
        rollup = collect_rollup(client, peer_ids)
        hb = _heartbeat_view(client, peer_ids)
        store_info = {"endpoint": endpoint,
                      "generation": client.get("srv/gen"),
                      "round": client.get("rdzv/round")}
        frame = render_top(rollup, hb_view=hb, store_info=store_info,
                           silent_after_s=silent_after_s)
    if serving:
        block = _render_serving_rows(client,
                                     silent_after_s=silent_after_s)
        frame = (frame + "\n\n" + block) if frame else block
    return frame


def cmd_top(args: argparse.Namespace) -> int:
    if not args.endpoint:
        return _fail("top needs --endpoint host:port "
                     "(or $DS_RDZV_ENDPOINT)")
    import time as _time

    from ..elasticity.rendezvous import RendezvousClient

    client = RendezvousClient(args.endpoint, retries=1, backoff_s=0.05)
    peers = [p for p in (args.peers or "").split(",") if p] or None
    frames = 0
    try:
        while True:
            try:
                frame = _render_top_frame(client, peers, args.endpoint,
                                          silent_after_s=args.silent_after,
                                          serving=getattr(args, "serving",
                                                          False))
            except (ValueError, ConnectionError, OSError) as e:
                return _fail(f"top: {e}")
            if frames:
                print()  # frame separator (no TTY games — pipe-friendly)
            print(f"--- {_time.strftime('%H:%M:%S')}")
            print(frame, flush=True)
            frames += 1
            if args.once or (args.frames and frames >= args.frames):
                return 0
            _time.sleep(max(args.interval, 0.1))
    except KeyboardInterrupt:
        return 0


# ---------------------------------------------------------------------------
# perf — the regression sentinel
# ---------------------------------------------------------------------------

def cmd_perf(args: argparse.Namespace) -> int:
    from .perf import baseline as perfmod

    try:
        run = perfmod.load_run(args.run)
    except (OSError, ValueError) as e:
        return _fail(f"perf {args.perf_cmd}: {e}")
    metrics = perfmod.extract_perf(run)

    if args.perf_cmd == "show":
        # satellite (ISSUE 13): an environment-failure artifact (no
        # device answered — value 0.0 + error, or the explicit marker)
        # is a SKIPPED round and must say so — `check` already understood
        # the marker, but `show` used to render 0.0 as if measured
        reason = perfmod.environment_failure_reason(run)
        if reason:
            print(f"run: {args.run}")
            print(f"  SKIPPED round — environment failure: {reason}")
            print("  (no metrics were measured; values in this artifact "
                  "are placeholders, not results)")
            return 0
        if not metrics:
            return _fail(f"{args.run}: no sentinel metrics "
                         f"({', '.join(perfmod.PERF_METRICS)})")
        print(f"run: {args.run}")
        for name in perfmod.PERF_METRICS:
            if name in metrics:
                print(f"  {name}: {metrics[name]:g}")
        return 0

    if args.perf_cmd == "baseline":
        try:
            doc = perfmod.save_baseline(args.out, run, source=args.run)
        except ValueError as e:
            return _fail(str(e))
        print(f"baseline written: {args.out} "
              f"({', '.join(sorted(doc['metrics']))})")
        return 0

    # check
    if not metrics:
        # a run that produced NO sentinel metrics: an environment
        # failure (no device answered: value 0.0 + error) is a SKIP with a
        # named reason — the bench never ran, so there is nothing to
        # gate; anything else stays an error (a healthy run without
        # metrics is a wiring bug the operator must see)
        reason = perfmod.environment_failure_reason(run)
        if reason:
            print(f"perf check SKIPPED: run artifact carries no data — "
                  f"environment failure ({reason}); nothing to gate")
            return 0
        return _fail(f"{args.run}: no sentinel metrics and no "
                     f"environment-failure marker — not a bench artifact?")
    try:
        base = perfmod.load_baseline(args.baseline)
    except OSError as e:
        return _fail(f"perf check: cannot read baseline "
                     f"{args.baseline} ({e}); run `perf baseline` first")
    try:
        tol = perfmod.parse_tolerances(args.tol)
    except ValueError as e:
        return _fail(str(e))
    result = perfmod.check_regression(metrics, base, tolerances=tol)
    print(perfmod.format_check_report(result))
    if not result["compared"]:
        return _fail("perf check: run and baseline share no metrics")
    if result["regressions"]:
        print(f"PERF REGRESSION: {len(result['regressions'])} metric(s) "
              f"beyond tolerance vs {args.baseline}")
        return 3
    print("perf check passed")
    return 0


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------

def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m deepspeed_tpu.telemetry",
        description="cluster observability: collect / summarize / diff "
                    "debug bundles, analyze collective desync")
    sub = p.add_subparsers(dest="cmd", required=True)

    c = sub.add_parser("collect", help="pull a cluster archive from a live "
                                       "rendezvous store or a shared FS dir")
    c.add_argument("--endpoint", default=os.environ.get("DS_RDZV_ENDPOINT"),
                   help="rendezvous store host:port "
                        "(default: $DS_RDZV_ENDPOINT)")
    c.add_argument("--peers", default="",
                   help="comma-separated node ids (default: the store's "
                        "current sealed round)")
    c.add_argument("--out", default="cluster_archives")
    c.add_argument("--timeout", type=float, default=30.0)
    c.add_argument("--no-request", action="store_true",
                   help="take already-published bundles as-is instead of "
                        "requesting fresh dumps")
    c.add_argument("--shared-fs", default="",
                   help="assemble from a shared-filesystem drop dir "
                        "instead of a live store")
    c.set_defaults(fn=cmd_collect)

    s = sub.add_parser("summary", help="summarize a bundle or archive")
    s.add_argument("path")
    s.add_argument("--steps", type=int, default=5,
                   help="last N steps/events to print")
    s.set_defaults(fn=cmd_summary)

    d = sub.add_parser("diff", help="compare two hosts' bundles")
    d.add_argument("a")
    d.add_argument("b")
    d.set_defaults(fn=cmd_diff)

    y = sub.add_parser("desync", help="offline collective-divergence "
                                      "analysis over an archive "
                                      "(exit 3 when desync found)")
    y.add_argument("archive")
    y.set_defaults(fn=cmd_desync)

    t = sub.add_parser("top", help="live cluster view from the store's "
                                   "metrics rollup (no bundles)")
    t.add_argument("--endpoint", default=os.environ.get("DS_RDZV_ENDPOINT"),
                   help="rendezvous store host:port "
                        "(default: $DS_RDZV_ENDPOINT)")
    t.add_argument("--peers", default="",
                   help="comma-separated node ids (default: the store's "
                        "current sealed round, else every publishing node)")
    t.add_argument("--once", action="store_true",
                   help="print one frame and exit 0")
    t.add_argument("--interval", type=float, default=2.0,
                   help="refresh period in seconds")
    t.add_argument("--frames", type=int, default=0,
                   help="stop after N frames (0 = until interrupted)")
    t.add_argument("--silent-after", type=float, default=30.0,
                   help="heartbeat age (s) past which a node renders "
                        "SILENT")
    t.add_argument("--serving", action="store_true",
                   help="also render registered serving workers (role, "
                        "endpoint health, active/queued, tok/s) from "
                        "the store")
    t.set_defaults(fn=cmd_top)

    from .perf.baseline import DEFAULT_BASELINE

    f = sub.add_parser("perf", help="perf-regression sentinel: show/"
                                    "baseline/check bench runs "
                                    "(check exits 3 on regression)")
    fsub = f.add_subparsers(dest="perf_cmd", required=True)
    fs = fsub.add_parser("show", help="print a run's sentinel metrics")
    fs.add_argument("run", help="bench JSON line, BENCH_r*.json artifact, "
                                "or saved baseline")
    fs.set_defaults(fn=cmd_perf)
    fb = fsub.add_parser("baseline", help="store a run as the baseline")
    fb.add_argument("run")
    fb.add_argument("--out", default=DEFAULT_BASELINE,
                    help=f"baseline file (default: {DEFAULT_BASELINE})")
    fb.set_defaults(fn=cmd_perf)
    fc = fsub.add_parser("check", help="compare a run vs the baseline; "
                                       "exit 3 on regression")
    fc.add_argument("run")
    fc.add_argument("--baseline", default=DEFAULT_BASELINE)
    fc.add_argument("--tol", action="append", default=[],
                    metavar="METRIC=FRAC",
                    help="override a tolerance, e.g. --tol mfu=0.05 "
                         "(repeatable)")
    fc.set_defaults(fn=cmd_perf)

    from .memory.cli import add_mem_parser

    add_mem_parser(sub)

    from .anatomy.cli import add_anatomy_parser

    add_anatomy_parser(sub)

    from .numerics.cli import add_numerics_parser

    add_numerics_parser(sub)

    from .profiler.cli import add_profile_parser

    add_profile_parser(sub)
    return p


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    return args.fn(args)
