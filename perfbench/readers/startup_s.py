"""One part of the set-up, as the program itself tells it: its start-up
record (the spans of ``initialize()`` / ``build_serving_frontend()`` and
each program's first call, kept whether its telemetry hub is on or off)
and its compile account (what JAX reported of every trace, lowering and
compile since the program's first module was imported), both read off the
program's process-wide hub and tracker.

The set-up runs from ``t_start = t_open - setup_s`` to ``t_open`` on the
runner's clock, which is the record's (``time.perf_counter()``); every
span is clipped to it and every counted event ended in it.  ``part``:

    before_entry   t_start to the first root's start: the harness's share
                   (interpreter, JAX, the device gate, the model built,
                   weights from the seed, a training cell's reference)
    entry          the roots, added up: the program's entry point
    after_entry    the rest: the last root's end to t_open (first calls,
                   warm-up, the serving check, the ramp), and what lies
                   between two roots.  The three add up to ``setup_s``
    import         the import spans under the roots, and the package's own
    placement      the placement spans (weights, optimizer state, pools)
    first_calls    each program's first call, wherever it falls
    trace_lower    the account's tracing and lowering seconds
    compile_load   the account's backend compiles, cache reads included
    cache_misses   programs compiled and written to the persistent cache

A program that keeps no such record (an older one) gives None for every
part, and so does a window with no root in it.
"""

ROOTS = ("startup/initialize", "startup/serving_frontend")


def _sources():
    try:
        from deepspeed_tpu.telemetry import get_compile_tracker, get_telemetry

        return (get_telemetry().startup.events(),
                get_compile_tracker().account)
    except (ImportError, AttributeError):
        return None


def _within(event, lo, hi):
    """Seconds of ``event`` that lie in ``[lo, hi]``."""
    return max(min(event["end"], hi) - max(event["start"], lo), 0.0)


def parts(events, account, t_start, t_open):
    """Every part at once, or None where no root lies in the window."""
    roots = sorted((e for e in events if e["name"] in ROOTS
                    and not e["args"].get("depth")
                    and _within(e, t_start, t_open) > 0.0),
                   key=lambda e: e["start"])
    if not roots:
        return None
    named = lambda test: sum(_within(e, t_start, t_open)
                             for e in events if test(e))
    under_a_root = lambda e: any(r["start"] <= e["start"]
                                 and e["end"] <= r["end"] for r in roots)
    made = account.sums(t_start, t_open)
    before = max(roots[0]["start"], t_start) - t_start
    entry = sum(_within(r, t_start, t_open) for r in roots)
    return {
        "before_entry": before, "entry": entry,
        "after_entry": (t_open - t_start) - before - entry,
        "import": named(lambda e: (e["name"] == "startup/import"
                                   and under_a_root(e))
                        or e["name"] == "startup/package_import"),
        "placement": named(lambda e: e["name"].startswith("startup/place/")),
        "first_calls": named(lambda e: e["name"] == "startup/first_call"),
        "trace_lower": made["trace_s"] + made["lower_s"],
        "compile_load": made["compile_s"],
        "cache_misses": made["cache_misses"],
    }


def read(obs, args):
    sources = _sources()
    if sources is None or obs.get("setup_s") is None:
        return None
    found = parts(*sources, obs["t_open"] - obs["setup_s"], obs["t_open"])
    return None if found is None else found[args["part"]]
