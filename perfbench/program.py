"""What every runner needs of the program besides its entry points: the
mesh, the device's memory figures, and what the compiler planned for each
program.  A model family's own classes are named in
``models/<model_type>.py`` and nowhere else.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple


def seed_key(seed: int):
    import jax

    return jax.random.PRNGKey(int(seed))


def memory_bytes(stat: str = "bytes_in_use") -> int:
    """One ``memory_stats()`` figure of the fullest chip (0 where the
    backend keeps none, as on the CPU)."""
    import jax

    return max(int((d.memory_stats() or {}).get(stat, 0))
               for d in jax.local_devices())


def planned_bytes(compiled: Any) -> Dict[str, int]:
    """What the compiler planned per chip for one program: its sizes as
    ``memory_analysis()`` gives them, and ``beyond_arguments``, the most it
    holds at once over and above its arguments.  ``peak_memory`` is the
    compiler's own high-water (arguments included; scratch reuses donated
    arguments, so arguments + temp overstates it: 19.0 against 15.9 GB for
    the BERT step, PR 23); where a backend leaves it 0, arguments, outputs
    that alias none and scratch are summed."""
    plan = compiled.memory_analysis()
    out = {key: int(getattr(plan, f"{key}_in_bytes", 0) or 0)
           for key in ("temp_size", "argument_size", "output_size",
                       "alias_size", "peak_memory")}
    peak = out["peak_memory"] or (out["argument_size"] + out["temp_size"]
                                  + out["output_size"] - out["alias_size"])
    out["beyond_arguments"] = peak - out["argument_size"]
    return out


class PlanHarvest:
    """Every program compiled through the program's compile tracker from
    now on, as :func:`planned_bytes` of its executable.  The tracker hands
    each executable to its cost harvesters once, when it is compiled (or
    read from the persistent cache): ``add_cost_harvester`` is its public
    hook, so no attribute of an engine is read."""

    def __init__(self) -> None:
        from deepspeed_tpu.telemetry.perf import configure_compile_tracker

        self.plans: List[Dict[str, int]] = []
        configure_compile_tracker(enabled=True).add_cost_harvester(
            lambda site, program, compiled: self.plans.append(
                dict(planned_bytes(compiled), site=site)))


def train_step_plan(engine: Any, batch: Any) -> Dict[str, int]:
    """The train step's plan.  The training engine keeps its compile
    tracker off unless telemetry is on (which is not how the cell runs),
    and has no public handle on its step's executable: until it has one
    (PERF.md, Open questions) this reads the jitted step it keeps, a read
    of the compile cache after the warm-up steps."""
    step = getattr(engine, "_train_step_fn", None)
    if step is None:
        raise SystemExit("perfbench: the engine keeps no jitted train step "
                         "to read the compiler's memory plan from")
    return planned_bytes(step.lower(engine.state, batch).compile())


def window_peak_bytes(in_use: int, plans: Any) -> int:
    """A cell's peak on the fullest chip: what is in use while the window
    runs (the programs' arguments among it) plus the most any of its
    programs holds beyond its arguments.  The runtime's ``bytes_in_use``
    and ``peak_bytes_in_use`` do not include a running program's scratch
    (PERF.md, PR 21), and the process-wide peak also holds the reference
    comparison, which is the benchmark's and not the system's."""
    return in_use + max(p["beyond_arguments"] for p in plans)


def mesh_for(run: Dict[str, Any], chips: int) -> Tuple[Any, int]:
    """(mesh over ``chips`` devices laid out as ``run["mesh"]``, size of
    its data axis)."""
    import jax

    from deepspeed_tpu.parallel import MeshLayout
    from deepspeed_tpu.parallel.mesh import AXIS_DATA, build_mesh
    from deepspeed_tpu.utils import groups

    layout = MeshLayout.infer(
        chips, tp=int(run.get("mesh", {}).get("tensor", 1)))
    groups.reset_mesh()
    mesh = groups.initialize_mesh(
        layout, build_mesh(layout, devices=jax.devices()[:chips]))
    return mesh, int(mesh.shape[AXIS_DATA])
