"""``ds_bench`` — collective micro-benchmark CLI.

Reference: ``bin/ds_bench`` [K] (thin shim over
``DeepSpeedExamples/benchmarks/communication``): time
all_reduce/all_gather/all_to_all/broadcast over a size sweep and print
busbw/algbw — the tool operators use to validate a fabric before training.

TPU-first: collectives are jitted ``jax.lax`` ops over the global mesh;
timings come from compiled-program replay fenced with
``jax.block_until_ready``.  Works on a
real slice or on a forced virtual CPU mesh (``--force_cpu_devices N``).
"""

from __future__ import annotations

import argparse
import sys
import time
from typing import List
from .jax_compat import shard_map as _shard_map


def _bench_collective(op: str, n_elems: int, trials: int, mesh) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    axis = tuple(mesh.axis_names)
    world = int(mesh.devices.size)
    # per-shard width rounded to a multiple of world so tiled all_to_all's
    # divisibility holds on any device count; report the ACTUAL bytes moved
    m = max(n_elems // world, world)
    m -= m % world
    n_elems = world * m
    x = jnp.ones((world, m), jnp.float32)
    x = jax.device_put(x, NamedSharding(mesh, P(axis)))

    # the comm verbs wrap the same lax collectives and feed the census —
    # a fabric-validation run should appear in the ledger like any other
    from ..comm.comm import (all_gather_in_graph, all_to_all_in_graph,
                             psum)

    def body(v):
        if op == "all_reduce":
            return psum(v, axis)
        if op == "all_gather":
            return all_gather_in_graph(v, axis, tiled=False)
        if op == "all_to_all":
            # local shard is [1, m]: exchange m/world-sized chunks
            return all_to_all_in_graph(v, axis, split_axis=1,
                                       concat_axis=0, tiled=True)
        if op == "broadcast":
            return psum(jnp.where(
                jax.lax.axis_index(axis[0]) == 0, v, jnp.zeros_like(v)),
                axis)
        raise ValueError(op)

    fn = jax.jit(_shard_map(body, mesh=mesh, in_specs=P(axis),
                               out_specs=P() if op == "all_reduce"
                               else P(axis),
                               check_vma=False))
    jax.block_until_ready(fn(x))  # compile + fence
    t0 = time.perf_counter()
    for _ in range(trials):
        out = fn(x)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / trials
    nbytes = n_elems * 4
    # ring busbw convention: allreduce moves 2(n-1)/n of the payload
    factor = 2 * (world - 1) / world if op == "all_reduce" else \
        (world - 1) / world
    return {"op": op, "bytes": nbytes, "time_us": dt * 1e6,
            "algbw_GBps": nbytes / dt / 1e9,
            "busbw_GBps": nbytes * factor / dt / 1e9}


def main(argv: List[str] = None) -> int:
    parser = argparse.ArgumentParser(prog="ds_bench")
    parser.add_argument("--op", default="all_reduce",
                        choices=["all_reduce", "all_gather", "all_to_all",
                                 "broadcast", "all"])
    parser.add_argument("--minsize", type=int, default=1 << 14)
    parser.add_argument("--maxsize", type=int, default=1 << 22)
    parser.add_argument("--trials", type=int, default=10)
    parser.add_argument("--force_cpu_devices", type=int, default=0,
                        help="virtual CPU mesh size (testing without TPUs)")
    args = parser.parse_args(argv)

    if args.force_cpu_devices:
        import os

        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count="
              f"{args.force_cpu_devices}")
        import jax
        import jax.extend.backend as jeb

        # drop any backend built before the flag was set
        jeb.clear_backends()
        jax.config.update("jax_platforms", "cpu")
    import jax
    from jax.sharding import Mesh

    mesh = Mesh(jax.devices(), ("data",))
    ops = (["all_reduce", "all_gather", "all_to_all", "broadcast"]
           if args.op == "all" else [args.op])
    print(f"ds_bench: {len(jax.devices())} x "
          f"{jax.devices()[0].device_kind}")
    print(f"{'op':>12} {'bytes':>12} {'time(us)':>10} {'algbw':>10} "
          f"{'busbw':>10}")
    for op in ops:
        n = args.minsize
        while n <= args.maxsize:
            r = _bench_collective(op, n, args.trials, mesh)
            print(f"{r['op']:>12} {r['bytes']:>12} {r['time_us']:>10.1f} "
                  f"{r['algbw_GBps']:>9.2f}G {r['busbw_GBps']:>9.2f}G")
            n *= 4
    return 0


if __name__ == "__main__":
    sys.exit(main())
