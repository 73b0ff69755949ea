#!/usr/bin/env python
"""Collective micro-benchmark over the device mesh (SURVEY C2 / §5 comms).

Times each collective in the ``dist`` façade (allreduce, all_gather,
reduce_scatter, ppermute, all_to_all) at a sweep of payload sizes, one
JSONL line per (op, bytes): achieved algorithmic bandwidth per chip. On a
pod this measures ICI (and DCN when the mesh spans slices); on the CPU sim
the numbers are meaningless but the harness and every lowering still run —
which is what the CI test asserts.

    python tools/collective_bench.py                    # whole-mesh axis
    python tools/collective_bench.py --axis data --mb 1 4 16

Algorithmic bandwidth convention (the NCCL one): busbw = bytes x
2(n-1)/n / t for allreduce, bytes x (n-1)/n / t for all_gather and
reduce_scatter, bytes / t for ppermute and (per-chip payload) all_to_all.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--axis", default="data",
                    help="mesh axis to benchmark over")
    ap.add_argument("--devices", type=int, default=0,
                    help="mesh size (0 = all visible devices)")
    ap.add_argument("--mb", type=float, nargs="*", default=[1, 8, 64],
                    help="payload megabytes per chip")
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()

    import jax

    p = os.environ.get("JAX_PLATFORMS")
    if p:
        jax.config.update("jax_platforms", p)
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from frl_distributed_ml_scaffold_tpu.dist import collectives as C

    devs = jax.devices()[: args.devices or None]
    n = len(devs)
    # Topology-aware ordering (mesh-adjacent == ICI-adjacent) — the raw
    # enumeration order would time multi-hop routes and under-report.
    from jax.experimental import mesh_utils

    try:
        dev_array = mesh_utils.create_device_mesh((n,), devices=devs)
    except (ValueError, AssertionError):  # e.g. CPU sim subsets
        dev_array = np.array(devs)
    mesh = Mesh(dev_array, (args.axis,))
    axis = args.axis
    primary = jax.process_index() == 0

    def emit(rec):
        if primary:
            print(json.dumps(rec), flush=True)

    def timed(fn, x):
        out0 = fn(x)  # compile
        # Settle on the compile call's OWN output — syncing on an unrelated
        # array would not order after fn(x)'s execution, letting leftover
        # compile-call work bleed into the first timed iteration. Must be
        # block_until_ready, not device_get: collective outputs sharded
        # P(axis) across a multi-host pod are not fully addressable, so
        # any host fetch raises; blocking needs no transfer.
        jax.block_until_ready(out0)
        t0 = time.perf_counter()
        out = None
        for _ in range(args.iters):
            out = fn(x)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.iters

    OPS = {
        # name: (shard_map fn, out_specs, busbw multiplier as f(n))
        "all_reduce": (
            lambda v: C.all_reduce(v, axis), P(),
            lambda n: 2 * (n - 1) / n,
        ),
        "all_gather": (
            lambda v: C.all_gather(v, axis), P(), lambda n: (n - 1) / n,
        ),
        "reduce_scatter": (
            lambda v: C.reduce_scatter(v, axis), P(axis),
            lambda n: (n - 1) / n,
        ),
        "permute": (
            lambda v: C.permute(
                v, axis, perm=[(i, (i + 1) % n) for i in range(n)]
            ),
            P(axis),
            lambda n: 1.0,
        ),
        "all_to_all": (
            lambda v: C.all_to_all(v, axis, split_axis=0, concat_axis=0),
            P(axis),
            lambda n: (n - 1) / n,
        ),
    }

    for mb in args.mb:
        per_chip = int(mb * 2**20 / 4)  # fp32 elements per chip
        per_chip = max(n, per_chip - per_chip % n)  # divisible for a2a
        # Assemble from per-process local data (multi-host pods cannot
        # device_put onto non-addressable devices) — the same pattern the
        # data pipeline uses.
        sharding = NamedSharding(mesh, P(axis))
        n_local = per_chip * n // jax.process_count()
        local = np.arange(n_local, dtype=np.float32)
        sharded = jax.make_array_from_process_local_data(
            sharding, local, (per_chip * n,)
        )
        for name, (fn, out_specs, mult) in OPS.items():
            from frl_distributed_ml_scaffold_tpu.dist.mesh import (
                shard_map_unchecked,
            )

            smfn = jax.jit(
                shard_map_unchecked(
                    fn, mesh=mesh, in_specs=P(axis), out_specs=out_specs,
                )
            )
            try:
                dt = timed(smfn, sharded)
                bytes_per_chip = per_chip * 4
                busbw = bytes_per_chip * mult(n) / dt
                emit({
                    "op": name, "axis": axis, "n": n,
                    "mb_per_chip": round(bytes_per_chip / 2**20, 2),
                    "time_us": round(dt * 1e6, 1),
                    "busbw_gbps": round(busbw / 1e9, 2),
                })
            except Exception as e:
                emit({
                    "op": name, "axis": axis, "n": n,
                    "error": str(e)[:160],
                })
    return 0


if __name__ == "__main__":
    sys.exit(main())
