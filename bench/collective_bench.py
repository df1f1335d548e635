#!/usr/bin/env python3
"""lax.all_to_all micro-benchmark — the ICI half of BASELINE.md row 7.

Times the padded all-to-all the sort engines actually use (uint32 lanes,
``tiled=True``) over the available mesh and reports achieved GB/s through
the metrics sidecar.  The native half is ``native/comm_bench.c`` (same
traffic pattern over the comm.h shim); run both for the MPI-vs-ICI
comparison the north star describes.

Usage: python bench/collective_bench.py [--bytes-per-peer B] [--reps R]
       [--ranks P] [--cpu]
"""

from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--bytes-per-peer", type=int, default=1 << 22)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--ranks", type=int, default=None)
    ap.add_argument("--cpu", action="store_true",
                    help="virtual CPU mesh (8 devices) instead of TPU")
    args = ap.parse_args()

    if args.cpu:
        import os

        from mpitest_tpu.utils import knobs

        os.environ["XLA_FLAGS"] = (
            knobs.get("XLA_FLAGS")
            + " --xla_force_host_platform_device_count=8"
        )
    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from mpitest_tpu.parallel.mesh import AXIS, make_mesh
    from mpitest_tpu.utils.metrics import Metrics

    mesh = make_mesh(args.ranks)
    n_ranks = int(mesh.devices.size)
    lanes = args.bytes_per_peer // 4  # uint32 lanes per peer block

    def step(x):
        # the exact exchange shape the sort engines use: [P, lanes] tiled
        return lax.all_to_all(x, AXIS, 0, 0, tiled=True)

    fn = jax.jit(
        jax.shard_map(
            lambda x: step(x), mesh=mesh, in_specs=P(AXIS), out_specs=P(AXIS)
        )
    )
    x = jnp.arange(n_ranks * n_ranks * lanes, dtype=jnp.uint32).reshape(
        n_ranks * n_ranks, lanes
    )
    from mpitest_tpu.models.ingest import checked_device_put

    x = checked_device_put(x, jax.sharding.NamedSharding(mesh, P(AXIS)))

    out = fn(x)  # compile + warm
    int(jax.device_get(out[-1, -1]))
    t0 = time.perf_counter()
    for _ in range(args.reps):
        out = fn(out)
    int(jax.device_get(out[-1, -1]))  # sync (block_until_ready is advisory here)
    dt = time.perf_counter() - t0

    # inter-rank bytes only, matching native/comm_bench.c (self-destined
    # blocks never cross the fabric)
    remote_peers = n_ranks - 1 if n_ranks > 1 else 1
    moved = float(n_ranks * remote_peers * lanes * 4) * args.reps
    m = Metrics(config={
        "ranks": n_ranks, "bytes_per_peer": args.bytes_per_peer,
        "reps": args.reps, "platform": jax.devices()[0].platform,
    })
    gbs = m.bandwidth("lax_all_to_all_gb_per_s", int(moved), dt)
    m.dump()
    print(f"lax.all_to_all: {gbs:.3f} GB/s over {n_ranks} ranks", file=sys.stderr)


if __name__ == "__main__":
    main()
