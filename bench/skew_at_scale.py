#!/usr/bin/env python3
"""At-scale skew measurement (VERDICT r3 #2) — the single-chip proxy for
BASELINE row 5 (Zipf 2^30 int64 on v5e-16).

Two modes, each fitting a ~9-minute chip budget per invocation:

* default (``--chip``): device-resident int64 keys at 2^27 (or
  ``SKEW_LOG2N``) for uniform / Zipf(1.1) / Zipf(1.5), through the
  public ``sort(algorithm='sample')`` path, bit-exact median verified
  (``np.partition`` — O(n), no full host sort), timed like ``bench.py``
  (warm + repeats, forced scalar sync).  On ONE device the sample
  algorithm specializes to the fused local sort (no exchange exists to
  skew), so these rows measure what skewed *data* costs the machine at
  scale; the routing/sniff behavior at the same key counts is the
  second mode's job.
* ``--mesh-counters``: 8-device virtual CPU mesh, device-resident
  Zipf int64 at ``SKEW_MESH_LOG2N`` (default 2^24): ASSERTS the
  at-scale contract VERDICT r3 #2 names — Zipf(1.5) reroutes via the
  on-device sniff (``sample_skew_fallback == 1``) with ZERO failed
  exchange rounds (``exchange_retries == 0``), Zipf(1.1) stays on the
  sample path (fallback 0) with a bounded cap — and verifies the full
  sorted output.  (The reference's corresponding failure mode is the
  silent bucket overflow, ``mpi_sample_sort.c:140-144``.)

Each config appends one JSONL row to ``bench/BASELINE_RESULTS.jsonl``.
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import numpy as np

RESULTS = Path(__file__).resolve().parent / "BASELINE_RESULTS.jsonl"


def _append(row: dict) -> None:
    with open(RESULTS, "a") as f:
        f.write(json.dumps(row) + "\n")


def _dists(n: int):
    from mpitest_tpu.utils.io import generate_uniform, generate_zipf

    return {
        "uniform": lambda: generate_uniform(n, np.int64, seed=1),
        "zipf11": lambda: generate_zipf(n, a=1.1, dtype=np.int64, seed=1),
        "zipf15": lambda: generate_zipf(n, a=1.5, dtype=np.int64, seed=1),
    }


def chip_rows() -> int:
    import jax

    jax.config.update("jax_enable_x64", True)  # device-resident int64
    from mpitest_tpu.models.api import checked_device_put, sort
    from mpitest_tpu.parallel.mesh import make_mesh
    from mpitest_tpu.utils.trace import Tracer

    if jax.default_backend() == "cpu":
        print("skew_at_scale --chip: no TPU attached", flush=True)
        return 2
    from mpitest_tpu.utils import knobs

    log2n = knobs.get("SKEW_LOG2N")
    repeats = knobs.get("SKEW_REPEATS")
    # Resumability: SKEW_DISTS selects a subset so a timed-out sweep
    # continues where it stopped (completed rows are already appended).
    only = knobs.get("SKEW_DISTS")
    sel = set(only) if only else None
    n = 1 << log2n
    mesh = make_mesh()
    for name, gen in _dists(n).items():
        if sel is not None and name not in sel:
            continue
        x = gen()
        k = n // 2 - 1
        want = int(np.partition(x, k)[k])
        print(f"{name} 2^{log2n}: ingesting {x.nbytes >> 20} MiB",
              flush=True)
        t0 = time.perf_counter()
        x_dev = checked_device_put(x, mesh.devices.flat[0])
        x_dev.block_until_ready()
        jax.device_get(x_dev[-1:])  # the transfer is lazy until synced
        print(f"{name} 2^{log2n}: ingest {time.perf_counter() - t0:.1f}s",
              flush=True)
        tracer = Tracer()
        r = sort(x_dev, algorithm="sample", mesh=mesh, return_result=True,
                 tracer=tracer)  # warm: compile + cap settle
        got = int(r.median_probe_raw())
        ok = got == want
        del r
        times = []
        for i in range(repeats):
            tr = Tracer()
            t0 = time.perf_counter()
            r = sort(x_dev, algorithm="sample", mesh=mesh, return_result=True,
                     tracer=tr)
            jax.device_get(r.words[0][-1:])  # forced sync
            times.append(time.perf_counter() - t0)
            del r
            tracer = tr
            print(f"  run {i}: {times[-1]:.3f}s = {n/times[-1]/1e6:.1f} Mkeys/s",
                  flush=True)
        mkeys = n / min(times) / 1e6
        row = {
            "ts": time.time(),
            "config": f"tpu_sample_{name}_int64_2e{log2n}_device_resident",
            "metric": "mkeys_per_s", "value": round(mkeys, 1),
            "median_ok": ok, "span": "device_resident",
            "counters": dict(tracer.counters),
        }
        _append(row)
        print(f"{name} 2^{log2n}: {mkeys:.1f} Mkeys/s, median "
              f"{'OK' if ok else 'MISMATCH'}, counters {dict(tracer.counters)}",
              flush=True)
        if not ok:
            return 1
    return 0


def mesh_counters() -> int:
    from mpitest_tpu.utils.platform import ensure_virtual_cpu_devices

    ensure_virtual_cpu_devices(8)
    import jax

    jax.config.update("jax_enable_x64", True)
    from mpitest_tpu.models.api import checked_device_put, sort
    from mpitest_tpu.parallel.mesh import make_mesh
    from mpitest_tpu.utils.trace import Tracer

    from mpitest_tpu.utils import knobs

    log2n = knobs.get("SKEW_MESH_LOG2N")
    n = 1 << log2n
    mesh = make_mesh(8)
    expect = {"zipf11": 0, "zipf15": 1}  # sample_skew_fallback per dist
    rc = 0
    for name, gen in _dists(n).items():
        if name not in expect:
            continue
        x = gen()
        x_dev = checked_device_put(x, jax.devices()[0])  # device-resident input
        tracer = Tracer()
        t0 = time.perf_counter()
        got = sort(x_dev, algorithm="sample", mesh=mesh, tracer=tracer)
        wall = time.perf_counter() - t0
        correct = bool(np.array_equal(got, np.sort(x)))
        fb = tracer.counters.get("sample_skew_fallback", 0)
        retries = tracer.counters.get("exchange_retries", 0)
        ok = correct and fb == expect[name] and retries == 0
        rc |= 0 if ok else 1
        row = {
            "ts": time.time(),
            "config": f"mesh8_sample_{name}_int64_2e{log2n}_device_resident",
            "wall_s": round(wall, 2), "correct": correct,
            "sample_skew_fallback": fb, "exchange_retries": retries,
            "expected_fallback": expect[name], "ok": ok,
        }
        _append(row)
        print(f"{name} 2^{log2n} on mesh8: sorted={correct} fallback={fb} "
              f"(expect {expect[name]}) retries={retries} wall={wall:.1f}s "
              f"-> {'OK' if ok else 'FAIL'}", flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(mesh_counters() if "--mesh-counters" in sys.argv else chip_rows())
