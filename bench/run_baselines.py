#!/usr/bin/env python3
"""Run the BASELINE.md measurement plan end-to-end and record results.

Executes every config the driver metadata defines (scaled to the hardware
this image actually has — one TPU chip and one CPU core), appending one
JSON line per measurement to ``bench/BASELINE_RESULTS.jsonl``:

  1. native sample_sort wall-time, 2^20 uniform int32, 4 local ranks
  2. native radix_sort  wall-time, 2^20 uniform int32, 4 local ranks
  3. TPU sample_sort Mkeys/s        (BENCH_LOG2N, default 2^26)
  4. TPU radix_sort  Mkeys/s        (BENCH_LOG2N, default 2^26)
  5. Zipf(1.1) int64 skew stress    (TPU path via host codec)
  6. native alltoallv GB/s + lax.all_to_all GB/s (BASELINE row 7)

Usage: python bench/run_baselines.py [--log2n-native 20] [--log2n-tpu 26]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
OUT = REPO / "bench" / "BASELINE_RESULTS.jsonl"


def emit(obj: dict) -> None:
    obj = {"ts": time.time(), **obj}
    with open(OUT, "a") as f:
        f.write(json.dumps(obj) + "\n")
    print(json.dumps(obj))


def run_native(tag: str, binary: Path, path: str, ranks: int) -> None:
    from mpitest_tpu.utils.nativebench import run_native_sort

    secs, err = run_native_sort(binary, path, ranks, timeout=600)
    if err:
        emit({"config": tag, "error": err[:200]})
        return
    emit({"config": tag, "metric": "wall_time_s", "value": secs,
          "ranks": ranks})


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--log2n-native", type=int, default=20)
    ap.add_argument("--log2n-tpu", type=int, default=26)
    args = ap.parse_args()

    sys.path.insert(0, str(REPO))
    import numpy as np

    from mpitest_tpu.utils import io

    # build native binaries + micro-bench
    for d in ("mpi_sample_sort", "mpi_radix_sort", "bench"):
        subprocess.run(["make", "-C", str(REPO / d)], check=True,
                       capture_output=True)

    # configs 1-2: native CPU reference numbers, reference timer contract
    n_native = 1 << args.log2n_native
    keys = io.generate_uniform(n_native, seed=0)
    datafile = "/tmp/baseline_keys.txt"
    io.write_keys_text(datafile, keys)
    run_native("native_sample_2e%d_4ranks" % args.log2n_native,
               REPO / "mpi_sample_sort" / "sample_sort", datafile, 4)
    run_native("native_radix_2e%d_4ranks" % args.log2n_native,
               REPO / "mpi_radix_sort" / "radix_sort", datafile, 4)

    # configs 3-4: TPU Mkeys/s via bench.py (one JSON line on stdout)
    for algo in ("sample", "radix"):
        r = subprocess.run(
            [sys.executable, str(REPO / "bench.py")], capture_output=True,
            text=True, timeout=1200,
            env=dict(os.environ, BENCH_ALGO=algo,
                     BENCH_LOG2N=str(args.log2n_tpu)),
        )
        if r.returncode == 0 and r.stdout.strip():
            emit({"config": f"tpu_{algo}_2e{args.log2n_tpu}",
                  **json.loads(r.stdout.strip().splitlines()[-1])})
        else:
            emit({"config": f"tpu_{algo}", "error": r.stderr.strip()[-200:]})

    # config 5: Zipf(1.1) int64 skew stress (host codec path, real TPU)
    from mpitest_tpu.models.api import sort
    from mpitest_tpu.parallel.mesh import make_mesh

    from mpitest_tpu.utils.trace import Tracer

    n_zipf = 1 << max(args.log2n_tpu - 4, 16)
    z = io.generate_zipf(n_zipf, dtype=np.int64, seed=1)
    mesh = make_mesh()
    sort(z, algorithm="sample", mesh=mesh)  # warm/compile + settle caps
    tr = Tracer()
    t0 = time.perf_counter()
    out = sort(z, algorithm="sample", mesh=mesh, tracer=tr)
    dt = time.perf_counter() - t0
    ok = bool(np.array_equal(out, np.sort(z)))
    # NOTE: unlike the device-resident headline metric, this row times
    # the full HOST round-trip — encode, device_put and result decode
    # are in dt here; phases_ms attributes it.
    emit({"config": f"tpu_sample_zipf11_int64_2e{n_zipf.bit_length()-1}",
          "metric": "mkeys_per_s", "value": round(n_zipf / dt / 1e6, 2),
          "correct": ok, "span": "host_roundtrip",
          "phases_ms": {k: round(v * 1e3, 1) for k, v in tr.phases.items()},
          "counters": dict(tr.counters)})

    # config 6: the collective micro-bench pair (BASELINE row 7)
    r = subprocess.run(
        [str(REPO / "bench" / "comm_bench")], capture_output=True, text=True,
        env=dict(os.environ, COMM_RANKS="8"), timeout=600,
    )
    if r.returncode == 0 and r.stdout.strip():
        emit({"config": "native_alltoallv_8ranks", **json.loads(r.stdout)})
    r = subprocess.run(
        [sys.executable, str(REPO / "bench" / "collective_bench.py"),
         "--reps", "10"], capture_output=True, text=True, timeout=600,
    )
    for line in r.stderr.splitlines():
        if "GB/s" in line and "lax" in line:
            emit({"config": "lax_all_to_all", "detail": line.strip()})


if __name__ == "__main__":
    main()
