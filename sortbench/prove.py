#!/usr/bin/env python3
"""Readings for the limits: the program over many seeds and the control,
in one process, so set-up (JAX's start, compiles) is paid once.

    python sortbench/prove.py --workload <cell> --seeds 1,2,3 \\
        --control-seeds 7,8,9 --seconds 5

For each program seed: the cell's pool from the seed, its first array
sorted once (set-up), the references, a window of ``--seconds`` with
each answer checked against its reference as its call returns.  For each control seed the same window with the reference's
``control`` (one guarantee broken) in the program's place.  One JSON
line per seed on stdout: the compared numbers (``checks``), calls and
throughput.  The benchmark's own runs never run this.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def log(msg: str) -> None:
    print(f"[prove] {msg}", file=sys.stderr, flush=True)


def seeds(s: str) -> list[int]:
    return [int(v) for v in s.split(",") if v]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from sortbench import harness, spec

    cell = spec.resolve(args.workload)
    try:
        devices, sort_fn = harness.open_program(cell, log)
    except harness.NoChip as e:
        log(str(e))
        return 2
    compiles = harness.CompileCounter()

    def control_fn(x, tracer):
        return cell.reference.control(x)

    jobs = ([("program", s, sort_fn) for s in args.seeds]
            + [("control", s, control_fn) for s in args.control_seeds])
    for kind, seed, fn in jobs:
        t = time.perf_counter()
        run = harness.run_cell(cell, seed, args.seconds, False, t, devices,
                               fn, compiles, log=log)
        line = harness.result_line(run, False)
        print(json.dumps({"kind": kind, "seed": seed,
                          "correct": line["correct"],
                          "attempted": line["attempted"],
                          "setup_s": run.setup_s,
                          "mkeys_per_s": run.keys_ok / run.window_s / 1e6,
                          "checks": line["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
