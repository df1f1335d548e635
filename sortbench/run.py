#!/usr/bin/env python3
"""The benchmark's command: one run of one cell of ``BENCHMARK.json``.

    python sortbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

In order: JAX's persistent compile cache at the checkout's fixed path;
the platform must be ``tpu`` with exactly the cell's ``chips`` devices
(anything else exits 2 with no result); ``native/libencode.so`` is
built if missing; the cell's key pool comes from ``--seed``; set-up
sorts the pool's first array once; the plain reference of each pool
array is computed (after set-up is read, before the window); the window
runs calls until their summed durations reach ``--seconds``, and each
answer is checked against its reference as its call returns, outside
the window, then dropped.  The last
line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks``: each compared number beside its limit).  The same
numbers are the last lines of stderr.
"""

import time

T_PROCESS = time.perf_counter()  # before any import: set-up starts here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def log(msg: str) -> None:
    print(f"[sortbench] {msg}", file=sys.stderr, flush=True)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from sortbench import harness, spec

    cell = spec.resolve(args.workload)
    if not (ROOT / "mpitest_tpu").is_dir():
        log(f"no mpitest_tpu package in {ROOT}: nothing to measure")
        return 2
    try:
        devices, sort_fn = harness.open_program(cell, log)
    except harness.NoChip as e:
        log(str(e))
        return 2
    run = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_PROCESS, devices, sort_fn,
                           harness.CompileCounter(), log=log)
    line = harness.result_line(run, bool(args.trace))
    for name, c in line["checks"].items():
        log(f"check {name} = {c['value']} (limit {c['limit']})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
