"""Out-of-core gate: the `make external-selftest` matrix (ISSUE 15).

Proves the external-sort subsystem end to end on any image, with the
memory budget forced FAR below the dataset so the whole spill/merge
machinery actually runs:

1. **budget gate** — a dataset >= 4x the forced ``SORT_MEM_BUDGET``
   externally sorts BIT-IDENTICAL to the in-memory supervised sort
   (and ``np.sort``), across >= 4 spill runs; a second cell forces a
   small ``SORT_MERGE_FANIN`` so the multi-pass (intermediate-run)
   merge path is exercised too.
2. **record gate** — key+payload sorts (the in-memory argsort-gather
   AND the external spill path) bit-identical to the numpy
   ``argsort(kind="stable")`` gather oracle across every codec dtype.
3. **fault cells** — ``spill_corrupt`` and ``merge_drop`` each fire
   once and must recover verified (blamed run re-spilled / merge
   re-ran; result still exact, ``recoveries`` recorded); a persistent
   ``spill_corrupt:inf`` must exhaust the recovery budget into a typed
   ``SortIntegrityError`` — never silent wrong bytes.
4. **serve gate** — a spawned ``sort_server`` with a tiny admission
   byte bound: a ``payload_bytes`` record request round-trips
   bit-identical, and an over-budget request succeeds THROUGH the
   spill tier (``spilled: true`` in the reply + plan digest) instead
   of the old typed ``bytes`` rejection — each reply bit-identical to
   the solo in-memory oracle.

``--row`` instead emits the scale-gated bench row
(``external_sort_mkeys_per_s``: spill+merge throughput, run count,
disk bytes) for ``bench.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "bench"))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("SORT_RETRY_BACKOFF", "0")

import numpy as np  # noqa: E402

#: Gitignored checkout-scoped staging (never a shared /tmp path).
SPILL_DIR = REPO / "bench" / ".spill-out" / "selftest"

#: Forced budget + dataset sizing: the dataset is >= 4x the budget by
#: construction (the acceptance floor; measured ratio asserted below).
BUDGET = 1 << 18
N_KEYS = (4 * BUDGET) // 4          # int32 → dataset bytes = 4x budget

FAIL = 0


def check(name: str, ok: bool, detail: str = "") -> None:
    global FAIL
    if not ok:
        FAIL += 1
    print(f"  {'ok ' if ok else 'BAD'} {name:<46} {detail}", flush=True)


def lib_legs() -> None:
    from mpitest_tpu import faults
    from mpitest_tpu.models import records
    from mpitest_tpu.models.api import sort as api_sort
    from mpitest_tpu.models.supervisor import SortIntegrityError
    from mpitest_tpu.store import external
    from mpitest_tpu.utils.trace import Tracer

    rng = np.random.default_rng(15)
    x = rng.integers(-(2**31), 2**31 - 1, size=N_KEYS, dtype=np.int32)
    assert x.nbytes >= 4 * BUDGET
    ref = np.sort(x)

    print(f"budget gate: {x.nbytes} B dataset under a "
          f"{BUDGET} B budget ({x.nbytes / BUDGET:.1f}x)")
    t0 = time.perf_counter()
    tr = Tracer()
    res = external.external_sort(x, budget=BUDGET,
                                 spill_dir=str(SPILL_DIR / "keys"),
                                 tracer=tr)
    dt = time.perf_counter() - t0
    inmem = api_sort(x)
    check("external == in-memory == np.sort",
          bool(np.array_equal(res.keys, inmem)
               and np.array_equal(res.keys, ref)),
          f"runs={res.runs} disk={res.disk_bytes}B "
          f"{x.size / dt / 1e6:.1f} Mkeys/s")
    check("spilled across >= 4 runs", res.runs >= 4,
          f"runs={res.runs}")

    res2 = external.external_sort(x, budget=BUDGET, fanin=4,
                                  spill_dir=str(SPILL_DIR / "fanin"))
    check("multi-pass merge (fanin=4) bit-identical",
          bool(np.array_equal(res2.keys, ref)
               and res2.merge_passes >= 2),
          f"passes={res2.merge_passes}")

    print("record gate: key+payload vs numpy stable argsort-gather")
    for dt_name in ("int32", "uint32", "int64", "uint64",
                    "float32", "float64"):
        dt_ = np.dtype(dt_name)
        n = 40_000
        if dt_.kind == "f":
            keys = (rng.standard_normal(n) * 10.0
                    ** rng.integers(-20, 20, n)).astype(dt_)
        else:
            info = np.iinfo(dt_)
            keys = rng.integers(info.min, info.max, n, dtype=dt_)
        pay = rng.integers(0, 256, (n, 7), dtype=np.uint8)
        order = np.argsort(keys, kind="stable")
        sk, sp = records.sort_records(keys, pay)
        check(f"records in-memory [{dt_name}]",
              bool(np.array_equal(sk, keys[order])
                   and np.array_equal(sp, pay[order])))
        rese = external.external_sort(
            keys, pay, budget=BUDGET // 4,
            spill_dir=str(SPILL_DIR / f"rec_{dt_name}"))
        check(f"records external  [{dt_name}]",
              bool(np.array_equal(rese.keys, keys[order])
                   and np.array_equal(rese.payload, pay[order])),
              f"runs={rese.runs}")

    print("fault cells: recover-verified-or-fail-loudly")
    for site in ("spill_corrupt", "merge_drop"):
        reg = faults.FaultRegistry(site, seed=7)
        faults.install(reg)
        tr = Tracer()
        try:
            res = external.external_sort(
                x, budget=BUDGET, tracer=tr,
                spill_dir=str(SPILL_DIR / f"fault_{site}"))
            check(f"{site} x1 recovered",
                  bool(np.array_equal(res.keys, ref)
                       and reg.injected > 0 and res.recoveries > 0),
                  f"injected={reg.injected} "
                  f"recoveries={res.recoveries}")
        except SortIntegrityError as e:
            check(f"{site} x1 recovered", False,
                  f"typed error on a one-shot fault: {e}")
        finally:
            faults.install(None)

    reg = faults.FaultRegistry("spill_corrupt:inf", seed=7)
    faults.install(reg)
    try:
        external.external_sort(x, budget=BUDGET,
                               spill_dir=str(SPILL_DIR / "fault_inf"))
        check("spill_corrupt:inf fails typed", False,
              "persistent corruption shipped bytes")
    except SortIntegrityError:
        check("spill_corrupt:inf fails typed", True,
              "SortIntegrityError")
    finally:
        faults.install(None)


def serve_leg() -> None:
    """The acceptance pair (ISSUE 15): payload_bytes round trip + the
    over-budget request served by the spill tier, both bit-identical to
    the solo in-memory oracle."""
    from serve_load import Server

    from mpitest_tpu.serve.client import ServeClient

    out_dir = SPILL_DIR / "serve"
    out_dir.mkdir(parents=True, exist_ok=True)
    rng = np.random.default_rng(16)
    max_bytes = 1 << 16
    srv = Server(out_dir, "external", env_overrides={
        "SORT_SERVE_MAX_BYTES": str(max_bytes),
        "SORT_SERVE_SPILL": "auto",
        "SORT_MEM_BUDGET": str(1 << 15),
        "SORT_SPILL_DIR": str(out_dir / "spill"),
        "SORT_SERVE_BATCH_WINDOW_MS": "0",
        "SORT_METRICS_PORT": "-1",
    })
    try:
        print("serve gate: payload_bytes + spill tier")
        with ServeClient("127.0.0.1", srv.port, timeout=300.0) as c:
            n = 2000
            keys = rng.integers(-(2**31), 2**31 - 1, n, dtype=np.int32)
            pay = rng.integers(0, 256, (n, 8), dtype=np.uint8)
            order = np.argsort(keys, kind="stable")
            rep = c.sort(keys, payload=pay)
            check("payload_bytes round trip",
                  bool(rep.ok and np.array_equal(rep.arr, keys[order])
                       and np.array_equal(rep.payload, pay[order])),
                  f"spilled={rep.spilled}")

            big = rng.integers(-(2**31), 2**31 - 1, 50_000,
                               dtype=np.int32)
            assert big.nbytes > max_bytes
            rep = c.sort(big)
            check("over-budget request via spill tier",
                  bool(rep.ok and rep.spilled
                       and np.array_equal(rep.arr, np.sort(big))),
                  f"plan={rep.plan}")

            nbig = 30_000
            bigk = rng.integers(-(2**31), 2**31 - 1, nbig,
                                dtype=np.int32)
            bigp = rng.integers(0, 256, (nbig, 8), dtype=np.uint8)
            order = np.argsort(bigk, kind="stable")
            rep = c.sort(bigk, payload=bigp)
            check("over-budget RECORD request via spill tier",
                  bool(rep.ok and rep.spilled
                       and np.array_equal(rep.arr, bigk[order])
                       and np.array_equal(rep.payload, bigp[order])),
                  f"spilled={rep.spilled}")
    finally:
        srv.stop()


def row_main() -> int:
    """Emit the bench row: spill+merge throughput on a dataset 4x the
    forced budget, output verified in-process before the row prints."""
    from mpitest_tpu.store import external
    from mpitest_tpu.utils import knobs

    # ISSUE 17: the row says which local engine sorted the runs so the
    # trajectory column can attribute a throughput move to an engine
    # flip.  Measured rows pin lax.
    os.environ.setdefault("SORT_LOCAL_ENGINE", "lax")

    rng = np.random.default_rng(17)
    x = rng.integers(-(2**31), 2**31 - 1, size=N_KEYS, dtype=np.int32)
    spill = SPILL_DIR / "row"
    # warm the compile caches so the row times spill+merge, not XLA
    external.external_sort(x[: N_KEYS // 4], budget=BUDGET // 4,
                           spill_dir=str(spill))
    t0 = time.perf_counter()
    res = external.external_sort(x, budget=BUDGET,
                                 spill_dir=str(spill))
    dt = time.perf_counter() - t0
    if not np.array_equal(res.keys, np.sort(x)):
        print("external row: WRONG RESULT", file=sys.stderr)
        return 1
    print(json.dumps({
        "metric": "external_sort_mkeys_per_s",
        "value": round(x.size / dt / 1e6, 3),
        "unit": "Mkeys/s",
        "n": int(x.size), "dtype": "int32",
        "budget_bytes": BUDGET,
        "dataset_x_budget": round(x.nbytes / BUDGET, 2),
        "runs": res.runs, "disk_bytes": res.disk_bytes,
        "merge_passes": res.merge_passes,
        "wall_s": round(dt, 4),
        "local_engine": str(knobs.get("SORT_LOCAL_ENGINE")),
        # ISSUE 20: compression + async-IO trajectory fields (rows
        # from older rounds lack them and render "-")
        "spill_ratio": round(res.spill_ratio, 3),
        "disk_overlap": round(res.disk_overlap, 3),
        "spill_compress": str(knobs.get("SORT_SPILL_COMPRESS")),
    }))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--row", action="store_true",
                    help="emit the bench JSONL row instead of the gate")
    args = ap.parse_args()
    if SPILL_DIR.exists():
        shutil.rmtree(SPILL_DIR)
    SPILL_DIR.mkdir(parents=True, exist_ok=True)
    try:
        if args.row:
            return row_main()
        lib_legs()
        serve_leg()
    finally:
        shutil.rmtree(SPILL_DIR, ignore_errors=True)
    print(f"\nexternal-selftest: "
          f"{'CLEAN' if FAIL == 0 else f'{FAIL} BAD cell(s)'}")
    return 1 if FAIL else 0


if __name__ == "__main__":
    sys.exit(main())
