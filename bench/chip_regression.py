#!/usr/bin/env python3
"""One-command ON-CHIP regression gate (VERDICT r3 #3).

The interpret-mode tests (`tests/test_bitonic.py`, `test_pallas_pack.py`)
catch logic bugs and the AOT compiles catch lowering breaks, but real-
Mosaic *numerics* — what the hardware actually computes — were previously
only checked in manual sessions.  This script is the recorded gate: run
``make chip-test`` (or ``python -u bench/chip_regression.py``) in any
session with a real TPU attached; it finishes in minutes and appends one
JSONL row to ``bench/BASELINE_RESULTS.jsonl``.

Checks (all correctness verdicts computed ON DEVICE — scalars, not
hundreds of MB, cross to the host):

1. Real-Mosaic bitonic engine vs ``lax.sort`` at 2^26: bit-equal output
   (the engines must agree exactly — sorted uint32 is canonical), plus
   slope-method timing of both (recorded, not gated; the ratio is the
   number to eyeball against BASELINE.md's 1.6-2.2x).
2. ``segment_pack`` (the Pallas DMA exchange pack) vs a numpy reference
   on ragged segments.
3. The 5-pattern adversarial battery (sorted / reverse / all-equal /
   few-distinct / organ-pipe) at 2^26 through the real kernels, verified
   on device by sortedness + sum/xor multiset invariants.

Exit 0 = all correctness checks passed (timings are informational).
Exit 2 = no TPU attached (the gate is meaningless in interpret mode).
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import numpy as np

RESULTS = Path(__file__).resolve().parent / "BASELINE_RESULTS.jsonl"


def main() -> int:
    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        print("chip_regression: no real TPU attached "
              f"(backend={jax.default_backend()}); refusing to gate on "
              "interpret-mode numerics", flush=True)
        return 2

    from mpitest_tpu.ops import bitonic
    from mpitest_tpu.ops.pallas_kernels import CHUNK, segment_pack

    row: dict = {"ts": time.time(), "config": "chip_regression"}
    ok = True

    # ---- 1. bitonic vs lax.sort @ 2^26: bit-equal + slope timings ----
    log2n = 26
    n = 1 << log2n
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32))

    @jax.jit
    def both_agree(v):
        b = bitonic.sort_padded(v, n, bitonic.BLOCK_LOG2)
        l = jax.lax.sort([v], num_keys=1, is_stable=False)[0]
        return jnp.all(b == l)

    t0 = time.perf_counter()
    agree = bool(jax.device_get(both_agree(x)))
    print(f"bitonic==lax.sort @2^{log2n}: {'OK' if agree else 'FAIL'} "
          f"({time.perf_counter() - t0:.1f}s incl. compile)", flush=True)
    row["bitonic_matches_lax"] = agree
    ok &= agree

    def slope(fn, args, reps=(1, 3), tries=3):
        """Slope-method device time of ``fn`` (operand tuple -> operand
        tuple), with a forced scalar fetch after each timed call."""
        out = {}
        for r in reps:
            @jax.jit
            def g(ops, r=r):
                for _ in range(r):
                    ops = fn(*ops)
                return ops
            y = g(args)
            jax.device_get(y[0][:1])
            ts = []
            for _ in range(tries):
                t = time.perf_counter()
                y = g(args)
                jax.device_get(y[0][:1])
                ts.append(time.perf_counter() - t)
            out[r] = min(ts)
        return (out[reps[1]] - out[reps[0]]) / (reps[1] - reps[0])

    bit_ms = slope(
        lambda v: (bitonic.sort_padded(v, n, bitonic.BLOCK_LOG2),), (x,)) * 1e3
    lax_ms = slope(
        lambda v: (jax.lax.sort([v], num_keys=1, is_stable=False)[0],),
        (x,)) * 1e3
    ratio = lax_ms / bit_ms if bit_ms > 0 else float("nan")
    print(f"bitonic {bit_ms:.1f} ms  lax.sort {lax_ms:.1f} ms  "
          f"ratio {ratio:.2f}x (BASELINE.md regression band: 2.0-4.2x "
          "post-relayout; r4 band was 1.6-2.2x)",
          flush=True)
    row.update(bitonic_ms=round(bit_ms, 1), lax_sort_ms=round(lax_ms, 1),
               bitonic_speedup=round(ratio, 2))

    # ---- 1b. 64-bit pair engine vs variadic lax.sort: bit-equal + slope ----
    from mpitest_tpu.ops import kernels

    lo2 = jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint64)
                      .astype(np.uint32))

    @jax.jit
    def pair_agree(h, l):
        hs, ls, bad, _ = kernels.sort_two_words_bitonic(h, l)
        ref = jax.lax.sort([h, l], num_keys=2, is_stable=False)
        return jnp.all(hs == ref[0]) & jnp.all(ls == ref[1]) & ~bad

    pagree = bool(jax.device_get(pair_agree(x, lo2)))
    print(f"pair engine == lax.sort 2w @2^{log2n}: "
          f"{'OK' if pagree else 'FAIL'}", flush=True)
    row["pair_matches_lax"] = pagree
    ok &= pagree

    pair_ms = slope(
        lambda h, l: kernels.sort_two_words_bitonic(h, l)[:2],
        (x, lo2)) * 1e3
    lax2_ms = slope(
        lambda h, l: tuple(jax.lax.sort([h, l], num_keys=2,
                                        is_stable=False)),
        (x, lo2)) * 1e3
    pratio = lax2_ms / pair_ms if pair_ms > 0 else float("nan")
    print(f"pair {pair_ms:.1f} ms  lax.sort-2w {lax2_ms:.1f} ms  "
          f"ratio {pratio:.2f}x (regression band: 1.5-2.3x post-relayout; "
          "r4 band was 1.25-1.45x)", flush=True)
    row.update(pair_ms=round(pair_ms, 1), lax_sort_2w_ms=round(lax2_ms, 1),
               pair_speedup=round(pratio, 2))

    # ---- 2. segment_pack vs numpy on ragged segments ----
    P = 8
    nd = 1 << 20
    cnts = rng.integers(0, nd // P, P).astype(np.int32)
    starts = np.concatenate([[0], np.cumsum(cnts)[:-1]]).astype(np.int32)
    data = rng.integers(0, 2**32, nd, dtype=np.uint64).astype(np.uint32)
    cap = int(-(-int(cnts.max()) // CHUNK) * CHUNK)
    got = np.asarray(segment_pack(jnp.asarray(data), jnp.asarray(starts),
                                  jnp.asarray(cnts), cap, P, fill=0))
    want = np.zeros((P, cap), np.uint32)
    for p in range(P):
        want[p, : cnts[p]] = data[starts[p]: starts[p] + cnts[p]]
    pack_ok = bool(np.array_equal(got, want))
    print(f"segment_pack ragged [P={P}, cap={cap}]: "
          f"{'OK' if pack_ok else 'FAIL'}", flush=True)
    row["segment_pack_ok"] = pack_ok
    ok &= pack_ok

    # ---- 3. adversarial pattern battery @ 2^26 on the real kernels ----
    @jax.jit
    def sort_and_check(v):
        out = bitonic.sort_padded(v, n, bitonic.BLOCK_LOG2)
        is_sorted = jnp.all(out[1:] >= out[:-1])
        xor = lambda a: jax.lax.reduce(a, jnp.uint32(0),  # sortlint: disable=SL010 -- single-device jit checksum, no SPMD partitioner
                                       jax.lax.bitwise_xor, (0,))
        return is_sorted, v.sum() == out.sum(), xor(v) == xor(out)

    pats = {
        "sorted": np.arange(n, dtype=np.uint32),
        "reverse": np.arange(n, 0, -1).astype(np.uint32),
        "all-equal": np.full(n, 0xABCD1234, np.uint32),
        "few-distinct": rng.integers(0, 3, n).astype(np.uint32),
        "organ-pipe": np.concatenate([
            np.arange(n // 2, dtype=np.uint32),
            np.arange(n // 2, 0, -1).astype(np.uint32)]),
    }
    pat_ok = True
    for name, p in pats.items():
        checks = [bool(t) for t in jax.device_get(sort_and_check(jnp.asarray(p)))]
        good = all(checks)
        pat_ok &= good
        print(f"adversarial {name} @2^{log2n}: {'OK' if good else f'FAIL {checks}'}",
              flush=True)
    row["patterns_ok"] = pat_ok
    ok &= pat_ok

    row["all_ok"] = ok
    with open(RESULTS, "a") as f:
        f.write(json.dumps(row) + "\n")
    print(f"chip_regression: {'ALL OK' if ok else 'FAILURES'} "
          f"(row appended to {RESULTS.name})", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
