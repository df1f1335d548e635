#!/usr/bin/env python3
"""Price the equal-hi run fix-up depth (VERDICT r4 weak #3 / next #3).

Today runs longer than ``fix_passes=8`` that evade the 1024-key sniff
cost pair-network + full ``lax.sort`` (the residual fallback) — worst
case ~2.4x ``lax.sort`` alone.  The mid-tier candidate: deeper in-VMEM
fix-up (the kernel already takes ``passes``).  This probe prices, on
chip at 2^26:

1. The marginal cost of passes in {8, 16, 32} on uniform keys (what
   everyone pays when the fix-up is NOT needed).
2. The runs-of-16 adversarial pattern (mid-length equal-hi runs the
   sniff cannot see) at each depth: at 8 it double-sorts via the
   residual fallback; at >= 16 the fix-up handles it in-VMEM.
3. On-device exactness of the runs-16 pattern at the chosen depth.

Resumable: ``FIX_PARTS=uniform,runs16,exact`` (default all).
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


def main() -> int:
    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        print("fixdepth_probe: needs a real TPU", flush=True)
        return 2

    from mpitest_tpu.ops import kernels

    from mpitest_tpu.utils import knobs

    parts = knobs.get("FIX_PARTS")
    n = 1 << 26
    rng = np.random.default_rng(11)
    row: dict = {"ts": time.time(), "config": "fixdepth_probe_2e26"}

    ku = jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint64)
                     .astype(np.uint32))
    pu = jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint64)
                     .astype(np.uint32))
    # runs-of-16: every hi value repeats exactly 16x, shuffled — longer
    # than fix_passes=8, invisible to a 1024-key strided sniff.
    hi16 = np.repeat(rng.choice(2**32, n // 16, replace=False)
                     .astype(np.uint32), 16)
    perm = rng.permutation(n)
    k16 = jnp.asarray(hi16[perm])
    p16 = jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint64)
                      .astype(np.uint32))

    def slope(fn, args, reps=(1, 3), tries=3):
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
        return (out[reps[1]] - out[reps[0]]) / (reps[1] - reps[0]) * 1e3

    def full_with_fallback(passes):
        """The b_pair branch shape: pair path at ``passes``, residual ->
        on-device lax fallback (what the fused jit runs)."""
        def f(h, l):
            hs, ls, bad, _ = kernels.sort_two_words_bitonic(
                h, l, fix_passes=passes)
            hs, ls = jax.lax.cond(
                bad, lambda a, b: tuple(jax.lax.sort([a, b], num_keys=2,
                                                     is_stable=False)),
                lambda a, b: (hs, ls), h, l)
            return hs, ls
        return f

    if "uniform" in parts:
        for passes in (8, 16, 32):
            ms = slope(full_with_fallback(passes), (ku, pu))
            print(f"uniform, fix_passes={passes}: {ms:.1f} ms", flush=True)
            row[f"uniform_fix{passes}_ms"] = round(ms, 1)

    if "runs16" in parts:
        for passes in (8, 16, 32):
            ms = slope(full_with_fallback(passes), (k16, p16))
            print(f"runs-of-16, fix_passes={passes}: {ms:.1f} ms "
                  f"({'double-sorts via fallback' if passes < 16 else 'in-VMEM fix'})",
                  flush=True)
            row[f"runs16_fix{passes}_ms"] = round(ms, 1)
        lax_ms = slope(
            lambda h, l: tuple(jax.lax.sort([h, l], num_keys=2,
                                            is_stable=False)), (k16, p16))
        print(f"runs-of-16, lax 2w: {lax_ms:.1f} ms", flush=True)
        row["runs16_lax_ms"] = round(lax_ms, 1)

    if "exact" in parts:
        def make_check(passes):
            @jax.jit
            def check(h, l):
                hs, ls, bad, _ = kernels.sort_two_words_bitonic(
                    h, l, fix_passes=passes)
                ref = jax.lax.sort([h, l], num_keys=2, is_stable=False)
                return jnp.all(hs == ref[0]) & jnp.all(ls == ref[1]), bad
            return check

        for passes in (16, 32):
            ok, bad = (bool(v) for v in
                       jax.device_get(make_check(passes)(k16, p16)))
            print(f"runs-of-16 exact at fix_passes={passes}: {ok} "
                  f"(residual={bad})", flush=True)
            row[f"runs16_exact_fix{passes}"] = ok and not bad
    row_ok = all(v for k, v in row.items() if k.startswith("runs16_exact"))
    row["all_ok"] = row_ok
    with open(RESULTS, "a") as f:
        f.write(json.dumps(row) + "\n")
    print("fixdepth_probe: done", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
