#!/usr/bin/env python3
"""Primitive-cost probes behind the BASELINE.md kernel design study.

Measures, on the real chip, the per-call device time of the primitives
that bound any Pallas sort kernel on this hardware: streaming copy
(bandwidth floor), elementwise VPU ops, sublane vs lane rolls (the 15x
asymmetry that shaped ``ops/bitonic.py``), block transpose, `lax.sort`,
and the bitonic engine itself.

Method: slope of chained in-jit calls between two rep counts — (1, 17)
for the sub-millisecond primitive probes, (1, 3) for the two full sorts
— with a forced scalar ``device_get`` after each timed call: the fixed
per-dispatch cost swamps single-call timings (the round-1 numbers in
the table at the top of BASELINE.md suffered exactly that).

Usage: python bench/kernel_probes.py [--log2n 26]
Emits one metrics-sidecar JSON line per probe on stderr and a summary
table on stdout.
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--log2n", type=int, default=26)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from mpitest_tpu.ops import bitonic
    from mpitest_tpu.utils.metrics import Metrics

    n = 1 << args.log2n
    s_rows, lanes = 512, 128
    nblk = n // (s_rows * lanes)
    rng = np.random.default_rng(0)
    x = jnp.asarray(
        rng.integers(-(2**31), 2**31, n, dtype=np.int32)
    ).reshape(nblk, s_rows, lanes)

    spec = pl.BlockSpec((1, s_rows, lanes), lambda i: (i, 0, 0),
                        memory_space=pltpu.VMEM)

    def kernel_call(body, k_reps):
        def kern(x_ref, o_ref):
            v = x_ref[0]
            for k in range(k_reps):
                v = body(v, k)
            o_ref[0] = v
        return pl.pallas_call(  # sortlint: disable=SL013 -- on-chip pricing probe, not a production kernel; results feed BASELINE.md, never a sort
            kern,
            out_shape=jax.ShapeDtypeStruct((nblk, s_rows, lanes), jnp.int32),
            grid=(nblk,), in_specs=[spec], out_specs=spec,
        )

    def slope(fn, reps=(1, 17), tries=4):
        out = {}
        for r in reps:
            @jax.jit
            def g(v, r=r):
                for _ in range(r):
                    v = fn(v)
                return v
            y = g(x)
            jax.device_get(y.reshape(-1)[:1])
            ts = []
            for _ in range(tries):
                t0 = time.perf_counter()
                y = g(x)
                jax.device_get(y.reshape(-1)[:1])
                ts.append(time.perf_counter() - t0)
            out[r] = min(ts)
        return (out[reps[1]] - out[reps[0]]) / (reps[1] - reps[0])

    K = 96
    probes = [
        ("copy_pass", kernel_call(lambda v, k: v + 1, 1), 1),  # noise floor ~±0.2 ms
        ("vpu_add", kernel_call(lambda v, k: v + k, K), K),
        ("vpu_min_mul_add", kernel_call(lambda v, k: jnp.minimum(v, v * 2 + k), K), K),
        ("sublane_roll", kernel_call(lambda v, k: pltpu.roll(v, 1 << (k % 6), 0), K), K),
        ("lane_roll", kernel_call(lambda v, k: pltpu.roll(v, 1 << (k % 6), 1), K), K),
        ("transpose_pair",
         kernel_call(lambda v, k: pltpu.roll(v.T, 1, 0).T, K), K),
    ]

    metrics = Metrics(config={"probe": "kernel_primitives",
                              "log2n": args.log2n})
    print(f"{'probe':22s} {'ms/unit':>10s}")
    for name, call, units in probes:
        per = slope(lambda v, c=call: c(v)) / units
        metrics.record(f"{name}_ms", round(per * 1e3, 4), "ms")
        print(f"{name:22s} {per*1e3:10.4f}")

    # 2-word lexicographic compare-exchange layer vs the 1-word form —
    # the measured basis for "the engine stays one-word" (BASELINE.md):
    # a 64-bit key split into (hi, lo) uint32 planes needs 4 rolls + a
    # 5-op lexicographic compare each way + per-word selects, vs the
    # 1-word layer's 2 rolls + min + max + select.  VERDICT r2 #3 asked
    # for this ratio measured, not projected.
    def kernel_call2(body, k_reps):
        def kern(hi_ref, lo_ref, ohi_ref, olo_ref):
            hi, lo = hi_ref[0], lo_ref[0]
            for k in range(k_reps):
                hi, lo = body(hi, lo, k)
            ohi_ref[0], olo_ref[0] = hi, lo
        return pl.pallas_call(  # sortlint: disable=SL013 -- on-chip pricing probe, not a production kernel; results feed BASELINE.md, never a sort
            kern,
            out_shape=[jax.ShapeDtypeStruct((nblk, s_rows, lanes), jnp.int32)] * 2,
            grid=(nblk,), in_specs=[spec, spec], out_specs=[spec, spec],
        )

    def asc_layer_1w(v, k):
        d, log = 1 << (3 + k % 3), 3 + k % 3  # sublane distances, like bitonic
        size = v.shape[0]
        fwd = pltpu.roll(v, size - d, 0)
        bwd = pltpu.roll(v, d, 0)
        idx = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        low = ((idx >> log) & 1) == 0
        return jnp.where(low, jnp.minimum(v, fwd), jnp.maximum(v, bwd))

    def asc_layer_2w(hi, lo, k):
        d, log = 1 << (3 + k % 3), 3 + k % 3
        size = hi.shape[0]
        fhi, flo = pltpu.roll(hi, size - d, 0), pltpu.roll(lo, size - d, 0)
        bhi, blo = pltpu.roll(hi, d, 0), pltpu.roll(lo, d, 0)
        # predicates ride as int32 0/1 — Mosaic rejects selects whose
        # RESULTS are i1 vectors ("unsupported target bitwidth")
        lt_f = ((hi < fhi) | ((hi == fhi) & (lo < flo))).astype(jnp.int32)
        gt_b = ((hi > bhi) | ((hi == bhi) & (lo > blo))).astype(jnp.int32)
        idx = jax.lax.broadcasted_iota(jnp.int32, hi.shape, 0)
        low = ((idx >> log) & 1) == 0
        keep = jnp.where(low, lt_f, gt_b) == 1  # keep self on the winning side
        out_hi = jnp.where(keep, hi, jnp.where(low, fhi, bhi))
        out_lo = jnp.where(keep, lo, jnp.where(low, flo, blo))
        return out_hi, out_lo

    def asc_layer_kp(k, p, kk):
        """Key+payload compare-exchange: min/max on the KEY plane (the
        1-word form, no lexicographic predicate chain) plus one <=/>=
        predicate that routes the PAYLOAD plane.  The core primitive of
        the MSD-hybrid 64-bit structure (sort by hi word, lo rides as
        payload; equal keys keep their own payloads — consistent on both
        sides, so no element is lost).  VERDICT r3 #1 asks this priced
        before building."""
        d, log = 1 << (3 + kk % 3), 3 + kk % 3
        size = k.shape[0]
        fk, fp = pltpu.roll(k, size - d, 0), pltpu.roll(p, size - d, 0)
        bk, bp = pltpu.roll(k, d, 0), pltpu.roll(p, d, 0)
        idx = jax.lax.broadcasted_iota(jnp.int32, k.shape, 0)
        low = ((idx >> log) & 1) == 0
        out_k = jnp.where(low, jnp.minimum(k, fk), jnp.maximum(k, bk))
        # int32 0/1 predicates: Mosaic rejects i1-vector select results
        le = (k <= fk).astype(jnp.int32)
        ge = (k >= bk).astype(jnp.int32)
        keep = jnp.where(low, le, ge) == 1
        out_p = jnp.where(keep, p, jnp.where(low, fp, bp))
        return out_k, out_p

    def asc_layer_kp2(k, p, kk):
        """kp variant: payload route derived from the key RESULT —
        ``keep = (out_k == k)`` (low side: out==k ⟺ k<=partner; high:
        ⟺ k>=partner; ties keep own payload on BOTH sides — a
        consistent no-swap).  One equality replaces two compares + two
        int32 casts + one select of the naive kp form."""
        d, log = 1 << (3 + kk % 3), 3 + kk % 3
        size = k.shape[0]
        fk, fp = pltpu.roll(k, size - d, 0), pltpu.roll(p, size - d, 0)
        bk, bp = pltpu.roll(k, d, 0), pltpu.roll(p, d, 0)
        idx = jax.lax.broadcasted_iota(jnp.int32, k.shape, 0)
        low = ((idx >> log) & 1) == 0
        out_k = jnp.where(low, jnp.minimum(k, fk), jnp.maximum(k, bk))
        out_p = jnp.where(out_k == k, p, jnp.where(low, fp, bp))
        return out_k, out_p

    layer1 = kernel_call(asc_layer_1w, K)
    layer2 = kernel_call2(asc_layer_2w, K)
    layerkp = kernel_call2(asc_layer_kp, K)
    layerkp2 = kernel_call2(asc_layer_kp2, K)
    per1 = slope(lambda v: layer1(v)) / K
    x2 = (x, jnp.asarray(
        rng.integers(-(2**31), 2**31, n, dtype=np.int32)
    ).reshape(nblk, s_rows, lanes))

    def slope2(fn, reps=(1, 17), tries=4):
        out = {}
        for r in reps:
            @jax.jit
            def g(pair, r=r):
                hi, lo = pair
                for _ in range(r):
                    hi, lo = fn(hi, lo)
                return hi, lo
            y = g(x2)
            jax.device_get(y[0].reshape(-1)[:1])
            ts = []
            for _ in range(tries):
                t0 = time.perf_counter()
                y = g(x2)
                jax.device_get(y[0].reshape(-1)[:1])
                ts.append(time.perf_counter() - t0)
            out[r] = min(ts)
        return (out[reps[1]] - out[reps[0]]) / (reps[1] - reps[0])

    per2 = slope2(lambda h, l: layer2(h, l)) / K
    perkp = slope2(lambda h, l: layerkp(h, l)) / K
    perkp2 = slope2(lambda h, l: layerkp2(h, l)) / K
    metrics.record("bitonic_layer_1w_ms", round(per1 * 1e3, 4), "ms")
    metrics.record("bitonic_layer_2w_ms", round(per2 * 1e3, 4), "ms")
    metrics.record("bitonic_layer_2w_ratio", round(per2 / per1, 3), "x")
    metrics.record("bitonic_layer_kp_ms", round(perkp * 1e3, 4), "ms")
    metrics.record("bitonic_layer_kp_ratio", round(perkp / per1, 3), "x")
    metrics.record("bitonic_layer_kp2_ms", round(perkp2 * 1e3, 4), "ms")
    metrics.record("bitonic_layer_kp2_ratio", round(perkp2 / per1, 3), "x")
    print(f"{'bitonic_layer_1w':22s} {per1*1e3:10.4f}")
    print(f"{'bitonic_layer_2w':22s} {per2*1e3:10.4f}   ratio {per2/per1:.2f}x "
          f"(compare against lax.sort's own 2-word penalty — BASELINE.md)")
    print(f"{'bitonic_layer_kp':22s} {perkp*1e3:10.4f}   ratio {perkp/per1:.2f}x "
          f"(key+payload: the MSD-hybrid core primitive)")
    print(f"{'bitonic_layer_kp2':22s} {perkp2*1e3:10.4f}   ratio {perkp2/per1:.2f}x "
          f"(key+payload via out_k==k routing)")

    flat = x.reshape(-1)
    def slope_flat(fn, reps=(1, 3)):
        out = {}
        for r in reps:
            @jax.jit
            def g(v, r=r):
                for _ in range(r):
                    v = fn(v)
                return v
            y = g(flat)
            jax.device_get(y[:1])
            ts = []
            for _ in range(3):
                t0 = time.perf_counter()
                y = g(flat)
                jax.device_get(y[:1])
                ts.append(time.perf_counter() - t0)
            out[r] = min(ts)
        return (out[reps[1]] - out[reps[0]]) / (reps[1] - reps[0])

    for name, fn in [
        ("lax_sort", lambda v: jax.lax.sort([v], num_keys=1, is_stable=False)[0]),
        ("pallas_bitonic", lambda v: jax.lax.bitcast_convert_type(
            bitonic.sort_padded(
                jax.lax.bitcast_convert_type(v, jnp.uint32), n,
                bitonic.BLOCK_LOG2),
            jnp.int32)),
    ]:
        per = slope_flat(fn)
        metrics.record(f"{name}_ms", round(per * 1e3, 2), "ms")
        print(f"{name:22s} {per*1e3:10.2f}")

    metrics.dump()


if __name__ == "__main__":
    main()
