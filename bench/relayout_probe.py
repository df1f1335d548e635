#!/usr/bin/env python3
"""On-chip A/B probe for the round-5 rotation-relayout cross schedule.

VERDICT r4 #1: 56% of the pair network is 36 single cross layers at
~2.5x their streaming floor (3n traffic each: two reads + one write).
The relayout schedule fuses them into 4-member closure visits (2 layers
per n-read + n-write) plus a rotation-aware merge.  This probe measures
build-or-refute on the real chip:

1. Correctness ON DEVICE at 2^26: relayout keys bit-equal to the
   variadic ``lax.sort`` keys; pair multiset preserved (order-invariant
   pairing-sensitive checksum); and the two schedules' key planes
   bit-equal to each other.
2. Slope timings (two rep counts, forced scalar sync — see verify
   skill): relayout network vs round-4 network vs variadic 2-word
   ``lax.sort``, plus the full ``sort_two_words_bitonic`` path.

Resumable: ``PROBE_PARTS=agree,net,1w,full`` (default all),
``PROBE_LOG2N`` (default 26).
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
        print("relayout_probe: needs a real TPU", flush=True)
        return 2

    from mpitest_tpu.ops import bitonic, kernels

    from mpitest_tpu.utils import knobs

    log2n = knobs.get("PROBE_LOG2N")
    parts = knobs.get("PROBE_PARTS")
    n = 1 << log2n
    rng = np.random.default_rng(7)
    k = jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint64)
                    .astype(np.uint32))
    p = jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint64)
                    .astype(np.uint32))
    B = bitonic.PAIR_BLOCK_LOG2
    row: dict = {"ts": time.time(), "config": f"relayout_probe_2e{log2n}"}
    ok = True

    def cksum(kk, pp):
        """Order-invariant, pairing-sensitive: mixes each pair before
        the commutative reduces."""
        m = (kk * jnp.uint32(2654435761)) ^ pp
        x = jax.lax.reduce(  # sortlint: disable=SL010 -- single-device jit checksum, no SPMD partitioner
            m, jnp.uint32(0), jax.lax.bitwise_xor, (0,))
        return m.sum(), x

    if "agree" in parts:
        @jax.jit
        def agree(kk, pp):
            rk, rp = bitonic.sort_pairs_padded(kk, pp, n, B, relayout=True)
            ok_, op = bitonic.sort_pairs_padded(kk, pp, n, B, relayout=False)
            ref = jax.lax.sort([kk, pp], num_keys=2, is_stable=False)
            s_in, x_in = cksum(kk, pp)
            s_r, x_r = cksum(rk, rp)
            return (jnp.all(rk == ref[0]), jnp.all(rk == ok_),
                    (s_in == s_r) & (x_in == x_r))

        t0 = time.perf_counter()
        vs_lax, vs_old, multiset = (bool(v) for v in
                                    jax.device_get(agree(k, p)))
        print(f"relayout keys==lax: {vs_lax}  ==r4-schedule: {vs_old}  "
              f"pair-multiset: {multiset} "
              f"({time.perf_counter() - t0:.1f}s incl. compile)", flush=True)
        row.update(relayout_keys_match_lax=vs_lax,
                   relayout_keys_match_r4=vs_old,
                   relayout_pair_multiset_ok=multiset)
        ok &= vs_lax and vs_old and multiset

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
        return (out[reps[1]] - out[reps[0]]) / (reps[1] - reps[0])

    if "net" in parts:
        new_ms = slope(
            lambda kk, pp: bitonic.sort_pairs_padded(kk, pp, n, B,
                                                     relayout=True),
            (k, p)) * 1e3
        print(f"pair network relayout: {new_ms:.1f} ms", flush=True)
        old_ms = slope(
            lambda kk, pp: bitonic.sort_pairs_padded(kk, pp, n, B,
                                                     relayout=False),
            (k, p)) * 1e3
        print(f"pair network r4:       {old_ms:.1f} ms "
              f"(relayout {old_ms / new_ms:.2f}x faster)", flush=True)
        row.update(pair_net_relayout_ms=round(new_ms, 1),
                   pair_net_r4_ms=round(old_ms, 1))

    if "1w" in parts:
        @jax.jit
        def agree1(kk):
            r = bitonic.sort_padded(kk, n, bitonic.BLOCK_LOG2, relayout=True)
            o = bitonic.sort_padded(kk, n, bitonic.BLOCK_LOG2, relayout=False)
            ref = jax.lax.sort([kk], num_keys=1, is_stable=False)[0]
            return jnp.all(r == ref), jnp.all(r == o)

        vs_lax1, vs_old1 = (bool(v) for v in jax.device_get(agree1(k)))
        print(f"1w relayout keys==lax: {vs_lax1}  ==r4-schedule: {vs_old1}",
              flush=True)
        row.update(relayout1w_matches_lax=vs_lax1,
                   relayout1w_matches_r4=vs_old1)
        ok &= vs_lax1 and vs_old1
        new1 = slope(
            lambda kk: (bitonic.sort_padded(kk, n, bitonic.BLOCK_LOG2,
                                            relayout=True),), (k,)) * 1e3
        old1 = slope(
            lambda kk: (bitonic.sort_padded(kk, n, bitonic.BLOCK_LOG2,
                                            relayout=False),), (k,)) * 1e3
        lax1 = slope(
            lambda kk: (jax.lax.sort([kk], num_keys=1, is_stable=False)[0],),
            (k,)) * 1e3
        print(f"1w net relayout {new1:.1f} ms  r4 {old1:.1f} ms  "
              f"lax {lax1:.1f} ms  (vs lax {lax1 / new1:.2f}x)", flush=True)
        row.update(net1w_relayout_ms=round(new1, 1),
                   net1w_r4_ms=round(old1, 1), lax_sort_1w_ms=round(lax1, 1))

    if "full" in parts:
        full_ms = slope(
            lambda kk, pp: kernels.sort_two_words_bitonic(kk, pp)[:2],
            (k, p)) * 1e3
        lax2_ms = slope(
            lambda kk, pp: tuple(jax.lax.sort([kk, pp], num_keys=2,
                                              is_stable=False)),
            (k, p)) * 1e3
        print(f"full pair path: {full_ms:.1f} ms  lax 2w: {lax2_ms:.1f} ms  "
              f"ratio {lax2_ms / full_ms:.2f}x", flush=True)
        row.update(pair_full_ms=round(full_ms, 1),
                   lax_sort_2w_ms=round(lax2_ms, 1),
                   pair_speedup=round(lax2_ms / full_ms, 2))

    row["all_ok"] = ok
    with open(RESULTS, "a") as f:
        f.write(json.dumps(row) + "\n")
    print(f"relayout_probe: {'OK' if ok else 'FAIL'}", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
