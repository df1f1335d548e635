#!/usr/bin/env python3
"""Ragged-gather DMA probe — the BUILT linear-work-movement experiment
(VERDICT r2 #2: "build, don't model").

BASELINE.md prices every linear-work alternative to the bitonic engine
(DMA-composed radix, one-hot MXU permutation, butterfly splits) from
component measurements plus a "fragmentation law": moving (block, digit)
runs by DMA costs ``n·G/B`` run-copies per pass, each charged a serial
~0.5 us issue cost.  That issue-cost assumption was modeled, never
measured — and it is THE deciding number: any radix/MSD hybrid's merge
phase is "concatenate R variable-length runs into the output in a
permuted order", i.e. exactly this kernel.  If real DMA issue overlaps
(multiple outstanding copies hide the latency), the law's 30 ms/pass
floor collapses and a blocksort+DMA-merge MSD sort could beat the
bitonic engine; if issue serializes, the boundary claim gets its
measured footing.

The kernel (built on the ``segment_pack`` misaligned-copy pattern,
``ops/pallas_kernels.py``): grid over 1024-element output chunks; each
chunk gathers up to K source segments (descriptors precomputed on the
host and streamed per-chunk into SMEM: src base, destination offset in
chunk, length).  All K segment DMAs are STARTED before the first wait,
so within a chunk the copies overlap; Mosaic's grid pipelining overlaps
chunks.  Each segment lands via one aligned 2-tile DMA + a vectorized
roll-shift + mask-blend — no per-element addressing anywhere.
Correctness is asserted against the numpy concatenation on every
configuration before it is timed.

Measured sweep: run lengths 2^13 .. 2^8 at 2^26 elements — spanning the
(G, B) design space of any DMA-composed scheme (run length = B/G).

Usage: python bench/ragged_gather_probe.py [--log2n 26] [--interpret]
"""

from __future__ import annotations

import argparse
import functools
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

LANES = 128
ROWS = 8
CHUNK = ROWS * LANES  # 1024 elements = one output tile


def build_descriptors(run_starts, run_lens, order, nchunk):
    """Host-side: for each output chunk, the (src_base, dst_off, len)
    descriptors covering its slice of the permuted-run concatenation.
    K is sized to the actual maximum segments per chunk — padding slots
    would otherwise inflate the measured per-chunk cost with dummy
    DMA+blend work (round-3 review finding)."""
    import numpy as np

    starts = np.asarray(run_starts)[order]
    lens = np.asarray(run_lens)[order]
    out_off = np.concatenate([[0], np.cumsum(lens)])
    total = int(out_off[-1])
    segs = [[] for _ in range(nchunk)]
    for r in range(len(lens)):
        o, ln = int(out_off[r]), int(lens[r])
        src = int(starts[r])
        while ln > 0:
            c = o // CHUNK
            take = min(ln, (c + 1) * CHUNK - o)
            segs[c].append((src, o - c * CHUNK, take))
            o += take
            src += take
            ln -= take
    assert total % CHUNK == 0
    K = max(len(s) for s in segs)
    desc = np.zeros((nchunk, K, 3), np.int32)
    for c, s in enumerate(segs):
        for k, row in enumerate(s):
            desc[c, k] = row
    return desc, K


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--log2n", type=int, default=26)
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--platform", default=None,
                    help="cpu forces the virtual-CPU backend (CI)")
    args = ap.parse_args()

    if args.platform == "cpu":
        from mpitest_tpu.utils.platform import ensure_virtual_cpu_devices

        ensure_virtual_cpu_devices(1)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from mpitest_tpu.utils.metrics import Metrics

    n = 1 << args.log2n
    nchunk = n // CHUNK

    def gather_kernel(K, desc_ref, data_ref, out_ref, scratch, sem):
        elem = (jax.lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 0) * LANES
                + jax.lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 1))
        for k in range(K):  # issue ALL segment DMAs up front: overlap
            src = desc_ref[0, k, 0]
            arow = pl.multiple_of(((src // LANES) // ROWS) * ROWS, ROWS)
            pltpu.make_async_copy(
                data_ref.at[pl.ds(arow, 2 * ROWS), :], scratch.at[k], sem.at[k]
            ).start()
        acc = jnp.zeros((ROWS, LANES), jnp.uint32)
        for k in range(K):
            src = desc_ref[0, k, 0]
            dst = desc_ref[0, k, 1]
            ln = desc_ref[0, k, 2]
            arow = pl.multiple_of(((src // LANES) // ROWS) * ROWS, ROWS)
            pltpu.make_async_copy(
                data_ref.at[pl.ds(arow, 2 * ROWS), :], scratch.at[k], sem.at[k]
            ).wait()
            # shift the 2-tile window so window[sh + e] lands at element e
            # (sh may be negative — rolls are cyclic and the 16-row window
            # covers every index sh+e in [0, 2048) exactly)
            sh = (src - arow * LANES) - dst
            x = scratch[k]
            r = sh // LANES
            l = sh - r * LANES  # 0..127
            a = pltpu.roll(x, -r, 0)
            b = pltpu.roll(x, -(r + 1), 0)
            la = pltpu.roll(a, -l, 1)
            lb = pltpu.roll(b, -l, 1)
            lane = jax.lax.broadcasted_iota(jnp.int32, (2 * ROWS, LANES), 1)
            y = jnp.where(lane < LANES - l, la, lb)[:ROWS, :]
            sel = (elem >= dst) & (elem < dst + ln)
            acc = jnp.where(sel, y, acc)
        out_ref[0] = acc

    @functools.partial(jax.jit, static_argnames=("K", "interpret"))
    def ragged_gather(data, desc, K, interpret=False):
        pad = (-n) % LANES + 2 * CHUNK
        data_2d = jnp.concatenate(
            [data, jnp.zeros((pad,), data.dtype)]
        ).reshape(-1, LANES)
        out = pl.pallas_call(  # sortlint: disable=SL013 -- rejected-design probe (measures why the gather kernel lost); never on a production path
            functools.partial(gather_kernel, K),
            grid=(nchunk,),
            in_specs=[
                pl.BlockSpec((1, K, 3), lambda c: (c, 0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, ROWS, LANES), lambda c: (c, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((nchunk, ROWS, LANES), jnp.uint32),
            scratch_shapes=[
                pltpu.VMEM((K, 2 * ROWS, LANES), jnp.uint32),
                pltpu.SemaphoreType.DMA((K,)),
            ],
            interpret=interpret,
        )(desc, data_2d)
        return out.reshape(-1)

    rng = np.random.default_rng(0)
    data_np = rng.integers(0, 2**32, n, dtype=np.uint32)
    data = jnp.asarray(data_np)

    def timed(g, v):
        t0 = time.perf_counter()
        y = g(v)
        jax.device_get(y[:1])
        return time.perf_counter() - t0

    metrics = Metrics(config={"probe": "ragged_gather", "log2n": args.log2n})
    print(f"{'layout':>8s} {'run_len':>8s} {'runs':>9s} {'K':>3s} {'ms':>9s} "
          f"{'GB/s':>7s} {'us/run':>7s}")
    configs = []
    for run_log2 in (13, 12, 11, 10, 9, 8):
        # aligned: uniform chunk-multiple runs (the kindest case — each
        # chunk is exactly one segment); ragged: lengths jittered ±25%
        # like real digit runs, so segments straddle chunk boundaries.
        configs.append(("aligned", run_log2, False))
        configs.append(("ragged", run_log2, True))
    for layout, run_log2, jitter in configs:
        run_len = 1 << run_log2
        nruns = n // run_len
        if nruns < 1:
            print(f"{layout:>8s} {run_len:8d} — skipped (n < run_len)")
            continue
        if jitter:
            # bounded ±25% jitter, total corrected back to n by spreading
            # the residual ±1 per run — lengths stay within [run_len/2,
            # 3·run_len/2], so the per-chunk segment count (K) stays
            # bounded instead of spiking on an outlier chunk
            d = rng.integers(-(run_len // 4), run_len // 4 + 1,
                             size=nruns).astype(np.int64)
            d -= d.sum() // nruns
            res = int(d.sum())
            sgn = 1 if res < 0 else -1
            d[: abs(res)] += sgn
            lens = run_len + d
            assert int(lens.sum()) == n and (lens > 0).all()
            starts = np.concatenate([[0], np.cumsum(lens)[:-1]])
        else:
            starts = np.arange(nruns, dtype=np.int64) * run_len
            lens = np.full(nruns, run_len, np.int64)
        order = rng.permutation(nruns)
        desc, K = build_descriptors(starts, lens, order, nchunk)
        desc_j = jnp.asarray(desc)

        out = ragged_gather(data, desc_j, K, interpret=args.interpret)
        want = data_np[
            np.concatenate([np.arange(starts[r], starts[r] + lens[r])
                            for r in order])
        ]
        # Position-weighted checksums, computed on device (pulling 256 MB
        # to the host per config would dominate the probe): two independent sum_i out[i]*phi_m(i) mod 2^32 — any
        # misplacement, drop, or duplication flips them with probability
        # ~1-2^-64.  All uint32: Mosaic/this image lack i64 vectors.
        MULS = (np.uint32(2654435761), np.uint32(0x9E3779B1 ^ 0x55555555))

        @jax.jit
        def checksum(v):
            i = jnp.arange(v.shape[0], dtype=jnp.uint32)
            return tuple(
                jnp.sum(v * ((i + jnp.uint32(m0)) * jnp.uint32(mul)),
                        dtype=jnp.uint32)
                for m0, mul in ((1, MULS[0]), (7, MULS[1]))
            )

        i_np = np.arange(n, dtype=np.uint32)
        want_sums = tuple(
            int(np.sum(want * ((i_np + np.uint32(m0)) * mul),
                       dtype=np.uint32))
            for m0, mul in ((1, MULS[0]), (7, MULS[1]))
        )
        got_sums = tuple(int(s) for s in jax.device_get(checksum(out)))
        assert got_sums == want_sums, f"MISMATCH at run_len={run_len}"

        # slope timing: the gather's output is a same-length uint32 array,
        # so chain reps by feeding it back — same access pattern per rep.
        ts = {}
        for reps in (1, 3):
            @jax.jit
            def g(v, reps=reps):
                for _ in range(reps):
                    v = ragged_gather(v, desc_j, K, interpret=args.interpret)
                return v
            y = g(data)
            jax.device_get(y[:1])
            ts[reps] = min(timed(g, data) for _ in range(3))
        per = (ts[3] - ts[1]) / 2
        gbs = 2 * 4 * n / per / 1e9
        metrics.record(f"ragged_gather_{layout}_runlen{run_len}_ms",
                       round(per * 1e3, 3), "ms")
        print(f"{layout:>8s} {run_len:8d} {nruns:9d} {K:3d} {per*1e3:9.2f} "
              f"{gbs:7.1f} {per/nruns*1e6:7.3f}")
    metrics.dump()


if __name__ == "__main__":
    main()
