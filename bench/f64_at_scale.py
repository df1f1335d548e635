#!/usr/bin/env python3
"""float64 at scale via host-encoded word planes (VERDICT r4 #4).

This stack cannot hold f64 on device exactly (f32-pair emulation,
~2e-15 rel err) nor lower f64→u32 bitcasts (``models/api.py``
``_f64_known_broken``), so ``sort()`` host-fallbacks for device f64
arrays.  That blocks the *device-array* path, NOT the measurement: the
framework's 64-bit machinery operates on uint32 word planes, and the
f64 totalOrder codec (``ops/keys.py``) produces those on host
losslessly.  This probe encodes on host, ``device_put``s the two word
planes, and times the full adaptive 64-bit device program (pair
network + run fix + residual cond) — the exact sort a
native-f64-capable stack would run — with a bit-exact encoded-median
probe.

Env: ``F64_LOG2N`` (default 27), ``F64_REPEATS`` (default 2).
Appends one JSONL row.
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
        print("f64_at_scale: needs a real TPU", flush=True)
        return 2

    from mpitest_tpu.models.ingest import checked_device_put
    from mpitest_tpu.ops import kernels
    from mpitest_tpu.ops.keys import codec_for

    from mpitest_tpu.utils import knobs

    log2n = knobs.get("F64_LOG2N")
    repeats = knobs.get("F64_REPEATS")
    n = 1 << log2n
    rng = np.random.default_rng(3)
    # Wide-dynamic-range doubles incl. the totalOrder edge cases.
    x = rng.standard_normal(n) * 10.0 ** rng.integers(-250, 250, n)
    x[:4] = [0.0, -0.0, np.inf, -np.inf]
    x = x.astype(np.float64)

    codec = codec_for(np.float64)
    t0 = time.perf_counter()
    hi_np, lo_np = codec.encode(x)
    enc_s = time.perf_counter() - t0
    # Reference: encoded uint64 median (int truncation collides floats).
    enc64 = (hi_np.astype(np.uint64) << np.uint64(32)) | lo_np
    ref_median = int(np.partition(enc64, n // 2 - 1)[n // 2 - 1])

    t0 = time.perf_counter()
    hi = checked_device_put(jnp.asarray(hi_np), jax.devices()[0])
    lo = checked_device_put(jnp.asarray(lo_np), jax.devices()[0])
    jax.device_get(hi[-1:]), jax.device_get(lo[-1:])
    ingest_s = time.perf_counter() - t0
    print(f"host encode {enc_s:.2f}s; ingest {ingest_s:.1f}s "
          f"({x.nbytes / ingest_s / 1e9:.2f} GB/s)", flush=True)

    @jax.jit
    def sort_words(h, l):
        hs, ls, bad, _ = kernels.sort_two_words_bitonic(h, l)
        hs, ls = jax.lax.cond(
            bad,
            lambda a, b: tuple(jax.lax.sort([a, b], num_keys=2,
                                            is_stable=False)),
            lambda a, b: (hs, ls), h, l)
        return hs, ls, bad

    # Warmup (compile) + probe; `residual` records which route the
    # timed runs take (False = pair network, True = lax fallback), so
    # the JSONL row carries its route like every other round-5 row.
    hs, ls, bad = sort_words(hi, lo)
    residual = bool(jax.device_get(bad))
    print(f"route: {'lax fallback (residual)' if residual else 'pair network'}",
          flush=True)
    got = ((int(jax.device_get(hs[n // 2 - 1])) << 32)
           | int(jax.device_get(ls[n // 2 - 1])))
    ok = got == ref_median
    print(f"encoded median probe: {'OK' if ok else 'MISMATCH'} "
          f"({got} vs {ref_median})", flush=True)

    times = []
    for i in range(repeats):
        t0 = time.perf_counter()
        hs, ls, _ = sort_words(hi, lo)
        jax.device_get(hs[-1:])
        dt = time.perf_counter() - t0
        times.append(dt)
        print(f"run {i}: {dt:.3f}s = {n / dt / 1e6:.1f} Mkeys/s", flush=True)
    best = min(times)
    mkeys = n / best / 1e6
    # Round-trip decode check on a sample: codec order law.
    back = codec.decode((np.asarray(jax.device_get(hs[:4096])),
                         np.asarray(jax.device_get(ls[:4096]))))
    mono = bool(np.all(np.diff(back[np.isfinite(back)]) >= 0))
    print(f"decoded prefix monotone: {mono}", flush=True)

    row = {"ts": time.time(),
           "config": f"tpu_f64_words_2e{log2n}_device_resident",
           "metric": "mkeys_per_s", "value": round(mkeys, 1),
           "median_ok": ok, "decoded_monotone": mono,
           "route": "lax_fallback" if residual else "bitonic_pair",
           "span": "device_words", "host_encode_s": round(enc_s, 2)}
    with open(RESULTS, "a") as f:
        f.write(json.dumps(row) + "\n")
    print(f"f64_at_scale: {mkeys:.1f} Mkeys/s "
          f"{'OK' if ok and mono else 'FAIL'}", flush=True)
    return 0 if ok and mono else 1


if __name__ == "__main__":
    sys.exit(main())
