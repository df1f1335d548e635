"""Final randomized stress validation on the real chip via the public API."""
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import mpitest_tpu
from mpitest_tpu.utils.io import generate

def randomized_api_battery() -> None:
    rng = np.random.default_rng(123)
    mesh = mpitest_tpu.make_mesh()
    fails = 0
    cases = []
    for trial in range(14):
        n = int(rng.integers(1, 3_000_000))
        dtype = rng.choice([np.int32, np.uint32, np.int64, np.uint64,
                            np.float32, np.float64])
        algo = rng.choice(["radix", "sample"])
        dt = np.dtype(dtype)
        if dt.kind != "f" and rng.choice(["full", "narrow"]) == "narrow":
            x = rng.integers(0, 1000, n).astype(dt)  # heavy-duplication span
        else:
            x = generate("uniform", n, dt, seed=int(rng.integers(2**31)))
        got = mpitest_tpu.sort(x, algorithm=str(algo), mesh=mesh)
        ok = np.array_equal(got, np.sort(x))
        cases.append((n, dt.name, str(algo), ok))
        if not ok:
            fails += 1
            print("FAIL", cases[-1])
    print(f"{len(cases)-fails}/{len(cases)} stress cases OK")


def adversarial_patterns_at_scale(log2n: int = 28) -> None:
    """Extreme input patterns at full scale, verified ON DEVICE (sorted +
    sum/xor multiset invariants) — a full result download would
    dominate otherwise.  Catches scale-dependent kernel bugs the
    small-shape interpret tests cannot."""
    import jax
    import jax.numpy as jnp

    from mpitest_tpu.ops import bitonic

    n = 1 << log2n

    @jax.jit
    def sort_and_check(v):
        out = bitonic.sort_padded(v, n, bitonic.BLOCK_LOG2)
        is_sorted = jnp.all(out[1:] >= out[:-1])
        sum_ok = v.sum() == out.sum()
        xor = lambda a: jax.lax.reduce(a, jnp.uint32(0),  # sortlint: disable=SL010 -- single-device jit checksum, no SPMD partitioner
                                       jax.lax.bitwise_xor, (0,))
        return is_sorted, sum_ok, xor(v) == xor(out)

    r = np.random.default_rng(0)
    pats = {
        "sorted": np.arange(n, dtype=np.uint32),
        "reverse": np.arange(n, 0, -1).astype(np.uint32),
        "all-equal": np.full(n, 0xABCD1234, np.uint32),
        "few-distinct": r.integers(0, 3, n).astype(np.uint32),
        "organ-pipe": np.concatenate([
            np.arange(n // 2, dtype=np.uint32),
            np.arange(n // 2, 0, -1).astype(np.uint32)]),
    }
    for name, x in pats.items():
        checks = [bool(t) for t in
                  jax.device_get(sort_and_check(jnp.asarray(x)))]
        assert all(checks), (name, checks)
        print(f"adversarial {name} @2^{log2n}: OK")


def adversarial_patterns_64(log2n: int = 26) -> None:
    """At-scale int64 battery through the PUBLIC API on the pair engine
    (round 4): one pattern per adaptive route — pair engine, both
    constant-word shortcuts, the duplication-sniff reroute, and the
    residual on-device fallback (runs the sniff cannot see) — each
    verified ON DEVICE (lexicographic sortedness of the word planes +
    per-word sum/xor multiset invariants vs the encoded input; results
    never cross to the host).  Asserts the tracer recorded the expected
    engine route, so a silent routing regression fails loudly.

    ``STRESS64_PATTERNS=a,b`` selects a subset (resumable);
    ``STRESS64_LOG2N`` overrides the size.
    """
    import os

    import jax
    import jax.numpy as jnp

    jax.config.update("jax_enable_x64", True)
    from mpitest_tpu.models.api import checked_device_put
    from mpitest_tpu.ops.keys import codec_for
    from mpitest_tpu.utils.trace import Tracer

    from mpitest_tpu.utils import knobs

    log2n = knobs.get("STRESS64_LOG2N") or log2n
    n = 1 << log2n
    r = np.random.default_rng(5)
    codec = codec_for(np.int64)

    def runs_of(length):
        # runs of `length` equal-hi keys over ~n/length distinct hi
        # values: far too many distinct values for the 1024-key sniff
        # to see.  At length <= 16 the in-VMEM fix-up (round-5 mid-tier,
        # bench/fixdepth_probe.py) handles them with NO fallback; above
        # it the residual flag MUST fire and the on-device lax fallback
        # must produce exact bytes.
        def gen():
            # DISTINCT hi values (odd-multiplier hash of arange is
            # injective mod 2^31): drawing n/16 values with replacement
            # from 2^31 yields ~n^2/2^37 birthday collisions, each
            # merging two runs into one of 2*length — which legitimately
            # exceeds the fix depth and made the expected route flaky.
            k = n // length + 1
            hi = ((np.arange(k, dtype=np.int64) * 2654435761) % (2**31))
            hi = np.repeat(hi, length)[:n]
            x = (hi << 32) | r.integers(0, 2**32, n).astype(np.int64)
            r.shuffle(x)
            return x
        return gen

    pats = {
        # name: (generator, accepted engine routes)
        "uniform": (lambda: r.integers(-(2**63), 2**63 - 1, n,
                                       dtype=np.int64),
                    {"bitonic_pair"}),
        "narrow-hi": (lambda: r.integers(0, 2**31, n, dtype=np.int64),
                      {"bitonic_1w1"}),
        "wide-lo-const": (lambda: (n - 1 - np.arange(n, dtype=np.int64)) << 37,
                          {"bitonic_1w0"}),
        "all-equal": (lambda: np.full(n, -42, np.int64), {"constant"}),
        # hi from 8 values: the sniff must catch it and reroute
        "hi-dup8": (lambda: (r.integers(0, 8, n).astype(np.int64) << 33)
                    | r.integers(0, 2**32, n).astype(np.int64), {"lax"}),
        # covered by the 16-pass in-VMEM fix-up: no residual fallback
        # (r5).  The 1024-key sniff still has ~11% odds at 2^26 of
        # sampling two members of one run and rerouting up front —
        # 'lax' is a correct (if pessimistic) route, like mid-runs24.
        "mid-runs16": (runs_of(16), {"bitonic_pair", "lax"}),
        # sniff usually misses (residual fallback); a lucky sample
        # collision may reroute up front — both are correct routes
        "mid-runs24": (runs_of(24), {"bitonic_pair+lax_fallback", "lax"}),
    }
    only = knobs.get("STRESS64_PATTERNS")
    sel = set(only) if only else None

    @jax.jit
    def check(x, hi_o, lo_o):
        hi_i, lo_i = codec.encode_jax(x)
        asc = (hi_o[1:] > hi_o[:-1]) | ((hi_o[1:] == hi_o[:-1])
                                        & (lo_o[1:] >= lo_o[:-1]))
        xor = lambda a: jax.lax.reduce(a, jnp.uint32(0),  # sortlint: disable=SL010 -- single-device jit checksum, no SPMD partitioner
                                       jax.lax.bitwise_xor, (0,))
        return (jnp.all(asc),
                (hi_i.sum() == hi_o.sum()) & (lo_i.sum() == lo_o.sum()),
                (xor(hi_i) == xor(hi_o)) & (xor(lo_i) == xor(lo_o)))

    for name, (gen, routes) in pats.items():
        if sel is not None and name not in sel:
            continue
        x = gen()
        dev = checked_device_put(x, jax.devices()[0])
        jax.device_get(dev[-1:])  # materialize the (lazy) ingest
        tracer = Tracer()
        res = mpitest_tpu.sort(dev, algorithm="radix", return_result=True,
                               tracer=tracer)
        hi_o, lo_o = res.words
        checks = [bool(t) for t in jax.device_get(check(dev, hi_o, lo_o))]
        route = tracer.counters.get("local_engine")
        ok = all(checks) and route in routes
        print(f"int64 {name} @2^{log2n}: "
              f"{'OK' if ok else f'FAIL {checks}'} route={route}"
              f"{'' if route in routes else f' (expected {sorted(routes)})'}",
              flush=True)
        assert ok, (name, checks, route)
        del res, hi_o, lo_o, dev


if __name__ == "__main__":
    # `--patterns` runs ONLY the at-scale adversarial battery; \
    # `--patterns64` the int64 pair-engine battery (each mode alone
    # fits a 10-minute chip budget); default = the randomized
    # cross-dtype API battery.
    if "--patterns64" in sys.argv:
        adversarial_patterns_64()
    elif "--patterns" in sys.argv:
        adversarial_patterns_at_scale()
    else:
        randomized_api_battery()
