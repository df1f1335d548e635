#!/usr/bin/env python3
"""On-chip smoke test: drive the sort's main path once on a real TPU.

Usage::

    python chip_smoke.py [--seed S]          # one chip: library, server, CLI
    python chip_smoke.py --chips 4 [--seed S]  # four chips: the sharded sort

One process runs every phase (a chip belongs to one process at a time).
Each phase compares its output with ``np.sort`` and asserts from the
tracer that the Mosaic kernels ran: no interpreter, no ``lax`` stand-in,
no degradation rung, no host fallback.  Progress and timings go to
stdout as ``[smoke] ...`` lines; the LAST line is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Any failure raises and exits non-zero without that line.  Without a
TPU, or run from a directory that lacks the repository, the script
exits 2 before any phase.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import shutil
import subprocess
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
#: gitignored scratch directory for the CLI phase's key file
WORK = ROOT / ".chip_smoke"

#: Counter-name parts that mean a hidden fallback or a degradation
#: fired (``*_degraded``, ``*fallback``, the host rung's ``degraded_to``).
_BAD_COUNTERS = ("degraded", "fallback")


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def fail(msg: str) -> "SystemExit":
    print(f"[smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    return SystemExit(2)


def setup() -> None:
    """Build the native libraries from tracked sources and pin the
    knobs that turn silent fallbacks into errors."""
    for cmd in (["make", "-s", "native-encode"],
                ["make", "-s", "-C", "bench", "libspillz"]):
        subprocess.run(cmd, cwd=ROOT, check=True)
    os.environ["SORT_NATIVE_ENCODE"] = "on"
    os.environ["SORT_FALLBACK"] = "0"


def check_counters(tracer, what: str, **expect: str) -> None:
    """Expected engine counters, and no fallback / degradation counter."""
    c = tracer.counters
    for key, want in expect.items():
        if c.get(key) != want:
            raise AssertionError(f"{what}: {key}={c.get(key)!r}, "
                                 f"expected {want!r} (counters {c})")
    bad = {k: v for k, v in c.items()
           if any(b in k for b in _BAD_COUNTERS) and v}
    if bad:
        raise AssertionError(f"{what}: fallback counters fired: {bad}")


def check_equal(out, ref, what: str) -> None:
    import numpy as np

    if out.dtype != ref.dtype or not np.array_equal(out, ref):
        raise AssertionError(f"{what}: result differs from np.sort")


def timed_sort(x, what: str, expect: dict, **kw):
    """Cold call (compile included), then a timed warm call; both
    checked against the engines in ``expect`` (and each verified by the
    sort itself).  Returns the warm call's result and tracer."""
    import mpitest_tpu
    from mpitest_tpu.utils.trace import Tracer

    for label in ("cold", "warm"):
        tr = Tracer()
        t0 = time.perf_counter()
        out = mpitest_tpu.sort(x, tracer=tr, **kw)
        dt = time.perf_counter() - t0
        check_counters(tr, f"{what} ({label})", **expect)
        log(f"{what} {label} {dt:.6f} s "
            f"local_engine={tr.counters.get('local_engine')} "
            f"exchange_engine={tr.counters.get('exchange_engine')}")
    return out, tr


def library_phase(rng, log2_n32: int = 28, log2_n64: int = 27) -> None:
    import numpy as np

    from mpitest_tpu.models.api import ingest_to_mesh
    import mpitest_tpu
    from mpitest_tpu.utils.trace import Tracer

    what = f"library int32 2^{log2_n32}"
    x32 = rng.integers(-2**31, 2**31, size=1 << log2_n32, dtype=np.int32)
    ref32 = np.sort(x32)
    out, _ = timed_sort(x32, what, {"local_engine": "bitonic"})
    check_equal(out, ref32, what)
    # the streamed-ingest entry point: encode + DMA in chunks, then sort
    tr = Tracer()
    t0 = time.perf_counter()
    out = mpitest_tpu.sort(ingest_to_mesh(x32, tracer=tr), tracer=tr)
    log(f"{what} streamed-ingest {time.perf_counter() - t0:.6f} s")
    check_counters(tr, f"{what} streamed", local_engine="bitonic")
    check_equal(out, ref32, f"{what} streamed")
    del x32, ref32, out

    what = f"library int64 2^{log2_n64}"
    x64 = rng.integers(-2**63, 2**63 - 1, size=1 << log2_n64, dtype=np.int64)
    out, _ = timed_sort(x64, what, {"local_engine": "bitonic_pair"})
    check_equal(out, np.sort(x64), what)


def server_phase(rng) -> None:
    import numpy as np

    from mpitest_tpu.serve.client import ServeClient
    from mpitest_tpu.serve.server import ServerCore, SortServer

    core = ServerCore()
    built = core.prewarm(log=lambda m: log(f"server: {m}"))
    if built <= 0:
        raise AssertionError("server prewarm built no executables")
    core.start_watchdog()
    server = SortServer(core, "127.0.0.1", 0)
    th = threading.Thread(target=server.serve_forever, name="smoke-serve",
                          daemon=True)
    th.start()
    reqs = [rng.integers(-2**31, 2**31,
                         size=int(rng.integers(1 << 8, (1 << 16) + 1)),
                         dtype=np.int32) for _ in range(28)]
    reqs += [rng.integers(-2**63, 2**63 - 1,
                          size=int(rng.integers(1 << 8, 1 << 12)),
                          dtype=np.int64) for _ in range(4)]

    def send_all(label: str) -> None:
        t0 = time.perf_counter()
        with ServeClient("127.0.0.1", server.bound_port) as cl:
            for i, r in enumerate(reqs):
                rep = cl.sort(r)
                if not rep.ok:
                    raise AssertionError(f"server request {i} failed: "
                                         f"{rep.header}")
                check_equal(rep.arr, np.sort(r), f"server request {i}")
        log(f"server {label}: {len(reqs)} requests in "
            f"{time.perf_counter() - t0:.6f} s")

    try:
        send_all("first pass")
        st = core.cache.stats
        misses, jit = st.misses, core.tracer.counters.get("jit_first_calls", 0)
        send_all("warm pass")
        new_jit = core.tracer.counters.get("jit_first_calls", 0) - jit
        if st.misses != misses or new_jit:
            raise AssertionError(
                f"warm requests compiled: {st.misses - misses} cache "
                f"misses, {new_jit} first jit calls")
        log(f"server: prewarmed={built} cache hits={st.hits} "
            f"misses={st.misses} compile_s={st.compile_s:.6f} "
            f"ok={core.requests_ok} err={core.requests_err}")
        check_counters(core.tracer, "server")
        if core.requests_err:
            raise AssertionError(f"{core.requests_err} server errors")
    finally:
        drained = core.drain_and_stop(timeout=60.0)
        server.shutdown()
        server.server_close()
        th.join(timeout=10.0)
    if not drained:
        raise AssertionError("server did not drain cleanly")


def cli_phase(rng, log2_n: int = 24) -> None:
    import numpy as np

    from drivers import sort_cli
    from mpitest_tpu.utils.io import write_keys_text

    WORK.mkdir(exist_ok=True)
    path = WORK / f"keys_2p{log2_n}.txt"
    x = rng.integers(-2**31, 2**31, size=1 << log2_n, dtype=np.int32)
    write_keys_text(str(path), x)
    buf = io.StringIO()
    t0 = time.perf_counter()
    try:
        with contextlib.redirect_stdout(buf):
            rc = sort_cli.main(["sort_cli.py", str(path)])
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    log(f"cli 2^{log2_n} text file: rc={rc} in {time.perf_counter() - t0:.6f} s")
    if rc != 0:
        raise AssertionError(f"sort_cli exited {rc}: {buf.getvalue()}")
    want = int(np.sort(x)[(1 << log2_n) // 2 - 1])
    line = f"The n/2-th sorted element: {want}"
    if line not in buf.getvalue().splitlines():
        raise AssertionError(f"cli: expected {line!r}, got "
                             f"{buf.getvalue()!r}")
    log(f"cli: {line}")


def four_chip_phase(rng, log2_n32: int = 28, log2_n64: int = 27) -> None:
    import jax
    import numpy as np

    from mpitest_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(4)
    x32 = rng.integers(-2**31, 2**31, size=1 << log2_n32, dtype=np.int32)
    ref32 = np.sort(x32)
    x64 = rng.integers(-2**63, 2**63 - 1, size=1 << log2_n64, dtype=np.int64)
    ref64 = np.sort(x64)
    cases = [
        ("radix", "pallas", x32, ref32, "lax"),
        ("sample", "pallas", x32, ref32, "bitonic"),
        ("radix", "lax", x32, ref32, "lax"),
        ("sample", "lax", x32, ref32, "bitonic"),
        ("sample", "pallas", x64, ref64, "bitonic"),
    ]
    for algo, xeng, x, ref, leng in cases:
        what = f"4-chip {algo} {x.dtype} 2^{x.size.bit_length() - 1} {xeng}"
        res, tr = timed_sort(x, what, {"exchange_engine": xeng,
                                       "local_engine": leng},
                             algorithm=algo, mesh=mesh, exchange_engine=xeng,
                             return_result=True)
        devs = {d for w in res.words for d in w.sharding.device_set}
        if len(devs) != 4 or any(len(w.addressable_shards) != 4
                                 for w in res.words):
            raise AssertionError(f"{what}: result not sharded over 4 "
                                 f"devices ({devs})")
        check_equal(res.to_numpy(), ref, what)
        log(f"{what}: equal to np.sort, passes="
            f"{tr.counters.get('exchange_passes')} "
            f"cap={tr.counters.get('exchange_cap')} "
            f"on {len(jax.devices())} devices")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "mpitest_tpu").is_dir():
        raise fail(f"no mpitest_tpu package next to {__file__}; run from "
                   "a checkout of the repository")
    sys.path.insert(0, str(ROOT))
    from mpitest_tpu.utils.platform import enable_compile_cache

    cache = enable_compile_cache()
    import jax
    import numpy as np

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise fail(f"no TPU: JAX platform is {devs[0].platform!r}")
    if len(devs) < args.chips:
        raise fail(f"--chips {args.chips} needs {args.chips} TPU devices, "
                   f"found {len(devs)}")
    log(f"jax {jax.__version__}: {len(devs)} x {devs[0].device_kind}; "
        f"compile cache {cache}")
    setup()
    rng = np.random.default_rng(args.seed)
    phases = ([four_chip_phase] if args.chips == 4
              else [library_phase, server_phase, cli_phase])
    for phase in phases:
        t0 = time.perf_counter()
        log(f"phase {phase.__name__} start")
        phase(rng)
        log(f"phase {phase.__name__} ok in {time.perf_counter() - t0:.6f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
