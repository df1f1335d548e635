"""Benchmark driver: one JSON metric line on stdout, details on stderr.

Primary metric (BASELINE.md rows 3-4): sort throughput in Mkeys/s on the
flagship device-resident path at the driver-specified scale (2^28
default on TPU; 2^30 via BENCH_LOG2N=30 when HBM allows).

``vs_baseline`` is the north-star ratio (BASELINE.json): this framework
vs the repo's OWN native backend at 8 host-CPU ranks sorting the same
keys at the same N — the moral equivalent of the reference's
``mpirun -np 8`` on one host.  ``vs_np_sort`` (single-core ``np.sort``)
is reported as a secondary field.

The timed span is the framework's steady-state contract: keys start and
end **device-resident and sharded on the mesh** (the design removes
every root/host round-trip the reference pays — SURVEY.md §5
long-context row), so the metric times encode + full SPMD sort to
completion.  The host→device ingest runs through the streamed pipeline
(models/ingest.py: chunked parse/encode overlapped with per-shard DMA)
and is reported separately in the stderr sidecar with parse/encode/
transfer sub-metrics and overlap efficiency;
``sort_incl_ingest_mkeys_per_s`` is ONE measured end-to-end run of
streamed ingest + sort on the staged words (ISSUE 2 headline).  Every
dispatch carries a fixed host cost that amortizes with N, one reason the
target scale is 2^28+.

Env knobs: BENCH_LOG2N (default 28 on TPU, 20 on CPU), BENCH_ALGO
(radix|sample), BENCH_REPEATS (default 3), BENCH_DTYPE (int32),
BENCH_NATIVE_RANKS (default 8; 0 disables the native denominator),
BENCH_NATIVE_REPEATS (default 3 — the denominator is the MEDIAN of
these runs; see CANONICAL_NATIVE_MKEYS for the pinned cross-round
protocol, VERDICT r4 weak #4).

Output contract: one JSON line per measured configuration — the primary
row (unchanged since round 1, so the r01+ trajectory stays comparable)
plus, unless ``BENCH_MULTICHIP=off``, the ``devices=8`` scale-out row
(ISSUE 7): measured on the real mesh when >= 8 chips are visible, else
on a ``BENCH_PLATFORM=cpu:8`` virtual mesh in a subprocess, at the
largest N that fits, carrying per-rank exchange balance and the
negotiated-vs-worst-case capacity saving.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def tooling_state() -> dict:
    """The lint/sanitizer gate state stamped into every bench row, so a
    BENCH number is attributable to the tooling that was in force when
    it was measured (ISSUE 4): sortlint version + rule count, the C
    warning flags, the sanitizer matrix, and the mypy version when the
    strict gate could run (None = gate skipped on this image)."""
    t: dict = {
        "cwarn": "-Wconversion -Wshadow -Werror",
        "sanitize": "tsan:local asan,ubsan:local+minimpi",
    }
    try:
        from tools.sortlint import LINT_VERSION, RULES

        t["sortlint"] = LINT_VERSION
        t["sortlint_rules"] = len(RULES)
    except Exception as e:  # tools/ not importable: record why, loudly
        t["sortlint"] = f"unavailable ({type(e).__name__})"
    try:
        from mypy.version import __version__ as mypy_version

        t["mypy"] = mypy_version
    except Exception:
        t["mypy"] = None
    return t


def encoded_median(x_or_scalar, dtype: np.dtype) -> int:
    """Collapse key(s) to one comparable integer for the median probe:
    the native value for ints; the encoded totalOrder bit pattern for
    floats (int truncation collides distinct float medians, and
    ``np.sort``'s placement of ±0.0 at the median index need not match
    totalOrder).  Arrays are encoded, sorted, and probed at n/2-1;
    scalars are encoded directly."""
    from mpitest_tpu.ops.keys import codec_for

    arr = np.asarray(x_or_scalar, dtype=dtype).reshape(-1)
    if dtype.kind != "f":
        val = np.sort(arr)[arr.size // 2 - 1] if arr.size > 1 else arr[0]
        return int(val)
    words = codec_for(dtype).encode(arr)
    enc = words[0] if len(words) == 1 else (
        (words[0].astype(np.uint64) << np.uint64(32)) | words[1])
    return int(np.sort(enc)[arr.size // 2 - 1]) if arr.size > 1 else int(enc[0])


#: The scale-out row's mesh size (ISSUE 7): the north-star target shape
#: is v5e-8, and the TPU-less fallback (`BENCH_PLATFORM=cpu:8`) uses the
#: same count so the row is structurally identical either way.
MULTICHIP_DEVICES = 8


def _measure_multichip(algo: str, dtype: np.dtype, log2n: int,
                       repeats: int, platform: str) -> dict | None:
    """Measure the ``devices=8`` scale-out row on ``make_mesh(8)`` —
    requires >= 8 visible devices (real chips or a virtual CPU mesh).

    "Largest N that fits": starts at ``log2n`` and backs off one power
    of two per RESOURCE_EXHAUSTED until the sharded sort completes (the
    2^30-on-v5e-8 target is HBM-edge by design).  The row carries the
    scale-out telemetry the 1-device rows cannot: per-rank exchange-byte
    balance (max/mean), the negotiated-vs-worst-case capacity saving,
    and whether the skew re-stage fired.  Returns None (after logging)
    when nothing fits — never kills the primary row."""
    import jax

    from mpitest_tpu.models.api import (SortRetryExhausted,
                                        checked_device_put, sort)
    from mpitest_tpu.parallel.mesh import key_sharding, make_mesh
    from mpitest_tpu.utils import knobs, timeline
    from mpitest_tpu.utils.io import generate
    from mpitest_tpu.utils.metrics import Metrics
    from mpitest_tpu.utils.trace import Tracer

    mesh = make_mesh(MULTICHIP_DEVICES)
    x = x_dev = None
    while log2n >= 16:
        n = 1 << log2n
        try:
            x = generate("uniform", n, dtype, seed=0)
            ref_median = encoded_median(x, dtype)
            x_dev = checked_device_put(x, key_sharding(mesh))
            x_dev.block_until_ready()
            log(f"multichip: devices={MULTICHIP_DEVICES} algo={algo} "
                f"N=2^{log2n} dtype={dtype}")
            tracer = Tracer()
            res = sort(x_dev, algorithm=algo, mesh=mesh,
                       return_result=True, tracer=tracer)
            probe = encoded_median(res.median_probe_raw(), dtype)
            del res
            if probe != ref_median:
                log("multichip: CORRECTNESS FAILURE — omitting row")
                return None
            times = []
            for i in range(repeats):
                run_tracer = Tracer()
                t0 = time.perf_counter()
                r = sort(x_dev, algorithm=algo, mesh=mesh,
                         return_result=True, tracer=run_tracer)
                for w in r.words:
                    w.block_until_ready()
                jax.device_get(r.words[0][-1:])
                dt = time.perf_counter() - t0
                del r
                times.append(dt)
                tracer = run_tracer
                log(f"multichip run {i}: {dt:.3f}s = {n/dt/1e6:.1f} Mkeys/s")
            break
        except (jax.errors.JaxRuntimeError, SortRetryExhausted) as e:
            cause = f"{e} {getattr(e, '__cause__', None) or ''}"
            if "RESOURCE_EXHAUSTED" not in cause:
                raise
            # free the failed attempt's buffers BEFORE shrinking: the
            # retry must not have to fit beside the buffer that just
            # exhausted HBM, or the backoff lands far below the true
            # largest-N-that-fits
            x = x_dev = None
            log(f"multichip: 2^{log2n} exhausted HBM; retrying at "
                f"2^{log2n - 1}")
            log2n -= 1
    else:
        log("multichip: no N fits; omitting row")
        return None

    mkeys = n / min(times) / 1e6
    c = tracer.counters
    row: dict = {
        "metric": f"{algo}_sort_mkeys_per_s_2e{log2n}_{dtype.name}_8dev",
        "value": round(mkeys, 2),
        "unit": "Mkeys/s",
        "devices": MULTICHIP_DEVICES,
        "platform": platform,
        # ISSUE 13: the engine the timed exchange ran (the primary/8dev
        # rows pin lax for trajectory comparability; the pallas smoke
        # cell below carries the new engine's parity evidence).
        "exchange_engine": c.get("exchange_engine", "lax"),
        # ISSUE 17: local-sort engine column (pinned lax on measured
        # rows; the fused engine's evidence is `make localsort-selftest`
        # until a real-TPU round re-baselines).
        "local_engine": c.get("local_engine", "lax"),
        # ISSUE 14: planner column (pinned off on measured rows).
        "planner": str(knobs.get("SORT_PLANNER")),
    }
    if str(row["local_engine"]).startswith("radix_pallas"):
        row["local_engine_note"] = (
            "fused engine never lowered on real TPU; interpret-mode "
            "evidence only — re-baseline on first TPU session")
    # ISSUE 16: the timeline fold's trajectory scalars — worst per-pass
    # straggler (max/median rank bytes) and the dominant phase — from
    # the LAST timed run's spans; absent keys render "-" downstream.
    row.update(timeline.bench_fold(tracer.spans.spans))
    metrics = Metrics(config={"platform": platform, "algo": algo,
                              "log2n": log2n, "dtype": dtype.name,
                              "devices": MULTICHIP_DEVICES})
    metrics.throughput("sort_mkeys_per_s_8dev", n, min(times))
    # Scale-out telemetry (ISSUE 7): exchange balance + capacity saving.
    if "negotiated_cap" in c:
        neg, worst = int(c["negotiated_cap"]), int(c["worst_cap"])
        saving = round(100.0 * (1.0 - neg / worst), 2) if worst else 0.0
        row["negotiated_cap"] = neg
        row["worst_cap"] = worst
        row["cap_saving_pct"] = saving
        row["exchange_balance_ratio"] = c.get("exchange_balance_ratio")
        row["exchange_peer_ratio"] = c.get("exchange_peer_ratio")
        log(f"multichip: negotiated cap {neg} vs worst-case {worst} "
            f"({saving}% saved), recv balance "
            f"{c.get('exchange_balance_ratio')}")
    if c.get("skew_restage"):
        row["restaged"] = int(c["skew_restage"])
    # Plan digest (ISSUE 12): the decisions behind this row's number,
    # pinned beside it so `report.py --baseline` can flag DECISION
    # drift (algo/cap/restage/regret), not just throughput drift.
    if "plan_regret" in c:
        row["plan_regret"] = round(float(c["plan_regret"]), 6)
        metrics.record("plan_regret", row["plan_regret"], "x")
    if "plan_cap_regret" in c:
        row["plan_cap_regret"] = round(float(c["plan_cap_regret"]), 6)
    # ISSUE 13: the pallas_interpret smoke cell — parity evidence for
    # the second exchange engine, SCALE-GATED to a tiny fixed N so the
    # interpreter never times (or delays) a measured row.  On a TPU
    # backend the same knob value exercises the fused pack under the
    # interpreter while the remote-DMA hop rides the lax transport
    # (ops/exchange.py interpret contract).
    try:
        n_smoke = 1 << 12
        xs = generate("uniform", n_smoke, dtype, seed=1)
        out_lax = sort(xs, algorithm=algo, mesh=mesh, exchange_engine="lax")
        out_pal = sort(xs, algorithm=algo, mesh=mesh,
                       exchange_engine="pallas_interpret")
        parity = bool(np.array_equal(out_lax, out_pal)
                      and out_lax.tobytes() == out_pal.tobytes())
        row["pallas_interpret_smoke"] = {
            "n": n_smoke, "parity": parity, "engine": "pallas_interpret"}
        metrics.record("exchange_pallas_smoke_parity", int(parity))
        log(f"multichip: pallas_interpret smoke at 2^12 — "
            f"{'bit-identical' if parity else 'PARITY FAILURE'}")
        if not parity:
            # zero BOTH surfaces: the sidecar must not keep a healthy
            # throughput for a round whose row was zeroed for parity
            row["value"] = 0.0
            metrics.record("sort_mkeys_per_s_8dev", 0.0, "Mkeys/s")
            log("multichip: CORRECTNESS FAILURE (engine parity) — "
                "reporting value 0")
    except Exception as e:  # noqa: BLE001 — smoke must not kill the row
        log(f"multichip: pallas smoke skipped ({type(e).__name__}: {e})")
        row["pallas_interpret_smoke"] = {"error": type(e).__name__}
    metrics.record_tracer(tracer)
    metrics.dump()
    return row


def _emit_multichip_row(log2n: int, algo: str, dtype: np.dtype,
                        repeats: int, primary_mkeys: float,
                        platform: str) -> None:
    """Emit the second (devices=8) JSONL row: in-process when the mesh
    is already big enough, else a ``BENCH_PLATFORM=cpu:8`` subprocess —
    the fallback every image supports.  Best-effort by contract: any
    failure logs and skips, never costs the primary row."""
    import jax

    try:
        if len(jax.devices()) >= MULTICHIP_DEVICES:
            row = _measure_multichip(algo, dtype, log2n, repeats, platform)
            if row is not None:
                if row["value"] > 0 and primary_mkeys > 0 \
                        and f"2e{log2n}_" in row["metric"]:
                    row["vs_primary"] = round(row["value"] / primary_mkeys, 3)
                print(json.dumps(row))
            return
        # Too few visible devices: re-exec on a virtual cpu:8 mesh (the
        # XLA device-count flag only takes effect before backend init,
        # so this NEEDS a fresh process).  Virtual CPU devices share one
        # host, so the row size is capped at the CPU default scale.
        env = dict(os.environ,
                   BENCH_PLATFORM=f"cpu:{MULTICHIP_DEVICES}",
                   BENCH_LOG2N=str(min(log2n, 20)))
        log(f"multichip: {len(jax.devices())} visible device(s); "
            f"spawning the cpu:{MULTICHIP_DEVICES} virtual-mesh fallback")
        r = subprocess.run(
            [sys.executable, str(REPO / "bench.py"), "--multichip-row"],
            capture_output=True, text=True, env=env, timeout=3600)
        for line in r.stderr.splitlines():
            log(f"multichip| {line}")
        rows = [ln for ln in r.stdout.splitlines() if ln.strip()]
        if r.returncode != 0 or not rows:
            log(f"multichip: fallback run failed (rc={r.returncode}); "
                "omitting row")
            return
        row = json.loads(rows[-1])  # re-validate before re-emitting
        print(json.dumps(row))
    except Exception as e:  # noqa: BLE001 — the row is best-effort
        log(f"multichip: skipped ({type(e).__name__}: {e})")


def _emit_serve_row() -> None:
    """Third JSONL row (ISSUE 8): the sort-as-a-service measurement —
    ``bench/serve_load.py``'s batched phase, its server run IN THIS
    PROCESS on threads (this process already holds the chip, and a
    child could not get it), emitting the p50/p99 + Mkeys/s row beside
    the 1-chip and devices=8 rows.  Best-effort by contract: any
    failure logs and skips, never costs the other rows."""
    try:
        sys.path.insert(0, str(REPO / "bench"))
        import serve_load

        out = REPO / "bench" / ".serve-row"
        out.mkdir(parents=True, exist_ok=True)
        if serve_load.row(out, server_cls=serve_load.InProcServer) != 0:
            log("serve: row run failed; omitting row")
    except Exception as e:  # noqa: BLE001 — the row is best-effort
        log(f"serve: skipped ({type(e).__name__}: {e})")


def _emit_planner_row() -> None:
    """Fourth JSONL row (ISSUE 14): the adversarial-mix measurement —
    ``bench/planner_selftest.py --row`` runs the sorted/near-sorted/
    dup/skew/uniform mix on a cpu:8 virtual mesh (its own subprocess,
    like the multichip fallback) with the planner PINNED OFF, so the
    r01+ trajectory stays policy-comparable; the planner's on-vs-off
    evidence is `make planner-selftest`.  Best-effort by contract."""
    try:
        r = subprocess.run(
            [sys.executable, str(REPO / "bench" / "planner_selftest.py"),
             "--row"],
            capture_output=True, text=True, timeout=1800)
        for line in r.stderr.splitlines():
            log(f"planner| {line}")
        rows = [ln for ln in r.stdout.splitlines() if ln.strip()]
        if r.returncode != 0 or not rows:
            log(f"planner: row run failed (rc={r.returncode}); "
                "omitting row")
            return
        row = json.loads(rows[-1])  # re-validate before re-emitting
        print(json.dumps(row))
    except Exception as e:  # noqa: BLE001 — the row is best-effort
        log(f"planner: skipped ({type(e).__name__}: {e})")


def _emit_external_row() -> None:
    """Fifth JSONL row (ISSUE 15): the out-of-core measurement —
    ``bench/external_selftest.py --row`` externally sorts a dataset 4x
    a forced ``SORT_MEM_BUDGET`` (spill runs + k-way merge, output
    verified bit-identical in-process) and emits spill+merge Mkeys/s
    with run count and disk bytes.  Best-effort by contract, its own
    subprocess like the planner row."""
    try:
        r = subprocess.run(
            [sys.executable,
             str(REPO / "bench" / "external_selftest.py"), "--row"],
            capture_output=True, text=True, timeout=1800)
        for line in r.stderr.splitlines():
            log(f"external| {line}")
        rows = [ln for ln in r.stdout.splitlines() if ln.strip()]
        if r.returncode != 0 or not rows:
            log(f"external: row run failed (rc={r.returncode}); "
                "omitting row")
            return
        row = json.loads(rows[-1])  # re-validate before re-emitting
        print(json.dumps(row))
    except Exception as e:  # noqa: BLE001 — the row is best-effort
        log(f"external: skipped ({type(e).__name__}: {e})")


def multichip_main() -> None:
    """``bench.py --multichip-row``: measure ONLY the devices=8 row (the
    subprocess side of :func:`_emit_multichip_row`)."""
    from mpitest_tpu.utils import knobs

    try:
        ndev = knobs.get("BENCH_PLATFORM")
        dtype = np.dtype(knobs.get("BENCH_DTYPE"))
        knobs.validate("BENCH_LOG2N", "BENCH_ALGO", "BENCH_REPEATS")
    except ValueError as e:
        raise SystemExit(str(e)) from None
    if ndev:
        from mpitest_tpu.utils.platform import ensure_virtual_cpu_devices

        ensure_virtual_cpu_devices(ndev)
    import jax

    if dtype.itemsize == 8:
        jax.config.update("jax_enable_x64", True)
    # same supervisor + engine pinning as the primary driver:
    # degradation, retry sleeps or an engine flip must not silently
    # rewrite a metric (the pallas evidence is the smoke cell)
    os.environ.setdefault("SORT_FALLBACK", "0")
    os.environ.setdefault("SORT_MAX_RETRIES", "0")
    os.environ.setdefault("SORT_EXCHANGE_ENGINE", "lax")
    os.environ.setdefault("SORT_PLANNER", "off")
    os.environ.setdefault("SORT_LOCAL_ENGINE", "lax")  # ISSUE 17
    platform = jax.devices()[0].platform
    if len(jax.devices()) < MULTICHIP_DEVICES:
        raise SystemExit(
            f"--multichip-row needs >= {MULTICHIP_DEVICES} devices "
            f"(have {len(jax.devices())}); set BENCH_PLATFORM=cpu:8")
    log2n = knobs.get("BENCH_LOG2N") or (28 if platform != "cpu" else 20)
    row = _measure_multichip(knobs.get("BENCH_ALGO"), dtype, log2n,
                             knobs.get("BENCH_REPEATS"), platform)
    if row is None:
        raise SystemExit("multichip row failed")
    print(json.dumps(row))


#: Canonical north-star denominator (VERDICT r4 weak #4): the native
#: backend's throughput measured median-of-5 in one quiet session (no
#: concurrent chip or pytest load on this image's single CPU core), so
#: the headline ratio has a reproducible denominator instead of a
#: weather-dependent one.  Keyed by (algo, log2n, dtype, ranks);
#: measured band recorded beside it.  bench.py reports BOTH the same-run
#: ratio (vs_baseline) and vs_canonical when the config matches.
CANONICAL_NATIVE_MKEYS: dict = {
    # Median of 5 runs, quiet session (no concurrent pytest/chip jobs),
    # 2026-07-31; band 9.94-13.78 Mkeys/s.  A loaded-CPU session the
    # same day measured 4.65 (band 3.95-6.11) — the 2.7x swing is why
    # the ratio is pinned.  Protocol to re-pin: BASELINE.md round-5
    # "north-star denominator" section.
    #
    # "host" is the provenance fingerprint of the machine class the pin
    # was measured on (utils/platform.py host_fingerprint — CPU vendor/
    # family/model + cores, the thing that actually determines native
    # throughput).  On any other host bench.py OMITS vs_canonical_native
    # and records why, instead of silently comparing against another
    # machine's CPU (ADVICE round 5).  Re-pinning on a new host = re-run
    # the BASELINE.md protocol there and update value + host together.
    ("radix", 28, "int32", 8): {"mkeys": 12.641,
                                "host": "GenuineIntel-6-143/2c"},
}


def measure_native(x: np.ndarray, algo: str, ranks: int,
                   repeats: int = 3) -> tuple[float | None, int]:
    """Run the repo's native backend (pthreads, `ranks` host-CPU ranks) on
    the same keys; return ``(median_seconds, repeats_used)`` — the MEDIAN
    of up to ``repeats`` runs of its own timer (the reference span:
    after-read through final gather), or ``(None, 0)`` if unavailable.
    ``repeats_used`` < ``repeats`` means some runs failed and the median
    rides a degraded denominator — callers surface it in the JSONL row
    (ADVICE round 5), not just this stderr log.  Median-of-N because the
    8-rank run on this image's one CPU core swings 1.5-4x run to run
    (VERDICT r4 weak #4).  Never raises: a missing toolchain / full /tmp
    / timeout must not cost the already-measured TPU result its stdout
    JSON line."""
    try:
        if x.dtype != np.int32:
            log("native baseline: skipped (int32 only)")
            return None, 0
        if shutil.which("cc") is None and shutil.which("gcc") is None:
            log("native baseline: skipped (no C compiler)")
            return None, 0
        d = "mpi_radix_sort" if algo == "radix" else "mpi_sample_sort"
        binary = REPO / d / ("radix_sort" if algo == "radix" else "sample_sort")
        r = subprocess.run(["make", "-C", str(REPO / d), "BACKEND=local"],
                           capture_output=True, text=True)
        if r.returncode != 0:
            log(f"native baseline: build failed: {r.stderr[-500:]}")
            return None, 0
        from mpitest_tpu.utils.io import write_keys_binary
        from mpitest_tpu.utils.nativebench import run_native_sort

        with tempfile.NamedTemporaryFile(suffix=".bin", delete=False) as f:
            path = f.name
        try:
            write_keys_binary(path, x)
            times = []
            for _ in range(max(1, repeats)):
                secs, err = run_native_sort(binary, path, ranks)
                if err:
                    log(f"native baseline: {err}")
                if secs is None:
                    break
                times.append(secs)
            if not times:
                return None, 0
            times.sort()
            if len(times) > 1:
                log(f"native baseline: median of {len(times)} runs "
                    f"(band {times[0]:.2f}-{times[-1]:.2f}s)")
            return times[len(times) // 2], len(times)
        finally:
            os.unlink(path)
    except Exception as e:  # noqa: BLE001 — baseline is best-effort
        log(f"native baseline: failed ({type(e).__name__}: {e})")
        return None, 0


def main() -> None:
    if "--multichip-row" in sys.argv[1:]:
        # subprocess side of the devices=8 row (see _emit_multichip_row)
        multichip_main()
        return
    # BENCH_PLATFORM=cpu[:N] forces an N-device virtual CPU mesh (for
    # TPU-less CI of the bench contract) via the one shared recipe —
    # must land before the first backend query.  The knob registry
    # parses cpu[:N] to the device count (garbage raises KnobError).
    from mpitest_tpu.utils import knobs

    try:
        ndev = knobs.get("BENCH_PLATFORM")
        dtype = np.dtype(knobs.get("BENCH_DTYPE"))
        knobs.validate("BENCH_LOG2N", "BENCH_ALGO", "BENCH_REPEATS",
                       "BENCH_NATIVE_RANKS", "BENCH_NATIVE_REPEATS")
    except ValueError as e:
        # the pre-registry contract: one clean line, never a traceback
        raise SystemExit(str(e)) from None
    if ndev:
        from mpitest_tpu.utils.platform import ensure_virtual_cpu_devices

        ensure_virtual_cpu_devices(ndev)
    import jax

    if dtype.itemsize == 8:
        # Device-resident 64-bit keys exist only under x64 — without it
        # jax.device_put silently DOWNCASTS the host array (observed:
        # float64 2^18 bench produced a wrong sort via a float32 shadow).
        jax.config.update("jax_enable_x64", True)

    from mpitest_tpu.models.api import (SortRetryExhausted,
                                        checked_device_put, ingest_to_mesh,
                                        sort)
    from mpitest_tpu.parallel.mesh import key_sharding, make_mesh
    from mpitest_tpu.utils.metrics import Metrics
    from mpitest_tpu.utils.trace import Tracer

    # The bench measures the DEVICE path: graceful degradation to a host
    # sort or retry backoff sleeps would silently rewrite the metric, so
    # the supervisor is pinned fail-fast here (the chaos grid — `make
    # fault-selftest` — is where recovery is exercised).  Verification
    # stays ON by default: its cost is part of the honest number and is
    # reported below as verify_overhead_s.
    os.environ.setdefault("SORT_FALLBACK", "0")
    os.environ.setdefault("SORT_MAX_RETRIES", "0")
    # ISSUE 13: the measured rows pin the lax exchange engine so the
    # r01+ trajectory stays engine-comparable (auto would flip the
    # primary row to pallas on the first TPU session); the pallas
    # engine's evidence rides the scale-gated smoke cell in the
    # multichip row + `bench/multichip_selftest.py`'s engine axis.
    # Remove the pin deliberately (SORT_EXCHANGE_ENGINE=pallas) when a
    # TPU round is ready to re-baseline the trajectory.
    os.environ.setdefault("SORT_EXCHANGE_ENGINE", "lax")
    # ISSUE 14: measured rows pin the planner off for the same reason —
    # a policy flip (passthrough, algo reroute, learned margin) must
    # never silently rewrite the r01+ trajectory; the planner's own
    # evidence is `make planner-selftest`'s A/B gate.
    os.environ.setdefault("SORT_PLANNER", "off")
    # ISSUE 17: measured rows pin the lax LOCAL engine too — the fused
    # radix_pallas family has only ever run under the interpreter (no
    # Mosaic lowering exercised on a real TPU yet), so auto flipping it
    # in would rewrite the trajectory with an unbaselined engine.  The
    # fused engine's evidence is `make localsort-selftest`; remove the
    # pin deliberately on the first real-TPU re-baseline round.
    os.environ.setdefault("SORT_LOCAL_ENGINE", "lax")

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    log2n = knobs.get("BENCH_LOG2N") or (28 if on_tpu else 20)
    algo = knobs.get("BENCH_ALGO")
    repeats = knobs.get("BENCH_REPEATS")
    native_ranks = knobs.get("BENCH_NATIVE_RANKS")
    n = 1 << log2n

    log(f"bench: platform={platform} devices={len(jax.devices())} "
        f"algo={algo} N=2^{log2n} dtype={dtype} repeats={repeats}")

    from mpitest_tpu.utils.io import generate

    x = generate("uniform", n, dtype, seed=0)
    mesh = make_mesh()

    # Secondary baseline: single-core np.sort of the same keys.
    t0 = time.perf_counter()
    xs = np.sort(x)
    np_s = time.perf_counter() - t0
    np_mkeys = n / np_s / 1e6
    log(f"baseline np.sort: {np_s:.3f}s = {np_mkeys:.1f} Mkeys/s")
    # Correctness reference for the median probe.  Ints reuse the sort
    # above; floats need the encoded (totalOrder) sort for exact bits.
    ref_median = (encoded_median(x, dtype) if dtype.kind == "f"
                  else int(xs[n // 2 - 1]))
    del xs

    # Ingest: stream the keys onto the mesh once through the chunked
    # double-buffered pipeline (models/ingest.py) — untimed for the
    # primary metric; wall + per-stage seconds + overlap recorded.  The
    # staged words feed the ingest-inclusive end-to-end run below.
    t0 = time.perf_counter()
    staged = ingest_to_mesh(x, mesh=mesh)
    for w in staged.words:
        w.block_until_ready()
    ingest_s = time.perf_counter() - t0
    ing = staged.stats
    log(f"ingest (streamed host→mesh): {ingest_s:.3f}s = "
        f"{x.nbytes/ingest_s/1e9:.2f} GB/s (parse {ing.parse_s:.3f}s, "
        f"encode {ing.encode_s:.3f}s [{ing.encode_engine}], "
        f"transfer {ing.transfer_s:.3f}s, "
        f"overlap {ing.overlap_efficiency()*100:.0f}%, {ing.chunks} chunks)")
    del staged  # free the staged words before the steady-state loop

    # Steady-state input: device-resident raw keys (dtype-guarded put —
    # the silent-downcast hazard this file used to only footnote is now
    # a hard error at the source, models/ingest.checked_device_put).
    x_dev = checked_device_put(x, key_sharding(mesh))
    x_dev.block_until_ready()

    # Warmup: compiles the program and settles the exchange cap.
    res = sort(x_dev, algorithm=algo, mesh=mesh, return_result=True)
    probe = encoded_median(res.median_probe_raw(), dtype)
    ok = probe == ref_median
    del res  # free the result buffers: at 2^30 two live results OOM HBM
    log(f"median probe: got {probe} expect {ref_median} ({'OK' if ok else 'MISMATCH'})")
    metric_name = f"{algo}_sort_mkeys_per_s_2e{log2n}_{dtype.name}"
    if not ok:
        log("CORRECTNESS FAILURE — reporting value 0")
        print(json.dumps({"metric": metric_name, "value": 0.0,
                          "unit": "Mkeys/s", "vs_baseline": 0.0,
                          "tooling": tooling_state()}))
        return

    metrics = Metrics(config={"platform": platform, "algo": algo,
                              "log2n": log2n, "dtype": dtype.name,
                              "devices": len(jax.devices())})
    times = []
    tracer = Tracer()  # tracer of the last COMPLETED run (sidecar source)
    for i in range(repeats):
        run_tracer = Tracer()  # per-run: counters/phases must not accumulate
        t0 = time.perf_counter()
        try:
            r = sort(x_dev, algorithm=algo, mesh=mesh, return_result=True,
                     tracer=run_tracer)
            for w in r.words:
                w.block_until_ready()
        except (jax.errors.JaxRuntimeError, SortRetryExhausted) as e:
            # Near the HBM limit (2^30 = 4 GB keys on a 16 GB chip) the
            # previous run's buffers may not have deallocated yet; keep
            # whatever repeats completed rather than losing the result.
            # (With SORT_MAX_RETRIES=0 the supervisor surfaces the OOM
            # as SortRetryExhausted with the real error as __cause__.)
            cause = f"{e} {getattr(e, '__cause__', None) or ''}"
            if "RESOURCE_EXHAUSTED" not in cause or not times:
                raise
            log(f"run {i}: skipped (HBM exhausted; keeping {len(times)} runs)")
            break
        dt = time.perf_counter() - t0
        del r  # free before the next run (2^30: two live results OOM)
        times.append(dt)
        tracer = run_tracer
        log(f"run {i}: {dt:.3f}s = {n/dt/1e6:.1f} Mkeys/s")

    best = min(times)
    mkeys = metrics.throughput("sort_mkeys_per_s", n, best)

    # North-star denominator: the native backend, 8 host-CPU ranks, same
    # keys, same N (BASELINE.json: ">=8x the throughput of 8-rank
    # host-CPU MPI"; the pthreads backend is the same shared-memory
    # transport class mpirun uses on one host).
    vs_native = None
    native_repeats = knobs.get("BENCH_NATIVE_REPEATS")
    native_repeats_used = None
    if native_ranks > 0:
        native_s, native_repeats_used = measure_native(
            x, algo, native_ranks, repeats=native_repeats)
        if native_s is not None:
            native_mkeys = n / native_s / 1e6
            vs_native = mkeys / native_mkeys
            log(f"native {algo} x{native_ranks} ranks: {native_s:.3f}s = "
                f"{native_mkeys:.1f} Mkeys/s -> vs_native = {vs_native:.2f}x")
            metrics.record(f"native_{native_ranks}rank_mkeys_per_s",
                           round(native_mkeys, 3), "Mkeys/s")
            metrics.record("native_repeats_used", native_repeats_used)
    # Canonical (pinned) denominator: reproducible across rounds even
    # when the same-run native measurement rides a loaded CPU.  The pin
    # is host-specific — on any other machine class it is OMITTED and
    # the skip reason recorded instead (ADVICE round 5).
    canon = CANONICAL_NATIVE_MKEYS.get((algo, log2n, dtype.name, native_ranks))
    vs_canonical = canon_skipped = None
    if canon:
        from mpitest_tpu.utils.platform import host_fingerprint

        fp = host_fingerprint()
        if fp == canon["host"]:
            vs_canonical = mkeys / canon["mkeys"]
            log(f"vs_canonical (pinned {canon['mkeys']} Mkeys/s): "
                f"{vs_canonical:.2f}x")
            metrics.record("vs_canonical_native", round(vs_canonical, 3), "x")
        else:
            canon_skipped = (f"host {fp!r} != pinned {canon['host']!r}")
            log(f"vs_canonical_native omitted: {canon_skipped}")

    # Ingest-inclusive end-to-end: ONE measured run of the real pipeline
    # — streamed ingest (parse/encode overlapped with DMA) feeding the
    # sort directly on the staged words (no device-side re-encode).
    # Programs are warm from the loop above, so this times steady-state
    # data movement + sort, exactly what a production request pays.
    staged = None
    try:
        t0 = time.perf_counter()
        staged = ingest_to_mesh(x, mesh=mesh)
        r = sort(staged, algorithm=algo, mesh=mesh, return_result=True)
        for w in r.words:
            w.block_until_ready()
        jax.device_get(r.words[0][-1:])
        incl_s = time.perf_counter() - t0
        incl_probe = encoded_median(r.median_probe_raw(), dtype)
        del r
        if incl_probe != ref_median:
            log("ingest-inclusive run: MEDIAN MISMATCH — omitting metric")
            incl_s = None
        else:
            # the recorded sub-metrics must describe the SAME run as the
            # sort_incl_ingest headline in this row — the first (warmup)
            # staging ran under different memory/cache conditions
            ing = staged.stats
            ingest_s = ing.wall_s
    except (jax.errors.JaxRuntimeError, SortRetryExhausted) as e:
        # the second staging doubles resident key bytes next to x_dev —
        # near the HBM limit it may OOM; keep the already-measured row.
        cause = f"{e} {getattr(e, '__cause__', None) or ''}"
        if "RESOURCE_EXHAUSTED" not in cause:
            raise
        log("ingest-inclusive run: skipped (HBM exhausted)")
        incl_s = None
    del staged

    metrics.record("baseline_np_sort_mkeys_per_s", round(np_mkeys, 3), "Mkeys/s")
    # Ingest sub-metrics (ISSUE 2): the split that shows WHERE host-path
    # time goes and how much of it the pipeline hides.  overlap
    # efficiency = fraction of transfer wall time intersected by host
    # parse/encode intervals (0 = serial, →1 = fully hidden).
    metrics.record("ingest_gb_per_s", round(x.nbytes / ingest_s / 1e9, 3), "GB/s")
    metrics.record("ingest_wall_s", round(ingest_s, 4), "s")
    metrics.record("ingest_parse_s", round(ing.parse_s, 4), "s")
    metrics.record("ingest_encode_s", round(ing.encode_s, 4), "s")
    metrics.record("ingest_transfer_s", round(ing.transfer_s, 4), "s")
    metrics.record("ingest_overlap_efficiency",
                   round(ing.overlap_efficiency(), 4))
    metrics.record("ingest_chunks", ing.chunks)
    # ISSUE 6: which engine encoded (auto may have degraded — the row
    # must say so, not just the spans) and its measured throughput.
    encode_engine = ing.encode_engine
    encode_gbs = (round(ing.host_bytes / ing.encode_s / 1e9, 3)
                  if ing.encode_s else None)
    metrics.record("encode_engine", encode_engine)
    if encode_gbs is not None:
        metrics.record("encode_gb_per_s", encode_gbs, "GB/s")
    ingest_ratio = None
    if incl_s is not None:
        incl_mkeys = metrics.throughput("sort_incl_ingest_mkeys_per_s",
                                        n, incl_s)
        ingest_ratio = round(incl_mkeys / mkeys, 4)
        metrics.record("ingest_ratio", ingest_ratio, "x")
    # Robustness cost accounting (ISSUE 3): retries actually paid,
    # faults injected (nonzero only under SORT_FAULTS drills), and the
    # wall seconds the always-on verifier added to the LAST timed run —
    # so BENCH JSONs track what robustness costs, not just that it
    # exists.  The acceptance budget is verifier overhead < 5% of sort
    # wall time.
    retries = int(tracer.counters.get("exchange_retries", 0)
                  + tracer.counters.get("sort_retries", 0))
    faults_injected = int(tracer.counters.get("faults_injected", 0))
    verify_s = round(tracer.phases.get("verify", 0.0), 6)
    if verify_s:
        log(f"verifier overhead: {verify_s:.4f}s = "
            f"{100.0 * verify_s / best:.2f}% of best sort wall")
    metrics.record("retries", retries)
    metrics.record("faults_injected", faults_injected)
    metrics.record("verify_overhead_s", verify_s, "s")
    metrics.record_tracer(tracer)  # last run's tracer: per-run values
    metrics.dump()  # structured sidecar → stderr

    # The driver contract: exactly one JSON line on stdout.  vs_baseline
    # is the north-star ratio (vs 8-rank native); when that baseline
    # could not run, the fallback denominator is named in "baseline" so
    # a consumer can never mistake np.sort for the 8-rank target.
    vs_baseline = vs_native if vs_native is not None else mkeys / np_mkeys
    out = {
        "metric": metric_name,
        "value": round(mkeys, 2),
        "unit": "Mkeys/s",
        "vs_baseline": round(vs_baseline, 3),
        "baseline": (f"native_{native_ranks}rank" if vs_native is not None
                     else "np_sort"),
        "vs_np_sort": round(mkeys / np_mkeys, 3),
        "retries": retries,
        "faults_injected": faults_injected,
        "verify_overhead_s": verify_s,
        "encode_engine": encode_engine,
        "exchange_engine": tracer.counters.get("exchange_engine", "lax"),
        # ISSUE 17: the LOCAL engine the timed sort ran (measured rows
        # pin lax via the setdefault above); string cell, no regression
        # math in bench_history.
        "local_engine": tracer.counters.get("local_engine", "lax"),
        # ISSUE 14: the planner column — measured rows pin "off" (see
        # the setdefault above); string cell, no regression math.
        "planner": str(knobs.get("SORT_PLANNER")),
        "tooling": tooling_state(),
    }
    if str(out["local_engine"]).startswith("radix_pallas"):
        # honest caveat: the fused engine has never lowered on a real
        # TPU — any number it produced here is interpreter/CPU-scale
        # evidence, not a TPU measurement.
        out["local_engine_note"] = (
            "fused engine never lowered on real TPU; interpret-mode "
            "evidence only — re-baseline on first TPU session")
    if encode_gbs is not None:
        out["encode_gb_per_s"] = encode_gbs
    if ingest_ratio is not None:
        out["ingest_ratio"] = ingest_ratio
    # ISSUE 16: the timeline fold's trajectory scalars (straggler
    # factor, critical-path phase) from the last timed run's spans —
    # single-device runs carry no exchange balance, so the straggler
    # key is usually absent here and the bench-history cell renders "-".
    from mpitest_tpu.utils import timeline
    out.update(timeline.bench_fold(tracer.spans.spans))
    # Plan digest (ISSUE 12): decision provenance pinned in the row so
    # the trajectory captures what was DECIDED, not only what it scored.
    if "plan_regret" in tracer.counters:
        out["plan_regret"] = round(float(tracer.counters["plan_regret"]),
                                   6)
    if vs_canonical is not None:
        out["vs_canonical_native"] = round(vs_canonical, 3)
    elif canon_skipped:
        out["vs_canonical_native_skipped"] = canon_skipped
    if (native_repeats_used is not None and vs_native is not None
            and native_repeats_used < max(1, native_repeats)):
        # Degraded denominator: fewer native runs succeeded than the
        # documented median-of-N protocol — visible in the row itself,
        # not just the stderr log (ADVICE round 5).
        out["native_repeats_used"] = native_repeats_used
    print(json.dumps(out))

    # Second JSONL row (ISSUE 7): the devices=8 scale-out measurement —
    # real chips when the mesh has them, the BENCH_PLATFORM=cpu:8
    # virtual mesh in a subprocess otherwise.  The primary row above is
    # untouched so the r01+ trajectory stays comparable.
    if knobs.get("BENCH_MULTICHIP") != "off":
        _emit_multichip_row(log2n, algo, dtype, repeats, mkeys, platform)

    # Third JSONL row (ISSUE 8): the sort-as-a-service headline — the
    # persistent server under the closed-loop small-request mix
    # (bench/serve_load.py), p50/p99 latency + throughput.  Scale-gated
    # like the multichip row: tiny-scale runs are driver-contract
    # smoke tests (and several tests scrape stdout's last line as the
    # primary row), so only measured-scale benches pay the ~minute of
    # server spawns.
    if knobs.get("BENCH_SERVE") != "off":
        if log2n >= 16:
            _emit_serve_row()
        else:
            log(f"serve: skipped at 2^{log2n} (scale-gated like the "
                "multichip row; run bench/serve_load.py --row directly)")

    # Fourth JSONL row (ISSUE 14): the adversarial-mix measurement,
    # planner pinned off for trajectory comparability.  Scale-gated
    # like the serve row.
    if knobs.get("BENCH_PLANNER") != "off":
        if log2n >= 16:
            _emit_planner_row()
        else:
            log(f"planner: skipped at 2^{log2n} (scale-gated; run "
                "bench/planner_selftest.py --row directly)")

    # Fifth JSONL row (ISSUE 15): the out-of-core measurement — the
    # same dataset spilled + k-way-merged under a forced SORT_MEM_BUDGET
    # far below its size (spill+merge throughput, run count, disk
    # bytes).  Scale-gated like the serve/planner rows.
    if knobs.get("BENCH_EXTERNAL") != "off":
        if log2n >= 16:
            _emit_external_row()
        else:
            log(f"external: skipped at 2^{log2n} (scale-gated; run "
                "bench/external_selftest.py --row directly)")


if __name__ == "__main__":
    # process-level choice, set by the entry point (see drivers/)
    from mpitest_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    main()
