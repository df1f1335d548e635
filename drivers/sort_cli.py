#!/usr/bin/env python3
"""CLI driver — the reference's argv/stdout/stderr contract on the TPU backend.

Byte-compatible machine interface (SURVEY.md §5 metrics row):

* argv: ``sort_cli.py <datafile> [debug]`` — positional, like the
  reference ``main()`` (``mpi_sample_sort.c:220-241``); wrong argc prints
  ``Usage: %s <file: Data file to read>`` to stderr and exits non-zero
  (``:230-234``), unreadable file prints ``sort(): '<file>' is not a
  valid file for read.`` (``:46-48``).
* stdout: ``Each bucket will be put %u items.`` (sample algorithm,
  ``:74``), full ``%u|%u`` dump at debug>2 (``:203``), and the
  correctness probe ``The n/2-th sorted element: %d`` (``:205``).
* stderr: ``Endtime()-Starttime() = %.5f sec`` (``:207``), spanning
  after-file-read to result materialization, like the reference's
  ``MPI_Wtime`` pair (``:61,201``).

Knobs the reference put in ``mpirun -np``/source constants ride env vars
here: ``SORT_ALGO`` ∈ {sample, radix} (default sample — the reference
binary of the same name), ``SORT_RANKS`` (mesh size; default all
devices), ``SORT_DIGIT_BITS`` (radix digit width, default auto),
``SORT_DTYPE`` (default int32), ``SORT_CAP_FACTOR`` (exchange cap as a
multiple of the fair per-peer share, default 2.0 — the principled form
of the reference's fixed ``1.5*size_bucket`` bucket cap,
``mpi_sample_sort.c:140``), ``SORT_OVERSAMPLE`` (samples per shard for
splitter selection, default ``2P-1`` like the reference ``:90``).

Streaming ingest (ISSUE 2 — on by default for large inputs): the file
format is sniffed once (``read_keys_auto``) and SORTBIN1 inputs open as
an mmap (no upfront materialization); the sort's host path then runs the
chunked parse/encode/DMA pipeline (``mpitest_tpu/models/ingest.py``),
emitting ``ingest.*`` / ``egress.*`` spans into ``SORT_TRACE``.  Knobs:
``SORT_INGEST`` ∈ {auto, stream, mono} (auto streams above ~32 MiB),
``SORT_INGEST_CHUNK`` (keys per chunk, default 2^22),
``SORT_INGEST_THREADS`` (parse/encode workers, default 2) — all
validated fail-fast like every other knob.

Observability (SURVEY.md §5 metrics row — additions the reference
lacks, off by default so the byte-compatible contract is untouched):
``SORT_METRICS=<path>`` appends one JSON sidecar line per run (phase ms,
Mkeys/s, exchange bytes + achieved GB/s); ``SORT_TRACE=<path>`` streams
the structured span log (nested phases, jit compile-vs-execute split,
one span per radix pass / splitter round / collective with byte counts
— ``mpitest_tpu/utils/spans.py``) as JSONL, aggregated by ``python -m
mpitest_tpu.report`` alongside the native backends' ``COMM_STATS``
records; ``SORT_TRACE_CHROME=<path>`` writes the same run as Chrome
trace-event JSON (opens in Perfetto); ``SORT_PROFILE=<logdir>`` wraps
the sort in a ``jax.profiler`` trace for TensorBoard.

Robustness (ISSUE 3 — the supervised, self-verifying sort): every run
is verified (on-device sortedness + multiset fingerprint against the
input, ``SORT_VERIFY={1,0}``), dispatch retries transient failures
(``SORT_MAX_RETRIES``, ``SORT_RETRY_BACKOFF``) and degrades gracefully
(``SORT_FALLBACK={1,0}``: other algorithm, then host sort).  Fault
injection for drills: ``SORT_FAULTS=<spec>`` / ``SORT_FAULTS_SEED``
(``mpitest_tpu/faults.py``).  Terminal failures map to DISTINCT exit
codes so wrappers can tell data corruption from infrastructure death:

* exit :data:`EXIT_INTEGRITY` (3) — ``SortIntegrityError``: no path
  produced a result that passes verification;
* exit :data:`EXIT_RETRIES` (4) — ``SortRetryExhausted``: dispatch kept
  failing past the retry budget (and fallback was off or failed too).

Both print one ``[ERROR]`` line to stderr — never a traceback — the
same fail-fast contract as the env-knob validation.
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

import numpy as np

# Script-invocation bootstrap: the repo root (not drivers/) holds the
# package, and this image cannot `pip install -e .` (see verify skill).
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

#: Distinct terminal exit codes (see module docstring).  1 stays the
#: usage/knob/file-error code, matching the reference binaries.
EXIT_INTEGRITY = 3
EXIT_RETRIES = 4


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv if argv is None else argv
    # --explain (ISSUE 12): print the run's decision tree (plan
    # provenance) after the sort.  A flag, not a positional — the
    # byte-compatible reference argv contract stays untouched without it.
    explain = "--explain" in argv
    if explain:
        argv = [a for a in argv if a != "--explain"]
    if len(argv) not in (2, 3):
        print(f"Usage: {argv[0]} <file: Data file to read>", file=sys.stderr)
        return 1
    path = argv[1]
    # atoi() semantics, like the reference (mpi_sample_sort.c:237):
    # non-numeric debug arg parses as 0, never crashes.
    debug = 0
    if len(argv) == 3:
        import re

        m = re.match(r"\s*[+-]?\d+", argv[2])
        debug = int(m.group()) if m else 0

    from mpitest_tpu.models.api import sort
    from mpitest_tpu.parallel.mesh import make_mesh
    from mpitest_tpu.utils import io as kio
    from mpitest_tpu.utils import knobs
    from mpitest_tpu.utils.trace import Tracer, jax_profile

    # Env-knob validation: any garbage value is one clean `[ERROR]` line
    # to stderr + nonzero exit — the reference's fail-fast contract
    # (mpi_sample_sort.c:46-48,230-234 prints and aborts; it never dumps
    # a stack trace), VERDICT r4 weak #5.  Every knob reads through the
    # central registry (utils/knobs.py), which owns the typed parsing
    # and the knob-naming error messages; one validate() sweep covers
    # the knobs the sort consumes later (ingest, robustness, faults) so
    # a garbage fault spec dies here, not mid-sort.
    def knob_error(msg: str) -> None:
        print(f"[ERROR] {msg}", file=sys.stderr)

    tracer = Tracer(level=debug)
    try:
        algo = knobs.get("SORT_ALGO")
        dtype = knobs.get("SORT_DTYPE")
        digit_bits = knobs.get("SORT_DIGIT_BITS")
        ranks = knobs.get("SORT_RANKS")
        cap_factor = knobs.get("SORT_CAP_FACTOR")
        oversample = knobs.get("SORT_OVERSAMPLE")
        knobs.validate(
            "SORT_INGEST", "SORT_INGEST_CHUNK", "SORT_INGEST_THREADS",
            "SORT_DONATE", "SORT_NATIVE_ENCODE", "SORT_VERIFY",
            "SORT_MAX_RETRIES", "SORT_RETRY_BACKOFF", "SORT_FALLBACK",
            "SORT_FAULTS", "SORT_FAULTS_SEED", "SORT_LOCAL_ENGINE",
            "SORT_EXCHANGE_ENGINE",
            "SORT_DEVICES", "SORT_NEGOTIATE", "SORT_RESTAGE",
            "SORT_RESTAGE_RATIO",
            # live-telemetry knobs (ISSUE 10): the span sampler runs in
            # every SpanLog and the flight recorder dumps on typed
            # errors, so garbage dies here, not mid-sort
            "SORT_TRACE_SAMPLE", "SORT_FLIGHT_RECORDER_SIZE",
            "SORT_FLIGHT_RECORDER_DIR",
            # plan provenance (ISSUE 12): minted on every run by default
            "SORT_PLAN",
            # self-tuning planner (ISSUE 14): the policy layer rides
            # every sort when enabled, so garbage dies here
            "SORT_PLANNER", "SORT_PLANNER_WINDOW",
            "SORT_PLANNER_HYSTERESIS",
            # out-of-core external sort (ISSUE 15): inputs above the
            # byte budget spill to runs and k-way merge back
            "SORT_MEM_BUDGET", "SORT_SPILL_DIR", "SORT_MERGE_FANIN",
            # spill compression + simulated-disk throttle (ISSUE 20):
            # both are read in the spill hot path, so garbage dies here
            "SORT_SPILL_COMPRESS", "SORT_SPILL_THROTTLE_MBPS",
            # streaming sentinel (ISSUE 16): the knobs are serve-side
            # but shared tooling (report --doctor thresholds) reads
            # them, so garbage dies here too
            "SORT_SENTINEL", "SORT_SENTINEL_WINDOW_S",
            "SORT_ALERT_BURN_RATE",
        )
        # resolve the encode engine NOW: SORT_NATIVE_ENCODE=on with no
        # usable libencode.so is one clean [ERROR] line here, never a
        # RuntimeError traceback out of the first streamed chunk
        from mpitest_tpu.utils import native_encode

        native_encode.engine()
    except (ValueError, RuntimeError) as e:
        knob_error(str(e))
        return 1
    # Out-of-core routing (ISSUE 15): with a byte budget set and a file
    # larger than it, the sort runs externally — partition chunks spill
    # to sorted runs (text inputs parse chunk-by-chunk straight into
    # runs, so even THEY never materialize: the PR 2 documented
    # full-file text peak is gone on this path) and a streamed k-way
    # merge probes the median without holding the result.  Debug runs
    # (dump lines, per-rank logs) keep the materializing path —
    # observability over memory, by choice.
    mem_budget = knobs.get("SORT_MEM_BUDGET")
    try:
        file_bytes = Path(path).stat().st_size
    except OSError:
        print(f"sort(): '{path}' is not a valid file for read.",
              file=sys.stderr)
        return 1
    if mem_budget and file_bytes > mem_budget and debug <= 0:
        return _external_main(path, dtype, algo, mem_budget, ranks,
                              tracer)

    try:
        # One magic sniff; SORTBIN1 opens as an mmap so the streaming
        # ingest pages keys in chunk-by-chunk instead of materializing
        # the file up front (text parses through the threaded chunk
        # reader).
        keys = kio.read_keys_auto(path, dtype=dtype, mmap=True)
    except (OSError, ValueError, OverflowError):
        # OverflowError: an out-of-range decimal token (both engines
        # raise it — numpy's int cast and the native parser's ERANGE)
        print(f"sort(): '{path}' is not a valid file for read.", file=sys.stderr)
        return 1
    n = keys.size
    if n == 0:
        print(f"sort(): '{path}' is not a valid file for read.", file=sys.stderr)
        return 1

    mesh = make_mesh(ranks)
    n_ranks = int(mesh.devices.size)
    # Per-rank protocol lines, debug>=2 — the reference's shapes
    # (mpi_sample_sort.c:30 "[COMMON] Working %u/%u", :68 "[SLAVE] %u
    # Recv(size_input): %u").  One host drives all mesh ranks, so the
    # lines are emitted in rank order instead of interleaving.
    for r in range(n_ranks):
        tracer.common(f"Working {r}/{n_ranks}", min_level=2)
    tracer.master(f"Read file: {path}")
    tracer.master(f"File read OK, {n} numbers {keys[0]}-{keys[-1]}.")
    for r in range(1, n_ranks):
        tracer.slave(f"{r} Recv(size_input): {n}")

    if algo == "sample":
        # ceil(N/P): the reference's size_bucket line (mpi_sample_sort.c:74).
        print(f"Each bucket will be put {-(-n // n_ranks)} items.")

    from mpitest_tpu.models.supervisor import (SortIntegrityError,
                                               SortRetryExhausted)

    start = time.perf_counter()  # after file read, like MPI_Wtime at :61
    try:
        with jax_profile(knobs.get("SORT_PROFILE")):
            res = sort(
                keys, algorithm=algo, mesh=mesh, digit_bits=digit_bits,
                cap_factor=cap_factor, oversample=oversample,
                tracer=tracer, return_result=True,
            )
            # materialize = the reference's final Gatherv (streamed egress
            # above the auto threshold: decode overlaps the shard fetches)
            out = res.to_numpy(tracer=tracer)
    except SortIntegrityError as e:
        # Data-integrity terminal: the result could not be verified and
        # every recovery rung failed — distinct exit code so callers can
        # quarantine the input/run, never trust partial output.
        knob_error(f"sort integrity failure: {e}")
        return EXIT_INTEGRITY
    except SortRetryExhausted as e:
        # Infrastructure terminal: dispatch kept dying past the retry
        # budget — distinct code so schedulers can retry elsewhere.
        knob_error(f"sort failed after retries: {e}")
        return EXIT_RETRIES
    end = time.perf_counter()

    chrome_path = knobs.get("SORT_TRACE_CHROME")
    if chrome_path:
        # Perfetto / chrome://tracing export of the same span log the
        # SORT_TRACE JSONL streams (utils/spans.py).
        import json

        with open(chrome_path, "w") as f:
            json.dump(tracer.spans.to_chrome_trace(), f)

    metrics_path = knobs.get("SORT_METRICS")
    if metrics_path:
        from mpitest_tpu.utils.metrics import Metrics

        m = Metrics(config={"algo": algo, "n": n, "dtype": dtype.name,
                            "ranks": n_ranks, "digit_bits": digit_bits})
        m.record("wall_time_s", round(end - start, 6), "s")
        m.throughput("sort_mkeys_per_s", n, end - start)
        m.record_tracer(tracer)
        m.dump(metrics_path)

    if debug > 2:
        mask = (1 << (8 * dtype.itemsize)) - 1
        if algo == "radix" and dtype.kind in "iu":
            # Per-pass intermediate dumps — the reference's debug>2 loop
            # contract (mpi_radix_sort.c:175-178), same line format as the
            # native core (native/radix_core.h).  Emitted outside the
            # timed span (observability must not bend the benchmark).
            from mpitest_tpu.models.api import radix_pass_states

            for k, _shard, full in radix_pass_states(
                keys, mesh=mesh, digit_bits=digit_bits, cap_factor=cap_factor
            ):
                # Pads are copies of the max real key and, by stability,
                # the LAST occurrences of that value in every pass state:
                # drop exactly those to recover the real-key global order.
                pc = full.size - n
                if pc:
                    keep = np.ones(full.size, bool)
                    keep[np.flatnonzero(full == full.max())[-pc:]] = False
                    real = full[keep]
                else:
                    real = full
                # RADIX labels follow the reference/native block contract
                # (rank r owns n//P + (r < n%P) keys — sort_common.h
                # block_count), not the padded uniform device shards, so
                # the dump is line-for-line comparable for ANY N.
                q, rem = divmod(n, n_ranks)
                off = 0
                for r in range(n_ranks):
                    cnt = q + (1 if r < rem else 0)
                    print(f"[COMMON] {r}: Main Queue Completed, LEN={cnt}")
                    for v in real[off:off + cnt]:
                        print(f"DUMP: LOOP {k} RADIX {r} = {int(v) & mask}")
                    off += cnt
        for i, v in enumerate(out):
            # Floats dump as shortest-unique decimals (round-trippable
            # bits); the reference's %u masking is an int-key contract.
            print(f"{i}|{v}" if dtype.kind == "f" else f"{i}|{int(v) & mask}")
    # The reference indexes size_input/2 - 1 (UB for n == 1; we clamp).
    med = out[max(n // 2 - 1, 0)]
    if dtype.kind == "f":
        # Bit-exact float probe: numpy's shortest-unique decimal str
        # round-trips to the same bits.  int truncation would collide
        # distinct float medians — the pitfall bench.py's encoded_median
        # fixes (VERDICT r3 weak #3).
        print(f"The n/2-th sorted element: {med}")
    else:
        print(f"The n/2-th sorted element: {int(med)}")
    print(f"Endtime()-Starttime() = {end - start:.5f} sec", file=sys.stderr)
    if explain:
        # the same renderer report.py --explain uses, fed from this
        # run's in-process span log — no trace file required
        from mpitest_tpu.report import explain_view

        rows = [dict(s.to_dict(), kind="span")
                for s in tracer.spans.spans]
        view = explain_view(rows)
        print(view if view is not None
              else "(no plan recorded — SORT_PLAN=off)")
    return 0


def _external_main(path: str, dtype, algo: str, mem_budget: int,
                   ranks, tracer) -> int:
    """The out-of-core CLI leg (ISSUE 15): streamed external sort of
    ``path`` under ``SORT_MEM_BUDGET`` — chunks spill to sorted runs,
    the k-way merge streams past a running median probe, and the full
    result is never materialized.  Same stdout/stderr/exit contract as
    the in-memory path (the timer starts before the read because the
    read IS interleaved with the sort here)."""
    import time as _time

    from mpitest_tpu.models.supervisor import (SortIntegrityError,
                                               SortRetryExhausted)
    from mpitest_tpu.parallel.mesh import make_mesh
    from mpitest_tpu.store import external
    from mpitest_tpu.utils import knobs
    from mpitest_tpu.utils.io import sniff_format

    def knob_error(msg: str) -> None:
        print(f"[ERROR] {msg}", file=sys.stderr)

    try:
        sniff_format(path)
    except OSError:
        print(f"sort(): '{path}' is not a valid file for read.",
              file=sys.stderr)
        return 1
    mesh = make_mesh(ranks)
    n_ranks = int(mesh.devices.size)
    probe = {"off": 0, "med": None, "n": 0, "announced": False}

    def sink_factory(n: int):
        # invoked once per MERGE ATTEMPT (an integrity recovery re-runs
        # the merge): reset the running probe so a recovered attempt
        # can never report a median captured from the aborted stream
        probe["off"], probe["med"], probe["n"] = 0, None, n
        if algo == "sample" and not probe["announced"]:
            # the reference's size_bucket line (mpi_sample_sort.c:74) —
            # printable only once the partition pass measured n
            print(f"Each bucket will be put {-(-n // n_ranks)} items.")
            probe["announced"] = True
        med_idx = max(n // 2 - 1, 0)

        def sink(k, _p) -> None:
            off = probe["off"]
            if off <= med_idx < off + int(k.size):
                probe["med"] = k[med_idx - off]
            probe["off"] = off + int(k.size)

        return sink

    start = _time.perf_counter()
    try:
        external.external_sort_file(
            path, dtype=dtype, algorithm=algo, mesh=mesh, tracer=tracer,
            budget=mem_budget, sink="array", sink_factory=sink_factory)
    except SortIntegrityError as e:
        knob_error(f"sort integrity failure: {e}")
        return EXIT_INTEGRITY
    except SortRetryExhausted as e:
        knob_error(f"sort failed after retries: {e}")
        return EXIT_RETRIES
    except (OSError, ValueError, OverflowError):
        print(f"sort(): '{path}' is not a valid file for read.",
              file=sys.stderr)
        return 1
    end = _time.perf_counter()
    if probe["n"] == 0:
        print(f"sort(): '{path}' is not a valid file for read.",
              file=sys.stderr)
        return 1

    metrics_path = knobs.get("SORT_METRICS")
    if metrics_path:
        from mpitest_tpu.utils.metrics import Metrics

        m = Metrics(config={"algo": algo, "n": probe["n"],
                            "dtype": dtype.name, "ranks": n_ranks,
                            "external": True})
        m.record("wall_time_s", round(end - start, 6), "s")
        m.throughput("sort_mkeys_per_s", probe["n"], end - start)
        m.record_tracer(tracer)
        m.dump(metrics_path)

    med = probe["med"]
    if dtype.kind == "f":
        print(f"The n/2-th sorted element: {med}")
    else:
        print(f"The n/2-th sorted element: {int(med)}")
    print(f"Endtime()-Starttime() = {end - start:.5f} sec",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    # the persistent compile cache is a process-level choice: set by the
    # entry point, never by main() (in-process callers keep their own)
    from mpitest_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
