#!/usr/bin/env python3
"""Sort-as-a-service entry point: the persistent server (ISSUE 8).

Where ``sort_cli.py`` is the reference's one-shot batch contract, this
driver is the production shape the ROADMAP's north star actually needs:
a long-lived process that compiles once (AOT executor cache with
power-of-two shape bucketing), bounds its queue (typed backpressure),
packs concurrent small requests into one segmented device dispatch
(multi-tenant batching), and supervises every request so a poisoned
input yields a typed per-request error — never server death.

Usage::

    python drivers/sort_server.py            # knobs configure everything

Knobs (all validated fail-fast — garbage is one ``[ERROR]`` line and
exit 1, never a traceback): ``SORT_SERVE_PORT`` (0 = ephemeral; the
bound port is printed either way), ``SORT_SERVE_HOST``,
``SORT_SERVE_MAX_INFLIGHT`` / ``SORT_SERVE_MAX_BYTES`` (admission),
``SORT_SERVE_BATCH_WINDOW_MS`` / ``SORT_SERVE_BATCH_KEYS`` (batching),
``SORT_SERVE_SHAPE_BUCKETS`` / ``SORT_SERVE_PREWARM`` (executor cache),
``SORT_SERVE_ALLOW_FAULTS`` (test mode), plus every ordinary sort knob
(``SORT_ALGO``, ``SORT_DEVICES``, ``SORT_VERIFY``, ...).

Startup prints exactly one ``sort_server listening on HOST:PORT`` line
to stdout (flushed) once the socket accepts — load generators and the
selftest synchronize on it.  ``SIGTERM``/``SIGINT`` drain gracefully:
in-flight requests complete, new work gets a typed ``draining``
rejection, then the process exits 0.

Telemetry: ``SORT_TRACE=<path>`` streams every ``serve.request`` /
``serve.batch`` / ``serve.compile_cache`` span (plus all the ordinary
sort spans) as JSONL; ``python -m mpitest_tpu.report`` renders the
p50/p99 SLO table from exactly that stream.

Live telemetry (ISSUE 10): a second stdout line ``sort_server metrics
on HOST:PORT`` names the side port (``SORT_METRICS_PORT``; -1 disables)
serving ``/metrics`` (Prometheus text), ``/healthz``, ``/varz``,
``/flightrecorder`` (the in-memory span ring; ``?dump=1`` writes an
artifact) and ``/profile?n=K`` (jax.profiler capture of the next K
dispatches).  Every request carries a ``trace_id`` (client-minted or
server-minted, echoed in the response) stamped on every span it
touches; ``SIGQUIT`` dumps the flight recorder WITHOUT shutting down;
``SORT_TRACE_SAMPLE`` down-samples the full JSONL stream under load.
"""

from __future__ import annotations

import signal
import sys
import threading
from pathlib import Path

# Script-invocation bootstrap: the repo root (not drivers/) holds the
# package, and this image cannot `pip install -e .`.
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv if argv is None else argv
    if len(argv) != 1:
        print(f"Usage: {argv[0]}  (configuration rides the SORT_SERVE_* "
              "environment knobs)", file=sys.stderr)
        return 1

    from mpitest_tpu.utils import knobs

    def err(msg: str) -> None:
        print(f"[ERROR] {msg}", file=sys.stderr)

    # Fail-fast knob validation — the CLI contract: a garbage knob is
    # one clean [ERROR] line naming the knob, before any JAX work.
    try:
        host = knobs.get("SORT_SERVE_HOST")
        port = knobs.get("SORT_SERVE_PORT")
        metrics_port = knobs.get("SORT_METRICS_PORT")
        knobs.validate(
            "SORT_SERVE_MAX_INFLIGHT", "SORT_SERVE_MAX_BYTES",
            "SORT_SERVE_BATCH_WINDOW_MS", "SORT_SERVE_BATCH_KEYS",
            "SORT_SERVE_SHAPE_BUCKETS", "SORT_SERVE_PREWARM",
            "SORT_SERVE_ALLOW_FAULTS",
            # the request-lifecycle robustness layer (ISSUE 11)
            "SORT_SERVE_IDLE_TIMEOUT_S", "SORT_SERVE_READ_TIMEOUT_S",
            "SORT_SERVE_DISPATCH_TIMEOUT_S",
            "SORT_SERVE_BREAKER_BACKOFF_S",
            "SORT_SERVE_COMPLETION_TIMEOUT_S", "SORT_FAULT_STALL_MS",
            # the live-telemetry layer (ISSUE 10)
            "SORT_TRACE_SAMPLE", "SORT_FLIGHT_RECORDER_SIZE",
            "SORT_FLIGHT_RECORDER_DIR", "SORT_PROFILE",
            "SORT_PROFILE_EVERY",
            # the sort knobs every dispatch consumes
            "SORT_ALGO", "SORT_DTYPE", "SORT_DEVICES", "SORT_RANKS",
            "SORT_VERIFY", "SORT_MAX_RETRIES", "SORT_RETRY_BACKOFF",
            "SORT_FALLBACK", "SORT_FAULTS", "SORT_FAULTS_SEED",
            "SORT_LOCAL_ENGINE", "SORT_EXCHANGE_ENGINE",
            "SORT_NEGOTIATE", "SORT_RESTAGE",
            "SORT_RESTAGE_RATIO", "SORT_NATIVE_ENCODE",
            # plan provenance (ISSUE 12): the decision record behind
            # the response header's plan digest and /varz snapshot
            "SORT_PLAN",
            # self-tuning planner (ISSUE 14): per-request policies +
            # the serve window/bucket tuner
            "SORT_PLANNER", "SORT_PLANNER_WINDOW",
            "SORT_PLANNER_HYSTERESIS",
            # out-of-core spill tier (ISSUE 15): over-budget requests
            # stream to disk and ride the external sort
            "SORT_SERVE_SPILL", "SORT_SPILL_DIR", "SORT_MEM_BUDGET",
            "SORT_MERGE_FANIN",
            # spill compression + simulated-disk throttle (ISSUE 20)
            "SORT_SPILL_COMPRESS", "SORT_SPILL_THROTTLE_MBPS",
            # crash-durable spill tier (ISSUE 18): journaled manifests,
            # kill-resume, the orphan GC sweep, the disk-fault drills
            "SORT_RESUME", "SORT_SPILL_GC_AGE_S", "SORT_FAULT_ENOSPC_AT",
            # streaming sentinel (ISSUE 16): live anomaly alerting in
            # the serve core — garbage thresholds die here, not on the
            # first span close
            "SORT_SENTINEL", "SORT_SENTINEL_WINDOW_S",
            "SORT_ALERT_BURN_RATE",
        )
        from mpitest_tpu.utils import native_encode

        native_encode.engine()  # =on with no usable lib dies HERE
    except (ValueError, RuntimeError) as e:
        err(str(e))
        return 1

    from mpitest_tpu.serve.server import ServerCore, SortServer

    def log(msg: str) -> None:
        print(f"sort_server: {msg}", file=sys.stderr, flush=True)

    core = ServerCore()
    core.prewarm(log)
    # Startup orphan GC (ISSUE 18): reclaim spill files no live
    # manifest references — age-gated (SORT_SPILL_GC_AGE_S) so a
    # concurrent sort's fresh files are never swept.  Journals that DO
    # replay are left alone: they are exactly the resume signal.
    if knobs.get("SORT_SERVE_SPILL") != "off":
        from mpitest_tpu.store import external as _external

        swept = _external.gc_spill_dir(tracer=core.tracer)
        if swept:
            log(f"spill GC: reclaimed {swept} orphaned file(s)")
    # dispatch watchdog (ISSUE 11): monitors the single dispatch
    # thread's heartbeat; a dispatch past SORT_SERVE_DISPATCH_TIMEOUT_S
    # trips the circuit breaker (healthz 503, fast typed rejections,
    # flight-recorder artifact) and half-opens with a probe after
    # backoff.  0 disables.
    core.start_watchdog()
    try:
        server = SortServer(core, host, port)
    except OSError as e:
        err(f"cannot bind {host}:{port}: {e}")
        return 1
    # Live-telemetry side port (ISSUE 10): /metrics, /healthz, /varz,
    # /flightrecorder, /profile.  -1 disables; 0 = ephemeral.
    telemetry = None
    if metrics_port >= 0:
        from mpitest_tpu.serve.telemetry import TelemetryServer

        try:
            telemetry = TelemetryServer(core, host, metrics_port)
            telemetry.start()
        except OSError as e:
            err(f"cannot bind metrics port {host}:{metrics_port}: {e}")
            server.server_close()
            return 1
    stop = threading.Event()

    def on_signal(signum: int, frame: object) -> None:
        log(f"signal {signum}: draining (in-flight requests complete; "
            "new work gets a typed 'draining' rejection)")
        core.start_drain()
        stop.set()

    def on_sigquit(signum: int, frame: object) -> None:
        # incident snapshot, NOT shutdown: dump the flight-recorder
        # ring and keep serving (the operator's kill -QUIT at 3am).
        from mpitest_tpu.utils import flight_recorder

        path = flight_recorder.get().dump("sigquit")
        log(f"SIGQUIT: flight recorder dumped to {path or '(nothing)'}")

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGINT, on_signal)
    signal.signal(signal.SIGQUIT, on_sigquit)

    serve_thread = threading.Thread(target=server.serve_forever,
                                    name="serve-accept", daemon=True)
    serve_thread.start()
    # The sync line load generators / the selftest wait for (stdout, one
    # line, flushed — parse the real bound port from it when PORT=0).
    # The metrics line follows SECOND so single-line readers keep
    # working; scrapers read both.
    print(f"sort_server listening on {host}:{server.bound_port}",
          flush=True)
    if telemetry is not None:
        print(f"sort_server metrics on {host}:{telemetry.bound_port}",
              flush=True)
    stop.wait()
    drained = core.drain_and_stop(timeout=60.0)
    server.shutdown()
    server.server_close()
    if telemetry is not None:
        telemetry.shutdown()
        telemetry.server_close()
    if not drained:
        # ISSUE 11 satellite: a drain timeout is an INCIDENT, not a
        # quiet log line — name the stuck requests, record the typed
        # drain_timeout evidence (span event -> live counter via the
        # bridge), dump the flight recorder, exit dirty.
        import time as _time

        from mpitest_tpu.utils import flight_recorder

        stuck = core.stuck_trace_ids()
        core.tracer.spans.record(
            "serve.watchdog", _time.perf_counter(), 0.0,
            event="drain_timeout", trace_ids=stuck)
        path = flight_recorder.get().dump("drain_timeout")
        log(f"drain TIMEOUT: {len(stuck)} request(s) still in flight "
            f"(trace_ids={stuck}); flight recorder dumped to "
            f"{path or '(nothing)'}")
        # ISSUE 18: a dirty exit may strand journaled external sorts —
        # name the datasets a restarted server can warm-resume (the
        # manifests stay on disk; only a clean finish deletes them).
        if knobs.get("SORT_SERVE_SPILL") != "off":
            from mpitest_tpu.store import external as _external
            from mpitest_tpu.store import manifest as _mfst

            live = _mfst.live_manifests(
                _external.resolve_spill_dir(None))
            if live:
                log("resumable spill datasets: "
                    + ", ".join(m.dataset for m in live)
                    + " (a restarted server re-enters them at the "
                    "merge phase)")
    log(f"drained={'clean' if drained else 'TIMEOUT'} "
        f"served_ok={core.requests_ok} errors={core.requests_err} "
        f"rejected={core.admission.rejected} "
        f"batches={core.batcher.batches} "
        f"watchdog_trips={core.breaker.trips} "
        f"cache_hits={core.cache.stats.hits} "
        f"cache_misses={core.cache.stats.misses}")
    return 0 if drained else 1


if __name__ == "__main__":
    # the persistent compile cache is a process-level choice: set by the
    # entry point, never by main() (in-process callers keep their own)
    from mpitest_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
