#!/usr/bin/env python3
"""Closed-loop load generator + regression gate for the sort server.

Drives ``drivers/sort_server.py`` over the wire protocol with a mixed
small-request size distribution (log-uniform 2^7..2^10 int32 keys — the
"heavy traffic from millions of users" shape where per-dispatch
overhead, not device throughput, dominates) and C concurrent closed-loop
clients (each sends its next request when its previous reply lands).
Every reply is verified BIT-IDENTICAL to ``np.sort`` of its request —
the batched multi-tenant path must be indistinguishable from a private
sort.

Modes:

* ``--selftest`` (the ``make serve-selftest`` gate):

  1. **warm-cache gate** — after warmup, the measured window's server
     span stream must contain ZERO compile activity: no
     ``jit_compile_execute`` spans and no ``serve.compile_cache``
     misses (the executor cache's whole point).
  2. **batching gate** — server-side DISPATCH throughput (keys per
     second of ``serve.batch`` pipeline wall: pack + device sort +
     verify + split) of the batched server must be >= 2x the same load
     against a ``SORT_SERVE_BATCH_WINDOW_MS=0`` server (per-request
     dispatch): the measured value of multi-tenant packing, isolated
     from per-request socket/framing costs that are identical in both
     modes.
  3. **backpressure gate** — a burst against a ``MAX_INFLIGHT=1``
     server must produce typed ``backpressure`` rejections AND leave
     the server serving.
  4. **fault gate** — a poisoned request (per-request ``faults`` spec,
     test mode) must come back as a typed ``integrity`` error while the
     next clean request succeeds: per-request isolation.

* ``--row``: spawn, warm, measure the batched phase, emit ONE JSON
  bench row on stdout (p50/p99 + Mkeys/s).  bench.py's serve row calls
  :func:`row` in its own process with :class:`InProcServer` instead —
  it already holds the chip, which a spawned server could not get.

The spawned server writes ``SORT_TRACE`` JSONL; ``python -m
mpitest_tpu.report`` renders the p50/p99 SLO table from exactly that
stream (the Makefile target does both).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from mpitest_tpu.report import percentile          # noqa: E402
from mpitest_tpu.serve.client import ServeClient   # noqa: E402
from mpitest_tpu.utils import knobs                # noqa: E402
from mpitest_tpu.utils import metrics_live         # noqa: E402

#: Request-size mix: log-uniform in [2^LOG2_MIN, 2^LOG2_MAX] int32 keys
#: — small enough that per-dispatch overhead (not O(n log n) sort work)
#: is what a request pays, which is exactly the traffic shape batching
#: exists to amortize.
LOG2_MIN, LOG2_MAX = 7, 10

#: Batching gate (ISSUE 8 acceptance): batched throughput must be at
#: least this multiple of per-request sequential dispatch.
MIN_BATCH_SPEEDUP = 2.0

#: Batch window the measured/bench phases use: wide enough that a
#: closed-loop round's worth of tenants packs into one dispatch on a
#: loaded 1-2 core runner (measured sweet spot; the production default
#: knob stays latency-leaning).
BATCH_WINDOW_MS = "8"

#: --chaos leg (ISSUE 11): injected response-delay tail — every Nth
#: proxied connection's reply is held this long, so the chaos p99 is a
#: deterministic property of the schedule, not of runner weather.
CHAOS_DELAY_MS = 400
CHAOS_EVERY = 4
CHAOS_REQUESTS = 32

HOST = "127.0.0.1"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------- server mgmt

class _ServerBase:
    """What the load phases need of a server: its ports, its span
    trace, a /metrics scrape, and stop()."""

    trace: Path
    port: int
    metrics_port: "int | None"

    def scrape_metrics(self) -> str:
        """One /metrics scrape (Prometheus text)."""
        import urllib.request

        assert self.metrics_port is not None, "metrics port disabled"
        with urllib.request.urlopen(
                f"http://{HOST}:{self.metrics_port}/metrics",
                timeout=30) as r:
            return r.read().decode("utf-8")

    def trace_cut(self) -> int:
        """Current trace line count — the warm-window marker."""
        try:
            return len(self.trace.read_text().splitlines())
        except FileNotFoundError:
            return 0

    def spans_after(self, cut: int) -> list[dict]:
        rows = []
        for ln in self.trace.read_text().splitlines()[cut:]:
            try:
                rows.append(json.loads(ln))
            except json.JSONDecodeError:
                pass
        return rows


class InProcServer(_ServerBase):
    """The server core in THIS process, on threads — for a caller that
    already holds the chip (bench.py's serve row): a child process
    could not get the device."""

    def __init__(self, out_dir: Path, tag: str,
                 env_overrides: dict | None = None) -> None:
        from mpitest_tpu.serve.server import ServerCore, SortServer
        from mpitest_tpu.serve.telemetry import TelemetryServer

        self.trace = out_dir / f"server_trace_{tag}.jsonl"
        with knobs.scoped_env(SORT_TRACE=str(self.trace),
                              **(env_overrides or {})):
            self.core = ServerCore()
        self.core.prewarm(lambda m: log(f"  server[{tag}]| {m}"))
        self.core.start_watchdog()
        self.server = SortServer(self.core, HOST, 0)
        self.port = self.server.bound_port
        self.telemetry = TelemetryServer(self.core, HOST, 0)
        self.telemetry.start()
        self.metrics_port = self.telemetry.bound_port
        self._accept = threading.Thread(target=self.server.serve_forever,
                                        name="serve-accept", daemon=True)
        self._accept.start()
        log(f"server[{tag}] up in-process on :{self.port} "
            f"(metrics :{self.metrics_port})")

    def stop(self) -> int:
        drained = self.core.drain_and_stop(timeout=60.0)
        self.server.shutdown()
        self.server.server_close()
        self.telemetry.shutdown()
        self.telemetry.server_close()
        self._accept.join(timeout=10.0)
        return 0 if drained else 1


class Server(_ServerBase):
    """One spawned sort_server subprocess (ephemeral port, own trace)."""

    #: Startup budget: jax import + prewarm compiles on a loaded
    #: shared runner.  The wait is select()-bounded — a wedged server
    #: fails HERE at the deadline, never hangs the CI job on a
    #: blocking pipe read.
    STARTUP_TIMEOUT_S = 180.0

    def __init__(self, out_dir: Path, tag: str,
                 env_overrides: dict | None = None) -> None:
        self.trace = out_dir / f"server_trace_{tag}.jsonl"
        # stderr goes to a FILE, not a pipe: the child may log more
        # than a pipe buffer before binding (prewarm lines), and an
        # undrained pipe would deadlock exactly the startup path the
        # timeout exists to bound.
        self.stderr_path = out_dir / f"server_{tag}.stderr.log"
        self._stderr_f = open(self.stderr_path, "w")
        # defaults first, THEN overrides — a caller may override any
        # default (e.g. SORT_METRICS_PORT=-1), which dict(**kwargs)
        # would reject as a duplicate keyword
        env = dict(os.environ)
        env.update({"SORT_SERVE_PORT": "0",
                    "SORT_METRICS_PORT": "0",
                    "SORT_TRACE": str(self.trace)})
        env.update(env_overrides or {})
        self.proc = subprocess.Popen(
            [sys.executable, str(REPO / "drivers" / "sort_server.py")],
            stdout=subprocess.PIPE, stderr=self._stderr_f, text=True,
            env=env)
        assert self.proc.stdout is not None
        self._stdout_buf = ""
        line = self._await_listening_line()
        m = re.search(r"listening on [\d.]+:(\d+)", line or "")
        if not m:
            self.proc.kill()
            self.proc.wait(timeout=10)
            raise RuntimeError(
                f"server ({tag}) did not come up: {line!r}\n"
                f"{self._stderr_tail()}")
        self.port = int(m.group(1))
        # second sync line (ISSUE 10): the telemetry side port.  Only
        # awaited when the spawn env left metrics enabled.
        self.metrics_port: int | None = None
        if env.get("SORT_METRICS_PORT") != "-1":
            mline = self._await_listening_line()
            mm = re.search(r"metrics on [\d.]+:(\d+)", mline or "")
            if not mm:
                self.proc.kill()
                self.proc.wait(timeout=10)
                raise RuntimeError(
                    f"server ({tag}) printed no metrics line: {mline!r}"
                    f"\n{self._stderr_tail()}")
            self.metrics_port = int(mm.group(1))
        log(f"server[{tag}] up on :{self.port}"
            + (f" (metrics :{self.metrics_port})"
               if self.metrics_port else ""))

    def _await_listening_line(self) -> str:
        """Bounded wait for ONE sync line: select() + os.read on the
        raw fd with our own line buffer.  Two sync lines are read back
        to back (listening + metrics) and they usually arrive in ONE
        pipe chunk — a text-mode readline() would swallow both into
        Python's internal buffer, and a later select() on the fd would
        then block on data that already arrived."""
        import select

        deadline = time.monotonic() + self.STARTUP_TIMEOUT_S
        stdout = self.proc.stdout
        assert stdout is not None
        fd = stdout.fileno()
        while time.monotonic() < deadline:
            if "\n" in self._stdout_buf:
                line, self._stdout_buf = self._stdout_buf.split("\n", 1)
                return line + "\n"
            if self.proc.poll() is not None:
                return ""          # child died before binding
            ready, _, _ = select.select([fd], [], [],
                                        min(1.0, deadline
                                            - time.monotonic()))
            if ready:
                chunk = os.read(fd, 1 << 16)
                if not chunk:
                    return ""      # EOF
                self._stdout_buf += chunk.decode("utf-8", "replace")
        return ""

    def _stderr_tail(self, nbytes: int = 2000) -> str:
        try:
            return self.stderr_path.read_text()[-nbytes:]
        except OSError:
            return "(no stderr captured)"

    def stop(self) -> int:
        import signal

        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            rc = -9
        self._stderr_f.close()
        for ln in self._stderr_tail().strip().splitlines()[-3:]:
            log(f"  server| {ln}")
        return rc


# ------------------------------------------------------------ load driving

def run_load(port: int, requests: int, concurrency: int, seed: int,
             ) -> dict:
    """Closed-loop phase: C clients, ``requests`` total, every reply
    verified bit-identical to np.sort of its request.  Returns
    latencies (ok only), statuses, keys, wall seconds."""
    lock = threading.Lock()
    lat: list[float] = []
    statuses: dict[str, int] = {}
    keys = [0]
    bad_parity = [0]
    counter = [0]
    #: plan digests echoed in response headers (ISSUE 12): the
    #: client-visible decision record, folded into the bench row
    plans: list[dict] = []

    def worker(widx: int) -> None:
        rng = np.random.default_rng(seed + widx)
        client = ServeClient(HOST, port)
        try:
            while True:
                with lock:
                    if counter[0] >= requests:
                        return
                    counter[0] += 1
                n = int(2 ** rng.uniform(LOG2_MIN, LOG2_MAX))
                x = rng.integers(-2**31, 2**31 - 1, size=n, dtype=np.int32)
                t0 = time.perf_counter()
                try:
                    r = client.sort(x)
                except (ConnectionError, OSError) as e:
                    # Every CLAIMED request must land in a status
                    # bucket — a silently vanished request would let
                    # the gates pass on a partial measurement.  One
                    # reconnect attempt keeps a dropped keep-alive
                    # (e.g. after a framing-lost rejection) from
                    # wiping the rest of this worker's share.
                    with lock:
                        st = f"client_error:{type(e).__name__}"
                        statuses[st] = statuses.get(st, 0) + 1
                    try:
                        client.close()
                        client = ServeClient(HOST, port)
                        continue
                    except OSError:
                        return
                dt = time.perf_counter() - t0
                with lock:
                    st = "ok" if r.ok else (r.error or "?")
                    statuses[st] = statuses.get(st, 0) + 1
                    if r.ok:
                        lat.append(dt)
                        keys[0] += n
                        if r.plan is not None:
                            plans.append(r.plan)
                        if not np.array_equal(r.arr, np.sort(x)):
                            bad_parity[0] += 1
        finally:
            try:
                client.close()
            except OSError:
                pass

    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    return {"wall_s": wall, "latencies": sorted(lat),
            "latency_hist": latency_histogram(lat),
            "statuses": statuses, "keys": keys[0],
            "bad_parity": bad_parity[0],
            "keys_per_s": keys[0] / wall if wall > 0 else 0.0,
            "plan": fold_plans(plans)}


def fold_plans(plans: list) -> dict:
    """Fold response-header plan digests (ISSUE 12) into the summary
    the bench row pins: digest count, algo census, mean regret and the
    bucket set — `report.py --baseline` flags drift in these alongside
    the throughput numbers."""
    regrets = [float(p["regret"]) for p in plans
               if isinstance(p.get("regret"), (int, float))]
    algos: dict = {}
    for p in plans:
        a = str(p.get("algo", "?"))
        algos[a] = algos.get(a, 0) + 1
    return {
        "digests": len(plans),
        "algos": algos,
        "mean_regret": (round(sum(regrets) / len(regrets), 6)
                        if regrets else None),
        "buckets": sorted({int(p["bucket"]) for p in plans
                           if isinstance(p.get("bucket"), int)}),
    }


def latency_histogram(latencies: list) -> dict:
    """Client-observed latency histogram over the SAME fixed buckets —
    and the same bucketing rule (``metrics_live.cumulative_buckets``) —
    as the server's live registry (ISSUE 10), so the two sides line up
    1:1: ``{"le_<bound>": cumulative count}``."""
    out = {f"le_{b:g}": cum for b, cum in metrics_live.cumulative_buckets(
        latencies, metrics_live.LATENCY_BUCKETS_S)}
    out["le_inf"] = len(latencies)
    return out


def reconcile_with_server(prom_text: str, statuses: dict) -> list[str]:
    """The dropped-reply catcher (ISSUE 10 satellite): the server's
    ``sort_serve_requests_total`` MUST equal the client's own reply
    accounting — a silently dropped reply shows up as a server-side
    request with no client-side status.  Also validates the exposition
    format and that every exported name is registered.  Returns a list
    of failures (empty = reconciled)."""
    errs = metrics_live.check_exposition(prom_text)
    try:
        fams = metrics_live.parse_prom_text(prom_text)
    except ValueError as e:
        return errs + [f"/metrics unparseable: {e}"]
    reqs = fams.get("sort_serve_requests_total")
    server_total = int(sum(v for _n, _l, v in reqs["samples"])) \
        if reqs else 0
    # a transport-level client error usually means no server reply —
    # but the server counts a request in _finish BEFORE writing the
    # reply bytes, so a connection dropped mid-reply is counted
    # server-side while the client files it under client_error.  Exact
    # equality is therefore required only against the clean count; each
    # client_error may or may not have a server-side twin.
    client_clean = sum(v for k, v in statuses.items()
                       if not k.startswith("client_error:"))
    client_errors = sum(v for k, v in statuses.items()
                        if k.startswith("client_error:"))
    if not client_clean <= server_total <= client_clean + client_errors:
        errs.append(
            f"request-count reconciliation failed: server counted "
            f"{server_total}, client observed {client_clean} clean "
            f"(+{client_errors} transport errors; statuses {statuses}) "
            "— replies were dropped or double-counted")
    return errs


def phase_stats(name: str, st: dict) -> None:
    lat = st["latencies"]
    log(f"{name}: {sum(st['statuses'].values())} requests "
        f"({st['statuses']}), {st['keys']} keys in {st['wall_s']:.3f}s "
        f"= {st['keys_per_s']/1e6:.3f} Mkeys/s; "
        f"p50 {percentile(lat, 50)*1e3:.2f} ms, "
        f"p99 {percentile(lat, 99)*1e3:.2f} ms")


def measure_phase(out: Path, tag: str, window_ms: str, requests: int,
                  concurrency: int, seed: int,
                  server_cls: type = Server,
                  ) -> tuple[dict, list[dict], int]:
    """Start a server (a child process, or ``InProcServer``) at the
    given batch window, warm it, run the measured phase; returns
    (stats, measured-window spans, server rc).
    The default ``SORT_SERVE_SHAPE_BUCKETS`` prewarm covers every
    bucket the packed path can request, so the warm-cache gate holds
    with a default-config server.

    Before shutdown the server's live ``/metrics`` endpoint is scraped
    (ISSUE 10): exposition validated, server-side request count
    reconciled against the client's own accounting over BOTH phases
    (warmup + measured) — failures land in ``stats["metrics_errors"]``
    and fail the selftest leg."""
    srv = server_cls(out, tag, {
        "SORT_SERVE_BATCH_WINDOW_MS": window_ms,
    })
    try:
        warm = run_load(srv.port, max(16, concurrency), concurrency,
                        seed + 1000)
        phase_stats(f"{tag} warmup", warm)
        cut = srv.trace_cut()
        stats = run_load(srv.port, requests, concurrency, seed)
        phase_stats(tag, stats)
        spans = srv.spans_after(cut)
        combined = dict(warm["statuses"])
        for k, v in stats["statuses"].items():
            combined[k] = combined.get(k, 0) + v
        try:
            prom = srv.scrape_metrics()
        except OSError as e:
            stats["metrics_errors"] = [f"/metrics scrape failed: {e}"]
        else:
            stats["metrics_errors"] = reconcile_with_server(prom, combined)
            (out / f"metrics_{tag}.prom").write_text(prom)
    finally:
        rc = srv.stop()
    return stats, spans, rc


def dispatch_mkeys_per_s(spans: list) -> float:
    """Server-side DISPATCH throughput over a measured window: keys per
    second of dispatch-pipeline wall (``serve.batch`` span durations —
    pack + device sort + verify + split).  This is the quantity
    multi-tenant packing amortizes; client-side closed-loop numbers add
    per-request socket/framing costs that are identical in both modes
    and would mask it."""
    keys = sum(s.get("attrs", {}).get("keys", 0) for s in spans
               if s.get("name") == "serve.batch")
    secs = sum(s.get("dt", 0.0) for s in spans
               if s.get("name") == "serve.batch")
    return keys / secs / 1e6 if secs > 0 else 0.0


def emit_row(stats: dict, extra: dict) -> dict:
    lat = stats["latencies"]
    row = {
        "metric": "serve_small_mix_mkeys_per_s",
        "value": round(stats["keys_per_s"] / 1e6, 3),
        "unit": "Mkeys/s",
        "p50_ms": round(percentile(lat, 50) * 1e3, 3),
        "p99_ms": round(percentile(lat, 99) * 1e3, 3),
        "requests": sum(stats["statuses"].values()),
        "keys": stats["keys"],
        # client-observed latency histogram (same buckets as the
        # server's live registry — the two sides line up 1:1)
        "latency_hist": stats.get("latency_hist"),
        **extra,
    }
    # plan digest summary (ISSUE 12): the decisions the server made for
    # this row's traffic, pinned so decision drift is baseline-flaggable
    p = stats.get("plan") or {}
    if p.get("digests"):
        row["plan_digests"] = p["digests"]
        row["plan_algos"] = p["algos"]
        if p.get("mean_regret") is not None:
            row["plan_regret"] = p["mean_regret"]
        if p.get("buckets"):
            row["plan_buckets"] = p["buckets"]
    print(json.dumps(row), flush=True)
    return row


def record_metrics(stats: dict, speedup: float | None) -> None:
    """SORT_METRICS sidecar (when set): the SLO numbers as metrics so
    the report CLI folds them beside the span-derived table."""
    if not knobs.get("SORT_METRICS"):
        return
    from mpitest_tpu.utils.metrics import Metrics

    lat = stats["latencies"]
    m = Metrics(config={"driver": "serve_load",
                        "mix": f"2^{LOG2_MIN}..2^{LOG2_MAX} int32"})
    m.record("serve_mkeys_per_s", round(stats["keys_per_s"] / 1e6, 3),
             "Mkeys/s")
    m.record("serve_p50_ms", round(percentile(lat, 50) * 1e3, 3), "ms")
    m.record("serve_p99_ms", round(percentile(lat, 99) * 1e3, 3), "ms")
    if speedup is not None:
        m.record("serve_batched_speedup", round(speedup, 3), "x")
    m.dump(knobs.get("SORT_METRICS"))


# ----------------------------------------------------------- planner leg

#: --planner A/B leg (ISSUE 14): both servers run the same deliberately
#: mis-set fixed window — small enough that a closed-loop round's worth
#: of tenants cannot pack — and only the planner-on server may re-size
#: it from the observed mix.  The A/B isolates the tuner's value.
PLANNER_FIXED_WINDOW_MS = "1"

#: Warmup requests of the planner leg: the tuner commits after two
#: consecutive agreeing evaluations (RETUNE_EVERY observations each),
#: so the warm phase must span >= 2 evaluation rounds.
PLANNER_WARMUP_REQUESTS = 96


def planner_phase(out: Path, requests: int, concurrency: int,
                  seed: int) -> dict:
    """Window-auto vs fixed-window dispatch throughput and p99 beside
    the clean row (ISSUE 14).  Both legs keep the full correctness
    contract: every reply bit-identical to ``np.sort``, clean SIGTERM,
    and ``reconcile_with_server`` still exact (the tuner must never
    cost a reply).  Returns the extra row fields (``None`` values when
    a leg failed its correctness checks)."""
    fields: dict = {"planner_fixed_window_ms":
                    float(PLANNER_FIXED_WINDOW_MS),
                    "planner_dispatch_mkeys_per_s": None,
                    "fixed_dispatch_mkeys_per_s": None,
                    "p99_planner_ms": None, "p99_fixed_ms": None,
                    "planner_window_retunes": None}
    legs: dict[str, tuple[dict, list[dict], str]] = {}
    for tag, mode in (("planner_fixed", "off"), ("planner_auto", "on")):
        srv = Server(out, tag, {
            "SORT_SERVE_BATCH_WINDOW_MS": PLANNER_FIXED_WINDOW_MS,
            "SORT_PLANNER": mode,
        })
        try:
            warm = run_load(srv.port, PLANNER_WARMUP_REQUESTS,
                            concurrency, seed + 2000)
            phase_stats(f"{tag} warmup", warm)
            cut = srv.trace_cut()
            stats = run_load(srv.port, requests, concurrency, seed + 2500)
            phase_stats(tag, stats)
            spans = srv.spans_after(cut)
            combined = dict(warm["statuses"])
            for k, v in stats["statuses"].items():
                combined[k] = combined.get(k, 0) + v
            prom = srv.scrape_metrics()
            errs = reconcile_with_server(prom, combined)
        except (OSError, ConnectionError, RuntimeError) as e:
            log(f"planner leg {tag} failed: {e}")
            srv.stop()
            return fields
        rc = srv.stop()
        if rc != 0 or stats["bad_parity"] or errs:
            log(f"planner leg {tag} FAILED correctness: rc={rc} "
                f"bad_parity={stats['bad_parity']} errs={errs}")
            return fields
        (out / f"metrics_{tag}.prom").write_text(prom)
        legs[tag] = (stats, spans, prom)
    fixed_stats, fixed_spans, _ = legs["planner_fixed"]
    auto_stats, auto_spans, auto_prom = legs["planner_auto"]
    retunes = 0.0
    try:
        fams = metrics_live.parse_prom_text(auto_prom)
        ret = fams.get("sort_serve_window_retunes_total")
        if ret:
            retunes = sum(v for _n, _l, v in ret["samples"])
    except ValueError:
        pass
    fields.update({
        "planner_dispatch_mkeys_per_s":
            round(dispatch_mkeys_per_s(auto_spans), 3),
        "fixed_dispatch_mkeys_per_s":
            round(dispatch_mkeys_per_s(fixed_spans), 3),
        "p99_planner_ms":
            round(percentile(auto_stats["latencies"], 99) * 1e3, 3),
        "p99_fixed_ms":
            round(percentile(fixed_stats["latencies"], 99) * 1e3, 3),
        "planner_window_retunes": int(retunes),
    })
    log(f"planner leg: dispatch {fields['planner_dispatch_mkeys_per_s']}"
        f" (auto, {fields['planner_window_retunes']} retune(s)) vs "
        f"{fields['fixed_dispatch_mkeys_per_s']} Mkeys/s (fixed "
        f"{PLANNER_FIXED_WINDOW_MS} ms); p99 {fields['p99_planner_ms']}"
        f" vs {fields['p99_fixed_ms']} ms")
    return fields


# ------------------------------------------------------------- chaos leg

def chaos_phase(out: Path, seed: int) -> dict:
    """p99-under-chaos beside the clean row (ISSUE 11): a fresh server
    behind the chaos proxy's deterministic injected tail
    (``wire_delay_response@CHAOS_DELAY_MS:CHAOS_EVERY``), measured
    twice — plain client, then hedged (``hedge_after_s=0.1``).  Returns
    the extra row fields (``None`` values when the leg failed)."""
    from wire_chaos import ChaosProxy

    from mpitest_tpu.serve.client import ResilientClient

    rng = np.random.default_rng(seed + 7000)
    spec = f"wire_delay_response@{CHAOS_DELAY_MS}:{CHAOS_EVERY}"
    srv = Server(out, "chaosleg", {"SORT_SERVE_BATCH_WINDOW_MS": "0",
                                   "SORT_SERVE_SHAPE_BUCKETS": "10"})
    fields: dict = {"chaos_spec": spec, "p99_chaos_ms": None,
                    "p99_chaos_hedged_ms": None}
    try:
        warm = rng.integers(-2**31, 2**31 - 1, size=512, dtype=np.int32)
        with ServeClient(HOST, srv.port) as c:
            if not c.sort(warm).ok:
                log("chaos leg: warmup failed; skipping")
                return fields

        def run(hedge: "float | None") -> list[float]:
            lats: list[float] = []
            with ChaosProxy(HOST, srv.port, spec) as px:
                client = ResilientClient(HOST, px.port,
                                         read_timeout=30.0,
                                         max_attempts=1,
                                         hedge_after_s=hedge)
                for _ in range(CHAOS_REQUESTS):
                    a = rng.integers(-2**31, 2**31 - 1, size=512,
                                     dtype=np.int32)
                    t0 = time.perf_counter()
                    r = client.sort(a)
                    lats.append(time.perf_counter() - t0)
                    if not (r.ok and np.array_equal(r.arr, np.sort(a))):
                        raise RuntimeError(f"chaos reply bad: {r.header}")
            return sorted(lats)

        plain = run(None)
        hedged = run(0.1)
        fields["p99_chaos_ms"] = round(percentile(plain, 99) * 1e3, 3)
        fields["p99_chaos_hedged_ms"] = round(
            percentile(hedged, 99) * 1e3, 3)
        log(f"chaos leg ({spec}): p99 {fields['p99_chaos_ms']} ms "
            f"plain vs {fields['p99_chaos_hedged_ms']} ms hedged")
    except (OSError, ConnectionError, RuntimeError) as e:
        log(f"chaos leg failed: {e}")
    finally:
        srv.stop()
    return fields


# ---------------------------------------------------------------- selftest

def check_leg(tag: str, stats: dict, rc: int, requests: int,
              fails: list) -> None:
    """Correctness checks EVERY measured leg must pass — retry legs
    included: a leg whose replies are not bit-identical, whose server
    did not drain cleanly, or whose request accounting leaks may not
    contribute to any throughput gate."""
    if rc != 0:
        fails.append(f"{tag}: server exited rc={rc} on SIGTERM")
    if stats["bad_parity"]:
        fails.append(f"{tag}: {stats['bad_parity']} replies were NOT "
                     "bit-identical to np.sort")
    if set(stats["statuses"]) != {"ok"}:
        fails.append(f"{tag}: non-ok statuses under clean load: "
                     f"{stats['statuses']}")
    if sum(stats["statuses"].values()) != requests:
        fails.append(f"{tag}: request accounting mismatch: "
                     f"{sum(stats['statuses'].values())} recorded of "
                     f"{requests} claimed")
    for e in stats.get("metrics_errors", []):
        fails.append(f"{tag}: {e}")


def selftest(out: Path, requests: int, concurrency: int, seed: int) -> int:
    fails: list[str] = []

    # -- 1+2: batched phase, warm-cache gate, then the sequential A/B --
    stats, spans, rc = measure_phase(out, "batched", BATCH_WINDOW_MS,
                                     requests, concurrency, seed)
    check_leg("batched", stats, rc, requests, fails)
    compiles = [s for s in spans if s.get("name") == "jit_compile_execute"]
    misses = [s for s in spans if s.get("name") == "serve.compile_cache"
              and not s.get("attrs", {}).get("hit")]
    if compiles or misses:
        fails.append(f"warm window recorded compile activity: "
                     f"{len(compiles)} jit_compile_execute span(s), "
                     f"{len(misses)} executor-cache miss(es)")
    else:
        log("warm-cache gate OK: zero compile spans in the measured "
            "window")
    batched_reqs = [s for s in spans if s.get("name") == "serve.request"
                    and s.get("attrs", {}).get("batched")]
    if not batched_reqs:
        fails.append("no batched serve.request spans in the measured "
                     "window (batching never engaged)")

    batched_tput = dispatch_mkeys_per_s(spans)
    speedup = None
    for attempt in (1, 2, 3):
        # every attempt is a MATCHED pair measured back to back: on a
        # loaded shared runner either leg can catch a bad patch of
        # machine weather, so a retry re-measures both, never just the
        # denominator
        pre = len(fails)
        if attempt > 1:
            b_stats, b_spans, b_rc = measure_phase(
                out, f"batched{attempt}", BATCH_WINDOW_MS, requests,
                concurrency, seed)
            check_leg(f"batched{attempt}", b_stats, b_rc, requests,
                      fails)
            if len(fails) > pre:
                break     # a corrupt retry leg may not feed the gate
            attempt_tput = dispatch_mkeys_per_s(b_spans)
            batched_tput = max(batched_tput, attempt_tput)
        else:
            attempt_tput = batched_tput
        seq, seq_spans, seq_rc = measure_phase(
            out, f"sequential{attempt}", "0", requests, concurrency,
            seed)
        check_leg(f"sequential{attempt}", seq, seq_rc, requests, fails)
        if len(fails) > pre:
            break
        seq_tput = dispatch_mkeys_per_s(seq_spans)
        if seq_tput > 0:
            ratio = attempt_tput / seq_tput
            speedup = max(speedup or 0.0, ratio)
            log(f"dispatch throughput: batched {attempt_tput:.3f} vs "
                f"sequential {seq_tput:.3f} Mkeys/s -> {ratio:.2f}x "
                f"(closed-loop client: {stats['keys_per_s']/1e6:.3f} vs "
                f"{seq['keys_per_s']/1e6:.3f} Mkeys/s)")
            if speedup >= MIN_BATCH_SPEEDUP:
                break
            if attempt < 3:
                log("below the gate; re-measuring the matched A/B pair "
                    "(shared-runner jitter)")
    if speedup is None or speedup < MIN_BATCH_SPEEDUP:
        fails.append(f"batched dispatch throughput only "
                     f"{speedup or 0:.2f}x sequential "
                     f"(gate {MIN_BATCH_SPEEDUP}x)")
    else:
        log(f"batching gate OK: {speedup:.2f}x >= {MIN_BATCH_SPEEDUP}x")

    # -- 3+4: backpressure typing + per-request fault isolation --------
    srv = Server(out, "limits", {
        "SORT_SERVE_SHAPE_BUCKETS": "10",
        "SORT_SERVE_MAX_INFLIGHT": "1",
        "SORT_SERVE_BATCH_WINDOW_MS": "20",
        "SORT_SERVE_ALLOW_FAULTS": "1",
        "SORT_FALLBACK": "0",
        "SORT_MAX_RETRIES": "0",
        # the result-corruption fault sites live on the DISTRIBUTED
        # sort path; a 1-device CPU process would take the local path
        # and never exercise them, so this server gets a 2-device
        # virtual mesh
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
    })
    try:
        burst = run_load(srv.port, 16, 8, seed + 3000)
        log(f"backpressure burst statuses: {burst['statuses']}")
        if burst["statuses"].get("backpressure", 0) < 1:
            fails.append("MAX_INFLIGHT=1 burst produced no typed "
                         "backpressure rejection")
        if burst["statuses"].get("ok", 0) < 1:
            fails.append("MAX_INFLIGHT=1 burst produced no successful "
                         "request (server wedged?)")
        rng = np.random.default_rng(seed)
        x = rng.integers(-2**31, 2**31 - 1, size=4096, dtype=np.int32)
        with ServeClient(HOST, srv.port) as c:
            r = c.sort(x, faults="result_swap:inf")
            if r.ok or r.error != "integrity":
                fails.append(f"poisoned request: expected typed "
                             f"'integrity' error, got "
                             f"{r.header}")
            else:
                log(f"fault gate: typed error OK ({r.error}: "
                    f"{r.detail[:60]})")
            r2 = c.sort(x)
            if not (r2.ok and np.array_equal(r2.arr, np.sort(x))):
                fails.append("server did not keep serving after the "
                             "poisoned request")
            else:
                log("fault gate OK: server kept serving, next request "
                    "verified")
    finally:
        srv.stop()

    emit_row(stats, {"batched_speedup":
                     round(speedup, 3) if speedup else None,
                     "dispatch_mkeys_per_s": round(batched_tput, 3),
                     "concurrency": concurrency})
    record_metrics(stats, speedup)
    if fails:
        for f in fails:
            log(f"[FAIL] {f}")
        return 1
    log("serve selftest OK (warm cache, batching >= "
        f"{MIN_BATCH_SPEEDUP}x, typed backpressure, per-request fault "
        "isolation, graceful drain)")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--selftest", action="store_true",
                    help="run the full serve gate (make serve-selftest)")
    ap.add_argument("--row", action="store_true",
                    help="measure the batched phase only; emit one "
                         "bench JSON row (bench.py serve row)")
    ap.add_argument("--chaos", action="store_true",
                    help="also measure p99 under the chaos proxy's "
                         "injected response-delay tail, plain AND "
                         "hedged, recorded in the row beside the "
                         "clean numbers (ISSUE 11)")
    ap.add_argument("--planner", action="store_true",
                    help="also measure the window-auto vs fixed-window "
                         "A/B (SORT_PLANNER=on vs off at a mis-set "
                         "fixed window), recorded in the row beside "
                         "the clean numbers (ISSUE 14)")
    ap.add_argument("--out", default="/tmp/mpitest_serve_load",
                    help="artifact dir (server traces)")
    ap.add_argument("--requests", type=int, default=160)
    ap.add_argument("--concurrency", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    if args.selftest:
        return selftest(out, args.requests, args.concurrency, args.seed)
    # --row (and the bare default): batched measurement + row
    return row(out, args.requests, args.concurrency, args.seed,
               chaos=args.chaos, planner=args.planner)


def row(out: Path, requests: int = 160, concurrency: int = 8,
        seed: int = 0, chaos: bool = False, planner: bool = False,
        server_cls: type = Server) -> int:
    """Measure the batched phase and print ONE JSON bench row; 0 on
    success.  bench.py calls this in its own process with
    ``server_cls=InProcServer``."""
    stats, spans, rc = measure_phase(out, "batched", BATCH_WINDOW_MS,
                                     requests, concurrency, seed,
                                     server_cls)
    if rc != 0:
        log(f"server exited rc={rc}")
        return 1
    if stats["bad_parity"] or set(stats["statuses"]) != {"ok"}:
        log(f"load errors: {stats['statuses']} "
            f"bad_parity={stats['bad_parity']}")
        return 1
    if stats.get("metrics_errors"):
        for e in stats["metrics_errors"]:
            log(f"[FAIL] {e}")
        return 1
    extra = {"concurrency": concurrency,
             "dispatch_mkeys_per_s":
             round(dispatch_mkeys_per_s(spans), 3),
             # ISSUE 14: the planner column (the measured phase runs
             # whatever the spawn env set — off unless overridden)
             "planner": str(knobs.get("SORT_PLANNER"))}
    if chaos:
        extra.update(chaos_phase(out, seed))
    if planner:
        extra.update(planner_phase(out, requests, concurrency, seed))
    emit_row(stats, extra)
    record_metrics(stats, None)
    return 0


if __name__ == "__main__":
    sys.exit(main())
