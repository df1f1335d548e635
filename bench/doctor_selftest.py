#!/usr/bin/env python3
"""Doctor + sentinel selftest — the ISSUE 16 diagnosis-layer gate.

Plants each registered pathology DETERMINISTICALLY (synthesized
registered-span streams through the real ``SpanLog`` API — the same
spans the live producers emit) and asserts the doctor names it, and
ONLY it, with evidence citations.  Cells:

* one cell per ``DOCTOR_RULES`` pathology (9): the evidence fold +
  timeline + ``diagnose()`` over the planted trace yields exactly the
  planted rule, and the rendered finding cites its evidence spans;
* a CLI drill: ``report.py --doctor <trace>`` renders the skew cell's
  finding and exits 0, and every planted trace passes ``report.py
  --check --require-registered-spans`` (the pathologies are built
  from REGISTERED vocabulary only);
* a clean-run cell: a REAL tiny sort's trace raises ZERO findings —
  the doctor's false-positive gate;
* sentinel cells (in-process ``SpanLog`` + ``LiveMetrics`` + bridge +
  ``SortSentinel``, the exact server wiring): a clean window raises
  zero alerts; an error burst raises exactly ``deadline_burn``
  (critical) — bridged into ``sort_alerts_total{rule,severity}`` and
  dumping a flight-recorder artifact that passes ``report.py
  --check``; repeated skewed exchanges raise ``skew_imbalance``; the
  per-rule cooldown keeps a sustained burst at one alert per window.

Run directly or via ``make doctor-selftest`` (CI wires it beside the
fault/serve/multichip/external selftests).
"""

from __future__ import annotations

import argparse
import contextlib
import io
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

FAIL = 0


def check(name: str, ok: bool, detail: str = "") -> None:
    global FAIL
    tag = "ok " if ok else "BAD"
    print(f"  [{tag}] {name}" + (f" — {detail}" if detail else ""))
    if not ok:
        FAIL += 1


def _diagnose_rows(rows: list[dict], slo_target: float | None = None):
    """The exact fold chain ``report.py --doctor`` runs."""
    from mpitest_tpu import doctor
    from mpitest_tpu.utils import timeline

    ev = doctor.evidence_from_rows(
        rows, timeline=timeline.build_timeline(rows))
    if slo_target is not None:
        ev["slo_target_pct"] = slo_target
    return doctor.diagnose(ev)


def _planted_log(out_dir: Path, cell: str):
    """Fresh SpanLog streaming to a per-cell trace file."""
    from mpitest_tpu.utils.spans import SpanLog

    log = SpanLog()
    log.stream_path = str(out_dir / f"{cell}.jsonl")
    return log


# ---------------------------------------------------------------- cells

def plant_skew(log) -> None:
    log.record("sort", 0.0, 2.0)
    log.record("exchange_balance", 0.5, 0.0,
               recv_bytes=[100.0, 110.0, 90.0, 420.0],
               send_bytes=[180.0, 180.0, 180.0, 180.0],
               negotiated_cap=256, worst_cap=1024)


def plant_cap_thrash(log) -> None:
    log.record("sort", 0.0, 1.0)
    log.record("sort.plan", 1.0, 0.0, algo="sample", decisions={
        "cap": {"chosen": 128, "predicted": {"cap": 128},
                "actual": {"cap": 310, "regrows": 3}, "regret": 1.4}})


def plant_compile_storm(log) -> None:
    for i in range(6):
        log.record("serve.compile_cache", float(i), 0.0, hit=False,
                   bucket=1 << (10 + i), dtype="int32", compile_s=0.2)
    log.record("serve.compile_cache", 7.0, 0.0, hit=True,
               bucket=1024, dtype="int32")


def plant_window_misfit(log) -> None:
    log.record("sort.plan", 0.0, 0.0, algo="sample", decisions={
        "batch": {"chosen": 4096, "predicted": {"waste": 0.1},
                  "actual": {"waste": 0.7}, "regret": 0.6}})


def plant_spill_bound(log) -> None:
    log.record("jit_execute", 0.0, 0.5)
    log.record("external.run", 0.5, 1.2, run=0, n=1 << 20,
               bytes=1 << 22, dtype="int32", payload_width=0)
    log.record("external.merge", 1.7, 2.3, runs=4, n=1 << 22,
               merge_pass=0, final=True)


def plant_verify_overhead(log) -> None:
    log.record("phase:sort", 0.0, 2.0)
    log.record("phase:verify", 2.0, 1.0)


def plant_spill_churn(log) -> None:
    # one integrity recovery + one crash resume in the same trace —
    # the spill volume itself becomes the suspect (ISSUE 18)
    log.record("external.recover", 0.0, 0.0, reason="fingerprint",
               bad_runs=1, attempt=1)
    log.record("external.resume", 1.0, 0.0, dataset="ds1", committed=4,
               valid=4, skipped_lines=0)


def plant_breaker_flap(log) -> None:
    log.record("serve.watchdog", 0.0, 0.0, event="trip", age_s=130.0)
    log.record("serve.watchdog", 1.0, 0.0, event="recovered")
    log.record("serve.watchdog", 2.0, 0.0, event="trip", age_s=131.0)


def plant_deadline_burn(log) -> None:
    for i in range(12):
        log.record("serve.request", float(i), 0.01, status="ok",
                   n=4096, dtype="int32")
    for i in range(4):
        log.record("serve.request", 12.0 + i, 0.01, status="deadline",
                   n=4096, dtype="int32")
        log.record("serve.deadline", 12.0 + i, 0.0, stage="queue")


PATHOLOGY_CELLS = (
    ("skew_imbalance", plant_skew),
    ("cap_thrash", plant_cap_thrash),
    ("compile_storm", plant_compile_storm),
    ("window_misfit", plant_window_misfit),
    ("spill_bound", plant_spill_bound),
    ("verify_overhead_regression", plant_verify_overhead),
    ("spill_churn", plant_spill_churn),
    ("breaker_flap", plant_breaker_flap),
    ("deadline_burn", plant_deadline_burn),
)


def run_pathology_cells(out_dir: Path) -> None:
    from mpitest_tpu import doctor, report

    print(f"pathology cells ({len(PATHOLOGY_CELLS)} planted rules):")
    assert {c[0] for c in PATHOLOGY_CELLS} == set(doctor.DOCTOR_RULES), \
        "cell list out of sync with DOCTOR_RULES"
    for rule, plant in PATHOLOGY_CELLS:
        log = _planted_log(out_dir, rule)
        plant(log)
        trace = Path(log.stream_path)
        rows = report.load_rows(str(trace))
        findings = _diagnose_rows(rows)
        named = [f.rule for f in findings]
        check(f"{rule}: diagnosed", named == [rule],
              f"findings={named}")
        if findings:
            f = findings[0]
            check(f"{rule}: evidence cited",
                  bool(f.evidence) and all(isinstance(c, str) and c
                                           for c in f.evidence),
                  f"{len(f.evidence)} citation(s)")
            check(f"{rule}: knob suggested",
                  bool(f.knob) and bool(f.direction),
                  f"{f.knob} -> {f.direction}")
        # the planted stream is registered-vocabulary only
        rc = report.main(["--check", "--require-registered-spans",
                          str(trace)])
        check(f"{rule}: trace passes --check --require-registered-spans",
              rc == 0, f"rc={rc}")


def run_cli_cell(out_dir: Path) -> None:
    from mpitest_tpu import report

    print("report.py --doctor CLI drill:")
    trace = out_dir / "skew_imbalance.jsonl"
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = report.main(["--doctor", str(trace)])
    rendered = buf.getvalue()
    check("--doctor exits 0", rc == 0, f"rc={rc}")
    check("--doctor names the rule", "skew_imbalance" in rendered)
    check("--doctor cites evidence", "exchange_balance" in rendered)
    check("--doctor suggests a knob", "SORT_RESTAGE" in rendered)


def run_clean_cell(out_dir: Path) -> None:
    import numpy as np

    from mpitest_tpu.models.api import sort
    from mpitest_tpu.utils.io import generate
    from mpitest_tpu.utils.trace import Tracer

    print("clean-run cell (real tiny sort, zero findings):")
    tracer = Tracer()
    x = generate("uniform", 1 << 10, np.dtype(np.int32), seed=7)
    out = sort(x, algorithm="sample", tracer=tracer)
    check("sorted", bool(np.array_equal(out, np.sort(x))))
    rows = [s.to_dict() for s in tracer.spans.spans]
    findings = _diagnose_rows(rows)
    check("zero findings on a clean run", not findings,
          f"findings={[f.rule for f in findings]}")


def run_sentinel_cells(out_dir: Path) -> None:
    from mpitest_tpu import report
    from mpitest_tpu.serve.sentinel import SortSentinel
    from mpitest_tpu.utils import flight_recorder
    from mpitest_tpu.utils.metrics_live import LiveMetrics, SpanMetricsBridge
    from mpitest_tpu.utils.spans import SpanLog

    print("sentinel cells (in-process server wiring):")

    def wired(trace_name: str):
        log = SpanLog()
        log.stream_path = str(out_dir / trace_name)
        metrics = LiveMetrics()
        log.observers.append(SpanMetricsBridge(metrics))
        s = SortSentinel(metrics, log, window_s=60.0, burn_rate=2.0)
        log.observers.append(s)
        return log, metrics, s

    # clean window: ok traffic only -> zero alerts, zero alert spans
    log, metrics, s = wired("sentinel_clean.jsonl")
    for _ in range(30):
        log.record("serve.request", time.perf_counter(), 0.01,
                   status="ok", n=4096)
    check("clean window: zero alerts", len(s.alerts) == 0,
          f"{len(s.alerts)} alert(s)")
    check("clean window: no serve.alert spans",
          not any(sp.name == "serve.alert" for sp in log.spans))

    # error burst -> exactly deadline_burn, critical, with a flight
    # artifact that passes report --check
    flight_recorder.reset()
    log, metrics, s = wired("sentinel_burn.jsonl")
    for _ in range(12):
        log.record("serve.request", time.perf_counter(), 0.01,
                   status="ok", n=4096)
    for _ in range(6):
        log.record("serve.request", time.perf_counter(), 0.01,
                   status="deadline", n=4096)
    rules = [a["rule"] for a in s.alerts]
    check("burst: exactly deadline_burn", rules == ["deadline_burn"],
          f"alerts={rules}")
    sevs = [a["severity"] for a in s.alerts]
    check("burst: critical severity", sevs == ["critical"],
          f"severities={sevs}")
    prom = metrics.render_prom()
    check("burst: bridged into sort_alerts_total",
          'sort_alerts_total{rule="deadline_burn",severity="critical"} 1'
          in prom)
    check("burst: serve.alert span emitted",
          sum(1 for sp in log.spans if sp.name == "serve.alert") == 1)
    rec = flight_recorder.get()
    check("burst: flight artifact dumped", rec.dumps == 1,
          f"dumps={rec.dumps}")
    dump_files = sorted(Path(rec.directory).glob("*.jsonl"),
                        key=os.path.getmtime)
    rc = report.main(["--check", str(dump_files[-1])]) \
        if dump_files else 1
    check("burst: flight artifact passes report --check", rc == 0,
          f"rc={rc} file={dump_files[-1].name if dump_files else None}")
    # cooldown: a sustained burst stays at one alert per window
    for _ in range(6):
        log.record("serve.request", time.perf_counter(), 0.01,
                   status="internal", n=4096)
    check("cooldown: still one alert in the window",
          len(s.alerts) == 1, f"{len(s.alerts)} alert(s)")

    # repeated skewed exchanges -> skew_imbalance via the EWMA
    log, metrics, s = wired("sentinel_skew.jsonl")
    for i in range(4):
        log.record("exchange_balance", time.perf_counter(), 0.0,
                   recv_bytes=[100.0, 100.0, 100.0, 400.0],
                   send_bytes=[175.0] * 4, peer_ratio=4.0,
                   negotiated_cap=256)
    rules = [a["rule"] for a in s.alerts]
    check("skew: exactly skew_imbalance", rules == ["skew_imbalance"],
          f"alerts={rules}")
    # the /alerts snapshot carries the series state
    snap = s.snapshot()
    check("snapshot: enabled with series",
          snap.get("enabled") is True and "series" in snap
          and snap["series"]["imbalance_ewma"] is not None)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="/tmp/mpitest_doctor_selftest",
                    help="directory for per-cell traces and flight "
                         "artifacts")
    args = ap.parse_args()
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    # flight artifacts land inside the selftest dir (env write — reads
    # stay inside the knob registry)
    os.environ["SORT_FLIGHT_RECORDER_DIR"] = str(out_dir / "flightrec")

    run_pathology_cells(out_dir)
    run_cli_cell(out_dir)
    run_clean_cell(out_dir)
    run_sentinel_cells(out_dir)

    print(f"doctor selftest: "
          f"{'CLEAN' if FAIL == 0 else f'{FAIL} BAD cell(s)'}")
    return 1 if FAIL else 0


if __name__ == "__main__":
    sys.exit(main())
