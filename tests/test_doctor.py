"""Sort doctor + timeline + sentinel unit tests (ISSUE 16).

Three layers, smallest fixtures that pin the math:

* **timeline** — straggler factor (incl. ragged / missing-rank byte
  lists and the median-zero fallback), bytes-proportional rank lanes
  scaled into the anchor span, critical-path phase attribution,
  compute/DMA overlap, and a Chrome enrichment that draws no estimated
  rank lane.
* **doctor rules** — one minimal fixture per registered pathology in
  ``DOCTOR_RULES``; each must produce EXACTLY its finding with the
  evidence cited and a knob suggested, and a clean evidence snapshot
  must produce zero findings.
* **sentinel** — the rolling-window math in-process: clean window
  raises nothing, an error burst raises exactly ``deadline_burn`` and
  bridges into ``sort_alerts_total``, p99 drift vs the EWMA raises,
  and the per-rule cooldown holds one alert per window.
"""

import json

import pytest

from mpitest_tpu import doctor
from mpitest_tpu.utils import timeline
from mpitest_tpu.utils.spans import SpanLog


# -- timeline math ----------------------------------------------------

def test_straggler_stats_basic():
    st = timeline.straggler_stats([100.0, 100.0, 100.0, 300.0])
    assert st is not None
    assert st["factor"] == 3.0
    assert st["max"] == 300.0 and st["median"] == 100.0


def test_straggler_stats_degenerate():
    # <2 usable ranks or an all-zero list carries no signal
    assert timeline.straggler_stats([5.0]) is None
    assert timeline.straggler_stats([0.0, 0.0]) is None
    # median 0 (most ranks idle) falls back to the mean: [0,0,0,9]
    # -> mean 2.25 -> factor 4.0
    st = timeline.straggler_stats([0.0, 0.0, 0.0, 9.0])
    assert st is not None and st["factor"] == 4.0


def _rows_fixture():
    """One anchored pass + phases + overlapping compute/DMA, as plain
    dict rows (the duck-typed input report.py feeds the fold)."""
    return [
        {"name": "sort_pass", "id": 1, "parent": None,
         "t0": 0.0, "dt": 2.0, "attrs": {}},
        {"name": "exchange_balance", "id": 2, "parent": 1,
         "t0": 0.5, "dt": 0.0,
         "attrs": {"recv_bytes": [100, 110, 90, 440],
                   "negotiated_cap": 512, "algorithm": "radix"}},
        {"name": "phase:sort", "t0": 0.0, "dt": 2.0, "attrs": {}},
        {"name": "phase:verify", "t0": 2.0, "dt": 0.3, "attrs": {}},
        {"name": "jit_execute", "t0": 0.0, "dt": 1.0, "attrs": {}},
        {"name": "ingest.transfer", "t0": 0.5, "dt": 1.0,
         "attrs": {"bytes": 4096}},
    ]


def test_build_timeline_lanes_straggler_critical_path():
    tl = timeline.build_timeline(_rows_fixture())
    # sorted bytes [90,100,110,440]: median 105, factor 440/105
    assert tl["straggler_factor"] == pytest.approx(440 / 105, abs=1e-3)
    assert tl["ranks"] == [0, 1, 2, 3]
    # lanes scale the ANCHOR's 2.0s budget by bytes/peak
    lane3 = tl["lanes"][3][0]
    assert lane3["dt"] == pytest.approx(2.0) and lane3["estimated"]
    assert tl["lanes"][0][0]["dt"] == pytest.approx(2.0 * 100 / 440)
    assert tl["passes"][0]["anchor"] == "sort_pass"
    assert tl["critical_path_phase"] == "sort"
    assert tl["phases"]["verify"] == pytest.approx(0.3)
    # compute [0,1] vs DMA [0.5,1.5]: 0.5s overlap = 50% of DMA
    assert tl["overlap"]["compute_dma_pct"] == pytest.approx(50.0)
    assert tl["counters"]["exchange_cap"] == [(0.5, 512.0)]
    assert tl["counters"]["inflight_bytes"][0] == (0.5, 4096.0)
    assert tl["counters"]["inflight_bytes"][-1] == (1.5, 0.0)


def test_build_timeline_ragged_and_unanchored():
    rows = [
        # non-numeric entries drop; 2 usable ranks is still a signal
        {"name": "exchange_balance", "id": 7, "parent": None,
         "t0": 0.0, "dt": 0.0,
         "attrs": {"recv_bytes": [100, None, "x", 300]}},
    ]
    tl = timeline.build_timeline(rows)
    p = tl["passes"][0]
    assert p["rank_bytes"] == [100.0, 300.0]
    assert p["straggler"] == 1.5  # max/median of the usable pair
    # no dt>0 ancestor -> no lane estimates, factor still reported
    assert p["anchor"] is None and tl["lanes"] == {}
    assert tl["straggler_factor"] == 1.5
    # a single usable rank carries no imbalance signal at all
    tl2 = timeline.build_timeline(
        [{"name": "exchange_balance", "t0": 0.0, "dt": 0.0,
          "attrs": {"recv_bytes": [100, "?"]}}])
    assert tl2["passes"][0]["straggler"] is None
    assert tl2["straggler_factor"] is None


def test_bench_fold_keys_only_when_signal_present():
    assert timeline.bench_fold([]) == {}
    fold = timeline.bench_fold(_rows_fixture())
    assert fold["straggler_factor"] == pytest.approx(440 / 105, abs=1e-3)
    assert fold["critical_path_phase"] == "sort"


def test_chrome_events_stable_rank_tids():
    """The Chrome enrichment draws measured tracks only: no estimated
    per-rank lane, while the counter tracks stay."""
    events = timeline.chrome_events(_rows_fixture())
    assert not any("estimated" in str(e["args"].get("name", ""))
                   or e["args"].get("estimated") for e in events)
    assert not [e for e in events if e.get("ph") == "X"
                and e.get("tid") != timeline.DISK_TID]
    counters = {e["name"] for e in events if e.get("ph") == "C"}
    assert {"inflight bytes", "exchange cap"} <= counters


def test_chrome_trace_export_carries_rank_lanes():
    """SpanLog.to_chrome_trace keeps the host lane and its measured
    extras, and draws no estimated rank lane beside them."""
    log = SpanLog()
    with log.span("sort_pass"):
        log.event("exchange_balance", recv_bytes=[10, 20, 30, 40])
    trace = log.to_chrome_trace()
    events = trace["traceEvents"] if isinstance(trace, dict) else trace
    json.dumps(events)  # must stay valid trace-event JSON
    assert {e.get("tid") for e in events} == {timeline.HOST_TID}
    assert not any(e.get("args", {}).get("estimated") for e in events)
    assert [e["name"] for e in events if e.get("ph") == "X"] == \
        ["sort_pass"]


# -- doctor rules: one fixture per registered pathology ---------------

def _only(findings, rule):
    assert [f.rule for f in findings] == [rule], \
        f"expected exactly {rule}, got {[f.rule for f in findings]}"
    return findings[0]


def test_rule_vocabulary_is_fully_registered():
    assert set(doctor.DOCTOR_RULES) == set(doctor._RULES)
    with pytest.raises(KeyError):
        doctor.run_rule("bogus_rule", doctor.empty_evidence())
    with pytest.raises(KeyError):
        doctor.Finding("bogus_rule", "warn", "x")
    with pytest.raises(ValueError):
        doctor.Finding("skew_imbalance", "meh", "x")


def test_clean_evidence_zero_findings():
    ev = doctor.evidence_from_rows(
        [{"name": "serve.request", "dt": 0.01,
          "attrs": {"status": "ok"}}] * 20,
        timeline={"straggler_factor": 1.1,
                  "phases": {"sort": 1.0, "verify": 0.05}})
    assert doctor.diagnose(ev) == []


def test_rule_skew_imbalance():
    ev = doctor.empty_evidence()
    ev["timeline"] = {"straggler_factor": 4.0,
                      "passes": [{"seq": 0, "straggler": 4.0,
                                  "rank_bytes": [100.0, 400.0]}]}
    f = _only(doctor.diagnose(ev), "skew_imbalance")
    assert f.severity == "critical"  # >= SKEW_FACTOR_CRITICAL
    assert f.knob == "SORT_RESTAGE" and f.evidence
    assert f.value == 4.0 and f.threshold == doctor.SKEW_FACTOR_WARN


def test_rule_cap_thrash():
    rows = [{"name": "sort.plan", "attrs": {
        "decisions": {"cap": {"chosen": 512,
                              "actual": {"regrows": 3}}}}}]
    f = _only(doctor.diagnose(doctor.evidence_from_rows(rows)),
              "cap_thrash")
    assert f.knob == "SORT_CAP_FACTOR" and f.value == 3.0
    assert any("regrows=3" in c for c in f.evidence)


def test_rule_compile_storm():
    rows = ([{"name": "serve.compile_cache", "attrs": {"hit": False}}] * 5
            + [{"name": "serve.compile_cache", "attrs": {"hit": True}}])
    f = _only(doctor.diagnose(doctor.evidence_from_rows(rows)),
              "compile_storm")
    assert f.knob == "SORT_SERVE_SHAPE_BUCKETS" and f.value == 5.0


def test_rule_window_misfit_waste_and_occupancy():
    rows = [{"name": "sort.plan", "attrs": {
        "decisions": {"batch": {"actual": {"waste": 0.7}}}}}]
    f = _only(doctor.diagnose(doctor.evidence_from_rows(rows)),
              "window_misfit")
    assert f.severity == "warn" and f.value == 0.7
    # the never-packs shape: N batches, N segments -> occupancy info
    rows = [{"name": "serve.batch", "attrs": {"segments": 1}}] * 4
    f = _only(doctor.diagnose(doctor.evidence_from_rows(rows)),
              "window_misfit")
    assert f.severity == "info" and f.value == 1.0


def test_rule_spill_bound():
    ev = doctor.empty_evidence()
    ev["timeline"] = {"overlap": {"disk_s": 3.5, "compute_s": 0.5,
                                  "compute_disk_pct": 10.0}}
    f = _only(doctor.diagnose(ev), "spill_bound")
    assert f.knob == "SORT_MERGE_FANIN"
    assert f.value == pytest.approx(3.5 / 4.0)


def test_rule_verify_overhead_and_absolute_floor():
    ev = doctor.empty_evidence()
    ev["timeline"] = {"phases": {"sort": 2.0, "verify": 1.0}}
    f = _only(doctor.diagnose(ev), "verify_overhead_regression")
    assert f.knob == "SORT_VERIFY"
    assert f.value == pytest.approx(1.0 / 3.0, abs=1e-3)
    # a tiny run below VERIFY_MIN_SECONDS never fires, whatever the
    # ratio — cold-compile verify on small inputs is not a pathology
    ev["timeline"] = {"phases": {"sort": 0.04,
                                 "verify": doctor.VERIFY_MIN_SECONDS / 2}}
    assert doctor.diagnose(ev) == []


def test_rule_breaker_flap():
    rows = ([{"name": "serve.watchdog", "attrs": {"event": "trip"}}] * 2
            + [{"name": "serve.watchdog", "attrs": {"event": "recovered"}}])
    f = _only(doctor.diagnose(doctor.evidence_from_rows(rows)),
              "breaker_flap")
    assert f.severity == "critical" and f.value == 2.0
    assert any("recovered" in c for c in f.evidence)


def test_rule_spill_churn():
    # one recovery alone is normal operation — below the gate
    rows = [{"name": "external.recover",
             "attrs": {"reason": "fingerprint", "bad_runs": 1}}]
    assert doctor.diagnose(doctor.evidence_from_rows(rows)) == []
    # recovery + crash resume in one trace = churn (warn)
    rows.append({"name": "external.resume",
                 "attrs": {"dataset": "ds1", "committed": 4,
                           "valid": 4}})
    f = _only(doctor.diagnose(doctor.evidence_from_rows(rows)),
              "spill_churn")
    assert f.severity == "warn" and f.value == 2.0
    assert f.knob == "SORT_SPILL_DIR"
    assert any("external.recover" in c for c in f.evidence)
    assert any("external.resume" in c for c in f.evidence)
    # repeated integrity recoveries escalate to critical
    rows = [{"name": "external.recover",
             "attrs": {"reason": "fingerprint", "bad_runs": 1}}] * 2
    f = _only(doctor.diagnose(doctor.evidence_from_rows(rows)),
              "spill_churn")
    assert f.severity == "critical"


def test_rule_deadline_burn():
    rows = ([{"name": "serve.request", "dt": 0.01,
              "attrs": {"status": "ok"}}] * 12
            + [{"name": "serve.request", "dt": 0.0,
                "attrs": {"status": "deadline"}}] * 4
            + [{"name": "serve.deadline", "attrs": {}}] * 4)
    f = _only(doctor.diagnose(doctor.evidence_from_rows(rows)),
              "deadline_burn")
    # 4/16 = 25% vs the 0.1% allowance: way past 2x -> critical
    assert f.severity == "critical"
    assert any("4 expired" in c for c in f.evidence)
    assert f.knob == "SORT_SERVE_MAX_INFLIGHT"


def test_plan_findings_compact_digest_block():
    attrs = {"decisions": {"cap": {"actual": {"regrows": 2}},
                           "batch": {"actual": {"waste": 0.9}}}}
    block = doctor.plan_findings(attrs)
    assert sorted(b["rule"] for b in block) == ["cap_thrash",
                                                "window_misfit"]
    assert all(set(b) == {"rule", "severity", "summary"} for b in block)
    assert doctor.plan_findings({}) == []


def test_render_shapes():
    assert "no findings" in doctor.render([])
    f = doctor.Finding("cap_thrash", "warn", "caps", evidence=["e1"],
                       knob="SORT_CAP_FACTOR", direction="raise")
    out = doctor.render([f])
    assert "[WARN] cap_thrash" in out and "evidence: e1" in out
    assert "SORT_CAP_FACTOR" in out


# -- sentinel math ----------------------------------------------------

def _wired(window_s=60.0, burn_rate=2.0):
    from mpitest_tpu.serve.sentinel import SortSentinel
    from mpitest_tpu.utils.metrics_live import (LiveMetrics,
                                                SpanMetricsBridge)
    log = SpanLog()
    metrics = LiveMetrics()
    log.observers.append(SpanMetricsBridge(metrics))
    sen = SortSentinel(metrics, log, window_s=window_s,
                       burn_rate=burn_rate)
    log.observers.append(sen)
    return log, metrics, sen


def test_sentinel_clean_window_stays_silent():
    log, _metrics, sen = _wired()
    for _ in range(30):
        log.record("serve.request", 0.0, 0.01, status="ok")
    assert sen.alerts_total == 0
    assert not any(s.name == "serve.alert" for s in log.spans)


def test_sentinel_burn_alert_bridges_and_cools_down():
    log, metrics, sen = _wired()
    for _ in range(12):
        log.record("serve.request", 0.0, 0.01, status="ok")
    for _ in range(6):
        log.record("serve.request", 0.0, 0.0, status="deadline")
    assert sen.alerts_total == 1  # cooldown: one alert per window
    alert = sen.alerts[0]
    assert alert["rule"] == "deadline_burn"
    assert alert["severity"] == "critical"  # 33% vs 0.1% allowance
    spans = [s for s in log.spans if s.name == "serve.alert"]
    assert len(spans) == 1 and spans[0].attrs["rule"] == "deadline_burn"
    assert ('sort_alerts_total{rule="deadline_burn",'
            'severity="critical"} 1') in metrics.render_prom()
    snap = sen.snapshot()
    assert snap["alerts_total"] == 1
    assert snap["series"]["window_errors"] == 6


def test_sentinel_p99_drift():
    log, _metrics, sen = _wired()
    # 10 clean samples seed the EWMA at ~10ms ...
    for _ in range(10):
        log.record("serve.request", 0.0, 0.010, status="ok")
    assert sen.alerts_total == 0 and sen._p99_ewma == pytest.approx(10.0)
    # ... then one 100ms sample drives p99 past DRIFT_FACTOR x EWMA
    log.record("serve.request", 0.0, 0.100, status="ok")
    assert sen.alerts_total == 1
    assert sen.alerts[0]["rule"] == "deadline_burn"
    assert sen.alerts[0]["severity"] == "warn"


def test_sentinel_skew_and_cap_rules():
    log, _m, sen = _wired()
    for _ in range(3):  # MIN_IMBALANCE_SAMPLES before the EWMA alerts
        log.record("exchange_balance", 0.0, 0.0, peer_ratio=4.0)
    assert [a["rule"] for a in sen.alerts] == ["skew_imbalance"]
    assert sen.alerts[0]["severity"] == "critical"
    log2, _m2, sen2 = _wired()
    for _ in range(2):
        log2.record("sort.plan", 0.0, 0.0,
                    decisions={"cap": {"actual": {"regrows": 1}}})
    assert [a["rule"] for a in sen2.alerts] == ["cap_thrash"]
