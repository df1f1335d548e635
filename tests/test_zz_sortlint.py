"""Tests for the static-analysis subsystem (ISSUE 4): sortlint rules on
good/bad fixture snippets, the knob registry's contracts, the span
schema, the comm parity checker, and the repo-wide dogfood run.

Named ``test_zz_*`` to sort LAST: tier-1 is timeout-bound and
dots-counted, and everything here is pure ast/text/registry work (no
jit compiles), so the whole module stays in low single-digit seconds.
"""

from __future__ import annotations

import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from tools import comm_parity  # noqa: E402
from tools.sortlint import (  # noqa: E402
    LINT_VERSION, RULES, lint_repo, lint_source)

from mpitest_tpu.utils import knobs, span_schema  # noqa: E402


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ------------------------------------------------------------- fixtures

def test_sl001_env_read_flagged_writes_allowed():
    bad = "import os\nv = os.environ.get('SORT_ALGO', 'sample')\n"
    assert rules_of(lint_source(bad, "mpitest_tpu/x.py")) == ["SL001"]
    bad2 = "import os\nv = os.getenv('SORT_ALGO')\n"
    assert rules_of(lint_source(bad2, "x.py")) == ["SL001"]
    bad3 = "import os\nv = os.environ['SORT_ALGO']\n"
    assert rules_of(lint_source(bad3, "x.py")) == ["SL001"]
    # writes and subprocess-env construction stay legal
    good = ("import os\nos.environ['A'] = '1'\n"
            "os.environ.setdefault('B', '2')\ndel os.environ['A']\n"
            "env = dict(os.environ, C='3')\n")
    assert lint_source(good, "x.py") == []
    # the registry itself is exempt (it IS the sanctioned reader)
    assert lint_source(bad, "mpitest_tpu/utils/knobs.py") == []


def test_sl002_span_requires_with():
    bad = "s = tracer.spans.span('sort')\n"
    assert "SL002" in rules_of(lint_source(bad, "x.py"))
    good = "with tracer.spans.span('sort'):\n    pass\n"
    assert lint_source(good, "x.py") == []
    # wrapper idiom: returning the context manager is allowed
    wrapper = ("def f():\n    return spans.maybe_span('radix_pass')\n")
    assert lint_source(wrapper, "x.py") == []


def test_sl003_span_names_come_from_schema():
    bad = "with tracer.spans.span('totally_new_span'):\n    pass\n"
    assert rules_of(lint_source(bad, "x.py")) == ["SL003"]
    bad_phase = "with tracer.phase('warp'):\n    pass\n"
    assert rules_of(lint_source(bad_phase, "x.py")) == ["SL003"]
    good = ("with tracer.phase('sort'):\n"
            "    with tracer.spans.span('radix_pass'):\n        pass\n")
    assert lint_source(good, "x.py") == []
    nonliteral = "with tracer.spans.span(name):\n    pass\n"
    assert rules_of(lint_source(nonliteral, "x.py")) == ["SL003"]


def test_sl000_suppression_needs_reason():
    sup_ok = ("with tracer.spans.span(n):  "
              "# sortlint: disable=SL003 -- n is provably registered\n"
              "    pass\n")
    assert lint_source(sup_ok, "x.py") == []
    # a reasonless directive does NOT suppress: the original finding
    # survives and the directive itself is flagged
    sup_bad = ("with tracer.spans.span(n):  # sortlint: disable=SL003\n"
               "    pass\n")
    assert rules_of(lint_source(sup_bad, "x.py")) == ["SL000", "SL003"]


def test_sl004_metric_names_come_from_registry():
    bad = "metrics.counter('made_up_total').inc(1)\n"
    assert rules_of(lint_source(bad, "x.py")) == ["SL004"]
    # wrong kind for a registered name
    kind = "metrics.gauge('sort_serve_requests_total').set(1)\n"
    assert rules_of(lint_source(kind, "x.py")) == ["SL004"]
    nonlit = "metrics.counter(name).inc(1)\n"
    assert rules_of(lint_source(nonlit, "x.py")) == ["SL004"]
    good = ("self.metrics.counter('sort_serve_requests_total')"
            ".inc(1, status='ok')\n"
            "metrics.histogram('sort_serve_queue_wait_seconds')"
            ".observe(0.1)\n")
    assert lint_source(good, "x.py") == []
    # unrelated receivers never match (kernels.histogram is a jnp op)
    unrelated = "h = kernels.histogram(dest, n_ranks)\n"
    assert lint_source(unrelated, "x.py") == []
    # the registry module itself is exempt
    assert lint_source(bad, "mpitest_tpu/utils/metrics_live.py") == []


def test_sl005_plan_decisions_come_from_registry():
    bad = "plan.decide('warp_speed', chosen=1)\n"
    assert rules_of(lint_source(bad, "x.py")) == ["SL005"]
    bad2 = "self.plan.actual('made_up', need=3)\n"
    assert rules_of(lint_source(bad2, "x.py")) == ["SL005"]
    nonlit = "plan.bump(name, 'regrows')\n"
    assert rules_of(lint_source(nonlit, "x.py")) == ["SL005"]
    good = ("plan.decide('cap', chosen=128, cap=128, need=100)\n"
            "self.plan.bump('cap', 'regrows')\n"
            "plan.actual('restage', peer_ratio=1.1)\n")
    assert lint_source(good, "x.py") == []
    # unrelated receivers never match (a dict named `state`, say)
    unrelated = "state.decide('whatever')\n"
    assert lint_source(unrelated, "x.py") == []
    # the registry module itself is exempt
    assert lint_source(bad, "mpitest_tpu/models/plan.py") == []


def test_sl006_planner_policies_come_from_registry():
    bad = "p = planner.policy('warp_speed')\n"
    assert rules_of(lint_source(bad, "x.py")) == ["SL006"]
    bad2 = "planner_mod.policy('made_up')\n"
    assert rules_of(lint_source(bad2, "x.py")) == ["SL006"]
    # a dynamic lookup is allowed: policy() raises KeyError on
    # unregistered names at runtime — the call IS the registry check
    nonlit = "planner.policy(name)\n"
    assert lint_source(nonlit, "x.py") == []
    # the recorded verdict is policed too: plan.decide("planner",
    # chosen=...) must use a registered policy name
    bad3 = "plan.decide('planner', chosen='warp_speed', applied=True)\n"
    assert rules_of(lint_source(bad3, "x.py")) == ["SL006"]
    good = ("p = planner.policy('verify_passthrough')\n"
            "plan.decide('planner', chosen='window_auto', applied=True)\n"
            "plan.decide('planner', chosen=pchoice.policy)\n")
    assert lint_source(good, "x.py") == []
    # unrelated receivers never match
    unrelated = "cfg.policy('whatever')\n"
    assert lint_source(unrelated, "x.py") == []
    # the registry module itself is exempt
    assert lint_source(bad, "mpitest_tpu/models/planner.py") == []


def test_sl007_doctor_rules_come_from_registry():
    bad = "doctor.run_rule('warp_drive_misfire', ev)\n"
    assert rules_of(lint_source(bad, "x.py")) == ["SL007"]
    # alerts are policed on ANY receiver — the sentinel raises them
    bad2 = "self._alert('made_up', 'warn', 'x', value=1.0, threshold=1)\n"
    assert rules_of(lint_source(bad2, "x.py")) == ["SL007"]
    # serve.alert emissions carry a rule label that must be registered
    bad3 = "spans.record('serve.alert', 0.0, 0.0, rule='made_up')\n"
    assert rules_of(lint_source(bad3, "x.py")) == ["SL007"]
    # a computed name is allowed: run_rule/_alert raise KeyError on
    # unregistered names at runtime — the call IS the registry check
    nonlit = ("doctor.run_rule(name, ev)\n"
              "self._alert(rule, sev, msg, value=v, threshold=t)\n"
              "spans.record('serve.alert', 0.0, 0.0, rule=rule)\n")
    assert lint_source(nonlit, "x.py") == []
    good = ("doctor.run_rule('cap_thrash', ev)\n"
            "self._alert('deadline_burn', 'critical', 'x', value=3.0, "
            "threshold=2.0)\n"
            "spans.record('serve.alert', 0.0, 0.0, rule='skew_imbalance')\n")
    assert lint_source(good, "x.py") == []
    # unrelated receivers never match the run_rule shape
    unrelated = "router.run_rule('whatever', ev)\n"
    assert lint_source(unrelated, "x.py") == []
    # the registry module itself is exempt
    assert lint_source(bad, "mpitest_tpu/doctor.py") == []


def test_doctor_registry_vocabulary():
    from mpitest_tpu import doctor as doctor_mod

    assert all(doc for doc in doctor_mod.DOCTOR_RULES.values())
    assert {"skew_imbalance", "cap_thrash", "compile_storm",
            "window_misfit", "spill_bound",
            "verify_overhead_regression", "breaker_flap",
            "deadline_burn", "spill_churn"} == set(doctor_mod.DOCTOR_RULES)
    # every vocabulary key has a registered diagnosis function
    assert set(doctor_mod.DOCTOR_RULES) == set(doctor_mod._RULES)
    assert all(s in doctor_mod.SEVERITIES
               for s in ("info", "warn", "critical"))


def test_planner_registry_vocabulary():
    from mpitest_tpu.models import planner as planner_mod

    assert all(doc for doc in planner_mod.PLANNER_POLICIES.values())
    for must in ("static", "verify_passthrough", "merge_sample",
                 "radix_narrow", "cap_margin", "window_auto"):
        assert must in planner_mod.PLANNER_POLICIES


def test_plan_registry_vocabulary():
    from mpitest_tpu.models import plan as plan_mod

    assert all(doc for doc in plan_mod.PLAN_DECISIONS.values())
    assert {"algo", "cap", "restage", "engine", "exchange_engine",
            "passes", "ladder", "batch",
            "planner", "external"} == set(plan_mod.PLAN_DECISIONS)


def test_metrics_registry_vocabulary():
    from mpitest_tpu.utils import metrics_live

    assert all(kind in ("counter", "gauge", "histogram") and doc
               for kind, doc in metrics_live.METRICS.values())
    # every histogram bucket set belongs to a registered histogram
    for name in metrics_live._HISTOGRAM_BUCKETS:
        assert metrics_live.METRICS[name][0] == "histogram"


def test_sl010_lax_reduce_banned():
    bad = "import jax\nout = jax.lax.reduce(x, 0, op, (0,))\n"
    assert rules_of(lint_source(bad, "x.py")) == ["SL010"]
    good = "import jax.numpy as jnp\nout = jnp.sum(x)\n"
    assert lint_source(good, "x.py") == []


def test_sl011_bare_device_put():
    bad = "import jax\ny = jax.device_put(x, dev)\n"
    assert rules_of(lint_source(bad, "x.py")) == ["SL011"]
    # ... except inside the guard's own definition
    good = ("def checked_device_put(x, t):\n"
            "    import jax\n    return jax.device_put(x, t)\n")
    assert lint_source(good, "x.py") == []


def test_sl012_host_sync_inside_traced_fn():
    bad = ("import jax\nimport numpy as np\n"
           "def f(x):\n    return np.asarray(x) + 1\n"
           "g = jax.jit(f)\n")
    assert "SL012" in rules_of(lint_source(bad, "x.py"))
    bad2 = ("import jax\n"
            "def f(x):\n    x.block_until_ready()\n    return x\n"
            "g = jax.jit(f)\n")
    assert "SL012" in rules_of(lint_source(bad2, "x.py"))
    # the same calls OUTSIDE traced functions are fine
    good = ("import numpy as np\n"
            "def h(x):\n    return np.asarray(x)\n")
    assert lint_source(good, "x.py") == []


def test_sl013_pallas_call_home_and_interpret():
    """ISSUE 13: pl.pallas_call lives only in mpitest_tpu/ops/, and the
    entry point around it must expose an `interpret=` parameter so the
    CPU parity gates can drive every kernel."""
    call = ("from jax.experimental import pallas as pl\n"
            "def launch(x: object, interpret: bool = False) -> object:\n"
            "    return pl.pallas_call(lambda r, o: None,\n"
            "                          interpret=interpret)(x)\n")
    # outside ops/: flagged wherever it sits
    assert rules_of(lint_source(call, "mpitest_tpu/models/x.py")) == ["SL013"]
    assert rules_of(lint_source(call, "bench/x.py")) == ["SL013"]
    # in ops/ with an interpret= entry-point parameter: clean
    assert lint_source(call, "mpitest_tpu/ops/x.py") == []
    # in ops/ but the entry point cannot be driven in interpret mode
    no_interp = ("from jax.experimental import pallas as pl\n"
                 "def launch(x):\n"
                 "    return pl.pallas_call(lambda r, o: None)(x)\n")
    assert rules_of(lint_source(no_interp, "mpitest_tpu/ops/x.py")) == \
        ["SL013"]
    # nested launcher inherits the outer entry point's parameter
    nested = ("from jax.experimental import pallas as pl\n"
              "def outer(x, interpret=False):\n"
              "    def inner(y):\n"
              "        return pl.pallas_call(lambda r, o: None,\n"
              "                              interpret=interpret)(y)\n"
              "    return inner(x)\n")
    assert lint_source(nested, "mpitest_tpu/ops/x.py") == []


def test_sl014_spill_file_fence():
    """ISSUE 15: run-file reads/writes live only in store/runs.py —
    ad-hoc open()/np.memmap of a spill artifact bypasses the SORTBIN1
    framing checks and the fingerprint sidecar fold."""
    lit = 'def f() -> None:\n    open("/tmp/spill/r0.run", "rb")\n'
    assert rules_of(lint_source(lit, "mpitest_tpu/serve/x.py")) == \
        ["SL014"]
    fstr = ('def f(d: str) -> None:\n'
            '    open(f"{d}/part.fpr.json")\n')
    assert rules_of(lint_source(fstr, "bench/x.py")) == ["SL014"]
    mm = ('import numpy as np\n'
          'def f(info: object) -> None:\n'
          '    np.memmap(info.pay_path, dtype=np.uint8)\n')
    assert rules_of(lint_source(mm, "mpitest_tpu/store/external.py")) \
        == ["SL014"]
    # the home module is exempt — it IS the fence
    assert lint_source(lit, "mpitest_tpu/store/runs.py") == []
    # unrelated open() stays legal everywhere
    ok = 'def f() -> None:\n    open("/tmp/keys.bin", "rb")\n'
    assert lint_source(ok, "mpitest_tpu/serve/x.py") == []


def test_sl014_manifest_journal_fence():
    """ISSUE 18: spill-manifest journals (.mfst) are fenced into
    store/manifest.py — the commit protocol (atomic begin, fsync'd
    appends, torn-tail replay) lives there, and runs.py is NOT a valid
    home for them either."""
    lit = 'def f() -> None:\n    open("/spill/ds1.mfst", "ab")\n'
    assert rules_of(lint_source(lit, "mpitest_tpu/serve/x.py")) == \
        ["SL014"]
    # runs.py is the RUN home, not the manifest home
    assert rules_of(lint_source(lit, "mpitest_tpu/store/runs.py")) == \
        ["SL014"]
    # the manifest home is exempt for .mfst ...
    assert lint_source(lit, "mpitest_tpu/store/manifest.py") == []
    # ... but not for run files
    run_open = 'def f() -> None:\n    open("/spill/r0.run", "rb")\n'
    assert rules_of(lint_source(
        run_open, "mpitest_tpu/store/manifest.py")) == ["SL014"]


def test_sl014_spill_rename_needs_replace():
    """ISSUE 18: publishing a spill artifact with os.rename (instead
    of os.replace) is a finding ANYWHERE, home modules included — the
    durable-commit protocol is replace + fsync(dir)."""
    bad = ('import os\n'
           'def f(d: str) -> None:\n'
           '    os.rename(f"{d}/r0.run.tmp", f"{d}/r0.run")\n')
    assert rules_of(lint_source(bad, "mpitest_tpu/store/runs.py")) == \
        ["SL014"]
    bad_m = ('import os\n'
             'def f(d: str) -> None:\n'
             '    os.rename(f"{d}/a.mfst.tmp", f"{d}/a.mfst")\n')
    assert rules_of(lint_source(
        bad_m, "mpitest_tpu/store/manifest.py")) == ["SL014"]
    # os.replace is the blessed publish; non-spill renames stay legal
    ok = ('import os\n'
          'def f(d: str) -> None:\n'
          '    os.replace(f"{d}/r0.run.tmp", f"{d}/r0.run")\n'
          '    os.rename(f"{d}/log.txt", f"{d}/log.old")\n')
    assert lint_source(ok, "mpitest_tpu/store/runs.py") == []


def test_sl040_typed_core_annotations():
    bad = "def f(x):\n    return x\n"
    path = "mpitest_tpu/models/newmod.py"
    assert rules_of(lint_source(bad, path)) == ["SL040"]
    good = "def f(x: int) -> int:\n    return x\n"
    assert lint_source(good, path) == []
    # nested defs (jit bodies) are exempt by design
    nested = ("def outer() -> object:\n"
              "    def f(x):\n        return x\n    return f\n")
    assert lint_source(nested, path) == []
    # ...and the same file outside the typed core is untouched
    assert lint_source(bad, "bench/newprobe.py") == []


# ------------------------------------------------------------- dogfood

def test_repo_lints_clean():
    """The acceptance gate, as a test: 0 findings over the whole repo.
    Pure ast — this is the expensive-looking assertion that actually
    runs in ~a second."""
    findings = lint_repo(REPO)
    assert findings == [], "\n".join(f.render() for f in findings)
    assert len(RULES) >= 10
    assert LINT_VERSION.startswith("sortlint.")


# ------------------------------------------------------ parity checker

def test_comm_parity_clean_and_catches_rank_conditional(tmp_path):
    assert comm_parity.main() == 0
    bad = tmp_path / "bad_sorter.c"
    bad.write_text(
        "void run(comm_ctx *c) {\n"
        "    int rank = comm_rank(c);\n"
        "    if (rank == 0) {\n"
        "        comm_barrier(c);\n"
        "    }\n"
        "}\n")
    findings = comm_parity.check_rank_conditional_collectives(bad)
    assert findings and "comm_barrier" in findings[0]
    ok = tmp_path / "ok_sorter.c"
    ok.write_text(
        "void run(comm_ctx *c) {\n"
        "    comm_barrier(c);\n"
        "    if (rank == 0) { printf(\"root\\n\"); }\n"
        "}\n")
    assert comm_parity.check_rank_conditional_collectives(ok) == []


def test_comm_parity_sequences_cover_both_sorters():
    seq_r = comm_parity.collective_sequence(REPO / "native" / "radix_sort.c")
    seq_s = comm_parity.collective_sequence(REPO / "native" / "sample_sort.c")
    assert seq_r[0] == "comm_bcast" and "comm_gatherv" in seq_r
    assert "comm_alltoallv" in seq_s


# ------------------------------------------------------- knob registry

def test_knob_registry_validation_contracts(monkeypatch):
    monkeypatch.setenv("SORT_MAX_RETRIES", "-1")
    with pytest.raises(ValueError, match="SORT_MAX_RETRIES"):
        knobs.get("SORT_MAX_RETRIES")
    monkeypatch.setenv("SORT_CAP_FACTOR", "nan")
    with pytest.raises(ValueError, match="finite number > 0"):
        knobs.get("SORT_CAP_FACTOR")
    monkeypatch.setenv("SORT_FALLBACK", "yes")
    with pytest.raises(ValueError, match="SORT_FALLBACK"):
        knobs.get("SORT_FALLBACK")
    monkeypatch.delenv("SORT_FALLBACK")
    assert knobs.get("SORT_FALLBACK") is True
    monkeypatch.setenv("BENCH_PLATFORM", "gpu:2")
    with pytest.raises(ValueError, match="cpu"):
        knobs.get("BENCH_PLATFORM")
    monkeypatch.setenv("BENCH_PLATFORM", "cpu:4")
    assert knobs.get("BENCH_PLATFORM") == 4
    # unregistered names are a hard error, not a silent None
    with pytest.raises(KeyError):
        knobs.get("SORT_NOT_A_KNOB")
    with pytest.raises(KeyError):
        knobs.get_raw("SORT_NOT_A_KNOB")


def test_knob_scoped_env_restores(monkeypatch):
    monkeypatch.setenv("SORT_ALGO", "radix")
    with knobs.scoped_env(SORT_ALGO="sample", SORT_RANKS="4"):
        assert knobs.get("SORT_ALGO") == "sample"
        assert knobs.get("SORT_RANKS") == 4
    assert knobs.get("SORT_ALGO") == "radix"
    assert knobs.get("SORT_RANKS") is None
    with knobs.scoped_env(SORT_ALGO=None):
        assert knobs.get("SORT_ALGO") == "sample"  # default when unset
    assert knobs.get("SORT_ALGO") == "radix"


def test_knob_reference_table_matches_readme():
    """README embeds the GENERATED table — drift fails here and in
    sortlint SL031."""
    table = knobs.reference_table()
    readme = (REPO / "README.md").read_text()
    for k in knobs.iter_knobs():
        assert f"`{k.name}`" in table
        assert f"`{k.name}`" in readme
    # the embedded block is byte-identical to the generator's output
    assert table in readme


def test_knob_cli_prints_table():
    out = subprocess.run(
        [sys.executable, "-m", "mpitest_tpu.utils.knobs"],
        capture_output=True, text=True, cwd=REPO)
    assert out.returncode == 0
    assert "| `SORT_ALGO` |" in out.stdout


# ---------------------------------------------------------- span schema

def test_span_schema_registry():
    assert span_schema.is_registered("sort")
    assert span_schema.is_registered("phase:verify")
    assert span_schema.is_registered("ingest.transfer")
    assert not span_schema.is_registered("phase:warp")
    assert not span_schema.is_registered("made_up")
    assert set(span_schema.INGEST_HOST_STAGES) <= set(span_schema.SPAN_NAMES)
    # every registered name carries a nonempty doc
    assert all(doc for doc in span_schema.SPAN_NAMES.values())


def test_report_flags_unregistered_span(tmp_path):
    from mpitest_tpu import report

    f = tmp_path / "t.jsonl"
    f.write_text('{"v": "span.v1", "name": "mystery", "id": 0, '
                 '"parent": null, "t0": 0.0, "dt": 0.1, "attrs": {}}\n')
    assert report.main(["--check", str(f)]) == 0
    assert report.main(["--check", "--require-registered-spans",
                        str(f)]) == 1


# ------------------------------------------------------ tooling state

def test_bench_row_tooling_state():
    import bench

    t = bench.tooling_state()
    assert t["sortlint"] == LINT_VERSION
    assert t["sortlint_rules"] == len(RULES)
    assert "-Wconversion" in t["cwarn"]
    assert "tsan" in t["sanitize"]
