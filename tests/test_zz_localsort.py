"""Local-sort engine tests: the SORT_LOCAL_ENGINE knob, its resolution
per dispatch, the engine in the plan record, and the planner's
key-width compaction of the radix pass count.  Named ``test_zz_*`` to
sort late: the mesh cells compile shard_map programs on the mesh8
fixture.
"""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

import jax

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from mpitest_tpu.models.api import sort  # noqa: E402
from mpitest_tpu.models.local import _resolve_local_engine  # noqa: E402
from mpitest_tpu.utils import knobs  # noqa: E402
from mpitest_tpu.utils.trace import Tracer  # noqa: E402


# ------------------------------------------------------- knob contract

def test_local_engine_knob_validation():
    """SORT_LOCAL_ENGINE is registered, typed, and fail-fast."""
    with knobs.scoped_env(SORT_LOCAL_ENGINE="warp9"):
        with pytest.raises(knobs.KnobError, match="SORT_LOCAL_ENGINE"):
            knobs.get("SORT_LOCAL_ENGINE")
    for ok in ("auto", "bitonic", "lax"):
        with knobs.scoped_env(SORT_LOCAL_ENGINE=ok):
            assert knobs.get("SORT_LOCAL_ENGINE") == ok
    assert knobs.get("SORT_LOCAL_ENGINE") == "auto"  # default


def test_local_engine_knob_fail_fast_in_cli_and_server():
    """Both drivers validate the knob at startup (same contract as the
    exchange engine: garbage -> one [ERROR] line + rc != 0)."""
    from drivers import sort_cli

    with knobs.scoped_env(SORT_LOCAL_ENGINE="warp9"):
        rc = sort_cli.main(["sort_cli.py", "/nonexistent-but-knobs-first"])
        assert rc != 0
    server_src = (REPO / "drivers" / "sort_server.py").read_text()
    assert '"SORT_LOCAL_ENGINE"' in server_src
    cli_src = (REPO / "drivers" / "sort_cli.py").read_text()
    assert '"SORT_LOCAL_ENGINE"' in cli_src


def test_local_engine_resolution_on_cpu():
    """Off-TPU, auto and lax resolve to lax and a forced bitonic to its
    interpreter form; keys wider than two words always keep lax."""
    for n in (64, 4096, 1 << 18):
        assert _resolve_local_engine("auto", 2, n) == "lax"
    assert _resolve_local_engine("lax", 2, 4096) == "lax"
    assert _resolve_local_engine("bitonic", 1, 4096) == "bitonic_interpret"
    assert _resolve_local_engine("bitonic", 3, 4096) == "lax"


# ------------------------------------------------------ plan provenance

def test_plan_actual_carries_local_engine_and_backend(mesh8, rng):
    """The engine decision's actual record names the resolved local
    engine AND the backend: sample sort's per-shard sorts on the forced
    bitonic engine."""
    x = rng.integers(0, 1 << 16, size=1 << 12, dtype=np.int32)
    t = Tracer()
    with knobs.scoped_env(SORT_LOCAL_ENGINE="bitonic"):
        out = sort(x, algorithm="sample", mesh=mesh8, tracer=t)
    np.testing.assert_array_equal(out, np.sort(x))
    a = t.plan.decisions["engine"].actual
    assert a.get("local_engine") == "bitonic_interpret"
    assert a.get("backend") == str(jax.default_backend())


# ------------------------------------------------- planner compaction

def test_profile_reports_key_width(rng):
    from mpitest_tpu.models import plan as plan_mod

    narrow = rng.integers(0, 1 << 20, size=4096, dtype=np.int64)
    prof = plan_mod.profile_host_array(narrow)
    assert 0 < prof["key_width"] <= 20
    floats = rng.normal(size=4096).astype(np.float32)
    assert "key_width" not in plan_mod.profile_host_array(floats)


def test_planner_chooses_radix_compact_for_narrow_keys():
    from mpitest_tpu.models import planner

    prof = {"key_width": 20, "sortedness": 0.5, "dup_ratio": 0.1}
    c = planner.choose(prof, "radix", verify_on=True)
    assert c.policy == "radix_compact" and c.trigger == "range_narrow"
    # prediction mirrors the auto digit-width rule: min over 8/16-bit
    assert c.predicted["passes"] == 2  # ceil(20/16) beats ceil(20/8)
    assert c.algo is None  # requested radix: the reroute is a no-op
    c = planner.choose(dict(prof, key_width=9), "sample", verify_on=True)
    assert c.predicted["passes"] == 1 and c.algo == "radix"
    # wide or constant keys never compact
    for w in (0, 21, 64):
        assert planner.choose(dict(prof, key_width=w), "radix",
                              verify_on=True).policy != "radix_compact"
    # earlier policies keep priority: a sorted profile is passthrough
    c = planner.choose({"key_width": 12, "sortedness": 1.0},
                       "radix", verify_on=True)
    assert c.policy == "verify_passthrough"


def test_planner_passes_prediction_regret(mesh8, rng):
    """Honest narrow profile: predicted pass count == ran, regret 0."""
    x = rng.integers(0, 1 << 20, size=1 << 13, dtype=np.int64)
    t = Tracer()
    with knobs.scoped_env(SORT_PLANNER="on"):
        out = sort(x, algorithm="radix", mesh=mesh8, tracer=t)
    assert out.tobytes() == np.sort(x).tobytes()
    d = t.plan.decisions["passes"]
    assert d.trigger == "planner"
    assert int(d.predicted["passes"]) == int(d.chosen)
    assert d.regret == 0.0


def test_planner_lying_profile_stamps_passes_regret(mesh8, rng):
    """A profile that under-reports the key width promises too few
    passes — the 'passes' decision prices the lie as relative regret."""
    from mpitest_tpu.models import plan as plan_mod

    x = rng.integers(-(2**62), 2**62, size=1 << 13, dtype=np.int64)
    orig = plan_mod.profile_host_array

    def lying(arr, *a, **kw):
        out = dict(orig(arr, *a, **kw))
        out["key_width"] = 18  # the lie: true width is ~63 bits
        return out

    plan_mod.profile_host_array = lying
    try:
        t = Tracer()
        with knobs.scoped_env(SORT_PLANNER="on"):
            out = sort(x, algorithm="radix", mesh=mesh8, tracer=t)
    finally:
        plan_mod.profile_host_array = orig
    assert out.tobytes() == np.sort(x).tobytes()
    d = t.plan.decisions["passes"]
    assert d.trigger == "planner" and (d.regret or 0.0) > 0.0
