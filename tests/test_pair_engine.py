"""The 64-bit pair-engine local path (VERDICT r3 #1 — the MSD hybrid).

Runs the real orchestration (``models/api.py::_local_pair_sort``) on a
1-device CPU mesh with the engine forced, so the Pallas pair kernels run
through the interpreter.  Every adaptive route is pinned by its tracer
counter: constant-word shortcut, duplication-sniff reroute, the pair
engine itself, and the residual-run fallback — correctness must hold on
all of them (the sniff is a performance heuristic, never a correctness
gate).
"""

import numpy as np
import pytest

from mpitest_tpu.models.api import sort
from mpitest_tpu.parallel.mesh import make_mesh
from mpitest_tpu.utils.trace import Tracer

N = 15_000  # > MIN_SORT_LOG2 and past the pad break-even (pow2 = 16384)


@pytest.fixture
def mesh1():
    return make_mesh(1)


def _run(x, mesh1, monkeypatch):
    monkeypatch.setenv("SORT_LOCAL_ENGINE", "bitonic")
    tracer = Tracer()
    got = sort(x, algorithm="radix", mesh=mesh1, tracer=tracer)
    np.testing.assert_array_equal(got, np.sort(x))
    return tracer


@pytest.mark.parametrize("dtype", [np.int64, np.uint64])
def test_pair_engine_full_range(dtype, mesh1, rng, monkeypatch):
    info = np.iinfo(np.dtype(dtype))
    x = rng.integers(info.min, info.max, size=N, dtype=dtype, endpoint=True)
    tracer = _run(x, mesh1, monkeypatch)
    assert tracer.counters["local_engine"] == "bitonic_pair"
    assert "pair_residual_fallback" not in tracer.counters


def test_pair_engine_float64_totalorder(mesh1, rng, monkeypatch):
    x = (rng.standard_normal(N) * 10.0 ** rng.integers(-200, 200, N))
    x = x.astype(np.float64)
    x[:4] = [0.0, -0.0, np.inf, -np.inf]
    monkeypatch.setenv("SORT_LOCAL_ENGINE", "bitonic")
    tracer = Tracer()
    got = sort(x, algorithm="radix", mesh=mesh1, tracer=tracer)
    np.testing.assert_array_equal(got, np.sort(x))
    assert tracer.counters["local_engine"] == "bitonic_pair"


def test_narrow_range_collapses_to_one_word(mesh1, rng, monkeypatch):
    """int64 values inside one 32-bit window: the hi word is constant and
    the sort collapses to the 1-word engine on the lo word."""
    x = rng.integers(0, 2**31, size=N, dtype=np.int64)
    tracer = _run(x, mesh1, monkeypatch)
    assert tracer.counters["local_engine"] == "bitonic_1w1"


def test_low_word_constant_collapses(mesh1, rng, monkeypatch):
    """Keys = k * 2^32: lo constant, hi carries all the information."""
    x = rng.integers(0, 2**30, size=N, dtype=np.int64) << 32
    tracer = _run(x, mesh1, monkeypatch)
    assert tracer.counters["local_engine"] == "bitonic_1w0"


def test_all_equal_constant_shortcut(mesh1, monkeypatch):
    x = np.full(N, -(7 << 40), np.int64)
    tracer = _run(x, mesh1, monkeypatch)
    assert tracer.counters["local_engine"] == "constant"


def test_heavy_hi_duplication_reroutes(mesh1, rng, monkeypatch):
    """hi drawn from 8 values: runs ~N/8, the sniff must catch it and
    route straight to lax.sort — no wasted pair phase."""
    hi = rng.integers(0, 8, size=N).astype(np.int64)
    x = (hi << 33) | rng.integers(0, 2**32, size=N).astype(np.int64)
    tracer = _run(x, mesh1, monkeypatch)
    assert tracer.counters["local_engine"] == "lax"
    assert tracer.counters.get("pair_dup_reroute") == 1


def test_mid_runs_in_vmem_fix(mesh1, rng, monkeypatch):
    """Runs of 16 equal-hi keys — the class that used to double-sort via
    the residual fallback now rides the 16-pass in-VMEM fix-up (the
    round-5 mid-tier, priced in bench/fixdepth_probe.py): exact output,
    NO fallback."""
    from mpitest_tpu.models import api

    monkeypatch.setattr(api, "_host_hi_dup_sniff", lambda hi: False)
    n_runs = -(-N // 16)
    hi = np.repeat(np.arange(n_runs, dtype=np.int64) * 37 + 5, 16)[:N]
    x = (hi << 32) | rng.integers(0, 2**32, size=N).astype(np.int64)
    rng.shuffle(x)
    tracer = _run(x, mesh1, monkeypatch)
    assert tracer.counters["local_engine"] == "bitonic_pair"
    assert "pair_residual_fallback" not in tracer.counters


def test_mid_runs_residual_fallback(mesh1, rng, monkeypatch):
    """Runs of 24 equal-hi keys — longer than the 16-pass fix-up covers.
    At test scale the 1024-key sniff could catch this, so the miss is
    forced by stubbing the sniff: the residual flag must fire and the
    fallback must still return exact bytes — correctness must never
    depend on the sniff's sensitivity."""
    from mpitest_tpu.models import api

    monkeypatch.setattr(api, "_host_hi_dup_sniff", lambda hi: False)
    n_runs = -(-N // 24)
    hi = np.repeat(np.arange(n_runs, dtype=np.int64) * 37 + 5, 24)[:N]
    x = (hi << 32) | rng.integers(0, 2**32, size=N).astype(np.int64)
    rng.shuffle(x)  # runs exist in key space, not in input order
    tracer = _run(x, mesh1, monkeypatch)
    assert tracer.counters["local_engine"] == "bitonic_pair"
    assert tracer.counters.get("pair_residual_fallback") == 1


hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402


@settings(max_examples=20, deadline=None)
@given(data=st.data())
def test_property_sort_two_words_contract(data):
    """For ARBITRARY run profiles (run lengths 1..24 — straddling the
    16-pass fix-up threshold both ways — random lo, shuffled input,
    non-power-of-two n): sort_two_words_bitonic either returns the
    exact lexicographic sort with residual=False, or residual=True;
    the pair multiset is preserved in every case, and residual=False
    is GUARANTEED when all runs are <= fix_passes (16).  The
    correctness contract the api fallback relies on."""
    import jax.numpy as jnp

    from mpitest_tpu.ops import bitonic, kernels

    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    n = data.draw(st.integers(300, 3000))
    max_run = data.draw(st.integers(1, 24))
    lens = []
    total = 0
    while total < n:
        l = min(int(rng.integers(1, max_run + 1)), n - total)
        lens.append(l)
        total += l
    hi = np.repeat(
        rng.choice(2**32, size=len(lens), replace=False).astype(np.uint32),
        lens)
    lo = rng.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32)
    perm = rng.permutation(n)
    hi, lo = hi[perm], lo[perm]
    # shrink the engine constants so these sizes run the REAL network
    # (multi-block: cross + merge + run-fix + boundary strips), not the
    # small-n lax shortcut
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(bitonic, "MIN_SORT_LOG2", 8)
        mp.setattr(bitonic, "PAIR_BLOCK_LOG2", 9)
        hs, ls, bad = kernels.sort_two_words_bitonic(
            jnp.asarray(hi), jnp.asarray(lo), interpret=True)
    hs, ls, bad = np.asarray(hs), np.asarray(ls), bool(bad)
    key_in = (hi.astype(np.uint64) << 32) | lo
    key_out = (hs.astype(np.uint64) << 32) | ls
    np.testing.assert_array_equal(np.sort(key_out), np.sort(key_in))
    if max(lens) <= 16:  # the round-5 default fix depth
        assert not bad
    if not bad:
        np.testing.assert_array_equal(key_out, np.sort(key_in))


def test_device_resident_pair_engine(mesh1, rng, monkeypatch):
    """Device-resident int64 input goes through the fused on-device
    encode+range+sniff program (no host round-trip of the keys)."""
    import jax

    monkeypatch.setenv("SORT_LOCAL_ENGINE", "bitonic")
    x = rng.integers(-(2**62), 2**62, size=N, dtype=np.int64)
    with jax.enable_x64(True):
        dev = jax.device_put(x, jax.devices()[0])
        tracer = Tracer()
        got = sort(dev, algorithm="radix", mesh=mesh1, tracer=tracer)
    np.testing.assert_array_equal(got, np.sort(x))
    assert tracer.counters["local_engine"] == "bitonic_pair"
