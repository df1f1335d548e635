"""Exchange-engine tests (ISSUE 13): the Pallas ICI engine's plumbing,
parity, overlap-loop invariants, ladder degradation and provenance.

The remote-DMA kernel itself lowers only on a TPU backend (the Pallas
interpreter cannot simulate cross-device DMA — ``ops/exchange.py``
module docstring); on this CPU mesh the ``pallas_interpret`` engine
runs the fused multi-word pack kernel + the no-dest segment arithmetic
+ all engine plumbing for real, with the rank-to-rank hop on the
bit-identical ``lax.all_to_all``.  Named ``test_zz_*`` to sort late:
the parity cells compile shard_map programs on the mesh8 fixture.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from mpitest_tpu.models.api import (  # noqa: E402
    _resolve_exchange_engine, sort)
from mpitest_tpu.ops import exchange as xeng  # noqa: E402
from mpitest_tpu.utils import knobs  # noqa: E402
from mpitest_tpu.utils.trace import Tracer  # noqa: E402


def _spans(tracer, name):
    return [s for s in tracer.spans.spans if s.name == name]


# ------------------------------------------------------- knob contract

def test_engine_knob_validation():
    """SORT_EXCHANGE_ENGINE is registered, typed, and fail-fast."""
    with knobs.scoped_env(SORT_EXCHANGE_ENGINE="warp9"):
        with pytest.raises(knobs.KnobError, match="SORT_EXCHANGE_ENGINE"):
            knobs.get("SORT_EXCHANGE_ENGINE")
    for ok in ("auto", "lax", "pallas", "pallas_interpret"):
        with knobs.scoped_env(SORT_EXCHANGE_ENGINE=ok):
            assert knobs.get("SORT_EXCHANGE_ENGINE") == ok
    assert knobs.get("SORT_EXCHANGE_ENGINE") == "auto"  # default


def test_engine_knob_fail_fast_in_cli_and_server():
    """Both drivers validate the knob at startup: the CLI maps garbage
    to one [ERROR] line + rc != 0 (in-process, like test_cli), and the
    server's validate() sweep names the knob (test_zz_serve contract —
    the sweep raises the same KnobError before any socket binds)."""
    from drivers import sort_cli

    with knobs.scoped_env(SORT_EXCHANGE_ENGINE="warp9"):
        rc = sort_cli.main(["sort_cli.py", "/nonexistent-but-knobs-first"])
        assert rc != 0
    # the server's startup sweep covers the knob (source-level pin: the
    # sweep is a literal validate() list; spawning a server per knob
    # would pay seconds for the same evidence)
    server_src = (REPO / "drivers" / "sort_server.py").read_text()
    assert '"SORT_EXCHANGE_ENGINE"' in server_src
    cli_src = (REPO / "drivers" / "sort_cli.py").read_text()
    assert '"SORT_EXCHANGE_ENGINE"' in cli_src


def test_engine_resolution_on_cpu():
    """auto = lax off-TPU; a forced pallas runs the interpreter form
    (same convention as the bitonic local engine)."""
    assert _resolve_exchange_engine(None) == "lax"  # auto default, CPU
    assert _resolve_exchange_engine("lax") == "lax"
    assert _resolve_exchange_engine("pallas") == "pallas_interpret"
    assert _resolve_exchange_engine("pallas_interpret") == "pallas_interpret"
    with knobs.scoped_env(SORT_EXCHANGE_ENGINE="pallas"):
        assert _resolve_exchange_engine(None) == "pallas_interpret"
    with pytest.raises(ValueError, match="exchange engine"):
        _resolve_exchange_engine("warp9")


# ------------------------------------------------------- kernel units

def test_block_send_segments_matches_searchsorted():
    """The no-dest clip-arithmetic segments equal the lax engine's
    searchsorted-over-dest form, bit for bit, on random histograms."""
    from mpitest_tpu.parallel.collectives import block_send_segments

    rng = np.random.default_rng(5)
    for _ in range(20):
        P, bins = int(rng.integers(2, 9)), int(rng.integers(2, 33))
        n = int(rng.integers(1, 257))
        h = rng.multinomial(n, np.ones(bins) / bins).astype(np.int32)
        # a valid global arrangement: base[d] = my run start for digit d
        # (any non-decreasing assignment with room for h works)
        gaps = rng.integers(0, 4, size=bins)
        base = np.cumsum(np.concatenate([[0], (h + gaps)[:-1]])).astype(
            np.int32)
        n_total = int(base[-1] + h[-1] + rng.integers(0, 4))
        # reference: materialize dest per element, searchsorted
        dest = np.concatenate(
            [base[d] + np.arange(h[d]) for d in range(bins)]).astype(
                np.int64)
        dest.sort()
        shard = max(1, -(-n_total // P))
        bounds = np.arange(P + 1) * shard
        cum_ref = np.searchsorted(dest, bounds, side="left")
        start, cnt = block_send_segments(
            jnp.asarray(h), jnp.asarray(base), shard, P)
        np.testing.assert_array_equal(np.asarray(start), cum_ref[:-1])
        np.testing.assert_array_equal(np.asarray(cnt), np.diff(cum_ref))


def test_fused_pass_pack_matches_xla_spread():
    """The fused multi-word pack kernel (interpret) produces the exact
    send matrices the XLA scatter spread builds — both word planes,
    fills included."""
    rng = np.random.default_rng(9)
    P, cap, n = 4, 2048, 1500
    hi = rng.integers(0, 2**32, n, dtype=np.uint32)
    lo = rng.integers(0, 2**32, n, dtype=np.uint32)
    cuts = np.sort(rng.integers(0, n, size=P - 1))
    starts = np.concatenate([[0], cuts]).astype(np.int32)
    ends = np.concatenate([cuts, [n]]).astype(np.int32)
    cnts = (ends - starts).astype(np.int32)
    fills = (0xFFFFFFFF, 0)

    outs = xeng.fused_pass_pack(
        (jnp.asarray(hi), jnp.asarray(lo)), jnp.asarray(starts),
        jnp.asarray(cnts), cap, P, fills=fills, interpret=True)
    for a, fill, out in zip((hi, lo), fills, outs):
        want = np.full((P, cap), fill, np.uint32)
        for p in range(P):
            c = min(int(cnts[p]), cap)
            want[p, :c] = a[starts[p]:starts[p] + c]
        np.testing.assert_array_equal(np.asarray(out), want)


def test_remote_a2a_interpret_contract(mesh8):
    """Under interpret the transport is lax.all_to_all — pin the
    recv[s] = row-sent-by-s contract on the virtual mesh."""
    from jax.sharding import PartitionSpec as P

    from mpitest_tpu.parallel.mesh import AXIS

    n_ranks, cap = 8, 1024
    x = jnp.arange(n_ranks * n_ranks * cap, dtype=jnp.uint32).reshape(
        n_ranks * n_ranks, cap)

    def f(block):
        return xeng.remote_a2a(block, n_ranks, AXIS, interpret=True)

    out = jax.jit(jax.shard_map(
        f, mesh=mesh8, in_specs=P(AXIS), out_specs=P(AXIS),
        check_vma=False))(x)
    got = np.asarray(out).reshape(n_ranks, n_ranks, cap)
    ref = np.asarray(x).reshape(n_ranks, n_ranks, cap)
    for me in range(n_ranks):
        for s in range(n_ranks):
            np.testing.assert_array_equal(got[me, s], ref[s, me])


# ------------------------------------------------- parity on the mesh

@pytest.mark.parametrize("dtype", [np.int32, np.uint64, np.float32])
@pytest.mark.parametrize("algo", ["radix", "sample"])
def test_lax_vs_interpret_parity_mesh8(algo, dtype, mesh8, rng):
    """Bit-identical output across the engine knob, both algorithms,
    1- and 2-word codecs and the float totalOrder codec."""
    if np.dtype(dtype).kind == "f":
        x = rng.normal(size=1 << 12).astype(dtype)
    else:
        info = np.iinfo(dtype)
        x = rng.integers(info.min, info.max, size=1 << 12,
                         dtype=dtype, endpoint=True)
    # SORT_FALLBACK=0 pins each engine: without it a broken pallas path
    # would silently degrade to lax and the byte comparison would pass
    # vacuously (lax vs lax).
    with knobs.scoped_env(SORT_FALLBACK="0"):
        a = sort(x, algorithm=algo, mesh=mesh8, exchange_engine="lax")
        t = Tracer()
        b = sort(x, algorithm=algo, mesh=mesh8,
                 exchange_engine="pallas_interpret", tracer=t)
    assert t.counters["exchange_engine"] == "pallas_interpret"
    assert "exchange_engine_degraded" not in t.counters
    assert a.dtype == b.dtype == np.dtype(dtype)
    assert a.tobytes() == b.tobytes()


def test_overlap_loop_pass_count_invariants(mesh8, rng):
    """The pallas overlap loop runs EXACTLY the lax engine's pass
    structure: same pass count, one exchange per pass, one overlap-hook
    slot plane per exchange — and the trace carries the engine."""
    x = rng.integers(-2**31, 2**31 - 1, size=1 << 12, dtype=np.int32)
    results = {}
    for eng in ("lax", "pallas_interpret"):
        t = Tracer()
        with knobs.scoped_env(SORT_FALLBACK="0"):  # pin: no silent degrade
            results[eng] = sort(x, algorithm="radix", mesh=mesh8,
                                digit_bits=8, exchange_engine=eng,
                                tracer=t)
        assert "exchange_engine_degraded" not in t.counters
        passes = _spans(t, "radix_pass")
        a2a = _spans(t, "ragged_all_to_all")
        assert len(passes) == 4  # full-range int32 at 8-bit digits
        assert len(a2a) == len(passes)  # one exchange per pass, no extras
        for e in a2a:
            assert e.attrs["engine"] == eng
        if eng != "lax":
            # the engine owns the pack on the pallas path
            assert all(e.attrs["pack"] == eng for e in a2a)
    assert results["lax"].tobytes() == results["pallas_interpret"].tobytes()


# ------------------------------------------- ladder + plan provenance

def test_ladder_degrades_pallas_to_lax_verified(mesh8, rng):
    """A pallas engine failure re-runs the SAME algorithm on the lax
    rung; the result is fingerprint-verified and the degrade is a plan
    decision + counters, never a silent engine swap.

    The key count is deliberately odd (3333): the injected fault fires
    at TRACE time, so this test must miss every compile-cache entry the
    other cells populated — a cached executable never re-traces and the
    patched transport would never be reached."""
    x = rng.integers(-2**31, 2**31 - 1, size=3333, dtype=np.int32)

    orig = xeng.remote_a2a

    def boom(*a, **kw):
        raise jax.errors.JaxRuntimeError(
            "INTERNAL: injected pallas exchange fault (test)")

    xeng.remote_a2a = boom
    try:
        with knobs.scoped_env(SORT_MAX_RETRIES="0", SORT_FALLBACK="1"):
            t = Tracer()
            out = sort(x, algorithm="radix", mesh=mesh8,
                       exchange_engine="pallas_interpret", tracer=t)
    finally:
        xeng.remote_a2a = orig
    np.testing.assert_array_equal(out, np.sort(x))
    assert t.counters["exchange_engine"] == "lax"
    assert t.counters["exchange_engine_degraded"] == 1
    assert t.counters["verify_runs"] >= 1
    assert "degraded_to" not in t.counters  # same algorithm, engine rung
    d = t.plan.decisions["exchange_engine"]
    assert d.chosen == "lax" and d.trigger == "pallas_fault"
    assert d.regret == 1.0
    assert t.plan.digest()["exchange_engine"] == "lax"


def test_ladder_engine_descent_blames_actual_cause(mesh8, rng):
    """A descent off the pallas rung caused by VERIFICATION failure
    (e.g. a result fault that equally implicates the data) is recorded
    as trigger=verify_failure, not blamed on the kernel."""
    from mpitest_tpu import faults

    x = rng.integers(-2**31, 2**31 - 1, size=4321, dtype=np.int32)
    # result_swap:2 corrupts both verification tries of rung 1, then
    # exhausts — the lax rung runs clean and the ladder ends verified.
    reg = faults.FaultRegistry("result_swap:2")
    faults.install(reg)
    try:
        with knobs.scoped_env(SORT_MAX_RETRIES="0", SORT_FALLBACK="1"):
            t = Tracer()
            out = sort(x, algorithm="radix", mesh=mesh8,
                       exchange_engine="pallas_interpret", tracer=t)
    finally:
        faults.install(None)
    np.testing.assert_array_equal(out, np.sort(x))
    d = t.plan.decisions["exchange_engine"]
    assert d.chosen == "lax" and d.trigger == "verify_failure"
    assert d.regret == 1.0


def test_ladder_pinned_engine_fails_loudly(mesh8, rng):
    """SORT_FALLBACK=0 pins the engine: a pallas failure is a typed
    error, never a silent lax re-run (the bench/selftest contract)."""
    from mpitest_tpu.models.api import SortRetryExhausted

    # odd size: must miss the compile caches (see the test above)
    x = rng.integers(0, 100, size=999, dtype=np.int32)
    orig = xeng.remote_a2a

    def boom(*a, **kw):
        raise jax.errors.JaxRuntimeError("INTERNAL: injected (test)")

    xeng.remote_a2a = boom
    try:
        with knobs.scoped_env(SORT_MAX_RETRIES="0", SORT_FALLBACK="0"):
            with pytest.raises(SortRetryExhausted):
                sort(x, algorithm="radix", mesh=mesh8,
                     exchange_engine="pallas_interpret")
    finally:
        xeng.remote_a2a = orig


def test_balance_event_carries_engine(mesh8, rng):
    """The exchange_balance event (the scale-out table's source) names
    the engine that sized the capacity."""
    x = np.sort(rng.integers(0, 1 << 16, size=1 << 12).astype(np.int32))
    for eng in ("lax", "pallas_interpret"):
        t = Tracer()
        sort(x, algorithm="radix", mesh=mesh8, exchange_engine=eng,
             tracer=t)
        events = _spans(t, "exchange_balance")
        assert events, "negotiated run must emit exchange_balance"
        assert all(e.attrs["exchange_engine"] == eng for e in events)
        assert t.counters["exchange_engine"] == eng


def test_explain_shows_engine_decision(mesh8, rng, tmp_path):
    """`report.py --explain` renders the exchange_engine decision from
    the sort.plan span stream."""
    from mpitest_tpu import report

    trace = tmp_path / "trace.jsonl"
    x = rng.integers(-2**31, 2**31 - 1, size=1 << 12, dtype=np.int32)
    with knobs.scoped_env(SORT_TRACE=str(trace)):
        sort(x, algorithm="radix", mesh=mesh8,
             exchange_engine="pallas_interpret")
    rows = report.load_rows(str(trace))
    view = report.explain_view(rows)
    assert view is not None
    assert "exchange_engine" in view
    assert "chosen=pallas_interpret" in view
