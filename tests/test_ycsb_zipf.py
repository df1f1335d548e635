"""YCSB zipfian int64 keys (``sortbench/gen/ycsb_scrambled_zipfian.py``)
and the one-device 64-bit host route they drive.

The generator is checked against a plain scalar port of YCSB's
``Utils.fnvhash64`` loop and against the skew YCSB's constants imply
(the hottest key holds 1/ZETAN of the draws).  The sort runs the real
orchestration on a 1-device CPU mesh with ``SORT_LOCAL_ENGINE=bitonic``,
as ``tests/test_pair_engine.py`` does, so the same route as on the chip
runs: the raw keys shipped and split into their two words on the
device, the hi-word duplicate sniff, and on skewed keys the reroute to
the variadic ``lax.sort``.
"""

import numpy as np
import pytest

from mpitest_tpu.models.api import sort
from mpitest_tpu.parallel.mesh import make_mesh
from mpitest_tpu.utils.trace import Tracer
from sortbench import harness, spec

CELL = "ycsb_zipf_i64.1chip"
GEN = spec.load_module(spec.HERE / "gen" / "ycsb_scrambled_zipfian.py")
REF = spec.load_module(spec.HERE / "reference" / "np_sort.py")
PARAMS = spec.resolve(CELL).config["distribution_params"]
MASK64 = (1 << 64) - 1


def _fnvhash64_scalar(val: int) -> int:
    """YCSB ``Utils.fnvhash64``, one long at a time, in Java's arithmetic."""
    hashval = 0xCBF29CE484222325
    for _ in range(8):
        octet = val & 0xFF
        val >>= 8
        hashval ^= octet
        hashval = (hashval * 1099511628211) & MASK64
    signed = hashval - (1 << 64) if hashval >> 63 else hashval
    return signed if signed == -(1 << 63) else abs(signed)  # Math.abs


def _pool(seed, keys):
    return harness.make_pool(spec.resolve(CELL), seed, keys)


def test_fnvhash64_matches_ycsb_loop():
    vals = list(range(300)) + [10**9, 10**10, 2**62 + 12345, 2**63 - 1, -1,
                               -(2**63)]
    got = GEN.fnvhash64(np.array(vals, dtype=np.int64))
    assert got.dtype == np.int64
    assert got.tolist() == [_fnvhash64_scalar(v) for v in vals]


def test_keys_are_nonnegative_int64():
    x = np.concatenate(_pool(2**33 + 5, 1 << 20))
    assert x.dtype == np.int64
    assert x.min() >= 0
    # hashed keys spread over the whole positive range
    assert x.max() > 2**62


def test_hottest_key_share():
    """Rank 0 takes 1/ZETAN = 3.78 % of the draws; its key is the hash
    of its scrambled key number."""
    x = _pool(2**31 + 9, 1 << 20)[0]
    keys, counts = np.unique(x, return_counts=True)
    assert counts.max() / x.size == pytest.approx(1 / PARAMS["zetan"],
                                                  abs=0.003)
    keynum0 = _fnvhash64_scalar(0) % (PARAMS["recordcount"] + 1)
    assert keys[np.argmax(counts)] == _fnvhash64_scalar(keynum0)


@pytest.mark.parametrize("seed", [0, -7, 2**31 + 9, 2**70])
def test_same_seed_same_pool(seed):
    a = _pool(seed, (1 << 20) + 3)
    b = _pool(seed, (1 << 20) + 3)
    assert all(np.array_equal(u, v) for u, v in zip(a, b))
    assert not np.array_equal(a[0], a[1])
    assert not np.array_equal(a[0], _pool(seed + 1, (1 << 20) + 3)[0])


def test_keys_independent_of_chunking(monkeypatch):
    """Each key depends on its own draws only: a shorter chunk of one
    stream is the prefix of a longer one, and one thread draws the pool
    the harness draws in many."""
    long = GEN.generate(np.random.default_rng(11), 1 << 17, "int64", PARAMS)
    short = GEN.generate(np.random.default_rng(11), 1 << 15, "int64", PARAMS)
    np.testing.assert_array_equal(short, long[: short.size])
    many = _pool(2**40 + 1, (1 << 20) + 7)
    monkeypatch.setattr(harness.os, "cpu_count", lambda: 1)
    one = _pool(2**40 + 1, (1 << 20) + 7)
    assert all(np.array_equal(u, v) for u, v in zip(many, one))


def test_np_sort_control_mismatches_on_ycsb_keys():
    x = _pool(3, 1 << 16)[0]
    assert np.count_nonzero(REF.control(x) != REF.reference(x)) > 0


@pytest.fixture(scope="module", params=[14, 17], ids=["2^14", "2^17"])
def ycsb_sorted(request):
    """One sort of YCSB keys on a 1-device mesh: (input, output, tracer)."""
    x = _pool(2**32 + request.param, 1 << request.param)[0]
    tracer = Tracer()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("SORT_LOCAL_ENGINE", "bitonic")
        got = sort(x, algorithm="radix", mesh=make_mesh(1), tracer=tracer)
    return x, got, tracer


def test_ycsb_sort_equals_np_sort(ycsb_sorted):
    x, got, _ = ycsb_sorted
    assert got.dtype == np.int64
    np.testing.assert_array_equal(got, np.sort(x))


def test_ycsb_sort_takes_the_dup_reroute(ycsb_sorted):
    c = ycsb_sorted[2].counters
    assert c["local_engine"] == "lax"
    assert c["pair_dup_reroute"] == 1
    assert c["pair_sniff_dups"] > 0
    assert c["encode_site"] == "device"
    assert not [k for k in c if "fallback" in k or "degraded" in k]


def test_ycsb_sort_spans_nest_in_phase_encode(ycsb_sorted):
    spans = ycsb_sorted[2].spans.spans
    by_id = {s.id: s for s in spans}
    for name in ("encode.words", "pair.plan"):
        mine = [s for s in spans if s.name == name]
        assert len(mine) == 1, name
        assert by_id[mine[0].parent].name == "phase:encode", name


def test_uniform_int64_keeps_the_pair_engine(monkeypatch):
    """Full-range uniform int64 at ``test_pair_engine.py``'s size: the
    sniff counts no duplicate and the pair engine sorts."""
    x = np.random.default_rng(1234).integers(
        np.iinfo(np.int64).min, np.iinfo(np.int64).max, size=15_000,
        dtype=np.int64, endpoint=True)
    monkeypatch.setenv("SORT_LOCAL_ENGINE", "bitonic")
    tracer = Tracer()
    got = sort(x, algorithm="radix", mesh=make_mesh(1), tracer=tracer)
    np.testing.assert_array_equal(got, np.sort(x))
    assert tracer.counters["pair_sniff_dups"] == 0
    assert tracer.counters["local_engine"] == "bitonic_pair"
