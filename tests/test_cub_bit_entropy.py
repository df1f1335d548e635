"""CUB's bit-entropy int64 keys (``sortbench/gen/cub_bit_entropy.py``)
and the pair engine they drive on one device.

The generator is checked for what nvbench_helper's keys are: uniform
bits over the type's whole range, and with ``and_draws=k`` each bit 1
with frequency ``2^-k``.  The sort runs the real orchestration on a
1-device CPU mesh with ``SORT_LOCAL_ENGINE=bitonic``, as
``tests/test_pair_engine.py`` does, so the chip's route runs: the raw
keys shipped and split on the device, the hi-word sniff that finds no
tie, the Pallas pair network (interpreted) and its run fix-up, and the
readback of the residual flag with the high-word tie count.
"""

import numpy as np
import pytest

from mpitest_tpu.models.api import sort
from mpitest_tpu.parallel.mesh import make_mesh
from mpitest_tpu.utils.trace import Tracer
from sortbench import harness, spec

CELL = "uniform_i64.1chip"
GEN = spec.load_module(spec.HERE / "gen" / "cub_bit_entropy.py")
REF = spec.load_module(spec.HERE / "reference" / "np_sort.py")
PARAMS = spec.resolve(CELL).config["distribution_params"]
#: the entropy nvbench_helper names for 1 to 5 AND-ed draws
ENTROPY = {1: "1.000", 2: "0.811", 3: "0.544", 4: "0.337", 5: "0.201"}


def _pool(seed, keys):
    return harness.make_pool(spec.resolve(CELL), seed, keys)


def _hi_ties(x: np.ndarray) -> int:
    """NumPy's count of equal adjacent high words of the sorted keys."""
    hi = np.sort(x) >> 32
    return int(np.count_nonzero(hi[1:] == hi[:-1]))


def test_config_is_cub_full_entropy_int64():
    cfg = spec.resolve(CELL).config
    assert (cfg["dtype"], cfg["keys_per_call"]) == ("int64", 1 << 28)
    assert PARAMS == {"entropy": "1.000", "and_draws": 1}
    for part in ("radix_sort/keys.cu", "T{ct}=int64_t", "Entropy=1.000",
                 "Elements{io}=2^28"):
        assert part in cfg["source"], part


@pytest.mark.parametrize("seed", [0, -7, 2**31 + 9, 2**70])
def test_same_seed_same_pool(seed):
    a = _pool(seed, (1 << 20) + 3)
    b = _pool(seed, (1 << 20) + 3)
    assert all(np.array_equal(u, v) for u, v in zip(a, b))
    assert not np.array_equal(a[0], a[1])
    assert not np.array_equal(a[0], _pool(seed + 1, (1 << 20) + 3)[0])


@pytest.mark.parametrize("k", [1, 3])
def test_keys_independent_of_chunking(k, monkeypatch):
    """Each key depends on its own draws only: a shorter chunk of one
    stream is the prefix of a longer one, two chunks drawn in turn are
    one chunk, and one thread draws the pool the harness draws in many."""
    p = {"entropy": ENTROPY[k], "and_draws": k}
    long = GEN.generate(np.random.default_rng(11), 1 << 17, "int64", p)
    rng = np.random.default_rng(11)
    short = GEN.generate(rng, 1 << 15, "int64", p)
    rest = GEN.generate(rng, (1 << 17) - (1 << 15), "int64", p)
    np.testing.assert_array_equal(np.concatenate([short, rest]), long)
    many = _pool(2**40 + 1, (1 << 20) + 7)
    monkeypatch.setattr(harness.os, "cpu_count", lambda: 1)
    one = _pool(2**40 + 1, (1 << 20) + 7)
    assert all(np.array_equal(u, v) for u, v in zip(many, one))


def test_keys_cover_the_whole_int64_range():
    """Entropy 1.000: uniform over all of int64, so about half the keys
    are negative and both ends of the range are reached to 2^-8."""
    x = np.concatenate(_pool(2**33 + 5, 1 << 20))
    assert x.dtype == np.int64
    assert abs(np.count_nonzero(x < 0) / x.size - 0.5) < 0.005
    assert x.min() < np.iinfo(np.int64).min + 2**55
    assert x.max() > np.iinfo(np.int64).max - 2**55


@pytest.mark.parametrize("k", [1, 2, 3, 4, 5])
def test_each_bit_is_one_with_frequency_two_to_minus_k(k):
    """``and_draws=k``: each of the 64 bits is 1 in a share 2^-k of the
    keys, within 6 binomial standard deviations (a false alarm about 1
    in 10^7 a bit)."""
    n = 1 << 18
    x = GEN.generate(np.random.default_rng(2**32 + k), n, "int64",
                     {"entropy": ENTROPY[k], "and_draws": k})
    u = x.view(np.uint64)
    ones = np.array([np.count_nonzero((u >> np.uint64(b)) & np.uint64(1))
                     for b in range(64)])
    p = 2.0 ** -k
    tol = 6 * np.sqrt(n * p * (1 - p))
    assert np.all(np.abs(ones - n * p) < tol), (ones.min(), ones.max())


def test_entropy_and_draws_must_agree():
    with pytest.raises(ValueError, match="entropy"):
        GEN.generate(np.random.default_rng(0), 8, "int64",
                     {"entropy": "1.000", "and_draws": 5})
    with pytest.raises(ValueError, match="integers"):
        GEN.generate(np.random.default_rng(0), 8, "float64", PARAMS)


def test_np_sort_control_mismatches_on_cub_keys():
    x = _pool(3, 1 << 16)[0]
    assert np.count_nonzero(REF.control(x) != REF.reference(x)) > 0


@pytest.fixture(scope="module", params=[14, 17], ids=["2^14", "2^17"])
def cub_sorted(request):
    """One sort of CUB keys on a 1-device mesh: (input, output, tracer)."""
    x = _pool(2**32 + request.param, 1 << request.param)[0]
    tracer = Tracer()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("SORT_LOCAL_ENGINE", "bitonic")
        got = sort(x, algorithm="radix", mesh=make_mesh(1), tracer=tracer)
    return x, got, tracer


def test_cub_sort_equals_np_sort(cub_sorted):
    x, got, _ = cub_sorted
    assert got.dtype == np.int64
    np.testing.assert_array_equal(got, np.sort(x))


def test_cub_sort_takes_the_pair_engine(cub_sorted):
    x, _, tracer = cub_sorted
    c = tracer.counters
    assert c["local_engine"] == "bitonic_pair"
    assert c["pair_sniff_dups"] == 0
    assert c["encode_site"] == "device"
    assert c["pair_hi_ties"] == _hi_ties(x)
    assert not [k for k in c if "fallback" in k or "degraded" in k
                or k == "pair_dup_reroute"]


def test_pair_residual_span_nests_in_phase_sort(cub_sorted):
    spans = cub_sorted[2].spans.spans
    by_id = {s.id: s for s in spans}
    mine = [s for s in spans if s.name == "pair.residual"]
    assert len(mine) == 1
    assert by_id[mine[0].parent].name == "phase:sort"


@pytest.fixture
def blind_sniff(monkeypatch):
    """A sniff that samples one key, so it never sees a duplicate (the
    device split takes its sample as a trace-time constant, hence a
    fresh compile before and after)."""
    from mpitest_tpu.models import local

    monkeypatch.setattr(local, "_sniff_indices",
                        lambda n: np.zeros(1, np.int64))
    local._compile_pair_split.cache_clear()
    yield
    local._compile_pair_split.cache_clear()


@pytest.mark.parametrize("run", [2, 5, 16])
def test_hi_ties_count_planted_runs(run, blind_sniff, monkeypatch):
    """Equal-hi runs of ``run`` keys, up to the fix-up depth of 16, that
    the blinded sniff misses: the pair engine sorts them exactly, and
    ``pair_hi_ties`` is NumPy's count, ``run - 1`` a run."""
    n = 1 << 14
    rng = np.random.default_rng(run)
    hi = np.repeat(rng.choice(2**31, n // run + 1, replace=False), run)[:n]
    x = (hi.astype(np.int64) << 32) | rng.integers(0, 2**32, n)
    rng.shuffle(x)
    monkeypatch.setenv("SORT_LOCAL_ENGINE", "bitonic")
    tracer = Tracer()
    got = sort(x, algorithm="radix", mesh=make_mesh(1), tracer=tracer)
    np.testing.assert_array_equal(got, np.sort(x))
    c = tracer.counters
    assert c["local_engine"] == "bitonic_pair"
    assert c["pair_hi_ties"] == _hi_ties(x) > 0
    assert not [k for k in c if "fallback" in k]
