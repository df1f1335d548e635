"""One-device egress: the constant-XOR codecs (int32, uint32, int64,
uint64) decode the sorted words on the device, the verifier checks the
decoded keys in a program of its own, and the fetch hands the caller
those bytes with no host pass.

Everything else keeps the host decode: floats and narrow ints,
``return_result=True`` (the caller gets the words), the planner's
passthrough hit (verified as words), and the distributed paths."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpitest_tpu.models import api, local
from mpitest_tpu.models.supervisor import SortIntegrityError
from mpitest_tpu.ops.keys import DECODE_ROW, codec_for
from mpitest_tpu.parallel.mesh import make_mesh
from mpitest_tpu.utils import knobs
from mpitest_tpu.utils.trace import Tracer

RAW = (np.int32, np.uint32, np.int64, np.uint64)
#: 2-word keys on the pair engine: at least 2^13 (bitonic.MIN_SORT_LOG2)
N_PAIR = 1 << 14


def _edges(dtype) -> np.ndarray:
    """The width's extremes and their neighbours, as ``dtype``."""
    info = np.iinfo(dtype)
    lo, hi = int(info.min), int(info.max)
    mid = 0 if lo < 0 else 1 << (8 * np.dtype(dtype).itemsize - 1)
    return np.array([lo, lo + 1, mid - 1, mid, mid + 1, hi - 1, hi],
                    dtype=object).astype(dtype)


def _keys(dtype, n: int, seed: int = 3) -> np.ndarray:
    """``n`` keys of ``dtype``: the extremes, repeated, then uniform."""
    info = np.iinfo(dtype)
    rng = np.random.default_rng(seed)
    bulk = rng.integers(info.min, info.max, n, dtype=dtype, endpoint=True)
    edges = np.tile(_edges(dtype), 3)
    return np.concatenate([edges, bulk])[:n]


def _sort(x, tr: Tracer, **kw):
    return api.sort(x, mesh=make_mesh(1), tracer=tr, **kw)


@pytest.mark.parametrize("n", [1, 2, 21, 3001, 4096])
@pytest.mark.parametrize("dtype", RAW, ids=lambda d: d.__name__)
def test_one_device_sort_decodes_on_the_device(dtype, n):
    """The extremes and n not a power of two: exact, decoded on the
    device, and in the caller's dtype."""
    x = _keys(dtype, n)
    tr = Tracer()
    got = _sort(x, tr)
    assert got.dtype == np.dtype(dtype) and got.shape == (n,)
    np.testing.assert_array_equal(got, np.sort(x))
    assert tr.counters["decode_site"] == "device"
    assert tr.counters["verify_runs"] == 1


def _plan_class(case: str, dtype) -> np.ndarray:
    """N_PAIR keys of one 64-bit plan class, extremes included."""
    rng = np.random.default_rng(17)
    lo = rng.integers(0, 2**32, N_PAIR, dtype=np.uint64)
    hi = rng.integers(0, 2**32, N_PAIR, dtype=np.uint64)
    hi[:2], lo[:2] = (0, 2**32 - 1), (0, 2**32 - 1)
    if case == "constant":
        u = np.full(N_PAIR, 0x8000_0000_FFFF_FFFF, np.uint64)
    elif case == "const_hi":
        u = (np.uint64(0xFFFF_FFFF) << np.uint64(32)) | lo
    elif case == "const_lo":
        u = (hi << np.uint64(32)) | np.uint64(0)
    elif case == "dup_hi":
        u = (rng.choice(hi[:8], N_PAIR) << np.uint64(32)) | lo
    else:
        u = (hi << np.uint64(32)) | lo
    return u.view(dtype)


@pytest.mark.parametrize("case,engine", [
    ("constant", "constant"), ("const_hi", "bitonic_1w1"),
    ("const_lo", "bitonic_1w0"), ("dup_hi", "lax"),
    ("pair", "bitonic_pair"),
])
@pytest.mark.parametrize("dtype", [np.int64, np.uint64],
                         ids=lambda d: d.__name__)
def test_64bit_plan_branches_decode_on_the_device(dtype, case, engine):
    """Every branch of the 64-bit plan (constant, 1-word on either word,
    the dup reroute, the pair engine) hands its words to the device
    decode, and the keys come back exact."""
    x = _plan_class(case, dtype)
    tr = Tracer()
    with knobs.scoped_env(SORT_LOCAL_ENGINE="bitonic"):
        got = _sort(x, tr)
    np.testing.assert_array_equal(got, np.sort(x))
    assert tr.counters["local_engine"] == engine
    assert tr.counters["encode_site"] == "device"
    assert tr.counters["decode_site"] == "device"


@pytest.mark.parametrize("dtype", RAW, ids=lambda d: d.__name__)
def test_device_and_staged_input_decode_on_the_device(dtype):
    """Device-resident keys and the streamed ingest's staged words take
    the device decode too; the caller's device array survives it, with
    donation on."""
    x = _keys(dtype, 2049)
    one = make_mesh(1)
    with jax.enable_x64(x.dtype.itemsize == 8):
        dev = jax.device_put(x, one.devices.flat[0])
        tr = Tracer()
        with knobs.scoped_env(SORT_DONATE="1"):
            got = _sort(dev, tr)
        np.testing.assert_array_equal(got, np.sort(x))
        assert tr.counters["decode_site"] == "device"
        assert not dev.is_deleted()
        np.testing.assert_array_equal(np.asarray(dev), x)
    tr = Tracer()
    got = _sort(api.ingest_to_mesh(x, mesh=one), tr)
    np.testing.assert_array_equal(got, np.sort(x))
    assert tr.counters["decode_site"] == "device"


@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.int16],
                         ids=lambda d: d.__name__)
def test_other_dtypes_decode_on_the_host(dtype):
    rng = np.random.default_rng(4)
    if np.dtype(dtype).kind == "f":
        x = (rng.standard_normal(3001) * 1e6).astype(dtype)
    else:
        x = rng.integers(-(2**15), 2**15, 3001).astype(dtype)
    tr = Tracer()
    got = _sort(x, tr)
    np.testing.assert_array_equal(got, np.sort(x))
    assert tr.counters["decode_site"] == "host"
    assert "decode" not in {s.attrs.get("label") for s in tr.spans.spans}


@pytest.mark.parametrize("dtype", RAW, ids=lambda d: d.__name__)
def test_return_result_keeps_the_encoded_words(dtype):
    x = _keys(dtype, 3001)
    tr = Tracer()
    res = _sort(x, tr, return_result=True)
    assert res.keys is None
    want = codec_for(dtype).encode(np.sort(x))
    assert len(res.words) == len(want)
    for w, v in zip(res.words, want):
        np.testing.assert_array_equal(np.asarray(w), v)
    assert tr.counters["decode_site"] == "host"
    np.testing.assert_array_equal(res.to_numpy(), np.sort(x))


def test_distributed_results_decode_on_the_host(mesh8):
    x = _keys(np.int32, 4099)
    tr = Tracer()
    got = api.sort(x, mesh=mesh8, tracer=tr)
    np.testing.assert_array_equal(got, np.sort(x))
    assert tr.counters["decode_site"] == "host"


@pytest.mark.parametrize("dtype", RAW, ids=lambda d: d.__name__)
def test_a_corrupt_decode_fails_verification(dtype, monkeypatch):
    """A decode program that changes one key makes the sort raise: the
    verifier reads the decoded keys the fetch would hand back, not the
    sorted words."""
    real, hits = local._compile_decode, []

    def corrupt(dtype_name, donate):
        fn = real(dtype_name, donate)

        def run(*words):
            hits.append(dtype_name)
            keys = fn(*words)
            flat, k = keys.reshape(-1), keys.ndim  # uint32s a key
            return flat.at[7 * k:8 * k].set(flat[8 * k:9 * k]).reshape(
                keys.shape)  # key 7 becomes key 8
        return run

    monkeypatch.setattr(local, "_compile_decode", corrupt)
    tr = Tracer()
    with pytest.raises(SortIntegrityError):
        _sort(_keys(dtype, 3001), tr)
    assert hits == [np.dtype(dtype).name]
    assert tr.counters["decode_site"] == "device"


@pytest.mark.parametrize("dtype", [np.int32, np.int64],
                         ids=lambda d: d.__name__)
def test_passthrough_hit_keeps_the_host_decode(dtype):
    """A planner passthrough hit returns the input words it verified,
    decoded on the host; no decode program runs."""
    x = np.arange(-(1 << 12), 1 << 12, dtype=dtype)
    tr = Tracer()
    with knobs.scoped_env(SORT_PLANNER="on"):
        got = _sort(x, tr)
    np.testing.assert_array_equal(got, x)
    assert tr.counters["planner_passthrough"] == 1
    assert tr.counters["decode_site"] == "host"
    assert "decode" not in {s.attrs.get("label") for s in tr.spans.spans}


@pytest.mark.parametrize("dtype", RAW, ids=lambda d: d.__name__)
def test_decode_jax_inverts_encode_jax(dtype):
    x = _keys(dtype, 1000)
    codec = codec_for(dtype)
    with jax.enable_x64(x.dtype.itemsize == 8):
        words = codec.encode_jax(jnp.asarray(x))
        for w, v in zip(words, codec.encode(x)):
            np.testing.assert_array_equal(np.asarray(w), v)
        back = codec.decode_jax(words)
        if codec.n_words == 1:
            assert back.dtype == np.dtype(dtype)
            host = np.asarray(back)
        else:  # the native bytes: rows of uint32 halves, max-key pads
            assert back.dtype == np.uint32
            assert back.shape == (8, 2 * DECODE_ROW)  # ceil(1000 / 128)
            again = codec.encode_jax(jax.lax.bitcast_convert_type(
                back.reshape(8, DECODE_ROW, 2), dtype))
            for w, v in zip(again, words):
                np.testing.assert_array_equal(
                    np.asarray(w).reshape(-1)[:1000], np.asarray(v))
            host = np.asarray(back).view(dtype).reshape(-1)
            assert (host[1000:] == np.iinfo(dtype).max).all()
        np.testing.assert_array_equal(host[:1000], x)


@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.int16])
def test_decode_jax_refuses_other_codecs(dtype):
    with pytest.raises(TypeError):
        codec_for(dtype).decode_jax((jnp.zeros(4, jnp.uint32),))


@pytest.mark.parametrize("donate", [True, False])
@pytest.mark.parametrize("dtype", RAW, ids=lambda d: d.__name__)
def test_decode_program_takes_one_word_buffers(dtype, donate):
    """Donated 1-word words are consumed by the decode; 2-word words,
    whose keys are twice their size, never are."""
    codec = codec_for(dtype)
    x = np.sort(_keys(dtype, 512))
    words = tuple(jax.device_put(w) for w in codec.encode(x))
    keys = local.decode_on_device(words, codec, donate, Tracer())
    np.testing.assert_array_equal(
        np.asarray(keys).view(dtype).reshape(-1), x)  # 512 keys: no pad
    assert words[0].is_deleted() == (donate and codec.n_words == 1)


def test_programs_are_named_and_tagged():
    from mpitest_tpu.models import verify

    w = jax.ShapeDtypeStruct((128,), jnp.uint32)
    text = local._compile_decode("int32", False).lower(w).compile().as_text()
    assert "HloModule jit_decode" in text and 'layer="egress"' in text
    k = jax.ShapeDtypeStruct((128,), jnp.int32)
    text = verify._compile_keys_contig("int32", 100, 128).lower(k) \
        .compile().as_text()
    assert "HloModule jit_verify_keys" in text and 'layer="verify"' in text


def test_to_numpy_of_decoded_keys_records_the_fetch_alone():
    keys = jax.device_put(np.arange(16, dtype=np.int32))
    res = api.DistributedSortResult((), 10, np.dtype(np.int32), keys=keys)
    tr = Tracer()
    np.testing.assert_array_equal(res.to_numpy(tracer=tr), np.arange(10))
    assert [(s.name, s.attrs) for s in tr.spans.spans] == \
        [("egress.fetch", {"bytes": 64})]
