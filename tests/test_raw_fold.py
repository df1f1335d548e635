"""Raw-key ingest on one device: 32- and 64-bit integer host keys ship
as they are and encode on the device, and the input fingerprint is
folded from the raw keys.

The fold must equal the fingerprint of the host-encoded words bit for
bit (the device verifier compares it with the fold of the sorted
words), the route must engage only for the constant-XOR codecs, the
64-bit route's device plan must be the host plan, and the host fold
must still cover the transfer."""

from __future__ import annotations

import numpy as np
import pytest

from mpitest_tpu.models import api
from mpitest_tpu.models import verify as vfy
from mpitest_tpu.models.supervisor import SortIntegrityError
from mpitest_tpu.ops.keys import KeyCodec, codec_for
from mpitest_tpu.utils import knobs
from mpitest_tpu.utils.trace import Tracer

I32 = np.iinfo(np.int32)
#: INT32_MIN, -1, 0, INT32_MAX: the sign-bit and all-ones edges
SPECIALS = np.array([I32.min, -1, 0, I32.max], np.int32)


def _keys(case: str, dtype) -> np.ndarray:
    """The edge cases of the key's width (INTn_MIN, -1, 0, INTn_MAX),
    alone and with a random bulk of odd or even length, as ``dtype``."""
    signed = np.int64 if np.dtype(dtype).itemsize == 8 else np.int32
    info = np.iinfo(signed)
    specials = np.array([info.min, -1, 0, info.max], signed)
    rng = np.random.default_rng(11)
    bulk = rng.integers(info.min, info.max, 4097, dtype=np.int64,
                        endpoint=True).astype(signed)
    x = {"empty": specials[:0],
         "min": specials[:1], "minus_one": specials[1:2],
         "zero": specials[2:3], "max": specials[3:],
         "three": specials[:3], "four": specials,
         "even": np.concatenate([specials, bulk[:4092]]),
         "odd": np.concatenate([specials, bulk])}[case]
    return x.view(dtype)


@pytest.mark.parametrize("case", ["empty", "min", "minus_one", "zero", "max",
                                  "three", "four", "even", "odd"])
@pytest.mark.parametrize("dtype", [np.int32, np.uint32, np.int64, np.uint64])
def test_raw_fold_is_the_encoded_words_fingerprint(dtype, case):
    x = _keys(case, dtype)
    codec = codec_for(dtype)
    assert codec.raw_bias is not None
    assert vfy.fingerprint_raw(x, codec.raw_bias) == \
        vfy.fingerprint_host(codec.encode(x))


@pytest.mark.parametrize("dtype,engine,site", [
    (np.int32, None, "device"),
    (np.uint32, None, "device"),
    (np.float32, None, "host"),
    (np.int32, "radix_pallas_interpret", "host"),
])
def test_one_device_host_input_encode_site(dtype, engine, site, monkeypatch):
    from mpitest_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(5)
    if np.dtype(dtype).kind == "f":
        x = rng.standard_normal(3001).astype(dtype)
    else:
        x = np.concatenate([SPECIALS, rng.integers(
            I32.min, I32.max, 2997, dtype=np.int64).astype(np.int32)])
        x = x.view(dtype)
    if site == "device":
        def refuse(self, keys):
            raise AssertionError("host encode on the device route")
        monkeypatch.setattr(KeyCodec, "encode", refuse)
    tr = Tracer()
    with knobs.scoped_env(SORT_LOCAL_ENGINE=engine):
        got = api.sort(x, mesh=make_mesh(1), tracer=tr)
    np.testing.assert_array_equal(got, np.sort(x))
    assert tr.counters["encode_site"] == site
    assert ("encode" in tr.phases) == (site == "host")


def test_raw_route_fold_covers_the_transfer(monkeypatch):
    """A key changed in flight (after the host fold's source, before the
    device program) must fail verification: the fold reads the host
    array, never the transferred copy."""
    from mpitest_tpu.parallel.mesh import make_mesh

    x = np.random.default_rng(9).integers(-1000, 1000, 2048,
                                          dtype=np.int64).astype(np.int32)
    real, hits = api.checked_device_put, []

    def corrupt_once(v, target):
        out = real(v, target)
        if not hits:
            hits.append(target)
            out = out.at[17].add(1)
        return out

    monkeypatch.setattr(api, "checked_device_put", corrupt_once)
    tr = Tracer()
    with pytest.raises(SortIntegrityError):
        api.sort(x, mesh=make_mesh(1), tracer=tr)
    assert len(hits) == 1 and tr.counters["encode_site"] == "device"


N64 = 1 << 14


def _plan_class(case: str, dtype) -> np.ndarray:
    """2^14 keys of one 64-bit plan class, as ``dtype``."""
    rng = np.random.default_rng(23)
    lo = rng.integers(0, 2**32, N64, dtype=np.uint64)
    hi = rng.integers(0, 2**32, N64, dtype=np.uint64)
    if case == "all_equal":
        u = np.full(N64, 0x8000_0007_1234_5678, np.uint64)
    elif case == "const_hi":
        u = (np.uint64(0x7FFF_FFFF) << np.uint64(32)) | lo
    elif case == "const_lo":
        u = (hi << np.uint64(32)) | np.uint64(0x9ABC_DEF0)
    elif case == "ycsb":
        from sortbench import spec

        gen = spec.load_module(spec.HERE / "gen" / "ycsb_scrambled_zipfian.py")
        params = spec.resolve("ycsb_zipf_i64.1chip").config[
            "distribution_params"]
        u = gen.generate(rng, N64, "int64", params).view(np.uint64)
    else:
        u = (hi << np.uint64(32)) | lo
    return u.view(dtype)


def _host_plan(x: np.ndarray) -> tuple[str, int]:
    """The engine and sniff count the host-encoded words give."""
    hi, lo = codec_for(x.dtype).encode(x)
    dups = api._host_hi_dup_sniff(hi)
    const = (hi.min() == hi.max(), lo.min() == lo.max())
    return {(True, True): "constant", (True, False): "bitonic_1w1",
            (False, True): "bitonic_1w0"}.get(
                const, "lax" if dups else "bitonic_pair"), dups


@pytest.mark.parametrize("case,engine", [
    ("all_equal", "constant"), ("const_hi", "bitonic_1w1"),
    ("const_lo", "bitonic_1w0"), ("ycsb", "lax"), ("uniform", "bitonic_pair"),
])
@pytest.mark.parametrize("dtype", [np.int64, np.uint64])
def test_64bit_host_input_splits_and_plans_on_device(dtype, case, engine,
                                                     monkeypatch):
    """One-device 64-bit host keys never meet the host encode, and the
    device split's plan is the host plan: same engine, same sniff."""
    from mpitest_tpu.parallel.mesh import make_mesh

    x = _plan_class(case, dtype)
    want_engine, want_dups = _host_plan(x)
    assert want_engine == engine

    real = KeyCodec.encode

    def refuse_64bit(self, keys):
        if self.dtype.itemsize == 8 and self.dtype.kind in "iu":
            raise AssertionError("host encode on the device route")
        return real(self, keys)

    monkeypatch.setattr(KeyCodec, "encode", refuse_64bit)
    tr = Tracer()
    with knobs.scoped_env(SORT_LOCAL_ENGINE="bitonic"):
        got = api.sort(x, mesh=make_mesh(1), tracer=tr)
    np.testing.assert_array_equal(got, np.sort(x))
    c = tr.counters
    assert c["encode_site"] == "device"
    assert (c["local_engine"], c["pair_sniff_dups"]) == (engine, want_dups)
    assert c.get("pair_dup_reroute", 0) == (engine == "lax")


@pytest.mark.parametrize("dtype", [np.int64, np.uint64])
def test_64bit_raw_route_fold_covers_the_transfer(dtype, monkeypatch):
    """As for 32-bit keys: a 64-bit key changed in flight fails
    verification, since the fold reads the host array."""
    from mpitest_tpu.parallel.mesh import make_mesh

    x = np.random.default_rng(9).integers(
        -1000, 1000, 1 << 13, dtype=np.int64).view(dtype)
    real, hits = api.checked_device_put, []

    def corrupt_once(v, target):
        out = real(v, target)
        if not hits:
            hits.append(target)
            out = out.at[17].add(1)
        return out

    monkeypatch.setattr(api, "checked_device_put", corrupt_once)
    tr = Tracer()
    with knobs.scoped_env(SORT_LOCAL_ENGINE="bitonic"), \
            pytest.raises(SortIntegrityError):
        api.sort(x, mesh=make_mesh(1), tracer=tr)
    assert len(hits) == 1 and tr.counters["encode_site"] == "device"
