"""Raw-key ingest on one device: 32-bit integer host keys ship as they
are and encode on the device, and the input fingerprint is folded from
the raw keys.

The fold must equal the fingerprint of the host-encoded words bit for
bit (the device verifier compares it with the fold of the sorted
words), the route must engage only for the constant-XOR codecs, and the
host fold must still cover the transfer."""

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
    rng = np.random.default_rng(11)
    bulk = rng.integers(I32.min, I32.max, 4097, dtype=np.int64,
                        endpoint=True).astype(np.int32)
    x = {"empty": SPECIALS[:0],
         "min": SPECIALS[:1], "minus_one": SPECIALS[1:2],
         "zero": SPECIALS[2:3], "max": SPECIALS[3:],
         "three": SPECIALS[:3], "four": SPECIALS,
         "even": np.concatenate([SPECIALS, bulk[:4092]]),
         "odd": np.concatenate([SPECIALS, bulk])}[case]
    return x.view(dtype)


@pytest.mark.parametrize("case", ["empty", "min", "minus_one", "zero", "max",
                                  "three", "four", "even", "odd"])
@pytest.mark.parametrize("dtype", [np.int32, np.uint32])
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
