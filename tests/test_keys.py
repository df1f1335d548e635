"""Key codec round-trip + order-preservation properties."""

import numpy as np
import pytest

from mpitest_tpu.ops.keys import codec_for


DTYPES = [np.int32, np.uint32, np.int64, np.uint64]


@pytest.mark.parametrize("dtype", DTYPES)
def test_roundtrip(dtype, rng):
    info = np.iinfo(np.dtype(dtype))
    x = rng.integers(info.min, info.max, size=1000, dtype=dtype, endpoint=True)
    # extremes as a typed array: concatenating a Python list would promote
    # through float64 and round the uint64/int64 extremes off by one
    x = np.concatenate([x, np.array([info.min, info.max, 0, 1], dtype=dtype)])
    codec = codec_for(dtype)
    words = codec.encode(x)
    assert all(w.dtype == np.uint32 for w in words)
    assert len(words) == codec.n_words
    np.testing.assert_array_equal(codec.decode(words), x)


@pytest.mark.parametrize("dtype", DTYPES)
def test_order_preserved(dtype, rng):
    """Lexicographic unsigned word order == native key order.

    This is the property the reference *breaks* for negatives
    (abs() digit math, mpi_radix_sort.c:50,56)."""
    info = np.iinfo(np.dtype(dtype))
    x = rng.integers(info.min, info.max, size=500, dtype=dtype, endpoint=True)
    codec = codec_for(dtype)
    words = codec.encode(x)
    # sort natively, and lexicographically by words
    native = np.sort(x)
    order = np.lexsort(tuple(reversed(words)))  # lexsort: last key is primary
    lex = x[order]
    np.testing.assert_array_equal(lex, native)


@pytest.mark.parametrize("layout", ["strided", "2d", "big_endian", "one"])
@pytest.mark.parametrize("dtype", [np.int64, np.uint64])
def test_64bit_words_of_any_layout(dtype, layout, rng):
    """The 64-bit codecs read the keys' halves in place: a strided,
    2-D, byte-swapped or one-key array gives the words of the shifted
    and masked key, as contiguous arrays of their own."""
    info = np.iinfo(np.dtype(dtype))
    base = rng.integers(info.min, info.max, size=600, dtype=dtype,
                        endpoint=True)
    x = {"strided": base[::3], "2d": base.reshape(20, 30),
         "big_endian": base.astype(np.dtype(dtype).newbyteorder(">")),
         "one": base[:1]}[layout]
    codec = codec_for(dtype)
    hi, lo = codec.encode(x)
    u = np.asarray(x, dtype=dtype).view(np.uint64)
    if dtype == np.int64:
        u = u ^ np.uint64(1 << 63)
    np.testing.assert_array_equal(hi, (u >> np.uint64(32)).astype(np.uint32))
    np.testing.assert_array_equal(lo, (u & np.uint64(0xFFFFFFFF))
                                  .astype(np.uint32))
    assert all(w.flags.c_contiguous and w.flags.owndata for w in (hi, lo))
    back = codec.decode((hi.reshape(-1), lo.reshape(-1)))
    assert back.dtype == np.dtype(dtype)
    np.testing.assert_array_equal(back, np.asarray(x).reshape(-1))


def test_sentinel_is_max():
    for dtype in DTYPES:
        codec = codec_for(dtype)
        sent = np.array(codec.max_sentinel(), dtype=np.uint64)
        assert np.all(sent == 0xFFFFFFFF)
        decoded = codec.decode(tuple(np.full(1, s, np.uint32) for s in codec.max_sentinel()))
        assert decoded[0] == np.iinfo(np.dtype(dtype)).max


FLOAT_DTYPES = [np.float32, np.float64]


def _float_specials(dtype, rng):
    f = np.dtype(dtype)
    x = rng.standard_normal(1000).astype(f) * 1e10
    specials = np.array(
        [0.0, -0.0, np.inf, -np.inf, np.nan, -np.nan, 1e-40, -1e-40],
        dtype=f,
    )
    return np.concatenate([x, specials])


@pytest.mark.parametrize("dtype", FLOAT_DTYPES)
def test_float_roundtrip_bits(dtype, rng):
    """encode∘decode is the identity on BITS — NaN payloads, -0.0 and
    denormals all survive exactly."""
    x = _float_specials(dtype, rng)
    codec = codec_for(dtype)
    back = codec.decode(codec.encode(x))
    np.testing.assert_array_equal(
        back.view(np.uint32 if dtype == np.float32 else np.uint64),
        x.view(np.uint32 if dtype == np.float32 else np.uint64),
    )


@pytest.mark.parametrize("dtype", FLOAT_DTYPES)
def test_float_total_order(dtype, rng):
    """Word order == IEEE totalOrder: -NaN < -inf < ... < -0.0 < +0.0 <
    ... < +inf < +NaN (documented divergence from np.sort's NaNs-last)."""
    x = _float_specials(dtype, rng)
    codec = codec_for(dtype)
    words = codec.encode(x)
    order = np.lexsort(tuple(reversed(words)))
    s = x[order]
    finite = s[np.isfinite(s)]
    assert (np.diff(finite) >= 0).all()
    # -NaN block at the head, +NaN block at the tail
    sign = np.signbit(s)
    assert np.isnan(s[0]) and sign[0]
    assert np.isnan(s[-1]) and not sign[-1]
    # -0.0 strictly before +0.0
    zeros = np.where(s == 0)[0]
    assert sign[zeros[0]] and not sign[zeros[-1]]


# ---- property-based (hypothesis): the codec laws hold for ARBITRARY
# values, not just the sampled corpora above.  hypothesis is optional
# (not a declared dependency): absent, only these two tests skip. ------

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

ALL_DTYPES = [np.int8, np.uint8, np.int16, np.uint16,
              np.int32, np.uint32, np.int64, np.uint64]


def _ints_for(dtype):
    info = np.iinfo(np.dtype(dtype))
    return st.integers(min_value=int(info.min), max_value=int(info.max))


@pytest.mark.parametrize("dtype", ALL_DTYPES)
@settings(max_examples=25, deadline=None)
@given(data=st.data())
def test_property_int_roundtrip_and_order(dtype, data):
    """For every integer dtype (each with its own example budget) and ANY
    pair of values: encode∘decode is the identity, and key comparison ==
    lexicographic unsigned word comparison (the law every sort in this
    framework rests on)."""
    a = data.draw(_ints_for(dtype))
    b = data.draw(_ints_for(dtype))
    codec = codec_for(dtype)
    x = np.array([a, b], dtype=dtype)
    words = codec.encode(x)
    np.testing.assert_array_equal(codec.decode(words), x)
    wa = tuple(int(w[0]) for w in words)
    wb = tuple(int(w[1]) for w in words)
    assert (a < b) == (wa < wb) and (a == b) == (wa == wb)


@settings(max_examples=40, deadline=None)
@given(data=st.data())
def test_property_float_roundtrip_and_totalorder(data):
    """For float32/float64 and ANY bit patterns (including NaN payloads,
    infinities, denormals, signed zeros): encode∘decode preserves the
    exact bits, and word order == IEEE-754 totalOrder."""
    wide = data.draw(st.booleans())
    ftype, utype = (np.float64, np.uint64) if wide else (np.float32, np.uint32)
    bits = st.integers(0, 2 ** (64 if wide else 32) - 1)
    a = data.draw(bits)
    b = data.draw(bits)
    x = np.array([a, b], dtype=utype).view(ftype)
    codec = codec_for(ftype)
    words = codec.encode(x)
    np.testing.assert_array_equal(
        codec.decode(words).view(utype), x.view(utype))

    def total_order_key(u):
        # IEEE-754 totalOrder as an unsigned integer: flip all bits of
        # negatives, set the sign bit of non-negatives
        sign = 1 << (63 if wide else 31)
        return (~u) & (2 ** (64 if wide else 32) - 1) if u & sign else u | sign

    wa = tuple(int(w[0]) for w in words)
    wb = tuple(int(w[1]) for w in words)
    assert (total_order_key(a) < total_order_key(b)) == (wa < wb)
