"""Key codecs: map host integer dtypes to tuples of sortable uint32 words.

TPU-first design decision: JAX on TPU runs with 32-bit integers by default
(no x64), and the MXU/VPU paths are widest for 32-bit lanes.  Rather than
flipping global x64 flags, every key dtype is encoded as a tuple of
**uint32 words, most-significant first**, such that lexicographic unsigned
comparison of the word tuple equals the native comparison of the original
keys.  ``lax.sort`` with ``num_keys=len(words)`` then sorts any supported
dtype, and LSD radix passes simply iterate words from least- to
most-significant.

This fixes a reference bug: ``mpi_radix_sort.c:50,56`` takes ``abs(value)``,
so negative keys sort by magnitude with the sign dropped.  The biased
encoding here (sign-bit flip) makes signed sorts actually correct; the
divergence is documented in SURVEY.md §7.4.

Host-side padding (models/api.py) replicates the maximum *real* key, not a
synthetic sentinel, so pads never widen the key range the radix pass
planner sees.  The all-ones word :data:`MAX_WORD` is still used inside the
SPMD programs as the fill for invalid exchange lanes (sample sort), where
it guarantees fills sort to the tail of the static buffer; validity there
is tracked by explicit counts, so collisions with real all-ones keys are
harmless.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

_SIGN32 = np.uint32(0x80000000)

#: where the high and the low uint32 half of a 64-bit key lie in memory
_HI, _LO = (1, 0) if np.little_endian else (0, 1)


def _halves(x: np.ndarray) -> np.ndarray:
    """64-bit keys as a ``x.shape + (2,)`` uint32 view of their halves.
    The 64-bit codecs read and write the halves in place, so a 1 GiB
    batch costs its two word arrays and no 64-bit temporaries."""
    return np.ascontiguousarray(x).view(np.uint32).reshape(x.shape + (2,))


#: All-ones uint32 word — exchange-lane fill that sorts to the tail.
MAX_WORD = 0xFFFFFFFF

#: 64-bit keys per row of :meth:`KeyCodec.decode_jax`'s output
DECODE_ROW = 128


@dataclass(frozen=True)
class KeyCodec:
    """Encode/decode a host numeric dtype to/from uint32 word tuples.

    Floats use the IEEE total-order flip (negative values: all bits
    inverted; non-negative: sign bit set), a bit-preserving bijection, so
    NaNs, infinities, -0.0 < +0.0 and NaN payloads all sort in
    ``totalOrder`` and decode back to their exact input bits.  This is a
    *documented divergence* from ``np.sort`` (which moves every NaN to
    the tail and treats ±0.0 as equal); the sorted multiset of bit
    patterns is identical.
    """

    dtype: np.dtype
    n_words: int
    #: pad with the all-ones sentinel instead of the max real key
    #: (floats: np.max is NaN-poisoned and NaN payloads break max-key
    #: padding; the sentinel is the totalOrder maximum by construction).
    sentinel_pad: bool = False
    #: One ``b`` per word (msw first) where the encode is the constant
    #: XOR of each uint32 half with its ``b`` (int32: the sign bit;
    #: uint32: 0; int64: the sign bit on the high half; uint64: 0, 0),
    #: else None.  Such keys can ship raw and encode on the device, with
    #: the words' fingerprint folded from the raw keys
    #: (``verify.fingerprint_raw``).
    raw_bias: tuple[int, ...] | None = None

    def _split64(self, u: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        return (
            (u >> np.uint64(32)).astype(np.uint32),
            (u & np.uint64(0xFFFFFFFF)).astype(np.uint32),
        )

    def encode(self, x: np.ndarray) -> tuple[np.ndarray, ...]:
        """Host array -> tuple of uint32 word arrays, most-significant first."""
        x = np.asarray(x, dtype=self.dtype)
        if self.dtype in (np.dtype(np.int16), np.dtype(np.uint16),
                          np.dtype(np.int8), np.dtype(np.uint8)):
            # narrow ints widen losslessly into the 32-bit paths
            wide = np.int32 if self.dtype.kind == "i" else np.uint32
            return codec_for(wide).encode(x.astype(wide))
        if self.dtype == np.dtype(np.int32):
            return ((x.view(np.uint32) ^ _SIGN32),)
        if self.dtype == np.dtype(np.uint32):
            return (x.copy(),)
        if self.dtype == np.dtype(np.float32):
            u = x.view(np.uint32)
            return (np.where(u & _SIGN32, ~u, u ^ _SIGN32),)
        if self.dtype == np.dtype(np.int64):
            h = _halves(x)
            return (h[..., _HI] ^ _SIGN32, h[..., _LO].copy())
        if self.dtype == np.dtype(np.uint64):
            h = _halves(x)
            return (h[..., _HI].copy(), h[..., _LO].copy())
        if self.dtype == np.dtype(np.float64):
            u = x.view(np.uint64)
            s = np.uint64(0x8000000000000000)
            return self._split64(np.where(u & s, ~u, u ^ s))
        raise TypeError(f"unsupported key dtype: {self.dtype}")

    def decode(self, words: tuple[np.ndarray, ...]) -> np.ndarray:
        """Tuple of uint32 word arrays (msw first) -> host array of dtype."""
        words = tuple(np.asarray(w, dtype=np.uint32) for w in words)
        if len(words) != self.n_words:
            raise ValueError(f"expected {self.n_words} words, got {len(words)}")
        if self.dtype in (np.dtype(np.int16), np.dtype(np.uint16),
                          np.dtype(np.int8), np.dtype(np.uint8)):
            wide = np.int32 if self.dtype.kind == "i" else np.uint32
            return codec_for(wide).decode(words).astype(self.dtype)
        if self.dtype == np.dtype(np.int32):
            return (words[0] ^ _SIGN32).view(np.int32)
        if self.dtype == np.dtype(np.uint32):
            return words[0].copy()
        if self.dtype == np.dtype(np.float32):
            e = words[0]
            return np.where(e & _SIGN32, e ^ _SIGN32, ~e).view(np.float32)
        if self.dtype == np.dtype(np.float64):
            u = ((words[0].astype(np.uint64) << np.uint64(32))
                 | words[1].astype(np.uint64))
            s = np.uint64(0x8000000000000000)
            return np.where(u & s, u ^ s, ~u).view(np.float64)
        # int64 / uint64: write the halves in place
        halves = np.empty(words[0].shape + (2,), np.uint32)
        if self.dtype == np.dtype(np.int64):
            np.bitwise_xor(words[0], _SIGN32, out=halves[..., _HI])
        else:
            halves[..., _HI] = words[0]
        halves[..., _LO] = words[1]
        return halves.view(self.dtype)[..., 0]

    def encode_jax(self, x):
        """Device-side encode: bitcast + sign-bias XOR, elementwise — XLA
        fuses it into the consumer sort.

        64-bit dtypes (which only exist as device arrays under
        ``jax_enable_x64``) never touch 64-bit arithmetic here:
        ``bitcast_convert_type`` to uint32 appends a trailing word dim
        (minor word = least significant on TPU/x86), so the split into
        (hi, lo) uint32 words is a pure relayout that works with or
        without x64 — device-resident 64-bit keys stay on the mesh with
        no host round-trip (the framework's steady-state contract)."""
        import jax.numpy as jnp
        from jax import lax

        if self.dtype in (np.dtype(np.int16), np.dtype(np.uint16),
                          np.dtype(np.int8), np.dtype(np.uint8)):
            wide = jnp.int32 if self.dtype.kind == "i" else jnp.uint32
            return codec_for(np.dtype(wide)).encode_jax(x.astype(wide))
        if self.dtype == np.dtype(np.int32):
            return (lax.bitcast_convert_type(x, jnp.uint32) ^ jnp.uint32(0x80000000),)
        if self.dtype == np.dtype(np.uint32):
            return (x,)
        if self.dtype == np.dtype(np.float32):
            u = lax.bitcast_convert_type(x, jnp.uint32)
            neg = (u & jnp.uint32(0x80000000)) != 0
            return (jnp.where(neg, ~u, u ^ jnp.uint32(0x80000000)),)
        if self.dtype in (np.dtype(np.int64), np.dtype(np.uint64),
                          np.dtype(np.float64)):
            if x.dtype != self.dtype:
                raise TypeError(
                    f"device array has dtype {x.dtype}, expected {self.dtype} "
                    "(64-bit device-resident keys require jax_enable_x64)"
                )
            w = lax.bitcast_convert_type(x, jnp.uint32)  # [..., 2], minor=lsw
            lo, hi = w[..., 0], w[..., 1]
            if self.dtype == np.dtype(np.int64):
                hi = hi ^ jnp.uint32(0x80000000)
            elif self.dtype == np.dtype(np.float64):
                neg = (hi & jnp.uint32(0x80000000)) != 0
                hi2 = jnp.where(neg, ~hi, hi ^ jnp.uint32(0x80000000))
                lo = jnp.where(neg, ~lo, lo)
                hi = hi2
            return (hi, lo)
        raise TypeError(f"device-side encode unsupported for {self.dtype}")

    def decode_jax(self, words):
        """Device-side decode of the constant-XOR codecs (``raw_bias``),
        the inverse of :meth:`encode_jax`: each word XOR its bias.  One
        word is then bitcast to the key dtype.

        Two words come back as the native bytes of the 64-bit keys:
        their uint32 halves in host byte order, :data:`DECODE_ROW` keys
        to a row (``[ceil(N / DECODE_ROW), 2 * DECODE_ROW]``, the tail
        padded with the maximum key), which the host views as ``dtype``
        with no pass over them.  On a v5e a 64-bit array fetches about
        4.5 s per GiB, an ``[N, 2]`` array lands as two planes and a
        flat ``[2N]`` one does not compile (its interleave would pad
        64-fold); these rows fetch in about 0.32 s per GiB."""
        import jax.numpy as jnp
        from jax import lax

        if self.raw_bias is None:
            raise TypeError(f"device-side decode unsupported for {self.dtype}")
        if self.n_words == 1:
            return lax.bitcast_convert_type(
                words[0] ^ jnp.uint32(self.raw_bias[0]), self.dtype)
        pad = (0, -words[0].shape[0] % DECODE_ROW)
        fill = jnp.uint32(MAX_WORD)
        halves = [None, None]
        halves[_HI], halves[_LO] = (
            jnp.pad(v, pad, constant_values=fill).reshape(-1, DECODE_ROW)
            ^ jnp.uint32(b) for v, b in zip(words, self.raw_bias))
        return jnp.stack(halves, -1).reshape(-1, 2 * DECODE_ROW)

    def max_sentinel(self) -> tuple[int, ...]:
        """Word values that encode the maximum representable key (sorts
        last); the per-word exchange-lane fill (see :data:`MAX_WORD`)."""
        return (MAX_WORD,) * self.n_words


_CODECS = {
    np.dtype(np.int8): KeyCodec(np.dtype(np.int8), 1),
    np.dtype(np.uint8): KeyCodec(np.dtype(np.uint8), 1),
    np.dtype(np.int16): KeyCodec(np.dtype(np.int16), 1),
    np.dtype(np.uint16): KeyCodec(np.dtype(np.uint16), 1),
    np.dtype(np.int32): KeyCodec(np.dtype(np.int32), 1,
                                 raw_bias=(int(_SIGN32),)),
    np.dtype(np.uint32): KeyCodec(np.dtype(np.uint32), 1, raw_bias=(0,)),
    np.dtype(np.int64): KeyCodec(np.dtype(np.int64), 2,
                                 raw_bias=(int(_SIGN32), 0)),
    np.dtype(np.uint64): KeyCodec(np.dtype(np.uint64), 2, raw_bias=(0, 0)),
    np.dtype(np.float32): KeyCodec(np.dtype(np.float32), 1, sentinel_pad=True),
    np.dtype(np.float64): KeyCodec(np.dtype(np.float64), 2, sentinel_pad=True),
}


def codec_for(dtype) -> KeyCodec:
    dt = np.dtype(dtype)
    if dt not in _CODECS:
        raise TypeError(
            f"unsupported key dtype {dt}; supported: {sorted(str(k) for k in _CODECS)}"
        )
    return _CODECS[dt]
