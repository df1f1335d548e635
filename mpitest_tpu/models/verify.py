"""Always-on output verification: sortedness + multiset fingerprint.

The reference's only correctness signal is the (n/2)-th-element probe —
a single value that silent truncation, duplication or corruption can
easily leave intact.  Here every ``sort()`` proves its own result:

1. **On-device sortedness**: one fused check that the result words are
   lexicographically non-decreasing (contiguous layouts check the whole
   padded array — pads are the maximum key, so they extend the order;
   ragged layouts check within-shard adjacency plus a lex-cummax chain
   across shard boundaries that is robust to empty shards).
2. **Multiset fingerprint**: per encoded word, the XOR and the wrapping
   uint32 SUM over the *valid* keys, plus the exact count.  The input
   side is folded where the keys are first touched — chunk-by-chunk
   during streamed ingest, from the raw keys for one-device 32- and
   64-bit integer input that encodes on the device, during the host
   encode otherwise,
   or by one tiny on-device reduction for device-resident input — so no extra
   pass over the data ever happens.  The output side is computed by the
   same reduction over the result and compared host-side.  Truncation
   moves the count, duplication moves the sum, corruption moves the
   XOR: each of the reference's silent failure classes trips at least
   one component.

Cost: the fingerprint is a pair of O(n) elementwise reductions fused
into one small program — measured well under the 5%-of-sort-wall budget
(bench.py records ``verify_overhead_s`` per run).  ``SORT_VERIFY=0``
disables it (benchmark A/B), but the default is ON: a production sorter
that cannot prove its result is the reference's failure mode with extra
steps.

Why not compare against ``np.sort``?  That is O(n log n) host work per
run — the verifier is O(n) device work, and the fingerprint equality +
sortedness of a multiset TOGETHER imply the result *is* the sorted
input (sortedness fixes the permutation; the fingerprint ties the
multiset with collision probability ~2^-64 per word against random
corruption).
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass
from functools import lru_cache
from typing import TYPE_CHECKING, Callable

import numpy as np

from mpitest_tpu.utils.programs import program, traced_call

if TYPE_CHECKING:
    import jax

    from mpitest_tpu.models.api import DistributedSortResult
    from mpitest_tpu.utils.trace import Tracer

_U32 = 0xFFFFFFFF


@dataclass(frozen=True)
class Fingerprint:
    """Order-independent digest of a key-word multiset."""

    count: int
    xors: tuple            # per word, uint32
    sums: tuple            # per word, uint32 (wrapping)

    def combine(self, other: "Fingerprint") -> "Fingerprint":
        return Fingerprint(
            self.count + other.count,
            tuple((a ^ b) & _U32 for a, b in zip(self.xors, other.xors)),
            tuple((a + b) & _U32 for a, b in zip(self.sums, other.sums)),
        )

    @staticmethod
    def empty(n_words: int) -> "Fingerprint":
        return Fingerprint(0, (0,) * n_words, (0,) * n_words)


def fingerprint_host(words: "tuple[np.ndarray, ...]") -> Fingerprint:
    """Fold host uint32 word arrays (one numpy pass, memory-bound)."""
    words = tuple(np.asarray(w, dtype=np.uint32) for w in words)
    return Fingerprint(
        int(words[0].size),
        tuple(int(np.bitwise_xor.reduce(w)) if w.size else 0 for w in words),
        tuple(int(w.sum(dtype=np.uint64)) & _U32 for w in words),
    )


#: Keys per chunk of the 64-bit raw fold (2 MiB): its three reductions
#: read each chunk from cache after the first, so memory is read once.
_RAW_FOLD_CHUNK = 1 << 18


def fingerprint_raw(x: np.ndarray, bias: "tuple[int, ...]") -> Fingerprint:
    """Fingerprint of the encoded words of 32- or 64-bit integer keys,
    folded from ``x`` itself without writing the words.  Word ``i``
    (msw first) is the key's ``i``-th uint32 half XOR ``bias[i]``, and
    each bias is 0 or the sign bit, so ``u ^ b == u + b`` (mod 2^32):
    the sum gains ``n * b``, the XOR gains ``b`` when the count is odd,
    and the count is the same.

    64-bit keys need no strided pass over the halves: the uint64 XOR
    holds both halves' XORs, the uint64 sum's low 32 bits are the low
    halves' sum, and the uint32 sum over every half, less that, is the
    high halves' sum (all mod 2^32)."""
    assert len(bias) in (1, 2) and set(bias) <= {0, 0x80000000}, bias
    u = np.ascontiguousarray(x).reshape(-1)
    n = int(u.size)
    if len(bias) == 1:
        fp = fingerprint_host((u.view(np.uint32),))
        xors, sums = fp.xors, fp.sums
    else:
        u64, u32 = u.view(np.uint64), u.view(np.uint32)
        x64 = s64 = s32 = 0
        for i in range(0, n, _RAW_FOLD_CHUNK):
            c = u64[i:i + _RAW_FOLD_CHUNK]
            x64 ^= int(np.bitwise_xor.reduce(c))
            s64 += int(c.sum(dtype=np.uint64))
            s32 += int(u32[2 * i:2 * (i + _RAW_FOLD_CHUNK)]
                       .sum(dtype=np.uint32))
        xors = (x64 >> 32, x64 & _U32)
        sums = ((s32 - s64) & _U32, s64 & _U32)
    odd = n & 1
    return Fingerprint(n,
                       tuple(v ^ (b if odd else 0) for v, b in zip(xors, bias)),
                       tuple((s + n * b) & _U32 for s, b in zip(sums, bias)))


# ---------------------------------------------------------------- records

#: Per-word-index odd multipliers for the record binding word — each is
#: a bijection on uint32 (odd ⇒ invertible mod 2^32), distinct per word
#: position so transposing two words of one record moves the mix.
def _mix_mult(i: int) -> np.uint32:
    return np.uint32((0x9E3779B1 * (2 * i + 3)) & _U32 | 1)


def record_mix(words: "tuple[np.ndarray, ...]") -> np.ndarray:
    """Per-record binding word over a record's key+payload words: the
    XOR of each word scaled by a position-distinct odd constant.  The
    per-word XOR/sum folds alone cannot see a *pairing* error — a sort
    that permutes keys correctly but gathers the wrong payload lanes
    preserves both multisets individually — while the mix multiset
    moves unless every (key, payload) pair survives intact."""
    mix = np.zeros(words[0].shape, np.uint32)
    for i, w in enumerate(words):
        mix ^= np.asarray(w, np.uint32) * _mix_mult(i)
    return mix


def fingerprint_records(key_words: "tuple[np.ndarray, ...]",
                        payload_words: "tuple[np.ndarray, ...]",
                        ) -> Fingerprint:
    """Multiset fingerprint of key+payload records: the ordinary
    per-word fold over every key AND payload word, plus one extra
    folded word — :func:`record_mix` — that binds each key to its own
    payload.  Input side folds at pack/spill time, output side after
    the permuted gather; equality (with sorted keys) means the output
    is the key-sorted permutation of exactly the input records."""
    words = tuple(key_words) + tuple(payload_words)
    return fingerprint_host(words + (record_mix(words),))


# ------------------------------------------------------------------ device

def _xor_reduce_1d(w: "jax.Array") -> "jax.Array":
    """XOR-reduce a 1-D uint32 array with a trace-time halving fold —
    XLA's SPMD partitioner only understands the standard reduction
    kinds (a custom xor ``lax.reduce`` is UNIMPLEMENTED on sharded
    operands), so the fold uses nothing but slices and elementwise xor.
    O(n) total work, O(log n) ops."""
    import jax.numpy as jnp

    if w.shape[0] == 0:
        return jnp.uint32(0)
    while w.shape[0] > 1:
        n = w.shape[0]
        tail = w[n - 1:] if n % 2 else None
        half = (n - (n % 2)) // 2
        w = w[:half] ^ w[half:half * 2]
        if tail is not None:
            w = jnp.concatenate([w, tail]) if half else tail
    return w[0]


@lru_cache(maxsize=64)
def _compile_contig(n_words: int, n_valid: int, total: int,
                    check_sorted: bool) -> "Callable[..., object]":
    """Fingerprint (+ optional sortedness) of a contiguous layout: real
    keys occupy [0, n_valid), pads (max key / sentinel) the tail.  The
    valid-region reduction is pad-region subtraction — two static
    slices, no index arrays, so there is nothing to overflow at any
    scale (the int32-iota hazard of ADVICE r3 #1 never arises)."""
    import jax

    def f(*words):
        return _contig(words, n_valid, total, check_sorted)

    return jax.jit(program("verify_contig", "verify", f))


def _contig(words: "tuple[jax.Array, ...]", n_valid: int, total: int,
            check_sorted: bool) -> "tuple[jax.Array, tuple, tuple]":
    """The body of :func:`_compile_contig`: ``(ok, xors, sums)``."""
    import jax.numpy as jnp

    xors, sums = [], []
    for w in words:
        pad = w[n_valid:total]
        xors.append(_xor_reduce_1d(w) ^ _xor_reduce_1d(pad))
        sums.append(jnp.sum(w, dtype=jnp.uint32)
                    - jnp.sum(pad, dtype=jnp.uint32))
    if not check_sorted:
        return jnp.ones((), bool), tuple(xors), tuple(sums)
    # lexicographic adjacency over the full array (pads = max extend
    # the order, so they never mask a violation among real keys):
    # pair ok iff the first differing word (msw first) increases,
    # or all words tie.
    lt = jnp.zeros((max(total - 1, 0),), bool)
    eq = jnp.ones_like(lt)
    for w in words:
        a, b = w[:-1], w[1:]
        lt = lt | (eq & (a < b))
        eq = eq & (a == b)
    return jnp.all(lt | eq), tuple(xors), tuple(sums)


@lru_cache(maxsize=64)
def _compile_keys_contig(dtype_name: str, n_valid: int,
                         total: int) -> "Callable[..., object]":
    """:func:`_compile_contig`'s check of a contiguous result decoded on
    the device (``DistributedSortResult.keys``), with the encode
    (``codec.encode_jax``) as its prologue: the words it checks are
    re-derived from the bytes the caller gets.  64-bit keys (rows of
    their uint32 halves, ``KeyCodec.decode_jax``) are bitcast to
    ``dtype`` first, so the program is traced and called under
    ``jax_enable_x64``."""
    import jax

    from mpitest_tpu.ops.keys import codec_for

    codec = codec_for(np.dtype(dtype_name))

    def f(keys):
        if keys.ndim == 2:
            keys = jax.lax.bitcast_convert_type(
                keys.reshape(keys.shape[0], -1, 2), codec.dtype)
        words = tuple(w.reshape(-1) for w in codec.encode_jax(keys))
        return _contig(words, n_valid, total, True)

    return jax.jit(program("verify_keys", "verify", f))


@lru_cache(maxsize=64)
def _compile_ragged(n_words: int, n_valid: int, slots: int,
                    n_ranks: int) -> "Callable[..., object]":
    """Fingerprint + sortedness of the ragged (sample) layout: shard r
    owns slots [r·S, (r+1)·S), of which the first counts[r] are valid,
    sentinel fill sorted to the shard tail.  Valid lanes below global
    position ``n_valid`` (counts-exclusive-scan order) are fingerprinted
    — that excludes exactly the pad copies, which sort to the global
    tail.  Returns (ok, fp_count, xors, sums)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mpitest_tpu.ops import kernels

    total = n_ranks * slots

    def lex_lt(a, b):
        lt = jnp.zeros(a[0].shape, bool)
        eq = jnp.ones(a[0].shape, bool)
        for aw, bw in zip(a, b):
            lt = lt | (eq & (aw < bw))
            eq = eq & (aw == bw)
        return lt

    def f(counts, *words):
        counts = counts.astype(jnp.int32)
        starts = lax.iota(jnp.int32, n_ranks) * slots
        # per-lane shard metadata, gather-free (kernels.piecewise_fill)
        cnt_at = kernels.piecewise_fill(starts, counts, total)
        base = jnp.cumsum(counts) - counts           # exclusive scan
        base_at = kernels.piecewise_fill(starts, base, total)
        start_at = kernels.piecewise_fill(starts, starts, total)
        pos = lax.iota(jnp.int32, total) - start_at  # slot within shard
        gpos = base_at + pos                         # global sorted position
        valid = (pos < cnt_at) & (gpos < n_valid)

        xors, sums = [], []
        for w in words:
            wm = jnp.where(valid, w, jnp.uint32(0))
            xors.append(_xor_reduce_1d(wm))
            sums.append(jnp.sum(wm, dtype=jnp.uint32))
        n_in = jnp.sum(valid.astype(jnp.int32))

        # within-shard adjacency (the sentinel tail is all-ones = max,
        # so whole-buffer adjacency holds for a correct shard)
        lt = jnp.zeros((max(total - 1, 0),), bool)
        eq = jnp.ones_like(lt)
        for w in words:
            a, b = w[:-1], w[1:]
            lt = lt | (eq & (a < b))
            eq = eq & (a == b)
        within = jnp.all(lt | eq | (pos[1:] == 0))   # skip shard seams

        # cross-shard: running lex-max of last-valid keys must not
        # exceed the next nonempty shard's first key (empty shards are
        # skipped by giving them MIN last / MAX first).
        first = tuple(lax.slice(w, (0,), ((n_ranks - 1) * slots + 1,),
                                (slots,)) for w in words)
        last_idx = starts + jnp.maximum(counts - 1, 0)
        last = tuple(jnp.take(w, last_idx) for w in words)
        empty = counts == 0
        first = tuple(jnp.where(empty, jnp.uint32(_U32), fw) for fw in first)
        last = tuple(jnp.where(empty, jnp.uint32(0), lw) for lw in last)

        def lex_max(a, b):
            keep_b = lex_lt(a, b)
            return tuple(jnp.where(keep_b, bw, aw) for aw, bw in zip(a, b))

        run = lax.associative_scan(lex_max, last)
        prev = tuple(r[:-1] for r in run)
        nxt = tuple(fw[1:] for fw in first)
        cross = jnp.all(~lex_lt(nxt, prev) | empty[1:])
        return within & cross, n_in, tuple(xors), tuple(sums)

    return jax.jit(program("verify_ragged", "verify", f))


@lru_cache(maxsize=16)
def _compile_encode_fp(dtype_name: str) -> "Callable[..., object]":
    """Fused device-side encode + fingerprint for raw (unencoded)
    device-resident input — the single-device local paths, whose sort
    programs fuse their own encode and never expose the words."""
    import jax
    import jax.numpy as jnp

    from mpitest_tpu.ops.keys import codec_for

    codec = codec_for(np.dtype(dtype_name))

    def f(x):
        words = codec.encode_jax(x)
        xors = tuple(_xor_reduce_1d(w) for w in words)
        sums = tuple(jnp.sum(w, dtype=jnp.uint32) for w in words)
        return xors, sums

    return jax.jit(program("verify_encode_fp", "verify", f))


def fingerprint_device_input(x: "jax.Array", dtype: "np.dtype | str",
                             tracer: "Tracer") -> Fingerprint:
    """Fingerprint of raw device-resident keys (encode fused in); the
    program runs under ``tracer``'s compile-vs-execute span."""
    xors, sums = traced_call(tracer, "verify_encode_fp",
                             _compile_encode_fp(np.dtype(dtype).name), x)
    return Fingerprint(int(x.size),
                       tuple(int(v) for v in xors),
                       tuple(int(s) for s in sums))


def fingerprint_device(words: "tuple[jax.Array, ...]", n_valid: int,
                       tracer: "Tracer") -> Fingerprint:
    """Input-side device fingerprint over a contiguous padded layout
    (one tiny fused reduction, one scalar sync)."""
    n_words = len(words)
    total = int(words[0].shape[0])
    _, xors, sums = traced_call(
        tracer, "verify_contig",
        _compile_contig(n_words, n_valid, total, False), *words)
    return Fingerprint(n_valid,
                       tuple(int(x) for x in xors),
                       tuple(int(s) for s in sums))


def verify_result(res: "DistributedSortResult",
                  input_fp: Fingerprint | None,
                  tracer: "Tracer") -> tuple[bool, bool]:
    """Verify a DistributedSortResult on device: returns
    ``(sorted_ok, fp_ok)``.  ``fp_ok`` is True when no input fingerprint
    is available (nothing to compare — sortedness still gates).  A
    result decoded on the device is checked through its keys
    (:func:`_compile_keys_contig`).  The programs run under
    ``tracer``'s compile-vs-execute spans."""
    n_words = len(res.words)
    if res.keys is not None:
        import jax

        rows = res.keys.ndim == 2
        total = int(res.keys.size) // (2 if rows else 1)
        with jax.enable_x64(True) if rows else contextlib.nullcontext():
            ok, xors, sums = traced_call(
                tracer, "verify_keys",
                _compile_keys_contig(np.dtype(res.dtype).name,
                                     min(res.n_valid, total), total),
                res.keys)
        n_in = res.n_valid
    elif res.counts is None:
        total = int(res.words[0].shape[0])
        ok, xors, sums = traced_call(
            tracer, "verify_contig",
            _compile_contig(n_words, min(res.n_valid, total), total, True),
            *res.words)
        n_in = res.n_valid
    else:
        n_ranks = len(res.counts)
        ok, n_in, xors, sums = traced_call(
            tracer, "verify_ragged",
            _compile_ragged(n_words, res.n_valid, res.shard_slots, n_ranks),
            np.asarray(res.counts, np.int32), *res.words)
    out_fp = Fingerprint(int(n_in),
                         tuple(int(x) for x in xors),
                         tuple(int(s) for s in sums))
    fp_ok = input_fp is None or out_fp == input_fp
    return bool(ok), fp_ok
