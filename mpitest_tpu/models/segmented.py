"""Segmented (multi-tenant) batched sort — the pack/dispatch/split core.

The sort server's batching layer (ISSUE 8) packs many concurrent small
requests into ONE device dispatch: per-request dispatch overhead (host
→device staging, program launch, the result sync) dominates small-sort
latency, and a persistent server seeing heavy small-request traffic
amortizes it by sorting many tenants' keys in a single fused program.

Mechanism — segment-ID-prefixed keys: request ``i``'s keys encode
through the ordinary order-preserving codec (``ops/keys.py``) into
uint32 words, and a constant extra word holding the segment id ``i`` is
prepended as the MOST significant word.  A lexicographic sort of the
``(seg, *key_words)`` tuples therefore orders first by segment, then by
key — i.e. it sorts every segment independently in one pass, and each
segment's slice of the output is **bit-identical** to sorting that
request alone (same codec, same comparison; the tests pin this parity
against :func:`mpitest_tpu.models.api.sort`).  Pad lanes carry segment
id ``PAD_SEG`` (the uint32 maximum, above any real id) so they sort to
the global tail past every tenant.

Shapes are power-of-two **buckets** (:func:`bucket_for`): the packed
program is compiled per (word count, bucket), so any mix of request
sizes whose total lands in the same bucket reuses one executable — the
executor cache (``serve/executor_cache.py``) AOT-compiles and memoizes
exactly these.

Verification is per segment, host-side (batches are small by
construction — ``SORT_SERVE_BATCH_KEYS`` caps the packed size): each
segment must be lexicographically sorted AND reproduce the input-side
multiset fingerprint folded at pack time.  A segment that fails (e.g. a
poisoned request, or an injected result fault) is re-run solo under the
PR 3 supervisor by the server — the other tenants' results are already
proven good, so one bad request can never poison its batchmates.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache
from typing import Any, Callable, Sequence

import numpy as np

from mpitest_tpu.models.verify import Fingerprint, fingerprint_host
from mpitest_tpu.ops.keys import KeyCodec, codec_for
from mpitest_tpu.utils.programs import program

#: Segment id of pad lanes — the uint32 maximum, strictly above any real
#: segment id (the batcher caps segments per batch far below it), so
#: pads sort to the global tail past every tenant's keys.
PAD_SEG = 0xFFFFFFFF

#: Smallest bucket: below this the compile zoo costs more than the
#: padding wastes (a 1024-lane uint32 word is 4 KiB).
MIN_BUCKET = 1 << 10


def bucket_for(n: int, min_bucket: int = MIN_BUCKET) -> int:
    """Power-of-two shape bucket for ``n`` packed lanes: the smallest
    power of two >= max(n, min_bucket).  Bucketing is what turns an
    unbounded family of request shapes into a handful of compiled
    executables — warm traffic never compiles."""
    if n < 0:
        raise ValueError(f"bucket_for: negative size {n}")
    b = max(int(min_bucket), 1)
    # next power of two >= max(n, min_bucket)
    target = max(n, b)
    return 1 << (target - 1).bit_length() if target > 1 else 1


@dataclass(frozen=True)
class PackedBatch:
    """One packed multi-tenant batch, host-side: the ``(seg, *words)``
    uint32 arrays (padded to ``bucket``), per-segment geometry, and the
    per-segment input fingerprints the post-sort verification compares
    against."""

    words: tuple[np.ndarray, ...]      # (1 + n_words) uint32, len bucket
    sizes: tuple[int, ...]             # per-segment key counts
    offsets: tuple[int, ...]           # per-segment start lane
    fps: tuple[Fingerprint, ...]       # per-segment input fold (key words)
    dtype: np.dtype
    bucket: int

    @property
    def n_valid(self) -> int:
        return int(sum(self.sizes))

    @property
    def n_segments(self) -> int:
        return len(self.sizes)


def pack_segments(arrays: Sequence[np.ndarray], dtype: np.dtype,
                  bucket: int | None = None) -> PackedBatch:
    """Encode + pack request key arrays into one segment-prefixed word
    tuple padded to a shape bucket.  All arrays must share ``dtype``;
    the segment order is the argument order (and the split order)."""
    codec: KeyCodec = codec_for(dtype)
    if len(arrays) >= PAD_SEG:
        raise ValueError(f"too many segments ({len(arrays)})")
    sizes = tuple(int(a.size) for a in arrays)
    total = sum(sizes)
    if bucket is None:
        bucket = bucket_for(total)
    if total > bucket:
        raise ValueError(f"segments hold {total} keys > bucket {bucket}")
    offsets = tuple(int(v) for v in np.cumsum((0,) + sizes)[:-1])

    seg = np.full(bucket, PAD_SEG, np.uint32)
    key_words = tuple(np.zeros(bucket, np.uint32)
                      for _ in range(codec.n_words))
    fps = []
    for i, a in enumerate(arrays):
        flat = np.asarray(a, dtype=dtype).reshape(-1)
        w = codec.encode(flat)
        lo, hi = offsets[i], offsets[i] + sizes[i]
        seg[lo:hi] = np.uint32(i)
        for dst, src in zip(key_words, w):
            dst[lo:hi] = src
        fps.append(fingerprint_host(w))
    return PackedBatch((seg,) + key_words, sizes, offsets, tuple(fps),
                       np.dtype(dtype), bucket)


@lru_cache(maxsize=64)
def compile_packed_sort(n_words_total: int,
                        bucket: int) -> Callable[..., Any]:
    """AOT-compile the packed-batch program: one fused lexicographic
    sort of ``n_words_total`` uint32 word arrays of length ``bucket``.
    Returns the compiled executable (``jit(...).lower(...).compile()``),
    so a warm call never touches the compiler.  lru-cached
    process-wide; the server's
    :class:`~mpitest_tpu.serve.executor_cache.ExecutorCache` layers
    per-server hit/miss telemetry and prewarm on top.

    Two lowerings, same bytes out:

    * ``n_words_total == 2`` (segment word + a 1-word codec — the int32
      /uint32/f32 small-request common case): the two words fuse into
      ONE uint64 ``(seg << 32) | key`` and sort as a single key —
      XLA:CPU's multi-operand sort runs a per-pair comparator call and
      measured 2-4x slower than the single-key form at batch sizes
      (28.4 vs 7.5 ms at 2^16 lanes); the u64 order is identical to the
      lexicographic (seg, key) order by construction.  The program is
      *lowered* under a scoped ``enable_x64`` (u64 is otherwise
      unavailable); inputs and outputs stay uint32, so callers never
      see the flag.
    * wider keys: the variadic ``ops/kernels.local_sort`` (the segment
      word is just the most significant key word).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mpitest_tpu.ops import kernels

    specs = tuple(jax.ShapeDtypeStruct((bucket,), jnp.uint32)
                  for _ in range(n_words_total))
    if n_words_total == 2:
        def f2(seg: Any, key: Any) -> Any:
            u = ((seg.astype(jnp.uint64) << np.uint64(32))
                 | key.astype(jnp.uint64))
            s = lax.sort([u], num_keys=1, is_stable=False)[0]
            return ((s >> np.uint64(32)).astype(jnp.uint32),
                    s.astype(jnp.uint32))

        with jax.enable_x64(True):
            return jax.jit(program("segmented_sort", "local_sort", f2)
                           ).lower(*specs).compile()

    def f(*words: Any) -> Any:
        return kernels.local_sort(words)

    return jax.jit(program("segmented_sort", "local_sort", f)
                   ).lower(*specs).compile()


def executable_stats(exe: Any) -> dict[str, float]:
    """XLA cost/compile statistics of an AOT-compiled executable —
    flops, bytes accessed, generated code size — recorded into the
    ``serve.compile_cache`` miss event at compile time (ISSUE 10
    device profiling hook).  Every probe is best-effort: the
    cost-analysis surface varies across jax versions and backends, and
    telemetry must never fail a compile that succeeded."""
    out: dict[str, float] = {}
    try:
        ca = exe.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if isinstance(ca, dict):
            for src, dst in (("flops", "flops"),
                             ("bytes accessed", "bytes_accessed")):
                v = ca.get(src)
                if v is not None and float(v) >= 0:
                    out[dst] = float(v)
    except Exception:  # noqa: BLE001 — version-dependent surface
        pass
    try:
        ma = exe.memory_analysis()
        v = getattr(ma, "generated_code_size_in_bytes", None)
        if v is not None:
            out["code_bytes"] = float(v)
    except Exception:  # noqa: BLE001
        pass
    return out


def run_packed(batch: PackedBatch,
               executable: Callable[..., Any] | None = None,
               ) -> tuple[np.ndarray, ...]:
    """Dispatch the packed batch (through ``executable`` when the caller
    holds a cache entry, else the shared compiled program) and return
    the sorted words on the host."""
    fn = executable if executable is not None else \
        compile_packed_sort(len(batch.words), batch.bucket)
    out = fn(*batch.words)
    return tuple(np.asarray(w) for w in out)


def lex_sorted_host(words: Sequence[np.ndarray]) -> bool:
    """Host-side lexicographic non-decreasing check over word arrays
    (msw first) — the batch verifier's sortedness half."""
    n = int(words[0].size)
    if n < 2:
        return True
    lt = np.zeros(n - 1, bool)
    eq = np.ones(n - 1, bool)
    for w in words:
        a, b = w[:-1], w[1:]
        lt |= eq & (a < b)
        eq &= a == b
    return bool(np.all(lt | eq))


def split_segments(batch: PackedBatch,
                   sorted_words: tuple[np.ndarray, ...],
                   ) -> list[np.ndarray]:
    """Decode each segment's slice of the sorted packed words back to
    its tenant's native-dtype sorted array.  Segment ``i`` occupies
    lanes ``[offsets[i], offsets[i] + sizes[i])`` — the sort is keyed on
    the segment word first, so every segment's keys land contiguously in
    segment-id order, sizes unchanged."""
    codec = codec_for(batch.dtype)
    out = []
    for lo, size in zip(batch.offsets, batch.sizes):
        segs = tuple(w[lo:lo + size] for w in sorted_words[1:])
        out.append(codec.decode(segs))
    return out


def verify_segments(batch: PackedBatch,
                    sorted_words: tuple[np.ndarray, ...],
                    ) -> list[bool]:
    """Per-segment verification of a sorted packed batch: the segment
    word must be exactly the packed segment layout (ids in order, pads
    at the tail), each segment's key words lexicographically sorted, and
    each segment's multiset fingerprint equal to its input-side fold.
    Returns one verdict per segment — a poisoned tenant flags ONLY its
    own segment."""
    seg_out = sorted_words[0]
    verdicts = []
    for i, (lo, size) in enumerate(zip(batch.offsets, batch.sizes)):
        ok = bool(np.all(seg_out[lo:lo + size] == np.uint32(i)))
        key_segs = tuple(w[lo:lo + size] for w in sorted_words[1:])
        ok = ok and lex_sorted_host(key_segs)
        ok = ok and fingerprint_host(key_segs) == batch.fps[i]
        verdicts.append(ok)
    return verdicts
