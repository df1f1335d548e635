"""Fused radix-pass and merge-order Pallas kernels (local-sort engine #3).

Two kernels, both gated behind ``SORT_LOCAL_ENGINE=radix_pallas``:

* :func:`fused_radix_sort` — LSD radix sort where **one pass is one
  ``pallas_call``**: the digit histogram, the exclusive prefix (bucket
  bases), the per-element rank and the stable scatter all happen inside
  a single kernel over VMEM-resident word planes, replacing the
  ``lax.sort`` / ``searchsorted`` / ``gather`` chain of HBM round-trips
  the lax engine lowers to.  Pass *count* is planner-driven: the pass
  plan is computed on host from per-word value ranges
  (:func:`pass_plan`), so a range-narrow input (e.g. 20 significant
  bits in an int64) sorts in fewer, narrower passes.

* :func:`merge_order` — the device inner loop of the external sort's
  k-way merge: given the lexicographic key planes of one bounded merge
  round it returns the permutation that sorts them, bit-identical to
  the host ``np.lexsort`` it replaces.  The bounded read-ahead and
  safe-boundary logic stay on host in ``store/merge.py``; only the
  rank-by-comparison inner loop runs on device.

Honesty notes (mirrors ops/exchange.py): this engine has only ever run
under ``interpret=True`` on CPU — Mosaic has never lowered it on a real
TPU, so the first TPU-capable session must re-baseline (see PARITY.md).
The fused kernel keeps every word plane as an (n_pad, 1) VMEM ref and
its scatter loop is serial over each chunk; on real hardware the VMEM
footprint caps n well below :data:`FUSED_MAX_ELEMS` per core and the
scatter wants a DMA formulation — both are flagged TPU follow-ups, the
win this image can certify is pass-count and launch-count reduction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpitest_tpu.utils.programs import program, tagged

Words = tuple[jax.Array, ...]

#: Digit width of one fused pass.  Bins per kernel = 2**bits + 1: the
#: extra bin is the *pad bin* — padding rows are binned by index, not
#: value, so compacted pass plans that skip constant high bits can
#: never interleave pads with real keys.
DIGIT_BITS = 8

#: Rows per in-kernel chunk of the histogram / rank / scatter loops.
#: Multiple of the (8, 128) native tile's sublane count.
SORT_CHUNK = 512

#: Fused-engine element cap.  Every word plane lives in VMEM as an
#: (n_pad, 1) ref for the whole pass, so ~16 MiB VMEM bounds n_words *
#: n_pad * 4 B; beyond this the resolver falls back to the lax engine.
FUSED_MAX_ELEMS = 1 << 20

#: Widest key (in u32 words) the fused engine accepts.
FUSED_MAX_WORDS = 4

#: Merge-order element cap per merge round.  The rank kernel is
#: O(n^2) compares; above this the host lexsort is the better engine
#: even on TPU, and under interpret the quadratic cost bites early.
MERGE_MAX_ELEMS = 1 << 12

#: Rows per chunk of the merge-order rank loop.
MERGE_CHUNK = 256

#: Smallest padded size the merge kernel compiles for; sizes bucket up
#: to the next power of two so the jit cache stays small across the
#: varying window sizes merge rounds produce.
_MERGE_MIN_PAD = 256

_PAD_WORD = 0xFFFFFFFF

#: Trace-time launch counter: incremented once per fused-pass
#: ``pallas_call`` *trace*.  The launch-count acceptance gate compiles
#: a fresh shape and asserts the delta equals the pass-plan length —
#: one kernel launch per pass, no hidden sort/gather chain.
_PASS_LAUNCHES = 0


def pass_launches() -> int:
    """Return the number of fused-pass kernels traced so far."""
    return _PASS_LAUNCHES


def pass_plan(diffs: tuple[int, ...] | None,
              n_words: int,
              digit_bits: int = DIGIT_BITS,
              ) -> tuple[tuple[int, int, int], ...]:
    """Plan the fused passes for a key whose per-word value ranges are
    known.

    ``diffs`` is msw-first (``diffs[0]`` is the most significant word),
    each entry the XOR-fold / max-min spread of that word — the same
    shape ``models/api.py`` feeds ``_passes_from_diffs``.  ``None``
    means "unknown": plan full-width passes for every word.

    Returns ``((word_idx, shift, bits), ...)`` in execution order
    (least-significant word first — LSD radix), where ``bits`` may be
    narrower than ``digit_bits`` on the top pass of a word.  Words
    whose range is constant are skipped entirely: that is the
    key-width-compaction win.
    """
    if diffs is None:
        diffs = (_PAD_WORD,) * n_words
    if len(diffs) != n_words:
        raise ValueError(
            f"pass_plan: {len(diffs)} diffs for {n_words} words")
    plan: list[tuple[int, int, int]] = []
    for wi in range(n_words - 1, -1, -1):       # lsw -> msw
        width = int(diffs[wi]).bit_length()
        shift = 0
        while shift < width:
            bits = min(digit_bits, width - shift)
            plan.append((wi, shift, bits))
            shift += bits
    return tuple(plan)


def _pass_kernel(n: int, n_words: int, widx: int, shift: int, bits: int,
                 chunk: int, *refs) -> None:
    """One radix pass: histogram -> exclusive prefix -> stable scatter.

    ``refs`` = n_words input planes, n_words output planes, then one
    (chunk, 1) int32 scratch; every plane is (n_pad, 1) uint32 in VMEM.
    Rows at index >= n are pads and are forced into the extra bin
    ``bins`` regardless of content, so they sit stably at the tail of
    every pass and real rows keep the invariant "reals in [0, n)".
    """
    in_refs = refs[:n_words]
    out_refs = refs[n_words:2 * n_words]
    dest_scr = refs[2 * n_words]
    n_pad = in_refs[0].shape[0]
    nchunks = n_pad // chunk
    bins = 1 << bits
    mask = jnp.uint32(bins - 1)
    bin_iota = lax.broadcasted_iota(jnp.int32, (1, bins + 1), 1)

    def onehot(c):
        w = in_refs[widx][pl.ds(c * chunk, chunk), :]
        d = ((w >> jnp.uint32(shift)) & mask).astype(jnp.int32)
        row = c * chunk + lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
        d = jnp.where(row < n, d, bins)
        return (d == bin_iota).astype(jnp.int32)        # (chunk, bins+1)

    def hist_body(c, h):
        return h + jnp.sum(onehot(c), axis=0, keepdims=True)

    hist = lax.fori_loop(
        0, nchunks, hist_body, jnp.zeros((1, bins + 1), jnp.int32))
    base = jnp.cumsum(hist, axis=1) - hist              # exclusive

    def scatter_body(c, seen):
        oh = onehot(c)
        # Rank within the chunk among equal digits (stable), then add
        # the bucket base plus the count already scattered by earlier
        # chunks ("seen").
        prior = jnp.cumsum(oh, axis=0) - oh
        dest_scr[...] = jnp.sum(
            oh * (base + seen + prior), axis=1, keepdims=True)

        def store(j, carry):
            dst = dest_scr[j, 0]
            src = c * chunk + j
            for w_in, w_out in zip(in_refs, out_refs):
                w_out[dst, 0] = w_in[src, 0]
            return carry

        lax.fori_loop(0, chunk, store, 0)
        return seen + jnp.sum(oh, axis=0, keepdims=True)

    lax.fori_loop(
        0, nchunks, scatter_body, jnp.zeros((1, bins + 1), jnp.int32))


def _fused_pass(planes: Words, n: int, widx: int, shift: int, bits: int,
                interpret: bool) -> Words:
    """Run ONE radix pass as ONE ``pallas_call`` over padded planes."""
    global _PASS_LAUNCHES
    _PASS_LAUNCHES += 1
    n_words = len(planes)
    n_pad = planes[0].shape[0]
    out = pl.pallas_call(
        functools.partial(
            _pass_kernel, n, n_words, widx, shift, bits, SORT_CHUNK),
        out_shape=[jax.ShapeDtypeStruct((n_pad, 1), jnp.uint32)
                   for _ in range(n_words)],
        scratch_shapes=[pltpu.VMEM((SORT_CHUNK, 1), jnp.int32)],
        name="radix_pallas.pass",
        interpret=interpret,
    )(*planes)
    return tuple(out)


@tagged("local_sort")
def fused_radix_sort(words: Words,
                     diffs: tuple[int, ...] | None = None,
                     digit_bits: int = DIGIT_BITS,
                     interpret: bool = False) -> Words:
    """Sort u32 word planes lexicographically (words[0] most
    significant) with one fused kernel launch per radix pass.

    Bit-identical to ``lax.sort(words, num_keys=len(words))`` for any
    ``diffs`` that covers the data (``None`` always does): each pass is
    a stable counting sort by the planned digit, and constant bits
    never discriminate.  ``diffs`` must be host-static — the planner
    derives it from the profiler's per-word min/max.
    """
    n_words = len(words)
    n = int(words[0].shape[0])
    plan = pass_plan(diffs, n_words, digit_bits)
    if n <= 1 or not plan:
        # Zero/one element, or every word constant: already sorted.
        return words
    n_pad = -(-n // SORT_CHUNK) * SORT_CHUNK
    pad = n_pad - n
    if pad:
        fill = jnp.full((pad,), _PAD_WORD, jnp.uint32)
        planes = tuple(jnp.concatenate([w, fill]).reshape(n_pad, 1)
                       for w in words)
    else:
        planes = tuple(w.reshape(n_pad, 1) for w in words)
    for widx, shift, bits in plan:
        planes = _fused_pass(planes, n, widx, shift, bits, interpret)
    return tuple(p.reshape(-1)[:n] for p in planes)


# ---------------------------------------------------------------------
# Device merge-order kernel (external sort / store compaction inner loop)
# ---------------------------------------------------------------------


def _cmp_i32(x: jax.Array) -> jax.Array:
    """Order-preserving u32 -> i32 bijection (sign-flip + bitcast).

    Mosaic has no unsigned vector compare; flipping the sign bit and
    comparing as int32 yields the unsigned order.
    """
    return lax.bitcast_convert_type(x ^ jnp.uint32(0x80000000), jnp.int32)


def _order_kernel(n_planes: int, chunk: int, *refs) -> None:
    """Rank-by-comparison merge order: rank[i] = #{j : key_j < key_i},
    lexicographic over ``n_planes`` planes (plane 0 most significant).

    ``refs`` = n_planes column planes (n_pad, 1), the SAME n_planes
    planes again in row layout (1, n_pad) — passed twice from host to
    avoid an in-kernel transpose — then the (n_pad, 1) int32 order
    output and a (chunk, 1) int32 rank scratch.  Keys must be unique
    (the caller appends run-id and position tie-breaker planes), so
    ranks form a permutation and every output row is written once.
    """
    cols = refs[:n_planes]
    rows = refs[n_planes:2 * n_planes]
    out_ref = refs[2 * n_planes]
    rank_scr = refs[2 * n_planes + 1]
    n_pad = cols[0].shape[0]
    nchunks = n_pad // chunk

    def body(c, carry):
        lt = None
        eq = None
        for colr, rowr in zip(cols, rows):
            a = _cmp_i32(colr[pl.ds(c * chunk, chunk), :])  # (chunk, 1)
            b = _cmp_i32(rowr[...])                         # (1, n_pad)
            p_lt = b < a
            if lt is None:
                lt, eq = p_lt, (b == a)
            else:
                lt = lt | (eq & p_lt)
                eq = eq & (b == a)
        rank_scr[...] = jnp.sum(lt.astype(jnp.int32), axis=1,
                                keepdims=True)

        def store(j, k):
            # order[rank_i] = i : scatter this chunk's global indices.
            out_ref[rank_scr[j, 0], 0] = c * chunk + j
            return k

        lax.fori_loop(0, chunk, store, 0)
        return carry

    lax.fori_loop(0, nchunks, body, 0)


@functools.lru_cache(maxsize=32)
def _compile_merge_order(n_planes: int, n_pad: int, interpret: bool):
    """jit-compiled merge-order entry for one (plane count, padded
    size) bucket; the pallas_call sits behind the literal ``interpret``
    parameter (SL013)."""

    def run(*planes):
        cols = tuple(p.reshape(n_pad, 1) for p in planes)
        rows = tuple(p.reshape(1, n_pad) for p in planes)
        order = pl.pallas_call(
            functools.partial(_order_kernel, n_planes, MERGE_CHUNK),
            out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
            scratch_shapes=[pltpu.VMEM((MERGE_CHUNK, 1), jnp.int32)],
            name="radix_pallas.merge_order",
            interpret=interpret,
        )(*cols, *rows)
        return order.reshape(-1)

    return jax.jit(program("merge_order", "local_sort", run))


def merge_order(planes: Words, interpret: bool = False) -> jax.Array:
    """Return the int32 permutation that sorts ``planes``
    lexicographically (plane 0 most significant).

    Device twin of ``np.lexsort((planes[-1], ..., planes[0]))`` —
    bit-identical when keys are unique, which ``store/merge.py``
    guarantees by appending (run id, position) tie-breaker planes.
    The LAST plane must never legitimately hold 0xFFFFFFFF (positions
    and run ids are small), because pads claim that value and stay
    unique via an iota in the final plane.
    """
    n_planes = len(planes)
    n = int(planes[0].shape[0])
    if n > MERGE_MAX_ELEMS:
        raise ValueError(
            f"merge_order: n={n} above MERGE_MAX_ELEMS={MERGE_MAX_ELEMS}"
            " — O(n^2) ranking; use the host lexsort")
    if n <= 1:
        return jnp.zeros((n,), jnp.int32)
    n_pad = _MERGE_MIN_PAD
    while n_pad < n:
        n_pad *= 2
    pad = n_pad - n
    if pad:
        hi = jnp.full((pad,), _PAD_WORD, jnp.uint32)
        # Pads outrank every real key on the leading planes; the final
        # plane's iota keeps them mutually distinct so the rank image
        # is a full permutation.
        tie = jnp.arange(pad, dtype=jnp.uint32)
        padded = tuple(
            jnp.concatenate([jnp.asarray(p, jnp.uint32),
                             tie if i == n_planes - 1 else hi])
            for i, p in enumerate(planes))
    else:
        padded = tuple(jnp.asarray(p, jnp.uint32) for p in planes)
    order = _compile_merge_order(n_planes, n_pad, interpret)(*padded)
    return order[:n]
