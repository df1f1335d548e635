"""Distributed LSD radix sort — device-resident, root-free, load-balanced.

The reference (``mpi_radix_sort.c:60-205``) runs, per digit: root scatters
the whole array, every rank buckets by digit value, buckets travel to the
rank *owning that digit* (rank = digit, radix = P), then everything gathers
back to root — O(N) bytes through rank 0 every pass, and digit ownership
means skewed data piles onto one rank.

The TPU design is different in three load-bearing ways:

1. **Keys never leave the mesh.**  The array stays sharded [P, n] across
   all passes; only 256-entry histograms are globally replicated
   (``all_gather``).  This removes the root bandwidth bottleneck
   (SURVEY.md §5 "long-context" row).

2. **Destination = global sorted position, not digit owner.**  Each pass
   computes, for every key, its exact global index in the digit-stable
   order:

       dest(key i, digit d) = digit_base[d] + rank_base[r, d] + occ_i

   where ``digit_base`` is the exclusive scan of global digit totals,
   ``rank_base`` the exclusive scan over ranks (the MPI_Exscan analogue),
   and ``occ_i`` the key's stable occurrence number locally.  Keys then
   move to ``dest // n`` — so every device ends every pass with *exactly*
   ``n`` keys, regardless of skew.  (The reference's per-pass root
   round-trip is what re-balances its shards; here balance is intrinsic.)

3. **8-bit digits, integer math.**  Digit width decouples from mesh size
   (the reference couples radix to P, ``mpi_radix_sort.c:64``) and digits
   are shift/mask, not ``pow()`` (``mpi_radix_sort.c:54-58``).

**One sort per pass.**  A naive receiver re-sorts the [P, cap] exchange
buffer by digit to merge it (a second full comparison sort every pass —
round-1 design, flagged by its review).  Here the receiver instead
*computes* each incoming lane's exact slot from information it already
has replicated — sender s's segment start toward me and the (base − lo)
step function of s's digit runs, all derived from the H matrix (see
:func:`_lane_slots`; everything is K-element scatters, row cumsums and
``searchsorted``, no per-element gathers) — and the next pass's single
``lax.sort`` keyed on ``(next_digit, slot)`` performs the pending merge
and the new digit grouping in one fused pass.  The pending merge of the
*last* pass is materialized by one 1-key sort on ``slot``.

Stability across ranks matches the reference's in-rank-order Recv loop
(``mpi_radix_sort.c:168-173``): ``slot`` IS the exact global position,
so output is bit-identical run to run — arrival order never matters.
"""

from __future__ import annotations

from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp
from jax import lax

from mpitest_tpu.ops import kernels
from mpitest_tpu.parallel import collectives as coll
from mpitest_tpu.parallel.mesh import AXIS
from mpitest_tpu.utils import spans

if TYPE_CHECKING:
    import contextlib

Words = tuple[jax.Array, ...]


def _pass_span(
    k: int, w_idx: int, shift: int, digit_bits: int, n: int, cap: int,
) -> "contextlib.AbstractContextManager[spans.Span | None]":
    """Trace-time span for one radix pass (utils/spans.py granularity
    contract): the collectives traced inside the pass body nest under
    it, so the SORT_TRACE stream shows pass → {all_gather, exchange}
    structure.  ``trace_time`` marks the dt as host tracing wall, not
    device execution (the fused program is one dispatch)."""
    return spans.maybe_span("radix_pass", pass_index=k, word=w_idx,
                            shift=shift, digit_bits=digit_bits, n=n,
                            cap=cap, trace_time=True)


def _lane_slots(recv_cnt: jax.Array, H: jax.Array, digit_base: jax.Array,
                rank_base: jax.Array, n: int, cap: int,
                axis: str) -> jax.Array:
    """Local output slot of every received lane, from replicated state.

    Lane (s, c) of the exchange buffer holds element ``j = j0[s] + c`` of
    sender s's digit-sorted shard, where ``j0[s]`` is the start of s's
    segment toward me.  Its pass destination is

        dest = base[s, d] + (j - lo[s, d]),   d = digit of the key,

    with ``base[s, d] = digit_base[d] + rank_base[s, d]`` (s's global run
    start for digit d) and ``lo[s, d]`` the run start *within* s's shard
    — both functions of the replicated H matrix, so nothing extra rides
    the wire.  Since lanes within a row arrive digit-sorted, the gather
    ``(base - lo)[s, d(c)]`` is a per-row step function whose run
    boundaries in lane space are ``lo[s, ·] - j0[s]`` — the digit values
    themselves are never touched: K-element scatter + cumsum
    (:func:`kernels.piecewise_fill`), never a per-element gather
    (10-40x a sort's cost on v5e; see ops/kernels.py).

    Returns int32 [P, cap]: local slot in [0, n) for valid lanes, ``n``
    for invalid ones.  Valid slots tile [0, n) exactly once — dest
    partitions the global key space and my block receives exactly n.
    """
    me = lax.axis_index(axis)
    n_ranks = H.shape[0]
    base = digit_base[None, :] + rank_base          # [P, bins]
    lo = coll.exclusive_cumsum(H, axis=1)           # [P, bins]
    # j0[s] = #{keys of s with dest < me*n} = sum_d clip(me*n - base, 0, H)
    j0 = jnp.clip(me * n - base, 0, H).sum(axis=1).astype(jnp.int32)  # [P]

    # Per-row step function of (base - lo) over the lane axis: run of
    # digit d occupies lanes [lo[s,d] - j0[s], lo[s,d+1] - j0[s]).
    starts = jnp.clip(lo - j0[:, None], 0, cap).astype(jnp.int32)     # [P, bins]
    values = (base - lo).astype(jnp.int32)                            # [P, bins]
    fill = jax.vmap(kernels.piecewise_fill, in_axes=(0, 0, None))(
        starts, values, cap
    )                                                                 # [P, cap]

    c = lax.iota(jnp.int32, cap)[None, :]
    slot = fill + j0[:, None] + c - me * n
    valid = c < recv_cnt[:, None]
    return jnp.where(valid, slot, n).astype(jnp.int32)


def _send_segments(sorted_dest: jax.Array, n: int,
                   n_ranks: int) -> tuple[jax.Array, jax.Array]:
    """Contiguous per-destination-device segments of the dest-monotone
    shard (dest strictly increasing ⇒ one segment per device)."""
    bounds = lax.iota(jnp.int32, n_ranks) * n
    send_start = jnp.searchsorted(sorted_dest, bounds, side="left").astype(jnp.int32)
    seg_end = jnp.concatenate([send_start[1:], jnp.asarray([n], jnp.int32)])
    return send_start, seg_end - send_start


def radix_probe_spmd(
    words: Words,
    digit_bits: int,
    n_ranks: int,
    axis: str = AXIS,
) -> jax.Array:
    """Capacity-negotiation count probe (ISSUE 7): the EXACT per-peer
    send counts of the first radix exchange, with zero key movement.

    Pass 1 always works on the least-significant digit of the
    least-significant word (the plan loop below), and its destination is
    the exact global digit-stable position — fully determined by the
    ``[P, bins]`` histogram matrix ``H``.  So one local digit histogram
    plus the same tiny histogram ``all_gather`` the real pass pays
    anyway yields, via :func:`collectives.block_send_counts`, the
    precise capacity the ``[P, cap]`` exchange buffer needs — before any
    buffer is allocated or any worst-case cap guessed.  (Later passes
    depend on the post-exchange arrangement; the supervisor's regrow
    loop remains the backstop for them.)

    The histogram rides a sort + binary search rather than a scatter-add
    (``kernels.histogram_sorted`` — scatter lowers to serialized updates
    on TPU, ~40x slower at scale).

    Returns int32[P, P], replicated: row r = counts rank r sends to each
    peer (self included — the self block occupies exchange lanes too).
    """
    n = words[0].shape[0]
    n_bins = 1 << digit_bits
    with spans.maybe_span("negotiate_probe", algorithm="radix",
                          ranks=n_ranks, n=n, trace_time=True):
        d = kernels.digit_at(words[-1], 0, digit_bits)
        h, _ = kernels.histogram_sorted(jnp.sort(d), n_bins)
        H = coll.all_gather(h, axis)                  # [P, bins]
        mine = coll.block_send_counts(H, n, axis)     # [P]
        return coll.all_gather(mine, axis)            # [P, P]


def radix_sort_spmd(
    words: Words,
    n_words: int,
    digit_bits: int,
    n_ranks: int,
    cap: int,
    passes: int | None = None,
    axis: str = AXIS,
    pack: str = "xla",
    exchange_engine: str = "lax",
) -> tuple[Words, jax.Array]:
    """Full multi-pass radix sort of the shard. SPMD; call under shard_map.

    ``passes`` limits the number of digit passes (host may have computed
    that high words are all-equal — the reference's ``number_digits``
    optimization, ``mpi_radix_sort.c:100``, done right).  Passes run from
    the least-significant digit of the least-significant word upward.

    ``exchange_engine`` (ISSUE 13) selects the per-pass exchange path:

    * ``"lax"`` — the original pass: after the fused sort, the n-element
      ``dest`` plane materializes (piecewise_fill + iota), segments come
      from ``searchsorted(dest)``, and the pack/transport ride
      :func:`collectives.ragged_all_to_all` with the ``pack`` impl.
    * ``"pallas"`` / ``"pallas_interpret"`` — the fused pass: segments
      come straight from the histogram's clip-arithmetic
      (:func:`collectives.block_send_segments` — histogram → exclusive
      scan → segments is [bins]-sized math, the dest plane and its two
      extra n-element HBM round-trips never exist), all key words pack
      in ONE fused kernel sweep, the transport is the remote-DMA kernel
      (``ops/exchange.py``), and the **overlap loop** double-buffers:
      pass k+1's lane-slot (scatter) plane is computed via the
      ``pre_exchange`` hook while pass k's bucket sends are still in
      flight — it depends only on the tiny count exchange + replicated
      H state, never on the payload DMAs.  Both engines are
      bit-identical by construction (same sorts, same segment values,
      same fill contract); the parity gates pin it.

    Returns ``(sorted_words, max_send_cnt_over_passes)`` — the second value
    > cap means an exchange overflowed and the host must retry with at
    least that cap (an overflowed pass corrupts later passes, so the
    reported value is a lower bound; the host loop grows the cap
    monotonically until no pass overflows).
    """
    from mpitest_tpu.ops import exchange as xeng

    n = words[0].shape[0]
    n_bins = 1 << digit_bits
    my = lax.axis_index(axis)
    per_word = (32 + digit_bits - 1) // digit_bits
    total = per_word * n_words if passes is None else passes
    max_cnt = jnp.zeros((), jnp.int32)
    fused = xeng.is_pallas(exchange_engine)

    plan = []  # (word_idx, shift), lsw first
    for w_idx in range(n_words - 1, -1, -1):
        for p in range(per_word):
            if len(plan) < total:
                plan.append((w_idx, p * digit_bits))

    if not plan:
        return words, max_cnt

    # recv-buffer state between exchanges; None before the first pass.
    recv: Words | None = None
    recv_cnt = None
    prev = None  # lax engine: (H, digit_base, rank_base) of the pending exchange
    slot_carry = None  # pallas engine: the overlapped lane-slot plane

    for k, (w_idx, shift) in enumerate(plan):
        with _pass_span(k + 1, w_idx, shift, digit_bits, n, cap):
            if recv is None:
                # First pass: the flat shard is trivially "merged"; one
                # stable 1-key sort groups by digit (stability = position
                # order, exactly the (digit, slot) key of later passes).
                d = kernels.digit_at(words[w_idx], shift, digit_bits)
                ops = lax.sort([d] + list(words), num_keys=1,
                               is_stable=True)
                sd, sorted_words = ops[0], tuple(ops[1:])
            else:
                # Fused pass: merge the pending exchange buffer AND group by
                # the new digit with ONE sort keyed on (digit, slot) — the
                # pair is unique per valid lane, so no stability needed.
                # Under the pallas engine the slot plane was already
                # computed while the previous exchange's DMAs were in
                # flight (the pre_exchange hook below).
                slot = slot_carry if fused else \
                    _lane_slots(recv_cnt, *prev, n, cap, axis)
                d = kernels.digit_at(recv[w_idx], shift, digit_bits)
                c = lax.iota(jnp.int32, cap)[None, :]
                d = jnp.where(c < recv_cnt[:, None], d, n_bins)
                ops = lax.sort(
                    [d.reshape(-1), slot.reshape(-1)] + [r.reshape(-1) for r in recv],
                    num_keys=2, is_stable=False,
                )
                # Valid lanes total exactly n and sort to the front (invalid
                # carry the n_bins sentinel digit).
                sd = ops[0][:n]
                sorted_words = tuple(o[:n] for o in ops[2:])

            # Histogram + first-occurrence offsets from the sorted digits.
            h, lo_local = kernels.histogram_sorted(sd, n_bins)
            H, tot, rank_base = coll.exscan_counts(h, axis)
            digit_base = coll.exclusive_cumsum(tot)
            base = digit_base + rank_base[my]      # [bins] my global run starts

            if fused:
                # Fused pass (ISSUE 13): segments from [bins]-sized clip
                # arithmetic — no n-element dest plane — and the next
                # pass's scatter half precomputed during the DMA window.
                send_start, send_cnt = coll.block_send_segments(
                    h, base, n, n_ranks)

                def _pre(rc: jax.Array, H: jax.Array = H,
                         db: jax.Array = digit_base,
                         rb: jax.Array = rank_base) -> jax.Array:
                    return _lane_slots(rc, H, db, rb, n, cap, axis)

                recv, recv_cnt, mc, slot_carry = coll.ragged_all_to_all(
                    sorted_words, send_start, send_cnt, cap, n_ranks,
                    axis, pack=pack, engine=exchange_engine,
                    pre_exchange=_pre,
                )
            else:
                # dest[j] = base[sd[j]] + (j - lo[sd[j]]) — gather-free
                # step fn.
                dest = kernels.piecewise_fill(
                    lo_local, base - lo_local, n) + lax.iota(jnp.int32, n)
                send_start, send_cnt = _send_segments(dest, n, n_ranks)

                recv, recv_cnt, mc = coll.ragged_all_to_all(
                    sorted_words, send_start, send_cnt, cap, n_ranks,
                    axis, pack=pack,
                )
                prev = (H, digit_base, rank_base)
            max_cnt = jnp.maximum(max_cnt, mc)

    # Materialize the last pass's pending merge: one 1-key sort on slot.
    slot = slot_carry if fused else _lane_slots(recv_cnt, *prev, n, cap, axis)
    flat = lax.sort(
        [slot.reshape(-1)] + [r.reshape(-1) for r in recv],
        num_keys=1, is_stable=False,
    )
    out_words = tuple(o[:n] for o in flat[1:])
    return out_words, max_cnt
