"""The comm layer: XLA collectives shaped like the reference's MPI surface.

This is the Python twin of the native ``comm/comm.h`` shim (SURVEY.md §2.3
maps every ``MPI_*`` call to its TPU-native equivalent).  Everything here is
meant to be called *inside* a ``shard_map``-ed function over the 1-D mesh
axis; all shapes are static, so the whole SPMD program compiles to one XLA
executable with collectives scheduled on ICI.

The centerpiece is :func:`ragged_all_to_all` — the replacement for the
reference's hand-rolled ``MPI_Alltoallv`` (payload length smuggled in the
message tag, ``mpi_sample_sort.c:159-171``; per-peer Isend/Recv loops,
``mpi_radix_sort.c:150-173``).  XLA's ``all_to_all`` is fixed-shape, so
variable buckets ride a static per-peer cap with explicit counts — which
*legitimizes* the reference's own fixed ``max_size_bucket``-plus-length-
in-tag scheme, minus the tag hack and minus the silent overflow
(``mpi_sample_sort.c:140-144``): overflow is detected and reported so the
host can retry with the exact required cap (see models/api.py).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

from mpitest_tpu import faults
from mpitest_tpu.parallel.mesh import AXIS
from mpitest_tpu.utils import spans

Words = tuple[jax.Array, ...]


def _emit_collective(name: str, x: jax.Array, axis: str,
                     **attrs: object) -> None:
    """Trace-time telemetry: one point event per collective per compile,
    with the static byte accounting (see utils/spans.py on why device
    collectives are trace-time events, not host-timed spans).  ``bytes``
    is the per-rank payload entering the collective; ``bytes_out`` the
    per-rank result size where the axis size is statically known."""
    log = spans.current_log()
    if log is None:
        return
    b_in = int(x.size) * x.dtype.itemsize
    P_ = int(lax.axis_size(axis))
    attrs.setdefault("ranks", P_)
    if name == "all_gather":
        attrs.setdefault("bytes_out", b_in * P_)
    # sortlint: disable=SL003 -- name is each wrapper's literal (all registered)
    log.event(name, bytes=b_in, axis=axis, **attrs)


def rank(axis: str = AXIS) -> jax.Array:
    """``MPI_Comm_rank`` → ``lax.axis_index`` (traced scalar)."""
    return lax.axis_index(axis)


def all_gather(x: jax.Array, axis: str = AXIS) -> jax.Array:
    """``MPI_Allgather`` (and the gather-to-root patterns): every shard gets
    [P, ...] — strictly more than MPI's rooted Gather gives, for free."""
    _emit_collective("all_gather", x, axis)
    return lax.all_gather(x, axis)


def psum(x: jax.Array, axis: str = AXIS) -> jax.Array:
    """``MPI_Allreduce(SUM)``."""
    _emit_collective("psum", x, axis, op="sum")
    return lax.psum(x, axis)


def pmax(x: jax.Array, axis: str = AXIS) -> jax.Array:
    _emit_collective("pmax", x, axis, op="max")
    return lax.pmax(x, axis)


def exclusive_cumsum(x: jax.Array, axis: int = 0) -> jax.Array:
    """Exclusive prefix sum — the root-side displacement computation
    (``mpi_sample_sort.c:188-192``) done replicated on-device."""
    c = jnp.cumsum(x, axis=axis)
    return c - x  # exclusive


def block_send_counts(H: jax.Array, n: int, axis: str = AXIS) -> jax.Array:
    """MY per-destination-block send counts, from the replicated histogram
    alone — the cheap pre-exchange behind capacity negotiation (ISSUE 7).

    Under the "destination = exact global position" repartition (the
    radix pass contract, models/radix_sort.py), my keys of digit ``d``
    occupy global positions ``[base[d], base[d] + H[me, d])`` where
    ``base[d] = digit_base[d] + rank_base[me, d]``.  The number of my
    keys landing in destination block s — ``[s·n, (s+1)·n)`` — is then a
    sum of clipped interval intersections over digits: pure arithmetic
    on the ``[P, bins]`` ``H`` matrix every rank already holds after the
    tiny histogram ``all_gather``.  No key moves; the full per-peer
    requirement of the upcoming ragged exchange is known *before* any
    ``[P, cap]`` buffer is allocated, so the host can compile with the
    exact capacity instead of a worst-case guess.

    Returns int32[P]: exact counts this rank will send to each peer
    (self included — the self block never crosses a link but still
    occupies exchange-buffer lanes).
    """
    me = lax.axis_index(axis)
    n_ranks = H.shape[0]
    tot = H.sum(axis=0)                          # [bins]
    digit_base = exclusive_cumsum(tot)           # [bins]
    rank_base = exclusive_cumsum(H, 0)           # [P, bins]
    base = digit_base + rank_base[me]            # [bins] my global run starts
    bounds = lax.iota(jnp.int32, n_ranks + 1) * n
    # cum[s] = #{my keys with dest < s*n} = Σ_d clip(s*n - base[d], 0, H[me, d])
    cum = jnp.clip(bounds[:, None] - base[None, :], 0, H[me][None, :]).sum(
        axis=1)
    return (cum[1:] - cum[:-1]).astype(jnp.int32)


def block_send_segments(h: jax.Array, base: jax.Array, n: int,
                        n_ranks: int) -> tuple[jax.Array, jax.Array]:
    """Contiguous per-destination send segments of MY digit-sorted
    shard, straight from the histogram + its global bases — the fused
    pallas-pass form of :func:`radix_sort._send_segments` (ISSUE 13).

    Under the dest = exact-global-position contract, my keys of digit
    ``d`` occupy global positions ``[base[d], base[d] + h[d])`` and my
    shard is dest-monotone, so the number of my keys landing before
    block boundary ``s·n`` is ``cum[s] = Σ_d clip(s·n − base[d], 0,
    h[d])`` — the same clipped-interval sum as :func:`block_send_counts`
    but anchored at MY ``base`` (= ``digit_base + rank_base[me]``).
    ``send_start[p] = cum[p]`` equals the lax engine's
    ``searchsorted(dest, p·n)`` bit for bit, with **no n-element dest
    array ever materialized**: the histogram → exclusive scan → segment
    chain is [bins]-sized arithmetic, and the pack kernel reads the key
    planes directly.  Returns ``(send_start, send_cnt)``, both int32[P].
    """
    bounds = lax.iota(jnp.int32, n_ranks + 1) * n
    cum = jnp.clip(bounds[:, None] - base[None, :], 0,
                   h[None, :]).sum(axis=1).astype(jnp.int32)
    return cum[:-1], cum[1:] - cum[:-1]


def exscan_counts(h: jax.Array, axis: str = AXIS) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Global exclusive scan of per-rank count vectors.

    ``h``: int32[B] local histogram.  Returns ``(H, tot, rank_base)`` where
    ``H`` is int32[P, B] (all ranks' histograms, replicated via all_gather),
    ``tot[b] = sum_r H[r, b]``, and ``rank_base[r, b] = sum_{r'<r} H[r', b]``
    — the ``MPI_Exscan`` equivalent, computed replicated because P×B is tiny
    next to the key payload.
    """
    H = all_gather(h, axis)            # [P, B]
    tot = H.sum(axis=0)                # [B]
    rank_base = exclusive_cumsum(H, 0)  # [P, B]
    return H, tot, rank_base


def ragged_all_to_all(
    arrays: Words,
    send_start: jax.Array,  # int32[P] — start offset of the segment for peer p
    send_cnt: jax.Array,    # int32[P] — number of valid elements for peer p
    cap: int,               # static per-peer capacity
    n_ranks: int,           # static mesh axis size
    axis: str = AXIS,
    fill: tuple[int, ...] | None = None,  # per-array fill word for invalid lanes
    pack: str = "xla",      # "xla" | "pallas" | "pallas_interpret"
    engine: str = "lax",    # "lax" | "pallas" | "pallas_interpret" (ISSUE 13)
    pre_exchange: "Callable[[jax.Array], Any] | None" = None,
) -> "tuple[Words, jax.Array, jax.Array] | tuple[Words, jax.Array, jax.Array, Any]":
    """``MPI_Alltoallv`` for contiguous ragged segments, on static shapes.

    Each local array is logically partitioned into P contiguous segments
    (``send_start[p] .. send_start[p]+send_cnt[p]``); segment p is delivered
    to rank p.  Both sort algorithms produce *contiguous* per-destination
    segments by construction (keys are in destination-monotone order before
    the exchange), so one monotone scatter spreads the data into the
    ``[P, cap]`` send matrix without any serial packing loop.

    ``engine`` selects the exchange transport (ISSUE 13): ``"lax"`` is
    the XLA collective with the per-array ``pack`` impl; ``"pallas"`` /
    ``"pallas_interpret"`` route through :mod:`mpitest_tpu.ops.exchange`
    — ONE fused multi-word pack kernel plus the remote-DMA all-to-all
    (``lax.all_to_all`` bit-identically under the interpreter, which
    cannot simulate cross-device DMA).  ``pre_exchange(recv_cnt)`` is
    the compute/DMA overlap hook: it runs between the tiny count
    exchange and the payload transport, so work that depends only on
    the counts and replicated state (the next radix pass's lane-slot
    plane) carries **no data dependence on the payload DMAs** and the
    scheduler is free to run it while the buckets are in flight; its
    result is returned as a fourth element.

    Returns ``(recv_arrays, recv_cnt, max_send_cnt[, pre_result])``:
      * ``recv_arrays[k]``: [P, cap] — lane (s, c) holds element c of the
        segment rank s sent to me (valid iff ``c < recv_cnt[s]``);
      * ``recv_cnt``: int32[P] — the explicit count exchange that replaces
        the reference's tag-as-length trick;
      * ``max_send_cnt``: int32 scalar, globally reduced — ``> cap`` means
        the exchange overflowed and lanes were dropped; the caller retries
        with ``cap = max_send_cnt`` (exact, since the program is
        deterministic);
      * ``pre_result``: only when ``pre_exchange`` was given.
    """
    from mpitest_tpu.ops import exchange as xeng
    from mpitest_tpu.ops import kernels

    n = arrays[0].shape[0]
    use_pallas = xeng.is_pallas(engine)
    interp = engine == "pallas_interpret"
    if use_pallas:
        # the engine owns the pack: one fused multi-word kernel sweep
        pack = engine
    log = spans.current_log()
    if log is not None:
        # Static byte accounting of the padded exchange (trace-time; see
        # utils/spans.py): each array ships a [P, cap] block matrix of
        # which the self-block never crosses a link, plus the explicit
        # int32[P] count exchange that replaces the tag-as-length trick.
        itemsize = sum(a.dtype.itemsize for a in arrays)
        log.event(
            "ragged_all_to_all",
            bytes=n_ranks * cap * itemsize + n_ranks * 4,
            wire_bytes=(n_ranks - 1) * cap * itemsize + (n_ranks - 1) * 4,
            ranks=n_ranks, cap=cap, n=n, arrays=len(arrays), pack=pack,
            engine=engine, axis=axis,
        )
    if pack == "xla":
        j = lax.iota(jnp.int32, n)
        # Destination rank and segment start per element, gather-free: two
        # P-element scatters + cumsums (per-element gathers from even tiny
        # tables are ~10× a full sort's cost on v5e; kernels.piecewise_fill).
        p_j = kernels.piecewise_fill(send_start, lax.iota(jnp.int32, n_ranks), n)
        s_j = kernels.piecewise_fill(send_start, send_start, n)
        c_j = j - s_j                                 # offset within segment
        slot = jnp.where(c_j < cap, p_j * cap + c_j, n_ranks * cap)

    # Explicit count exchange (replaces tag-as-length, mpi_sample_sort.c:161,168).
    recv_cnt = lax.all_to_all(jnp.minimum(send_cnt, cap), axis, 0, 0, tiled=True)
    # Overlap hook: issued before the payload transport — depends only
    # on the counts + replicated state, never on the payload DMAs.
    pre_result = pre_exchange(recv_cnt) if pre_exchange is not None else None

    recv_arrays = []
    if use_pallas:
        sends = xeng.fused_pass_pack(
            tuple(arrays), send_start, send_cnt, cap, n_ranks,
            fills=tuple(fill) if fill is not None else (0,) * len(arrays),
            interpret=interp, vma=(axis,),
        )
        for send in sends:
            recv_arrays.append(xeng.remote_a2a(send, n_ranks, axis,
                                               interpret=interp))
    else:
        for k, a in enumerate(arrays):
            fillv = 0 if fill is None else fill[k]
            if pack == "xla":
                send = (
                    jnp.full((n_ranks * cap,), fillv, a.dtype)
                    .at[slot].set(a, mode="drop")
                    .reshape(n_ranks, cap)
                )
            else:
                # Pallas DMA pack: whole-chunk copies, no per-element
                # scatter (4.7× the XLA spread at 2^26 on v5e;
                # ops/pallas_kernels.py).
                from mpitest_tpu.ops.pallas_kernels import segment_pack

                send = segment_pack(
                    a, send_start, send_cnt, cap, n_ranks, fill=fillv,
                    interpret=(pack == "pallas_interpret"), vma=(axis,),
                )
            recv = lax.all_to_all(send, axis, 0, 0, tiled=True)
            recv_arrays.append(recv)

    # Fault injection (ISSUE 3): the armed exchange fault lands HERE —
    # between the all_to_all and the receiver's local sort/merge — the
    # exact window where the reference's overflow bug corrupted data.
    # No-op (and not even traced) unless the dispatching supervisor
    # armed a fault for this compile (mpitest_tpu/faults.py).
    recv_t, recv_cnt = faults.apply_exchange_fault(tuple(recv_arrays),
                                                   recv_cnt)

    max_send_cnt = lax.pmax(send_cnt.max(), axis)
    if pre_exchange is not None:
        return recv_t, recv_cnt, max_send_cnt, pre_result
    return recv_t, recv_cnt, max_send_cnt
