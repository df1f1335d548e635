"""Pallas ICI exchange engine — remote-DMA all-to-all + the fused pass pack.

The generic exchange (``parallel/collectives.py ragged_all_to_all`` with
``engine="lax"``) lowers the inter-device hop through ``lax.all_to_all``:
XLA owns the schedule, every pass pays a scatter (or per-array pack
kernel) into the send matrix, and the receive side cannot begin until
the collective op retires.  This module is the second engine
(``SORT_EXCHANGE_ENGINE={auto,lax,pallas,pallas_interpret}``): the
rank-to-rank hop becomes a Pallas kernel that streams each negotiated
per-peer bucket straight into the peer's recv buffer over ICI with
``pltpu.make_async_remote_copy`` + DMA semaphores (SNIPPETS.md [1]/[3]
pattern), and the per-pass pack fuses into ONE multi-word kernel sweep.

Three pieces:

* :func:`fused_pass_pack` — the fused radix-pass pack: ALL key words
  spread into their ``[P, cap]`` send matrices in one kernel over the
  existing pack kernel's (8, 128)/CHUNK tiling (``ops/pallas_kernels``).
  The segment table it prefetches IS the histogram + exclusive-scan
  output (the clip-arithmetic ``block_send_segments`` of
  ``parallel/collectives.py``), so the per-pass chain histogram → scan →
  pack touches the n-element key planes exactly once — the lax engine's
  per-pass ``dest`` materialization (K-element scatter + cumsum + iota +
  searchsorted, three extra n-element HBM round-trips) does not exist
  on this path.  Per output chunk the kernel runs one address/validity
  computation and one 2-chunk DMA **per word**, versus one whole
  ``segment_pack`` launch (scalar prefetch, grid setup, address math)
  per word per pass.
* :func:`remote_a2a` — the rank-to-rank transport: every peer stream is
  started before any is waited on, so all P-1 outgoing buckets are in
  flight concurrently while the local self-block copy (and, upstream,
  the next pass's lane-slot plane — see ``models/radix_sort.py``)
  computes; this is the compute/DMA overlap the XLA collective cannot
  express.  A neighborhood barrier (``get_barrier_semaphore``) keeps a
  fast rank from writing into a peer whose recv buffer is not yet live.
* :func:`digit_histogram_words` is deliberately absent: the per-pass
  histogram stays on the post-sort ``searchsorted`` form
  (``ops/kernels.histogram_sorted``) — counts are order-invariant and
  that form is one log-pass over data the sort just touched; a Mosaic
  scatter histogram would need the per-element cross-tile addressing
  the VPU lacks (see ``ops/pallas_kernels.py`` module docstring).

Interpret-mode contract (the CPU test mesh): the Pallas
interpreter cannot simulate a cross-device DMA (``make_async_remote_copy``
rejects traced ``device_id`` outside a real TPU lowering), so
``interpret=True`` routes the transport through ``lax.all_to_all`` —
**bit-identical semantics** (``recv[s] = the row rank s sent me``) —
while the fused pack kernel, the no-dest segment arithmetic and the
whole engine plumbing run for real under the interpreter.  That is what
the parity gates pin (``bench/multichip_selftest.py`` engine axis,
``tests/test_zz_exchange.py``); the remote-DMA kernel itself lowers
only on a TPU backend, where the supervisor ladder (pallas → lax,
fingerprint-verified) guarantees a kernel bug degrades loudly instead
of shipping a wrong answer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpitest_tpu.ops.pallas_kernels import (
    CHUNK, LANES, ROWS, chunk_geometry)

#: Engine names accepted by the exchange dispatch (the knob adds "auto").
ENGINES = ("lax", "pallas", "pallas_interpret")

#: ``collective_id`` of the remote-DMA kernel's barrier semaphore — one
#: exchange kernel class exists, so one id suffices (ids must only be
#: unique across concurrently-running collective Pallas kernels).
_A2A_COLLECTIVE_ID = 7


def is_pallas(engine: str) -> bool:
    """True for both execution forms of the Pallas engine."""
    return engine.startswith("pallas")


def _fused_pack_kernel(n: int, fills: tuple[int, ...], n_arrays: int,
                       starts_ref, cnts_ref, *refs) -> None:
    """Grid (P, cap//CHUNK): instance (p, i) produces output chunk i of
    destination p for EVERY word plane: ``data[starts[p] + i*CHUNK
    ...][:CHUNK]`` where in-segment, the per-word fill beyond
    ``cnts[p]``.  One address/shift/validity computation serves all
    words; the per-word DMAs are all started before any is waited on.
    """
    data_refs = refs[:n_arrays]
    out_refs = refs[n_arrays:2 * n_arrays]
    scratch = refs[2 * n_arrays:3 * n_arrays]
    sems = refs[3 * n_arrays:4 * n_arrays]
    p = pl.program_id(0)
    i = pl.program_id(1)
    # ONE address/shift/validity computation serves every word plane —
    # the geometry itself is shared with the per-array pack kernel
    # (pallas_kernels.chunk_geometry: one home for the invariants).
    arow, shift, valid = chunk_geometry(starts_ref[p], cnts_ref[p], i, n)

    dmas = [
        pltpu.make_async_copy(
            data_refs[a].at[pl.ds(arow, 2 * ROWS), :], scratch[a], sems[a]
        )
        for a in range(n_arrays)
    ]
    for dma in dmas:
        dma.start()

    for a in range(n_arrays):
        dmas[a].wait()
        out_refs[a][0, 0] = jnp.where(valid, shift(scratch[a][...]),
                                      jnp.uint32(fills[a]))


@functools.partial(
    jax.jit,
    static_argnames=("cap", "n_ranks", "fills", "interpret", "vma"),
)
def fused_pass_pack(
    arrays: tuple[jax.Array, ...],  # uint32[n] each; segment p = [starts[p]:+cnts[p]]
    starts: jax.Array,              # int32[P], ascending, starts[0] == 0
    cnts: jax.Array,                # int32[P]
    cap: int,                       # static row capacity, multiple of CHUNK
    n_ranks: int,
    fills: tuple[int, ...] = (),    # per-array fill word (default 0)
    interpret: bool = False,
    vma: tuple[str, ...] = (),
) -> tuple[jax.Array, ...]:         # uint32[P, cap] per array
    """Spread every word plane's ragged segments into its padded send
    matrix in ONE kernel sweep (the fused radix-pass pack)."""
    assert cap % CHUNK == 0, cap
    n_arrays = len(arrays)
    if not fills:
        fills = (0,) * n_arrays
    n = arrays[0].shape[0]
    pad = (-n) % LANES + 2 * CHUNK   # row-shape the data + DMA headroom
    data_2d = tuple(
        jnp.concatenate([a, jnp.zeros((pad,), a.dtype)]).reshape(-1, LANES)
        for a in arrays
    )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_ranks, cap // CHUNK),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n_arrays,
        out_specs=tuple(
            pl.BlockSpec((1, 1, ROWS, LANES), lambda p, i, *_: (p, i, 0, 0))
            for _ in range(n_arrays)
        ),
        scratch_shapes=(
            [pltpu.VMEM((2 * ROWS, LANES), jnp.uint32)] * n_arrays
            + [pltpu.SemaphoreType.DMA(())] * n_arrays
        ),
    )
    outs = pl.pallas_call(
        functools.partial(_fused_pack_kernel, n, fills, n_arrays),
        grid_spec=grid_spec,
        out_shape=tuple(
            jax.ShapeDtypeStruct(
                (n_ranks, cap // CHUNK, ROWS, LANES), a.dtype,
                vma=frozenset(vma),
            )
            for a in arrays
        ),
        interpret=interpret,
    )(starts.astype(jnp.int32), cnts.astype(jnp.int32), *data_2d)
    if n_arrays == 1 and not isinstance(outs, (tuple, list)):
        outs = (outs,)
    return tuple(o.reshape(n_ranks, cap) for o in outs)


def _remote_a2a_kernel(n_ranks: int, axis: str, x_ref, out_ref,
                       local_sem, send_sems, recv_sems) -> None:
    """All-to-all over ICI: rank r's row ``x[dst]`` lands in dst's
    ``out[r]``.  Balanced permutation schedule (step k: send to
    ``(me+k) % P``, receive from ``(me-k) % P`` on slot k) — every
    link carries one stream per step and no two ranks convoy on the
    same destination.  All remote streams START before anything is
    waited on: the P-1 bucket sends are in flight while the local
    self-block copy runs — the kernel-level half of the engine's
    compute/DMA overlap (the pass-loop half precomputes the next
    pass's lane-slot plane during the same window, models/radix_sort).
    """
    me = lax.axis_index(axis)

    # Ready barrier: a fast rank must not stream into a peer whose
    # output buffer is not yet live in this kernel invocation.
    barrier = pltpu.get_barrier_semaphore()
    for k in range(1, n_ranks):
        peer = (me + k) % n_ranks
        pltpu.semaphore_signal(
            barrier, inc=1, device_id=peer,
            device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_wait(barrier, n_ranks - 1)

    # Self block: a local HBM copy, overlapped with the remote streams.
    local = pltpu.make_async_copy(x_ref.at[me], out_ref.at[me], local_sem)
    local.start()

    copies = []
    for k in range(1, n_ranks):
        dst = (me + k) % n_ranks
        # dst_ref is addressed with MY rank: on the receiving core the
        # same SPMD expression denotes row <sender> of ITS buffer.
        rc = pltpu.make_async_remote_copy(
            src_ref=x_ref.at[dst],
            dst_ref=out_ref.at[me],
            send_sem=send_sems.at[k],
            recv_sem=recv_sems.at[k],
            device_id=dst,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rc.start()
        copies.append(rc)

    local.wait()
    for rc in copies:
        # wait() = wait_send + wait_recv: slot k's recv is the row from
        # (me-k) % P — its sender also used slot k, and all rows are
        # uniformly shaped, so the descriptor prices the wait exactly.
        rc.wait()


def remote_a2a(
    x: jax.Array,           # [P, cap] — row p is my bucket for rank p
    n_ranks: int,
    axis: str,
    interpret: bool = False,
) -> jax.Array:             # [P, cap] — row s is the bucket rank s sent me
    """Rank-to-rank bucket exchange: remote-DMA kernel on TPU, the
    bit-identical ``lax.all_to_all`` under ``interpret`` (the Pallas
    interpreter cannot simulate cross-device DMA — module docstring).
    """
    if n_ranks == 1:
        return x
    if interpret:
        # Same contract, XLA transport: recv[s] = row sent by rank s.
        return lax.all_to_all(x, axis, 0, 0, tiled=True)
    cap = x.shape[1]
    x3 = x.reshape(n_ranks, cap // LANES, LANES)
    out = pl.pallas_call(
        functools.partial(_remote_a2a_kernel, n_ranks, axis),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(
            (n_ranks, cap // LANES, LANES), x.dtype, vma=frozenset((axis,))),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((n_ranks,)),
            pltpu.SemaphoreType.DMA((n_ranks,)),
        ],
        compiler_params=pltpu.CompilerParams(
            collective_id=_A2A_COLLECTIVE_ID),
        # never interpreted: interpret=True returned above via the
        # bit-identical lax transport — the interpreter cannot simulate
        # the cross-device DMA this kernel exists for
        interpret=False,
    )(x3)
    return out.reshape(n_ranks, cap)
