"""Pallas TPU kernels — escalation for ops XLA lowers poorly.

One hot op qualifies today: the exchange *pack* — spreading contiguous
ragged segments into the fixed ``[P, cap]`` all-to-all send matrix.  XLA
expresses it as an n-element scatter (or row gather), which lowers to
per-element updates (measured 10-40× a fused sort at 2^26 on v5e,
`.claude/skills/verify/SKILL.md`).  The Pallas version moves whole chunks:
per output chunk, one aligned 2-chunk DMA from HBM plus a vectorized
misaligned-copy shift (two row rolls + a lane roll + select) — no
per-element addressing anywhere.

Mosaic constraints that shaped the kernel (discovered on hardware):
sliced-DMA shapes and tile indices must honor the (8, 128) int32 tiling —
hence the row-aligned loads and the in-register shift instead of an
arbitrary-offset DMA; 1-D vector ops are unsupported — hence everything
is [rows, 128].

The local sorts stay on ``lax.sort`` — a measured trade-off, NOT a
memory-bound claim (round 1 asserted "near memory-bound" here; the
arithmetic refutes it — see BASELINE.md "Roofline analysis", which puts
``lax.sort`` at 2^26 roughly 250× the 2-pass HBM bound, as expected of
an O(n log² n) comparison network).  It survives because every measured
alternative is worse on this hardware: XLA scatter/gather permutations
run 3-6× slower than the sort they would replace, batched row sorts
only get cheap below rows of 2^14 while bucketing into rows that small
forces padding blowup and a second sort that eats the gain, and a
Mosaic radix scatter would need per-element cross-tile addressing — the
primitive the VPU lacks.  The realistic escalation path is a fused
in-VMEM bitonic/column-sort kernel (future work, tracked in
BASELINE.md), not a radix scatter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpitest_tpu.utils.programs import layer


LANES = 128
ROWS = 8                    # (8, 128) = one int32 tile
CHUNK = ROWS * LANES        # 1024 elements = 4 KiB per DMA


def chunk_geometry(start, cnt, i, n: int):
    """The (8, 128)/CHUNK pack-chunk geometry, shared by this module's
    ``_pack_kernel`` and the fused multi-word pack of ``ops/exchange.py``
    (ISSUE 13) — ONE home for the addressing invariants, so a fix to
    the window math can never leave one engine's copy stale.

    For output chunk ``i`` of a segment at ``start`` with ``cnt`` valid
    elements in an ``n``-element (LANES-padded) buffer, returns
    ``(arow, shift, valid)``:

      * ``arow`` — the ROWS-aligned source row to DMA a 2-chunk window
        from (clamped so beyond-count chunks never read past the padded
        buffer);
      * ``shift(x)`` — the in-register misaligned copy: shifts the
        ``[2*ROWS, LANES]`` window left by ``base - arow*LANES``
        elements (= r row rolls + a lane roll + select) and returns the
        ``[ROWS, LANES]`` chunk plane;
      * ``valid`` — the ``[ROWS, LANES]`` in-segment mask (beyond
        ``cnt``, callers write their fill word).
    """
    base = jnp.minimum(start + i * CHUNK, n)
    arow = pl.multiple_of(((base // LANES) // ROWS) * ROWS, ROWS)
    sh = base - arow * LANES
    r, l = sh // LANES, sh % LANES
    lane = jax.lax.broadcasted_iota(jnp.int32, (2 * ROWS, LANES), 1)
    sel = lane < LANES - l

    def shift(x):
        a = pltpu.roll(x, -r, 0)
        b = pltpu.roll(x, -(r + 1), 0)
        return jnp.where(sel, pltpu.roll(a, -l, 1),
                         pltpu.roll(b, -l, 1))[:ROWS, :]

    elem = (jax.lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 1))
    valid = elem < (cnt - i * CHUNK)
    return arow, shift, valid


def _pack_kernel(n: int, fill: int, starts_ref, cnts_ref, data_ref,
                 out_ref, scratch, sem):
    """Grid (P, cap//CHUNK): instance (p, i) produces out chunk i of
    destination p: data[starts[p] + i*CHUNK ...][:CHUNK] where in-segment,
    the fill word beyond ``cnts[p]``."""
    p = pl.program_id(0)
    i = pl.program_id(1)
    arow, shift, valid = chunk_geometry(starts_ref[p], cnts_ref[p], i, n)

    dma = pltpu.make_async_copy(
        data_ref.at[pl.ds(arow, 2 * ROWS), :], scratch, sem
    )
    dma.start()
    dma.wait()

    out_ref[0, 0] = jnp.where(valid, shift(scratch[...]), jnp.uint32(fill))


@functools.partial(
    jax.jit, static_argnames=("cap", "n_ranks", "fill", "interpret", "vma")
)
def segment_pack(
    data: jax.Array,     # uint32[n] — segment p is data[starts[p]:+cnts[p]]
    starts: jax.Array,   # int32[P], ascending, starts[0] == 0
    cnts: jax.Array,     # int32[P]
    cap: int,            # static row capacity, multiple of CHUNK
    n_ranks: int,
    fill: int = 0,
    interpret: bool = False,
    vma: tuple[str, ...] = (),  # mesh axes the output varies over (shard_map)
) -> jax.Array:          # uint32[P, cap]
    """Spread ragged contiguous segments into the padded send matrix."""
    assert cap % CHUNK == 0, cap
    n = data.shape[0]
    pad = (-n) % LANES + 2 * CHUNK   # row-shape the data + DMA headroom
    data_2d = jnp.concatenate(
        [data, jnp.zeros((pad,), data.dtype)]
    ).reshape(-1, LANES)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_ranks, cap // CHUNK),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(
            (1, 1, ROWS, LANES), lambda p, i, *_: (p, i, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((2 * ROWS, LANES), jnp.uint32),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    with layer("exchange"):
        out = pl.pallas_call(
            functools.partial(_pack_kernel, n, fill),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(
                (n_ranks, cap // CHUNK, ROWS, LANES), data.dtype,
                vma=frozenset(vma),
            ),
            name="exchange.segment_pack",
            interpret=interpret,
        )(starts.astype(jnp.int32), cnts.astype(jnp.int32), data_2d)
        return out.reshape(n_ranks, cap)
