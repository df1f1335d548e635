"""Pallas bitonic sort — the single-chip local sort engine.

Replaces ``lax.sort`` for large one-word (uint32) shards.  XLA's TPU sort
lowers to a comparison network whose per-layer cost is dominated by
generic lowering overhead: measured 84.6 ms for 2^26 uint32 on v5e (see
BASELINE.md "kernel design study"), ~30x the two-pass HBM streaming
floor.  This kernel implements the same O(n log^2 n) bitonic network
with every data movement expressed as *static* circular shifts
(``pltpu.roll``) — the partner of element ``i`` at distance ``d = 2^j``
is ``i ^ d``, reachable by two rolls and one select — so the whole
network compiles to dense VPU code with no data-dependent addressing,
which the TPU does not have (no vectorized gather/scatter; the roofline
study in BASELINE.md prices every alternative).

Design (tpu-first, not a port of any CPU/GPU radix scheme):

- The array lives as ``[nblk, S, 128]`` (row-major flat order), block =
  ``S*128 = 2^B`` elements (256 KiB at B=16 — the largest the unrolled
  layer chain fits in scoped VMEM).
- One **standard bitonic network over the whole padded array**; layers
  are partitioned by compare distance into three kernels:

  * ``block-sort``: all stages with size <= 2^B, unrolled in-VMEM per
    block (grid over blocks, one HBM round-trip total).  Directions come
    from the *global* flat index, so block b ends sorted ascending /
    descending by the parity the merge stages expect.
  * ``cross``: one layer at block distance >= 8, moved as contiguous
    8-block groups — pure elementwise min/max between paired groups;
    the take-min side is constant per group (a block-index bit), so
    there are no per-element masks at all.
  * ``merge``: each stage's tail — its lowest <=3 cross layers (the
    XOR-neighborhood of a contiguous 2^c-block group, paired at the
    Python level) AND the whole trailing in-block sweep — in one VMEM
    visit per block.

- Two measured v5e facts shape the inner loop: **lane rolls cost ~15x
  sublane rolls**, so every distance<128 layer runs on the transposed
  block where it becomes a sublane roll; and direction selects are
  dearer than flip bookkeeping, so descending segments are kept
  bit-flipped (``~x`` reverses int32 order) and every layer is the
  6-op ascending form.
- Compare distances and stage numbers ride in as scalar-prefetch
  operands (``PrefetchScalarGridSpec``), so each kernel compiles
  **once** per array shape, not once per layer.
- Round 5 replaced the single-cross schedule with the **rotation
  relayout** (see the "relayout cross fusion" section below): fused
  XOR-closure visits + rotation-aware merges, 2-3x fewer HBM bytes
  for the cross phase; the r4 schedule stays available as the A/B
  baseline (``relayout=False``).

The network is oblivious (layer sequence depends only on N), so output
is deterministic and bit-identical run to run — the same canonical
sorted bytes ``lax.sort`` or ``qsort`` would produce (reference output
contract: ``mpi_sample_sort.c:203-205``).

Scope: one-word uint32 keys (the encoded form of int32/uint32/float32 —
see ``ops/keys.py``) for the key-only engine, PLUS a key+payload twin
(round 4) that sorts ``(key, payload)`` uint32 pairs by the key plane —
the core of the 64-bit MSD-hybrid local sort (``kernels`` /
``models/api.py``): hi word as key, lo word as payload, equal-hi runs
fixed by a short segmented pass afterwards.  The pair layer routes the
payload from the key *result* (``out_k == k``: low side keeps its
payload iff ``k <= partner``, high iff ``k >= partner``, ties keep own
on both sides — a consistent no-swap), which measures **1.98x** the
1-word layer on v5e where the lexicographic 2-word form measures 4.8x
(``bench/kernel_probes.py`` ``bitonic_layer_kp2``) — the payload plane
costs its bandwidth and nothing else.  The radix per-pass variadic
sorts keep ``lax.sort``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


LANES = 128
LANES_LOG2 = 7
#: log2 of elements per block: S = 2^(B-7) sublanes x 128 lanes = 256 KiB
#: u32.  2^18 (the VMEM-optimal choice on paper) OOMs scoped VMEM: Mosaic
#: keeps ~34 copies of the block live across the unrolled 100+-layer chain.
BLOCK_LOG2 = 16
#: below this the padded network does not beat lax.sort's fixed costs.
MIN_SORT_LOG2 = 13
#: pair-engine shape: two planes double the in-VMEM footprint.  Keeping
#: the 2^16 block (shrinking it to 2^15 measured 2.2x SLOWER on the
#: whole network — extra stages + HBM visits dwarf everything) and
#: instead halving the merge/cross transfer groups to 4 blocks keeps the
#: 8-member pair merge's 25.6 MiB scoped-vmem demand (measured, over the
#: 16 MiB limit) at ~13 MiB.
PAIR_BLOCK_LOG2 = 16
_PAIR_CROSS_GROUP = 4      # blocks per pair cross-layer transfer group
_PAIR_MERGE_BITS = 2       # cross bits fused into the pair merge tail
#: blocks per cross-layer transfer group (see ``_cross_kernel``).
_CROSS_GROUP = 8
#: Raised scoped-VMEM budget, applied to EVERY kernel in this module.
#: The 16 MiB default is a compiler parameter, not hardware (v5e VMEM
#: is 128 MiB); 48 MiB admits the wide shapes round 4 recorded as
#: walls (2-block member windows, the 25.6 MiB 8-member pair merge,
#: the B=17 block experiment) while leaving ample room for the
#: pipeline's double buffers.
_VMEM_LIMIT = 48 * 1024 * 1024
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)

#: Index-map constants pinned to int32: under jax_enable_x64 (the
#: device-resident 64-bit path) Python-int literals in index maps
#: weak-promote to i64, which Mosaic's block-map functions reject.
_Z = np.int32(0)


def _zmap(i, *_):
    return (i, _Z, _Z)


def _asc_layer(x, lj: int, t_layout: bool = False):
    """Ascending compare-exchange at distance ``2^lj`` — 6 vector ops.

    The partner of element ``i`` is ``i ^ 2^lj``.  Low-side elements
    (bit ``lj`` clear) keep ``min(x, x[i+d])``, high-side keep
    ``max(x, x[i-d])`` — no separate partner select, no direction mask:
    *every* segment compares ascending because the callers bit-flip the
    values of descending segments up front (``~x`` reverses int32
    order), which is what makes the per-layer cost 2 rolls + min + max
    + mask + select instead of the 12-op direct form.

    Layout: in the natural ``[S, 128]`` block, ``lj >= 7`` distances
    are *sublane* rolls and ``lj < 7`` would be lane rolls — which cost
    ~15x a sublane roll on v5e (measured; the cross-lane shift network
    is the scarce resource).  Callers therefore run all ``lj < 7``
    layers on the transposed ``[128, S]`` block (``t_layout=True``),
    where the original lane index is the sublane axis and the same
    distances become sublane rolls; two [S,128] transposes per section
    amortize over seven avoided lane-roll layers.  Both rolls are
    cyclic, but segments of ``2^(lj+1)`` tile the axis exactly, so the
    selected half never reads a wrapped value.
    """
    if t_layout:
        assert lj < LANES_LOG2
        axis, shift, log = 0, 1 << lj, lj
    elif lj < LANES_LOG2:
        axis, shift, log = 1, 1 << lj, lj
    else:
        axis, shift, log = 0, 1 << (lj - LANES_LOG2), lj - LANES_LOG2
    size = x.shape[axis]
    fwd = pltpu.roll(x, np.int32(size - shift), axis)  # out[i] = in[i + shift]
    bwd = pltpu.roll(x, np.int32(shift), axis)         # out[i] = in[i - shift]
    idx = lax.broadcasted_iota(jnp.int32, x.shape, axis)
    low = ((idx >> log) & 1) == 0            # bit clear -> partner above
    return jnp.where(low, jnp.minimum(x, fwd), jnp.maximum(x, bwd))


def _sweep(x, b_log2: int):
    """The trailing in-block sweep: layers ``B-1 .. 0`` ascending, with
    the ``lj < 7`` tail run on the transposed block (see
    :func:`_asc_layer` on why lane rolls are banned)."""
    for lj in range(b_log2 - 1, LANES_LOG2 - 1, -1):
        x = _asc_layer(x, lj)
    xt = x.T
    for lj in range(LANES_LOG2 - 1, -1, -1):
        xt = _asc_layer(xt, lj, t_layout=True)
    return xt.T


def _flat_bit(shape, j: int, t_layout: bool):
    """Mask ``bit_j(flat index) == 1`` for a block in either layout.

    flat = r*128 + l; natural layout is [r=S sublanes, l=128 lanes],
    transposed is [l, r]: bit j < 7 lives on the lane index, the rest
    on the row index."""
    if j < LANES_LOG2:
        axis = 0 if t_layout else 1
        bit = j
    else:
        axis = 1 if t_layout else 0
        bit = j - LANES_LOG2
    idx = lax.broadcasted_iota(jnp.int32, shape, axis)
    return ((idx >> bit) & 1) == 1


# ---------------------------------------------------------------- kernels


def _block_sort_kernel(x_ref, o_ref, *, s_rows: int, b_log2: int):
    """Stages 1..B of the network, in-VMEM, one block per grid step.

    Flip-state bookkeeping: before stage ``m`` runs, values in its
    descending segments (bit ``m`` of the flat index set) are held
    bit-flipped, so every layer is the cheap ascending form.  Between
    stages only the *difference* of the two masks re-flips (one xor-mask
    pass per stage vs a direction select per layer); all masks here
    depend on the local index only — block-independent — except the
    final unflip, whose mask degenerates to the block parity.
    """
    blk = pl.program_id(0)
    x = x_ref[0]

    def transition(x, m, t_layout):
        """Re-flip from stage ``m``'s direction mask to stage ``m+1``'s
        (or unflip after the last stage); masks are local-index bits
        except bit B, which is the block parity."""
        delta = _flat_bit(x.shape, m, t_layout)
        if m + 1 < b_log2:
            delta = delta ^ _flat_bit(x.shape, m + 1, t_layout)
        elif m + 1 == b_log2:
            delta = delta ^ ((blk & 1) == 1)
        else:  # after the final stage: unflip from the parity state
            delta = (blk & 1) == 1
            return jnp.where(delta, ~x, x)
        return jnp.where(delta, ~x, x)

    # Stages 1..7 run wholly on the transposed block: every layer there
    # has lane-sized distance, and lane rolls are what we must avoid.
    xt = x.T
    xt = jnp.where(_flat_bit(xt.shape, 1, True), ~xt, xt)
    for m in range(1, LANES_LOG2 + 1):
        for lj in range(m - 1, -1, -1):
            xt = _asc_layer(xt, lj, t_layout=True)
        xt = transition(xt, m, True)
    x = xt.T
    for m in range(LANES_LOG2 + 1, b_log2 + 1):
        for lj in range(m - 1, LANES_LOG2 - 1, -1):
            x = _asc_layer(x, lj)
        xt = x.T
        for lj in range(LANES_LOG2 - 1, -1, -1):
            xt = _asc_layer(xt, lj, t_layout=True)
        x = xt.T
        x = transition(x, m, False)
    o_ref[0] = x


def _cross_kernel(s_ref, xl_ref, xh_ref, o_ref):
    """One distance >= 2^(B+3) layer, one output *group* per grid step.

    The transfer unit is a contiguous group of ``_CROSS_GROUP`` blocks:
    every cross layer handled here has block distance >= 8 (the lowest
    three cross bits belong to the merge kernel), so partner blocks
    have equal low-3 bits and whole groups pair with whole groups —
    the same XOR pairing lifted to group indices, with ~2 MiB DMAs
    instead of 256 KiB ones.

    Scalar prefetch ``s_ref = [sjg, sm]``: the layer's distance in
    *group-index bits* (``sjg = lj - B - 3``) and stage size in
    block-index bits (``sm = lk - B``).  Grid is ``(group_pairs, 2)``:
    step ``(q, r)`` reads both groups of pair ``q`` and writes only the
    ``r``-side one, so one output array receives every group with no
    reconciliation pass (the pair's min/max is computed twice — three
    VPU ops against an HBM-bound layer).  The take-min side is a bit of
    the group id (``sm >= 4`` exceeds the in-group bits): no
    per-element masks at all.
    """
    sjg, sm = s_ref[0], s_ref[1]
    q = pl.program_id(0)
    r = pl.program_id(1)
    mask = (1 << sjg) - 1
    glo = ((q & ~mask) << 1) | (q & mask)
    blo = glo * _CROSS_GROUP  # any block of the low group: shared high bits
    take_min_low = ((blo >> sm) & 1) == 0
    lo = jnp.minimum(xl_ref[:], xh_ref[:])
    hi = jnp.maximum(xl_ref[:], xh_ref[:])
    o_ref[:] = jnp.where(take_min_low ^ (r == 1), lo, hi)


def _merge_kernel(s_ref, x_ref, o_ref, *, n_members: int, s_rows: int,
                  b_log2: int):
    """A stage's trailing chunk: the ``c = log2(G)`` lowest cross layers
    AND the whole in-block sweep, in ONE visit of each block to VMEM.

    Grid step ``g`` owns the *contiguous* member group ``{g*G + i}`` —
    the XOR-neighborhood of the cross layers at block-bit positions
    ``c-1 .. 0``: member ``i`` pairs with ``i ^ 2^k``, a Python-level
    slice pairing with no data movement.  Cross compare directions are
    scalar per member (a block-id bit), so a fused cross layer costs
    three vector ops per element; the fusion is what turns the merge
    tail from one HBM round-trip per layer into one per stage.

    Scalar ``s_ref = [m]``: the stage number, for compare directions.
    """
    m = s_ref[0]
    g = pl.program_id(0)
    sign_shift = m - b_log2
    bids = [g * n_members + i for i in range(n_members)]
    # Stage direction is a block-id bit — one scalar flip per member
    # makes every fused layer the raw ascending form.
    desc = [((bid >> sign_shift) & 1) == 1 for bid in bids]
    xs = [jnp.where(desc[i], ~x_ref[i], x_ref[i]) for i in range(n_members)]
    # Members of a pair share the stage-direction bit (they differ only
    # in bits below sign_shift), so flipped ascending min/max is exact.
    _min_max_ladder(xs, n_members.bit_length() - 1)
    for i in range(n_members):
        x = _sweep(xs[i], b_log2)
        o_ref[i] = jnp.where(desc[i], ~x, x)


# ----------------------------------------------------------- host drivers


@functools.lru_cache(maxsize=16)
def _compile_block_sort(nblk: int, s_rows: int, b_log2: int, interpret: bool):
    spec = pl.BlockSpec((1, s_rows, LANES), _zmap,
                        memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_block_sort_kernel, s_rows=s_rows, b_log2=b_log2),
        out_shape=jax.ShapeDtypeStruct((nblk, s_rows, LANES), jnp.int32),
        grid=(nblk,),
        in_specs=[spec],
        out_specs=spec,
        # No aliasing: in-place measured ~1.5x slower (12.9 vs 8.5 ms at
        # 2^26) — same defensive-copy/pipelining penalty as the merge.
        # Raised budget: admits the B=17 block experiment (the unrolled
        # chain holds ~34 live block copies); no effect at B=16.
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )


@functools.lru_cache(maxsize=16)
def _compile_cross(nblk: int, s_rows: int, interpret: bool):
    """One call exchanges every 8-block group with its partner group at
    group distance ``2^sjg``.

    The pair layout rides in through the index maps, which receive the
    scalar-prefetch ref: grid step ``(q, r)`` loads groups ``glo`` (bit
    ``sjg`` clear) and ``glo | 2^sjg`` and writes the ``r``-side one.
    One compilation serves every distance.
    """
    def pair_map(side):
        def f(q, r, s_ref):
            sjg = s_ref[0]
            mask = (1 << sjg) - 1
            glo = ((q & ~mask) << 1) | (q & mask)
            pick = side if side is not None else r
            return (glo | (pick << sjg), _Z, _Z)
        return f

    ngroups = nblk // _CROSS_GROUP
    gspec = lambda m: pl.BlockSpec((_CROSS_GROUP, s_rows, LANES), m,
                                   memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(ngroups // 2, 2),
        in_specs=[gspec(pair_map(0)), gspec(pair_map(1))],
        out_specs=gspec(pair_map(None)),
    )
    return pl.pallas_call(
        _cross_kernel,
        out_shape=jax.ShapeDtypeStruct((nblk, s_rows, LANES), jnp.int32),
        grid_spec=grid_spec,
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )


@functools.lru_cache(maxsize=16)
def _compile_merge(n_members: int, nblk: int, s_rows: int, b_log2: int,
                   interpret: bool):
    spec = pl.BlockSpec((n_members, s_rows, LANES), lambda g, s: (g, _Z, _Z),
                        memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nblk // n_members,),
        in_specs=[spec],
        out_specs=spec,
    )
    return pl.pallas_call(
        functools.partial(_merge_kernel, n_members=n_members, s_rows=s_rows,
                          b_log2=b_log2),
        out_shape=jax.ShapeDtypeStruct((nblk, s_rows, LANES), jnp.int32),
        grid_spec=grid_spec,
        # Raised budget (see _VMEM_LIMIT): the 8-member window at B=17
        # needs 28.3 MiB; no effect on the shipped B=16 shapes.
        compiler_params=_COMPILER_PARAMS,
        # No input_output_aliases here although each grid step reads only
        # the group it writes: in-place was measured 3.3x SLOWER at 2^30
        # (11.1 s vs 3.4 s end-to-end — XLA inserts defensive copies /
        # the revolving-window pipeline serializes).  The extra buffer is
        # the cheaper trade.  The cross kernel could not alias anyway:
        # both (q, 0) and (q, 1) steps read the pair.
        interpret=interpret,
    )


def sort_padded(x, n_pow2: int, b_log2: int, interpret: bool = False,
                relayout: bool = True):
    """Bitonic-sort a padded power-of-two uint32 array of ``n_pow2``.

    ``x``: flat uint32 [n_pow2], ``n_pow2 = 2^t``, ``t >= b_log2 >= 10``.
    Returns the sorted flat array.  Pure function of shapes — jittable.

    The network itself runs in the *int32* domain (Mosaic has no
    unsigned vector min/max): the sign bit is flipped on the way in and
    out — an order-preserving bijection uint32 -> int32, two cheap
    elementwise passes against ~100 network layers.

    ``relayout`` (round 5, default): stages with single cross layers
    run the rotation-relayout schedule (fused closure visits of up to
    3 bits at 2-block member windows + the rotation-aware 8-member
    merge) instead of one grouped cross layer at a time; see the
    "relayout cross fusion" section and BASELINE.md round 5.
    """
    t = n_pow2.bit_length() - 1
    assert 1 << t == n_pow2 and t >= b_log2
    s_rows = 1 << (b_log2 - LANES_LOG2)
    nblk = n_pow2 >> b_log2
    x = lax.bitcast_convert_type(x ^ jnp.uint32(0x80000000), jnp.int32)
    xb = x.reshape(nblk, s_rows, LANES)

    xb = _compile_block_sort(nblk, s_rows, b_log2, interpret)(xb)

    cross = (None if relayout else
             (_compile_cross(nblk, s_rows, interpret)
              if t > b_log2 + 3 else None))

    for m in range(b_log2 + 1, t + 1):
        nbits = m - b_log2  # cross layers at block-bit positions nbits-1..0
        if relayout and nbits > 3:
            n_single = nbits - 3
            jarr = jnp.asarray([nbits], jnp.int32)
            if n_single % 3:
                c = n_single % 3
                visit = _compile_relayout_cross(1 << c, nblk, s_rows,
                                                interpret)
                xb = visit(jarr, *([xb] * (1 << c)))
            visit3 = _compile_relayout_cross(8, nblk, s_rows, interpret)
            for _ in range(n_single // 3):
                xb = visit3(jarr, *([xb] * 8))
            xb = _compile_rot_merge(nblk, s_rows, b_log2, 3, interpret)(
                jarr, *([xb] * 8))
            continue
        # High cross layers (block distance >= 8) one at a time; the
        # lowest min(nbits, 3) fuse into the merge kernel with the sweep.
        for sj in range(nbits - 1, 2, -1):
            xb = cross(jnp.asarray([sj - 3, nbits], jnp.int32), xb, xb)
        g_final = 1 << min(nbits, 3)
        merge = _compile_merge(g_final, nblk, s_rows, b_log2, interpret)
        xb = merge(jnp.asarray([m], jnp.int32), xb)
    out = xb.reshape(-1)
    return lax.bitcast_convert_type(out, jnp.uint32) ^ jnp.uint32(0x80000000)


# ------------------------------------------- 1-word relayout cross (r5)
#
# Key-only twins of the rotation-relayout visit / rot-merge pair
# kernels below (see the "relayout cross fusion" section): same
# geometry, no payload plane.  Being single-plane, the 1-word shapes
# afford 8-member closures (c=3) at 2-block member windows inside the
# raised scoped-vmem budget, so each visit retires up to three cross
# layers per n-read + n-write.


def _min_max_ladder(ks, c: int):
    """Key-only XOR-closure ladder: pairwise min/max, highest bit first
    (members of a pair share the stage-direction bit, so the flipped
    ascending form is exact — see :func:`_merge_kernel`)."""
    n_members = len(ks)
    for kbit in range(c - 1, -1, -1):
        for i in range(n_members):
            if (i >> kbit) & 1:
                continue
            jm = i | (1 << kbit)
            ks[i], ks[jm] = jnp.minimum(ks[i], ks[jm]), \
                jnp.maximum(ks[i], ks[jm])


def _relayout_cross_kernel(s_ref, *refs, n_members: int, bpm: int):
    """Key-only :func:`_relayout_cross_pair_kernel`."""
    j_bits = s_ref[0]
    g = pl.program_id(0)
    c = n_members.bit_length() - 1
    lb = bpm.bit_length() - 1
    desc = ((g >> (j_bits - lb - c)) & 1) == 1
    o_ref = refs[n_members]
    for b in range(bpm):
        ks = [jnp.where(desc, ~refs[i][b], refs[i][b])
              for i in range(n_members)]
        _min_max_ladder(ks, c)
        for i in range(n_members):
            o_ref[b * n_members + i] = jnp.where(desc, ~ks[i], ks[i])


@functools.lru_cache(maxsize=16)
def _compile_relayout_cross(n_members: int, nblk: int, s_rows: int,
                            interpret: bool, bpm: int = 2):
    """Key-only :func:`_compile_relayout_cross_pair`."""
    c = n_members.bit_length() - 1
    lb = bpm.bit_length() - 1

    def member_map(s):
        def f(g, s_ref):
            j_w = s_ref[0] - lb
            qbits = j_w - c
            seg = g >> qbits
            w = g & ((1 << qbits) - 1)
            return ((seg << j_w) + (s << qbits) + w, _Z, _Z)
        return f

    mspec = lambda s: pl.BlockSpec((bpm, s_rows, LANES), member_map(s),
                                   memory_space=pltpu.VMEM)
    ospec = pl.BlockSpec((bpm * n_members, s_rows, LANES),
                         lambda g, s: (g, _Z, _Z),
                         memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nblk // (bpm * n_members),),
        in_specs=[mspec(s) for s in range(n_members)],
        out_specs=ospec,
    )
    return pl.pallas_call(
        functools.partial(_relayout_cross_kernel, n_members=n_members,
                          bpm=bpm),
        out_shape=jax.ShapeDtypeStruct((nblk, s_rows, LANES), jnp.int32),
        grid_spec=grid_spec,
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )


def _rot_merge_kernel(s_ref, *refs, n_members: int, s_rows: int,
                      b_log2: int, tail: int, bpm: int):
    """Key-only :func:`_rot_merge_pair_kernel`: ``n_members = 2^tail``
    gathered through the stage's accumulated rotation, cross ladder +
    full sweep, natural-order contiguous write."""
    j_bits = s_ref[0]
    lb = bpm.bit_length() - 1
    g = pl.program_id(0)
    desc = ((g >> (j_bits - tail - lb)) & 1) == 1
    o_ref = refs[n_members]
    for b in range(bpm):
        ks = [jnp.where(desc, ~refs[i][b], refs[i][b])
              for i in range(n_members)]
        _min_max_ladder(ks, tail)
        for i in range(n_members):
            k = _sweep(ks[i], b_log2)
            o_ref[b * n_members + i] = jnp.where(desc, ~k, k)


@functools.lru_cache(maxsize=16)
def _compile_rot_merge(nblk: int, s_rows: int, b_log2: int, tail: int,
                       interpret: bool, bpm: int = 2):
    """Key-only :func:`_compile_rot_merge_pair` with a ``2^tail``-member
    group (the 1-word engine fuses three cross bits into its merge)."""
    n_members = 1 << tail
    lb = bpm.bit_length() - 1

    def member_map(s):
        def f(g, s_ref):
            j_w = s_ref[0] - lb
            wbits = j_w - tail
            seg = g >> wbits
            w = g & ((1 << wbits) - 1)
            return ((seg << j_w) + (s << wbits) + w, _Z, _Z)
        return f

    mspec = lambda s: pl.BlockSpec((bpm, s_rows, LANES), member_map(s),
                                   memory_space=pltpu.VMEM)
    ospec = pl.BlockSpec((bpm * n_members, s_rows, LANES),
                         lambda g, s: (g, _Z, _Z),
                         memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nblk // (bpm * n_members),),
        in_specs=[mspec(s) for s in range(n_members)],
        out_specs=ospec,
    )
    return pl.pallas_call(
        functools.partial(_rot_merge_kernel, n_members=n_members,
                          s_rows=s_rows, b_log2=b_log2, tail=tail, bpm=bpm),
        out_shape=jax.ShapeDtypeStruct((nblk, s_rows, LANES), jnp.int32),
        grid_spec=grid_spec,
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )


# ------------------------------------------------------- key+payload twin


def _asc_layer_pair(k, p, lj: int, t_layout: bool = False):
    """Pair compare-exchange at distance ``2^lj``: the key plane runs the
    exact 6-op ascending form of :func:`_asc_layer`; the payload plane is
    routed by ``out_k == k`` (see module docstring — measured 1.98x the
    1-word layer, vs 4.8x for a lexicographic 2-word compare)."""
    if t_layout:
        assert lj < LANES_LOG2
        axis, shift, log = 0, 1 << lj, lj
    elif lj < LANES_LOG2:
        axis, shift, log = 1, 1 << lj, lj
    else:
        axis, shift, log = 0, 1 << (lj - LANES_LOG2), lj - LANES_LOG2
    size = k.shape[axis]
    fk = pltpu.roll(k, np.int32(size - shift), axis)
    bk = pltpu.roll(k, np.int32(shift), axis)
    fp = pltpu.roll(p, np.int32(size - shift), axis)
    bp = pltpu.roll(p, np.int32(shift), axis)
    idx = lax.broadcasted_iota(jnp.int32, k.shape, axis)
    low = ((idx >> log) & 1) == 0
    out_k = jnp.where(low, jnp.minimum(k, fk), jnp.maximum(k, bk))
    out_p = jnp.where(out_k == k, p, jnp.where(low, fp, bp))
    return out_k, out_p


def _sweep_pair(k, p, b_log2: int):
    """Pair twin of :func:`_sweep`: the trailing in-block sweep with the
    ``lj < 7`` tail on the transposed planes."""
    for lj in range(b_log2 - 1, LANES_LOG2 - 1, -1):
        k, p = _asc_layer_pair(k, p, lj)
    kt, pt = k.T, p.T
    for lj in range(LANES_LOG2 - 1, -1, -1):
        kt, pt = _asc_layer_pair(kt, pt, lj, t_layout=True)
    return kt.T, pt.T


def _block_sort_pair_kernel(k_ref, p_ref, ok_ref, op_ref, *, s_rows: int,
                            b_log2: int):
    """Pair twin of :func:`_block_sort_kernel`.  Flip bookkeeping touches
    the KEY plane only — the payload is never compared, so descending
    segments keep their payloads as-is and ``out_k == k`` routing stays
    exact on the flipped keys (equality is flip-invariant)."""
    blk = pl.program_id(0)

    def transition(k, m, t_layout):
        delta = _flat_bit(k.shape, m, t_layout)
        if m + 1 < b_log2:
            delta = delta ^ _flat_bit(k.shape, m + 1, t_layout)
        elif m + 1 == b_log2:
            delta = delta ^ ((blk & 1) == 1)
        else:
            delta = (blk & 1) == 1
            return jnp.where(delta, ~k, k)
        return jnp.where(delta, ~k, k)

    kt, pt = k_ref[0].T, p_ref[0].T
    kt = jnp.where(_flat_bit(kt.shape, 1, True), ~kt, kt)
    for m in range(1, LANES_LOG2 + 1):
        for lj in range(m - 1, -1, -1):
            kt, pt = _asc_layer_pair(kt, pt, lj, t_layout=True)
        kt = transition(kt, m, True)
    k, p = kt.T, pt.T
    for m in range(LANES_LOG2 + 1, b_log2 + 1):
        for lj in range(m - 1, LANES_LOG2 - 1, -1):
            k, p = _asc_layer_pair(k, p, lj)
        kt, pt = k.T, p.T
        for lj in range(LANES_LOG2 - 1, -1, -1):
            kt, pt = _asc_layer_pair(kt, pt, lj, t_layout=True)
        k, p = kt.T, pt.T
        k = transition(k, m, False)
    ok_ref[0], op_ref[0] = k, p


def _cross_pair_kernel(s_ref, kl_ref, kh_ref, pl_ref, ph_ref,
                       ok_ref, op_ref):
    """Pair twin of :func:`_cross_kernel` (group = ``_PAIR_CROSS_GROUP``
    blocks): key min/max as before; each side's payload follows its key
    result (``lo == kl`` / ``hi == kh`` — ties route both payloads to
    their own sides, a consistent no-swap, so the pair multiset is
    preserved exactly)."""
    sjg, sm = s_ref[0], s_ref[1]
    q = pl.program_id(0)
    r = pl.program_id(1)
    mask = (1 << sjg) - 1
    glo = ((q & ~mask) << 1) | (q & mask)
    blo = glo * _PAIR_CROSS_GROUP
    take_min_low = ((blo >> sm) & 1) == 0
    kl, kh = kl_ref[:], kh_ref[:]
    lo = jnp.minimum(kl, kh)
    hi = jnp.maximum(kl, kh)
    p_lo = jnp.where(lo == kl, pl_ref[:], ph_ref[:])
    p_hi = jnp.where(hi == kh, ph_ref[:], pl_ref[:])
    side = take_min_low ^ (r == 1)
    ok_ref[:] = jnp.where(side, lo, hi)
    op_ref[:] = jnp.where(side, p_lo, p_hi)


def _merge_pair_kernel(s_ref, k_ref, p_ref, ok_ref, op_ref, *,
                       n_members: int, s_rows: int, b_log2: int):
    """Pair twin of :func:`_merge_kernel` (fused cross tail + sweep)."""
    m = s_ref[0]
    g = pl.program_id(0)
    sign_shift = m - b_log2
    bids = [g * n_members + i for i in range(n_members)]
    desc = [((bid >> sign_shift) & 1) == 1 for bid in bids]
    ks = [jnp.where(desc[i], ~k_ref[i], k_ref[i]) for i in range(n_members)]
    ps = [p_ref[i] for i in range(n_members)]
    _closure_ladder(ks, ps, n_members.bit_length() - 1)
    for i in range(n_members):
        k, p = _sweep_pair(ks[i], ps[i], b_log2)
        ok_ref[i] = jnp.where(desc[i], ~k, k)
        op_ref[i] = p


@functools.lru_cache(maxsize=16)
def _compile_block_sort_pair(nblk: int, s_rows: int, b_log2: int,
                             interpret: bool):
    spec = pl.BlockSpec((1, s_rows, LANES), _zmap,
                        memory_space=pltpu.VMEM)
    shape = jax.ShapeDtypeStruct((nblk, s_rows, LANES), jnp.int32)
    return pl.pallas_call(
        functools.partial(_block_sort_pair_kernel, s_rows=s_rows,
                          b_log2=b_log2),
        out_shape=[shape, shape],
        grid=(nblk,),
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )


@functools.lru_cache(maxsize=16)
def _compile_cross_pair(nblk: int, s_rows: int, interpret: bool):
    def pair_map(side):
        def f(q, r, s_ref):
            sjg = s_ref[0]
            mask = (1 << sjg) - 1
            glo = ((q & ~mask) << 1) | (q & mask)
            pick = side if side is not None else r
            return (glo | (pick << sjg), _Z, _Z)
        return f

    ngroups = nblk // _PAIR_CROSS_GROUP
    gspec = lambda m: pl.BlockSpec((_PAIR_CROSS_GROUP, s_rows, LANES), m,
                                   memory_space=pltpu.VMEM)
    shape = jax.ShapeDtypeStruct((nblk, s_rows, LANES), jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(ngroups // 2, 2),
        in_specs=[gspec(pair_map(0)), gspec(pair_map(1)),
                  gspec(pair_map(0)), gspec(pair_map(1))],
        out_specs=[gspec(pair_map(None)), gspec(pair_map(None))],
    )
    return pl.pallas_call(
        _cross_pair_kernel,
        out_shape=[shape, shape],
        grid_spec=grid_spec,
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )


@functools.lru_cache(maxsize=16)
def _compile_merge_pair(n_members: int, nblk: int, s_rows: int, b_log2: int,
                        interpret: bool):
    spec = pl.BlockSpec((n_members, s_rows, LANES), lambda g, s: (g, _Z, _Z),
                        memory_space=pltpu.VMEM)
    shape = jax.ShapeDtypeStruct((nblk, s_rows, LANES), jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nblk // n_members,),
        in_specs=[spec, spec],
        out_specs=[spec, spec],
    )
    return pl.pallas_call(
        functools.partial(_merge_pair_kernel, n_members=n_members,
                          s_rows=s_rows, b_log2=b_log2),
        out_shape=[shape, shape],
        grid_spec=grid_spec,
        # Raised budget: the 8-member shape (tail_bits=3 experiment)
        # needs 25.6 MiB scoped vmem; no effect on the 2/4-member forms.
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )


def sort_pairs_padded(k, p, n_pow2: int, b_log2: int,
                      interpret: bool = False, relayout: bool = True,
                      tail_bits: int | None = None):
    """Bitonic-sort uint32 ``(k, p)`` pairs by the KEY plane only.

    Same network as :func:`sort_padded`; the payload plane rides every
    exchange via ``out_k == k`` routing.  Equal keys keep their own
    payloads at every comparator, so the output payload order within an
    equal-key run is an arbitrary (but deterministic) permutation — the
    64-bit caller fixes runs afterwards (``kernels.sort_two_words``).

    ``relayout`` (round 5, default): stages with >= 1 single cross
    layer run the rotation-relayout schedule — fused 2-bit (odd
    residue: one 1-bit) closure visits at 2n traffic per visit instead
    of 3n per layer, closed by the rotation-aware merge.  ``False``
    keeps the round-4 one-layer-at-a-time cross path (the A/B
    baseline; see BASELINE.md round-5 section).

    ``tail_bits`` (relayout only; 2 or 3): cross bits fused into the
    stage-final merge.  Default 2; 3 trades 4 closure visits for
    8-member merges — measured session-dependent (see the tail
    selection comment below), kept for pricing.

    Returns ``(k_sorted, p_permuted)``, both flat uint32 [n_pow2].
    """
    t = n_pow2.bit_length() - 1
    assert 1 << t == n_pow2 and t >= b_log2
    s_rows = 1 << (b_log2 - LANES_LOG2)
    nblk = n_pow2 >> b_log2
    k = lax.bitcast_convert_type(k ^ jnp.uint32(0x80000000), jnp.int32)
    p = lax.bitcast_convert_type(p, jnp.int32)  # payload: bits only
    kb = k.reshape(nblk, s_rows, LANES)
    pb = p.reshape(nblk, s_rows, LANES)

    kb, pb = _compile_block_sort_pair(nblk, s_rows, b_log2, interpret)(kb, pb)

    # Merge tail width: 2 stays the shipped default.  The 3-bit tail
    # (8-member rot-merge at bpm=1; drops 4 closure visits at 2^26)
    # was priced same-process on chip across three sessions and
    # STRADDLES parity: 1.08x and 1.29x faster in sessions with a high
    # per-kernel overhead, 0.97x (slower) in a clean session where the
    # 4-member merge pipelines better.  Clean sessions are the headline
    # regime, so tail=2 ships; ``tail_bits=3`` remains available and
    # tested.
    if tail_bits is not None:
        if not relayout:
            raise ValueError("tail_bits applies to the relayout schedule "
                             "only (the r4 path keeps its 2-bit tail)")
        if tail_bits not in (2, 3):
            raise ValueError(f"tail_bits={tail_bits!r}: supported widths "
                             "are 2 and 3 (wider 2^tail-member merges "
                             "exceed the scoped-vmem budget)")
    tail = tail_bits if (relayout and tail_bits is not None) \
        else _PAIR_MERGE_BITS
    cross = (None if relayout else
             (_compile_cross_pair(nblk, s_rows, interpret)
              if t > b_log2 + tail else None))

    for m in range(b_log2 + 1, t + 1):
        nbits = m - b_log2
        if relayout and nbits > tail:
            # Rotation-relayout schedule: highest logical bit first, so
            # an odd single-layer count leads with the 1-bit visit.
            n_single = nbits - tail
            jarr = jnp.asarray([nbits], jnp.int32)
            if n_single % 2:
                kb, pb = _compile_relayout_cross_pair(
                    2, nblk, s_rows, interpret, bpm=2)(jarr, kb, kb, pb, pb)
            visit2 = _compile_relayout_cross_pair(4, nblk, s_rows, interpret,
                                                  bpm=2)
            for _ in range(n_single // 2):
                kb, pb = visit2(jarr, *([kb] * 4), *([pb] * 4))
            nm = 1 << tail
            kb, pb = _compile_rot_merge_pair(
                nblk, s_rows, b_log2, interpret, tail=tail,
                bpm=2 if tail == 2 else 1)(
                jarr, *([kb] * nm), *([pb] * nm))
            continue
        for sj in range(nbits - 1, tail - 1, -1):
            kb, pb = cross(jnp.asarray([sj - tail, nbits], jnp.int32),
                           kb, kb, pb, pb)
        g_final = 1 << min(nbits, tail)
        merge = _compile_merge_pair(g_final, nblk, s_rows, b_log2, interpret)
        kb, pb = merge(jnp.asarray([m], jnp.int32), kb, pb)
    k_out = lax.bitcast_convert_type(kb.reshape(-1), jnp.uint32)
    p_out = lax.bitcast_convert_type(pb.reshape(-1), jnp.uint32)
    return k_out ^ jnp.uint32(0x80000000), p_out


# ------------------------------------------- relayout cross fusion (r5)
#
# The round-4 phase split attributed 56% of the pair network to its 36
# single cross layers (round 5's partial-network attribution corrected
# that to ~44% — BASELINE.md — but they were the biggest addressable
# phase either way): each one reads the whole array TWICE (both sides
# of the pair, so one output array receives every group) and writes it
# once — 3n traffic per layer, measured 1.89 ms against a 0.75 ms
# streaming floor at 2^26.  The wall named in BASELINE.md: consecutive cross
# layers at block bits (j, j-1) form 4-way XOR-closures whose members
# are NOT contiguous, and a pallas grid step cannot write 4 scattered
# windows of one output array.
#
# The fix is a *rotation relayout*: the closure members CAN be read
# scattered (input index maps are arbitrary), so a grid step reads the
# 4 blocks of one closure over the top two unprocessed block bits,
# applies BOTH layers in VMEM, and writes one CONTIGUOUS 4-block group
# — which implicitly rotates the two processed bits to the bottom of
# the physical block index.  The invariant that makes one kernel serve
# every visit: after each visit the next unprocessed logical bits sit
# at the TOP of the physical index again, so every visit is "process
# phys top bits, rotate them down", with the same index maps.  After
# all visits the stage's merge reads its members through the
# accumulated rotation (phys = s*2^(J-2) + h within the segment) and
# writes natural order — the permutation never escapes the stage.
#
# Traffic per 2 layers: n read + n write (vs 6n for two single cross
# layers).  Segment bits (>= J) never move, and every member of a
# closure shares them, so the stage direction stays one scalar flip.


def _closure_ladder(ks, ps, c: int):
    """The pairwise key min/max + ``out_k == k`` payload-routing ladder
    over an XOR-closure of ``2^c`` members, highest bit first — shared
    by the merge tails and the relayout visits (the tie rule — equal
    keys keep their own payloads on both sides — must stay identical
    across every schedule)."""
    n_members = len(ks)
    for kbit in range(c - 1, -1, -1):
        for i in range(n_members):
            if (i >> kbit) & 1:
                continue
            jm = i | (1 << kbit)
            lo = jnp.minimum(ks[i], ks[jm])
            hi = jnp.maximum(ks[i], ks[jm])
            p_lo = jnp.where(lo == ks[i], ps[i], ps[jm])
            p_hi = jnp.where(hi == ks[jm], ps[jm], ps[i])
            ks[i], ks[jm] = lo, hi
            ps[i], ps[jm] = p_lo, p_hi


def _relayout_cross_pair_kernel(s_ref, *refs, n_members: int, bpm: int):
    """Fused visit over the top ``c = log2(n_members)`` physical block
    bits of each 2^J-block segment (J = ``s_ref[0]`` in block bits):
    the c cross layers of one XOR-closure, highest logical bit first,
    in one VMEM visit.  ``refs`` = n_members key refs, n_members
    payload refs, then the key/payload outputs.  ``bpm`` = consecutive
    blocks per member window (``bpm = 2`` halves the grid and doubles
    the DMA size — each grid step carries two whole closures at
    adjacent q; measured: single-block member DMAs ran the visit at
    ~2x the streaming floor).  Sub-window b of member s belongs to
    closure q = bpm*w + b and writes output row ``b*n_members + s``."""
    j_bits = s_ref[0]
    g = pl.program_id(0)
    c = n_members.bit_length() - 1
    lb = bpm.bit_length() - 1
    desc = ((g >> (j_bits - lb - c)) & 1) == 1  # segment bit = flat bit m
    ok_ref, op_ref = refs[2 * n_members], refs[2 * n_members + 1]
    for b in range(bpm):
        ks = [jnp.where(desc, ~refs[i][b], refs[i][b])
              for i in range(n_members)]
        ps = [refs[n_members + i][b] for i in range(n_members)]
        _closure_ladder(ks, ps, c)
        for i in range(n_members):
            ok_ref[b * n_members + i] = jnp.where(desc, ~ks[i], ks[i])
            op_ref[b * n_members + i] = ps[i]


@functools.lru_cache(maxsize=16)
def _compile_relayout_cross_pair(n_members: int, nblk: int, s_rows: int,
                                 interpret: bool, bpm: int = 2):
    """One visit = grid over output groups of ``bpm * n_members``
    contiguous blocks; member ``s`` reads the PHYSICAL ``bpm``-block
    window at ``(seg << J') + (s << (J'-c)) + w`` in window units
    (J' = J - log2(bpm)) — the closures over the segment's top c
    physical bits for ``bpm`` adjacent q — and lands contiguously, so
    the c bits rotate to the bottom of the physical block index."""
    c = n_members.bit_length() - 1
    lb = bpm.bit_length() - 1

    def member_map(s):
        def f(g, s_ref):
            j_w = s_ref[0] - lb       # segment bits in window units
            qbits = j_w - c
            seg = g >> qbits
            w = g & ((1 << qbits) - 1)
            return ((seg << j_w) + (s << qbits) + w, _Z, _Z)
        return f

    mspec = lambda s: pl.BlockSpec((bpm, s_rows, LANES), member_map(s),
                                   memory_space=pltpu.VMEM)
    ospec = pl.BlockSpec((bpm * n_members, s_rows, LANES),
                         lambda g, s: (g, _Z, _Z),
                         memory_space=pltpu.VMEM)
    shape = jax.ShapeDtypeStruct((nblk, s_rows, LANES), jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nblk // (bpm * n_members),),
        in_specs=[mspec(s) for s in range(n_members)] * 2,
        out_specs=[ospec, ospec],
    )
    return pl.pallas_call(
        functools.partial(_relayout_cross_pair_kernel, n_members=n_members,
                          bpm=bpm),
        out_shape=[shape, shape],
        grid_spec=grid_spec,
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )


def _rot_merge_pair_kernel(s_ref, *refs, n_members: int, s_rows: int,
                           b_log2: int, tail: int, bpm: int):
    """:func:`_merge_pair_kernel` with gather inputs: member ``s`` was
    read through the stage's accumulated rotation, so the body is the
    identical cross-tail + sweep; the block id used for the stage
    direction is the segment bit, shared by all members.  ``bpm``
    consecutive rotation groups ride per window (same DMA-width trade
    as the visits); ``n_members = 2^tail``."""
    j_bits = s_ref[0]
    lb = bpm.bit_length() - 1
    g = pl.program_id(0)
    desc = ((g >> (j_bits - tail - lb)) & 1) == 1
    ok_ref, op_ref = refs[2 * n_members], refs[2 * n_members + 1]
    for b in range(bpm):
        ks = [jnp.where(desc, ~refs[i][b], refs[i][b])
              for i in range(n_members)]
        ps = [refs[n_members + i][b] for i in range(n_members)]
        _closure_ladder(ks, ps, tail)
        for i in range(n_members):
            k, p = _sweep_pair(ks[i], ps[i], b_log2)
            ok_ref[b * n_members + i] = jnp.where(desc, ~k, k)
            op_ref[b * n_members + i] = p


@functools.lru_cache(maxsize=16)
def _compile_rot_merge_pair(nblk: int, s_rows: int, b_log2: int,
                            interpret: bool, tail: int = 2, bpm: int = 2):
    """Stage-final merge reading through the accumulated rotation: after
    the visits consumed logical bits J-1..tail, the remaining logical
    bits (tail-1..0) sit at the TOP of the physical index — member
    ``s`` of logical group ``h`` lives at phys
    ``(seg << J) + (s << (J-tail)) + h`` (consecutive h adjacent, so
    ``bpm`` groups share one window).  Writes natural logical order
    (contiguous groups of 2^tail), closing the stage's permutation."""
    n_members = 1 << tail
    lb = bpm.bit_length() - 1

    def member_map(s):
        def f(g, s_ref):
            j_w = s_ref[0] - lb
            wbits = j_w - tail
            seg = g >> wbits
            w = g & ((1 << wbits) - 1)
            return ((seg << j_w) + (s << wbits) + w, _Z, _Z)
        return f

    mspec = lambda s: pl.BlockSpec((bpm, s_rows, LANES), member_map(s),
                                   memory_space=pltpu.VMEM)
    ospec = pl.BlockSpec((bpm * n_members, s_rows, LANES),
                         lambda g, s: (g, _Z, _Z),
                         memory_space=pltpu.VMEM)
    shape = jax.ShapeDtypeStruct((nblk, s_rows, LANES), jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nblk // (bpm * n_members),),
        in_specs=[mspec(s) for s in range(n_members)] * 2,
        out_specs=[ospec, ospec],
    )
    return pl.pallas_call(
        functools.partial(_rot_merge_pair_kernel, n_members=n_members,
                          s_rows=s_rows, b_log2=b_log2, tail=tail, bpm=bpm),
        out_shape=[shape, shape],
        grid_spec=grid_spec,
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )


def _fix_runs_pair_kernel(k_ref, p_ref, o_ref, *, passes: int, s_rows: int):
    """In-VMEM segment-masked odd-even transposition: ``passes`` passes
    of lo-exchange within equal-hi runs, per block.  The XLA formulation
    of the same passes costs ~6 ms/pass at 2^26 (measured — the
    shift-by-one copies stream the whole plane from HBM every pass);
    here all passes run on one VMEM visit.

    Neighbor construction in the natural ``[S, 128]`` layout: flat
    ``i+1`` is ``lane+1`` with a row carry at lane 127 — one cheap
    sublane roll plus one lane roll for the carry column, selected by
    the lane mask.  The block's last element pairs with nothing (its
    neighbor wraps); runs crossing block boundaries are finished by the
    XLA boundary-strip pass (``kernels._fix_boundary``).

    The lo plane is compared in the sign-flipped int32 domain (unsigned
    order; Mosaic has no unsigned vector compare) and unflipped on the
    way out.  hi is compared for equality only — flip-invariant.
    """
    hi = k_ref[0]
    lo = p_ref[0] ^ jnp.int32(-(2**31))
    lane = lax.broadcasted_iota(jnp.int32, hi.shape, 1)
    row = lax.broadcasted_iota(jnp.int32, hi.shape, 0)
    at_carry = lane == (LANES - 1)
    at_zero = lane == 0
    last = at_carry & (row == s_rows - 1)

    def nxt(v):
        up = pltpu.roll(v, np.int32(LANES - 1), 1)
        upc = pltpu.roll(up, np.int32(s_rows - 1), 0)
        return jnp.where(at_carry, upc, up)

    def prv(v):
        dn = pltpu.roll(v, np.int32(1), 1)
        dnc = pltpu.roll(dn, np.int32(1), 0)
        return jnp.where(at_zero, dnc, dn)

    same = (hi == nxt(hi)) & ~last
    par = lane & 1  # flat parity = lane bit 0
    for t in range(passes):
        nb = nxt(lo)
        act = same & (par == (t & 1)) & (lo > nb)
        a32 = act.astype(jnp.int32)
        # element 0's "previous" wraps to the block's last element,
        # which is always inactive -> act 0 -> safe
        pv_on = prv(a32) == 1
        lo = jnp.where(act, nb, jnp.where(pv_on, prv(lo), lo))
    o_ref[0] = lo ^ jnp.int32(-(2**31))


@functools.lru_cache(maxsize=16)
def _compile_fix_runs(nblk: int, s_rows: int, passes: int, interpret: bool):
    spec = pl.BlockSpec((1, s_rows, LANES), _zmap,
                        memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_fix_runs_pair_kernel, passes=passes,
                          s_rows=s_rows),
        out_shape=jax.ShapeDtypeStruct((nblk, s_rows, LANES), jnp.int32),
        grid=(nblk,),
        in_specs=[spec, spec],
        out_specs=spec,
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )


def fix_runs_pairs(hi, lo, passes: int, b_log2: int,
                   interpret: bool = False):
    """Sort ``lo`` within equal-``hi`` runs of length <= ``passes``
    (both flat uint32, ``hi`` sorted, power-of-two length): the in-VMEM
    per-block kernel above; cross-block runs are the caller's
    boundary-strip job."""
    n = hi.shape[0]
    s_rows = 1 << (b_log2 - LANES_LOG2)
    nblk = n >> b_log2
    kb = lax.bitcast_convert_type(hi, jnp.int32).reshape(nblk, s_rows, LANES)
    pb = lax.bitcast_convert_type(lo, jnp.int32).reshape(nblk, s_rows, LANES)
    out = _compile_fix_runs(nblk, s_rows, passes, interpret)(kb, pb)
    return lax.bitcast_convert_type(out.reshape(-1), jnp.uint32)


def bitonic_sort_u32(x, interpret: bool = False):
    """Sort a flat uint32 array ascending; drop-in for ``jnp.sort``.

    Pads to the next power of two with the max sentinel (pads sort to
    the tail and are sliced off — same contract as the API layer's
    pad-with-max, ``models/api.py``).  Arrays smaller than
    ``2^MIN_SORT_LOG2`` fall back to ``lax.sort`` — below that size the
    network's fixed padding/pass structure costs more than it saves.
    """
    n = x.shape[0]
    if n == 0:
        return x
    t = max((n - 1).bit_length(), MIN_SORT_LOG2) if n else 0
    # Break-even: the network runs on the padded 2^t array (~0.6x
    # lax.sort's per-element cost, measured), so heavily padded sizes
    # lose to sorting the exact n with lax.sort.
    if n < (1 << MIN_SORT_LOG2) or n * 10 < (1 << t) * 6:
        return lax.sort([x], num_keys=1, is_stable=False)[0]
    b_log2 = min(BLOCK_LOG2, t)
    n_pow2 = 1 << t
    if n_pow2 != n:
        pad = jnp.full((n_pow2 - n,), jnp.uint32(0xFFFFFFFF), jnp.uint32)
        xp = jnp.concatenate([x, pad])
    else:
        xp = x
    out = sort_padded(xp, n_pow2, b_log2, interpret=interpret)
    return out[:n] if n_pow2 != n else out
