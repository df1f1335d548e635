"""Per-primitive isolation tests for the XLA comm layer.

The native shim has ``native/comm_selftest.c`` ("each primitive checked
in isolation so a shim bug cannot hide behind an algorithm bug" —
SURVEY.md §4); this file is its twin for the Python/XLA side
(``mpitest_tpu/parallel/collectives.py``), on the virtual 8-device mesh:
closed-form checks for rank/all_gather/psum/exscan, and randomized
ragged configurations (zero-length segments, overflow past the cap)
for ``ragged_all_to_all`` against a numpy reference — both pack
implementations.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from mpitest_tpu.parallel import collectives as coll
from mpitest_tpu.parallel.mesh import AXIS

P_ = 8  # mesh8 fixture (conftest.py) provides the 8-device virtual mesh


def spmd(mesh, f, in_specs, out_specs, check_vma=True):
    # pallas_call internals mix varying/unvarying operands in ways the
    # vma checker rejects (same exemption as models/api.py's compiles)
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=check_vma))


def test_rank_allgather_psum_pmax(mesh8):
    def f(x):
        r = coll.rank()
        gathered = coll.all_gather(x)          # [P, n]
        total = coll.psum(x)
        biggest = coll.pmax(x)
        return r[None], gathered[None], total, biggest

    x = np.arange(P_ * 4, dtype=np.int32)
    ranks, gathered, total, biggest = spmd(
        mesh8, f, (P(AXIS),), (P(AXIS), P(AXIS), P(), P()),
    )(x)
    np.testing.assert_array_equal(np.asarray(ranks), np.arange(P_))
    # every rank gathered the same full [P, 4] matrix
    g = np.asarray(gathered).reshape(P_, P_, 4)
    for r in range(P_):
        np.testing.assert_array_equal(g[r], x.reshape(P_, 4))
    np.testing.assert_array_equal(np.asarray(total),
                                  x.reshape(P_, 4).sum(axis=0))
    np.testing.assert_array_equal(np.asarray(biggest),
                                  x.reshape(P_, 4).max(axis=0))


def test_exclusive_cumsum():
    x = np.array([[3, 1], [4, 1], [5, 9]], np.int32)
    got = np.asarray(coll.exclusive_cumsum(jnp.asarray(x), axis=0))
    np.testing.assert_array_equal(got, np.array([[0, 0], [3, 1], [7, 2]]))


def test_exscan_counts(mesh8):
    """The MPI_Exscan + Allreduce census rows, in one primitive: H is
    every rank's histogram, tot the global sum, rank_base the exclusive
    prefix over ranks (rank 0 = identity, defined — unlike MPI)."""
    B = 5
    rng = np.random.default_rng(0)
    hists = rng.integers(0, 100, size=(P_, B)).astype(np.int32)

    def f(h):
        H, tot, rank_base = coll.exscan_counts(h.reshape(-1))
        return H[None], tot[None], rank_base[None]

    H, tot, rank_base = spmd(
        mesh8, f, (P(AXIS),), (P(AXIS), P(AXIS), P(AXIS)),
    )(hists.reshape(-1))
    H = np.asarray(H).reshape(P_, P_, B)
    tot = np.asarray(tot).reshape(P_, B)
    rank_base = np.asarray(rank_base).reshape(P_, P_, B)
    want_base = np.cumsum(hists, axis=0) - hists
    for r in range(P_):  # replicated results identical on every rank
        np.testing.assert_array_equal(H[r], hists)
        np.testing.assert_array_equal(rank_base[r], want_base)
        np.testing.assert_array_equal(tot[r], hists.sum(axis=0))


def _ragged_reference(data, starts, cnts, cap):
    """numpy model: recv[d][s] = first min(cnt, cap) elements of the
    segment rank s sent to rank d."""
    recv = np.zeros((P_, P_, cap), np.uint32)
    rcnt = np.zeros((P_, P_), np.int32)
    for d in range(P_):
        for s in range(P_):
            c = min(int(cnts[s, d]), cap)
            seg = data[s, starts[s, d]:starts[s, d] + c]
            recv[d, s, :c] = seg
            rcnt[d, s] = c
    return recv, rcnt


@pytest.mark.parametrize("pack", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("seed,cap_mode", [(0, "fits"), (1, "fits"),
                                           (2, "overflow"), (3, "zeros")])
def test_ragged_all_to_all_random(pack, seed, cap_mode, mesh8):
    """Randomized Alltoallv configurations vs the numpy reference:
    ragged per-peer counts (including all-zero rows), and caps smaller
    than the largest segment (overflow must clip AND report the exact
    global max so the caller can retry)."""
    from mpitest_tpu.ops.pallas_kernels import CHUNK

    rng = np.random.default_rng(seed)
    n = 4 * CHUNK  # per-shard elements; CHUNK-aligned for the Pallas pack
    hi = 0 if cap_mode == "zeros" else 2 * n // P_
    cnts = rng.integers(0, max(hi, 1), size=(P_, P_)).astype(np.int32)
    cnts = np.minimum(cnts, n // P_)  # total per rank must fit its shard
    starts = (np.cumsum(cnts, axis=1) - cnts).astype(np.int32)
    data = rng.integers(0, 2**32, size=(P_, n), dtype=np.uint32)
    # the Pallas pack requires CHUNK-multiple caps (api.py rounds caps
    # accordingly); the XLA spread takes any cap
    cap = CHUNK if (cap_mode != "overflow" or pack.startswith("pallas")) \
        else CHUNK // 8
    if cap_mode == "overflow":
        cnts[0, :] = 0
        cnts[0, 3] = min(n, cap * 3)  # one oversized segment, total <= n
        starts = (np.cumsum(cnts, axis=1) - cnts).astype(np.int32)

    def f(d, st, ct):
        recv, rcnt, mx = coll.ragged_all_to_all(
            (d,), st.reshape(-1), ct.reshape(-1), cap, P_, pack=pack,
        )
        return recv[0][None], rcnt[None], mx

    recv, rcnt, mx = spmd(
        mesh8, f, (P(AXIS), P(AXIS), P(AXIS)), (P(AXIS), P(AXIS), P()),
        check_vma=(pack == "xla"),
    )(data.reshape(-1), starts, cnts)
    recv = np.asarray(recv).reshape(P_, P_, cap)
    rcnt = np.asarray(rcnt).reshape(P_, P_)
    want_recv, want_rcnt = _ragged_reference(data, starts, cnts, cap)
    np.testing.assert_array_equal(rcnt, want_rcnt)
    assert int(mx) == int(cnts.max())  # exact retry cap, globally reduced
    for d in range(P_):
        for s in range(P_):
            np.testing.assert_array_equal(
                recv[d, s, :rcnt[d, s]], want_recv[d, s, :rcnt[d, s]],
                err_msg=f"dst {d} src {s}",
            )
