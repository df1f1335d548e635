"""Compile the main path's kernels for a described v5e chip — no chip needed.

The CPU-mesh tests prove the logic under the Pallas interpreter; these
tests prove that the TPU compiler (libtpu, installed here) accepts the
real Mosaic kernels at the sizes users run: the bitonic 1-word engine,
the 64-bit pair engine, the Pallas pack and the remote-DMA exchange
over a 2x2 ICI mesh.  Nothing runs: a compile that passes is not a chip
run (``chip_smoke.py`` is that).

The topology is described inside a module fixture, never at import
time: only one process may load libtpu, and the test workers all import
this file.  Keep every such compile in this one file.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from mpitest_tpu.models import radix_sort, sample_sort
from mpitest_tpu.ops import kernels
from mpitest_tpu.parallel.mesh import AXIS

N_CHIPS = 4


def _describe(**kw):
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2", **kw)
    except Exception as e:  # noqa: BLE001 — no libtpu / unsupported API
        pytest.skip(f"no v5e:2x2 topology can be described here: "
                    f"{type(e).__name__}: {e}")


@pytest.fixture(scope="module")
def topo():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off here
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield _describe()
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices).reshape(N_CHIPS), (AXIS,))


def _words(mesh, n_per_chip, n_words=1):
    sh = NamedSharding(mesh, P(AXIS))
    n_chips = mesh.devices.size
    return tuple(jax.ShapeDtypeStruct((n_chips * n_per_chip,), jnp.uint32,
                                      sharding=sh) for _ in range(n_words))


def _compile_sample(mesh, n, cap, n_words=1, pack="pallas",
                    exchange_engine="lax"):
    n_chips = mesh.devices.size

    def step(words):
        out, cnt, mc = sample_sort.sample_sort_spmd(
            words, n_words, n_chips, cap, 2 * n_chips - 1, pack=pack,
            engine="bitonic", exchange_engine=exchange_engine)
        return (*out, cnt[None], mc)

    fn = jax.shard_map(step, mesh=mesh, in_specs=((P(AXIS),) * n_words,),
                       out_specs=(P(AXIS),) * (n_words + 1) + (P(),),
                       check_vma=False)
    return jax.jit(fn).lower(_words(mesh, n, n_words)).compile()


def _compile_radix(mesh, n, cap, passes, pack, exchange_engine):
    n_chips = mesh.devices.size

    def step(words):
        out, mc = radix_sort.radix_sort_spmd(
            words, 1, 16, n_chips, cap, passes, pack=pack,
            exchange_engine=exchange_engine)
        return out[0], mc

    fn = jax.shard_map(step, mesh=mesh, in_specs=((P(AXIS),),),
                       out_specs=(P(AXIS), P()),
                       check_vma=(pack == "xla"
                                  and exchange_engine == "lax"))
    return jax.jit(fn).lower(_words(mesh, n)).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# ------------------------------------------------ one chip, real sizes

def test_bitonic_1word_2p28_one_chip(one_chip):
    """The 1-word Mosaic bitonic engine at 2^28 keys (1 GiB) — the
    library's default local sort for int32 on one chip."""
    x = jax.ShapeDtypeStruct((1 << 28,), jnp.uint32, sharding=one_chip)
    c = jax.jit(lambda w: kernels.local_sort((w,), engine="bitonic")
                ).lower(x).compile()
    assert _has_kernel(c)


def test_pair_engine_2p26_one_chip(one_chip):
    """The 64-bit pair engine (pair block sort / cross / merge + run
    fix-up kernels + the residual cond) at 2 x 2^26 words."""
    x = jax.ShapeDtypeStruct((1 << 26,), jnp.uint32, sharding=one_chip)
    c = jax.jit(lambda h, l: kernels.sort_two_words_bitonic(h, l)
                ).lower(x, x).compile()
    assert _has_kernel(c)


def test_pair_local_fused_one_chip(one_chip):
    """The fused device-resident 64-bit program (encode + range + sniff
    + lax.cond over 1-word engine / lax / pair engine): every cond
    branch lowers through the TPU compiler."""
    from mpitest_tpu.models.api import _compile_pair_fused

    x = jax.ShapeDtypeStruct((1 << 14,), jnp.int64, sharding=one_chip)
    with jax.enable_x64(True):
        assert _compile_pair_fused("int64", "bitonic").lower(x).compile()


# -------------------------------------- v5e:2x2, the Pallas exchange

def test_sample_pallas_exchange_2p24_per_chip(mesh4):
    """Sample sort with the remote-DMA exchange, the fused Pallas pack
    and the bitonic engine per shard, at 2^24 keys per chip."""
    n = 1 << 24
    c = _compile_sample(mesh4, n, 2 * n // N_CHIPS,
                        pack="pallas", exchange_engine="pallas")
    assert _has_kernel(c)


def test_radix_pallas_exchange_2p24_per_chip(mesh4):
    """A 16-bit-digit radix pass over the remote-DMA exchange and the
    fused pass pack, at 2^24 keys per chip (the two-pass loop with its
    overlapped lane-slot plane is compiled small, below)."""
    n = 1 << 24
    c = _compile_radix(mesh4, n, 2 * n // N_CHIPS, 1, "pallas", "pallas")
    assert _has_kernel(c)


# ------------------------------------- v5e:2x2, small-shape variants

@pytest.mark.parametrize("pack,engine", [("xla", "lax"),
                                         ("pallas", "pallas")])
def test_radix_two_passes_2x2(mesh4, pack, engine):
    """The two-pass radix step on each exchange engine."""
    assert _compile_radix(mesh4, 1 << 14, 1 << 12, 2, pack, engine)


def test_sample_pallas_pack_2x2(mesh4):
    """Sample sort with the per-array Pallas pack and the bitonic
    engine under shard_map, XLA collective exchange."""
    assert _has_kernel(_compile_sample(mesh4, 1 << 14, 1 << 12))


def test_sample_pair_engine_2x2(mesh4):
    """The pair engine as the distributed sample path's 2-word
    per-shard sort."""
    assert _has_kernel(_compile_sample(mesh4, 1 << 14, 1 << 13, n_words=2))


def test_radix_two_slices(topo):
    """Two 2x2 slices (8 chips over DCN + ICI): the 1-D logical axis
    keeps the radix program topology-agnostic."""
    two = _describe(num_slices=2)
    mesh = Mesh(np.array(two.devices).reshape(-1), (AXIS,))
    assert mesh.devices.size == 2 * N_CHIPS
    assert _compile_radix(mesh, 1 << 13, 1 << 11, 2, "xla", "lax")
