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


def test_pair_sort_2p28_one_chip(one_chip):
    """The one-chip route's pair engine program (``pair_sort``) at
    2 x 2^28 words, the shape of ``uniform_i64.1chip``: it compiles with
    about one more copy of the words in temporaries; the run fix-up
    kernel carries ``stage="pair_fixup"`` beside ``layer="local_sort"``
    and the pair network does not; the residual and the high-word tie
    count come out of one tagged reduction."""
    import re

    from mpitest_tpu.models.local import _compile_pair_sort

    n = 1 << 28
    x = jax.ShapeDtypeStruct((n,), jnp.uint32, sharding=one_chip)
    lowered = _compile_pair_sort("bitonic").lower(x, x)
    lines = lowered.as_text().splitlines()
    fix = [ln for ln in lines if 'kernel_name = "bitonic.fix_runs"' in ln]
    assert fix and all('stage = "pair_fixup"' in ln
                       and 'layer = "local_sort"' in ln for ln in fix)
    net = [ln for ln in lines if "tpu_custom_call" in ln
           and 'kernel_name = "bitonic.fix_runs"' not in ln]
    assert net and not any("pair_fixup" in ln for ln in net)
    c = lowered.compile()
    assert _has_kernel(c)
    assert c.memory_analysis().temp_size_in_bytes <= 8 * n + (1 << 24)
    sums = r"= \(s32\[\]\{[^}]*\}, s32\[\]\{[^}]*\}\) fusion\("
    two_sums = re.compile(sums)
    assert [ln for ln in c.as_text().splitlines()
            if two_sums.search(ln) and 'stage="pair_fixup"' in ln]


def test_pair_local_fused_one_chip(one_chip):
    """The fused device-resident 64-bit program (encode + range + sniff
    + lax.cond over 1-word engine / lax / pair engine): every cond
    branch lowers through the TPU compiler."""
    from mpitest_tpu.models.local import _compile_pair_fused

    x = jax.ShapeDtypeStruct((1 << 14,), jnp.int64, sharding=one_chip)
    with jax.enable_x64(True):
        assert _compile_pair_fused("int64", "bitonic").lower(x).compile()


@pytest.mark.parametrize("dtype", [jnp.int64, jnp.uint64])
def test_pair_split_2p27_one_chip(one_chip, dtype):
    """The split and plan of raw 64-bit host keys at 2^27 (1 GiB in,
    two 512 MiB words out): it lowers, and writes the words straight
    from the chip's 64-bit halves, with no interleaved temporary (a
    ``bitcast_convert_type`` split holds 1 GiB of one)."""
    from mpitest_tpu.models.local import _compile_pair_split

    with jax.enable_x64(True):
        x = jax.ShapeDtypeStruct((1 << 27,), dtype, sharding=one_chip)
        c = _compile_pair_split(np.dtype(dtype).name).lower(x).compile()
    assert c.memory_analysis().temp_size_in_bytes <= 1 << 20


def test_pair_split_2p28_one_chip(one_chip):
    """The split and plan of ``uniform_i64.1chip``'s 2^28 raw int64 keys
    (2 GiB in): no interleaved temporary at this size either."""
    from mpitest_tpu.models.local import _compile_pair_split

    with jax.enable_x64(True):
        x = jax.ShapeDtypeStruct((1 << 28,), jnp.int64, sharding=one_chip)
        c = _compile_pair_split("int64").lower(x).compile()
    assert c.memory_analysis().temp_size_in_bytes <= 1 << 20


@pytest.mark.parametrize("dtype", ["int32", "uint32", "int64", "uint64"])
def test_device_decode_and_keys_verifier_2p27_one_chip(one_chip, dtype):
    """The egress decode and the verifier of the decoded keys at 2^27:
    both lower, a 1-word decode writes into the donated words' buffer,
    and 64-bit keys come out as rows of their uint32 halves, with no
    padded interleave (a flat ``[2N]`` one asks for 64 GiB)."""
    from mpitest_tpu.models import verify
    from mpitest_tpu.models.local import _compile_decode

    n, two = 1 << 27, dtype.endswith("64")
    w = jax.ShapeDtypeStruct((n,), jnp.uint32, sharding=one_chip)
    c = _compile_decode(dtype, True).lower(*(w,) * (1 + two)).compile()
    m = c.memory_analysis()
    assert m.alias_size_in_bytes == (0 if two else 4 * n)
    assert m.temp_size_in_bytes <= (16 if two else 0) * n
    keys = jax.ShapeDtypeStruct((n // 128, 256) if two else (n,),
                                jnp.uint32 if two else jnp.dtype(dtype),
                                sharding=one_chip)
    with jax.enable_x64(two):
        assert verify._compile_keys_contig(dtype, n, n).lower(keys).compile()


def test_int64_decode_and_keys_verifier_2p28_one_chip(one_chip):
    """``uniform_i64.1chip``'s egress at 2^28 int64 keys: the decode into
    ``[N/128, 256]`` rows with at most 16 B a key of temporaries, and the
    verifier of those rows, each within half of one v5e's 16 GB."""
    from mpitest_tpu.models import verify
    from mpitest_tpu.models.local import _compile_decode

    n = 1 << 28
    w = jax.ShapeDtypeStruct((n,), jnp.uint32, sharding=one_chip)
    c = _compile_decode("int64", True).lower(w, w).compile()
    rows = jax.ShapeDtypeStruct((n // 128, 256), jnp.uint32,
                                sharding=one_chip)
    assert c.memory_analysis().temp_size_in_bytes <= 16 * n
    with jax.enable_x64(True):
        v = verify._compile_keys_contig("int64", n, n).lower(rows).compile()
    for m in (c.memory_analysis(), v.memory_analysis()):
        assert (m.argument_size_in_bytes + m.output_size_in_bytes
                + m.temp_size_in_bytes) <= 8 << 30


def test_bitonic_kernels_carry_names_and_layer(one_chip):
    """Every 1-word bitonic kernel is named (``bitonic.*``) and tagged
    ``layer="local_sort"``, so the chip trace can tell it apart; no
    local-sort kernel names a mesh axis (the exchange kernel's mark)."""
    x = jax.ShapeDtypeStruct((1 << 20,), jnp.uint32, sharding=one_chip)
    text = jax.jit(lambda w: kernels.local_sort((w,), engine="bitonic")
                   ).lower(x).as_text()
    for name in ("bitonic.block_sort", "bitonic.merge",
                 "bitonic.relayout_cross", "bitonic.rot_merge"):
        assert f'kernel_name = "{name}"' in text, name
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert calls and all('layer = "local_sort"' in ln for ln in calls)
    assert "mesh_axes" not in text


# -------------------------------------- v5e:2x2, the Pallas exchange

def test_sample_pallas_exchange_2p24_per_chip(mesh4):
    """Sample sort with the remote-DMA exchange, the fused Pallas pack
    and the bitonic engine per shard, at 2^24 keys per chip."""
    n = 1 << 24
    c = _compile_sample(mesh4, n, 2 * n // N_CHIPS,
                        pack="pallas", exchange_engine="pallas")
    assert _has_kernel(c)


def test_radix_over_pallas_exchange_2p24_per_chip(mesh4):
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
