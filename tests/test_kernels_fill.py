"""Unit tests for the gather-free kernel building blocks + 1-device path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpitest_tpu.models.api import sort
from mpitest_tpu.ops import kernels
from mpitest_tpu.parallel.mesh import make_mesh


def test_piecewise_fill_basic():
    starts = jnp.asarray([0, 3, 3, 7], jnp.int32)   # empty segment at k=1→2
    values = jnp.asarray([5, 2, 9, -4], jnp.int32)
    out = np.asarray(jax.jit(kernels.piecewise_fill, static_argnums=2)(starts, values, 10))
    #           j: 0  1  2  3  4  5  6   7   8   9
    expect = np.array([5, 5, 5, 9, 9, 9, 9, -4, -4, -4], np.int32)
    np.testing.assert_array_equal(out, expect)


def test_piecewise_fill_tail_at_n():
    starts = jnp.asarray([0, 4, 4], jnp.int32)      # start == n tail segments
    values = jnp.asarray([1, 7, 8], jnp.int32)
    out = np.asarray(jax.jit(kernels.piecewise_fill, static_argnums=2)(starts, values, 4))
    np.testing.assert_array_equal(out, np.array([1, 1, 1, 1], np.int32))


def test_histogram_sorted_matches_scatter():
    rng = np.random.default_rng(0)
    d = np.sort(rng.integers(0, 256, 5000).astype(np.int32))
    h, lo = jax.jit(kernels.histogram_sorted, static_argnums=1)(jnp.asarray(d), 256)
    expect = np.bincount(d, minlength=256)
    np.testing.assert_array_equal(np.asarray(h), expect)
    np.testing.assert_array_equal(np.asarray(lo), np.concatenate([[0], np.cumsum(expect)[:-1]]))


@pytest.mark.parametrize("algo", ["radix", "sample"])
def test_device_resident_input_multi_device(algo, mesh8, rng):
    """Device-resident jax.Array input on a multi-device mesh: sharded,
    committed-to-one-device, and non-divisible-N variants."""
    from mpitest_tpu.parallel.mesh import key_sharding

    x = rng.integers(-(2**31), 2**31 - 1, size=8 * 512, dtype=np.int32)
    ref = np.sort(x)

    x_sharded = jax.device_put(x, key_sharding(mesh8))
    np.testing.assert_array_equal(sort(x_sharded, algorithm=algo, mesh=mesh8), ref)

    x_committed = jax.device_put(x, jax.devices("cpu")[0])
    np.testing.assert_array_equal(sort(x_committed, algorithm=algo, mesh=mesh8), ref)

    y = rng.integers(0, 2**32, size=1003, dtype=np.uint32)
    y_dev = jax.device_put(y, jax.devices("cpu")[0])
    np.testing.assert_array_equal(sort(y_dev, algorithm=algo, mesh=mesh8), np.sort(y))


@pytest.mark.parametrize("algo", ["radix", "sample"])
@pytest.mark.parametrize("dtype", [np.int64, np.uint64])
def test_device_resident_64bit_input(algo, dtype, mesh8, rng):
    """Device-resident 64-bit keys use the on-device 2-word codec (no host
    round-trip) — requires x64 only to *hold* the input array; the sort
    itself runs entirely on uint32 words."""
    info = np.iinfo(np.dtype(dtype))
    x = rng.integers(info.min, info.max, size=8 * 256 + 5, dtype=dtype,
                     endpoint=True)
    with jax.enable_x64(True):
        x_dev = jnp.asarray(x)
        assert x_dev.dtype == np.dtype(dtype)
        got = sort(x_dev, algorithm=algo, mesh=mesh8)
    np.testing.assert_array_equal(got, np.sort(x))


@pytest.mark.parametrize("n_mesh", [1, 8])
def test_device_resident_float64_host_fallback(n_mesh, rng, monkeypatch):
    """Some TPU stacks cannot lower the f64→u32 bitcast (XLA's x64
    rewrite lacks the rule — observed on v5e via this image's AOT
    service); a device-resident float64 input must then degrade to ONE
    documented host round-trip and still sort exactly, not surface an
    internal compiler error.  The failure is injected here (the CPU
    backend lowers the bitcast fine)."""
    import jax.errors

    from mpitest_tpu.models import api, local
    from mpitest_tpu.utils.trace import Tracer

    calls = {"n": 0}

    def boom(*a, **k):
        calls["n"] += 1

        def f(*args):
            raise jax.errors.JaxRuntimeError(
                "While rewriting computation to not contain X64 element "
                "types: %bitcast-convert injected")
        return f

    monkeypatch.setattr(local, "_f64_encode_broken_platforms", set())
    monkeypatch.setattr(api, "_compile_encode_pad", boom)
    monkeypatch.setattr(local, "_compile_local_device", boom)
    x = (rng.standard_normal(8 * 200 + 3) * 1e9).astype(np.float64)
    with jax.enable_x64(True):
        x_dev = jnp.asarray(x)
        tracer = Tracer()
        got = sort(x_dev, algorithm="radix", mesh=make_mesh(n_mesh),
                   tracer=tracer)
        np.testing.assert_array_equal(got, np.sort(x))
        assert tracer.counters.get("f64_host_fallback") == 1
        # the verdict memoizes: the second call must route straight to the
        # host path without re-attempting the doomed compile
        first_calls = calls["n"]
        tracer2 = Tracer()
        got2 = sort(x_dev, algorithm="radix", mesh=make_mesh(n_mesh),
                    tracer=tracer2)
        np.testing.assert_array_equal(got2, np.sort(x))
        assert tracer2.counters.get("f64_host_fallback") == 1
        assert calls["n"] == first_calls
        # int64 must NOT be silently degraded by the same path...
        y = rng.integers(-(2**62), 2**62, size=1000, dtype=np.int64)
        y_dev = jnp.asarray(y)
        with pytest.raises(jax.errors.JaxRuntimeError, match="bitcast"):
            sort(y_dev, algorithm="radix", mesh=make_mesh(n_mesh))
    # ...and any OTHER runtime error on f64 must re-raise, never
    # masquerade as the lowering gap: plain OOM/preemption, and errors
    # carrying only ONE of the gap's message fragments (a different
    # x64-rewrite failure, an unrelated bitcast error).
    for msg in ("RESOURCE_EXHAUSTED: injected",
                "some other bitcast-convert failure",
                "X64 element types trouble elsewhere"):
        monkeypatch.setattr(local, "_f64_encode_broken_platforms", set())

        def other(*a, _msg=msg, **k):
            def f(*args):
                raise jax.errors.JaxRuntimeError(_msg)
            return f

        monkeypatch.setattr(api, "_compile_encode_pad", other)
        monkeypatch.setattr(local, "_compile_local_device", other)
        with jax.enable_x64(True):
            with pytest.raises(jax.errors.JaxRuntimeError,
                               match=msg.split()[0].split(":")[0]):
                sort(jnp.asarray(x), algorithm="radix", mesh=make_mesh(n_mesh))
        assert not local._f64_encode_broken_platforms


@pytest.mark.parametrize("algo", ["radix", "sample"])
@pytest.mark.parametrize("dtype", [np.int32, np.int64])
def test_single_device_mesh_fast_path(algo, dtype, rng):
    """1-device mesh: both algorithms specialize to the local fused sort."""
    mesh1 = make_mesh(1)
    info = np.iinfo(np.dtype(dtype))
    x = rng.integers(info.min, info.max, size=10_001, dtype=dtype, endpoint=True)
    got = sort(x, algorithm=algo, mesh=mesh1)
    np.testing.assert_array_equal(got, np.sort(x))
    res = sort(x, algorithm=algo, mesh=mesh1, return_result=True)
    assert res.median_probe() == int(np.sort(x)[x.size // 2 - 1])


@pytest.mark.parametrize("algo", ["radix", "sample"])
def test_device_resident_float32(algo, mesh8, rng):
    """Device-resident float32 keys: the on-device totalOrder encode
    (keys.py encode_jax) keeps them off the host; NaN-free data matches
    np.sort byte-for-byte."""
    x = (rng.standard_normal(8 * 300 + 7) * 1e6).astype(np.float32)
    x_dev = jax.device_put(x, jax.devices("cpu")[0])
    got = sort(x_dev, algorithm=algo, mesh=mesh8)
    np.testing.assert_array_equal(got, np.sort(x))
