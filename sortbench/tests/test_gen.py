"""The generator: NPB keys in [0, 2^23) around 2^22, and the same seed
giving the same pool."""

import numpy as np
import pytest

from sortbench import harness, spec


def _pool(cell_name, seed, keys):
    return harness.make_pool(spec.resolve(cell_name), seed, keys)


def test_npb_keys_bounds_and_mean():
    x = np.concatenate(_pool("is_C.1chip", 2**33 + 5, 1 << 20))
    assert x.dtype == np.int32
    assert x.min() >= 0 and x.max() < 1 << 23
    assert abs(x.mean() / 2**22 - 1) < 1e-3
    # a bell, not a uniform: the middle half of the range holds most keys
    mid = np.count_nonzero((x >= 1 << 21) & (x < 3 << 21)) / x.size
    assert 0.85 < mid < 0.95


@pytest.mark.parametrize("seed", [0, -7, 2**31 + 9, 2**70])
def test_same_seed_same_pool(seed):
    a = _pool("is_C.1chip", seed, (1 << 22) + 3)
    b = _pool("is_C.1chip", seed, (1 << 22) + 3)
    assert all(np.array_equal(u, v) for u, v in zip(a, b))
    assert not np.array_equal(a[0], a[1])
    assert not np.array_equal(a[0], _pool("is_C.1chip", seed + 1,
                                          (1 << 22) + 3)[0])
