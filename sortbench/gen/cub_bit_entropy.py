"""CUB's radix-sort benchmark keys: uniform bits with reduced entropy.

A port of the key generator of NVIDIA CCCL's nvbench_helper, which
``cub/benchmarks/bench/radix_sort/keys.cu`` feeds through its
``Entropy`` axis: every key is a uniform random bit pattern of the
key's width, AND-ed with ``and_draws - 1`` further such patterns.  Each
bit is then 1 with probability ``2^-and_draws``, so a key carries
``H(2^-and_draws)`` bits of entropy per bit: 1 draw is ``Entropy=1.000``
(uniform over the type's whole range, negatives included), 2 are
0.811, 3 are 0.544, 4 are 0.337 and 5 are 0.201.  The configuration
states both the entropy and the draws, and they must agree.

CUB's device generator stream is not reproduced: the bits are the raw
64-bit outputs of the seeded generator handed in, ``and_draws`` of them
a key, in order.  Each key depends only on its own draws, so a shorter
chunk of one stream is the prefix of a longer one, and the keys do not
depend on how the harness splits the pool into chunks or threads.
"""

import math

import numpy as np


def entropy_of(and_draws: int) -> float:
    """Bits of entropy per bit of a key AND-ed over ``and_draws`` draws."""
    p = 2.0 ** -and_draws
    return -(p * math.log2(p) + (1 - p) * math.log2(1 - p))


def generate(rng: np.random.Generator, n: int, dtype: str,
             params: dict) -> np.ndarray:
    dt = np.dtype(dtype)
    if dt.kind not in "iu" or dt.itemsize > 8:
        raise ValueError(f"bit-entropy keys are integers, not {dt}")
    k = int(params["and_draws"])
    if k < 1 or f"{entropy_of(k):.3f}" != params["entropy"]:
        raise ValueError(f"and_draws={k} gives entropy "
                         f"{entropy_of(k):.3f}, not {params['entropy']}")
    bits = rng.bit_generator.random_raw(n * k)
    if k > 1:
        bits = np.bitwise_and.reduce(bits.reshape(n, k), axis=1)
    return bits.astype(f"u{dt.itemsize}", copy=False).view(dt)
