"""NPB IS ``create_seq`` keys: MAX_KEY/4 x (sum of four uniforms in [0, 1)).

NPB 3.x ``is.c`` draws each key as ``k * (x1 + x2 + x3 + x4)`` with
``k = MAX_KEY / 4`` and four ``randlc`` uniforms: a bell over
[0, MAX_KEY) whose mean is MAX_KEY / 2.  Here each uniform is a 32-bit
fraction ``u / 2**32`` from the seeded generator (NPB's ``randlc``
stream itself is not reproduced: see the config's ``assumed``), so the
key is exactly ``floor(k * sum(u) / 2**32)``, which is
``sum(u) >> (34 - log2 MAX_KEY)``.
"""

import numpy as np


def generate(rng: np.random.Generator, n: int, dtype: str,
             params: dict) -> np.ndarray:
    shift = np.uint64(34 - int(params["max_key_log2"]))
    # two 64-bit draws per key hold its four 32-bit uniforms
    raw = rng.integers(0, 2**64 - 1, size=(2, n), dtype=np.uint64,
                       endpoint=True)
    lo = np.uint64(0xFFFFFFFF)
    total = (raw[0] & lo) + (raw[0] >> np.uint64(32))
    total += raw[1] & lo
    total += raw[1] >> np.uint64(32)
    return (total >> shift).astype(dtype)
