"""YCSB ``CoreWorkload`` keys: ``requestdistribution=zipfian``, hashed inserts.

A vectorised port of YCSB's (github.com/brianfrankcooper/YCSB, ``core``)
key choice for a read or update:

1. ``ScrambledZipfianGenerator`` draws a rank from
   ``ZipfianGenerator(0, ITEM_COUNT, 0.99, ZETAN)`` (Gray et al., SIGMOD
   1994: ``u`` uniform, ``uz = u * zetan``; rank 0 if ``uz < 1``, rank 1
   if ``uz < 1 + 0.5**theta``, else
   ``floor(items * (eta*u - eta + 1)**(1/(1-theta)))``).  The Java
   bounds are inclusive, so ``items = ITEM_COUNT + 1``.
2. It scrambles the rank: ``keynum = fnvhash64(rank) % itemcount`` with
   ``itemcount = recordcount + 1`` (``CoreWorkload`` builds the chooser
   over ``[0, recordcount]``) and ``nextKeynum`` draws again while
   ``keynum > recordcount - 1``.
3. ``insertorder=hashed``: ``buildKeyName`` hashes the key number again,
   ``fnvhash64(keynum)``, a non-negative int64 (the ``user`` prefix and
   the decimal string are not part of the key sorted here).

``fnvhash64`` is FNV-1a over the 8 octets of a long, low to high, then
``Math.abs`` of the signed result (which leaves ``Long.MIN_VALUE``
negative, as Java does).  Java's ``Random`` stream is not reproduced:
every uniform comes from the seeded generator handed in.  Each key
depends only on its own draws, so the keys do not depend on how the
harness splits the pool into chunks or threads.
"""

import numpy as np

FNV_OFFSET_BASIS_64 = np.uint64(0xCBF29CE484222325)
FNV_PRIME_64 = np.uint64(1099511628211)


def fnvhash64(val: np.ndarray) -> np.ndarray:
    """YCSB ``Utils.fnvhash64`` on an int64 array, as int64."""
    v = np.asarray(val, dtype=np.int64).view(np.uint64)
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, dtype=np.uint64)
    octet = np.empty_like(v)
    for i in range(8):
        np.right_shift(v, np.uint64(8 * i), out=octet)
        np.bitwise_and(octet, np.uint64(0xFF), out=octet)
        np.bitwise_xor(h, octet, out=h)
        np.multiply(h, FNV_PRIME_64, out=h)
    return np.abs(h.view(np.int64))


def zipfian_ranks(u: np.ndarray, items: int, theta: float,
                  zetan: float) -> np.ndarray:
    """``ZipfianGenerator.nextValue`` (base 0) for the uniforms ``u``."""
    zeta2theta = 1.0 + 0.5 ** theta
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2theta / zetan)
    ranks = (items * np.power(eta * u - eta + 1.0, 1.0 / (1.0 - theta))
             ).astype(np.int64)
    uz = u * zetan
    ranks[uz < 1.0 + 0.5 ** theta] = 1
    ranks[uz < 1.0] = 0
    return ranks


def generate(rng: np.random.Generator, n: int, dtype: str,
             params: dict) -> np.ndarray:
    if params["insertorder"] != "hashed":
        raise ValueError("only insertorder=hashed keys are generated")
    items = int(params["zipfian_items"]) + 1
    theta = float(params["zipfian_constant"])
    zetan = float(params["zetan"])
    records = int(params["recordcount"])

    def keynums(m: int) -> np.ndarray:
        ranks = zipfian_ranks(rng.random(m), items, theta, zetan)
        return np.fmod(fnvhash64(ranks), records + 1)

    keynum = keynums(n)
    while True:  # nextKeynum: draw again past the last inserted key
        redo = np.flatnonzero(keynum > records - 1)
        if redo.size == 0:
            break
        keynum[redo] = keynums(redo.size)
    return fnvhash64(keynum).astype(dtype, copy=False)
