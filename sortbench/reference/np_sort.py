"""The plain reference of a key sort: NumPy's sort of the same keys.

Independent of the program: it imports nothing of ``mpitest_tpu`` and
takes only the generated input.  Exact total order of an integer dtype
is unique, so the sorted keys are THE answer and the comparison is
exact (limit 0).
"""

import numpy as np


def reference(x: np.ndarray) -> np.ndarray:
    return np.sort(x)


def control(x: np.ndarray) -> np.ndarray:
    """The control: the reference at the precision below int32.  It
    orders the keys by their top 16 bits only (an int16 key), stably, so
    it returns the input's multiset ordered to 16 bits: a sort that skips
    the low digits breaks the exact total order and must fail the check."""
    hi = (x.astype(np.int64) >> (8 * x.dtype.itemsize - 16)).astype(np.int16)
    return x[np.argsort(hi, kind="stable")]
