"""The one general traffic driver: reads a mix from ``traffic/<name>.json``.

One caller in a closed loop: it sends its next call only when the
previous one has returned.  The mix sets ``pool``, how many seeded input
arrays set-up generates; call ``i`` of the window sorts array
``i % pool``.  Inputs are never modified, so no call re-sorts its own
output.

Set-up makes one call at the cell's exact shape, on the first array of
the pool.  The window meets the others unseen, as a user's fresh data:
a program that compiles per input compiles there, and the check counts
it (``window_compiles``).  The window starts calls until ``seconds``
have passed and ends at the last call's return (a call boundary); every
started call finishes.
"""

from __future__ import annotations

import time
from typing import Any, Callable

import numpy as np


def warm_up(sort_fn: Callable, pool: list[np.ndarray], compiles: Any,
            log: Callable[[str], None]) -> None:
    """The set-up call: the first array of the pool, sorted once."""
    from sortbench.harness import one_call

    c = one_call(sort_fn, pool[0], -1, 0, compiles)
    log(f"warm-up call: {c.t1 - c.t0:.6f} s, {c.compiles} compiles"
        + (f", error {c.error}" if c.error else ""))


def window(sort_fn: Callable, pool: list[np.ndarray], seconds: float,
           compiles: Any) -> tuple[list, float, float]:
    """The measured window: returns (calls, start, end) on the host's
    ``perf_counter`` clock."""
    from sortbench.harness import one_call

    calls = []
    t_start = time.perf_counter()
    deadline = t_start + seconds
    while time.perf_counter() < deadline:
        j = len(calls) % len(pool)
        calls.append(one_call(sort_fn, pool[j], len(calls), j, compiles))
    return calls, t_start, calls[-1].t1
