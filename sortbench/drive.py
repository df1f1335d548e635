"""The one general traffic driver: reads a mix from ``traffic/<name>.json``.

One caller in a closed loop: it sends its next call only when the
previous one has returned.  The mix sets ``pool``, how many seeded input
arrays set-up generates; call ``i`` of the window sorts array
``i % pool``.  Inputs are never modified, so no call re-sorts its own
output.

Set-up makes one call at the cell's exact shape, on the first array of
the pool.  The window meets the others unseen, as a user's fresh data:
a program that compiles per input compiles there, and the check counts
it (``window_compiles``).

The window is the calls alone: its length is the sum of the calls'
durations (``t1 - t0`` on the host's clock), and it starts calls until
that sum reaches ``seconds``; every started call finishes.  Between two
calls, outside the window, the answer just returned is compared with
the reference of its input and dropped, as a caller that consumes each
answer before asking for the next.  In a closed loop with one caller
the sum differs from start-to-last-return only by those gaps.
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


def window(sort_fn: Callable, pool: list[np.ndarray],
           refs: list[np.ndarray], seconds: float, compiles: Any,
           ex: Any) -> list:
    """The measured window: call ``i`` sorts ``pool[i % len(pool)]`` and
    is checked against ``refs`` of the same index as it returns."""
    from sortbench.harness import one_call

    calls: list = []
    spent = 0.0
    while spent < seconds:
        j = len(calls) % len(pool)
        c = one_call(sort_fn, pool[j], len(calls), j, compiles, refs[j], ex)
        calls.append(c)
        spent += c.t1 - c.t0
    return calls
