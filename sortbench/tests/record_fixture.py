"""Record a reader-test fixture on the chip: two warm calls, one trace.

    python sortbench/tests/record_fixture.py <out.xplane.pb> [log2_keys]

Builds ``is_C.1chip``'s program (``harness.open_program``: one TPU chip,
the library's defaults), draws a pool of two NPB IS arrays of
``2^log2_keys`` keys (default 20) from a fixed seed, warms the shape up
with one call, then traces two calls, each inside a ``sortbench.call``
annotation, and copies the trace's ``.xplane.pb`` to ``out``.  Exits 2
without a chip.
"""

from __future__ import annotations

import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
SEED = 2147483647


def main(argv: list[str]) -> int:
    sys.path.insert(0, str(ROOT))
    from sortbench import harness, reduce, spec

    out = Path(argv[0])
    log2 = int(argv[1]) if len(argv) > 1 else 20
    cell = spec.resolve("is_C.1chip")
    try:
        _, sort_fn = harness.open_program(cell, print)
    except harness.NoChip as e:
        print(e)
        return 2
    import jax

    compiles = harness.CompileCounter()
    pool = harness.make_pool(cell, SEED, 1 << log2)
    harness.one_call(sort_fn, pool[0], -1, 0, compiles)
    trace_dir = tempfile.mkdtemp(prefix="sortbench-fixture-")
    jax.profiler.start_trace(trace_dir)
    try:
        calls = [harness.one_call(sort_fn, pool[i], i, i, compiles)
                 for i in range(2)]
    finally:
        jax.profiler.stop_trace()
    for c in calls:
        print(f"call {c.index}: {c.t1 - c.t0:.6f} s, error {c.error}, "
              f"compiles {c.compiles}")
    out.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(reduce.find_xplane(trace_dir), out)
    print(f"wrote {out} ({out.stat().st_size} B)")
    return 0 if not any(c.failed for c in calls) else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
