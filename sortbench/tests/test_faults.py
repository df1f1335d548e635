"""A run with the timed path broken underneath must come out not correct.

The harness's look for a chip is skipped (CPU devices are handed in);
the rest of a run is driven as on the chip, at a size a test can hold:
pool, the set-up call, window, check.  Faults planted under the window:
the answer is the input unchanged; half the keys are left out; one key
is altered where it is produced; a program compiles inside the window.
The control (the reference ordering keys by their top 16 bits only)
fails too, and the sound program passes.
"""

import time

import jax
import numpy as np
import pytest

import mpitest_tpu
from mpitest_tpu.parallel.mesh import make_mesh
from sortbench import harness, spec

CELL = "is_C.1chip"
KEYS = 1 << 12
SECONDS = 0.3
COMPILES = None


def _compiles():
    global COMPILES
    if COMPILES is None:
        COMPILES = harness.CompileCounter()
    return COMPILES


def _program(cell):
    mesh = make_mesh(cell.chips)

    def sort_fn(x, tracer):
        return mpitest_tpu.sort(x, algorithm=cell.config["algorithm"],
                                mesh=mesh, tracer=tracer)
    return sort_fn


def _run(fn_of):
    cell = spec.resolve(CELL)
    fn = fn_of(cell, _program(cell))
    run = harness.run_cell(cell, 2**31 + 17, SECONDS, False,
                           time.perf_counter(), jax.devices()[:cell.chips],
                           fn, _compiles(), keys=KEYS, log=lambda m: None)
    return run, harness.result_line(run, False)


def unchanged(cell, sort_fn):
    return lambda x, tracer: x.copy()


def half(cell, sort_fn):
    return lambda x, tracer: sort_fn(x[: x.size // 2], tracer)


def altered(cell, sort_fn):
    def fn(x, tracer):
        out = sort_fn(x, tracer).copy()
        out[x.size // 3] += 1
        return out
    return fn


def control(cell, sort_fn):
    return lambda x, tracer: cell.reference.control(x)


@pytest.mark.parametrize("fault", [unchanged, half, altered, control])
def test_fault_is_not_correct(fault):
    _, line = _run(fault)
    assert line["attempted"] > 0
    assert line["correct"] is False
    assert line["checks"]["mismatched_keys"]["value"] > 0
    assert line["failed"] == line["attempted"]


def test_compile_in_the_window_is_not_correct():
    def fault(cell, sort_fn):
        def fn(x, tracer):
            jax.jit(lambda v: v + 1)(np.int32(x.size))  # a fresh program
            return sort_fn(x, tracer)
        return fn

    _, line = _run(fault)
    assert line["correct"] is False
    assert line["checks"]["mismatched_keys"]["value"] == 0
    assert line["checks"]["window_compiles"]["value"] >= line["attempted"]
    assert line["failed"] == line["attempted"]


def test_window_meets_the_pool_unseen():
    seen = []

    def spy(cell, sort_fn):
        def fn(x, tracer):
            seen.append(x)
            return sort_fn(x, tracer)
        return fn

    run, line = _run(spy)
    pool = harness.make_pool(spec.resolve(CELL), 2**31 + 17, KEYS)
    assert len(pool) >= 2 and len(seen) == 1 + line["attempted"]
    assert np.array_equal(seen[0], pool[0])  # the one set-up call
    assert [c.pool_index for c in run.calls] == [
        i % len(pool) for i in range(len(run.calls))]
    assert np.array_equal(seen[2], pool[1])


def test_sound_program_is_correct():
    _, line = _run(lambda cell, sort_fn: sort_fn)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 1
    assert list(line)[-1] == "checks"
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in line["checks"].values())
    assert line["metrics"]["sort_mkeys_per_s"]["value"] > 0
    assert set(line["metrics"]) == {"sort_mkeys_per_s", "setup_s"}
