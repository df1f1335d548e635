"""The window as a caller that consumes each answer: checked when its
call returns, then dropped.  Stand-in programs on the CPU, at a size a
test can hold; the harness's look for a chip is skipped."""

import time
import weakref

import jax
import numpy as np

from sortbench import harness, spec

CELL = "is_C.1chip"
KEYS = 1 << 12
SECONDS = 0.05
SEED = 2**31 + 29


def _run(sort_fn):
    cell = spec.resolve(CELL)
    run = harness.run_cell(cell, SEED, SECONDS, False, time.perf_counter(),
                           jax.devices()[:cell.chips], sort_fn,
                           harness.CompileCounter(), keys=KEYS,
                           log=lambda m: None)
    return run, harness.result_line(run, False)


def test_at_most_one_answer_is_alive_when_a_call_starts():
    answers, alive = [], []

    def sort_fn(x, tracer):
        alive.append(sum(1 for r in answers if r() is not None))
        out = np.sort(x)
        answers.append(weakref.ref(out))
        time.sleep(0.002)
        return out

    run, line = _run(sort_fn)
    assert line["correct"] is True, line["checks"]
    assert len(run.calls) > 4 and len(alive) == 1 + len(run.calls)
    assert max(alive) <= 1


def test_window_is_the_sum_of_the_calls():
    def sort_fn(x, tracer):
        time.sleep(0.002)
        return np.sort(x)

    run, _ = _run(sort_fn)
    durations = [c.t1 - c.t0 for c in run.calls]
    assert run.window_s == sum(durations)
    assert sum(durations[:-1]) < SECONDS <= run.window_s
    # the checks between the calls lie outside the window
    assert run.window_s < run.calls[-1].t1 - run.calls[0].t0


def test_one_wrong_key_in_one_call_of_many_counts_once():
    seen = []

    def sort_fn(x, tracer):
        seen.append(1)
        out = np.sort(x)
        if len(seen) == 4:  # the window's third call
            out[KEYS // 3] += 1
        time.sleep(0.002)
        return out

    run, line = _run(sort_fn)
    assert len(run.calls) > 4
    assert line["checks"]["mismatched_keys"]["value"] == 1
    assert [c.mismatched for c in run.calls] == [
        int(c.index == 2) for c in run.calls]
    assert line["correct"] is False and line["failed"] == 1
