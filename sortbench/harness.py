"""One run of one cell: key pool, one warm-up call, references, the
measured window with each answer checked as its call returns.

The window drives ``sort_fn(keys, tracer=...)`` — in a benchmark run
``mpitest_tpu.sort`` with the program's defaults and the config's
``algorithm`` — on host keys and gets host keys back, as a batch user
who consumes each answer before asking for the next.  The plain
reference of every pool array (:mod:`sortbench.reference`) is computed
once set-up has been read and before the window opens.  When a call
returns, its answer is compared with the reference of its input at every
position, and then dropped: at most one answer is alive when a call
starts, so the host's memory does not grow with the number of calls.
The window is the calls alone: its length is the sum of the calls'
durations, and the compare between two calls lies outside it.

Numbers compared, each with its limit (an exact comparison: limit 0):

* ``mismatched_keys`` — positions where a returned array differs from
  the reference (a whole call's keys where length or dtype differ);
* ``unanswered_calls`` — calls that raised instead of answering;
* ``fallback_calls`` — calls whose tracer shows a ``*degraded`` or
  ``*fallback`` counter (a hidden host or interpreter path ran);
* ``window_compiles`` — compiles inside the window: the program's
  ``jit_first_calls`` counter plus JAX's own backend-compile events.
"""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Callable

import numpy as np

from sortbench import drive, reduce, spec

#: counter-name parts that mean a hidden fallback or a degradation fired
#: (the rule of the repo's chip_smoke.py, copied)
BAD_COUNTERS = ("degraded", "fallback")

#: JAX's monitoring event for one XLA backend compile (or persistent-cache
#: load) — any such event inside the window is a compile in the window
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

#: every compared number's limit; all are exact counts
LIMITS = {"mismatched_keys": 0, "unanswered_calls": 0, "fallback_calls": 0,
          "window_compiles": 0}

SortFn = Callable[..., np.ndarray]

#: keys compared per slice when an answer is checked (4-8 MiB of keys):
#: the threads share the compare and its temporaries stay small
CHECK_SLICE = 1 << 20

#: threads that compute the references and compare the answers
CHECK_THREADS = min(8, os.cpu_count() or 1)


class CompileCounter:
    """Counts JAX backend compiles in this process (a listener on JAX's
    monitoring events; registered once, never removed)."""

    def __init__(self) -> None:
        import jax

        self.count = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_: Any) -> None:
        if event == BACKEND_COMPILE_EVENT:
            with self._lock:
                self.count += 1


@dataclass
class Call:
    index: int
    pool_index: int
    n: int
    t0: float
    t1: float
    error: str | None
    phases: dict[str, float]
    counters: dict[str, Any]
    compiles: int
    mismatched: int | None = None  # None: unchecked, or no answer

    @property
    def bad_counters(self) -> dict[str, Any]:
        return {k: v for k, v in self.counters.items()
                if any(b in k for b in BAD_COUNTERS) and v}

    @property
    def failed(self) -> bool:
        return bool(self.error or self.mismatched or self.bad_counters
                    or self.compiles)


@dataclass
class Run:
    """What the metric readers see (``e2e/*.py``, ``layers/*.py``)."""

    cell: spec.Cell
    setup_s: float
    window_s: float
    calls: list[Call]
    device: dict[str, Any]
    trace: reduce.Trace | None = None
    checks: dict[str, dict[str, int]] = field(default_factory=dict)

    @property
    def keys_ok(self) -> int:
        return sum(c.n for c in self.calls if not c.failed)

    def phase_share_pct(self, *names: str) -> float:
        """Share of the window, in %, that the calls spent in the
        program's ``Tracer.phase`` spans of these names."""
        total = sum(c.phases.get(p, 0.0) for c in self.calls for p in names)
        return 100.0 * total / self.window_s


#: keys drawn per generator call; each chunk has its own stream, so the
#: keys do not depend on how many threads draw them.  Its temporaries
#: (16 MiB at most) stay under glibc's largest mmap threshold, so the
#: threads reuse heap memory instead of faulting in fresh pages.
GEN_CHUNK = 1 << 19


def make_pool(cell: spec.Cell, seed: int, keys: int | None = None
              ) -> list[np.ndarray]:
    """The cell's pool of input arrays from ``seed`` (any whole number):
    the same seed gives the same arrays.  Chunks are drawn in threads
    (NumPy's draws release the GIL)."""
    cfg = cell.config
    n = int(keys if keys is not None else cfg["keys_per_call"])
    pool = [np.empty(n, dtype=cfg["dtype"])
            for _ in range(int(cell.traffic["pool"]))]

    def fill(job: tuple[int, int]) -> None:
        i, s = job
        m = min(GEN_CHUNK, n - s)
        ss = np.random.SeedSequence(abs(seed),
                                    spawn_key=(int(seed < 0), i, s // GEN_CHUNK))
        pool[i][s:s + m] = cell.generator.generate(
            np.random.default_rng(ss), m, cfg["dtype"],
            cfg.get("distribution_params", {}))

    jobs = [(i, s) for i in range(len(pool)) for s in range(0, n, GEN_CHUNK)]
    with ThreadPoolExecutor(min(16, os.cpu_count() or 1)) as ex:
        list(ex.map(fill, jobs))
    return pool


def mismatches(out: Any, ref: np.ndarray, ex: ThreadPoolExecutor) -> int:
    """Positions where ``out`` differs from ``ref``: all of them where
    the shape or dtype differs, else counted in slices across threads
    (NumPy's compare releases the GIL)."""
    if out.shape != ref.shape or out.dtype != ref.dtype:
        return int(ref.size)
    return sum(ex.map(lambda s: int(np.count_nonzero(
        out[s:s + CHECK_SLICE] != ref[s:s + CHECK_SLICE])),
        range(0, ref.size, CHECK_SLICE)))


def one_call(sort_fn: SortFn, x: np.ndarray, index: int, pool_index: int,
             compiles: CompileCounter, ref: np.ndarray | None = None,
             ex: ThreadPoolExecutor | None = None) -> Call:
    """One timed call, host keys in to a host array out.  With ``ref``
    the answer is compared with it once the call has returned, outside
    ``t0``..``t1``; the answer itself is not kept."""
    import jax

    from mpitest_tpu.utils.trace import Tracer

    tr = Tracer()
    c0 = compiles.count
    out, err = None, None
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("sortbench.call", call=index):
        try:
            out = np.asarray(sort_fn(x, tracer=tr))
        except Exception as e:  # noqa: BLE001 -- a raising call is counted
            err = f"{type(e).__name__}: {e}"
    t1 = time.perf_counter()
    n_compiles = (compiles.count - c0
                  + int(tr.counters.get("jit_first_calls", 0)))
    call = Call(index, pool_index, int(x.size), t0, t1, err,
                dict(tr.phases), dict(tr.counters), n_compiles)
    if ref is not None and out is not None:
        call.mismatched = mismatches(out, ref, ex)
    return call


def check(calls: list[Call]) -> dict[str, dict[str, int]]:
    """Sum the calls' own counts: each compared number beside its limit."""
    values = {
        "mismatched_keys": sum(c.mismatched or 0 for c in calls),
        "unanswered_calls": sum(1 for c in calls if c.error),
        "fallback_calls": sum(1 for c in calls if c.bad_counters),
        "window_compiles": sum(c.compiles for c in calls),
    }
    return {k: {"value": v, "limit": LIMITS[k]} for k, v in values.items()}


def mem_available_mib() -> float | None:
    """``MemAvailable`` of ``/proc/meminfo`` in MiB; None where the host
    has no such file."""
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) / 1024
    except OSError:
        pass
    return None


def device_info(devices: list[Any]) -> dict[str, Any]:
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_process: float, devices: list[Any], sort_fn: SortFn,
             compiles: CompileCounter, keys: int | None = None,
             log: Callable[[str], None] = print) -> Run:
    """Set-up (pool, one warm-up call), the references, then the measured
    window, each call checked as it returns.  ``t_process`` is the
    ``perf_counter`` reading at process start, so ``setup_s`` covers
    JAX's start, the native build and key generation; the references
    come after it and outside the window.  ``keys`` overrides the
    config's keys per call (tests only)."""
    import jax

    t = time.perf_counter()
    pool = make_pool(cell, seed, keys)
    log(f"pool: {len(pool)} x {pool[0].size} {pool[0].dtype} in "
        f"{time.perf_counter() - t:.6f} s")
    drive.warm_up(sort_fn, pool, compiles, log)
    setup_s = time.perf_counter() - t_process
    with ThreadPoolExecutor(CHECK_THREADS) as ex:
        t = time.perf_counter()
        refs = list(ex.map(cell.reference.reference, pool))
        log(f"references: {len(refs)} in {time.perf_counter() - t:.6f} s")
        mem0 = mem_available_mib()
        if trace:
            trace_dir = tempfile.mkdtemp(prefix="sortbench-trace-")
            jax.profiler.start_trace(trace_dir)
        try:
            calls = drive.window(sort_fn, pool, refs, seconds, compiles, ex)
        finally:
            if trace:
                jax.profiler.stop_trace()
        mem1 = mem_available_mib()
    del refs
    log(f"MemAvailable: {mem0} MiB at the window's start, {mem1} MiB at "
        f"its end")
    info = device_info(devices)
    for c in calls:
        log(f"call {c.index} pool[{c.pool_index}] {c.t1 - c.t0:.6f} s "
            f"mismatched {c.mismatched} "
            f"phases {({k: round(v, 6) for k, v in c.phases.items()})} "
            f"counters {c.counters}"
            + (f" error {c.error}" if c.error else ""))
    run = Run(cell, setup_s, sum(c.t1 - c.t0 for c in calls), calls, info)
    if trace:
        t = time.perf_counter()
        run.trace = reduce.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace reduced in {time.perf_counter() - t:.6f} s")
    run.checks = check(calls)
    return run


def result_line(run: Run, trace: bool) -> dict[str, Any]:
    """The contract's last line: end-to-end metrics untraced, per-layer
    metrics traced; a reader that finds nothing is left out."""
    metrics = {}
    for m in (run.cell.per_layer if trace else run.cell.end_to_end):
        v = m.reader.read(run)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    failed = sum(1 for c in run.calls if c.failed)
    correct = bool(run.calls) and all(
        c["value"] <= c["limit"] for c in run.checks.values())
    line: dict[str, Any] = {"correct": correct, "attempted": len(run.calls),
                            "failed": failed, "metrics": metrics,
                            "device": dict(run.device)}
    if trace and run.trace is not None:
        line["device"]["busy_s"] = run.trace.busy_s_mean
        line["device"]["window_s"] = run.trace.window_s
        line["breakdown"] = run.trace.breakdown()
    line["checks"] = run.checks
    return line


class NoChip(RuntimeError):
    """Not the platform or the number of chips the cell asks for."""


def open_program(cell: spec.Cell, log: Callable[[str], None]
                 ) -> tuple[list[Any], SortFn]:
    """The system under test on the cell's chips: JAX's compile cache at
    the checkout's fixed path, a check that JAX sees exactly ``chips``
    TPU devices (:class:`NoChip` otherwise; there is no CPU fallback),
    ``native/libencode.so`` built if missing, and ``mpitest_tpu.sort``
    with its defaults and the config's ``algorithm`` over all of them.
    The harness sets no ``SORT_*`` knob."""
    from mpitest_tpu.utils.platform import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != cell.chips:
        raise NoChip(f"{cell.name} needs {cell.chips} TPU chip(s); JAX "
                     f"found {len(devices)} {devices[0].platform} device(s)")
    outside = sorted(k for k in os.environ if k.startswith("SORT_"))
    log(f"jax {jax.__version__}: {len(devices)} x {devices[0].device_kind}; "
        f"compile cache {cache}; SORT_* set from outside: {outside}")
    lib = spec.ROOT / "native" / "libencode.so"
    if not lib.exists():
        t = time.perf_counter()
        subprocess.run(["make", "-s", "native-encode"], cwd=spec.ROOT,
                       check=True, stdout=sys.stderr)
        log(f"built {lib} in {time.perf_counter() - t:.6f} s")

    import mpitest_tpu
    from mpitest_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(cell.chips)
    algorithm = cell.config["algorithm"]

    def sort_fn(x: np.ndarray, tracer: Any) -> np.ndarray:
        return mpitest_tpu.sort(x, algorithm=algorithm, mesh=mesh,
                                tracer=tracer)

    return devices, sort_fn
