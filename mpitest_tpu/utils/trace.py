"""Debug-log + phase-timing contract.

The reference's observability surface is (a) an integer debug level from
``argv[2]`` gating printf traces with ``[MASTER] [SLAVE] [COMMON] [VERBOSE]
[ERROR]`` prefixes (``mpi_sample_sort.c:30,42,62,117``), and (b) one
``MPI_Wtime`` pair on rank 0 printed to stderr
(``mpi_sample_sort.c:61,201,207``).  This module keeps that CLI contract
(same prefixes, same levels) and adds what the reference lacks: per-phase
wall timers and a structured metrics sidecar hook (see
:mod:`mpitest_tpu.utils.metrics`).
"""

from __future__ import annotations

import sys
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any

from mpitest_tpu.utils.span_schema import PHASE_PREFIX
from mpitest_tpu.utils.spans import Span, SpanLog


@dataclass
class Tracer:
    """Reference-compatible leveled logger + phase timer.

    Prefix vocabulary matches the reference exactly — ``[COMMON]``
    (any-rank step logs, ``mpi_sample_sort.c:30,87``), ``[MASTER]`` /
    ``[SLAVE]`` (root / non-root protocol logs, ``:42,68``),
    ``[VERBOSE]`` (value dumps, ``:84``), ``[ERROR]`` (``:97``).
    ``counters`` accumulates machine-readable measurements (bytes moved,
    pass counts) for the metrics sidecar — observability the reference
    lacks (SURVEY.md §5 metrics row).
    """

    level: int = 0
    phases: dict[str, float] = field(default_factory=dict)
    counters: dict[str, float] = field(default_factory=dict)
    #: Structured span log (utils/spans.py): the ``phase:*`` spans that
    #: feed :attr:`phases`, and sort()'s jit/collective/pass spans.
    #: ``SORT_TRACE=<path>`` streams it as JSONL (wired in models/api.py).
    spans: SpanLog = field(default_factory=SpanLog)
    #: The LAST finished decision record (models/plan.py SortPlan) —
    #: set by sort() at completion so drivers/serve can read the plan
    #: digest without re-parsing the span stream.  One dispatch thread
    #: per tracer by contract, so last-write is the right answer.
    plan: object | None = None

    # -- reference printf contract ------------------------------------
    def common(self, msg: str, min_level: int = 1) -> None:
        if self.level >= min_level:
            print(f"[COMMON] {msg}")

    def verbose(self, msg: str) -> None:
        if self.level >= 1:
            print(f"[VERBOSE] {msg}")

    def master(self, msg: str, min_level: int = 2) -> None:
        if self.level >= min_level:
            print(f"[MASTER] {msg}")

    def slave(self, msg: str, min_level: int = 2) -> None:
        """Non-root protocol log (the reference's per-rank Recv lines,
        ``mpi_sample_sort.c:68,132``)."""
        if self.level >= min_level:
            print(f"[SLAVE] {msg}")

    def error(self, msg: str) -> None:
        print(f"[ERROR] {msg}", file=sys.stderr)

    def count(self, name: str, value: float) -> None:
        self.counters[name] = self.counters.get(name, 0.0) + value

    # -- additions: per-phase timers ----------------------------------
    def __post_init__(self) -> None:
        self.spans.observers.append(self._phase_closed)

    @property
    def span(self) -> Any:
        """``span(name, **attrs)``: a nested structured span on
        :attr:`spans` (see :mod:`mpitest_tpu.utils.spans`), for a
        ``with`` statement.  A span named ``phase:<name>`` is a phase:
        its wall time also adds up in :attr:`phases` under ``<name>``."""
        return self.spans.span

    def _phase_closed(self, s: Span) -> None:
        if s.name.startswith(PHASE_PREFIX) and s.dt:
            name = s.name[len(PHASE_PREFIX):]
            self.phases[name] = self.phases.get(name, 0.0) + s.dt
            if self.level >= 1:
                print(f"[VERBOSE] phase {name}: {s.dt*1e3:.3f} ms")


@contextmanager
def jax_profile(logdir: str | None):
    """Optional jax.profiler trace around the hot region (TPU tracing hook)."""
    if not logdir:
        yield
        return
    import jax

    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
