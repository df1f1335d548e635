"""Structured span tracing — the nested telemetry layer of SURVEY.md §5.

The flat ``Tracer.phases`` dict answers "how long did phase X take in
total"; it cannot answer "which radix pass, which collective, which jit
compile".  This module adds the structured counterpart: nested ``Span``
events (name, parent, t0/dt, attrs) accumulated by a :class:`SpanLog`
that the existing :class:`~mpitest_tpu.utils.trace.Tracer` owns, emitted
three ways:

* **JSONL event stream** — one self-contained JSON object per completed
  span, appended live to ``SORT_TRACE=<path>`` (the native backends'
  ``COMM_STATS`` sidecar is the same schema family; see
  ``comm/comm_stats.h`` and :mod:`mpitest_tpu.report`).
* **Chrome trace-event export** — :meth:`SpanLog.to_chrome_trace`
  produces the ``{"traceEvents": [...]}`` JSON that chrome://tracing and
  Perfetto open directly.
* **In-process** — ``SpanLog.spans`` for tests and the report CLI.
* **On the profiler's clock** — while a ``jax.profiler`` trace runs
  (``SORT_PROFILE``), every timed span is also the same-named
  ``TraceAnnotation`` on its thread, opened in the caller's frame
  (``SpanLog.span``), so the device trace shows the program's spans
  beside the device ops they wait on, nested among the Python frames as
  the spans nest.

Device-side granularity contract: collectives and radix passes execute
inside ONE fused XLA program, so they are not individually host-timable
— their wall time lives in the enclosing ``jit`` span, and per-op device
timing remains ``SORT_PROFILE``'s job (``jax.profiler``; each op there
carries its layer, :mod:`mpitest_tpu.utils.programs`).  What IS
knowable per collective — and what the MPI-vs-ICI comparison needs — is
the static byte/shape accounting, so ``parallel/collectives.py`` and the
SPMD models emit **trace-time point events** (``dt == 0``) carrying
exact byte counts, nested under the jit span whose compile traced them.
A warm (cache-hit) jit call re-emits nothing; the report CLI aggregates
per compiled program, exactly like ``COMM_STATS`` aggregates per native
run.

Robustness vocabulary (ISSUE 3): the supervisor/verifier layer emits
``fault`` (one point event per injected fault, attrs: site/seq),
``supervisor_retry`` (one per retried dispatch, attrs: label/attempt/
error) and ``verify`` (one per verification, attrs: ok/sorted_ok/fp_ok)
— all point events on this same stream, aggregated by the report CLI's
robustness table, so a chaos drill's evidence rides the ordinary
``SORT_TRACE`` file.

Thread model: one SpanLog per Tracer.  The *nesting* API (``span()`` /
``event()``) remains single-threaded — only the host driver thread opens
nested spans.  Pipeline worker threads (the streaming ingest/egress
stages of :mod:`mpitest_tpu.models.ingest`, which measure their own
parse/encode/DMA intervals with ``perf_counter``) report through the
thread-safe :meth:`SpanLog.record` instead: it allocates ids, retains
and streams under a lock, and parents the span under the innermost span
the driver thread currently has open WITHOUT touching the nesting
stack, so concurrent workers can never corrupt span nesting.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Callable, Iterator

from mpitest_tpu.utils import knobs

#: In-memory retention cap per SpanLog.  phases/counters accumulate by
#: design across runs on a reused Tracer, but retaining every span of
#: every warm run forever would grow without bound (~a dozen spans per
#: sort); past the cap spans still STREAM to SORT_TRACE and still time
#: correctly — they are just not retained for in-process export, and
#: ``SpanLog.dropped`` counts them.
MAX_RETAINED_SPANS = 65_536

#: Version tag stamped on every JSONL line so the report CLI can reject
#: files from a future incompatible schema instead of misparsing them.
SCHEMA = "span.v1"

#: TPU collective -> its native comm.h twin (SURVEY.md §2.3 census) —
#: the shared vocabulary that lets `python -m mpitest_tpu.report` line
#: up TPU span rows against the C backends' COMM_STATS rows.
MPI_EQUIV = {
    "ragged_all_to_all": "alltoallv",
    "all_to_all": "alltoall",
    "all_gather": "allgather",
    "psum": "allreduce",
    "pmax": "allreduce",
}


def merge_intervals(iv: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted, coalesced ``(t0, t1)`` intervals — shared by the report
    CLI's overlap tables and the ingest pipeline's own stats, so both
    compute 'host work ∩ transfer' identically."""
    out: list[list[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def overlap_seconds(a: list[tuple[float, float]],
                    b: list[tuple[float, float]]) -> float:
    """Total intersection of two MERGED interval lists — the wall-clock
    seconds the two activities genuinely ran concurrently.  Clocks are
    process-relative ``perf_counter``, so this is only meaningful for
    intervals from one process."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


@dataclass
class Span:
    """One event: a timed interval (``dt >= 0``) or a point event
    (``dt == 0`` — trace-time collective/pass records)."""

    name: str
    id: int
    parent: int | None
    t0: float               # seconds, process-relative (perf_counter)
    dt: float = 0.0
    attrs: dict[str, object] = field(default_factory=dict)
    #: transient: excluded from the SORT_TRACE stream by the sampler
    #: (SORT_TRACE_SAMPLE < 1); never serialized.  A root's verdict is
    #: inherited by its whole subtree so parent links in the streamed
    #: JSONL always resolve.
    stream_drop: bool = field(default=False, repr=False, compare=False)

    def to_dict(self) -> dict[str, object]:
        # pid scopes the process-relative perf_counter timeline: rows
        # appended to one SORT_TRACE file by different runs must never
        # be compared on t0 (report.py groups overlap math by it).
        return {
            "v": SCHEMA, "name": self.name, "id": self.id,
            "parent": self.parent, "t0": round(self.t0, 9),
            "dt": round(self.dt, 9), "pid": os.getpid(),
            "attrs": self.attrs,
        }


#: Stack of SpanLogs with an open span — `emit()` targets the top one.
#: Module-level so trace-time code (collectives, SPMD models) needs no
#: plumbed-through handle: whatever sort() is running owns the log.
_ACTIVE: list["SpanLog"] = []


def current_log() -> "SpanLog | None":
    return _ACTIVE[-1] if _ACTIVE else None


def emit(name: str, **attrs: object) -> None:
    """Record a point event on the active SpanLog (no-op when tracing is
    off) — the one-line hook the parallel/model layers call."""
    log = current_log()
    if log is not None:
        log.event(name, **attrs)


#: The span scope type (:func:`_scope_type`), built on first use: JAX's
#: profiler is imported lazily, once.
_SCOPE: "type | None" = None


def annotation_type() -> type:
    """``jax.profiler.TraceAnnotation``.  Construct it in the frame that
    runs the annotated work — ``with annotation_type()(name):`` — so that
    on the profiler's trace the annotation nests inside that frame and
    around the work (a helper that constructs it and returns would leave
    the annotation open past its own frame)."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation


def _scope_type() -> type:
    global _SCOPE
    if _SCOPE is None:
        from jax.profiler import TraceAnnotation

        class SpanScope(TraceAnnotation):
            """One open span; each :class:`SpanLog` binds a subclass of
            its own as ``SpanLog.span``.  Calling it opens the span:
            ``__new__`` does the log's bookkeeping and returns, then
            ``TraceAnnotation``'s own ``__init__`` (native code, no
            Python frame) starts the same-named profiler annotation in
            the caller's frame.  So on the trace the annotation nests
            inside the caller and around the body, as the span does.
            While no trace runs the annotation costs its enabled-check,
            and the span's attributes are not formatted."""

            log: "SpanLog"

            def __new__(cls, name: str, **attrs: object) -> "SpanScope":
                scope = super().__new__(cls)
                scope.span, scope.outermost = cls.log._open(name, attrs)
                return scope

            def __enter__(self) -> Span:
                return self.span

            def __exit__(self, *exc: object) -> None:
                # close the span, then the annotation: this frame starts
                # inside the annotation, and ends just after it
                try:
                    type(self).log._close(self.span, self.outermost)
                finally:
                    TraceAnnotation.__exit__(self, *exc)

        # threadlint: disable=TL004 -- idempotent: racing writers bind equal types
        _SCOPE = SpanScope
    return _SCOPE


def maybe_span(
    name: str, **attrs: object,
) -> "contextlib.AbstractContextManager[Span | None]":
    """Span twin of :func:`emit`: a span on the active log, or a no-op
    context manager when tracing is off — what instrumented SPMD model
    code opens around trace-time regions (radix passes, splitter
    rounds).  Opened inside this helper, its profiler annotation starts
    in this frame; those regions run only while JAX traces a program."""
    log = current_log()
    if log is None:
        return contextlib.nullcontext()
    return log.span(name, **attrs)


#: Thread-local request/trace context (ISSUE 10): attributes merged
#: into EVERY span the current thread creates while a context is open.
#: This is how one serve request's ``trace_id`` (and its batch's
#: ``batch_id``) reaches the ``sort`` umbrella, its phases, the
#: supervisor's retry events and the verifier's verdicts WITHOUT
#: plumbing an argument through every layer — the dispatch thread opens
#: the context, everything it runs inherits the identity.
_TRACE_CTX = threading.local()


@contextmanager
def trace_context(**attrs: object) -> Iterator[None]:
    """Attach ``attrs`` (e.g. ``trace_id=...``, ``batch_id=...``) to
    every span this thread creates inside the block.  Nests: inner
    contexts merge over outer ones; explicit span attrs always win over
    context attrs."""
    prev: dict[str, object] | None = getattr(_TRACE_CTX, "attrs", None)
    _TRACE_CTX.attrs = {**prev, **attrs} if prev else dict(attrs)
    try:
        yield
    finally:
        _TRACE_CTX.attrs = prev


def current_trace_context() -> dict[str, object] | None:
    """The attrs the current thread's open :func:`trace_context` would
    stamp (None outside any context)."""
    return getattr(_TRACE_CTX, "attrs", None)


#: Lazily-bound flight-recorder hook (utils/flight_recorder.py): every
#: completed span of every SpanLog lands in the process-wide ring.
#: Bound on first flush so importing spans never drags the knob
#: registry's env reads in at import time.
_flight_record: "Callable[[Span], None] | None" = None


def _flight(s: Span) -> None:
    global _flight_record
    if _flight_record is None:
        from mpitest_tpu.utils import flight_recorder

        _flight_record = flight_recorder.record
    _flight_record(s)


class SpanLog:
    """Accumulates nested spans; exports JSONL and Chrome trace-event.

    ``stream_path``: when set, every completed span appends one JSON
    line immediately (the ``SORT_TRACE`` contract — a crash loses only
    the spans still open, and multiple runs append like any JSONL).
    """

    def __init__(self, stream_path: str | None = None) -> None:
        self.spans: list[Span] = []
        self.stream_path = stream_path
        self.dropped = 0       # spans past MAX_RETAINED_SPANS (streamed only)
        #: observers called with every COMPLETED span (the span-close
        #: path): the live-metrics bridge, tests.  Exceptions are
        #: swallowed — telemetry may never take down the traced path.
        self.observers: list[Callable[[Span], None]] = []
        self._stack: list[int] = []
        self._drop_stack: list[bool] = []   # sampler verdicts, mirrors _stack
        #: trace-context of each open span's OPENER thread (mirrors
        #: _stack) — worker-thread record()s inherit the innermost one.
        self._ctx_stack: list[dict[str, object] | None] = []
        self._next_id = 0
        # SORT_TRACE_SAMPLE stream down-sampling: keep ~rate of the
        # root spans (and each root's whole subtree — parent links in
        # the streamed JSONL must resolve); retention, observers and
        # the flight recorder always see everything.  Deterministic
        # error-diffusion keep rule (a root is kept iff its index
        # crosses an integer multiple of 1/rate), so EVERY rate in
        # (0, 1) thins the stream by exactly that fraction long-run —
        # a keep-every-Nth quantization would silently disable rates
        # above 2/3.
        try:
            rate = float(knobs.get("SORT_TRACE_SAMPLE"))
        except ValueError:
            rate = 1.0
        self._sample_rate = min(rate, 1.0)
        self._sample_seq = 0
        #: guards id allocation, retention and streaming — the pieces
        #: pipeline worker threads share with the driver thread.  The
        #: nesting stack stays driver-thread-only by contract.
        self._lock = threading.Lock()
        #: ``span(name, **attrs)``: a timed interval that nests under the
        #: enclosing open span, for a ``with`` statement (yields the
        #: :class:`Span`).  The outermost span activates this log for
        #: module-level :func:`emit`.  While a profiler trace runs, the
        #: same-named annotation covers the span's extent on the trace's
        #: clock (see ``SpanScope``).
        self.span = type("SpanScope", (_scope_type(),), {"log": self})

    # -- recording ----------------------------------------------------
    def _new(self, name: str, attrs: dict[str, object],
             t0: float | None = None, dt: float = 0.0) -> Span:
        ctx = current_trace_context()
        with self._lock:
            if ctx is None and self._ctx_stack:
                # a worker thread (ingest/egress stages) reporting under
                # the driver's innermost open span inherits THAT span's
                # trace context — "every span a request touches" must
                # include the pipeline stages its sort ran, even though
                # trace_context itself is thread-local
                ctx = self._ctx_stack[-1]
            if ctx:
                attrs = {**ctx, **attrs}
            s = Span(
                name=name, id=self._next_id,
                parent=self._stack[-1] if self._stack else None,
                t0=time.perf_counter() if t0 is None else t0,
                dt=dt, attrs=attrs,
            )
            self._next_id += 1
            if self._sample_rate < 1.0:
                if self._stack:
                    # subtree follows its root's verdict
                    s.stream_drop = (self._drop_stack[-1]
                                     if self._drop_stack else False)
                else:
                    seq = self._sample_seq
                    self._sample_seq += 1
                    keep = (int((seq + 1) * self._sample_rate)
                            != int(seq * self._sample_rate))
                    s.stream_drop = not keep
        return s

    def _retain(self, s: Span) -> None:
        with self._lock:
            if len(self.spans) < MAX_RETAINED_SPANS:
                self.spans.append(s)
            else:
                self.dropped += 1

    def record(self, name: str, t0: float, dt: float,
               **attrs: object) -> Span:
        """Thread-safe completed-span recording — the entry point for
        pipeline worker threads (ingest/egress stages), which time their
        own intervals and report them here after the fact.  Parents
        under the driver thread's innermost open span; never touches
        the nesting stack."""
        s = self._new(name, attrs, t0=t0, dt=dt)
        self._retain(s)
        self._flush(s)
        return s

    def event(self, name: str, **attrs: object) -> Span:
        """Point event (dt=0) under the currently open span."""
        s = self._new(name, attrs)
        self._retain(s)
        self._flush(s)
        return s

    def _open(self, name: str, attrs: dict[str, object]
              ) -> tuple[Span, bool]:
        """Open a span (``span``'s entry): returns it and whether it is
        the outermost open span of this log."""
        s = self._new(name, attrs)
        self._retain(s)
        # stack mutations under the SAME lock _new reads them under:
        # a worker-thread record() racing this push/pop must see the
        # (parent id, drop verdict) PAIR consistently — a torn read
        # could stream a kept span whose parent subtree was dropped
        # (a dangling parent, which the schema check rejects).
        opener_ctx = current_trace_context()
        with self._lock:
            if opener_ctx is None and self._ctx_stack:
                opener_ctx = self._ctx_stack[-1]  # inherit downward
            self._stack.append(s.id)
            self._drop_stack.append(s.stream_drop)
            self._ctx_stack.append(opener_ctx)
            outermost = len(self._stack) == 1
        if outermost:
            _ACTIVE.append(self)
        return s, outermost

    def _close(self, s: Span, outermost: bool) -> None:
        """Close the span :meth:`_open` opened (``span``'s exit)."""
        s.dt = time.perf_counter() - s.t0
        with self._lock:
            self._stack.pop()
            self._drop_stack.pop()
            self._ctx_stack.pop()
        if outermost and _ACTIVE and _ACTIVE[-1] is self:
            _ACTIVE.pop()
        self._flush(s)

    #: serializes stream appends across threads (O_APPEND writes of one
    #: line are atomic on Linux, but don't bet a JSONL schema on it).
    _flush_lock = threading.Lock()

    def _flush(self, s: Span) -> None:
        _flight(s)
        for cb in self.observers:
            try:
                cb(s)
            except Exception:  # noqa: BLE001 — observers never break the path
                pass
        if self.stream_path and not s.stream_drop:
            with self._flush_lock, open(self.stream_path, "a") as f:
                f.write(json.dumps(s.to_dict()) + "\n")

    # -- export -------------------------------------------------------
    def to_jsonl(self) -> str:
        return "\n".join(json.dumps(s.to_dict()) for s in self.spans)

    def dump(self, path: str) -> None:
        """Append ALL spans as JSONL (for logs not opened streaming)."""
        if self.spans:
            with open(path, "a") as f:
                f.write(self.to_jsonl() + "\n")

    def to_chrome_trace(self) -> dict[str, object]:
        """Chrome trace-event JSON (loads in chrome://tracing/Perfetto).

        Timed spans become ``"ph": "X"`` complete events; point events
        become ``"ph": "i"`` instants.  Timestamps are microseconds on
        the same process-relative clock the spans were recorded on.

        The host driver lane is tid 1; ``utils/timeline.py`` then adds
        the measured extras: a disk-IO track and counter tracks for
        inflight bytes / cap regrowth.  Per-rank device time is not
        drawn here: the ``SORT_PROFILE`` device trace holds the real
        device ops beside the program's own spans.
        """
        events: list[dict] = [{
            "name": "process_name", "ph": "M", "pid": 1, "tid": 1,
            "args": {"name": "mpitest_tpu"},
        }]
        for s in self.spans:
            args = {k: v for k, v in s.attrs.items()}
            args["span_id"] = s.id
            if s.parent is not None:
                args["parent_id"] = s.parent
            if s.dt:
                events.append({
                    "name": s.name, "ph": "X", "pid": 1, "tid": 1,
                    "ts": s.t0 * 1e6, "dur": s.dt * 1e6, "args": args,
                })
            else:
                events.append({
                    "name": s.name, "ph": "i", "s": "t", "pid": 1,
                    "tid": 1, "ts": s.t0 * 1e6, "args": args,
                })
        try:
            # lazy: timeline imports this module's interval helpers
            from mpitest_tpu.utils import timeline
            events.extend(timeline.chrome_events(list(self.spans)))
        except Exception:
            pass  # enrichment is best-effort; the host lane stands alone
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    # -- aggregation (shared with the report CLI) ---------------------
    def collective_totals(self) -> dict[str, dict[str, float]]:
        """Per-collective ``{calls, bytes, seconds}`` — the SAME schema
        the native backends dump at ``COMM_STATS`` (comm/comm_stats.h),
        keyed by the comm.h name via :data:`MPI_EQUIV`.  ``seconds`` is
        0.0 for trace-time point events (device-side wall time is not
        per-op observable; see module docstring)."""
        out: dict[str, dict[str, float]] = {}
        for s in self.spans:
            if s.name not in MPI_EQUIV:
                continue
            row = out.setdefault(
                MPI_EQUIV[s.name], {"calls": 0, "bytes": 0, "seconds": 0.0})
            row["calls"] += 1
            row["bytes"] += int(s.attrs.get("bytes", 0))
            row["seconds"] += s.dt
        return out
