"""Span-tracing layer tests: nesting/ordering, Chrome trace golden,
SORT_TRACE stream, and the per-pass/per-collective acceptance contract
(ISSUE 1): a radix run must emit >= one span per radix pass and per
collective, each collective with byte counts, and the Chrome export must
be valid trace-event JSON."""

import json

import numpy as np
import pytest

from mpitest_tpu.utils import spans
from mpitest_tpu.utils.spans import MPI_EQUIV, SpanLog
from mpitest_tpu.utils.trace import Tracer


def test_span_nesting_and_ordering():
    log = SpanLog()
    with log.span("outer", kind="test"):
        log.event("point", bytes=7)
        with log.span("inner"):
            pass
        with log.span("inner"):  # second occurrence keeps its own id
            pass
    names = [s.name for s in log.spans]
    assert names == ["outer", "point", "inner", "inner"]
    outer, point, in1, in2 = log.spans
    assert outer.parent is None
    assert point.parent == outer.id and point.dt == 0.0
    assert in1.parent == outer.id and in2.parent == outer.id
    assert in1.id != in2.id
    # ids are allocated in creation order; dt only set on close
    assert [s.id for s in log.spans] == sorted(s.id for s in log.spans)
    assert outer.dt >= in1.dt >= 0.0


def test_active_log_registry():
    """Module-level emit() reaches the log whose outermost span is open,
    and is a no-op outside one — the hook collectives.py relies on."""
    spans.emit("orphan", bytes=1)  # no active log: silently dropped
    log = SpanLog()
    assert spans.current_log() is None
    with log.span("outer"):
        assert spans.current_log() is log
        spans.emit("collected", bytes=2)
        with log.span("inner"):   # nested spans don't re-register
            assert spans.current_log() is log
    assert spans.current_log() is None
    assert [s.name for s in log.spans] == ["outer", "collected", "inner"]


def test_chrome_trace_golden(monkeypatch):
    """Deterministic clock -> byte-exact Chrome trace-event export."""
    ticks = iter([1.0, 1.25, 2.0, 3.5])  # open, event, open, closes...
    monkeypatch.setattr(spans.time, "perf_counter",
                        lambda: next(ticks, 4.0))
    log = SpanLog()
    with log.span("run", n=8):
        log.event("coll", bytes=64)
        with log.span("step"):
            pass
    got = log.to_chrome_trace()
    assert got == {
        "traceEvents": [
            {"name": "process_name", "ph": "M", "pid": 1, "tid": 1,
             "args": {"name": "mpitest_tpu"}},
            {"name": "run", "ph": "X", "pid": 1, "tid": 1,
             "ts": 1.0e6, "dur": 3.0e6,
             "args": {"n": 8, "span_id": 0}},
            {"name": "coll", "ph": "i", "s": "t", "pid": 1, "tid": 1,
             "ts": 1.25e6, "args": {"bytes": 64, "span_id": 1,
                                    "parent_id": 0}},
            {"name": "step", "ph": "X", "pid": 1, "tid": 1,
             "ts": 2.0e6, "dur": 1.5e6,
             "args": {"span_id": 2, "parent_id": 0}},
        ],
        "displayTimeUnit": "ms",
    }
    # and it is serializable JSON (what a .json file for Perfetto needs)
    json.loads(json.dumps(got))


def test_jsonl_roundtrip_and_stream(tmp_path):
    stream = tmp_path / "stream.jsonl"
    log = SpanLog(stream_path=str(stream))
    with log.span("outer"):
        log.event("e", bytes=3)
    lines = [json.loads(line) for line in stream.read_text().splitlines()]
    # streamed in COMPLETION order: the event closes before the outer span
    assert [o["name"] for o in lines] == ["e", "outer"]
    assert all(o["v"] == spans.SCHEMA for o in lines)
    # dump() appends the same records in creation order
    full = tmp_path / "full.jsonl"
    log.dump(str(full))
    lines2 = [json.loads(line) for line in full.read_text().splitlines()]
    assert [o["name"] for o in lines2] == ["outer", "e"]


@pytest.fixture
def radix_traced(mesh8, rng):
    """One traced radix sort on the 8-device mesh with a FRESH program
    (unique n so the jit cache can't have it), returning the tracer."""
    from mpitest_tpu.models.api import sort

    x = rng.integers(-(2**31), 2**31 - 1, size=8 * 1096, dtype=np.int32)
    tracer = Tracer()
    out = sort(x, algorithm="radix", mesh=mesh8, digit_bits=16,
               tracer=tracer)
    np.testing.assert_array_equal(out, np.sort(x))
    return tracer


def test_radix_run_span_contract(radix_traced):
    """The ISSUE 1 acceptance criterion: >= one span per radix pass and
    per collective, byte counts on every collective span."""
    sp = radix_traced.spans.spans
    passes = [s for s in sp if s.name == "radix_pass"]
    # full-range int32 at 16-bit digits = 2 passes
    assert [p.attrs["pass_index"] for p in passes] == [1, 2]
    colls = [s for s in sp if s.name in MPI_EQUIV]
    assert len(colls) >= 4  # exscan all_gather + exchange, per pass
    for c in colls:
        assert c.attrs["bytes"] > 0
    a2a = [s for s in sp if s.name == "ragged_all_to_all"]
    assert len(a2a) == len(passes)  # one exchange per pass
    for s in a2a:
        assert s.attrs["ranks"] == 8 and s.attrs["wire_bytes"] > 0
    # every collective nests under a pass span; passes under the jit
    # span.  Capacity-negotiation probe collectives (ISSUE 7) are the
    # registered exception: they nest under negotiate_probe, which has
    # no pass (the probe runs before any pass exists).
    byid = {s.id: s for s in sp}
    for c in colls:
        chain = []
        p = c.parent
        while p is not None:
            chain.append(byid[p].name)
            p = byid[p].parent
        assert "sort" in chain
        if "negotiate_probe" in chain:
            continue
        assert "radix_pass" in chain
    # the totals aggregate on the shared comm.h vocabulary
    totals = radix_traced.spans.collective_totals()
    assert totals["alltoallv"]["calls"] == len(a2a)
    assert totals["allgather"]["bytes"] > 0


def test_compile_vs_execute_split(mesh8, rng):
    """First call of a program records jit_compile_execute; a warm rerun
    of the SAME program records jit_execute and re-emits no trace-time
    collective spans (they are per-compile records)."""
    from mpitest_tpu.models.api import sort

    x = rng.integers(-(2**31), 2**31 - 1, size=8 * 1097, dtype=np.int32)
    t1, t2 = Tracer(), Tracer()
    sort(x, algorithm="radix", mesh=mesh8, digit_bits=16, tracer=t1)
    sort(x, algorithm="radix", mesh=mesh8, digit_bits=16, tracer=t2)
    names1 = {s.name for s in t1.spans.spans}
    names2 = {s.name for s in t2.spans.spans}
    assert "jit_compile_execute" in names1
    assert "jit_execute" in names2 and "jit_compile_execute" not in names2
    assert "ragged_all_to_all" in names1
    assert "ragged_all_to_all" not in names2
    assert t1.counters.get("jit_first_calls", 0) >= 1
    assert "jit_first_calls" not in t2.counters


def test_sort_trace_env_streams_jsonl(tmp_path, mesh8, rng, monkeypatch):
    """SORT_TRACE=<path> streams a schema-clean JSONL file from a plain
    library sort() call — no CLI needed (the acceptance's 'SORT_TRACE
    run')."""
    from mpitest_tpu import report
    from mpitest_tpu.models.api import sort

    path = tmp_path / "trace.jsonl"
    monkeypatch.setenv("SORT_TRACE", str(path))
    x = rng.integers(-(2**31), 2**31 - 1, size=8 * 1098, dtype=np.int32)
    sort(x, algorithm="sample", mesh=mesh8)
    rows = report.load_rows(str(path))
    assert rows and all(r["kind"] == "span" for r in rows)
    assert report.check_rows(rows) == []
    names = {r["name"] for r in rows}
    assert "sort" in names and "splitter_round" in names
    assert "ragged_all_to_all" in names  # the sample exchange


def test_tracer_phase_spans():
    t = Tracer()
    with t.span("phase:alpha"):
        with t.span("phase:beta"):
            pass
        with t.span("not_a_phase"):
            pass
    assert set(t.phases) == {"alpha", "beta"}
    assert t.phases["alpha"] == t.spans.spans[0].dt
    names = [s.name for s in t.spans.spans]
    assert names == ["phase:alpha", "phase:beta", "not_a_phase"]
    assert t.spans.spans[1].parent == t.spans.spans[0].id
