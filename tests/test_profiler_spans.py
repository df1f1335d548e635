"""The program on the profiler's clock: span annotations, the split of
``phase:verify`` and ``phase:decode``, named and layer-tagged programs,
and the compile label of a call.

A sort traced with ``jax.profiler`` is read back with
``jax.profiler.ProfileData``: its spans must appear as annotations on
the calling thread's host line, nested as the span log nests them."""

from __future__ import annotations

import glob
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpitest_tpu.utils import span_schema, spans
from mpitest_tpu.utils.programs import traced_call
from mpitest_tpu.utils.trace import Tracer

REPO = Path(__file__).resolve().parents[1]

#: the children of phase:verify and phase:decode this layer adds
SPLIT = {"verify.fingerprint": "phase:verify", "verify.check": "phase:verify",
         "egress.fetch": "phase:decode", "egress.decode": "phase:decode"}


def _host_lines(trace_dir: str) -> list[list[tuple[str, int, int]]]:
    """Every host line of the one trace under ``trace_dir``, as
    (name, start_ns, end_ns) events."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.append([(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events])
    return out


@pytest.fixture(scope="module")
def traced_sort(tmp_path_factory):
    """One warm single-device sort of host keys, traced."""
    import mpitest_tpu
    from mpitest_tpu.parallel.mesh import make_mesh

    one = make_mesh(1)
    x = np.random.default_rng(7).integers(0, 1 << 23, 1 << 12,
                                          dtype=np.int32)
    mpitest_tpu.sort(x, mesh=one)          # compile outside the trace
    tr = Tracer()
    d = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(d):
        out = mpitest_tpu.sort(x, mesh=one, tracer=tr)
    np.testing.assert_array_equal(out, np.sort(x))
    return tr, _host_lines(d)


def test_span_is_its_profiler_annotation():
    log = spans.SpanLog()
    scope = log.span("sort", n=3)
    assert isinstance(scope, spans.annotation_type())
    with scope as s:
        assert isinstance(s, spans.Span) and s.attrs == {"n": 3}
    assert [x.name for x in log.spans] == ["sort"] and s.dt > 0


def test_sort_spans_nest_on_the_calling_threads_host_line(traced_sort):
    tr, lines = traced_sort
    (line,) = [ln for ln in lines
               if any(n == "phase:sort" for n, _, _ in ln)]
    ev = [e for e in line if not e[0].startswith("$")]
    names = [n for n, _, _ in ev]
    for phase in ("phase:device_put", "phase:sort", "phase:verify",
                  "phase:decode"):
        assert phase in names
    # one-word int32 keys ship raw and encode on the device
    assert "phase:encode" not in names
    assert tr.counters["encode_site"] == "device"
    # each annotation starts in the frame that runs its body: no Python
    # frame that encloses an annotation's start ends before it does
    frames = [e for e in line if e[0].startswith("$")]
    for name, s0, e0 in ev:
        assert not [f for f in frames if f[1] < s0 < f[2] < e0], name
    for child, parent in SPLIT.items():
        kids = [e for e in ev if e[0] == child]
        assert kids, child
        parents = [e for e in ev if e[0] == parent]
        for _, s, e in kids:
            assert any(ps <= s and e <= pe for _, ps, pe in parents), child
    # the span log holds the same names, and the phase totals are kept
    logged = {s.name for s in tr.spans.spans}
    assert set(SPLIT) <= logged
    assert {"device_put", "sort", "verify", "decode"} <= set(tr.phases)
    assert "encode" not in tr.phases


@pytest.mark.parametrize("name", sorted(SPLIT))
def test_split_span_names_are_registered(name):
    assert span_schema.is_registered(name)


@pytest.mark.parametrize("path", ["mpitest_tpu/models/api.py",
                                  "mpitest_tpu/models/local.py",
                                  "mpitest_tpu/models/verify.py",
                                  "mpitest_tpu/models/ingest.py",
                                  "mpitest_tpu/utils/programs.py"])
def test_span_sites_stay_sortlint_clean(path):
    sys.path.insert(0, str(REPO))
    from tools.sortlint import lint_source

    src = (REPO / path).read_text()
    assert [f for f in lint_source(src, path) if f.rule == "SL003"] == []


def test_verify_program_is_named_and_tagged():
    from mpitest_tpu.models import verify

    fn = verify._compile_contig(1, 100, 128, True)
    x = jax.ShapeDtypeStruct((128,), jnp.uint32)
    text = fn.lower(x).compile().as_text()
    assert "HloModule jit_verify_contig" in text
    assert 'layer="verify"' in text


def test_compile_label_follows_backend_compiles():
    """A warm callable meeting a new shape compiles, and says so; the
    second call at that shape is warm."""
    fn = jax.jit(lambda v: v * 3 + 1)
    tr = Tracer()
    traced_call(tr, "probe", fn, jnp.zeros(8, jnp.int32))
    traced_call(tr, "probe", fn, jnp.zeros(8, jnp.int32))
    traced_call(tr, "probe", fn, jnp.zeros(24, jnp.int32))
    traced_call(tr, "probe", fn, jnp.zeros(24, jnp.int32))
    names = [s.name for s in tr.spans.spans]
    assert names == ["jit_compile_execute", "jit_execute",
                     "jit_compile_execute", "jit_execute"]
    assert tr.counters["jit_first_calls"] == 2
    assert all(s.attrs["label"] == "probe" for s in tr.spans.spans)


def test_to_numpy_records_egress_only_with_a_tracer(mesh4):
    from mpitest_tpu.models.api import DistributedSortResult

    w = jax.device_put(np.arange(16, dtype=np.uint32) ^ np.uint32(1 << 31),
                       mesh4.devices.flat[0])
    res = DistributedSortResult((w,), 10, np.dtype(np.int32))
    tr = Tracer()
    np.testing.assert_array_equal(res.to_numpy(tracer=tr), np.arange(10))
    got = [(s.name, s.attrs) for s in tr.spans.spans]
    assert got == [("egress.fetch", {"bytes": 64}),
                   ("egress.decode", {"n": 10})]
    np.testing.assert_array_equal(res.to_numpy(), np.arange(10))
