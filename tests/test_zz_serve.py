"""Sort-as-a-service tests (ISSUE 8) — named to sort late (tier-1 is
timeout-bound): the segmented pack/split core, the AOT executor cache,
batching, typed backpressure, per-request fault isolation, and the
server driver's SIGTERM drain.

Most tests drive the transport-independent :class:`ServerCore`
in-process (the TCP layer is a thin framing shell over it, exercised by
``make serve-selftest`` plus one subprocess drill here)."""

from __future__ import annotations

import threading
from contextlib import contextmanager

import numpy as np
import pytest

from mpitest_tpu.models import segmented as sg
from mpitest_tpu.serve.admission import AdmissionControl, AdmissionReject
from mpitest_tpu.serve.executor_cache import ExecutorCache
from mpitest_tpu.utils import knobs
from mpitest_tpu.utils.spans import SpanLog


@contextmanager
def serve_core(**env):
    """A ServerCore configured via scoped knobs; its dispatch thread is
    stopped at exit so tests never leak threads into the suite."""
    from mpitest_tpu.serve.server import ServerCore

    with knobs.scoped_env(**env):
        core = ServerCore()
        try:
            yield core
        finally:
            core.batcher.stop(timeout=10)


# ------------------------------------------------------- segmented core

def test_bucket_for_power_of_two():
    assert sg.bucket_for(1) == sg.MIN_BUCKET
    assert sg.bucket_for(sg.MIN_BUCKET) == sg.MIN_BUCKET
    assert sg.bucket_for(sg.MIN_BUCKET + 1) == 2 * sg.MIN_BUCKET
    assert sg.bucket_for(3000) == 4096
    assert sg.bucket_for(4096) == 4096
    with pytest.raises(ValueError):
        sg.bucket_for(-1)


def test_pack_sort_split_bit_parity(rng):
    """The packed multi-tenant dispatch must be bit-identical to
    sorting each request alone — the acceptance contract."""
    arrs = [rng.integers(-2**31, 2**31 - 1, size=n, dtype=np.int32)
            for n in (307, 1, 900, 64)]
    batch = sg.pack_segments(arrs, np.dtype(np.int32))
    sorted_words = sg.run_packed(batch)
    outs = sg.split_segments(batch, sorted_words)
    for a, o in zip(arrs, outs):
        assert np.array_equal(o, np.sort(a))
    assert all(sg.verify_segments(batch, sorted_words))


def test_pack_sort_split_parity_uint64(rng):
    """Wider (2-word) keys ride the variadic lowering — same contract."""
    arrs = [rng.integers(0, 2**64, size=n, dtype=np.uint64)
            for n in (150, 40)]
    batch = sg.pack_segments(arrs, np.dtype(np.uint64))
    outs = sg.split_segments(batch, sg.run_packed(batch))
    for a, o in zip(arrs, outs):
        assert np.array_equal(o, np.sort(a))


def test_verify_flags_only_the_corrupt_segment(rng):
    arrs = [rng.integers(-2**31, 2**31 - 1, size=256, dtype=np.int32)
            for _ in range(3)]
    batch = sg.pack_segments(arrs, np.dtype(np.int32))
    sw = tuple(w.copy() for w in sg.run_packed(batch))
    sw[1][batch.offsets[1]] ^= 0x40        # corrupt one key of segment 1
    assert sg.verify_segments(batch, sw) == [True, False, True]


def test_pack_rejects_overflow(rng):
    a = rng.integers(-100, 100, size=600, dtype=np.int32)
    with pytest.raises(ValueError, match="bucket"):
        sg.pack_segments([a, a], np.dtype(np.int32), bucket=1024)


# ------------------------------------------------------- executor cache

def test_executor_cache_hit_miss_and_bucket_reuse():
    log = SpanLog()
    cache = ExecutorCache(log)
    # two different request totals land in ONE bucket -> one compile
    b1 = sg.bucket_for(300)
    b2 = sg.bucket_for(900)
    assert b1 == b2
    cache.get_packed(b1, "int32", 2)
    cache.get_packed(b2, "int32", 2)
    assert cache.stats.misses == 1 and cache.stats.hits == 1
    # a different bucket is a new entry
    cache.get_packed(sg.bucket_for(5000), "int32", 2)
    assert cache.stats.misses == 2
    events = [s for s in log.spans if s.name == "serve.compile_cache"]
    assert [e.attrs["hit"] for e in events] == [False, True, False]
    assert events[0].attrs["compile_s"] >= 0.0


def test_executor_cache_prewarm_cpu():
    cache = ExecutorCache()
    built = cache.prewarm((1024, 2048), ("int32",))
    assert built == 2
    assert cache.stats.buckets == {1024, 2048}
    # traffic into a prewarmed bucket never compiles
    cache.get_packed(1024, "int32", 2)
    assert cache.stats.hits == 1


# ---------------------------------------------------- admission control

def test_admission_typed_rejections():
    adm = AdmissionControl(max_inflight=2, max_bytes=1000)
    adm.admit(400)
    adm.admit(400)
    with pytest.raises(AdmissionReject) as e:
        adm.admit(10)          # count bound first
    assert e.value.reason == "inflight"
    adm.release(400)
    with pytest.raises(AdmissionReject) as e:
        adm.admit(700)         # byte bound
    assert e.value.reason == "bytes"
    adm.start_drain()
    with pytest.raises(AdmissionReject) as e:
        adm.admit(1)
    assert e.value.reason == "draining"
    adm.release(400)
    assert adm.wait_idle(timeout=1.0)


# ----------------------------------------------------------- ServerCore

def test_core_batches_concurrent_requests(rng):
    with serve_core(SORT_SERVE_BATCH_WINDOW_MS="60") as core:
        arrs = [rng.integers(-2**31, 2**31 - 1, size=400, dtype=np.int32)
                for _ in range(5)]
        results: dict = {}

        def send(i):
            results[i] = core.execute(arrs[i])

        threads = [threading.Thread(target=send, args=(i,))
                   for i in range(5)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, a in enumerate(arrs):
            st, out, _attrs = results[i]
            assert st == "ok"
            assert np.array_equal(out, np.sort(a))
        # the 60 ms window must have packed at least one multi-segment
        # batch out of 5 concurrent closed-loop arrivals
        assert any(r[2].get("batched") for r in results.values())
        assert core.batcher.batches < 5


def test_core_routes_large_requests_solo(rng, mesh8):
    with serve_core(SORT_SERVE_BATCH_KEYS="512") as core:
        a = rng.integers(-2**31, 2**31 - 1, size=2000, dtype=np.int32)
        st, out, attrs = core.execute(a)
        assert st == "ok" and np.array_equal(out, np.sort(a))
        assert attrs["batched"] is False


def test_core_backpressure_typed(rng):
    with serve_core(SORT_SERVE_MAX_INFLIGHT="1",
                    SORT_SERVE_BATCH_WINDOW_MS="30") as core:
        statuses = []

        def send(_):
            a = rng.integers(-2**31, 2**31 - 1, size=256, dtype=np.int32)
            statuses.append(core.execute(a)[0])

        threads = [threading.Thread(target=send, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert "backpressure" in statuses and "ok" in statuses
        assert set(statuses) <= {"backpressure", "ok"}
        # the server keeps serving after the burst
        a = rng.integers(-2**31, 2**31 - 1, size=64, dtype=np.int32)
        assert core.execute(a)[0] == "ok"


def test_per_request_fault_isolation(rng, mesh8):
    """A poisoned request (per-request SORT_FAULTS spec, test mode)
    yields a TYPED error; the next request on the same server
    succeeds — fault isolation, never server death."""
    with serve_core(SORT_SERVE_ALLOW_FAULTS="1", SORT_FALLBACK="0",
                    SORT_MAX_RETRIES="0") as core:
        a = rng.integers(-2**31, 2**31 - 1, size=2048, dtype=np.int32)
        st, detail, _ = core.execute(a, faults_spec="result_swap:inf")
        assert st == "integrity", (st, detail)
        st2, out, _ = core.execute(a)
        assert st2 == "ok" and np.array_equal(out, np.sort(a))


def test_batch_fault_isolated_to_segment(rng, mesh8):
    """Server-level SORT_FAULTS corrupting a packed batch result must
    flag only the touched segments; those re-run solo under the
    supervisor and every tenant still gets a verified result."""
    with serve_core(SORT_FAULTS="result_swap:1",
                    SORT_SERVE_BATCH_WINDOW_MS="60") as core:
        arrs = [rng.integers(-2**31, 2**31 - 1, size=500, dtype=np.int32)
                for _ in range(4)]
        results: dict = {}

        def send(i):
            results[i] = core.execute(arrs[i])

        threads = [threading.Thread(target=send, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, a in enumerate(arrs):
            st, out, _attrs = results[i]
            assert st == "ok"
            assert np.array_equal(out, np.sort(a))
        assert core.tracer.counters.get("serve_segment_requeues", 0) >= 1


# -------------------------------------------------------- knob contract

def test_serve_knob_validation():
    cases = {
        "SORT_SERVE_PORT": "70000",
        "SORT_SERVE_MAX_INFLIGHT": "0",
        "SORT_SERVE_MAX_BYTES": "x",
        "SORT_SERVE_BATCH_WINDOW_MS": "-1",
        "SORT_SERVE_BATCH_KEYS": "none",
        "SORT_SERVE_SHAPE_BUCKETS": "10,zap",
        "SORT_SERVE_PREWARM": "yes",
        "SORT_SERVE_ALLOW_FAULTS": "2",
    }
    for name, bad in cases.items():
        with knobs.scoped_env(**{name: bad}):
            with pytest.raises(knobs.KnobError, match=name):
                knobs.get(name)
    with knobs.scoped_env(SORT_SERVE_SHAPE_BUCKETS="14,10,14"):
        assert knobs.get("SORT_SERVE_SHAPE_BUCKETS") == (10, 14)


# ------------------------------------------- wire + SIGTERM drain drill

def test_server_driver_wire_and_sigterm_drain(tmp_path):
    """The full subprocess contract: listening line, a wire round trip,
    a typed bad-request error, then SIGTERM -> graceful drain, exit 0.
    One subprocess (slow jax import) covers all of it."""
    import json
    import os
    import re
    import signal
    import subprocess
    import sys

    from mpitest_tpu.serve.client import ServeClient

    trace = tmp_path / "trace.jsonl"
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               SORT_SERVE_PORT="0",
               SORT_SERVE_SHAPE_BUCKETS="10",
               SORT_TRACE=str(trace))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.Popen(
        [sys.executable, os.path.join(repo, "drivers", "sort_server.py")],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env)
    try:
        assert proc.stdout is not None
        line = proc.stdout.readline()
        m = re.search(r"listening on [\d.]+:(\d+)", line)
        assert m, f"no listening line: {line!r}"
        port = int(m.group(1))
        rng = np.random.default_rng(3)
        x = rng.integers(-2**31, 2**31 - 1, size=700, dtype=np.int32)
        with ServeClient("127.0.0.1", port) as c:
            r = c.sort(x, trace_id="wire-drill-1")
            assert r.ok and np.array_equal(r.arr, np.sort(x))
            # the wire layer echoes the client-minted trace id (ISSUE 10)
            assert r.trace_id == "wire-drill-1"
            # typed error, connection survives, next request works
            bad = c.sort(np.arange(8, dtype=np.int32), algo="bogus")
            assert not bad.ok and bad.error == "bad_request"
            r2 = c.sort(x)
            assert r2.ok
            # a trace id is minted when the client supplies... the
            # client always supplies one; the echo must be non-empty
            assert r2.trace_id
            # garbage trace ids are a typed wire error
            bad_tid = c.sort(x, trace_id="spaces are not ok")
            assert not bad_tid.ok and bad_tid.error == "bad_request"
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=60)
        assert rc == 0, proc.stderr.read()[-1000:]
        spans = [json.loads(ln) for ln in trace.read_text().splitlines()]
        names = {s["name"] for s in spans}
        assert "serve.request" in names and "serve.batch" in names
    finally:
        if proc.poll() is None:
            proc.kill()
