"""Sort-as-a-service (ISSUE 8): the persistent serving layer.

"Millions of users" means the process must outlive one sort.  The CLI
pays process start + mesh setup + jit compile on EVERY invocation —
milliseconds of device work under seconds of fixed overhead for small
requests.  This package is the layer that amortizes all of it:

* :mod:`~mpitest_tpu.serve.executor_cache` — AOT-compiles and memoizes
  executables per (shape-bucket, dtype, word-count, mesh) with
  power-of-two shape bucketing, so warm requests never touch the
  compiler; startup prewarm compiles the configured buckets in the
  server's own process.
* :mod:`~mpitest_tpu.serve.admission` — bounds in-flight requests and
  payload bytes; over-limit requests get a TYPED backpressure
  rejection, never a queue that grows until the process dies.
* :mod:`~mpitest_tpu.serve.batching` — packs concurrent small requests
  into one segmented device dispatch
  (:mod:`mpitest_tpu.models.segmented`) within a bounded window and
  splits the result per tenant.
* :mod:`~mpitest_tpu.serve.server` — the transport + orchestration:
  a newline-JSON-header/raw-payload TCP protocol, per-request
  supervision (a poisoned request yields a typed per-request error,
  never server death), ``serve.*`` spans for the report CLI's p50/p99
  SLO tables, and graceful SIGTERM drain.
* :mod:`~mpitest_tpu.serve.client` — the matching client used by
  ``bench/serve_load.py``, the tests, and anything else that talks to
  the server.

Entry point: ``drivers/sort_server.py``.  Load generator / regression
gate: ``bench/serve_load.py`` via ``make serve-selftest``.
"""

__all__ = [
    "AdmissionControl", "AdmissionReject", "ExecutorCache", "ServerCore",
    "SortServer", "bucket_for",
]

#: Lazy exports (PEP 562): ``serve.client`` must stay importable
#: without dragging in the server stack (jax, the models layer) —
#: load generators and remote clients import only the wire protocol.
_EXPORTS = {
    "AdmissionControl": "mpitest_tpu.serve.admission",
    "AdmissionReject": "mpitest_tpu.serve.admission",
    "ExecutorCache": "mpitest_tpu.serve.executor_cache",
    "bucket_for": "mpitest_tpu.serve.executor_cache",
    "ServerCore": "mpitest_tpu.serve.server",
    "SortServer": "mpitest_tpu.serve.server",
}


def __getattr__(name: str):
    if name in _EXPORTS:
        import importlib

        return getattr(importlib.import_module(_EXPORTS[name]), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
