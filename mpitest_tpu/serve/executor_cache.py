"""AOT executable cache: compile once per shape bucket, serve warm forever.

The compile is the dominant fixed cost of a small sort — hundreds of
milliseconds against sub-millisecond device work.  The cache removes it
from the request path twice over:

* **Shape bucketing** (:func:`bucket_for`, re-exported from
  ``models/segmented.py``): request/batch sizes round up to powers of
  two, so an unbounded family of request shapes maps to a handful of
  executables.  A 1300-key batch and a 1900-key batch both run the
  2048-lane program; the pad lanes sort to the tail and cost nanoseconds.
* **AOT compilation**: entries are built with
  ``jit(...).lower(...).compile()`` — the executable exists before the
  first request needs it (prewarm) or is built exactly once on first
  miss.  Warm requests call a finished executable; the selftest gate
  asserts a warm window records ZERO compile activity.

Every lookup emits a ``serve.compile_cache`` point event (hit/miss,
bucket, dtype, compile seconds on miss) so cache behavior is observable
in the same span stream as request latency.

Startup prewarm compiles in the server's own process: the process that
holds the chip is the only one that can compile for it."""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Any, Callable

from mpitest_tpu.models.segmented import (MIN_BUCKET, bucket_for,
                                          compile_packed_sort,
                                          executable_stats)

if TYPE_CHECKING:
    from mpitest_tpu.utils.spans import SpanLog

__all__ = ["CacheStats", "ExecutorCache", "MIN_BUCKET", "bucket_for"]


@dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    compile_s: float = 0.0
    prewarmed: int = 0
    buckets: set = field(default_factory=set)


class ExecutorCache:
    """Memoized AOT executables keyed by (kind, bucket, dtype name,
    total word count, mesh fingerprint).  ``dtype``/``mesh`` ride the
    key for honesty (an entry is only ever reused for the exact
    configuration it was built for) even where the underlying program
    depends on fewer coordinates — the packed sort is shape+word-count
    only, so e.g. int32 and uint32 share a *compile* via the lru-cached
    builder while keeping distinct cache entries and telemetry."""

    def __init__(self, spans: "SpanLog | None" = None) -> None:
        self._entries: dict[tuple, Callable[..., Any]] = {}
        self._lock = threading.Lock()
        self.stats = CacheStats()
        self.spans = spans

    def snapshot(self) -> dict:
        """Consistent point-in-time stats for the live /varz endpoint —
        copied under the cache lock (iterating the live ``buckets`` set
        while a miss mutates it would raise mid-scrape)."""
        with self._lock:
            return {"hits": self.stats.hits,
                    "misses": self.stats.misses,
                    "prewarmed": self.stats.prewarmed,
                    "compile_s": round(self.stats.compile_s, 4),
                    "buckets": sorted(self.stats.buckets)}

    # -- events -------------------------------------------------------
    def _event(self, **attrs: object) -> None:
        if self.spans is not None:
            self.spans.record("serve.compile_cache", time.perf_counter(),
                              0.0, **attrs)

    # -- lookup -------------------------------------------------------
    def get_packed(self, bucket: int, dtype_name: str,
                   n_words_total: int) -> Callable[..., Any]:
        """The compiled packed-batch executable for a shape bucket —
        the batcher's hot path.  First call per key compiles (one
        ``serve.compile_cache`` miss event with the compile seconds);
        every later call is a dict lookup."""
        key = ("packed", bucket, dtype_name, n_words_total)
        with self._lock:
            exe = self._entries.get(key)
            if exe is not None:
                self.stats.hits += 1
                dt = None
            else:
                # compile under the lock: two threads racing on a cold
                # key would otherwise both pay the compile (the dispatch
                # thread is single today, but the contract shouldn't
                # depend on it)
                t0 = time.perf_counter()
                # threadlint: disable=TL003 -- cold-key dogpile guard, reviewed hold
                exe = compile_packed_sort(n_words_total, bucket)
                dt = time.perf_counter() - t0
                self._entries[key] = exe
                self.stats.misses += 1
                self.stats.compile_s += dt
                self.stats.buckets.add(bucket)
        # threadlint TL002: span observers (metrics bridge, sentinel)
        # run on the EMITTING thread — never emit while holding the
        # cache lock, or the sentinel's lock nests under it
        if dt is None:
            self._event(hit=True, bucket=bucket, dtype=dtype_name)
        else:
            # ISSUE 10: stamp the miss event with the XLA cost analysis
            # (flops / bytes accessed / generated code size) so compile
            # cost AND program cost are attributable per shape bucket
            # straight from the span stream.
            self._event(hit=False, bucket=bucket, dtype=dtype_name,
                        compile_s=round(dt, 6), **executable_stats(exe))
        return exe

    def missing_packed(self, buckets: "tuple[int, ...]",
                       dtype_names: "tuple[str, ...]",
                       ) -> "tuple[tuple[int, str], ...]":
        """The (bucket, dtype) pairs no packed executable exists for
        yet (ISSUE 14: the serve tuner checks this before spawning a
        background prewarm — an already-covered recommendation must
        cost a lock acquire, not a thread).  Dtype-aware on purpose:
        executables are keyed per dtype, so an int32 build at a bucket
        does not cover a uint64 mix at the same bucket."""
        import numpy as np

        from mpitest_tpu.ops.keys import codec_for

        out: "list[tuple[int, str]]" = []
        with self._lock:
            for dn in dtype_names:
                nwt = 1 + codec_for(np.dtype(dn)).n_words
                for b in buckets:
                    if ("packed", b, dn, nwt) not in self._entries:
                        out.append((b, dn))
        return tuple(out)

    def _build_detached(self, bucket: int, dtype_name: str,
                        n_words_total: int) -> None:
        """Compile one packed executable WITHOUT holding the cache lock
        for the compile (ISSUE 14: the tuner's mid-traffic background
        prewarm must never stall a live ``get_packed`` — an XLA compile
        under ``self._lock`` would block the dispatch thread even on
        already-cached keys).  The trade is the reverse race:
        ``get_packed`` may compile the same cold key concurrently; both
        pay the compile, the first insert wins, and the dispatch path
        never waits on prewarm."""
        key = ("packed", bucket, dtype_name, n_words_total)
        with self._lock:
            hit = key in self._entries
            if hit:
                self.stats.hits += 1
        if hit:
            # threadlint TL002: emit outside the cache lock (observers
            # run on this thread and take their own locks)
            self._event(hit=True, bucket=bucket, dtype=dtype_name)
            return
        t0 = time.perf_counter()
        exe = compile_packed_sort(n_words_total, bucket)
        dt = time.perf_counter() - t0
        with self._lock:
            fresh = key not in self._entries
            if fresh:
                self._entries[key] = exe
                self.stats.misses += 1
                self.stats.compile_s += dt
                self.stats.buckets.add(bucket)
        if fresh:
            self._event(hit=False, bucket=bucket, dtype=dtype_name,
                        compile_s=round(dt, 6), **executable_stats(exe))

    # -- prewarm ------------------------------------------------------
    def prewarm(self, buckets: "tuple[int, ...]", dtype_names: tuple,
                log: Callable[[str], None] = lambda m: None) -> int:
        """AOT-compile the configured shape buckets before the first
        request (``SORT_SERVE_SHAPE_BUCKETS`` × prewarm dtypes).
        Returns the number of executables built."""
        import numpy as np

        from mpitest_tpu.ops.keys import codec_for

        built = 0
        for dtype_name in dtype_names:
            n_words = codec_for(np.dtype(dtype_name)).n_words
            for b in buckets:
                self._build_detached(b, dtype_name, 1 + n_words)
                built += 1
        # threadlint TL004: prewarm runs on the main thread AND the
        # tuner's background prewarm thread — count under the lock
        with self._lock:
            self.stats.prewarmed += built
        log(f"prewarmed {built} executable(s) "
            f"(buckets {sorted(self.stats.buckets)})")
        return built
