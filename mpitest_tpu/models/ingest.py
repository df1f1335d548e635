"""Streaming ingest/egress: chunked, double-buffered host↔device transfer.

Why this layer exists (ISSUE 2): the on-device sort runs at hundreds of
Mkeys/s, but the host path around it used to be fully serial — read the
whole file, ``codec.encode`` the whole array on one thread, then push it
through a single monolithic ``jax.device_put``.  End-to-end throughput
collapsed to a third of the device sort.  This module replaces that
serial staircase with a three-stage pipeline over fixed-size chunks:

* **parse** (producer thread): materialize chunk k — an mmap page-in for
  SORTBIN1 slices, a slice view for in-memory arrays — and hand it to a
  bounded queue (depth 2: double buffering, not unbounded buffering).
* **encode** (``SORT_INGEST_THREADS`` pool): encode chunk k into uint32
  key words while chunk k-1 is still transferring; also folds the
  chunk's per-word min/max (the radix pass planner's input), the
  running native max key (the padding value) and the verifier
  fingerprint, so the sort needs NO extra host pass over the data
  afterwards.  The stage is engine-dispatched (ISSUE 6,
  ``SORT_NATIVE_ENCODE``): the native C kernel
  (:mod:`mpitest_tpu.utils.native_encode`) does all of that in ONE
  GIL-released pass — for mmap'd SORTBIN1 it reads the pages in place,
  so the host path is zero-copy (mmap → fold → staging words → DMA);
  the Python engine is the original numpy multi-pass path, preserved
  bit-for-bit as fallback and parity oracle.
* **transfer** (one dedicated thread, in order): split the encoded chunk
  at shard boundaries (``parallel.mesh.shard_bounds``), ``device_put``
  each piece onto its owning device, and block until that chunk's DMA
  completes.  One thread keeps per-device piece lists ordered; being a
  *separate* thread is what makes the DMA of chunk k genuinely overlap
  the encode of chunk k+1 on the wall clock.

Each stage records its own ``ingest.*`` span (thread-safe
``SpanLog.record``), so ``python -m mpitest_tpu.report`` can show the
overlapped timeline and compute overlap efficiency from the same run.

The pipeline ends by gluing the per-device pieces (plus max-key padding)
into one key-sharded global array via
``jax.make_array_from_single_device_arrays`` — no host-side concatenate,
no second copy.  The result travels as a :class:`StagedIngest`, which
``models.api.sort`` accepts in place of raw keys (skipping its own
encode/pad), and whose word buffers the sort dispatch may *donate* back
to XLA so device memory is reused rather than doubled.

Egress is the mirror image (:func:`stream_result_to_numpy`): a fetch
thread pulls shard k+1 device→host while the decode of shard k runs,
emitting ``egress.*`` spans.  Decode is elementwise (the codec is an
order-preserving bijection), so per-shard decode is exact.

Host-memory bound: at most ~(``SORT_INGEST_THREADS`` + 4) chunks live at
once (2 queued parses, up to ``threads`` encodes in flight, 2 transfers
buffered) — a 2^30-key SORTBIN1 file streams through tens of MiB of
host memory instead of 8 GiB (mmap slices page in per chunk).  Text
inputs materialize once on read — shard bounds need the total key count
before the first DMA — and then pipeline from the in-memory array.
"""

from __future__ import annotations

import contextlib
import math
import queue
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Any, Callable

import jax
import numpy as np

from mpitest_tpu import faults
from mpitest_tpu.models.supervisor import verify_enabled
from mpitest_tpu.models.verify import Fingerprint
from mpitest_tpu.ops.keys import codec_for
from mpitest_tpu.parallel.mesh import assemble_sharded, shard_bounds
from mpitest_tpu.utils import io as kio
from mpitest_tpu.utils import native_encode
from mpitest_tpu.utils.spans import (SpanLog, annotation_type,
                                     merge_intervals, overlap_seconds)

if TYPE_CHECKING:
    from jax.sharding import Mesh

    from mpitest_tpu.utils.trace import Tracer

#: ``SORT_INGEST=auto`` streams only above this many key *bytes* — below
#: it the monolithic path's single device_put beats the pipeline's
#: thread machinery (measured crossover is ~10 MiB; 32 MiB is safely
#: past it and keeps tiny test inputs on the legacy path unless forced).
STREAM_MIN_BYTES = 1 << 25

#: ``auto`` egress streaming threshold (result bytes per the same logic).
EGRESS_MIN_BYTES = 1 << 22


def checked_device_put(x: "np.ndarray | jax.Array",
                       target: "jax.sharding.Sharding | jax.Device",
                       ) -> jax.Array:
    """``jax.device_put`` with a dtype-preservation guard: raises on ANY
    host→device dtype change instead of JAX's silent downcast.  Without
    x64, ``device_put`` of an int64/uint64/float64 host array silently
    lands a 32-bit shadow — a wrong *sort input*, not an error (the
    bench.py:171 hazard, observed producing a wrong float64 sort).  The
    ingest path routes every host→device transfer through here."""
    out = jax.device_put(x, target)
    src = np.dtype(x.dtype)
    if np.dtype(out.dtype) != src:
        raise TypeError(
            f"jax.device_put changed dtype {src} -> {out.dtype}: 64-bit "
            "host keys need jax_enable_x64 (the silent downcast would "
            "corrupt the sort input, not just its precision)"
        )
    return out


def use_stream(n_bytes: int) -> bool:
    """Resolve the SORT_INGEST mode against the input size."""
    mode = kio.ingest_mode()
    if mode == "stream":
        return True
    if mode == "mono":
        return False
    return n_bytes >= STREAM_MIN_BYTES


@dataclass
class IngestStats:
    """Wall/stage accounting of one streamed ingest — the source of the
    bench sub-metrics (parse/encode/transfer seconds, overlap)."""

    n: int = 0
    chunks: int = 0
    host_bytes: int = 0       # native key bytes read
    device_bytes: int = 0     # encoded word bytes shipped (pads included)
    parse_s: float = 0.0
    encode_s: float = 0.0
    transfer_s: float = 0.0
    wall_s: float = 0.0
    #: encode engine the run actually used ("native" | "python") — the
    #: observable half of the SORT_NATIVE_ENCODE=auto contract: a
    #: degraded fallback shows up here, in spans, and in bench rows.
    encode_engine: str = "python"
    host_iv: list = field(default_factory=list)  # (t0, t1) parse/encode
    xfer_iv: list = field(default_factory=list)  # (t0, t1) transfers

    def overlap_efficiency(self) -> float:
        """Fraction of transfer wall time hidden under host parse/encode
        work — interval intersection on one perf_counter timeline, the
        exact quantity ``report.py --require-ingest-overlap`` gates on.
        (A sum-of-stage-seconds formula would double-count concurrent
        encode workers and report fake overlap for a pipeline whose DMA
        never ran alongside host work.)"""
        xm = merge_intervals(self.xfer_iv)
        xfer = sum(b - a for a, b in xm)
        if xfer <= 0:
            return 0.0
        return overlap_seconds(merge_intervals(self.host_iv), xm) / xfer


@dataclass
class StagedIngest:
    """Encoded, padded, mesh-sharded key words plus everything the sort
    needs to plan without another pass over the data.  ``models.api.sort``
    accepts this in place of raw keys."""

    words: tuple                     # sharded [P*n] uint32 arrays, msw first
    n_valid: int                     # real keys (excludes padding)
    dtype: np.dtype
    word_diffs: tuple                # per-word max^min (pass-planner input)
    mesh: object
    stats: IngestStats
    #: host source for donation-retry rebuilds (sort may donate `words`
    #: to the SPMD program; an exchange-overflow retry then re-streams
    #: from here).  None ⇒ the caller keeps no source and the sort must
    #: not donate.
    source: np.ndarray | None = None
    #: pipeline configuration of the run that produced this — a rebuild
    #: must replay the SAME tracer/chunking, not silently fall back to
    #: env defaults (spans would vanish from the overlap tables).
    tracer: object | None = None
    chunk_elems: int | None = None
    threads: int | None = None
    #: set by a DONATED sort dispatch: the word buffers were handed to
    #: XLA and are dead.  A staged object is single-use under donation —
    #: sort() raises on reuse instead of dispatching on deleted arrays
    #: (use :meth:`rebuild` for another sort).
    consumed: bool = False
    #: input-side multiset fingerprint (models/verify.py), folded
    #: chunk-by-chunk by the encode workers — the half the always-on
    #: output verifier compares against; None only when verification
    #: was disabled during staging.
    fingerprint: "Fingerprint | None" = None

    @property
    def size(self) -> int:
        """Key count — mirrors ndarray.size so telemetry and callers can
        treat staged input like an array."""
        return self.n_valid

    def rebuild(self) -> "StagedIngest":
        if self.source is None:
            raise ValueError("StagedIngest has no source to re-stream from")
        return stream_to_mesh(self.source, self.mesh, tracer=self.tracer,
                              chunk_elems=self.chunk_elems,
                              threads=self.threads)


class _StreamState:
    """Cross-thread accumulator for stats and planner inputs."""

    def __init__(self, n_words: int, fold_fp: bool = True) -> None:
        self.lock = threading.Lock()
        self.word_min = [None] * n_words
        self.word_max = [None] * n_words
        self.native_max = None
        self.stats = IngestStats()
        #: running input fingerprint (models/verify.py): XOR + wrapping
        #: sum + count per word, folded chunk-by-chunk so the output
        #: verifier needs no second pass over the data.  ``fold_fp=False``
        #: (SORT_VERIFY=0) skips the per-chunk scans entirely — the A/B
        #: baseline must not silently pay verification cost.
        self.fold_fp = fold_fp
        self.fp = Fingerprint.empty(n_words) if fold_fp else None

    def apply_fold(self, los: list, his: list, m: object,
                   chunk_fp: "Fingerprint | None",
                   t0: float, dt_s: float) -> None:
        """Merge one chunk's already-computed reductions (engine output,
        utils/native_encode.encode_and_fold — the expensive scans ran
        OUTSIDE the lock, on the encode worker) into the running state;
        only these scalar folds need mutual exclusion."""
        with self.lock:
            self.stats.encode_s += dt_s
            self.stats.host_iv.append((t0, t0 + dt_s))
            if chunk_fp is not None:
                self.fp = self.fp.combine(chunk_fp)
            for i, (lo, hi) in enumerate(zip(los, his)):
                if self.word_min[i] is None or lo < self.word_min[i]:
                    self.word_min[i] = lo
                if self.word_max[i] is None or hi > self.word_max[i]:
                    self.word_max[i] = hi
            if m is not None and (self.native_max is None
                                  or m > self.native_max):
                self.native_max = m

    def word_diffs(self, n_words: int) -> tuple:
        return tuple(
            (self.word_max[i] ^ self.word_min[i])
            if self.word_min[i] is not None else 0
            for i in range(n_words)
        )


def _spans_of(tracer: "Tracer | None") -> "SpanLog | None":
    return tracer.spans if tracer is not None else None


def _stage_type(spans: "SpanLog | None") -> Callable[[str], Any]:
    """What a stage that ``spans`` records after the fact
    (:meth:`SpanLog.record`) opens around its work, on its own thread:
    the same-named profiler annotation, constructed in the stage's frame
    (``with stage("ingest.parse"):``); a no-op without a span log."""
    if spans is None:
        return lambda name: contextlib.nullcontext()
    return annotation_type()


def stream_to_mesh(x: np.ndarray, mesh: "Mesh",
                   tracer: "Tracer | None" = None,
                   chunk_elems: int | None = None,
                   threads: int | None = None) -> StagedIngest:
    """Run the full parse→encode→DMA pipeline over host keys ``x`` (a
    numpy array — possibly mmap-backed, in which case chunks page in
    lazily) and return the :class:`StagedIngest` the sort consumes.

    Deterministic by construction: chunk boundaries are fixed arithmetic,
    encode is elementwise, and the single transfer thread lands pieces in
    chunk order — the resulting sharded words are bit-identical to the
    monolithic path's.
    """
    t_wall = time.perf_counter()
    x = np.asarray(x).reshape(-1)
    dtype = np.dtype(x.dtype)
    codec = codec_for(dtype)
    N = int(x.size)
    if N == 0:
        raise ValueError("cannot stream an empty key array")
    chunk_elems = chunk_elems or kio.ingest_chunk_elems()
    threads = threads or kio.ingest_threads()
    # engine resolved ONCE per run (SORT_NATIVE_ENCODE=on raises here,
    # before any thread starts, if the library is missing)
    eng = native_encode.engine()
    n_ranks = int(mesh.devices.size)
    n = max(1, math.ceil(N / n_ranks))
    total = n_ranks * n
    bounds = shard_bounds(mesh, n)
    spans = _spans_of(tracer)
    stage = _stage_type(spans)
    state = _StreamState(codec.n_words, fold_fp=verify_enabled())
    state.stats.n = N
    state.stats.encode_engine = eng
    # chunk k's pieces per device, appended in chunk order by the single
    # transfer thread: per_dev[d] = [piece0_words, piece1_words, ...]
    per_dev: list[list[tuple]] = [[] for _ in bounds]
    # mmap-backed sources: with the PYTHON engine the parse stage
    # materializes the slice (the page-in IS the parse); the NATIVE
    # engine skips that copy entirely — the C kernel reads the mmap
    # pages in-place during its single encode pass, so SORTBIN1 ingest
    # is zero-copy on the host (mmap -> fold -> staging words, ISSUE 6).
    # Walk the full base chain — asarray/reshape wrap the memmap in
    # plain views.
    materialize = False
    if eng != "native":
        _b = x
        while _b is not None:
            if isinstance(_b, np.memmap):
                materialize = True
                break
            _b = getattr(_b, "base", None)

    abort = threading.Event()

    def _put(q: queue.Queue, item) -> bool:
        """Bounded put that gives up when the consumer aborted — the
        producer must never block forever on a full queue nobody will
        drain (that would leak the thread AND pin ``x`` for process
        lifetime)."""
        while not abort.is_set():
            try:
                q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def parse_chunks(q: queue.Queue):
        try:
            off = 0
            k = 0
            while off < N:
                t0 = time.perf_counter()
                with stage("ingest.parse"):
                    c = x[off:off + chunk_elems]
                    if materialize:
                        c = np.array(c)   # fault the pages in, off-thread
                dt = time.perf_counter() - t0
                with state.lock:
                    state.stats.parse_s += dt
                    state.stats.host_iv.append((t0, t0 + dt))
                    state.stats.chunks += 1
                    state.stats.host_bytes += c.nbytes
                if spans is not None:
                    spans.record("ingest.parse", t0, dt, chunk=k,
                                 n=int(c.size), bytes=int(c.nbytes))
                if not _put(q, (k, off, c)):
                    return
                off += c.size
                k += 1
            _put(q, None)
        except BaseException as e:  # surface parse failures to the consumer
            _put(q, e)

    def encode_one(k: int, chunk):
        # engine-dispatched one-call encode stage: words + per-word
        # min/max + pad key + fingerprint in one pass (native: a single
        # GIL-released C sweep that also faults the mmap pages in).
        # The timed interval covers the WHOLE stage for both engines,
        # so encode_s / encode_gb_per_s compare like for like.
        t0 = time.perf_counter()
        with stage("ingest.encode"):
            words, los, his, m, chunk_fp = native_encode.encode_and_fold(
                chunk, codec, state.fold_fp, eng)
        dt = time.perf_counter() - t0
        state.apply_fold(los, his, m, chunk_fp, t0, dt)
        # fault injection (SORT_FAULTS=ingest_poison): corrupt AFTER the
        # fingerprint fold — the device receives bytes the fingerprint
        # never saw, which the output verifier must flag.
        words = faults.maybe_poison_chunk(words, k)
        if spans is not None:
            spans.record("ingest.encode", t0, dt, chunk=k,
                         n=int(chunk.size), engine=eng,
                         bytes=int(sum(w.nbytes for w in words)))
        return words

    def transfer_one(k: int, off: int, words, pad: bool = False):
        with stage("ingest.transfer"):
            t0 = time.perf_counter()
            clen = words[0].size
            # issue EVERY per-device put before blocking on any: a chunk
            # spanning k shard boundaries then runs its k DMAs concurrently
            # instead of serializing device-by-device
            placed = []
            for d, (dev, start, stop) in enumerate(bounds):
                a = max(off, start)
                b = min(off + clen, stop)
                if a >= b:
                    continue
                placed.append((d, tuple(
                    checked_device_put(w[a - off:b - off], dev) for w in words
                )))
            nbytes = 0
            for d, piece in placed:
                for p in piece:
                    p.block_until_ready()
                    nbytes += p.nbytes
                per_dev[d].append(piece)
            dt = time.perf_counter() - t0
            with state.lock:
                state.stats.transfer_s += dt
                state.stats.xfer_iv.append((t0, t0 + dt))
                state.stats.device_bytes += nbytes
            if spans is not None:
                attrs = {"chunk": k, "bytes": int(nbytes)}
                if pad:
                    attrs["pad"] = True
                spans.record("ingest.transfer", t0, dt, **attrs)

    q: queue.Queue = queue.Queue(maxsize=2)
    # threadlint TL010: named like its registered root (ingest-parse)
    producer = threading.Thread(target=parse_chunks, args=(q,),
                                name="ingest-parse", daemon=True)
    producer.start()
    enc_pool = ThreadPoolExecutor(threads, thread_name_prefix="ingest-enc")
    xfer_pool = ThreadPoolExecutor(1, thread_name_prefix="ingest-xfer")
    try:
        encodes: deque = deque()   # (k, off, future) in chunk order
        xfers: deque = deque()     # transfer futures in chunk order

        def drain_encode_front():
            k0, off0, ef = encodes.popleft()
            xfers.append(xfer_pool.submit(transfer_one, k0, off0, ef.result()))
            while len(xfers) > 2:   # double buffer: ≤2 chunk DMAs buffered
                xfers.popleft().result()

        while True:
            item = q.get()
            if item is None:
                break
            if isinstance(item, BaseException):
                raise item
            k, off, chunk = item
            encodes.append((k, off, enc_pool.submit(encode_one, k, chunk)))
            # hand finished encodes to the transfer thread eagerly (the
            # DMA of chunk k starts the moment it is encoded), but let
            # up to `threads` encodes run concurrently before blocking
            # on the oldest — SORT_INGEST_THREADS>2 buys real encode
            # parallelism instead of being a silent no-op.
            while encodes and (encodes[0][2].done()
                               or len(encodes) > threads):
                drain_encode_front()
        while encodes:
            drain_encode_front()
        while xfers:
            xfers.popleft().result()
        producer.join()

        # padding: replicate the maximum real key (float codecs use the
        # totalOrder sentinel) — same contract as the monolithic path.
        # The pad rides transfer_one as a synthetic tail chunk at offset
        # N, so placement/accounting/spans stay in one place (total-N is
        # always < n_ranks: ceil division leaves less than one shard).
        if total > N:
            if codec.sentinel_pad:
                pad_words = codec.max_sentinel()
            else:
                pad_words = tuple(
                    int(w[0]) for w in codec.encode(
                        np.asarray([state.native_max], dtype))
                )
            transfer_one(-1, N, tuple(
                np.full(total - N, pw, np.uint32) for pw in pad_words
            ), pad=True)
    finally:
        # unblock + reap the producer FIRST (it may be parked on a full
        # queue); a leaked producer would pin x for process lifetime
        abort.set()
        while True:
            try:
                q.get_nowait()
            except queue.Empty:
                break
        producer.join(timeout=5.0)
        enc_pool.shutdown(wait=True)
        xfer_pool.shutdown(wait=True)

    # per-device shard assembly: single piece passes through untouched;
    # multi-piece shards concatenate ON the owning device (the pieces are
    # committed there, so eager concatenate never touches the host)
    import jax.numpy as jnp

    words_global = []
    for wi in range(codec.n_words):
        shards = []
        for d in range(len(bounds)):
            pieces = [p[wi] for p in per_dev[d]]
            shards.append(pieces[0] if len(pieces) == 1
                          else jnp.concatenate(pieces))
        words_global.append(assemble_sharded(mesh, shards, total))
    state.stats.wall_s = time.perf_counter() - t_wall
    if spans is not None:
        spans.record("ingest.pipeline", t_wall, state.stats.wall_s,
                     n=N, chunks=state.stats.chunks,
                     encode_engine=eng,
                     parse_s=round(state.stats.parse_s, 6),
                     encode_s=round(state.stats.encode_s, 6),
                     transfer_s=round(state.stats.transfer_s, 6),
                     overlap_efficiency=round(
                         state.stats.overlap_efficiency(), 4))
    return StagedIngest(
        words=tuple(words_global), n_valid=N, dtype=dtype,
        word_diffs=state.word_diffs(codec.n_words), mesh=mesh,
        stats=state.stats, source=x,
        tracer=tracer, chunk_elems=chunk_elems, threads=threads,
        fingerprint=state.fp,
    )


def stream_result_to_numpy(words: tuple[jax.Array, ...], n_valid: int,
                           dtype: "np.dtype | str",
                           tracer: "Tracer | None" = None) -> np.ndarray:
    """Streamed egress for contiguous (non-ragged) sorted results: fetch
    shard k+1 device→host on a dedicated thread while shard k decodes —
    the mirror image of the ingest pipeline, with ``egress.*`` spans.

    Per-shard decode is exact because the codec is elementwise; shard
    boundaries come from the arrays' own ``addressable_shards`` indices,
    so any 1-D block layout (including the last shard's pad tail) is
    handled by intersection with ``[0, n_valid)``.
    """
    codec = codec_for(np.dtype(dtype))
    # multi-host meshes: this process only sees its own shards, so the
    # streamed decode would leave remote-shard ranges of `out` as
    # uninitialized memory — refuse loudly (the legacy gather path
    # raises on non-addressable arrays; silence would be wrong data).
    if not getattr(words[0], "is_fully_addressable", True):
        raise ValueError(
            "streamed egress requires fully addressable result shards; "
            "on a multi-process mesh gather per-process results instead")
    spans = _spans_of(tracer)
    stage = _stage_type(spans)
    out = np.empty(n_valid, np.dtype(dtype))
    shard_lists = [w.addressable_shards for w in words]
    n_shards = len(shard_lists[0])

    def fetch(i: int):
        t0 = time.perf_counter()
        with stage("egress.fetch"):
            sl = shard_lists[0][i].index[0]
            host = tuple(np.asarray(sl_w[i].data) for sl_w in shard_lists)
        dt = time.perf_counter() - t0
        if spans is not None:
            spans.record("egress.fetch", t0, dt, shard=i,
                         bytes=int(sum(h.nbytes for h in host)))
        return sl, host

    def decode(i: int, sl, host):
        a = sl.start or 0
        b = min(sl.stop if sl.stop is not None else n_valid, n_valid)
        if a >= b:
            return
        t0 = time.perf_counter()
        with stage("egress.decode"):
            out[a:b] = codec.decode(tuple(h[: b - a] for h in host))
        dt = time.perf_counter() - t0
        if spans is not None:
            spans.record("egress.decode", t0, dt, shard=i,
                         n=int(b - a),
                         bytes=int((b - a) * out.itemsize))

    with ThreadPoolExecutor(1, thread_name_prefix="egress-fetch") as pool:
        nxt = pool.submit(fetch, 0)
        for i in range(n_shards):
            sl, host = nxt.result()
            if i + 1 < n_shards:
                nxt = pool.submit(fetch, i + 1)
            decode(i, sl, host)
    return out
