"""Thread-root and lock vocabularies — the concurrency registry (ISSUE 19).

The serving stack is genuinely multi-threaded (dispatch thread,
watchdog, sentinel observers, telemetry HTTP server, ingest/egress
pools, hedging client), and its load-bearing invariants — "only the
dispatch thread touches JAX", "compiles never run under the executor
cache lock on the prewarm path", "locks nest in one global order" —
lived only in docstrings until this registry.  ``tools/threadlint``
loads this module BY FILE PATH (it never imports the package under
lint, same contract as sortlint's registries), walks the call graph of
``mpitest_tpu/``, ``drivers/`` and ``bench/`` from every root declared
here, and enforces those invariants statically in the CI lint job.

Like the knob/span/metric/plan registries, the vocabulary is closed:

* every ``threading.Thread(target=...)``, pool submit target, handler
  entry and signal handler must resolve to a :class:`ThreadRoot` here
  (threadlint TL010 otherwise);
* every ``threading.Lock()`` / ``RLock()`` / ``Condition()`` creation
  site must carry a :class:`LockDecl` with a documented **rank** —
  the global acquisition order TL002 enforces (lower rank acquires
  first; a cycle or an out-of-rank nesting is a finding).

Stdlib-only by design; imports nothing, not even :mod:`threading`.
"""

from __future__ import annotations

from dataclasses import dataclass

#: Valid values for :attr:`ThreadRoot.kind`.
ROOT_KINDS = ("thread", "pool", "handler", "signal", "main")


@dataclass(frozen=True)
class ThreadRoot:
    """One registered thread entry point.

    ``entry`` is the module-qualified function the thread runs (nested
    defs join with dots: ``mpitest_tpu.models.ingest.stream_to_mesh.
    parse_chunks``).  ``jax_ok`` declares whether code reachable from
    this root may touch the JAX/XLA surface — the thread-ownership
    fence TL001 enforces.  Granting it is a REVIEWED act: the doc must
    say why the root is allowed on the device path."""

    name: str
    kind: str
    entry: str
    jax_ok: bool
    doc: str


@dataclass(frozen=True)
class LockDecl:
    """One registered lock instance site.

    ``site`` qualifies where the lock object lives (``module.Class.
    attr`` for instance/class locks, ``module.NAME`` for module
    globals, ``module.func.name`` for function locals).  ``rank`` is
    the position in the ONE global acquisition order: holding a lock
    while acquiring another is only legal when the second lock's rank
    is strictly greater (TL002).  ``reentrant`` marks RLocks, whose
    self-reacquisition is legal."""

    name: str
    rank: int
    site: str
    doc: str
    reentrant: bool = False


# ---------------------------------------------------------------- roots
#
# jax_ok=True is the short, audited list: the dispatch loop (the ONE
# thread the serve layer lets at the device), the tuner's background
# prewarm (deliberate warm compile; XLA releases the GIL), the ingest
# transfer/egress fetch stages (device DMA is their whole job), and the
# process main thread.

THREAD_ROOTS: tuple[ThreadRoot, ...] = (
    # -- serve layer --------------------------------------------------
    ThreadRoot(
        "serve-dispatch", "thread",
        "mpitest_tpu.serve.batching.Batcher._loop", True,
        "THE dispatch thread — the only serve thread allowed to touch "
        "JAX: executors, segmented dispatch, executor-cache lookups "
        "and the profiler hook all run here (ISSUE 8)."),
    ThreadRoot(
        "serve-watchdog", "thread",
        "mpitest_tpu.serve.watchdog.DispatchWatchdog._loop", False,
        "Ages the dispatch heartbeat and trips the breaker; its "
        "half-open probe goes THROUGH batcher.submit (the dispatch "
        "thread runs the actual sort), so the watchdog itself never "
        "touches the device (ISSUE 11)."),
    ThreadRoot(
        "serve-accept", "thread",
        "mpitest_tpu.serve.server.SortServer.serve_forever", False,
        "socketserver accept loop (stdlib body); per-connection work "
        "runs on the serve-wire-handler root."),
    ThreadRoot(
        "serve-wire-handler", "handler",
        "mpitest_tpu.serve.server._Handler.handle", False,
        "Per-connection wire handler (ThreadingTCPServer): parses, "
        "admits and ENQUEUES requests, then waits on the completion "
        "event — results are produced by the dispatch thread."),
    ThreadRoot(
        "serve-telemetry", "thread",
        "mpitest_tpu.serve.telemetry.TelemetryServer.serve_forever",
        False,
        "Telemetry side-port accept loop (stdlib body); scrapes run on "
        "the telemetry-http-handler root."),
    ThreadRoot(
        "telemetry-http-handler", "handler",
        "mpitest_tpu.serve.telemetry._Handler.do_GET", False,
        "/metrics /healthz /varz /flightrecorder /profile scrapes: "
        "read-only snapshots of core state; arming a profile capture "
        "flips a flag under the hook lock — jax.profiler itself runs "
        "on the dispatch thread (ISSUE 10)."),
    ThreadRoot(
        "serve-tuner-prewarm", "thread",
        "mpitest_tpu.serve.server.ServerCore._tuner_observe._prewarm",
        True,
        "The serve tuner's background warm compile (ISSUE 14): "
        "deliberately builds packed executables OFF the dispatch "
        "thread via _build_detached (compile outside the cache lock); "
        "XLA compiles release the GIL."),
    ThreadRoot(
        "client-hedge", "thread",
        "mpitest_tpu.serve.client.ResilientClient._hedged.attempt",
        False,
        "Hedged request attempt (primary and hedge legs share the "
        "entry): pure wire I/O against the server socket (ISSUE 11)."),
    # -- ingest/egress pipeline ---------------------------------------
    ThreadRoot(
        "ingest-parse", "thread",
        "mpitest_tpu.models.ingest.stream_to_mesh.parse_chunks", False,
        "Streamed-ingest producer: reads and splits the input file "
        "into bounded queue chunks; host-side bytes only (ISSUE 6)."),
    ThreadRoot(
        "ingest-enc", "pool",
        "mpitest_tpu.models.ingest.stream_to_mesh.encode_one", False,
        "Encode workers: numpy/native codec folds on host chunks; the "
        "device transfer belongs to ingest-xfer."),
    ThreadRoot(
        "ingest-xfer", "pool",
        "mpitest_tpu.models.ingest.stream_to_mesh.transfer_one", True,
        "The ONE transfer thread: checked_device_put + "
        "block_until_ready per chunk — device DMA is its whole job, "
        "serialized so chunk k+1's encode overlaps chunk k's DMA."),
    ThreadRoot(
        "egress-fetch", "pool",
        "mpitest_tpu.models.ingest.stream_result_to_numpy.fetch", True,
        "Egress prefetch: pulls device shard k+1 to host while the "
        "driver decodes shard k — reads device buffers by design."),
    ThreadRoot(
        "io-parse", "pool",
        "mpitest_tpu.utils.io._parse_text_block", False,
        "Text-ingest parse workers (iter_key_chunks): numpy/native "
        "parsing of file blocks; no device access."),
    # -- external-sort async spill IO (ISSUE 20) ----------------------
    ThreadRoot(
        "spill-readahead", "thread",
        "mpitest_tpu.store.aio.ReadAhead._worker", False,
        "Per-run merge read-ahead: reads + decodes the NEXT spill "
        "chunk (disk read, block decompression) while the merge "
        "consumes the current one; host bytes/numpy only — the merge "
        "loop owns any device work."),
    ThreadRoot(
        "spill-writebehind", "thread",
        "mpitest_tpu.store.aio.WriteBehind._worker", False,
        "Merge write-behind: drains output chunks into the "
        "RunStreamWriter (encode, compress, throttle, write) behind "
        "the emit loop; errors re-raise at the caller's next append."),
    # -- driver signals -----------------------------------------------
    ThreadRoot(
        "signal-drain", "signal",
        "drivers.sort_server.main.on_signal", False,
        "SIGTERM/SIGINT: flips admission to draining and sets the stop "
        "event; never touches the device."),
    ThreadRoot(
        "signal-flight-dump", "signal",
        "drivers.sort_server.main.on_sigquit", False,
        "SIGQUIT: dumps the flight-recorder ring WITHOUT shutting "
        "down (the operator's 3am incident snapshot)."),
    ThreadRoot(
        "server-main", "main",
        "drivers.sort_server.main", True,
        "The server process main thread: startup prewarm (compiles "
        "in-process), then parks on the stop event."),
    # -- bench/ load generators & selftests ---------------------------
    ThreadRoot(
        "chaos-accept", "thread",
        "bench.wire_chaos.ChaosProxy._accept_loop", False,
        "Chaos proxy accept loop (wire-level fault injection)."),
    ThreadRoot(
        "chaos-conn", "thread",
        "bench.wire_chaos.ChaosProxy._serve_conn", False,
        "Per-connection chaos pipe (downstream leg)."),
    ThreadRoot(
        "chaos-pipe-up", "thread",
        "bench.wire_chaos.ChaosProxy._pipe_up", False,
        "Per-connection chaos pipe (upstream leg)."),
    ThreadRoot(
        "load-worker", "thread",
        "bench.serve_load.run_load.worker", False,
        "Load-generator worker: hammers the wire protocol."),
    ThreadRoot(
        "telemetry-selftest-worker", "thread",
        "bench.telemetry_live_selftest.run.worker", False,
        "Telemetry selftest load worker."),
    ThreadRoot(
        "durability-victim", "thread",
        "bench.durability_selftest.main.send_victim", False,
        "Durability selftest: the request the kill drill strands."),
    ThreadRoot(
        "chaos-stalled-request", "thread",
        "bench.chaos_serve_selftest.watchdog_cell.stalled_request",
        False,
        "Chaos selftest: the deliberately wedged request that trips "
        "the watchdog."),
)


# ---------------------------------------------------------------- locks
#
# ONE global acquisition order.  Ranks are spaced by 5 so a future lock
# slots in without renumbering; the order encodes today's real nesting
# edges (admission -> metrics via the on_change publish; sentinel ->
# metrics via alert counters; spans.log -> spans.flush in _flush) plus
# a sensible default for locks that never nest.

LOCKS: tuple[LockDecl, ...] = (
    LockDecl("batcher.pending", 10,
             "mpitest_tpu.serve.batching.Batcher._pending_lock",
             "Guards the incompatible-requests set-aside list."),
    LockDecl("breaker.state", 15,
             "mpitest_tpu.serve.watchdog.CircuitBreaker._lock",
             "All breaker state transitions; leaf in practice."),
    LockDecl("admission.state", 20,
             "mpitest_tpu.serve.admission.AdmissionControl._lock",
             "Admission byte/inflight accounting; the on_change "
             "publish fires under it, so it ranks BELOW the metrics "
             "registry lock it reaches."),
    LockDecl("sentinel.series", 25,
             "mpitest_tpu.serve.sentinel.SortSentinel._lock",
             "Rolling alert series + cooldowns; written from every "
             "span-closing thread via the observer hook."),
    LockDecl("cache.entries", 30,
             "mpitest_tpu.serve.executor_cache.ExecutorCache._lock",
             "Executor-cache entries/stats.  get_packed compiles "
             "under it by documented choice (cold-key dogpile); "
             "_build_detached is the compile-outside-the-lock path "
             "TL003 enforces for the prewarm side."),
    LockDecl("tuner.series", 35,
             "mpitest_tpu.models.planner.ServeTuner._lock",
             "Tuner observation deques + retune bookkeeping."),
    LockDecl("batcher.heartbeat", 40,
             "mpitest_tpu.serve.batching.Batcher._hb_lock",
             "Dispatch heartbeat cell — set/cleared around every "
             "executor call; aged by the watchdog."),
    LockDecl("spans.log", 45,
             "mpitest_tpu.utils.spans.SpanLog._lock",
             "Span id allocation/retention/stacks; observers run "
             "AFTER release (flush holds no log lock)."),
    LockDecl("spans.flush", 50,
             "mpitest_tpu.utils.spans.SpanLog._flush_lock",
             "Serializes JSONL stream appends across threads."),
    LockDecl("flight.ring", 55,
             "mpitest_tpu.utils.flight_recorder.FlightRecorder._lock",
             "Flight-recorder ring; reentrant because dump() "
             "snapshots while holding it.", reentrant=True),
    LockDecl("flight.singleton", 60,
             "mpitest_tpu.utils.flight_recorder._SINGLETON_LOCK",
             "Double-checked init of the process flight recorder."),
    LockDecl("server.tally", 65,
             "mpitest_tpu.serve.server.ServerCore._tally_lock",
             "requests_ok/requests_err counters (leaf)."),
    LockDecl("server.inflight", 70,
             "mpitest_tpu.serve.server.ServerCore._inflight_lock",
             "The in-flight request map for stuck_trace_ids (leaf)."),
    LockDecl("profile.hook", 75,
             "mpitest_tpu.serve.telemetry.ProfileHook._lock",
             "Profile-capture arm/disarm state; the jax.profiler "
             "calls themselves run OUTSIDE it on the dispatch "
             "thread."),
    LockDecl("faults.registry", 80,
             "mpitest_tpu.faults.FaultRegistry._lock",
             "Fault budgets/rng — ingest workers poll concurrently."),
    LockDecl("compress.load", 83,
             "mpitest_tpu.store.compress._LOAD_LOCK",
             "One-time spill-compression library resolution (same "
             "double-checked shim shape as native.load)."),
    LockDecl("runs.throttle", 84,
             "mpitest_tpu.store.runs._THROTTLE_LOCK",
             "The shared spill-disk token bucket "
             "(SORT_SPILL_THROTTLE_MBPS): one bucket = one simulated "
             "disk across every reader/writer thread; the sleep "
             "happens OUTSIDE it (TL003)."),
    LockDecl("native.load", 85,
             "mpitest_tpu.utils.native_encode._LOAD_LOCK",
             "One-time native-library resolution."),
    LockDecl("aio.readahead", 86,
             "mpitest_tpu.store.aio.ReadAhead._lock",
             "Read-ahead IO/stall interval stats — appended from the "
             "worker AND the consuming merge thread (leaf)."),
    LockDecl("aio.writebehind", 87,
             "mpitest_tpu.store.aio.WriteBehind._lock",
             "Write-behind interval stats + the parked worker error "
             "re-raised at the caller's next append/close (leaf)."),
    LockDecl("ingest.stream", 88,
             "mpitest_tpu.models.ingest._StreamState.lock",
             "Streamed-ingest shared fold/stats state."),
    LockDecl("metrics.registry", 90,
             "mpitest_tpu.utils.metrics_live.LiveMetrics._lock",
             "The live metric registry + every series update; ranks "
             "ABOVE admission/sentinel which update metrics under "
             "their own locks (leaf — holds no other lock)."),
    LockDecl("client.stats", 95,
             "mpitest_tpu.serve.client.ResilientClient._stats_lock",
             "Client attempt/hedge accounting (leaf)."),
    # bench/ locals
    LockDecl("bench.chaos", 100, "bench.wire_chaos.ChaosProxy._lock",
             "Chaos proxy connection/fault bookkeeping."),
    LockDecl("bench.load", 101, "bench.serve_load.run_load.lock",
             "Load-generator latency accumulators."),
    LockDecl("bench.telemetry-selftest", 102,
             "bench.telemetry_live_selftest.run.lock",
             "Telemetry selftest latency accumulators."),
)

#: Lock objects reached through a second name: the admission Condition
#: wraps the admission lock (``with self._idle`` acquires ``_lock``),
#: and Metric handles borrow the registry lock at construction.
LOCK_ALIASES: dict[str, str] = {
    "mpitest_tpu.serve.admission.AdmissionControl._idle":
        "mpitest_tpu.serve.admission.AdmissionControl._lock",
    "mpitest_tpu.utils.metrics_live.Metric._lock":
        "mpitest_tpu.utils.metrics_live.LiveMetrics._lock",
}


# ------------------------------------------------- call-graph alias maps
#
# The analyzer resolves ``self.x.m()`` chains through these explicit
# tables (ISSUE 19: "receiver-type heuristics + an explicit alias
# table") — attribute -> class for object fields, attribute -> callees
# for constructor-injected callbacks, function -> class for factory
# returns, and caller -> callees for dynamic observer fan-out.

#: ``module.Class.attr`` -> class qualname of the object stored there.
RECEIVER_TYPES: dict[str, str] = {
    "mpitest_tpu.serve.server.ServerCore.batcher":
        "mpitest_tpu.serve.batching.Batcher",
    "mpitest_tpu.serve.server.ServerCore.cache":
        "mpitest_tpu.serve.executor_cache.ExecutorCache",
    "mpitest_tpu.serve.server.ServerCore.admission":
        "mpitest_tpu.serve.admission.AdmissionControl",
    "mpitest_tpu.serve.server.ServerCore.breaker":
        "mpitest_tpu.serve.watchdog.CircuitBreaker",
    "mpitest_tpu.serve.server.ServerCore.metrics":
        "mpitest_tpu.utils.metrics_live.LiveMetrics",
    "mpitest_tpu.serve.server.ServerCore.sentinel":
        "mpitest_tpu.serve.sentinel.SortSentinel",
    "mpitest_tpu.serve.server.ServerCore.tuner":
        "mpitest_tpu.models.planner.ServeTuner",
    "mpitest_tpu.serve.server.ServerCore.profile_hook":
        "mpitest_tpu.serve.telemetry.ProfileHook",
    "mpitest_tpu.serve.server.SortServer.core":
        "mpitest_tpu.serve.server.ServerCore",
    "mpitest_tpu.serve.server._Handler.server":
        "mpitest_tpu.serve.server.SortServer",
    "mpitest_tpu.serve.watchdog.DispatchWatchdog.core":
        "mpitest_tpu.serve.server.ServerCore",
    "mpitest_tpu.serve.watchdog.DispatchWatchdog.breaker":
        "mpitest_tpu.serve.watchdog.CircuitBreaker",
    "mpitest_tpu.serve.telemetry.TelemetryServer.core":
        "mpitest_tpu.serve.server.ServerCore",
    "mpitest_tpu.serve.telemetry._Handler.server":
        "mpitest_tpu.serve.telemetry.TelemetryServer",
    "mpitest_tpu.serve.sentinel.SortSentinel.spans":
        "mpitest_tpu.utils.spans.SpanLog",
    "mpitest_tpu.serve.sentinel.SortSentinel.metrics":
        "mpitest_tpu.utils.metrics_live.LiveMetrics",
    "mpitest_tpu.serve.executor_cache.ExecutorCache.spans":
        "mpitest_tpu.utils.spans.SpanLog",
}

#: Constructor-injected callbacks: calling ``<site>(...)`` runs these.
ATTR_CALLS: dict[str, tuple[str, ...]] = {
    # Batcher's executors are ServerCore methods handed to __init__
    "mpitest_tpu.serve.batching.Batcher.run_batch":
        ("mpitest_tpu.serve.server.ServerCore._run_batch",),
    "mpitest_tpu.serve.batching.Batcher.run_solo":
        ("mpitest_tpu.serve.server.ServerCore._run_solo",),
    # admission change observer -> the server's gauge publish
    "mpitest_tpu.serve.admission.AdmissionControl.on_change":
        ("mpitest_tpu.serve.server.ServerCore._publish_admission",),
}

#: Factory functions -> class qualname of the returned object.
RETURN_TYPES: dict[str, str] = {
    "mpitest_tpu.utils.flight_recorder.get":
        "mpitest_tpu.utils.flight_recorder.FlightRecorder",
}

#: Dynamic fan-out the AST cannot see: span close runs the registered
#: observers (the metrics bridge and the sentinel) on WHATEVER thread
#: closed the span — this edge is what makes the sentinel's state
#: multi-root and the TL004 lockset check on it meaningful.
EXTRA_EDGES: dict[str, tuple[str, ...]] = {
    "mpitest_tpu.utils.spans.SpanLog._flush":
        ("mpitest_tpu.serve.sentinel.SortSentinel.__call__",),
}


# ------------------------------------------------------ call surfaces

#: Attribute-chain heads that mean "the JAX surface" (TL001): any
#: ``jax.*`` / ``jnp.*`` call.
JAX_SURFACE_HEADS: tuple[str, ...] = ("jax", "jnp")

#: Call names (bare or attribute tail) that mean the JAX surface even
#: without a ``jax.`` head: the device-put guard, device syncs, the
#: executor-cache hot path, and the packed-sort compiler.
JAX_SURFACE_CALLS: tuple[str, ...] = (
    "device_put", "checked_device_put", "block_until_ready",
    "get_packed", "compile_packed_sort",
)

#: Blocking calls TL003 refuses under any registered lock, with the
#: label findings carry.  Names are matched as dotted chains
#: (``os.fsync``) or attribute tails (``.sendall``).
BLOCKING_CALLS: dict[str, str] = {
    "os.fsync": "fsync",
    "time.sleep": "sleep",
    "sleep": "sleep",
    "subprocess.run": "subprocess",
    "subprocess.Popen": "subprocess",
    "subprocess.check_output": "subprocess",
    "subprocess.check_call": "subprocess",
    "subprocess.call": "subprocess",
    ".sendall": "socket send",
    ".recv": "socket recv",
    ".recv_into": "socket recv",
    ".accept": "socket accept",
    ".connect": "socket connect",
    "jax.jit": "XLA compile",
}

#: Repo functions that perform an XLA compile (TL003's compile leg
#: resolves calls interprocedurally to these).
COMPILE_FUNCS: tuple[str, ...] = (
    "mpitest_tpu.models.segmented.compile_packed_sort",
)

#: Calls that can block FOREVER while holding the GIL (TL005): an
#: in-process watchdog can never fire on them, so every use must ride a
#: bounded child process living in a module of GIL_WEDGE_HOME.  None is
#: registered: the one such call the repo made (``get_topology_desc``
#: behind a remote compiler) now runs only inside the compile tests'
#: fixture, where no server thread waits on it.
GIL_WEDGE_CALLS: tuple[str, ...] = ()

#: Modules allowed to (indirectly) own GIL-wedge calls.
GIL_WEDGE_HOME: tuple[str, ...] = ()

#: Attribute sites whose unlocked multi-root writes are DOCUMENTED
#: GIL-atomic single-reference swaps (TL004 exemptions need the same
#: review a jax_ok grant does).
ATOMIC_OK: tuple[str, ...] = (
    # live window resize: one float swap, re-read at every pack open
    "mpitest_tpu.serve.batching.Batcher.window_s",
    "mpitest_tpu.serve.batching.Batcher.window_retunes",
    # lazy flight-recorder hook bind: every racing writer stores the
    # SAME function object (idempotent single-reference swap), and the
    # hot flush path must not pay a lock for it
    "mpitest_tpu.utils.spans._flight_record",
)
