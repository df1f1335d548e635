"""Deterministic fault injection — the chaos half of the robustness layer.

The reference programs fail *silently*: bucket overflow truncates data
(``mpi_sample_sort.c:140-144``) and a rank that exits early strands its
peers (SURVEY §7.4).  Our port fixed those two instances, but a fix that
is only exercised by the bug it patched proves nothing about the next
fault.  This module makes failure a first-class, reproducible input:

* ``SORT_FAULTS=<spec>`` arms a :class:`FaultRegistry` — a comma list of
  ``site[:count]`` entries (``count`` defaults to 1; ``inf`` = fire on
  every opportunity, the persistent-failure configuration).  The spec is
  consumed deterministically: the k-th opportunity at a site fires iff
  the site still has budget, and corruption values derive from a
  splitmix64 stream over ``SORT_FAULTS_SEED`` — same spec + seed = the
  same faults in the same places, every run.
* Each subsystem polls its own site at its own fault point (the
  supervisor at dispatch, the exchange between all_to_all and the local
  sort/merge, the ingest pipeline after the fingerprint fold, the result
  before verification), so every detection/recovery path in
  ``models/supervisor.py`` is reachable from an env var.
* The native backends mirror this with ``COMM_FAULTS``
  (``comm/comm_faults.h``): ``kill:<rank>@<nth>`` / ``stall:<rank>@<nth>:<ms>``
  at collective entry.

Sites (the chaos grid of ``make fault-selftest`` covers all of them for
both algorithms):

================  ==========================================================
``dispatch_error``  raise a transient ``JaxRuntimeError`` at SPMD dispatch
``dispatch_oom``    raise a ``RESOURCE_EXHAUSTED``-shaped error at dispatch
``exchange_corrupt`` XOR-corrupt one exchange lane between the
                    all_to_all and the local sort (in-program, trace-time)
``exchange_drop``   zero one peer's recv count — drop a whole segment
``cap_squeeze``     force the first exchange cap to the alignment minimum
``ingest_poison``   corrupt an encoded ingest chunk AFTER the input
                    fingerprint folded it (streamed ingest only)
``dispatch_stall``  block the dispatch thread for ``SORT_FAULT_STALL_MS``
                    before launching (the serving watchdog's drill:
                    models a hung device call)
``result_swap``     swap the first/last keys of the sorted result
                    (breaks sortedness — caught by the order check)
``result_dup``      overwrite key[1] with key[0] (stays sorted — caught
                    ONLY by the multiset fingerprint)
``spill_corrupt``   flip bits in a spill run's on-disk keys AFTER the
                    fingerprint sidecar folded them (store/runs.py —
                    the external sort's bad-disk drill)
``merge_drop``      drop one merged output chunk before the output fold
                    (store/merge.py — silent merge truncation)
``spill_torn_write`` chop tail bytes off a run's key file at close —
                    a torn write whose sidecar promises more bytes
                    than disk holds (store/runs.py commit path)
``spill_enospc``    raise ``OSError(ENOSPC)`` at the Nth spill write
                    (``SORT_FAULT_ENOSPC_AT``) — the full-volume shape
                    the typed capacity rejection must absorb
``spill_bitrot``    flip one byte in a run's key body AFTER commit —
                    at-rest decay the merge's read-back fold catches
``spill_block_garbage`` scramble one compressed block's header AFTER
                    commit (SORTRUN2 runs only) — an undecodable block
                    the reader must type as block corruption, never
                    decode into wrong keys
``manifest_torn``   drop the tail of one spill-manifest journal line —
                    the crashed-mid-append shape replay skips loudly
``merge_stall``     block ``SORT_FAULT_STALL_MS`` at merge entry — a
                    merge wedged on a dying disk (the durability
                    drill's deterministic SIGKILL barrier)
================  ==========================================================

Wire-level chaos (ISSUE 11) is a separate family: :data:`WIRE_SITES`
name faults injected OUTSIDE the process by the chaos TCP proxy
(``bench/wire_chaos.py``) between a client and the sort server — torn
headers, stalled payloads, mid-response disconnects, slow-drip writes,
delayed responses, connect-then-silence.  They share this module's
spec grammar (:func:`parse_wire_faults`) so one vocabulary covers the
whole chaos surface, but they never corrupt *data*: they attack the
server's request-lifecycle bounds (read/idle timeouts, admission-byte
reclamation) and the client's retry/hedging policy instead.

Injection never bypasses detection: faults corrupt *data*, and the
always-on verifier (``models/verify.py``) plus the supervisor decide
what the user sees — a retried, fingerprint-verified result or a typed
error with a nonzero exit.  A fault that the system silently absorbs
into a wrong answer is exactly the bug class this module exists to make
impossible to miss.
"""

from __future__ import annotations

import errno
import itertools
import math
import threading
import time
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Callable

from mpitest_tpu.utils import knobs

if TYPE_CHECKING:
    import jax
    import numpy as np

    from mpitest_tpu.models.api import DistributedSortResult

SITES = (
    "dispatch_error",
    "dispatch_oom",
    "dispatch_stall",
    "exchange_corrupt",
    "exchange_drop",
    "cap_squeeze",
    "ingest_poison",
    "result_swap",
    "result_dup",
    # out-of-core external sort (ISSUE 15, mpitest_tpu/store/):
    "spill_corrupt",   # flip bits in a spill run's on-disk keys AFTER
                       # the fingerprint sidecar folded them — a bad
                       # disk / torn write the merge must catch
    "merge_drop",      # drop one merged output chunk before the output
                       # fold — silent truncation in the merge engine
    # crash-durable spill tier (ISSUE 18, store/manifest.py + resume):
    "spill_torn_write",  # chop tail bytes off a run's key file at
                         # close — sidecar/manifest promise more bytes
                         # than disk holds (re-spilled on blame)
    "spill_enospc",      # OSError(ENOSPC) at the Nth spill write
                         # (SORT_FAULT_ENOSPC_AT) — must surface as
                         # the typed capacity rejection, never a 500
    "spill_bitrot",      # flip one byte in a run's key body AFTER
                         # commit — at-rest decay caught by the
                         # merge's read-back fold
    "manifest_torn",     # drop the tail of one manifest journal line
                         # — replay must skip it loudly
    "merge_stall",       # block SORT_FAULT_STALL_MS at merge entry —
                         # the kill-resume drill's SIGKILL barrier
    # compressed spill runs (ISSUE 20, SORTRUN2 framing):
    "spill_block_garbage",  # scramble one compressed block's header
                            # after commit — the reader must raise a
                            # typed block-corruption error naming run
                            # + block, and blame-respill must recover
)

#: Sites applied at trace time inside the compiled SPMD program (the
#: exchange faults) — arming one forces a fresh compile via a unique
#: ``fault_token`` so the poisoned trace can never be served from the
#: jit cache to a clean run.
EXCHANGE_SITES = ("exchange_corrupt", "exchange_drop")

#: Wire-level chaos vocabulary (ISSUE 11): injected by the chaos TCP
#: proxy (``bench/wire_chaos.py``) between client and server.  Each
#: site carries one integer parameter (a byte offset ``k`` or a delay
#: in milliseconds — see ``WIRE_DEFAULT_PARAM``).
WIRE_SITES = (
    "wire_torn_header",         # forward k request bytes, then close
    "wire_stall_payload",       # forward header + k payload bytes, then
                                # go silent (the slow-loris shape)
    "wire_disconnect_response", # forward k response bytes, then close
    "wire_slow_drip",           # drip request bytes with k ms pauses
    "wire_delay_response",      # hold the response back for k ms
    "wire_connect_silence",     # accept the client, forward nothing
)

#: Per-site default parameter (bytes for the offset sites, ms for the
#: delay sites) when the spec names no ``@param``.
WIRE_DEFAULT_PARAM: dict[str, int] = {
    "wire_torn_header": 5,
    "wire_stall_payload": 64,
    "wire_disconnect_response": 16,
    "wire_slow_drip": 200,
    "wire_delay_response": 500,
    "wire_connect_silence": 0,
}


@dataclass(frozen=True)
class WireFault:
    """One parsed wire-fault entry: ``site[@param][:every]``.

    ``param`` is the site's byte offset / delay ms; ``every`` selects
    which proxied connections the fault fires on — every ``every``-th
    (1-based), so ``every=1`` hits all connections and ``every=4``
    hits the 4th, 8th, ... (deterministic tail injection for the
    hedging cells)."""

    site: str
    param: int
    every: int = 1

    def spec(self) -> str:
        """The canonical spec string (``parse_wire_faults`` round-trips
        it)."""
        out = self.site
        if self.param != WIRE_DEFAULT_PARAM[self.site]:
            out += f"@{self.param}"
        if self.every != 1:
            out += f":{self.every}"
        return out

    def fires_on(self, conn_index: int) -> bool:
        """True when this fault applies to the ``conn_index``-th
        (0-based) proxied connection."""
        return (conn_index + 1) % self.every == 0


def parse_wire_faults(spec: str) -> tuple[WireFault, ...]:
    """Parse a comma list of ``site[@param][:every]`` wire-fault
    entries (the ``SORT_FAULTS``-style grammar extended with the wire
    family).  Raises ``ValueError`` naming the vocabulary on garbage —
    the same fail-fast contract as :class:`FaultRegistry`."""
    out: list[WireFault] = []
    for entry in spec.split(","):
        entry = entry.strip()
        if not entry:
            continue
        name, _, every_s = entry.partition(":")
        name, _, param_s = name.partition("@")
        if name not in WIRE_SITES:
            raise ValueError(
                f"wire faults: unknown site {name!r}; use one of "
                f"{WIRE_SITES}")
        param = WIRE_DEFAULT_PARAM[name]
        if param_s:
            try:
                param = int(param_s)
            except ValueError:
                param = -1
            if param < 0:
                raise ValueError(
                    f"wire faults: bad param {param_s!r} for {name!r}; "
                    "use an integer >= 0 (bytes or ms)")
        every = 1
        if every_s:
            try:
                every = int(every_s)
            except ValueError:
                every = 0
            if every < 1:
                raise ValueError(
                    f"wire faults: bad every-count {every_s!r} for "
                    f"{name!r}; use an integer >= 1")
        out.append(WireFault(name, param, every))
    if not out:
        raise ValueError(
            f"wire faults: empty spec; use a comma list of "
            f"site[@param][:every] over {WIRE_SITES}")
    return tuple(out)


def _splitmix64(state: int) -> tuple[int, int]:
    """One splitmix64 step: (next_state, output) — the same deterministic
    stream family native/comm_fuzz.c uses, so corruption values are
    reproducible from SORT_FAULTS_SEED alone."""
    state = (state + 0x9E3779B97F4A7C15) & 0xFFFFFFFFFFFFFFFF
    z = state
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & 0xFFFFFFFFFFFFFFFF
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & 0xFFFFFFFFFFFFFFFF
    return state, z ^ (z >> 31)


@dataclass
class _Site:
    name: str
    remaining: float  # math.inf = persistent


@dataclass
class FaultRegistry:
    """Parsed, seedable fault plan for ONE sort run.

    ``fire(site)`` consumes one unit of that site's budget (thread-safe:
    the ingest pool's workers poll ``ingest_poison`` concurrently) and
    records the firing in :attr:`fired`; ``on_fire`` (set by the
    supervisor) forwards each firing into the span/counter pipeline.
    """

    spec: str
    seed: int = 0
    sites: dict[str, _Site] = field(default_factory=dict)
    fired: list[tuple[str, dict[str, object]]] = field(default_factory=list)
    on_fire: Callable[[str, dict[str, object]], None] | None = None

    def __post_init__(self) -> None:
        self._lock = threading.Lock()
        self._rng_state = (self.seed * 0x2545F4914F6CDD1D + 1) & 0xFFFFFFFFFFFFFFFF
        self._seq = 0
        for entry in self.spec.split(","):
            entry = entry.strip()
            if not entry:
                continue
            name, _, cnt = entry.partition(":")
            if name not in SITES:
                raise ValueError(
                    f"SORT_FAULTS: unknown fault site {name!r}; "
                    f"use one of {SITES}"
                )
            if cnt in ("", None):
                count: float = 1
            elif cnt == "inf":
                count = math.inf
            else:
                try:
                    count = int(cnt)
                except ValueError:
                    count = 0
                if count < 1:
                    raise ValueError(
                        f"SORT_FAULTS: bad count {cnt!r} for {name!r}; "
                        "use a positive integer or 'inf'"
                    )
            site = self.sites.setdefault(name, _Site(name, 0))
            site.remaining += count

    # -- firing -------------------------------------------------------
    def would_fire(self, site: str) -> bool:
        """Non-consuming budget peek (lets hooks avoid advancing the
        corruption RNG for sites that are not armed — the RNG stream
        must depend only on the faults that actually fire)."""
        with self._lock:
            s = self.sites.get(site)
            return s is not None and s.remaining > 0

    def fire(self, site: str, **detail: object) -> bool:
        """Consume one unit of ``site``'s budget; True iff the fault
        fires now.  Records the firing and notifies ``on_fire``."""
        with self._lock:
            s = self.sites.get(site)
            if s is None or s.remaining <= 0:
                return False
            s.remaining -= 1
            self._seq += 1
            detail = dict(detail, seq=self._seq)
            self.fired.append((site, detail))
        cb = self.on_fire
        if cb is not None:
            cb(site, detail)
        return True

    def rand_word(self) -> int:
        """Deterministic nonzero uint32 corruption value."""
        with self._lock:
            self._rng_state, out = _splitmix64(self._rng_state)
        return (out & 0xFFFFFFFF) or 0xDEADBEEF

    @property
    def injected(self) -> int:
        return len(self.fired)


# -- run-scoped activation -------------------------------------------------

#: Registry installed explicitly (tests / the chaos driver) — takes
#: precedence over the SORT_FAULTS env spec.
_INSTALLED: FaultRegistry | None = None

#: Stack of registries active for the current run (sort() / ingest
#: pipeline); trace-time and worker-thread hooks read the top.
_ACTIVE: list[FaultRegistry] = []

#: Exchange fault handed from the host (supervisor) to the trace-time
#: hook in collectives.ragged_all_to_all; one-shot, popped at trace.
#: The supervisor drops it if the armed dispatch dies before tracing,
#: and run teardown (``active.__exit__``) clears any stragglers — a
#: stale entry must never leak into a later clean compile.
_PENDING_EXCHANGE: list[dict] = []

#: Process-global token sequence: every armed exchange fault gets a
#: token no earlier compile can have used, so the jit cache can never
#: serve a poisoned trace to a different run (or skip the trace that
#: would consume the pending entry).
_TOKEN_SEQ = itertools.count(1)


def install(reg: FaultRegistry | None) -> None:
    """Install a registry for subsequent runs (None clears).  Tests use
    this instead of mutating os.environ."""
    global _INSTALLED
    _INSTALLED = reg


def for_run() -> FaultRegistry | None:
    """The registry for a new run: the installed one, else a FRESH parse
    of ``SORT_FAULTS`` (counts reset every run — deterministic per run,
    not cumulative across a process)."""
    if _INSTALLED is not None:
        return _INSTALLED
    spec = knobs.get_raw("SORT_FAULTS")
    if not spec:
        return None
    return FaultRegistry(spec, seed=faults_seed())


def faults_seed() -> int:
    """``SORT_FAULTS_SEED`` (default 0): the corruption-stream seed."""
    return knobs.get("SORT_FAULTS_SEED")


def validate_env() -> None:
    """Fail-fast parse of the fault knobs (the CLI's [ERROR] contract)."""
    knobs.validate("SORT_FAULTS", "SORT_FAULTS_SEED")


class active:
    """Context manager scoping ``reg`` to the current run (re-entrant:
    a donated-retry re-ingest inside a sort nests cleanly)."""

    def __init__(self, reg: FaultRegistry | None) -> None:
        self.reg = reg

    def __enter__(self) -> FaultRegistry | None:
        if self.reg is not None:
            _ACTIVE.append(self.reg)
        return self.reg

    def __exit__(self, *exc: object) -> bool:
        if self.reg is not None and _ACTIVE and _ACTIVE[-1] is self.reg:
            _ACTIVE.pop()
        if self.reg is not None and not _ACTIVE:
            drop_pending()  # no armed-but-untraced fault may outlive a run
        return False


def current() -> FaultRegistry | None:
    return _ACTIVE[-1] if _ACTIVE else None


# -- site hooks ------------------------------------------------------------

def arm_exchange(reg: FaultRegistry | None) -> str:
    """Host side of the exchange faults: if one fires for this dispatch,
    queue its parameters for the trace-time hook and return a
    PROCESS-UNIQUE compile token (forces a fresh trace — a reused token
    would let the jit cache serve an old poisoned program AND leave the
    pending entry unconsumed, to be baked into the next clean trace).
    Empty token = clean compile, shared cache."""
    if reg is None:
        return ""
    for site in EXCHANGE_SITES:
        if not reg.would_fire(site):
            continue  # don't advance the RNG for unarmed sites
        word = reg.rand_word()
        if reg.fire(site, word=word):
            _PENDING_EXCHANGE.append({"site": site, "word": word})
            return f"{site}#{next(_TOKEN_SEQ)}"
    return ""


def drop_pending() -> int:
    """Discard any armed-but-unconsumed exchange fault — called when the
    armed dispatch dies before its first trace and at run teardown, so a
    stale entry can never corrupt a later clean compile.  Returns the
    number dropped (the caller records them as ``faults_dropped`` —
    they were counted as injected when armed but never touched data)."""
    n = len(_PENDING_EXCHANGE)
    _PENDING_EXCHANGE.clear()
    return n


def apply_exchange_fault(
    recv_arrays: tuple[jax.Array, ...], recv_cnt: jax.Array,
) -> tuple[tuple[jax.Array, ...], jax.Array]:
    """Trace-time hook (called from collectives.ragged_all_to_all, i.e.
    between the exchange and the local sort/merge): apply the pending
    exchange fault, if any, to the first traced exchange of the armed
    dispatch.  No-op on clean compiles."""
    if not _PENDING_EXCHANGE:
        return recv_arrays, recv_cnt
    import jax.numpy as jnp

    spec = _PENDING_EXCHANGE.pop()
    if spec["site"] == "exchange_drop":
        # drop the segment peer 0 sent to every rank: a truncated
        # exchange, the reference's silent-overflow shape
        recv_cnt = recv_cnt.at[0].set(0)
        return recv_arrays, recv_cnt
    # exchange_corrupt: flip deterministic bits in lane (0, 0) of the
    # first word array — a payload corrupted in flight
    w0 = recv_arrays[0]
    w0 = w0.at[0, 0].set(w0[0, 0] ^ jnp.uint32(spec["word"]))
    return (w0,) + tuple(recv_arrays[1:]), recv_cnt


def maybe_poison_chunk(words: tuple[np.ndarray, ...],
                       chunk_idx: int) -> tuple[np.ndarray, ...]:
    """Ingest-pipeline hook (worker threads): corrupt CHUNK 0's first
    encoded word AFTER the fingerprint fold — the device receives data
    the fingerprint never saw, so the output verifier must flag it.
    Pinned to chunk 0 (one budget unit per stream pass): encode workers
    race on the budget otherwise, and which chunk got poisoned would
    depend on thread scheduling — the registry's same-spec+seed
    determinism contract forbids that."""
    if chunk_idx != 0:
        return words
    reg = current()
    if reg is None or not reg.would_fire("ingest_poison"):
        return words
    word = reg.rand_word()
    if not reg.fire("ingest_poison", chunk=chunk_idx, word=word):
        return words
    w0 = words[0].copy()
    if w0.size:
        w0[0] ^= word & 0xFFFFFFFF
    return (w0,) + tuple(words[1:])


def maybe_corrupt_spill(raw: bytes) -> bytes:
    """Spill-run hook (store/runs.py write path): corrupt the first key
    bytes of a run AFTER its fingerprint sidecar folded the clean words
    — the on-disk bytes then disagree with the sidecar, exactly the
    torn-write/bit-rot shape the merge's read-back fold must flag."""
    reg = current()
    if reg is None or not reg.would_fire("spill_corrupt"):
        return raw
    word = reg.rand_word()
    if not reg.fire("spill_corrupt", word=word):
        return raw
    buf = bytearray(raw)
    if len(buf) >= 4:
        for i in range(4):
            buf[i] ^= (word >> (8 * i)) & 0xFF
    return bytes(buf)


def should_drop_merge_chunk(chunk_idx: int, n: int) -> bool:
    """Merge hook (store/merge.py emit path): True when the armed
    ``merge_drop`` site consumes this output chunk — the chunk vanishes
    from the merged output AND its fold, so the external driver's
    count/fingerprint comparison against the combined run sidecars must
    trip (silent truncation made loud)."""
    reg = current()
    if reg is None or not reg.would_fire("merge_drop"):
        return False
    return reg.fire("merge_drop", chunk=chunk_idx, n=n)


def spill_tear_bytes(body_bytes: int) -> int:
    """Spill-commit hook (store/runs.py close path): number of tail
    bytes to chop off the run's key file (0 = clean).  The sidecar (and
    any manifest line) already promise the full length, so the torn run
    is caught structurally — ``open_run`` / the merge's size check —
    and blamed + re-spilled, or discarded by resume re-validation."""
    reg = current()
    if reg is None or body_bytes <= 0 \
            or not reg.would_fire("spill_torn_write"):
        return 0
    word = reg.rand_word()
    cut = min(1 + (word % 7), body_bytes)
    if not reg.fire("spill_torn_write", cut=cut, body=body_bytes):
        return 0
    return cut


def spill_bitrot_word() -> int | None:
    """Post-commit bit-rot hook (store/runs.py close path): a nonzero
    corruption word to XOR into the middle of the run's key body AFTER
    the durable commit, or None when clean.  The on-disk bytes then
    disagree with the sidecar — at-rest decay the merge's read-back
    fold (and resume's ``verify_run``) must flag."""
    reg = current()
    if reg is None or not reg.would_fire("spill_bitrot"):
        return None
    word = reg.rand_word()
    if not reg.fire("spill_bitrot", word=word):
        return None
    return word


def spill_block_garbage_word() -> int | None:
    """Post-commit block-garbage hook (store/runs.py close path,
    compressed SORTRUN2 runs only): a corruption word used to scramble
    the middle block's header fields after the durable commit, or None
    when clean.  The block becomes undecodable — framing or checksum —
    so the reader's typed :class:`~mpitest_tpu.store.runs.
    BlockIntegrityError` must name the run and block, and the merge's
    blame ladder must re-spill the run, never emit garbage keys."""
    reg = current()
    if reg is None or not reg.would_fire("spill_block_garbage"):
        return None
    word = reg.rand_word()
    if not reg.fire("spill_block_garbage", word=word):
        return None
    return word


def maybe_spill_enospc(nbytes: int) -> None:
    """Spill-write hook (store/runs.py append path): raise a real
    ``OSError(ENOSPC)`` at the Nth write opportunity
    (``SORT_FAULT_ENOSPC_AT``, 1-based) — the volume-full shape the
    external driver must convert to the typed capacity rejection with
    partial outputs deleted, never an untyped 500."""
    reg = current()
    if reg is None or not reg.would_fire("spill_enospc"):
        return
    at = int(knobs.get("SORT_FAULT_ENOSPC_AT"))
    seen = int(getattr(reg, "_enospc_writes", 0)) + 1
    reg._enospc_writes = seen  # type: ignore[attr-defined]
    if seen < at:
        return
    if reg.fire("spill_enospc", write=seen, bytes=nbytes):
        raise OSError(errno.ENOSPC,
                      "No space left on device (injected spill_enospc)")


def manifest_tear_cut(line_len: int) -> int:
    """Manifest-journal hook (store/manifest.py commit path): number of
    tail bytes of this journal line that never reach disk (0 = clean)
    — the crashed-mid-append shape replay must skip loudly without
    losing the committed lines before it."""
    reg = current()
    if reg is None or line_len <= 1 \
            or not reg.would_fire("manifest_torn"):
        return 0
    cut = max(1, line_len // 2)
    if not reg.fire("manifest_torn", cut=cut, line_len=line_len):
        return 0
    return cut


def maybe_merge_stall() -> None:
    """Merge-entry hook (store/external.py): block the merging thread
    for ``SORT_FAULT_STALL_MS`` — a merge wedged on a dying disk.  The
    durability drill arms this as its deterministic barrier: the
    process is SIGKILLed mid-stall with every partition run already
    durably committed, so the restart must resume at the merge."""
    reg = current()
    if reg is None or not reg.would_fire("merge_stall"):
        return
    ms = int(knobs.get("SORT_FAULT_STALL_MS"))
    if reg.fire("merge_stall", ms=ms):
        time.sleep(ms / 1e3)


def maybe_corrupt_result(reg: FaultRegistry | None,
                         res: "DistributedSortResult",
) -> "DistributedSortResult":
    """Result hook (host side, before verification): swap endpoints
    (breaks sortedness) or duplicate a key (multiset change only — the
    fingerprint's job).  Returns a corrupted copy of ``res``'s words."""
    if reg is None:
        return res
    import numpy as np  # noqa: F811 — runtime import (lazy; jax-adjacent)

    from mpitest_tpu.models.ingest import checked_device_put

    for site in ("result_swap", "result_dup"):
        if reg.sites.get(site) and reg.sites[site].remaining > 0:
            if not reg.fire(site):
                continue
            new_words = []
            for w in res.words:
                host = np.asarray(w).copy()
                if host.size >= 2:
                    if site == "result_swap":
                        a, b = 0, min(res.n_valid, host.size) - 1
                        host[a], host[b] = host[b].copy(), host[a].copy()
                    else:
                        host[1] = host[0]
                new_words.append(checked_device_put(host, w.sharding))
            res.words = tuple(new_words)
            break
    return res
