"""Public sort API: dispatch, sharding, overflow-retry, host assembly.

This is the driver layer of the framework (the reference's ``main()`` +
``sort()`` scaffolding, ``mpi_sample_sort.c:28-82,220-241``, redesigned):
it owns everything that is *not* SPMD — dtype encoding, padding to static
shapes, placing shards on the mesh, compiling the shard_map program,
reacting to exchange overflow, and decoding results back to the host.

Static-shape contract: inputs pad to ``P·n`` with copies of the *maximum
real key* (SURVEY.md §7.4 "Scatter overflow" fix — padding also makes P∤N
inputs correct, which the reference gets wrong).  Pads tie with genuine
max keys and sort to the global tail, so slicing the first N elements
recovers the exact multiset — bit-identical output — and, unlike an
all-ones sentinel, pads never widen the key range seen by the radix
pass planner.

Overflow-retry contract: the SPMD programs return the global max per-peer
segment length.  If it exceeded the static cap, lanes were dropped and the
result is discarded; the host recompiles with that length as the new cap
and reruns.  For single-exchange sample sort the reported value is exact,
so one retry suffices; for multi-pass radix an overflowed early pass
corrupts what later passes see, so the reported max can understate a later
pass's need — the cap still grows strictly monotonically (bounded by the
shard size), so the loop terminates, possibly after more than one
recompile.  This replaces the reference's silent bucket overflow
(``mpi_sample_sort.c:140-144``) and its "no enough sample" abort
(``:96-99``) with a clean, always-correct path.
"""

from __future__ import annotations

import contextlib
import math
from dataclasses import dataclass
from functools import lru_cache
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from mpitest_tpu import faults
from mpitest_tpu.models import plan as plan_mod
from mpitest_tpu.models import planner as planner_mod
from mpitest_tpu.models import radix_sort, sample_sort
from mpitest_tpu.models import supervisor as supervision
from mpitest_tpu.models import verify as vfy
from mpitest_tpu.models.local import (
    LocalSort,
    _f64_host_input,
    _f64_known_broken,
    _is_f64_lowering_gap,
    _local_engine,
    _resolve_local_engine,
    decode_on_device,
    sort_one_device,
)
from mpitest_tpu.models.supervisor import (  # re-exported: public errors
    ExchangeCapExceeded,
    SortFaultError,
    SortIntegrityError,
    SortRetryExhausted,
    SortSupervisor,
)
from mpitest_tpu.models.ingest import (
    EGRESS_MIN_BYTES as _EGRESS_MIN_BYTES,
    StagedIngest,
    checked_device_put,
    stream_result_to_numpy,
    stream_to_mesh,
    use_stream,
)
from mpitest_tpu.ops.keys import KeyCodec, codec_for
from mpitest_tpu.parallel.mesh import AXIS, key_sharding, make_mesh
from mpitest_tpu.utils import io as kio
from mpitest_tpu.utils import knobs
from mpitest_tpu.utils.programs import program, traced_call
from mpitest_tpu.utils.trace import Tracer


def _no_span(name: str, **attrs: object) -> Any:
    return contextlib.nullcontext()


@dataclass
class DistributedSortResult:
    """Device-resident sorted output (sharded); decode lazily on demand."""

    words: tuple[jax.Array, ...]     # sharded [P*n] (radix) or [P*(P*cap)] (sample)
    n_valid: int                     # total real keys (excludes padding)
    dtype: np.dtype
    counts: np.ndarray | None = None  # per-shard valid counts (ragged layouts)
    shard_slots: int | None = None    # slots per shard for ragged layouts
    #: the sorted keys already decoded on the device (one-device
    #: results bound for the host; ``words`` is then empty): keys of the
    #: caller's dtype, or 64-bit keys as rows of their uint32 halves
    #: (``KeyCodec.decode_jax``).  The fetch lands the caller's bytes.
    keys: jax.Array | None = None

    def to_numpy(self, tracer: "Tracer | None" = None) -> np.ndarray:
        """The sorted keys on the host.  With a tracer, the device→host
        copy and the decode record ``egress.fetch`` / ``egress.decode``
        (the streamed path records its own, per shard; keys decoded on
        the device record the fetch alone, and come back read-only
        where the fetch is a view of the device buffer, as on CPU)."""
        if self.n_valid == 0:
            return np.empty(0, self.dtype)
        codec = codec_for(self.dtype)

        stage = tracer.span if tracer is not None else _no_span
        if self.keys is not None:
            with stage("egress.fetch", bytes=int(self.keys.nbytes)):
                host = np.asarray(self.keys)
            if host.flags.owndata:
                # a fresh host copy (the chip's fetch) is the caller's
                # alone: JAX marks it read-only only because it caches
                # it on `keys`, which is dropped with this result
                host.flags.writeable = True
            return host.view(self.dtype).reshape(-1)[: self.n_valid]
        fetched = sum(int(w.nbytes) for w in self.words)

        if self.counts is None:
            # Streamed egress (models/ingest.py): decode shard k while
            # shard k+1 is still fetching — on by default above the
            # auto threshold, forced by SORT_INGEST=stream, disabled by
            # =mono.  Ragged (sample) results keep the legacy gather.
            try:
                # multi-host arrays only expose local shards here; the
                # streamed decode cannot cover the rest, so those fall
                # through to the legacy gather (which raises loudly).
                n_shards = (len(self.words[0].addressable_shards)
                            if self.words[0].is_fully_addressable else 1)
            except Exception:
                n_shards = 1
            mode = kio.ingest_mode()
            nbytes = self.n_valid * np.dtype(self.dtype).itemsize
            if n_shards > 1 and (
                mode == "stream"
                or (mode == "auto" and nbytes >= _EGRESS_MIN_BYTES)
            ):
                return stream_result_to_numpy(
                    self.words, self.n_valid, self.dtype, tracer=tracer)
            with stage("egress.fetch", bytes=fetched):
                host = tuple(np.asarray(w) for w in self.words)
            with stage("egress.decode", n=self.n_valid):
                return codec.decode(tuple(w[: self.n_valid] for w in host))
        with stage("egress.fetch", bytes=fetched):
            host = tuple(np.asarray(w) for w in self.words)
        # ragged: concatenate the valid prefix of each shard's slot range,
        # then drop the padding sentinels (global max ⇒ they sit at the tail)
        parts = []
        for w in host:
            segs = [
                w[i * self.shard_slots : i * self.shard_slots + c]
                for i, c in enumerate(self.counts)
            ]
            parts.append((np.concatenate(segs) if segs else w[:0])[: self.n_valid])
        with stage("egress.decode", n=self.n_valid):
            return codec.decode(tuple(parts))

    def median_probe_raw(self) -> Any:
        """The (n/2)-th sorted element as a native-dtype scalar (exact
        bits — float probes must compare bit patterns, since distinct
        float medians can collide under int truncation)."""
        idx = self.n_valid // 2 - 1
        if idx < 0:
            raise ValueError("median probe undefined for < 2 keys")
        codec = codec_for(self.dtype)
        # Slice on device, THEN materialize: one element crosses the
        # host boundary, not the full multi-GB result.
        if self.counts is None:
            return codec.decode(tuple(np.asarray(w[idx : idx + 1]) for w in self.words))[0]
        cum = np.concatenate([[0], np.cumsum(self.counts)])
        shard = int(np.searchsorted(cum, idx, side="right")) - 1
        off = idx - cum[shard]
        s = self.shard_slots
        return codec.decode(
            tuple(np.asarray(w[shard * s + off : shard * s + off + 1]) for w in self.words)
        )[0]

    def median_probe(self) -> int:
        """The reference's correctness probe: the (n/2)-th sorted element
        (``int_buf[size_input / 2 - 1]``, mpi_sample_sort.c:205)."""
        return int(self.median_probe_raw())


def _round_cap(c: int, align: int = 128) -> int:
    """Round caps up to a lane-friendly multiple: 128 (TPU minor dim) for
    the XLA pack, 1024 (the DMA chunk) for the Pallas pack."""
    return max(align, ((c + align - 1) // align) * align)


_PACK_IMPLS = ("xla", "pallas", "pallas_interpret")


def _resolve_pack(pack: str | None) -> str:
    """Exchange-pack implementation: Pallas DMA pack on real TPU (4.7×
    the XLA scatter spread at 2^26 on v5e), XLA elsewhere."""
    if pack is None:
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if pack not in _PACK_IMPLS:
        raise ValueError(f"unknown pack {pack!r}; use one of {_PACK_IMPLS}")
    return pack


def _cap_align(pack: str) -> int:
    from mpitest_tpu.ops.pallas_kernels import CHUNK

    return CHUNK if pack.startswith("pallas") else 128


def _resolve_exchange_engine(engine: str | None) -> str:
    """Concrete exchange-engine impl (ISSUE 13): ``None`` reads the
    ``SORT_EXCHANGE_ENGINE`` knob.  ``auto`` = the remote-DMA Pallas
    engine on real TPU backends, the XLA collective elsewhere; a forced
    ``pallas`` without a TPU runs the engine's interpreter form (same
    convention as the bitonic local engine, ``local._bitonic_impl``) —
    the remote-copy hop itself then rides the bit-identical
    ``lax.all_to_all``, see ``ops/exchange.py``."""
    from mpitest_tpu.ops import exchange as xeng

    v = engine if engine is not None else supervision.exchange_engine_knob()
    if v == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "lax"
    if v not in xeng.ENGINES:
        raise ValueError(
            f"unknown exchange engine {v!r}; use one of "
            f"{('auto',) + xeng.ENGINES}")
    if v == "pallas" and jax.default_backend() != "tpu":
        return "pallas_interpret"
    return v


def _engine_pack(pack_impl: str, engine: str) -> tuple[str, int]:
    """(effective pack impl, cap alignment) for one ladder rung: the
    pallas exchange engine owns its pack (the fused multi-word kernel,
    CHUNK-aligned caps); the lax engine keeps the resolved ``pack``."""
    if engine.startswith("pallas"):
        return engine, _cap_align(engine)
    return pack_impl, _cap_align(pack_impl)


def _passes_from_diffs(diffs: tuple[int, ...], digit_bits: int) -> int:
    """Number of LSD passes actually required, from per-word ``max ^ min``
    diffs (msw first) — the one canonical pass planner, shared by the host
    path (diffs from :func:`_word_diffs`) and the device path (diffs from
    one scalar min/max sync per word).  Digits above the highest
    globally-differing bit are identical everywhere and can be skipped —
    the principled version of the reference's ``number_digits`` pre-pass
    (``mpi_radix_sort.c:100``).

    Digit alignment restarts at every 32-bit word boundary (the pass loop
    in :func:`radix_sort_spmd` walks ``per_word`` digits per word), so the
    count is ``per_word``-per-full-word plus the digits covering the
    differing bits of the first non-constant word — NOT a contiguous
    bit-count over the whole key, which would undercount whenever
    ``digit_bits`` does not divide 32.
    """
    n_words = len(diffs)
    per_word = (32 + digit_bits - 1) // digit_bits
    for wi, x in enumerate(diffs):  # msw first
        if x:
            full_words_below = n_words - 1 - wi
            return min(
                full_words_below * per_word + math.ceil(x.bit_length() / digit_bits),
                per_word * n_words,
            )
    return 0


def _word_diffs(words: tuple[np.ndarray, ...]) -> tuple[int, ...]:
    """Per-word ``max ^ min`` of host key words (msw first) — the one
    canonical input to pass planning; empty input has no differing bits."""
    if words[0].size == 0:
        return (0,) * len(words)
    return tuple(int(w.max()) ^ int(w.min()) for w in words)


@lru_cache(maxsize=8)
def _compile_word_range(dtype_name: str) -> Callable[..., Any]:
    """Per-word min/max of the encoded key words (msw first) — feeds the
    pass planner for device-resident input (one tiny reduction + scalar
    sync instead of abandoning pass skipping)."""
    codec = codec_for(np.dtype(dtype_name))

    def f(x):
        words = codec.encode_jax(x)
        return tuple((jnp.min(w), jnp.max(w)) for w in words)

    return jax.jit(program("word_range", "ingest", f))


def _mesh_platform(mesh: Mesh) -> str:
    """Platform the mesh compiles for — the memo key for the sharded
    path (``local._f64_encode_broken_platforms``): the failing compile
    is ``_compile_encode_pad(..., mesh)``, so
    keying on the *input's* platform would both poison a healthy backend
    (CPU input, broken TPU mesh) and miss the memo (TPU input, same
    broken mesh)."""
    return mesh.devices.flat[0].platform


@lru_cache(maxsize=16)
def _compile_encode_pad(dtype_name: str, total: int,
                        mesh: Mesh | None) -> Callable[..., Any]:
    """Device-side encode + pad-to-``total``-with-max.  With a mesh, the
    output is sharded on the key axis; with ``mesh=None`` the program runs
    wherever the input lives (used for non-divisible N, whose *input*
    cannot be evenly sharded — the padded output can, and is landed on the
    mesh by the caller).  Keeps device-resident keys off the host."""
    codec = codec_for(np.dtype(dtype_name))

    def f(x):
        words = codec.encode_jax(x)
        pad = total - x.shape[0]
        if pad:
            # Pad with the maximum real key in the *native* order (encode
            # is order-preserving, so its word tuple is lexicographically
            # max) — never a per-word max, which for multi-word keys could
            # fabricate a key larger than any real one.  Float codecs pad
            # with the all-ones sentinel instead: jnp.max is NaN-poisoned
            # and a NaN "max" need not be the totalOrder maximum.
            if codec.sentinel_pad:
                mx_words = tuple(jnp.full((1,), mw, jnp.uint32)
                                 for mw in codec.max_sentinel())
            else:
                mx_words = codec.encode_jax(jnp.max(x)[None])
            words = tuple(
                jnp.concatenate([w, jnp.broadcast_to(mw[0], (pad,))])
                for w, mw in zip(words, mx_words)
            )
        return words

    body = program("encode_pad", "ingest", f)
    if mesh is None:
        return jax.jit(body)
    return jax.jit(body, out_shardings=key_sharding(mesh))


@lru_cache(maxsize=64)
def _compile_radix(mesh: Mesh, n_words: int, n: int, digit_bits: int,
                   cap: int, passes: int, pack: str, donate: bool = False,
                   fault_token: str = "",
                   exchange_engine: str = "lax") -> Callable[..., Any]:
    # fault_token: unique per armed exchange fault (mpitest_tpu.faults) —
    # a poisoned trace gets its own cache entry and can never be served
    # to a clean dispatch.  "" = the shared clean compile.
    n_ranks = mesh.devices.size

    def f(*words):
        out, max_cnt = radix_sort.radix_sort_spmd(
            words, n_words, digit_bits, n_ranks, cap, passes, pack=pack,
            exchange_engine=exchange_engine,
        )
        return out, max_cnt

    return jax.jit(
        jax.shard_map(
            program("radix_sort", "exchange", f),
            mesh=mesh,
            in_specs=(P(AXIS),) * n_words,
            out_specs=((P(AXIS),) * n_words, P()),
            # pallas_call's internal ops mix varying/unvarying operands in
            # ways the vma checker rejects; out_specs are explicit here.
            # The engine conjunct matters only for DIRECT compiles (sort()
            # forces pack to the engine's impl via _engine_pack, but e.g.
            # radix_pass_states-style callers can pass pack="xla" with a
            # pallas engine, whose transport still runs pallas kernels).
            check_vma=(pack == "xla" and exchange_engine == "lax"),
        ),
        # Donation: the input word shards alias the output word shards
        # (same shape/dtype/sharding), so HBM holds ONE copy of the keys
        # during the sort instead of two — the streamed-ingest memory
        # contract.  Callers rebuild words before any overflow retry
        # (the donated buffers are dead after the call).
        donate_argnums=tuple(range(n_words)) if donate else (),
    )


@lru_cache(maxsize=64)
def _compile_sample(mesh: Mesh, n_words: int, n: int, cap: int,
                    oversample: int, pack: str, engine: str = "lax",
                    donate: bool = False,
                    fault_token: str = "",
                    exchange_engine: str = "lax") -> Callable[..., Any]:
    # fault_token: see _compile_radix.
    n_ranks = mesh.devices.size

    def f(*words):
        out, count, max_cnt = sample_sort.sample_sort_spmd(
            words, n_words, n_ranks, cap, oversample, pack=pack,
            engine=engine, exchange_engine=exchange_engine,
        )
        return out, count[None], max_cnt

    return jax.jit(
        jax.shard_map(
            program("sample_sort", "exchange", f),
            mesh=mesh,
            in_specs=(P(AXIS),) * n_words,
            out_specs=((P(AXIS),) * n_words, P(AXIS), P()),
            # pallas_call internals (exchange pack, bitonic engine) mix
            # varying/unvarying operands in ways the vma checker rejects.
            check_vma=(pack == "xla" and engine == "lax"
                       and exchange_engine == "lax"),
        ),
        # see _compile_radix: input/output word aliasing under donation
        # ([P*(P*cap)] outputs differ in shape from [P*n] inputs, so XLA
        # may only reuse rather than alias — still a net HBM win).
        donate_argnums=tuple(range(n_words)) if donate else (),
    )


@lru_cache(maxsize=32)
def _compile_radix_probe(mesh: Mesh, n_words: int, n: int,
                         digit_bits: int) -> Callable[..., Any]:
    """Capacity-negotiation probe (ISSUE 7): the exact pass-1 per-peer
    send-count matrix, no key movement (radix_sort.radix_probe_spmd)."""
    n_ranks = mesh.devices.size

    def f(*words: jax.Array) -> jax.Array:
        return radix_sort.radix_probe_spmd(words, digit_bits, n_ranks)

    return jax.jit(
        jax.shard_map(
            program("radix_probe", "exchange", f), mesh=mesh,
            in_specs=(P(AXIS),) * n_words, out_specs=P(),
            # the [P, P] matrix is replicated by construction (it comes
            # out of an all_gather) but the vma checker cannot prove it
            check_vma=False,
        )
    )


@lru_cache(maxsize=32)
def _compile_sample_probe(mesh: Mesh, n_words: int, n: int,
                          oversample: int) -> Callable[..., Any]:
    """Estimated splitter-repartition count matrix (sample_probe_spmd)."""
    n_ranks = mesh.devices.size

    def f(*words: jax.Array) -> jax.Array:
        return sample_sort.sample_probe_spmd(words, n_ranks, oversample)

    return jax.jit(
        jax.shard_map(
            program("sample_probe", "exchange", f), mesh=mesh,
            in_specs=(P(AXIS),) * n_words, out_specs=P(),
            check_vma=False,  # see _compile_radix_probe
        )
    )


@lru_cache(maxsize=16)
def _compile_interleave(mesh: Mesh, n_words: int,
                        n: int) -> Callable[..., Any]:
    """Skew-aware re-stage program (ISSUE 7): deal the global key array
    round-robin across shards — ``new[j*n + i] = old[i*P + j]`` — so a
    clustered arrangement (sorted/reverse-sorted input, the cap-blowing
    case) turns into one where every shard holds a representative
    stride of the whole distribution and per-peer exchange counts
    collapse toward the fair share.  A pure permutation: the sorted
    output (and the multiset fingerprint the verifier checks) is
    bit-identical.  Costs one resharding pass over the words — paid
    only when the measured imbalance says the exchange would otherwise
    need a near-worst-case capacity."""
    P_ = int(mesh.devices.size)

    def f(*words: jax.Array) -> tuple[jax.Array, ...]:
        return tuple(w.reshape(n, P_).T.reshape(-1) for w in words)

    return jax.jit(program("interleave", "exchange", f),
                   out_shardings=key_sharding(mesh))


#: Safety margin on the sample probe's ESTIMATED per-peer counts (its
#: splitters are sampled, the real run's are exact local quantiles —
#: see sample_sort.sample_probe_spmd); the radix probe is exact and
#: needs none.
SAMPLE_NEG_MARGIN = 1.25


def _negotiation_enabled(n_ranks: int) -> bool:
    """``SORT_NEGOTIATE``: capacity negotiation runs the count probe
    before compiling the exchange (auto/on = whenever the mesh is
    actually distributed; a 1-device mesh has no exchange to size)."""
    return knobs.get("SORT_NEGOTIATE") != "off" and n_ranks > 1


def _restage_enabled(n_ranks: int) -> bool:
    """``SORT_RESTAGE``: the skew-aware re-stage is armed (P>1 only)."""
    return knobs.get("SORT_RESTAGE") != "off" and n_ranks > 1


def _donation_enabled() -> bool:
    """Buffer donation on the sort dispatch: ``SORT_DONATE`` ∈
    {auto, 1, 0} (validated in one place, ``utils.io.donate_setting``).
    ``auto`` donates on real TPU backends only — that is where the
    aliasing saves HBM; CPU donation saves nothing and (on some jaxlib
    versions) emits an unusable-donation warning on every compile."""
    v = kio.donate_setting()
    if v == "auto":
        return jax.default_backend() == "tpu"
    return v == "1"


#: Recv-memory bound for the sample-sort exchange, in units of the fair
#: per-peer share ceil(n/P).  The [P, cap] recv buffer is then at most
#: 8·n words per device — O(n), never O(N) — and inputs needing more
#: (heavy duplication: every copy of a hot key routes to one splitter
#: interval) fall back to radix, whose destination = exact global
#: position is skew-immune by construction (SURVEY.md §7.3 Zipf config).
SAMPLE_CAP_LIMIT_FACTOR = 8


def _sample_skew_sniff(words_np: tuple[np.ndarray, ...], n_ranks: int) -> bool:
    """Cheap host-side skew detector: would quantile splitters degenerate?

    Takes an evenly-strided ~32·P-key sample, sorts it, and picks the same
    P-1 quantiles the SPMD program would.  Two *equal adjacent* splitters
    mean at least 2/P of the sample mass sits on one key value — every
    copy would route to a single destination and the exchange cap would
    blow through the O(n) bound, so route to radix up front instead of
    discovering it via a failed exchange round.  (Duplication below that
    threshold keeps splitters distinct and the cap bounded; the reactive
    in-loop bound still catches anything the sample misses.)
    """
    n_total = words_np[0].size
    s = min(n_total, max(64, 32 * n_ranks))
    idx = np.linspace(0, n_total - 1, s).astype(np.int64)
    # lexsort: last key is primary → feed words lsw-first.
    order = np.lexsort(tuple(w[idx] for w in reversed(words_np)))
    qpos = (np.arange(1, n_ranks) * s) // n_ranks
    picks = [tuple(int(w[idx[order[q]]]) for w in words_np) for q in qpos]
    return any(a == b for a, b in zip(picks, picks[1:]))


@lru_cache(maxsize=32)
def _compile_skew_sniff(mesh: Mesh, n_words: int, n_valid: int,
                        n_ranks: int) -> Callable[..., Any]:
    """Device-side twin of :func:`_sample_skew_sniff` for device-resident
    input (VERDICT r2 #4): the same evenly-strided sample, quantile picks
    and adjacent-equality verdict, computed on the mesh — one tiny
    compile + one scalar sync instead of discovering degeneracy through a
    failed full exchange round + recompile.  Samples index [0, n_valid)
    only, so pad slots (appended after the real keys) never join.

    The sample is a *static* strided ``lax.slice`` (start/stride/limit
    are Python ints baked into the program) rather than a gather: gather
    indices carry a dtype, and int32 ones silently wrap for
    n_valid ≥ 2^31 (ADVICE r3 #1) — a strided slice has no index array
    to overflow, at any scale.  The slice is anchored so its LAST pick
    is exactly index n_valid-1 (like the host twin's linspace endpoint):
    anchoring at 0 instead would leave up to ~n_valid/2 tail keys — and
    the global max — outside the sample."""
    s = min(n_valid, max(64, 32 * n_ranks))
    if s > 1:
        # Ceil, not floor: floor division made stride 1 whenever
        # n_valid < 2s, inflating the "sample" to nearly the whole shard
        # (ADVICE r4 #3).  Ceil keeps the pick count <= the requested s.
        stride = -(-(n_valid - 1) // (s - 1))
        s = (n_valid - 1) // stride + 1   # picks that fit the range
        start = (n_valid - 1) - (s - 1) * stride  # last pick = n_valid-1
    else:
        stride, start = 1, 0
    qpos = (np.arange(1, n_ranks) * s) // n_ranks

    def f(*words):
        # msw first = lexicographic order
        picks = [jax.lax.slice(w, (start,), (start + (s - 1) * stride + 1,),
                               (stride,))
                 for w in words]
        sp = jax.lax.sort(picks, num_keys=len(picks), is_stable=False)
        sp = sp if isinstance(sp, (list, tuple)) else (sp,)
        if qpos.size < 2:
            return jnp.zeros((), bool)
        eq = jnp.ones((qpos.size - 1,), bool)
        for p in sp:
            q = p[qpos]
            eq &= q[:-1] == q[1:]
        return jnp.any(eq)

    return jax.jit(program("skew_sniff", "exchange", f))


def _host_pad_words(codec: KeyCodec, flat: np.ndarray, dtype: np.dtype,
                    total: int) -> tuple[int, ...] | None:
    """Pad-word tuple for host input shorter than ``total``: the maximum
    real key (encode is order-preserving, so encoding the host max yields
    the lexicographically-max word tuple), or the all-ones sentinel for
    float codecs — ``np.max`` is NaN-poisoned and a NaN "max" need not be
    the totalOrder maximum.  None when no padding is needed (skips the
    host max() scan)."""
    if flat.size >= total:
        return None
    if codec.sentinel_pad:
        return codec.max_sentinel()
    return tuple(int(w[0]) for w in codec.encode(np.asarray([flat.max()], dtype)))


def _auto_digit_bits(diffs: tuple[int, ...]) -> int:
    """Auto digit width: a pass costs one full fused sort regardless of
    digit width (BASELINE.md roofline), so wider digits that cut the pass
    count win outright; 16-bit digits halve full-range int32 to 2 passes.
    The histogram / exscan metadata grows to [P, 65536] int32 — 256 KiB
    per device per pass, noise next to the shard itself."""
    return 16 if _passes_from_diffs(diffs, 16) < _passes_from_diffs(diffs, 8) else 8


def _shard_input(words_np: tuple[np.ndarray, ...], mesh: Mesh, n: int,
                 pad_words: tuple[int, ...] | None = None,
                 ) -> tuple[jax.Array, ...]:
    P_ = mesh.devices.size
    sharding = key_sharding(mesh)
    out = []
    for i, w in enumerate(words_np):
        if w.size < P_ * n:
            w = np.concatenate([w, np.full(P_ * n - w.size, pad_words[i], np.uint32)])
        out.append(checked_device_put(w, sharding))
    return tuple(out)


def radix_pass_states(
    x: Any, mesh: Mesh | None = None, digit_bits: int | None = None,
    cap_factor: float = 2.0, pack: str | None = None,
) -> Iterator[tuple[int, np.ndarray, np.ndarray]]:
    """Debug observability: the globally digit-sorted array after each LSD
    pass — the TPU twin of the reference's per-pass intermediate dump
    (``DUMP: LOOP %u RADIX %u = %u``, ``mpi_radix_sort.c:175-178``) and of
    the native core's debug>2 contract (``native/radix_core.h``).

    The fused SPMD program runs all passes inside one jit, so intermediate
    states are not observable in a production run; this helper *re-runs*
    the program with ``passes`` limited to 1..P — the LSD invariant makes
    the pass-``k`` output exactly the state after pass ``k`` (input stably
    sorted by its low ``k`` digits).  O(passes²) total work, debug-only.

    Yields ``(pass_index_1based, shard_size, full_padded_array)`` where the
    array is the decoded ``[P·shard]`` result (pads included — they are
    copies of the maximum real key and, by stability, the LAST occurrences
    of that value in every pass state; callers attributing keys to ranks
    drop exactly those trailing occurrences).
    """
    x = np.asarray(x)
    dtype = np.dtype(x.dtype)
    codec = codec_for(dtype)
    N = int(x.size)
    if N == 0:
        return
    if mesh is None:
        mesh = make_mesh()
    n_ranks = int(mesh.devices.size)
    n = max(1, math.ceil(N / n_ranks))
    flat = x.reshape(-1)
    words_np = codec.encode(flat)
    pad = _host_pad_words(codec, flat, dtype, n_ranks * n)
    words = _shard_input(words_np, mesh, n, pad)
    diffs = _word_diffs(words_np)
    if digit_bits is None:
        digit_bits = _auto_digit_bits(diffs)
    passes = _passes_from_diffs(diffs, digit_bits)
    pack_impl = _resolve_pack(pack)
    align = _cap_align(pack_impl)
    # cap only ever grows: an overflow discovered at pass prefix k would
    # recur at every k' > k, so keep the grown value across the loop.
    cap = _round_cap(int(n / n_ranks * cap_factor) + 1, align)
    for k in range(1, passes + 1):
        while True:
            fn = _compile_radix(mesh, codec.n_words, n, digit_bits, cap, k,
                                pack_impl)
            out, max_cnt = fn(*words)
            if int(max_cnt) <= cap:
                break
            cap = _round_cap(int(max_cnt), align)
        full = codec.decode(tuple(np.asarray(w) for w in out))
        yield k, n, full


def device_mem_peak(mesh: Mesh | None) -> int:
    """Peak HBM high-water across the mesh devices where the backend
    exposes ``memory_stats()`` (real TPU; CPU returns 0).  Best-effort
    telemetry — never raises.  The serve layer attaches this per packed
    batch (ISSUE 10); :func:`sort` attaches it to its umbrella span."""
    try:
        devs = list(mesh.devices.flat) if mesh is not None else jax.devices()
        peak = 0
        for d in devs:
            stats = d.memory_stats() if hasattr(d, "memory_stats") else None
            if stats:
                peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        return peak
    except Exception:
        return 0


def _device_mem_high_water(span: Any, mesh: Mesh | None) -> None:
    """Attach :func:`device_mem_peak` to ``span`` when nonzero."""
    peak = device_mem_peak(mesh)
    if peak:
        span.attrs["device_mem_peak_bytes"] = peak


def _finish_plan(tracer: Tracer, plan: "plan_mod.SortPlan | None") -> None:
    """Seal and emit the run's decision record (ISSUE 12): default the
    engine/restage decisions from what the counters already know, fold
    the per-decision regrets, stamp the scalars into ``tracer.counters``
    (the bench rows read them), and emit the registered ``sort.plan``
    point event — the record ``report.py --explain``, the live regret
    metrics and the ``/varz`` decision snapshot all consume."""
    if plan is None:
        return
    c = tracer.counters
    engine = c.get("local_engine")
    if engine is not None:
        if "engine" in plan.decisions:
            plan.actual("engine", local_engine=str(engine))
        else:
            plan.decide("engine", chosen=str(engine))
        # backend rides the engine actual: the same engine name means
        # a different kernel on a TPU and under the CPU interpreter
        plan.actual("engine", backend=str(jax.default_backend()))
    fallbacks = (int(c.get("pair_residual_fallback", 0))
                 - int(getattr(plan, "fallbacks_base", 0)))
    if fallbacks > 0:
        plan.actual("engine", fallbacks=fallbacks)
    if plan.ranks and plan.ranks > 1 and "restage" not in plan.decisions:
        # never restaged: its regret is every overflow regrow a
        # re-stage would have prevented (stamped by the supervisor)
        cap_d = plan.decisions.get("cap")
        plan.decide("restage", chosen=False)
        plan.actual("restage",
                    regrows=(cap_d.actual.get("regrows", 0)
                             if cap_d is not None else 0))
    total = plan.finalize()
    tracer.counters["plan_regret"] = total
    cap_d = plan.decisions.get("cap")
    if cap_d is not None and cap_d.regret is not None:
        tracer.counters["plan_cap_regret"] = cap_d.regret
    tracer.spans.event("sort.plan", **plan.to_attrs())
    tracer.plan = plan


def ingest_to_mesh(
    x: Any,
    mesh: Mesh | None = None,
    tracer: Tracer | None = None,
    chunk_elems: int | None = None,
    threads: int | None = None,
) -> StagedIngest:
    """Public streaming-ingest entry: run the chunked, double-buffered
    parse→encode→DMA pipeline (:mod:`mpitest_tpu.models.ingest`) over
    host keys ``x`` and return the :class:`StagedIngest` that
    :func:`sort` accepts in place of raw keys (skipping its own
    encode/pad/device_put entirely).  Every host→device transfer goes
    through the dtype-preservation guard (:func:`checked_device_put`).

    ``SORT_TRACE`` streaming applies here exactly as in :func:`sort`, so
    the ``ingest.*`` stage spans land in the same JSONL the report CLI
    aggregates."""
    if mesh is None:
        mesh = make_mesh()
    tracer = tracer or Tracer()
    trace_path = knobs.get("SORT_TRACE")
    if trace_path and tracer.spans.stream_path is None:
        tracer.spans.stream_path = trace_path
    reg = faults.for_run()
    supervision.wire_registry(reg, tracer)
    with tracer.spans.span("ingest", n=int(np.asarray(x).size),
                           dtype=str(np.asarray(x).dtype)), \
            faults.active(reg):
        return stream_to_mesh(x, mesh, tracer=tracer,
                              chunk_elems=chunk_elems, threads=threads)


def sort(
    x: Any,
    algorithm: str = "radix",
    mesh: Mesh | None = None,
    digit_bits: int | None = None,
    cap_factor: float = 2.0,
    oversample: int | None = None,
    tracer: Tracer | None = None,
    return_result: bool = False,
    pack: str | None = None,   # exchange pack impl; None = auto by backend
    exchange_engine: str | None = None,  # None = SORT_EXCHANGE_ENGINE knob
    payload: Any = None,       # per-record payload bytes -> record sort
) -> Any:
    """Sort integer keys on the mesh; returns a sorted numpy array
    (or the device-resident :class:`DistributedSortResult`).

    ``payload`` (ISSUE 15) turns the call into a **record sort**: each
    key drags an opaque per-record payload (bytes / any fixed-itemsize
    array, see :func:`models.records.as_payload_matrix`) that is
    permuted alongside the keys by a device-side argsort-gather —
    stable by key, verified end-to-end by the record fingerprint.  The
    return value is then the ``(sorted_keys, sorted_payload)`` pair
    (payload as an ``(n, width)`` uint8 matrix); ``return_result`` /
    ``exchange_engine`` do not apply — records ride the fused local
    program in :mod:`mpitest_tpu.models.records`.

    ``exchange_engine`` (ISSUE 13) selects the inter-device exchange
    path — ``lax`` (XLA collective) or ``pallas`` (remote-DMA kernel +
    fused pass, ``ops/exchange.py``; ``pallas_interpret`` is its
    interpreter form); ``None`` reads ``SORT_EXCHANGE_ENGINE`` (auto =
    pallas on TPU backends).  A pallas failure degrades to the lax
    engine on the supervisor ladder, fingerprint-verified and recorded
    as a plan decision.

    ``x`` may be a host array, a device-resident ``jax.Array``, or a
    :class:`StagedIngest` from :func:`ingest_to_mesh` (pre-encoded,
    pre-sharded words — the streaming pipeline's output).  Large host
    arrays automatically ride the same pipeline (``SORT_INGEST`` knob:
    auto/stream/mono); on TPU the staged word buffers are donated to the
    SPMD program so device memory holds one copy of the keys, not two.

    Telemetry: the run accumulates a structured span log on
    ``tracer.spans`` (:mod:`mpitest_tpu.utils.spans`) — nested phases,
    jit compile-vs-execute splits, one trace-time span per radix pass /
    splitter round / collective with byte counts, and the device memory
    high-water where ``memory_stats()`` exists.  ``SORT_TRACE=<path>``
    streams it as JSONL; ``tracer.spans.to_chrome_trace()`` exports the
    same run for Perfetto.  See the module docstring of utils/spans.py
    for the device-side granularity contract.
    """
    if algorithm not in ("radix", "sample"):
        raise ValueError(f"unknown algorithm {algorithm!r}")
    tracer = tracer or Tracer()
    trace_path = knobs.get("SORT_TRACE")
    if trace_path and tracer.spans.stream_path is None:
        tracer.spans.stream_path = trace_path
    if payload is not None:
        # record sort (ISSUE 15): key+payload through the fused
        # argsort-gather program — models/records.py owns the path,
        # including its always-on record-fingerprint verification.
        # The record path mints no plan record: clear any PREVIOUS
        # run's plan from a reused tracer (the serve dispatch thread),
        # or the reply digest would carry a stranger's decisions.
        from mpitest_tpu.models import records

        tracer.plan = None
        arr = np.asarray(x)
        with tracer.spans.span("sort", algorithm="records",
                               n=int(arr.size), dtype=str(arr.dtype)):
            return records.sort_records(arr, payload, mesh=mesh,
                                        tracer=tracer)
    size = getattr(x, "size", None)
    # Fault registry for THIS run (SORT_FAULTS env or an installed test
    # registry) — active for the whole run so the ingest/exchange hooks
    # see it; None in production is a no-op.
    reg = faults.for_run()
    # Plan provenance (ISSUE 12): ONE decision record per run, minted
    # here and carried on the tracer so every chokepoint below — algo
    # reroutes, negotiation, re-stage, the supervisor's regrow loop,
    # the fallback ladder — stamps into the same object.  SORT_PLAN=off
    # restores the PR 8 behavior.
    plan = (plan_mod.SortPlan(algo=algorithm,
                              dtype=str(getattr(x, "dtype", "")) or None)
            if plan_mod.enabled() else None)
    if plan is not None:
        # tracer counters accumulate across runs on a reused Tracer
        # (the serve dispatch thread): snapshot the fallback tally so
        # _finish_plan stamps THIS run's delta, not server-lifetime sums
        plan.fallbacks_base = int(
            tracer.counters.get("pair_residual_fallback", 0))
    tracer.plan = plan
    with tracer.spans.span(
        "sort", algorithm=algorithm,
        n=int(size) if size is not None else None,
        dtype=str(getattr(x, "dtype", "")) or None,
    ) as sp, faults.active(reg):
        try:
            out = _sort_impl(x, algorithm, mesh, digit_bits, cap_factor,
                             oversample, tracer, return_result, pack, reg,
                             exchange_engine)
            _finish_plan(tracer, plan)
        except supervision.SortFaultError as e:
            # ISSUE 10: a typed terminal error leaves an artifact — the
            # flight recorder's last-N spans (this run's retries, fault
            # events and failed verifications included) dumped where
            # SORT_FLIGHT_RECORDER_DIR points, rate-limited per reason.
            from mpitest_tpu.utils import flight_recorder

            flight_recorder.dump_on_error(type(e).__name__)
            raise
        _device_mem_high_water(sp, mesh)
    return out


def _sort_impl(
    x: Any,
    algorithm: str,
    mesh: Mesh | None,
    digit_bits: int | None,
    cap_factor: float,
    oversample: int | None,
    tracer: Tracer,
    return_result: bool,
    pack: str | None,
    reg: "faults.FaultRegistry | None" = None,
    exchange_engine: str | None = None,
) -> Any:
    """The sort() body (see the public wrapper's docstring — this layer
    assumes a validated algorithm and a live tracer/span log).

    Robustness contract (ISSUE 3): every result is verified before it is
    returned — on-device sortedness plus a multiset fingerprint compared
    against the input-side fingerprint folded during ingest/encode
    (:mod:`mpitest_tpu.models.verify`) — and the distributed dispatch
    runs under a :class:`SortSupervisor`: bounded retry with exponential
    backoff on transient ``JaxRuntimeError``, ONE shared cap-regrow loop
    for both algorithms, and a graceful-degradation ladder (requested
    algorithm → the other algorithm → host lexsort) on persistent
    failure.  The outcome is always a verified result or a typed
    :class:`SortIntegrityError` / :class:`SortRetryExhausted` — never a
    silent wrong answer.  Knobs: ``SORT_VERIFY``, ``SORT_MAX_RETRIES``,
    ``SORT_RETRY_BACKOFF``, ``SORT_FALLBACK``, ``SORT_FAULTS`` (fault
    injection, :mod:`mpitest_tpu.faults`).

    ``algorithm``: ``"radix"`` (flagship: perfectly load-balanced, fixed
    pass count) or ``"sample"`` (one exchange round; cap-sensitive under
    skew).  Both produce identical bytes — sorted output is canonical.

    Skew fallback (SURVEY.md §7.3): ``"sample"`` inputs whose quantile
    splitters would degenerate (heavy duplication — the Zipf stress
    config) route to radix automatically, either up front (host-side
    sniff, :func:`_sample_skew_sniff`) or reactively when the exchange
    cap would exceed the O(n)-per-device bound
    (:data:`SAMPLE_CAP_LIMIT_FACTOR`); ``tracer.counters
    ["sample_skew_fallback"]`` records the reroute.

    ``x`` may be a host array OR a device-resident ``jax.Array`` (any
    supported dtype — 64-bit device arrays exist only under
    ``jax_enable_x64`` and split into uint32 words on-device): the device
    path encodes/pads on-device and never round-trips the keys through
    the host — the framework's steady-state contract (keys live sharded
    on the mesh; SURVEY.md §5 long-context row).

    Device-resident ``float64`` caveats (measured on v5e, round 3): TPU
    stacks without a native f64→u32 bitcast lowering degrade to ONE
    documented host round-trip (``tracer.counters["f64_host_fallback"]``)
    instead of an internal compiler error; and on such stacks the
    *device array itself* is approximate (f64 held via f32-pair
    emulation — ~2e-15 relative error introduced by ``device_put``,
    before this function is called).  The sort is always bit-exact with
    respect to the bits actually resident on the device; host-input
    float64 is bit-exact, full stop.
    """
    staged = x if isinstance(x, StagedIngest) else None
    if staged is not None:
        if staged.consumed:
            raise ValueError(
                "StagedIngest was already consumed by a donated sort "
                "dispatch (its word buffers now belong to XLA); call "
                ".rebuild() or ingest_to_mesh() again for another sort")
        is_device = False
        dtype = staged.dtype
        codec = codec_for(dtype)
        N = staged.n_valid
        if mesh is None:
            mesh = staged.mesh
        elif mesh != staged.mesh:  # equality, not identity: make_mesh()
            raise ValueError(      # builds equal-but-distinct Mesh objects
                "StagedIngest was streamed onto a different mesh")
    else:
        is_device = isinstance(x, jax.Array)
        if not is_device:
            x = np.asarray(x)
        dtype = np.dtype(x.dtype)
        codec = codec_for(dtype)
        N = int(x.size)
    if N == 0:
        out = np.empty(0, dtype)
        return out if not return_result else DistributedSortResult((), 0, dtype)
    if mesh is None:
        mesh = make_mesh()
    n_ranks = int(mesh.devices.size)
    n = max(1, math.ceil(N / n_ranks))

    # ---- exchange engine (ISSUE 13): resolved once per run ----------
    # The ladder may later degrade it (pallas → lax); _eng is the ONE
    # mutable engine state every compile below reads.  Recorded in the
    # counters even for exchange-free (1-device) runs so bench rows
    # always carry the engine column.
    eng0 = _resolve_exchange_engine(exchange_engine)
    _eng = {"v": eng0}
    tracer.counters["exchange_engine"] = eng0
    # where the sorted words become the caller's keys: the host, unless
    # the one-device finish decodes them on the device
    tracer.counters["decode_site"] = "host"
    # ---- local-sort engine: the KNOB-level value (auto / bitonic /
    # lax); each dispatch resolves it per key width and size.
    leng0 = _local_engine()

    # ---- plan provenance (ISSUE 12): the run's decision record ------
    plan = tracer.plan if isinstance(tracer.plan, plan_mod.SortPlan) \
        else None
    if plan is not None:
        plan.n = N
        plan.ranks = n_ranks
        plan.decide("algo", chosen=algorithm, requested=algorithm)
        if staged is None and not is_device:
            # host input: sortedness / run-length / duplicate profile
            # from a ~1k strided sample (no extra key movement; the
            # probe adds entropy/skew once the histogram materializes)
            plan.profile.update(plan_mod.profile_host_array(x))

    verify_on = supervision.verify_enabled()
    # Wire fault telemetry BEFORE any word staging: the ingest_poison
    # site fires inside the streaming pipeline, long before the
    # supervisor object exists below.
    supervision.wire_registry(reg, tracer)

    # ---- self-tuning planner (ISSUE 14): the policy layer -----------
    # off: nothing below runs — the hand-set defaults byte-for-byte.
    # shadow: every policy is scored and logged as the registered
    # `planner` plan decision (applied=False) while the output path
    # stays untouched.  on: the algo policy may override `algorithm`
    # and the learned margin replaces SAMPLE_NEG_MARGIN.  The planner
    # rides the plan record, so SORT_PLAN=off also disables it.
    planner_mode = planner_mod.mode()
    pchoice: "planner_mod.PolicyChoice | None" = None
    neg_margin = SAMPLE_NEG_MARGIN
    if planner_mode != "off" and plan is not None:
        pchoice = planner_mod.choose(plan.profile, algorithm,
                                     verify_on=verify_on)
        # the margin only steers the sample negotiation: requests bound
        # for radix, 1-rank runs (no exchange) and negotiate-off runs
        # skip the flight-ring scan entirely — and never record
        # cap_margin as an applied policy they cannot act on (a
        # passthrough miss over a sample request still falls into the
        # sample path, so those keep it)
        if ((pchoice.algo or algorithm) == "sample"
                and _negotiation_enabled(n_ranks)):
            margin, margin_ev = planner_mod.learned_margin(
                SAMPLE_NEG_MARGIN)
        else:
            margin, margin_ev = SAMPLE_NEG_MARGIN, {}
        # the RECORDED policy: when the algo scorer chose nothing but
        # the margin policy learned, the margin IS the planner's move
        name = pchoice.policy
        if name == "static" and margin_ev.get("margin_learned"):
            name = "cap_margin"
        planner_mod.policy(name)  # runtime twin of SL006: loud KeyError
        plan.decide("planner", chosen=name, requested="static",
                    trigger=pchoice.trigger,
                    applied=(planner_mode == "on"),
                    algo=pchoice.algo, margin=round(margin, 4),
                    **dict(pchoice.predicted, **margin_ev))
        tracer.counters["planner"] = planner_mode
        tracer.counters["planner_policy"] = name
        if planner_mode == "on":
            neg_margin = margin
            if pchoice.algo is not None and pchoice.algo != algorithm:
                # the scored reroute: recorded exactly like the sniff/
                # probe reroutes, so plan_regret now measures the
                # planner itself (a wrong choice shows up as algo/cap
                # regret on a planner-triggered decision)
                plan.decide("algo", chosen=pchoice.algo,
                            trigger="planner")
                algorithm = pchoice.algo
            if (pchoice.policy == "radix_compact"
                    and "passes" in pchoice.predicted):
                # key-width compaction (ISSUE 17): the profile's min/max
                # promise a narrow key, so pre-record the predicted pass
                # count.  run_radix keeps this prediction when it plans
                # for real — the "passes" regret then prices a lying
                # profile (sampled min/max missed the range, more passes
                # ran than the planner promised).
                plan.decide("passes",
                            chosen=int(pchoice.predicted["passes"]),
                            trigger="planner",
                            passes=int(pchoice.predicted["passes"]))

    def _check_result(res_v, fp_v) -> bool:
        """Run the on-device verifier on a result; True = verified.
        Emits the ``verify`` span event (ok / sorted_ok / fp_ok) the
        report CLI's robustness table aggregates."""
        with tracer.span("phase:verify"), tracer.span("verify.check"):
            sorted_ok, fp_ok = vfy.verify_result(res_v, fp_v, tracer)
        tracer.count("verify_runs", 1)
        tracer.spans.event("verify", ok=bool(sorted_ok and fp_ok),
                           sorted_ok=bool(sorted_ok), fp_ok=bool(fp_ok),
                           n=N)
        if not (sorted_ok and fp_ok):
            tracer.verbose(
                f"output verification FAILED (sorted={bool(sorted_ok)}, "
                f"fingerprint={bool(fp_ok)})")
        return bool(sorted_ok and fp_ok)

    #: the planner's rung zero (ISSUE 14): the profile read fully
    #: sorted, so the encoded input words ARE a sort candidate
    passthrough_on = (pchoice is not None and planner_mode == "on"
                      and verify_on
                      and pchoice.policy == "verify_passthrough")

    def _passthrough(words, fp) -> bool:
        """One verify dispatch in place of the sort: True when the input
        words pass it.  A miss costs exactly the verify (typed as the
        planner decision's regret) before the sort runs."""
        if _check_result(DistributedSortResult(words, N, dtype), fp):
            tracer.count("planner_passthrough", 1)
            if plan is not None:
                plan.decide("ladder", chosen="passthrough")
            return True
        tracer.count("planner_passthrough_miss", 1)
        if plan is not None:
            plan.actual("planner", misses=1)
        return False

    def _finish_local(out: LocalSort):
        """Verify-and-return for the single-device paths.  No ladder
        here (the degradation machinery targets the distributed
        dispatch); a verification failure is a typed error.  A
        ``verified`` result is the planner's passthrough hit.

        Results bound for the host whose codec is a constant XOR decode
        on the device, and the verifier then reads the decoded keys: the
        bytes the caller gets are the bytes it checked.  A passthrough
        hit was checked as words, so it keeps the host decode.  ``out``
        is the only reference to the sorted words, so they are freed
        once the decode has read them."""
        res_l = DistributedSortResult(out.words, N, dtype)
        fp_l, verified = out.fingerprint, out.verified
        del out
        if plan is not None and not verified:
            # single-device runs have no distributed ladder: the rung
            # is the fused local path itself (the engine decision is
            # defaulted from the counters at _finish_plan time)
            plan.decide("ladder", chosen="local")
        if not (return_result or verified or codec.raw_bias is None):
            tracer.counters["decode_site"] = "device"
            with tracer.span("phase:decode"), tracer.span("egress.decode"):
                keys = decode_on_device(res_l.words, codec,
                                        _donation_enabled(), tracer)
            res_l = DistributedSortResult((), N, dtype, keys=keys)
        if verify_on and not verified and not _check_result(res_l, fp_l):
            raise SortIntegrityError(
                "single-device sort result failed verification")
        if return_result:
            return res_l
        with tracer.span("phase:decode"):
            return res_l.to_numpy(tracer=tracer)

    if n_ranks == 1:
        # one device: both algorithms degenerate to one local sort
        # (models/local.py).  The passthrough reaches only host input:
        # device-resident and staged inputs take no host profile, so the
        # scorer already chose `static` for them.
        return _finish_local(sort_one_device(
            x, codec, N, leng0, mesh.devices.flat[0], tracer, verify_on,
            passthrough=_passthrough if passthrough_on else None))

    #: per-word max^min already known without touching the data again
    #: (streamed ingest folds it chunk-by-chunk); None = plan from
    #: words_np or a device reduction as before.
    plan_diffs: tuple[int, ...] | None = None
    #: re-create the sharded input words after a *donated* dispatch
    #: consumed them (overflow retry / skew reroute); None disables
    #: donation for this input.
    rebuild_words = None
    #: input fingerprint folded by an in-sort streamed ingest (the
    #: device words may already carry an injected ingest fault, so the
    #: fingerprint must come from the HOST-side chunk folds).
    stream_fp = None

    if staged is not None:
        words = staged.words
        words_np = None
        plan_diffs = staged.word_diffs
        if staged.source is not None:
            rebuild_words = lambda: staged.rebuild().words  # noqa: E731
    if staged is None and is_device and _f64_known_broken(
            _mesh_platform(mesh), dtype, codec):
        x, is_device = _f64_host_input(x, tracer), False
    if staged is None and is_device:
        words_np = None

        def _device_encode_words():
            x_flat = x.reshape(-1)
            if N == n_ranks * n:
                # Land the input on the mesh first (no-op when already
                # sharded there); a committed single-device array would
                # otherwise conflict with the jit's mesh-wide
                # out_shardings.
                x_flat = checked_device_put(x_flat, key_sharding(mesh))
                return traced_call(
                    tracer, "encode_pad",
                    _compile_encode_pad(dtype.name, N, mesh), x_flat)
            # Uneven N cannot be mesh-sharded directly; encode+pad
            # wherever the input lives, then land the even result.
            ws = traced_call(
                tracer, "encode_pad",
                _compile_encode_pad(dtype.name, n_ranks * n, None),
                x_flat)
            return tuple(checked_device_put(w, key_sharding(mesh))
                         for w in ws)

        try:
            with tracer.span("phase:encode"):
                words = _device_encode_words()
            rebuild_words = _device_encode_words
        except jax.errors.JaxRuntimeError as e:
            # see the single-device branch: f64->u32 bitcast gap on some
            # TPU stacks — degrade to one documented host round-trip.
            # Memo key = the MESH's platform (the compile that failed),
            # not the input's.
            if not _is_f64_lowering_gap(e, dtype, codec, _mesh_platform(mesh)):
                raise
            x, is_device = _f64_host_input(x, tracer), False
    if staged is None and not is_device:
        flat = x.reshape(-1)
        if use_stream(flat.nbytes):
            # Streaming ingest (models/ingest.py): chunked parse/encode
            # overlapped with per-shard DMA, bounded host memory, and
            # the pass-planner diffs folded in flight — no second host
            # pass over the keys.
            with tracer.span("phase:ingest"):
                st = stream_to_mesh(flat, mesh, tracer=tracer)
            words = st.words
            words_np = None
            plan_diffs = st.word_diffs
            stream_fp = st.fingerprint
            rebuild_words = lambda: stream_to_mesh(  # noqa: E731
                flat, mesh, tracer=tracer).words
        else:
            with tracer.span("phase:encode"):
                words_np = codec.encode(flat)
                pad = _host_pad_words(codec, flat, dtype, n_ranks * n)

            with tracer.span("phase:device_put"):
                words = _shard_input(words_np, mesh, n, pad)
            rebuild_words = lambda: _shard_input(  # noqa: E731
                words_np, mesh, n, pad)

    pack_impl = _resolve_pack(pack)
    # cap alignment follows the FIRST rung's engine: the pallas engine's
    # fused pack needs CHUNK-aligned caps, and a CHUNK-aligned cap stays
    # valid (just 128-aligned too) if the ladder later degrades to lax.
    _, align = _engine_pack(pack_impl, eng0)
    if plan is not None:
        # the pack that will actually run: the pallas exchange engine
        # owns its fused pack regardless of the resolved pack impl
        plan.decide("engine", chosen=_engine_pack(pack_impl, eng0)[0])
        plan.decide("exchange_engine", chosen=eng0)
    # Donate the input word buffers to the SPMD program where the
    # backend profits (HBM aliasing) and the input can be rebuilt for
    # overflow retries (a donated buffer is dead after the dispatch).
    donate = _donation_enabled() and rebuild_words is not None
    if donate and staged is not None:
        # the first dispatch hands the staged buffers to XLA; flag the
        # object now so a reuse fails with a clear error instead of
        # dispatching on deleted arrays
        staged.consumed = True

    # ---- robustness layer (ISSUE 3): supervisor + input fingerprint --
    sup = SortSupervisor(tracer, registry=reg, plan=plan)
    input_fp = None
    if verify_on:
        with tracer.span("phase:verify"):
            if staged is not None:
                # folded chunk-by-chunk during streamed ingest — free
                input_fp = staged.fingerprint
            elif stream_fp is not None:
                input_fp = stream_fp  # in-sort streamed ingest, same fold
            elif words_np is not None:
                with tracer.span("verify.fingerprint"):
                    input_fp = vfy.fingerprint_host(words_np)
            else:
                # device-resident padded words: one tiny fused reduction
                input_fp = vfy.fingerprint_device(words, N, tracer)

    #: fair per-peer share of a shard — the ONE definition behind the
    #: sample cap bound, the skew-reroute cap, and every scale-out
    #: imbalance ratio (ISSUE 7).
    fair = max(1, -(-n // n_ranks))
    base_cap = _round_cap(int(n / n_ranks * cap_factor) + 1, align)
    # Radix cap for skew reroutes: duplication that degenerates splitters
    # also concentrates a radix pass's send runs, so start at the same
    # O(n)-per-device bound the sample path enforces instead of paying
    # overflow-retry recompiles to grow there.
    skew_cap = _round_cap(min(n, SAMPLE_CAP_LIMIT_FACTOR * fair), align)
    if oversample is None:
        oversample = max(2 * n_ranks - 1, 8)
    # Upper clamp: splitter quality saturates far below this, the
    # [P, oversample] sample gather replicates to every device, and
    # evenly_spaced_samples' int32 index math needs d^2 < 2^31.
    oversample = min(oversample, n, 16_384)

    # ---- scale-out layer (ISSUE 7): negotiation + skew re-stage -----
    negotiate = _negotiation_enabled(n_ranks)
    restage_on = _restage_enabled(n_ranks)
    restage_ratio = knobs.get("SORT_RESTAGE_RATIO")
    _restaged = {"done": False}

    # Live/dead tracking of the (possibly donated) input word buffers —
    # the ONE place that knows whether the next dispatch must re-stage.
    # Every dispatch of a donated program hands the words to XLA, so any
    # rerun (overflow regrow, transient retry, verification retry,
    # degradation rung) rebuilds through here.
    _wstate = {"words": words, "dead": False}

    def _interleave(ws: tuple) -> tuple:
        return traced_call(
            tracer, "interleave",
            _compile_interleave(mesh, codec.n_words, n), *ws)

    def live_words():
        if _wstate["dead"]:
            w = rebuild_words()
            if _restaged["done"]:
                # the run committed to the rebalanced arrangement; a
                # rebuild (donation retry / verify re-stage) must land
                # back on it, or the negotiated cap no longer fits
                w = _interleave(w)
            _wstate["words"] = w
            _wstate["dead"] = False
        return _wstate["words"]

    def do_restage() -> None:
        """Skew-aware re-stage: interleave the shards so per-peer
        exchange counts collapse toward the fair share (see
        _compile_interleave).  Idempotent — triggered proactively by
        the count probe or reactively by the supervisor's regrow loop,
        whichever detects the imbalance first."""
        if _restaged["done"]:
            return
        with tracer.spans.span("restage", ranks=n_ranks, n=n):
            _wstate["words"] = _interleave(live_words())
            _wstate["dead"] = False
        _restaged["done"] = True
        tracer.count("skew_restage", 1)
        tracer.verbose(
            "skew re-stage: interleaved shards to rebalance the exchange")

    def mark_dead():
        if donate:
            _wstate["dead"] = True

    def force_restage():
        """After a verification failure the staged words themselves are
        suspect (e.g. an ingest fault corrupted them after the
        fingerprint fold) — re-stage from the source even when donation
        is off, so the retry runs on freshly ingested data."""
        if rebuild_words is not None:
            _wstate["dead"] = True

    _plan: dict = {}

    def radix_plan():
        if not _plan:
            with tracer.span("phase:plan"):
                if plan_diffs is not None:
                    # Streamed ingest already folded per-word max^min
                    # chunk-by-chunk — planning is free.
                    diffs = plan_diffs
                elif words_np is None:
                    # Device-resident input: one scalar min/max sync per
                    # word plans the pass count (pads replicate the max
                    # key — range unchanged).
                    ranges = _compile_word_range(dtype.name)(x.reshape(-1))
                    diffs = tuple(int(lo) ^ int(hi) for lo, hi in ranges)
                else:
                    diffs = _word_diffs(words_np)
                db = digit_bits if digit_bits is not None \
                    else _auto_digit_bits(diffs)
                _plan["p"] = (db, _passes_from_diffs(diffs, db))
        return _plan["p"]

    def _balance_event(cnts: np.ndarray, algo_label: str, exact: bool,
                       negotiated: int, restaged: bool) -> None:
        """Fold a measured [P, P] count matrix into telemetry: the
        ``exchange_balance`` event (per-rank send/recv byte lists + the
        ratios) and the counters the bench/report scale-out tables
        read.  ``recv`` imbalance is the classic per-rank exchange-byte
        skew (radix is 1.0 by construction — destination blocks are
        n-sized); ``peer_ratio`` (max single-peer segment over the fair
        share) is what actually drives the capacity."""
        wpb = 4 * codec.n_words
        send = cnts.sum(axis=1) * wpb
        recv = cnts.sum(axis=0) * wpb
        rmean = float(recv.mean())
        recv_ratio = float(recv.max()) / rmean if rmean > 0 else 1.0
        peer_ratio = float(cnts.max()) / fair
        tracer.spans.event(
            "exchange_balance", algorithm=algo_label, ranks=n_ranks,
            exact=exact, peer_max=int(cnts.max()), fair=fair,
            negotiated_cap=negotiated, worst_cap=n,
            send_bytes=[int(v) for v in send],
            recv_bytes=[int(v) for v in recv],
            recv_ratio=round(recv_ratio, 4),
            peer_ratio=round(peer_ratio, 4), restaged=restaged,
            exchange_engine=_eng["v"])
        tracer.counters["negotiated_cap"] = negotiated
        tracer.counters["worst_cap"] = n
        tracer.counters["exchange_balance_ratio"] = round(recv_ratio, 4)
        tracer.counters["exchange_peer_ratio"] = round(peer_ratio, 4)

    def _probe(kind: str, db: int | None = None) -> np.ndarray:
        fn = (_compile_radix_probe(mesh, codec.n_words, n, db)
              if kind == "radix" else
              _compile_sample_probe(mesh, codec.n_words, n, oversample))
        with tracer.span("phase:plan"):
            return np.asarray(traced_call(
                tracer, f"{kind}_probe", fn, *live_words()))

    def _negotiate(kind: str, db: int | None = None) -> np.ndarray:
        """Run the count probe; re-stage once (and re-probe) when the
        measured per-peer imbalance crosses the re-stage ratio.  Returns
        the count matrix describing the arrangement the sort will
        actually exchange."""
        cnts = _probe(kind, db)
        if plan is not None:
            # the probe's [P, P] histogram is already materialized —
            # the input-distribution profile rides it for free
            plan.profile.update(plan_mod.profile_from_counts(cnts, fair))
        ratio = float(cnts.max()) / fair
        if (restage_on and not _restaged["done"]
                and ratio >= restage_ratio):
            tracer.verbose(
                f"{kind} probe: per-peer need {int(cnts.max())} >= "
                f"{restage_ratio:g}x fair share {fair}; re-staging")
            if plan is not None:
                plan.decide("restage", chosen=True, trigger="probe",
                            peer_ratio=round(ratio, 4))
            do_restage()
            cnts = _probe(kind, db)
            if plan is not None:
                plan.actual("restage",
                            peer_ratio=round(float(cnts.max()) / fair, 4))
        return cnts

    def run_radix(cap0: int) -> DistributedSortResult:
        db, passes = radix_plan()
        eng = _eng["v"]
        eff_pack, eff_align = _engine_pack(pack_impl, eng)
        tracer.counters["exchange_engine"] = eng
        # the digit passes sort with lax.sort: bitonic has no slot there
        tracer.counters["local_engine"] = "lax"
        if plan is not None:
            # keep a planner-predicted pass count (radix_compact) as
            # the prediction this decision is scored against; the
            # chosen/ran side comes from the real plan below.
            d_passes = plan.decisions.get("passes")
            keep = (d_passes is not None
                    and "passes" in d_passes.predicted)
            plan.decide("passes", chosen=passes, digit_bits=db,
                        **({} if keep else {"passes": passes}))
        if negotiate and passes > 0:
            cnts = _negotiate("radix", db)
            need = _round_cap(int(cnts.max()), eff_align)
            # pass 1's need is EXACT; later passes depend on the post-
            # exchange arrangement, so multi-pass runs keep the
            # cap_factor floor and the regrow loop as backstop instead
            # of risking a full re-run to undercut it.
            cap0 = need if passes == 1 else max(need, cap0)
            if plan is not None:
                plan.decide("cap", chosen=cap0, trigger="exact",
                            cap=cap0, need=int(cnts.max()), fair=fair)
            _balance_event(cnts, "radix", True, cap0, _restaged["done"])
        elif plan is not None:
            plan.decide("cap", chosen=cap0, trigger="off", cap=cap0,
                        fair=fair)
        last_need = {"v": None}

        def attempt(c: int):
            fn = _compile_radix(mesh, codec.n_words, n, db, c, passes,
                                eff_pack, donate, sup.arm_exchange(),
                                exchange_engine=eng)
            with tracer.span("phase:sort"):
                out, max_cnt = sup.dispatch(
                    "radix_spmd", fn, live_words, on_retry=mark_dead,
                    n=n, cap=c, passes=passes, digit_bits=db, ranks=n_ranks)
                mark_dead()
                max_cnt = int(max_cnt)
            last_need["v"] = max_cnt
            # Exchange accounting (SURVEY.md §5 metrics row), counted per
            # attempt so discarded overflow retries — whose all_to_all
            # traffic really crossed the links — are included: the padded
            # exchange ships full [P, cap] word blocks; wire bytes
            # exclude the self-block, which never leaves the device.
            tracer.count(
                "exchange_bytes",
                passes * n_ranks * (n_ranks - 1) * c * 4 * codec.n_words,
            )
            return out, max_cnt

        out, cap = sup.exchange_loop(
            "radix", attempt, sup.squeeze_cap(cap0, eff_align), eff_align,
            _round_cap, on_overflow=mark_dead,
            re_stage=do_restage if restage_on else None)
        tracer.count("exchange_passes", passes)
        tracer.counters["exchange_cap"] = cap  # last cap, not accumulated
        tracer.counters["digit_bits"] = db     # auto-resolved width
        if plan is not None:
            # actual side of the cap decision: the measured per-peer
            # need and its wire-byte size (vs the probe's prediction)
            plan.actual("cap", cap=cap, need=last_need["v"],
                        peer_recv_bytes=(last_need["v"] or 0)
                        * 4 * codec.n_words)
            plan.actual("passes", passes=passes)
        return DistributedSortResult(out, N, dtype)

    def run_sample() -> DistributedSortResult:
        eng = _eng["v"]
        eff_pack, eff_align = _engine_pack(pack_impl, eng)
        tracer.counters["exchange_engine"] = eng
        if words_np is not None:
            degenerate = _sample_skew_sniff(words_np, n_ranks)
        else:
            # Device-resident input: same sniff on the mesh — a tiny
            # strided sample + quantile check, one scalar sync.  Without
            # it, skewed device inputs would only discover degeneracy via
            # a failed exchange round + recompile (VERDICT r2 #4).
            degenerate = bool(
                _compile_skew_sniff(mesh, codec.n_words, N, n_ranks)(
                    *live_words())
            )
        if degenerate:
            tracer.verbose(
                "sample: quantile splitters degenerate (heavy duplication); "
                "routing to radix (skew-immune)"
            )
            tracer.count("sample_skew_fallback", 1)
            if plan is not None:
                plan.decide("algo", chosen="radix", trigger="skew_sniff")
            return run_radix(skew_cap)
        cap_limit = _round_cap(SAMPLE_CAP_LIMIT_FACTOR * fair, eff_align)
        cap_start = base_cap
        if negotiate:
            cnts = _negotiate("sample")
            # the sample probe is an ESTIMATE (sampled splitters) —
            # margin on top, and the regrow loop stays as backstop.
            # neg_margin is SAMPLE_NEG_MARGIN unless the planner is ON
            # and learned a tighter one from the flight ring's observed
            # estimate-error quantiles (ISSUE 14 cap/margin policy).
            need = _round_cap(
                int(float(cnts.max()) * neg_margin) + 1, eff_align)
            if need > cap_limit:
                # the estimate already busts the O(n) recv bound: route
                # to radix NOW instead of paying a doomed full exchange
                # to find out (the reactive ExchangeCapExceeded path
                # below stays for what the estimate misses)
                tracer.verbose(
                    f"sample probe estimates cap {need} > O(n) bound "
                    f"{cap_limit}; routing to radix (skew-immune)")
                tracer.count("sample_skew_fallback", 1)
                if plan is not None:
                    plan.decide("algo", chosen="radix",
                                trigger="probe_estimate")
                return run_radix(skew_cap)
            cap_start = need
            if plan is not None:
                plan.decide("cap", chosen=cap_start, trigger="estimate",
                            cap=cap_start, need=int(cnts.max()), fair=fair,
                            margin=round(neg_margin, 4))
            _balance_event(cnts, "sample", False, cap_start,
                           _restaged["done"])
        elif plan is not None:
            plan.decide("cap", chosen=cap_start, trigger="off",
                        cap=cap_start, fair=fair)
        spmd_engine = _resolve_local_engine(leng0, codec.n_words, n)
        tracer.counters["local_engine"] = spmd_engine

        last_need = {"v": None}

        def attempt(c: int):
            fn = _compile_sample(mesh, codec.n_words, n, c, oversample,
                                 eff_pack, spmd_engine, donate,
                                 sup.arm_exchange(),
                                 exchange_engine=eng)
            with tracer.span("phase:sort"):
                out, counts, max_cnt = sup.dispatch(
                    "sample_spmd", fn, live_words, on_retry=mark_dead,
                    n=n, cap=c, ranks=n_ranks)
                mark_dead()
                max_cnt = int(max_cnt)
            last_need["v"] = max_cnt
            tracer.count(
                "exchange_bytes",
                n_ranks * (n_ranks - 1) * c * 4 * codec.n_words,
            )
            return (out, counts), max_cnt

        try:
            (out, counts), cap = sup.exchange_loop(
                "sample", attempt, sup.squeeze_cap(cap_start, eff_align),
                eff_align, _round_cap, cap_limit=cap_limit,
                on_overflow=mark_dead,
                re_stage=do_restage if restage_on else None)
        except ExchangeCapExceeded as e:
            tracer.verbose(
                f"sample exchange needs cap {e.need} > O(n) bound "
                f"{e.limit}; routing to radix (skew-immune)"
            )
            tracer.count("sample_skew_fallback", 1)
            if plan is not None:
                # the LATE reroute: a full exchange ran and busted the
                # bound before the switch — the regret the up-front
                # sniff/probe reroutes exist to avoid
                plan.decide("algo", chosen="radix", trigger="cap_exceeded")
                plan.actual("algo", late_reroute=True)
            return run_radix(skew_cap)
        tracer.count("exchange_passes", 1)
        tracer.counters["exchange_cap"] = cap
        if plan is not None:
            plan.actual("cap", cap=cap, need=last_need["v"],
                        peer_recv_bytes=(last_need["v"] or 0)
                        * 4 * codec.n_words)
        return DistributedSortResult(
            out, N, dtype, counts=np.asarray(counts),
            shard_slots=n_ranks * cap
        )

    def run_host() -> tuple:
        """Last degradation rung: host lexsort over the encoded words —
        no device dispatch at all, so it survives a dead backend.  The
        result is fingerprint-verified on the host before anyone sees
        it."""
        tracer.verbose("graceful degradation: host lexsort fallback")
        if staged is not None:
            if staged.source is None:
                raise SortIntegrityError(
                    "host fallback impossible: StagedIngest kept no source")
            arr = np.asarray(staged.source).reshape(-1)
        else:
            arr = np.asarray(x).reshape(-1)
        with tracer.span("phase:sort"):
            w = codec.encode(arr)
            # np.lexsort: last key is primary -> feed words lsw-first
            order = np.lexsort(tuple(reversed(w)))
            sorted_w = tuple(wi[order] for wi in w)
        if verify_on and input_fp is not None:
            with tracer.span("phase:verify"), \
                    tracer.span("verify.fingerprint"):
                out_fp = vfy.fingerprint_host(sorted_w)
            tracer.count("verify_runs", 1)
            if out_fp != input_fp:
                raise SortIntegrityError(
                    "host fallback result failed fingerprint verification "
                    "(input changed between ingest and fallback?)")
        return sorted_w

    # ---- degradation ladder: pallas exchange engine -> lax engine
    # (same algorithm, ISSUE 13), then requested algorithm -> the other
    # one -> host lexsort.  Each rung gets one verification retry (a
    # transient corruption re-dispatches clean); persistent dispatch
    # failure or repeated verification failure moves down.  The ladder
    # ends in a VERIFIED result or a typed error — never a silent wrong
    # answer.
    rungs: list[tuple[str, str]] = [(algorithm, eng0)]
    if supervision.fallback_enabled():
        if eng0 != "lax":
            # the engine rung: a broken pallas kernel must not cost the
            # requested ALGORITHM — re-run it on the XLA collective
            rungs.append((algorithm, "lax"))
        rungs.append(("sample" if algorithm == "radix" else "radix", "lax"))
        rungs.append(("host", "lax"))
    if plan is not None:
        plan.decide("ladder", chosen=rungs[0][0])

    res = None
    host_words = None
    last_err: Exception | None = None
    #: why the previous rung ended: "dispatch" (supervised dispatch /
    #: device error) vs "verify" (fingerprint/sortedness failures) —
    #: the engine-degrade decision must blame the ACTUAL cause, not
    #: stamp every descent off a pallas rung as a kernel fault.
    last_fail = "dispatch"
    level = rungs[0][0]

    # ---- planner rung zero (ISSUE 14): verify-passthrough -----------
    # The profile's strided sample read fully sorted, so the staged
    # input words ARE a sort candidate: one O(n) verify dispatch (the
    # same always-on gate every ladder rung faces) replaces the whole
    # sort when it passes.  A miss — the sample hid a descent — costs
    # exactly the verify pass (the planner decision's regret) and the
    # ordinary ladder below sorts for real.  Only in `on` mode and only
    # with the verifier armed: without it the profile is a guess, and a
    # guess must not skip the sort.
    if passthrough_on and input_fp is not None:
        cand_words = live_words()
        if _passthrough(cand_words, input_fp):
            res = DistributedSortResult(cand_words, N, dtype)

    for level, rung_eng in (() if res is not None else rungs):
        if rung_eng != _eng["v"]:
            tracer.verbose(
                f"degrading exchange engine {_eng['v']} -> {rung_eng}")
            tracer.count("exchange_engine_degraded", 1)
            _eng["v"] = rung_eng
            if plan is not None:
                plan.decide(
                    "exchange_engine", chosen=rung_eng,
                    trigger=("pallas_fault" if last_fail == "dispatch"
                             else "verify_failure"))
        if level != rungs[0][0]:
            tracer.verbose(f"degrading to the {level} path")
            if plan is not None:
                plan.decide("ladder", chosen=level)
                plan.bump("ladder", "rungs_descended")
        done = False
        for ver_try in range(2 if verify_on else 1):
            try:
                if level == "host":
                    host_words = run_host()
                    done = True
                    break
                cand = run_sample() if level == "sample" else \
                    run_radix(base_cap)
                cand = faults.maybe_corrupt_result(reg, cand)
                ok = not verify_on or _check_result(cand, input_fp)
            except SortRetryExhausted as e:
                last_err = e
                last_fail = "dispatch"
                tracer.verbose(f"{level} path failed persistently: {e}")
                break
            except jax.errors.JaxRuntimeError as e:
                # A dead device can also surface OUTSIDE the supervised
                # sort dispatch — the skew sniff, the pass-planner
                # reduction, the verifier program.  The ladder exists
                # for exactly this: degrade instead of leaking an
                # untyped error past the typed-error contract (the host
                # rung needs no device at all).
                last_err = SortRetryExhausted(
                    f"{level} path failed outside the sort dispatch: "
                    f"{e}")
                last_err.__cause__ = e
                last_fail = "dispatch"
                tracer.count("sort_retries", 1)
                tracer.verbose(f"{level} path device failure: "
                               f"{type(e).__name__}; degrading")
                break
            if ok:
                res = cand
                done = True
                break
            tracer.count("verify_failures", 1)
            last_fail = "verify"
            force_restage()  # the input words themselves are suspect
        if done:
            break
    if res is None and host_words is None:
        if last_err is not None:
            raise last_err
        raise SortIntegrityError(
            "no sort path produced a verified result (verify_failures="
            f"{int(tracer.counters.get('verify_failures', 0))})")

    if host_words is not None:
        tracer.counters["degraded_to"] = "host"
        out_np = codec.decode(host_words)
        if not return_result:
            return out_np
        # best-effort re-stage of the host-sorted words onto the mesh
        # (already globally sorted; pads = max copies keep the contract).
        # If the device is GENUINELY dead — the scenario this rung
        # survives — the re-stage fails too: return a host-backed result
        # instead of leaking an untyped JaxRuntimeError past the typed-
        # error contract (DistributedSortResult's decode/probe paths are
        # plain array ops, so numpy words work throughout).
        try:
            pad = _host_pad_words(codec, out_np, dtype, n_ranks * n)
            return DistributedSortResult(
                _shard_input(host_words, mesh, n, pad), N, dtype)
        except jax.errors.JaxRuntimeError:
            tracer.verbose("device unavailable for re-staging the host "
                           "fallback result; returning host-backed words")
            return DistributedSortResult(host_words, N, dtype)

    if level != rungs[0][0]:
        tracer.counters["degraded_to"] = level
    if return_result:
        return res
    with tracer.span("phase:decode"):
        out_np = res.to_numpy(tracer=tracer)
    return out_np
