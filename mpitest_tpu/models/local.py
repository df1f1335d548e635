"""One-device sort: the routes a call takes when the mesh holds one device.

Both distributed algorithms degenerate to one local sort on a one-device
mesh: no exchange, no splitters, no digit passes.  :func:`route` picks
one of the routes (:data:`RouteName`) from what the call can observe —
where the keys are (host array, device array or :class:`StagedIngest`),
the codec, ``N``, the local-engine knob and the backend — and
:func:`sort_one_device` runs that route's body:

* ``staged`` — the streamed ingest's words go to :func:`_compile_local`.
* ``device_pair`` — device-resident 2-word keys on the pair engine:
  encode, plan and sort in one program (:func:`_compile_pair_fused`).
* ``device`` — other device-resident keys: encode and sort in one
  program (:func:`_compile_local_device`).
* ``raw`` — host keys whose codec is a constant XOR (``raw_bias``): the
  keys ship as their own bytes and encode on the device, while the host
  folds their fingerprint from ``x`` (never the device copy, so the fold
  covers the transfer).  Two-word keys are split and planned on the
  device (:func:`_compile_pair_split`).
* ``host`` — host encode, fold and put: floats, narrow ints, the
  planner's passthrough, and 2-word keys off the pair engine.

Both device routes degrade to ``host`` on the known float64 lowering gap.
For 2-word keys on the pair engine, ``raw`` and ``host`` share one
plan → engine step (:func:`_pair_by_plan`).  The caller
(``models/api.py``) verifies the returned words and decodes them; for
the constant-XOR codecs the decode runs on the device
(:func:`decode_on_device`) and the verifier reads the decoded keys.
"""

from __future__ import annotations

import contextlib
from functools import lru_cache
from typing import TYPE_CHECKING, Any, Callable, Literal, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from mpitest_tpu.models import verify as vfy
from mpitest_tpu.models.ingest import StagedIngest, checked_device_put
from mpitest_tpu.ops import bitonic, kernels
from mpitest_tpu.ops.keys import KeyCodec, codec_for
from mpitest_tpu.utils import knobs
from mpitest_tpu.utils.programs import layer, program, traced_call

if TYPE_CHECKING:
    from mpitest_tpu.utils.trace import Tracer

Words = tuple[jax.Array, ...]

#: Where the keys of a call are: a host array, a device array, or the
#: streamed ingest's staged words.
Site = Literal["host", "device", "staged"]

#: The routes of a one-device sort (see the module docstring).
RouteName = Literal["staged", "device_pair", "device", "raw", "host"]


# ----------------------------------------------------- local-engine rules

def _local_engine() -> str:
    """Local (single-device) sort engine: the Pallas bitonic kernel
    (``ops/bitonic.py``) on real TPU backends for large one-word keys —
    measured 2.0-4.2x ``lax.sort`` at 2^26 on v5e post-relayout (r5) —
    ``lax.sort`` otherwise.  ``SORT_LOCAL_ENGINE={auto,bitonic,lax}``
    overrides."""
    return knobs.get("SORT_LOCAL_ENGINE")


def _use_bitonic(engine: str, n_words: int, n: int) -> bool:
    if n_words > 2:
        return False  # wider keys keep the variadic lax.sort
    if engine == "bitonic":
        return True
    return engine == "auto" and jax.default_backend() == "tpu" and (
        n >= (1 << bitonic.MIN_SORT_LOG2)
    )


def _bitonic_impl() -> str:
    """Execution form of the bitonic engine: real Mosaic kernels on TPU
    backends, the Pallas interpreter elsewhere (CPU-mesh tests / forced
    ``SORT_LOCAL_ENGINE=bitonic`` without a TPU)."""
    return "bitonic" if jax.default_backend() == "tpu" else "bitonic_interpret"


def _resolve_local_engine(engine: str, n_words: int, n: int) -> str:
    """Concrete local-sort engine for one dispatch: the bitonic family
    where its rules take the dispatch, else ``lax``."""
    return _bitonic_impl() if _use_bitonic(engine, n_words, n) else "lax"


# ------------------------------------------------- the float64 lowering gap

#: Memo: platforms whose device-side float64 encode failed to lower
#: (XLA x64-rewrite gap, see sort() docstring) — later calls on the SAME
#: platform route f64 device input straight to the host fallback instead
#: of re-attempting a doomed (and slow) XLA compile every time.  Keyed
#: per platform so one broken backend never degrades another (e.g. a CPU
#: mesh in the same process, whose lowering is fine).
_f64_encode_broken_platforms: set[str] = set()

#: Error-text markers of the known f64 lowering gap ("While rewriting
#: computation to not contain X64 element types ... %bitcast-convert").
#: Anything else (OOM, preemption) must re-raise, not masquerade as it.
_F64_GAP_MARKERS = ("bitcast-convert", "X64 element types")


def _device_platform(x: jax.Array) -> str:
    """Platform string of the device(s) holding ``x`` — the memo key for
    the single-device path, whose encode compiles where ``x`` lives."""
    try:
        return next(iter(x.devices())).platform
    except Exception:
        return jax.default_backend()


def _f64_gap_applies(dtype: np.dtype, codec: KeyCodec) -> bool:
    return dtype.kind == "f" and codec.n_words == 2


def _is_f64_lowering_gap(e: Exception, dtype: np.dtype, codec: KeyCodec,
                         platform: str) -> bool:
    """True iff ``e`` is the known f64 device-encode lowering gap for a
    2-word float dtype; memoizes the verdict for later calls on the same
    platform.  The markers are fragments of ONE message and must all be
    present — a different x64-rewrite failure or an unrelated bitcast
    error is not this gap and must re-raise."""
    if not _f64_gap_applies(dtype, codec):
        return False
    msg = str(e)
    if not all(m in msg for m in _F64_GAP_MARKERS):
        return False
    _f64_encode_broken_platforms.add(platform)
    return True


def _f64_known_broken(platform: str, dtype: np.dtype,
                      codec: KeyCodec) -> bool:
    """Memoized verdict: ``platform`` already tripped the f64 gap."""
    return (_f64_gap_applies(dtype, codec)
            and platform in _f64_encode_broken_platforms)


def _f64_host_input(x: jax.Array, tracer: Tracer) -> np.ndarray:
    """Engage the documented f64 host fallback: tracer breadcrumbs plus
    the host copy of the device array."""
    tracer.verbose(
        "device-side float64 encode unsupported by this backend; "
        "falling back to one host round-trip"
    )
    tracer.count("f64_host_fallback", 1)
    return np.asarray(x)


# -------------------------------------------------------------- programs

def _sniff_indices(n: int) -> np.ndarray:
    """The hi-duplication sniff's sample: ~1024 evenly spaced keys."""
    return np.linspace(0, n - 1, min(1024, n)).astype(np.int64)


def _host_hi_dup_sniff(hi: np.ndarray) -> int:
    """Host twin of the hi-duplication sniff (same ~1024-key sample):
    the number of equal adjacent pairs in the sorted sample."""
    samp = np.sort(hi[_sniff_indices(hi.size)])
    return int(np.count_nonzero(samp[1:] == samp[:-1]))


@lru_cache(maxsize=16)
def _compile_local(n_words: int, engine: str = "auto") -> Callable[..., Any]:
    """One fused local sort of encoded words: the Pallas bitonic engine
    for large 1-word keys on TPU, else ``lax.sort``.  The reference run
    with ``-np 1`` still pays its full protocol; here the program
    specializes to what the hardware actually needs."""
    def f(*words):
        eng = _resolve_local_engine(engine, len(words), words[0].size)
        return kernels.local_sort(words, engine=eng)

    return jax.jit(program("local_sort", "local_sort", f))


@lru_cache(maxsize=8)
def _compile_local_device(dtype_name: str,
                          engine: str = "auto") -> Callable[..., Any]:
    """1-device program for device-resident input: fused encode + sort."""
    codec = codec_for(np.dtype(dtype_name))

    def f(x):
        with layer("ingest"):
            words = codec.encode_jax(x)
        eng = _resolve_local_engine(engine, len(words), x.size)
        return kernels.local_sort(words, engine=eng)

    return jax.jit(program("local_device_sort", "local_sort", f))


@lru_cache(maxsize=8)
def _compile_decode(dtype_name: str, donate: bool) -> Callable[..., Any]:
    """The caller's keys from the sorted words
    (:meth:`KeyCodec.decode_jax`), in a program of its own: the
    verifier re-encodes the keys in another, so it checks the bytes the
    fetch hands back (inside one program XLA would fold
    ``encode(decode(w))`` back to ``w``).  ``donate``: 1-word keys take
    the words' buffer, so the device peak does not grow."""
    codec = codec_for(np.dtype(dtype_name))

    def f(*words):
        return codec.decode_jax(words)

    return jax.jit(program("decode", "egress", f),
                   donate_argnums=(0,) if donate and codec.n_words == 1
                   else ())


def decode_on_device(words: Words, codec: KeyCodec, donate: bool,
                     tracer: Tracer) -> jax.Array:
    """Dispatch the device decode of the sorted ``words`` (a
    constant-XOR codec's) into the caller's keys, or rows of the native
    bytes of 64-bit keys.  ``donate`` gives 1-word words to the
    program, which frees them."""
    return traced_call(tracer, "decode",
                       _compile_decode(codec.dtype.name, donate), *words)


@lru_cache(maxsize=4)
def _compile_pair_sort(impl: str) -> Callable[..., Any]:
    """The pair engine on 2-word keys: the sorted words and int32
    ``[residual, hi ties]``, so one readback fetches both."""
    interpret = impl == "bitonic_interpret"

    def f(hi, lo):
        hs, ls, bad, ties = kernels.sort_two_words_bitonic(
            hi, lo, interpret=interpret)
        return hs, ls, jnp.stack([bad.astype(jnp.int32), ties])

    return jax.jit(program("pair_sort", "local_sort", f))


@lru_cache(maxsize=4)
def _compile_pair_split(dtype_name: str) -> Callable[..., Any]:
    """Word split and plan of raw 64-bit integer keys shipped as their
    own bytes (traced and called under a scoped ``jax.enable_x64``):
    the ``(hi, lo)`` uint32 words, ``[min hi, max hi, min lo, max lo]``
    and the hi-word sniff count over :func:`_sniff_indices`' sample (a
    trace-time constant), so the plan is the host plan's exactly.  The
    shift-and-convert split lowers to the TPU's own 64-bit halves (a
    ``bitcast_convert_type`` to uint32 interleaves them first)."""
    bias = codec_for(np.dtype(dtype_name)).raw_bias

    def f(x):
        u = jax.lax.bitcast_convert_type(x, jnp.uint64)
        hi = (u >> jnp.uint64(32)).astype(jnp.uint32) ^ jnp.uint32(bias[0])
        lo = u.astype(jnp.uint32) ^ jnp.uint32(bias[1])
        # one device holds < 2^31 keys: int32 indices suffice
        samp = jnp.sort(hi[_sniff_indices(hi.shape[0]).astype(np.int32)])
        dups = jnp.sum(samp[1:] == samp[:-1], dtype=jnp.int32)
        rng = jnp.stack([jnp.min(hi), jnp.max(hi), jnp.min(lo), jnp.max(lo)])
        return hi, lo, rng, dups

    return jax.jit(program("pair_split", "ingest", f))


#: Engine codes returned by the fused device program (scalar, one fetch).
_PAIR_CODES = {0: "constant", 1: "bitonic_1w1", 2: "bitonic_1w0",
               3: "lax", 4: "bitonic_pair", 5: "bitonic_pair+lax_fallback"}


@lru_cache(maxsize=8)
def _compile_pair_fused(dtype_name: str,
                        impl: str) -> Callable[..., Any]:
    """ONE-dispatch device program for 2-word device-resident local
    sorts: encode + range/dup planning + a ``lax.cond`` tree selecting
    constant-word 1-word engine / variadic ``lax.sort`` / pair engine
    (with its residual fallback folded in as a nested cond) — every
    branch returns the same shapes, so the whole adaptive decision runs
    on device.  Rationale: each extra dispatch pays a fixed host round
    trip; on the remote chip of rounds 1-5 that was ~0.15-0.2 s, larger
    than the pair engine's entire kernel-level win at 2^27 — the
    host-orchestrated version measured SLOWER end-to-end than the
    single-jit lax path despite a 1.4x faster device sort."""
    from jax import lax as jlax

    codec = codec_for(np.dtype(dtype_name))
    interpret = impl == "bitonic_interpret"

    def lax2w(hi, lo):
        out = jlax.sort([hi, lo], num_keys=2, is_stable=False)
        return out[0], out[1]

    def one_w(w):
        return kernels.local_sort((w,), engine=impl)[0]

    def f(x):
        with layer("ingest"):
            hi, lo = codec.encode_jax(x.reshape(-1))
        d0 = jnp.min(hi) ^ jnp.max(hi)
        d1 = jnp.min(lo) ^ jnp.max(lo)
        n = hi.shape[0]
        s = min(1024, n)
        if s > 1:
            stride = -(-(n - 1) // (s - 1))  # ceil: sample stays <= s picks
            s_eff = (n - 1) // stride + 1
            start = (n - 1) - (s_eff - 1) * stride
            samp = jlax.sort(
                [jlax.slice(hi, (start,),
                            (start + (s_eff - 1) * stride + 1,), (stride,))],
                num_keys=1, is_stable=False)[0]
            dup = jnp.any(samp[1:] == samp[:-1])
        else:
            dup = jnp.zeros((), bool)

        def b_both(h, l):   # both words constant: already sorted
            return h, l, jnp.int32(0)

        def b_hic(h, l):    # hi constant: 1-word engine on lo
            return h, one_w(l), jnp.int32(1)

        def b_loc(h, l):    # lo constant: 1-word engine on hi
            return one_w(h), l, jnp.int32(2)

        def b_lax(h, l):    # sniffed hi duplication: straight to lax
            hs, ls = lax2w(h, l)
            return hs, ls, jnp.int32(3)

        def b_pair(h, l):
            hs, ls, bad, _ = kernels.sort_two_words_bitonic(
                h, l, interpret=interpret)
            hs, ls = jlax.cond(bad, lax2w, lambda a, b: (hs, ls), h, l)
            return hs, ls, jnp.where(bad, jnp.int32(5), jnp.int32(4))

        def b_var(h, l):    # both words vary: sniff decides
            return jlax.cond(dup, b_lax, b_pair, h, l)

        return jlax.cond(
            d0 == jnp.uint32(0),
            lambda a, b: jlax.cond(d1 == jnp.uint32(0), b_both, b_hic, a, b),
            lambda a, b: jlax.cond(d1 == jnp.uint32(0), b_loc, b_var, a, b),
            hi, lo)

    return jax.jit(program("pair_fused", "local_sort", f))


# ---------------------------------------------------------------- routes

class Route(NamedTuple):
    """One route; ``pair`` marks 2-word keys planned for the pair
    engine."""

    name: RouteName
    pair: bool


class LocalSort(NamedTuple):
    """The sorted device words and the input fingerprint (None when
    verification is off).  ``verified``: the planner's passthrough
    accepted the words as they came, so no sort ran and the caller must
    not verify again."""

    words: Words
    fingerprint: vfy.Fingerprint | None
    verified: bool = False


#: The planner's passthrough check: ``(words, fingerprint) -> accepted``.
Passthrough = Callable[[Words, "vfy.Fingerprint | None"], bool]


def route(site: Site, codec: KeyCodec, n: int, engine: str, backend: str,
          platform: str | None = None, passthrough: bool = False) -> Route:
    """The route of a one-device sort of ``n`` keys at ``site`` under the
    engine knob value ``engine`` and the default ``backend``.
    ``platform`` is where device-resident keys live (the float64 gap
    memo's key); ``passthrough`` says the planner asked to check the
    encoded input words before sorting them.

    The pair engine takes 2-word keys of at least ``2^MIN_SORT_LOG2``
    whenever the engine is forced to ``bitonic``, and under ``auto`` on
    TPU backends.  Raw ingest takes the constant-XOR codecs: 2-word
    keys on the pair engine, 1-word keys unless the planner needs the
    host words."""
    pair = (codec.n_words == 2 and engine != "lax"
            and n >= (1 << bitonic.MIN_SORT_LOG2)
            and (engine == "bitonic" or backend == "tpu"))
    if site == "staged":
        return Route("staged", False)
    if site == "device" and not _f64_known_broken(platform, codec.dtype,
                                                  codec):
        return Route("device_pair" if pair else "device", pair)
    raw = codec.raw_bias is not None and (
        pair or (codec.n_words == 1 and not passthrough))
    return Route("raw" if raw else "host", pair)


def sort_one_device(x: Any, codec: KeyCodec, n: int, engine: str,
                    device: Any, tracer: Tracer, verify_on: bool,
                    passthrough: Passthrough | None = None) -> LocalSort:
    """Sort the ``n`` keys of ``x`` (a host array, a device array or a
    :class:`StagedIngest`) on ``device`` under the engine knob value
    ``engine`` (:func:`_local_engine`); returns the sorted words and the
    input fingerprint.  ``passthrough`` is the planner's check of the
    encoded host words: when it accepts them, they are returned unsorted
    and marked verified."""
    site: Site = ("staged" if isinstance(x, StagedIngest)
                  else "device" if isinstance(x, jax.Array) else "host")
    r = route(site, codec, n, engine, jax.default_backend(),
              platform=_device_platform(x) if site == "device" else None,
              passthrough=passthrough is not None)
    if not r.pair:
        # staged words are padded: the program resolves at their size
        tracer.counters["local_engine"] = _resolve_local_engine(
            engine, codec.n_words, x.words[0].size if site == "staged" else n)
    if r.name == "staged":
        tracer.counters["encode_site"] = "host"
        with tracer.span("phase:sort"):
            out = traced_call(tracer, "local",
                              _compile_local(codec.n_words, engine), *x.words)
        return LocalSort(out, x.fingerprint if verify_on else None)
    if r.name in ("device", "device_pair"):
        done = _device(x, codec, engine, r.pair, tracer, verify_on)
        if done is not None:
            return done
    if site == "device":  # the float64 gap, met now or memoized
        x = _f64_host_input(x, tracer)
    if r.name == "raw":
        return _raw(x, codec, engine, r.pair, device, tracer, verify_on)
    return _host(x, codec, engine, r.pair, device, tracer, verify_on,
                 passthrough)


def _device_input_fp(x: jax.Array, dtype: np.dtype,
                     tracer: Tracer) -> vfy.Fingerprint | None:
    """Input fingerprint for device-resident single-device input:
    one tiny fused encode+reduce dispatch.  The known f64 encode
    lowering gap degrades to sortedness-only verification (fp None)
    rather than breaking the sort."""
    try:
        return vfy.fingerprint_device_input(x.reshape(-1), dtype, tracer)
    except jax.errors.JaxRuntimeError:
        tracer.verbose("input fingerprint unavailable on this backend; "
                       "verifying sortedness only")
        return None


def _device(x: jax.Array, codec: KeyCodec, engine: str, pair: bool,
            tracer: Tracer, verify_on: bool) -> LocalSort | None:
    """Routes ``device`` and ``device_pair``: encode and sort in one
    dispatch (for 2-word keys on the pair engine the whole adaptive
    tree runs in it — host-side branching would cost a host round trip
    per decision).  None on the float64 lowering gap."""
    dtype = codec.dtype
    fp = _device_input_fp(x, dtype, tracer) if verify_on else None
    try:
        with tracer.span("phase:sort"):
            if pair:
                hi_s, lo_s, code = traced_call(
                    tracer, "pair_fused",
                    _compile_pair_fused(dtype.name, _bitonic_impl()), x)
                out, code = (hi_s, lo_s), int(code)
            else:
                out = traced_call(
                    tracer, "local_device",
                    _compile_local_device(dtype.name, engine), x.reshape(-1))
    except jax.errors.JaxRuntimeError as e:
        # float64 device-side encode needs a f64->u32 bitcast some TPU
        # stacks cannot lower (XLA's x64-rewrite pass lacks the rule;
        # int64 works).  Degrade to one documented host round-trip
        # instead of an internal compiler error; every other runtime
        # failure re-raises untouched.
        if not _is_f64_lowering_gap(e, dtype, codec, _device_platform(x)):
            raise
        return None
    if pair:
        tracer.counters["local_engine"] = _PAIR_CODES[code]
        if code == 3:
            tracer.count("pair_dup_reroute", 1)
        elif code == 5:
            tracer.verbose(
                "pair engine left residual runs (hi duplication the "
                "sniff missed); lax fallback ran on device")
            tracer.count("pair_residual_fallback", 1)
    tracer.counters["encode_site"] = "device"
    return LocalSort(out, fp)


def _fold_raw(x: np.ndarray, codec: KeyCodec,
              tracer: Tracer) -> vfy.Fingerprint:
    """The input fingerprint folded from the raw host keys."""
    with tracer.span("phase:verify"), tracer.span("verify.fingerprint"):
        return vfy.fingerprint_raw(x, codec.raw_bias)


def _raw(x: np.ndarray, codec: KeyCodec, engine: str, pair: bool,
         device: Any, tracer: Tracer, verify_on: bool) -> LocalSort:
    """Route ``raw``: the keys' own bytes go to the device.  One word:
    the fused encode+sort program encodes them, and the host fold runs
    while the transfer and the sort do.  Two words (under a scoped
    ``jax.enable_x64``): the fold goes first — on a v5e a dispatch made
    while the transfer is still starting blocks the host for ~30 ms —
    then the device splits and plans them."""
    tracer.counters["encode_site"] = "device"
    fp = None
    with jax.enable_x64(True) if pair else contextlib.nullcontext():
        with tracer.span("phase:device_put"):
            raw = checked_device_put(x.reshape(-1), device)
        if not pair:
            with tracer.span("phase:sort"):
                out = traced_call(
                    tracer, "local_device",
                    _compile_local_device(codec.dtype.name, engine), raw)
            return LocalSort(out, _fold_raw(x, codec, tracer)
                             if verify_on else None)
        if verify_on:
            fp = _fold_raw(x, codec, tracer)
        with tracer.span("phase:encode"), tracer.span("encode.words"):
            hi, lo, rng, dups = traced_call(
                tracer, "pair_split", _compile_pair_split(codec.dtype.name),
                raw)
    del raw  # freed once the split has run: the peak stays the sort's
    with tracer.span("phase:encode"), tracer.span("pair.plan"):
        rng, dups = jax.device_get((rng, dups))
    return _pair_by_plan((hi, lo), rng, dups, fp, engine, tracer)


def _host(x: np.ndarray, codec: KeyCodec, engine: str, pair: bool,
          device: Any, tracer: Tracer, verify_on: bool,
          passthrough: Passthrough | None) -> LocalSort:
    """Route ``host``: encode and fold on the host, then put the words.
    Two words on the pair engine are planned on the host; otherwise the
    planner's passthrough may accept the words before the one sort."""
    tracer.counters["encode_site"] = "host"
    with tracer.span("phase:encode"):
        with tracer.span("encode.words"):
            words_np = codec.encode(x.reshape(-1))
        if pair:
            with tracer.span("pair.plan"):
                rng = np.array([words_np[0].min(), words_np[0].max(),
                                words_np[1].min(), words_np[1].max()])
                dups = _host_hi_dup_sniff(words_np[0])
    fp = None
    if verify_on:
        with tracer.span("phase:verify"), tracer.span("verify.fingerprint"):
            fp = vfy.fingerprint_host(words_np)
    with tracer.span("phase:device_put"):
        words = tuple(checked_device_put(w, device) for w in words_np)
    if pair:
        return _pair_by_plan(words, rng, dups, fp, engine, tracer)
    if passthrough is not None and passthrough(words, fp):
        return LocalSort(words, fp, verified=True)
    with tracer.span("phase:sort"):
        out = traced_call(tracer, "local",
                          _compile_local(codec.n_words, engine), *words)
    return LocalSort(out, fp)


def _pair_by_plan(words: Words, rng: Any, dups: Any,
                  fp: vfy.Fingerprint | None, engine: str,
                  tracer: Tracer) -> LocalSort:
    """The 2-word engine by plan — the MSD-hybrid structure (VERDICT
    r3 #1), adaptive like the skew fallback:

    1. constant-word shortcut: a word with zero range never needs
       sorting — narrow-range int64 (values inside one 32-bit window,
       common in practice) collapses to the plain 1-word bitonic engine
       on the other word, ~2x faster again than the pair engine.
    2. hi-duplication sniff: heavy duplication would leave equal-hi runs
       longer than the pair engine's fixed run fix-up depth — route to
       the variadic ``lax.sort`` up front (no wasted phase).
    3. pair engine (``kernels.sort_two_words_bitonic``): key+payload
       bitonic by hi + segmented odd-even run fix-up.  The residual flag
       (runs the sniff missed) falls back to ``lax.sort`` — correctness
       never depends on the sniff.  Its readback (span ``pair.residual``)
       also brings the count of equal adjacent hi words of the output
       (counter ``pair_hi_ties``).

    ``rng`` is ``[min hi, max hi, min lo, max lo]``; ``dups`` the sniff
    count."""
    dups = int(dups)
    tracer.counters["pair_sniff_dups"] = dups
    diffs = (int(rng[0]) ^ int(rng[1]), int(rng[2]) ^ int(rng[3]))
    if diffs == (0, 0):  # all keys identical: already sorted
        tracer.counters["local_engine"] = "constant"
        return LocalSort(words, fp)
    for const_w, sort_w in ((0, 1), (1, 0)):
        if diffs[const_w] == 0:
            # the constant word never moves; 1-word engine on the other
            tracer.counters["local_engine"] = f"bitonic_1w{sort_w}"
            with tracer.span("phase:sort"):
                s_out = traced_call(
                    tracer, "local_1w", _compile_local(1, engine),
                    words[sort_w])[0]
            return LocalSort((words[0], s_out) if sort_w == 1
                             else (s_out, words[1]), fp)
    if dups > 0:
        tracer.counters["local_engine"] = "lax"
        tracer.count("pair_dup_reroute", 1)
        with tracer.span("phase:sort"):
            return LocalSort(traced_call(tracer, "local_2w_lax",
                                         _compile_local(2, "lax"), *words), fp)
    tracer.counters["local_engine"] = "bitonic_pair"
    with tracer.span("phase:sort"):
        hi_s, lo_s, flags = traced_call(tracer, "pair_sort",
                                        _compile_pair_sort(_bitonic_impl()),
                                        *words)
        with tracer.span("pair.residual"):
            bad, ties = (int(v) for v in jax.device_get(flags))
    tracer.counters["pair_hi_ties"] = ties
    if bad:
        tracer.verbose(
            "pair engine left residual runs (hi duplication the sniff "
            "missed); falling back to lax.sort")
        tracer.count("pair_residual_fallback", 1)
        with tracer.span("phase:sort"):
            return LocalSort(traced_call(tracer, "local_2w_lax",
                                         _compile_local(2, "lax"), *words), fp)
    return LocalSort((hi_s, lo_s), fp)
