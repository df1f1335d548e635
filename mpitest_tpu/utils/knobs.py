"""Central env-knob registry — the ONE place the process environment is read.

Before this module, ~36 ``os.environ`` reads were scattered across
``models/``, ``parallel/``, ``utils/``, ``faults.py``, the CLI driver and
the bench scripts, each with its own ad-hoc validation (or none).  A
typo'd knob could die deep inside a sort, and nothing listed what knobs
even existed.  Now every knob is **registered** here with a name, type,
default, validator and one-line doc; every read goes through
:func:`get` / :func:`get_raw`; and the whole surface is self-documenting
(:func:`reference_table` emits the markdown table README embeds —
``python -m mpitest_tpu.utils.knobs`` prints it).

The contract is enforced mechanically: ``tools/sortlint`` rule
``SL001 env-knob-read`` fails the lint gate on any ``os.environ.get`` /
``os.getenv`` / ``os.environ[...]`` read outside this file.  Writes
(``os.environ[k] = v``, ``setdefault``, building a subprocess env with
``dict(os.environ, ...)``) stay legal everywhere — the rule targets
*reads*, because reads are where unvalidated garbage enters.

Validation is fail-fast and message-stable: a bad value raises
:class:`KnobError` (a ``ValueError``) whose text names the knob and the
accepted values — the same ``[ERROR]``-line contract the CLI has had
since round 1, now produced in exactly one place.

Native-consumed knobs (``COMM_RANKS``, ``COMM_FAULTS``, ...) are
registered too with ``consumer="native"`` so the reference table covers
the whole system; their values are parsed and validated by the C side
(``comm/comm_faults.h`` etc.), so :func:`get` returns them raw.
"""

from __future__ import annotations

import contextlib
import math
import os
from dataclasses import dataclass
from typing import Any, Callable, Iterator

__all__ = [
    "Knob", "KnobError", "get", "get_raw", "iter_knobs", "main",
    "reference_table", "register", "scoped_env", "validate",
]

#: Default elements per streamed ingest chunk: 2^22 keys = 16 MiB of
#: int32 (utils/io.py re-exports this as DEFAULT_CHUNK_ELEMS).
DEFAULT_INGEST_CHUNK = 1 << 22


class KnobError(ValueError):
    """A knob's value failed validation.  Subclasses ``ValueError`` so
    every pre-existing ``except ValueError`` fail-fast site still
    catches it; the message always starts with ``NAME=<raw!r>``."""


@dataclass(frozen=True)
class Knob:
    """One registered environment knob."""

    name: str
    kind: str                     # int | float | flag | enum | csv | str | path | spec | dtype
    default: Any                  # typed default returned when unset (may be None)
    spec: str                     # one-line "validates" column for the table
    doc: str                      # one-line description (required non-empty)
    parse: Callable[[str], Any]   # raw string -> typed value; raises KnobError
    consumer: str = "python"      # "python" | "native" (validated by the C side)

    def read(self) -> Any:
        """Typed, validated value of this knob (the default when unset)."""
        raw = os.environ.get(self.name)
        if raw is None:
            if isinstance(self.default, str):
                # string defaults go through the same parser as env
                # input, so callers always see the parsed type (e.g.
                # SORT_DTYPE yields np.dtype whether set or defaulted)
                return self.parse(self.default)
            return self.default
        return self.parse(raw)


_REGISTRY: dict[str, Knob] = {}


def register(name: str, kind: str, default: Any, spec: str, doc: str,
             parse: Callable[[str], Any], consumer: str = "python") -> None:
    """Register one knob.  Every knob must carry a nonempty one-line doc
    — sortlint rule SL030 fails the gate otherwise, and SL031 fails it
    when a registered knob is missing from README's reference table."""
    if not doc:
        raise ValueError(f"knob {name}: doc must be nonempty")
    if name in _REGISTRY:
        raise ValueError(f"knob {name} registered twice")
    _REGISTRY[name] = Knob(name, kind, default, spec, doc, parse, consumer)


def get(name: str) -> Any:
    """The typed, validated value of registered knob ``name`` (its
    default when unset).  Raises :class:`KeyError` for unregistered
    names — reading an unregistered env var is exactly the bug class
    this module exists to end."""
    return _REGISTRY[name].read()


def get_raw(name: str) -> str | None:
    """The raw (unparsed) string value of a *registered* knob, or None
    when unset — for pass-through uses (subprocess env plumbing,
    read-modify-write of ``XLA_FLAGS``) where the consumer parses."""
    knob = _REGISTRY[name]  # KeyError on unregistered names, like get()
    return os.environ.get(knob.name)


def validate(*names: str) -> None:
    """Fail-fast parse of the named knobs (all registered python-side
    knobs when none given) — the CLI's startup contract: garbage in any
    knob is one clean ``[ERROR]`` line, never a mid-sort stack trace."""
    for name in names or tuple(_REGISTRY):
        knob = _REGISTRY[name]
        if knob.consumer == "python":
            knob.read()


def iter_knobs() -> Iterator[Knob]:
    """Registered knobs in name order (the table's row order)."""
    for name in sorted(_REGISTRY):
        yield _REGISTRY[name]


@contextlib.contextmanager
def scoped_env(**overrides: str | None) -> Iterator[None]:
    """Temporarily set (or, with ``None``, unset) environment variables,
    restoring the previous state on exit — the sanctioned way for
    drivers/tests to flip knobs for a scoped region (the save/restore
    dance ``bench/fault_selftest.py`` and ``bench/mesh_battery.py`` each
    hand-rolled before this existed)."""
    old = {k: os.environ.get(k) for k in overrides}
    try:
        for k, v in overrides.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


# ------------------------------------------------------------- parse kit

def _int(name: str, lo: int | None = None, hi: int | None = None,
         err: str | None = None) -> Callable[[str], int]:
    def parse(raw: str) -> int:
        v: int | None
        try:
            v = int(raw)
        except ValueError:
            v = None
        if (v is None or (lo is not None and v < lo)
                or (hi is not None and v > hi)):
            if err is not None:
                raise KnobError(err.format(name=name, raw=raw)) from None
            bound = f" >= {lo}" if lo is not None else ""
            raise KnobError(f"{name}={raw!r}: use an integer{bound}") from None
        return v
    return parse


def _float(name: str, lo: float, exclusive: bool = False,
           note: str | None = None) -> Callable[[str], float]:
    """Finite-float parser with one bound — the ONE rule for every
    float knob (a finite requirement everywhere: 'inf' backoffs and
    'nan' timeouts are garbage, not policy)."""
    def parse(raw: str) -> float:
        v: float | None
        try:
            v = float(raw)
        except ValueError:
            v = None
        ok = (v is not None and math.isfinite(v)
              and (v > lo if exclusive else v >= lo))
        if not ok:
            op = ">" if exclusive else ">="
            extra = f" ({note})" if note else ""
            raise KnobError(f"{name}={raw!r}: use a finite number "
                            f"{op} {lo:g}{extra}")
        return v  # type: ignore[return-value]
    return parse


def _float_ge0(name: str) -> Callable[[str], float]:
    return _float(name, 0.0)


def _flag(name: str) -> Callable[[str], bool]:
    def parse(raw: str) -> bool:
        if raw not in ("0", "1"):
            raise KnobError(f"{name}={raw!r}: use '1' or '0'")
        return raw == "1"
    return parse


def _enum(name: str, choices: tuple[str, ...],
          err: str | None = None) -> Callable[[str], str]:
    def parse(raw: str) -> str:
        if raw not in choices:
            raise KnobError(err.format(name=name, raw=raw) if err else
                            f"{name}={raw!r}; use one of {choices}")
        return raw
    return parse


def _csv(name: str) -> Callable[[str], tuple[str, ...]]:
    def parse(raw: str) -> tuple[str, ...]:
        parts = tuple(p.strip() for p in raw.split(",") if p.strip())
        if not parts:
            raise KnobError(f"{name}={raw!r}: use a comma-separated list")
        return parts
    return parse


def _passthrough(raw: str) -> str:
    return raw


# ---------------------------------------------------------- registrations
# Core sort knobs (drivers/sort_cli.py + models/api.py).

register("SORT_ALGO", "enum", "sample", "sample | radix",
         "Sort algorithm the CLI dispatches (reference default: sample).",
         _enum("SORT_ALGO", ("sample", "radix"),
               err="{name}={raw!r}: use 'sample' or 'radix'"))


def _dtype(name: str) -> Callable[[str], Any]:
    def parse(raw: str) -> Any:
        from mpitest_tpu.ops.keys import codec_for
        try:
            # np.dtype raises TypeError/ValueError/SyntaxError depending
            # on the garbage; codec_for rejects valid-but-unsupported
            # dtypes with the supported list in the message.
            return codec_for(raw).dtype
        except Exception as e:
            raise KnobError(f"{name}={raw!r}: {e}") from None
    return parse


register("SORT_DTYPE", "dtype", "int32", "a codec-supported numpy dtype",
         "Key dtype for text inputs (int32/uint32/int64/uint64/f32/f64).",
         _dtype("SORT_DTYPE"))


def _parse_digit_bits(raw: str) -> int | None:
    if raw == "auto":
        return None
    try:
        v = int(raw)
    except ValueError:
        v = 0
    if not 1 <= v <= 16:
        raise KnobError(f"SORT_DIGIT_BITS={raw!r}: use 'auto' or an "
                        "integer in [1, 16]") from None
    return v


register("SORT_DIGIT_BITS", "int", None, "'auto' or an integer in [1, 16]",
         "Radix digit width in bits; auto picks from key width and P.",
         _parse_digit_bits)


def _parse_ranks(raw: str) -> int | None:
    if raw == "":
        return None
    try:
        v = int(raw)
    except ValueError:
        v = 0
    if v < 1:
        raise KnobError(f"SORT_RANKS={raw!r}: use a positive integer")
    return v


register("SORT_RANKS", "int", None, "a positive integer (default: all devices)",
         "Mesh size (device count) the sort runs over.", _parse_ranks)


def _parse_cap_factor(raw: str) -> float:
    try:
        v = float(raw)
    except ValueError:
        v = 0.0
    # isfinite: 'nan' passes a <= 0 gate (NaN compares False) and 'inf'
    # overflows the downstream int() — both are garbage, same contract.
    if not math.isfinite(v) or v <= 0:
        raise KnobError(f"SORT_CAP_FACTOR={raw!r}: use a finite number > 0")
    return v


register("SORT_CAP_FACTOR", "float", 2.0, "a finite number > 0",
         "Exchange cap as a multiple of the fair per-peer share.",
         _parse_cap_factor)


def _parse_oversample(raw: str) -> int | None:
    if raw == "":
        return None
    try:
        v = int(raw)
    except ValueError:
        v = 0
    if v < 1:
        raise KnobError(f"SORT_OVERSAMPLE={raw!r}: use an integer >= 1")
    return v


register("SORT_OVERSAMPLE", "int", None, "an integer >= 1 (default: 2P-1)",
         "Samples per shard for sample sort's splitter selection.",
         _parse_oversample)

register("SORT_LOCAL_ENGINE", "enum", "auto",
         "auto | bitonic | lax",
         "Local (single-device) sort engine; auto = bitonic on TPU.",
         _enum("SORT_LOCAL_ENGINE", ("auto", "bitonic", "lax")))

register("SORT_EXCHANGE_ENGINE", "enum", "auto",
         "auto | lax | pallas | pallas_interpret",
         "Inter-device exchange engine (ops/exchange.py remote-DMA + "
         "fused pass vs lax.all_to_all); auto = pallas on TPU.",
         _enum("SORT_EXCHANGE_ENGINE",
               ("auto", "lax", "pallas", "pallas_interpret")))


def _parse_devices(raw: str) -> int | None:
    if raw == "auto":
        return None
    try:
        v = int(raw)
    except ValueError:
        v = 0
    if v < 1:
        raise KnobError(f"SORT_DEVICES={raw!r}: use 'auto' or an "
                        "integer >= 1") from None
    return v


# Scale-out knobs (ISSUE 7): the P-device sharded path is the primary
# path, so the device count, the capacity negotiation and the skew
# re-stage are all first-class, registered knobs.

register("SORT_DEVICES", "int", None, "'auto' or an integer >= 1",
         "Mesh device count when none is passed explicitly (auto: all).",
         _parse_devices)
register("SORT_NEGOTIATE", "enum", "auto", "auto | on | off",
         "Exchange-capacity negotiation from a count probe (auto: P>1).",
         _enum("SORT_NEGOTIATE", ("auto", "on", "off")))
register("SORT_RESTAGE", "enum", "auto", "auto | off",
         "Skew-aware re-stage (shard interleave) on exchange imbalance.",
         _enum("SORT_RESTAGE", ("auto", "off")))


def _parse_restage_ratio(raw: str) -> float:
    try:
        v = float(raw)
    except ValueError:
        v = 0.0
    if not math.isfinite(v) or v <= 1.0:
        raise KnobError(f"SORT_RESTAGE_RATIO={raw!r}: use a finite "
                        "number > 1")
    return v


register("SORT_RESTAGE_RATIO", "float", 4.0, "a finite number > 1",
         "Per-peer max/fair-share count ratio that triggers a re-stage.",
         _parse_restage_ratio)

# Plan provenance (ISSUE 12): every runtime decision (algo reroute,
# negotiated cap, re-stage, engine, ladder rung, serve bucket) is
# recorded with predicted-vs-actual quantities and a regret scalar —
# the read side of the ROADMAP item-5 planner.
register("SORT_PLAN", "enum", "on", "on | off",
         "Decision provenance: mint SortPlan records, emit sort.plan "
         "spans and the plan-regret metrics (off = PR 8 behavior).",
         _enum("SORT_PLAN", ("on", "off")))

# Self-tuning planner (ISSUE 14): the policy layer that acts on the
# plan telemetry — per-request algo/cap-margin policy, serve-side
# window/bucket auto-tuning, shadow/canary evaluation.


def _parse_hysteresis(raw: str) -> float:
    try:
        v = float(raw)
    except ValueError:
        v = 0.0
    if not math.isfinite(v) or v <= 1.0:
        raise KnobError(f"SORT_PLANNER_HYSTERESIS={raw!r}: use a finite "
                        "number > 1")
    return v


register("SORT_PLANNER", "enum", "off", "off | shadow | on",
         "Self-tuning planner: off = hand-set defaults (byte-identical "
         "pre-planner stack), shadow = compute + log every policy "
         "choice without acting, on = act (models/planner.py).",
         _enum("SORT_PLANNER", ("off", "shadow", "on")))
register("SORT_PLANNER_WINDOW", "int", 256, "an integer >= 16",
         "Rolling look-back of the planner's learning policies: flight-"
         "ring plan records (cap margin) / request arrivals (serve "
         "tuner).",
         # 16 = planner.MIN_OBSERVATIONS: the serve tuner declines to
         # recommend below it, so a smaller window would validate but
         # silently behave as 16 — fail fast instead
         _int("SORT_PLANNER_WINDOW", lo=16))
register("SORT_PLANNER_HYSTERESIS", "float", 1.5, "a finite number > 1",
         "Minimum up/down ratio a serve-tuner recommendation must "
         "differ by before it may commit (two consecutive agreeing "
         "evaluations required — the window never thrashes).",
         _parse_hysteresis)

# Observability sidecar paths (off when unset — the byte-compatible CLI
# contract is untouched by default).
register("SORT_TRACE", "path", None, "a writable file path",
         "Stream the structured span log as JSONL to this path.",
         _passthrough)
register("SORT_TRACE_CHROME", "path", None, "a writable file path",
         "Write the run's Chrome trace-event JSON (Perfetto) here.",
         _passthrough)
register("SORT_METRICS", "path", None, "a writable file path",
         "Append one JSON metrics sidecar line per run to this path.",
         _passthrough)
register("SORT_PROFILE", "path", None, "a writable directory path",
         "Capture a jax.profiler trace of the sort into this logdir.",
         _passthrough)

# Live-telemetry knobs (ISSUE 10): the operational layer — stream
# sampling, the /metrics side port, the always-on flight recorder, and
# the on-demand device profiling hooks.


def _parse_sample(raw: str) -> float:
    try:
        v = float(raw)
    except ValueError:
        v = 0.0
    if not (math.isfinite(v) and 0.0 < v <= 1.0):
        raise KnobError(f"SORT_TRACE_SAMPLE={raw!r}: use a number in "
                        "(0, 1]")
    return v


register("SORT_TRACE_SAMPLE", "float", 1.0, "a number in (0, 1]",
         "Down-sample the SORT_TRACE stream: keep ~this fraction of "
         "top-level spans (whole subtrees — parent links stay intact; "
         "the flight recorder still sees everything).",
         _parse_sample)


def _parse_metrics_port(raw: str) -> int:
    try:
        v = int(raw)
    except ValueError:
        v = -2
    if not -1 <= v <= 65535:
        raise KnobError(f"SORT_METRICS_PORT={raw!r}: use an integer in "
                        "[-1, 65535] (0 = ephemeral, -1 = disabled)")
    return v


register("SORT_METRICS_PORT", "int", 0, "an integer in [-1, 65535]",
         "Side port for the server's live /metrics, /healthz, /varz, "
         "/flightrecorder, /profile endpoints (0 = ephemeral, -1 = off).",
         _parse_metrics_port)
register("SORT_FLIGHT_RECORDER_SIZE", "int", 2048,
         "an integer >= 0 (0 disables)",
         "Flight-recorder ring capacity: recent spans kept in memory "
         "for incident dumps (typed errors, faults, SIGQUIT).",
         _int("SORT_FLIGHT_RECORDER_SIZE", lo=0))
register("SORT_FLIGHT_RECORDER_DIR", "path", "/tmp/mpitest_flightrec",
         "a writable directory path",
         "Directory flight-recorder dump artifacts land in.",
         _passthrough)
register("SORT_PROFILE_EVERY", "int", 0, "an integer >= 0 (0 = off)",
         "Capture a jax.profiler trace around every Nth server dispatch "
         "(into SORT_PROFILE, else <flight dir>/profile).",
         _int("SORT_PROFILE_EVERY", lo=0))

# Streaming-ingest knobs (utils/io.py + models/ingest.py).

register("SORT_INGEST", "enum", "auto", "auto | stream | mono",
         "Ingest pipeline selector; auto streams inputs above ~32 MiB.",
         _enum("SORT_INGEST", ("auto", "stream", "mono")))
register("SORT_INGEST_CHUNK", "int", None, "an integer >= 1 (default 2^22)",
         "Keys per streamed ingest chunk.",
         _int("SORT_INGEST_CHUNK", lo=1))
register("SORT_INGEST_THREADS", "int", 2, "an integer >= 1",
         "Host parse/encode worker threads in the ingest pipeline.",
         _int("SORT_INGEST_THREADS", lo=1))
register("SORT_DONATE", "enum", "auto", "auto | 1 | 0",
         "Donate staged word buffers to the SPMD program (auto: on TPU).",
         _enum("SORT_DONATE", ("auto", "1", "0"),
               err="{name}={raw!r}: use 'auto', '1' or '0'"))
register("SORT_NATIVE_ENCODE", "enum", "auto", "auto | on | off",
         "Native C encode/parse engine for ingest (utils/native_encode.py).",
         _enum("SORT_NATIVE_ENCODE", ("auto", "on", "off")))

# Robustness knobs (models/supervisor.py + faults.py).

register("SORT_VERIFY", "flag", True, "1 | 0",
         "Always-on output verification (sortedness + fingerprint).",
         _flag("SORT_VERIFY"))
register("SORT_MAX_RETRIES", "int", 2, "an integer >= 0",
         "Dispatch retry budget for transient SPMD launch failures.",
         _int("SORT_MAX_RETRIES", lo=0))
register("SORT_RETRY_BACKOFF", "float", 0.05, "a number >= 0",
         "Base seconds of exponential dispatch-retry backoff.",
         _float_ge0("SORT_RETRY_BACKOFF"))
register("SORT_FALLBACK", "flag", True, "1 | 0",
         "Graceful-degradation ladder (other algorithm, then host sort).",
         _flag("SORT_FALLBACK"))


def _parse_faults(raw: str) -> str | None:
    if not raw:
        return None
    from mpitest_tpu import faults
    faults.FaultRegistry(raw)  # raises ValueError with the site list
    return raw


register("SORT_FAULTS", "spec", None, "comma list of site[:count|:inf]",
         "Deterministic fault-injection plan (mpitest_tpu/faults.py).",
         _parse_faults)


def _parse_faults_seed(raw: str) -> int:
    try:
        return int(raw)
    except ValueError:
        raise KnobError(f"SORT_FAULTS_SEED={raw!r}: use an integer") from None


register("SORT_FAULTS_SEED", "int", 0, "an integer",
         "Seed of the splitmix64 stream fault corruption values draw from.",
         _parse_faults_seed)
register("SORT_FAULT_STALL_MS", "int", 250, "an integer >= 1",
         "Milliseconds the dispatch_stall fault site blocks the "
         "dispatch thread (the chaos drill behind the watchdog gate).",
         _int("SORT_FAULT_STALL_MS", lo=1))
register("SORT_FAULT_ENOSPC_AT", "int", 1, "an integer >= 1",
         "Which spill write (1-based, counted per registry) the armed "
         "spill_enospc fault site fails with ENOSPC — deterministic "
         "mid-merge disk-full drills.",
         _int("SORT_FAULT_ENOSPC_AT", lo=1))

# Sort-as-a-service knobs (ISSUE 8: mpitest_tpu/serve/ + the
# drivers/sort_server.py entry point).  All validated fail-fast at
# server startup — garbage in any of them is one [ERROR] line, never a
# traceback out of the first request.


def _parse_port(raw: str) -> int:
    try:
        v = int(raw)
    except ValueError:
        v = -1
    if not 0 <= v <= 65535:
        raise KnobError(f"SORT_SERVE_PORT={raw!r}: use an integer in "
                        "[0, 65535] (0 = ephemeral)") from None
    return v


register("SORT_SERVE_PORT", "int", 7077, "an integer in [0, 65535]",
         "TCP port the sort server listens on (0 = ephemeral).",
         _parse_port)
register("SORT_SERVE_HOST", "str", "127.0.0.1", "a bind address",
         "Address the sort server binds (default loopback).",
         _passthrough)
register("SORT_SERVE_MAX_INFLIGHT", "int", 64, "an integer >= 1",
         "Admission bound: concurrent in-flight requests before typed "
         "backpressure rejection.",
         _int("SORT_SERVE_MAX_INFLIGHT", lo=1))
register("SORT_SERVE_MAX_BYTES", "int", 1 << 28, "an integer >= 1",
         "Admission bound: total in-flight request payload bytes.",
         _int("SORT_SERVE_MAX_BYTES", lo=1))


def _parse_window_ms(raw: str) -> float:
    try:
        v = float(raw)
    except ValueError:
        v = -1.0
    if not (math.isfinite(v) and v >= 0.0):
        raise KnobError(f"SORT_SERVE_BATCH_WINDOW_MS={raw!r}: use a "
                        "finite number >= 0 (0 disables packing)")
    return v


register("SORT_SERVE_BATCH_WINDOW_MS", "float", 2.0, "a number >= 0",
         "Batching window: how long a dispatch waits to pack more "
         "small requests (0 = dispatch each alone).",
         _parse_window_ms)
register("SORT_SERVE_BATCH_KEYS", "int", 1 << 16, "an integer >= 1",
         "Requests up to this many keys are batchable; one packed "
         "dispatch carries at most this many keys.",
         _int("SORT_SERVE_BATCH_KEYS", lo=1))


def _parse_buckets(raw: str) -> tuple[int, ...]:
    out = []
    for part in raw.split(","):
        part = part.strip()
        if not part:
            continue
        try:
            v = int(part)
        except ValueError:
            v = 0
        if not 1 <= v <= 30:
            raise KnobError(
                f"SORT_SERVE_SHAPE_BUCKETS={raw!r}: use a comma list of "
                "log2 bucket sizes in [1, 30]") from None
        out.append(v)
    if not out:
        raise KnobError(f"SORT_SERVE_SHAPE_BUCKETS={raw!r}: use a comma "
                        "list of log2 bucket sizes in [1, 30]")
    return tuple(sorted(set(out)))


# Default prewarm covers every bucket the packed path can actually
# request: bucket_for() floors at 2^10 and a packed dispatch carries at
# most SORT_SERVE_BATCH_KEYS (default 2^16) keys — prewarming outside
# that range would pay startup compiles for executables no dispatch
# ever uses while leaving reachable buckets to compile on the request
# path (the warm-traffic latency spike prewarm exists to prevent).
register("SORT_SERVE_SHAPE_BUCKETS", "csv", "10,11,12,13,14,15,16",
         "comma list of log2 sizes in [1, 30]",
         "Power-of-two shape buckets the executor cache prewarms at "
         "server startup.",
         _parse_buckets)
register("SORT_SERVE_PREWARM", "enum", "auto", "auto | off",
         "AOT-prewarm the executor cache at startup (off = "
         "jit-on-first-use).",
         _enum("SORT_SERVE_PREWARM", ("auto", "off")))
register("SORT_SERVE_ALLOW_FAULTS", "flag", False, "1 | 0",
         "Honor per-request fault-injection specs (test mode only; "
         "production servers reject them as bad requests).",
         _flag("SORT_SERVE_ALLOW_FAULTS"))

# Request-lifecycle robustness knobs (ISSUE 11): every wire interaction
# and every dispatch is time-bounded, so a hostile network or a wedged
# device costs one bounded thread — never a pinned byte budget or a
# silently dead server.


def _float_gt0(name: str) -> Callable[[str], float]:
    return _float(name, 0.0, exclusive=True)


register("SORT_SERVE_IDLE_TIMEOUT_S", "float", 300.0, "a finite number > 0",
         "Per-connection idle bound: seconds a keep-alive connection may "
         "sit between requests before the server closes it.",
         _float_gt0("SORT_SERVE_IDLE_TIMEOUT_S"))
register("SORT_SERVE_READ_TIMEOUT_S", "float", 30.0, "a finite number > 0",
         "Total wire-read budget per request (header payload reads, "
         "rejected-payload drains, response writes): a client stalled "
         "mid-payload is disconnected and its admission bytes reclaimed "
         "within this bound.",
         _float_gt0("SORT_SERVE_READ_TIMEOUT_S"))


register("SORT_SERVE_DISPATCH_TIMEOUT_S", "float", 120.0,
         "a finite number >= 0 (0 = watchdog off)",
         "Dispatch watchdog bound: a single dispatch exceeding this "
         "trips the circuit breaker (healthz 503, fast typed "
         "rejections) and dumps the flight recorder.",
         _float("SORT_SERVE_DISPATCH_TIMEOUT_S", 0.0,
                note="0 disables the watchdog"))
register("SORT_SERVE_BREAKER_BACKOFF_S", "float", 5.0,
         "a finite number > 0",
         "Seconds the tripped circuit breaker stays open before "
         "half-opening with a probe request (doubles per failed probe).",
         _float_gt0("SORT_SERVE_BREAKER_BACKOFF_S"))
register("SORT_SERVE_COMPLETION_TIMEOUT_S", "float", 600.0,
         "a finite number > 0",
         "Backstop bound a handler thread waits for its dispatched "
         "request to complete before failing it typed 'internal'.",
         _float_gt0("SORT_SERVE_COMPLETION_TIMEOUT_S"))

# Out-of-core external-sort knobs (ISSUE 15: mpitest_tpu/store/).  The
# budget is deliberately forceable far below real device/host memory so
# the whole spill/merge path is CPU-testable; 0 (the default) disables
# the external path entirely — nothing spills unless asked to.

register("SORT_SPILL_DIR", "path", None,
         "a writable directory path (default: a per-process tmp dir)",
         "Directory spill runs are staged in (store/runs.py owns every "
         "read/write of it — sortlint SL014).",
         _passthrough)
register("SORT_MEM_BUDGET", "int", 0, "an integer >= 0 (0 = unlimited)",
         "Host/device byte budget the external sort partitions against; "
         "inputs above it spill to sorted runs and k-way merge back.",
         _int("SORT_MEM_BUDGET", lo=0))
register("SORT_MERGE_FANIN", "int", 16, "an integer >= 2",
         "Maximum runs merged per k-way merge pass; more runs merge in "
         "multiple passes through intermediate runs.",
         _int("SORT_MERGE_FANIN", lo=2))
register("SORT_SERVE_SPILL", "enum", "auto", "auto | off",
         "Route serve requests larger than SORT_SERVE_MAX_BYTES to the "
         "out-of-core spill tier instead of a typed 'bytes' rejection.",
         _enum("SORT_SERVE_SPILL", ("auto", "off")))
register("SORT_SPILL_COMPRESS", "enum", "auto", "auto | on | off",
         "Order-preserving compression of spill runs (SORTRUN2: delta + "
         "bitpacked key blocks, raw payload blocks): 'auto' compresses "
         "when native/libspillz.so loads, 'on' forces it (pure-Python "
         "codec if the library is missing), 'off' writes raw runs.",
         _enum("SORT_SPILL_COMPRESS", ("auto", "on", "off")))
register("SORT_SPILL_THROTTLE_MBPS", "float", 0.0,
         "a finite number >= 0 (0 = unthrottled)",
         "Simulated spill-disk bandwidth cap in MB/s, shared across ALL "
         "spill readers/writers in the process (one token bucket = one "
         "disk) — makes disk-bound external sorts reproducible on fast "
         "local storage for the spillperf gate.",
         _float_ge0("SORT_SPILL_THROTTLE_MBPS"))

# Crash-durable external sort (ISSUE 18: store/manifest.py) — journaled
# spill manifests, kill-resume at the merge phase, and the age-gated
# orphan GC sweep.

register("SORT_RESUME", "enum", "auto", "auto | off",
         "Crash resume of dataset-keyed external sorts: 'auto' "
         "durably journals every committed spill run in a manifest "
         "and a retried/restarted sort of the same dataset id replays "
         "it, re-validates the runs and re-enters at the merge phase; "
         "'off' disables journaling and resume entirely.",
         _enum("SORT_RESUME", ("auto", "off")))
register("SORT_SPILL_GC_AGE_S", "int", 3600, "an integer >= 0",
         "Minimum age in seconds before an orphaned spill file (one "
         "no live manifest references) is reclaimed by the startup GC "
         "sweep — a concurrent sort's fresh files are never swept.",
         _int("SORT_SPILL_GC_AGE_S", lo=0))

# Streaming-sentinel knobs (ISSUE 16: serve/sentinel.py) — live anomaly
# detection over the span stream; alerts ride registered serve.alert
# spans into /alerts, sort_alerts_total and the flight recorder.

register("SORT_SENTINEL", "enum", "on", "on | off",
         "Streaming SLO sentinel in the serve core: rolling-window "
         "burn-rate/drift/imbalance detection raising registered "
         "serve.alert events ('off' detaches the observer entirely).",
         _enum("SORT_SENTINEL", ("on", "off")))
register("SORT_SENTINEL_WINDOW_S", "float", 60.0, "a finite number > 0",
         "Rolling evaluation window of the sentinel's series (burn "
         "rate, regrows, breaker trips) — also the per-rule alert "
         "cooldown.",
         _float_gt0("SORT_SENTINEL_WINDOW_S"))
register("SORT_ALERT_BURN_RATE", "float", 2.0, "a finite number > 0",
         "Error-budget burn-rate multiple (vs the 99.9% SLO allowance) "
         "at which the sentinel raises deadline_burn; 2x that multiple "
         "escalates to critical and dumps the flight recorder.",
         _float_gt0("SORT_ALERT_BURN_RATE"))

# Bench-driver knobs (bench.py).


def _parse_bench_platform(raw: str) -> int | None:
    name, _, ndev = raw.partition(":")
    if name != "cpu":
        raise KnobError(f"BENCH_PLATFORM supports cpu[:N], got {raw!r}")
    try:
        n = int(ndev) if ndev else 1
    except ValueError:
        n = 0
    if n < 1:
        raise KnobError(f"BENCH_PLATFORM supports cpu[:N], got {raw!r}")
    return n


register("BENCH_PLATFORM", "str", None, "cpu[:N]",
         "Force an N-device virtual CPU mesh for TPU-less bench runs.",
         _parse_bench_platform)
register("BENCH_DTYPE", "dtype", "int32", "a codec-supported numpy dtype",
         "Key dtype the bench driver generates and sorts.",
         _dtype("BENCH_DTYPE"))
register("BENCH_LOG2N", "int", None, "an integer >= 1 (default 28 TPU / 20 CPU)",
         "log2 of the bench key count.", _int("BENCH_LOG2N", lo=1))
register("BENCH_ALGO", "enum", "radix", "radix | sample",
         "Algorithm the bench driver measures.",
         _enum("BENCH_ALGO", ("radix", "sample")))
register("BENCH_REPEATS", "int", 3, "an integer >= 1",
         "Timed sort repeats; the row reports the best.",
         _int("BENCH_REPEATS", lo=1))
register("BENCH_NATIVE_RANKS", "int", 8, "an integer >= 0 (0 disables)",
         "Host-CPU ranks for the native denominator run.",
         _int("BENCH_NATIVE_RANKS", lo=0))
register("BENCH_NATIVE_REPEATS", "int", 3, "an integer >= 1",
         "Native denominator runs; the median is the denominator.",
         _int("BENCH_NATIVE_REPEATS", lo=1))
register("BENCH_MULTICHIP", "enum", "auto", "auto | off",
         "Emit the devices=8 bench row (real mesh, else cpu:8 fallback).",
         _enum("BENCH_MULTICHIP", ("auto", "off")))
register("BENCH_SERVE", "enum", "auto", "auto | off",
         "Emit the sort-as-a-service bench row (bench/serve_load.py "
         "against a spawned server).",
         _enum("BENCH_SERVE", ("auto", "off")))
register("BENCH_PLANNER", "enum", "auto", "auto | off",
         "Emit the planner_mix_mkeys_per_s bench row (the adversarial "
         "mix of bench/planner_selftest.py, planner pinned off).",
         _enum("BENCH_PLANNER", ("auto", "off")))
register("BENCH_EXTERNAL", "enum", "auto", "auto | off",
         "Emit the external_sort_mkeys_per_s bench row (out-of-core "
         "spill+merge under a forced SORT_MEM_BUDGET).",
         _enum("BENCH_EXTERNAL", ("auto", "off")))

# Bench-script knobs (bench/*.py probes and batteries).

register("F64_LOG2N", "int", 27, "an integer >= 1",
         "log2 key count for the f64-at-scale probe.",
         _int("F64_LOG2N", lo=1))
register("F64_REPEATS", "int", 2, "an integer >= 1",
         "Repeats for the f64-at-scale probe.", _int("F64_REPEATS", lo=1))
register("MESHB_PARTS", "csv", ("dtypes", "zipf", "pack", "engines"),
         "comma list of battery parts",
         "Which mesh-battery parts to run.", _csv("MESHB_PARTS"))
register("MESHB_LOG2N", "int", 21, "an integer >= 1",
         "log2 key count for the mesh battery.", _int("MESHB_LOG2N", lo=1))
register("STRESS64_LOG2N", "int", None, "an integer >= 1",
         "log2 key count override for the 64-bit stress battery.",
         _int("STRESS64_LOG2N", lo=1))
register("STRESS64_PATTERNS", "csv", None, "comma list of pattern names",
         "Restrict the 64-bit stress battery to these patterns.",
         _csv("STRESS64_PATTERNS"))
register("SKEW_LOG2N", "int", 27, "an integer >= 1",
         "log2 key count for the skew-at-scale battery.",
         _int("SKEW_LOG2N", lo=1))
register("SKEW_REPEATS", "int", 2, "an integer >= 1",
         "Repeats for the skew-at-scale battery.", _int("SKEW_REPEATS", lo=1))
register("SKEW_DISTS", "csv", None, "comma list of distribution names",
         "Restrict the skew battery to these distributions.",
         _csv("SKEW_DISTS"))
register("SKEW_MESH_LOG2N", "int", 24, "an integer >= 1",
         "log2 key count for the skew battery's mesh sweep.",
         _int("SKEW_MESH_LOG2N", lo=1))
register("PROBE_LOG2N", "int", 26, "an integer >= 1",
         "log2 key count for the relayout probe.", _int("PROBE_LOG2N", lo=1))
register("PROBE_PARTS", "csv", ("agree", "net", "1w", "full"),
         "comma list of probe parts",
         "Which relayout-probe parts to run.", _csv("PROBE_PARTS"))
register("FIX_PARTS", "csv", ("uniform", "runs16", "exact"),
         "comma list of probe parts",
         "Which fixdepth-probe parts to run.", _csv("FIX_PARTS"))

# Infrastructure pass-throughs and native-consumed knobs.

register("XLA_FLAGS", "str", "", "XLA flag string (pass-through)",
         "Extra XLA flags; utils/platform.py appends the device-count flag.",
         _passthrough)
register("JAX_COMPILATION_CACHE_DIR", "str", "", "a directory path",
         "JAX's own persistent compile-cache directory; when unset, "
         "utils/platform.py enable_compile_cache uses <repo>/.jax_cache.",
         _passthrough)
register("COMM_RANKS", "int", None, "a positive integer",
         "Rank count for the native pthreads (local) comm backend.",
         _passthrough, consumer="native")
register("COMM_STATS", "path", None, "a writable file path",
         "Native backends append one comm-stats JSON line per run here.",
         _passthrough, consumer="native")
register("COMM_FAULTS", "spec", None,
         "kill:<rank>@<nth> | stall:<rank>@<nth>:<ms>",
         "Native fault drills at collective entry (comm/comm_faults.h).",
         _passthrough, consumer="native")
register("MINIMPI_NP", "int", None, "a positive integer",
         "Process count for the fork-based minimpi runtime.",
         _passthrough, consumer="native")


# ----------------------------------------------------------------- table

def reference_table() -> str:
    """The knob reference as a markdown table — the generated source of
    README's "Environment knobs" section (``make knob-docs`` regenerates
    it; a registered knob missing from README fails sortlint SL031)."""
    rows = ["| knob | type | default | validates | description |",
            "|---|---|---|---|---|"]
    for k in iter_knobs():
        if k.name == "SORT_INGEST_CHUNK":
            # registered default is None (= "use the constant"); the
            # table shows the effective value
            default = str(DEFAULT_INGEST_CHUNK) + " (2^22)"
        elif k.default is None:
            default = "_(unset)_"
        elif isinstance(k.default, bool):
            default = "1" if k.default else "0"
        elif isinstance(k.default, tuple):
            default = ",".join(k.default)
        else:
            default = str(k.default)
        doc = k.doc + (" _(consumed by the C backends)_"
                       if k.consumer == "native" else "")
        spec = k.spec.replace("|", "\\|")  # literal pipes inside md cells
        rows.append(f"| `{k.name}` | {k.kind} | {default} | {spec} | {doc} |")
    return "\n".join(rows)


def main() -> int:
    print(reference_table())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
