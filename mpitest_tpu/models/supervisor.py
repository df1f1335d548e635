"""SortSupervisor — retries, cap regrow, and graceful degradation.

Before this layer, ``models/api.py`` had two ad-hoc overflow-retry
loops (sample and radix each re-deriving "grow the cap, rebuild donated
words, count the retry") and NO policy for a dispatch that throws: a
transient ``JaxRuntimeError`` — a preempted device, a fleeting OOM —
killed the whole sort.  The reference is worse still: its failure
"policy" is silent truncation and stranded peers (SURVEY §7.4).

The supervisor centralizes all of it:

* :meth:`dispatch` — every SPMD program launch goes through one bounded
  retry loop with exponential backoff (``SORT_MAX_RETRIES`` /
  ``SORT_RETRY_BACKOFF``).  Each failed attempt emits a
  ``supervisor_retry`` span; donated input words are rebuilt before the
  re-launch (a failed donated dispatch may have consumed them).  The
  fault registry's ``dispatch_error`` / ``dispatch_oom`` sites inject
  here, so the retry path is exercised without a flaky device.
* :meth:`exchange_loop` — THE cap-regrow loop, shared by both
  algorithms: run an attempt at the current cap, grow to the reported
  need on overflow, rebuild donated words, and surface a typed
  :class:`ExchangeCapExceeded` when the need crosses the caller's O(n)
  bound (the sample→radix skew reroute keeps its policy in api.py; the
  mechanics live here, once).
* **Degradation ladder** (driven by ``_sort_impl``): exchange engine
  pallas → lax (ISSUE 13: a Pallas kernel failure re-runs the SAME
  algorithm on the XLA collective before anything else moves), then
  requested algorithm → the other algorithm → host ``np.lexsort`` —
  taken only on persistent dispatch failure or repeated verification
  failure, and every rung's result still faces the same fingerprint
  verification.
  The ladder ends in a *verified* result or a typed error
  (:class:`SortIntegrityError` / :class:`SortRetryExhausted`), never a
  silent wrong answer.  ``SORT_FALLBACK=0`` pins the requested
  algorithm (benchmarks, parity tests).

The CLI maps the two terminal errors to distinct exit codes
(``drivers/sort_cli.py``), and every retry / fault / verification event
lands in the span stream the report CLI aggregates — robustness is
observable, not just present.
"""

from __future__ import annotations

import time
from typing import TYPE_CHECKING, Callable

from mpitest_tpu import faults as flt
from mpitest_tpu.utils import knobs

if TYPE_CHECKING:
    from mpitest_tpu.models.plan import SortPlan
    from mpitest_tpu.utils.trace import Tracer


class SortFaultError(RuntimeError):
    """Base of the supervisor's typed terminal errors."""


class SortIntegrityError(SortFaultError):
    """Every recovery rung was exhausted without producing a result that
    passes the sortedness + fingerprint verification — the caller must
    treat the sort as failed (never as approximately right)."""


class SortRetryExhausted(SortFaultError):
    """Dispatch kept failing past the retry budget (and fallback was
    disabled or also failed); the underlying error is ``__cause__``."""


class ExchangeCapExceeded(Exception):
    """Internal control flow of :meth:`SortSupervisor.exchange_loop`:
    the exchange needs a cap beyond the caller's bound."""

    def __init__(self, need: int, limit: int) -> None:
        super().__init__(f"exchange needs cap {need} > bound {limit}")
        self.need = need
        self.limit = limit


def max_retries() -> int:
    """``SORT_MAX_RETRIES`` (default 2): the dispatch retry budget."""
    return knobs.get("SORT_MAX_RETRIES")


def retry_backoff() -> float:
    """``SORT_RETRY_BACKOFF`` (default 0.05): base backoff seconds."""
    return knobs.get("SORT_RETRY_BACKOFF")


def fallback_enabled() -> bool:
    """``SORT_FALLBACK`` (default on): the degradation ladder switch."""
    return knobs.get("SORT_FALLBACK")


def exchange_engine_knob() -> str:
    """``SORT_EXCHANGE_ENGINE`` (default auto): the exchange engine the
    ladder's first rung runs — resolution to a concrete impl (auto →
    pallas on TPU backends, lax elsewhere) lives in ``models/api.py``,
    which knows the backend; the pallas → lax rung below it is this
    module's ladder contract."""
    return knobs.get("SORT_EXCHANGE_ENGINE")


def verify_enabled() -> bool:
    """``SORT_VERIFY`` (default on): the always-on output verifier."""
    return knobs.get("SORT_VERIFY")


def wire_registry(reg: flt.FaultRegistry | None,
                  tracer: "Tracer") -> None:
    """Point a fault registry's ``on_fire`` at a tracer: every injected
    fault becomes a ``fault`` span event + a ``faults_injected`` count.
    Wired as early as possible in a run — the ingest-poison site fires
    inside the streaming pipeline, long before the dispatch supervisor
    exists."""
    if reg is None:
        return

    def _on_fault(site: str, detail: dict) -> None:
        tracer.count("faults_injected", 1)
        tracer.spans.record("fault", time.perf_counter(), 0.0,
                            site=site, **{k: v for k, v in detail.items()
                                          if k != "word"})
        # ISSUE 10: a firing fault site is an incident trigger — dump
        # the flight-recorder ring (rate-limited per site, so a chaos
        # grid documents each site once, not once per cell).
        from mpitest_tpu.utils import flight_recorder

        flight_recorder.dump_on_error(f"fault_{site}")

    reg.on_fire = _on_fault


class SortSupervisor:
    """Per-run supervisor: owns the retry budget, the fault registry
    hookup, and the shared cap-regrow loop.  One instance per sort()."""

    def __init__(self, tracer: "Tracer",
                 registry: "flt.FaultRegistry | None" = None,
                 plan: "SortPlan | None" = None) -> None:
        self.tracer = tracer
        self.registry = registry
        #: decision record (ISSUE 12): the supervisor is the layer that
        #: KNOWS how wrong a sizing decision was — overflow regrows and
        #: dispatch retries stamp their counts onto the plan here.
        self.plan = plan
        self.max_retries = max_retries()
        self.backoff = retry_backoff()
        wire_registry(registry, tracer)

    # -- fault arming -------------------------------------------------
    def squeeze_cap(self, cap: int, floor: int) -> int:
        """``cap_squeeze`` site: collapse the initial exchange cap to the
        alignment floor so the overflow-retry path runs for real."""
        if self.registry is not None and self.registry.fire(
                "cap_squeeze", cap=cap, floor=floor):
            return floor
        return cap

    def arm_exchange(self) -> str:
        """Compile token for the trace-time exchange faults ('' = clean,
        cache-shared compile)."""
        return flt.arm_exchange(self.registry)

    def _inject_dispatch_fault(self) -> None:
        import jax

        reg = self.registry
        if reg is None:
            return
        if reg.would_fire("dispatch_stall"):
            stall_ms = knobs.get("SORT_FAULT_STALL_MS")
            if reg.fire("dispatch_stall", stall_ms=stall_ms):
                # models a hung device call:
                # the SINGLE dispatch thread blocks here, which is
                # exactly what the serving watchdog exists to detect —
                # the sort itself still completes correctly afterwards
                time.sleep(stall_ms / 1e3)
        if reg.fire("dispatch_oom"):
            raise jax.errors.JaxRuntimeError(
                "RESOURCE_EXHAUSTED: injected fault (SORT_FAULTS=dispatch_oom)")
        if reg.fire("dispatch_error"):
            raise jax.errors.JaxRuntimeError(
                "INTERNAL: injected fault (SORT_FAULTS=dispatch_error)")

    # -- dispatch with bounded retry + backoff ------------------------
    def dispatch(self, label: str, fn: Callable[..., object],
                 args_fn: Callable[[], tuple[object, ...]],
                 on_retry: Callable[[], None] | None = None,
                 **attrs: object) -> object:
        """Run ``fn(*args_fn())`` under the retry budget.  ``args_fn`` is
        re-evaluated per attempt (donated buffers must be re-staged
        after a failed attempt — ``on_retry`` marks them dead so the
        caller's rebuild kicks in)."""
        import jax

        from mpitest_tpu.utils.programs import traced_call

        attempt = 0
        while True:
            try:
                self._inject_dispatch_fault()
                return traced_call(self.tracer, label, fn, *args_fn(),
                                    **attrs)
            except jax.errors.JaxRuntimeError as e:
                # an exchange fault armed for THIS dispatch may not have
                # been consumed (the program never traced) — drop it so
                # it cannot leak into a later clean compile.  It was
                # counted as injected at arm time but never touched
                # data: faults_dropped keeps the ledger honest.
                dropped = flt.drop_pending()
                if dropped:
                    self.tracer.count("faults_dropped", dropped)
                if attempt >= self.max_retries:
                    raise SortRetryExhausted(
                        f"{label} failed {attempt + 1} time(s); retry "
                        f"budget exhausted: {e}") from e
                delay = min(self.backoff * (2 ** attempt), 2.0)
                self.tracer.verbose(
                    f"{label} dispatch failed ({type(e).__name__}); "
                    f"retry {attempt + 1}/{self.max_retries} in {delay:.2f}s")
                self.tracer.count("sort_retries", 1)
                if self.plan is not None:
                    self.plan.bump("ladder", "dispatch_retries")
                self.tracer.spans.record(
                    "supervisor_retry", time.perf_counter(), 0.0,
                    label=label, attempt=attempt + 1,
                    error=type(e).__name__)
                if on_retry is not None:
                    on_retry()
                if delay:
                    time.sleep(delay)
                attempt += 1

    # -- the ONE cap-regrow loop --------------------------------------
    def exchange_loop(self, label: str,
                      attempt: "Callable[[int], tuple[object, int]]",
                      cap: int, align: int,
                      round_cap: Callable[[int, int], int],
                      cap_limit: int | None = None,
                      on_overflow: Callable[[], None] | None = None,
                      re_stage: Callable[[], None] | None = None,
                      ) -> tuple[object, int]:
        """Run ``attempt(cap) -> (payload, max_cnt)`` until the exchange
        fits; grow the cap to the reported need otherwise.  The cap only
        ever grows (bounded by the shard size), so the loop terminates.
        ``cap_limit``: raise :class:`ExchangeCapExceeded` when the need
        crosses it (the sample path's O(n) recv-memory bound).
        ``on_overflow``: invalidate donated input words before any
        rerun.  ``re_stage``: skew-aware rebalance hook (ISSUE 7) —
        invoked ONCE when the loop detects *persistent* imbalance (a
        second overflow regrow means the input arrangement, not a
        one-off estimate, is driving the cap); the callback interleaves
        the shards so per-peer counts collapse toward the fair share,
        and the already-grown cap is guaranteed to fit the rebalanced
        exchange."""
        regrows = 0
        while True:
            payload, max_cnt = attempt(cap)
            if max_cnt <= cap:
                return payload, cap
            need = round_cap(max_cnt, align)
            if on_overflow is not None:
                on_overflow()
            if cap_limit is not None and need > cap_limit:
                raise ExchangeCapExceeded(max_cnt, cap_limit)
            regrows += 1
            if self.plan is not None:
                # each regrow is a full discarded exchange dispatch —
                # the unit of cap-regret the explain view reports
                self.plan.bump("cap", "regrows")
            if re_stage is not None and regrows >= 2:
                self.tracer.verbose(
                    f"{label} exchange overflowed {regrows} times "
                    "(persistent imbalance); re-staging shards")
                if self.plan is not None:
                    self.plan.decide("restage", chosen=True,
                                     trigger="overflow")
                re_stage()
                re_stage = None  # once per run
            self.tracer.verbose(
                f"{label} exchange overflow (need {max_cnt} > cap {cap}); "
                "retrying")
            self.tracer.count("exchange_retries", 1)
            cap = need
