"""Named, layer-tagged device programs and the compile truth of a call.

Three things the device trace needs from the program, in one place:

* **Stable names.**  :func:`program` gives a jitted body a fixed
  function name, so its XLA module reads ``jit_<name>`` in the trace
  (``XLA Modules`` line) instead of ``jit_f``.
* **Layer tags.**  :func:`layer` sets the XLA frontend attribute
  ``layer=<name>`` on every op traced inside it; the trace's op text
  shows it (``frontend_attributes={...,layer="verify"}``).  Inner tags
  override outer ones, so a program tagged ``exchange`` that calls the
  local sort keeps ``local_sort`` on the sort's ops.  The values are the
  layers of PERF.md: :data:`LAYERS`.  :func:`stage` adds a second
  attribute, ``stage=<name>``, to a part of one layer's program that a
  metric reads on its own (:data:`STAGES`); the ``layer`` tag stays.
* **Compile truth.**  :func:`traced_call` runs one jitted call under a
  ``jit_execute`` span and renames it ``jit_compile_execute`` when JAX
  reported a backend compile (or a persistent-cache load) on the calling
  thread during the call — a warm callable meeting a new shape included.
  One ``jax.monitoring`` listener, registered once, keeps a per-thread
  count.  The span's profiler annotation keeps the name it opened with
  (``jit_execute``); JAX's own compile frames sit inside it.
"""

from __future__ import annotations

import functools
import threading
from typing import Any, Callable

#: the layers an op may be tagged with (PERF.md §3)
LAYERS = ("local_sort", "exchange", "verify", "ingest", "egress")

#: the stages an op may be tagged with inside its layer (PERF.md §3):
#: ``pair_fixup`` is the pair engine's run fix-up and its residual and
#: high-word tie reduction
STAGES = ("pair_fixup",)

#: JAX's monitoring event for one XLA backend compile or persistent-cache
#: load (``jax._src.dispatch.BACKEND_COMPILE_EVENT``)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_tls = threading.local()
_listen_lock = threading.Lock()
_listening = False


def layer(name: str) -> Any:
    """Context manager: tag every op traced inside with ``layer=<name>``."""
    if name not in LAYERS:
        raise ValueError(f"unknown layer {name!r}; use one of {LAYERS}")
    from jax.experimental.xla_metadata import set_xla_metadata

    return set_xla_metadata(layer=name)


def stage(name: str) -> Any:
    """Context manager: tag every op traced inside with ``stage=<name>``
    beside the ``layer`` tag it already carries."""
    if name not in STAGES:
        raise ValueError(f"unknown stage {name!r}; use one of {STAGES}")
    from jax.experimental.xla_metadata import set_xla_metadata

    return set_xla_metadata(stage=name)


def tagged(layer_name: str) -> Callable[[Callable[..., Any]],
                                       Callable[..., Any]]:
    """Decorator: every op the function traces is tagged ``layer_name``
    (for traced helpers that several programs share)."""
    def deco(fn: Callable[..., Any]) -> Callable[..., Any]:
        @functools.wraps(fn)
        def run(*args: Any, **kwargs: Any) -> Any:
            with layer(layer_name):
                return fn(*args, **kwargs)
        return run
    return deco


def program(name: str, layer_name: str,
            fn: Callable[..., Any]) -> Callable[..., Any]:
    """``fn`` renamed ``name`` with every op it traces tagged
    ``layer_name`` — wrap it in ``jax.jit`` (or ``jax.shard_map`` first,
    which keeps the name) to get the module ``jit_<name>``."""
    body = tagged(layer_name)(fn)
    body.__name__ = body.__qualname__ = name
    return body


def _on_event(event: str, duration: float, **_: Any) -> None:
    if event == BACKEND_COMPILE_EVENT:
        _tls.compiles = getattr(_tls, "compiles", 0) + 1


def _thread_compiles() -> int:
    """Backend compiles JAX has reported on the calling thread since the
    listener was registered (on the first call of this function)."""
    global _listening
    if not _listening:
        with _listen_lock:
            if not _listening:
                import jax

                jax.monitoring.register_event_duration_secs_listener(
                    _on_event)
                _listening = True
    return getattr(_tls, "compiles", 0)


def traced_call(tracer: Any, label: str, fn: Callable[..., Any],
                *args: Any, **attrs: object) -> Any:
    """Call a jitted program under a span that separates a call that
    compiled (``jit_compile_execute``, counted in the tracer's
    ``jit_first_calls``) from a warm one (``jit_execute``)."""
    c0 = _thread_compiles()
    with tracer.spans.span("jit_execute", label=label, **attrs) as sp:
        out = fn(*args)
        compiled = _thread_compiles() > c0
        if compiled:
            sp.name = "jit_compile_execute"
    if compiled:
        tracer.count("jit_first_calls", 1)
    return out
