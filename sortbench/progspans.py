"""What the program marks in a traced run, for the per-layer readers.

Two marks, both on the trace's own clock:

* span annotations: each span of the program (``phase:*``, the children
  of ``phase:verify`` and ``phase:decode``) is a profiler annotation on
  the calling thread's host line, which :mod:`sortbench.reduce` keeps
  as ``run.trace.host``;
* layer tags: each device op carries ``layer="<name>"`` in its HLO text
  (``run.trace.devices[*].ops``).

Every share is of the traced window, the union of the harness's
``sortbench.call`` intervals (:mod:`sortbench.reduce`): a span or an
idle instant between two calls counts toward none.

Every helper returns None where the trace holds no such mark — a
program that opens no annotation or tags no op — so the reader leaves
its metric out instead of reporting 0.
"""

from __future__ import annotations

from sortbench import reduce

PHASE_PREFIX = "phase:"


def annotations(run, match) -> list[tuple[float, float]] | None:
    """Union, inside the window, of the calling thread's annotations
    whose name ``match`` accepts; None without a trace or a match."""
    tr = run.trace
    if tr is None:
        return None
    iv = [(e.start, e.end) for e in tr.host if match(e.name)]
    return reduce.intersect(reduce.union(iv), tr.window) if iv else None


def annotation_share_pct(run, name: str) -> float | None:
    """Share of the window, in %, inside the annotations ``name``."""
    iv = annotations(run, lambda n: n == name)
    if iv is None:
        return None
    return 100.0 * sum(e - s for s, e in iv) * 1e-9 / run.trace.window_s


def layer_share_pct(run, layer: str) -> float | None:
    """Device time of the busiest chip's ops tagged ``layer``, as a share
    of the window in %; None when no op carries the tag."""
    tr = run.trace
    if tr is None:
        return None
    tag = f'layer="{layer}"'
    dev = tr.busiest()
    if not any(tag in o.name for o in dev.ops):
        return None
    return 100.0 * tr.op_seconds(dev, lambda h: tag in h) / tr.window_s


def idle_outside_pct(run, prefix: str = PHASE_PREFIX) -> float | None:
    """Share of the window, in %, in which the busiest chip ran no op
    and no annotation named ``prefix*`` was open on the calling thread:
    idle time no program layer names."""
    covered = annotations(run, lambda n: n.startswith(prefix))
    if covered is None:
        return None
    tr = run.trace
    idle = tr.busiest().gaps(tr.window)
    inside = 0.0
    j = 0
    for s, e in idle:
        while j < len(covered) and covered[j][1] <= s:
            j += 1
        k = j
        while k < len(covered) and covered[k][0] < e:
            inside += max(0.0, min(e, covered[k][1]) - max(s, covered[k][0]))
            k += 1
    outside = sum(e - s for s, e in idle) - inside
    return 100.0 * outside * 1e-9 / tr.window_s
