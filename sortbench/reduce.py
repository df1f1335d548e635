"""Reduce a JAX profiler trace (``.xplane.pb``) to device intervals.

What it keeps, per device plane (``/device:TPU:<n>``): every event of
the ``XLA Ops`` and ``Async XLA Ops`` lines, as (start, end, HLO text).
From the host planes it keeps the harness's ``sortbench.call``
annotations, whose union is the traced window, and the other events of
the host line that holds them: the calling thread's Python frames
(JAX's Python tracer), which name what the host was doing in each idle
gap.  The window is the calls alone: what the harness does between two
calls (the check of the answer) lies outside it, and no reading counts
an op or a frame there.

All times share the trace's clock (ns from the trace's start).  Busy
time is the union of op intervals inside the window; idle is the rest.

``python -m sortbench.reduce <file.xplane.pb>`` prints a summary of a
trace: the planes, lines and heaviest events, for checking op names by
hand.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import sys
from collections import defaultdict
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OP_LINES = ("XLA Ops", "Async XLA Ops")
CALL_ANNOTATION = "sortbench.call"
_SHORT = re.compile(r"^(%?[^ =]+) = .*?\s([a-z][\w\-]*)\(")


def short_name(hlo: str) -> str:
    """``%f.27 = s32[...] custom-call(...), ...`` -> ``%f.27 custom-call``."""
    m = _SHORT.match(hlo)
    return f"{m.group(1)} {m.group(2)}" if m else hlo[:80]


Intervals = list[tuple[float, float]]


def union(iv) -> Intervals:
    """Sorted union of (start, end) intervals; empty ones dropped."""
    out: list[list[float]] = []
    for s, e in sorted(iv):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def intersect(a: Intervals, b: Intervals) -> Intervals:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def complement(a: Intervals, window: Intervals) -> Intervals:
    """The parts of ``window`` that the sorted disjoint ``a`` leaves
    uncovered."""
    out, i = [], 0
    for lo, hi in window:
        t = lo
        while i < len(a) and a[i][1] <= lo:
            i += 1
        j = i
        while j < len(a) and a[j][0] < hi:
            if a[j][0] > t:
                out.append((t, a[j][0]))
            t = max(t, a[j][1])
            j += 1
        if hi > t:
            out.append((t, hi))
    return out


def overlap(s: float, e: float, window: Intervals) -> float:
    """Length of [s, e) inside the sorted disjoint ``window``."""
    k = max(0, bisect.bisect_right(window, (s, float("inf"))) - 1)
    total = 0.0
    while k < len(window) and window[k][0] < e:
        total += max(0.0, min(e, window[k][1]) - max(s, window[k][0]))
        k += 1
    return total


@dataclass
class Op:
    start: float  # ns
    end: float
    name: str     # the full HLO text of the op


@dataclass
class Device:
    name: str
    ops: list[Op] = field(default_factory=list)

    def merged(self, window: Intervals) -> Intervals:
        """Union of op intervals clipped to the window, sorted."""
        return intersect(union((o.start, o.end) for o in self.ops), window)

    def busy_ns(self, window: Intervals) -> float:
        return sum(e - s for s, e in self.merged(window))

    def gaps(self, window: Intervals) -> Intervals:
        """The window's idle parts: no op ran."""
        return complement(self.merged(window), window)


@dataclass
class HostEvent:
    start: float
    end: float
    name: str


@dataclass
class Trace:
    devices: list[Device]
    host: list[HostEvent]
    window: Intervals  # the calls' union: sorted, disjoint, ns, trace clock
    calls: int         # sortbench.call annotations in the window

    @property
    def window_s(self) -> float:
        """The window's length: the calls' summed durations."""
        return sum(e - s for s, e in self.window) * 1e-9

    def busy_s(self, dev: Device) -> float:
        return dev.busy_ns(self.window) * 1e-9

    @property
    def busy_s_mean(self) -> float:
        return sum(self.busy_s(d) for d in self.devices) / len(self.devices)

    def busiest(self) -> Device:
        return max(self.devices, key=self.busy_s)

    def op_seconds(self, dev: Device, match) -> float:
        """Device seconds of the ops on ``dev`` whose HLO text ``match``
        accepts, inside the window."""
        return sum(overlap(o.start, o.end, self.window) * 1e-9
                   for o in dev.ops if match(o.name))

    def breakdown(self, top: int = 10) -> dict[str, list[list]]:
        """The busiest device's heaviest ops, and its idle time split by
        what the calling thread was doing: each idle instant goes to the
        innermost frame then running on that thread (its self time).
        The ``top`` largest of each, in seconds."""
        dev = self.busiest()
        ops: dict[str, float] = defaultdict(float)
        for o in dev.ops:
            d = overlap(o.start, o.end, self.window)
            if d > 0:
                ops[short_name(o.name)] += d * 1e-9
        idle: dict[str, float] = defaultdict(float)
        frames = self_intervals(self.host)
        i = 0
        for s, e in dev.gaps(self.window):
            covered = 0.0
            while i < len(frames) and frames[i][1] <= s:
                i += 1
            j = i
            while j < len(frames) and frames[j][0] < e:
                ov = min(frames[j][1], e) - max(frames[j][0], s)
                if ov > 0:
                    idle[frames[j][2]] += ov * 1e-9
                    covered += ov
                j += 1
            if e - s > covered:
                idle["no Python frame on the calling thread"] += \
                    (e - s - covered) * 1e-9

        def biggest(d):
            return [[k, v] for k, v in
                    sorted(d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": biggest(ops), "idle_gaps": biggest(idle)}


def self_intervals(events: list[HostEvent]) -> list[tuple[float, float, str]]:
    """The self time of nested events on one thread: each event's
    interval minus its children's, as sorted (start, end, name)."""
    out: list[tuple[float, float, str]] = []
    stack: list[list] = []  # [event, cursor]: self time runs from cursor

    def close(upto: float) -> None:
        while stack and stack[-1][0].end <= upto:
            ev, cur = stack.pop()
            if ev.end > cur:
                out.append((cur, ev.end, ev.name.lstrip("$")))
            if stack:
                stack[-1][1] = ev.end

    for ev in sorted(events, key=lambda e: (e.start, -e.end)):
        close(ev.start)
        if stack:
            parent = stack[-1]
            if ev.start > parent[1]:
                out.append((parent[1], ev.start, parent[0].name.lstrip("$")))
            parent[1] = max(parent[1], ev.end)
        stack.append([ev, ev.start])
    close(float("inf"))
    out.sort()
    return out


def reduce_file(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: list[Device] = []
    host: list[HostEvent] = []
    calls: list[HostEvent] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = Device(plane.name)
            for line in plane.lines:
                if line.name in OP_LINES:
                    dev.ops.extend(Op(e.start_ns, e.start_ns + e.duration_ns,
                                      e.name) for e in line.events)
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [HostEvent(e.start_ns, e.start_ns + e.duration_ns,
                                    e.name) for e in line.events]
                mine = [e for e in events if e.name == CALL_ANNOTATION]
                if mine:  # the calling thread's line: its Python frames
                    calls.extend(mine)
                    host.extend(e for e in events
                                if e.name != CALL_ANNOTATION)
    devices.sort(key=lambda d: int(DEVICE_PLANE.match(d.name).group(2)))
    if not devices:
        raise ValueError(f"sortbench: no device plane in {path}")
    if calls:
        window = union((c.start, c.end) for c in calls)
    else:
        ops = [o for d in devices for o in d.ops]
        window = [(min(o.start for o in ops), max(o.end for o in ops))]
    return Trace(devices, host, window, len(calls))


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise ValueError(f"sortbench: expected one .xplane.pb under "
                         f"{trace_dir}, found {found}")
    return found[0]


def reduce_dir(trace_dir: str) -> Trace:
    return reduce_file(find_xplane(trace_dir))


def summary(path: str, top: int = 25) -> str:
    """Planes, lines and heaviest events of a trace, for reading by hand."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            agg: dict[str, list[float]] = defaultdict(lambda: [0, 0.0])
            for e in line.events:
                agg[e.name][0] += 1
                agg[e.name][1] += e.duration_ns * 1e-6
            out.append(f"  LINE {line.name!r}: {sum(a[0] for a in agg.values())}"
                       " events")
            for name, (n, ms) in sorted(agg.items(),
                                        key=lambda kv: -kv[1][1])[:top]:
                out.append(f"    {ms:12.3f} ms x{n:<5d} {name[:400]}")
    return "\n".join(out)


if __name__ == "__main__":
    print(summary(sys.argv[1]))
