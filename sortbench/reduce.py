"""Reduce a JAX profiler trace (``.xplane.pb``) to device intervals.

What it keeps, per device plane (``/device:TPU:<n>``): every event of
the ``XLA Ops`` and ``Async XLA Ops`` lines, as (start, end, HLO text).
From the host planes it keeps the harness's ``sortbench.call``
annotations, which fix the traced window (first call's start to last
call's end), and the other events of the host line that holds them:
the calling thread's Python frames (JAX's Python tracer), which name
what the host was doing in each idle gap.

All times share the trace's clock (ns from the trace's start).  Busy
time is the union of op intervals inside the window; idle is the rest.

``python -m sortbench.reduce <file.xplane.pb>`` prints a summary of a
trace: the planes, lines and heaviest events, for checking op names by
hand.
"""

from __future__ import annotations

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


@dataclass
class Op:
    start: float  # ns
    end: float
    name: str     # the full HLO text of the op


@dataclass
class Device:
    name: str
    ops: list[Op] = field(default_factory=list)

    def merged(self, lo: float, hi: float) -> list[tuple[float, float]]:
        """Union of op intervals clipped to [lo, hi], sorted."""
        out: list[list[float]] = []
        for s, e in sorted((max(o.start, lo), min(o.end, hi))
                           for o in self.ops):
            if e <= s:
                continue
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return [(s, e) for s, e in out]

    def busy_ns(self, lo: float, hi: float) -> float:
        return sum(e - s for s, e in self.merged(lo, hi))

    def gaps(self, lo: float, hi: float) -> list[tuple[float, float]]:
        out, t = [], lo
        for s, e in self.merged(lo, hi):
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if hi > t:
            out.append((t, hi))
        return out


@dataclass
class HostEvent:
    start: float
    end: float
    name: str


@dataclass
class Trace:
    devices: list[Device]
    host: list[HostEvent]
    window: tuple[float, float]  # ns, trace clock
    calls: int                   # sortbench.call annotations in the window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self, dev: Device) -> float:
        return dev.busy_ns(*self.window) * 1e-9

    @property
    def busy_s_mean(self) -> float:
        return sum(self.busy_s(d) for d in self.devices) / len(self.devices)

    def busiest(self) -> Device:
        return max(self.devices, key=self.busy_s)

    def op_seconds(self, dev: Device, match) -> float:
        """Device seconds of the ops on ``dev`` whose HLO text ``match``
        accepts, inside the window."""
        lo, hi = self.window
        return sum(max(0.0, min(o.end, hi) - max(o.start, lo)) * 1e-9
                   for o in dev.ops if match(o.name))

    def breakdown(self, top: int = 10) -> dict[str, list[list]]:
        """The busiest device's heaviest ops, and its idle time split by
        what the calling thread was doing: each idle instant goes to the
        innermost frame then running on that thread (its self time).
        The ``top`` largest of each, in seconds."""
        dev = self.busiest()
        lo, hi = self.window
        ops: dict[str, float] = defaultdict(float)
        for o in dev.ops:
            d = min(o.end, hi) - max(o.start, lo)
            if d > 0:
                ops[short_name(o.name)] += d * 1e-9
        idle: dict[str, float] = defaultdict(float)
        frames = self_intervals(self.host)
        i = 0
        for s, e in dev.gaps(lo, hi):
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
        window = (min(c.start for c in calls), max(c.end for c in calls))
    else:
        ops = [o for d in devices for o in d.ops]
        window = (min(o.start for o in ops), max(o.end for o in ops))
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
