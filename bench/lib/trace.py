"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The trace is read with ``jax.profiler.ProfileData`` (events with a start
and a duration in nanoseconds).  Device planes are ``/device:TPU:<n>``;
their ``XLA Ops`` line holds the operations that ran, their ``XLA Modules``
line one event per launched program.  The host's spans are the
benchmark's own ``TraceAnnotation`` events: ``window`` around the measured
window, and ``flush`` and ``ingest`` inside it.  Host and device events
share the profiler's clock.

Busy time is the union of the device's operation intervals inside the
window; idle time is the rest of the window.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
SPANS = ("window", "flush", "ingest")


def union(intervals):
    """Merged, sorted, non-overlapping (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged, lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` covered by ``merged`` intervals."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


@dataclass
class Device:
    ops: list = field(default_factory=list)       # (start, end, name)
    modules: list = field(default_factory=list)   # (start, end, name)


@dataclass
class Trace:
    """Intervals in seconds on the profiler's clock."""

    devices: list[Device] = field(default_factory=list)
    spans: dict = field(default_factory=dict)     # name -> [(start, end)]

    @property
    def window(self):
        w = self.spans.get("window")
        return (w[0][0], w[0][1]) if w else None

    def busy_s(self) -> float | None:
        """Device busy seconds in the window, averaged over the devices."""
        win = self.window
        if not self.devices or win is None:
            return None
        per = [covered(union((s, e) for s, e, _ in d.ops), *win)
               for d in self.devices]
        return sum(per) / len(per)

    def window_s(self) -> float | None:
        win = self.window
        return None if win is None else win[1] - win[0]

    def module_time(self, pattern: str):
        """(seconds, launches) of device programs whose name holds
        ``pattern``, inside the window, summed over devices."""
        win = self.window
        secs, n = 0.0, 0
        for d in self.devices:
            for s, e, name in d.modules:
                if pattern in name and win[0] <= s <= win[1]:
                    secs += e - s
                    n += 1
        return secs, n

    def host_minus_device(self, span: str) -> list[float]:
        """Per span: its length minus the device busy time inside it
        (first device)."""
        if not self.devices:
            return []
        merged = union((s, e) for s, e, _ in self.devices[0].ops)
        return [(e - s) - covered(merged, s, e)
                for s, e in self.spans.get(span, [])]

    def top_ops(self, n: int = 10):
        """The device operations that took most time in the window."""
        win = self.window
        tot: dict[str, float] = {}
        for d in self.devices:
            for s, e, name in d.ops:
                if win[0] <= s <= win[1]:
                    tot[name] = tot.get(name, 0.0) + (e - s)
        return sorted(([k, v] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n: int = 10):
        """The longest device-idle gaps in the window, each named by the
        host span around its midpoint (``flush``, ``ingest`` or ``none``)
        and its offset into the window."""
        win = self.window
        if not self.devices or win is None:
            return []
        merged = union((max(s, win[0]), min(e, win[1]))
                       for s, e, _ in self.devices[0].ops
                       if e > win[0] and s < win[1])
        gaps, cur = [], win[0]
        for s, e in merged:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < win[1]:
            gaps.append((cur, win[1]))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:n]:
            mid = (s + e) / 2
            name = next((span for span in ("flush", "ingest")
                         for a, b in self.spans.get(span, [])
                         if a <= mid <= b), "none")
            out.append([f"{name}@{s - win[0]:.3f}s", e - s])
        return out


def xplane_path(log_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def read(path: str) -> Trace:
    """Reduce one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    tr = Trace()
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = Device()
            for line in plane.lines:
                dest = {"XLA Ops": dev.ops,
                        "XLA Modules": dev.modules}.get(line.name)
                if dest is None:
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    dest.append((s, s + ev.duration_ns * 1e-9, ev.name))
            tr.devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPANS:
                        s = ev.start_ns * 1e-9
                        tr.spans.setdefault(ev.name, []).append(
                            (s, s + ev.duration_ns * 1e-9))
    for v in tr.spans.values():
        v.sort()
    return tr


def describe(path: str, per_line: int = 3) -> list[str]:
    """Planes, lines, event counts and a few event names: for reading a new
    platform's trace by hand."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            evs = list(line.events)
            names = sorted({e.name for e in evs[:2000]})[:per_line]
            out.append(f"{plane.name} | {line.name} | {len(evs)} | {names}")
    return out
