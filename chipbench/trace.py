"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics and the ``breakdown`` read.

A TPU plane (``/device:TPU:<n>``) holds a line of XLA programs ("XLA
Modules", one event per call of a compiled program, named after the jitted
function) and a line of XLA operations ("XLA Ops"). Host planes hold the
harness's own ``TraceAnnotation`` spans and JAX's host events, on the same
clock. Times are nanoseconds.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES_LINE, OPS_LINE = "XLA Modules", "XLA Ops"


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class DeviceTrace:
    index: int
    modules: list[Span]
    ops: list[Span]


@dataclasses.dataclass
class Trace:
    devices: list[DeviceTrace]
    host: list[Span]
    window: tuple[float, float]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self, dev: DeviceTrace) -> float:
        return union_ns(dev.ops or dev.modules, self.window) * 1e-9

    def mean_busy_s(self) -> float:
        if not self.devices:
            return 0.0
        return sum(self.busy_s(d) for d in self.devices) / len(self.devices)

    def calls(self, dev: DeviceTrace, program: str) -> list[Span]:
        """Calls of the compiled program whose name contains ``program``."""
        return [m for m in dev.modules if program in m.name
                and m.start >= self.window[0] and m.end <= self.window[1]]


def _spans(line) -> list[Span]:
    return [Span(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events if e.duration_ns > 0]


def reduce(profile, window_span: str | None = None) -> Trace:
    """``profile`` is a ``jax.profiler.ProfileData`` or a path to one.

    The window is the host span named ``window_span`` where one exists,
    else the extent of the device events."""
    from jax.profiler import ProfileData
    if isinstance(profile, (str, Path)):
        profile = ProfileData.from_file(str(profile))
    devices, host = [], []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        lines = {ln.name: ln for ln in plane.lines}
        if m:
            devices.append(DeviceTrace(
                int(m.group(1)),
                _spans(lines[MODULES_LINE]) if MODULES_LINE in lines else [],
                _spans(lines[OPS_LINE]) if OPS_LINE in lines else []))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend(_spans(ln))
    devices.sort(key=lambda d: d.index)
    marked = [s for s in host if s.name == window_span]
    if marked:
        window = (marked[0].start, marked[0].end)
    else:
        ev = [s for d in devices for s in d.ops + d.modules]
        window = (min(s.start for s in ev), max(s.end for s in ev)) if ev else (0.0, 0.0)
    return Trace(devices, host, window)


def merged(spans: list[Span], window: tuple[float, float]) -> list[tuple[float, float]]:
    """Union of the spans, clipped to the window, as disjoint intervals."""
    lo, hi = window
    iv = sorted((max(s.start, lo), min(s.end, hi)) for s in spans
                if s.end > lo and s.start < hi)
    out: list[list[float]] = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def union_ns(spans: list[Span], window: tuple[float, float]) -> float:
    return sum(b - a for a, b in merged(spans, window))


def overlap_ns(a: Span, b: Span) -> float:
    return max(0.0, min(a.end, b.end) - max(a.start, b.start))


def idle_gaps(trace: Trace, dev: DeviceTrace, n: int = 10,
              skip: tuple[str, ...] = ()) -> list[list]:
    """The ``n`` longest stretches of the window in which no operation ran on
    ``dev``, each named by the host span that covers most of it (the
    shortest such span on a tie), spans named in ``skip`` left out."""
    busy = merged(dev.ops or dev.modules, trace.window)
    edges = [trace.window[0]] + [x for iv in busy for x in iv] + [trace.window[1]]
    gaps = [Span("", edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: -g.dur)
    out = []
    for g in gaps[:n]:
        cover = [(overlap_ns(h, g), -h.dur, h.name) for h in trace.host
                 if h.name not in skip and overlap_ns(h, g) > 0]
        name = max(cover)[2] if cover else "no host span"
        out.append([name, g.dur * 1e-9])
    return out


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    """The ``n`` device operations that took most time in the window, summed
    by name and averaged over the devices."""
    tot: dict[str, float] = {}
    for dev in trace.devices:
        for o in dev.ops:
            t = overlap_ns(o, Span("", *trace.window))
            if t > 0:
                tot[o.name] = tot.get(o.name, 0.0) + t
    k = max(len(trace.devices), 1)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, t * 1e-9 / k] for name, t in ranked]
