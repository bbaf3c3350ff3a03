"""Reduction of a profiler trace (`.xplane.pb`) to what the per-layer
metrics read: device busy time, every device operation and program
execution, host spans, and the breakdown of device time and idle gaps.

Device planes are those named `/device:TPU:<n>`; their "XLA Ops" line holds
one event per operation and "XLA Modules" one per program execution. Host
spans (the harness's and the program's `TraceAnnotation`s) are the events
of the `/host:CPU` plane. All times are nanoseconds on the trace's clock.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, float, float]           # (name, start_ns, duration_ns)


def op_name(text: str) -> str:
    """An op event's name: the HLO instruction's name, without the '%' and
    the rest of the instruction the TPU trace spells out after ' = '."""
    return text.split(" = ", 1)[0].lstrip("%")


@dataclass
class DeviceTrace:
    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)


@dataclass
class Reduced:
    devices: Dict[str, DeviceTrace]
    host: List[Event]

    @property
    def device(self) -> DeviceTrace:
        """The first device (one-chip cells)."""
        return self.devices[sorted(self.devices)[0]]


def load(path: Path) -> Reduced:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices: Dict[str, DeviceTrace] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dt = devices.setdefault(plane.name, DeviceTrace())
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dt.ops.extend((op_name(e.name), e.start_ns,
                                   e.duration_ns) for e in line.events)
                elif line.name == "XLA Modules":
                    dt.modules.extend((e.name, e.start_ns, e.duration_ns)
                                      for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events if e.duration_ns > 0)
    for dt in devices.values():
        dt.ops.sort(key=lambda e: e[1])
        dt.modules.sort(key=lambda e: e[1])
    host.sort(key=lambda e: e[1])
    return Reduced(devices, host)


def union(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals of the events."""
    out: List[List[float]] = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        e = s + d
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(a, b) for a, b in out]


def busy_ns(events: Iterable[Event]) -> float:
    return sum(b - a for a, b in union(events))


def within(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    """Events that start in [lo, hi)."""
    return [e for e in events if lo <= e[1] < hi]


def inside(ops: List[Event], spans: List[Event]) -> List[List[Event]]:
    """For each span (e.g. a program execution), the ops that start in it.
    Both lists sorted by start."""
    out: List[List[Event]] = [[] for _ in spans]
    i = 0
    for k, (_, s, d) in enumerate(spans):
        while i < len(ops) and ops[i][1] < s:
            i += 1
        j = i
        while j < len(ops) and ops[j][1] < s + d:
            out[k].append(ops[j])
            j += 1
        i = j
    return out


def top_ops(ops: List[Event], n: int = 10) -> List[List]:
    """The device operations that took most time, summed by name."""
    tot: Dict[str, float] = {}
    for name, _, d in ops:
        tot[name] = tot.get(name, 0.0) + d
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def idle_gaps(ops: List[Event], host: List[Event], lo: float, hi: float,
              n: int = 10) -> List[List]:
    """The device's idle time in [lo, hi), summed by the innermost host
    span open at each gap's middle ("no host span" where none is)."""
    gaps: List[Tuple[float, float]] = []
    t = lo
    for a, b in union(within(ops, lo, hi)):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    tot: Dict[str, float] = {}
    for (a, b), label in zip(gaps, _labels(host, [(a + b) / 2
                                                   for a, b in gaps])):
        label = label or "no host span"
        tot[label] = tot.get(label, 0.0) + (b - a)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def _labels(host: List[Event], times: List[float]) -> List[Optional[str]]:
    """For each time (ascending), the shortest host span open at it."""
    out: List[Optional[str]] = []
    active: List[Event] = []
    i = 0
    for t in times:
        while i < len(host) and host[i][1] <= t:
            active.append(host[i])
            i += 1
        active = [e for e in active if e[1] + e[2] >= t]
        out.append(min(active, key=lambda e: e[2])[0] if active else None)
    return out


def host_window(host: List[Event], label: str) -> Optional[Tuple[float, float]]:
    """[start, end) of the (first) host span named `label`."""
    for name, s, d in host:
        if name == label:
            return s, s + d
    return None
