"""Device idle time pinned on the program's own host phases.

The program opens each host phase as a `repro.obs.Tracer` span, which is
also a profiler TraceAnnotation of the span's bare name, so its spans lie
on the device trace's clock among the harness's own (`bench/trace.py`
reads them into `Reduced.host`). Idle is the traced window [ctx.lo,
ctx.hi) less the union of the first device's operations that start in it,
as `device.idle.*` counts it; a phase's idle is that idle intersected with
the union of the phase's spans. Shares are of the window, in percent, so
the shares of disjoint phases add up toward `device.idle.*`.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Tuple

from bench import trace as tr

Interval = Tuple[float, float]

SYNC = "quant.sync."
TRACE_BLOCK = "quant.sync.trace_block"   # made only with tracing on


def named(*names: str) -> Callable[[str], bool]:
    return lambda name: name in names


def is_sync(name: str) -> bool:
    """A host sync of the quantize walk that the untraced walk makes too."""
    return name.startswith(SYNC) and name != TRACE_BLOCK


def spans(ctx, want: Callable[[str], bool]) -> List[tr.Event]:
    """Host spans whose name `want` accepts that overlap the window."""
    if ctx.reduced is None:
        return []
    return [e for e in ctx.reduced.host
            if want(e[0]) and e[1] < ctx.hi and e[1] + e[2] > ctx.lo]


def idle(ctx) -> List[Interval]:
    """The window's idle intervals on the first device, in order."""
    return subtract([(ctx.lo, ctx.hi)], tr.union(ctx.ops))


def intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Intersection of two ordered lists of disjoint intervals."""
    out: List[Interval] = []
    i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """`a` less `b`, both ordered lists of disjoint intervals."""
    out: List[Interval] = []
    j = 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > lo:
                out.append((lo, b[k][0]))
            lo = max(lo, b[k][1])
            k += 1
        if lo < hi:
            out.append((lo, hi))
    return out


def idle_share(ctx, inside: List[Interval]) -> float:
    """Percent of the window that is idle and inside `inside`."""
    t = sum(hi - lo for lo, hi in intersect(idle(ctx), inside))
    return 100.0 * t / (ctx.hi - ctx.lo)


def phase_idle(ctx, want: Callable[[str], bool]) -> Optional[float]:
    """Idle share inside the spans `want` accepts; None when the window
    holds none of them (a program without such spans reads null, not 0)."""
    if ctx.reduced is None or ctx.hi <= ctx.lo:
        return None
    found = spans(ctx, want)
    if not found:
        return None
    return idle_share(ctx, tr.union(found))
