"""The context every per-layer metric reader gets, and the loop that runs
the cell's readers after a traced window.

A reader is `bench/metrics/<metric>.py` with `read(ctx) -> float | None`;
None means it found nothing to read, and the metric is left out of the
line. A reader never returns 0 for a share of a roofline or a peak.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from bench import trace as tr
from bench.spec import BENCH_DIR


def peaks_for(kind: str, bench_dir=BENCH_DIR) -> Dict[str, float]:
    """The device's published peaks; a device not in the table is an
    error, never a default."""
    with open(bench_dir / "peaks.json") as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[kind]


@dataclass
class Ctx:
    cell: Any
    reduced: Optional[tr.Reduced]     # None when no trace could be read
    lo: float                         # the window on the trace's clock, ns
    hi: float
    peaks: Dict[str, float]
    run: Dict[str, Any] = field(default_factory=dict)   # the driver's record
    work: Dict[str, Any] = field(default_factory=dict)  # analytic counts

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def ops(self) -> List[tr.Event]:
        if self.reduced is None:
            return []
        return tr.within(self.reduced.device.ops, self.lo, self.hi)

    @property
    def modules(self) -> List[tr.Event]:
        if self.reduced is None:
            return []
        return tr.within(self.reduced.device.modules, self.lo, self.hi)

    def busy_s(self) -> float:
        """Union of operation time, averaged over the devices traced."""
        devs = self.reduced.devices.values()
        return sum(tr.busy_ns(tr.within(d.ops, self.lo, self.hi))
                   for d in devs) / max(len(devs), 1) * 1e-9

    def executions(self, kernel: str, also: Optional[str] = None
                   ) -> List[Tuple[tr.Event, List[tr.Event]]]:
        """Program executions in the window that hold an op of `kernel`
        (and of `also`, where given), each with its ops."""
        mods = self.modules
        want = [k for k in (kernel, also) if k]
        return [(m, ops) for m, ops in zip(mods, tr.inside(self.ops, mods))
                if all(any(is_kernel(o[0], k) for o in ops) for k in want)]

    def decode_kernel(self) -> str:
        """The attention kernel that marks the decode program."""
        return ("paged_attention_quant" if self.run.get("kv_bits")
                else "paged_attention")

    def spans(self, name: str) -> List[Dict[str, Any]]:
        """The program's own Tracer spans of that name that start inside
        the window (host clock)."""
        win = self.run.get("window", {})
        lo, hi = win.get("t0", 0.0) * 1e6, win.get("t1", 0.0) * 1e6
        return [e for e in self.run.get("spans", [])
                if e.get("ph") == "X" and e["name"] == name
                and lo <= e["ts"] < hi]

    def roofline(self, kernel: str, work: List[Tuple[float, float]],
                 also: Optional[str] = None) -> Optional[float]:
        """Percent of its roofline a kernel reached over the window: the
        least time the chip could take for each step's calls (the larger
        of flops over the bf16 peak and bytes over HBM bandwidth), summed,
        over the kernel's device time in the matching program executions
        (those that also hold `also`, where given). None when the trace
        holds no such execution, or not one per step."""
        execs = self.executions(kernel, also)
        if not execs or len(execs) != len(work):
            return None
        least = sum(max(f / self.peaks["bf16_flops"],
                        b / self.peaks["hbm_bytes_per_s"]) for f, b in work)
        spent = sum(o[2] for _, ops in execs for o in ops
                    if is_kernel(o[0], kernel)) * 1e-9
        return 100.0 * least / spent if spent > 0 else None


def is_kernel(op_name: str, kernel: str) -> bool:
    """An op is a kernel's call when named `<kernel>` or `<kernel>.<n>`."""
    return op_name == kernel or (op_name.startswith(kernel + ".")
                                 and op_name[len(kernel) + 1:].isdigit())


def per_layer(cell, outcome, driver):
    """Run every per-layer reader of the cell on the traced window.
    Returns (metrics, breakdown, {"busy_s", "window_s"})."""
    import time
    from bench.harness import log
    from bench.spec import load_reader
    t0 = time.time()
    run = outcome.trace or {}
    info = run.get("window", {})
    path = info.get("xplane")
    reduced = tr.load(path) if path else None
    log(f"trace: read {path} in {time.time() - t0:.3f} s")
    if reduced is not None and not reduced.devices:
        reduced = None               # a trace with no TPU plane
    lo = hi = 0.0
    if reduced is not None:
        span = tr.host_window(reduced.host, "bench.window")
        if span is not None:
            lo, hi = span
    ctx = Ctx(cell, reduced, lo, hi, peaks_for(outcome.device["kind"], cell.bench_dir),
              run=run)
    if hasattr(driver, "work"):
        ctx.work = driver.work(cell, run)
    metrics: Dict[str, float] = {}
    for m in cell.per_layer:
        value = load_reader(cell, m).read(ctx)
        if value is not None:
            metrics[m.name] = float(value)
    busy = {"busy_s": 0.0, "window_s": ctx.window_s}
    breakdown = None
    if reduced is not None and hi > lo:
        busy["busy_s"] = ctx.busy_s()
        breakdown = {
            "device_ops": tr.top_ops(ctx.ops),
            "idle_gaps": tr.idle_gaps(reduced.device.ops, reduced.host,
                                      lo, hi)}
    log(f"trace: reduced in {time.time() - t0:.3f} s")
    return metrics, breakdown, busy
