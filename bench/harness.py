"""What every run shares: the chip check, the compile cache and counter,
host annotations, the profiler window, and the result line.

Nothing here knows a model or a traffic mix; the drivers under
`bench/drivers/` do.
"""
from __future__ import annotations

import contextlib
import json
import math
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple


# a traced run profiles at most this much of its window (a decode cell its
# last stretch): a profiler trace of a 51 s decode window takes minutes to
# write and read
TRACE_SECONDS = 10.0


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def devices_for(chips: int, require_chip: bool = True):
    """The first `chips` devices. With `require_chip` (every real run) a
    platform other than TPU, or too few chips, raises NoChip: there is no
    fallback to the CPU."""
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r}); "
                     "the benchmark runs on the chip only")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devs)}")
    return devs[:chips]


def device_info(devs) -> Dict[str, Any]:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": max(peaks)}


def stage(t_start: float, label: str) -> None:
    """Seconds since the process started, at a stage of set-up."""
    log(f"stage {label}: {time.time() - t_start:.3f} s")


def memory_note(devs, label: str) -> None:
    """Bytes in use and peak on the first device, on standard error."""
    stats = devs[0].memory_stats() or {}
    log(f"memory [{label}]: in use {stats.get('bytes_in_use', 0)}, peak "
        f"{stats.get('peak_bytes_in_use', 0)}, limit "
        f"{stats.get('bytes_limit', 0)}")


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at one fixed path inside the
    checkout, `<root>/.jax_cache` (the directory `repro.launch.jax_cache`
    also uses there), whatever the environment says: the path is part of
    every cache key, and a cache outside the checkout could be shared with
    another checkout. Every program is cached, also those that compile in
    under a second, so only a checkout's first run of a cell compiles."""
    import jax
    where = str(Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileCounter:
    """Counts backend compilations (and persistent-cache hits) as JAX
    reports them; `mark()` starts a fresh count, e.g. at window open."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self._event = dispatch.BACKEND_COMPILE_EVENT
        self.compiles = 0
        self.compile_s = 0.0
        self._marked = (0, 0.0)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == self._event:
            self.compiles += 1
            self.compile_s += secs

    def mark(self) -> None:
        self._marked = (self.compiles, self.compile_s)

    def since_mark(self) -> Tuple[int, float]:
        return (self.compiles - self._marked[0],
                self.compile_s - self._marked[1])


def annotate(label: str):
    """A host span in the profiler's own trace (a no-op TraceMe when no
    profiler is attached). The harness wraps each call into a layer."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(label)


@contextlib.contextmanager
def profiled(enabled: bool, out_dir: Path):
    """Collect a profiler trace of the block into `out_dir` when enabled;
    yields a dict that receives the trace file's path and the block's
    host-clock bounds."""
    info: Dict[str, Any] = {"xplane": None}
    if not enabled:
        info["t0"] = time.time()
        yield info
        info["t1"] = time.time()
        return
    import jax
    shutil.rmtree(out_dir, ignore_errors=True)
    out_dir.mkdir(parents=True, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # no per-call Python events
    opts.host_tracer_level = 2       # TraceAnnotation spans and runtime
    jax.profiler.start_trace(str(out_dir), profiler_options=opts)
    try:
        info["t0"] = time.time()
        yield info
        info["t1"] = time.time()
    finally:
        t_stop = time.time()
        jax.profiler.stop_trace()
        log(f"trace: stopped and written in {time.time() - t_stop:.3f} s")
    found = sorted(out_dir.rglob("*.xplane.pb"))
    info["xplane"] = found[-1] if found else None


@dataclass
class Check:
    """One number that `correct` compares, beside its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        """The value is finite and at most its limit."""
        return math.isfinite(self.value) and self.value <= self.limit


def checks_for(limits: Dict[str, Any], readings: Dict[str, Any]
               ) -> List[Check]:
    """One Check for each limit of the cell whose number `readings`
    holds, in the order of the limits file."""
    return [Check(k, float(readings[k]), float(v))
            for k, v in limits.items() if k in readings]


@dataclass
class Outcome:
    """What a driver hands back after its window and its check."""
    end_to_end: Dict[str, float]
    attempted: int
    failed: int
    checks: List[Check]
    device: Dict[str, Any]
    trace: Optional[Any] = None      # the driver's record for readers
    sample: Optional[Any] = None     # what the check compared, for control

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


def result_line(outcome: Outcome, cell, metrics: Dict[str, float],
                breakdown: Optional[Dict[str, Any]] = None) -> str:
    """The run's last line of standard output: one JSON object."""
    units = {m.name: m.unit for m in cell.end_to_end + cell.per_layer}
    out: Dict[str, Any] = {
        "correct": outcome.correct,
        "attempted": int(outcome.attempted),
        "failed": int(outcome.failed),
        "metrics": {k: {"value": float(v), "unit": units[k]}
                    for k, v in metrics.items()},
        "device": outcome.device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": float(c.value),
                              "limit": float(c.limit),
                              "ok": c.ok} for c in outcome.checks}
    return json.dumps(out)


def print_checks(checks: List[Check]) -> None:
    """Each compared number beside its limit, as the last lines on
    standard error."""
    for c in checks:
        print(f"check {c.name}: {c.value!r} (limit <= {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
