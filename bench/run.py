#!/usr/bin/env python3
"""The on-chip benchmark: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

`--workload` names an entry of `workloads` in BENCHMARK.json; its
configuration, traffic mix, limits and metric readers are files under
bench/ found by name (`bench/spec.py`). The mix's "kind" picks the driver
(`bench/drivers/<kind>.py`), which sets up from the seed, measures a window
of `--seconds`, and checks what the window produced against the plain
reference. With `--trace 0` the result line carries the cell's end-to-end
metrics; with `--trace 1` the window runs under the profiler and the line
carries the per-layer metrics, read by `bench/metrics/<metric>.py`, and a
breakdown of device time and idle gaps.

The last line of standard output is one JSON object (`correct`,
`attempted`, `failed`, `metrics`, `device`, [`breakdown`], `checks`). A run
that finds no TPU, or fewer chips than the cell asks for, exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
TRACE_DIR = ROOT / ".bench_trace"


@dataclass
class Env:
    """What a driver gets from the harness besides its cell."""
    t_start: float
    devices: List[Any]
    counter: Any
    require_chip: bool
    trace_dir: Path

    @contextlib.contextmanager
    def window(self, trace: bool):
        from bench.harness import annotate, profiled
        with profiled(trace, self.trace_dir) as info:
            with annotate("bench.window"):
                yield info


def parse(argv: Optional[List[str]]):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv: Optional[List[str]] = None, require_chip: bool = True,
         root: Path = ROOT) -> int:
    """One run. `require_chip=False` is for tests on the CPU: it skips the
    look for a TPU and the check of the compiled kernels, nothing else."""
    args = parse(argv)
    for p in (str(root), str(root / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import harness, spec
    try:
        cell = spec.load_cell(args.workload, root)
        devs = harness.devices_for(cell.chips, require_chip)
    except (spec.SpecError, harness.NoChip) as e:
        harness.log(f"bench: {e}")
        return 2
    harness.enable_compile_cache(root)
    env = Env(T_START, devs, harness.CompileCounter(), require_chip,
              root / TRACE_DIR.name)
    driver = spec.load_driver(cell)
    outcome = driver.run(cell, args.seed, args.seconds, bool(args.trace),
                         env)
    breakdown = None
    if args.trace:
        import shutil
        from bench import readers
        metrics, breakdown, busy = readers.per_layer(cell, outcome, driver)
        outcome.device.update(busy)
        shutil.rmtree(env.trace_dir, ignore_errors=True)
    else:
        metrics = {m.name: outcome.end_to_end[m.name]
                   for m in cell.end_to_end}
    harness.print_checks(outcome.checks)
    print(harness.result_line(outcome, cell, metrics, breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
