#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's check numbers and the
control's, seed after seed, in one process, each judged as a run is.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 3

For each seed it makes a whole run of the cell (set-up, a window of
`--seconds`, the check) and then puts what the driver's `control` names in
the program's place on the same inputs: the reference in the next
precision below the configuration's, and for some cells a planted fault.
Each is held to the cell's committed limits through the same `Check` and
`Outcome.correct` a run uses; its check lines go to standard error, and one
JSON line per seed to standard output: {"seed", "program": {"correct",
"checks"}, "controls": {name: {"correct", "checks", "readings"}}}. A
control reads `correct: false`. The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def judged(checks):
    return {c.name: {"value": c.value, "limit": c.limit, "ok": c.ok}
            for c in checks}


def main(argv=None, require_chip: bool = True, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    for p in (str(root), str(root / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import harness, spec
    from bench.run import Env
    cell = spec.load_cell(args.workload, root)
    devs = harness.devices_for(cell.chips, require_chip)
    harness.enable_compile_cache(root)
    counter = harness.CompileCounter()
    driver = spec.load_driver(cell)
    for seed in (int(s) for s in args.seeds.split(",")):
        env = Env(time.time(), devs, counter, require_chip,
                  root / ".bench_trace")
        out = driver.run(cell, seed, args.seconds, False, env)
        harness.print_checks(out.checks)
        line = {"seed": seed,
                "program": {"correct": out.correct,
                            "checks": judged(out.checks)},
                "controls": {}, "end_to_end": out.end_to_end,
                "device": out.device}
        for name, readings in driver.control(cell, seed, out).items():
            ctl = harness.Outcome({}, 0, 0,
                                  harness.checks_for(cell.limits, readings),
                                  out.device)
            harness.log(f"control {name}:")
            harness.print_checks(ctl.checks)
            line["controls"][name] = {"correct": ctl.correct,
                                      "checks": judged(ctl.checks),
                                      "readings": readings}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
