"""The benchmark's data, found by name.

`BENCHMARK.json` at the root of the checkout lists metrics, configurations
and cells. Everything that belongs to one of them sits in a file of its
own, found from the name alone:

    bench/configs/<config>.json    sizes, source, serving/quantize settings
    bench/traffic/<traffic>.json   parameters of a traffic mix or job; its
                                   "kind" names the driver
    bench/drivers/<kind>.py        the general generator + window for a kind
    bench/limits/<cell>.json       the limits `correct` is judged by
    bench/metrics/<metric>.py      a per-layer metric's reader: read(ctx)

A new cell, mix or metric is new files plus entries in BENCHMARK.json;
no existing file changes. This module imports no JAX.
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


class SpecError(ValueError):
    """BENCHMARK.json or a file it names is missing or malformed."""


def _load_json(path: Path) -> Dict[str, Any]:
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


def check_name(name: str, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise SpecError(f"{what} {name!r}: names are 1-64 of A-Z a-z 0-9 "
                        "_ . - and start with a letter, digit or _")
    return name


def check_unit(unit: str, what: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise SpecError(f"{what}: unit {unit!r} is not 1-16 of "
                        "A-Z a-z 0-9 _ / % . -")
    return unit


@dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    layer: Optional[str] = None
    moves: Optional[str] = None
    workloads: Optional[List[str]] = None

    def applies_to(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclass
class Cell:
    """One workload of BENCHMARK.json with every file it names loaded."""
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Metric] = field(default_factory=list)
    per_layer: List[Metric] = field(default_factory=list)
    root: Path = ROOT

    @property
    def bench_dir(self) -> Path:
        return self.root / "bench"

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    spec = _load_json(root / "BENCHMARK.json")
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        if not isinstance(spec.get(key), list) or not spec[key]:
            raise SpecError(f"BENCHMARK.json: {key!r} must be a non-empty "
                            "list")
    return spec


def _metric(entry: Dict[str, Any]) -> Metric:
    check_name(entry["name"], "metric")
    check_unit(entry["unit"], f"metric {entry['name']}")
    if entry["better"] not in ("lower", "higher"):
        raise SpecError(f"metric {entry['name']}: better must be lower or "
                        "higher")
    return Metric(entry["name"], entry["unit"], entry["better"],
                  entry["source"], entry.get("layer"), entry.get("moves"),
                  entry.get("workloads"))


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = load_benchmark(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; known: "
                        f"{sorted(cells)}")
    w = cells[name]
    check_name(w["config"], "config")
    check_name(w["traffic"], "traffic")
    if w["config"] not in {c["name"] for c in spec["configs"]}:
        raise SpecError(f"workload {name}: config {w['config']!r} is not "
                        "listed under configs")
    bench = root / "bench"
    cell = Cell(name=name, config_name=w["config"],
                traffic_name=w["traffic"], chips=int(w["chips"]),
                config=_load_json(bench / "configs" / f"{w['config']}.json"),
                traffic=_load_json(bench / "traffic" / f"{w['traffic']}.json"),
                limits=_load_json(bench / "limits" / f"{name}.json"),
                root=root)
    cell.end_to_end = [m for m in map(_metric, spec["end_to_end"])
                       if m.applies_to(name)]
    cell.per_layer = [m for m in map(_metric, spec["per_layer"])
                      if m.applies_to(name)]
    return cell


def load_module(path: Path, name: str):
    """Import a file under bench/ by path (names may hold dots)."""
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_driver(cell: Cell):
    kind = check_name(cell.kind, "traffic kind")
    return load_module(cell.bench_dir / "drivers" / f"{kind}.py",
                       f"bench_driver_{kind}")


def load_reader(cell: Cell, metric: Metric):
    return load_module(cell.bench_dir / "metrics" / f"{metric.name}.py",
                       "bench_metric_" + metric.name.replace(".", "_"))


def validate(root: Path = ROOT) -> List[str]:
    """The contract's static rules on BENCHMARK.json and the files it
    names. Returns the faults found (empty when sound)."""
    faults: List[str] = []
    spec = _load_json(root / "BENCHMARK.json")
    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(spec) != want:
        faults.append(f"top-level keys {sorted(spec)} != {sorted(want)}")
    rs = spec.get("run_seconds")
    if not (isinstance(rs, int) and 1 <= rs <= 51):
        faults.append(f"run_seconds {rs!r} is not a whole number 1..51")

    def name_ok(n, what):
        try:
            check_name(n, what)
        except SpecError as e:
            faults.append(str(e))

    def text_ok(t, what):
        if not (isinstance(t, str) and 1 <= len(t) <= 200
                and "\n" not in t and "\t" not in t):
            faults.append(f"{what}: 1-200 characters on one line, no tab")

    configs = {c["name"]: c for c in spec.get("configs", [])}
    cells = {w["name"]: w for w in spec.get("workloads", [])}
    for c in configs.values():
        name_ok(c["name"], "config")
        text_ok(c["source"], f"config {c['name']} source")
        text_ok(c["why"], f"config {c['name']} why")
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            faults.append(f"config {c['name']}: keys {sorted(c)}")
        for k in c.get("reduced", []):
            name_ok(k, f"config {c['name']} reduced key")
        if not (root / c["file"]).is_file():
            faults.append(f"config {c['name']}: no file {c['file']}")
        elif c["file"] != f"bench/configs/{c['name']}.json":
            faults.append(f"config {c['name']}: file is not found by name")
        if not any(w["config"] == c["name"] for w in cells.values()):
            faults.append(f"config {c['name']} is used by no cell")
    pairs = set()
    for w in cells.values():
        name_ok(w["name"], "workload")
        name_ok(w["traffic"], f"workload {w['name']} traffic")
        text_ok(w["why"], f"workload {w['name']} why")
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            faults.append(f"workload {w['name']}: keys {sorted(w)}")
        if w["chips"] not in (1, 4):
            faults.append(f"workload {w['name']}: chips {w['chips']}")
        if w["config"] not in configs:
            faults.append(f"workload {w['name']}: unknown config")
        if (w["config"], w["traffic"]) in pairs:
            faults.append(f"workload {w['name']}: pair appears twice")
        pairs.add((w["config"], w["traffic"]))
        for f in (f"bench/traffic/{w['traffic']}.json",
                  f"bench/limits/{w['name']}.json"):
            if not (root / f).is_file():
                faults.append(f"workload {w['name']}: no file {f}")
    e2e = {m["name"]: m for m in spec.get("end_to_end", [])}
    metric_names = list(e2e) + [m["name"] for m in spec.get("per_layer", [])]
    if len(set(metric_names)) != len(metric_names):
        faults.append("two metrics share a name")
    for m in spec.get("end_to_end", []) + spec.get("per_layer", []):
        try:
            _metric(m)
        except (SpecError, KeyError) as e:
            faults.append(str(e))
        for cell in m.get("workloads", []):
            if cell not in cells:
                faults.append(f"metric {m['name']}: unknown cell {cell}")
    for m in spec.get("end_to_end", []):
        if set(m) - {"workloads"} != {"name", "unit", "better", "bound",
                                      "source"}:
            faults.append(f"metric {m['name']}: keys {sorted(m)}")
        if m["source"] not in ("host_clock", "device_trace"):
            faults.append(f"metric {m['name']}: source {m['source']}")
        if not 0.01 <= m["bound"] <= 0.25:
            faults.append(f"metric {m['name']}: bound {m['bound']}")
    if "setup_s" not in e2e or "workloads" in e2e.get("setup_s", {}):
        faults.append("setup_s must be an end-to-end metric of every cell")
    for m in spec.get("per_layer", []):
        if set(m) - {"workloads"} != {"name", "unit", "better", "source",
                                      "layer", "moves"}:
            faults.append(f"metric {m['name']}: keys {sorted(m)}")
        text_ok(m["layer"], f"metric {m['name']} layer")
        if m["moves"] not in e2e:
            faults.append(f"metric {m['name']}: moves {m['moves']!r}")
        if not (root / "bench/metrics" / f"{m['name']}.py").is_file():
            faults.append(f"metric {m['name']}: no reader file")
        for cell in m.get("workloads", []):
            moved = e2e.get(m["moves"], {})
            if cell not in moved.get("workloads", [cell]):
                faults.append(f"metric {m['name']}: cell {cell} does not "
                              f"report {m['moves']}")
    for cell in cells:
        reported = [n for n, m in e2e.items()
                    if cell in m.get("workloads", [cell])]
        layer = [m for m in spec.get("per_layer", [])
                 if cell in m.get("workloads", [cell])]
        if len(reported) < 2 or not layer:
            faults.append(f"cell {cell}: reports {reported} end to end "
                          f"and {len(layer)} per-layer metrics")
    return faults
