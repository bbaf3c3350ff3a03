"""The harness is driven by data: a new cell, mix and metric are new files
and BENCHMARK.json entries, and the run finds them by name."""
import hashlib
import json

from bench import run, spec
from conftest import TINY_CONFIG, TINY_TRAFFIC, add_cell

READER = '''"""Test metric: decode steps in the window (program spans)."""


def read(ctx):
    return float(len(ctx.spans("decode_step"))) or None
'''


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in root.rglob("*") if p.is_file()}


def test_benchmark_json_meets_the_contract():
    assert spec.validate() == []
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert spec.NAME_RE.match(m["name"]) and spec.UNIT_RE.match(m["unit"])
    for w in bench["workloads"]:
        for n in (w["name"], w["config"], w["traffic"]):
            assert spec.NAME_RE.match(n)


def test_new_cell_mix_and_metric_are_only_new_files(tiny_root, capsys):
    before = _digests(tiny_root)
    cfg = dict(TINY_CONFIG, name="tiny-gqa-3L")
    cfg["model"] = dict(cfg["model"], num_hidden_layers=3)
    mix = dict(TINY_TRAFFIC, prompt=dict(TINY_TRAFFIC["prompt"], median=40))
    cell = "tiny-gqa-3L.offline-longer"
    add_cell(tiny_root, cell, cfg, mix, {"served_logit_gap_mean": 0.002})
    (tiny_root / "bench/metrics/test.decode_steps.py").write_text(READER)
    bj = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bj["per_layer"].append({
        "name": "test.decode_steps", "unit": "steps", "better": "higher",
        "source": "program_span", "layer": "scheduler",
        "moves": "tokens_per_s", "workloads": [cell]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bj))
    after = _digests(tiny_root)
    changed = {p for p in before if before[p] != after[p]}
    assert changed == {spec.Path("BENCHMARK.json")}
    assert spec.validate(tiny_root) == []

    rc = run.main(["--workload", cell, "--seed", "5", "--seconds", "1",
                   "--trace", "1"], require_chip=False, root=tiny_root)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True
    assert line["metrics"]["test.decode_steps"]["value"] > 0
    assert line["metrics"]["test.decode_steps"]["unit"] == "steps"
    assert {"busy_s", "window_s"} <= set(line["device"])


def test_unknown_cell_is_refused(capsys):
    assert run.main(["--workload", "no-such.cell", "--seed", "1",
                     "--seconds", "1"], require_chip=False) != 0
    assert capsys.readouterr().out == ""
