"""A copy of the benchmark under a temporary root with one tiny cell of
its own, so tests drive `bench/run.py` end to end on the CPU."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TINY_CELL = "tiny-gqa.offline-decode"

TINY_CONFIG = {
    "name": "tiny-gqa",
    "source": "test configuration: the dense GQA family at smoke widths",
    "registry": "h2o-danube-1.8b",
    "model": {"hidden_act": "silu", "hidden_size": 64,
              "intermediate_size": 128, "num_attention_heads": 4,
              "num_hidden_layers": 2, "num_key_value_heads": 2,
              "head_dim": 16, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
              "sliding_window": 32, "tie_word_embeddings": False,
              "vocab_size": 256},
    "reduced": [],
    "weights": {"out_gain": 0.5},
    "serve": {"max_slots": 4, "block_size": 8, "num_blocks": 64,
              "kv_bits": 0},
}

TINY_TRAFFIC = {
    "kind": "serve_offline",
    "backlog": 512,
    "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5, "min": 8,
               "max": 64},
    "output": {"dist": "lognormal", "median": 12, "sigma": 0.3, "min": 8,
               "max": 24},
    "prefill_buckets": [16, 32, 64],
    "greedy": True,
    # every request a 1 s window serves on the CPU: a fault confined to
    # some slots then always reaches the sample
    "check_requests": 64,
}


TINY_QUANT_CELL = "tiny-gqa-q.quantize"

TINY_QUANT_CONFIG = dict(
    TINY_CONFIG, name="tiny-gqa-q",
    model=dict(TINY_CONFIG["model"], hidden_size=128,
               intermediate_size=256, num_attention_heads=4,
               num_key_value_heads=2, head_dim=32, vocab_size=512,
               sliding_window=0))

TINY_QUANT_TRAFFIC = {
    "kind": "quantize", "method": "comq_blocked", "bits": 4,
    "granularity": "per_channel", "order": "greedy", "sweeps": 3,
    "lam": 0.9, "calib_batch": 4, "calib_seq": 128,
}


LIKE = {"serve_offline": "h2o-danube-1.8b.offline-decode",
        "quantize": "h2o-danube-1.8b-4L.quantize"}


def add_cell(root: Path, cell: str, config: dict, traffic: dict,
             limits: dict) -> None:
    """New files plus a BENCHMARK.json entry: all a new cell takes. The
    cell reports the metrics of the benchmark's cell of the same kind."""
    cfg_name, traffic_name = cell.split(".", 1)[0], cell.split(".", 1)[1]
    (root / "bench/configs" / f"{cfg_name}.json").write_text(
        json.dumps(config))
    (root / "bench/traffic" / f"{traffic_name}.json").write_text(
        json.dumps(traffic))
    (root / "bench/limits" / f"{cell}.json").write_text(json.dumps(limits))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    if cfg_name not in {c["name"] for c in spec["configs"]}:
        spec["configs"].append({
            "name": cfg_name, "source": config["source"],
            "file": f"bench/configs/{cfg_name}.json", "reduced": [],
            "why": "test cell"})
    spec["workloads"].append({"name": cell, "config": cfg_name,
                              "traffic": traffic_name, "chips": 1,
                              "why": "test cell"})
    like = LIKE[traffic["kind"]]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if like in m.get("workloads", ()):
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def copy_bench(dst: Path) -> Path:
    """bench/ and BENCHMARK.json under `dst`; the copy's peak table also
    names the CPU (made-up peaks, for tests only: a CPU run's device
    numbers mean nothing)."""
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    peaks = json.loads((dst / "bench/peaks.json").read_text())
    peaks["devices"]["cpu"] = {"bf16_flops": 1e12, "int8_ops": 1e12,
                               "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
    (dst / "bench/peaks.json").write_text(json.dumps(peaks))
    return dst


@pytest.fixture
def tiny_root(tmp_path):
    root = copy_bench(tmp_path)
    add_cell(root, TINY_CELL, TINY_CONFIG, TINY_TRAFFIC,
             {"served_logit_gap_mean": 0.002})
    add_cell(root, TINY_QUANT_CELL, TINY_QUANT_CONFIG, TINY_QUANT_TRAFFIC,
             {"exact_tap_code_mismatch": 0.001, "err_excess_max": 0.1,
              "jobs_differ": 0})
    return root
