"""bench/run.py end to end on the CPU at smoke widths: a sound run is
correct, a run with no chip prints nothing, and a broken timed path comes
out not correct."""
import json

import pytest

from bench import run, weights
from conftest import TINY_CELL

ARGS = ["--workload", TINY_CELL, "--seconds", "1"]


def _run(root, capsys, seed=11, trace=0):
    rc = run.main(ARGS + ["--seed", str(seed), "--trace", str(trace)],
                  require_chip=False, root=root)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1])


def test_sound_run_is_correct(tiny_root, capsys):
    rc, line = _run(tiny_root, capsys, seed=2**31 + 77)
    assert rc == 0
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    assert line["metrics"]["tokens_per_s"]["unit"] == "tokens/s"
    assert line["device"]["platform"] == "cpu"
    gap = line["checks"]["served_logit_gap_mean"]
    assert gap["value"] <= gap["limit"]


def test_no_chip_no_result(tiny_root, capsys):
    rc = run.main(ARGS + ["--seed", "1"], root=tiny_root)
    assert rc != 0
    assert capsys.readouterr().out == ""


def _swap_planes(tree):
    def swap(qt):
        qt.codes = (qt.codes << 4) | (qt.codes >> 4)
        return qt
    for mod in ("attn", "mlp"):
        tree["layers"][mod] = {k: swap(v)
                               for k, v in tree["layers"][mod].items()}
    return tree


def test_swapped_nibble_planes_fail(tiny_root, capsys, monkeypatch):
    made = weights.program_params
    monkeypatch.setattr(weights, "program_params",
                        lambda *a: _swap_planes(made(*a)))
    rc, line = _run(tiny_root, capsys)
    assert rc == 0 and line["correct"] is False


def _pool_unchanged(step):
    """The decode step hands back the pool it was given: no K/V written."""
    def bad(p, cfg, plan, pool, *rest):
        return step(p, cfg, plan, pool, *rest)[0], pool
    return bad


def _half_batch(step):
    """The decode step's logits for the second half of the slots are
    dropped (left at zero)."""
    def bad(*args):
        logits, pool = step(*args)
        half = logits.shape[0] // 2
        return logits.at[half:].set(0.0), pool
    return bad


@pytest.mark.parametrize("fault", [_pool_unchanged, _half_batch])
def test_broken_decode_step_fails(tiny_root, capsys, monkeypatch, fault):
    from repro.serve import runtime
    monkeypatch.setattr(runtime, "decode_step_paged",
                        fault(runtime.decode_step_paged))
    rc, line = _run(tiny_root, capsys)
    assert rc == 0 and line["correct"] is False


def test_altered_token_fails(tiny_root, capsys, monkeypatch):
    """A token altered where the runtime produces it."""
    from repro.serve import runtime
    emit = runtime.Runtime._emit

    def bad_emit(self, req, token, now):
        if len(req.out_tokens) == 5:
            token = (token + 1) % self.cfg.vocab_size
        emit(self, req, token, now)

    monkeypatch.setattr(runtime.Runtime, "_emit", bad_emit)
    rc, line = _run(tiny_root, capsys)
    assert rc == 0 and line["correct"] is False


@pytest.mark.parametrize("seed", [1, 2])
def test_fp8_control_reads_above_the_limit(tiny_root, seed):
    """The reference in fp8, put in the program's place, fails the limit
    the program passes, on the same sequences: 16 requests served to the
    end through the runtime (no window, so no clock decides the sample)."""
    import numpy as np
    from repro.models import BuildPlan
    from repro.serve import Runtime, ServeConfig, blocks_for
    from bench import spec, traffic
    from bench.drivers.serve_offline import model_config
    from bench.reference import dense_gqa
    cell = spec.load_cell(TINY_CELL, tiny_root)
    model, sv = cell.config["model"], cell.config["serve"]
    dm = weights.dims(model)
    gain = cell.config["weights"]["out_gain"]
    cfg = model_config(cell.config)
    rt = Runtime(weights.program_params(seed, dm, gain, cfg), cfg,
                 BuildPlan(remat=False),
                 ServeConfig(max_slots=sv["max_slots"],
                             block_size=sv["block_size"],
                             num_blocks=sv["num_blocks"],
                             buckets=tuple(cell.traffic["prefill_buckets"]),
                             max_blocks_per_slot=blocks_for(88, 8)))
    reqs = [rt.submit(p, max_new_tokens=n) for p, n in traffic.backlog(
        dict(cell.traffic, backlog=16), seed, dm["vocab"], 4)]
    rt.run()
    seqs = [(np.asarray(r.prompt), list(r.out_tokens)) for r in reqs]
    limit = cell.limits["served_logit_gap_mean"]
    assert dense_gqa.served_gap(seed, dm, model, gain, seqs)[1] <= limit
    assert dense_gqa.control_gap(seed, dm, model, gain, seqs)[1] > limit
