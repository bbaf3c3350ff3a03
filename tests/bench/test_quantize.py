"""The quantize cell on the CPU at smoke widths: the reference solve is
the program's COMQ, a sound job is correct, the control (the solve's
products in bf16) and the planted faults come out not correct."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, run, spec
from bench.harness import Outcome, checks_for
from bench.reference import comq as ref
from conftest import TINY_QUANT_CELL, TINY_QUANT_CONFIG


@pytest.mark.parametrize("m,n", [(96, 64), (300, 40)])
def test_reference_solve_is_the_programs_comq(m, n):
    """On a random problem both solvers land on the same codes and grid."""
    from repro.core import QuantSpec, comq_quantize_blocked
    kx, kw = jax.random.split(jax.random.PRNGKey(m))
    x = jax.random.normal(kx, (4 * m, m))
    w = jax.random.normal(kw, (m, n)) / np.sqrt(m)
    h = x.T @ x
    spec_ = QuantSpec(bits=4, granularity="per_channel", lam=0.9, sweeps=3,
                      order="greedy")
    r = comq_quantize_blocked(h, w, spec_, block=64)
    q, delta, z = ref.solve(h, w, bits=4, lam=0.9, sweeps=3, block=64)
    assert np.array_equal(np.asarray(r.z_lo), np.asarray(z))
    assert np.mean(np.asarray(r.q) != np.asarray(q)) < 1e-3
    assert np.allclose(r.delta, delta, rtol=1e-5)
    # and COMQ beats round-to-nearest on its own objective
    qr, dr, zr = ref.rtn(w, 4, 0.9)
    assert jnp.sum(ref.err2(h, w, q, delta, z)) < \
        jnp.sum(ref.err2(h, w, qr, dr, zr))


def test_sound_job_is_correct_and_control_is_not(tiny_root, capsys):
    rc = run.main(["--workload", TINY_QUANT_CELL, "--seed", "9",
                   "--seconds", "1"], require_chip=False, root=tiny_root)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True
    assert set(line["metrics"]) == {"quantize_s_per_layer", "setup_s"}
    assert list(line["checks"]) == ["exact_tap_code_mismatch",
                                    "err_excess_max", "jobs_differ"]
    assert line["checks"]["exact_tap_code_mismatch"]["value"] == 0.0

    cell = spec.load_cell(TINY_QUANT_CELL, tiny_root)
    driver = spec.load_driver(cell)
    ctl = driver.control(cell, 9, None)
    # the bf16 solve fails the exact taps; a solve that returns its
    # starting grid fails both numbers
    bf16 = Outcome({}, 0, 0, checks_for(cell.limits, ctl["bf16_solve"]), {})
    assert not bf16.correct
    assert ctl["bf16_solve"]["exact_tap_code_mismatch"] > \
        cell.limits["exact_tap_code_mismatch"]
    assert ctl["unswept"]["err_excess_max"] > cell.limits["err_excess_max"]
    assert ctl["unswept"]["exact_tap_code_mismatch"] > \
        cell.limits["exact_tap_code_mismatch"]


def test_control_tool_judges_controls_as_runs(tiny_root, capsys):
    """bench/control.py holds each control to the committed limits
    through the run's own checks: the program reads correct, every
    control not."""
    rc = control.main(["--workload", TINY_QUANT_CELL, "--seeds", "9",
                       "--seconds", "1"], require_chip=False, root=tiny_root)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["program"]["correct"] is True
    assert set(line["controls"]) == {"bf16_solve", "unswept"}
    assert all(c["correct"] is False for c in line["controls"].values())


def _stale_layers(monkeypatch):
    """The walk feeds every layer the embedded calibration tokens in place
    of the previous layer's output: the later solves see wrong Grams."""
    from repro.core import pipeline
    staged = pipeline._quantize_layer_staged

    def stale(lp, x, *a, **kw):
        lp_q, _, rest = staged(lp, x, *a, **kw)
        return lp_q, x, rest
    monkeypatch.setattr(pipeline, "_quantize_layer_staged", stale)


def _half_batch(monkeypatch):
    """Every Gram leaves out half of the calibration batch, the rest
    counted twice."""
    import jax.numpy as jnp
    from repro.core import calibrate

    def half(tap):
        x = tap[:tap.shape[0] // 2].reshape(-1, tap.shape[-1])
        x = x.astype(jnp.float32)
        return 2.0 * (x.T @ x)
    monkeypatch.setattr(calibrate, "gram_from_tap", half)


def _altered_down(monkeypatch):
    """Every w_down's codes are moved one step where they are stored."""
    import jax.numpy as jnp
    from repro.core import pipeline
    make = pipeline.make_qtensor
    cfg = TINY_QUANT_CONFIG["model"]
    down = (cfg["intermediate_size"], cfg["hidden_size"])

    def altered(q, delta, z_lo, shape, bits=8):
        if tuple(shape) == down:
            q = jnp.where(q > z_lo, q - 1, q + 1)
        return make(q, delta, z_lo, shape, bits=bits)
    monkeypatch.setattr(pipeline, "make_qtensor", altered)


@pytest.mark.parametrize("fault", [_stale_layers, _half_batch,
                                   _altered_down])
def test_planted_fault_fails(tiny_root, capsys, monkeypatch, fault):
    fault(monkeypatch)
    rc = run.main(["--workload", TINY_QUANT_CELL, "--seed", "9",
                   "--seconds", "1"], require_chip=False, root=tiny_root)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is False


def test_unswept_codes_fail(tiny_root, capsys, monkeypatch):
    """A solve that hands back its starting grid (round-to-nearest, no
    coordinate descent) comes out not correct."""
    from repro.core import pipeline
    solve = pipeline.solve
    monkeypatch.setattr(pipeline, "solve",
                        lambda h, w, spec, method="comq", **kw:
                        solve(h, w, spec, "rtn", **kw))
    rc = run.main(["--workload", TINY_QUANT_CELL, "--seed", "9",
                   "--seconds", "1"], require_chip=False, root=tiny_root)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is False
