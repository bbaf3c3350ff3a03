"""End-to-end system behaviour: the full COMQ workflow — train a small
model on the structured stream, quantize it with COMQ, verify the
quantized model retains the learned behaviour better than RTN at 3 bits
(the paper's central claim transplanted to this stack)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import RunConfig
from repro.core import QuantSpec, materialize, quantize_model
from repro.data import SyntheticLM
from repro.models import BuildPlan, lm_loss
from repro.train.trainer import Trainer


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    cfg = get_smoke_config("h2o-danube-1.8b")
    plan = BuildPlan(remat=False)
    run_cfg = RunConfig(arch="h2o-danube-1.8b",
                        ckpt_dir=str(tmp_path_factory.mktemp("ck")),
                        ckpt_every=1000, total_steps=60, learning_rate=3e-3,
                        warmup_steps=5, async_ckpt=False)
    t = Trainer(cfg, plan, run_cfg)
    out = t.run_loop(total_steps=60, seq_len=64, global_batch=8)
    return cfg, plan, out["state"]["params"], out["metrics"]


def _eval_loss(params, cfg, plan, seed=123):
    data = SyntheticLM(cfg.vocab_size, seed=0).sample(8, 64, step=9999)
    batch = {"tokens": jnp.asarray(data["tokens"]),
             "labels": jnp.asarray(data["labels"])}
    return float(lm_loss(params, cfg, plan, batch)[0])


def test_training_learned_structure(trained):
    cfg, plan, params, metrics = trained
    assert metrics[-1]["loss"] < metrics[0]["loss"] - 0.8


def test_comq_beats_rtn_on_trained_model(trained):
    """Paper Tab. 3/4 analogue: at 3 bits, COMQ preserves the trained
    model's eval loss better than RTN on the identical grid."""
    cfg, plan, params, _ = trained
    calib = jnp.asarray(SyntheticLM(cfg.vocab_size, seed=0)
                        .sample(8, 64, step=5000)["tokens"])
    base = _eval_loss(params, cfg, plan)
    losses = {}
    for method in ("comq", "rtn"):
        spec = QuantSpec(bits=3, granularity="per_channel", lam=0.9,
                         sweeps=3, order="greedy")
        qp, _ = quantize_model(params, cfg, plan, calib, spec, method=method)
        losses[method] = _eval_loss(materialize(qp, cfg), cfg, plan)
    assert losses["comq"] <= losses["rtn"] + 1e-4, (base, losses)
    # and COMQ's degradation from fp is bounded
    assert losses["comq"] - base < 1.0, (base, losses)


def test_quantize_then_serve_roundtrip(trained):
    from repro.serve.engine import Engine
    cfg, plan, params, _ = trained
    calib = jnp.asarray(SyntheticLM(cfg.vocab_size, seed=0)
                        .sample(4, 64, step=77)["tokens"])
    spec = QuantSpec(bits=4, granularity="per_channel", lam=0.9, sweeps=2,
                     order="greedy")
    qp, _ = quantize_model(params, cfg, plan, calib, spec)
    eng = Engine(materialize(qp, cfg), cfg, plan)
    prompts = np.asarray(calib[:2, :32])
    out = eng.generate_batch(prompts, max_new_tokens=8)
    assert out.shape == (2, 8)
    assert (out >= 0).all() and (out < cfg.vocab_size).all()


@pytest.mark.parametrize("kv_bits", ["0", "8"])
def test_launcher_quantize_file_feeds_serve(tmp_path, kv_bits):
    """The quantizer's packed file is what the server loads: `quantize
    --save-packed F` then `serve --load-quantized F` answers every request
    from packed codes (the seam the two launchers share)."""
    from repro.launch import quantize, serve
    ckpt = str(tmp_path / "q.qpk")
    arch = ["--arch", "h2o-danube-1.8b", "--smoke"]
    q = quantize.main(arch + [
        "--method", "comq_blocked", "--bits", "4", "--sweeps", "1",
        "--calib-batch", "2", "--calib-seq", "48",
        "--out-dir", str(tmp_path / "ck"), "--save-packed", ckpt])
    assert q["comq_vs_rtn_error_improvement"] > 0
    assert np.isfinite(q["fp_loss"]) and np.isfinite(q["quant_loss"])
    s = serve.main(arch + [
        "--load-quantized", ckpt, "--num-requests", "4", "--mixed",
        "--prompt-len", "24", "--max-new", "6", "--stagger", "2",
        "--kv-bits", kv_bits])
    assert s["packed_qt"] and s["requests"] == 4
    assert s["out_tokens"] == 4 * 6
    assert s["finish_reasons"] == ["length"] * 4
