"""Whole-model COMQ pipeline: quality vs RTN, loss preservation, quantized
serving, and the distributed-solve column independence property."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import (QuantSpec, comq_quantize_h, gram, materialize,
                        quantize_model)
from repro.core.pipeline import dequant_qtensor, is_qtensor
from repro.models import BuildPlan, init_params, lm_loss

PLAN = BuildPlan(remat=False)
KEY = jax.random.PRNGKey(0)
SPEC = QuantSpec(bits=4, granularity="per_channel", lam=0.9, sweeps=2,
                 order="greedy")


@pytest.mark.parametrize("arch", ["qwen2-7b", "granite-moe-3b-a800m",
                                  "rwkv6-7b", "hymba-1.5b"])
def test_pipeline_improves_over_rtn_and_preserves_loss(arch):
    cfg = get_smoke_config(arch)
    params = init_params(KEY, cfg, PLAN)
    tokens = jax.random.randint(KEY, (4, 64), 0, cfg.vocab_size)
    qparams, report = quantize_model(params, cfg, PLAN, tokens, SPEC)
    assert report.total_improvement() > 0.05, \
        f"COMQ should beat RTN reconstruction, got {report.total_improvement()}"
    mat = materialize(qparams, cfg)
    batch = {"tokens": tokens, "labels": tokens}
    fp = float(lm_loss(params, cfg, PLAN, batch)[0])
    q4 = float(lm_loss(mat, cfg, PLAN, batch)[0])
    assert abs(q4 - fp) < 0.35, (fp, q4)


def test_bits_sweep_orders_errors():
    """Lower bit-width => higher reconstruction error (2 > 3 > 4 bits),
    the paper's central quality axis (Tab. 1)."""
    cfg = get_smoke_config("mistral-large-123b")
    params = init_params(KEY, cfg, PLAN)
    tokens = jax.random.randint(KEY, (4, 64), 0, cfg.vocab_size)
    errs = {}
    for bits in (2, 3, 4):
        spec = QuantSpec(bits=bits, granularity="per_channel", lam=0.9,
                         sweeps=2, order="greedy")
        _, rep = quantize_model(params, cfg, PLAN, tokens, spec)
        errs[bits] = sum(r.err_after for r in rep.layers)
    assert errs[2] > errs[3] > errs[4], errs


def test_quantized_serving_consistency():
    """Greedy decode from materialized quantized params stays close to fp:
    same ranking on most positions at 8-bit."""
    from repro.serve.engine import Engine
    cfg = get_smoke_config("qwen2-7b")
    params = init_params(KEY, cfg, PLAN)
    tokens = jax.random.randint(KEY, (2, 48), 0, cfg.vocab_size)
    spec = QuantSpec(bits=8, granularity="per_channel", lam=1.0, sweeps=2,
                     order="greedy")
    qparams, _ = quantize_model(params, cfg, PLAN, tokens, spec)
    mat = materialize(qparams, cfg)
    e_fp = Engine(params, cfg, PLAN)
    e_q = Engine(mat, cfg, PLAN)
    prompts = np.asarray(tokens[:, :32])
    out_fp = e_fp.generate_batch(prompts, max_new_tokens=8)
    out_q = e_q.generate_batch(prompts, max_new_tokens=8)
    agree = float((out_fp == out_q).mean())
    assert agree >= 0.5, f"8-bit greedy decode agreement {agree}"


def test_qtensor_leaves_and_dequant_shapes():
    cfg = get_smoke_config("qwen2-7b")
    params = init_params(KEY, cfg, PLAN)
    tokens = jax.random.randint(KEY, (2, 32), 0, cfg.vocab_size)
    qparams, _ = quantize_model(params, cfg, PLAN, tokens, SPEC)
    table = qparams["__qlayers__"]
    assert len(table) == cfg.n_layers
    lp0 = table["0"]
    qt = lp0["attn"]["wq"]
    assert is_qtensor(qt)
    assert qt["codes"].dtype == jnp.uint8
    deq = dequant_qtensor(qt)
    assert deq.shape == params["layers"]["attn"]["wq"].shape[1:]


def _qtensor_leaves(table):
    out = {}
    for lkey, lp in table.items():
        for mod, leaves in lp.items():
            if not isinstance(leaves, dict):
                continue
            for leaf, v in leaves.items():
                if is_qtensor(v):
                    out[(lkey, mod, leaf)] = v
    return out


@pytest.mark.parametrize("arch", ["qwen2-7b", "granite-moe-3b-a800m"])
def test_fused_shared_tap_solves_match_per_leaf(arch, monkeypatch):
    """Shared-tap fusion ([wq|wk|wv] on attn_in, [w_gate|w_up] on mlp_in /
    expert_in) must produce bit-identical QTensors to per-leaf solves —
    per-channel columns are independent given δ (paper eq. (3))."""
    from repro.core import pipeline
    cfg = get_smoke_config(arch)
    params = init_params(KEY, cfg, PLAN)
    tokens = jax.random.randint(KEY, (2, 32), 0, cfg.vocab_size)
    qp_fused, _ = quantize_model(params, cfg, PLAN, tokens, SPEC)
    monkeypatch.setattr(pipeline, "_fusable", lambda spec, method: False)
    qp_sep, _ = quantize_model(params, cfg, PLAN, tokens, SPEC)
    fused = _qtensor_leaves(qp_fused["__qlayers__"])
    sep = _qtensor_leaves(qp_sep["__qlayers__"])
    assert fused.keys() == sep.keys() and len(fused) > 0
    for key in fused:
        qf, qs = fused[key], sep[key]
        assert bool(jnp.all(qf["codes"] == qs["codes"])), key
        assert bool(jnp.all(qf["z_lo"] == qs["z_lo"])), key
        np.testing.assert_allclose(np.asarray(qf["scale"]),
                                   np.asarray(qs["scale"]), rtol=1e-6,
                                   err_msg=str(key))
        assert qf["shape"] == qs["shape"], key


def test_gram_computed_once_per_tap(monkeypatch):
    """The dense family has 7 mapped leaves but only 4 distinct taps per
    layer — the TapGramCache must issue exactly 4 Gram matmuls per layer."""
    from repro.core import calibrate
    calls = {"n": 0}
    orig = calibrate.gram_from_tap

    def counting(tap):
        calls["n"] += 1
        return orig(tap)

    monkeypatch.setattr(calibrate, "gram_from_tap", counting)
    cfg = get_smoke_config("qwen2-7b")
    params = init_params(KEY, cfg, PLAN)
    tokens = jax.random.randint(KEY, (2, 32), 0, cfg.vocab_size)
    quantize_model(params, cfg, PLAN, tokens, SPEC)
    assert calls["n"] == 4 * cfg.n_layers, calls["n"]


def test_layer_report_seconds_reset_per_leaf():
    """Regression: dispatch time was measured from one t0 per *layer*,
    inflating later leaves cumulatively. Each leaf now reports its own
    dispatch time, so the per-layer sum must be far below n_leaves ×
    layer wall time. (`seconds` is the deprecated alias and must keep
    reading the dispatch field.)"""
    cfg = get_smoke_config("qwen2-7b")
    params = init_params(KEY, cfg, PLAN)
    tokens = jax.random.randint(KEY, (2, 32), 0, cfg.vocab_size)
    import time as _time
    t0 = _time.time()
    _, report = quantize_model(params, cfg, PLAN, tokens, SPEC)
    wall = _time.time() - t0
    assert all(r.dispatch_seconds >= 0.0 for r in report.layers)
    # the cumulative-t0 bug multiple-counted solve time (leaf k charged the
    # sum of leaves 1..k), pushing the report total well past wall clock;
    # per-leaf timing keeps the total within the actual elapsed time
    total = sum(r.dispatch_seconds for r in report.layers)
    assert total <= wall + 1e-6, (total, wall)
    # no tracer: the walk stays sync-free, wall time is unmeasured
    assert all(r.wall_seconds == 0.0 for r in report.layers)


def test_layer_report_wall_seconds_with_tracer():
    """With a tracer the `leaf_solve` span blocks on the solved codes, so
    every leaf reports a real wall time ≥ its dispatch time, and their
    total stays within the end-to-end run wall."""
    from repro.obs import Tracer
    cfg = get_smoke_config("qwen2-7b")
    params = init_params(KEY, cfg, PLAN)
    tokens = jax.random.randint(KEY, (2, 32), 0, cfg.vocab_size)
    tr = Tracer(run="wall-test")
    _, report = quantize_model(params, cfg, PLAN, tokens, SPEC, tracer=tr)
    assert report.layers
    assert all(r.wall_seconds > 0.0 for r in report.layers)
    assert all(r.wall_seconds + 1e-9 >= r.dispatch_seconds
               for r in report.layers)
    assert sum(r.wall_seconds for r in report.layers) \
        <= report.wall_seconds + 1e-6
    spans = [e for e in tr.events if e["name"] == "leaf_solve"]
    assert len(spans) > 0 and all(e["ph"] == "X" for e in spans)


def test_staged_runs_one_layer_forward_per_layer(monkeypatch):
    """The default (staged) schedule must evaluate layer_full exactly once
    per layer — the tap walk quantizes mid-forward and propagates in the
    same evaluation (the legacy schedule needed two)."""
    from repro.models import transformer as tfm
    calls = {"n": 0}
    orig = tfm.layer_full

    def counting(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    monkeypatch.setattr(tfm, "layer_full", counting)
    cfg = get_smoke_config("qwen2-7b")
    params = init_params(KEY, cfg, PLAN)
    tokens = jax.random.randint(KEY, (2, 32), 0, cfg.vocab_size)
    quantize_model(params, cfg, PLAN, tokens, SPEC)
    assert calls["n"] == cfg.n_layers, calls["n"]


@pytest.mark.parametrize("arch", ["qwen2-7b", "granite-moe-3b-a800m"])
def test_staged_err_not_worse_than_legacy(arch):
    """Staged propagation calibrates intra-layer taps on the *quantized*
    upstream sub-blocks, so per-leaf reconstruction error must not degrade
    vs the legacy two-forward schedule (and usually improves). MoE gets a
    wider band: the router re-routes on the quantized stream, so expert
    buffers differ structurally between schedules, not just numerically."""
    cfg = get_smoke_config(arch)
    moe = cfg.moe is not None
    leaf_tol, total_tol = (1.05, 1.01) if moe else (1.02, 1.001)
    params = init_params(KEY, cfg, PLAN)
    tokens = jax.random.randint(KEY, (4, 64), 0, cfg.vocab_size)
    _, r_staged = quantize_model(params, cfg, PLAN, tokens, SPEC)
    _, r_legacy = quantize_model(params, cfg, PLAN, tokens, SPEC,
                                 propagation="legacy")
    assert len(r_staged.layers) == len(r_legacy.layers) > 0
    for a, b in zip(r_staged.layers, r_legacy.layers):
        assert a.name == b.name and a.layer == b.layer
        # per-leaf: within bf16 propagation noise of the legacy error
        assert a.err_after <= b.err_after * leaf_tol, (a.name, a.err_after,
                                                       b.err_after)
    total_s = sum(r.err_after for r in r_staged.layers)
    total_l = sum(r.err_after for r in r_legacy.layers)
    assert total_s <= total_l * total_tol, (total_s, total_l)


def test_staged_vlm_pipeline():
    """Staged walk through the VLM group structure (self layers via
    layer_full callbacks, cross layers via cross_layer_full): same leaf
    inventory as legacy, COMQ still beats the RTN grid init."""
    cfg = get_smoke_config("llama-3.2-vision-90b")
    params = init_params(KEY, cfg, PLAN)
    tokens = jax.random.randint(KEY, (2, 32), 0, cfg.vocab_size)
    ve = jax.random.normal(KEY, (2, cfg.cross_attn.n_vision_tokens,
                                 cfg.cross_attn.vision_dim), jnp.bfloat16)
    spec = QuantSpec(bits=4, granularity="per_channel", lam=0.9, sweeps=1,
                     order="greedy")
    qs, rs = quantize_model(params, cfg, PLAN, tokens, spec,
                            vision_embeds=ve)
    _, rl = quantize_model(params, cfg, PLAN, tokens, spec,
                           vision_embeds=ve, propagation="legacy")
    assert [r.name for r in rs.layers] == [r.name for r in rl.layers]
    assert rs.total_improvement() > 0.05
    assert any(r.name.startswith("cross.") for r in rs.layers)
    assert len(qs["__qlayers__"]) > 0


def test_staged_rejects_unknown_propagation():
    cfg = get_smoke_config("qwen2-7b")
    params = init_params(KEY, cfg, PLAN)
    tokens = jax.random.randint(KEY, (2, 32), 0, cfg.vocab_size)
    with pytest.raises(ValueError):
        quantize_model(params, cfg, PLAN, tokens, SPEC, propagation="eager")


def test_column_independence_enables_sharded_solve():
    """Per-channel COMQ on a column subset equals those columns of the full
    solve — the property that lets the launcher shard columns across the
    mesh with zero solve-time communication (DESIGN.md §4)."""
    k1, k2 = jax.random.split(KEY)
    x = jax.random.normal(k1, (128, 48))
    w = jax.random.normal(k2, (48, 32)) * 0.1
    h = gram(x)
    full = comq_quantize_h(h, w, SPEC)
    half = comq_quantize_h(h, w[:, :16], SPEC)
    assert bool(jnp.all(full.q[:, :16] == half.q))
    np.testing.assert_allclose(np.asarray(full.delta[:16]),
                               np.asarray(half.delta), rtol=1e-6)
