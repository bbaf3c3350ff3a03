"""Unified observability layer (repro.obs, DESIGN.md §10): tracer spans
+ Chrome-trace schema, metrics registry + sinks, per-request timeline
reconstruction (preempt/resume edges, crash-replay dedup), and the
zero-cost-when-disabled guarantees — serve tokens and packed-ckpt bytes
must be bit-identical with and without instrumentation attached."""
import json

import jax
import numpy as np
import pytest

from repro.ckpt import pack_tree, save_packed_ckpt
from repro.configs import get_smoke_config
from repro.core import QuantSpec, quantize_model
from repro.ft.watchdog import Heartbeat
from repro.models import BuildPlan, init_params
from repro.obs import (NULL_METRICS, NULL_TRACER, MetricsRegistry, Tracer,
                       dedup_events, next_trace_path, reconstruct_timelines,
                       validate_timeline, validate_trace,
                       validate_trace_file)
from repro.obs import report as obs_report
from repro.obs import validate as obs_validate
from repro.serve import Runtime, ServeConfig

KEY = jax.random.PRNGKey(0)


def _f32_setup(arch="qwen2-7b"):
    cfg = get_smoke_config(arch).replace(compute_dtype="float32")
    plan = BuildPlan(remat=False, cache_dtype=jax.numpy.float32)
    params = init_params(KEY, cfg, plan)
    return cfg, plan, params


# ---------------------------------------------------------------------------
# tracer: span nesting + Chrome-trace schema
# ---------------------------------------------------------------------------

def test_span_nesting_and_chrome_trace_schema(tmp_path):
    tr = Tracer(run="unit")
    with tr.span("outer", layer=3) as outer:
        assert outer.elapsed_s >= 0.0
        with tr.span("inner", leaf="wq"):
            pass
        with tr.span("note") as note:   # args may be added before close
            note.args.update(k=1)
    tr.request_event("submit", 7, prompt_len=5)
    tr.token_event(7, 0, 42, 1234.5)

    evs = tr.events
    by_name = {e["name"]: e for e in evs}
    # inner closes before outer, so it lands first; both are "X" spans
    # with the inner interval contained in the outer one (same tid lane)
    inner, outer = by_name["inner"], by_name["outer"]
    assert inner["ph"] == outer["ph"] == "X" and inner["cat"] == "span"
    assert inner["tid"] == outer["tid"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1.0
    assert outer["args"] == {"layer": 3}
    # request events carry category + scope; token_event takes the ts given
    assert by_name["note"]["args"] == {"k": 1}
    assert by_name["submit"]["cat"] == "request"
    assert by_name["submit"]["args"]["rid"] == 7
    tok = by_name["token"]
    assert tok["cat"] == "request" and tok["ts"] == 1234.5
    assert tok["args"] == {"rid": 7, "i": 0, "token": 42}

    assert validate_trace(tr.to_chrome_trace()) == []
    path = next_trace_path(str(tmp_path), "unit")
    assert path.endswith("unit.g0.trace.json")
    tr.save(path)
    assert validate_trace_file(path) == []
    # a second generation gets a distinct filename
    assert next_trace_path(str(tmp_path), "unit").endswith("unit.g1.trace.json")


def test_validate_trace_rejects_malformed():
    assert validate_trace([]) != []
    assert validate_trace({"traceEvents": [{"name": "x"}]}) != []
    bad = {"traceEvents": [{"name": "x", "ph": "X", "ts": 0.0,
                            "pid": 1, "tid": 1, "dur": -1.0}]}
    assert any("dur" in p for p in validate_trace(bad))


def test_null_singletons_are_inert():
    assert NULL_TRACER.enabled is False and NULL_METRICS.enabled is False
    with NULL_TRACER.span("x", step=1) as s:
        assert s is NULL_TRACER.span("y")      # one shared no-op span
    assert NULL_TRACER.request_event("submit", 0) is None
    assert NULL_TRACER.token_event(0, 0, 0, 0.0) is None
    c = NULL_METRICS.counter("a")
    assert c is NULL_METRICS.histogram("b")    # one shared instrument
    c.inc()
    c.observe(3.0)
    assert c.value == 0.0 and c.count == 0
    assert NULL_METRICS.snapshot() == {}


# ---------------------------------------------------------------------------
# metrics: quantiles + sinks
# ---------------------------------------------------------------------------

def test_histogram_quantile_matches_numpy():
    rs = np.random.RandomState(3)
    vals = rs.randn(101).tolist()
    reg = MetricsRegistry(run="unit")
    h = reg.histogram("itl")
    for v in vals:
        h.observe(v)
    for q in (0.0, 0.25, 0.5, 0.9, 0.99, 1.0):
        assert h.quantile(q) == float(np.percentile(vals, q * 100.0))
    one = reg.histogram("one")
    one.observe(2.5)
    assert one.quantile(0.99) == 2.5
    assert np.isnan(reg.histogram("empty").quantile(0.5))


def test_metrics_sinks_roundtrip(tmp_path):
    reg = MetricsRegistry(run="unit")
    reg.counter("serve.tokens").inc(5)
    reg.gauge("pool.free").set(8.0)
    h = reg.histogram("serve.itl_seconds")
    for v in (0.001, 0.002, 0.4):
        h.observe(v)

    jpath = str(tmp_path / "metrics.jsonl")
    reg.dump_jsonl(jpath)
    recs = {r["name"]: r for r in
            (json.loads(ln) for ln in open(jpath) if ln.strip())}
    assert recs["serve.tokens"] == {"name": "serve.tokens",
                                    "kind": "counter", "run": "unit",
                                    "value": 5.0}
    assert recs["pool.free"]["value"] == 8.0
    # histograms carry the raw values so any quantile recomputes exactly
    assert recs["serve.itl_seconds"]["values"] == [0.001, 0.002, 0.4]
    assert recs["serve.itl_seconds"]["count"] == 3

    ppath = str(tmp_path / "metrics.prom")
    reg.dump_prometheus(ppath)
    prom = open(ppath).read()
    assert "# TYPE serve_tokens counter" in prom
    assert "serve_tokens 5.0" in prom
    assert "# TYPE serve_itl_seconds histogram" in prom
    assert 'serve_itl_seconds_bucket{le="0.0025"} 2' in prom
    assert 'serve_itl_seconds_bucket{le="+Inf"} 3' in prom
    assert "serve_itl_seconds_count 3" in prom

    snap = reg.snapshot()
    assert snap["serve.tokens"] == 5.0
    assert snap["serve.itl_seconds"]["count"] == 3
    assert snap["serve.itl_seconds"]["p50"] == 0.002


# ---------------------------------------------------------------------------
# timelines: crash-replay dedup (synthetic event streams)
# ---------------------------------------------------------------------------

def _rev(name, ts, **args):
    return {"name": name, "ph": "i", "cat": "request", "s": "t",
            "ts": float(ts), "pid": 1, "tid": 1, "args": args}


def test_timeline_crash_replay_rid_dedup():
    """Two restart generations of one request: the replay re-emits
    submit/first_token and the already-delivered token prefix; dedup
    keeps the first occurrence of each (token events by (rid, i)) while
    genuinely-new events (the resume admit, token i=2, retire) land."""
    gen0 = [
        _rev("submit", 1, rid=0, prompt_len=4, max_new_tokens=3, priority=0),
        _rev("admit", 2, rid=0, slot=0, resumed=False, prefill_len=4),
        _rev("first_token", 3, rid=0, token=7),
        _rev("token", 3, rid=0, i=0, token=7),
        _rev("token", 4, rid=0, i=1, token=8),
        _rev("preempt", 5, rid=0, n_preempts=1),
        # exact duplicate admit (torn journal flush) collapses too
        _rev("admit", 2, rid=0, slot=0, resumed=False, prefill_len=4),
    ]
    gen1 = [       # crash-replay generation: re-delivers the prefix
        _rev("submit", 11, rid=0, prompt_len=4, max_new_tokens=3, priority=0),
        _rev("admit", 12, rid=0, slot=1, resumed=True, prefill_len=8),
        _rev("first_token", 12, rid=0, token=7),
        _rev("token", 12, rid=0, i=0, token=7),
        _rev("token", 13, rid=0, i=1, token=8),
        _rev("token", 14, rid=0, i=2, token=9),
        _rev("retire", 15, rid=0, reason="length", new_tokens=3),
    ]
    merged = gen0 + gen1
    deduped = dedup_events(merged)
    assert sum(e["name"] == "token" for e in deduped) == 3
    assert sum(e["name"] == "submit" for e in deduped) == 1
    assert sum(e["name"] == "admit" for e in deduped) == 2

    tls = reconstruct_timelines(merged)
    assert set(tls) == {0}
    tl = tls[0]
    assert tl.t_submit == 1.0 and tl.t_first_token == 3.0
    assert tl.t_retire == 15.0 and tl.new_tokens == 3
    assert tl.tokens == [(0, 7), (1, 8), (2, 9)]
    assert tl.preempts == [5.0] and tl.resumes == [12.0]
    assert len(tl.admits) == 2
    assert tl.complete and validate_timeline(tl) == []
    assert tl.ttft_s == pytest.approx(2.0 / 1e6)
    assert tl.wall_s == pytest.approx(14.0 / 1e6)


def test_timeline_validation_flags_inconsistencies():
    # token count disagrees with the retire record
    evs = [_rev("submit", 1, rid=4, prompt_len=2),
           _rev("admit", 2, rid=4, slot=0, resumed=False, prefill_len=2),
           _rev("first_token", 3, rid=4, token=1),
           _rev("token", 3, rid=4, i=0, token=1),
           _rev("retire", 9, rid=4, reason="length", new_tokens=2)]
    tl = reconstruct_timelines(evs)[4]
    assert any("token events" in p for p in validate_timeline(tl))
    # never admitted
    tl2 = reconstruct_timelines([_rev("submit", 1, rid=5, prompt_len=2)])[5]
    assert any("never admitted" in p for p in validate_timeline(tl2))


# ---------------------------------------------------------------------------
# end to end: instrumented runtime under preemption, vs a plain one
# ---------------------------------------------------------------------------

def test_serve_obs_end_to_end_preempt_resume():
    """An over-subscribed instrumented run (a) emits bit-identical tokens
    to the uninstrumented runtime, (b) reconstructs a clean timeline for
    every request — at least one with preempt AND resume edges — whose
    token events equal the delivered stream, and (c) lands consistent
    registry counts."""
    cfg, plan, params = _f32_setup()
    rs = np.random.RandomState(7)
    prompts = [rs.randint(0, cfg.vocab_size, (l,)).astype(np.int32)
               for l in (14, 9, 12)]
    sc = ServeConfig(max_slots=3, block_size=8, num_blocks=6,
                     buckets=(8, 16, 32), max_blocks_per_slot=6)

    rt_plain = Runtime(params, cfg, plan, sc)
    assert rt_plain.tracer is NULL_TRACER and rt_plain.metrics is NULL_METRICS
    plain = rt_plain.generate([p for p in prompts], max_new_tokens=8)

    tr, reg = Tracer(run="test"), MetricsRegistry(run="test")
    rt = Runtime(params, cfg, plan, sc, tracer=tr, metrics=reg)
    reqs = [rt.submit(p, max_new_tokens=8) for p in prompts]
    rt.run()
    assert rt.scheduler.preemptions > 0

    for r, want in zip(reqs, plain):               # (a) bit-identity
        np.testing.assert_array_equal(np.asarray(r.out_tokens),
                                      np.asarray(want))

    assert validate_trace(tr.to_chrome_trace()) == []
    tls = reconstruct_timelines(tr.events)         # (b) timelines
    assert set(tls) == {r.rid for r in reqs}
    for r in reqs:
        tl = tls[r.rid]
        assert tl.complete and validate_timeline(tl) == []
        assert [t for _, t in tl.tokens] == [int(t) for t in r.out_tokens]
        assert tl.prompt_len == len(r.prompt)
        assert tl.finish_reason == r.finish_reason
    assert any(tls[r.rid].preempts and tls[r.rid].resumes for r in reqs)
    span_names = {e["name"] for e in tr.events if e["ph"] == "X"}
    assert {"decode_step", "serve.run"} <= span_names

    snap = reg.snapshot()                          # (c) metrics agree
    assert snap["serve.preemptions"] == rt.scheduler.preemptions
    assert snap["serve.tokens_emitted"] == sum(len(r.out_tokens)
                                               for r in reqs)
    assert snap["serve.requests_retired"] == len(reqs)
    assert snap["serve.ttft_seconds"]["count"] == len(reqs)
    assert snap["serve.resumes"] > 0
    # heartbeat snapshots embed the registry + runtime health dicts
    assert "live_occupancy" in rt.metrics_snapshot()


def test_disabled_tracer_quantize_bit_identical_packed_bytes(tmp_path):
    """quantize_model with a live tracer+registry must produce the same
    codes — packed-ckpt bytes compared — as an uninstrumented run; the
    tracer only *adds* span-derived wall_seconds to the layer reports."""
    cfg = get_smoke_config("qwen2-7b")
    plan = BuildPlan(remat=False)
    params = init_params(KEY, cfg, plan)
    tokens = jax.random.randint(KEY, (4, 64), 0, cfg.vocab_size)
    spec = QuantSpec(bits=4, granularity="per_channel", lam=0.9, sweeps=1,
                     order="greedy")

    q_ref, rep_ref = quantize_model(params, cfg, plan, tokens, spec,
                                    method="comq_blocked")
    tr, reg = Tracer(run="q"), MetricsRegistry(run="q")
    q_obs, rep_obs = quantize_model(params, cfg, plan, tokens, spec,
                                    method="comq_blocked", tracer=tr,
                                    metrics=reg)

    def packed_bytes(q, path):
        host = jax.tree_util.tree_map(
            lambda a: np.asarray(jax.device_get(a))
            if isinstance(a, jax.Array) else a,
            pack_tree(q["__qlayers__"]))
        save_packed_ckpt(str(path), host)
        return open(path, "rb").read()

    assert packed_bytes(q_ref, tmp_path / "ref.qpk") == \
        packed_bytes(q_obs, tmp_path / "obs.qpk")

    rows = lambda rep: [(lr.layer, lr.name, lr.err_before, lr.err_after)
                        for lr in rep.layers]
    assert rows(rep_ref) == rows(rep_obs)
    # dispatch timing exists either way; true wall only with the tracer
    assert all(lr.wall_seconds == 0.0 for lr in rep_ref.layers)
    assert any(lr.wall_seconds > 0.0 for lr in rep_obs.layers)

    assert {"layer", "leaf_solve"} <= {e["name"] for e in tr.events
                                       if e["ph"] == "X"}
    snap = reg.snapshot()
    assert snap["quant.leaves_solved"] > 0
    assert snap["quant.leaf_wall_seconds"]["count"] == \
        snap["quant.leaf_dispatch_seconds"]["count"]


# ---------------------------------------------------------------------------
# heartbeat snapshots + CLIs
# ---------------------------------------------------------------------------

def test_heartbeat_metrics_snapshot(tmp_path):
    hb = Heartbeat(str(tmp_path), host_id=0)
    hb.beat(3)
    rec = json.load(open(hb.path))
    assert rec["step"] == 3 and "metrics" not in rec

    reg = MetricsRegistry(run="hb")
    reg.counter("quant.layers_done").inc(4)
    hb.beat(4, metrics=reg.snapshot())
    rec = json.load(open(hb.path))
    assert rec["metrics"]["quant.layers_done"] == 4.0
    alive = Heartbeat.alive_hosts(str(tmp_path))
    assert alive[0]["metrics"]["quant.layers_done"] == 4.0


def _synthetic_run_dir(tmp_path):
    tr = Tracer(run="synthetic")
    with tr.span("decode_step", step=0):
        pass
    for e in [_rev("submit", 1, rid=0, prompt_len=4, max_new_tokens=1),
              _rev("admit", 2, rid=0, slot=0, resumed=False, prefill_len=4),
              _rev("first_token", 3, rid=0, token=7),
              _rev("token", 3, rid=0, i=0, token=7),
              _rev("retire", 4, rid=0, reason="length", new_tokens=1)]:
        tr._events.append(("i", e["name"], "request", e["ts"], 1, e["args"]))
    tr.save(next_trace_path(str(tmp_path), "serve"))
    reg = MetricsRegistry(run="synthetic")
    reg.counter("serve.tokens_emitted").inc()
    reg.histogram("serve.itl_seconds").observe(0.01)
    reg.dump_jsonl(str(tmp_path / "metrics.jsonl"))
    return tmp_path


def test_report_cli_smoke(tmp_path, capsys):
    run_dir = _synthetic_run_dir(tmp_path)
    assert obs_report.main([str(run_dir)]) == 0
    out = capsys.readouterr().out
    assert "== spans ==" in out and "decode_step" in out
    assert "== requests ==" in out and "== metrics ==" in out
    empty = tmp_path / "empty"
    empty.mkdir()
    assert obs_report.main([str(empty)]) == 1


def test_validate_cli_timelines(tmp_path, capsys):
    run_dir = _synthetic_run_dir(tmp_path)
    trace = str(run_dir / "serve.g0.trace.json")
    assert obs_validate.main(["--timelines", trace]) == 0
    # the synthetic request never preempts, so --require-preempt fails
    assert obs_validate.main(["--timelines", "--require-preempt",
                              trace]) == 1
    capsys.readouterr()
