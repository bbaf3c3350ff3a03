"""Serving-runtime benches (BENCH_serve.json rows):

* serve/paged_vs_dense_cache — continuous-batching Runtime (paged KV pool)
  vs the static-slot Engine (dense per-slot max_len cache) on the same
  equal-length greedy batch; `derived` = dense/paged wall ratio. On CPU
  this tracks the gather-fallback + scheduler overhead against the dense
  masked attend, not the HBM savings a TPU sees — the *capacity* win
  (pages scale with live tokens, not slots x max_len) is the point.
* serve/obs_overhead — instrumentation cost of a fully live Tracer +
  MetricsRegistry on the decode hot path; `us_per_call` = microbenched
  per-decode-step hook-sequence cost (µs), `derived` = 1 + that cost
  over the measured per-step wall, hard-gated < 1.02 (the DESIGN.md
  §10.3 budget) with a bit-identical-tokens gate on top.
* serve/packed_qt_vs_materialized — the Runtime serving a packed QT-leaf
  tree (quant_matmul path, no materialize) vs the same COMQ codes
  materialized to dense; `derived` = materialized/packed wall ratio.
  Also reports the params-tree bytes ratio as serve/packed_qt_bytes.
* serve/preempt_occupancy_vs_reserved — the same over-subscribed mixed
  workload under admission policy "preempt" (incremental pages +
  preemption-by-page-reclaim) vs "reserve" (PR-4 full-lifetime
  reservation); `derived` = preempt/reserve mean live-token occupancy
  (pages holding real K/V rows / pool size, averaged per decode step) —
  > 1.0 means reclaiming idle reservations keeps more of the pool doing
  useful work. Correctness-gated: both policies must emit exactly the
  solo-run tokens for every request. serve/preempt_itl_p99 reports the
  tail inter-token latency cost of the recompute-based resumes.
* serve/paged_int8_vs_bf16 — the Runtime on int8 KV pages (per-page
  scales, kv_bits=8) vs bf16 pages, same workload; `derived` =
  bf16/int8 wall ratio. serve/paged_int8_vs_bf16_bytes reports the
  pool-bytes ratio (code payload + scale tensors vs bf16 rows),
  hard-gated >= 1.8x. Token identity is gated on the preempt oracle:
  int8 must match its own solo runs exactly under mixed + staggered +
  preempted traffic; 4-bit gates a prefix-agreement drift bound
  (serve/paged_kv4_prefix_agreement).
* roofline/kv_bytes_predicted_vs_measured — the analytic
  bytes-per-decode-token model (roofline/kv_bytes.py) vs the
  HLO-measured decode-step bytes of the compiled runtime; `derived` =
  predicted/measured int8-vs-bf16 ratio-of-ratios, hard-gated within
  [0.75, 1.25] (the ISSUE's 25% accuracy bar).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.core import QuantSpec, materialize, quantize_model, serving_params
from repro.ckpt import tree_bytes
from repro.models import BuildPlan, init_params
from repro.serve import Engine, Runtime, ServeConfig

ARCH = "qwen2-7b"
N_REQ, PROMPT, MAX_NEW = 4, 32, 16


def _runtime_for(params, cfg, plan, **kw):
    return Runtime(params, cfg, plan,
                   ServeConfig(max_slots=N_REQ, block_size=16,
                               num_blocks=N_REQ * 4, buckets=(PROMPT,),
                               max_blocks_per_slot=4), **kw)


def _time_runtime(params, cfg, plan, prompts, repeats=3):
    rt = _runtime_for(params, cfg, plan)   # reused: jit caches stay warm
    rt.generate([p for p in prompts], max_new_tokens=MAX_NEW)  # compile
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        rt.generate([p for p in prompts], max_new_tokens=MAX_NEW)
        best = min(best, time.perf_counter() - t0)
    return best


def run():
    rows = []
    cfg = get_smoke_config(ARCH)
    plan = BuildPlan(remat=False)
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg, plan)
    prompts = np.asarray(
        jax.random.randint(key, (N_REQ, PROMPT), 0, cfg.vocab_size))

    # --- paged runtime vs dense static engine -----------------------------
    t_paged = _time_runtime(params, cfg, plan, prompts)
    eng = Engine(params, cfg, plan, max_len=PROMPT + MAX_NEW)
    eng.generate_batch(prompts, max_new_tokens=MAX_NEW)      # compile
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        eng.generate_batch(prompts, max_new_tokens=MAX_NEW)
        best = min(best, time.perf_counter() - t0)
    rows.append(("serve/paged_vs_dense_cache", round(t_paged * 1e6, 1),
                 round(best / t_paged, 3)))

    # --- observability overhead (DESIGN.md §10.3 budget) ------------------
    # Two hard gates on a fully instrumented Runtime (live Tracer +
    # MetricsRegistry): (1) it must emit bit-identical tokens to the
    # plain one; (2) its per-decode-step instrumentation cost must stay
    # under 2% of the measured decode-step wall. The cost side is NOT
    # taken by differencing two whole-run walls -- on shared CI boxes
    # per-call jitter (+-50% observed) dwarfs the ~1% true overhead and
    # any such gate flakes. Instead the exact per-step hook sequence
    # (decode_step span, one token_event + counter per live slot, the
    # two pool gauges) is replayed standalone, where it microbenches
    # stably at the microsecond level, and divided by the median
    # per-step wall of the real traced run. An events-per-step
    # cross-check pins the replayed sequence to what Runtime.step
    # actually emits, so a new hook on the hot path can't silently
    # dodge the gate.
    from repro.obs import MetricsRegistry, Tracer
    OBS_MAX_NEW = 48
    obs_cfg = dict(max_slots=N_REQ, block_size=16, num_blocks=N_REQ * 6,
                   buckets=(PROMPT,), max_blocks_per_slot=6)
    rt_plain = Runtime(params, cfg, plan, ServeConfig(**obs_cfg))
    rt_traced = Runtime(params, cfg, plan, ServeConfig(**obs_cfg),
                        tracer=Tracer(run="bench"),
                        metrics=MetricsRegistry(run="bench"))
    toks_plain = rt_plain.generate([p for p in prompts],
                                   max_new_tokens=OBS_MAX_NEW)   # compile
    toks_traced = rt_traced.generate([p for p in prompts],
                                     max_new_tokens=OBS_MAX_NEW)  # compile
    for a, b in zip(toks_plain, toks_traced):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    tr, reg = rt_traced.tracer, rt_traced.metrics
    ev0, st0 = len(tr.events), rt_traced.steps
    walls = []
    for _ in range(6):
        t0 = time.perf_counter()
        rt_traced.generate([p for p in prompts],
                           max_new_tokens=OBS_MAX_NEW)
        walls.append(time.perf_counter() - t0)
    real_steps = rt_traced.steps - st0
    real_ev_per_step = (len(tr.events) - ev0) / real_steps
    wall_per_step = float(np.median(walls)) * 6 / real_steps

    m_tok = reg.counter("serve.tokens_emitted")
    m_free = reg.gauge("serve.pool_free_blocks")
    m_occ = reg.gauge("serve.pool_live_occupancy")
    m_kvb = reg.gauge("serve.pool_kv_bytes")

    def obs_step(i):
        # mirror of Runtime.step()'s per-step instrumentation with all
        # N_REQ slots live (the traced workload's steady state)
        with tr.span("decode_step", step=i, slots=N_REQ):
            pass
        now_us = time.time() * 1e6
        for s in range(N_REQ):
            tr.token_event(s, i, 42, now_us)
            m_tok.inc()
        m_free.set(8)
        m_occ.set(0.5)
        m_kvb.set(123456)

    ev0 = len(tr.events)
    obs_step(0)
    replay_ev_per_step = len(tr.events) - ev0
    # lifecycle events (submit/admit/first_token/retire) amortize to
    # well under one event per step; anything bigger means the replay
    # no longer mirrors the real hot path
    assert abs(replay_ev_per_step - real_ev_per_step) <= 1.0, (
        f"obs replay drift: step() emits {real_ev_per_step:.2f} "
        f"events/step, replay emits {replay_ev_per_step}")
    REPS = 20000
    t0 = time.perf_counter()
    for i in range(REPS):
        obs_step(i)
    obs_s_per_step = (time.perf_counter() - t0) / REPS
    ratio = 1.0 + obs_s_per_step / wall_per_step
    assert ratio < 1.02, (f"obs overhead {ratio:.3f} breaches the 2% "
                          f"tokens/s budget ({obs_s_per_step * 1e6:.1f}us "
                          f"per {wall_per_step * 1e6:.0f}us step)")
    rows.append(("serve/obs_overhead", round(obs_s_per_step * 1e6, 2),
                 round(ratio, 3)))

    # --- packed QT vs materialized ----------------------------------------
    calib = jax.random.randint(key, (4, 32), 0, cfg.vocab_size)
    spec = QuantSpec(bits=4, granularity="per_channel", lam=0.9, sweeps=2,
                     order="cyclic")
    qparams, _ = quantize_model(params, cfg, plan, calib, spec)
    packed = serving_params(qparams, cfg)
    mat = materialize(qparams, cfg)
    t_packed = _time_runtime(packed, cfg, plan, prompts)
    t_mat = _time_runtime(mat, cfg, plan, prompts)
    rows.append(("serve/packed_qt_vs_materialized",
                 round(t_packed * 1e6, 1), round(t_mat / t_packed, 3)))
    rows.append(("serve/packed_qt_bytes", tree_bytes(packed),
                 round(tree_bytes(mat) / tree_bytes(packed), 3)))

    # --- preempt vs reserve occupancy -------------------------------------
    # Short prompts with a long decode tail: each request's lifetime bound
    # is 3 pages (prompt + 16 decode rows @ block 8) but it only *lives*
    # in 1 page for its first ~8 decode steps. "reserve" ties up the idle
    # tail pages at admission (8-page pool -> 2 concurrent lifetimes);
    # "preempt" admits all four on prefill footprint and reclaims pages on
    # demand, trading a couple of recompute-resumes (visible in the
    # preempt_itl_p99 tail) for strictly higher live occupancy.
    P_MAX_NEW = 17
    rs = np.random.RandomState(0)
    mixed = [rs.randint(0, cfg.vocab_size, (l,)).astype(np.int32)
             for l in (8, 7, 8, 6)]
    solo_rt = Runtime(params, cfg, plan,
                      ServeConfig(max_slots=1, block_size=8, num_blocks=3,
                                  buckets=(8, 16, 32), max_blocks_per_slot=3))
    solo = [solo_rt.generate([p], max_new_tokens=P_MAX_NEW)[0]
            for p in mixed]
    occ = {}
    for policy in ("preempt", "reserve"):
        rt = Runtime(params, cfg, plan,
                     ServeConfig(max_slots=4, block_size=8, num_blocks=8,
                                 buckets=(8, 16, 32), max_blocks_per_slot=3,
                                 policy=policy))
        reqs = [rt.submit(p, max_new_tokens=P_MAX_NEW) for p in mixed]
        m = rt.run()
        for r, want in zip(reqs, solo):     # correctness gate
            np.testing.assert_array_equal(np.asarray(r.out_tokens), want)
        occ[policy] = m
    rows.append(("serve/preempt_occupancy_vs_reserved",
                 round(occ["preempt"]["mean_live_occupancy"], 4),
                 round(occ["preempt"]["mean_live_occupancy"]
                       / occ["reserve"]["mean_live_occupancy"], 3)))
    rows.append(("serve/preempt_itl_p99",
                 round(occ["preempt"]["itl_p99_s"] * 1e6, 1),
                 round(occ["reserve"]["itl_p99_s"]
                       / max(occ["preempt"]["itl_p99_s"], 1e-9), 3)))

    # --- quantized KV pages: int8 pool vs bf16 pool (DESIGN.md §11) -------
    # Time row reuses the first section's bf16 paged wall; bytes row is the
    # pool-accounting ratio (code payload + per-page scales vs bf16 rows),
    # hard-gated >= 1.8x. Correctness rides the preempt oracle above: the
    # int8 runtime must emit, under the over-subscribed mixed + staggered
    # workload with preemption-by-page-reclaim, exactly the tokens its own
    # solo (one-slot, unpreempted) runs emit — quantization error must be a
    # pure function of the written pages, never of scheduling history.
    # 4-bit pages trade exactness for bytes: the same workload gates a
    # prefix-agreement drift bound instead (rounding at 15 levels shifts
    # near-tie logits a few steps into some decodes).
    from repro.serve.kv_cache import paged_cache_bytes
    plan_q8 = plan.replace(kv_bits=8)
    t_q8 = _time_runtime(params, cfg, plan_q8, prompts)
    rows.append(("serve/paged_int8_vs_bf16", round(t_q8 * 1e6, 1),
                 round(t_paged / t_q8, 3)))
    b_bf16 = paged_cache_bytes(cfg, plan, N_REQ * 4, 16)
    b_q8 = paged_cache_bytes(cfg, plan_q8, N_REQ * 4, 16)
    bytes_ratio = b_bf16 / b_q8
    assert bytes_ratio >= 1.8, (
        f"int8 pool bytes reduction {bytes_ratio:.3f}x < 1.8x")
    rows.append(("serve/paged_int8_vs_bf16_bytes", b_q8,
                 round(bytes_ratio, 3)))

    for kv_bits, exact in ((8, True), (4, False)):
        plan_kv = plan.replace(kv_bits=kv_bits)
        solo_kv_rt = Runtime(params, cfg, plan_kv,
                             ServeConfig(max_slots=1, block_size=8,
                                         num_blocks=3, buckets=(8, 16, 32),
                                         max_blocks_per_slot=3))
        solo_kv = [solo_kv_rt.generate([p], max_new_tokens=P_MAX_NEW)[0]
                   for p in mixed]
        rt = Runtime(params, cfg, plan_kv,
                     ServeConfig(max_slots=4, block_size=8, num_blocks=8,
                                 buckets=(8, 16, 32), max_blocks_per_slot=3,
                                 policy="preempt"))
        reqs = [rt.submit(p, max_new_tokens=P_MAX_NEW) for p in mixed]
        rt.run()
        if exact:
            for r, want in zip(reqs, solo_kv):
                np.testing.assert_array_equal(np.asarray(r.out_tokens),
                                              np.asarray(want))
        else:
            agree = []
            for r, want in zip(reqs, solo_kv):
                got, want = np.asarray(r.out_tokens), np.asarray(want)
                n = min(len(got), len(want))
                same = got[:n] == want[:n]
                pfx = int(np.argmin(same)) if not same.all() else n
                agree.append(pfx / P_MAX_NEW)
            mean_agree = float(np.mean(agree))
            assert mean_agree >= 0.5, (
                f"4-bit pages drifted past the tolerance: mean prefix "
                f"agreement {mean_agree:.3f} < 0.5 ({agree})")
            rows.append(("serve/paged_kv4_prefix_agreement",
                         round(mean_agree, 4), round(min(agree), 3)))

    # --- roofline: predicted vs measured decode-step bytes ratio ----------
    # The analytic byte model (roofline/kv_bytes.py) must predict the
    # HLO-measured int8-vs-bf16 decode-step bytes ratio within 25%. The
    # f32-cache config is the one the write-once cost model tracks: with
    # bf16 pages the CPU backend inserts f32-upcast copies that push the
    # measured ratio above what any storage-width model can produce.
    from repro.roofline.kv_bytes import predicted_vs_measured_ratio
    plan_rf = BuildPlan(remat=False, cache_dtype=jnp.float32)
    rf_sc = ServeConfig(max_slots=N_REQ, block_size=16, num_blocks=64,
                        buckets=(PROMPT,), max_blocks_per_slot=16)
    rf = predicted_vs_measured_ratio(
        params, cfg, plan_rf, plan_rf.replace(kv_bits=8),
        max_slots=N_REQ, block_size=16, max_blocks_per_slot=16,
        num_blocks=64,
        make_runtime=lambda p: Runtime(params, cfg, p, rf_sc))
    rr = rf["ratio_of_ratios"]
    assert 0.75 <= rr <= 1.25, (
        f"roofline kv-bytes model off by >25%: predicted "
        f"{rf['predicted']:.3f}x vs measured {rf['measured']:.3f}x")
    rows.append(("roofline/kv_bytes_predicted_vs_measured",
                 round(rf["measured"], 3), round(rr, 3)))
    return rows
