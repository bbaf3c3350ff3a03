"""Quantized paged KV pages (DESIGN.md §11): encode/decode round-trip,
write-path scale discipline (prefill scatter + decode append), in-kernel
dequant vs XLA fallback vs oracle agreement across GQA/MQA/SWA shapes,
partitioned allocator, and token identity under scheduling churn
(mixed + staggered arrivals, preemption, crash-replay) for int8 pages
with a bounded-drift gate for 4-bit."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.ft import FaultInjector, Journal, SimulatedKill
from repro.kernels.ref import paged_attention_quant_ref, paged_attention_ref
from repro.models import BuildPlan, init_params
from repro.models.attention import (head_to_kv_map, paged_decode_attend_quant,
                                    paged_insert_quant)
from repro.serve import Runtime, ServeConfig, recover_runtime
from repro.serve.kv_cache import (BlockAllocator, kv_decode, kv_encode,
                                  kv_scale_of, paged_cache_bytes,
                                  write_prefill)

KEY = jax.random.PRNGKey(0)


def _f32_setup(arch="qwen2-7b", kv_bits=0):
    cfg = get_smoke_config(arch).replace(compute_dtype="float32")
    plan = BuildPlan(remat=False, cache_dtype=jnp.float32, kv_bits=kv_bits)
    params = init_params(KEY, cfg, plan)
    return cfg, plan, params


def _runtime(params, cfg, plan, **kw):
    sc = dict(max_slots=3, block_size=8, num_blocks=24, buckets=(8, 16, 32),
              max_blocks_per_slot=6)
    sc.update(kw)
    return Runtime(params, cfg, plan, ServeConfig(**sc))


# ---------------------------------------------------------------------------
# encode/decode round-trip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_bits,tol", [(8, 0.02), (4, 0.35)])
def test_kv_roundtrip_bounded(kv_bits, tol):
    rows = jax.random.normal(KEY, (6, 2, 32), jnp.float32)
    scale = kv_scale_of(jnp.max(jnp.abs(rows), axis=-1), kv_bits)
    back = kv_decode(kv_encode(rows, scale, kv_bits), scale, kv_bits)
    err = np.max(np.abs(np.asarray(back - rows)))
    amax = float(np.max(np.abs(np.asarray(rows))))
    assert err <= tol * amax, (err, amax)


def test_kv_zero_scale_encodes_exact_zero():
    rows = jnp.zeros((4, 2, 16))
    for kv_bits in (8, 4):
        scale = kv_scale_of(jnp.max(jnp.abs(rows), axis=-1), kv_bits)
        codes = kv_encode(rows, scale, kv_bits)
        assert not np.any(np.asarray(kv_decode(codes, scale, kv_bits)))


# ---------------------------------------------------------------------------
# kernel agreement: oracle vs in-kernel dequant vs XLA fallback
# ---------------------------------------------------------------------------

def _quant_pool(key, NB, BS, KV, hd, kv_bits):
    kk, kv_ = jax.random.split(key)
    kf = jax.random.normal(kk, (NB, BS, KV, hd), jnp.float32)
    vf = jax.random.normal(kv_, (NB, BS, KV, hd), jnp.float32)
    ks = kv_scale_of(jnp.max(jnp.abs(kf), axis=(1, 3)), kv_bits)  # (NB, KV)
    vs = kv_scale_of(jnp.max(jnp.abs(vf), axis=(1, 3)), kv_bits)
    kq = kv_encode(kf.transpose(0, 2, 1, 3), ks[:, :, None],
                   kv_bits).transpose(0, 2, 1, 3)
    vq = kv_encode(vf.transpose(0, 2, 1, 3), vs[:, :, None],
                   kv_bits).transpose(0, 2, 1, 3)
    return kq, vq, ks, vs


def _lane_dense(pool):
    """(..., KV, w) -> the runtime's lane-dense (..., KV·w) rows."""
    return pool.reshape(*pool.shape[:-2], -1)


def _heads(rows, n_kv):
    """Lane-dense (..., KV·w) rows -> (..., KV, w)."""
    return rows.reshape(*rows.shape[:-1], n_kv, -1)


def _quant_pool_stack(key, L, NB, BS, KV, hd, kv_bits):
    """L independent `_quant_pool` layers stacked as the runtime stores
    them: lane-dense codes (L, NB, BS, KV·hd/cpb), scales (L, NB, KV)."""
    layers = [_quant_pool(k, NB, BS, KV, hd, kv_bits)
              for k in jax.random.split(key, L)]
    kq, vq, ks, vs = (jnp.stack(parts) for parts in zip(*layers))
    return _lane_dense(kq), _lane_dense(vq), ks, vs


@pytest.mark.parametrize("kv_bits", [8, 4])
def test_quant_ref_equals_bf16_ref_on_dequantized_pool(kv_bits):
    """The quantized oracle IS the bf16 oracle applied to the dequantized
    pool — exactly, not approximately."""
    NB, BS, KV, hd, B, H, MAXB = 10, 8, 2, 32, 3, 8, 4
    kq, vq, ks, vs = _quant_pool(KEY, NB, BS, KV, hd, kv_bits)
    q = jax.random.normal(jax.random.PRNGKey(1), (B, H, hd), jnp.float32)
    bt = jnp.asarray(np.random.RandomState(0).randint(0, NB, (B, MAXB)),
                     jnp.int32)
    lengths = jnp.asarray([17, 0, 32], jnp.int32)
    want = paged_attention_ref(
        q, kv_decode(kq.transpose(0, 2, 1, 3), ks[:, :, None],
                     kv_bits).transpose(0, 2, 1, 3),
        kv_decode(vq.transpose(0, 2, 1, 3), vs[:, :, None],
                  kv_bits).transpose(0, 2, 1, 3),
        bt, lengths)
    got = paged_attention_quant_ref(q, kq, vq, ks, vs, bt, lengths,
                                    kv_bits=kv_bits)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("kv_bits", [8, 4])
@pytest.mark.parametrize("H,KV,window", [(8, 2, 0),    # GQA
                                         (8, 8, 0),    # MHA
                                         (8, 1, 9),    # MQA + SWA
                                         (8, 2, 9)])   # GQA + SWA
def test_quant_fallback_and_kernel_match_ref(kv_bits, H, KV, window):
    """XLA gather fallback and the interpret-mode Pallas kernel (per-page
    scales folded into online softmax) both match the dequantizing oracle
    on the layer they are given of a layer-stacked pool. The kernel
    matches everywhere incl. inactive slots (exact zeros); the fallback's
    dense attend is only defined on active slots."""
    L, NB, BS, hd, B, MAXB = 3, 10, 8, 32, 3, 4
    kq, vq, ks, vs = _quant_pool_stack(KEY, L, NB, BS, KV, hd, kv_bits)
    q1 = jax.random.normal(jax.random.PRNGKey(2), (B, 1, H, hd), jnp.float32)
    bt = jnp.asarray(np.random.RandomState(0).randint(0, NB, (B, MAXB)),
                     jnp.int32)
    lengths = jnp.asarray([17, 0, 32], jnp.int32)
    hm = head_to_kv_map(H, H, KV)
    layer = jnp.int32(2)
    want = np.asarray(paged_attention_quant_ref(
        q1[:, 0], _heads(kq[2], KV), _heads(vq[2], KV), ks[2], vs[2], bt,
        lengths, window=window, kv_bits=kv_bits))
    got_x = np.asarray(paged_decode_attend_quant(
        q1, kq, vq, ks, vs, layer, bt, lengths, hm, window=window,
        kv_bits=kv_bits, mode="xla"))[:, 0]
    act = np.asarray(lengths) > 0
    np.testing.assert_allclose(got_x[act], want[act], rtol=2e-5, atol=2e-5)
    got_p = np.asarray(paged_decode_attend_quant(
        q1, kq, vq, ks, vs, layer, bt, lengths, hm, window=window,
        kv_bits=kv_bits, mode="interpret"))[:, 0]
    np.testing.assert_allclose(got_p, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kv_bits", [0, 8, 4])
@pytest.mark.parametrize("layer", [1, 3])
def test_paged_kernels_read_the_layer_they_are_given(kv_bits, layer):
    """Both Pallas kernels (interpret mode), handed the whole layer-stacked
    pool and a layer index l != 0, match the XLA gather path on pool[l]
    for bf16, int8 and 4-bit pages — and not the path on another layer, so
    an index map that reads the wrong layer fails here."""
    from repro.models.attention import paged_decode_attend
    L, NB, BS, KV, H, hd, B, MAXB = 4, 10, 8, 2, 8, 32, 3, 4
    q1 = jax.random.normal(jax.random.PRNGKey(5), (B, 1, H, hd), jnp.float32)
    bt = jnp.asarray(np.random.RandomState(1).randint(0, NB, (B, MAXB)),
                     jnp.int32)
    lengths = jnp.asarray([19, 32, 5], jnp.int32)
    hm = head_to_kv_map(H, H, KV)
    if kv_bits:
        pool = _quant_pool_stack(KEY, L, NB, BS, KV, hd, kv_bits)

        def attend(l, mode):
            return paged_decode_attend_quant(
                q1, *pool, jnp.int32(l), bt, lengths, hm, kv_bits=kv_bits,
                mode=mode)
    else:
        kk, kv_ = jax.random.split(KEY)
        pool = (jax.random.normal(kk, (L, NB, BS, KV * hd), jnp.float32),
                jax.random.normal(kv_, (L, NB, BS, KV * hd), jnp.float32))

        def attend(l, mode):
            return paged_decode_attend(q1, *pool, jnp.int32(l), bt, lengths,
                                       hm, mode=mode)
    got = np.asarray(attend(layer, "interpret"))
    want = np.asarray(attend(layer, "xla"))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    other = np.asarray(attend(layer - 1, "xla"))
    assert np.max(np.abs(got - other)) > 1e-2


# ---------------------------------------------------------------------------
# write paths: prefill scatter + decode append
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_bits", [8, 4])
def test_write_prefill_quantizes_and_wipes_stale_scales(kv_bits):
    L, NB, BS, KV, hd, S, MAXB = 2, 6, 4, 2, 8, 10, 3
    cpb = 1 if kv_bits == 8 else 2
    dt = jnp.int8 if kv_bits == 8 else jnp.uint8
    pool = {"k": jnp.zeros((L, NB, BS, KV * (hd // cpb)), dt),
            "v": jnp.zeros((L, NB, BS, KV * (hd // cpb)), dt),
            "k_scale": jnp.zeros((L, NB, KV), jnp.float32),
            "v_scale": jnp.zeros((L, NB, KV), jnp.float32)}
    # poison a page this request will reuse: prefill must overwrite its
    # scale, not max against the stale one
    pool["k_scale"] = pool["k_scale"].at[:, 2].set(99.0)
    k_seq = jax.random.normal(KEY, (L, S, KV, hd), jnp.float32)
    v_seq = jax.random.normal(jax.random.PRNGKey(3), (L, S, KV, hd),
                              jnp.float32)
    pos = jnp.arange(S, dtype=jnp.int32).at[5].set(-1)   # one masked row
    table = jnp.asarray([2, 0, 4], jnp.int32)            # pages 2, 0, 4
    out = write_prefill(pool, k_seq, v_seq, pos, table, kv_bits=kv_bits)
    assert float(jnp.max(out["k_scale"][:, 2])) < 99.0   # stale wiped
    assert not np.any(np.asarray(out["k_scale"][:, 1]))  # untouched page
    assert not np.any(np.asarray(out["k"][:, 1]))
    # reconstruction within the code width at each page's scale
    for name, seq in (("k", k_seq), ("v", v_seq)):
        rows = kv_decode(_heads(out[name], KV).transpose(0, 1, 3, 2, 4),
                         out[name + "_scale"][:, :, :, None],
                         kv_bits).transpose(0, 1, 3, 2, 4)
        for s in range(S):
            if s == 5:
                continue
            page, off = table[s // BS], s % BS
            got = np.asarray(rows[:, page, off])
            want = np.asarray(seq[:, s])
            scale = np.asarray(out[name + "_scale"][:, page])[..., None]
            assert np.max(np.abs(got - want) - 0.51 * scale) <= 0


@pytest.mark.parametrize("kv_bits", [8, 4])
def test_paged_insert_quant_running_max_and_fresh_reset(kv_bits):
    """Decode append into layer 1 of a two-layer pool; layer 0 is never
    touched."""
    L, NB, BS, KV, hd, B, MAXB = 2, 6, 4, 2, 8, 2, 3
    kq = jnp.zeros((L, NB, BS, KV * (hd // (1 if kv_bits == 8 else 2))),
                   jnp.int8 if kv_bits == 8 else jnp.uint8)
    ks = jnp.zeros((L, NB, KV), jnp.float32)
    bt = jnp.asarray([[0, 1, 2], [3, 4, 5]], jnp.int32)
    rs = np.random.RandomState(4)
    layer = jnp.int32(1)

    def tok(scale):
        return jnp.asarray(rs.normal(scale=scale, size=(B, 1, KV, hd)),
                           jnp.float32)

    # fresh page (off == 0): scale resets to this token's absmax
    t0 = tok(1.0)
    kq1, ks1, vq1, vs1 = paged_insert_quant(
        kq, kq, ks, ks, t0, t0, layer, bt, jnp.asarray([0, 0], jnp.int32),
        kv_bits=kv_bits)
    for a in (kq1, ks1, vq1, vs1):
        assert not np.any(np.asarray(a[0]))              # layer 0 untouched
    back = kv_decode(_heads(kq1[1], KV).transpose(0, 2, 1, 3),
                     ks1[1][:, :, None], kv_bits).transpose(0, 2, 1, 3)
    got = np.asarray(back[np.asarray(bt[:, 0]), 0])
    assert np.max(np.abs(got - np.asarray(t0[:, 0]))) \
        <= 0.51 * float(np.max(np.asarray(ks1))) + 1e-6
    # appending a larger token raises the scale; old codes rescale with
    # bounded drift
    t1 = tok(4.0)
    kq2, ks2, _, _ = paged_insert_quant(
        kq1, vq1, ks1, vs1, t1, t1, layer, bt,
        jnp.asarray([1, 1], jnp.int32), kv_bits=kv_bits)
    assert np.all(np.asarray(ks2[1][np.asarray(bt[:, 0])])
                  >= np.asarray(ks1[1][np.asarray(bt[:, 0])]) - 1e-7)
    back2 = kv_decode(_heads(kq2[1], KV).transpose(0, 2, 1, 3),
                      ks2[1][:, :, None], kv_bits).transpose(0, 2, 1, 3)
    drift = np.abs(np.asarray(back2[np.asarray(bt[:, 0]), 0])
                   - np.asarray(back[np.asarray(bt[:, 0]), 0]))
    assert np.max(drift) <= 1.01 * float(np.max(np.asarray(ks2)))
    # inactive slot (-1): nothing written
    kq3, ks3, _, _ = paged_insert_quant(
        kq1, vq1, ks1, vs1, t1, t1, layer, bt,
        jnp.asarray([1, -1], jnp.int32), kv_bits=kv_bits)
    np.testing.assert_array_equal(np.asarray(kq3[1, 3:]),
                                  np.asarray(kq1[1, 3:]))
    np.testing.assert_array_equal(np.asarray(ks3[1, 3:]),
                                  np.asarray(ks1[1, 3:]))


def test_paged_insert_quant_same_scale_is_byte_stable():
    """Appending a token no larger than the page's current range must not
    rewrite existing codes (ratio = 1 path is exact, not approximate)."""
    L, NB, BS, KV, hd, B = 2, 4, 4, 2, 8, 1
    kq = jnp.zeros((L, NB, BS, KV * hd), jnp.int8)
    ks = jnp.zeros((L, NB, KV), jnp.float32)
    bt = jnp.asarray([[0, 1]], jnp.int32)
    big = jnp.full((B, 1, KV, hd), 2.0, jnp.float32)
    small = jnp.full((B, 1, KV, hd), 0.5, jnp.float32)
    layer = jnp.int32(1)
    kq1, ks1, vq1, vs1 = paged_insert_quant(
        kq, kq, ks, ks, big, big, layer, bt, jnp.asarray([0], jnp.int32),
        kv_bits=8)
    kq2, ks2, _, _ = paged_insert_quant(
        kq1, vq1, ks1, vs1, small, small, layer, bt,
        jnp.asarray([1], jnp.int32), kv_bits=8)
    np.testing.assert_array_equal(np.asarray(ks2), np.asarray(ks1))
    np.testing.assert_array_equal(np.asarray(kq2[1, 0, 0]),
                                  np.asarray(kq1[1, 0, 0]))


# ---------------------------------------------------------------------------
# partitioned allocator
# ---------------------------------------------------------------------------

def test_allocator_partitions_own_disjoint_ranges():
    a = BlockAllocator(12, partitions=3)
    assert a.partition_blocks == 4
    got = {p: a.alloc(4, part=p) for p in range(3)}
    for p, pages in got.items():
        assert all(a.partition_of(b) == p for b in pages)
        assert set(pages) == set(range(p * 4, (p + 1) * 4))
        assert a.num_free_in(p) == 0
    assert a.alloc(1, part=1) is None       # partition exhausted
    a.free(got[1])
    assert a.num_free_in(1) == 4
    a.check_integrity()


def test_allocator_single_partition_order_unchanged():
    """partitions=1 must allocate in exactly the legacy LIFO order — the
    solo-run oracle depends on page-id determinism."""
    legacy = BlockAllocator(6)
    assert legacy.alloc(3) == [0, 1, 2]
    part = BlockAllocator(6, partitions=1)
    assert part.alloc(3) == [0, 1, 2]


# ---------------------------------------------------------------------------
# end-to-end: runtime on quantized pages
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_bits,dt,div", [(8, jnp.int8, 1),
                                            (4, jnp.uint8, 2)])
def test_runtime_pool_layout_and_bytes(kv_bits, dt, div):
    cfg, plan, params = _f32_setup(kv_bits=kv_bits)
    rt = _runtime(params, cfg, plan)
    hd = cfg.resolved_head_dim
    assert rt.pool["k"].dtype == dt
    assert rt.pool["k"].shape[-1] == cfg.n_kv_heads * hd // div
    assert rt.pool["k_scale"].shape == (cfg.n_layers, 24, cfg.n_kv_heads)
    ratio = (paged_cache_bytes(cfg, plan.replace(kv_bits=0), 24, 8)
             / paged_cache_bytes(cfg, plan, 24, 8))
    # f32 cache dtype here: int8 halves again on the bf16 deployment plan
    assert ratio >= (3.6 if kv_bits == 8 else 6.0)


@pytest.mark.parametrize("arch", ["qwen2-7b",           # GQA
                                  "h2o-danube-1.8b",    # SWA
                                  "granite-moe-3b-a800m"])   # MoE
def test_int8_pages_token_identity_mixed_staggered(arch):
    """int8 self-identity: under mixed lengths, staggered arrivals, and
    slot/page reuse, every request's greedy tokens equal its solo run on
    the same quantized runtime — quantization error is a function of the
    written pages only, never of scheduling history."""
    cfg, plan, params = _f32_setup(arch, kv_bits=8)
    rs = np.random.RandomState(1)
    lens = [30, 16, 28, 8] if cfg.sliding_window else [5, 16, 11, 8]
    prompts = [rs.randint(0, cfg.vocab_size, (l,)).astype(np.int32)
               for l in lens]
    rt = _runtime(params, cfg, plan, max_slots=2, num_blocks=12)
    reqs = [rt.submit(p, max_new_tokens=6) for p in prompts[:2]]
    rt.step()
    reqs.append(rt.submit(prompts[2], max_new_tokens=6))
    rt.step()
    reqs.append(rt.submit(prompts[3], max_new_tokens=6))
    rt.run()
    for p, r in zip(prompts, reqs):
        solo = _runtime(params, cfg, plan, max_slots=2,
                        num_blocks=12).generate([p], max_new_tokens=6)[0]
        np.testing.assert_array_equal(np.asarray(r.out_tokens), solo)
    assert rt.allocator.num_free == rt.allocator.num_blocks


def test_int8_pages_near_identity_under_preemption():
    """A pool too small for all lifetimes forces preemption-by-page-
    reclaim. A resumed request re-prefills its history, which re-rounds
    page codes once at the final scatter-max scale, where the solo run's
    append path rounded at intermediate running-max scales and rescaled
    — same final scales, codes within 1 LSB. So the gate here is long
    shared prefixes (a near-tie argmax can flip late in a decode), with
    every pre-resume token exact; the bf16 deployment config's exact
    preempted identity is gated in benchmarks/serve_bench.py."""
    cfg, plan, params = _f32_setup(kv_bits=8)
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, cfg.vocab_size, (l,)).astype(np.int32)
               for l in (8, 7, 8, 6)]
    solo_rt = _runtime(params, cfg, plan, max_slots=1, num_blocks=3,
                       max_blocks_per_slot=3)
    solo = [solo_rt.generate([p], max_new_tokens=17)[0] for p in prompts]
    rt = _runtime(params, cfg, plan, max_slots=4, num_blocks=8,
                  max_blocks_per_slot=3)
    reqs = [rt.submit(p, max_new_tokens=17) for p in prompts]
    m = rt.run()
    assert m["preemptions"] > 0          # the pool genuinely thrashed
    agree = []
    for r, want in zip(reqs, solo):
        got, want = np.asarray(r.out_tokens), np.asarray(want)
        same = got == want
        agree.append((int(np.argmin(same)) if not same.all() else 17) / 17)
    assert np.mean(agree) >= 0.85, agree


def test_int8_pages_crash_replay_token_identity(tmp_path):
    """Kill mid-decode and recover: the quantized-pool runtime journals /
    replays like the bf16 one, and replayed tokens match solo runs."""
    cfg, plan, params = _f32_setup(kv_bits=8)
    rs = np.random.RandomState(23)
    prompts = [rs.randint(0, cfg.vocab_size, (int(l),)).astype(np.int32)
               for l in rs.randint(6, 15, 3)]
    solo = _runtime(params, cfg, plan).generate(prompts, max_new_tokens=8)
    inj = FaultInjector({"kill": {4}})
    sc = ServeConfig(max_slots=3, block_size=8, num_blocks=24,
                     buckets=(8, 16, 32), max_blocks_per_slot=6)
    rt = Runtime(params, cfg, plan, sc, journal=Journal(str(tmp_path)),
                 injector=inj)
    reqs = [rt.submit(p, max_new_tokens=8) for p in prompts]
    with pytest.raises(SimulatedKill):
        rt.run()
    rt2, st = recover_runtime(params, cfg, plan, str(tmp_path), sc)
    assert rt2.kv_bits == 8 and "k_scale" in rt2.pool
    assert set(st.inflight) == {r.rid for r in reqs}
    replayed = {r.rid: r for r in rt2.scheduler.queue}
    rt2.run()
    for r, want in zip(reqs, solo):
        np.testing.assert_array_equal(
            np.asarray(replayed[r.rid].out_tokens), want)


def test_kv4_pages_bounded_drift_vs_solo():
    """4-bit pages: same preemption workload as the int8 identity test,
    gated on prefix agreement with the 4-bit solo runs instead of
    exactness (15-level rounding shifts near-tie logits a few steps into
    some decodes)."""
    cfg, plan, params = _f32_setup(kv_bits=4)
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, cfg.vocab_size, (l,)).astype(np.int32)
               for l in (8, 7, 8, 6)]
    solo_rt = _runtime(params, cfg, plan, max_slots=1, num_blocks=3,
                       max_blocks_per_slot=3)
    solo = [solo_rt.generate([p], max_new_tokens=17)[0] for p in prompts]
    rt = _runtime(params, cfg, plan, max_slots=4, num_blocks=8,
                  max_blocks_per_slot=3)
    reqs = [rt.submit(p, max_new_tokens=17) for p in prompts]
    rt.run()
    agree = []
    for r, want in zip(reqs, solo):
        got, want = np.asarray(r.out_tokens), np.asarray(want)
        n = min(len(got), len(want))
        same = got[:n] == want[:n]
        agree.append((int(np.argmin(same)) if not same.all() else n) / 17)
    assert np.mean(agree) >= 0.5, agree


# ---------------------------------------------------------------------------
# TP slot+page sharding (forced 8 host devices, subprocess)
# ---------------------------------------------------------------------------

_TP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from repro.configs import get_smoke_config
from repro.models import BuildPlan, init_params
from repro.serve import Runtime, ServeConfig
from repro.analysis.contracts import Contract, check_lowered
assert jax.device_count() == 8, jax.device_count()
cfg = get_smoke_config("qwen2-7b").replace(compute_dtype="float32")
plan = BuildPlan(remat=False, cache_dtype=jnp.float32, kv_bits=8)
params = init_params(jax.random.PRNGKey(0), cfg, plan)
rs = np.random.RandomState(0)
prompts = [rs.randint(0, cfg.vocab_size, (l,)).astype(np.int32)
           for l in (9, 14, 7, 12)]
sc = ServeConfig(max_slots=4, block_size=8, num_blocks=16,
                 buckets=(8, 16), max_blocks_per_slot=4)
base = Runtime(params, cfg, plan, sc).generate(prompts, max_new_tokens=8)
mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(4), ("model",))
rt = Runtime(params, cfg, plan, sc, mesh=mesh)
got = rt.generate(prompts, max_new_tokens=8)
for i, (a, b) in enumerate(zip(base, got)):
    assert np.array_equal(a, b), (i, a, b)
B = sc.max_slots
args = (rt.params, rt.pool, jnp.zeros((B, rt.maxb), jnp.int32),
        jnp.zeros((B, 1), jnp.int32), jnp.zeros((B,), jnp.int32))
viol = check_lowered(rt._decode, *args,
                     con=Contract(name="serve.decode_step.tp",
                                  collectives=0, donated=(1,)))
assert not viol, viol
bucket = sc.buckets[0]
_, cache = rt._prefill_fn(bucket)(rt.params,
                                  jnp.zeros((1, bucket), jnp.int32))
kv = cache["kv"]
fn = rt._write_fn(int(kv.k.shape[2]))
wargs = (rt.pool, kv.k[:, 0], kv.v[:, 0], kv.pos[0, 0],
         jnp.int32(bucket), jnp.zeros((rt.maxb,), jnp.int32))
viol = check_lowered(fn, *wargs,
                     con=Contract(name="serve.prefill_write.tp",
                                  collectives=0, donated=(0,)))
assert not viol, viol
print("TP_SERVE_OK")
"""


def test_forced_8_device_tp_quantized_serving():
    """Slot+page-sharded int8-page serving on a forced 4-way model mesh:
    token parity with the meshless runtime, decode step lowers with zero
    collectives and the sharded pool donated, prefill-write likewise
    (tests/test_dist.py subprocess idiom: conftest forbids in-process
    XLA_FLAGS)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _TP_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "TP_SERVE_OK" in out.stdout
