"""Continuous-batching paged serving runtime (serve/runtime.py):
scheduler admission + block accounting, paged attention kernel vs
fallback, equivalence vs the static engine and vs solo runs, packed-QT
serving without materialize."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import QuantSpec, materialize, quantize_model, serving_params
from repro.models import BuildPlan, init_params
from repro.models.attention import head_to_kv_map, paged_decode_attend
from repro.serve import Engine, Runtime, ServeConfig
from repro.serve.kv_cache import BlockAllocator, blocks_for
from repro.serve.scheduler import Request, Scheduler

KEY = jax.random.PRNGKey(0)


def _f32_setup(arch="qwen2-7b"):
    cfg = get_smoke_config(arch).replace(compute_dtype="float32")
    plan = BuildPlan(remat=False, cache_dtype=jnp.float32)
    params = init_params(KEY, cfg, plan)
    return cfg, plan, params


def _runtime(params, cfg, plan, **kw):
    sc = dict(max_slots=3, block_size=8, num_blocks=24, buckets=(8, 16, 32),
              max_blocks_per_slot=6)
    sc.update(kw)
    return Runtime(params, cfg, plan, ServeConfig(**sc))


# ---------------------------------------------------------------------------
# equivalence: runtime vs static engine / solo runs
# ---------------------------------------------------------------------------

def test_runtime_matches_engine_equal_length():
    cfg, plan, params = _f32_setup()
    prompts = np.asarray(jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size))
    eng = Engine(params, cfg, plan, max_len=32)
    want = eng.generate_batch(prompts, max_new_tokens=8)
    # matched cache extents (2 slots, 4 pages x 8 = engine max_len 32)
    rt = _runtime(params, cfg, plan, max_slots=2, num_blocks=8,
                  buckets=(16,), max_blocks_per_slot=4)
    got = rt.generate([prompts[0], prompts[1]], max_new_tokens=8)
    np.testing.assert_array_equal(np.stack(got), want)


@pytest.mark.parametrize("arch", ["qwen2-7b", "deepseek-67b",
                                  "granite-moe-3b-a800m",
                                  "h2o-danube-1.8b"])
def test_mixed_length_staggered_matches_solo(arch):
    """Mixed prompt lengths arriving over time, with fewer slots than
    requests (slot + block reuse): every request's greedy tokens equal its
    solo run through the same runtime. Covers dense (qkv-bias), dense,
    MoE, and sliding-window archs — the danube lengths push past its
    32-token smoke window so SWA masking + ring prefill scatter bind."""
    cfg, plan, params = _f32_setup(arch)
    rs = np.random.RandomState(1)
    lens = [5, 16, 11, 8]
    if cfg.sliding_window:
        lens = [30, 16, 28, 8]      # 30 + 6 new tokens > window=32
    prompts = [rs.randint(0, cfg.vocab_size, (l,)).astype(np.int32)
               for l in lens]

    rt = _runtime(params, cfg, plan, max_slots=2, num_blocks=12)
    reqs = [rt.submit(p, max_new_tokens=6) for p in prompts[:2]]
    rt.step()                       # arrivals staggered across decode steps
    reqs.append(rt.submit(prompts[2], max_new_tokens=6))
    rt.step()
    reqs.append(rt.submit(prompts[3], max_new_tokens=6))
    rt.run()
    mixed = [np.asarray(r.out_tokens) for r in reqs]

    for p, got in zip(prompts, mixed):
        solo_rt = _runtime(params, cfg, plan, max_slots=2, num_blocks=12)
        solo = solo_rt.generate([p], max_new_tokens=6)[0]
        np.testing.assert_array_equal(got, solo)

    # slot/block reuse actually happened and nothing leaked
    assert rt.allocator.peak_in_use <= rt.allocator.num_blocks
    assert rt.allocator.num_free == rt.allocator.num_blocks
    assert not rt.scheduler.running and not rt.scheduler.queue


def test_swa_prefill_bucket_invariance():
    """SWA arch with prompt > window and a bucket larger than the window:
    the right-pad rows must not ring-evict real in-window prompt K/V
    before the paged scatter. Regression: a 40-token danube prompt
    (window=32) served through a 64 bucket must decode identically to the
    same prompt through a 40 bucket (where no eviction is possible)."""
    cfg, plan, params = _f32_setup("h2o-danube-1.8b")
    assert cfg.sliding_window == 32
    p = np.random.RandomState(3).randint(0, cfg.vocab_size,
                                         (40,)).astype(np.int32)
    outs = []
    for buckets in ((40,), (64,)):
        rt = _runtime(params, cfg, plan, max_slots=1, num_blocks=12,
                      buckets=buckets, max_blocks_per_slot=9)
        outs.append(rt.generate([p], max_new_tokens=6)[0])
    np.testing.assert_array_equal(outs[0], outs[1])


def test_backpressure_queue_drains_fcfs():
    """More requests than slots *and* than free pages: admission stalls on
    cache exhaustion, completions free pages, everything finishes FCFS."""
    cfg, plan, params = _f32_setup()
    rt = _runtime(params, cfg, plan, max_slots=2, num_blocks=4,
                  buckets=(8,), max_blocks_per_slot=2)
    prompts = [np.arange(6, dtype=np.int32) % cfg.vocab_size
               for _ in range(5)]
    reqs = [rt.submit(p, max_new_tokens=4) for p in prompts]
    rt.run()
    assert all(len(r.out_tokens) == 4 for r in reqs)
    done_order = [r.rid for r in rt.scheduler.completed]
    assert done_order == sorted(done_order)     # FCFS with equal work
    assert rt.allocator.num_free == rt.allocator.num_blocks


# ---------------------------------------------------------------------------
# paged attention: pallas kernel vs XLA fallback
# ---------------------------------------------------------------------------

def test_paged_attention_ref_vs_fallback():
    """Kernel, oracle and model fallback all read layer `layer` of the
    layer-stacked, lane-dense pool and agree with the oracle on that
    layer's pages."""
    from repro.kernels import ops, ref
    L, B, H, KV, hd, NB, BS, MAXB = 3, 3, 4, 2, 16, 10, 4, 5
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, 1, H, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (L, NB, BS, KV * hd), jnp.float32)
    vp = jax.random.normal(ks[2], (L, NB, BS, KV * hd), jnp.float32)
    bt = jnp.asarray(np.random.RandomState(0).randint(0, NB, (B, MAXB)),
                     jnp.int32)
    lengths = jnp.asarray([17, 4, 0], jnp.int32)
    hmap = head_to_kv_map(H, H, KV)
    layer = jnp.int32(1)
    for window in (0, 6):
        # model fallback (gather + _dense_attention)
        o_fb = paged_decode_attend(q, kp, vp, layer, bt, lengths, hmap,
                                   window=window, mode="xla")
        # jnp oracle in kernels/ref.py
        o_ref = ops.paged_attention(q[:, 0], kp, vp, layer, bt, lengths,
                                    window=window, mode="xla")
        np.testing.assert_array_equal(
            np.asarray(o_ref), np.asarray(ref.paged_attention_ref(
                q[:, 0], kp[1].reshape(NB, BS, KV, hd),
                vp[1].reshape(NB, BS, KV, hd), bt, lengths,
                window=window)))
        # pallas kernel, interpret mode
        o_pl = ops.paged_attention(q[:, 0], kp, vp, layer, bt, lengths,
                                   window=window, mode="interpret")
        np.testing.assert_allclose(np.asarray(o_fb[:2, 0]),
                                   np.asarray(o_ref[:2]), atol=1e-5)
        np.testing.assert_allclose(np.asarray(o_pl[:2]),
                                   np.asarray(o_ref[:2]), atol=1e-5)
        # inactive slot: kernel and oracle both produce exact zeros
        assert float(jnp.abs(o_pl[2]).max()) == 0.0
        assert float(jnp.abs(o_ref[2]).max()) == 0.0


# ---------------------------------------------------------------------------
# paged decode step: the pool rides the layer scan, layers index it
# ---------------------------------------------------------------------------

def _prefilled_pool(cfg, plan, params, tables, lens, seed=7):
    """Prefill one request per row of `tables` into a fresh pool; returns
    (pool, last prompt token per slot)."""
    from repro.models.model import forward
    from repro.serve.kv_cache import init_paged_cache, write_prefill
    BS, NB = 4, 12
    pool = init_paged_cache(cfg, plan, NB, BS)
    rs = np.random.RandomState(seed)
    toks = []
    for s, n in enumerate(lens):
        t = jnp.asarray(rs.randint(0, cfg.vocab_size, (1, n)), jnp.int32)
        _, _, cache = forward(params, cfg, plan.replace(kernel_mode=None), t,
                              make_cache=True)
        kv = cache["kv"]
        pool = write_prefill(pool, kv.k[:, 0], kv.v[:, 0], kv.pos[0, 0],
                             jnp.asarray(tables[s]),
                             kv_bits=plan.kv_bits)
        toks.append(int(t[0, -1]))
    return pool, jnp.asarray(toks, jnp.int32)[:, None]


def _paged_decode_run(cfg, plan, params, steps=3):
    """Two prefilled slots, `steps` greedy paged decode steps: returns
    (stacked logits, final pool)."""
    from repro.models.model import decode_step_paged
    tables = np.asarray([[3, 7, 1, 0], [9, 2, 5, 0]], np.int32)
    lens = [6, 9]
    pool, tok = _prefilled_pool(cfg, plan, params, tables, lens)
    pos = jnp.asarray(lens, jnp.int32)
    step = jax.jit(lambda p, pool, bt, t, pos: decode_step_paged(
        p, cfg, plan, pool, bt, t, pos))
    out = []
    for _ in range(steps):
        logits, pool = step(params, pool, jnp.asarray(tables), tok, pos)
        out.append(np.asarray(logits))
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        pos = pos + 1
    return np.stack(out), pool


# sha256 of the float32 logits of `_paged_decode_run` on the danube smoke
# config (seed 0). The XLA gather path gives the same bytes it gave when
# the decode step still sliced each layer's (…, KV, hd) pool out of the
# scan's xs: reading the stacked lane-dense pool in place changes none of
# its arithmetic. The interpret-mode kernels pin their own bytes: their
# block-diagonal q sums each head's scores over all KV·hd lanes (zeros
# outside its own KV head), which moves bf16 and int8 pages by a few ulps
# from the grouped kernel they replace; 4-bit pages kept their bytes.
PINNED_LOGITS = {
    (0, "xla"): "e039a799b1f4dd306afe81ef099eea58"
                "bd251a02827f3c4bdb4f40479ec5552e",
    (0, "interpret"): "6aa12064c170c04e3ac74b015bc3da5c"
                      "88db3190bb522cf11499b8c699feb7ae",
    (8, "xla"): "4db58947c6fbdd1d78d4a27a9627b592"
                "0978e2ddec4a33a0c22af4cea2848a72",
    (8, "interpret"): "40a473de426b69657b9d5a0d9269c6a0"
                      "f486c3aea289f80e943ad5baad42947c",
    (4, "xla"): "0d89ab893795e9f757e89e2315e07779"
                "1ec096a479ca55d32d957124bf3a7f73",
    (4, "interpret"): "dfe5522ee5bb656ee1a238dad88efc0d"
                      "906b46517614643b7e588d2a3ed9244a",
}


@pytest.mark.parametrize("kv_bits,mode", sorted(PINNED_LOGITS))
def test_paged_decode_logits_pinned(kv_bits, mode):
    import hashlib
    cfg = get_smoke_config("h2o-danube-1.8b").replace(
        compute_dtype="float32")
    plan = BuildPlan(remat=False, cache_dtype=jnp.float32, kv_bits=kv_bits,
                     kernel_mode=mode)
    params = init_params(jax.random.PRNGKey(0), cfg, plan)
    logits, _ = _paged_decode_run(cfg, plan, params)
    assert logits.dtype == np.float32 and logits.shape == (3, 2, 256)
    digest = hashlib.sha256(logits.tobytes()).hexdigest()
    assert digest == PINNED_LOGITS[(kv_bits, mode)]


@pytest.mark.parametrize("kv_bits", [0, 8])
def test_decode_step_paged_segmented_equals_plain(kv_bits):
    """A SegmentedLayers tree (one scan per segment) gives each segment's
    layers their global indices into the pool: logits and every layer of
    the pool equal the plain single-scan tree's."""
    from repro.core.apply import SegmentedLayers
    cfg = get_smoke_config("h2o-danube-1.8b").replace(
        compute_dtype="float32", n_layers=4)
    plan = BuildPlan(remat=False, cache_dtype=jnp.float32, kv_bits=kv_bits)
    params = init_params(KEY, cfg, plan)
    bounds = [(0, 1), (1, 3), (3, 4)]
    seg = dict(params, layers=SegmentedLayers(
        tuple(jax.tree_util.tree_map(lambda a: a[lo:hi], params["layers"])
              for lo, hi in bounds), tuple(hi - lo for lo, hi in bounds)))
    want, want_pool = _paged_decode_run(cfg, plan, params, steps=2)
    got, got_pool = _paged_decode_run(cfg, plan, seg, steps=2)
    np.testing.assert_array_equal(got, want)
    for name in want_pool:
        np.testing.assert_array_equal(np.asarray(got_pool[name]),
                                      np.asarray(want_pool[name]))
    # every layer of the pool was written by its own layer
    assert all(np.any(np.asarray(want_pool["k"][l]))
               for l in range(cfg.n_layers))


# ---------------------------------------------------------------------------
# block allocator / scheduler
# ---------------------------------------------------------------------------

def test_block_allocator_leak_and_double_free():
    a = BlockAllocator(8)
    x = a.alloc(3)
    y = a.alloc(5)
    assert a.num_free == 0 and a.alloc(1) is None
    a.free(y)
    assert a.num_free == 5 and a.peak_in_use == 8
    with pytest.raises(ValueError):
        a.free(y[:1])               # double free
    with pytest.raises(ValueError):
        a.free([99])                # unknown block
    a.free(x)
    assert a.num_free == 8


def test_scheduler_buckets_and_admission():
    a = BlockAllocator(6)
    s = Scheduler(max_slots=2, allocator=a, buckets=(8, 16), block_size=4,
                  max_blocks_per_slot=4)
    assert s.bucket_for(3) == 8 and s.bucket_for(9) == 16
    with pytest.raises(ValueError):
        s.bucket_for(17)
    r1 = s.submit(Request(prompt=np.arange(8), max_new_tokens=5))
    r2 = s.submit(Request(prompt=np.arange(8), max_new_tokens=5))
    r3 = s.submit(Request(prompt=np.arange(4), max_new_tokens=2))
    adm = s.admit()
    assert [r.rid for r in adm] == [r1.rid, r2.rid]   # 3 pages each
    assert s.admit() == []          # no free slot (and no pages)
    s.release(r1)
    assert [r.rid for r in s.admit()] == [r3.rid]
    s.release(r2)
    s.release(r3)
    assert a.num_free == 6 and s.idle


# ---------------------------------------------------------------------------
# packed-QT serving (no materialize)
# ---------------------------------------------------------------------------

def test_packed_qt_serve_matches_materialized():
    cfg, plan, params = _f32_setup()
    calib = jax.random.randint(KEY, (4, 32), 0, cfg.vocab_size)
    spec = QuantSpec(bits=4, granularity="per_channel", lam=0.9, sweeps=2,
                     order="cyclic")
    qparams, _ = quantize_model(params, cfg, plan, calib, spec)
    packed = serving_params(qparams, cfg)
    mat = materialize(qparams, cfg)

    from repro.core.apply import is_qt
    assert any(is_qt(l) for l in
               jax.tree_util.tree_leaves(packed, is_leaf=is_qt))

    prompts = [np.asarray(jax.random.randint(KEY, (12,), 0,
                                             cfg.vocab_size)),
               np.asarray(jax.random.randint(jax.random.PRNGKey(7), (16,),
                                             0, cfg.vocab_size))]
    rt_q = _runtime(packed, cfg, plan)
    rt_m = _runtime(mat, cfg, plan)
    out_q = rt_q.generate(prompts, max_new_tokens=8)
    out_m = rt_m.generate(prompts, max_new_tokens=8)
    for a, b in zip(out_q, out_m):
        np.testing.assert_array_equal(a, b)

    # logits-level agreement of the fused quant_matmul decode path
    from repro.models import prefill, decode_step
    plan2 = plan.replace(prefill_cache_len=20)
    tokens = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    lq, cq = prefill(packed, cfg, plan2, tokens)
    lm, cm = prefill(mat, cfg, plan2, tokens)
    np.testing.assert_allclose(np.asarray(lq), np.asarray(lm), atol=1e-5)
    gq, _ = decode_step(packed, cfg, plan2, cq, tokens[:, :1], jnp.int32(16))
    gm, _ = decode_step(mat, cfg, plan2, cm, tokens[:, :1], jnp.int32(16))
    np.testing.assert_allclose(np.asarray(gq), np.asarray(gm), atol=1e-5)


def test_serving_params_stripped_checkpoint_roundtrip():
    """pack -> strip -> unpack -> serve: byte-light checkpoint reconstructs
    both the packed serving tree and the materialized tree exactly."""
    from repro.ckpt import pack_tree, strip_for_serving, tree_bytes, \
        unpack_tree
    cfg, plan, params = _f32_setup()
    calib = jax.random.randint(KEY, (2, 32), 0, cfg.vocab_size)
    spec = QuantSpec(bits=4, granularity="per_channel", lam=0.9, sweeps=1,
                     order="cyclic")
    qparams, _ = quantize_model(params, cfg, plan, calib, spec)
    stripped = pack_tree(strip_for_serving(qparams))
    assert tree_bytes(stripped) < tree_bytes(pack_tree(qparams))
    restored = unpack_tree(stripped)

    mat_a = materialize(qparams, cfg)
    mat_b = materialize(restored, cfg)
    for a, b in zip(jax.tree_util.tree_leaves(mat_a),
                    jax.tree_util.tree_leaves(mat_b)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)

    sp = serving_params(restored, cfg)
    prompts = [np.asarray(jax.random.randint(KEY, (10,), 0,
                                             cfg.vocab_size))]
    out_a = _runtime(sp, cfg, plan).generate(prompts, max_new_tokens=4)
    out_b = _runtime(mat_a, cfg, plan).generate(prompts, max_new_tokens=4)
    np.testing.assert_array_equal(out_a[0], out_b[0])


def test_serving_params_single_layer_stack():
    """Regression: a 1-layer model's scan-sliced QT (static shape
    (1, d, H, hd), 2D codes) must dequantize to the logical per-layer
    rank, not rebroadcast the unit stack dim."""
    cfg = get_smoke_config("qwen2-7b").replace(compute_dtype="float32",
                                               n_layers=1)
    plan = BuildPlan(remat=False, cache_dtype=jnp.float32,
                     prefill_cache_len=20)
    params = init_params(KEY, cfg, plan)
    calib = jax.random.randint(KEY, (2, 32), 0, cfg.vocab_size)
    spec = QuantSpec(bits=4, granularity="per_channel", lam=0.9, sweeps=1,
                     order="cyclic")
    qparams, _ = quantize_model(params, cfg, plan, calib, spec)
    sp = serving_params(qparams, cfg)
    mat = materialize(qparams, cfg)
    from repro.models import prefill
    tokens = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    lq, _ = prefill(sp, cfg, plan, tokens)
    lm, _ = prefill(mat, cfg, plan, tokens)
    np.testing.assert_allclose(np.asarray(lq), np.asarray(lm), atol=1e-5)


def test_serving_params_mqa_single_kv_head():
    """Regression: MQA (n_kv_heads=1) wk/wv QTs must resolve their output
    dims to (1, hd), not (hd,) — the unit KV axis is not a stack dim."""
    cfg = get_smoke_config("qwen2-7b").replace(compute_dtype="float32",
                                               n_kv_heads=1)
    plan = BuildPlan(remat=False, cache_dtype=jnp.float32,
                     prefill_cache_len=20)
    params = init_params(KEY, cfg, plan)
    calib = jax.random.randint(KEY, (2, 32), 0, cfg.vocab_size)
    spec = QuantSpec(bits=4, granularity="per_channel", lam=0.9, sweeps=1,
                     order="cyclic")
    qparams, _ = quantize_model(params, cfg, plan, calib, spec)
    sp = serving_params(qparams, cfg)
    mat = materialize(qparams, cfg)
    from repro.models import decode_step, prefill
    tokens = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    lq, cq = prefill(sp, cfg, plan, tokens)
    lm, cm = prefill(mat, cfg, plan, tokens)
    np.testing.assert_allclose(np.asarray(lq), np.asarray(lm), atol=1e-5)
    gq, _ = decode_step(sp, cfg, plan, cq, tokens[:, :1], jnp.int32(16))
    gm, _ = decode_step(mat, cfg, plan, cm, tokens[:, :1], jnp.int32(16))
    np.testing.assert_allclose(np.asarray(gq), np.asarray(gm), atol=1e-5)


def test_vlm_stripped_checkpoint_materializes():
    """strip_for_serving drops the VLM 'groups' stacks too; materialize
    rebuilds them from the table bit-identically."""
    from repro.ckpt import pack_tree, strip_for_serving, tree_bytes, \
        unpack_tree
    cfg = get_smoke_config("llama-3.2-vision-90b").replace(
        compute_dtype="float32")
    plan = BuildPlan(remat=False, cache_dtype=jnp.float32)
    params = init_params(KEY, cfg, plan)
    calib = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    ve = jax.random.normal(KEY, (2, cfg.cross_attn.n_vision_tokens,
                                 cfg.cross_attn.vision_dim), jnp.float32)
    spec = QuantSpec(bits=4, granularity="per_channel", lam=0.9, sweeps=1,
                     order="cyclic")
    qparams, _ = quantize_model(params, cfg, plan, calib, spec,
                                vision_embeds=ve)
    stripped = pack_tree(strip_for_serving(qparams))
    assert tree_bytes(stripped) < tree_bytes(pack_tree(qparams))
    mat_a = materialize(qparams, cfg)
    mat_b = materialize(unpack_tree(stripped), cfg)
    for a, b in zip(jax.tree_util.tree_leaves(mat_a),
                    jax.tree_util.tree_leaves(mat_b)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


# ---------------------------------------------------------------------------
# streaming + metrics surface
# ---------------------------------------------------------------------------

def test_streaming_callback_and_metrics():
    cfg, plan, params = _f32_setup()
    seen = []
    rt = _runtime(params, cfg, plan)
    p = np.asarray(jax.random.randint(KEY, (9,), 0, cfg.vocab_size))
    req = rt.submit(p, max_new_tokens=5,
                    stream_cb=lambda r, t: seen.append((r.rid, t)))
    m = rt.run()
    assert [t for _, t in seen] == req.out_tokens
    assert m["requests"] == 1 and m["new_tokens"] == 5
    assert m["ttft_s"][0] >= 0.0 and len(req.itl) == 4
    assert 0 < m["cache_peak_occupancy"] <= 1.0
    assert m["finish_reasons"] == ["length"]


# ---------------------------------------------------------------------------
# EOS / stop-token termination
# ---------------------------------------------------------------------------

def test_stop_token_terminates_early_and_frees_pages():
    """A request whose greedy stream hits its stop token retires on that
    step: the stop token is the last emitted token, no tokens follow it,
    slot + every reserved page return to the pool, and run() metrics
    count only the actually-emitted tokens."""
    cfg, plan, params = _f32_setup()
    p = np.asarray(jax.random.randint(KEY, (9,), 0, cfg.vocab_size))
    # discover what greedy would emit, then stop on its 3rd token
    ref = _runtime(params, cfg, plan).generate([p], max_new_tokens=8)[0]
    stop = int(ref[2])
    rt = _runtime(params, cfg, plan)
    req = rt.submit(p, max_new_tokens=8, stop_tokens=(stop,))
    m = rt.run()
    assert req.finish_reason == "stop_token"
    assert req.out_tokens[-1] == stop
    assert len(req.out_tokens) == 3
    np.testing.assert_array_equal(np.asarray(req.out_tokens), ref[:3])
    assert m["new_tokens"] == 3 and m["finish_reasons"] == ["stop_token"]
    assert rt.allocator.num_free == rt.allocator.num_blocks
    assert not rt.scheduler.running and not rt.scheduler.queue


def test_stop_token_on_first_prefill_token():
    """The TTFT token itself can be the stop token — the request retires
    at admission without entering the decode batch."""
    cfg, plan, params = _f32_setup()
    p = np.asarray(jax.random.randint(KEY, (9,), 0, cfg.vocab_size))
    first = int(_runtime(params, cfg, plan).generate(
        [p], max_new_tokens=1)[0][0])
    rt = _runtime(params, cfg, plan)
    req = rt.submit(p, max_new_tokens=8, stop_tokens=(first,))
    m = rt.run()
    assert req.out_tokens == [first]
    assert req.finish_reason == "stop_token"
    assert m["decode_steps"] == 0
    assert rt.allocator.num_free == rt.allocator.num_blocks


def test_stop_token_preserves_batchmates_token_identity():
    """One request stopping early must not perturb the other slots: the
    surviving requests' tokens equal their solo runs, and the freed pages
    let a queued request admit sooner."""
    cfg, plan, params = _f32_setup()
    rs = np.random.RandomState(5)
    prompts = [rs.randint(0, cfg.vocab_size, (l,)).astype(np.int32)
               for l in (9, 12, 7)]
    solo = [_runtime(params, cfg, plan).generate([p], max_new_tokens=8)[0]
            for p in prompts]
    stop = int(solo[0][1])      # request 0 stops after 2 tokens
    # make the stopper's stop token unique to it: if another stream also
    # emits it the test would conflate retirements
    assert stop not in solo[1][:8] and stop not in solo[2][:8]
    rt = _runtime(params, cfg, plan, max_slots=2, num_blocks=12)
    reqs = [rt.submit(prompts[0], max_new_tokens=8, stop_tokens=(stop,)),
            rt.submit(prompts[1], max_new_tokens=8),
            rt.submit(prompts[2], max_new_tokens=8)]   # queued (2 slots)
    rt.run()
    assert reqs[0].finish_reason == "stop_token"
    np.testing.assert_array_equal(np.asarray(reqs[0].out_tokens),
                                  solo[0][:2])
    np.testing.assert_array_equal(np.asarray(reqs[1].out_tokens), solo[1])
    np.testing.assert_array_equal(np.asarray(reqs[2].out_tokens), solo[2])
    assert rt.allocator.num_free == rt.allocator.num_blocks


# ---------------------------------------------------------------------------
# priority admission + preemption-by-page-reclaim
# ---------------------------------------------------------------------------

def test_priority_admission_order():
    """Admission is (priority, rid): priority class first, arrival order
    within a class — and priority=0 everywhere degrades to FCFS."""
    a = BlockAllocator(24)
    s = Scheduler(max_slots=1, allocator=a, buckets=(8,), block_size=4,
                  max_blocks_per_slot=4)
    lo1 = s.submit(Request(prompt=np.arange(4), max_new_tokens=2,
                           priority=5))
    hi = s.submit(Request(prompt=np.arange(4), max_new_tokens=2,
                          priority=0))
    lo2 = s.submit(Request(prompt=np.arange(4), max_new_tokens=2,
                           priority=5))
    order = []
    while not s.idle:
        adm = s.admit()
        assert len(adm) == 1        # one slot
        order.append(adm[0].rid)
        s.release(adm[0])
    assert order == [hi.rid, lo1.rid, lo2.rid]
    assert a.num_free == a.num_blocks


def test_admission_preempts_running_low_priority():
    """A strictly more urgent head reclaims the victim's slot+pages at
    admission; the victim re-queues (state machine only, no model)."""
    a = BlockAllocator(4)
    s = Scheduler(max_slots=1, allocator=a, buckets=(8,), block_size=4,
                  max_blocks_per_slot=4)
    lo = s.submit(Request(prompt=np.arange(8), max_new_tokens=5,
                          priority=5))
    assert s.admit() == [lo]
    lo.out_tokens = [1, 2]          # mid-flight progress
    hi = s.submit(Request(prompt=np.arange(8), max_new_tokens=5,
                          priority=0))
    cleared = []
    adm = s.admit(on_preempt=cleared.append)
    assert adm == [hi] and cleared == [lo]
    assert lo.state == "queued" and lo.slot == -1 and not lo.blocks
    assert lo.n_preempts == 1 and s.preemptions == 1
    # equal urgency must NOT preempt: a same-class later arrival waits
    eq = s.submit(Request(prompt=np.arange(8), max_new_tokens=5,
                          priority=0))
    assert s.admit() == []
    assert eq.state == "queued" and hi.state == "running"
    s.release(hi)
    a.check_integrity()


def test_starvation_freedom_preempted_keeps_rid():
    """A preempted request keeps its rid, so within its priority class it
    re-admits ahead of every later arrival — bounded bypass, no
    starvation."""
    a = BlockAllocator(24)
    s = Scheduler(max_slots=1, allocator=a, buckets=(8,), block_size=4,
                  max_blocks_per_slot=4)
    old = s.submit(Request(prompt=np.arange(4), max_new_tokens=2,
                           priority=1))
    s.admit()
    s.preempt(old)
    newer = s.submit(Request(prompt=np.arange(4), max_new_tokens=2,
                             priority=1))
    assert s.admit() == [old]       # not newer: old's rid is smaller
    assert newer.state == "queued"
    s.release(old)
    assert s.admit() == [newer]
    s.release(newer)


@pytest.mark.parametrize("arch", ["qwen2-7b", "granite-moe-3b-a800m"])
def test_preempt_resume_token_identity(arch):
    """Pool too small for all requests' lifetimes: decode growth preempts
    and resumes mid-stream, yet every request's tokens equal its solo run
    (recompute-based resume feeds the last emitted token through the
    normal decode program). Allocator ends clean."""
    cfg, plan, params = _f32_setup(arch)
    rs = np.random.RandomState(7)
    prompts = [rs.randint(0, cfg.vocab_size, (l,)).astype(np.int32)
               for l in (14, 9, 12)]
    solo = [_runtime(params, cfg, plan).generate([p], max_new_tokens=8)[0]
            for p in prompts]

    # 3 slots but only 6 pages: three 2-page prefills admit, decode growth
    # past each 16-row boundary must reclaim someone's pages
    rt = _runtime(params, cfg, plan, num_blocks=6)
    reqs = [rt.submit(p, max_new_tokens=8) for p in prompts]
    rt.run()
    assert rt.scheduler.preemptions > 0
    for r, want in zip(reqs, solo):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), want)
    assert rt.allocator.num_free == rt.allocator.num_blocks
    rt.allocator.check_integrity()
    assert rt.scheduler.idle


def test_priority_latecomer_finishes_first():
    """An urgent request arriving after low-priority traffic saturates the
    pool preempts a victim, runs immediately, and still emits exactly its
    solo tokens — as do the preempted victims after resume."""
    cfg, plan, params = _f32_setup()
    rs = np.random.RandomState(11)
    prompts = [rs.randint(0, cfg.vocab_size, (10,)).astype(np.int32)
               for _ in range(3)]
    solo = [_runtime(params, cfg, plan).generate([p], max_new_tokens=6)[0]
            for p in prompts]
    rt = _runtime(params, cfg, plan, max_slots=2, num_blocks=4)
    lo = [rt.submit(p, max_new_tokens=6, priority=5) for p in prompts[:2]]
    rt.step()                       # the low-priority pair gets going
    hi = rt.submit(prompts[2], max_new_tokens=6, priority=0)
    rt.run()
    assert rt.scheduler.preemptions > 0
    done = [r.rid for r in rt.scheduler.completed]
    assert done.index(hi.rid) < max(done.index(r.rid) for r in lo)
    for r, want in zip(lo + [hi], solo):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), want)
    assert rt.allocator.num_free == rt.allocator.num_blocks


def test_reserve_policy_never_preempts():
    """policy="reserve" keeps the PR-4 contract: full-lifetime pages at
    admission, zero preemptions, exhaustion backpressures the queue."""
    cfg, plan, params = _f32_setup()
    rs = np.random.RandomState(13)
    prompts = [rs.randint(0, cfg.vocab_size, (10,)).astype(np.int32)
               for _ in range(3)]
    rt = _runtime(params, cfg, plan, num_blocks=6, policy="reserve")
    reqs = [rt.submit(p, max_new_tokens=8) for p in prompts]
    rt.run()
    assert rt.scheduler.preemptions == 0
    assert all(len(r.out_tokens) == 8 for r in reqs)
    assert rt.allocator.num_free == rt.allocator.num_blocks


def test_allocator_integrity_under_injected_alloc_faults():
    """Seeded page-alloc failures at admission and growth: no leak, no
    double free, no lost request — every stream still matches solo."""
    from repro.ft import FaultInjector
    from repro.serve import ServeConfig
    cfg, plan, params = _f32_setup()
    rs = np.random.RandomState(17)
    prompts = [rs.randint(0, cfg.vocab_size, (l,)).astype(np.int32)
               for l in (12, 9, 14)]
    solo = [_runtime(params, cfg, plan).generate([p], max_new_tokens=6)[0]
            for p in prompts]
    inj = FaultInjector({"page_alloc": {2, 4, 7}})
    sc = ServeConfig(max_slots=3, block_size=8, num_blocks=24,
                     buckets=(8, 16, 32), max_blocks_per_slot=6)
    rt = Runtime(params, cfg, plan, sc, injector=inj)
    reqs = [rt.submit(p, max_new_tokens=6) for p in prompts]
    rt.run()
    assert [pt for pt, _ in inj.fired] == ["page_alloc"] * 3
    for r, want in zip(reqs, solo):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), want)
    assert rt.allocator.num_free == rt.allocator.num_blocks
    rt.allocator.check_integrity()
    assert len(rt.scheduler.completed) == 3     # none lost, none duplicated
    assert sorted(r.rid for r in rt.scheduler.completed) == [0, 1, 2]
