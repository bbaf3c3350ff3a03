"""Per-leaf mixed-precision policy engine (core/policy.py): resolution
order, the budgeted backprop-free allocator, uniform-policy bit-identity
with the global-QuantSpec path, and mixed-bit packing/serving/ckpt."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import (QuantPolicy, QuantSpec, allocate_bits, as_policy,
                        materialize, measure_bit_curves, parse_policy,
                        policy_from_budget, quantize_model, serving_params)
from repro.core.pipeline import is_qtensor, qtensor_bits
from repro.core.quantizer import (codes_per_byte, pack_codes, pack_int2,
                                  pack_int4, unpack_codes, unpack_int2)
from repro.models import BuildPlan, init_params

KEY = jax.random.PRNGKey(0)
PLAN = BuildPlan(remat=False)
SPEC = QuantSpec(bits=4, granularity="per_channel", lam=0.9, sweeps=2,
                 order="greedy")


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


# ---------------------------------------------------------------------------
# policy resolution
# ---------------------------------------------------------------------------

def test_resolution_order_rules_then_overrides_then_base():
    pol = QuantPolicy(base=SPEC, rules=(("*.w_down", 8), ("2.attn.wq", 3)),
                      first_layer_bits=8, last_layer_bits=8)
    n = 6
    # pattern rules win over first/last overrides
    assert pol.resolve("mlp.w_down", 0, n).bits == 8
    assert pol.resolve("attn.wq", 2, n).bits == 3         # layer-qualified
    assert pol.resolve("attn.wq", 3, n).bits == 4         # base
    assert pol.resolve("attn.wq", 0, n).bits == 8         # first override
    assert pol.resolve("attn.wq", n - 1, n).bits == 8     # last override
    # only bits vary; everything else stays policy-wide
    r = pol.resolve("mlp.w_down", 3, n)
    assert (r.granularity, r.lam, r.sweeps, r.order) == \
        (SPEC.granularity, SPEC.lam, SPEC.sweeps, SPEC.order)


def test_first_rule_wins_and_uniform_detection():
    pol = QuantPolicy(base=SPEC, rules=(("mlp.*", 2), ("mlp.w_down", 8)))
    assert pol.resolve("mlp.w_down", 1, 4).bits == 2      # first match
    assert not pol.is_uniform()
    assert QuantPolicy(base=SPEC).is_uniform()
    assert as_policy(SPEC).resolve("attn.wq", 0, 4) == SPEC


def test_parse_policy_string():
    pol = parse_policy("*.w_down=8,first=8,last=8,kv=8,3.attn.wq=2", SPEC)
    assert ("*.w_down", 8) in pol.rules and ("3.attn.wq", 2) in pol.rules
    assert pol.first_layer_bits == 8 and pol.last_layer_bits == 8
    assert pol.kv_bits == 8
    with pytest.raises(ValueError):
        parse_policy("w_down", SPEC)


def test_policy_dict_roundtrip():
    from repro.core.policy import policy_from_dict, policy_to_dict
    pol = QuantPolicy(base=SPEC, rules=(("*.w_down", 8),),
                      first_layer_bits=8, kv_bits=8)
    assert policy_from_dict(policy_to_dict(pol)) == pol


# ---------------------------------------------------------------------------
# packing: int2 + bits-dispatched pack_codes
# ---------------------------------------------------------------------------

def test_pack_int2_roundtrip():
    u = jnp.asarray(np.random.RandomState(0).randint(0, 4, (16, 24)),
                    jnp.uint8)
    p = pack_int2(u)
    assert p.shape == (16, 6)
    assert bool(jnp.all(unpack_int2(p) == u))


@pytest.mark.parametrize("cpb", [2, 4])
def test_planar_pack_layout(cpb):
    """Planar packing: bit field f of packed column c is code
    f·(n/cpb) + c — a packed tile widens into cpb contiguous planes with
    shifts and masks alone (what the TPU kernels rely on)."""
    rs = np.random.RandomState(cpb)
    w = 8 // cpb
    u = jnp.asarray(rs.randint(0, 2 ** w, (6, 8 * cpb)), jnp.uint8)
    p = np.asarray(pack_int4(u) if cpb == 2 else pack_int2(u))
    n = u.shape[1] // cpb
    assert p.shape == (6, n)
    un = np.asarray(u)
    for f in range(cpb):
        np.testing.assert_array_equal((p >> (w * f)) & (2 ** w - 1),
                                      un[:, f * n:(f + 1) * n])
    assert bool(jnp.all(unpack_codes(jnp.asarray(p), cpb) == u))


def test_pack_codes_dispatch_and_alignment_fallback():
    rs = np.random.RandomState(1)
    assert (codes_per_byte(2), codes_per_byte(3), codes_per_byte(4),
            codes_per_byte(8)) == (4, 2, 2, 1)
    u = jnp.asarray(rs.randint(0, 4, (8, 16)), jnp.uint8)
    packed, cpb = pack_codes(u, 2)
    assert cpb == 4 and packed.shape == (8, 4)
    assert bool(jnp.all(unpack_codes(packed, cpb) == u))
    # 3-bit codes fit nibbles
    u3 = jnp.asarray(rs.randint(0, 8, (8, 16)), jnp.uint8)
    packed3, cpb3 = pack_codes(u3, 3)
    assert cpb3 == 2 and bool(jnp.all(unpack_codes(packed3, cpb3) == u3))
    # 8-bit passes through
    u8 = jnp.asarray(rs.randint(0, 256, (8, 16)), jnp.uint8)
    packed8, cpb8 = pack_codes(u8, 8)
    assert cpb8 == 1 and packed8 is not None
    assert bool(jnp.all(packed8 == u8))
    # misaligned last dim: stored unpacked rather than padded
    u_odd = jnp.asarray(rs.randint(0, 4, (8, 15)), jnp.uint8)
    _, cpb_odd = pack_codes(u_odd, 2)
    assert cpb_odd == 1


def test_quant_matmul_bits_dispatch_matches_ref():
    """ops.quant_matmul over every storage density vs the unpacked oracle
    (the 2-bit four-per-byte layout takes the documented XLA fallback)."""
    from repro.kernels import ops, ref
    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.randn(8, 32), jnp.float32)
    for bits in (2, 3, 4, 8):
        u = jnp.asarray(rs.randint(0, 2 ** bits, (32, 16)), jnp.uint8)
        scale = jnp.asarray(rs.rand(16) * 0.1 + 0.01, jnp.float32)
        z = jnp.asarray(rs.randint(-2 ** (bits - 1), 0, (16,)), jnp.int32)
        want = ref.quant_matmul_ref(x, u, scale, z.astype(jnp.float32))
        packed, cpb = pack_codes(u, bits)
        got = ops.quant_matmul(x, packed, scale, z.astype(jnp.float32),
                               bits=bits, cpb=cpb, mode="xla")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5, err_msg=str(bits))
        got_ref = ref.quant_matmul_packed_ref(x, packed, scale,
                                              z.astype(jnp.float32), cpb=cpb)
        np.testing.assert_allclose(np.asarray(got_ref), np.asarray(want),
                                   rtol=1e-5)


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------

def _toy_curves():
    # leaf "a" is twice as sensitive as "b"; "c" tiny but very sensitive
    curves = {
        "a": {2: 8.0, 3: 4.0, 4: 2.0, 8: 0.5},
        "b": {2: 4.0, 3: 2.0, 4: 1.0, 8: 0.25},
        "c": {2: 100.0, 3: 10.0, 4: 1.0, 8: 0.0},
    }
    sizes = {"a": 1000, "b": 1000, "c": 10}
    return curves, sizes


def test_allocator_budget_satisfaction_and_endpoints():
    curves, sizes = _toy_curves()
    from repro.core.policy import alloc_bits_per_param
    for budget in (2.0, 2.5, 3.0, 4.0, 5.5, 8.0, 16.0):
        alloc = allocate_bits(curves, sizes, budget)
        assert alloc_bits_per_param(alloc, sizes) <= budget + 1e-9
    # endpoints are satisfied exactly
    assert set(allocate_bits(curves, sizes, 2.0).values()) == {2}
    assert set(allocate_bits(curves, sizes, 8.0).values()) == {8}
    with pytest.raises(ValueError):
        allocate_bits(curves, sizes, 1.0)     # below the smallest choice


def test_allocator_monotone_error_in_budget():
    curves, sizes = _toy_curves()

    def total_err(alloc):
        return sum(curves[l][alloc[l]] for l in alloc)

    prev_err = float("inf")
    prev_alloc = None
    for budget in np.linspace(2.0, 8.0, 25):
        alloc = allocate_bits(curves, sizes, float(budget))
        err = total_err(alloc)
        assert err <= prev_err + 1e-12, (budget, err, prev_err)
        if prev_alloc is not None:     # allocations nest
            assert all(alloc[l] >= prev_alloc[l] for l in alloc)
        prev_err, prev_alloc = err, alloc


def test_allocator_spends_where_it_matters():
    """The tiny, hyper-sensitive leaf upgrades first (best err/bit·param);
    the least sensitive big leaf is the last to leave 2 bits."""
    curves, sizes = _toy_curves()
    alloc = allocate_bits(curves, sizes, 3.0)
    assert alloc["c"] == 8                      # ~nothing to spend, huge gain
    assert alloc["a"] >= alloc["b"]             # a is more sensitive


def test_allocator_handles_nonconvex_curves():
    """A curve whose 3→4 step gains more per bit than 2→3 must not strand
    the leaf at 2 bits (the convexified merged step applies atomically)."""
    curves = {"x": {2: 10.0, 3: 9.9, 4: 1.0, 8: 0.5}}
    sizes = {"x": 100}
    alloc = allocate_bits(curves, sizes, 4.0)
    assert alloc["x"] == 4


def test_measured_curves_monotone_and_allocator_integration():
    cfg = get_smoke_config("qwen2-7b")
    params = init_params(KEY, cfg, PLAN)
    tokens = jax.random.randint(KEY, (2, 32), 0, cfg.vocab_size)
    base = dataclasses.replace(SPEC, sweeps=1, order="cyclic")
    curves, sizes = measure_bit_curves(params, cfg, PLAN, tokens, base)
    assert len(curves) == 7 * cfg.n_layers      # dense family leaf count
    for name, c in curves.items():
        assert c[2] >= c[3] >= c[4] >= c[8] >= 0.0, (name, c)
        assert sizes[name] > 0
    policy, alloc, _ = policy_from_budget(params, cfg, PLAN, tokens, base,
                                          4.0)
    from repro.core.policy import alloc_bits_per_param
    assert alloc_bits_per_param(alloc, sizes) <= 4.0 + 1e-9
    assert set(alloc) == set(curves)
    # the emitted policy reproduces the allocation exactly
    for name, bits in alloc.items():
        layer, leaf = name.split(".", 1)
        assert policy.resolve(leaf, int(layer), cfg.n_layers).bits == bits


# ---------------------------------------------------------------------------
# uniform-policy bit-identity with the global-QuantSpec path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["qwen2-7b", "granite-moe-3b-a800m"])
def test_uniform_policy_bit_identical_to_spec(arch):
    """QuantPolicy(base=spec) with no rules must reproduce the global-spec
    pipeline exactly — codes, zero-points, scales, shapes — including the
    fused shared-tap solves the default greedy order triggers."""
    cfg = get_smoke_config(arch)
    params = init_params(KEY, cfg, PLAN)
    tokens = jax.random.randint(KEY, (2, 32), 0, cfg.vocab_size)
    qp_spec, _ = quantize_model(params, cfg, PLAN, tokens, SPEC)
    qp_pol, _ = quantize_model(params, cfg, PLAN, tokens,
                               QuantPolicy(base=SPEC))
    a = _qtensor_leaves(qp_spec["__qlayers__"])
    b = _qtensor_leaves(qp_pol["__qlayers__"])
    assert a.keys() == b.keys() and len(a) > 0
    for key in a:
        assert bool(jnp.all(a[key]["codes"] == b[key]["codes"])), key
        assert bool(jnp.all(a[key]["z_lo"] == b[key]["z_lo"])), key
        np.testing.assert_array_equal(np.asarray(a[key]["scale"]),
                                      np.asarray(b[key]["scale"]),
                                      err_msg=str(key))
        assert a[key]["shape"] == b[key]["shape"]
        assert qtensor_bits(a[key]) == qtensor_bits(b[key]) == SPEC.bits


# ---------------------------------------------------------------------------
# mixed-bit pipeline + packed serving + checkpoint round-trip
# ---------------------------------------------------------------------------

def _mixed_setup():
    cfg = get_smoke_config("qwen2-7b").replace(compute_dtype="float32",
                                               n_layers=4)
    plan = BuildPlan(remat=False, cache_dtype=jnp.float32)
    params = init_params(KEY, cfg, plan)
    calib = jax.random.randint(KEY, (2, 32), 0, cfg.vocab_size)
    base = QuantSpec(bits=4, granularity="per_channel", lam=0.9, sweeps=1,
                     order="cyclic")
    pol = QuantPolicy(base=base, rules=(("*.w_down", 8),),
                      first_layer_bits=8, last_layer_bits=8)
    qparams, _ = quantize_model(params, cfg, plan, calib, pol)
    return cfg, plan, params, qparams


def test_mixed_policy_assigns_per_leaf_bits():
    cfg, plan, _, qparams = _mixed_setup()
    leaves = _qtensor_leaves(qparams["__qlayers__"])
    bits = {k: qtensor_bits(v) for k, v in leaves.items()}
    assert bits[("0", "attn", "wq")] == 8          # first-layer override
    assert bits[("3", "mlp", "w_up")] == 8         # last-layer override
    assert bits[("1", "mlp", "w_down")] == 8       # pattern rule
    assert bits[("1", "attn", "wq")] == 4          # base
    # codes of the 8-bit leaves actually use the wider grid somewhere
    assert int(jnp.max(leaves[("1", "mlp", "w_down")]["codes"])) > 15


def test_mixed_serving_params_segments():
    from repro.core.apply import is_segmented
    cfg, plan, _, qparams = _mixed_setup()
    sp = serving_params(qparams, cfg)
    layers = sp["layers"]
    assert is_segmented(layers)
    assert sum(layers.sizes) == cfg.n_layers
    assert layers.sizes == (1, 2, 1)               # first | bulk | last
    # every segment's QT leaves are homogeneous and packed to their width
    from repro.core.apply import is_qt
    seg_bulk = layers.segments[1]
    wq = seg_bulk["attn"]["wq"]
    wd = seg_bulk["mlp"]["w_down"]
    assert is_qt(wq) and wq.bits == 4 and wq.cpb == 2
    assert is_qt(wd) and wd.bits == 8 and wd.cpb == 1
    first = layers.segments[0]["attn"]["wq"]
    assert first.bits == 8 and first.cpb == 1


def test_mixed_packed_serve_matches_materialized_tokens_and_logits():
    """Acceptance: a 4/8 mixed-policy model serves packed end-to-end (no
    materialize) with tokens identical to the materialized reference and
    matching logits."""
    from repro.serve import Runtime, ServeConfig
    cfg, plan, _, qparams = _mixed_setup()
    sp = serving_params(qparams, cfg)
    mat = materialize(qparams, cfg)

    def rt(p):
        return Runtime(p, cfg, plan,
                       ServeConfig(max_slots=2, block_size=8, num_blocks=16,
                                   buckets=(16,), max_blocks_per_slot=4))

    prompts = [np.asarray(jax.random.randint(KEY, (12,), 0,
                                             cfg.vocab_size)),
               np.asarray(jax.random.randint(jax.random.PRNGKey(7), (9,),
                                             0, cfg.vocab_size))]
    out_q = rt(sp).generate(prompts, max_new_tokens=8)
    out_m = rt(mat).generate(prompts, max_new_tokens=8)
    for a, b in zip(out_q, out_m):
        np.testing.assert_array_equal(a, b)

    from repro.models import decode_step, prefill
    plan2 = plan.replace(prefill_cache_len=20)
    tokens = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    lq, cq = prefill(sp, cfg, plan2, tokens)
    lm, cm = prefill(mat, cfg, plan2, tokens)
    np.testing.assert_allclose(np.asarray(lq), np.asarray(lm), atol=1e-5)
    gq, _ = decode_step(sp, cfg, plan2, cq, tokens[:, :1], jnp.int32(16))
    gm, _ = decode_step(mat, cfg, plan2, cm, tokens[:, :1], jnp.int32(16))
    np.testing.assert_allclose(np.asarray(gq), np.asarray(gm), atol=1e-5)


def test_mixed_ckpt_roundtrip_preserves_bits_and_tokens():
    """pack -> strip -> unpack -> serve for a mixed 4/8 table: per-leaf
    pack densities round-trip and the served tokens match materialized."""
    from repro.ckpt import pack_tree, strip_for_serving, unpack_tree
    from repro.serve import Runtime, ServeConfig
    cfg, plan, _, qparams = _mixed_setup()
    packed = pack_tree(strip_for_serving(qparams))
    pl4 = packed["__qlayers__"]["1"]["attn"]["wq"]
    pl8 = packed["__qlayers__"]["1"]["mlp"]["w_down"]
    assert pl4["packed_cpb"] == 2 and "packed_cpb" not in pl8
    restored = unpack_tree(packed)
    a = _qtensor_leaves(qparams["__qlayers__"])
    b = _qtensor_leaves(restored["__qlayers__"])
    for key in a:
        assert bool(jnp.all(a[key]["codes"] == b[key]["codes"])), key
        assert qtensor_bits(a[key]) == qtensor_bits(b[key])

    sp = serving_params(restored, cfg)
    mat = materialize(qparams, cfg)
    prompts = [np.asarray(jax.random.randint(KEY, (10,), 0,
                                             cfg.vocab_size))]

    def rt(p):
        return Runtime(p, cfg, plan,
                       ServeConfig(max_slots=2, block_size=8, num_blocks=16,
                                   buckets=(16,), max_blocks_per_slot=4))

    out_a = rt(sp).generate(prompts, max_new_tokens=4)
    out_b = rt(mat).generate(prompts, max_new_tokens=4)
    np.testing.assert_array_equal(out_a[0], out_b[0])


def test_policy_ckpt_metadata_roundtrip(tmp_path):
    from repro.ckpt import (CheckpointManager, pack_tree, policy_extra,
                            restore_policy, strip_for_serving, unpack_tree)
    cfg, plan, _, qparams = _mixed_setup()
    pol = QuantPolicy(base=SPEC, rules=(("*.w_down", 8),),
                      first_layer_bits=8)
    packed = pack_tree(strip_for_serving(qparams))
    mgr = CheckpointManager(str(tmp_path), keep=1)
    mgr.save(0, packed, extra=policy_extra(policy=pol, arch=cfg.name))
    restored, meta = mgr.restore(
        None, jax.tree_util.tree_map(lambda a: a, packed))
    assert meta["extra"]["arch"] == cfg.name
    assert restore_policy(meta["extra"]) == pol
    b = _qtensor_leaves(unpack_tree(restored)["__qlayers__"])
    a = _qtensor_leaves(qparams["__qlayers__"])
    for key in a:
        assert bool(jnp.all(a[key]["codes"] == b[key]["codes"])), key
