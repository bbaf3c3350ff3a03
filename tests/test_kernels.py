"""Pallas kernel validation: shape/dtype sweeps in interpret mode against
the pure-jnp oracles in kernels/ref.py (per assignment)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.comq_panel import comq_panel_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.quant_matmul import quant_matmul_pallas

KEY = jax.random.PRNGKey(42)


@pytest.mark.parametrize("mkn", [(64, 256, 128), (128, 512, 128),
                                 (32, 128, 256), (40, 256, 256)])
@pytest.mark.parametrize("bits", [8, 4, 2])
@pytest.mark.parametrize("xdtype", [jnp.float32, jnp.bfloat16])
def test_quant_matmul_sweep(mkn, bits, xdtype):
    """Interpret-mode kernel vs the unpacked oracle at every storage
    density (cpb 1/2/4, planar packing), multi-block grids and a ragged
    M that the wrapper pads."""
    from repro.core.quantizer import pack_codes
    M, K, N = mkn
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, M + K + N + bits))
    x = jax.random.normal(k1, (M, K), xdtype)
    u = jax.random.randint(k2, (K, N), 0, 2 ** bits).astype(jnp.uint8)
    scale = jax.random.uniform(k1, (N,), jnp.float32, 0.01, 0.05)
    z = jax.random.randint(k2, (N,), -(2 ** (bits - 1)), 0).astype(jnp.int32)
    want = ref.quant_matmul_ref(x.astype(jnp.float32), u, scale, z)
    codes, cpb = pack_codes(u, bits)
    assert cpb == {8: 1, 4: 2, 2: 4}[bits]
    got = quant_matmul_pallas(x, codes, scale, z, bits=bits, cpb=cpb,
                              bm=32, bn=64 // max(1, cpb // 2), bk=128,
                              interpret=True)
    rel = float(jnp.max(jnp.abs(got - want)) /
                (jnp.max(jnp.abs(want)) + 1e-9))
    assert rel < 3e-2, rel  # bf16 MXU accumulation tolerance


@pytest.mark.parametrize("bn", [(16, 32), (32, 64), (64, 96)])
def test_comq_panel_sweep(bn):
    B, n = bn
    ks = jax.random.split(jax.random.fold_in(KEY, B * n), 5)
    h = jax.random.normal(ks[0], (B, 4 * B))
    h_bb = h @ h.T / (4 * B) + jnp.eye(B) * 0.1
    s0 = jax.random.normal(ks[1], (B, n))
    qf = jax.random.normal(ks[2], (B, n)) * 3
    delta = jax.random.uniform(ks[3], (n,), minval=0.05, maxval=0.2)
    z_lo = jnp.full((n,), -8.0)
    z_hi = jnp.full((n,), 7.0)
    want = ref.comq_panel_ref(h_bb, s0, qf, delta, z_lo, z_hi,
                              jnp.diag(h_bb))
    got = comq_panel_pallas(h_bb, s0, qf, delta, z_lo, z_hi,
                            jnp.diag(h_bb), col_block=32, interpret=True)
    assert bool(jnp.all(want == got)), "panel kernel must be bit-exact"


@pytest.mark.parametrize("cfg", [
    dict(BH=4, BHkv=2, T=256, hd=64, causal=True, window=0),
    dict(BH=8, BHkv=8, T=128, hd=32, causal=True, window=0),
    dict(BH=4, BHkv=1, T=256, hd=64, causal=True, window=96),
    dict(BH=2, BHkv=2, T=128, hd=64, causal=False, window=0),
    dict(BH=6, BHkv=3, T=192, hd=16, causal=True, window=0),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(cfg, dtype):
    ks = jax.random.split(jax.random.fold_in(KEY, cfg["BH"] * cfg["T"]
                                             + cfg["window"]), 3)
    q = jax.random.normal(ks[0], (cfg["BH"], cfg["T"], cfg["hd"]), dtype)
    k = jax.random.normal(ks[1], (cfg["BHkv"], cfg["T"], cfg["hd"]), dtype)
    v = jax.random.normal(ks[2], (cfg["BHkv"], cfg["T"], cfg["hd"]), dtype)
    want = ref.flash_attention_ref(q.astype(jnp.float32),
                                   k.astype(jnp.float32),
                                   v.astype(jnp.float32),
                                   causal=cfg["causal"],
                                   window=cfg["window"])
    got = flash_attention_pallas(q, k, v, causal=cfg["causal"],
                                 window=cfg["window"], bq=64, bk=64,
                                 interpret=True)
    atol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=atol, rtol=atol)


def test_model_flash_matches_dense_reference():
    """The model's jnp pair-scan flash (models/attention.py) against the
    kernel oracle — same math, different schedule."""
    from repro.models.attention import flash_attention, head_to_kv_map
    B, T, H, KV, hd = 2, 128, 8, 2, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, T, H, hd))
    k = jax.random.normal(ks[1], (B, T, KV, hd))
    v = jax.random.normal(ks[2], (B, T, KV, hd))
    hmap = head_to_kv_map(H, H, KV)
    out = flash_attention(q, k, v, hmap, causal=True, window=0,
                          block_size=32)
    want = ref.flash_attention_ref(
        q.transpose(0, 2, 1, 3).reshape(B * H, T, hd),
        k.transpose(0, 2, 1, 3).reshape(B * KV, T, hd),
        v.transpose(0, 2, 1, 3).reshape(B * KV, T, hd), causal=True)
    want = want.reshape(B, H, T, hd).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


def test_model_flash_sliding_window():
    from repro.models.attention import flash_attention, head_to_kv_map
    B, T, H, KV, hd, w = 1, 128, 4, 4, 16, 48
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, T, H, hd))
    k = jax.random.normal(ks[1], (B, T, KV, hd))
    v = jax.random.normal(ks[2], (B, T, KV, hd))
    hmap = head_to_kv_map(H, H, KV)
    out = flash_attention(q, k, v, hmap, causal=True, window=w,
                          block_size=32)
    want = ref.flash_attention_ref(
        q.transpose(0, 2, 1, 3).reshape(B * H, T, hd),
        k.transpose(0, 2, 1, 3).reshape(B * KV, T, hd),
        v.transpose(0, 2, 1, 3).reshape(B * KV, T, hd), causal=True,
        window=w)
    want = want.reshape(B, H, T, hd).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
