"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Each kernel test sweeps shapes/dtypes and asserts allclose against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def quant_matmul_ref(x: Array, codes_u: Array, scale: Array, z_lo: Array,
                     out_dtype=jnp.float32) -> Array:
    """x: (M, K); codes_u: (K, N) uint8 offset-binary; scale/z_lo: (N,).

    Y = X · W_q,  W_q[k, n] = scale[n] · (codes_u[k, n] + z_lo[n]).
    """
    w = (codes_u.astype(jnp.float32) + z_lo.astype(jnp.float32)) * scale
    return (x.astype(jnp.float32) @ w).astype(out_dtype)


def quant_matmul_packed_ref(x: Array, codes_p: Array, scale: Array,
                            z_lo: Array, *, cpb: int,
                            out_dtype=jnp.float32) -> Array:
    """Packed-storage oracle: codes_p (K, N/cpb) at `cpb` codes per byte
    (quantizer.codes_per_byte — 4 for 2-bit, 2 for 3/4-bit, 1 pass-through)
    is unpacked then contracted; ground truth for every mixed-precision
    storage layout the serve path streams."""
    from repro.core.quantizer import unpack_codes
    return quant_matmul_ref(x, unpack_codes(codes_p, cpb), scale, z_lo,
                            out_dtype=out_dtype)


def paged_attention_ref(q: Array, k_pool: Array, v_pool: Array,
                        block_tables: Array, lengths: Array, *,
                        window: int = 0) -> Array:
    """q: (B, H, hd); k_pool/v_pool: (NB, BS, KV, hd), one layer's pages
    with the heads split out (`ops.paged_attention` hands it `pool[layer]`
    so); block_tables:
    (B, MAXB); lengths: (B,). Pure-XLA oracle: gather the slot's pages into
    a contiguous (B, MAXB·BS, KV, hd) view, then masked softmax attention.
    Inactive slots (length 0) return exact zeros, matching the kernel."""
    B, H, hd = q.shape
    NB, BS, KV, _ = k_pool.shape
    S = block_tables.shape[1] * BS
    idx = (block_tables[:, :, None] * BS
           + jnp.arange(BS, dtype=jnp.int32)[None, None]).reshape(B, S)
    kg = k_pool.reshape(NB * BS, KV, hd)[idx].astype(jnp.float32)
    vg = v_pool.reshape(NB * BS, KV, hd)[idx].astype(jnp.float32)
    g = H // KV
    qg = q.astype(jnp.float32).reshape(B, KV, g, hd)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    s = jnp.einsum("bkgh,bskh->bkgs", qg, kg) * scale
    kpos = jnp.arange(S, dtype=jnp.int32)[None]
    mask = kpos < lengths[:, None]
    if window > 0:
        mask &= (lengths[:, None] - 1) - kpos < window
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where((lengths > 0)[:, None, None, None], p, 0.0)
    return jnp.einsum("bkgs,bskh->bkgh", p, vg).reshape(B, H, hd)


def paged_attention_quant_ref(q: Array, k_pool: Array, v_pool: Array,
                              k_scale: Array, v_scale: Array,
                              block_tables: Array, lengths: Array, *,
                              window: int = 0, kv_bits: int = 8) -> Array:
    """Quantized-pool oracle: k_pool/v_pool hold integer codes
    (NB, BS, KV, hd/cpb — int8, or packed 4-bit nibble pairs) with one f32
    scale per (page, kv_head) in k_scale/v_scale (NB, KV). Dequantizes
    page-wise with `serve.kv_cache.kv_decode` and delegates to the bf16
    oracle — the ground truth both the Pallas in-kernel dequant and the
    XLA gather fallback must match."""
    from repro.serve.kv_cache import kv_decode
    kd = kv_decode(k_pool, k_scale[:, None], kv_bits)   # (NB, BS, KV, hd)
    vd = kv_decode(v_pool, v_scale[:, None], kv_bits)
    return paged_attention_ref(q, kd, vd, block_tables, lengths,
                               window=window)


def comq_panel_ref(h_bb: Array, s0: Array, qf: Array, delta: Array,
                   z_lo: Array, z_hi: Array, hdiag: Array) -> Array:
    """Intra-panel COMQ sweep oracle — delegates to the core reference."""
    from repro.core.comq_hessian import panel_sweep_ref
    return panel_sweep_ref(h_bb, s0, qf, delta, z_lo, z_hi, hdiag)


def comq_panel_dq_ref(h_bb: Array, s0: Array, qf: Array, delta: Array,
                      z_lo: Array, z_hi: Array, hdiag: Array):
    """Fused (qf', ΔW) panel sweep oracle — delegates to the core ref."""
    from repro.core.comq_hessian import panel_sweep_dq_ref
    return panel_sweep_dq_ref(h_bb, s0, qf, delta, z_lo, z_hi, hdiag)


def flash_attention_ref(q: Array, k: Array, v: Array, *, causal: bool = True,
                        window: int = 0) -> Array:
    """q: (BH, Tq, hd); k/v: (BH_kv, Tk, hd) with BH % BH_kv == 0 (GQA).

    Plain softmax attention oracle in f32.
    """
    g = q.shape[0] // k.shape[0]
    k = jnp.repeat(k, g, axis=0)
    v = jnp.repeat(v, g, axis=0)
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
    s = jnp.einsum("btk,bsk->bts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    Tq, Tk = q.shape[1], k.shape[1]
    if causal:
        qpos = jnp.arange(Tq)[:, None]
        kpos = jnp.arange(Tk)[None, :]
        mask = qpos >= kpos
        if window > 0:
            mask &= qpos - kpos < window
        s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bts,bsk->btk", p, v.astype(jnp.float32))
