"""jit'd public wrappers around the Pallas kernels with backend dispatch.

On TPU the real kernels run; `interpret` executes the kernel body on the
CPU for correctness tests, and the `xla` mode uses the pure-jnp oracle
(what the dry-run lowers — Pallas does not lower to the host platform).
Mode resolution: an explicit argument, else the backend default — Pallas
on TPU, XLA elsewhere. There is no environment override: on a TPU the
kernels run unless a caller asks for another mode by name.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.comq_panel import (comq_panel_dq_pallas,
                                      comq_panel_pallas)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import (paged_attention_pallas,
                                           paged_attention_quant_pallas)
from repro.kernels.quant_matmul import quant_matmul_pallas

Array = jax.Array


def resolve_mode(mode: Optional[str] = None) -> str:
    if mode:
        if mode not in ("pallas", "interpret", "xla"):
            raise ValueError(f"unknown kernel mode {mode!r}")
        return mode
    return "pallas" if jax.default_backend() == "tpu" else "xla"


# ---------------------------------------------------------------------------


@functools.partial(jax.jit,
                   static_argnames=("bits", "cpb", "mode", "out_dtype"))
def quant_matmul(x: Array, codes_u: Array, scale: Array, z_lo: Array, *,
                 bits: int = 8, cpb: Optional[int] = None,
                 mode: Optional[str] = None,
                 out_dtype=jnp.float32) -> Array:
    """Y = X · (scale ⊙ (codes + z)) — bits-dispatched.

    `cpb` is the storage density (codes per byte, quantizer.codes_per_byte;
    defaults to the historical rule: nibble-packed iff bits==4). The Pallas
    kernel covers every layout — cpb ∈ {1, 2, 4}: unpacked any-bit codes,
    nibble-packed 3/4-bit codes, and the quad-packed 2-bit 4-per-byte
    layout (in-register quad unpack, so 2-bit decode streams a quarter of
    the bytes instead of paying an XLA unpack materialization)."""
    mode = resolve_mode(mode)
    if cpb is None:
        cpb = 2 if bits == 4 else 1
    if mode == "xla":
        from repro.core.quantizer import unpack_codes
        u = unpack_codes(codes_u, cpb)
        return ref.quant_matmul_ref(x, u, scale, z_lo, out_dtype=out_dtype)
    return quant_matmul_pallas(x, codes_u, scale, z_lo, cpb=cpb,
                               out_dtype=out_dtype,
                               interpret=(mode == "interpret"))


def comq_panel(h_bb: Array, s0: Array, qf: Array, delta: Array, z_lo: Array,
               z_hi: Array, hdiag: Array, *, mode: Optional[str] = None
               ) -> Array:
    mode = resolve_mode(mode)
    if mode == "xla":
        return ref.comq_panel_ref(h_bb, s0, qf, delta, z_lo, z_hi, hdiag)
    return comq_panel_pallas(h_bb, s0, qf, delta,
                             jnp.asarray(z_lo, jnp.float32),
                             jnp.asarray(z_hi, jnp.float32), hdiag,
                             interpret=(mode == "interpret"))


def comq_panel_dq(h_bb: Array, s0: Array, qf: Array, delta: Array,
                  z_lo: Array, z_hi: Array, hdiag: Array, *,
                  mode: Optional[str] = None):
    """Fused panel sweep returning (qf', ΔW) — ΔW = (qf' − qf)·δ feeds the
    blocked solver's trailing update as one dense matmul (DESIGN.md §3.3)."""
    mode = resolve_mode(mode)
    if mode == "xla":
        return ref.comq_panel_dq_ref(h_bb, s0, qf, delta, z_lo, z_hi, hdiag)
    return comq_panel_dq_pallas(h_bb, s0, qf, delta,
                                jnp.asarray(z_lo, jnp.float32),
                                jnp.asarray(z_hi, jnp.float32), hdiag,
                                interpret=(mode == "interpret"))


def _heads(rows: Array, n_kv: int) -> Array:
    """(..., KV·w) lane-dense page rows -> (..., KV, w)."""
    return rows.reshape(*rows.shape[:-1], n_kv, rows.shape[-1] // n_kv)


@functools.partial(jax.jit, static_argnames=("window", "mode"))
def paged_attention(q: Array, k_pool: Array, v_pool: Array, layer: Array,
                    block_tables: Array, lengths: Array, *,
                    window: int = 0, mode: Optional[str] = None) -> Array:
    """Decode attention over a paged KV pool (serve/kv_cache.py layout):
    q (B, Hp, hd) single query token per slot; k_pool/v_pool the layer-
    stacked, lane-dense (L, NB, BS, KV·hd) pages, read at layer `layer`
    (int32 scalar); block_tables (B, MAXB) physical page ids; lengths (B,)
    valid tokens (0 = inactive slot)."""
    mode = resolve_mode(mode)
    if mode == "xla":
        n_kv = k_pool.shape[-1] // q.shape[-1]
        return ref.paged_attention_ref(
            q, _heads(k_pool[layer], n_kv), _heads(v_pool[layer], n_kv),
            block_tables, lengths, window=window).astype(q.dtype)
    return paged_attention_pallas(q, k_pool, v_pool, layer, block_tables,
                                  lengths, window=window,
                                  interpret=(mode == "interpret"))


@functools.partial(jax.jit, static_argnames=("window", "kv_bits", "mode"))
def paged_attention_quant(q: Array, k_pool: Array, v_pool: Array,
                          k_scale: Array, v_scale: Array, layer: Array,
                          block_tables: Array, lengths: Array, *,
                          window: int = 0, kv_bits: int = 8,
                          mode: Optional[str] = None) -> Array:
    """Decode attention over a *quantized* paged pool: k_pool/v_pool hold
    layer-stacked, lane-dense integer codes (L, NB, BS, KV·hd/cpb; int8 /
    packed 4-bit) and k_scale/v_scale (L, NB, KV) the per-(layer, page,
    kv_head) scales, read at layer `layer`. The Pallas path streams codes
    and folds the scales inside the kernel; `xla` takes the dequantizing
    oracle."""
    mode = resolve_mode(mode)
    if mode == "xla":
        n_kv = k_scale.shape[-1]
        return ref.paged_attention_quant_ref(
            q, _heads(k_pool[layer], n_kv), _heads(v_pool[layer], n_kv),
            k_scale[layer], v_scale[layer], block_tables, lengths,
            window=window, kv_bits=kv_bits).astype(q.dtype)
    return paged_attention_quant_pallas(q, k_pool, v_pool, k_scale, v_scale,
                                        layer, block_tables, lengths,
                                        window=window, kv_bits=kv_bits,
                                        interpret=(mode == "interpret"))


def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    window: int = 0, mode: Optional[str] = None) -> Array:
    mode = resolve_mode(mode)
    if mode == "xla":
        return ref.flash_attention_ref(q, k, v, causal=causal,
                                       window=window).astype(q.dtype)
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  interpret=(mode == "interpret"))
