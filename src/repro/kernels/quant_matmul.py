"""Pallas TPU kernel: dequant-fused GEMM for COMQ-quantized weights.

Y = X · W_q with W_q = diag-free per-channel form scale[n]·(u[k,n] + z[n]).
The zero-point term factors out of the contraction:

    Y[m,n] = scale[n]·( Σ_k X[m,k]·u[k,n]  +  z[n]·Σ_k X[m,k] )

so the kernel streams uint8 codes HBM→VMEM (4×/8× less HBM traffic than
bf16 weights — this is what moves the decode roofline, EXPERIMENTS.md
§Perf), widens them through int32 to bf16 *in VMEM*, runs the MXU dot,
and applies scale/zero in the epilogue on the last K step.

Packed codes use the planar layout of `quantizer.pack_int4/pack_int2`:
with `cpb` codes per byte, bit field f of packed column c holds output
column f·(N/cpb) + c. A packed block therefore unpacks into `cpb` plane
blocks with shifts and masks alone — no lane interleave — and each plane
accumulates into its own output plane. The planes are concatenated along
N outside the kernel.

Grid: (M/bm, (N/cpb)/bn, K/bk), K innermost (sequential accumulation into
a VMEM f32 scratch). Block sizes default to the largest tile-aligned
divisors of the dims (≤ 128 rows, ≤ 512 packed columns / K rows), or the
full dim where no aligned divisor exists.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array


def _kernel(x_ref, u_ref, scale_ref, z_ref, *refs, n_k: int, cpb: int):
    o_refs, acc_ref, rsum_ref = refs[:cpb], refs[cpb], refs[cpb + 1]
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        rsum_ref[...] = jnp.zeros_like(rsum_ref)

    x = x_ref[...]                                    # (bm, bk)
    xw = x.astype(jnp.bfloat16)
    # uint8 -> int32 is the widening the TPU lowering accepts; the bit
    # fields then convert int32 -> f32 -> bf16 for the MXU
    u = u_ref[...].astype(jnp.int32)                  # (bk, bn)
    width, mask = 8 // cpb, (1 << (8 // cpb)) - 1
    for f in range(cpb):
        uf = (u >> (width * f)) & mask if cpb > 1 else u
        acc_ref[f] += jax.lax.dot(
            xw, uf.astype(jnp.float32).astype(jnp.bfloat16),
            preferred_element_type=jnp.float32)
    rsum_ref[...] += jnp.sum(x.astype(jnp.float32), axis=1, keepdims=True)

    @pl.when(ki == n_k - 1)
    def _epilogue():
        for f in range(cpb):
            scale = scale_ref[f:f + 1, :]             # (1, bn)
            z = z_ref[f:f + 1, :]
            y = acc_ref[f] * scale + rsum_ref[...] * (scale * z)
            o_refs[f][...] = y.astype(o_refs[f].dtype)


def _block(dim: int, pref: int, align: int) -> int:
    """Largest multiple of `align` that divides `dim` and is <= `pref`;
    the full `dim` when there is none (a full-extent block is always a
    legal TPU tile)."""
    for b in range(min(pref, dim) // align * align, 0, -align):
        if dim % b == 0:
            return b
    return dim


def quant_matmul_pallas(x: Array, codes_u: Array, scale: Array, z_lo: Array,
                        *, bits: int = 8, cpb: Optional[int] = None,
                        bm: Optional[int] = None, bn: Optional[int] = None,
                        bk: Optional[int] = None, out_dtype=jnp.float32,
                        interpret: bool = False) -> Array:
    """x: (M, K) float; codes_u: (K, N/cpb) uint8 — unpacked (cpb=1),
    nibble-packed 3/4-bit (cpb=2) or quad-packed 2-bit (cpb=4), planar
    layout; scale/z_lo: (N,). Returns (M, N). cpb defaults from bits
    (packed iff bits==4). `bn` counts packed columns; explicit block
    sizes must divide their dims, defaults are picked tile-aligned."""
    M, K = x.shape
    if cpb is None:
        cpb = 2 if bits == 4 else 1
    if cpb not in (1, 2, 4):
        raise ValueError(f"pallas quant_matmul covers cpb 1/2/4, got {cpb}")
    Np = codes_u.shape[1]
    N = Np * cpb
    bm = bm or (M if M <= 128 else 128)
    bn = bn or _block(Np, 512, 128)
    bk = bk or _block(K, 512, 128)
    Mp = -(-M // bm) * bm
    if Mp != M:       # ragged prefill rows: pad, compute, slice off
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))
    if Np % bn or K % bk:
        raise ValueError(f"shape (K={K}, N/cpb={Np}) not divisible by "
                         f"blocks (bk={bk}, bn={bn})")
    n_k = K // bk

    scale2 = scale.reshape(cpb, Np).astype(jnp.float32)
    z2 = z_lo.reshape(cpb, Np).astype(jnp.float32)

    outs = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k, cpb=cpb),
        grid=(Mp // bm, Np // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((cpb, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((cpb, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=[pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))] * cpb,
        out_shape=[jax.ShapeDtypeStruct((Mp, Np), out_dtype)] * cpb,
        scratch_shapes=[
            _vmem((cpb, bm, bn), jnp.float32),
            _vmem((bm, 1), jnp.float32),
        ],
        name="quant_matmul",
        interpret=interpret,
    )(x, codes_u, scale2, z2)
    y = outs[0] if cpb == 1 else jnp.concatenate(outs, axis=1)
    return y[:M] if Mp != M else y


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)
