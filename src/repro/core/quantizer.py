"""Uniform quantization grids, scale/zero-point initialization, packing.

The paper's setting (§3): b-bit *asymmetric uniform* quantization with
bit-code set S = {z, z+1, ..., z + 2^b - 1} and decomposition W_q = δ·Q.

* per-layer  (Alg. 1): one shared δ; init δ⁰ = mean_j ‖w_j‖∞ / 2^{b-1},
  z = -2^{b-1} (symmetric code range around zero).
* per-channel (Alg. 2): δ_j = λ·(max w_j - min w_j)/(2^b - 1), λ ≤ 1
  (Tab. 10 ablation), z_j = round(min w_j / δ_j).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array
EPS = 1e-12


@dataclass(frozen=True)
class QuantSpec:
    bits: int = 4
    granularity: str = "per_channel"      # per_channel | per_layer
    lam: float = 1.0                      # λ init shrink (per-channel)
    sweeps: int = 3                       # K in the paper (Tab. 7: 3-4 best)
    order: str = "greedy"                 # greedy | cyclic

    @property
    def n_levels(self) -> int:
        return 2 ** self.bits


def init_per_layer(w: Array, bits: int) -> Tuple[Array, Array, Array]:
    """Returns (delta0 scalar, z_lo scalar, z_hi scalar)."""
    col_inf = jnp.max(jnp.abs(w), axis=0)             # ‖w_j‖∞ per column
    delta0 = jnp.mean(col_inf) / (2.0 ** (bits - 1))
    delta0 = jnp.maximum(delta0, EPS)
    z = -(2 ** (bits - 1))
    return delta0, jnp.int32(z), jnp.int32(z + 2 ** bits - 1)


def init_per_channel(w: Array, bits: int, lam: float
                     ) -> Tuple[Array, Array, Array]:
    """Returns (delta0 (n,), z_lo (n,), z_hi (n,)) for w: (m, n)."""
    wmax = jnp.max(w, axis=0)
    wmin = jnp.min(w, axis=0)
    delta0 = lam * (wmax - wmin) / (2.0 ** bits - 1.0)
    delta0 = jnp.maximum(delta0, EPS)
    z_lo = jnp.round(wmin / delta0).astype(jnp.int32)
    return delta0, z_lo, z_lo + 2 ** bits - 1


def quantize_rtn(w: Array, delta: Array, z_lo: Array, z_hi: Array) -> Array:
    """Round-to-nearest onto the grid (baseline + COMQ initialization)."""
    q = jnp.round(w / delta)
    return jnp.clip(q, z_lo, z_hi).astype(jnp.int32)


def dequantize(q: Array, delta: Array) -> Array:
    return q.astype(jnp.float32) * delta


# ---------------------------------------------------------------------------
# storage: offset-binary codes (codes - z_lo in [0, 2^b-1]) packed for HBM
# ---------------------------------------------------------------------------

def to_unsigned(q: Array, z_lo: Array) -> Array:
    return (q - z_lo).astype(jnp.uint8)


def from_unsigned(u: Array, z_lo: Array) -> Array:
    return u.astype(jnp.int32) + z_lo


def _pack_planes(u: Array, cpb: int) -> Array:
    """Planar packing along the last dim: bit field f (lowest first) of
    byte c holds code f·(n/cpb) + c. Unpacking is shifts, masks and one
    concatenation — no interleave — so a tile of packed bytes widens into
    `cpb` tile-aligned planes inside a TPU kernel (kernels/quant_matmul,
    kernels/paged_attention)."""
    n = u.shape[-1]
    assert n % cpb == 0, f"planar packing needs last dim % {cpb} == 0"
    w, p = 8 // cpb, n // cpb
    out = u[..., :p].astype(jnp.uint8)
    for f in range(1, cpb):
        out = out | (u[..., f * p:(f + 1) * p].astype(jnp.uint8) << (w * f))
    return out


def _unpack_planes(b: Array, cpb: int) -> Array:
    w, mask = 8 // cpb, jnp.uint8((1 << (8 // cpb)) - 1)
    return jnp.concatenate([(b >> (w * f)) & mask for f in range(cpb)],
                           axis=-1)


def pack_int4(u: Array) -> Array:
    """Pack uint4 codes (last dim even) two per byte, planar: the low
    nibble holds the first half of the last dim, the high nibble the
    second half."""
    return _pack_planes(u, 2)


def unpack_int4(b: Array) -> Array:
    return _unpack_planes(b, 2)


def pack_int2(u: Array) -> Array:
    """Pack uint2 codes (last dim % 4 == 0) four per byte, planar (field
    f holds the f-th quarter of the last dim) — the 0.25 B/param storage
    of a 2-bit policy leaf."""
    return _pack_planes(u, 4)


def unpack_int2(b: Array) -> Array:
    return _unpack_planes(b, 4)


def codes_per_byte(bits: int) -> int:
    """Storage density for offset-binary codes of a given bit width:
    2-bit codes pack four per byte, 3/4-bit codes share the nibble
    packing (3-bit codes fit a nibble), 5..8-bit codes pass through as
    one uint8 each (the explicit int8 pass-through)."""
    if bits <= 2:
        return 4
    if bits <= 4:
        return 2
    return 1


def pack_codes(u: Array, bits: int):
    """Pack offset-binary uint8 codes to the densest byte layout their bit
    width allows. Returns (packed, cpb) where cpb is the achieved
    codes-per-byte — 1 when the last dim doesn't align to the pack width
    (callers store the codes unpacked rather than padding)."""
    cpb = codes_per_byte(bits)
    if cpb == 1 or u.shape[-1] % cpb:
        return u.astype(jnp.uint8), 1
    if cpb == 4:
        return pack_int2(u), 4
    return pack_int4(u), 2


def unpack_codes(b: Array, cpb: int) -> Array:
    """Inverse of pack_codes for a known codes-per-byte."""
    if cpb == 4:
        return unpack_int2(b)
    if cpb == 2:
        return unpack_int4(b)
    return b


def reconstruction_error(x: Array, w: Array, w_q: Array) -> Array:
    """‖X W_q − X W‖_F — the paper's layer-wise objective (Fig. 3 metric)."""
    return jnp.linalg.norm(x @ (w_q - w))
