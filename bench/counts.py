"""Operations and bytes the algorithm needs, from logical shapes.

Counts use the model's logical widths (head_dim 80 stays 80, whatever lane
layout a kernel stores it in) and the configuration's precisions: bf16
activations and pages, 4-bit codes with one f32 scale and one int32 zero
per output column. They do not change when an implementation does, so a
roofline share moves only with time.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

from bench.weights import LEAF_ORDER, LEAVES

ACT_BYTES = 2          # bf16 activations (compute dtype)


def leaf_shapes(dm: Dict[str, int]) -> Dict[str, Tuple[int, int]]:
    """(K, N) of each projection leaf of one layer."""
    return {n: (dm[LEAVES[n][1]], dm[LEAVES[n][2]]) for n in LEAF_ORDER}


def quant_matmul_call(m: int, k: int, n: int, bits: int = 4
                      ) -> Tuple[float, float]:
    """(flops, bytes) of Y (m, n) = X (m, k) @ dequant(codes (k, n)):
    packed codes, per-column scale and zero, activations in, output out."""
    flops = 2.0 * m * k * n
    nbytes = k * n * bits / 8 + 8.0 * n + ACT_BYTES * (m * k + m * n)
    return flops, nbytes


def quant_matmul_step(dm: Dict[str, int], m: int) -> Tuple[float, float]:
    """Every quant_matmul call of one decode step (all layers, M = m rows)."""
    f = b = 0.0
    for k, n in leaf_shapes(dm).values():
        cf, cb = quant_matmul_call(m, k, n)
        f += cf
        b += cb
    return dm["n_layers"] * f, dm["n_layers"] * b


def kv_bytes_per_token(dm: Dict[str, int], kv_bits: int = 0) -> float:
    """Logical K and V bytes of one token over all layers (bf16 pages, or
    kv_bits-wide codes)."""
    elem = kv_bits / 8 if kv_bits else 2.0
    return 2.0 * dm["n_layers"] * dm["n_kv"] * dm["head_dim"] * elem


def paged_attention_step(dm: Dict[str, int], contexts: Iterable[int],
                         kv_bits: int = 0) -> Tuple[float, float]:
    """(flops, bytes) of the decode attention of one step, all layers:
    per slot, QK^T and PV over its live context, K/V read at logical width,
    q in and the output out."""
    ctx = list(contexts)
    H, hd, L = dm["n_heads"], dm["head_dim"], dm["n_layers"]
    live = float(sum(ctx))
    flops = 4.0 * L * H * hd * live
    nbytes = (kv_bytes_per_token(dm, kv_bits) * live
              + L * len(ctx) * 2 * H * hd * ACT_BYTES)
    return flops, nbytes


def decode_token_flops(dm: Dict[str, int], context: int) -> float:
    """Model FLOPs of one decoded token: every projection and the
    unembedding (2 per weight), plus attention over its live context."""
    proj = sum(k * n for k, n in leaf_shapes(dm).values())
    return (2.0 * (dm["n_layers"] * proj + dm["d_model"] * dm["vocab"])
            + 4.0 * dm["n_layers"] * dm["n_heads"] * dm["head_dim"]
            * context)


def weight_codes_bytes(dm: Dict[str, int], bits: int = 4) -> float:
    """Packed code bytes of every projection leaf of every layer."""
    return dm["n_layers"] * sum(k * n for k, n in
                                leaf_shapes(dm).values()) * bits / 8
