"""Plain reference of the COMQ job: the paper's per-leaf coordinate descent
in Gram space, and the layer walk that feeds it, in `jax.numpy`.

Per leaf (COMQ, arXiv:2403.07134, per-channel grid): the grid starts at
delta = lam * (max - min) / (2^b - 1) per column, zero-point round(min /
delta); codes start at W / delta; each sweep visits the rows in one shared
greedy order (descending sqrt(H_ii) * |W_i|, |W_i| the row's norm) and sets
each code to the rounded minimiser of ||X (W - delta * Q)||^2 with the
others held, then refits delta per column by least squares. All of it is a
function of H = X^T X and W. Sweeps run in panels of rows: inside a panel
the residual row is rebuilt from the panel's own code changes, after it the
whole product H (W - delta Q) takes the panel's change at once.

The walk: calibration tokens are embedded; per layer, in the order the
taps arise (attn_in -> wq wk wv, wo_in -> wo, mlp_in -> w_gate w_up,
down_in -> w_down), the tap's Gram is formed, the leaves are solved, and
the forward continues through the quantized leaves, so each tap sees the
quantized layer upstream of it. Activations are bf16 at the points the
configuration computes in bf16 (every product's output, norms, rotary,
attention, residual stream), each product accumulating in float32.

`prec="bf16"` is the control: the solve's products (H W, H R, the panel
updates, the delta refit) take bf16 operands, the precision below the
float32 the solve states.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from bench.reference.dense_gqa import rope

HIGHEST = jax.lax.Precision.HIGHEST
BF16 = jnp.bfloat16
EPS = 1e-12


def _mm(a, b, prec: str):
    if prec == "bf16":
        return jnp.matmul(a.astype(BF16), b.astype(BF16),
                          preferred_element_type=jnp.float32)
    return jnp.matmul(a, b, precision=HIGHEST)


def grid(w, bits: int, lam: float):
    """Per-column (delta, z_lo, z_hi) of the initial grid."""
    wmax, wmin = jnp.max(w, 0), jnp.min(w, 0)
    delta = jnp.maximum(lam * (wmax - wmin) / (2.0 ** bits - 1.0), EPS)
    z_lo = jnp.round(wmin / delta).astype(jnp.int32)
    return delta, z_lo, z_lo + 2 ** bits - 1


def rtn(w, bits: int, lam: float):
    delta, z_lo, z_hi = grid(w, bits, lam)
    q = jnp.clip(jnp.round(w / delta), z_lo, z_hi).astype(jnp.int32)
    return q, delta, z_lo


@functools.partial(jax.jit, static_argnames=("bits", "lam", "sweeps",
                                             "block", "prec"))
def solve(h, w, *, bits: int, lam: float, sweeps: int, block: int = 256,
          prec: str = "f32"):
    """COMQ on one leaf: (q int32 (m, n), delta (n,), z_lo (n,))."""
    m, n = w.shape
    delta, z_lo, z_hi = grid(w, bits, lam)
    lo, hi = z_lo.astype(jnp.float32), z_hi.astype(jnp.float32)
    order = jnp.argsort(-(jnp.sqrt(jnp.diag(h)) * jnp.linalg.norm(w, axis=1)))
    hp, wp = h[order][:, order], w[order]
    B = min(block, m)
    mp = -(-m // B) * B
    hp = jnp.pad(hp, ((0, mp - m), (0, mp - m)))
    wp = jnp.pad(wp, ((0, mp - m), (0, 0)))
    hd = jnp.diag(hp)
    qf = wp / delta
    hw = _mm(hp, wp, prec)

    def panel(b, carry):
        p, qf, delta = carry
        rows = jax.lax.dynamic_slice(hp, (b * B, 0), (B, mp))
        s0 = jax.lax.dynamic_slice(p, (b * B, 0), (B, n))
        q0 = jax.lax.dynamic_slice(qf, (b * B, 0), (B, n))
        hbb = jax.lax.dynamic_slice(rows, (0, b * B), (B, B))
        hdb = jax.lax.dynamic_slice(hd, (b * B,), (B,))

        def row(t, c):
            qb, du = c
            s = s0[t] - _mm(hbb[t][None], du, prec)[0]
            den = delta * hdb[t]
            r = s / jnp.where(den > 0, den, 1.0)
            new = jnp.where(hdb[t] > EPS,
                            jnp.clip(jnp.round(r + qb[t]), lo, hi),
                            jnp.clip(jnp.round(qb[t]), lo, hi))
            return qb.at[t].set(new), du.at[t].set((new - qb[t]) * delta)

        qb, du = jax.lax.fori_loop(0, B, row, (q0, jnp.zeros_like(q0)))
        p = p - _mm(rows.T, du, prec)
        return p, jax.lax.dynamic_update_slice(qf, qb, (b * B, 0)), delta

    for _ in range(sweeps):
        p = _mm(hp, wp - qf * delta, prec)
        p, qf, delta = jax.lax.fori_loop(0, mp // B, panel, (p, qf, delta))
        hq = _mm(hp, qf, prec)
        num, den = jnp.sum(qf * hw, 0), jnp.sum(qf * hq, 0)
        delta = jnp.where(den > EPS, num / den, 1.0)
    q = jnp.clip(jnp.round(qf[:m]), lo, hi).astype(jnp.int32)
    return q[jnp.argsort(order)], delta, z_lo


@jax.jit
def err2(h, w, q, delta, z_lo):
    """||X (W - W_q)||^2 per column from H, W_q = delta * q."""
    r = w - q.astype(jnp.float32) * delta
    return jnp.sum(r * jnp.matmul(h, r, precision=HIGHEST), 0)


@jax.jit
def gram(tap):
    x = tap.reshape(-1, tap.shape[-1]).astype(jnp.float32)
    return jnp.matmul(x.T, x, precision=HIGHEST)


def _bf(x):
    return x.astype(BF16)


def _proj(x, w):
    """bf16 activations times a float32 weight: bf16 operands, float32
    accumulation, bf16 out."""
    return _bf(jnp.matmul(_bf(x).astype(jnp.float32),
                          _bf(w).astype(jnp.float32), precision=HIGHEST))


def _norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return _bf(y * w)


@functools.partial(jax.jit, static_argnames=("dm", "window", "theta"))
def _attention(xq, xk, xv, *, dm, window, theta):
    dm = dict(dm)
    B, T, _ = xq.shape
    H, KV, hd = dm["n_heads"], dm["n_kv"], dm["head_dim"]
    pos = jnp.arange(T)
    q = _bf(rope(xq.reshape(B, T, H, hd).astype(jnp.float32), pos, theta))
    k = _bf(rope(xk.reshape(B, T, KV, hd).astype(jnp.float32), pos, theta))
    v = xv.reshape(B, T, KV, hd)
    qg = q.reshape(B, T, KV, H // KV, hd).astype(jnp.float32)
    s = jnp.einsum("btkgh,bskh->bkgts", qg, k.astype(jnp.float32),
                   precision=HIGHEST) / math.sqrt(hd)
    i = jnp.arange(T)
    mask = i[:, None] >= i[None, :]
    if window > 0:
        mask &= i[:, None] - i[None, :] < window
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bkgts,bskh->btkgh", p, v.astype(jnp.float32),
                   precision=HIGHEST)
    return _bf(o.reshape(B, T, H * hd))


def walk_layer(x, lw: Dict[str, jax.Array], ln1, ln2, *, dm, model,
               quant: Dict, prec: str = "f32"):
    """One layer of the walk. x: (B, T, d) bf16. lw: the layer's float32
    leaves as (K, N) matrices. Returns (x', {leaf: (q, delta, z_lo)},
    {tap: H})."""
    eps, theta = float(model["rms_norm_eps"]), float(model["rope_theta"])
    window = int(model.get("sliding_window") or 0)
    kw = dict(bits=int(quant["bits"]), lam=float(quant["lam"]),
              sweeps=int(quant["sweeps"]), prec=prec)
    out, grams = {}, {}

    def quantized(tap_name, tap, leaves):
        h = gram(tap)
        grams[tap_name] = h
        ws = []
        for name in leaves:
            q, delta, z = solve(h, lw[name], **kw)
            out[name] = (q, delta, z)
            ws.append(q.astype(jnp.float32) * delta)
        return ws

    xn = _norm(x, ln1, eps)
    wq, wk, wv = quantized("attn_in", xn, ("wq", "wk", "wv"))
    o = _attention(_proj(xn, wq), _proj(xn, wk), _proj(xn, wv),
                   dm=tuple(sorted(dm.items())), window=window, theta=theta)
    (wo,) = quantized("wo_in", o, ("wo",))
    x = _bf(x.astype(jnp.float32) + _proj(o, wo).astype(jnp.float32))
    xn = _norm(x, ln2, eps)
    wg, wu = quantized("mlp_in", xn, ("w_gate", "w_up"))
    g, u = _proj(xn, wg), _proj(xn, wu)
    hmid = _bf(jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32))
    (wd,) = quantized("down_in", hmid, ("w_down",))
    x = _bf(x.astype(jnp.float32) + _proj(hmid, wd).astype(jnp.float32))
    return x, out, grams


def leaf_tap(name: str) -> Tuple[str, str]:
    return {"wq": ("attn_in", "attn"), "wk": ("attn_in", "attn"),
            "wv": ("attn_in", "attn"), "wo": ("wo_in", "attn"),
            "w_gate": ("mlp_in", "mlp"), "w_up": ("mlp_in", "mlp"),
            "w_down": ("down_in", "mlp")}[name]
