"""Pallas TPU kernel: intra-panel COMQ coordinate sweep (DESIGN.md §3.2).

The blocked COMQ solver (core/comq_hessian.py) reduces each panel's cross-
panel work to a dense MXU matmul; what remains is the strictly sequential
B-step sweep that only touches

    H[blk, blk]  (B×B)   +   S = (H·R)[blk]  (B×n)   +   the Q panel (B×n)

— a working set small enough to pin entirely in VMEM. The kernel runs the
B-step `fori_loop` in-register per column tile; the column grid dimension is
embarrassingly parallel (per-channel COMQ columns are independent given δ,
paper eq. (3)).

The fused variant additionally emits ΔW_blk = (Q' − Q)·δ so the trailing
update of the maintained product P = H·R (DESIGN.md §3.3) is a single dense
(m × B)·(B × n) matmul with no extra elementwise pass over the panel.

Per-program VMEM at B=256, cn=256: H_bb 256 KiB + 3×(S,Q,ΔW) 768 KiB ≈ 1 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.quantizer import EPS

Array = jax.Array


def _kernel(h_bb_ref, s_ref, qf_ref, delta_ref, zlo_ref, zhi_ref, hd_ref,
            out_ref, dq_ref, *, panel: int):
    h_bb = h_bb_ref[...]                      # (B, B)
    s0 = s_ref[...]                           # (B, cn)
    qf0 = qf_ref[...]                         # (B, cn)
    delta = delta_ref[...][0]                 # (cn,)
    z_lo = zlo_ref[...][0]
    z_hi = zhi_ref[...][0]
    hdiag = hd_ref[...][:, 0]                 # (B,)

    # lazy sweep (mirrors core.comq_hessian.panel_sweep_dq_ref op-for-op):
    # accumulate scaled deltas ΔW and materialize each step's S row as one
    # (1×B)·(B×cn) matvec — MXU work instead of B·cn VPU writes per step.
    def step(t, carry):
        qf, du = carry
        qg = jax.lax.dynamic_index_in_dim(qf, t, 0, keepdims=False)
        hg = jax.lax.dynamic_index_in_dim(hdiag, t, 0, keepdims=False)
        s0t = jax.lax.dynamic_index_in_dim(s0, t, 0, keepdims=False)
        hrow = jax.lax.dynamic_index_in_dim(h_bb, t, 0, keepdims=False)
        st = s0t - hrow @ du                  # rows ≥ t of du are still 0
        denom = delta * hg
        ratio = st / jnp.where(denom > 0, denom, 1.0)
        q_new = jnp.clip(jnp.round(ratio + qg), z_lo, z_hi)
        q_new = jnp.where(hg > EPS, q_new, jnp.clip(jnp.round(qg), z_lo, z_hi))
        du = jax.lax.dynamic_update_index_in_dim(du, (q_new - qg) * delta,
                                                 t, 0)
        qf = jax.lax.dynamic_update_index_in_dim(qf, q_new, t, 0)
        return qf, du

    qf, du = jax.lax.fori_loop(0, panel, step,
                               (qf0, jnp.zeros_like(qf0)))
    out_ref[...] = qf
    dq_ref[...] = du


def _panel_call(h_bb: Array, s0: Array, qf: Array, delta: Array,
                z_lo: Array, z_hi: Array, hdiag: Array, *,
                col_block: int, interpret: bool):
    B, n = qf.shape
    delta = jnp.broadcast_to(jnp.asarray(delta, jnp.float32), (n,))
    z_lo = jnp.broadcast_to(jnp.asarray(z_lo, jnp.float32), (n,))
    z_hi = jnp.broadcast_to(jnp.asarray(z_hi, jnp.float32), (n,))
    cn = min(col_block, n)
    while n % cn:
        cn //= 2
    grid = (n // cn,)
    return pl.pallas_call(
        functools.partial(_kernel, panel=B),
        grid=grid,
        in_specs=[
            pl.BlockSpec((B, B), lambda j: (0, 0)),
            pl.BlockSpec((B, cn), lambda j: (0, j)),
            pl.BlockSpec((B, cn), lambda j: (0, j)),
            pl.BlockSpec((1, cn), lambda j: (0, j)),
            pl.BlockSpec((1, cn), lambda j: (0, j)),
            pl.BlockSpec((1, cn), lambda j: (0, j)),
            pl.BlockSpec((B, 1), lambda j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((B, cn), lambda j: (0, j)),
            pl.BlockSpec((B, cn), lambda j: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, n), jnp.float32),
            jax.ShapeDtypeStruct((B, n), jnp.float32),
        ],
        interpret=interpret,
        name="comq_panel",
    )(h_bb.astype(jnp.float32), s0.astype(jnp.float32),
      qf.astype(jnp.float32), delta.reshape(1, n), z_lo.reshape(1, n),
      z_hi.reshape(1, n), hdiag.astype(jnp.float32).reshape(B, 1))


def comq_panel_pallas(h_bb: Array, s0: Array, qf: Array, delta: Array,
                      z_lo: Array, z_hi: Array, hdiag: Array, *,
                      col_block: int = 256, interpret: bool = False) -> Array:
    """Drop-in replacement for core.comq_hessian.panel_sweep_ref.

    h_bb: (B, B); s0/qf: (B, n); delta/z_lo/z_hi: (n,) or scalar;
    hdiag: (B,). Returns updated qf (B, n)."""
    qf_new, _ = _panel_call(h_bb, s0, qf, delta, z_lo, z_hi, hdiag,
                            col_block=col_block, interpret=interpret)
    return qf_new


def comq_panel_dq_pallas(h_bb: Array, s0: Array, qf: Array, delta: Array,
                         z_lo: Array, z_hi: Array, hdiag: Array, *,
                         col_block: int = 256, interpret: bool = False):
    """Fused panel sweep: returns (qf', ΔW) with ΔW = (qf' − qf)·δ already
    scaled in-kernel, ready for the trailing update P -= H[:, blk] @ ΔW."""
    return _panel_call(h_bb, s0, qf, delta, z_lo, z_hi, hdiag,
                       col_block=col_block, interpret=interpret)


def panel_fn_interpret(h_bb, s0, qf, delta, z_lo, z_hi, hdiag):
    """panel_fn adapter for comq_quantize_blocked (interpret mode)."""
    return comq_panel_pallas(h_bb, s0, qf, delta,
                             z_lo.astype(jnp.float32),
                             z_hi.astype(jnp.float32), hdiag, interpret=True)


def panel_fn_dq_interpret(h_bb, s0, qf, delta, z_lo, z_hi, hdiag):
    """Fused (qf', ΔW) panel_fn adapter (interpret mode)."""
    return comq_panel_dq_pallas(h_bb, s0, qf, delta,
                                z_lo.astype(jnp.float32),
                                z_hi.astype(jnp.float32), hdiag,
                                interpret=True)
