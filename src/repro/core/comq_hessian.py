"""COMQ in Gram/Hessian space — the at-scale solvers (DESIGN.md §3).

Every COMQ quantity is a function of H = XᵀX (m×m) and W only:

    ⟨x_i, s_ij⟩            = (H·R)_ij + (W_q)_ij · H_ii ,  R = W − W_q
    ‖x_i‖²                 = H_ii
    δ-update numerators     ⟨Xq_j, Xw_j⟩ = q_jᵀ H w_j
    greedy keys            ‖x_i‖·|w_ij| = √H_ii · |w_ij|

so the solve never touches the N×m calibration features after a single
accumulation pass. Two implementations:

* `comq_quantize_h`   — row-at-a-time, supports exact per-column greedy
  order (gather-based), bit-identical to the X-space solver.
* `comq_quantize_blocked` — panel/blocked updates with a *trailing-update*
  schedule (DESIGN.md §3.3): the product P = H·R is maintained across the
  whole solve and each solved panel contributes one rank-B dense matmul
  `P -= H[:, blk] @ ΔW_blk` (MXU work) — no per-panel residual
  materialization, no per-sweep H·R refresh. With HW = H·W precomputed
  once, the δ-updates and error evaluations are elementwise reads of the
  maintained P, eliminating their per-sweep (m, m)·(m, n) matmuls too.
  The intra-panel sequential sweep touches only H[blk,blk] + the Q panel
  (VMEM-resident in the Pallas kernel `kernels/comq_panel.py`). Shared-order
  only — the panel structure requires all columns to visit rows in the same
  order. Exactly equals the row-at-a-time solver under the same shared
  order (tested). `schedule="refresh"` keeps the legacy per-panel-refresh
  schedule for A/B benchmarking (benchmarks/runtime_compare.py).

Both solvers run as a single jitted program per (shape, spec) — the multi-
sweep driver is `jax.jit`-compiled with the permuted/padded operands donated
on accelerator backends, so per-leaf solves in the whole-model pipeline pay
one dispatch instead of eager op-by-op dispatch.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.comq import QuantResult, make_orders
from repro.core.quantizer import (EPS, QuantSpec, init_per_channel,
                                  init_per_layer)

Array = jax.Array

# Every product of the solve runs at full f32 precision. A TPU multiplies
# f32 operands as one bf16 pass by default, which rounds H, W and the
# maintained P = H·R to 8 mantissa bits: on a TPU v5e that changed 4.3 %
# of h2o-danube-1.8b's layer-0 w_down codes and 0.7 % of its wq codes
# (DESIGN.md §3). The Grams need no such setting — their bf16-valued taps
# multiply exactly. On the CPU this is the default anyway.
_mm = partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)


def gram(x: Array) -> Array:
    x = x.astype(jnp.float32)
    return x.T @ x


def _h_error(h: Array, w: Array, wq: Array) -> Array:
    """‖X(W − W_q)‖ from H: sqrt(tr(RᵀHR))."""
    r = w - wq
    val = jnp.sum(r * _mm(h, r))
    return jnp.sqrt(jnp.maximum(val, 0.0))


def _delta_update_h(h: Array, w: Array, qf: Array, per_layer: bool) -> Array:
    hq = _mm(h, qf)
    if per_layer:
        num = jnp.sum(qf * _mm(h, w))
        den = jnp.sum(qf * hq)
        return jnp.where(den > EPS, num / den, 1.0)
    num = jnp.sum(qf * _mm(h, w), axis=0)
    den = jnp.sum(qf * hq, axis=0)
    return jnp.where(den > EPS, num / den, 1.0)


# ---------------------------------------------------------------------------
# row-at-a-time H-space sweep (exact per-column greedy supported)
# ---------------------------------------------------------------------------

def _sweep_h(h: Array, p: Array, qf: Array, delta: Array, z_lo, z_hi,
             orders: Array, hdiag: Array):
    """p: (m, n) maintained product H·R with R = W − δ·Q."""
    m, n = qf.shape
    cols = jnp.arange(n)

    def step(t, carry):
        p, qf = carry
        idx = orders[t]                                   # (n,)
        qg = qf[idx, cols]
        hg = hdiag[idx]
        denom = delta * hg
        ratio = p[idx, cols] / jnp.where(denom > 0, denom, 1.0)
        q_new = jnp.clip(jnp.round(ratio + qg),
                         z_lo.astype(jnp.float32), z_hi.astype(jnp.float32))
        q_new = jnp.where(hg > EPS, q_new,
                          jnp.clip(jnp.round(qg), z_lo.astype(jnp.float32),
                                   z_hi.astype(jnp.float32)))
        du = (q_new - qg) * delta                         # ΔW_q row entries
        p = p - h[:, idx] * du[None, :]                   # rank-1 per column
        qf = qf.at[idx, cols].set(q_new)
        return p, qf

    return jax.lax.fori_loop(0, m, step, (p, qf))


def _comq_h_core(h: Array, w: Array, *, spec: QuantSpec):
    m, n = w.shape
    per_layer = spec.granularity == "per_layer"
    if per_layer:
        delta, z_lo, z_hi = init_per_layer(w, spec.bits)
    else:
        delta, z_lo, z_hi = init_per_channel(w, spec.bits, spec.lam)

    hdiag = jnp.diag(h)
    orders = make_orders(spec.order, jnp.sqrt(hdiag), w)
    qf = w / delta
    errs = [_h_error(h, w, qf * delta)]

    for _ in range(spec.sweeps):
        p = _mm(h, w - qf * delta)                        # H·R
        p, qf = _sweep_h(h, p, qf, delta, z_lo, z_hi, orders, hdiag)
        delta = _delta_update_h(h, w, qf, per_layer)
        errs.append(_h_error(h, w, qf * delta))

    q = jnp.clip(jnp.round(qf), z_lo, z_hi).astype(jnp.int32)
    return q, delta, z_lo, z_hi, jnp.stack(errs)


_comq_h_jit = partial(jax.jit, static_argnames=("spec",))(_comq_h_core)


def comq_quantize_h(h: Array, w: Array, spec: QuantSpec,
                    x_for_error: Optional[Array] = None) -> QuantResult:
    """H-space COMQ. `h` = XᵀX. Bit-identical to comq.comq_quantize.

    The whole multi-sweep solve runs as one jitted program (cached per
    shape and spec), so repeated per-leaf solves pay a single dispatch."""
    q, delta, z_lo, z_hi, errs = _comq_h_jit(
        h.astype(jnp.float32), w.astype(jnp.float32), spec=spec)
    return QuantResult(q=q, delta=delta, z_lo=z_lo, z_hi=z_hi, errors=errs)


# ---------------------------------------------------------------------------
# blocked / panel solver (the TPU-shaped schedule; shared order only)
# ---------------------------------------------------------------------------

def shared_order(h: Array, w: Array, spec: QuantSpec) -> Array:
    """The (m,) shared visit order the blocked solver would derive for
    (h, w). Exposed so the column-sharded solve can compute it once on the
    replicated full weight and pass it through `perm=` — the order is the
    only column-coupled solver quantity (DESIGN.md §4.3)."""
    order_name = {"greedy": "greedy_shared"}.get(spec.order, spec.order)
    return make_orders(order_name, jnp.sqrt(jnp.diag(h)),
                       w.astype(jnp.float32))[:, 0]

def panel_sweep_ref(h_bb: Array, s0: Array, qf_b: Array, delta: Array,
                    z_lo, z_hi, hdiag_b: Array):
    """Reference intra-panel sweep (the Pallas kernel's oracle)."""
    qf_b, _ = panel_sweep_dq_ref(h_bb, s0, qf_b, delta, z_lo, z_hi, hdiag_b)
    return qf_b


def panel_sweep_dq_ref(h_bb: Array, s0: Array, qf_b: Array, delta: Array,
                       z_lo, z_hi, hdiag_b: Array):
    """Reference intra-panel sweep emitting the scaled code delta (the
    Pallas kernel's oracle, kernels/comq_panel.py::comq_panel_dq_pallas).

    h_bb: (B, B) block of H; s0: (B, n) = (H·R)[blk] before the panel;
    qf_b: (B, n) panel codes. Returns (qf_b', ΔW) with ΔW = (qf_b' − qf_b)·δ
    so the caller's trailing update is a single dense matmul.

    The sweep is *lazy*: instead of eagerly rank-1-updating all B rows of S
    after every step (B·n writes per step), it accumulates the scaled deltas
    ΔW and materializes each step's row as one (1×B)·(B×n) matvec
    s_t = s0[t] − h_bb[t, :]·ΔW — same FLOPs, a fraction of the memory
    traffic (n writes per step), and ΔW falls out for free."""
    B = qf_b.shape[0]

    def step(t, carry):
        qf_b, du = carry
        qg = qf_b[t]
        hg = hdiag_b[t]
        st = s0[t] - _mm(h_bb[t, :], du)      # rows ≥ t of du are still 0
        denom = delta * hg
        ratio = st / jnp.where(denom > 0, denom, 1.0)
        q_new = jnp.clip(jnp.round(ratio + qg),
                         z_lo.astype(jnp.float32), z_hi.astype(jnp.float32))
        q_new = jnp.where(hg > EPS, q_new,
                          jnp.clip(jnp.round(qg), z_lo.astype(jnp.float32),
                                   z_hi.astype(jnp.float32)))
        du = du.at[t].set((q_new - qg) * delta)
        qf_b = qf_b.at[t].set(q_new)
        return qf_b, du

    return jax.lax.fori_loop(0, B, step, (qf_b, jnp.zeros_like(qf_b)))


def _panel_and_dq(panel_fn, h_bb, s0, qf_b, delta, z_lo, z_hi, hd_b):
    """Normalize panel_fn output to (qf_b', ΔW): fused kernels return the
    scaled delta directly; legacy single-output panel_fns get it computed
    here (one extra elementwise pass over the panel)."""
    out = panel_fn(h_bb, s0, qf_b, delta, z_lo, z_hi, hd_b)
    if isinstance(out, tuple):
        return out
    return out, (out - qf_b) * delta


def _blocked_core(hp: Array, wp: Array, hdiag: Array, delta, z_lo, z_hi, *,
                  spec: QuantSpec, m: int, block: int, panel_fn, schedule: str):
    """Jitted multi-sweep blocked solve over permuted/padded operands.

    trailing (default): P = H·R is maintained exactly across sweeps — each
    panel solve is followed by one rank-B dense matmul P -= H[:, blk] @ ΔW.
    Between sweeps, H·Q is recovered elementwise from (HW − P)/δ so the
    δ-update and the error trajectory cost no matmuls at all.

    refresh: the legacy schedule — every panel recomputes the full residual
    product s0 = H[blk, :]·(W − δQ), and δ-updates/errors each pay another
    (m, m)·(m, n) matmul per sweep. Kept for A/B benchmarking.
    """
    per_layer = spec.granularity == "per_layer"
    m_pad, n = wp.shape
    B = block
    n_blocks = m_pad // B
    qf = wp / delta

    if schedule == "trailing":
        hw = _mm(hp, wp)                                   # H·W, once
        p = _mm(hp, wp - qf * delta)                       # P⁰ = H·R⁰

        def h_err(p, qf, delta):
            # ‖XR‖ = sqrt(tr(RᵀHR)) = sqrt(Σ R⊙P); padded rows of H are
            # zero, so P's padded rows vanish and the sum is exact.
            r = wp - qf * delta
            return jnp.sqrt(jnp.maximum(jnp.sum(r * p), 0.0))

        errs = [h_err(p, qf, delta)]
        for _ in range(spec.sweeps):
            def body(b, carry):
                p, qf = carry
                s0 = jax.lax.dynamic_slice(p, (b * B, 0), (B, n))
                h_cols = jax.lax.dynamic_slice(hp, (0, b * B), (m_pad, B))
                h_bb = jax.lax.dynamic_slice(h_cols, (b * B, 0), (B, B))
                qf_b = jax.lax.dynamic_slice(qf, (b * B, 0), (B, n))
                hd_b = jax.lax.dynamic_slice(hdiag, (b * B,), (B,))
                qf_b, dq = _panel_and_dq(panel_fn, h_bb, s0, qf_b, delta,
                                         z_lo, z_hi, hd_b)
                p = p - _mm(h_cols, dq)                    # rank-B trailing
                qf = jax.lax.dynamic_update_slice(qf, qf_b, (b * B, 0))
                return p, qf

            p, qf = jax.lax.fori_loop(0, n_blocks, body, (p, qf))
            # δ-update from the maintained P: H·Q = (HW − P)/δ, elementwise
            safe = jnp.where(jnp.abs(delta) > EPS, delta, 1.0)
            hq = (hw - p) / safe
            if per_layer:
                num = jnp.sum(qf * hw)
                den = jnp.sum(qf * hq)
            else:
                num = jnp.sum(qf * hw, axis=0)
                den = jnp.sum(qf * hq, axis=0)
            delta = jnp.where(den > EPS, num / den, 1.0)
            p = hw - delta * hq                            # rescale P to δ'
            errs.append(h_err(p, qf, delta))
    elif schedule == "refresh":
        errs = [_h_error(hp[:m, :m], wp[:m], (qf * delta)[:m])]
        for _ in range(spec.sweeps):
            def body(b, qf):
                r = wp - qf * delta
                h_rows = jax.lax.dynamic_slice(hp, (b * B, 0), (B, m_pad))
                s0 = _mm(h_rows, r)                        # (B, n) MXU
                h_bb = jax.lax.dynamic_slice(h_rows, (0, b * B), (B, B))
                qf_b = jax.lax.dynamic_slice(qf, (b * B, 0), (B, n))
                hd_b = jax.lax.dynamic_slice(hdiag, (b * B,), (B,))
                qf_b, _ = _panel_and_dq(panel_fn, h_bb, s0, qf_b, delta,
                                        z_lo, z_hi, hd_b)
                return jax.lax.dynamic_update_slice(qf, qf_b, (b * B, 0))

            qf = jax.lax.fori_loop(0, n_blocks, body, qf)
            delta = _delta_update_h(hp[:m, :m], wp[:m], qf[:m], per_layer)
            errs.append(_h_error(hp[:m, :m], wp[:m], (qf * delta)[:m]))
    else:
        raise ValueError(f"unknown schedule {schedule!r}")

    # return the full padded float codes: the caller rounds/clips/slices
    # outside the jit, and the (m_pad, n) output is what lets the donated
    # wp buffer alias in place (int32 q in here could alias nothing — the
    # donation audit in repro.analysis caught exactly that)
    return qf, delta, jnp.stack(errs)


_BLOCK_STATICS = ("spec", "m", "block", "panel_fn", "schedule")
_blocked_jit = partial(jax.jit, static_argnames=_BLOCK_STATICS)(_blocked_core)
# donate the operands that genuinely alias an output: wp -> the returned
# (m_pad, n) float codes, delta -> the updated delta. hp/hdiag alias nothing
# (donating them is silently dropped by JAX — audited in analysis/registry);
# the audit contract for this entry point is donated={1, 3}
_blocked_jit_donate = partial(jax.jit, static_argnames=_BLOCK_STATICS,
                              donate_argnums=(1, 3))(_blocked_core)


def comq_quantize_blocked(h: Array, w: Array, spec: QuantSpec,
                          block: int = 256, panel_fn=None,
                          schedule: str = "trailing",
                          perm: Optional[Array] = None) -> QuantResult:
    """Blocked COMQ: cyclic or shared-greedy order. `panel_fn` defaults to
    the pure-jnp fused panel sweep; the launcher swaps in the Pallas kernel
    (kernels/comq_panel.py::panel_fn_dq_interpret or the compiled variant).

    `schedule` picks the cross-panel update strategy ("trailing" maintains
    P = H·R with rank-B updates; "refresh" recomputes it per panel — see
    DESIGN.md §3.3 for the FLOP accounting). Both produce identical codes.

    `perm` optionally supplies the shared (m,) visit order. The shared
    greedy order is the only solver quantity coupled across *columns* of W;
    precomputing it from the full weight makes every remaining operand
    column-offset-invariant, which is what lets the column-sharded solve
    (repro.dist.sharded_solve, DESIGN.md §4.3) run each shard on its column
    slice bit-identically to the replicated solve.
    """
    h = h.astype(jnp.float32)
    w = w.astype(jnp.float32)
    m, n = w.shape
    per_layer = spec.granularity == "per_layer"
    if per_layer:
        delta, z_lo, z_hi = init_per_layer(w, spec.bits)
    else:
        delta, z_lo, z_hi = init_per_channel(w, spec.bits, spec.lam)

    hdiag0 = jnp.diag(h)
    if perm is None:
        order_name = {"greedy": "greedy_shared"}.get(spec.order, spec.order)
        perm = make_orders(order_name, jnp.sqrt(hdiag0), w)[:, 0]  # (m,)
    inv_perm = jnp.argsort(perm)
    hp = h[perm][:, perm]
    wp = w[perm]
    hdiag = jnp.diag(hp)
    panel_fn = panel_fn or panel_sweep_dq_ref

    # pad rows to a multiple of the panel size (H rows padded with zeros:
    # zero-diagonal rows keep their code — no effect on real rows)
    B = min(block, m)
    m_pad = ((m + B - 1) // B) * B
    if m_pad != m:
        hp = jnp.pad(hp, ((0, m_pad - m), (0, m_pad - m)))
        wp = jnp.pad(wp, ((0, m_pad - m), (0, 0)))
        hdiag = jnp.pad(hdiag, (0, m_pad - m))

    qf, delta, errs = _blocked_jit_donate(
        hp, wp, hdiag, delta, z_lo, z_hi, spec=spec, m=m, block=B,
        panel_fn=panel_fn, schedule=schedule)
    q = jnp.clip(jnp.round(qf[:m]), z_lo, z_hi).astype(jnp.int32)
    q = q[inv_perm]
    return QuantResult(q=q, delta=delta, z_lo=z_lo, z_hi=z_hi, errors=errs)
