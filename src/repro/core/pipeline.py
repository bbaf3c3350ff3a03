"""Whole-model COMQ: GPTQ-style sequential layer-by-layer quantization with
*quantized propagation* — layer l+1 is calibrated on the activations
produced by the already-quantized layers 1..l, so downstream layers absorb
upstream quantization error (standard PTQ pipeline structure).

The pipeline walks the stacked layer params, uses the model's activation
taps (models/*.py `taps=` hooks) to get the exact input X of every
projection, solves COMQ in H-space per projection, and returns a params
pytree where quantized leaves are `QTensor` dicts.

Two propagation schedules (DESIGN.md §4.1):

* ``staged`` (default) — **one forward per layer**: the layer's single
  tap-collecting forward quantizes each leaf group *in tap order*
  (attn_in → wo_in → mlp_in → down_in) via the `quantize_cb` hook in
  models/*.py, so every downstream sub-path is computed with the already-
  quantized upstream sub-blocks. Halves calibration forward FLOPs and
  makes intra-layer taps exact w.r.t. the quantized model.
* ``legacy`` — the two-forward schedule (float tap forward, then a second
  quantized-propagation forward), kept for A/B
  (benchmarks/runtime_compare.py::pipeline/staged_vs_legacy).

Reporting is sync-free: per-leaf errors stay on device during the walk and
are materialized by one batched transfer at the end (`_finalize_report`).
With a ``mesh`` (a "data" axis), calibration is data-parallel: tokens are
sharded over the mesh and each tap's (m, m) Gram block reduces with a
single psum — the only communication (repro.dist, DESIGN.md §4.2).

"Which bits" is a per-leaf decision, not a constructor argument: every
solve receives the QuantSpec a `core.policy.QuantPolicy` resolves for
that (layer, leaf) — pattern rules, first/last overrides, or a budgeted
backprop-free allocation (DESIGN.md §6). A plain QuantSpec still works
everywhere and is bit-identical to the pre-policy pipeline.
"""
from __future__ import annotations

import functools
import json
import sys
import time
import zlib
from dataclasses import asdict, dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import calibrate
from repro.core import guards as _guards
from repro.core.baselines import gptq_quantize, rtn_quantize
from repro.core.comq_hessian import comq_quantize_blocked, comq_quantize_h
from repro.core.guards import GuardContext, GuardEvent, guarded_solve
from repro.core.policy import as_policy, policy_to_dict
from repro.core.quantizer import QuantSpec
from repro.ft.inject import InjectedFault, SimulatedKill
from repro.ft.journal import QuantJournal, ResumeMismatch
from repro.obs.metrics import NULL_METRICS
from repro.obs.trace import NULL_TRACER
from repro.models import transformer as tfm
from repro.models.common import apply_norm

Array = jax.Array

# which tap feeds which weight leaf, per layer family
DENSE_TAPS = {
    ("attn", "wq"): "attn_in", ("attn", "wk"): "attn_in",
    ("attn", "wv"): "attn_in", ("attn", "wo"): "wo_in",
    ("mlp", "w_gate"): "mlp_in", ("mlp", "w_up"): "mlp_in",
    ("mlp", "w_down"): "down_in",
}
MOE_TAPS = {
    ("attn", "wq"): "attn_in", ("attn", "wk"): "attn_in",
    ("attn", "wv"): "attn_in", ("attn", "wo"): "wo_in",
    ("moe", "w_gate"): "expert_in", ("moe", "w_up"): "expert_in",
    ("moe", "w_down"): "expert_down_in",
}
RWKV_TAPS = {
    ("tm", "w_r"): "tm_r_in", ("tm", "w_k"): "tm_k_in",
    ("tm", "w_v"): "tm_v_in", ("tm", "w_g"): "tm_g_in",
    ("tm", "w_o"): "tm_o_in",
    ("cm", "w_k"): "cm_k_in", ("cm", "w_r"): "cm_r_in",
    ("cm", "w_v"): "cm_v_in",
}
SSM_EXTRA_TAPS = {
    ("ssm", "w_in"): "ssm_in", ("ssm", "w_out"): "ssm_out_in",
}
CROSS_TAPS = {
    ("xattn", "wq"): "xattn_q_in", ("xattn", "wo"): "xattn_wo_in",
    ("mlp", "w_gate"): "mlp_in", ("mlp", "w_up"): "mlp_in",
    ("mlp", "w_down"): "down_in",
}


def taps_for(cfg) -> Dict[Tuple[str, str], str]:
    if cfg.attn_free:
        return dict(RWKV_TAPS)
    t = dict(MOE_TAPS if cfg.moe is not None else DENSE_TAPS)
    if cfg.parallel_ssm_heads:
        t.update(SSM_EXTRA_TAPS)
    return t


def is_qtensor(leaf) -> bool:
    # bool(), not `is True`: CheckpointManager restores scalar leaves as
    # 0-d ndarrays, and a restored QTensor table must still be recognized
    return isinstance(leaf, dict) and bool(leaf.get("__qtensor__", False))


def make_qtensor(q: Array, delta: Array, z_lo: Array, shape,
                 bits: int = 8) -> dict:
    """Codes stored offset-binary (q - z_lo ∈ [0, 2^b-1]) as uint8 so any
    zero-point fits; dequant restores W_q = δ·(u + z). `bits` records the
    width the solve used — the packing/serving layers dispatch on it
    instead of inspecting code values (core/apply, ckpt/quantized)."""
    u = (q - z_lo).astype(jnp.uint8)
    return {"__qtensor__": True, "codes": u,
            "scale": jnp.asarray(delta, jnp.float32),
            "z_lo": jnp.asarray(z_lo, jnp.int32),
            "shape": tuple(int(s) for s in shape),
            "bits": int(bits)}


def qtensor_bits(t: dict) -> int:
    """Bit width of a pipeline QTensor (pre-policy trees default to 8:
    codes were stored one-per-byte and packers re-inspect nothing)."""
    return int(t.get("bits", 8))


def dequant_qtensor(t: dict, dtype=jnp.float32) -> Array:
    q = t["codes"].astype(jnp.int32) + t["z_lo"]
    w2d = q.astype(jnp.float32) * t["scale"]
    return w2d.reshape(t["shape"]).astype(dtype)


def dequantize_tree(tree, dtype=jnp.float32):
    """Replace every QTensor leaf with its dequantized dense weight."""
    def walk(node):
        if is_qtensor(node):
            return dequant_qtensor(node, dtype)
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v) for v in node)
        return node
    return walk(tree)


@dataclass
class LayerReport:
    layer: int
    name: str
    err_before: float     # ‖X(W - RTN(W))‖ on the COMQ grid init
    err_after: float      # ‖X(W - W_q)‖ after COMQ
    # host time spent *dispatching* this leaf's solve: the walk is sync-free
    # (errors stay on device until one batched transfer at the end), so on
    # an async backend this is not the solve's compute time
    dispatch_seconds: float = 0.0
    # span-derived wall time of the leaf's solve (dispatch + device
    # compute), measured by the `leaf_solve` tracer span which blocks on
    # the solved codes before closing. Only populated when a tracer is
    # enabled — with tracing off the walk stays sync-free and this is
    # 0.0 (unmeasured). Fused shared-tap groups split the group wall
    # evenly, like dispatch_seconds.
    wall_seconds: float = 0.0
    # comma-joined guard-event kinds for this leaf ("" = no intervention;
    # e.g. "dead_columns,damping_escalated") — see QuantReport.guard_events
    # for the full records
    guard: str = ""


@dataclass
class QuantReport:
    layers: List[LayerReport] = field(default_factory=list)
    # end-to-end quantize_model wall time (measured around the whole walk,
    # after the finalizing device_get — includes all device compute)
    wall_seconds: float = 0.0
    # every numeric-guard intervention of the run (core/guards.GuardEvent):
    # NaN/Inf sentinels, dead columns, damping escalations, solver
    # fallbacks — empty on a healthy run
    guard_events: List[GuardEvent] = field(default_factory=list)
    # leaves re-applied from the quantization journal instead of re-solved
    resumed_leaves: int = 0

    def total_improvement(self) -> float:
        b = sum(r.err_before for r in self.layers)
        a = sum(r.err_after for r in self.layers)
        return (b - a) / max(b, 1e-12)


# ---------------------------------------------------------------------------
# solver dispatch + shared-tap fused solves
# ---------------------------------------------------------------------------

def solve(h: Array, w2d: Array, spec: QuantSpec, method: str = "comq",
          block: int = 256, schedule: Optional[str] = None):
    """`schedule` only applies to comq_blocked (None = trailing); the
    guard fallback chain (core/guards.solver_chain) uses it to retry a
    failed trailing-update solve on the per-panel-refresh schedule."""
    if method == "comq":
        return comq_quantize_h(h, w2d, spec)
    if method == "comq_blocked":
        return comq_quantize_blocked(h, w2d, spec, block=block,
                                     schedule=schedule or "trailing")
    if method == "rtn":
        return rtn_quantize(w2d, spec, h=h)
    if method == "gptq":
        return gptq_quantize(h, w2d, spec)
    raise ValueError(f"unknown method {method!r}")


def _col_shardable(spec: QuantSpec, method: str) -> bool:
    """True when the solve can run with W's output columns sharded over the
    "model" mesh axis bit-identically to the replicated solve.

    Requires per-channel granularity (per-layer shares one δ across all
    columns) and a solver whose per-column computation is robust to running
    on a column *slice*: the blocked trailing-update solver (its one
    column-coupled quantity — the shared visit order — is precomputed on
    the full W and passed in; see comq_hessian.shared_order) and RTN
    (elementwise). The row-at-a-time solvers (comq/gptq) are column-
    *separable* in exact arithmetic but their per-coordinate descent
    cascades FP-rounding differences across sweeps under a different XLA
    fusion context, so they stay replicated."""
    if spec.granularity != "per_channel":
        return False
    return method in ("comq_blocked", "rtn")


def _fusable(spec: QuantSpec, method: str) -> bool:
    """True when leaves sharing a tap can be solved as one column-
    concatenated matrix with results identical to per-leaf solves.

    Per-channel grids have column-wise δ/zero-points, and per-channel COMQ
    columns are independent given δ (paper eq. (3)) — so fusion is exact
    whenever the visit order is also per-column (cyclic, exact greedy).
    Shared-order solvers (greedy_shared; blocked's shared greedy) derive the
    order from *all* columns, so fusing would change it."""
    if spec.granularity != "per_channel":
        return False
    if method == "comq_blocked":
        return spec.order == "cyclic"
    if method in ("rtn", "gptq"):
        return True
    return spec.order in ("cyclic", "greedy")


def _w2d(w: Array, m: int) -> Array:
    """2D view (m, cols) of an any-rank weight against tap feature dim m:
    attention (d, H, hd) flattens to (d, H·hd); wo (H, hd, d) to (H·hd, d)."""
    if w.ndim == 2:
        return w
    if w.ndim == 3 and w.shape[0] == m:
        return w.reshape(m, w.shape[1] * w.shape[2])
    if w.ndim == 3 and w.shape[0] * w.shape[1] == m:
        return w.reshape(m, w.shape[2])
    raise ValueError(f"cannot 2D-ify weight {w.shape} for tap dim {m}")


@jax.jit
def _col_err2(h: Array, w: Array, wq: Array) -> Array:
    """Per-column squared reconstruction error Σ_i R⊙(HR): lets one fused
    H·R matmul attribute exact per-leaf errors after a concatenated solve."""
    r = w - wq
    return jnp.sum(r * jnp.matmul(h, r, precision=jax.lax.Precision.HIGHEST),
                   axis=0)


def _norm_of(e2_slice: Array) -> Array:
    """Device scalar — never forces a host sync; see _finalize_report."""
    return jnp.sqrt(jnp.maximum(jnp.sum(e2_slice), 0.0))


def _expert_norm_sum(e2: Array) -> Array:
    """(E, cols) per-column err² -> sum over experts of per-expert norms,
    matching the historical per-leaf MoE reporting (device scalar)."""
    return jnp.sum(jnp.sqrt(jnp.maximum(jnp.sum(e2, axis=1), 0.0)))


def _uniform(specs) -> bool:
    return all(s == specs[0] for s in specs)


def _results_finite(results, tracer) -> bool:
    """Host bool: every (qt, eb, ea, secs) row has finite scales and
    errors — one batched transfer (the post-solve guard sentinel), under
    a `quant.sync.results_finite` span of `tracer`."""
    flags = [jnp.all(jnp.isfinite(qt["scale"]))
             & jnp.isfinite(jnp.asarray(eb, jnp.float32))
             & jnp.isfinite(jnp.asarray(ea, jnp.float32))
             for qt, eb, ea, _ in results]
    with tracer.span("quant.sync.results_finite"):
        return bool(jax.device_get(jnp.all(jnp.stack(flags))))  # comq: allow(host-sync) one batched finiteness verdict


def _solve_group(ws, h: Array, specs, method: str,
                 block: int = 256, solve_sh=None, *,
                 gctx: Optional[GuardContext] = None, layer: int = -1,
                 names=None):
    """Solve the weight leaves `ws` (all calibrated by the same Gram h),
    each under its own resolved per-leaf spec (`specs`, same length).

    When the group's specs are identical AND fusion is exact (see
    _fusable), the leaves are solved as one column-concatenated
    [w_a|w_b|…] matrix — one solver invocation and one grid init per tap
    instead of one per leaf — then split back per leaf. Mixed-bit groups
    fall back to per-leaf solves: the δ grid init depends on the bit
    width, so fusing across widths would change every column's grid.

    `solve_sh` (from quantize_model when the mesh has a nontrivial "model"
    axis) runs the solve with output columns sharded over "model"
    (dist.sharded_solve): bit-identical codes, zero solve-time collectives.
    The sharded path mirrors the replicated fusion decision exactly — the
    fused concatenation solves as one column-sharded matrix, per-leaf
    solves shard per leaf (each with its own spec) — so sharded and
    replicated pipelines agree at every bit width.
    With an enabled `gctx` (core/guards.GuardContext) the group runs the
    full guard policy: one batched health check sanitizes NaN/Inf in H
    and the weights and counts dead Gram columns, solves go through
    `guarded_solve` (escalating damping + solver fallback chain), and a
    sharded solve whose output is non-finite is redone replicated under
    the guarded chain. A healthy group takes the exact unguarded compute
    path, so guarded and unguarded pipelines are bit-identical unless a
    guard actually fires.

    Returns [(qtensor, err_before, err_after, seconds), ...]."""
    m = h.shape[0]
    w2ds = [_w2d(w, m) for w in ws]
    spec0 = specs[0]
    guarding = gctx is not None and gctx.enabled
    if names is None:
        names = [f"leaf{i}" for i in range(len(ws))]
    if guarding:
        with gctx.tracer.span("quant.sync.gram_health"):
            n_bad_h, n_dead, n_bad_ws = _guards.gram_health(h, w2ds)
        if n_bad_h:
            h = jnp.where(jnp.isfinite(h), h, jnp.zeros((), h.dtype))
            for nm in names:
                gctx.record(layer, nm, "nonfinite_gram", count=n_bad_h)
        for i, (nb, nm) in enumerate(zip(n_bad_ws, names)):
            if nb:
                w2ds[i] = jnp.where(jnp.isfinite(w2ds[i]), w2ds[i],
                                    jnp.zeros((), w2ds[i].dtype))
                gctx.record(layer, nm, "nonfinite_weight", count=nb)
        if n_dead:
            for nm in names:
                gctx.record(layer, nm, "dead_columns", warn=False,
                            count=n_dead)

    if solve_sh is not None and _col_shardable(spec0, method):
        fuse = len(ws) > 1 and _uniform(specs) and _fusable(spec0, method)
        t0 = time.time()
        if fuse:
            wcat = jnp.concatenate([w.astype(jnp.float32) for w in w2ds],
                                   axis=1)
            q, delta, z_lo, e2b, e2a = solve_sh(h, wcat, spec=spec0,
                                                block=block)
            secs = (time.time() - t0) / len(ws)
            out, lo = [], 0
            for w, w2d in zip(ws, w2ds):
                hi = lo + w2d.shape[1]
                qt = make_qtensor(q[:, lo:hi], delta[lo:hi], z_lo[lo:hi],
                                  w.shape, bits=spec0.bits)
                out.append((qt, _norm_of(e2b[lo:hi]), _norm_of(e2a[lo:hi]),
                            secs))
                lo = hi
        else:
            out = []
            for w, w2d, spec in zip(ws, w2ds, specs):
                t0 = time.time()
                q, delta, z_lo, e2b, e2a = solve_sh(h, w2d, spec=spec,
                                                    block=block)
                qt = make_qtensor(q, delta, z_lo, w.shape, bits=spec.bits)
                out.append((qt, _norm_of(e2b), _norm_of(e2a),
                            time.time() - t0))
        if guarding and not _results_finite(out, gctx.tracer):
            # the sharded program has no guard hooks — redo this group
            # replicated under the full guarded chain
            for nm in names:
                gctx.record(layer, nm, "sharded_solve_nonfinite")
            return _solve_group(ws, h, specs, method, block, None,
                                gctx=gctx, layer=layer, names=names)
        return out

    if len(ws) > 1 and _uniform(specs) and _fusable(spec0, method):
        t0 = time.time()
        wcat = jnp.concatenate([w.astype(jnp.float32) for w in w2ds], axis=1)
        if guarding:
            r = guarded_solve(h, wcat, spec0, method, block=block,
                              gctx=gctx, layer=layer, names=names,
                              solve_fn=solve, presanitized=True)
        else:
            r = solve(h, wcat, spec0, method, block=block)
        e2_after = _col_err2(h, wcat, r.q.astype(jnp.float32) * r.delta)
        rt = rtn_quantize(wcat, spec0)
        e2_before = _col_err2(h, wcat, rt.q.astype(jnp.float32) * rt.delta)
        secs = (time.time() - t0) / len(ws)
        out, lo = [], 0
        for w, w2d in zip(ws, w2ds):
            hi = lo + w2d.shape[1]
            qt = make_qtensor(r.q[:, lo:hi], r.delta[lo:hi], r.z_lo[lo:hi],
                              w.shape, bits=spec0.bits)
            out.append((qt, _norm_of(e2_before[lo:hi]),
                        _norm_of(e2_after[lo:hi]), secs))
            lo = hi
        return out

    out = []
    for i, (w, w2d, spec) in enumerate(zip(ws, w2ds, specs)):
        t0 = time.time()
        if guarding:
            r = guarded_solve(h, w2d, spec, method, block=block, gctx=gctx,
                              layer=layer, names=names[i:i + 1],
                              solve_fn=solve, presanitized=True)
        else:
            r = solve(h, w2d, spec, method, block=block)
        rt = rtn_quantize(w2d, spec, h=h)
        qt = make_qtensor(r.q, r.delta, r.z_lo, w.shape, bits=spec.bits)
        out.append((qt, rt.errors[-1], r.errors[-1], time.time() - t0))
    return out


def _expert_qtensor(q, delta, z_lo, shape, bits: int):
    """Per-expert scale/zero reshaped to broadcast against (E, m, n)."""
    delta_b = (jnp.asarray(delta, jnp.float32)[:, None, :]
               if delta.ndim == 2
               else jnp.asarray(delta, jnp.float32)[:, None, None])
    z_b = (z_lo[:, None, :] if z_lo.ndim == 2 else z_lo[:, None, None])
    return make_qtensor(q, delta_b, z_b, shape, bits=bits)


def _solve_group_experts(ws, hs: Array, specs, method: str, *,
                         gctx: Optional[GuardContext] = None,
                         layer: int = -1, names=None):
    """Stacked-expert leaves (E, d, f_k) sharing per-expert Grams hs
    (E, d, d): vmapped per-expert solves, column-fused across leaves when
    exact (identical specs only — mixed-bit expert groups solve per leaf).

    The vmapped solve body cannot host-sync per expert, so the guard
    policy here is group-batched: sanitize non-finite Grams up front, run
    the unguarded solve, and only if the *group's* results are non-finite
    retry the whole group under escalating damping, finally falling back
    to (data-free) RTN. A healthy group is bit-identical to the unguarded
    path. Returns [(qtensor, err_before, err_after, seconds), ...]."""

    def one_fn(spec, meth):
        def one(h_e, w_e):
            r = solve(h_e, w_e, spec, meth)
            rt = rtn_quantize(w_e, spec)
            e2a = _col_err2(h_e, w_e, r.q.astype(jnp.float32) * r.delta)
            e2b = _col_err2(h_e, w_e, rt.q.astype(jnp.float32) * rt.delta)
            return r.q, r.delta, r.z_lo, e2a, e2b
        return one

    spec0 = specs[0]

    def run(hs_in, meth):
        if len(ws) > 1 and _uniform(specs) and _fusable(spec0, meth):
            t0 = time.time()
            wcat = jnp.concatenate([w.astype(jnp.float32) for w in ws],
                                   axis=-1)
            q, delta, z_lo, e2a, e2b = jax.vmap(one_fn(spec0, meth))(
                hs_in, wcat)
            secs = (time.time() - t0) / len(ws)
            out, lo = [], 0
            for w in ws:
                hi = lo + w.shape[-1]
                qt = _expert_qtensor(q[:, :, lo:hi], delta[:, lo:hi],
                                     z_lo[:, lo:hi], w.shape, spec0.bits)
                out.append((qt, _expert_norm_sum(e2b[:, lo:hi]),
                            _expert_norm_sum(e2a[:, lo:hi]), secs))
                lo = hi
            return out
        out = []
        for w, spec in zip(ws, specs):
            t0 = time.time()
            q, delta, z_lo, e2a, e2b = jax.vmap(one_fn(spec, meth))(
                hs_in, w.astype(jnp.float32))
            qt = _expert_qtensor(q, delta, z_lo, w.shape, spec.bits)
            out.append((qt, _expert_norm_sum(e2b), _expert_norm_sum(e2a),
                        time.time() - t0))
        return out

    guarding = gctx is not None and gctx.enabled
    if not guarding:
        return run(hs, method)
    if names is None:
        names = [f"leaf{i}" for i in range(len(ws))]
    with gctx.tracer.span("quant.sync.gram_health"):
        n_bad = _guards.nonfinite_count(hs)
    if n_bad:
        hs = jnp.where(jnp.isfinite(hs), hs, jnp.zeros((), hs.dtype))
        for nm in names:
            gctx.record(layer, nm, "nonfinite_gram", count=n_bad)
    out = run(hs, method)
    if _results_finite(out, gctx.tracer):
        return out
    for mult in _guards.DAMP_MULTS:
        out = run(_guards.damp_hessian(hs, mult), method)
        if _results_finite(out, gctx.tracer):
            for nm in names:
                gctx.record(layer, nm, "damping_escalated", mult=mult)
            return out
    out = run(hs, "rtn")
    for nm in names:
        gctx.record(layer, nm, "fallback", solver="rtn")
    return out


# ---------------------------------------------------------------------------
# crash-safe run context: journaling, resume, fault injection (DESIGN.md §8)
# ---------------------------------------------------------------------------

def _spec_digest(spec: QuantSpec, method: str) -> int:
    """crc32 of the resolved spec + solver — part of the journal key, so a
    journaled leaf is only re-applied when a re-solve would have received
    the identical spec (a changed policy/method invalidates it)."""
    payload = {**asdict(spec), "method": method}
    return zlib.crc32(json.dumps(payload, sort_keys=True).encode())


def _run_digest(cfg, policy, method: str, propagation: str, tok_host,
                quantize_unembed: bool, mesh) -> int:
    """crc32 over everything that must match for journaled leaves to be
    bit-identical to a fresh solve: architecture, solver, policy,
    propagation schedule, the calibration token bytes, and the mesh shape
    (a different mesh reduces Grams in a different order)."""
    tok = np.asarray(tok_host)
    payload = {
        "arch": cfg.name, "family": cfg.family, "n_layers": cfg.n_layers,
        "method": method, "propagation": propagation,
        "policy": policy_to_dict(policy),
        "unembed": bool(quantize_unembed),
        "tokens": [zlib.crc32(tok.tobytes()), list(tok.shape),
                   str(tok.dtype)],
        "mesh": (sorted([str(k), int(v)] for k, v in mesh.shape.items())
                 if mesh is not None else None),
    }
    return zlib.crc32(json.dumps(payload, sort_keys=True).encode())


def _calib_leaf_dims(cfg) -> Dict[str, int]:
    """Leaf-class input dims for the calibration coverage check: a Gram
    over fewer tokens than columns is guaranteed rank-deficient."""
    dims = {"d_model": cfg.d_model}
    if not cfg.attn_free:
        dims["wo_in"] = cfg.n_heads * cfg.resolved_head_dim
        dims["down_in"] = cfg.d_ff
    return dims


class _RunCtx:
    """Per-run plumbing threaded through the layer walk: the numeric-guard
    context (core/guards), the quantization journal (resume lookup +
    durable leaf commit, ft/journal.QuantJournal), and the fault injector
    (ft/inject). A default-constructed ctx without journal/injector and a
    disabled gctx is a no-op at every hook — the historical pipeline."""

    def __init__(self, method: str, gctx: Optional[GuardContext] = None,
                 journal: Optional[QuantJournal] = None, solved=None,
                 injector=None, progress_cb=None, tracer=None,
                 metrics=None):
        self.method = method
        self.gctx = gctx
        self.journal = journal
        self.solved = dict(solved or {})   # (layer, name) -> leaf record
        self.injector = injector
        self.progress_cb = progress_cb
        self.resumed = 0
        # observability (DESIGN.md §10): null singletons when disabled
        self.tracer = tracer or NULL_TRACER
        self.metrics = metrics or NULL_METRICS
        self.m_layers = self.metrics.counter("quant.layers_done")
        self.m_leaves = self.metrics.counter("quant.leaves_solved")

    # -- fault injection ----------------------------------------------------

    def fault(self, point: str, exc=InjectedFault) -> None:
        if self.injector is not None:
            self.injector.check(point, exc=exc)

    def poison_tap(self, tap: Array) -> Array:
        """nan_tap fault: poison one tap entry instead of raising —
        exercises the NaN sentinels end-to-end."""
        if self.injector is not None and self.injector.fire("nan_tap"):
            tap = tap.at[(0,) * tap.ndim].set(jnp.nan)
        return tap

    def sanitize_tap(self, tap: Array, layer: int, names) -> Array:
        """Tap-collection NaN/Inf sentinel: scrub (and record) non-finite
        activations before they poison the Gram."""
        if self.gctx is None or not self.gctx.enabled:
            return tap
        with self.tracer.span("quant.sync.tap_finite"):
            n_bad = _guards.nonfinite_count(tap)
        if n_bad:
            tap = jnp.where(jnp.isfinite(tap), tap, jnp.zeros((), tap.dtype))
            for nm in names:
                self.gctx.record(layer, nm, "nonfinite_tap", count=n_bad)
        return tap

    # -- journal: resume lookup + durable commit ----------------------------

    def lookup(self, layer: int, names, specs):
        """All-or-nothing journal hit for one tap group: every leaf must
        be journaled under its current spec digest, else the whole group
        re-solves (a partial hit would change fused-solve membership).
        Returns [(qtensor, leaf record), ...] or None."""
        if self.journal is None or not self.solved:
            return None
        recs = []
        for nm, spec in zip(names, specs):
            rec = self.solved.get((layer, nm))
            if rec is None or rec["spec"] != _spec_digest(spec, self.method):
                return None
            recs.append(rec)
        loaded = []
        for rec in recs:
            qt_host = QuantJournal.load_leaf(self.journal.dir, rec)
            # intern the dict keys: each spill unpickles fresh string
            # objects, and downstream pickles (ckpt --save-packed) would
            # lose key memo-sharing vs a freshly-solved tree — the bytes
            # must be identical, not just the values
            qt = {sys.intern(str(k)): (jnp.asarray(v)
                                       if isinstance(v, np.ndarray) else v)
                  for k, v in qt_host.items()}
            loaded.append((qt, rec))
        self.resumed += len(loaded)
        return loaded

    def commit(self, layer: int, names, specs, results):
        """Durably persist each solved leaf — spill (atomic packed file)
        strictly before its journal record, so a journaled leaf always
        has a valid spill — and return rows with host-float errors.
        Journaling forces one host sync per group (durability needs the
        bytes); without a journal the walk stays sync-free."""
        if self.journal is None:
            return results
        errs = jax.device_get(  # comq: allow(host-sync) journal commit: one batched pull per run

            jnp.stack([jnp.stack([jnp.asarray(eb, jnp.float32),
                                  jnp.asarray(ea, jnp.float32)])
                       for _, eb, ea, *_ in results]))
        rows = []
        for (nm, spec, (qt, _, _, secs, wall)), (ebf, eaf) in zip(
                zip(names, specs, results), errs):
            # comq: allow(host-sync) journal payloads must be host arrays
            qt_host = {k: np.asarray(jax.device_get(v))
                       if isinstance(v, jax.Array) else v
                       for k, v in qt.items()}
            fname, crc = self.journal.spill_leaf(
                layer, nm, qt_host, fault_cb=self._ckpt_write_fault)
            self.journal.record_leaf(layer, nm,
                                     _spec_digest(spec, self.method),
                                     fname, crc, float(ebf), float(eaf))
            rows.append((qt, float(ebf), float(eaf), secs, wall))
        return rows

    def _ckpt_write_fault(self) -> None:
        self.fault("ckpt_write")

    def layer_done(self, layer: int) -> None:
        """End-of-layer hook: journal the marker, report progress to the
        supervisor, and give the (shared) kill fault point its between-
        layers shot — after the layer's leaves are durably journaled."""
        if self.journal is not None:
            self.journal.record_layer_done(layer)
        self.m_layers.inc()
        if self.progress_cb is not None:
            self.progress_cb(layer)
        self.fault("kill", SimulatedKill)


def _timed_solve(ctx: "_RunCtx", layer: int, tapname: str, names,
                 solve_thunk):
    """Run one tap group's solve under a `leaf_solve` tracer span and
    extend each (qt, eb, ea, secs) row with a span-derived wall_seconds.

    With tracing on, the span blocks on the solved codes before closing
    (a `quant.sync.trace_block` span: a sync the untraced walk does not
    make), so its duration — split evenly across the group like dispatch
    secs — is true solve wall time. With tracing off the thunk runs bare
    and the walk makes no sync of its own (wall 0.0 = unmeasured)."""
    if not ctx.tracer.enabled:
        results = solve_thunk()
        ctx.m_leaves.inc(len(results))
        return [r + (0.0,) for r in results]
    with ctx.tracer.span("leaf_solve", layer=layer, tap=tapname,
                         leaves=",".join(names)) as sp:
        results = solve_thunk()
        with ctx.tracer.span("quant.sync.trace_block"):
            # comq: allow(host-sync) span wall time: tracing-on path only
            jax.block_until_ready([qt["codes"] for qt, *_ in results])
        wall = sp.elapsed_s / max(len(results), 1)
    ctx.m_leaves.inc(len(results))
    return [r + (wall,) for r in results]


def _tap_groups(lp, tapmap) -> Dict[str, List[Tuple[str, str]]]:
    """tapname -> [(mod, leaf), ...] for the leaves present in this layer."""
    groups: Dict[str, List[Tuple[str, str]]] = {}
    for (mod, leaf), tapname in tapmap.items():
        if mod not in lp or leaf not in lp[mod]:
            continue
        groups.setdefault(tapname, []).append((mod, leaf))
    return groups


def _gram_fns(mesh):
    """(gram_fn, batched_fn) for (B,T,d) and (E,C,d) taps. With a mesh the
    Gram reduces via shard_map + one psum over the "data" axis (expert taps
    fall back to the replicated Gram when the routed capacity doesn't
    divide the axis — see dist.calibrate)."""
    if mesh is None:
        return (lambda tap: calibrate.gram_from_tap(tap),
                lambda tap: calibrate.batched_gram(tap))
    from repro import dist
    return (lambda tap: dist.sharded_gram(mesh, tap),
            lambda tap: dist.sharded_batched_gram(mesh, tap))


def _group_specs(resolve, layer_idx: int, entries, prefix: str = ""):
    """Resolved per-leaf specs for one tap group, in entry order."""
    return [resolve(layer_idx, f"{prefix}{mod}.{leaf}")
            for mod, leaf in entries]


def _quantize_layer_leaves(lp, taps, tapmap, resolve, method: str,
                           pending: List[tuple], layer_idx: int,
                           gram_fn=None, batched_fn=None, prefix: str = "",
                           solve_sh=None, ctx: Optional[_RunCtx] = None):
    """Legacy-schedule body: quantize every mapped leaf of one layer from a
    pre-collected `taps` dict, grouped by activation tap (TapGramCache: one
    Gram per tap; fused solves when exact). `resolve(layer_idx, name)`
    supplies each leaf's QuantSpec (core/policy). Returns the layer params
    with QTensor leaves; appends per-leaf (idx, name, err, err, secs)
    records with the errors left on device (host floats when journaling)."""
    if ctx is None:
        ctx = _RunCtx(method)
    cache = calibrate.TapGramCache(gram_fn=gram_fn, batched_fn=batched_fn)
    groups = _tap_groups(lp, tapmap)

    lp_q = dict(lp)
    for tapname, entries in groups.items():
        ws = [lp[mod][leaf] for mod, leaf in entries]
        specs = _group_specs(resolve, layer_idx, entries, prefix)
        names = [f"{prefix}{mod}.{leaf}" for mod, leaf in entries]
        cached = ctx.lookup(layer_idx, names, specs)
        if cached is not None:
            for (mod, leaf), nm, (qt, rec) in zip(entries, names, cached):
                lp_q = _set_nested(lp_q, mod, leaf, qt)
                pending.append((layer_idx, nm, rec["err_before"],
                                rec["err_after"], 0.0, 0.0))
            continue
        ctx.fault("gram_accumulate")
        tap = ctx.sanitize_tap(ctx.poison_tap(taps[tapname]), layer_idx,
                               names)
        for _ in names:
            ctx.fault("leaf_solve")
        if tapname.startswith("expert"):
            with ctx.tracer.span("quant.gram", layer=layer_idx, tap=tapname):
                hs = cache.batched(tapname, tap)
            results = _timed_solve(
                ctx, layer_idx, tapname, names,
                lambda: _solve_group_experts(ws, hs, specs, method,
                                             gctx=ctx.gctx, layer=layer_idx,
                                             names=names))
        else:
            with ctx.tracer.span("quant.gram", layer=layer_idx, tap=tapname):
                h = cache.gram(tapname, tap)
            results = _timed_solve(
                ctx, layer_idx, tapname, names,
                lambda: _solve_group(ws, h, specs, method,
                                     solve_sh=solve_sh, gctx=ctx.gctx,
                                     layer=layer_idx, names=names))
        results = ctx.commit(layer_idx, names, specs, results)
        for (mod, leaf), nm, (qt, eb, ea, secs, wall) in zip(entries, names,
                                                             results):
            lp_q = _set_nested(lp_q, mod, leaf, qt)
            pending.append((layer_idx, nm, eb, ea, secs, wall))
    return lp_q


def _staged_cb(lp, groups, taps, resolve, method: str,
               pending: List[tuple], layer_idx: int, holder: dict,
               gram_fn, batched_fn, prefix: str = "", solve_sh=None,
               ctx: Optional[_RunCtx] = None):
    """The staged-schedule `quantize_cb`: invoked by the model's tap hooks
    mid-forward, right after tap `tapname` is recorded and before the
    weights it feeds are applied. Solves the tap's leaf group (each leaf
    under its resolved per-leaf spec), stashes the QTensors, and returns
    dequantized replacements so the rest of the forward runs on the
    quantized sub-blocks.

    On `--resume` the ctx journal lookup short-circuits the solve: the
    journaled QTensors are re-applied through this same callback, so the
    forward still propagates through the identical quantized sub-blocks
    and every downstream tap — and therefore every remaining solve — is
    bit-identical to the uninterrupted run."""
    if ctx is None:
        ctx = _RunCtx(method)

    def cb(tapname: str):
        entries = groups.get(tapname)
        if not entries:
            return {}
        ws = [lp[mod][leaf] for mod, leaf in entries]
        specs = _group_specs(resolve, layer_idx, entries, prefix)
        names = [f"{prefix}{mod}.{leaf}" for mod, leaf in entries]
        cached = ctx.lookup(layer_idx, names, specs)
        if cached is not None:
            repl = {}
            for (mod, leaf), nm, (qt, rec) in zip(entries, names, cached):
                holder["lp_q"] = _set_nested(holder["lp_q"], mod, leaf, qt)
                pending.append((layer_idx, nm, rec["err_before"],
                                rec["err_after"], 0.0, 0.0))
                repl[leaf] = dequant_qtensor(qt)
            return repl
        ctx.fault("gram_accumulate")
        tap = ctx.sanitize_tap(ctx.poison_tap(taps[tapname]), layer_idx,
                               names)
        for _ in names:
            ctx.fault("leaf_solve")
        if tapname.startswith("expert"):
            with ctx.tracer.span("quant.gram", layer=layer_idx, tap=tapname):
                hs = batched_fn(tap)
            results = _timed_solve(
                ctx, layer_idx, tapname, names,
                lambda: _solve_group_experts(ws, hs, specs, method,
                                             gctx=ctx.gctx, layer=layer_idx,
                                             names=names))
        else:
            with ctx.tracer.span("quant.gram", layer=layer_idx, tap=tapname):
                h = gram_fn(tap)
            results = _timed_solve(
                ctx, layer_idx, tapname, names,
                lambda: _solve_group(ws, h, specs, method,
                                     solve_sh=solve_sh, gctx=ctx.gctx,
                                     layer=layer_idx, names=names))
        results = ctx.commit(layer_idx, names, specs, results)
        repl = {}
        for (mod, leaf), nm, (qt, eb, ea, secs, wall) in zip(entries, names,
                                                             results):
            holder["lp_q"] = _set_nested(holder["lp_q"], mod, leaf, qt)
            pending.append((layer_idx, nm, eb, ea, secs, wall))
            repl[leaf] = dequant_qtensor(qt)
        return repl
    return cb


def _staged_ctx(lp, tapmap, resolve, method: str,
                pending: List[tuple], layer_idx: int, gram_fn, batched_fn,
                prefix: str = "", solve_sh=None,
                ctx: Optional[_RunCtx] = None):
    """(taps, holder, cb) for one staged layer walk — shared by the
    homogeneous, VLM-self, and VLM-cross paths so the callback protocol
    has a single definition."""
    taps: Dict[str, Array] = {}
    holder = {"lp_q": lp}
    cb = _staged_cb(lp, _tap_groups(lp, tapmap), taps, resolve, method,
                    pending, layer_idx, holder, gram_fn, batched_fn,
                    prefix=prefix, solve_sh=solve_sh, ctx=ctx)
    return taps, holder, cb


def _quantize_layer_staged(lp, x, state, cfg, plan, tapmap,
                           resolve, method: str,
                           pending: List[tuple], layer_idx: int,
                           gram_fn, batched_fn, solve_sh=None,
                           ctx: Optional[_RunCtx] = None):
    """Staged schedule: ONE `layer_full` evaluation quantizes the layer in
    tap order *and* propagates x through the quantized sub-blocks — every
    downstream tap is exact w.r.t. the quantized upstream. Returns
    (lp_q, new_x, new_state)."""
    taps, holder, cb = _staged_ctx(lp, tapmap, resolve, method, pending,
                                   layer_idx, gram_fn, batched_fn,
                                   solve_sh=solve_sh, ctx=ctx)
    rwkv_state = state if cfg.attn_free else None
    ssm_state = state if cfg.parallel_ssm_heads else None
    y, _, _, new_state = tfm.layer_full(lp, x, cfg, plan, False,
                                        rwkv_state=rwkv_state,
                                        ssm_state=ssm_state, taps=taps,
                                        quantize_cb=cb)
    return holder["lp_q"], y, new_state


def _finalize_report(report: "QuantReport", pending: List[tuple],
                     metrics=NULL_METRICS):
    """Materialize every accumulated on-device error scalar with a single
    batched transfer — the pipeline walk itself never blocks on the host.
    Per-leaf metrics (solve seconds, final errors) are observed here, on
    the already-host values — never mid-walk."""
    if not pending:
        return report
    errs = jnp.stack([jnp.stack([jnp.asarray(eb, jnp.float32),
                                 jnp.asarray(ea, jnp.float32)])
                      for (_, _, eb, ea, _, _) in pending])
    vals = jax.device_get(errs)  # comq: allow(host-sync) one batched pull at report finalize
    h_err = metrics.histogram("quant.leaf_err_after")
    h_disp = metrics.histogram("quant.leaf_dispatch_seconds")
    h_wall = metrics.histogram("quant.leaf_wall_seconds")
    for (li, name, _, _, secs, wall), (eb, ea) in zip(pending, vals):
        report.layers.append(LayerReport(li, name, float(eb), float(ea),
                                         secs, wall))
        h_err.observe(float(ea))
        h_disp.observe(secs)
        h_wall.observe(wall)
    return report


# ---------------------------------------------------------------------------
# the sequential pipeline
# ---------------------------------------------------------------------------

def _tree_slice(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def _tree_set(tree, i, sub):
    return jax.tree_util.tree_map(lambda a, s: a.at[i].set(s), tree, sub)


@functools.lru_cache(maxsize=16)
def _legacy_layer_fn(cfg, plan):
    """Jitted two-forward-schedule layer evaluator, cached across
    quantize_model calls (cfg/plan are frozen dataclasses)."""
    return jax.jit(lambda lp, x, st: _layer_with_taps(lp, x, st, cfg, plan))


def quantize_model(params, cfg, plan, tokens: Array, spec,
                   method: str = "comq",
                   vision_embeds: Optional[Array] = None,
                   quantize_unembed: bool = False,
                   propagation: str = "staged",
                   mesh=None, *,
                   guards: bool = True,
                   journal=None,
                   resume: bool = False,
                   injector=None,
                   progress_cb: Optional[Callable[[int], None]] = None,
                   tracer=None,
                   metrics=None):
    """Quantize all projection weights of an LM. `tokens`: (B, T) calib batch.

    `spec` is either a global QuantSpec (every leaf gets it — bit-identical
    to the historical path) or a `core.policy.QuantPolicy` whose pattern
    rules / first-last overrides / budget-allocated assignments resolve a
    *per-leaf* spec (only the bit width varies; granularity/order/λ/sweeps
    are policy-wide). Fused shared-tap solves require identical resolved
    specs across the group; mixed-bit groups solve per leaf.

    propagation="staged" (default) runs exactly one layer forward per layer
    (leaves quantized mid-forward in tap order, downstream taps exact
    w.r.t. quantized upstream); "legacy" keeps the two-forward schedule
    for A/B. mesh (optional, with a "data" axis) shards the calibration
    batch data-parallel: each Gram block reduces with a single psum
    (repro.dist; DESIGN.md §4.2). A nontrivial "model" axis additionally
    shards every column-shardable leaf solve (per-channel comq_blocked /
    rtn — see _col_shardable; the gate depends only on policy-wide fields,
    so it is decided once and each leaf's sharded solve runs under its own
    resolved spec) over the mesh columns, bit-identical to the replicated
    solve with zero solve-time collectives (DESIGN.md §4.3); other methods
    keep replicated solves. With a multi-device "data" axis the MoE
    routing capacity is rounded up to it (BuildPlan.moe_capacity_multiple)
    so expert taps always take the Gram-psum path.

    Robustness plumbing (DESIGN.md §8), all optional:

    * guards=True runs the numeric-guard policy (core/guards): NaN/Inf
      sentinels at tap collection and per Gram/weight, dead-column
      counting, escalating damping and the solver fallback chain on
      failed solves. A healthy run takes the exact unguarded compute
      path (bit-identical); every intervention lands in
      QuantReport.guard_events and the leaf's LayerReport.guard.
    * journal (a directory or a ft.QuantJournal) makes the run
      crash-safe: every solved leaf is durably spilled (atomic packed
      file) and journaled; resume=True re-applies journaled leaves
      through the same quantize_cb instead of re-solving, producing
      bit-identical codes/scales to an uninterrupted run. A resume
      against a journal whose run digest (arch/policy/method/calib/mesh)
      differs raises ft.ResumeMismatch.
    * injector (ft.FaultInjector) arms the pipeline fault points
      (gram_accumulate / leaf_solve / ckpt_write / kill / nan_tap);
      progress_cb(layer) fires after each durably-journaled layer (the
      supervisor's progress signal, e.g. ft.Heartbeat.beat).
    * tracer (obs.Tracer) records layer, quant.gram and leaf_solve
      spans and a quant.sync.<site> span around each host sync — with a
      tracer each tap group's span blocks on its solved codes
      (quant.sync.trace_block) so LayerReport.wall_seconds is true wall
      time; without one the walk adds no sync of its own. metrics
      (obs.MetricsRegistry) accumulates quant.* counters/histograms and,
      under a mesh, the dist.bytes_all_reduced counter. Both default to
      disabled null singletons with zero cost (DESIGN.md §10).

    Returns (qparams, QuantReport). qparams has QTensor leaves (each
    carrying its resolved bit width); use `dequantize_tree` (or the
    quantized serving path) to run it.
    """
    from repro.data import (check_calib_coverage, validate_calib_features,
                            validate_calib_tokens)
    from repro.models.model import embed_tokens
    if propagation not in ("staged", "legacy"):
        raise ValueError(f"unknown propagation {propagation!r}")
    policy = as_policy(spec)
    n_layers = cfg.n_layers

    def resolve(layer_idx: int, name: str) -> QuantSpec:
        return policy.resolve(name, layer_idx, n_layers)

    tok_host = np.asarray(jax.device_get(tokens))
    validate_calib_tokens(tok_host, vocab_size=cfg.vocab_size)
    if cfg.family == "vlm" and vision_embeds is not None:
        validate_calib_features(vision_embeds)
    check_calib_coverage(int(tok_host.shape[0]) * int(tok_host.shape[1]),
                         _calib_leaf_dims(cfg))

    # journal setup + resume decision — the run digest hashes the
    # *unsharded* calibration bytes, so replicated and resharded runs of
    # the same calibration agree on identity up to the mesh term
    qj: Optional[QuantJournal] = None
    own_journal = False
    solved: Dict[Tuple[int, str], Dict] = {}
    if journal is not None:
        own_journal = not isinstance(journal, QuantJournal)
        qj = QuantJournal(journal) if own_journal else journal
        digest = _run_digest(cfg, policy, method, propagation, tok_host,
                             quantize_unembed, mesh)
        st = QuantJournal.replay(qj.dir)
        if resume and st.run is not None:
            if int(st.run["run"]) != digest:
                if own_journal:
                    qj.close()
                raise ResumeMismatch(
                    f"journal {qj.dir} was written by run digest "
                    f"{st.run['run']}, current run digest is {digest} "
                    "(arch/policy/method/calibration/mesh changed) — "
                    "refusing to mix journaled leaves into a different run")
            solved = dict(st.leaves)
            qj.record_resume(len(solved))
        else:
            qj.record_run_start(digest, arch=cfg.name, method=method,
                                propagation=propagation,
                                n_layers=cfg.n_layers)

    gctx = GuardContext(enabled=guards, tracer=tracer)
    ctx = _RunCtx(method, gctx=gctx, journal=qj, solved=solved,
                  injector=injector, progress_cb=progress_cb,
                  tracer=tracer, metrics=metrics)

    t_start = time.time()
    report = QuantReport()
    pending: List[tuple] = []
    gram_fn, batched_fn = _gram_fns(mesh)
    # dist bytes-all-reduced accounting: install the counter hook for the
    # run's duration (shape-derived host ints, no device sync)
    dist_obs_prev = None
    dist_obs_set = False
    if mesh is not None and ctx.metrics.enabled:
        from repro.dist import calibrate as _dcal
        _c_bytes = ctx.metrics.counter("dist.bytes_all_reduced")
        dist_obs_prev = _dcal.set_allreduce_observer(_c_bytes.inc)
        dist_obs_set = True
    solve_sh = None
    if mesh is not None:
        from repro.dist import model_size, shard_batch, sharded_solve
        tokens = shard_batch(mesh, tokens)
        ndata = int(mesh.shape.get("data", 1))
        if ndata > 1 and cfg.moe is not None:
            # align routed-expert capacity so (E, C, d) taps divide the
            # data axis and never fall off the Gram-psum path
            plan = plan.replace(moe_capacity_multiple=ndata)
        if model_size(mesh) > 1 and _col_shardable(policy.base, method):
            solve_sh = functools.partial(sharded_solve, mesh, method=method)

    try:
        x = embed_tokens(params, cfg, plan, tokens)
        qparams = jax.tree_util.tree_map(lambda a: a, params)  # shallow copy
        tapmap = taps_for(cfg)

        if cfg.family == "vlm":
            qparams = _quantize_vlm(params, cfg, plan, x, resolve, method,
                                    vision_embeds, pending, propagation,
                                    gram_fn, batched_fn, solve_sh=solve_sh,
                                    ctx=ctx)
        else:
            init_states = None
            if cfg.attn_free:
                from repro.models.rwkv import init_rwkv_state
                init_states = init_rwkv_state(x.shape[0], cfg)
            elif cfg.parallel_ssm_heads:
                from repro.models.ssm import init_ssm_state
                init_states = init_ssm_state(x.shape[0], cfg)

            state = init_states
            if propagation == "legacy":
                layer_full_j = _legacy_layer_fn(cfg, plan)
                for l in range(cfg.n_layers):
                    with ctx.tracer.span("layer", layer=l,
                                         schedule="legacy"):
                        lp = _tree_slice(params["layers"], l)
                        _, taps, _ = layer_full_j(lp, x, state)
                        lp_q = _quantize_layer_leaves(
                            lp, taps, tapmap, resolve, method, pending, l,
                            gram_fn, batched_fn, solve_sh=solve_sh, ctx=ctx)
                        # propagate through the *quantized* layer
                        lp_deq = dequantize_tree(lp_q)
                        x, _, state = layer_full_j(lp_deq, x, state)
                        qparams = _store_layer(qparams, l, lp_q)
                    ctx.layer_done(l)
            else:
                for l in range(cfg.n_layers):
                    with ctx.tracer.span("layer", layer=l,
                                         schedule="staged"):
                        lp_q, x, state = _quantize_layer_staged(
                            _tree_slice(params["layers"], l), x, state,
                            cfg, plan, tapmap, resolve, method,
                            pending, l, gram_fn, batched_fn,
                            solve_sh=solve_sh, ctx=ctx)
                        qparams = _store_layer(qparams, l, lp_q)
                    ctx.layer_done(l)

            if quantize_unembed and "unembed" in params:
                names, specs = ["unembed"], [resolve(-1, "unembed")]
                cached = ctx.lookup(-1, names, specs)
                if cached is not None:
                    qt, rec = cached[0]
                    pending.append((-1, "unembed", rec["err_before"],
                                    rec["err_after"], 0.0, 0.0))
                else:
                    ctx.fault("gram_accumulate")
                    xn = ctx.sanitize_tap(
                        ctx.poison_tap(apply_norm(params["final_norm"], x,
                                                  cfg)), -1, names)
                    ctx.fault("leaf_solve")
                    with ctx.tracer.span("quant.gram", layer=-1,
                                         tap="unembed_in"):
                        h = gram_fn(xn)
                    results = _timed_solve(
                        ctx, -1, "unembed_in", names,
                        lambda: _solve_group([params["unembed"]], h, specs,
                                             method, solve_sh=solve_sh,
                                             gctx=ctx.gctx, layer=-1,
                                             names=names))
                    qt, eb, ea, secs, wall = ctx.commit(-1, names, specs,
                                                        results)[0]
                    pending.append((-1, "unembed", eb, ea, secs, wall))
                qparams["unembed"] = qt
        if qj is not None:
            qj.record_run_done()
    finally:
        if own_journal and qj is not None:
            qj.close()
        if dist_obs_set:
            _dcal.set_allreduce_observer(dist_obs_prev)

    _finalize_report(report, pending, metrics=ctx.metrics)
    report.wall_seconds = time.time() - t_start
    report.guard_events = list(gctx.events)
    report.resumed_leaves = ctx.resumed
    ctx.metrics.counter("quant.guard_events").inc(len(report.guard_events))
    ctx.metrics.counter("quant.resumed_leaves").inc(ctx.resumed)
    gmap = gctx.by_leaf()
    if gmap:
        for lr in report.layers:
            lr.guard = gmap.get((lr.layer, lr.name), "")
    return qparams, report


def _set_nested(lp, mod, leaf, value):
    lp = dict(lp)
    lp[mod] = dict(lp[mod])
    lp[mod][leaf] = value
    return lp


def _store_layer(qparams, l, lp_q):
    """Store per-layer QTensors under a side table (stacked storage would
    force all layers to share scales)."""
    qparams = dict(qparams)
    table = dict(qparams.get("__qlayers__", {}))
    table[str(l)] = lp_q
    qparams["__qlayers__"] = table
    return qparams


def _layer_with_taps(lp, x, state, cfg, plan):
    taps: Dict[str, Array] = {}
    rwkv_state = state if cfg.attn_free else None
    ssm_state = state if cfg.parallel_ssm_heads else None
    y, _, _, new_state = tfm.layer_full(lp, x, cfg, plan, False,
                                        rwkv_state=rwkv_state,
                                        ssm_state=ssm_state, taps=taps)
    return y, taps, new_state


def _quantize_vlm(params, cfg, plan, x, resolve, method, vision_embeds,
                  pending, propagation, gram_fn, batched_fn, solve_sh=None,
                  ctx: Optional[_RunCtx] = None):
    from repro.models.model import _vlm_group_counts
    if ctx is None:
        ctx = _RunCtx(method)
    g, spg = _vlm_group_counts(cfg)
    cd = x.dtype
    ve = jnp.einsum("bnv,vd->bnd", vision_embeds.astype(cd),
                    params["vision_proj"].astype(cd))
    qparams = dict(params)
    table = {}
    staged = propagation == "staged"
    for gi in range(g):
        for si in range(spg):
            lp = _tree_slice(_tree_slice(params["groups"]["self"], gi), si)
            lidx = gi * (spg + 1) + si
            if staged:
                lp_q, x, _ = _quantize_layer_staged(
                    lp, x, None, cfg, plan, DENSE_TAPS, resolve, method,
                    pending, lidx, gram_fn, batched_fn, solve_sh=solve_sh,
                    ctx=ctx)
            else:
                taps: Dict[str, Array] = {}
                y, _, _, _ = tfm.layer_full(lp, x, cfg, plan, False,
                                            taps=taps)
                lp_q = _quantize_layer_leaves(lp, taps, DENSE_TAPS, resolve,
                                              method, pending, lidx,
                                              gram_fn, batched_fn,
                                              solve_sh=solve_sh, ctx=ctx)
                x, _, _, _ = tfm.layer_full(dequantize_tree(lp_q), x, cfg,
                                            plan, False)
            table[f"self_{gi}_{si}"] = lp_q
            ctx.layer_done(lidx)
        cp = _tree_slice(params["groups"]["cross"], gi)
        vkv = tfm.vision_kv_for_layer(cp, ve)
        lidx = gi * (spg + 1) + spg
        if staged:
            taps, holder, cb = _staged_ctx(cp, CROSS_TAPS, resolve, method,
                                           pending, lidx, gram_fn,
                                           batched_fn, prefix="cross.",
                                           solve_sh=solve_sh, ctx=ctx)
            x = tfm.cross_layer_full(cp, x, cfg, plan, vkv, taps=taps,
                                     quantize_cb=cb)
            cp_q = holder["lp_q"]
        else:
            taps = {}
            _ = tfm.cross_layer_full(cp, x, cfg, plan, vkv, taps=taps)
            cp_q = _quantize_layer_leaves(cp, taps, CROSS_TAPS, resolve,
                                          method, pending, lidx, gram_fn,
                                          batched_fn, prefix="cross.",
                                          solve_sh=solve_sh, ctx=ctx)
            x = tfm.cross_layer_full(dequantize_tree(cp_q), x, cfg, plan,
                                     vkv)
        table[f"cross_{gi}"] = cp_q
        ctx.layer_done(lidx)
    qparams["__qlayers__"] = table
    return qparams


# ---------------------------------------------------------------------------
# materialize a runnable dequantized model
# ---------------------------------------------------------------------------

def materialize(qparams, cfg, dtype=jnp.float32) -> Any:
    """Fold the __qlayers__ side table back into stacked dense params
    (quantized leaves dequantized to `dtype`; the model computes in its
    compute dtype either way, so bf16 halves the footprint and changes no
    result)."""
    params = {k: v for k, v in qparams.items() if k != "__qlayers__"}
    table = qparams.get("__qlayers__", {})
    if not table:
        return params
    if cfg.family == "vlm":
        from repro.models.model import _vlm_group_counts
        g, spg = _vlm_group_counts(cfg)
        if "groups" in params:
            self_p = params["groups"]["self"]
            cross_p = params["groups"]["cross"]
            for gi in range(g):
                for si in range(spg):
                    deq = dequantize_tree(table[f"self_{gi}_{si}"], dtype)
                    self_p = jax.tree_util.tree_map(
                        lambda a, s: a.at[gi, si].set(s.astype(a.dtype)),
                        self_p, deq)
                deq = dequantize_tree(table[f"cross_{gi}"], dtype)
                cross_p = jax.tree_util.tree_map(
                    lambda a, s: a.at[gi].set(s.astype(a.dtype)),
                    cross_p, deq)
        else:
            # stripped checkpoint (ckpt.strip_for_serving): rebuild the
            # (G, spg, ...) / (G, ...) stacks from the table
            self_rows = [
                jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs),
                    *[dequantize_tree(table[f"self_{gi}_{si}"], dtype)
                      for si in range(spg)])
                for gi in range(g)]
            self_p = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                            *self_rows)
            cross_p = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs),
                *[dequantize_tree(table[f"cross_{gi}"], dtype)
                  for gi in range(g)])
        params = dict(params)
        params["groups"] = {"self": self_p, "cross": cross_p}
        return params
    if "layers" in params:
        layers = params["layers"]
        for key, lp_q in table.items():
            l = int(key)
            deq = dequantize_tree(lp_q, dtype)
            layers = jax.tree_util.tree_map(
                lambda a, s: a.at[l].set(s.astype(a.dtype)), layers, deq)
    else:
        # stripped checkpoint (ckpt.strip_for_serving): rebuild the stack
        # from the table (it carries every per-layer leaf, dense included)
        per = [dequantize_tree(table[k], dtype)
               for k in sorted(table, key=int)]
        layers = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per)
    params = dict(params)
    params["layers"] = layers
    if is_qtensor(params.get("unembed", None)):
        params["unembed"] = dequant_qtensor(params["unembed"], dtype)
    return params
