"""Numerical guards for the quantization pipeline (DESIGN.md §8.2).

COMQ is hyperparameter-free — "only dot products and rounding" — so
robustness to degenerate calibration has to come from the pipeline, not
from tuning. This module is the single implementation both solvers share:

* **Sentinels** — `sanitize_array` / `gram_health` count non-finite
  entries (and zero Gram diagonals = dead input columns) with one small
  host transfer, and zero out NaN/Inf *only when any were actually
  found*, so the healthy path stays bit-identical to the unguarded one.
* **Escalating diagonal damping** — `damp_hessian(h, mult)` adds
  `mult · mean(diag H) · I`; `DAMP_MULTS` is the escalation schedule a
  failed solve walks (an undamped attempt always runs first).
  `damped_inverse` is the jit/vmap-safe variant the GPTQ baseline uses:
  a `lax.while_loop` that re-inverts under 10× stronger damping until
  H⁻¹ is finite.
* **Fallback chain** — `guarded_solve` retries a failed solve through
  `solver_chain(method)` (comq_blocked: trailing → refresh → RTN;
  comq/gptq: → RTN), escalating damping within each stage, and finally
  data-free RTN, which is finite by construction. Every escalation and
  fallback is recorded as a `GuardEvent` on the `GuardContext` (surfaced
  in `QuantReport.guard_events` / `LayerReport.guard`) and warned loudly
  — degradation is never silent.

Dead columns need no special-casing here: every solver already routes a
zero Gram diagonal to plain rounding per column (the `hg > EPS` where-
clauses in comq/comq_hessian), which is exactly the RTN-per-dead-column
rule; the guards just *count and report* them.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.quantizer import EPS, QuantSpec
from repro.obs.trace import NULL_TRACER

Array = jax.Array

# escalation schedule, as multiples of mean(diag H); an undamped attempt
# always runs first so healthy solves stay bit-identical to the
# unguarded pipeline
DAMP_MULTS = (1e-4, 1e-2, 1e-1, 1.0)

# a solve whose final H-space error exceeds this multiple of its initial
# (grid/RTN) error has diverged, even if finite — escalate
EXPLODE_FACTOR = 10.0


@dataclass
class GuardEvent:
    """One guard intervention, keyed to the leaf it protected."""
    layer: int
    name: str
    kind: str            # nonfinite_tap | nonfinite_gram | nonfinite_weight
    #                    | dead_columns | damping_escalated | fallback
    #                    | sharded_solve_nonfinite
    detail: Dict[str, Any] = field(default_factory=dict)


class GuardContext:
    """Collects GuardEvents across one quantize_model walk. A disabled
    context makes every guard hook a no-op (and bit-exact). `tracer`
    (obs.Tracer) gets a `quant.sync.<site>` span around each host sync
    the guards make."""

    def __init__(self, enabled: bool = True, tracer=None):
        self.enabled = enabled
        self.tracer = tracer or NULL_TRACER
        self.events: List[GuardEvent] = []

    def record(self, layer: int, name: str, kind: str, warn: bool = True,
               **detail) -> GuardEvent:
        ev = GuardEvent(int(layer), str(name), kind, dict(detail))
        self.events.append(ev)
        if warn:
            warnings.warn(
                f"quantization guard [{kind}] layer {layer} leaf {name}: "
                f"{detail}", stacklevel=3)
        return ev

    def by_leaf(self) -> Dict[Tuple[int, str], str]:
        """(layer, name) -> comma-joined distinct event kinds, for the
        per-leaf LayerReport.guard annotation."""
        out: Dict[Tuple[int, str], List[str]] = {}
        for e in self.events:
            kinds = out.setdefault((e.layer, e.name), [])
            if e.kind not in kinds:
                kinds.append(e.kind)
        return {k: ",".join(v) for k, v in out.items()}


# ---------------------------------------------------------------------------
# sentinels
# ---------------------------------------------------------------------------

def nonfinite_count(x: Array) -> int:
    """Host int: number of NaN/Inf entries (one small transfer)."""
    # comq: allow(host-sync) sentinel: one small intentional transfer
    return int(jax.device_get(jnp.sum(~jnp.isfinite(x))))

def sanitize_array(x: Array) -> Tuple[Array, int]:
    """(x with NaN/Inf zeroed, how many there were). The replacement runs
    only when the count is nonzero, so clean inputs pass through
    untouched — bit-identity of the healthy path is structural."""
    n_bad = nonfinite_count(x)
    if n_bad:
        x = jnp.where(jnp.isfinite(x), x, jnp.zeros((), x.dtype))
    return x, n_bad


def gram_health(h: Array, w2ds: Sequence[Array] = ()) -> Tuple[int, int,
                                                               List[int]]:
    """(nonfinite entries of H, dead diagonal columns of H, nonfinite
    entries per weight) in ONE batched device transfer — the per-group
    sentinel the pipeline runs before each solve."""
    diag = jnp.diagonal(h, axis1=-2, axis2=-1)
    vals = [jnp.sum(~jnp.isfinite(h)), jnp.sum(diag <= EPS)]
    vals += [jnp.sum(~jnp.isfinite(w)) for w in w2ds]
    # comq: allow(host-sync) sentinel: one batched health transfer per Gram
    out = jax.device_get(jnp.stack([jnp.asarray(v, jnp.int32)
                                    for v in vals]))
    return int(out[0]), int(out[1]), [int(v) for v in out[2:]]


# ---------------------------------------------------------------------------
# escalating diagonal damping
# ---------------------------------------------------------------------------

def damp_hessian(h: Array, mult, diag_mean=None) -> Array:
    """H + mult · mean(diag H) · I. Works batched ((..., m, m) with a
    (...,)-shaped diag_mean) so the vmapped per-expert path can reuse it;
    the mean is floored at EPS so an all-zero H still moves."""
    m = h.shape[-1]
    if diag_mean is None:
        diag_mean = jnp.mean(jnp.diagonal(h, axis1=-2, axis2=-1), axis=-1)
    lam = jnp.asarray(mult * jnp.maximum(
        jnp.asarray(diag_mean, jnp.float32), EPS))
    return h + jnp.eye(m, dtype=h.dtype) * lam[..., None, None]


def damped_inverse(h: Array, start: float = 0.01, diag_mean=None,
                   max_tries: int = 4) -> Tuple[Array, Array]:
    """(H + λI)⁻¹ with λ escalated ×10 per retry until the inverse is
    finite; pure-JAX (lax.while_loop) so it is jit/vmap-safe — the GPTQ
    baseline calls it from inside jitted/vmapped solves. Returns
    (hinv, final multiplier); after max_tries a still-bad inverse is
    NaN→0-scrubbed and left for the caller's fallback chain (the
    post-solve result check catches the exploded error)."""
    m = h.shape[-1]
    if diag_mean is None:
        diag_mean = jnp.mean(jnp.diag(h))
    base = jnp.maximum(jnp.asarray(diag_mean, jnp.float32), EPS)
    eye = jnp.eye(m, dtype=h.dtype)

    def inv_at(mult):
        return jnp.linalg.inv(h + eye * (mult * base))

    def cond(carry):
        hinv, mult, tries = carry
        return (~jnp.all(jnp.isfinite(hinv))) & (tries < max_tries)

    def body(carry):
        _, mult, tries = carry
        mult = mult * 10.0
        return inv_at(mult), mult, tries + 1

    hinv, mult, _ = jax.lax.while_loop(
        cond, body, (inv_at(jnp.float32(start)), jnp.float32(start),
                     jnp.int32(0)))
    hinv = jnp.where(jnp.isfinite(hinv), hinv, 0.0)
    return hinv, mult


# ---------------------------------------------------------------------------
# guarded solve: damping escalation + structured fallback chain
# ---------------------------------------------------------------------------

def solver_chain(method: str) -> Tuple[Tuple[str, Optional[str]], ...]:
    """(method, schedule) stages to try in order. comq_blocked falls back
    to the per-panel-refresh schedule (different FP accumulation path can
    survive conditioning the trailing update cannot) before RTN; the
    row/sequential solvers go straight to RTN."""
    if method == "comq_blocked":
        return (("comq_blocked", "trailing"), ("comq_blocked", "refresh"),
                ("rtn", None))
    if method in ("comq", "gptq"):
        return ((method, None), ("rtn", None))
    return (("rtn", None),)


def result_ok(r, ref_err=None) -> bool:
    """Host bool: scales and errors finite and — when `ref_err` (the
    data-free RTN error on the same grid, the natural do-no-harm
    reference) is given — the final H-space error did not explode past
    EXPLODE_FACTOR × it. The solvers' own errors[0] is NOT a usable
    reference: comq/comq_blocked log the float-Q⁰ error there (≈ 0)."""
    delta = jnp.asarray(r.delta, jnp.float32)
    errs = jnp.asarray(r.errors, jnp.float32)
    ok = jnp.all(jnp.isfinite(delta)) & jnp.all(jnp.isfinite(errs))
    if ref_err is not None:
        base = jnp.maximum(jnp.asarray(ref_err, jnp.float32),
                           jnp.float32(1e-6))
        ok = ok & (errs[-1] <= EXPLODE_FACTOR * base)
    return bool(jax.device_get(ok))  # comq: allow(host-sync) one scalar verdict per solve


def guarded_solve(h: Array, w2d: Array, spec: QuantSpec, method: str, *,
                  block: int = 256, gctx: Optional[GuardContext] = None,
                  layer: int = -1, names: Sequence[str] = ("?",),
                  solve_fn=None, presanitized: bool = False):
    """pipeline.solve with the full guard policy: sanitize inputs, try
    the method undamped (bit-identical when healthy), then escalate
    damping through DAMP_MULTS, then walk solver_chain, and as a last
    resort quantize data-free RTN. Records one GuardEvent per protected
    leaf name for everything it had to do."""
    if solve_fn is None:
        from repro.core.pipeline import solve as solve_fn
    if gctx is None or not gctx.enabled:
        return solve_fn(h, w2d, spec, method, block=block)

    if not presanitized:
        h, n_bad = sanitize_array(h)
        if n_bad:
            for nm in names:
                gctx.record(layer, nm, "nonfinite_gram", count=n_bad)
        w2d, n_badw = sanitize_array(w2d)
        if n_badw:
            for nm in names:
                gctx.record(layer, nm, "nonfinite_weight", count=n_badw)
        # comq: allow(host-sync) sentinel: one scalar per guarded solve
        n_dead = int(jax.device_get(jnp.sum(jnp.diag(h) <= EPS)))
        if n_dead:
            for nm in names:
                gctx.record(layer, nm, "dead_columns", warn=False,
                            count=n_dead)

    from repro.core.baselines import rtn_quantize   # lazy: baselines imports us
    # the do-no-harm explosion reference: the data-free RTN error on the
    # same (sanitized) H — a solve that lands >10× above plain rounding
    # has diverged even if every value is finite
    ref_err = rtn_quantize(w2d, spec, h=h).errors[-1]
    diag_mean = jnp.mean(jnp.diag(h))
    for stage, (meth, schedule) in enumerate(solver_chain(method)):
        tag = meth if schedule in (None, "trailing") else f"{meth}:{schedule}"
        for mult in (0.0,) + DAMP_MULTS:
            hd = h if mult == 0.0 else damp_hessian(h, mult, diag_mean)
            r = solve_fn(hd, w2d, spec, meth, block=block, schedule=schedule)
            with gctx.tracer.span("quant.sync.solve_verdict"):
                ok = result_ok(r, ref_err)
            if ok:
                if mult:
                    for nm in names:
                        gctx.record(layer, nm, "damping_escalated",
                                    mult=mult, solver=tag)
                if stage:
                    for nm in names:
                        gctx.record(layer, nm, "fallback", solver=tag)
                return r
    r = rtn_quantize(w2d, spec)     # data-free: finite by construction
    for nm in names:
        gctx.record(layer, nm, "fallback", solver="rtn_no_h")
    return r
