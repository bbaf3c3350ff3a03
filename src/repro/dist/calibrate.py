"""Data-parallel COMQ calibration + column-sharded solves (DESIGN.md §4.2/§4.3).

The calibration batch is sharded over the mesh's "data" axis; every tap
forward then runs SPMD on the local shard, and the only communication the
whole pipeline needs is one `psum` of each (m, m) Gram block — solves run
on the maintained-P blocked solver (ROADMAP constraint), either replicated
or, with a nontrivial "model" axis, with W's output columns sharded over
"model" (`sharded_solve`): H is replicated, every per-column operand is
partitioned, and the solve issues zero collectives (the shared greedy
order — the only column-coupled quantity — is precomputed on the full W
and passed in replicated).

Communication accounting per transformer layer (dense family): 4 taps →
4 Gram all-reduces of m·m f32 ≈ 4·d² + (Hp·hd)² + f² bytes·4, independent
of the number of calibration tokens. Compare the data it replaces: an
all-gather of the (N, m) features would move N·m·4 bytes per tap.

Both wire invariants are *checked against compiled HLO*, not just
documented: the analysis gate (`repro.analysis.registry`) holds the
`dist.gram` contract to exactly one all-reduce and the `dist.solve`
contract to zero collectives, and tests/test_dist.py re-asserts them via
`repro.analysis.check_lowered` on the local mesh.
"""
from __future__ import annotations

import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.dist.collectives import psum_gram

Array = jax.Array

# obs hook (DESIGN.md §10): fires once per Gram psum with the all-reduced
# byte count, derived from static shapes on the host — no device sync and
# no cost when unset. The pipeline installs a metrics-counter callback
# here for the run's duration (`dist.bytes_all_reduced`).
_allreduce_observer = None


def set_allreduce_observer(cb):
    """Install `cb(n_bytes)` (or None to clear); returns the previous
    observer so callers can restore it."""
    global _allreduce_observer
    prev = _allreduce_observer
    _allreduce_observer = cb
    return prev


def data_mesh(n: Optional[int] = None) -> Mesh:
    """1-axis ("data",) mesh over the first n (default: all) local devices.
    Under XLA_FLAGS=--xla_force_host_platform_device_count=K this is the
    forced-host smoke mesh the multi-device CI job runs on."""
    devices = jax.devices()
    n = n or len(devices)
    return Mesh(np.asarray(devices[:n]).reshape(n), ("data",))


def calib_mesh(model: int = 1, data: Optional[int] = None) -> Mesh:
    """("data", "model") calibration mesh: the batch (and Gram psum) use
    "data"; solve columns shard over "model" (`sharded_solve`). With
    data=None the data axis takes all devices the model axis leaves."""
    devices = jax.devices()
    n = len(devices)
    if model < 1 or n % model:
        raise ValueError(f"model axis {model} must divide {n} devices")
    data = n // model if data is None else data
    if data < 1 or data * model > n:
        raise ValueError(f"mesh ({data}, {model}) needs {data * model} "
                         f"devices, have {n}")
    return Mesh(np.asarray(devices[:data * model]).reshape(data, model),
                ("data", "model"))


def model_size(mesh: Optional[Mesh]) -> int:
    return 1 if mesh is None else int(mesh.shape.get("model", 1))


def shard_batch(mesh: Mesh, x: Array) -> Array:
    """Place x with its leading (batch) axis sharded over the "data" axis."""
    ndata = mesh.shape["data"]
    if x.shape[0] % ndata:
        raise ValueError(
            f"batch {x.shape[0]} not divisible by data axis {ndata}")
    return jax.device_put(x, NamedSharding(mesh, P("data")))


@functools.lru_cache(maxsize=8)
def _gram_fn(mesh: Mesh):
    """Jitted shard_map'd Gram, cached per mesh (and per shape via jit):
    the calibration walk calls this once per tap per layer — without the
    cache every call would re-trace the shard_map."""
    return jax.jit(jax.shard_map(lambda t: psum_gram(t, "data"), mesh=mesh,
                                 in_specs=P("data"), out_specs=P(),
                                 check_vma=False))


@functools.lru_cache(maxsize=8)
def _batched_gram_fn(mesh: Mesh):
    def local(t):
        t = t.astype(jnp.float32)
        return jax.lax.psum(jnp.einsum("ecd,ecf->edf", t, t), "data")
    return jax.jit(jax.shard_map(local, mesh=mesh,
                                 in_specs=P(None, "data"), out_specs=P(),
                                 check_vma=False))


def sharded_gram(mesh: Mesh, tap: Array) -> Array:
    """(B, T, d) tap (batch-sharded or not) -> replicated (d, d) Gram.

    shard_map computes the local-shard XᵀX and all-reduces it with a single
    psum — the only cross-device traffic of the calibration walk."""
    if tap.shape[0] % mesh.shape["data"]:
        # batch doesn't divide the axis: fall back to the replicated Gram
        warnings.warn(
            f"sharded_gram: tap batch {tap.shape[0]} does not divide the "
            f"data axis {mesh.shape['data']}; falling back to the "
            "replicated Gram (no psum) for this tap", stacklevel=2)
        from repro.core.calibrate import gram_from_tap
        return gram_from_tap(tap)
    h = _gram_fn(mesh)(tap)
    if _allreduce_observer is not None:
        _allreduce_observer(int(h.shape[0]) * int(h.shape[1]) * 4)
    return h


def sharded_batched_gram(mesh: Mesh, tap: Array) -> Array:
    """(E, C, d) stacked-expert tap with the capacity axis sharded ->
    replicated (E, d, d) per-expert Grams, one psum.

    The capacity axis must divide the data axis — `quantize_model` aligns
    MoE routing capacity via BuildPlan.moe_capacity_multiple precisely so
    expert taps never take the replicated fallback; if one still does
    (e.g. a hand-built tap), warn rather than silently leaving the psum
    path."""
    if tap.shape[1] % mesh.shape["data"]:
        warnings.warn(
            f"sharded_batched_gram: expert capacity {tap.shape[1]} does not "
            f"divide the data axis {mesh.shape['data']}; falling back to "
            "the replicated per-expert Gram (no psum). Align the routing "
            "capacity (BuildPlan.moe_capacity_multiple) to stay on the "
            "psum path.", stacklevel=2)
        from repro.core.calibrate import batched_gram
        return batched_gram(tap)
    hs = _batched_gram_fn(mesh)(tap)
    if _allreduce_observer is not None:
        _allreduce_observer(int(hs.shape[0]) * int(hs.shape[1])
                            * int(hs.shape[2]) * 4)
    return hs


# ---------------------------------------------------------------------------
# column-sharded solves (DESIGN.md §4.3)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=32)
def _solve_fn(mesh: Mesh, spec, method: str, block: int):
    """Jitted shard_map'd column-sharded solve, cached per
    (mesh, spec, method, block); jit caches per operand shape.

    The local function runs the *unmodified* solver on this shard's column
    slice — bit-identical to the replicated solve because every operand it
    touches is column-offset-invariant (the shared visit order arrives
    precomputed via `perm`). It also computes the per-column squared
    errors for reporting (one local H·R matmul each for the RTN init and
    the final codes), so nothing downstream needs the solver's scalar
    error trajectory — the shard_map body contains zero collectives."""
    from repro.core.baselines import rtn_quantize
    from repro.core.comq_hessian import comq_quantize_blocked
    from repro.core.pipeline import _col_err2
    from repro.dist.sharding import solver_specs

    def local(h, w, perm):
        if method == "comq_blocked":
            r = comq_quantize_blocked(h, w, spec, block=block, perm=perm)
        elif method == "rtn":
            r = rtn_quantize(w, spec, h=h)
        else:
            raise ValueError(f"method {method!r} is not column-shardable")
        wq = r.q.astype(jnp.float32) * r.delta
        e2_after = _col_err2(h, w, wq)
        rt = rtn_quantize(w, spec)
        e2_before = _col_err2(h, w, rt.q.astype(jnp.float32) * rt.delta)
        return r.q, r.delta, r.z_lo, e2_before, e2_after

    s = solver_specs(mesh)
    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(s["h"], s["w"], s["perm"]),
        out_specs=(s["q"], s["delta"], s["z"], s["col_err2"],
                   s["col_err2"]),
        check_vma=False))


def sharded_solve(mesh: Mesh, h: Array, w2d: Array, spec, method: str,
                  block: int = 256):
    """Column-sharded COMQ solve: W's output columns partition over the
    "model" axis; H and the shared visit order are replicated; the solve
    issues no collectives (asserted in tests on the compiled HLO).

    Returns (q, delta, z_lo, e2_before, e2_after) with the column-
    partitioned outputs still sharded — callers slice them per leaf
    exactly like the fused replicated path. Columns are zero-padded up to
    a multiple of the model axis (trailing pad; column independence makes
    the shard assignment irrelevant to bit-identity) and stripped before
    returning.

    Per-leaf mixed-precision policies pass each leaf's *resolved* spec
    here (the pipeline no longer binds one global spec): _solve_fn caches
    one compiled shard_map per distinct (mesh, spec, method, block), so a
    first/bulk/last bit mix costs a handful of cache entries, and every
    leaf's sharded solve stays bit-identical to its replicated solve at
    its own width (tested on the forced (2, 4) mesh).
    """
    from repro.core.comq_hessian import shared_order
    from repro.models.common import pad_to_multiple

    tp = model_size(mesh)
    h = h.astype(jnp.float32)
    w2d = w2d.astype(jnp.float32)
    n = w2d.shape[1]
    n_pad = pad_to_multiple(n, tp)
    wp = (jnp.pad(w2d, ((0, 0), (0, n_pad - n))) if n_pad != n else w2d)
    if method == "comq_blocked":
        # the one column-coupled quantity, computed once on the full W —
        # from the *unpadded* columns so the order (and therefore every
        # code) matches the replicated solve exactly
        perm = shared_order(h, w2d, spec)
    else:
        perm = jnp.arange(h.shape[0], dtype=jnp.int32)
    q, delta, z_lo, e2b, e2a = _solve_fn(mesh, spec, method, block)(
        h, wp, perm)
    if n_pad != n:
        q, delta, z_lo = q[:, :n], delta[:n], z_lo[:n]
        e2b, e2a = e2b[:n], e2a[:n]
    return q, delta, z_lo, e2b, e2a
