"""Pallas TPU kernel: block-causal flash attention with native GQA.

Grid (BH, Tq/bq, Tk/bk) with the KV dimension innermost; online-softmax
state (m, l, acc) lives in VMEM scratch and is re-initialized at kv step 0.
Causal (and sliding-window) masking is applied with in-register iota
compares on the diagonal band; fully-masked blocks are skipped with
`pl.when`, so — unlike a dense masked attention — no MXU work is issued
above the diagonal or outside the SWA band.

GQA is expressed in the K/V BlockSpec index maps (`bh // group`), so K/V
are never materialized per-q-head in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bq: int, bk: int, n_k: int, scale: float, causal: bool,
            window: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q0 = qi * bq
    k0 = ki * bk
    # block is live iff it intersects the causal band
    live = True
    if causal:
        live = k0 <= q0 + bq - 1
        if window > 0:
            live = jnp.logical_and(live, q0 - (k0 + bk - 1) < window)

    @pl.when(live if causal else True)
    def _compute():
        q = q_ref[0].astype(jnp.float32)              # (bq, hd)
        k = k_ref[0].astype(jnp.float32)              # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = qpos >= kpos
            if window > 0:
                mask = jnp.logical_and(mask, qpos - kpos < window)
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                           # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _epilogue():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(q: Array, k: Array, v: Array, *,
                           causal: bool = True, window: int = 0,
                           bq: int = 512, bk: int = 512,
                           interpret: bool = False) -> Array:
    """q: (BH, Tq, hd); k/v: (BHkv, Tk, hd), BH % BHkv == 0 (GQA groups).

    Returns (BH, Tq, hd) in q.dtype."""
    BH, Tq, hd = q.shape
    BHkv, Tk, _ = k.shape
    group = BH // BHkv
    bq = min(bq, Tq)
    bk = min(bk, Tk)
    while Tq % bq:
        bq //= 2
    while Tk % bk:
        bk //= 2
    n_k = Tk // bk
    scale = 1.0 / float(hd) ** 0.5
    grid = (BH, Tq // bq, n_k)

    return pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, n_k=n_k, scale=scale,
                          causal=causal, window=window),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Tq, hd), q.dtype),
        scratch_shapes=[
            _vmem((bq, 1), jnp.float32),
            _vmem((bq, 1), jnp.float32),
            _vmem((bq, hd), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)
