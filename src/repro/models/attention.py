"""Grouped-query attention with TPU-friendly structure.

Key design points (see DESIGN.md §4):

* **Head padding.** The production mesh has a 16-way model axis; q-heads are
  padded up to a multiple of the TP degree (qwen2 28->32, hymba 25->32,
  granite 24->32). Padded heads use zeroed projections and map to kv head 0.
  The waste shows up honestly in the HLO-flops/model-flops ratio.
* **KV replication.** n_kv_heads is 4-8 for most archs — smaller than the
  model axis — so K/V projections are computed replicated across the model
  axis (their weights are FSDP-sharded on the data axis only). GQA expansion
  is a static gather `k[:, :, head_to_kv, :]`, which SPMD keeps local.
* **Block-causal flash attention** implemented as a `lax.scan` over the
  *static list of lower-triangular (q-block, kv-block) pairs* with an online
  softmax carry. Unlike a dense mask, no flops are spent above the diagonal,
  so HLO flops match the true causal cost; unlike a nested q/kv scan there
  is one rolled loop (small HLO). Sliding-window archs restrict the pair
  list to the diagonal band — again at zero masked-block cost.
* **Decode** attends over the full (or ring-buffer) cache with a position
  mask; softmax/contract reductions over the sequence-sharded cache dim
  lower to small per-head collectives under SPMD.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import (Array, apply_rope, dense_init, zeros_init)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def attn_param_shapes(cfg, n_heads_padded: int, q_in: Optional[int] = None,
                      kv_in: Optional[int] = None) -> dict:
    d, hd, kv = cfg.d_model, cfg.resolved_head_dim, cfg.n_kv_heads
    q_in = q_in or d
    kv_in = kv_in or d
    shapes = {
        "wq": (q_in, n_heads_padded, hd),
        "wk": (kv_in, kv, hd),
        "wv": (kv_in, kv, hd),
        "wo": (n_heads_padded, hd, d),
    }
    if cfg.qkv_bias:
        shapes.update({"bq": (n_heads_padded, hd), "bk": (kv, hd),
                       "bv": (kv, hd)})
    return shapes


def init_attn(key: Array, cfg, n_heads_padded: int, stack: Tuple[int, ...] = (),
              q_in: Optional[int] = None, kv_in: Optional[int] = None) -> dict:
    shapes = attn_param_shapes(cfg, n_heads_padded, q_in, kv_in)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for (name, shp), k in zip(sorted(shapes.items()), keys):
        if name.startswith("b"):
            out[name] = zeros_init(k, (*stack, *shp))
        else:
            out[name] = dense_init(k, (*stack, *shp))
    return out


def head_to_kv_map(n_heads: int, n_heads_padded: int, n_kv: int) -> Array:
    """Static q-head -> kv-head index map.

    When the padded head count divides evenly into kv groups we use the
    uniform grouping h -> h // (Hp/KV): this makes the GQA contraction a
    reshape + grouped einsum (no materialized K/V expansion). Otherwise
    (hymba: 32 padded q heads over 5 kv) fall back to floor mapping with
    padded heads parked on kv 0."""
    if n_heads_padded % n_kv == 0:
        return jnp.arange(n_heads_padded) // (n_heads_padded // n_kv)
    q_per_kv = max(n_heads // n_kv, 1)
    idx = jnp.arange(n_heads_padded) // q_per_kv
    idx = jnp.where(jnp.arange(n_heads_padded) < n_heads,
                    jnp.minimum(idx, n_kv - 1), 0)
    return idx


# ---------------------------------------------------------------------------
# projections
# ---------------------------------------------------------------------------

def _project_in(w, x: Array, cd) -> Array:
    """(B, T, d) · w -> (B, T, H, hd); w dense (d, H, hd) or a fused-layout
    QT whose codes are (d, H·hd) — routed through the dequant-fused GEMM
    (repro.kernels.ops.quant_matmul) so decode streams int4/int8 codes."""
    from repro.core.apply import is_qt, qt_linear, qt_out_dims
    if is_qt(w):
        B, T, d = x.shape
        y = qt_linear(w, x.reshape(B * T, d), out_dtype=cd)
        return y.reshape(B, T, *qt_out_dims(w))
    return jnp.einsum("btd,dhk->bthk", x, w.astype(cd))


def qkv_project(p: dict, x: Array, kv_x: Optional[Array] = None):
    """x: (B, T, d) -> q (B,T,Hp,hd), k/v (B,T,KV,hd)."""
    kv_x = x if kv_x is None else kv_x
    cd = x.dtype
    q = _project_in(p["wq"], x, cd)
    k = _project_in(p["wk"], kv_x, cd)
    v = _project_in(p["wv"], kv_x, cd)
    if "bq" in p:
        q = q + p["bq"].astype(cd)
        k = k + p["bk"].astype(cd)
        v = v + p["bv"].astype(cd)
    return q, k, v


def out_project(p: dict, o: Array) -> Array:
    from repro.core.apply import is_qt, qt_linear, qt_out_dims
    w = p["wo"]
    if is_qt(w):
        B, T, H, hd = o.shape
        y = qt_linear(w, o.reshape(B * T, H * hd), out_dtype=o.dtype)
        return y.reshape(B, T, *qt_out_dims(w))
    return jnp.einsum("bthk,hkd->btd", o, w.astype(o.dtype))


# ---------------------------------------------------------------------------
# block-causal flash attention (training / prefill)
# ---------------------------------------------------------------------------

def _tril_pairs(n_blocks: int, band: Optional[int]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Static (i, j) lower-triangle block pairs; band limits |i-j| for SWA."""
    pi, pj = [], []
    for i in range(n_blocks):
        j0 = 0 if band is None else max(0, i - band)
        for j in range(j0, i + 1):
            pi.append(i)
            pj.append(j)
    return jnp.asarray(pi, jnp.int32), jnp.asarray(pj, jnp.int32)


def pick_block_size(seq_len: int, target: int = 512) -> int:
    c = min(target, seq_len)
    while seq_len % c:
        c //= 2
    return max(c, 1)


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "block_size"))
def flash_attention(q: Array, k: Array, v: Array, head_map: Array, *,
                    causal: bool = True, window: int = 0,
                    block_size: int = 512) -> Array:
    """q: (B,T,Hp,hd); k,v: (B,T,KV,hd). Returns (B,T,Hp,hd).

    Scan over static lower-triangular block pairs with an online-softmax
    carry. `window > 0` enables sliding-window masking and prunes the pair
    list to the diagonal band. GQA: when Hp divides into KV groups the
    contraction is a grouped einsum (K/V never materialize per-q-head);
    otherwise a static gather expands K/V (hymba's 5-kv case).

    Jitted at definition (static mask config): eager callers — the staged
    calibration walk quantizes mid-forward and therefore runs un-jitted at
    the layer level — hit the jit cache instead of retracing the pair scan
    per call; jitted callers inline it as before.
    """
    B, T, H, hd = q.shape
    KV = k.shape[2]
    if not causal:
        return _dense_attention(q, k, v, head_map, causal=False, window=0)
    C = pick_block_size(T, block_size)
    n = T // C
    band = None if window <= 0 else (window + C - 1) // C
    pi, pj = _tril_pairs(n, band)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))

    grouped = (H % KV == 0)
    if grouped:
        G = H // KV
        qb = q.reshape(B, n, C, KV, G, hd)
        kb = k.reshape(B, n, C, KV, hd)
        vb = v.reshape(B, n, C, KV, hd)
        return _flash_grouped(qb, kb, vb, pi, pj, scale, C, window, B, T, H,
                              hd, q.dtype)
    k = k[:, :, head_map, :]                      # (B, T, Hp, hd) gqa-expand
    v = v[:, :, head_map, :]
    qb = q.reshape(B, n, C, H, hd)
    kb = k.reshape(B, n, C, H, hd)
    vb = v.reshape(B, n, C, H, hd)

    o0 = jnp.zeros((B, n, C, H, hd), jnp.float32)
    m0 = jnp.full((B, n, H, C), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, n, H, C), jnp.float32)

    def step(carry, ij):
        o, m, l = carry
        i, j = ij
        qi = jax.lax.dynamic_index_in_dim(qb, i, 1, keepdims=False)
        kj = jax.lax.dynamic_index_in_dim(kb, j, 1, keepdims=False)
        vj = jax.lax.dynamic_index_in_dim(vb, j, 1, keepdims=False)
        s = jnp.einsum("bchk,bshk->bhcs", qi, kj,
                       preferred_element_type=jnp.float32) * scale
        # 2D additive bias (pre-broadcast) so loop-invariant hoisting stays
        # (n_pairs, C, C) instead of materializing (n_pairs, B, H, C, C)
        qpos = i * C + jnp.arange(C)
        kpos = j * C + jnp.arange(C)
        mask = qpos[:, None] >= kpos[None, :]
        if window > 0:
            mask &= qpos[:, None] - kpos[None, :] < window
        bias = jnp.where(mask, 0.0, NEG_INF).astype(jnp.float32)
        s = s + bias[None, None]

        mi = jax.lax.dynamic_index_in_dim(m, i, 1, keepdims=False)  # (B,H,C)
        li = jax.lax.dynamic_index_in_dim(l, i, 1, keepdims=False)
        oi = jax.lax.dynamic_index_in_dim(o, i, 1, keepdims=False)  # (B,C,H,hd)
        m_new = jnp.maximum(mi, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])                 # (B,H,C,S)
        corr = jnp.exp(mi - m_new)                        # (B,H,C)
        l_new = li * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhcs,bshk->bchk", p.astype(vj.dtype), vj,
                        preferred_element_type=jnp.float32)
        o_new = oi * corr.transpose(0, 2, 1)[..., None] + pv
        o = jax.lax.dynamic_update_index_in_dim(o, o_new, i, 1)
        m = jax.lax.dynamic_update_index_in_dim(m, m_new, i, 1)
        l = jax.lax.dynamic_update_index_in_dim(l, l_new, i, 1)
        return (o, m, l), None

    # remat the step: the backward recomputes scores/p per block instead of
    # saving (n_pairs × B × H × C × C) f32 residuals — the flash-attention
    # backward memory policy.
    step = jax.checkpoint(step)
    (o, m, l), _ = jax.lax.scan(step, (o0, m0, l0), (pi, pj))
    l = jnp.maximum(l, 1e-20)
    out = o / l.transpose(0, 1, 3, 2)[..., None]
    return out.reshape(B, T, H, hd).astype(q.dtype)


def _flash_grouped(qb, kb, vb, pi, pj, scale, C, window, B, T, H, hd, dtype):
    """Grouped-GQA flash pair-scan: qb (B,n,C,KV,G,hd); kb/vb (B,n,C,KV,hd)."""
    n = qb.shape[1]
    KV, G = qb.shape[3], qb.shape[4]
    o0 = jnp.zeros((B, n, C, KV, G, hd), jnp.float32)
    m0 = jnp.full((B, n, KV, G, C), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, n, KV, G, C), jnp.float32)

    def step(carry, ij):
        o, m, l = carry
        i, j = ij
        qi = jax.lax.dynamic_index_in_dim(qb, i, 1, keepdims=False)
        kj = jax.lax.dynamic_index_in_dim(kb, j, 1, keepdims=False)
        vj = jax.lax.dynamic_index_in_dim(vb, j, 1, keepdims=False)
        s = jnp.einsum("bckgh,bskh->bkgcs", qi, kj,
                       preferred_element_type=jnp.float32) * scale
        qpos = i * C + jnp.arange(C)
        kpos = j * C + jnp.arange(C)
        mask = qpos[:, None] >= kpos[None, :]
        if window > 0:
            mask &= qpos[:, None] - kpos[None, :] < window
        bias = jnp.where(mask, 0.0, NEG_INF).astype(jnp.float32)
        s = s + bias[None, None, None]

        mi = jax.lax.dynamic_index_in_dim(m, i, 1, keepdims=False)
        li = jax.lax.dynamic_index_in_dim(l, i, 1, keepdims=False)
        oi = jax.lax.dynamic_index_in_dim(o, i, 1, keepdims=False)
        m_new = jnp.maximum(mi, s.max(axis=-1))           # (B,KV,G,C)
        p = jnp.exp(s - m_new[..., None])                 # (B,KV,G,C,S)
        corr = jnp.exp(mi - m_new)
        l_new = li * corr + p.sum(axis=-1)
        pv = jnp.einsum("bkgcs,bskh->bckgh", p.astype(vj.dtype), vj,
                        preferred_element_type=jnp.float32)
        o_new = oi * corr.transpose(0, 3, 1, 2)[..., None] + pv
        o = jax.lax.dynamic_update_index_in_dim(o, o_new, i, 1)
        m = jax.lax.dynamic_update_index_in_dim(m, m_new, i, 1)
        l = jax.lax.dynamic_update_index_in_dim(l, l_new, i, 1)
        return (o, m, l), None

    step = jax.checkpoint(step)
    (o, m, l), _ = jax.lax.scan(step, (o0, m0, l0), (pi, pj))
    l = jnp.maximum(l, 1e-20)
    out = o / l.transpose(0, 1, 4, 2, 3)[..., None]
    return out.reshape(B, T, H, hd).astype(dtype)


def _dense_attention(q: Array, k: Array, v: Array, head_map: Array, *,
                     causal: bool, window: int,
                     q_positions: Optional[Array] = None,
                     kv_positions: Optional[Array] = None,
                     kv_valid: Optional[Array] = None) -> Array:
    """Reference/dense path: encoders, cross-attn, decode-over-cache.

    kv_positions/kv_valid: (B, S) absolute positions + validity for masking
    (ring buffers); q_positions: (B, Tq). Grouped GQA einsum when possible
    (no K/V expansion in memory)."""
    B, Tq, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    grouped = H % KV == 0
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    if grouped:
        G = H // KV
        qg = q.reshape(B, Tq, KV, G, hd)
        s = jnp.einsum("btkgh,bskh->bkgts", qg, k,
                       preferred_element_type=jnp.float32) * scale
    else:
        k = k[:, :, head_map, :]
        v = v[:, :, head_map, :]
        s = jnp.einsum("bthk,bshk->bhts", q, k,
                       preferred_element_type=jnp.float32) * scale
    mask = jnp.ones((B, 1, Tq, S), bool)
    if causal:
        qp = (q_positions if q_positions is not None
              else jnp.broadcast_to(jnp.arange(Tq), (B, Tq)))
        kp = (kv_positions if kv_positions is not None
              else jnp.broadcast_to(jnp.arange(S), (B, S)))
        mask &= qp[:, None, :, None] >= kp[:, None, None, :]
        if window > 0:
            mask &= qp[:, None, :, None] - kp[:, None, None, :] < window
    if kv_valid is not None:
        mask &= kv_valid[:, None, None, :]
    if grouped:
        s = jnp.where(mask[:, :, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgts,bskh->btkgh", p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        return out.reshape(B, Tq, H, hd).astype(q.dtype)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhts,bshk->bthk", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# KV cache (supports full caches and SWA ring buffers)
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    k: Array          # (B, S_cache, KV, hd) — rope pre-applied
    v: Array          # (B, S_cache, KV, hd)
    pos: Array        # (B, S_cache) absolute positions, -1 = empty
    # int8 cache mode: k/v hold int8 codes, scales are per-entry absmax/127
    k_scale: Optional[Array] = None   # (B, S_cache, KV)
    v_scale: Optional[Array] = None


def init_kv_cache(batch: int, cache_len: int, n_kv: int, hd: int,
                  dtype=jnp.bfloat16, quantized: bool = False) -> KVCache:
    if quantized:
        return KVCache(
            k=jnp.zeros((batch, cache_len, n_kv, hd), jnp.int8),
            v=jnp.zeros((batch, cache_len, n_kv, hd), jnp.int8),
            pos=jnp.full((batch, cache_len), -1, jnp.int32),
            k_scale=jnp.zeros((batch, cache_len, n_kv), jnp.float32),
            v_scale=jnp.zeros((batch, cache_len, n_kv), jnp.float32),
        )
    return KVCache(
        k=jnp.zeros((batch, cache_len, n_kv, hd), dtype),
        v=jnp.zeros((batch, cache_len, n_kv, hd), dtype),
        pos=jnp.full((batch, cache_len), -1, jnp.int32),
    )


def _q8_kv(x: Array):
    """(..., hd) -> int8 codes + per-vector scale."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _dq8_kv(q: Array, scale: Array, dtype) -> Array:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def cache_insert(cache: KVCache, k_new: Array, v_new: Array,
                 pos: Array) -> KVCache:
    """Insert one token (B, 1, KV, hd) at absolute position `pos` (scalar).

    Ring semantics: slot = pos % cache_len. Implemented as a masked write so
    SPMD keeps sequence-sharded caches local (each shard writes iff the slot
    lands in its range)."""
    S = cache.k.shape[1]
    slot = pos % S
    onehot = (jnp.arange(S) == slot)[None, :, None, None]
    if cache.k_scale is not None:
        kq, ks = _q8_kv(k_new)
        vq, vs = _q8_kv(v_new)
        k = jnp.where(onehot, kq, cache.k)
        v = jnp.where(onehot, vq, cache.v)
        ksc = jnp.where(onehot[..., 0], ks, cache.k_scale)
        vsc = jnp.where(onehot[..., 0], vs, cache.v_scale)
        p = jnp.where(onehot[..., 0, 0], pos.astype(jnp.int32), cache.pos)
        return KVCache(k, v, p, ksc, vsc)
    k = jnp.where(onehot, k_new.astype(cache.k.dtype), cache.k)
    v = jnp.where(onehot, v_new.astype(cache.v.dtype), cache.v)
    p = jnp.where(onehot[..., 0, 0], pos.astype(jnp.int32), cache.pos)
    return KVCache(k, v, p)


def cache_prefill(cache: KVCache, k: Array, v: Array) -> KVCache:
    """Write a full prefix (B, T, KV, hd) into the cache (T <= S ring-aware)."""
    B, T = k.shape[0], k.shape[1]
    S = cache.k.shape[1]
    if cache.k_scale is not None:
        kq, ks = _q8_kv(k)
        vq, vs = _q8_kv(v)
        if T <= S:
            kc = jax.lax.dynamic_update_slice(cache.k, kq, (0, 0, 0, 0))
            vc = jax.lax.dynamic_update_slice(cache.v, vq, (0, 0, 0, 0))
            ksc = jax.lax.dynamic_update_slice(cache.k_scale, ks, (0, 0, 0))
            vsc = jax.lax.dynamic_update_slice(cache.v_scale, vs, (0, 0, 0))
            pos = jnp.broadcast_to(jnp.arange(T), (B, T)).astype(jnp.int32)
            pc = jax.lax.dynamic_update_slice(cache.pos, pos, (0, 0))
            return KVCache(kc, vc, pc, ksc, vsc)
        shift = (T - S) % S
        pc = jnp.roll(jnp.broadcast_to(jnp.arange(T - S, T), (B, S))
                      .astype(jnp.int32), shift, axis=1)
        return KVCache(jnp.roll(kq[:, -S:], shift, 1),
                       jnp.roll(vq[:, -S:], shift, 1), pc,
                       jnp.roll(ks[:, -S:], shift, 1),
                       jnp.roll(vs[:, -S:], shift, 1))
    if T <= S:
        kc = jax.lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype),
                                          (0, 0, 0, 0))
        vc = jax.lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype),
                                          (0, 0, 0, 0))
        pos = jnp.broadcast_to(jnp.arange(T), (B, T)).astype(jnp.int32)
        pc = jax.lax.dynamic_update_slice(cache.pos, pos, (0, 0))
        return KVCache(kc, vc, pc)
    # ring: keep the last S positions
    kc = k[:, -S:].astype(cache.k.dtype)
    vc = v[:, -S:].astype(cache.v.dtype)
    pc = jnp.broadcast_to(jnp.arange(T - S, T), (B, S)).astype(jnp.int32)
    # rotate so that slot = pos % S
    shift = (T - S) % S
    kc = jnp.roll(kc, shift, axis=1)
    vc = jnp.roll(vc, shift, axis=1)
    pc = jnp.roll(pc, shift, axis=1)
    return KVCache(kc, vc, pc)


def decode_attend(q: Array, cache: KVCache, head_map: Array, *,
                  pos: Array, window: int = 0) -> Array:
    """q: (B, 1, Hp, hd) at absolute position `pos` (scalar int32)."""
    B = q.shape[0]
    qp = jnp.broadcast_to(pos[None], (B, 1)).astype(jnp.int32)
    valid = cache.pos >= 0
    k, v = cache.k, cache.v
    if cache.k_scale is not None:   # int8 cache: HBM streams codes
        k = _dq8_kv(k, cache.k_scale, q.dtype)
        v = _dq8_kv(v, cache.v_scale, q.dtype)
    return _dense_attention(q, k, v, head_map, causal=True,
                            window=window, q_positions=qp,
                            kv_positions=cache.pos, kv_valid=valid)


# ---------------------------------------------------------------------------
# paged KV cache (serve/kv_cache.py owns the pool + block tables; these are
# the per-layer device ops the decode scan body runs). Every op takes the
# whole layer-stacked, lane-dense pool (L, NB, BS, KV·hd) and the layer
# index, and reads or writes that layer's pages in place: the pool rides
# the layer scan's carry, so no op ever slices out or writes back a whole
# layer.
# ---------------------------------------------------------------------------

def paged_insert(k_pool: Array, v_pool: Array, k_new: Array, v_new: Array,
                 layer: Array, block_tables: Array, pos: Array):
    """Write one token per slot into layer `layer` of the paged pool.

    k_pool/v_pool: (L, NB, BS, KV·hd); k_new/v_new: (B, 1, KV, hd);
    block_tables: (B, MAXB) physical block ids; pos: (B,) absolute write
    position, -1 = inactive slot (write dropped). Slots own disjoint blocks
    so the B scattered rows never collide."""
    NB, BS = k_pool.shape[1], k_pool.shape[2]
    B = pos.shape[0]
    safe = jnp.maximum(pos, 0)
    phys = jnp.take_along_axis(block_tables, (safe // BS)[:, None],
                               axis=1)[:, 0]
    page = jnp.where(pos >= 0, phys, NB)                 # OOB -> drop
    off = safe % BS
    k_pool = k_pool.at[layer, page, off].set(
        k_new.reshape(B, -1).astype(k_pool.dtype), mode="drop")
    v_pool = v_pool.at[layer, page, off].set(
        v_new.reshape(B, -1).astype(v_pool.dtype), mode="drop")
    return k_pool, v_pool


def paged_gather(pool: Array, layer: Array, block_tables: Array,
                 n_kv: int) -> Array:
    """(L, NB, BS, KV·w) + layer + (B, MAXB) -> (B, MAXB·BS, KV, w): a
    slot's pages of that layer in logical order (row i holds position i),
    heads split out."""
    B, MAXB = block_tables.shape
    BS = pool.shape[2]
    pages = jnp.repeat(block_tables, BS, axis=1)          # (B, MAXB·BS)
    offs = jnp.tile(jnp.arange(BS, dtype=jnp.int32), MAXB)[None]
    rows = pool[layer, pages, offs]
    return rows.reshape(B, MAXB * BS, n_kv, -1)


def _check_grouped(q: Array, k_pool: Array, n_kv: int) -> None:
    """The Pallas paged kernels need grouped GQA; a non-grouped head
    layout is refused, never routed silently to the XLA gather (the
    paged runtime serves grouped archs only — launch/serve.py sends the
    rest to the static engine)."""
    H = q.shape[2]
    if H % n_kv:
        raise ValueError(
            f"paged decode kernel needs grouped GQA: q {tuple(q.shape)} has "
            f"{H} heads over k_pool {tuple(k_pool.shape)} with {n_kv} kv "
            "heads; pass mode='xla' explicitly for the gather path")


def _paged_positions(lengths: Array, S: int):
    """Key positions, their validity and the query position of a gathered
    (B, S) paged context."""
    B = lengths.shape[0]
    kpos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    valid = kpos < lengths[:, None]
    qp = jnp.maximum(lengths - 1, 0)[:, None].astype(jnp.int32)
    return kpos, valid, qp


def paged_decode_attend(q: Array, k_pool: Array, v_pool: Array,
                        layer: Array, block_tables: Array, lengths: Array,
                        head_map: Array, *, window: int = 0,
                        mode: Optional[str] = None) -> Array:
    """q: (B, 1, Hp, hd); k_pool/v_pool: (L, NB, BS, KV·hd), read at
    `layer`; lengths: (B,) valid tokens per slot (0 inactive).

    Backend dispatch mirrors kernels/ops.py: on TPU (or with an explicit
    mode="interpret") the Pallas paged kernel DMAs pages via scalar-
    prefetched layer index and block tables; the XLA path (the CPU
    default, or an explicit mode="xla") gathers the slot's pages into
    logical order and runs the same `_dense_attention` the dense decode
    path uses — so paged and dense decode agree bitwise for equal cache
    extents."""
    from repro.kernels.ops import resolve_mode
    mode = resolve_mode(mode)
    n_kv = k_pool.shape[-1] // q.shape[-1]
    if mode != "xla":
        _check_grouped(q, k_pool, n_kv)
        from repro.kernels import ops
        o = ops.paged_attention(q[:, 0], k_pool, v_pool, layer, block_tables,
                                lengths, window=window, mode=mode)
        return o[:, None].astype(q.dtype)
    kg = paged_gather(k_pool, layer, block_tables, n_kv).astype(q.dtype)
    vg = paged_gather(v_pool, layer, block_tables, n_kv).astype(q.dtype)
    kpos, valid, qp = _paged_positions(lengths, kg.shape[1])
    return _dense_attention(q, kg, vg, head_map, causal=True, window=window,
                            q_positions=qp, kv_positions=kpos,
                            kv_valid=valid)


def paged_insert_quant(k_pool: Array, v_pool: Array, k_scale: Array,
                       v_scale: Array, k_new: Array, v_new: Array,
                       layer: Array, block_tables: Array, pos: Array, *,
                       kv_bits: int):
    """Write one token per slot into layer `layer` of a *quantized* pool
    (decode append).

    k_pool/v_pool: (L, NB, BS, KV·hd/cpb) integer codes; k_scale/v_scale:
    (L, NB, KV) f32 per-(layer, page, kv_head) scales; k_new/v_new:
    (B, 1, KV, hd) float; pos: (B,), -1 = inactive (write dropped).

    The page scale is a running max: appending a token with a larger
    absmax raises the page scale, and the page's existing codes rescale
    in-register by old/new (exact identity when the scale is unchanged —
    the common case — and at most one code unit of double-rounding when it
    grows). A token landing at page offset 0 starts a fresh page: the old
    scale/codes belong to a freed request and are overwritten, not
    maxed."""
    from repro.serve.kv_cache import _kv_qmax, kv_encode, kv_scale_of
    NB, BS = k_pool.shape[1], k_pool.shape[2]
    B, KV = pos.shape[0], k_scale.shape[-1]
    qmax = _kv_qmax(kv_bits)
    safe = jnp.maximum(pos, 0)
    phys = jnp.take_along_axis(block_tables, (safe // BS)[:, None],
                               axis=1)[:, 0]
    off = safe % BS
    dest = jnp.where(pos >= 0, phys, NB)             # OOB page -> drop
    fresh = (off == 0)[:, None]                      # (B, 1)
    out = []
    for pool, scale, new in ((k_pool, k_scale, k_new),
                             (v_pool, v_scale, v_new)):
        row = new[:, 0].astype(jnp.float32)          # (B, KV, hd)
        s_tok = kv_scale_of(jnp.max(jnp.abs(row), axis=-1), kv_bits)
        old = scale[layer, phys]                     # (B, KV)
        s_new = jnp.where(fresh, s_tok, jnp.maximum(old, s_tok))
        # rescale the page's existing codes to the (possibly) raised
        # scale; ratio 0 wipes a fresh page's stale codes outright
        ratio = jnp.where(fresh | (s_new <= 0), 0.0,
                          old / jnp.where(s_new > 0, s_new, 1.0))
        page = pool[layer, phys].reshape(B, BS, KV, -1)  # (B, BS, KV, w)
        if kv_bits == 8:
            pq = page.astype(jnp.float32) * ratio[:, None, :, None]
            page2 = jnp.clip(jnp.round(pq), -qmax, qmax).astype(jnp.int8)
        else:
            from repro.core.quantizer import pack_int4, unpack_int4
            pq = (unpack_int4(page).astype(jnp.float32) - 8.0) \
                * ratio[:, None, :, None]
            pq = jnp.clip(jnp.round(pq), -qmax, qmax)
            page2 = pack_int4((pq + 8.0).astype(jnp.uint8))
        tok = kv_encode(row, s_new, kv_bits)         # (B, KV, hd/cpb)
        at_off = jnp.arange(BS)[None, :, None, None] \
            == off[:, None, None, None]
        page2 = jnp.where(at_off, tok[:, None], page2).reshape(B, BS, -1)
        out.append(pool.at[layer, dest].set(page2, mode="drop"))
        out.append(scale.at[layer, dest].set(s_new, mode="drop"))
    return tuple(out)  # (k_pool, k_scale, v_pool, v_scale)


def paged_decode_attend_quant(q: Array, k_pool: Array, v_pool: Array,
                              k_scale: Array, v_scale: Array, layer: Array,
                              block_tables: Array, lengths: Array,
                              head_map: Array, *, window: int = 0,
                              kv_bits: int = 8,
                              mode: Optional[str] = None) -> Array:
    """Quantized-pool decode attention at layer `layer`. Pallas/interpret
    streams the codes and folds the per-page scales inside the kernel; the
    XLA fallback gathers codes + per-row scales, dequantizes, and runs the
    same `_dense_attention` as the bf16 fallback — elementwise it is
    exactly the bf16 fallback applied to the dequantized pool."""
    from repro.serve.kv_cache import kv_decode
    from repro.kernels.ops import resolve_mode
    mode = resolve_mode(mode)
    n_kv = k_scale.shape[-1]
    if mode != "xla":
        _check_grouped(q, k_pool, n_kv)
        from repro.kernels import ops
        o = ops.paged_attention_quant(q[:, 0], k_pool, v_pool, k_scale,
                                      v_scale, layer, block_tables, lengths,
                                      window=window, kv_bits=kv_bits,
                                      mode=mode)
        return o[:, None].astype(q.dtype)
    BS = k_pool.shape[2]
    # per-row scales in logical order: page scale repeated over the page
    ks_rows = jnp.repeat(k_scale[layer, block_tables], BS,
                         axis=1)                     # (B, MAXB*BS, KV)
    vs_rows = jnp.repeat(v_scale[layer, block_tables], BS, axis=1)
    kg = kv_decode(paged_gather(k_pool, layer, block_tables, n_kv), ks_rows,
                   kv_bits, dtype=q.dtype)
    vg = kv_decode(paged_gather(v_pool, layer, block_tables, n_kv), vs_rows,
                   kv_bits, dtype=q.dtype)
    kpos, valid, qp = _paged_positions(lengths, kg.shape[1])
    return _dense_attention(q, kg, vg, head_map, causal=True, window=window,
                            q_positions=qp, kv_positions=kpos,
                            kv_valid=valid)
