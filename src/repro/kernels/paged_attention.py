"""Pallas TPU kernel: paged decode attention over a block-table KV pool.

One query token per slot attends over that slot's KV blocks. The pool is
(num_blocks, block_size, KV, hd) in HBM; each slot owns a row of the block
table mapping logical block i -> physical block id. The grid is
(batch, blocks_per_slot) with the block table and per-slot lengths passed
as scalar-prefetch operands, so the K/V BlockSpec index maps read the
table and DMA exactly the pages a slot references — non-contiguous pages
stream HBM->VMEM with no gather materialization (guide: paged attention,
§8-10). Online-softmax state (m, l, acc) lives in VMEM scratch; blocks
past a slot's length are skipped with `pl.when` (zero MXU work), and an
inactive slot (length 0) produces exact zeros.

GQA is expressed by reshaping q to (KV, G, hd) — requires Hp % KV == 0
(every production config after TP head padding). A non-grouped layout is
refused with its shapes; the paged runtime serves grouped archs only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array
NEG_INF = -1e30


def _kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
            acc_ref, *, bs: int, n_blocks: int, scale: float, window: int,
            n_kv: int, group: int):
    b = pl.program_id(0)
    i = pl.program_id(1)
    H = n_kv * group

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]

    @pl.when(i * bs < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32).reshape(n_kv, group, -1)
        k = k_ref[0].astype(jnp.float32)              # (bs, KV, hd)
        s = jnp.einsum("kgh,skh->kgs", q, k,
                       preferred_element_type=jnp.float32) * scale
        kpos = i * bs + jax.lax.broadcasted_iota(jnp.int32,
                                                 (n_kv, group, bs), 2)
        mask = kpos < length
        if window > 0:   # query sits at position length-1
            mask = jnp.logical_and(mask, (length - 1) - kpos < window)
        s = jnp.where(mask, s, NEG_INF).reshape(H, bs)
        m_prev = m_ref[...]                           # (H, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        pv = jnp.einsum("kgs,skh->kgh", p.reshape(n_kv, group, bs), v,
                        preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv.reshape(H, -1)
        m_ref[...] = m_new

    @pl.when(i == n_blocks - 1)
    def _epilogue():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _quant_kernel(bt_ref, len_ref, ks_ref, vs_ref, q_ref, k_ref, v_ref,
                  o_ref, m_ref, l_ref, acc_ref, *, bs: int, n_blocks: int,
                  scale: float, window: int, n_kv: int, group: int,
                  kv_bits: int):
    """Quantized-pool variant: k_ref/v_ref stream integer codes (int8, or
    4-bit nibbles in the planar layout of `kv_cache.kv_encode`) and the
    per-(page, kv_head) scales arrive as extra scalar-prefetch operands.
    q, the accumulator and the output are split into the same `cpb`
    head-dim planes as the codes (plane f = dims [f·hd/cpb, (f+1)·hd/cpb)),
    so each code plane widens with a shift and a mask and meets its own
    q plane — no lane interleave. The scales fold into the online-softmax
    inputs (scores) and the PV accumulation — K/V never materialize
    dequantized in HBM."""
    b = pl.program_id(0)
    i = pl.program_id(1)
    H = n_kv * group
    cpb = 1 if kv_bits == 8 else 2

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]

    def planes(codes):
        """(bs, KV, hd/cpb) codes -> cpb f32 planes of signed code values
        (uint8/int8 widen through int32, the cast the TPU lowering takes)."""
        c = codes.astype(jnp.int32)
        if kv_bits == 8:
            return [c.astype(jnp.float32)]
        return [(c & 0x0F).astype(jnp.float32) - 8.0,
                ((c >> 4) & 0x0F).astype(jnp.float32) - 8.0]

    def per_head(ref, shape):
        """This block's n_kv SMEM scales broadcast along axis 0 of `shape`
        (scalar selects — SMEM scalars do not stack into a vector)."""
        base = (b * n_blocks + i) * n_kv
        idx = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        out = jnp.zeros(shape, jnp.float32)
        for j in range(n_kv):
            out = jnp.where(idx == j, ref[base + j], out)
        return out

    @pl.when(i * bs < length)
    def _compute():
        s = None
        for f, k in enumerate(planes(k_ref[0])):      # (bs, KV, hd/cpb)
            q = q_ref[0, f].astype(jnp.float32).reshape(n_kv, group, -1)
            sf = jnp.einsum("kgh,skh->kgs", q, k,
                            preferred_element_type=jnp.float32)
            s = sf if s is None else s + sf
        s = s * (scale * per_head(ks_ref, (n_kv, group, bs)))
        kpos = i * bs + jax.lax.broadcasted_iota(jnp.int32,
                                                 (n_kv, group, bs), 2)
        mask = kpos < length
        if window > 0:   # query sits at position length-1
            mask = jnp.logical_and(mask, (length - 1) - kpos < window)
        s = jnp.where(mask, s, NEG_INF).reshape(H, bs)
        m_prev = m_ref[...]                           # (H, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        p = p.reshape(n_kv, group, bs)
        for f, v in enumerate(planes(v_ref[0])):
            pv = jnp.einsum("kgs,skh->kgh", p, v,
                            preferred_element_type=jnp.float32)
            pv = pv * per_head(vs_ref, pv.shape)
            acc_ref[f] = acc_ref[f] * corr + pv.reshape(H, -1)
        m_ref[...] = m_new

    @pl.when(i == n_blocks - 1)
    def _epilogue():
        l = jnp.maximum(l_ref[...], 1e-20)
        for f in range(cpb):
            o_ref[0, f] = (acc_ref[f] / l).astype(o_ref.dtype)


def paged_attention_quant_pallas(q: Array, k_pool: Array, v_pool: Array,
                                 k_scale: Array, v_scale: Array,
                                 block_tables: Array, lengths: Array, *,
                                 window: int = 0, kv_bits: int = 8,
                                 interpret: bool = False) -> Array:
    """Quantized-pool paged attention: k_pool/v_pool (NB, BS, KV, hd/cpb)
    integer codes, k_scale/v_scale (NB, KV) f32 per-page scales. Only the
    scales of the pages each slot's block table names ride as the flat
    scalar-prefetch operands 3/4 (B·MAXB·KV words: SMEM holds a batch's
    worth, not the pool's). Same grid/softmax structure as the bf16
    kernel; q enters and the output leaves split into cpb head-dim planes
    (a reshape/transpose outside the kernel). Returns (B, Hp, hd) in
    q.dtype."""
    B, H, hd = q.shape
    NB, BS, KV, hdp = k_pool.shape
    MAXB = block_tables.shape[1]
    if H % KV:
        raise ValueError(f"pallas paged kernel needs grouped GQA, got "
                         f"{H} query heads over {KV} kv heads")
    if kv_bits not in (4, 8):
        raise ValueError(f"kv_bits must be 4 or 8, got {kv_bits}")
    cpb = hd // hdp
    group = H // KV
    scale = 1.0 / float(hd) ** 0.5
    from jax.experimental.pallas import tpu as pltpu
    qp = q.reshape(B, H, cpb, hdp).transpose(0, 2, 1, 3)  # (B, cpb, H, hdp)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, MAXB),
        in_specs=[
            pl.BlockSpec((1, cpb, H, hdp),
                         lambda b, i, bt, ln, ks, vs: (b, 0, 0, 0)),
            pl.BlockSpec((1, BS, KV, hdp),
                         lambda b, i, bt, ln, ks, vs: (bt[b, i], 0, 0, 0)),
            pl.BlockSpec((1, BS, KV, hdp),
                         lambda b, i, bt, ln, ks, vs: (bt[b, i], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, cpb, H, hdp),
                               lambda b, i, bt, ln, ks, vs: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((cpb, H, hdp), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_quant_kernel, bs=BS, n_blocks=MAXB, scale=scale,
                          window=window, n_kv=KV, group=group,
                          kv_bits=kv_bits),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, cpb, H, hdp), q.dtype),
        name="paged_attention_quant",
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      k_scale[block_tables].astype(jnp.float32).reshape(-1),
      v_scale[block_tables].astype(jnp.float32).reshape(-1),
      qp, k_pool, v_pool)
    return out.transpose(0, 2, 1, 3).reshape(B, H, hd)


def paged_attention_pallas(q: Array, k_pool: Array, v_pool: Array,
                           block_tables: Array, lengths: Array, *,
                           window: int = 0,
                           interpret: bool = False) -> Array:
    """q: (B, Hp, hd); k_pool/v_pool: (NB, BS, KV, hd); block_tables:
    (B, MAXB) int32 physical block ids; lengths: (B,) valid tokens per slot
    (0 = inactive -> zero output). Returns (B, Hp, hd) in q.dtype."""
    B, H, hd = q.shape
    NB, BS, KV, _ = k_pool.shape
    MAXB = block_tables.shape[1]
    if H % KV:
        raise ValueError(f"pallas paged kernel needs grouped GQA, got "
                         f"{H} query heads over {KV} kv heads")
    group = H // KV
    scale = 1.0 / float(hd) ** 0.5
    from jax.experimental.pallas import tpu as pltpu

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, MAXB),
        in_specs=[
            pl.BlockSpec((1, H, hd), lambda b, i, bt, ln: (b, 0, 0)),
            pl.BlockSpec((1, BS, KV, hd),
                         lambda b, i, bt, ln: (bt[b, i], 0, 0, 0)),
            pl.BlockSpec((1, BS, KV, hd),
                         lambda b, i, bt, ln: (bt[b, i], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, hd), lambda b, i, bt, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, bs=BS, n_blocks=MAXB, scale=scale,
                          window=window, n_kv=KV, group=group),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        name="paged_attention",
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q, k_pool, v_pool)
