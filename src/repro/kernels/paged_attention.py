"""Pallas TPU kernel: paged decode attention over a block-table KV pool.

One query token per slot attends over that slot's KV blocks. The pool is
the whole layer-stacked, lane-dense (layers, num_blocks, block_size,
KV·hd) array in HBM (serve/kv_cache.py); each slot owns a row of the block
table mapping logical block i -> physical block id. The grid is
(batch, blocks_per_slot) with the layer index, the block table and
per-slot lengths passed as scalar-prefetch operands, so the K/V BlockSpec
index maps read (layer, table entry) and DMA exactly the pages a slot
references straight from the stacked pool — non-contiguous pages stream
HBM->VMEM with no gather materialization and no per-layer slice of the
pool (guide: paged attention, §8-10). Online-softmax state (m, l, acc)
lives in VMEM scratch; blocks past a slot's length are skipped with
`pl.when` (zero MXU work), and an inactive slot (length 0) produces exact
zeros.

A page row holds every KV head side by side on the lanes, and a head's
segment (hd lanes, 80 for danube) need not sit on the 128-lane grid, so
the kernel never splits a row by head. GQA instead enters through q:
outside the kernel each query head's vector is placed in its KV head's
segment of an otherwise zero KV·hd row (block-diagonal q), so one
(H, KV·hd) x (KV·hd, BS) product gives every head its scores against its
own KV head; P·V accumulates whole (H, KV·hd) rows, and outside the kernel
each head keeps its KV head's segment. Requires Hp % KV == 0 (every
production config after TP head padding); a non-grouped layout is refused
with its shapes — the paged runtime serves grouped archs only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array
NEG_INF = -1e30


def _scores(q, k):
    """(H, D) x (BS, D) -> (H, BS) in f32."""
    return jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _softmax_step(s, i, length, *, bs: int, window: int, m_ref, l_ref):
    """Mask (H, bs) scores past the slot's length or window, fold them into
    the running max and sum; returns (p, corr, m_new)."""
    kpos = i * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < length
    if window > 0:   # query sits at position length-1
        mask = jnp.logical_and(mask, (length - 1) - kpos < window)
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[...]                               # (H, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    return p, corr, m_new


def _kernel(layer_ref, bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_ref,
            l_ref, acc_ref, *, bs: int, n_blocks: int, scale: float,
            window: int):
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]

    @pl.when(i * bs < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32)              # (H, KV·hd)
        k = k_ref[0].astype(jnp.float32)              # (bs, KV·hd)
        p, corr, m_new = _softmax_step(_scores(q, k) * scale, i, length,
                                       bs=bs, window=window, m_ref=m_ref,
                                       l_ref=l_ref)
        v = v_ref[0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(i == n_blocks - 1)
    def _epilogue():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _quant_kernel(layer_ref, bt_ref, len_ref, ks_ref, vs_ref, q_ref, k_ref,
                  v_ref, o_ref, m_ref, l_ref, acc_ref, *, bs: int,
                  n_blocks: int, scale: float, window: int, n_kv: int,
                  group: int, kv_bits: int):
    """Quantized-pool variant: k_ref/v_ref stream integer codes (int8, or
    4-bit nibbles in the planar layout of `kv_cache.kv_encode`) and the
    per-(page, kv_head) scales arrive as extra scalar-prefetch operands.
    q, the accumulator and the output are split into the same `cpb`
    head-dim planes as the codes (plane f = dims [f·hd/cpb, (f+1)·hd/cpb)
    of every head), so each code plane widens with a shift and a mask and
    meets its own q plane — no lane interleave. The scales fold into the
    online-softmax inputs (scores) and the PV accumulation — K/V never
    materialize dequantized in HBM."""
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
        """(bs, KV·hd/cpb) codes -> cpb f32 planes of signed code values
        (uint8/int8 widen through int32, the cast the TPU lowering takes)."""
        c = codes.astype(jnp.int32)
        if kv_bits == 8:
            return [c.astype(jnp.float32)]
        return [(c & 0x0F).astype(jnp.float32) - 8.0,
                ((c >> 4) & 0x0F).astype(jnp.float32) - 8.0]

    def per_head(ref):
        """(H, 1): each query head's KV head's SMEM scale for this block
        (scalar selects — SMEM scalars do not stack into a vector)."""
        base = (b * n_blocks + i) * n_kv
        row = jax.lax.broadcasted_iota(jnp.int32, (H, 1), 0)
        out = jnp.zeros((H, 1), jnp.float32)
        for j in range(n_kv):
            own = jnp.logical_and(row >= j * group, row < (j + 1) * group)
            out = jnp.where(own, ref[base + j], out)
        return out

    @pl.when(i * bs < length)
    def _compute():
        s = None
        for f, k in enumerate(planes(k_ref[0])):      # (bs, KV·hd/cpb)
            sf = _scores(q_ref[0, f].astype(jnp.float32), k)
            s = sf if s is None else s + sf
        s = s * (scale * per_head(ks_ref))
        p, corr, m_new = _softmax_step(s, i, length, bs=bs, window=window,
                                       m_ref=m_ref, l_ref=l_ref)
        vs = per_head(vs_ref)
        for f, v in enumerate(planes(v_ref[0])):
            pv = jnp.dot(p, v, preferred_element_type=jnp.float32) * vs
            acc_ref[f] = acc_ref[f] * corr + pv
        m_ref[...] = m_new

    @pl.when(i == n_blocks - 1)
    def _epilogue():
        l = jnp.maximum(l_ref[...], 1e-20)
        for f in range(cpb):
            o_ref[0, f] = (acc_ref[f] / l).astype(o_ref.dtype)


def _layer_operand(layer) -> Array:
    """The layer index as the (1,) int32 scalar-prefetch operand."""
    return jnp.asarray(layer, jnp.int32).reshape(1)


def _kv_of_head(H: int, n_kv: int) -> Array:
    """(H, KV) one-hot: query head h reads KV head h // (H / KV)."""
    if H % n_kv:
        raise ValueError(f"pallas paged kernel needs grouped GQA, got "
                         f"{H} query heads over {n_kv} kv heads")
    return (jnp.arange(H)[:, None] // (H // n_kv)
            == jnp.arange(n_kv)[None, :])


def _block_diagonal(q: Array, n_kv: int) -> Array:
    """(..., H, w) -> (..., H, KV·w): head h's vector in its KV head's
    segment, zeros elsewhere (exact: a product with 1 or 0)."""
    H, w = q.shape[-2], q.shape[-1]
    own = _kv_of_head(H, n_kv).astype(q.dtype)[:, :, None]
    return (q[..., :, None, :] * own).reshape(*q.shape[:-2], H, n_kv * w)


def _own_segment(o: Array, n_kv: int) -> Array:
    """(..., H, KV·w) -> (..., H, w): each head's KV head's segment."""
    H = o.shape[-2]
    kv = jnp.arange(H) // (H // n_kv)                 # (H,)
    o = o.reshape(*o.shape[:-1], n_kv, -1)            # (..., H, KV, w)
    idx = jnp.broadcast_to(kv[:, None, None], (*o.shape[:-2], 1, 1))
    return jnp.take_along_axis(o, idx, axis=-2)[..., 0, :]


def paged_attention_quant_pallas(q: Array, k_pool: Array, v_pool: Array,
                                 k_scale: Array, v_scale: Array, layer,
                                 block_tables: Array, lengths: Array, *,
                                 window: int = 0, kv_bits: int = 8,
                                 interpret: bool = False) -> Array:
    """Quantized-pool paged attention: k_pool/v_pool (L, NB, BS, KV·hd/cpb)
    integer codes, k_scale/v_scale (L, NB, KV) f32 per-page scales;
    `layer` picks the layer the call reads. Only the scales of the pages
    each slot's block table names in that layer ride as the flat
    scalar-prefetch operands 4/5 (B·MAXB·KV words: SMEM holds a batch's
    worth, not the pool's). Same grid/softmax structure as the bf16
    kernel; q enters split into cpb head-dim planes, block-diagonal over
    the KV heads, and the output leaves as planes (reshapes outside the
    kernel). Returns (B, Hp, hd) in q.dtype."""
    B, H, hd = q.shape
    BS, D = k_pool.shape[2], k_pool.shape[3]
    KV = k_scale.shape[-1]
    MAXB = block_tables.shape[1]
    if kv_bits not in (4, 8):
        raise ValueError(f"kv_bits must be 4 or 8, got {kv_bits}")
    cpb = 1 if kv_bits == 8 else 2
    if D * cpb != KV * hd:
        raise ValueError(f"pool rows of {D} codes do not hold {KV} kv heads "
                         f"of head_dim {hd} at {kv_bits} bits")
    group = H // KV
    scale = 1.0 / float(hd) ** 0.5
    from jax.experimental.pallas import tpu as pltpu
    qp = q.reshape(B, H, cpb, hd // cpb).transpose(0, 2, 1, 3)
    qp = _block_diagonal(qp, KV)                      # (B, cpb, H, D)
    layer = _layer_operand(layer)

    def page(b, i, l, bt, ln, ks, vs):
        return (l[0], bt[b, i], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B, MAXB),
        in_specs=[
            pl.BlockSpec((1, cpb, H, D),
                         lambda b, i, l, bt, ln, ks, vs: (b, 0, 0, 0)),
            pl.BlockSpec((pl.squeezed, 1, BS, D), page),
            pl.BlockSpec((pl.squeezed, 1, BS, D), page),
        ],
        out_specs=pl.BlockSpec((1, cpb, H, D),
                               lambda b, i, l, bt, ln, ks, vs: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((cpb, H, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_quant_kernel, bs=BS, n_blocks=MAXB, scale=scale,
                          window=window, n_kv=KV, group=group,
                          kv_bits=kv_bits),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, cpb, H, D), q.dtype),
        name="paged_attention_quant",
        interpret=interpret,
    )(layer, block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      k_scale[layer[0], block_tables].astype(jnp.float32).reshape(-1),
      v_scale[layer[0], block_tables].astype(jnp.float32).reshape(-1),
      qp, k_pool, v_pool)
    out = _own_segment(out, KV)                       # (B, cpb, H, hd/cpb)
    return out.transpose(0, 2, 1, 3).reshape(B, H, hd)


def paged_attention_pallas(q: Array, k_pool: Array, v_pool: Array, layer,
                           block_tables: Array, lengths: Array, *,
                           window: int = 0,
                           interpret: bool = False) -> Array:
    """q: (B, Hp, hd); k_pool/v_pool: (L, NB, BS, KV·hd) layer-stacked,
    lane-dense pages; layer: the layer to read (int32 scalar);
    block_tables: (B, MAXB) int32 physical block ids; lengths: (B,) valid
    tokens per slot (0 = inactive -> zero output). Returns (B, Hp, hd) in
    q.dtype."""
    B, H, hd = q.shape
    BS, D = k_pool.shape[2], k_pool.shape[3]
    MAXB = block_tables.shape[1]
    if D % hd:
        raise ValueError(f"pool rows of {D} lanes do not hold whole heads "
                         f"of head_dim {hd}")
    KV = D // hd
    scale = 1.0 / float(hd) ** 0.5
    from jax.experimental.pallas import tpu as pltpu

    def page(b, i, l, bt, ln):
        return (l[0], bt[b, i], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, MAXB),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, i, l, bt, ln: (b, 0, 0)),
            pl.BlockSpec((pl.squeezed, 1, BS, D), page),
            pl.BlockSpec((pl.squeezed, 1, BS, D), page),
        ],
        out_specs=pl.BlockSpec((1, H, D),
                               lambda b, i, l, bt, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bs=BS, n_blocks=MAXB, scale=scale,
                          window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        name="paged_attention",
        interpret=interpret,
    )(_layer_operand(layer), block_tables.astype(jnp.int32),
      lengths.astype(jnp.int32), _block_diagonal(q, KV), k_pool, v_pool)
    return _own_segment(out, KV)
