"""Paged KV-cache pool: fixed-size pages + per-slot block tables.

The dense engine allocates a (B, max_len) cache per slot — every slot pays
for the longest possible sequence. The paged pool instead owns
`num_blocks` pages of `block_size` tokens shared by all slots; a slot maps
logical block i -> physical page via its block-table row, pages are
allocated at admission and freed at completion, and attention walks the
table (kernels/paged_attention.py Pallas kernel on TPU, gather fallback on
XLA — models/attention.paged_decode_attend). Memory scales with the
*live* tokens, not max_slots x max_len.

Device layout (models/model.decode_step_paged carries the whole pool
through its layer scan and each layer reads and writes its own pages in
place):

    pool["k"], pool["v"]: (L, num_blocks, block_size, KV·hd)   lane-dense
    block_tables:         (max_slots, max_blocks_per_slot) int32
    pos:                  (max_slots,) absolute next position, -1 inactive

A page row holds the token's KV heads side by side (head j on lanes
[j·hd, (j+1)·hd)). Lane-dense rows are what lets the pool stay in place:
a TPU lays out an array whose minor dim is a multiple of 128 row-major,
the layout the paged attention kernels read, while a (…, KV, hd) pool
with head_dim off the lane grid (danube's 80) gets the page-id dim
minor-most and every program would relayout the whole pool around the
kernel (DESIGN.md §11.4).

`BlockAllocator` is plain host state (the scheduler thread owns it); the
jitted `write_prefill` scatters a prefilled dense cache's rows into the
slot's pages (ring-aware: rows route by their absolute `pos`, so SWA
prefill caches land on the right pages).
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

Array = jax.Array


def blocks_for(tokens: int, block_size: int) -> int:
    """Pages needed to hold `tokens` positions."""
    return max(1, math.ceil(tokens / block_size))


# Quantized pages (DESIGN.md §11): integer codes + one f32 scale per
# (layer, page, kv_head). int8 is symmetric absmax/127; 4-bit packs two
# offset-binary nibbles per byte (code = q + 8, q in [-7, 7]) with a
# clip-aware scale shrink — at 4 bits the absmax code wastes range on the
# single largest row entry, and clipping the tail slightly beats pure
# absmax (arXiv 2510.04044 range-estimation discipline).
KV4_CLIP = 0.96


def _kv_qmax(kv_bits: int) -> float:
    return 127.0 if kv_bits == 8 else 7.0


def kv_code_width(kv_bits: int) -> int:
    """Codes per byte of pool storage (1 for int8, 2 for packed 4-bit)."""
    if kv_bits not in (4, 8):
        raise ValueError(f"kv_bits must be 4 or 8, got {kv_bits}")
    return 1 if kv_bits == 8 else 2


def kv_scale_of(absmax: Array, kv_bits: int) -> Array:
    """Per-(page, kv_head) scale from the page's row absmax."""
    clip = 1.0 if kv_bits == 8 else KV4_CLIP
    return (clip / _kv_qmax(kv_bits)) * absmax.astype(jnp.float32)


def kv_encode(rows: Array, scale: Array, kv_bits: int) -> Array:
    """rows (..., hd) float -> integer codes under `scale` (broadcast over
    hd). Zero scale (all-zero page) encodes to zero codes exactly."""
    qmax = _kv_qmax(kv_bits)
    s = scale.astype(jnp.float32)[..., None]
    q = jnp.where(s > 0, rows.astype(jnp.float32) / jnp.where(s > 0, s, 1.0),
                  0.0)
    q = jnp.clip(jnp.round(q), -qmax, qmax)
    if kv_bits == 8:
        return q.astype(jnp.int8)
    from repro.core.quantizer import pack_int4  # function-level: no cycle
    return pack_int4((q + 8.0).astype(jnp.uint8))


def kv_decode(codes: Array, scale: Array, kv_bits: int,
              dtype=jnp.float32) -> Array:
    """Inverse of kv_encode: codes (..., hd / cpb) -> (..., hd) floats."""
    if kv_bits == 8:
        q = codes.astype(jnp.float32)
    else:
        from repro.core.quantizer import unpack_int4  # no import cycle
        q = unpack_int4(codes).astype(jnp.float32) - 8.0
    return (q * scale.astype(jnp.float32)[..., None]).astype(dtype)


def init_paged_cache(cfg, plan, num_blocks: int,
                     block_size: int) -> Dict[str, Array]:
    """Zeroed K/V page pools, stacked over layers for the decode scan, one
    lane-dense row of KV·hd values (or codes) per token. With
    `plan.kv_bits` in {4, 8} pages hold integer codes plus per-
    (layer, page, kv_head) f32 scales under "k_scale"/"v_scale"."""
    hd = cfg.resolved_head_dim
    kv_bits = int(getattr(plan, "kv_bits", 0) or 0)
    if not kv_bits:
        shape = (cfg.n_layers, num_blocks, block_size, cfg.n_kv_heads * hd)
        return {"k": jnp.zeros(shape, plan.cache_dtype),
                "v": jnp.zeros(shape, plan.cache_dtype)}
    cpb = kv_code_width(kv_bits)
    if hd % cpb:
        raise ValueError(f"kv_bits={kv_bits} needs head_dim % {cpb} == 0")
    dt = jnp.int8 if kv_bits == 8 else jnp.uint8
    shape = (cfg.n_layers, num_blocks, block_size,
             cfg.n_kv_heads * (hd // cpb))
    sshape = (cfg.n_layers, num_blocks, cfg.n_kv_heads)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt),
            "k_scale": jnp.zeros(sshape, jnp.float32),
            "v_scale": jnp.zeros(sshape, jnp.float32)}


def paged_cache_bytes(cfg, plan, num_blocks: int, block_size: int) -> int:
    """Device bytes the pool holds: code (or bf16) payload plus, when
    quantized, the per-(layer, page, kv_head) f32 scale tensors."""
    hd = cfg.resolved_head_dim
    kv_bits = int(getattr(plan, "kv_bits", 0) or 0)
    if not kv_bits:
        itemsize = jnp.dtype(plan.cache_dtype).itemsize
        return 2 * cfg.n_layers * num_blocks * block_size * cfg.n_kv_heads \
            * hd * itemsize
    payload = 2 * cfg.n_layers * num_blocks * block_size * cfg.n_kv_heads \
        * (hd // kv_code_width(kv_bits))
    scales = 2 * cfg.n_layers * num_blocks * cfg.n_kv_heads * 4
    return payload + scales


class BlockAllocator:
    """Host-side free list over the physical pages. No device state: the
    pool itself never moves — allocation only decides which page ids a
    slot's block-table row points at.

    `fail_hook` is the fault-injection seam (ft/inject.py): when set and it
    returns True, alloc reports exhaustion even with pages free —
    exercising the backpressure/preemption paths deterministically.

    `partitions` > 1 splits the pool into contiguous equal ranges for the
    TP-sharded runtime: partition p owns pages [p*npp, (p+1)*npp), which
    is exactly shard p's slice of the page-dim-sharded device pool, so a
    slot pinned to partition p only ever references device-local pages
    (dist/sharding.py `paged_pool_specs`; 0-collective decode)."""

    def __init__(self, num_blocks: int,
                 fail_hook: Optional[Callable[[], bool]] = None,
                 partitions: int = 1):
        if partitions < 1 or num_blocks % partitions:
            raise ValueError(f"num_blocks={num_blocks} must split evenly "
                             f"over {partitions} partitions")
        self.num_blocks = num_blocks
        self.partitions = partitions
        self.partition_blocks = num_blocks // partitions
        npp = self.partition_blocks
        # LIFO within each partition, matching the single-partition order
        self._frees: List[List[int]] = [
            list(range((p + 1) * npp - 1, p * npp - 1, -1))
            for p in range(partitions)]
        self._held: set = set()
        self.peak_in_use = 0
        self.fail_hook = fail_hook

    @property
    def num_free(self) -> int:
        return sum(len(f) for f in self._frees)

    def num_free_in(self, part: int) -> int:
        return len(self._frees[part])

    @property
    def in_use(self) -> int:
        return self.num_blocks - self.num_free

    def alloc(self, n: int, part: int = 0) -> Optional[List[int]]:
        """n pages from `part`, or None when the partition is exhausted
        (admission backpressure / preemption trigger) or when the
        injected fault hook fires."""
        if self.fail_hook is not None and self.fail_hook():
            return None
        free = self._frees[part]
        if n > len(free):
            return None
        out = [free.pop() for _ in range(n)]
        self._held.update(out)
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        return out

    def partition_of(self, block: int) -> int:
        return block // self.partition_blocks

    def free(self, blocks: List[int]) -> None:
        for b in blocks:
            if b < 0 or b >= self.num_blocks:
                raise ValueError(f"freeing unknown block {b}")
            if b not in self._held:
                raise ValueError(f"double free of block {b}")
        for b in blocks:
            self._held.discard(b)
            self._frees[self.partition_of(b)].append(b)

    def check_integrity(self) -> None:
        """Free list and held set must exactly partition the pool — the
        no-leak/no-double-free oracle the fault tests assert after every
        injected failure."""
        free = set()
        for p, fl in enumerate(self._frees):
            if len(set(fl)) != len(fl):
                raise AssertionError("duplicate page ids on the free list")
            for b in fl:
                if self.partition_of(b) != p:
                    raise AssertionError(
                        f"page {b} on partition {p}'s free list")
            free.update(fl)
        if free & self._held:
            raise AssertionError(
                f"pages both free and held: {sorted(free & self._held)}")
        if len(free) + len(self._held) != self.num_blocks:
            missing = set(range(self.num_blocks)) - free - self._held
            raise AssertionError(f"leaked pages: {sorted(missing)}")


def write_prefill(pool: Dict[str, Array], k_seq: Array, v_seq: Array,
                  pos_row: Array, table_row: Array,
                  kv_bits: int = 0) -> Dict[str, Array]:
    """Scatter one request's prefilled K/V rows into its pages.

    k_seq/v_seq: (L, S, KV, hd) from the dense prefill cache, written as
    lane-dense KV·hd rows; pos_row: (S,) absolute positions (-1 = unwritten
    row, dropped); table_row: (MAXB,) physical page ids. Rows route by position — block pos//BS, offset
    pos%BS — so ring-buffer (SWA) prefill caches scatter correctly.

    With `kv_bits` set the rows quantize on the way in: every touched page
    gets a fresh scale from a scatter-max of its incoming row absmaxes
    (prefill owns all live rows of its pages, so overwriting the page
    scale is exact and also wipes any stale scale left by a freed
    request), then rows encode at their page's scale and the codes
    scatter. Untouched pages keep code and scale bits untouched."""
    k_pool, v_pool = pool["k"], pool["v"]
    L, NB, BS = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    safe = jnp.maximum(pos_row, 0)
    valid = pos_row >= 0
    phys = table_row[safe // BS]
    dest = jnp.where(valid, phys * BS + safe % BS, NB * BS)
    S = pos_row.shape[0]
    # every (layer, row) pair is its own scatter index, so the scatter's
    # window is one lane-dense row and the pool keeps its row-major layout
    at = (jnp.arange(L)[:, None], dest[None, :])
    if not kv_bits:
        kf = k_pool.reshape(L, NB * BS, k_pool.shape[3])
        vf = v_pool.reshape(L, NB * BS, v_pool.shape[3])
        kf = kf.at[at].set(k_seq.reshape(L, S, -1).astype(kf.dtype),
                           mode="drop")
        vf = vf.at[at].set(v_seq.reshape(L, S, -1).astype(vf.dtype),
                           mode="drop")
        return {"k": kf.reshape(k_pool.shape), "v": vf.reshape(v_pool.shape)}

    page = jnp.where(valid, phys, NB)           # OOB sentinel -> drop
    touched = jnp.zeros((NB,), bool).at[page].set(True, mode="drop")
    out = {}
    for name, cpool, rows in (("k", k_pool, k_seq), ("v", v_pool, v_seq)):
        KV = rows.shape[2]
        absmax = jnp.max(jnp.abs(rows.astype(jnp.float32)), axis=-1)
        absmax = jnp.where(valid[None, :, None], absmax, 0.0)   # (L, S, KV)
        pmax = jnp.zeros((L, NB, KV), jnp.float32) \
            .at[:, page].max(absmax, mode="drop")
        new_scale = jnp.where(touched[None, :, None],
                              kv_scale_of(pmax, kv_bits),
                              pool[name + "_scale"])
        codes = kv_encode(rows, new_scale[:, page], kv_bits)
        cf = cpool.reshape(L, NB * BS, cpool.shape[3])
        cf = cf.at[at].set(codes.reshape(L, S, -1), mode="drop")
        out[name] = cf.reshape(cpool.shape)
        out[name + "_scale"] = new_scale
    return out
