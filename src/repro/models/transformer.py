"""Decoder/encoder stack assembly.

All homogeneous stacks are `lax.scan` over layer-stacked params (HLO size is
depth-independent). The VLM stack (llama-3.2-vision) scans over *groups* of
(`every`-1 self layers + 1 gated cross-attn layer), with an inner scan over
the self layers — params are stacked (G, every-1, ...) and (G, ...).

A `BuildPlan` carries mesh-derived static facts (TP padding) and an optional
`constrain(x, kind)` callback used by the launcher to pin intermediate
shardings (residual stream, logits, caches) without the model importing any
mesh code.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.models.attention import (KVCache, cache_insert, cache_prefill,
                                    decode_attend, flash_attention,
                                    head_to_kv_map, init_kv_cache,
                                    out_project, paged_decode_attend,
                                    paged_insert, qkv_project)
from repro.models.common import (Array, apply_norm, apply_rope, dense_init,
                                 norm_params, pad_to_multiple, zeros_init)


def _ident_constrain(x, kind):
    return x


@dataclass(frozen=True)
class BuildPlan:
    tp: int = 1
    attn_block_size: int = 512
    moe_token_chunk: int = 4096
    # round MoE routing capacity up to this multiple: quantize_model sets
    # it to the mesh "data" axis so (E, C, d) expert taps always divide it
    # and calibration Grams stay on the psum path (dist.calibrate)
    moe_capacity_multiple: int = 1
    remat: bool = True
    cache_dtype: Any = jnp.bfloat16
    cache_quant: bool = False    # int8 KV cache (per-entry absmax scales)
    # paged-pool KV quantization (serve runtime): 0 = bf16 pages, 8/4 =
    # integer codes with per-(layer, page, kv_head) scales (DESIGN.md §11)
    kv_bits: int = 0
    # prefill cache capacity (0 -> prompt length); serving engines set
    # prompt+max_new so decode can continue without ring eviction
    prefill_cache_len: int = 0
    # paged decode attention dispatch (kernels/ops.resolve_mode): None =
    # the backend's default (Pallas on TPU); "xla" / "interpret" only by
    # explicit choice — the on-chip oracle comparison and CPU tests
    kernel_mode: Optional[str] = None
    constrain: Callable[[Array, str], Array] = _ident_constrain

    def heads_padded(self, cfg) -> int:
        return pad_to_multiple(cfg.n_heads, self.tp)

    def experts_padded(self, cfg) -> int:
        if cfg.moe is None:
            return 0
        return pad_to_multiple(cfg.moe.n_experts, self.tp)

    def vocab_padded(self, cfg) -> int:
        """Vocab rows padded so TP sharding divides (and int8-moment blocks
        align); padded logit columns are masked to -inf in unembed()."""
        if self.tp <= 1:
            return cfg.vocab_size
        return pad_to_multiple(cfg.vocab_size, 256)

    def replace(self, **kw) -> "BuildPlan":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# per-layer init
# ---------------------------------------------------------------------------

def init_layer(key: Array, cfg, plan: BuildPlan, stack=()) -> dict:
    ks = jax.random.split(key, 6)
    hp = plan.heads_padded(cfg)
    p: Dict[str, Any] = {"ln1": norm_params(ks[0], cfg, stack)}
    if cfg.attn_free:   # rwkv6
        p["tm"] = rwkv_mod.init_time_mix(ks[1], cfg, stack)
        p["ln2"] = norm_params(ks[2], cfg, stack)
        p["cm"] = rwkv_mod.init_channel_mix(ks[3], cfg, stack)
        return p
    p["attn"] = attn_mod.init_attn(ks[1], cfg, hp, stack)
    if cfg.parallel_ssm_heads:
        p["ssm"] = ssm_mod.init_ssm(ks[2], cfg, stack)
    p["ln2"] = norm_params(ks[3], cfg, stack)
    if cfg.moe is not None:
        p["moe"] = moe_mod.init_moe(ks[4], cfg, plan.experts_padded(cfg), stack)
    else:
        p["mlp"] = mlp_mod.init_mlp(ks[4], cfg, stack)
    return p


def init_cross_layer(key: Array, cfg, plan: BuildPlan, stack=()) -> dict:
    ks = jax.random.split(key, 6)
    hp = plan.heads_padded(cfg)
    return {
        "ln1": norm_params(ks[0], cfg, stack),
        "xattn": attn_mod.init_attn(ks[1], cfg, hp, stack, kv_in=cfg.d_model),
        "gate_attn": zeros_init(ks[2], (*stack,)),
        "ln2": norm_params(ks[3], cfg, stack),
        "mlp": mlp_mod.init_mlp(ks[4], cfg, stack),
        "gate_mlp": zeros_init(ks[5], (*stack,)),
    }


# ---------------------------------------------------------------------------
# layer application (full-sequence: train / prefill)
# ---------------------------------------------------------------------------

def _self_attention_full(p, x, cfg, plan, make_cache: bool, taps=None,
                         quantize_cb=None):
    hp = plan.heads_padded(cfg)
    hmap = head_to_kv_map(cfg.n_heads, hp, cfg.n_kv_heads)
    ap = p["attn"]
    if taps is not None:
        taps["attn_in"] = x                   # feeds wq / wk / wv
        if quantize_cb is not None:
            ap = {**ap, **quantize_cb("attn_in")}
    q, k, v = qkv_project(ap, x)
    if cfg.causal:
        B, T = x.shape[:2]
        pos = jnp.broadcast_to(jnp.arange(T), (B, T))
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    o = flash_attention(q, k, v, hmap, causal=cfg.causal,
                        window=cfg.sliding_window,
                        block_size=plan.attn_block_size)
    if taps is not None:
        taps["wo_in"] = o.reshape(*o.shape[:2], -1)   # feeds wo (Hp*hd, d)
        if quantize_cb is not None:
            ap = {**ap, **quantize_cb("wo_in")}
    cache = None
    if make_cache:
        B, T = x.shape[:2]
        # SWA: allocate at least the full window so decode can continue
        # past the prompt without evicting in-window entries. A larger
        # prefill_cache_len (serving runtimes pad prompts to bucket
        # lengths) also wins: right-pad rows must never ring-evict real
        # in-window rows before the paged scatter drops them.
        if cfg.sliding_window:
            clen = max(cfg.sliding_window, plan.prefill_cache_len)
        else:
            clen = max(plan.prefill_cache_len, T)
        cache = init_kv_cache(B, clen, cfg.n_kv_heads,
                              cfg.resolved_head_dim, plan.cache_dtype,
                              quantized=plan.cache_quant)
        cache = cache_prefill(cache, k, v)
        cache = plan.constrain(cache, "kv_cache")
    return attn_mod.out_project(ap, o), cache


def layer_full(p: dict, x: Array, cfg, plan: BuildPlan, make_cache: bool,
               rwkv_state=None, ssm_state=None, taps=None, quantize_cb=None):
    """One layer over a full sequence. Returns (x, cache_out, aux, states).

    `quantize_cb` (calibration only, requires `taps`): called once per
    activation tap *right after the tap is recorded and before the weights
    it feeds are applied*; returns replacement (dequantized-quantized)
    leaves for the owning module, so the rest of this forward — including
    every downstream tap — is computed with the already-quantized upstream
    sub-blocks. This is the staged one-forward-per-layer calibration walk
    (core/pipeline.py, DESIGN.md §4.1).
    """
    aux = jnp.float32(0.0)
    x = plan.constrain(x, "block_in")   # Megatron-SP gather (no-op w/o SP)
    if cfg.attn_free:
        h, new_tm, new_s = rwkv_mod.apply_time_mix(
            p["tm"], apply_norm(p["ln1"], x, cfg), cfg, rwkv_state, taps=taps,
            quantize_cb=quantize_cb)
        x = x + h
        h, new_cm = rwkv_mod.apply_channel_mix(
            p["cm"], apply_norm(p["ln2"], x, cfg), cfg, rwkv_state.x_cm,
            taps=taps, quantize_cb=quantize_cb)
        x = x + h
        new_state = rwkv_mod.RWKVState(new_tm, new_cm, new_s)
        return x, None, aux, new_state

    xn = apply_norm(p["ln1"], x, cfg)
    a_out, cache = _self_attention_full(p, xn, cfg, plan, make_cache, taps,
                                        quantize_cb)
    new_ssm = None
    if cfg.parallel_ssm_heads:
        s_out, new_ssm = ssm_mod.apply_ssm(p["ssm"], xn, cfg, ssm_state,
                                           taps=taps, quantize_cb=quantize_cb)
        a_out = 0.5 * (a_out + s_out)
    x = x + a_out
    xn = apply_norm(p["ln2"], x, cfg)
    if cfg.moe is not None:
        m_out, aux = moe_mod.apply_moe(p["moe"], xn, cfg,
                                       plan.experts_padded(cfg),
                                       plan.moe_token_chunk, taps=taps,
                                       quantize_cb=quantize_cb,
                                       capacity_multiple=
                                       plan.moe_capacity_multiple)
    else:
        m_out = mlp_mod.apply_mlp(p["mlp"], xn, cfg, taps=taps,
                                  constrain=plan.constrain,
                                  quantize_cb=quantize_cb)
    x = x + m_out
    return x, cache, aux, new_ssm


def cross_layer_full(p: dict, x: Array, cfg, plan: BuildPlan,
                     vision_kv: Tuple[Array, Array], taps=None,
                     quantize_cb=None) -> Array:
    hp = plan.heads_padded(cfg)
    hmap = head_to_kv_map(cfg.n_heads, hp, cfg.n_kv_heads)
    xn = apply_norm(p["ln1"], x, cfg)
    cd = x.dtype
    xp = p["xattn"]
    if taps is not None:
        taps["xattn_q_in"] = xn
        if quantize_cb is not None:
            xp = {**xp, **quantize_cb("xattn_q_in")}
    q = jnp.einsum("btd,dhk->bthk", xn, xp["wq"].astype(cd))
    k, v = vision_kv
    o = attn_mod._dense_attention(q, k.astype(cd), v.astype(cd), hmap,
                                  causal=False, window=0)
    if taps is not None:
        taps["xattn_wo_in"] = o.reshape(*o.shape[:2], -1)
        if quantize_cb is not None:
            xp = {**xp, **quantize_cb("xattn_wo_in")}
    x = x + jnp.tanh(p["gate_attn"]).astype(cd) * attn_mod.out_project(
        xp, o)
    xn = apply_norm(p["ln2"], x, cfg)
    x = x + jnp.tanh(p["gate_mlp"]).astype(cd) * mlp_mod.apply_mlp(
        p["mlp"], xn, cfg, taps=taps, quantize_cb=quantize_cb)
    return x


def vision_kv_for_layer(p_cross: dict, vision_embeds: Array):
    """Precompute cross-attn K/V from projected vision embeddings."""
    cd = vision_embeds.dtype
    k = jnp.einsum("bnd,dhk->bnhk", vision_embeds, p_cross["xattn"]["wk"].astype(cd))
    v = jnp.einsum("bnd,dhk->bnhk", vision_embeds, p_cross["xattn"]["wv"].astype(cd))
    return k, v


# ---------------------------------------------------------------------------
# layer application (single-token decode)
# ---------------------------------------------------------------------------

def layer_decode(p: dict, x: Array, cfg, plan: BuildPlan, kv_cache, pos,
                 rwkv_state=None, ssm_state=None, vision_kv=None,
                 is_cross: bool = False):
    """x: (B, 1, d). Returns (x, new_kv_cache, new_rwkv, new_ssm)."""
    if cfg.attn_free:
        h, new_tm, new_s = rwkv_mod.apply_time_mix(
            p["tm"], apply_norm(p["ln1"], x, cfg), cfg, rwkv_state)
        x = x + h
        h, new_cm = rwkv_mod.apply_channel_mix(
            p["cm"], apply_norm(p["ln2"], x, cfg), cfg, rwkv_state.x_cm)
        x = x + h
        return x, None, rwkv_mod.RWKVState(new_tm, new_cm, new_s), None

    if is_cross:
        hp = plan.heads_padded(cfg)
        hmap = head_to_kv_map(cfg.n_heads, hp, cfg.n_kv_heads)
        xn = apply_norm(p["ln1"], x, cfg)
        cd = x.dtype
        q = jnp.einsum("btd,dhk->bthk", xn, p["xattn"]["wq"].astype(cd))
        k, v = vision_kv
        o = attn_mod._dense_attention(q, k.astype(cd), v.astype(cd), hmap,
                                      causal=False, window=0)
        x = x + jnp.tanh(p["gate_attn"]).astype(cd) * attn_mod.out_project(
            p["xattn"], o)
        xn = apply_norm(p["ln2"], x, cfg)
        x = x + jnp.tanh(p["gate_mlp"]).astype(cd) * mlp_mod.apply_mlp(
            p["mlp"], xn, cfg)
        return x, kv_cache, None, None

    hp = plan.heads_padded(cfg)
    hmap = head_to_kv_map(cfg.n_heads, hp, cfg.n_kv_heads)
    xn = apply_norm(p["ln1"], x, cfg)
    q, k, v = qkv_project(p["attn"], xn)
    B = x.shape[0]
    posb = jnp.broadcast_to(pos[None], (B, 1))
    q = apply_rope(q, posb, cfg.rope_theta)
    k = apply_rope(k, posb, cfg.rope_theta)
    kv_cache = cache_insert(kv_cache, k, v, pos)
    o = decode_attend(q, kv_cache, hmap, pos=pos, window=cfg.sliding_window)
    a_out = attn_mod.out_project(p["attn"], o)
    new_ssm = None
    if cfg.parallel_ssm_heads:
        s_out, new_ssm = ssm_mod.decode_ssm(p["ssm"], xn, cfg, ssm_state)
        a_out = 0.5 * (a_out + s_out)
    x = x + a_out
    x = x + _decode_ffn(p, x, cfg, plan)
    return x, kv_cache, None, new_ssm


def _decode_ffn(p: dict, x: Array, cfg, plan: BuildPlan) -> Array:
    xn = apply_norm(p["ln2"], x, cfg)
    if cfg.moe is not None:
        m_out, _ = moe_mod.apply_moe(p["moe"], xn, cfg,
                                     plan.experts_padded(cfg),
                                     plan.moe_token_chunk,
                                     capacity_multiple=
                                     plan.moe_capacity_multiple)
        return m_out
    return mlp_mod.apply_mlp(p["mlp"], xn, cfg)


def layer_decode_paged(p: dict, x: Array, cfg, plan: BuildPlan,
                       pool: dict, layer: Array, block_tables: Array,
                       pos: Array):
    """One decode step of layer `layer` against the paged KV pool
    (serve/kv_cache.py).

    x: (B, 1, d); pool: the whole layer-stacked pool, {"k", "v"} of
    lane-dense (L, NB, BS, KV·hd) pages; layer: int32 index of this layer in the
    stack; block_tables: (B, MAXB) physical page ids per slot; pos: (B,)
    absolute write position per slot, -1 = inactive (write dropped,
    output garbage that the runtime masks). Unlike `layer_decode`,
    positions are per-slot vectors — slots sit at different sequence
    lengths (continuous batching). The append and the attention read and
    write layer `layer` of the stacked pool in place. Returns (x, pool).

    With `plan.kv_bits` set the pool holds integer codes and
    "k_scale"/"v_scale" (L, NB, KV) carry the per-(layer, page, kv_head)
    scales: the append re-quantizes under a running-max page scale and
    attention dequantizes in-kernel (or in the gather fallback)."""
    hp = plan.heads_padded(cfg)
    hmap = head_to_kv_map(cfg.n_heads, hp, cfg.n_kv_heads)
    xn = apply_norm(p["ln1"], x, cfg)
    q, k, v = qkv_project(p["attn"], xn)
    posb = jnp.maximum(pos, 0)[:, None]                   # (B, 1)
    q = apply_rope(q, posb, cfg.rope_theta)
    k = apply_rope(k, posb, cfg.rope_theta)
    lengths = jnp.maximum(pos + 1, 0)
    if plan.kv_bits:
        kp, ks, vp, vs = attn_mod.paged_insert_quant(
            pool["k"], pool["v"], pool["k_scale"], pool["v_scale"], k, v,
            layer, block_tables, pos, kv_bits=plan.kv_bits)
        pool = {"k": kp, "v": vp, "k_scale": ks, "v_scale": vs}
        o = attn_mod.paged_decode_attend_quant(
            q, kp, vp, ks, vs, layer, block_tables, lengths, hmap,
            window=cfg.sliding_window, kv_bits=plan.kv_bits,
            mode=plan.kernel_mode)
    else:
        kp, vp = paged_insert(pool["k"], pool["v"], k, v, layer,
                              block_tables, pos)
        pool = {"k": kp, "v": vp}
        o = paged_decode_attend(q, kp, vp, layer, block_tables, lengths,
                                hmap, window=cfg.sliding_window,
                                mode=plan.kernel_mode)
    x = x + attn_mod.out_project(p["attn"], o)
    x = x + _decode_ffn(p, x, cfg, plan)
    return x, pool
