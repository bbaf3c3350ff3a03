"""Top-level model API.

    params = init_params(key, cfg, plan)
    logits, aux = forward(params, cfg, plan, tokens, ...)
    loss, metrics = lm_loss(params, cfg, plan, batch)
    cache = init_cache(cfg, plan, batch, cache_len)
    logits, cache = prefill(params, cfg, plan, tokens, ...)
    logits, cache = decode_step(params, cfg, plan, cache, tokens, pos)

Everything is a pure function over pytrees; the launcher jits/shards these.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import rwkv as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.models import transformer as tfm
from repro.models.attention import KVCache, init_kv_cache
from repro.models.common import (Array, dense_init, dtype_of, embed_init,
                                 norm_params, zeros_init)
from repro.models.transformer import BuildPlan

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _vlm_group_counts(cfg):
    every = cfg.cross_attn.every
    assert cfg.n_layers % every == 0, "vlm layers must divide into groups"
    return cfg.n_layers // every, every - 1   # (n_groups, self_per_group)


def init_params(key: Array, cfg, plan: Optional[BuildPlan] = None) -> Params:
    plan = plan or BuildPlan()
    ks = jax.random.split(key, 8)
    d, v = cfg.d_model, plan.vocab_padded(cfg)
    p: Params = {}
    if cfg.family == "encoder":
        p["pos_embed"] = embed_init(ks[0], (4096, d))
        p["cls_head"] = dense_init(ks[1], (d, cfg.vocab_size))
    else:
        p["embed"] = embed_init(ks[0], (v, d))
        if not cfg.tie_embeddings:
            p["unembed"] = dense_init(ks[1], (d, v))
    if cfg.family == "vlm":
        g, spg = _vlm_group_counts(cfg)
        p["vision_proj"] = dense_init(ks[2], (cfg.cross_attn.vision_dim, d))
        p["groups"] = {
            "self": tfm.init_layer(ks[3], cfg, plan, stack=(g, spg)),
            "cross": tfm.init_cross_layer(ks[4], cfg, plan, stack=(g,)),
        }
    else:
        p["layers"] = tfm.init_layer(ks[3], cfg, plan, stack=(cfg.n_layers,))
    p["final_norm"] = norm_params(ks[5], cfg)
    return p


def count_params(cfg, plan: Optional[BuildPlan] = None) -> int:
    import math
    plan = plan or BuildPlan()
    shapes = jax.eval_shape(
        lambda k: init_params(k, cfg, plan), jax.random.PRNGKey(0))
    return sum(math.prod(x.shape)
               for x in jax.tree_util.tree_leaves(shapes))


def count_params_analytic(cfg, active_only: bool = False) -> int:
    """Param count on the *logical* (unpadded, tp=1) architecture."""
    total = count_params(cfg, BuildPlan(tp=1))
    if active_only and cfg.moe is not None:
        # subtract inactive expert params
        e, k = cfg.moe.n_experts, cfg.moe.top_k
        n_ff_mats = 2 if cfg.act == "gelu_mlp" else 3
        per_expert = n_ff_mats * cfg.d_model * cfg.d_ff
        total -= cfg.n_layers * (e - k) * per_expert
    return total


# ---------------------------------------------------------------------------
# embedding / unembedding (shard-friendly)
# ---------------------------------------------------------------------------

def embed_tokens(p: Params, cfg, plan: BuildPlan, tokens: Array) -> Array:
    cd = dtype_of(cfg.compute_dtype)
    emb = p["embed"]
    from repro.core.apply import QT, is_qt
    if is_qt(emb):
        # gather code rows first, dequantize only the touched rows
        from repro.core.quantizer import unpack_codes
        rows = unpack_codes(jnp.take(emb.codes, tokens, axis=0), emb.cpb)
        x = ((rows.astype(jnp.float32) + emb.z_lo.astype(jnp.float32))
             * emb.scale).astype(cd)
    else:
        x = jnp.take(emb, tokens, axis=0).astype(cd)
    return plan.constrain(x, "residual")


def unembed(p: Params, cfg, plan: BuildPlan, x: Array) -> Array:
    cd = x.dtype
    from repro.core.apply import is_qt
    w = p["unembed"] if not cfg.tie_embeddings else p["embed"].T
    if is_qt(w):
        w = w.dequant(cd)
    logits = jnp.einsum("btd,dv->btv", x, w.astype(cd))
    vp = logits.shape[-1]
    if vp > cfg.vocab_size:   # mask padded vocab columns
        logits = jnp.where(jnp.arange(vp) < cfg.vocab_size, logits,
                           jnp.asarray(-1e30, logits.dtype))
    return plan.constrain(logits, "logits")


# ---------------------------------------------------------------------------
# forward (full sequence: train / prefill)
# ---------------------------------------------------------------------------

def scan_layers(body, x, layers, *per_layer_xs):
    """`jax.lax.scan(body, x, (layers, *per_layer_xs))`, segment-aware.

    `layers` is either a plain stacked layer tree (one scan — the
    historical path, byte-identical) or a `core.apply.SegmentedLayers`
    (mixed-bit serving_params): then one scan runs per homogeneous
    segment, each over its slice of the per-layer operands (KV caches,
    states, layer indices), the carry passing from one segment's scan to
    the next, and the stacked ys re-concatenate along the layer axis — so
    a mixed 4/8-bit tree keeps every segment's codes packed at their own
    width inside its own compiled scan."""
    from repro.core.apply import is_segmented
    if not is_segmented(layers):
        return jax.lax.scan(body, x, (layers, *per_layer_xs))
    lo = 0
    ys_parts = []
    for seg, n in zip(layers.segments, layers.sizes):
        xs = tuple(jax.tree_util.tree_map(lambda a: a[lo:lo + n], xa)
                   for xa in per_layer_xs)
        x, ys = jax.lax.scan(body, x, (seg, *xs))
        ys_parts.append(ys)
        lo += n
    ys = jax.tree_util.tree_map(lambda *parts: jnp.concatenate(parts,
                                                               axis=0),
                                *ys_parts)
    return x, ys


def _run_homogeneous(p: Params, cfg, plan, x, make_cache: bool,
                     init_states=None):
    """Scan over stacked layers. Returns (x, caches, aux, states)."""
    L = cfg.n_layers

    def body(x, xs):
        lp, st = xs
        from repro.core.apply import dequantize_qt_tree
        lp = dequantize_qt_tree(lp, dtype_of(cfg.compute_dtype))
        rwkv_state = st.get("rwkv") if st else None
        ssm_state = st.get("ssm") if st else None
        x, cache, aux, new_state = tfm.layer_full(
            lp, x, cfg, plan, make_cache,
            rwkv_state=rwkv_state, ssm_state=ssm_state)
        x = plan.constrain(x, "residual")
        return x, (cache, aux, new_state)

    if plan.remat:
        body = jax.checkpoint(body)

    def scan_fn(carry, xs):
        x2, ys = body(carry, xs)
        return x2, ys

    x, (caches, auxs, states) = scan_layers(scan_fn, x, p["layers"],
                                            init_states)
    return x, caches, jnp.sum(auxs), states


def _run_vlm(p: Params, cfg, plan, x, make_cache: bool, vision_embeds):
    g, spg = _vlm_group_counts(cfg)
    ve = jnp.einsum("bnv,vd->bnd", vision_embeds.astype(x.dtype),
                    p["vision_proj"].astype(x.dtype))

    def self_body(x, lp):
        x, cache, _, _ = tfm.layer_full(lp, x, cfg, plan, make_cache)
        return plan.constrain(x, "residual"), cache

    if plan.remat:
        self_body = jax.checkpoint(self_body)

    def group_body(x, xs):
        gp_self, gp_cross = xs
        x, caches = jax.lax.scan(self_body, x, gp_self)
        vkv = tfm.vision_kv_for_layer(gp_cross, ve)
        x = tfm.cross_layer_full(gp_cross, x, cfg, plan, vkv)
        x = plan.constrain(x, "residual")
        return x, (caches, vkv if make_cache else None)

    if plan.remat:
        group_body = jax.checkpoint(group_body)
    x, (caches, vkvs) = jax.lax.scan(group_body, x,
                                     (p["groups"]["self"], p["groups"]["cross"]))
    return x, caches, vkvs


def forward(p: Params, cfg, plan: BuildPlan, tokens: Array,
            vision_embeds: Optional[Array] = None,
            embeds: Optional[Array] = None,
            make_cache: bool = False):
    """Returns (logits, aux, cache_pytree_or_None)."""
    cd = dtype_of(cfg.compute_dtype)
    if cfg.family == "encoder":
        x = embeds.astype(cd)
        T = x.shape[1]
        x = x + p["pos_embed"][:T].astype(cd)
        x, _, aux, _ = _run_homogeneous(p, cfg, plan, x, False)
        from repro.models.common import apply_norm
        x = apply_norm(p["final_norm"], x, cfg)
        pooled = x.mean(axis=1)
        logits = jnp.einsum("bd,dc->bc", pooled, p["cls_head"].astype(cd))
        return logits.astype(jnp.float32), aux, None

    x = embed_tokens(p, cfg, plan, tokens)
    B, T = tokens.shape

    cache = None
    if cfg.family == "vlm":
        x, kv, vkv = _run_vlm(p, cfg, plan, x, make_cache, vision_embeds)
        aux = jnp.float32(0.0)
        if make_cache:
            cache = {"kv": kv, "xkv": vkv}
    else:
        init_states = None
        if cfg.attn_free:
            init_states = {"rwkv": _stacked_rwkv_state(cfg, B)}
        elif cfg.parallel_ssm_heads:
            init_states = {"ssm": _stacked_ssm_state(cfg, B)}
        x, kv, aux, states = _run_homogeneous(p, cfg, plan, x, make_cache,
                                              init_states)
        if make_cache:
            cache = {}
            if kv is not None:
                cache["kv"] = kv
            if cfg.attn_free:
                cache["rwkv"] = states
            elif cfg.parallel_ssm_heads:
                cache["ssm"] = states

    from repro.models.common import apply_norm
    x = apply_norm(p["final_norm"], x, cfg)
    logits = unembed(p, cfg, plan, x)
    return logits, aux, cache


def _stacked_rwkv_state(cfg, batch):
    L = cfg.n_layers
    s = rwkv_mod.init_rwkv_state(batch, cfg)
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (L, *a.shape)), s)


def _stacked_ssm_state(cfg, batch, layers=None):
    L = layers if layers is not None else cfg.n_layers
    s = ssm_mod.init_ssm_state(batch, cfg)
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (L, *a.shape)), s)


# ---------------------------------------------------------------------------
# loss (vocab-shard-friendly cross entropy with z-loss)
# ---------------------------------------------------------------------------

def lm_loss(p: Params, cfg, plan: BuildPlan, batch: Dict[str, Array],
            z_loss: float = 1e-4, aux_weight: float = 1e-2):
    if cfg.family == "encoder":
        logits, aux, _ = forward(p, cfg, plan, None, embeds=batch["embeds"])
        labels = batch["labels"]
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        loss = jnp.mean(lse - ll)
        return loss, {"loss": loss, "aux": aux}
    logits, aux, _ = forward(p, cfg, plan, batch["tokens"],
                             vision_embeds=batch.get("vision_embeds"))
    labels = batch["labels"]
    logits = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(logits.max(axis=-1, keepdims=True))
    shifted = logits - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + m[..., 0]
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    ll = jnp.sum(logits * onehot, axis=-1)
    nll = lse - ll
    loss = jnp.mean(nll)
    zl = z_loss * jnp.mean(jnp.square(lse))
    total = loss + zl + aux_weight * aux
    return total, {"loss": loss, "z_loss": zl, "aux": aux,
                   "ppl_proxy": jnp.exp(jnp.minimum(loss, 20.0))}


# ---------------------------------------------------------------------------
# serving: cache init / prefill / decode
# ---------------------------------------------------------------------------

def cache_len_for(cfg, seq_len: int) -> int:
    if cfg.sliding_window:
        return min(cfg.sliding_window, seq_len)
    return seq_len


def init_cache(cfg, plan: BuildPlan, batch: int, seq_len: int):
    """Allocate an empty cache pytree for decode at context length seq_len."""
    clen = cache_len_for(cfg, seq_len)
    hd = cfg.resolved_head_dim
    cache: Dict[str, Any] = {}
    if cfg.attn_free:
        cache["rwkv"] = _stacked_rwkv_state(cfg, batch)
        return cache
    L = cfg.n_layers
    if cfg.family == "vlm":
        g, spg = _vlm_group_counts(cfg)
        kv = init_kv_cache(batch, clen, cfg.n_kv_heads, hd, plan.cache_dtype,
                           quantized=plan.cache_quant)
        cache["kv"] = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (g, spg, *a.shape)), kv)
        nv = cfg.cross_attn.n_vision_tokens
        cache["xkv"] = (
            jnp.zeros((g, batch, nv, cfg.n_kv_heads, hd), plan.cache_dtype),
            jnp.zeros((g, batch, nv, cfg.n_kv_heads, hd), plan.cache_dtype))
        return cache
    kv = init_kv_cache(batch, clen, cfg.n_kv_heads, hd, plan.cache_dtype,
                       quantized=plan.cache_quant)
    cache["kv"] = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (L, *a.shape)), kv)
    if cfg.parallel_ssm_heads:
        cache["ssm"] = _stacked_ssm_state(cfg, batch)
    return cache


def prefill(p: Params, cfg, plan: BuildPlan, tokens: Array,
            vision_embeds: Optional[Array] = None):
    logits, _, cache = forward(p, cfg, plan, tokens,
                               vision_embeds=vision_embeds, make_cache=True)
    return logits[:, -1], cache


def decode_step(p: Params, cfg, plan: BuildPlan, cache, tokens: Array,
                pos: Array):
    """tokens: (B, 1) int32; pos: scalar int32 absolute position.

    Layer params may carry quantized (QT) leaves: they are dequantized
    *inside* the scan body, so HBM streams int4/int8 codes per layer."""
    from repro.core.apply import dequantize_qt_tree
    x = embed_tokens(p, cfg, plan, tokens)

    if cfg.attn_free:
        def body(x, xs):
            lp, st = xs
            lp = dequantize_qt_tree(lp, dtype_of(cfg.compute_dtype))
            x, _, new_rwkv, _ = tfm.layer_decode(lp, x, cfg, plan, None, pos,
                                                 rwkv_state=st)
            return plan.constrain(x, "residual"), new_rwkv
        x, new_states = scan_layers(body, x, p["layers"], cache["rwkv"])
        new_cache = {"rwkv": new_states}
    elif cfg.family == "vlm":
        def self_body(x, xs):
            lp, kv = xs
            lp = dequantize_qt_tree(lp, dtype_of(cfg.compute_dtype))
            x, kv, _, _ = tfm.layer_decode(lp, x, cfg, plan, kv, pos)
            return plan.constrain(x, "residual"), kv

        def group_body(x, xs):
            gp_self, gp_cross, kv, xkv = xs
            x, new_kv = jax.lax.scan(self_body, x, (gp_self, kv))
            x, _, _, _ = tfm.layer_decode(dequantize_qt_tree(gp_cross, dtype_of(cfg.compute_dtype)), x,
                                          cfg, plan, None, pos,
                                          vision_kv=xkv, is_cross=True)
            return plan.constrain(x, "residual"), new_kv

        x, new_kv = jax.lax.scan(
            group_body, x,
            (p["groups"]["self"], p["groups"]["cross"], cache["kv"],
             cache["xkv"]))
        new_cache = {"kv": new_kv, "xkv": cache["xkv"]}
    else:
        has_ssm = cfg.parallel_ssm_heads

        def body(x, xs):
            lp, kv, st = xs
            # keep_fused: COMQ-layout QT projections stay packed and route
            # through the dequant-fused quant_matmul (core/apply.qt_linear)
            lp = dequantize_qt_tree(lp, dtype_of(cfg.compute_dtype),
                                    keep_fused=True)
            x, kv, _, new_ssm = tfm.layer_decode(lp, x, cfg, plan, kv, pos,
                                                 ssm_state=st)
            return plan.constrain(x, "residual"), (kv, new_ssm)

        ssm_in = cache.get("ssm") if has_ssm else None
        x, (new_kv, new_ssm) = scan_layers(body, x, p["layers"],
                                           cache["kv"], ssm_in)
        new_cache = {"kv": new_kv}
        if has_ssm:
            new_cache["ssm"] = new_ssm

    from repro.models.common import apply_norm
    x = apply_norm(p["final_norm"], x, cfg)
    logits = unembed(p, cfg, plan, x)
    return logits[:, 0], new_cache


def decode_step_paged(p: Params, cfg, plan: BuildPlan, pool, block_tables,
                      tokens: Array, pos: Array):
    """One continuous-batching decode step against a paged KV pool.

    tokens: (B, 1) int32; pos: (B,) int32 absolute write positions per slot
    (-1 = inactive slot: K/V write dropped, logits garbage the runtime
    masks); pool: {"k","v"} of (L, NB, BS, KV·hd) lane-dense pages, plus
    {"k_scale","v_scale"} (L, NB, KV) for quantized pages (serve/kv_cache);
    block_tables: (B, MAXB) physical page ids.

    The pool rides the layer scan's carry and each layer reads and writes
    its pages in place by its global layer index (the scan's xs carry
    `arange(L)` beside the layer params; a `SegmentedLayers` tree gives
    each segment its own range of it) — no layer's pool is sliced out or
    written back, so with the pool donated the step moves only the bytes
    it attends over and appends (DESIGN.md §11.4).

    Unlike `decode_step`, every slot carries its own position — a mixed-
    length, staggered-arrival batch decodes in one jitted program. Covers
    the attention families (dense/MoE/GQA/SWA); attention-free, parallel-
    SSM and VLM archs keep the dense-cache path."""
    if cfg.attn_free or cfg.parallel_ssm_heads or cfg.family == "vlm":
        raise NotImplementedError(
            f"paged decode does not cover family={cfg.family!r} "
            "(attention-free / ssm / vlm use the dense-cache decode_step)")
    from repro.core.apply import dequantize_qt_tree
    x = embed_tokens(p, cfg, plan, tokens)
    cd = dtype_of(cfg.compute_dtype)

    def body(carry, xs):
        x, pool = carry
        lp, layer = xs
        lp = dequantize_qt_tree(lp, cd, keep_fused=True)
        x, pool = tfm.layer_decode_paged(lp, x, cfg, plan, pool, layer,
                                         block_tables, pos)
        return (plan.constrain(x, "residual"), pool), None

    n_layers = pool["k"].shape[0]
    (x, pool), _ = scan_layers(body, (x, pool), p["layers"],
                               jnp.arange(n_layers, dtype=jnp.int32))
    from repro.models.common import apply_norm
    x = apply_norm(p["final_norm"], x, cfg)
    logits = unembed(p, cfg, plan, x)
    return logits[:, 0], pool


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins for the dry-run; no allocation)
# ---------------------------------------------------------------------------

def input_specs(cfg, shape, plan: Optional[BuildPlan] = None) -> Dict[str, Any]:
    """Stand-ins for every model input of `shape` (a ShapeConfig)."""
    plan = plan or BuildPlan()
    gb, T = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if cfg.family == "encoder":
        return {
            "embeds": jax.ShapeDtypeStruct((gb, 197, cfg.d_model), jnp.bfloat16),
            "labels": jax.ShapeDtypeStruct((gb,), i32),
        }
    specs: Dict[str, Any] = {}
    if shape.kind == "train":
        specs["tokens"] = jax.ShapeDtypeStruct((gb, T), i32)
        specs["labels"] = jax.ShapeDtypeStruct((gb, T), i32)
    elif shape.kind == "prefill":
        specs["tokens"] = jax.ShapeDtypeStruct((gb, T), i32)
    else:  # decode: one new token against a cache of length T
        specs["tokens"] = jax.ShapeDtypeStruct((gb, 1), i32)
        specs["pos"] = jax.ShapeDtypeStruct((), i32)
        cache = jax.eval_shape(lambda: init_cache(cfg, plan, gb, T))
        specs["cache"] = cache
    if cfg.family == "vlm" and shape.kind != "decode":
        ca = cfg.cross_attn
        specs["vision_embeds"] = jax.ShapeDtypeStruct(
            (gb, ca.n_vision_tokens, ca.vision_dim), jnp.bfloat16)
    return specs
