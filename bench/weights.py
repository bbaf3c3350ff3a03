"""Weights drawn from `--seed`, made on the device.

A dense GQA model (llama/mistral family) served as 4-bit per-channel
codes, the way a COMQ checkpoint serves it: every projection leaf holds
offset-binary integer codes u in [0, 16), a float32 scale and an int32
zero-point per output column; the weight is
W[k, n] = scale[n] * (u[k, n] + z[n]). Embedding, unembedding and norm
scales stay float32, as the packed checkpoint keeps them.

The codes are drawn as integers, one random byte giving two of them (its
low nibble for column c, its high nibble for column c + N/2). They reach
the program as a `quantize_model` table and go through the program's own
`repro.core.serving_params`, which decides how they are packed; the
reference reads the integers.

Every leaf of layer l is a function of (seed, l) alone: `program_params`
builds all layers in one jitted call, and the reference regenerates one
layer at a time with `layer_arrays`, bit for bit the same.

Scales are chosen so the model is not chaotic: the in-projections keep a
unit-variance output, the out-projections (wo, w_down) add a fraction
`out_gain` of that to the residual stream, and the embedding rows have
unit variance. A trained model behaves so; a random one at fan-in scale
on every leaf amplifies bf16 rounding from layer to layer until greedy
tokens say nothing about the arithmetic.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

# leaf -> (module, input dim key, output dim key, is out-projection)
LEAVES = {
    "wq": ("attn", "d_model", "q_dim", False),
    "wk": ("attn", "d_model", "kv_dim", False),
    "wv": ("attn", "d_model", "kv_dim", False),
    "wo": ("attn", "q_dim", "d_model", True),
    "w_gate": ("mlp", "d_model", "d_ff", False),
    "w_up": ("mlp", "d_model", "d_ff", False),
    "w_down": ("mlp", "d_ff", "d_model", True),
}
LEAF_ORDER = tuple(LEAVES)
CODE_RMS = math.sqrt(21.5)   # rms of u + z, u uniform on 0..15, z in {-8,-7}


def dims(m: Dict[str, Any]) -> Dict[str, int]:
    """Widths of a model section of a config file (HF key names)."""
    d = int(m["hidden_size"])
    h = int(m["num_attention_heads"])
    kv = int(m["num_key_value_heads"])
    hd = int(m.get("head_dim") or d // h)
    return {"d_model": d, "n_heads": h, "n_kv": kv, "head_dim": hd,
            "q_dim": h * hd, "kv_dim": kv * hd,
            "d_ff": int(m["intermediate_size"]),
            "vocab": int(m["vocab_size"]),
            "n_layers": int(m["num_hidden_layers"])}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number up to 64 bits."""
    seed = int(seed)
    k = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(k, (seed >> 31) & 0xFFFFFFFF)


def _leaf(key, k_in: int, n_out: int, std: float):
    kc, ks, kz = jax.random.split(key, 3)
    b = jax.random.bits(kc, (k_in, n_out // 2), jnp.uint8)
    codes = jnp.concatenate([b & 15, b >> 4], axis=-1)
    # spread 0.75..1.25 in steps of 1/128: exact, so any compilation of
    # this function gives the same bits
    spread = (96 + jax.random.randint(ks, (n_out,), 0, 64)) / 128.0
    scale = jnp.float32(std / CODE_RMS) * spread.astype(jnp.float32)
    z = -8 + jax.random.bernoulli(kz, 0.5, (n_out,)).astype(jnp.int32)
    return codes, scale, z


def _norm_scale(key, d: int):
    """Norm weights 0.875..1.109 in steps of 1/64 (exact)."""
    k = jax.random.randint(key, (d,), 0, 16)
    return 1.0 + (k - 8).astype(jnp.float32) / 64.0


def layer_arrays(key, layer, dm: Dict[str, int], out_gain: float):
    """Layer `layer`'s leaves: {name: (codes, scale, z)} and its two norm
    scales. Traceable, with `layer` a traced int."""
    lk = jax.random.fold_in(jax.random.fold_in(key, 1), layer)
    keys = jax.random.split(lk, len(LEAF_ORDER) + 2)
    out = {}
    for i, name in enumerate(LEAF_ORDER):
        _, kin, kout, is_out = LEAVES[name]
        std = (out_gain if is_out else 1.0) / math.sqrt(dm[kin])
        out[name] = _leaf(keys[i], dm[kin], dm[kout], std)
    d = dm["d_model"]
    return out, _norm_scale(keys[-2], d), _norm_scale(keys[-1], d)


def outer_arrays(key, dm: Dict[str, int]):
    """Embedding (V, d), unembedding (d, V), final norm scale (d,)."""
    ke, ku, kn = jax.random.split(jax.random.fold_in(key, 0), 3)
    d, v = dm["d_model"], dm["vocab"]
    embed = jax.random.normal(ke, (v, d), jnp.float32)
    unembed = jax.random.normal(ku, (d, v), jnp.float32) / math.sqrt(d)
    fnorm = _norm_scale(kn, d)
    return embed, unembed, fnorm


def logical_shape(name: str, dm: Dict[str, int]) -> Tuple[int, ...]:
    d, h, kv, hd, f = (dm["d_model"], dm["n_heads"], dm["n_kv"],
                       dm["head_dim"], dm["d_ff"])
    return {"wq": (d, h, hd), "wk": (d, kv, hd), "wv": (d, kv, hd),
            "wo": (h, hd, d), "w_gate": (d, f), "w_up": (d, f),
            "w_down": (f, d)}[name]


def _table_entry(key, layer, dm: Dict[str, int], out_gain: float):
    """Layer `layer` as `repro.core.quantize_model` writes it in its
    per-layer table: the drawn codes as QTensors, beside the norm scales.
    Traceable, with `layer` a traced int."""
    from repro.core.pipeline import make_qtensor
    leaves, ln1, ln2 = layer_arrays(key, layer, dm, out_gain)
    entry = {"ln1": {"scale": ln1}, "ln2": {"scale": ln2},
             "attn": {}, "mlp": {}}
    for name, (u, scale, z) in leaves.items():
        entry[LEAVES[name][0]][name] = make_qtensor(
            u.astype(jnp.int32) + z, scale, z, logical_shape(name, dm),
            bits=4)
    return entry


def params_tree(key, dm: Dict[str, int], out_gain: float, cfg):
    """The packed serving tree (traceable): the drawn table folded by the
    program's own `serving_params`, the path a stripped `quantize_model`
    checkpoint takes to the server.

    Layers are drawn and folded one at a time (`lax.map`): the program's
    fold is traced once, not once a layer, and one layer's unpacked codes
    are live at a time. The L one-layer folds, stacked, hold the leaves of
    the fold of all L layers, whose structure `eval_shape` gives."""
    from repro.core import serving_params

    def fold(entries):
        table = {str(i): e for i, e in enumerate(entries)}
        return serving_params({"__qlayers__": table}, cfg)["layers"]

    L = dm["n_layers"]
    stacked = jax.lax.map(
        lambda l: fold([_table_entry(key, l, dm, out_gain)]), jnp.arange(L))
    whole = jax.eval_shape(
        lambda: fold([_table_entry(key, 0, dm, out_gain)] * L))
    shapes, treedef = jax.tree_util.tree_flatten(whole)
    layers = jax.tree_util.tree_unflatten(treedef, [
        a.reshape(s.shape)
        for a, s in zip(jax.tree_util.tree_leaves(stacked), shapes)])
    embed, unembed, fnorm = outer_arrays(key, dm)
    return {"embed": embed, "unembed": unembed,
            "final_norm": {"scale": fnorm}, "layers": layers}


def program_params(seed: int, dm: Dict[str, int], out_gain: float, cfg):
    """The serving tree of `seed` for the program's ModelConfig `cfg`,
    made on the device in one jitted call."""
    return jax.jit(lambda k: params_tree(k, dm, out_gain, cfg))(
        seed_key(seed))


def program_bytes(dm: Dict[str, int]) -> Dict[str, int]:
    """Bytes of the served weights at their logical size: 4-bit codes,
    per-column scale and zero-point, and the float32 embed/unembed/norms."""
    codes = scales = 0
    for name in LEAF_ORDER:
        _, kin, kout, _ = LEAVES[name]
        codes += dm[kin] * dm[kout] // 2
        scales += 8 * dm[kout]
    L = dm["n_layers"]
    outer = 4 * (2 * dm["vocab"] * dm["d_model"] + dm["d_model"])
    return {"codes": L * codes, "scales": L * scales,
            "norms": L * 8 * dm["d_model"], "outer": outer}


# ---------------------------------------------------------------------------
# float32 master weights, for the quantize job
# ---------------------------------------------------------------------------

def _gauss(key, shape, std: float):
    """Near-normal draws made exactly: a sum of four uniform 16-bit
    integers (Irwin-Hall), centred and scaled by one multiply, so every
    compilation of this function gives the same bits."""
    u = jax.random.randint(key, (4, *shape), 0, 65536)
    s = (jnp.sum(u, 0) - 131070).astype(jnp.float32)
    return s * jnp.float32(std / (65536.0 * math.sqrt(1.0 / 3.0)))


def dense_layer(key, layer, dm: Dict[str, int], out_gain: float):
    """Layer `layer`'s float32 leaves as (K, N) matrices, and its norms."""
    lk = jax.random.fold_in(jax.random.fold_in(key, 2), layer)
    keys = jax.random.split(lk, len(LEAF_ORDER) + 2)
    out = {}
    for i, name in enumerate(LEAF_ORDER):
        _, kin, kout, is_out = LEAVES[name]
        std = (out_gain if is_out else 1.0) / math.sqrt(dm[kin])
        out[name] = _gauss(keys[i], (dm[kin], dm[kout]), std)
    d = dm["d_model"]
    return out, _norm_scale(keys[-2], d), _norm_scale(keys[-1], d)


def dense_embed(key, dm: Dict[str, int]):
    return _gauss(jax.random.fold_in(key, 3), (dm["vocab"], dm["d_model"]),
                  1.0)


def dense_tree(key, dm: Dict[str, int], out_gain: float):
    """The float32 master the quantizer takes (`repro.models.init_params`
    layout: leaves stacked over layers at their logical shapes)."""
    leaves, ln1, ln2 = jax.lax.map(
        lambda l: dense_layer(key, l, dm, out_gain),
        jnp.arange(dm["n_layers"]))
    L = dm["n_layers"]
    w = {n: leaves[n].reshape(L, *logical_shape(n, dm)) for n in LEAF_ORDER}
    d, v = dm["d_model"], dm["vocab"]
    ku, kn = jax.random.split(jax.random.fold_in(key, 4))
    return {"embed": dense_embed(key, dm),
            "unembed": _gauss(ku, (d, v), d ** -0.5),
            "final_norm": {"scale": _norm_scale(kn, d)},
            "layers": {"ln1": {"scale": ln1}, "ln2": {"scale": ln2},
                       "attn": {n: w[n] for n in ("wq", "wk", "wv", "wo")},
                       "mlp": {n: w[n] for n in ("w_gate", "w_up",
                                                 "w_down")}}}
