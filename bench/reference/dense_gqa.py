"""Plain reference forward of the dense GQA family (llama/mistral: RMSNorm,
rotary positions, grouped-query attention with an optional sliding window,
SiLU-gated MLP, untied unembedding), in float32 `jax.numpy` at
`precision=HIGHEST`, over the weights `bench.weights` draws from the seed.

It imports nothing of the program. Departures from the published models,
each one a relabelling that random weights cannot tell apart:

* rotary pairs are adjacent dims (2i, 2i+1) of each head, as the program
  stores heads; the HF checkpoints pair (i, i + head_dim/2) — the same
  model under a fixed permutation of each head's columns of wq and wk;
* the 4-bit codes are dequantized exactly, W = scale * (u + z).

The model runs one layer at a time over a batch of sequences, each layer's
weights regenerated from the seed, so a configuration of any depth fits.

`mode="fp8"` is the control: every matrix product takes float8 (e4m3)
operands, scaled per row of the activations and per tensor of the weights,
and accumulates in float32 — the next precision below the bf16 the
configurations serve in.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _to_f8(a: jax.Array, axis) -> Tuple[jax.Array, jax.Array]:
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(F8), s


def mm(a: jax.Array, b: jax.Array, mode: str) -> jax.Array:
    """a (..., K) @ b (K, N) in float32, or with fp8 operands."""
    if mode == "f32":
        return jnp.matmul(a, b, precision=HIGHEST)
    a8, sa = _to_f8(a, -1)
    b8, sb = _to_f8(b, None)
    y = jnp.matmul(a8, b8, preferred_element_type=jnp.float32)
    return y * sa * sb


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x: (B, T, H, hd); pos: (T,). Adjacent pairs rotate together."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * freqs          # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def dequant(u, scale, z):
    """Integer codes u (K, N), per-column scale and zero -> float32."""
    return (u.astype(jnp.float32) + z.astype(jnp.float32)) * scale


def _attend(q, k, v, window: int, mode: str):
    """One sequence: q (T, H, hd), k/v (T, KV, hd) -> (T, H*hd)."""
    T, H, hd = q.shape
    KV = k.shape[1]
    qg = q.reshape(T, KV, H // KV, hd)
    if mode == "f32":
        s = jnp.einsum("tkgh,skh->kgts", qg, k, precision=HIGHEST)
    else:   # fp8 per (token, head) row of q and k
        q8, sq = _to_f8(qg, -1)                       # sq (T, KV, G, 1)
        k8, sk = _to_f8(k, -1)                        # sk (S, KV, 1)
        s = jnp.einsum("tkgh,skh->kgts", q8, k8,
                       preferred_element_type=jnp.float32)
        s = (s * jnp.transpose(sq[..., 0], (1, 2, 0))[..., None]
             * sk[..., 0].T[:, None, None, :])
    s = s / math.sqrt(hd)
    i = jnp.arange(T)
    mask = i[:, None] >= i[None, :]
    if window > 0:
        mask &= i[:, None] - i[None, :] < window
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    if mode == "f32":
        o = jnp.einsum("kgts,skh->tkgh", p, v, precision=HIGHEST)
    else:   # fp8 per row of p, per (kv head, dim) column of v
        p8, sp = _to_f8(p, -1)                        # sp (KV, G, T, 1)
        v8, sv = _to_f8(v, 0)                         # sv (1, KV, hd)
        o = jnp.einsum("kgts,skh->tkgh", p8, v8,
                       preferred_element_type=jnp.float32)
        o = (o * jnp.transpose(sp[..., 0], (2, 0, 1))[..., None]
             * sv[:, :, None, :])
    return o.reshape(T, H * hd)


@functools.partial(jax.jit, static_argnames=("dm", "eps", "theta",
                                             "window", "mode"))
def layer(x, leaves, ln1, ln2, *, dm, eps, theta, window, mode):
    """x: (B, T, d) float32 -> the layer's output."""
    dm = dict(dm)
    B, T, d = x.shape
    H, KV, hd = dm["n_heads"], dm["n_kv"], dm["head_dim"]
    w = {n: dequant(*leaves[n]) for n in leaves}
    h = rmsnorm(x, ln1, eps)
    pos = jnp.arange(T)
    q = rope(mm(h, w["wq"], mode).reshape(B, T, H, hd), pos, theta)
    k = rope(mm(h, w["wk"], mode).reshape(B, T, KV, hd), pos, theta)
    v = mm(h, w["wv"], mode).reshape(B, T, KV, hd)
    o = jax.lax.map(lambda a: _attend(*a, window, mode), (q, k, v))
    x = x + mm(o, w["wo"], mode)
    h = rmsnorm(x, ln2, eps)
    g = mm(h, w["w_gate"], mode)
    u = mm(h, w["w_up"], mode)
    return x + mm(jax.nn.silu(g) * u, w["w_down"], mode)


@functools.partial(jax.jit, static_argnames=("dm", "out_gain"))
def _layer_weights(key, l, *, dm, out_gain):
    from bench.weights import layer_arrays
    return layer_arrays(key, l, dict(dm), out_gain)


@functools.partial(jax.jit, static_argnames=("dm",))
def _outer(key, *, dm):
    from bench.weights import outer_arrays
    return outer_arrays(key, dict(dm))


def final_hidden(seed: int, dm: Dict[str, int], model: Dict, out_gain: float,
                 tokens: np.ndarray, mode: str = "f32") -> jax.Array:
    """Normed last-layer states (B, T, d) of the token rows `tokens`."""
    from bench.weights import seed_key
    key = seed_key(seed)
    dmt = tuple(sorted(dm.items()))
    embed, _, fnorm = _outer(key, dm=dmt)
    x = jnp.take(embed, jnp.asarray(tokens), axis=0)
    del embed
    kw = dict(dm=dmt, eps=float(model["rms_norm_eps"]),
              theta=float(model["rope_theta"]),
              window=int(model.get("sliding_window") or 0), mode=mode)
    for l in range(dm["n_layers"]):
        leaves, ln1, ln2 = _layer_weights(key, l, dm=dmt, out_gain=out_gain)
        x = layer(x, leaves, ln1, ln2, **kw)
        del leaves
    return rmsnorm(x, fnorm, float(model["rms_norm_eps"]))


def unembed_matrix(seed: int, dm: Dict[str, int]) -> jax.Array:
    from bench.weights import seed_key
    return _outer(seed_key(seed), dm=tuple(sorted(dm.items())))[1]


@jax.jit
def _gap_rows(h_ref, unemb, served):
    """Per row: reference best logit minus the reference logit of the
    served token."""
    lg = jnp.matmul(h_ref, unemb, precision=HIGHEST)
    best = jnp.max(lg, -1)
    return best - jnp.take_along_axis(lg, served[:, None], -1)[:, 0]


@jax.jit
def _control_gap_rows(h_ref, h_ctl, unemb):
    """Per row: reference best minus the reference logit of the token the
    fp8 control puts first."""
    lg = jnp.matmul(h_ref, unemb, precision=HIGHEST)
    pick = jnp.argmax(mm(h_ctl, unemb, "fp8"), -1)
    return jnp.max(lg, -1) - jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]


def pack(seqs: Sequence[Tuple[np.ndarray, Sequence[int]]], multiple: int = 128
         ) -> Tuple[np.ndarray, List[np.ndarray], List[np.ndarray]]:
    """Rows of prompt + served tokens but the last (what the program saw),
    right-padded to one length; per row the positions whose logits chose
    each served token, and those tokens."""
    rows = [np.concatenate([p, np.asarray(s[:-1], np.int32)])
            for p, s in seqs]
    T = -(-max(len(r) for r in rows) // multiple) * multiple
    tokens = np.zeros((len(rows), T), np.int32)
    where, served = [], []
    for i, ((p, s), r) in enumerate(zip(seqs, rows)):
        tokens[i, :len(r)] = r
        where.append(np.arange(len(p) - 1, len(p) - 1 + len(s)))
        served.append(np.asarray(s, np.int32))
    return tokens, where, served


def _rows(h, where, block: int = 1024):
    flat = [(i, j) for i, w in enumerate(where) for j in w]
    for lo in range(0, len(flat), block):
        part = flat[lo:lo + block]
        b = np.asarray([i for i, _ in part])
        t = np.asarray([j for _, j in part])
        yield lo, h[b, t]


def served_gap(seed, dm, model, out_gain,
               seqs: Sequence[Tuple[np.ndarray, Sequence[int]]]
               ) -> Tuple[float, float]:
    """(widest, mean) over every served token of `seqs` of the gap by which
    the served token's reference logit lies below the reference's best."""
    tokens, where, served = pack(seqs)
    h = final_hidden(seed, dm, model, out_gain, tokens)
    unemb = unembed_matrix(seed, dm)
    flat_served = np.concatenate(served)
    gaps = []
    for lo, hr in _rows(h, where):
        s = jnp.asarray(flat_served[lo:lo + hr.shape[0]])
        gaps.append(np.asarray(_gap_rows(hr, unemb, s)))
    g = np.concatenate(gaps)
    return float(g.max()), float(g.mean())


def control_gap(seed, dm, model, out_gain,
                seqs: Sequence[Tuple[np.ndarray, Sequence[int]]]
                ) -> Tuple[float, float]:
    """The same numbers for the fp8 control put in the program's place: at
    each position of the same rows, the gap of the control's first token."""
    tokens, where, _ = pack(seqs)
    h_ref = final_hidden(seed, dm, model, out_gain, tokens)
    h_ctl = final_hidden(seed, dm, model, out_gain, tokens, mode="fp8")
    unemb = unembed_matrix(seed, dm)
    gaps = [np.asarray(_control_gap_rows(hr, hc, unemb))
            for (_, hr), (_, hc) in zip(_rows(h_ref, where),
                                        _rows(h_ctl, where))]
    g = np.concatenate(gaps)
    return float(g.max()), float(g.mean())
