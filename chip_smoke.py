#!/usr/bin/env python3
"""On-chip smoke run of the main path: COMQ-quantize h2o-danube-1.8b at its
published config (24 layers, d_model 2560, 32 heads over 8 KV heads of
head_dim 80, d_ff 6912, vocab 32000; random weights from seed 0), write the
packed checkpoint, and serve it packed with the paged runtime — through the
launchers a user calls (`repro.launch.quantize`, `repro.launch.serve`).

    python3 chip_smoke.py              # one TPU chip
    python3 chip_smoke.py --chips 4    # four chips: column-sharded solve

One chip, phase by phase:

  quantize   `quantize --method comq_blocked --bits 4 --sweeps 1` on
             16 x 512 calibration tokens (full-rank Gram at d_ff 6912),
             `--save-packed` to a temp dir; COMQ must beat RTN and both
             losses must be finite.
  serve      `serve --load-quantized` on the paged engine, bf16 pages:
             8 requests, mixed prompt lengths up to 512, 32 new tokens
             each, staggered arrivals.
  serve_kv8  the same with `--kv-bits 8` (int8 pages).
  check      one decode step compiled from the packed tree must contain
             the Pallas `quant_matmul` and `paged_attention` custom calls
             (bf16 and int8 pages), and its logits must agree with the XLA
             oracle (`kernel_mode="xla"` on materialized weights) on the
             same chip within LOGITS_REL_TOL; every output finite.

With `--chips 4` only the sharded quantize runs: `quantize --shard-solve 4`
on the same model, whose layer-0 wq/wk/wv codes must equal a one-chip solve
of those leaves; then the widest leaf (w_down, 6912 x 2560) is solved
column-sharded and on one chip from the same Gram — codes equal up to rare
one-step flips (CODE_FLIP_FRAC), scales within rtol 2e-6 (DESIGN.md §4.3)
— and the Gram and the code columns must sit on all four chips.

The parent process never imports JAX: each phase runs in a child process
(`--phase NAME`), one after another, so one process holds the chip at a
time. Every child fails when JAX reports no TPU — there is no CPU
fallback. The figures the phases print (wall and compile seconds, peak
device memory, token counts) are smoke figures, not benchmark results.
The last line of standard output is the verdict,
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`,
printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import subprocess
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
RESULT_TAG = "chip_smoke phase result: "
DEADLINE_S = 1140          # whole run, compilation included (limit 1200)

ARCH = "h2o-danube-1.8b"
SMOKE = False              # the published config; True only for rehearsal
CALIB = (16, 512)          # calibration batch x seq: full-rank Gram at 6912
N_REQ, PROMPT_LEN, MAX_NEW = 8, 512, 32
CHECK_PROMPT_LENS = (512, 384, 249, 5)
# Packed Pallas path vs XLA oracle. A random-weight 24-layer model is
# chaotic: perturbing every weight by its bf16 rounding (what the oracle's
# bf16 compute path does to the materialized weights, while the kernel
# multiplies exact integer codes and applies the f32 scale after the dot)
# moves a free-running decode step's logits by a rel-L2 of ~0.4-0.6 at
# depth 8-24 (CPU measurement, reduced widths), and even one layer's bf16
# rounding noise varies with the random instance (1-12 % of the update).
# So the check is layer-synchronised and measures the tolerance in place:
# every layer of the packed Pallas path (A), of the XLA oracle (D) and of
# an f32 reference (T: f32 compute, HIGHEST matmuls, exact dequantized
# weights) takes the same input — the oracle's hidden state. A and D are
# two independent bf16 roundings of the same layer, so |A - D| runs at
# about sqrt(2) x |D - T|, with a wide spread from layer to layer (on a
# TPU v5e: 0.7-2.9 x per layer, 1.53 x as an RMS over the 24 layers).
# The gate takes that RMS (each layer's distances over the norm of its
# update) and the logits from the last layer's outputs:
#     rms_l |A - D| <= ORACLE_NOISE_X * rms_l |D - T|,
#     |A - D|_logits <= ORACLE_NOISE_X * |D - T|_logits.
# A wrong code, scale, page or head mapping moves A by O(|update|): the
# CPU mutations (swapped nibble planes, KV scales one head off) sat 120 x
# and 60 x the oracle's own error. The free-running difference of the
# whole decode step's logits is printed, not gated.
ORACLE_NOISE_X = 2.5
# sharded vs one-chip scales: per-column δ reductions tile differently at
# a quarter of the width (DESIGN.md §4.3) — the rtol tests/test_dist.py
# holds the forced-host mesh to
SCALE_RTOL = 2e-6
# On four TPU v5e chips the column-sharded w_down solve (6912 x 2560) left
# 213 of its 17.7 M codes different from the one-chip solve of the same
# Gram (1.2e-5; the launcher's layer-0 wq/wk/wv codes were identical),
# all by one step and all in 2 of the 2560 columns: the quarter-width f32
# matmuls round differently, a code on a rounding tie flips, and the flip
# cascades down its column's coordinate descent (that column's scale then
# moves by 2.7e-4; the other columns' scales agreed to 4.0e-7). The
# forced-host CPU mesh reproduces the solve bit for bit. The gate admits
# one-step flips up to this fraction of the codes and holds scales to
# SCALE_RTOL in the columns without a flip.
CODE_FLIP_FRAC = 1e-4


def _ckpt(tmp: str) -> str:
    return os.path.join(tmp, "danube_w4.qpk")


# ---------------------------------------------------------------------------
# child side: everything below runs in a `--phase` process
# ---------------------------------------------------------------------------

def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke check failed: {msg}")


def _finite(x) -> bool:
    import jax.numpy as jnp
    return bool(jnp.all(jnp.isfinite(x)))


def _config():
    from repro.configs import get_config, get_smoke_config
    return get_smoke_config(ARCH) if SMOKE else get_config(ARCH)


def _arch_args():
    return ["--arch", ARCH] + (["--smoke"] if SMOKE else [])


def _quantize_args(tmp: str, out: str):
    return _arch_args() + [
        "--method", "comq_blocked", "--bits", "4", "--sweeps", "1",
        "--calib-batch", str(CALIB[0]), "--calib-seq", str(CALIB[1]),
        "--out-dir", os.path.join(tmp, out), "--save-packed", _ckpt(tmp)]


def phase_quantize(tmp: str) -> dict:
    from repro.launch import quantize
    out = quantize.main(_quantize_args(tmp, "ckpt"))
    _check(out["comq_vs_rtn_error_improvement"] > 0,
           f"COMQ did not beat RTN: {out['comq_vs_rtn_error_improvement']}")
    _check(math.isfinite(out["fp_loss"]) and math.isfinite(out["quant_loss"]),
           f"non-finite loss: fp {out['fp_loss']} quant {out['quant_loss']}")
    _check(os.path.getsize(_ckpt(tmp)) > 0, "no packed checkpoint written")
    calib = CALIB[0] * CALIB[1]
    return {"tokens": f"{calib} calibration + {calib} eval tokens",
            "improvement": out["comq_vs_rtn_error_improvement"],
            "fp_loss": out["fp_loss"], "quant_loss": out["quant_loss"],
            "ckpt_bytes": out["ckpt_bytes"], "solve_seconds": out["seconds"]}


def _serve(tmp: str, extra) -> dict:
    from repro.launch import serve
    out = serve.main(_arch_args() + [
        "--load-quantized", _ckpt(tmp), "--num-requests", str(N_REQ),
        "--mixed", "--prompt-len", str(PROMPT_LEN), "--max-new",
        str(MAX_NEW), "--stagger", "2"] + list(extra))
    _check(out["packed_qt"], "served a materialized tree, not packed codes")
    _check(out["requests"] == N_REQ, f"{out['requests']} of {N_REQ} "
           "requests completed")
    _check(out["out_tokens"] == N_REQ * MAX_NEW,
           f"{out['out_tokens']} tokens out, expected {N_REQ * MAX_NEW}")
    return {"tokens": f"{sum(out['prompt_lens'])} prompt + "
                      f"{out['out_tokens']} generated tokens, "
                      f"{out['decode_steps']} decode steps in run()",
            "sample": out["sample"]}


def phase_serve(tmp: str) -> dict:
    return _serve(tmp, [])


def phase_serve_kv8(tmp: str) -> dict:
    return _serve(tmp, ["--kv-bits", "8"])


def _custom_calls(hlo: str) -> set:
    """Names of the Pallas kernels in compiled HLO text (the instruction
    is named after the kernel, its target is tpu_custom_call)."""
    names = set()
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            names.add(line.split("=", 1)[0].strip().lstrip("%")
                      .rsplit(".", 1)[0])
    return names


def _decode_state(params, cfg, plan):
    """Admit CHECK_PROMPT_LENS through a packed runtime (prefill + the
    first decode step) and return the live decode inputs."""
    import jax.numpy as jnp
    import numpy as np
    from repro.serve import Runtime, ServeConfig, blocks_for
    maxb = blocks_for(max(CHECK_PROMPT_LENS) + 16, 16)
    n = len(CHECK_PROMPT_LENS)
    sc = ServeConfig(max_slots=n, block_size=16, num_blocks=n * maxb,
                     buckets=(128, 256, 512), max_blocks_per_slot=maxb)
    rt = Runtime(params, cfg, plan, sc)
    rs = np.random.RandomState(1)
    for L in CHECK_PROMPT_LENS:
        rt.submit(rs.randint(0, cfg.vocab_size, (L,)).astype(np.int32),
                  max_new_tokens=8)
    rt.step()
    live = np.asarray(rt._pos) >= 0
    _check(int(live.sum()) == n, f"{int(live.sum())} of {n} slots live")
    return (rt.pool, jnp.asarray(rt._bt), jnp.asarray(rt._tok[:, None]),
            jnp.asarray(rt._pos))


def _step(cfg, plan):
    from repro.models.model import decode_step_paged

    def step(p, pool, bt, tok, pos):
        return decode_step_paged(p, cfg, plan, pool, bt, tok, pos)[0]
    return step


def _layer_fn(cfg, plan):
    """One jitted paged decode layer (the body of decode_step_paged's
    scan): (layer params, x, block tables, pos, the pool, layer index)
    -> x."""
    import jax
    from repro.core.apply import dequantize_qt_tree
    from repro.models.common import dtype_of
    from repro.models.transformer import layer_decode_paged

    def layer(lp, x, bt, pos, pool, l):
        lp = dequantize_qt_tree(lp, dtype_of(cfg.compute_dtype),
                                keep_fused=True)
        return layer_decode_paged(lp, x, cfg, plan, pool, l, bt, pos)[0]
    return jax.jit(layer)


def _layer_sync(packed, dense, table, cfg, plan, state):
    """Run the packed Pallas path (A), the XLA oracle (D) and the f32
    reference (T) layer by layer from the oracle's hidden state. Returns
    (|A-D|, |D-T|) / |update| per layer, then the same for the logits."""
    import jax
    import jax.numpy as jnp
    from repro.core.pipeline import dequantize_tree
    from repro.models.common import apply_norm
    from repro.models.model import embed_tokens, unembed
    pool, bt, tok, pos = state
    xla = plan.replace(kernel_mode="xla")
    cfg32 = cfg.replace(compute_dtype="float32")
    f_pal, f_xla, f_ref = (_layer_fn(cfg, plan), _layer_fn(cfg, xla),
                           _layer_fn(cfg32, xla))
    x = embed_tokens(dense, cfg, plan, tok)

    def f32(a):
        return a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a

    def norm(a):
        return float(jnp.linalg.norm(a.astype(jnp.float32)))

    rows = []

    def gate(a, d, t, base):
        a, d, t, base = (v.astype(jnp.float32) for v in (a, d, t, base))
        upd = norm(d - base)
        rows.append((norm(a - d) / upd, norm(d - t) / upd))

    pool32 = jax.tree_util.tree_map(f32, pool)
    for l in range(cfg.n_layers):
        li = jnp.int32(l)
        y_pal = f_pal(jax.tree_util.tree_map(lambda a: a[l],
                                             packed["layers"]),
                      x, bt, pos, pool, li)
        y_xla = f_xla(jax.tree_util.tree_map(lambda a: a[l],
                                             dense["layers"]),
                      x, bt, pos, pool, li)
        with jax.default_matmul_precision("highest"):
            y_ref = f_ref(dequantize_tree(table[str(l)]), f32(x), bt, pos,
                          pool32, li)
        _check(_finite(y_pal) and _finite(y_xla) and _finite(y_ref),
               f"layer {l}: non-finite activations")
        gate(y_pal, y_xla, y_ref, x)
        x = y_xla

    def logits(p, y, c):
        return unembed(p, c, plan, apply_norm(p["final_norm"], y, c))

    with jax.default_matmul_precision("highest"):
        lg_ref = logits(dense, y_ref, cfg32)
    lg_xla = logits(dense, y_xla, cfg)
    gate(logits(packed, y_pal, cfg), lg_xla, lg_ref, jnp.zeros_like(lg_xla))
    return rows


def phase_check(tmp: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.ckpt import load_packed_ckpt, unpack_tree
    from repro.core import materialize, serving_params
    from repro.models import BuildPlan

    cfg = _config()
    blob = load_packed_ckpt(_ckpt(tmp))
    qparams = unpack_tree(jax.tree_util.tree_map(
        lambda x: jnp.asarray(x) if isinstance(x, np.ndarray) else x,
        blob["tree"]))
    packed = serving_params(qparams, cfg)
    # bf16 materialization: the dense path casts weights to the bf16
    # compute dtype anyway, so this is the f32 oracle at half the bytes
    dense = materialize(qparams, cfg, dtype=jnp.bfloat16)
    table = qparams["__qlayers__"]
    out = {}
    for kv_bits in (0, 8):
        plan = BuildPlan(remat=False, kv_bits=kv_bits)
        state = _decode_state(packed, cfg, plan)
        compiled = jax.jit(_step(cfg, plan)).lower(packed, *state).compile()
        kernels = _custom_calls(compiled.as_text())
        want = {"quant_matmul",
                "paged_attention_quant" if kv_bits else "paged_attention"}
        _check(want <= kernels, f"kv_bits={kv_bits}: decode step lacks "
               f"Pallas kernels {sorted(want - kernels)} (has "
               f"{sorted(kernels)})")
        got = compiled(packed, *state)
        del compiled
        ref = jax.jit(_step(cfg, plan.replace(kernel_mode="xla")))(
            dense, *state)
        _check(_finite(got) and _finite(ref),
               f"kv_bits={kv_bits}: non-finite logits")
        free = float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))
        rows = _layer_sync(packed, dense, table, cfg, plan, state)
        ad, dt = (float(np.sqrt(np.mean([r[i] ** 2 for r in rows[:-1]])))
                  for i in (0, 1))
        _check(ad <= ORACLE_NOISE_X * dt
               and rows[-1][0] <= ORACLE_NOISE_X * rows[-1][1],
               f"kv_bits={kv_bits}: packed Pallas path differs from the "
               f"XLA oracle by more than {ORACLE_NOISE_X}x the oracle's "
               f"own error: layers rms {ad:.4f} vs {dt:.4f}, logits "
               f"{rows[-1][0]:.4f} vs {rows[-1][1]:.4f} (per layer "
               f"|A-D|, |D-T| over |update|: {rows[:-1]})")
        out[f"kv{kv_bits}"] = {
            "kernels": sorted(kernels),
            "layers_rms_pallas_vs_oracle": ad,
            "layers_rms_oracle_vs_f32": dt,
            "layers_pallas_vs_oracle": [r[0] for r in rows[:-1]],
            "layers_oracle_vs_f32": [r[1] for r in rows[:-1]],
            "logits_pallas_vs_oracle": rows[-1][0],
            "logits_oracle_vs_f32": rows[-1][1],
            "logits_rel_l2_free_running": free}
    out["tokens"] = (f"{sum(CHECK_PROMPT_LENS)} prompt tokens, "
                     f"{len(CHECK_PROMPT_LENS)}-slot decode step x 2 pools")
    return out


def phase_shard4(tmp: str) -> dict:
    """Column-sharded quantize over 4 chips vs one-chip solves."""
    import jax
    import numpy as np
    from repro.ckpt import load_packed_ckpt, unpack_tree
    from repro.core import QuantSpec
    from repro.core.comq_hessian import comq_quantize_blocked
    from repro.core.pipeline import _layer_with_taps, _w2d
    from repro.dist import calib_mesh, sharded_gram, sharded_solve
    from repro.launch import quantize
    from repro.models import BuildPlan, init_params
    from repro.models.model import embed_tokens

    n_dev = len(jax.devices())
    _check(n_dev == 4, f"--chips 4 needs 4 devices, JAX sees {n_dev}")
    out = quantize.main(_quantize_args(tmp, "ckpt4") + ["--shard-solve", "4"])
    _check(out["model_shards"] == 4, f"model axis {out['model_shards']}")
    _check(out["comq_vs_rtn_error_improvement"] > 0, "COMQ lost to RTN")
    _check(math.isfinite(out["quant_loss"]), "non-finite quant loss")

    # the launcher's inputs, rebuilt exactly (quantize.main: seed 0)
    cfg = _config()
    plan = BuildPlan(remat=False)
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg, plan)
    tokens = jax.random.randint(key, CALIB, 0, cfg.vocab_size)
    spec = QuantSpec(bits=4, granularity="per_channel", lam=0.9, sweeps=1,
                     order="greedy")
    lp0 = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    x = embed_tokens(params, cfg, plan, tokens)
    _, taps, _ = _layer_with_taps(lp0, x, None, cfg, plan)
    del params, x
    table = unpack_tree(load_packed_ckpt(_ckpt(tmp))["tree"])["__qlayers__"]
    mesh = calib_mesh(model=4, data=1)
    dev0 = jax.devices()[0]
    res = {}

    def one_chip(h, w):
        h, w = jax.device_put((h, w), dev0)
        return comq_quantize_blocked(h, w, spec)

    # (1) the launcher's layer-0 attention codes (input: the embedding,
    # which no quantized upstream touches) vs a one-chip solve from the
    # Gram the launcher computes (the same shard_map'd Gram function)
    h_attn = sharded_gram(mesh, taps["attn_in"])
    for leaf in ("wq", "wk", "wv"):
        w = _w2d(lp0["attn"][leaf], h_attn.shape[0])
        r = one_chip(h_attn, w)
        qt = table["0"]["attn"][leaf]
        codes1 = np.asarray(r.q - r.z_lo).astype(np.uint8)
        _check(np.array_equal(np.asarray(qt["codes"]), codes1),
               f"launcher layer-0 {leaf} codes differ from the one-chip "
               f"solve in {int((np.asarray(qt['codes']) != codes1).sum())} "
               "entries")
        res[f"launcher_{leaf}"] = _scales_agree(leaf, qt["scale"], r.delta)

    # (2) the widest leaf, sharded vs one chip from the same Gram
    h = sharded_gram(mesh, taps["down_in"])
    _check(len(h.sharding.device_set) == 4,
           f"Gram on {len(h.sharding.device_set)} devices, expected 4")
    w = _w2d(lp0["mlp"]["w_down"], h.shape[0])
    q, delta, z_lo, _, _ = sharded_solve(mesh, h, w, spec, "comq_blocked")
    cols = {s.device: s.data.shape for s in q.addressable_shards}
    _check(len(cols) == 4 and all(c == (w.shape[0], w.shape[1] // 4)
                                  for c in cols.values()),
           f"code columns not split over 4 chips: {cols}")
    r = one_chip(h, w)
    q1, q0 = np.asarray(q), np.asarray(r.q)
    flips = q1 != q0
    step = int(np.abs(q1 - q0).max())
    _check(flips.mean() <= CODE_FLIP_FRAC and step <= 1,
           f"w_down: {int(flips.sum())} codes differ sharded vs one chip "
           f"({flips.mean():.2e}, by up to {step})")
    clean = ~flips.any(axis=0)
    res["w_down"] = _scales_agree("w_down", np.asarray(delta)[clean],
                                  np.asarray(r.delta)[clean])
    _check(np.array_equal(np.asarray(z_lo), np.asarray(r.z_lo)),
           "w_down zero-points differ")
    res["w_down"].update(
        shape=list(w.shape), codes_equal=not flips.any(),
        codes_differ=int(flips.sum()), codes_max_abs_diff=step,
        columns_with_flips=int((~clean).sum()),
        code_shards={str(d): list(c) for d, c in cols.items()})
    res["tokens"] = f"{CALIB[0] * CALIB[1]} calibration tokens"
    res["improvement"] = out["comq_vs_rtn_error_improvement"]
    res["quant_loss"] = out["quant_loss"]
    return res


def _scales_agree(leaf: str, a, b) -> dict:
    """Codes already matched; scales within SCALE_RTOL, ulps reported."""
    import numpy as np
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    rel = float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))
    ulps = int(np.max(np.abs(a.view(np.int32).astype(np.int64)
                             - b.view(np.int32).astype(np.int64))))
    _check(rel <= SCALE_RTOL, f"{leaf} scales differ by rel {rel:.3e} "
           f"({ulps} ulp) > {SCALE_RTOL}")
    return {"codes_equal": True, "scale_max_rel": rel, "scale_max_ulp": ulps}


PHASES = {"quantize": phase_quantize, "serve": phase_serve,
          "serve_kv8": phase_serve_kv8, "check": phase_check,
          "shard4": phase_shard4}


def run_phase(name: str, tmp: str) -> int:
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from jax._src import dispatch
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {devs[0].platform!r}"
              f", {len(devs)} device(s)); this script runs on the chip only",
              file=sys.stderr)
        return 1
    from repro.launch.jax_cache import enable_compile_cache
    enable_compile_cache()
    compile_s = [0.0, 0]

    def on_duration(event, secs, **_):
        if event == dispatch.BACKEND_COMPILE_EVENT:
            compile_s[0] += secs
            compile_s[1] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    t0 = time.time()
    result = PHASES[name](tmp)
    wall = time.time() - t0
    peaks = [d.memory_stats().get("peak_bytes_in_use", -1) for d in devs]
    print(f"smoke figures [{name}] (smoke run, not a benchmark result): "
          f"wall {wall:.3f} s, backend compile {compile_s[0]:.3f} s over "
          f"{compile_s[1]} programs, peak device memory {peaks} bytes, "
          f"{result.pop('tokens')}")
    result.update(phase=name, wall_s=wall, compile_s=compile_s[0],
                  compiles=compile_s[1], peak_bytes=peaks,
                  device={"platform": devs[0].platform,
                          "kind": devs[0].device_kind, "count": len(devs)})
    print(RESULT_TAG + json.dumps(result), flush=True)
    return 0


# ---------------------------------------------------------------------------
# parent side: no JAX here
# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--phase", choices=sorted(PHASES), help=argparse.SUPPRESS)
    ap.add_argument("--tmp", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.phase:
        return run_phase(args.phase, args.tmp)

    phases = (["shard4"] if args.chips == 4
              else ["quantize", "serve", "serve_kv8", "check"])
    start = time.time()
    tmp = tempfile.mkdtemp(prefix="chip_smoke_")
    device = None
    try:
        for name in phases:
            left = DEADLINE_S - (time.time() - start)
            cmd = [sys.executable, str(Path(__file__).resolve()),
                   "--phase", name, "--tmp", tmp]
            try:
                proc = subprocess.run(cmd, stdout=subprocess.PIPE, text=True,
                                      timeout=max(left, 1))
            except subprocess.TimeoutExpired as e:   # child killed
                out = e.stdout or b""
                sys.stdout.write(out if isinstance(out, str)
                                 else out.decode(errors="replace"))
                print(f"chip_smoke: phase {name} ran past the deadline",
                      file=sys.stderr)
                return 124
            result = None
            for line in proc.stdout.splitlines():
                if line.startswith(RESULT_TAG):
                    result = json.loads(line[len(RESULT_TAG):])
                else:
                    print(line)
            if proc.returncode != 0 or result is None:
                print(f"chip_smoke: phase {name} failed (exit "
                      f"{proc.returncode})", file=sys.stderr)
                return proc.returncode or 1
            print(f"phase {name}: {json.dumps(result)}", flush=True)
            if device is not None and result["device"] != device:
                print(f"chip_smoke: device changed between phases: {device}"
                      f" vs {result['device']}", file=sys.stderr)
                return 1
            device = result["device"]
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    if device["count"] != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX saw "
              f"{device['count']} devices", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
