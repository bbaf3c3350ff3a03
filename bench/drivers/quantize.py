"""The COMQ quantize job through `repro.core.quantize_model`.

Set-up: the float32 master weights (`bench.weights.dense_tree`) and the
calibration tokens are drawn from the seed on the device, and one whole
job runs to compile every program the walk uses.

Window: jobs back to back, each a whole `quantize_model` call over every
layer of the configuration with the traffic's method, grid, sweeps and
calibration batch. The window closes at the end of the first job that ends
after `seconds`; seconds per layer are the window's wall time over the
layers the jobs completed. Each job blocks on its codes before the next.
A traced run profiles the window's first job.

Check: every job's codes and scales must equal the first job's (an exact
comparison). The first job's codes are held against the plain reference
walk (`bench/reference/comq.py`) on the same weights and tokens, on every
leaf of every layer; see `compare` for the numbers.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, Optional

import numpy as np

from bench import weights
from bench.harness import (Outcome, annotate, checks_for, device_info, log,
                           memory_note, stage)

MODS = {"wq": "attn", "wk": "attn", "wv": "attn", "wo": "attn",
        "w_gate": "mlp", "w_up": "mlp", "w_down": "mlp"}


def _spec(tr):
    from repro.core import QuantSpec
    return QuantSpec(bits=int(tr["bits"]), granularity=tr["granularity"],
                     lam=float(tr["lam"]), sweeps=int(tr["sweeps"]),
                     order=tr["order"])


def _tokens(tr, seed: int, vocab: int) -> np.ndarray:
    rng = np.random.default_rng(int(seed))
    return rng.integers(0, vocab, (int(tr["calib_batch"]),
                                   int(tr["calib_seq"])), dtype=np.int32)


def program_codes(qparams, dm) -> Dict[int, Dict[str, Any]]:
    """{layer: {leaf: (q int32, delta, z_lo)}} of a quantize_model output."""
    import jax.numpy as jnp
    out = {}
    for l in range(dm["n_layers"]):
        lp = qparams["__qlayers__"][str(l)]
        out[l] = {}
        for name, mod in MODS.items():
            t = lp[mod][name]
            q = t["codes"].astype(jnp.int32) + t["z_lo"]
            out[l][name] = (q.reshape(q.shape[0], -1) if q.ndim > 2 else q,
                            t["scale"], t["z_lo"])
    return out


def run(cell, seed: int, seconds: float, trace: bool, env) -> Outcome:
    import jax
    import jax.numpy as jnp
    from repro.core import quantize_model
    from repro.models import BuildPlan
    from repro.obs.trace import Tracer
    from bench.drivers.serve_offline import model_config

    tr = cell.traffic
    dm = weights.dims(cell.config["model"])
    gain = float(cell.config["weights"]["out_gain"])
    cfg = model_config(cell.config)
    plan = BuildPlan(remat=False)
    spec = _spec(tr)
    with annotate("bench.weights"):
        params = jax.jit(lambda k: weights.dense_tree(k, dm, gain))(
            weights.seed_key(seed))
        tokens = jnp.asarray(_tokens(tr, seed, dm["vocab"]))
        jax.block_until_ready(params)
    stage(env.t_start, "weights")
    memory_note(env.devices, "master weights")
    tracer = Tracer(run=cell.name) if trace else None

    def job():
        with annotate("bench.quantize_model"):
            qp, _ = quantize_model(params, cfg, plan, tokens, spec,
                                   method=tr["method"], tracer=tracer)
            jax.block_until_ready(qp["__qlayers__"])
        return qp

    with annotate("bench.warmup"):
        first = job()
    stage(env.t_start, "warm-up job")
    memory_note(env.devices, "warm")
    ref_codes = program_codes(first, dm)
    del first
    tables = []
    env.counter.mark()
    t_open = time.time()
    # a traced run profiles the window's first job only: a job's blocked
    # solves put millions of op events into a trace
    with env.window(trace) as win:
        tables.append(job()["__qlayers__"])
    while time.time() - t_open < seconds:
        tables.append(job()["__qlayers__"])
    t_close = time.time()
    jobs = len(tables)
    n_compiles, compile_s = env.counter.since_mark()
    window_s = t_close - t_open
    layers = jobs * dm["n_layers"]
    log(f"window: {window_s:.3f} s, {jobs} jobs, {layers} layers, compiles "
        f"in window: {n_compiles} ({compile_s:.3f} s)")
    device = device_info(env.devices)
    same = []
    for table in tables:
        got = program_codes({"__qlayers__": table}, dm)
        same += [jnp.array_equal(got[l][n][i], ref_codes[l][n][i])
                 for l in got for n in got[l] for i in (0, 1)]
    repeat_ok = bool(jax.device_get(jnp.all(jnp.stack(same))))
    del tables, same
    spans = tracer.events if tracer else []
    del params
    gc.collect()

    with annotate("bench.reference"):
        readings = compare(cell, seed, {"program": ref_codes})["program"]
    readings["jobs_differ"] = float(not repeat_ok)
    checks = checks_for(cell.limits, readings)
    log(f"reference: {readings}")
    out = Outcome(end_to_end={"quantize_s_per_layer": window_s / layers,
                              "setup_s": t_open - env.t_start},
                  attempted=jobs, failed=0 if repeat_ok else jobs,
                  checks=checks, device=device, sample=ref_codes)
    if trace:
        out.trace = {"window": win, "spans": spans, "dm": dm,
                     "layers": dm["n_layers"],
                     "tokens": int(tokens.shape[0] * tokens.shape[1]),
                     "sweeps": spec.sweeps}
    return out


def reference_walk(cell, seed: int, prec: str = "f32",
                   sweeps: Optional[int] = None):
    """The reference walk over the run's weights and tokens, layer by
    layer: yields (layer, float32 leaves, {leaf: (q, delta, z_lo)},
    {tap: H}). `prec` and `sweeps` (default: the traffic's) change the
    solve, for the control and the planted faults."""
    import jax
    import jax.numpy as jnp
    from bench.reference import comq as ref
    dm = weights.dims(cell.config["model"])
    gain = float(cell.config["weights"]["out_gain"])
    quant = dict(cell.traffic)
    if sweeps is not None:
        quant["sweeps"] = sweeps
    key = weights.seed_key(seed)
    layer_w = jax.jit(lambda k, l: weights.dense_layer(k, l, dm, gain))
    x = jnp.take(jax.jit(lambda k: weights.dense_embed(k, dm))(key),
                 jnp.asarray(_tokens(cell.traffic, seed, dm["vocab"])),
                 axis=0).astype(jnp.bfloat16)
    for l in range(dm["n_layers"]):
        lw, ln1, ln2 = layer_w(key, l)
        x, solved, grams = ref.walk_layer(
            x, lw, ln1, ln2, dm=dm, model=cell.config["model"],
            quant=quant, prec=prec)
        yield l, lw, solved, grams


def compare(cell, seed: int, sources: Dict[str, Any]
            ) -> Dict[str, Dict[str, Any]]:
    """Hold each source of codes against the float32 reference walk's
    solves, leaf by leaf, in one walk. A source is {layer: {leaf: (q,
    delta, z_lo)}} or an iterator yielding each layer's such dict in turn
    (a walk run beside the reference's).

    Compared, per source:
    - `exact_tap_code_mismatch`: the largest share of codes that differ
      on the leaves whose tap the reference reproduces bit for bit (layer
      0's attn_in: the embedded tokens through one norm, all bf16-exact),
      where a sound solve differs only on rare ties;
    - `err_excess_max`: over every leaf of every layer, the largest
      excess of the source's reconstruction error on the reference's Gram
      over the reference solve's, sqrt(e_source / e_ref) - 1. Every other
      leaf's tap passes through bf16 products and attention that round
      differently in any second implementation, so its codes differ from
      the reference's (`other_code_mismatch`, printed); the error they
      leave on the reference's own problem is what a sound solve keeps.
    Printed: `err_vs_rtn_max`, the largest ratio of the source's error to
    round-to-nearest's on the same Gram, sqrt(e_source / e_rtn)."""
    import jax
    import jax.numpy as jnp
    from bench.reference import comq as ref
    bits, lam = int(cell.traffic["bits"]), float(cell.traffic["lam"])
    layers = {k: iter(v.values()) if isinstance(v, dict) else iter(v)
              for k, v in sources.items()}
    acc = {k: {"exact": [], "mismatch": [], "excess": [], "rtn": []}
           for k in sources}
    for l, lw, solved, grams in reference_walk(cell, seed):
        got = {k: next(it) for k, it in layers.items()}
        for name, (q_r, d_r, z_r) in solved.items():
            h = grams[ref.leaf_tap(name)[0]]
            e_r = jnp.sum(ref.err2(h, lw[name], q_r, d_r, z_r))
            e_n = jnp.sum(ref.err2(h, lw[name], *ref.rtn(lw[name], bits,
                                                         lam)))
            exact = l == 0 and ref.leaf_tap(name)[0] == "attn_in"
            for k, codes in got.items():
                q_p, d_p, z_p = codes[name]
                a = acc[k]
                share = jnp.mean((q_p != q_r).astype(jnp.float32))
                (a["exact"] if exact else a["mismatch"]).append(share)
                e_p = jnp.sum(ref.err2(h, lw[name], q_p, d_p, z_p))
                a["excess"].append(jnp.sqrt(e_p / e_r) - 1.0)
                a["rtn"].append(jnp.sqrt(e_p / e_n))
    out = {}
    for k, a in acc.items():
        x, m, e, r = jax.device_get(tuple(jnp.stack(a[n]) for n in
                                          ("exact", "mismatch", "excess",
                                           "rtn")))
        out[k] = {"exact_tap_code_mismatch": float(np.max(x)),
                  "err_excess_max": float(np.max(e)),
                  "err_vs_rtn_max": float(np.max(r)),
                  "other_code_mismatch": [float(v) for v in m],
                  "err_excess": [float(v) for v in e]}
    return out


def control(cell, seed: int, outcome: Outcome) -> Dict[str, Dict]:
    """Readings of what is put in the program's place, held against the
    float32 walk in one pass: `bf16_solve`, the control (the reference
    walk with its solve's products in bf16), and `unswept`, the fault of
    a solve that returns its starting state (the reference walk with no
    sweep: round-to-nearest on the initial grid)."""
    def codes(**kw):
        return (solved for _, _, solved, _ in
                reference_walk(cell, seed, **kw))
    return compare(cell, seed, {"bf16_solve": codes(prec="bf16"),
                                "unswept": codes(sweeps=0)})


def work(cell, run: Dict[str, Any]) -> Dict[str, Any]:
    """Algorithm FLOPs of the layers quantized in the traced window: the
    four tap Grams (2 T K^2 each), the layer forward (2 T per weight), and
    COMQ (2 K^2 N per sweep per leaf). Fixed by the paper's algorithm."""
    from bench import counts
    dm, T = run["dm"], run["tokens"]
    shapes = counts.leaf_shapes(dm)
    gram = 2.0 * T * (2 * dm["d_model"] ** 2 + dm["q_dim"] ** 2
                      + dm["d_ff"] ** 2)
    fwd = 2.0 * T * sum(k * n for k, n in shapes.values())
    solve = run["sweeps"] * sum(2.0 * k * k * n for k, n in shapes.values())
    return {"algorithm_flops": run["layers"] * (gram + fwd + solve)}
