"""Offline batch decode through the program's paged serving runtime.

Set-up: the packed 4-bit serving tree is drawn from the seed on the device
(`bench.weights`), a `repro.serve.Runtime` is built over it, one request per
prefill bucket warms every prefill program, the decode program and the
token pickers, and the compiled decode program must hold both Pallas
kernels. Then the whole backlog is submitted and the runtime steps until
every slot is running.

Window: `Runtime.step()` back to back for `seconds`. Tokens per second are
every token the steps emit (first tokens of admitted requests included)
over the window's wall time; each step ends with the host pulling that
step's tokens, so the clock reads finished work. A traced run profiles the
window's last `TRACE_SECONDS`, where retirements bring admissions.

Check: after the window, with the runtime freed, a sample of the requests
that were served (the one with the most served tokens among them, and
each with every token it was served) is run through the plain reference
(`bench/reference/dense_gqa.py`); the widest gap by which a served token's
reference logit lies below the reference's best is printed, and the mean
of that gap over the served tokens is held to the cell's limit. The
widest gap is not compared: its readings on sound runs and on the fp8
control lie within 2.5x of each other (PERF.md), so no limit separates
them. The backlog must not run dry inside the window.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import numpy as np

from bench import counts, traffic, weights
from bench.harness import (TRACE_SECONDS, Outcome, annotate, checks_for,
                           device_info, log, memory_note, stage)

# the kernels the compiled decode program must hold, by page width
DECODE_KERNELS = {0: ("quant_matmul", "paged_attention"),
                  8: ("quant_matmul", "paged_attention_quant")}


def model_config(cfg_file: Dict[str, Any]):
    """The program's ModelConfig: the registry's family template with
    every width and setting taken from the configuration file."""
    from repro.configs import get_config
    m = cfg_file["model"]
    dm = weights.dims(m)
    if m.get("hidden_act", "silu") != "silu" or m.get("tie_word_embeddings"):
        raise ValueError("the dense GQA reference covers silu-gated, "
                         "untied models only")
    return get_config(cfg_file["registry"]).replace(
        n_layers=dm["n_layers"], d_model=dm["d_model"],
        n_heads=dm["n_heads"], n_kv_heads=dm["n_kv"],
        head_dim=dm["head_dim"], d_ff=dm["d_ff"], vocab_size=dm["vocab"],
        sliding_window=int(m.get("sliding_window") or 0),
        rope_theta=float(m["rope_theta"]), norm_eps=float(m["rms_norm_eps"]),
        qkv_bias=False, tie_embeddings=False, compute_dtype="bfloat16")


def custom_calls(hlo: str) -> set:
    """Names of the Pallas kernels in compiled HLO text."""
    names = set()
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            names.add(line.split("=", 1)[0].strip().lstrip("%")
                      .rsplit(".", 1)[0])
    return names


def decode_kernels(rt) -> set:
    """Pallas kernels in the runtime's compiled decode program (a
    persistent-cache hit once the warm-up has compiled it)."""
    import jax
    import jax.numpy as jnp
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    B = rt.serve_cfg.max_slots
    args = (jax.tree_util.tree_map(sds, rt.params),
            jax.tree_util.tree_map(sds, rt.pool),
            jax.ShapeDtypeStruct((B, rt.maxb), jnp.int32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32))
    return custom_calls(rt._decode.lower(*args).compile().as_text())


def _decode_batch(rt, done_before: int) -> List[int]:
    """Live context of every slot the last step decoded: the requests
    still running and those it retired."""
    reqs = list(rt.scheduler.running.values()) + \
        rt.scheduler.completed[done_before:]
    return [r.prompt_len + len(r.out_tokens) - 1 for r in reqs]


def run(cell, seed: int, seconds: float, trace: bool, env) -> Outcome:
    import jax
    from repro.models import BuildPlan
    from repro.obs.trace import Tracer
    from repro.serve import Runtime, ServeConfig, blocks_for

    tr, sv = cell.traffic, cell.config["serve"]
    dm = weights.dims(cell.config["model"])
    gain = float(cell.config["weights"]["out_gain"])
    cfg = model_config(cell.config)
    kv_bits = int(sv.get("kv_bits", 0))
    plan = BuildPlan(remat=False, kv_bits=kv_bits)
    slots, bs = int(sv["max_slots"]), int(sv["block_size"])
    buckets = tuple(int(b) for b in tr["prefill_buckets"])

    with annotate("bench.weights"):
        params = weights.program_params(seed, dm, gain, cfg)
        jax.block_until_ready(params)
    stage(env.t_start, "weights")
    backlog = traffic.backlog(tr, seed, dm["vocab"], slots)
    longest = int(tr["prompt"]["max"]) + int(tr["output"]["max"])
    sc = ServeConfig(max_slots=slots, block_size=bs,
                     num_blocks=int(sv["num_blocks"]), buckets=buckets,
                     max_blocks_per_slot=blocks_for(longest, bs))
    tracer = Tracer(run=cell.name) if trace else None
    rt = Runtime(params, cfg, plan, sc, tracer=tracer)
    memory_note(env.devices, "weights and pool")

    stage(env.t_start, "runtime")
    # warm every program this traffic drives: one request per bucket
    rng = np.random.default_rng(seed ^ 0x5EED)
    with annotate("bench.warmup"):
        for b in buckets:
            rt.submit(rng.integers(0, dm["vocab"], b, dtype=np.int32),
                      max_new_tokens=2)
        rt.run()
    stage(env.t_start, "warm-up")
    kernels = set()
    if env.require_chip:
        kernels = decode_kernels(rt)
        want = set(DECODE_KERNELS[kv_bits])
        if not want <= kernels:
            raise RuntimeError(f"the compiled decode program lacks Pallas "
                               f"kernels {sorted(want - kernels)} (has "
                               f"{sorted(kernels)})")
    memory_note(env.devices, "warm")

    stage(env.t_start, "kernel check")
    with annotate("bench.submit"):
        reqs = [rt.submit(p, max_new_tokens=n) for p, n in backlog]
    with annotate("bench.fill"):
        while len(rt.scheduler.running) < slots:
            rt.step()

    stage(env.t_start, "fill")
    steps: List[List[int]] = []    # decoded contexts, traced steps
    count = [0, 0]                 # tokens, steps
    preempt0 = rt.scheduler.preemptions

    def stepping(until: float, record: bool) -> None:
        while True:
            done0 = len(rt.scheduler.completed)
            with annotate("bench.step"):
                count[0] += rt.step()
            count[1] += 1
            if record:
                steps.append(_decode_batch(rt, done0))
            if not rt.scheduler.queue:
                raise RuntimeError("the backlog ran dry inside the window; "
                                   "raise the traffic's backlog")
            if time.time() >= until:
                return

    env.counter.mark()
    t_open = time.time()
    end = t_open + seconds
    if trace and seconds > TRACE_SECONDS:
        # a traced run profiles the window's last stretch: by then requests
        # retire and admissions (batch-1 prefills) run among the steps
        stepping(end - TRACE_SECONDS, False)
    with env.window(trace) as win:
        stepping(end, trace)
    t_close = time.time()
    tokens = count[0]
    n_compiles, compile_s = env.counter.since_mark()
    window_s = t_close - t_open
    log(f"window: {window_s:.3f} s, {count[1]} steps, "
        f"{tokens} tokens, compiles in window: {n_compiles} "
        f"({compile_s:.3f} s), preemptions: "
        f"{rt.scheduler.preemptions - preempt0}, queued at close: "
        f"{len(rt.scheduler.queue)}")

    device = device_info(env.devices)
    served = [r for r in reqs if r.out_tokens]
    failed = sum(1 for r in reqs if r.cb_errors)
    attempted = len(served)
    seqs = _sample(served, int(tr["check_requests"]), seed)
    spans = tracer.events if tracer else []
    del rt, params, reqs
    gc.collect()

    from bench.reference import dense_gqa
    with annotate("bench.reference"):
        widest, mean = dense_gqa.served_gap(seed, dm, cell.config["model"],
                                            gain, seqs)
    checks = checks_for(cell.limits, {"served_logit_gap_mean": mean})
    n_served = sum(len(s) for _, s in seqs)
    log(f"reference: {len(seqs)} requests, {n_served} served tokens, "
        f"gap widest {widest!r} mean {mean!r}")
    out = Outcome(end_to_end={"tokens_per_s": tokens / window_s,
                              "setup_s": t_open - env.t_start},
                  attempted=attempted, failed=failed, checks=checks,
                  device=device, sample=seqs)
    if trace:
        out.trace = {"window": win, "steps": steps, "spans": spans,
                     "dm": dm, "slots": slots, "kv_bits": kv_bits,
                     "kernels": sorted(kernels)}
    return out


def control(cell, seed: int, outcome: Outcome) -> Dict[str, Dict]:
    """The control's readings of the check's numbers: the reference in fp8
    put in the program's place, on the sequences the run compared."""
    from bench.reference import dense_gqa
    dm = weights.dims(cell.config["model"])
    gain = float(cell.config["weights"]["out_gain"])
    widest, mean = dense_gqa.control_gap(seed, dm, cell.config["model"],
                                         gain, outcome.sample)
    return {"fp8": {"served_logit_gap": widest,
                    "served_logit_gap_mean": mean}}


def _sample(served, n: int, seed: int):
    """The request with the most served tokens and n-1 others drawn from
    the seed, each with every token served so far (finished or not)."""
    if not served:
        return []
    by_len = sorted(served, key=lambda r: (-len(r.out_tokens), r.rid))
    rest = by_len[1:]
    rng = np.random.default_rng(seed)
    pick = [by_len[0]] + [rest[i] for i in
                          rng.permutation(len(rest))[:n - 1]]
    return [(np.asarray(r.prompt, np.int32), list(r.out_tokens))
            for r in pick]


def work(cell, run: Dict[str, Any]) -> Dict[str, Any]:
    """Analytic work of the traced window's decode steps, one entry per
    step: quant_matmul and paged attention (flops, bytes), and the model
    FLOPs of every token decoded."""
    dm, steps = run["dm"], run["steps"]
    qm = counts.quant_matmul_step(dm, run["slots"])
    return {"quant_matmul": [qm] * len(steps),
            "paged_attention": [counts.paged_attention_step(
                dm, ctx, run["kv_bits"]) for ctx in steps],
            "model_flops": sum(counts.decode_token_flops(dm, c)
                               for ctx in steps for c in ctx)}
