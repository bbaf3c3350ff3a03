"""Serving launcher: continuous-batching generation from a (optionally
COMQ-quantized, optionally packed-on-disk) checkpoint or a fresh init.

    # quantize, save the packed checkpoint, serve packed (no materialize)
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --smoke \
        --quantize --bits 4 --save-quantized /tmp/q.pkl \
        --num-requests 4 --max-new 16 --mixed --stagger 2

    # later runs start straight from the packed checkpoint
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --smoke \
        --load-quantized /tmp/q.pkl --num-requests 4 --max-new 16

    # fault-tolerant serving: journal every request, inject a kill, then
    # resume — the replayed streams are token-identical
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --smoke \
        --journal /tmp/j --inject kill:5 --restarts 2

`--engine paged` (default) drives serve.Runtime — paged KV cache,
priority admission with preemption-by-page-reclaim (`--admission reserve`
keeps the legacy full-lifetime reservation for A/B), mixed prompt
lengths, staggered arrivals. `--engine static` keeps the equal-length
Engine baseline. `--materialize` dequantizes to a dense tree first;
without it quantized params are served as a packed QT-leaf tree.

`--journal DIR` appends every request lifecycle to a crash-replay journal
(fsync-gated); `--resume` rebuilds the queue from DIR instead of
synthesizing prompts; `--restarts N` wraps the drain in the
`ft.run_with_restarts` supervisor (progress = retired requests, so the
attempt budget resets whenever any request completes); `--inject SPEC`
seeds deterministic faults (e.g. "page_alloc:3+7,kill:5").
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import (load_packed_ckpt, pack_tree, save_packed_ckpt,
                        strip_for_serving, tree_bytes, unpack_tree)
from repro.configs import get_config, get_smoke_config
from repro.core import (QuantSpec, materialize, quantize_model,
                        serving_params)
from repro.ft import (FaultInjector, Heartbeat, Journal, SimulatedKill,
                      run_with_restarts)
from repro.launch.jax_cache import enable_compile_cache
from repro.models import BuildPlan, count_params, init_params
from repro.obs import MetricsRegistry, Tracer, next_trace_path
from repro.serve import (Engine, Runtime, ServeConfig, blocks_for,
                         recover_runtime)


def _quantize(params, cfg, plan, bits: int):
    key = jax.random.PRNGKey(0)
    calib = jax.random.randint(key, (4, 64), 0, cfg.vocab_size)
    ve = None
    if cfg.family == "vlm":
        ve = jax.random.normal(
            key, (4, cfg.cross_attn.n_vision_tokens,
                  cfg.cross_attn.vision_dim), jnp.bfloat16)
    spec = QuantSpec(bits=bits, granularity="per_channel",
                     lam=0.9, sweeps=3, order="greedy")
    qparams, report = quantize_model(params, cfg, plan, calib, spec,
                                     vision_embeds=ve)
    print(f"quantized {len(report.layers)} projections; COMQ vs RTN "
          f"reconstruction improvement {report.total_improvement():.1%}")
    return qparams


def main(argv=None):
    """Parse `argv` (default: sys.argv) and serve; prints the JSON summary
    line and returns it as a dict."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--quantize", action="store_true")
    ap.add_argument("--bits", type=int, default=4)
    ap.add_argument("--save-quantized", metavar="PATH", default=None,
                    help="pack_tree the quantized tree to PATH "
                         "(headered + crc32-checksummed single file)")
    ap.add_argument("--load-quantized", metavar="PATH", default=None,
                    help="serve from a packed quantized tree on disk "
                         "instead of re-quantizing (validated header)")
    ap.add_argument("--materialize", action="store_true",
                    help="dequantize to dense before serving (default: "
                         "serve the packed QT tree)")
    ap.add_argument("--engine", choices=("paged", "static"), default="paged")
    ap.add_argument("--num-requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--mixed", action="store_true",
                    help="vary prompt lengths across requests")
    ap.add_argument("--stagger", type=int, default=0, metavar="N",
                    help="submit N requests up front, the rest one per "
                         "decode step (arrival-over-time)")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--stop-token", type=int, action="append", default=[],
                    metavar="ID", help="stop-token id(s): generation ends "
                    "when one is sampled (repeatable; paged engine only)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=0.0)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="0 -> sized for num_requests at full length")
    ap.add_argument("--kv-bits", type=int, default=0, choices=(0, 4, 8),
                    help="quantize the paged KV pool: 8/4-bit page codes "
                         "with per-(layer, page, kv_head) scales, "
                         "dequantized inside the attention kernel "
                         "(0 = bf16 pages; paged engine only)")
    ap.add_argument("--admission", choices=("preempt", "reserve"),
                    default="preempt",
                    help="preempt: incremental pages + preemption-by-page-"
                         "reclaim; reserve: legacy full-lifetime "
                         "reservation (A/B)")
    ap.add_argument("--priorities", default=None, metavar="CSV",
                    help="per-request priority classes (lower = more "
                         "urgent), e.g. '0,1,1,0'; cycled if shorter "
                         "than --num-requests")
    ap.add_argument("--journal", default=None, metavar="DIR",
                    help="append a crash-replay request journal to DIR "
                         "(paged engine only)")
    ap.add_argument("--resume", action="store_true",
                    help="rebuild the queue from --journal DIR and replay "
                         "in-flight requests instead of submitting new "
                         "ones")
    ap.add_argument("--restarts", type=int, default=0, metavar="N",
                    help="supervise the drain with ft.run_with_restarts: "
                         "recover from the journal up to N consecutive "
                         "no-progress crashes (requires --journal)")
    ap.add_argument("--inject", default=None, metavar="SPEC",
                    help="deterministic fault schedule, e.g. "
                         "'page_alloc:3+7,decode_step:5,kill:9'")
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="write a Chrome-trace JSON (host spans + per-"
                         "request lifecycle events) to DIR/serve.gN."
                         "trace.json; inspect with chrome://tracing, "
                         "Perfetto, or `python -m repro.obs.report DIR` "
                         "(paged engine only)")
    ap.add_argument("--metrics", default=None, metavar="DIR",
                    help="dump the metrics registry (TTFT/ITL histograms, "
                         "pool gauges, preemption counters) to "
                         "DIR/metrics.jsonl + DIR/metrics.prom "
                         "(paged engine only)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    plan = BuildPlan(remat=False)
    if args.kv_bits:
        if args.engine == "static":
            print("note: --kv-bits quantizes the paged pool; the static "
                  "engine's dense cache ignores it")
        else:
            plan = plan.replace(kv_bits=args.kv_bits)
    if args.engine == "paged" and (cfg.attn_free or cfg.parallel_ssm_heads
                                   or cfg.family == "vlm"):
        print(f"note: {cfg.family}/attention-free archs use the dense-"
              "cache static engine (paged runtime is attention-family "
              "only; see ROADMAP)")
        args.engine = "static"
    if (args.resume or args.restarts) and not args.journal:
        raise SystemExit("--resume/--restarts need --journal DIR")
    # bf16 deployment baseline: 2 bytes/param regardless of master dtype
    # (analytic count — no dense tree is allocated just to measure it)
    bf16_bytes = 2 * count_params(cfg, plan)

    params = None
    qparams = None
    if args.load_quantized:
        blob = load_packed_ckpt(args.load_quantized)
        saved_arch = blob.get("arch")
        if saved_arch is not None and saved_arch != cfg.name:
            raise SystemExit(
                f"--load-quantized checkpoint is for arch {saved_arch!r}, "
                f"not {cfg.name!r} (pass the matching --arch/--smoke)")
        packed = jax.tree_util.tree_map(
            lambda x: jnp.asarray(x) if isinstance(x, np.ndarray) else x,
            blob["tree"])
        print(f"loaded packed tree: {tree_bytes(packed):,} bytes vs "
              f"{bf16_bytes:,} bf16 "
              f"({bf16_bytes / max(tree_bytes(packed), 1):.1f}x smaller)")
        qparams = unpack_tree(packed)
    elif args.quantize:
        params = init_params(jax.random.PRNGKey(0), cfg, plan)
        qparams = _quantize(params, cfg, plan, args.bits)

    if qparams is not None and args.save_quantized:
        packed = pack_tree(strip_for_serving(qparams))
        host = jax.tree_util.tree_map(
            lambda a: np.asarray(jax.device_get(a))
            if hasattr(a, "dtype") else a, packed)
        save_packed_ckpt(args.save_quantized, host, bits=args.bits,
                         arch=cfg.name)
        print(f"saved packed tree to {args.save_quantized}: "
              f"{tree_bytes(packed):,} bytes vs {bf16_bytes:,} bf16 "
              f"({bf16_bytes / tree_bytes(packed):.1f}x smaller)")

    packed_serve = False
    if qparams is not None:
        if args.materialize or args.engine == "static":
            params = materialize(qparams, cfg)
        else:
            params = serving_params(qparams, cfg)
            packed_serve = True
    elif params is None:
        params = init_params(jax.random.PRNGKey(0), cfg, plan)

    rs = np.random.RandomState(0)
    lens = [args.prompt_len] * args.num_requests
    if args.mixed:
        if args.engine == "static":
            print("note: --engine static only batches equal-length "
                  "prompts; ignoring --mixed")
        else:
            lens = [max(4, int(l)) for l in
                    rs.randint(args.prompt_len // 2, args.prompt_len + 1,
                               args.num_requests)]
    prompts = [rs.randint(0, cfg.vocab_size, (l,)).astype(np.int32)
               for l in lens]
    priorities = [0] * args.num_requests
    if args.priorities:
        cycle = [int(p) for p in args.priorities.split(",")]
        priorities = [cycle[i % len(cycle)] for i in range(args.num_requests)]

    t0 = time.time()
    if args.engine == "static":
        engine = Engine(params, cfg, plan,
                        max_len=args.prompt_len + args.max_new)
        out = engine.generate_batch(
            np.stack(prompts),
            max_new_tokens=args.max_new, temperature=args.temperature)
        dt = time.time() - t0
        summary = {
            "arch": cfg.name, "engine": "static",
            "requests": args.num_requests, "new_tokens": int(out.size),
            "seconds": round(dt, 2),
            "tok_per_s": round(out.size / dt, 1),
            "sample": out[0, :8].tolist(),
        }
        print(json.dumps(summary))
        return summary

    bucket = 1 << max(args.prompt_len - 1, 1).bit_length()
    maxb = blocks_for(bucket + args.max_new, args.block_size)
    num_blocks = args.num_blocks or maxb * min(args.num_requests, 8)
    serve_cfg = ServeConfig(max_slots=min(args.num_requests, 8),
                            block_size=args.block_size,
                            num_blocks=num_blocks,
                            buckets=(bucket // 4, bucket // 2, bucket),
                            max_blocks_per_slot=maxb,
                            policy=args.admission)
    if plan.kv_bits:
        from repro.serve import paged_cache_bytes
        pool_b = paged_cache_bytes(cfg, plan, num_blocks, args.block_size)
        bf16_b = paged_cache_bytes(cfg, plan.replace(kv_bits=0),
                                   num_blocks, args.block_size)
        print(f"kv pages: int{plan.kv_bits} pool {pool_b:,} bytes vs "
              f"{bf16_b:,} bf16 ({bf16_b / pool_b:.2f}x smaller)")
    injector = FaultInjector.parse(args.inject) if args.inject else None
    # observability (DESIGN.md §10): absent flags keep the runtime on the
    # zero-cost null singletons (the static-engine branch returned above)
    tracer = Tracer(run=f"serve:{cfg.name}") if args.trace else None
    registry = (MetricsRegistry(run=f"serve:{cfg.name}")
                if args.metrics else None)
    hb = Heartbeat(args.journal, host_id=0) if args.journal else None
    kw = dict(max_new_tokens=args.max_new, temperature=args.temperature,
              top_k=args.top_k, top_p=args.top_p,
              stop_tokens=tuple(args.stop_token))

    box = {}           # box["rt"] is set as soon as a runtime exists, so a
                       # crash inside build() still lets the supervisor
                       # close that attempt's journal handle before retrying

    def build(resume: bool):
        if resume:
            rt, state = recover_runtime(params, cfg, plan, args.journal,
                                        serve_cfg, injector=injector,
                                        tracer=tracer, metrics=registry)
            box["rt"] = rt
            print(f"resume: {len(state.completed)} retired in journal, "
                  f"replaying {len(state.inflight)} in-flight")
            reqs = list(rt.scheduler.queue)
            if not args.resume:
                # restart of *this* launch: prompts map 1:1 to rids in
                # submission order, so any prompt past max_rid crashed
                # before its submit record was durable — re-submit it
                # rather than lose it
                for p, pr in zip(prompts[state.max_rid + 1:],
                                 priorities[state.max_rid + 1:]):
                    reqs.append(rt.submit(p, priority=pr, **kw))
            return rt, reqs
        journal = Journal(args.journal) if args.journal else None
        rt = Runtime(params, cfg, plan, serve_cfg, journal=journal,
                     injector=injector, tracer=tracer, metrics=registry)
        box["rt"] = rt
        n_up_front = args.stagger if args.stagger > 0 else len(prompts)
        reqs = [rt.submit(p, priority=pr, **kw)
                for p, pr in zip(prompts[:n_up_front],
                                 priorities[:n_up_front])]
        for p, pr in zip(prompts[n_up_front:], priorities[n_up_front:]):
            rt.step()
            reqs.append(rt.submit(p, priority=pr, **kw))
        return rt, reqs

    if args.restarts > 0:

        def attempt(_):
            prev = box.pop("rt", None)
            if prev is not None and prev.journal is not None:
                prev.journal.close()
            # a crash inside build() (e.g. during staggered submits) has
            # already journaled some requests, so decide resume from the
            # journal itself, not from whether build() ever returned
            resume = args.resume or bool(Journal.replay(args.journal).records)
            rt, reqs = build(resume)
            box["reqs"] = reqs
            if hb is not None:   # watchdog file inspectable mid-run
                hb.beat(rt.steps, metrics=rt.metrics_snapshot())
            out = rt.run()
            if hb is not None:
                hb.beat(rt.steps, metrics=rt.metrics_snapshot())
            return out

        def progress():
            return len(Journal.replay(args.journal).completed)

        metrics = run_with_restarts(
            attempt, progress, max_restarts=args.restarts,
            exceptions=(RuntimeError, SimulatedKill), backoff_s=0.0)
        rt, reqs = box["rt"], box["reqs"]
    else:
        rt, reqs = build(args.resume)
        metrics = rt.run()
        if hb is not None:
            hb.beat(rt.steps, metrics=rt.metrics_snapshot())

    if tracer is not None:
        tpath = next_trace_path(args.trace, "serve")
        tracer.save(tpath)
        print(f"trace: {tpath} ({len(tracer.events)} events)")
    if registry is not None:
        registry.dump_jsonl(os.path.join(args.metrics, "metrics.jsonl"))
        registry.dump_prometheus(os.path.join(args.metrics, "metrics.prom"))
        print(f"metrics: {args.metrics}/metrics.jsonl + metrics.prom")

    metrics.update({
        "arch": cfg.name, "engine": "paged",
        "admission": args.admission,
        "packed_qt": packed_serve,
        "prompt_lens": [int(r.prompt_len) for r in reqs],
        "out_tokens": sum(len(r.out_tokens) for r in reqs),
        "ttft_s": [round(t, 4) for t in metrics["ttft_s"]],
        "sample": reqs[0].out_tokens[:8] if reqs else [],
    })
    if injector is not None:
        metrics["faults_fired"] = injector.fired
    metrics = {k: (round(v, 4) if isinstance(v, float) else v)
               for k, v in metrics.items()}
    print(json.dumps(metrics))
    return metrics


if __name__ == "__main__":
    main()
