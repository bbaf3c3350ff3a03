"""COMQ quantization launcher: calibrate → quantize → quantized checkpoint.

    PYTHONPATH=src python -m repro.launch.quantize --arch qwen2-7b --smoke \
        --bits 4 --order greedy --granularity per_channel --sweeps 3

At scale the per-channel solve runs with output columns sharded over the
full mesh (COMQ's solve needs zero communication — DESIGN.md §4); here the
same code path runs on local devices against the smoke configs.

Crash-safe runs (DESIGN.md §8): `--journal DIR` journals every solved
leaf durably (solve → spill → journal) and `--restarts N` supervises the
run with ft.run_with_restarts — on a crash (or an injected `--inject
kill:…` fault) the surviving journal resumes the walk, re-applying
journaled leaves bit-identically instead of re-solving them. The
journaled-leaf count is the supervisor's progress signal and a
ft.Heartbeat in the journal directory tracks liveness.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import (CheckpointManager, pack_tree, policy_extra,
                        save_packed_ckpt, strip_for_serving, tree_bytes)
from repro.configs import get_config, get_smoke_config
from repro.core import (QuantSpec, parse_policy, policy_from_budget,
                        quantize_model, serving_params)
from repro.ft import (FaultInjector, Heartbeat, QuantJournal,
                      run_with_restarts)
from repro.launch.jax_cache import enable_compile_cache
from repro.models import BuildPlan, init_params, lm_loss
from repro.obs import MetricsRegistry, Tracer, next_trace_path


def main(argv=None):
    """Parse `argv` (default: sys.argv), quantize, save, evaluate; prints
    the JSON summary line and returns it as a dict."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--bits", type=int, default=4)
    ap.add_argument("--granularity", default="per_channel",
                    choices=["per_channel", "per_layer"])
    ap.add_argument("--order", default="greedy",
                    choices=["greedy", "cyclic", "greedy_shared"])
    ap.add_argument("--sweeps", type=int, default=3)
    ap.add_argument("--lam", type=float, default=0.9)
    ap.add_argument("--method", default="comq",
                    choices=["comq", "comq_blocked", "rtn", "gptq"])
    ap.add_argument("--calib-batch", type=int, default=8)
    ap.add_argument("--calib-seq", type=int, default=128)
    ap.add_argument("--propagation", default="staged",
                    choices=["staged", "legacy"],
                    help="staged = one forward per layer (default); "
                         "legacy = two-forward A/B schedule")
    ap.add_argument("--shard-data", action="store_true",
                    help="shard the calibration batch over the mesh data "
                         "axis (repro.dist: one Gram psum per tap)")
    ap.add_argument("--shard-solve", type=int, default=0, metavar="TP",
                    help="shard solve columns over a model axis of this "
                         "size (0 = off; with --shard-data the remaining "
                         "devices form the data axis). Zero-communication, "
                         "bit-identical for per-channel comq_blocked/rtn "
                         "(DESIGN.md §4.3); other methods keep replicated "
                         "solves.")
    ap.add_argument("--policy", default=None, metavar="RULES",
                    help="per-leaf mixed-precision rules, e.g. "
                         "'*.w_down=8,first=8,last=8,kv=8' — patterns "
                         "match '{layer}.{leaf}' then the bare leaf name "
                         "(core/policy.py; --bits stays the base width)")
    ap.add_argument("--bits-budget", type=float, default=0.0, metavar="BPP",
                    help="allocate per-leaf bit widths (2/3/4/8) under "
                         "this bits-per-param budget with the greedy "
                         "backprop-free knapsack on layerwise H-space "
                         "errors (overrides --policy rules)")
    ap.add_argument("--out-dir", default="/tmp/repro_quant")
    ap.add_argument("--journal", default=None, metavar="DIR",
                    help="journal directory: durably record every solved "
                         "leaf so a crashed run can --resume bit-"
                         "identically (ft.QuantJournal, DESIGN.md §8)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from --journal (also implied when the "
                         "journal already has leaves under --restarts)")
    ap.add_argument("--restarts", type=int, default=0, metavar="N",
                    help="supervise the run with ft.run_with_restarts: up "
                         "to N restarts without progress (journaled-leaf "
                         "count), resuming from --journal after each")
    ap.add_argument("--inject", default=None, metavar="SPEC",
                    help="deterministic fault injection, e.g. 'kill:2' or "
                         "'leaf_solve:3,ckpt_write:1' (ft.FaultInjector; "
                         "points: gram_accumulate, leaf_solve, ckpt_write, "
                         "kill, nan_tap)")
    ap.add_argument("--save-packed", default=None, metavar="PATH",
                    help="also save the packed tree as one atomic "
                         "checksummed file (byte-deterministic — the CI "
                         "fault-smoke compares these across runs)")
    ap.add_argument("--no-guards", action="store_true",
                    help="disable the numeric guards (core/guards); "
                         "healthy runs are bit-identical either way")
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="write a Chrome-trace JSON of the run (layer + "
                         "leaf_solve spans; open in chrome://tracing or "
                         "Perfetto, or summarize with `python -m "
                         "repro.obs.report DIR`)")
    ap.add_argument("--metrics", default=None, metavar="DIR",
                    help="dump the quant.* metrics registry (layers/"
                         "leaves counters, per-leaf error + seconds "
                         "histograms) as metrics.jsonl + metrics.prom")
    args = ap.parse_args(argv)
    if args.restarts and not args.journal:
        raise SystemExit("--restarts needs --journal (resume source)")
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    plan = BuildPlan(remat=False)
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg, plan)
    tokens = jax.random.randint(key, (args.calib_batch, args.calib_seq), 0,
                                cfg.vocab_size)
    ve = None
    if cfg.family == "vlm":
        ve = jax.random.normal(key, (args.calib_batch,
                                     cfg.cross_attn.n_vision_tokens,
                                     cfg.cross_attn.vision_dim), jnp.bfloat16)

    base = QuantSpec(bits=args.bits, granularity=args.granularity,
                     lam=args.lam, sweeps=args.sweeps, order=args.order)
    spec = base
    parsed = parse_policy(args.policy, base) if args.policy else None
    if args.bits_budget:
        # the budget allocation supersedes explicit bit rules, but the
        # kv rider still applies (it is orthogonal to weight widths)
        if parsed is not None and (parsed.rules
                                   or parsed.first_layer_bits is not None
                                   or parsed.last_layer_bits is not None):
            print("# note: --bits-budget supersedes the --policy bit "
                  "rules; only its kv= rider is kept")
        kv = parsed.kv_bits if parsed is not None else 0
        spec, alloc, sizes = policy_from_budget(params, cfg, plan, tokens,
                                                base, args.bits_budget,
                                                kv_bits=kv)
        hist = {}
        for b in alloc.values():
            hist[b] = hist.get(b, 0) + 1
        print(f"# bit allocation under {args.bits_budget} bits/param: "
              f"{dict(sorted(hist.items()))}")
    elif parsed is not None:
        spec = parsed
    if spec is not base and spec.kv_bits:
        if spec.kv_bits not in (4, 8):
            raise SystemExit(f"kv={spec.kv_bits} unsupported (0, 4 or 8)")
        if spec.kv_bits == 8:
            # dense eval cache quantizes per-entry at int8 (core/apply.py)
            plan = plan.replace(cache_quant=True)
        # the paged runtime consumes the same rider as page codes with
        # per-(layer, page, kv_head) scales (serve --kv-bits; 4-bit has no
        # dense-cache analogue, so eval there runs bf16 caches)
        plan = plan.replace(kv_bits=spec.kv_bits)
    mesh = None
    if args.shard_solve:
        from repro.dist import calib_mesh
        mesh = calib_mesh(model=args.shard_solve,
                          data=None if args.shard_data else 1)
        from repro.core import as_policy
        from repro.core.pipeline import _col_shardable
        if not _col_shardable(as_policy(spec).base, args.method):
            print(f"# note: method={args.method} granularity="
                  f"{args.granularity} is not column-shardable; solves "
                  "stay replicated (see DESIGN.md §4.3)")
    elif args.shard_data:
        from repro.dist import data_mesh
        mesh = data_mesh()
    # quality: eval loss fp vs quantized on a held-out batch. The fp loss
    # is taken before the solve so the f32 master copy can be dropped
    # after it: the quantized loss runs on the packed serving tree, and
    # no two dense f32 copies of the model are ever live at once.
    ev = jax.random.randint(jax.random.PRNGKey(7),
                            (args.calib_batch, args.calib_seq), 0,
                            cfg.vocab_size)
    batch = {"tokens": ev, "labels": ev}
    if ve is not None:
        batch["vision_embeds"] = ve
    eval_loss = jax.jit(lambda p, b: lm_loss(p, cfg, plan, b)[0])
    fp_loss = float(eval_loss(params, batch))
    dense_bytes = sum(l.size * l.dtype.itemsize for l in
                      jax.tree_util.tree_leaves(params))

    injector = FaultInjector.parse(args.inject) if args.inject else None
    # observability (DESIGN.md §10): absent flags keep the pipeline on the
    # zero-cost null singletons
    tracer = Tracer(run=f"quantize:{cfg.name}") if args.trace else None
    registry = (MetricsRegistry(run=f"quantize:{cfg.name}")
                if args.metrics else None)
    hb = Heartbeat(args.journal, host_id=0) if args.journal else None
    progress_cb = None
    if hb is not None:
        # the heartbeat doubles as a liveness + health publisher: each
        # layer beat carries the current metrics snapshot when enabled
        def progress_cb(layer):
            hb.beat(layer, metrics=(registry.snapshot()
                                    if registry is not None else None))

    def run_once(resume: bool):
        return quantize_model(params, cfg, plan, tokens, spec,
                              method=args.method, vision_embeds=ve,
                              propagation=args.propagation, mesh=mesh,
                              guards=not args.no_guards,
                              journal=args.journal, resume=resume,
                              injector=injector, progress_cb=progress_cb,
                              tracer=tracer, metrics=registry)

    t0 = time.time()
    if args.journal:
        box = {}

        def attempt(_):
            # resume whenever the journal already holds leaves of this (or
            # an explicitly-resumed) run; assert journal↔spill integrity
            # before trusting any of them
            resume = args.resume or bool(
                QuantJournal.replay(args.journal).leaves)
            if resume:
                QuantJournal.check_integrity(args.journal)
            box["out"] = run_once(resume)

        def progress():
            return len(QuantJournal.replay(args.journal).leaves)

        run_with_restarts(attempt, progress, max_restarts=args.restarts,
                          exceptions=(RuntimeError,), backoff_s=0.0)
        qparams, report = box.pop("out")
    else:
        qparams, report = run_once(args.resume)
    dt = time.time() - t0

    # quantized checkpoint: the stripped serving tree (top-level params +
    # the __qlayers__ table, each QTensor packed to its own bit width) —
    # exactly what `serve --load-quantized` reads — plus the policy
    # metadata that produced it (ckpt.restore_policy reads it);
    # CheckpointManager writes are atomic+fsynced (tmp → rename)
    served = strip_for_serving(qparams)
    qparams = params = None          # drop the f32 master copy
    packed = pack_tree(served)
    mgr = CheckpointManager(args.out_dir, keep=2)
    mgr.save(0, packed, extra=policy_extra(policy=spec, arch=cfg.name,
                                           bits=args.bits))
    if args.save_packed:
        # single-file form with deterministic bytes (npz embeds zip
        # timestamps; pickled host arrays do not) — what the CI fault
        # smoke byte-compares between faulted-resumed and clean runs
        host = jax.tree_util.tree_map(
            lambda a: np.asarray(jax.device_get(a))
            if isinstance(a, jax.Array) else a, packed)
        save_packed_ckpt(args.save_packed, host, arch=cfg.name,
                         bits=args.bits)
        del host
    if cfg.family == "vlm":
        from repro.core import materialize
        qeval = materialize(served, cfg)
    else:
        qeval = serving_params(served, cfg)
    q_loss = float(eval_loss(qeval, batch))
    del qeval

    if tracer is not None:
        tp = next_trace_path(args.trace, "quantize")
        tracer.save(tp)
        print(f"# trace: {tp} ({len(tracer.events)} events)")
    if registry is not None:
        registry.dump_jsonl(os.path.join(args.metrics, "metrics.jsonl"))
        registry.dump_prometheus(os.path.join(args.metrics, "metrics.prom"))
        print(f"# metrics: {args.metrics}/metrics.jsonl + metrics.prom")

    from repro.core import QuantPolicy
    summary = {
        "arch": cfg.name, "method": args.method, "bits": args.bits,
        "mixed_policy": (isinstance(spec, QuantPolicy)
                         and not spec.is_uniform()),
        "bits_budget": args.bits_budget or None,
        "propagation": args.propagation,
        "data_shards": 1 if mesh is None else int(mesh.shape["data"]),
        "model_shards": 1 if mesh is None else int(mesh.shape.get("model",
                                                                  1)),
        "order": args.order, "granularity": args.granularity,
        "layers_quantized": len(report.layers),
        "comq_vs_rtn_error_improvement": round(report.total_improvement(), 4),
        "fp_loss": round(fp_loss, 4), "quant_loss": round(q_loss, 4),
        "seconds": round(dt, 1),
        "ckpt_bytes": tree_bytes(packed),
        "dense_bytes": dense_bytes,
        "compression": round(dense_bytes / max(tree_bytes(packed), 1), 1),
        "guard_events": len(report.guard_events),
        "resumed_leaves": report.resumed_leaves,
        "faults_fired": (len(injector.fired) if injector is not None
                         else 0),
    }
    print(json.dumps(summary))
    return summary


if __name__ == "__main__":
    main()
