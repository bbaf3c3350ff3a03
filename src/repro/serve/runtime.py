"""Continuous-batching serving runtime over the paged KV cache.

The runtime ties together:

* `serve/scheduler.py` — priority admission, prefill buckets, incremental
  page allocation + preemption-by-page-reclaim (or the legacy
  full-lifetime reservation under ``policy="reserve"``);
* `serve/kv_cache.py` — the paged pool + block tables + host allocator;
* `models/model.py::decode_step_paged` — one jitted decode program with
  per-slot positions, so slots at different sequence lengths (mixed
  lengths, staggered arrivals) share every decode step;
* `serve/sampler.py::sample_batch_seeded` — per-slot sampling settings as
  arrays, with every draw a pure function of (request seed, token index);
* `ft/journal.py` — optional crash-replay request journal: submits,
  first tokens and retirements are fsync-gated, and `recover_runtime`
  rebuilds the queue after a process death, replaying in-flight requests
  token-identically (bit-deterministic decode + seeded sampling);
* `ft/inject.py` — optional deterministic fault injection (page-alloc
  failure, decode-step exception, callback error, simulated kill) for the
  invariant tests.

Compile surface is bounded and static: one prefill program per bucket
length (resume extents round up to powers of two), one scatter program per
prefill-cache extent, one decode program, one sampler program. The pool is
donated through prefill-writes and decode steps so XLA updates pages in
place.

Preemption is recompute-based: the victim's pages are freed and it
re-queues; on re-admission the runtime re-prefills prompt + all emitted
tokens but the last, then feeds the last emitted token through the normal
decode step — every resumed token is produced by the same decode program
as an uninterrupted run, which is what makes preempt/resume
token-identity hold (and testable) rather than merely approximate.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.retrace import guard_jit
from repro.ft.inject import InjectedFault, SimulatedKill  # noqa: F401
from repro.ft.journal import Journal
from repro.models.model import decode_step_paged, forward
from repro.obs.metrics import NULL_METRICS, Histogram
from repro.obs.trace import NULL_TRACER
from repro.serve.kv_cache import (BlockAllocator, blocks_for,
                                  init_paged_cache, paged_cache_bytes,
                                  write_prefill)
from repro.serve.sampler import sample_batch_seeded
from repro.serve.scheduler import DEFAULT_BUCKETS, Request, Scheduler


def sample(logits, seed, count, temperature, top_k, top_p):
    """Per-slot seeded sampling of one decode step's tokens."""
    return sample_batch_seeded(logits, seed, count, temperature=temperature,
                               top_k=top_k, top_p=top_p)


def argmax(logits):
    """Greedy tokens of one decode step."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_slots: int = 4
    block_size: int = 16
    num_blocks: int = 64
    buckets: Tuple[int, ...] = DEFAULT_BUCKETS
    max_blocks_per_slot: Optional[int] = None
    rng_seed: int = 0
    policy: str = "preempt"          # "preempt" | "reserve" (legacy A/B)


class Runtime:
    """Continuous-batching runtime: submit() requests, run() to drain."""

    def __init__(self, params, cfg, plan, serve_cfg: ServeConfig = None,
                 journal: Optional[Journal] = None, injector=None,
                 tracer=None, metrics=None, mesh=None):
        if cfg.attn_free or cfg.parallel_ssm_heads or cfg.family == "vlm":
            raise NotImplementedError(
                f"paged runtime does not cover family={cfg.family!r} / "
                "attention-free / parallel-ssm archs — use serve.Engine")
        # Quantized pages (DESIGN.md §11): a `kv=` policy rider on the
        # paged path means integer page codes + per-(layer, page, kv_head)
        # scales, not the dense engine's per-slot int8 cache — so the plan
        # the prefill programs see must produce bf16 rows (cache_quant
        # off) for write_prefill to quantize page-wise on the way in.
        kv_bits = int(getattr(plan, "kv_bits", 0) or 0)
        if plan.cache_quant and kv_bits == 0:
            kv_bits = 8
        if kv_bits not in (0, 4, 8):
            raise ValueError(f"kv_bits must be 0, 4 or 8, got {kv_bits}")
        if kv_bits:
            plan = plan.replace(cache_quant=False, kv_bits=kv_bits)
        self.kv_bits = kv_bits
        self.params = params
        self.cfg = cfg
        self.plan = plan
        sc = serve_cfg or ServeConfig()
        self.serve_cfg = sc
        self.mesh = mesh
        if mesh is not None:
            from repro.dist.sharding import tp_size
            tp = tp_size(mesh)
        else:
            tp = 1
        self._tp = tp
        self.journal = journal
        self.injector = injector
        # observability (DESIGN.md §10): null singletons when disabled, so
        # every hook below is an unconditional call that costs nothing.
        # Instrument handles are resolved once here — hot-zone call sites
        # never do registry lookups, only a float add / list append on
        # values that are already host scalars (sync-free rule).
        self.tracer = tracer or NULL_TRACER
        self.metrics = metrics or NULL_METRICS
        self._m_ttft = self.metrics.histogram("serve.ttft_seconds")
        self._m_itl = self.metrics.histogram("serve.itl_seconds")
        self._m_tokens = self.metrics.counter("serve.tokens_emitted")
        self._m_retired = self.metrics.counter("serve.requests_retired")
        self._m_preempt = self.metrics.counter("serve.preemptions")
        self._m_admits = self.metrics.counter("serve.admits")
        self._m_resumes = self.metrics.counter("serve.resumes")
        self._m_free = self.metrics.gauge("serve.pool_free_blocks")
        self._m_occ = self.metrics.gauge("serve.pool_live_occupancy")
        self._m_pool_bytes = self.metrics.gauge("serve.pool_kv_bytes")

        fail_hook = None
        if injector is not None:
            fail_hook = lambda: injector.fire("page_alloc")  # noqa: E731
        self.allocator = BlockAllocator(sc.num_blocks, fail_hook=fail_hook,
                                        partitions=tp)
        self.scheduler = Scheduler(sc.max_slots, self.allocator,
                                   buckets=sc.buckets,
                                   block_size=sc.block_size,
                                   max_blocks_per_slot=sc.max_blocks_per_slot,
                                   policy=sc.policy)
        self.maxb = self.scheduler.max_blocks_per_slot
        self.pool = init_paged_cache(cfg, plan, sc.num_blocks, sc.block_size)
        # bytes per live page (codes + its share of the scale rows) — the
        # pool-bytes gauge below is a host multiply, never a device sync
        self._page_bytes = paged_cache_bytes(
            cfg, plan, sc.num_blocks, sc.block_size) // sc.num_blocks
        if mesh is not None:
            from repro.dist.sharding import named, paged_runtime_specs
            self._specs = paged_runtime_specs(self.pool, mesh, sc.max_slots,
                                              sc.num_blocks)
            # pages live pre-sharded over "model" so the donated decode
            # pool never reshards (slot s's pages sit on s's partition)
            self.pool = jax.device_put(self.pool,
                                       named(mesh, self._specs["pool"]))

        B = sc.max_slots
        # host-side decode state, one row per slot
        self._bt = np.zeros((B, self.maxb), np.int32)
        self._pos = np.full((B,), -1, np.int32)
        self._tok = np.zeros((B,), np.int32)
        self._temp = np.zeros((B,), np.float32)
        self._topk = np.zeros((B,), np.int32)
        self._topp = np.zeros((B,), np.float32)
        self._seed = np.zeros((B,), np.uint32)   # per-request sampling seed
        self._count = np.zeros((B,), np.int32)   # tokens emitted so far

        self._prefill_cache: Dict[int, object] = {}
        self._write_cache: Dict[int, object] = {}
        # retrace budgets (analysis/retrace.py): the decode program compiles
        # exactly once per Runtime — a second trace means shape-unstable
        # decode state and would serialize every step behind a compile.
        # Programs are named after their functions (XLA modules
        # jit_decode_step, jit_argmax, jit_sample), so a profiler trace
        # finds them by name.
        if mesh is None:
            def decode_step(p, pool, bt, t, pos):
                return decode_step_paged(p, cfg, plan, pool, bt, t, pos)
        else:
            from jax.sharding import PartitionSpec as P
            nbl = sc.num_blocks // tp
            sp = self._specs

            def decode_step(p, pool, bt, t, pos):
                # block tables carry *global* page ids; a shard's slots
                # only ever hold pages it owns (partitioned allocator), so
                # localizing is a subtract — the clamp only touches the
                # padding entries past a slot's live blocks, which the
                # length mask already hides from attention
                me = jax.lax.axis_index("model")
                btl = jnp.maximum(bt - me * nbl, 0)
                return decode_step_paged(p, cfg, plan, pool, btl, t, pos)

            decode_step = jax.shard_map(
                decode_step, mesh=mesh,
                in_specs=(P(), sp["pool"], sp["bt"], sp["tok"], sp["pos"]),
                out_specs=(sp["logits"], sp["pool"]),
                check_vma=False)
        self._decode = guard_jit(
            decode_step, name="serve.decode_step", max_traces=1,
            donate_argnums=(1,))
        self._sample = guard_jit(sample, name="serve.sample",
                                 per_signature=True)
        # all-greedy fast path: skips the (B, V) sort/softmax machinery
        self._argmax = guard_jit(argmax, name="serve.argmax",
                                 per_signature=True)
        # device-resident block tables, re-uploaded only on change: steady
        # greedy decode keeps the table constant, so the per-step
        # host->device copy is pure overhead the moment tables settle
        self._bt_dev = None
        self._bt_dirty = True
        self._any_sampling = False   # any live slot with temperature > 0
        # run() metrics
        self.steps = 0
        self.decode_seconds = 0.0
        self._occ_sum = 0.0          # live-token occupancy, summed per step
        self._occ_steps = 0

    # -- jitted closures (bounded: one per bucket / cache extent) ------------

    def _prefill_fn(self, bucket: int):
        fn = self._prefill_cache.get(bucket)
        if fn is None:
            cfg = self.cfg
            # cache capacity >= bucket even for SWA archs: the right-pad
            # rows must not ring-evict real in-window rows (the scatter
            # drops the pads afterwards; attention masks by window)
            plan = self.plan.replace(prefill_cache_len=bucket)

            def prefill_full(p, t):
                logits, _, cache = forward(p, cfg, plan, t, make_cache=True)
                return logits, cache

            fn = guard_jit(prefill_full, name=f"serve.prefill[{bucket}]",
                           max_traces=1)
            self._prefill_cache[bucket] = fn
        return fn

    def _write_fn(self, cache_len: int):
        fn = self._write_cache.get(cache_len)
        if fn is None:
            kv_bits = self.kv_bits

            def write(pool, k_seq, v_seq, kv_pos, tlen, table_row):
                # exclude right-pad rows: only positions < true length
                pos_row = jnp.where((kv_pos >= 0) & (kv_pos < tlen),
                                    kv_pos, -1)
                return write_prefill(pool, k_seq, v_seq, pos_row, table_row,
                                     kv_bits=kv_bits)

            if self.mesh is not None:
                from jax.sharding import PartitionSpec as P
                nbl = self.serve_cfg.num_blocks // self._tp
                sp = self._specs

                def write_sharded(pool, k_seq, v_seq, kv_pos, tlen,
                                  table_row):
                    # the prefill rows are replicated; every shard runs
                    # the same scatter with unowned pages remapped to the
                    # local out-of-range sentinel, so only the owner's
                    # pages take the rows (write_prefill drops OOB)
                    me = jax.lax.axis_index("model")
                    owned = (table_row // nbl) == me
                    tbl = jnp.where(owned, table_row - me * nbl, nbl)
                    return write(pool, k_seq, v_seq, kv_pos, tlen, tbl)

                inner = jax.shard_map(
                    write_sharded, mesh=self.mesh,
                    in_specs=(sp["pool"], P(), P(), P(), P(), P()),
                    out_specs=sp["pool"], check_vma=False)
            else:
                inner = write
            fn = guard_jit(inner, name=f"serve.prefill_write[{cache_len}]",
                           max_traces=1, donate_argnums=(0,))
            self._write_cache[cache_len] = fn
        return fn

    # -- request intake ------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int = 32,
               temperature: float = 0.0, top_k: int = 0, top_p: float = 0.0,
               stop_tokens=(), stream_cb=None, priority: int = 0,
               seed: Optional[int] = None) -> Request:
        req = Request(prompt=np.asarray(prompt, np.int32).reshape(-1),
                      max_new_tokens=max_new_tokens, temperature=temperature,
                      top_k=top_k, top_p=top_p,
                      stop_tokens=tuple(int(t) for t in stop_tokens),
                      stream_cb=stream_cb, priority=priority, seed=seed)
        self.scheduler.submit(req)
        if req.seed is None:
            # deterministic per-request default, journaled for replay
            req.seed = (self.serve_cfg.rng_seed * 1_000_003
                        + req.rid) & 0x7FFFFFFF
        if self.journal is not None:
            self.journal.record_submit(req)
        self.tracer.request_event("submit", req.rid,
                                  prompt_len=int(req.prompt.shape[0]),
                                  max_new_tokens=int(max_new_tokens),
                                  priority=int(priority))
        return req

    # -- serving loop --------------------------------------------------------

    def _emit(self, req: Request, token: int, now: float) -> None:
        inj = self.injector
        if inj is not None and req.stream_cb is not None:
            orig = req.stream_cb

            def guarded(r, t):
                if inj.fire("callback"):
                    raise InjectedFault("injected stream-callback failure")
                orig(r, t)

            req.stream_cb = guarded
            try:
                req.emit(token, now)    # cb errors contained per-request
            finally:
                req.stream_cb = orig
        else:
            req.emit(token, now)
        # token index is its position in the output stream; crash-replay
        # re-delivers the same prefix, so timelines dedup by (rid, i)
        self.tracer.token_event(req.rid, len(req.out_tokens) - 1, token,
                                now * 1e6)
        self._m_tokens.inc()

    def _clear_slot(self, req: Request) -> None:
        """Scheduler preemption callback: wipe the victim's device-side
        slot state while `req.slot` is still assigned."""
        s = req.slot
        self._pos[s] = -1
        self._bt[s] = 0
        self._tok[s] = 0
        self._temp[s] = 0.0
        self._topk[s] = 0
        self._topp[s] = 0.0
        self._seed[s] = 0
        self._count[s] = 0
        self._bt_dirty = True
        self._any_sampling = bool((self._temp > 0.0).any())
        if self.journal is not None:
            self.journal.record_preempt(req)
        self.tracer.request_event("preempt", req.rid,
                                  n_preempts=int(req.n_preempts) + 1)
        self._m_preempt.inc()

    def _admit_one(self, req: Request) -> int:
        """Prefill + scatter for a newly (re-)admitted request. Fresh
        requests sample their first token from the prefill logits (TTFT)
        and return 1; resumed requests re-prefill prompt + emitted[:-1]
        and feed emitted[-1] through the next decode step — every resumed
        token then comes from the same decode program as an uninterrupted
        run (token-identity), and 0 new tokens are emitted here."""
        sched = self.scheduler
        resume = bool(req.out_tokens)
        if resume:
            tokens_in = np.concatenate(
                [req.prompt, np.asarray(req.out_tokens[:-1], np.int32)])
        else:
            tokens_in = req.prompt
        tlen = int(len(tokens_in))
        bucket = sched.bucket_for(tlen, extend=resume)
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :tlen] = tokens_in
        logits, cache = self._prefill_fn(bucket)(self.params,
                                                 jnp.asarray(tokens))
        kv = cache["kv"]
        table_row = np.zeros((self.maxb,), np.int32)
        table_row[:len(req.blocks)] = req.blocks
        table_row_j = jnp.asarray(table_row)
        self.pool = self._write_fn(int(kv.k.shape[2]))(
            self.pool, kv.k[:, 0], kv.v[:, 0], kv.pos[0, 0],
            jnp.int32(tlen), table_row_j)
        s = req.slot
        self._bt[s] = table_row
        self._pos[s] = tlen          # next decode writes K/V here
        self._temp[s] = req.temperature
        self._topk[s] = req.top_k
        self._topp[s] = req.top_p
        self._seed[s] = np.uint32(req.seed or 0)
        self._bt_dirty = True
        self._any_sampling = bool((self._temp > 0.0).any())
        self.tracer.request_event("admit", req.rid, slot=int(s),
                                  resumed=resume, prefill_len=tlen)
        self._m_admits.inc()
        if resume:
            self._tok[s] = req.out_tokens[-1]
            self._count[s] = len(req.out_tokens)
            if self.journal is not None:
                self.journal.record_resume(req)
            self._m_resumes.inc()
            return 0
        # first token comes straight from the prefill logits (TTFT token)
        if req.temperature <= 0.0:
            first = self._argmax(logits[:, tlen - 1])
        else:
            first = self._sample(
                logits[:, tlen - 1],
                jnp.asarray([req.seed or 0], jnp.uint32),
                jnp.asarray([0], jnp.int32),
                jnp.asarray([req.temperature], jnp.float32),
                jnp.asarray([req.top_k], jnp.int32),
                jnp.asarray([req.top_p], jnp.float32))
        first = int(np.asarray(first)[0])  # comq: allow(host-sync) TTFT token must reach the stream callback
        self._emit(req, first, time.time())
        self._tok[s] = first
        self._count[s] = 1
        if self.journal is not None:
            self.journal.record_first_token(req, first)
        self.tracer.request_event("first_token", req.rid, token=first)
        self._m_ttft.observe(req.ttft)
        if req.finished():       # max_new == 1, or the TTFT token is a stop
            self._retire(req)
        return 1

    def _retire(self, req: Request) -> None:
        s = req.slot
        # the retire record is the source of truth for "delivered": it is
        # durable before the pages are reused, so a crash can re-stream a
        # request's tokens (at-least-once) but never lose or re-run a
        # retired request
        req.finished()               # ensure finish_reason is set
        if self.journal is not None:
            self.journal.record_retire(req)
        self.tracer.request_event("retire", req.rid,
                                  reason=req.finish_reason,
                                  new_tokens=len(req.out_tokens))
        self._m_retired.inc()
        for dt in req.itl:           # host floats collected by emit()
            self._m_itl.observe(dt)
        self.scheduler.release(req)
        self._pos[s] = -1
        self._bt[s] = 0
        self._tok[s] = 0
        self._count[s] = 0
        # clear sampling settings too: greedy rows of the seeded sampler
        # are bit-identical to the argmax fast path, so dropping back to
        # it when the last sampling request retires cannot change tokens
        self._temp[s] = 0.0
        self._topk[s] = 0
        self._topp[s] = 0.0
        self._bt_dirty = True
        self._any_sampling = bool((self._temp > 0.0).any())

    def step(self) -> int:
        """Admit what fits (possibly preempting lower-priority victims),
        grow pages for the rows this step writes (possibly preempting),
        then run one decode step for all active slots. Returns the number
        of tokens emitted (prefill first-tokens included)."""
        if self.injector is not None:
            self.injector.check("kill", SimulatedKill)
        # one span per host phase (obs/trace.py): each is also a profiler
        # TraceMe, so device idle time can be pinned on the phase it falls in
        tracer = self.tracer
        emitted = 0
        with tracer.span("serve.admit"):
            for req in self.scheduler.admit(on_preempt=self._clear_slot):
                emitted += self._admit_one(req)
        bs = self.serve_cfg.block_size
        with tracer.span("serve.pages"):
            for s, req in sorted(self.scheduler.running.items()):
                if req.state != "running":      # preempted earlier this pass
                    continue
                needed = int(self._pos[s]) // bs + 1
                self.scheduler.ensure_pages(req, needed,
                                            on_preempt=self._clear_slot)
            running = dict(self.scheduler.running)
            for s, req in running.items():
                row = np.asarray(req.blocks, np.int32)       # grown tables
                if not np.array_equal(self._bt[s, :len(row)], row):
                    self._bt[s, :len(row)] = row
                    self._bt_dirty = True
            # block tables only cross to the device when they changed
            # (admit, retire, preempt, page growth) — steady decode re-uses
            # the device-resident copy instead of re-uploading (B, maxb)
            if running and (self._bt_dirty or self._bt_dev is None):
                self._bt_dev = jnp.asarray(self._bt)
                self._bt_dirty = False
        if not running:
            return emitted
        if self.injector is not None:
            self.injector.check("decode_step")
        t0 = time.time()
        # decode_step brackets dispatch + the token pull the loop needs
        # anyway — no extra syncs
        with tracer.span("decode_step", step=self.steps, slots=len(running)):
            with tracer.span("serve.dispatch"):
                logits, self.pool = self._decode(
                    self.params, self.pool, self._bt_dev,
                    jnp.asarray(self._tok[:, None]), jnp.asarray(self._pos))
            with tracer.span("serve.pull"):
                if self._any_sampling:
                    toks = np.asarray(self._sample(  # comq: allow(host-sync) decode loop needs the tokens
                        logits, jnp.asarray(self._seed),
                        jnp.asarray(self._count), jnp.asarray(self._temp),
                        jnp.asarray(self._topk), jnp.asarray(self._topp)))
                else:
                    toks = np.asarray(self._argmax(logits))  # comq: allow(host-sync) decode loop needs the tokens
        now = time.time()
        self.steps += 1
        self.decode_seconds += now - t0
        with tracer.span("serve.emit"):
            for s, req in running.items():
                self._emit(req, int(toks[s]), now)
                emitted += 1
                self._pos[s] += 1
                self._tok[s] = int(toks[s])
                self._count[s] += 1
                # stop-token or length: slot + pages free on this very
                # step, so queued requests can admit next step. Tokens
                # after the stop are never emitted — metrics count what
                # was actually streamed.
                if req.finished():
                    self._retire(req)
            # live-token occupancy: pages actually holding written K/V
            # rows — under "reserve" this is what full-lifetime
            # reservation caps
            live = sum(blocks_for(int(self._pos[s]), bs)
                       for s in range(self.serve_cfg.max_slots)
                       if self._pos[s] >= 0)
            self._occ_sum += live / self.allocator.num_blocks
            self._occ_steps += 1
            self._m_free.set(self.allocator.num_free)
            self._m_occ.set(live / self.allocator.num_blocks)
            self._m_pool_bytes.set(live * self._page_bytes)
        return emitted

    def run(self) -> Dict[str, object]:
        """Drain the queue; returns aggregate + per-request metrics for
        *this* call (tokens emitted and requests completed while run()
        was draining — pre-run step() calls and earlier run()s are not
        re-counted, so wall-clock rates stay honest)."""
        t0 = time.time()
        done_before = len(self.scheduler.completed)
        steps_before = self.steps
        occ_sum0, occ_n0 = self._occ_sum, self._occ_steps
        preempt0 = self.scheduler.preemptions
        new_tokens = 0
        with self.tracer.span("serve.run"):
            while not self.scheduler.idle:
                new_tokens += self.step()
        wall = time.time() - t0
        done = self.scheduler.completed[done_before:]
        occ_n = self._occ_steps - occ_n0
        # histogram over this run's ITLs: quantile() matches
        # np.percentile bit-for-bit (obs/metrics.py), so swapping the
        # ad-hoc percentile math for the histogram changed no numbers
        itl_hist = Histogram("serve.itl_seconds")
        for r in done:
            for dt in r.itl:
                itl_hist.observe(dt)
        return {
            "requests": len(done),
            "finish_reasons": [r.finish_reason for r in done],
            "new_tokens": new_tokens,
            "wall_seconds": wall,
            "tok_per_s": new_tokens / max(wall, 1e-9),
            "ttft_s": [r.ttft for r in done],
            "itl_mean_s": (itl_hist.sum / itl_hist.count
                           if itl_hist.count else 0.0),
            "itl_p50_s": itl_hist.quantile(0.5) if itl_hist.count else 0.0,
            "itl_p99_s": itl_hist.quantile(0.99) if itl_hist.count else 0.0,
            "decode_steps": self.steps - steps_before,
            "preemptions": self.scheduler.preemptions - preempt0,
            "cache_blocks": self.allocator.num_blocks,
            "cache_peak_blocks": self.allocator.peak_in_use,
            "cache_peak_occupancy": (self.allocator.peak_in_use
                                     / self.allocator.num_blocks),
            "mean_live_occupancy": ((self._occ_sum - occ_sum0) / occ_n
                                    if occ_n else 0.0),
            "cache_bytes": paged_cache_bytes(
                self.cfg, self.plan, self.serve_cfg.num_blocks,
                self.serve_cfg.block_size),
        }

    # -- observability -------------------------------------------------------

    def metrics_snapshot(self) -> Dict[str, object]:
        """Cheap host-side health snapshot for `ft.Heartbeat`: what an
        operator tailing the watchdog file needs to see mid-run. No
        device state is touched."""
        live = sum(blocks_for(int(self._pos[s]), self.serve_cfg.block_size)
                   for s in range(self.serve_cfg.max_slots)
                   if self._pos[s] >= 0)
        return {
            "retired": len(self.scheduler.completed),
            "queued": len(self.scheduler.queue),
            "running": len(self.scheduler.running),
            "live_occupancy": live / self.allocator.num_blocks,
            "preemptions": self.scheduler.preemptions,
            "decode_steps": self.steps,
        }

    # -- convenience ---------------------------------------------------------

    def generate(self, prompts, max_new_tokens: int = 32, **kw
                 ) -> List[np.ndarray]:
        """Submit `prompts` (list of 1-D int arrays) in order, drain, and
        return each request's tokens in submission order."""
        reqs = [self.submit(p, max_new_tokens=max_new_tokens, **kw)
                for p in prompts]
        self.run()
        return [np.asarray(r.out_tokens, np.int32) for r in reqs]


def recover_runtime(params, cfg, plan, journal_dir: str,
                    serve_cfg: ServeConfig = None, injector=None,
                    fsync: bool = True, tracer=None, metrics=None,
                    mesh=None):
    """Crash-recovery entry point: rebuild a Runtime from a request
    journal after a process death. Retired requests are never re-run
    (their tokens live in the journal); every in-flight request is
    re-submitted exactly once under its original rid/seed/settings, so
    draining the returned runtime replays each stream token-identically
    to the uninterrupted run. Returns ``(runtime, journal_state)`` —
    `journal_state.completed` holds the pre-crash outputs."""
    state = Journal.replay(journal_dir)
    journal = Journal(journal_dir, fsync=fsync)
    rt = Runtime(params, cfg, plan, serve_cfg, journal=journal,
                 injector=injector, tracer=tracer, metrics=metrics,
                 mesh=mesh)
    rt.scheduler.advance_rids(state.max_rid)
    for rid in sorted(state.inflight):
        rec = state.inflight[rid]
        req = Request(prompt=np.asarray(rec["prompt"], np.int32),
                      max_new_tokens=rec["max_new_tokens"],
                      temperature=rec["temperature"],
                      top_k=rec["top_k"], top_p=rec["top_p"],
                      stop_tokens=tuple(rec["stop_tokens"]),
                      priority=rec["priority"], seed=rec["seed"])
        rt.scheduler.resubmit(req, rid)
        journal.record_replayed(rid)
    return rt, state
