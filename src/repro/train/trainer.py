"""The training loop: data, step, checkpoints, watchdog, restart.

`Trainer.run()` executes `total_steps` with: sharded batches, microbatched
train_step, periodic async checkpoints (params + optimizer + loader
position), heartbeats, straggler events, and an injectable failure hook
(used by the fault-tolerance tests). `resume()` restores the latest
committed checkpoint — including onto a different device count (elastic).
"""
from __future__ import annotations

import os
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.ckpt import CheckpointManager
from repro.data import ShardedLoader
from repro.ft import Heartbeat, Watchdog
from repro.models.model import init_params
from repro.optim import AdamWConfig
from repro.train.train_step import init_train_state, make_train_step


class Trainer:
    def __init__(self, cfg, plan, run_cfg, *, adamw_cfg: AdamWConfig = None,
                 host_id: int = 0, failure_hook: Optional[Callable] = None,
                 shard_state_fn: Optional[Callable] = None):
        self.cfg = cfg
        self.plan = plan
        self.run = run_cfg
        self.adamw_cfg = adamw_cfg or AdamWConfig(
            weight_decay=run_cfg.weight_decay)
        self.ckpt = CheckpointManager(run_cfg.ckpt_dir, keep=run_cfg.keep_ckpts)
        self.watchdog = Watchdog()
        self.heartbeat = Heartbeat(os.path.join(run_cfg.ckpt_dir, "hb"),
                                   host_id)
        self.failure_hook = failure_hook
        self.shard_state_fn = shard_state_fn   # elastic re-shard on restore
        if run_cfg.grad_compression == "int8_ef":
            # compressed_psum needs a named mesh axis: run the step under a
            # shard_map over a 1-shard "data" axis — the single-process
            # Trainer's whole batch is one shard, so this exercises the
            # int8-EF quantize/carry path end-to-end; multi-shard
            # deployments wire their own shard_map (see train_step.py)
            from jax.sharding import PartitionSpec as P
            from repro.dist import data_mesh
            step = make_train_step(cfg, plan, run_cfg, self.adamw_cfg,
                                   axis_name="data")
            self.step_fn = jax.jit(
                jax.shard_map(step, mesh=data_mesh(1), in_specs=(P(), P()),
                              out_specs=(P(), P()), check_vma=False),
                donate_argnums=(0,))
        else:
            self.step_fn = jax.jit(
                make_train_step(cfg, plan, run_cfg, self.adamw_cfg),
                donate_argnums=(0,))
        self.metrics_log = []

    def init_state(self):
        params = init_params(jax.random.PRNGKey(self.run.seed), self.cfg,
                             self.plan)
        return init_train_state(params, self.adamw_cfg, self.run)

    def resume_or_init(self):
        latest = self.ckpt.latest_step()
        state = self.init_state()
        start_step = 0
        if latest is not None:
            shardings = (self.shard_state_fn(state)
                         if self.shard_state_fn else None)
            state, meta = self.ckpt.restore(latest, state, shardings)
            start_step = meta["step"]
        return state, start_step

    def run_loop(self, total_steps: Optional[int] = None,
                 seq_len: Optional[int] = None,
                 global_batch: Optional[int] = None) -> Dict[str, Any]:
        total = total_steps or self.run.total_steps
        state, start = self.resume_or_init()
        loader = ShardedLoader(self.cfg.vocab_size,
                               global_batch or 8,
                               seq_len or 128,
                               seed=self.run.seed, start_step=start)
        step = start
        try:
            while step < total:
                batch = next(loader)
                batch = {k: jnp.asarray(v) for k, v in batch.items()}
                self.watchdog.step_start()
                state, metrics = self.step_fn(state, batch)
                metrics = {k: float(v) for k, v in metrics.items()}
                ev = self.watchdog.step_end(step)
                if ev is not None:
                    metrics["straggler"] = ev.seconds
                self.metrics_log.append(metrics)
                step += 1
                self.heartbeat.beat(step)
                if self.failure_hook is not None:
                    self.failure_hook(step)   # may raise (injected failure)
                if step % self.run.ckpt_every == 0 or step == total:
                    self.ckpt.save(step, state,
                                   extra={"loader": loader.state()},
                                   blocking=not self.run.async_ckpt)
        finally:
            loader.close()
            self.ckpt.wait()
        return {"final_step": step, "state": state,
                "metrics": self.metrics_log}
