"""End-to-end training orchestration with LCAP activity tracking.

Wires together every substrate: sharded data pipeline, pjit train step,
per-host ActivityTracker producers, the LCAP proxy, and the consumer
groups (metrics DB, checkpoint committer, straggler detector, elastic
controller).  This is the host-side program each node runs; on CPU it
drives reduced configs end-to-end (examples/, tests/).
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from .. import configs as C
from ..checkpoint import AsyncCheckpointer, latest_step, restore_checkpoint
from ..core.proxy import LcapProxy
from ..data import ShardedTokenPipeline
from ..models import transformer as T
from ..obs.spans import TRACER
from ..optim import adamw
from ..track import (ActivityTracker, CheckpointCommitter, MetricsDB,
                     StragglerDetector)
from .elastic import make_elastic_mesh, reshard_state, state_shardings
from .sharding import LogicalRules, use_rules
from .steps import TrainHParams, build_train_step


class Trainer:
    def __init__(self, cfg, *, workdir: str, mesh=None, hp: TrainHParams = None,
                 global_batch: int = 8, seq_len: int = 32, n_hosts: int = 2,
                 ckpt_every: int = 10, n_metrics_workers: int = 2,
                 seed: int = 0):
        self.cfg = cfg
        self.workdir = workdir
        os.makedirs(workdir, exist_ok=True)
        self.mesh = mesh or make_elastic_mesh()
        self.hp = hp or TrainHParams(n_micro=1, attn_impl="naive",
                                     remat=False)
        self.global_batch = global_batch
        self.seq_len = seq_len
        self.ckpt_every = ckpt_every

        # --- LCAP backbone: one producer per (simulated) host ------------
        self.trackers = [
            ActivityTracker(run_id=1, host_id=h, jobid=f"{cfg.arch_id}",
                            shard=(0, h, 0, 0),
                            path=os.path.join(workdir, f"host{h}.llog"))
            for h in range(n_hosts)]
        self.proxy = LcapProxy({t.llog.producer_id: t.llog
                                for t in self.trackers})
        self.metrics = [MetricsDB(self.proxy,
                                  os.path.join(workdir, "metrics.sqlite"))
                        for _ in range(n_metrics_workers)]
        self.committer = CheckpointCommitter(
            self.proxy, os.path.join(workdir, "manifests"))
        self.straggler = StragglerDetector(self.proxy)
        self.ckpt = AsyncCheckpointer(os.path.join(workdir, "ckpt"),
                                      n_shards=n_hosts,
                                      tracker=self.trackers[0])

        # --- data ----------------------------------------------------------
        self.pipes = [ShardedTokenPipeline(
            cfg.vocab_size, seq_len, global_batch, n_hosts, h, seed=seed,
            tracker=t) for h, t in enumerate(self.trackers)]

        # --- model/optimizer state ------------------------------------------
        # params and AdamW state are created in their shardings by one
        # jitted init, so no whole copy of either lands on one device
        self.rules = LogicalRules(self.mesh)
        p_sh, o_sh = state_shardings(cfg, self.rules)

        def init_state():
            params = T.init_params(cfg, seed=seed)
            return params, adamw.init(params)

        with use_rules(self.rules):
            self.params, self.opt_state = jax.jit(
                init_state, out_shardings=(p_sh, o_sh))()
        self.step = 0
        self._maybe_restore()

        replicated = NamedSharding(self.mesh, PartitionSpec())
        self.train_step = jax.jit(
            build_train_step(cfg, self.hp), donate_argnums=(0, 1),
            out_shardings=(p_sh, o_sh, replicated))
        self.history: List[Dict[str, float]] = []

    # ------------------------------------------------------------------ io
    def _maybe_restore(self) -> None:
        ck_dir = os.path.join(self.workdir, "ckpt")
        last = latest_step(ck_dir)
        if last is None:
            return
        tree = {"params": self.params, "opt": self.opt_state}
        restored = restore_checkpoint(tree, last, ck_dir)
        self.params, self.opt_state, _ = reshard_state(
            self.cfg, restored["params"], restored["opt"], self.mesh)
        self.step = last
        for p in self.pipes:
            p.seek(last)

    # ---------------------------------------------------------------- loop
    def pump_consumers(self) -> None:
        """Span ``train.pump``: the proxy, each MetricsDB worker, the
        committer, the straggler detector, the upstream flush."""
        with TRACER.span("train.pump"):
            with TRACER.span("pump.proxy"):
                self.proxy.pump()
            for w in self.metrics:
                with TRACER.span("pump.metrics_db"):
                    w.poll()
            with TRACER.span("pump.committer"):
                self.committer.poll()
            with TRACER.span("pump.straggler"):
                self.straggler.poll()
            with TRACER.span("pump.flush"):
                self.proxy.flush_upstream()

    def run(self, n_steps: int) -> List[Dict[str, float]]:
        """Take ``n_steps`` steps, each span ``train.step`` (tokens) over
        ``train.data`` (batch assembly), ``train.launch`` (the jitted
        step's call), ``train.wait`` (until the loss is on the host),
        ``train.track`` (the trackers' journal writes and the checkpoint
        submit) and ``train.pump``."""
        tokens = self.global_batch * self.seq_len
        with use_rules(self.rules), self.mesh:
            for _ in range(n_steps):
                with TRACER.span("train.step") as span:
                    span.count = tokens
                    t0 = time.time()
                    with TRACER.span("train.data"):
                        shards = [next(p) for p in self.pipes]
                        batch = {k: np.concatenate([s[k] for s in shards])
                                 for k in shards[0]}
                    with TRACER.span("train.launch"):
                        self.params, self.opt_state, metrics = \
                            self.train_step(self.params, self.opt_state,
                                            batch)
                    with TRACER.span("train.wait"):
                        loss = float(metrics["loss"])
                    dt = time.time() - t0
                    self.step += 1
                    with TRACER.span("train.track"):
                        for t in self.trackers:
                            t.step_commit(self.step, loss, dt, tokens)
                            t.heartbeat(self.step, dt)
                        if self.step % self.ckpt_every == 0:
                            self.ckpt.submit({"params": self.params,
                                              "opt": self.opt_state},
                                             self.step)
                    self.pump_consumers()
                    self.history.append({"step": self.step, "loss": loss,
                                         "time": dt})
        return self.history

    def close(self) -> None:
        self.ckpt.close()
        for w in self.metrics:
            w.close()
