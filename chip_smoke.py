#!/usr/bin/env python3
"""Bring-up smoke run on a TPU, through the entry points users call.

    python3 chip_smoke.py [--seed N]              # one chip: both phases
    python3 chip_smoke.py --chips 4 [--seed N]    # the sharded trainer only

One chip runs two phases:

- fabric: 16 in-memory MDT journals (an mdtest-like metadata mix,
  one ``cr_jobid`` per rank, 250k records each) routed through an
  in-process 4-shard ``LcapCluster`` whose routing columns are hashed
  on the chip (``REPRO_JAX_ROUTING=1``), drained through
  ``connect(cluster).subscribe(...)`` into one persistent group of two
  members; then the tiled Pallas routing kernel on 1M+ records.
- trainer: ``Trainer`` on mamba2-780m at its published width, remat on,
  4 steps with one checkpoint written and committed.

``--chips 4`` runs only granite-moe-1b-a400m on the (2, 2) mesh that
``make_elastic_mesh`` plans, checks that its state is sharded over all
four chips and evenly, and compares its first loss with a (4, 1) mesh.

Each phase prints one ``smoke/<phase>`` line of counts, compile seconds
and wall seconds: smoke output, not metrics.  The last line is
``{"ok": true, "device": {...}}``.  The script exits non-zero, without
that line, when JAX finds no TPU or a phase fails.  Everything runs in
this one process: a chip belongs to one process at a time.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import tempfile
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

N_MDT = 16
RECORDS_PER_MDT = 250_000
N_SHARDS = 4
GROUP_MEMBERS = 2
N_RANKS = 64
PALLAS_RECORDS = (1 << 20) + 4321        # not a whole number of tiles
TRAIN_ARCH = "mamba2-780m"
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 2, 512, 4
SHARDED_ARCH = "granite-moe-1b-a400m"
SHARDED_BATCH, SHARDED_SEQ, SHARDED_STEPS = 8, 1024, 2
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Seconds JAX spent in backend compiles (persistent-cache loads
    included), read from ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.seconds += duration
            self.count += 1

    def mark(self):
        return self.seconds, self.count

    def since(self, mark):
        return {"compile_s": self.seconds - mark[0],
                "compiles": self.count - mark[1]}


def bytes_in_use(device):
    return device.memory_stats()["bytes_in_use"]


def report(phase, fields):
    print(f"smoke/{phase} " + json.dumps(
        {**fields, "note": "smoke output, not metrics"}), flush=True)


# --------------------------------------------------------------- fabric
def fill_mdt(log, mdt, n, rng):
    """``n`` metadata records on one MDT: create/setattr/rename/unlink
    on FIDs of the MDT's own sequence, each stamped with its rank's
    jobid."""
    from repro.core import records as R

    kinds = [R.CL_CREATE, R.CL_SETATTR, R.CL_RENAME, R.CL_UNLINK]
    kind = rng.choice(4, n, p=[0.4, 0.3, 0.1, 0.2])
    oids = rng.integers(1, 1 << 20, n)
    parents = rng.integers(1, 1 << 10, (n, 2))
    ranks = rng.integers(0, N_RANKS, n)
    seq = 0x200000400 + mdt
    jobids = [b"mdtest.%03d" % r for r in range(N_RANKS)]
    t0 = 1_700_000_000 * 10**9
    chunk = []
    for i in range(n):
        k = kinds[kind[i]]
        oid = int(oids[i])
        rec = R.ChangelogRecord(
            type=k, time=t0 + i, tfid=R.Fid(seq, oid, 0),
            pfid=R.Fid(seq, int(parents[i, 0]), 0), name=b"f%07d" % oid,
            jobid=jobids[ranks[i]])
        if k == R.CL_RENAME:
            rec.sfid = R.Fid(seq, oid, 0)
            rec.spfid = R.Fid(seq, int(parents[i, 1]), 0)
            rec.sname = b"f%07d.old" % oid
        chunk.append(rec)
        if len(chunk) == 8192:
            log.log_batch(chunk)
            chunk = []
    log.log_batch(chunk)


def fabric_phase(seed, clock):
    import numpy as np

    os.environ["REPRO_JAX_ROUTING"] = "1"   # before the cluster routes
    from repro.core import cluster as CL
    from repro.core.llog import Llog
    from repro.core.session import Subscription, connect
    from repro.kernels import stream_ops

    mark = clock.mark()
    t_start = time.perf_counter()
    twin = stream_ops.fid_slots
    seen = {"batches": 0, "records": 0}

    def checked_twin(seq, oid, ver, n_slots):
        got = twin(seq, oid, ver, n_slots)
        if not np.array_equal(got, CL.fid_slots(seq, oid, ver, n_slots)):
            raise AssertionError(
                f"device slots differ from numpy on batch {seen['batches']}")
        seen["batches"] += 1
        seen["records"] += len(got)
        return got

    stream_ops.fid_slots = checked_twin
    try:
        logs = {f"mdt{m}": Llog(f"mdt{m}") for m in range(N_MDT)}
        cluster = CL.LcapCluster(logs, n_shards=N_SHARDS)  # arms journals
        session = connect(cluster)
        members = [session.subscribe(Subscription(group="smoke",
                                                  auto_commit=False))
                   for _ in range(GROUP_MEMBERS)]
        for m, log in enumerate(logs.values()):
            fill_mdt(log, m, RECORDS_PER_MDT,
                     np.random.default_rng([seed, m]))
        total = N_MDT * RECORDS_PER_MDT
        t_filled = time.perf_counter()

        delivered = {pid: np.zeros(RECORDS_PER_MDT + 1, np.int64)
                     for pid in logs}
        rounds = idle = 0
        while True:
            moved = cluster.pump()
            for stream in members:
                for pid, batch in stream.fetch():
                    np.add.at(delivered[pid], batch.indices_np()
                              .astype(np.int64), 1)
                    moved += len(batch)
                stream.commit()
            rounds += 1
            trimmed = sum(log.first_index == log.last_index + 1
                          for log in logs.values())
            idle = 0 if moved else idle + 1
            if idle and trimmed == N_MDT:
                break
            if idle > 100:
                raise AssertionError(
                    f"drain stalled: {trimmed}/{N_MDT} journals trimmed")
        t_drained = time.perf_counter()
        for stream in members:
            stream.close()
    finally:
        stream_ops.fid_slots = twin

    counts = np.concatenate([d[1:] for d in delivered.values()])
    if not (counts == 1).all():
        raise AssertionError(
            f"not exactly once: {int((counts == 0).sum())} lost, "
            f"{int((counts > 1).sum())} duplicated")
    if cluster.stats["shards_failed"]:
        raise AssertionError(f"shards failed: {cluster.stats}")
    if not (seen["records"] == cluster.stats["routed"] == total):
        raise AssertionError(f"device routing covered {seen['records']} of "
                             f"{cluster.stats['routed']} routed, {total} "
                             "logged")

    rng = np.random.default_rng([seed, N_MDT])
    seq = rng.integers(0, 1 << 64, PALLAS_RECORDS, dtype=np.uint64)
    oid = rng.integers(0, 1 << 32, PALLAS_RECORDS, dtype=np.uint32)
    ver = rng.integers(0, 1 << 32, PALLAS_RECORDS, dtype=np.uint32)
    t_p = time.perf_counter()
    got = stream_ops.fid_slots_pallas(seq, oid, ver, CL.DEFAULT_SLOTS)
    t_p = time.perf_counter() - t_p
    if not np.array_equal(got, CL.fid_slots(seq, oid, ver,
                                            CL.DEFAULT_SLOTS)):
        raise AssertionError("Pallas slots differ from numpy")

    report("fabric", {
        "records": total, "journals": N_MDT, "shards": N_SHARDS,
        "group_members": GROUP_MEMBERS,
        "delivered_exactly_once": int(counts.size),
        "journals_trimmed": trimmed, "shards_failed": 0,
        "device_routed_batches": seen["batches"],
        "device_routed_records": seen["records"],
        "device_slots_equal_numpy": True, "pump_rounds": rounds,
        "pallas_records": PALLAS_RECORDS, "pallas_equal_numpy": True,
        "pallas_call_s": t_p, "fill_s": t_filled - t_start,
        "drain_s": t_drained - t_filled,
        "wall_s": time.perf_counter() - t_start, **clock.since(mark)})


# -------------------------------------------------------------- trainer
def trainer_phase(seed, clock):
    from repro import configs as C
    from repro.runtime.steps import TrainHParams
    from repro.runtime.train_loop import Trainer

    mark = clock.mark()
    t_start = time.perf_counter()
    cfg = C.get_config(TRAIN_ARCH)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as wd:
        trainer = Trainer(cfg, workdir=wd, hp=TrainHParams(remat=True),
                          global_batch=TRAIN_BATCH, seq_len=TRAIN_SEQ,
                          ckpt_every=TRAIN_STEPS, seed=seed)
        try:
            hist = trainer.run(TRAIN_STEPS)
            trainer.ckpt.wait()
            trainer.pump_consumers()     # fold the shard-write records
            rows = trainer.metrics[0].query("SELECT COUNT(*) FROM events")
            committed = trainer.committer.latest_committed()
        finally:
            trainer.close()
    losses = [h["loss"] for h in hist]
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"non-finite loss: {losses}")
    if not rows[0][0]:
        raise AssertionError("MetricsDB holds no rows")
    if committed != TRAIN_STEPS:
        raise AssertionError(f"committed checkpoint {committed}, "
                             f"want {TRAIN_STEPS}")
    report("trainer", {
        "arch": cfg.arch_id, "d_model": cfg.d_model,
        "n_layers": cfg.n_layers, "global_batch": TRAIN_BATCH,
        "seq_len": TRAIN_SEQ, "steps": len(hist), "losses": losses,
        "first_step_s": hist[0]["time"],
        "later_step_s": [h["time"] for h in hist[1:]],
        "metrics_rows": rows[0][0], "committed_step": committed,
        "wall_s": time.perf_counter() - t_start, **clock.since(mark)})


# ------------------------------------------------------- four chips
def sharded_trainer_phase(seed, clock):
    import jax

    from repro import configs as C
    from repro.launch.mesh import make_host_mesh
    from repro.runtime.elastic import make_elastic_mesh
    from repro.runtime.steps import TrainHParams
    from repro.runtime.train_loop import Trainer

    mark = clock.mark()
    t_start = time.perf_counter()
    cfg = C.get_config(SHARDED_ARCH)
    n_dev = len(jax.devices())

    def run_on_mesh(mesh, steps, wd, check):
        trainer = Trainer(cfg, workdir=wd, mesh=mesh,
                          hp=TrainHParams(remat=True),
                          global_batch=SHARDED_BATCH, seq_len=SHARDED_SEQ,
                          ckpt_every=1 << 30, seed=seed)
        try:
            facts = check(trainer) if check else {}
            hist = trainer.run(steps)
            facts["losses"] = [h["loss"] for h in hist]
            facts["step_s"] = [h["time"] for h in hist]
        finally:
            trainer.close()
        del trainer
        gc.collect()
        return facts

    def check_placement(trainer):
        leaves = (jax.tree.leaves(trainer.params)
                  + jax.tree.leaves(trainer.opt_state))
        large = 0
        for leaf in leaves:
            if len(leaf.sharding.device_set) != n_dev:
                raise AssertionError(f"leaf {leaf.shape} on "
                                     f"{len(leaf.sharding.device_set)} "
                                     "devices")
            if leaf.size >= 1 << 20:
                large += 1
                if leaf.sharding.shard_shape(leaf.shape) == leaf.shape:
                    raise AssertionError(f"large leaf {leaf.shape} is "
                                         "replicated, not sharded")
        in_use = [bytes_in_use(d) for d in trainer.mesh.devices.flat]
        if max(in_use) > 2 * min(in_use):
            raise AssertionError(f"uneven device memory: {in_use}")
        return {"leaves": len(leaves), "large_leaves_sharded": large,
                "bytes_in_use": in_use}

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as wd:
        mesh22 = make_elastic_mesh()
        if dict(mesh22.shape) != {"data": 2, "model": 2}:
            raise AssertionError(f"elastic mesh {dict(mesh22.shape)}")
        m22 = run_on_mesh(mesh22, SHARDED_STEPS, os.path.join(wd, "m22"),
                          check_placement)
        m41 = run_on_mesh(make_host_mesh(n_dev, 1), 1,
                          os.path.join(wd, "m41"), None)
    a, b = m22["losses"][0], m41["losses"][0]
    if not all(math.isfinite(x) for x in m22["losses"] + m41["losses"]):
        raise AssertionError(f"non-finite loss: {m22} {m41}")
    rel = abs(a - b) / abs(b)
    if rel > 2e-2:
        raise AssertionError(f"(2,2) loss {a} vs (4,1) loss {b}: rel {rel}")
    report("sharded_trainer", {
        "arch": cfg.arch_id, "d_model": cfg.d_model,
        "n_layers": cfg.n_layers, "global_batch": SHARDED_BATCH,
        "seq_len": SHARDED_SEQ, "mesh_2x2": m22,
        "mesh_4x1": m41, "first_loss_rel_diff": rel,
        "wall_s": time.perf_counter() - t_start, **clock.since(mark)})


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache

    print(f"smoke/setup compile cache at {enable_compile_cache()}",
          flush=True)
    clock = CompileClock()
    try:
        if args.chips == 4:
            sharded_trainer_phase(args.seed, clock)
        else:
            fabric_phase(args.seed, clock)
            trainer_phase(args.seed, clock)
    except Exception:
        traceback.print_exc()
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
