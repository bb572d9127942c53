"""Framework integration of LCAP (paper usage examples mapped to
training): shared-DB metrics group, checkpoint commit protocol,
straggler detection, elastic membership, cache invalidation, index
bootstrap."""

import os
import time

from repro import configs as C
from repro.core import records as R
from repro.core.llog import Llog
from repro.core.proxy import LcapProxy
from repro.obs.spans import TRACER
from repro.runtime.train_loop import Trainer
from repro.track import (ActivityTracker, CacheInvalidator,
                         CheckpointCommitter, ElasticController, MetricsDB,
                         StragglerDetector, synthesize_index_stream)


def mk_world(n_hosts=4):
    trackers = [ActivityTracker(run_id=1, host_id=h, jobid=f"run-1",
                                shard=(0, h, h // 2, h % 2))
                for h in range(n_hosts)]
    proxy = LcapProxy({t.llog.producer_id: t.llog for t in trackers})
    return trackers, proxy


def pump_all(proxy, workers, rounds=10):
    for _ in range(rounds):
        proxy.pump()
        moved = sum(w.poll() for w in workers)
        proxy.flush_upstream()
        if not moved:
            break


def test_metrics_db_shared_across_group(tmp_path):
    """N MetricsDB instances of one group replicate the stream into one
    shared database — the Robinhood-distributed configuration."""
    trackers, proxy = mk_world(4)
    db = str(tmp_path / "metrics.sqlite")
    workers = [MetricsDB(proxy, db) for _ in range(3)]
    for step in range(5):
        for t in trackers:
            t.step_commit(step, loss=1.0 / (step + 1), step_time_s=0.1,
                          tokens=1024)
    pump_all(proxy, workers)
    rows = workers[0].query("SELECT COUNT(*) FROM events WHERE type=?",
                            (R.CL_STEP_COMMIT,))
    assert rows[0][0] == 20
    # every instance processed a share (load-balanced)
    per = [w.query("SELECT COUNT(*) FROM events")[0][0] for w in workers]
    assert per[0] == 20                       # shared DB: all rows visible
    # and the journals were trimmed (collective ack made it upstream)
    assert all(t.llog.first_index == t.llog.last_index + 1 for t in trackers)
    for w in workers:
        w.close()


def test_checkpoint_commit_protocol(tmp_path):
    """CKPT_WRITE records from all hosts -> committer group publishes the
    manifest exactly when every shard landed."""
    trackers, proxy = mk_world(4)
    committers = [CheckpointCommitter(proxy, str(tmp_path / "manifests"))
                  for _ in range(2)]
    step = 7
    for shard, t in enumerate(trackers[:-1]):
        t.ckpt_write(step, shard_id=shard, nbytes=1 << 20,
                     path=f"/ckpt/s{shard}", total_shards=4)
    pump_all(proxy, committers)
    assert committers[0].latest_committed() is None   # one shard missing
    trackers[-1].ckpt_write(step, shard_id=3, nbytes=1 << 20,
                            path="/ckpt/s3", total_shards=4)
    pump_all(proxy, committers)
    assert committers[0].latest_committed() == step
    assert os.path.exists(committers[0].manifest_path(step))


def test_checkpoint_committer_concurrent_members_no_lost_update(tmp_path):
    """Two load-balanced group members recording *different* shards of
    the same step concurrently must not lose either update.  The old
    shared ``step-*.shards.json`` was a read-modify-write that a
    per-instance lock cannot order across members; per-shard files
    cannot collide."""
    import threading

    trackers, proxy = mk_world(2)
    c1 = CheckpointCommitter(proxy, str(tmp_path / "manifests"))
    c2 = CheckpointCommitter(proxy, str(tmp_path / "manifests"))
    steps = list(range(25))

    def rec_for(step, shard):
        return R.ChangelogRecord(
            type=R.CL_CKPT_WRITE, tfid=R.Fid(1, shard, step),
            name=f"/ckpt/s{shard}".encode(), metrics=(1024.0,),
            xattr={"total_shards": 2})

    barrier = threading.Barrier(2)
    errors = []

    def member(committer, shard):
        try:
            for step in steps:
                barrier.wait()      # maximally overlap the two writers
                committer.handle("host0", rec_for(step, shard))
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)

    threads = [threading.Thread(target=member, args=(c1, 0)),
               threading.Thread(target=member, args=(c2, 1))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    import json
    for step in steps:
        path = c1.manifest_path(step)
        assert os.path.exists(path), f"step {step} never committed"
        with open(path) as fh:
            manifest = json.load(fh)
        assert set(manifest["shards"]) == {"0", "1"}, step
    # committed steps leave no shard-file litter behind (the directory
    # stays bounded by in-flight steps)
    leftovers = [f for f in os.listdir(c1.dir) if ".shard-" in f]
    assert leftovers == []
    # a redelivered record of a committed step neither litters nor
    # rewrites the manifest
    c1.handle("host0", rec_for(steps[0], 0))
    assert not [f for f in os.listdir(c1.dir) if ".shard-" in f]
    c1.close()
    c2.close()


def test_straggler_detection():
    trackers, proxy = mk_world(4)
    det = StragglerDetector(proxy)
    for step in range(10):
        for h, t in enumerate(trackers):
            t.heartbeat(step, step_time_s=0.1 if h != 2 else 0.5)
    pump_all(proxy, [det])
    assert det.flagged == {2}


def test_straggler_evicted_on_leave():
    """flag -> leave -> unflag: a straggler that leaves the fleet
    (ELASTIC_LEAVE) is evicted from the EWMA map so it stops skewing
    the fleet median and ``flagged`` is not pinned forever."""
    trackers, proxy = mk_world(4)
    det = StragglerDetector(proxy)
    for step in range(10):
        for h, t in enumerate(trackers):
            t.heartbeat(step, step_time_s=0.1 if h != 2 else 0.5)
    pump_all(proxy, [det])
    assert det.flagged == {2}
    trackers[2].elastic(joined=False, n_hosts=3, step=10)
    pump_all(proxy, [det])
    assert 2 not in det.ewma
    assert det.flagged == set()
    # the survivors keep reporting; nobody is flagged against a median
    # the departed host no longer distorts
    for step in range(10, 15):
        for h, t in enumerate(trackers):
            if h != 2:
                t.heartbeat(step, step_time_s=0.1)
    pump_all(proxy, [det])
    assert det.flagged == set()


def test_straggler_stale_host_aged_out():
    """A host that silently stops heartbeating (no ELASTIC_LEAVE) is
    aged out once its last sample falls ``stale_after_s`` behind the
    newest sample in the stream."""
    trackers, proxy = mk_world(3)
    det = StragglerDetector(proxy, stale_after_s=30.0)
    t0 = R.now_ns()

    def hb(host, step, dt, at_s):
        trackers[host].llog.log(R.ChangelogRecord(
            type=R.CL_HEARTBEAT, tfid=R.Fid(1, host, step),
            time=t0 + int(at_s * 1e9), metrics=(dt,)))

    for step in range(5):
        for h in range(3):
            hb(h, step, 0.1 if h != 2 else 0.5, at_s=step)
    pump_all(proxy, [det])
    assert det.flagged == {2}
    # 40 stream-seconds later only hosts 0/1 are still alive
    for step in range(5, 8):
        for h in range(2):
            hb(h, step, 0.1, at_s=40 + step)
    pump_all(proxy, [det])
    assert 2 not in det.ewma
    assert det.flagged == set()


def test_elastic_membership_plan():
    trackers, proxy = mk_world(4)
    ctl = ElasticController(proxy, chips_per_host=4)
    for t in trackers:
        t.elastic(joined=True, n_hosts=4, step=0)
    pump_all(proxy, [ctl])
    assert ctl.members == {0, 1, 2, 3}
    assert ctl.plan()["usable"] == 16
    trackers[1].elastic(joined=False, n_hosts=3, step=5)
    pump_all(proxy, [ctl])
    assert ctl.members == {0, 2, 3}
    assert ctl.plan()["usable"] == 8          # 12 chips -> 8 usable


def test_cache_invalidation_ephemeral():
    """Ganesha-style: an ephemeral reader invalidates local cache entries
    on EVICT records, without ever blocking the journal trim."""
    trackers, proxy = mk_world(2)
    from repro.core.reader import LocalReader
    anchor = LocalReader(proxy, "metrics")    # persistent group
    cache = {(5, 1): "page-a", (6, 1): "page-b"}
    inv = CacheInvalidator(proxy, cache)
    trackers[0].evict(5, 1)
    proxy.pump()
    inv.poll()
    assert (5, 1) not in cache and (6, 1) in cache
    assert inv.invalidated == 1
    for pid, rec in anchor.fetch():
        anchor.ack(pid, rec.index)
    assert trackers[0].llog.first_index == trackers[0].llog.last_index + 1


def test_bootstrap_index_traversal(tmp_path):
    """§IV-C-2: a synthetic changelog stream from the object index is
    consumed collaboratively to populate a fresh metrics DB."""
    index = [(i, 1, f"obj{i}", 4096 * i) for i in range(100)]
    log = synthesize_index_stream(index)
    proxy = LcapProxy({"index0": log})
    db = str(tmp_path / "boot.sqlite")
    workers = [MetricsDB(proxy, db) for _ in range(4)]
    pump_all(proxy, workers)
    assert workers[0].query("SELECT COUNT(*) FROM events")[0][0] == 100
    # collaborative: every instance handled part of the traversal
    handled = [proxy.consumers[w.stream.cid].delivered for w in workers]
    assert all(h > 0 for h in handled) and sum(handled) == 100
    for w in workers:
        w.close()


def test_data_consume_records_support_replay():
    trackers, proxy = mk_world(2)
    from repro.core.reader import LocalReader
    r = LocalReader(proxy, "replay")
    trackers[0].data_consume(step=3, shard_id=11, lo=0, hi=512)
    trackers[1].data_consume(step=3, shard_id=12, lo=512, hi=1024)
    proxy.pump()
    got = r.fetch()
    ranges = sorted((rec.xattr["lo"], rec.xattr["hi"]) for _, rec in got)
    assert ranges == [(0, 512), (512, 1024)]


def test_cache_invalidator_requeues_on_handler_failure():
    """A persistent-mode invalidator whose handler dies mid-round must
    not lose the fetched batches: the base poll requeues them and the
    next poll retries from exactly where the failure hit."""
    trackers, proxy = mk_world(2)
    cache = {(oid, 1): f"page-{oid}" for oid in range(8)}
    inv = CacheInvalidator(proxy, cache, mode="persistent")
    for oid in range(8):
        trackers[oid % 2].evict(oid, 1)
    proxy.pump()

    real = inv.handle_batch
    calls = {"n": 0}

    def flaky(pid, batch):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient handler failure")
        real(pid, batch)

    inv.handle_batch = flaky
    try:
        inv.poll()
    except RuntimeError:
        pass
    else:
        raise AssertionError("poll swallowed the handler failure")
    # nothing was acknowledged unhandled; the retry sees every record
    n = 0
    for _ in range(10):
        n += inv.poll()
        proxy.pump()
    assert not cache
    assert inv.invalidated == 8
    inv.close()


def test_metrics_db_failed_close_parks_and_resumes(tmp_path):
    """close(failed=True) on a crashed MetricsDB parks the durable
    cursor (no TypeError from a mismatched override signature); a new
    instance under the same name resumes exactly there."""
    trackers, proxy = mk_world(1)
    db = str(tmp_path / "metrics.sqlite")
    w1 = MetricsDB(proxy, db, name="m0")
    for step in range(10):
        trackers[0].step_commit(step, loss=1.0, step_time_s=0.1, tokens=1)
    proxy.pump()
    w1.poll()                                  # commits: cursor at 10+
    cursor = dict(w1.stream.resume_token)
    for step in range(10, 20):
        trackers[0].step_commit(step, loss=1.0, step_time_s=0.1, tokens=1)
    proxy.pump()                               # dispatched, not yet polled
    w1.close(failed=True)                      # crash: park, don't drop

    w2 = MetricsDB(proxy, db, name="m0")
    assert proxy.stats["resumed"] == 1
    assert w2.stream.resumed
    assert w2.stream.resume_token == cursor    # resumed at the ack cursor
    n = 0
    for _ in range(10):
        n += w2.poll()
        proxy.pump()
    assert n == 10                             # only the unacked backlog
    assert w2.query("SELECT COUNT(*) FROM events")[0][0] == 20
    w2.close()


def test_trainer_step_records_its_spans_nested(tmp_path):
    """One ``Trainer.run`` step records train.step over its parts, and
    train.pump over each consumer's poll, nested as they run."""
    trainer = Trainer(C.get_smoke("mamba2-780m"), workdir=str(tmp_path),
                      global_batch=2, seq_len=16, n_hosts=2,
                      n_metrics_workers=2)
    try:
        lo = time.perf_counter()
        trainer.run(1)
        hi = time.perf_counter()
    finally:
        trainer.close()

    def sel(name):
        return TRACER.select(name, lo, hi)

    (step,) = sel("train.step")
    assert step["count"] == 2 * 16
    for part in ("train.data", "train.launch", "train.wait", "train.track",
                 "train.pump"):
        (s,) = sel(part)
        assert s["parent"] == step["seq"], part
        assert step["t0"] <= s["t0"] <= s["t1"] <= step["t1"]
    (pump,) = sel("train.pump")
    assert len(sel("pump.metrics_db")) == 2
    for part in ("pump.proxy", "pump.metrics_db", "pump.committer",
                 "pump.straggler", "pump.flush"):
        assert (sel(part)["parent"] == pump["seq"]).all(), part
    (proxy_pump,) = sel("proxy.pump")
    assert proxy_pump["parent"] == sel("pump.proxy")["seq"][0]
