"""Sharded LCAP cluster (tentpole): FID-hash routing determinism,
fan-in subscriptions over every shard, collective upstream ack across
shards, and shard failure -> slot re-routing + backlog redelivery with
at-least-once delivery preserved."""

import math
import time

import numpy as np
import pytest

from repro.core import records as R
from repro.core.cluster import (DEFAULT_SLOTS, LcapCluster,
                                LcapClusterService, fid_slot, fid_slots)
from repro.core.errors import ClusterError
from repro.core.llog import Llog
from repro.core.session import Subscription, connect
from repro.obs.spans import TRACER


def rec(oid=1, ver=0, t=R.CL_CREATE, name=b"f", **kw):
    return R.ChangelogRecord(type=t, tfid=R.Fid(1, oid, ver),
                             pfid=R.Fid(1, 0, 0), name=name, **kw)


def mk_cluster(n_producers=2, n_shards=3, **kw):
    logs = {f"mdt{i}": Llog(f"mdt{i}") for i in range(n_producers)}
    return LcapCluster(logs, n_shards=n_shards, **kw), logs


def feed(logs, n_each=20, oids=7):
    for pid, log in logs.items():
        for i in range(n_each):
            log.log(rec(oid=i % oids, name=f"{pid}-{i}".encode()))


def drain_until(cluster, stream, logs, expect, rounds=200):
    """Pump + fetch + commit until ``expect`` (pid, index) pairs were
    seen and every journal trimmed; returns the seen set."""
    seen = set()
    for _ in range(rounds):
        cluster.pump()
        moved = 0
        for pid, batch in stream.fetch(4096):
            seen.update((pid, i) for i in batch.indices())
            moved += len(batch)
        stream.commit()
        if not moved and seen >= expect and all(
                log.first_index == log.last_index + 1
                for log in logs.values()):
            break
    return seen


# ------------------------------------------------------------- routing
def test_fid_slot_is_deterministic_and_uniform():
    keys = [(s, o, v) for s in range(3) for o in range(40) for v in range(3)]
    slots = [fid_slot(k) for k in keys]
    assert slots == [fid_slot(k) for k in keys]       # stable across calls
    assert all(0 <= s < DEFAULT_SLOTS for s in slots)
    hit = set(slots)
    assert len(hit) > DEFAULT_SLOTS // 2              # spreads, no clumping


def test_records_of_one_target_never_split_across_shards():
    """cr_prev chains stay intact: every record of one target FID lands
    on the same shard, so per-target ordering is preserved."""
    cluster, logs = mk_cluster(n_producers=2, n_shards=4)
    sess = connect(cluster)
    stream = sess.subscribe("g", auto_commit=False)
    feed(logs, 40, oids=11)
    owner_by_target = {}
    for _ in range(50):
        cluster.pump()
        moved = 0
        # fetch from each child separately to observe the owning shard
        for shard_idx, child in stream._children:
            for pid, batch in child.fetch(4096):
                for i in range(len(batch)):
                    key = (pid,) + tuple(batch.packed_tfid(i))
                    prev = owner_by_target.setdefault(key, shard_idx)
                    assert prev == shard_idx, \
                        f"target {key} split across shards {prev}/{shard_idx}"
                moved += len(batch)
        stream.commit()
        if not moved and all(log.first_index == log.last_index + 1
                             for log in logs.values()):
            break
    assert owner_by_target                       # something was routed
    assert len({s for s in owner_by_target.values()}) > 1  # actually sharded
    # the routing matches the cluster's published slot map
    for (pid, seq, oid, ver), shard in owner_by_target.items():
        assert cluster.shard_of((seq, oid, ver)) == shard


def test_per_target_order_is_preserved_within_a_shard():
    cluster, logs = mk_cluster(n_producers=1, n_shards=3)
    sess = connect(cluster)
    stream = sess.subscribe("g", auto_commit=False)
    feed(logs, 60, oids=5)
    order_by_target = {}
    for _ in range(50):
        cluster.pump()
        moved = 0
        for pid, batch in stream.fetch(4096):
            for i in range(len(batch)):
                key = batch.packed_tfid(i)
                order_by_target.setdefault(key, []).append(
                    batch.packed_index(i))
            moved += len(batch)
        stream.commit()
        if not moved:
            break
    for key, indices in order_by_target.items():
        assert indices == sorted(indices), key


# ------------------------------------------------------- fan-in + acks
def test_every_group_sees_every_record_and_all_journals_trim():
    cluster, logs = mk_cluster(n_producers=3, n_shards=3)
    sess = connect(cluster)
    s1 = sess.subscribe("g1", auto_commit=False)
    s2 = sess.subscribe("g2", auto_commit=False)
    feed(logs, 25)
    expect = {(pid, i) for pid in logs for i in range(1, 26)}
    seen1, seen2 = set(), set()
    for _ in range(200):
        cluster.pump()
        moved = 0
        for stream, seen in ((s1, seen1), (s2, seen2)):
            for pid, batch in stream.fetch(4096):
                for i in batch.indices():
                    assert (pid, i) not in seen   # exactly once per group
                    seen.add((pid, i))
                moved += len(batch)
            stream.commit()
        if not moved and seen1 == expect and seen2 == expect:
            break
    assert seen1 == expect and seen2 == expect
    # cross-shard collective ack: min watermark across shards trims
    # every journal completely
    for log in logs.values():
        assert log.first_index == log.last_index + 1


def test_fan_in_load_balances_one_group_across_members():
    cluster, logs = mk_cluster(n_producers=1, n_shards=2)
    sess = connect(cluster)
    members = [sess.subscribe("g", auto_commit=False) for _ in range(3)]
    feed(logs, 90, oids=30)
    counts = [0] * len(members)
    for _ in range(100):
        cluster.pump()
        moved = 0
        for k, stream in enumerate(members):
            for pid, batch in stream.fetch(4096):
                counts[k] += len(batch)
                moved += len(batch)
            stream.commit()
        if not moved and sum(counts) >= 90:
            break
    assert sum(counts) == 90
    assert all(c > 0 for c in counts)     # spread across the group


def test_producer_registered_once_late_producer_routes():
    cluster, logs = mk_cluster(n_producers=1, n_shards=2)
    sess = connect(cluster)
    stream = sess.subscribe("g", auto_commit=False)
    extra = Llog("late")
    cluster.add_producer("late", extra)
    extra.log(rec(oid=3))
    feed(logs, 2)
    expect = {("mdt0", 1), ("mdt0", 2), ("late", 1)}
    seen = drain_until(cluster, stream, {**logs, "late": extra}, expect)
    assert seen == expect
    assert extra.first_index == extra.last_index + 1


def test_ephemeral_subscription_fans_in_without_blocking_trim():
    cluster, logs = mk_cluster(n_producers=1, n_shards=2)
    sess = connect(cluster)
    group = sess.subscribe("g", auto_commit=False)
    feed(logs, 5)                          # history
    cluster.pump()
    eph = sess.subscribe(mode="ephemeral", auto_commit=False)
    for i in range(5, 8):
        logs["mdt0"].log(rec(oid=i))
    expect = {("mdt0", i) for i in range(1, 9)}
    seen = drain_until(cluster, group, logs, expect)
    assert seen == expect
    got = {i for _, b in eph.fetch(4096) for i in b.indices()}
    assert got.issubset({6, 7, 8})         # no history (§IV-B)
    # the ephemeral never acked, yet every journal trimmed
    assert logs["mdt0"].first_index == logs["mdt0"].last_index + 1


# ------------------------------------------------------------- failure
def test_shard_kill_redelivers_backlog_no_loss_and_trims():
    cluster, logs = mk_cluster(n_producers=2, n_shards=3)
    sess = connect(cluster)
    stream = sess.subscribe("g", auto_commit=False)
    feed(logs, 50, oids=17)
    cluster.pump()
    # fetch some records without committing: they are in flight on
    # their shards when shard 0 dies
    precrash = stream.fetch(30)
    seen = {(pid, i) for pid, b in precrash for i in b.indices()}
    cluster.kill_shard(0)
    assert cluster.alive[0] is False
    assert all(owner != 0 for owner in cluster.slot_owner)  # re-routed
    stream.commit()                        # acks for shard 0 are dropped
    expect = {(pid, i) for pid in logs for i in range(1, 51)}
    for _ in range(200):
        cluster.pump()
        moved = 0
        for pid, batch in stream.fetch(4096):
            seen.update((pid, i) for i in batch.indices())
            moved += len(batch)
        stream.commit()
        if not moved and seen >= expect and all(
                log.first_index == log.last_index + 1
                for log in logs.values()):
            break
    assert expect - seen == set()          # at-least-once: nothing lost
    assert stream.lost == [0]              # fan-in dropped the dead child
    for log in logs.values():              # dead shard no longer gates trim
        assert log.first_index == log.last_index + 1
    assert cluster.stats["shards_failed"] == 1
    assert cluster.stats["failover_redelivered"] > 0


def test_new_records_after_kill_route_to_survivors():
    cluster, logs = mk_cluster(n_producers=1, n_shards=2)
    sess = connect(cluster)
    stream = sess.subscribe("g", auto_commit=False)
    cluster.kill_shard(1)
    feed(logs, 20, oids=19)                # all slots now owned by shard 0
    expect = {("mdt0", i) for i in range(1, 21)}
    seen = drain_until(cluster, stream, logs, expect)
    assert seen == expect


def test_killing_the_last_shard_raises():
    cluster, logs = mk_cluster(n_producers=1, n_shards=1)
    with pytest.raises(ClusterError):
        cluster.kill_shard(0)


def test_subscribe_after_kill_attaches_only_to_survivors():
    cluster, logs = mk_cluster(n_producers=1, n_shards=2)
    cluster.kill_shard(0)
    stream = connect(cluster).subscribe("g", auto_commit=False)
    assert stream.shards == [1]


# ------------------------------------------------------------- daemons
def test_cluster_service_wire_fan_in_and_shard_aware_subscribe():
    logs = {f"h{i}": Llog(f"h{i}") for i in range(2)}
    cluster = LcapCluster(logs, n_shards=2)
    svc = LcapClusterService(cluster).start()
    try:
        assert len(svc.addresses) == 2     # each shard its own daemon
        sess = connect(svc)
        stream = sess.subscribe(Subscription(group="g", auto_commit=False))
        # the cluster-aware subscribe verb stamped each shard's position
        assert sorted(stream.shards) == [0, 1]
        for pid, log in logs.items():
            for i in range(30):
                log.log(rec(oid=i % 5, name=b"wire"))
        expect = {(pid, i) for pid in logs for i in range(1, 31)}
        seen = set()
        deadline = time.time() + 20
        while time.time() < deadline:
            moved = 0
            for pid, batch in stream.fetch(4096):
                seen.update((pid, i) for i in batch.indices())
                moved += len(batch)
            stream.commit()
            if seen == expect and all(log.first_index == log.last_index + 1
                                      for log in logs.values()):
                break
            if not moved:
                time.sleep(0.005)
        assert seen == expect
        for log in logs.values():
            assert log.first_index == log.last_index + 1
        sess.close()
    finally:
        svc.stop()


def test_cluster_stats_aggregate_across_shards():
    cluster, logs = mk_cluster(n_producers=1, n_shards=2)
    sess = connect(cluster)
    stream = sess.subscribe("g", auto_commit=False)
    feed(logs, 10)
    expect = {("mdt0", i) for i in range(1, 11)}
    drain_until(cluster, stream, logs, expect)
    stats = sess.stats()
    assert stats["dispatched"] == 10       # summed across both shards
    assert set(stats["per_shard"]) == {0, 1}


# ------------------------------------------------------- program spans
def test_one_round_records_every_fabric_span_with_the_stats_counts():
    """One in-process round: the journal appends, the journal wait, the
    coordinator's round over route (over the slot kernel), offer and
    ack, each shard's pump over ingest and dispatch, the buffer and
    outbox waits, and the members' fetch and commit, each with the
    records the stats count."""
    cluster, logs = mk_cluster(n_producers=3, n_shards=2)
    stream = connect(cluster).subscribe(Subscription(group="g",
                                                     auto_commit=False))
    lo = time.perf_counter()
    t0 = time.time_ns()
    for pid, log in logs.items():
        log.log_batch([rec(oid=i, name=pid.encode(), time=t0)
                       for i in range(40)])
    cluster.pump()
    fetched = sum(len(b) for _, b in stream.fetch(4096))
    committed = stream.commit()
    hi = time.perf_counter()

    def sel(name):
        return TRACER.select(name, lo, hi)

    routed = cluster.stats["routed"]
    dispatched = sum(s.proxy.stats["dispatched"] for s in cluster.shards)
    assert routed == dispatched == fetched == committed == 120
    assert sel("journal.append")["count"].tolist() == [40, 40, 40]
    (wait,) = sel("journal.wait")
    assert wait["count"] == routed
    # the records waited from their cr_time (t0) to the route's read
    assert 0 < wait["t1"] - wait["t0"] <= time.time_ns() - t0
    (rnd,) = sel("cluster.round")
    (route,) = sel("cluster.route")
    assert rnd["count"] == route["count"] == routed
    assert route["parent"] == rnd["seq"]
    # one slot-kernel call for the round's three journal reads
    (slots,) = sel("cluster.route.slots")
    assert slots["count"] == routed and cluster.stats["slot_calls"] == 1
    assert slots["parent"] == route["seq"]
    (offer,) = sel("cluster.offer")
    assert offer["parent"] == route["seq"] and offer["count"] == routed
    (ack,) = sel("cluster.ack")
    assert ack["parent"] == rnd["seq"]
    pumps = sel("proxy.pump")
    assert len(pumps) == 2 and (pumps["parent"] == rnd["seq"]).all()
    disp = sel("proxy.dispatch")
    assert disp["count"].sum() == dispatched
    assert set(disp["parent"].tolist()) == set(pumps["seq"].tolist())
    assert len(sel("proxy.ingest")) == 2
    assert len(sel("proxy.dispatch.fallback")) == 0
    assert sel("proxy.buffer_wait")["count"].sum() == dispatched
    assert sel("proxy.outbox_wait")["count"].sum() == fetched
    assert sel("session.fetch")["count"].sum() == fetched
    assert sel("session.commit")["count"].sum() == committed


def test_round_with_the_recorder_off_delivers_and_records_nothing(
        monkeypatch):
    monkeypatch.setattr(TRACER, "enabled", False)
    cluster, logs = mk_cluster(n_producers=2, n_shards=2)
    stream = connect(cluster).subscribe(Subscription(group="g",
                                                     auto_commit=False))
    feed(logs, n_each=30)
    lo = time.perf_counter()
    expect = {(pid, i) for pid in logs for i in range(1, 31)}
    assert drain_until(cluster, stream, logs, expect) == expect
    monkeypatch.setattr(TRACER, "enabled", True)
    for name in ("cluster.round", "journal.wait", "proxy.outbox_wait",
                 "session.fetch"):
        assert len(TRACER.select(name, lo)) == 0


# ------------------------------------------------------- slot-kernel calls
@pytest.fixture(params=["twin", "numpy"])
def slot_calls(request, monkeypatch):
    """Every call of the routing kernel, as (length, slots): the device
    twin, wrapped where the chip benchmark's recorder wraps it, or the
    numpy hash."""
    from repro.core import cluster as cluster_mod
    if request.param == "twin":
        from repro.kernels import stream_ops
        monkeypatch.setenv("REPRO_JAX_ROUTING", "1")
        module = stream_ops
    else:
        monkeypatch.delenv("REPRO_JAX_ROUTING", raising=False)
        module = cluster_mod
    calls = []
    inner = module.fid_slots

    def counted(seq, oid, ver, n_slots):
        out = inner(seq, oid, ver, n_slots)
        calls.append((len(seq), out))
        return out

    monkeypatch.setattr(module, "fid_slots", counted)
    cluster_mod._reset_jax_probe()
    yield calls
    cluster_mod._reset_jax_probe()


def test_a_round_over_16_journals_takes_one_slot_call_per_batch_size(
        slot_calls, monkeypatch):
    """The round's reads go to the kernel together, in calls of at most
    ``batch_size`` records, and partition exactly as each journal read
    routed alone would."""
    cluster, logs = mk_cluster(n_producers=16, n_shards=4, batch_size=64)
    connect(cluster).subscribe(Subscription(group="g", auto_commit=False))
    for k, log in enumerate(logs.values()):
        log.log_batch([rec(oid=1000 * k + i) for i in range(10 + 7 * k)])
    # the reference: each journal read routed alone, by the numpy hash
    owner = cluster.routing.owner_array()
    want_slots, want_offers = [], [[] for _ in cluster.shards]
    for pid, log in cluster.journals.items():
        lo = cluster.cursors[pid]
        while batch := log.read(lo, cluster.batch_size):
            slots = fid_slots(*batch.tfid_cols(), DEFAULT_SLOTS)
            lo = batch.packed_index(len(batch) - 1) + 1
            want_slots.append(slots)
            for i, shard_offers in enumerate(want_offers):
                rows = np.flatnonzero(owner[slots] == i)
                shard_offers.append((pid, batch.select(rows).indices(),
                                     lo - 1))
    offered = []
    offer = cluster._offer

    def capture(offers):
        offered.append([[(pid, b.indices(), hi) for pid, b, hi in o]
                        for o in offers])
        return offer(offers)

    monkeypatch.setattr(cluster, "_offer", capture)
    cluster.pump()
    routed = cluster.stats["routed"]
    assert routed == sum(10 + 7 * k for k in range(16)) == 1000
    assert len(want_slots) > 16           # some journals take two reads
    assert [n for n, _ in slot_calls] == [64] * 15 + [40]
    assert len(slot_calls) == math.ceil(routed / cluster.batch_size) \
        == cluster.stats["slot_calls"]
    np.testing.assert_array_equal(
        np.concatenate([out for _, out in slot_calls]),
        np.concatenate(want_slots))
    assert offered == [want_offers]


def test_reads_stop_at_the_park_cap_while_slots_drain(slot_calls):
    """While a migration drains, each read is placed before the next, so
    the round stops reading exactly where the parked rows reach the
    cap, as when every read took its own call; the stream still ends
    exactly once."""
    cluster, logs = mk_cluster(n_producers=4, n_shards=2, park_cap=40,
                               batch_size=16)
    stream = connect(cluster).subscribe(Subscription(group="g",
                                                     auto_commit=False))

    def feed_each(lo, hi):
        for k, log in enumerate(logs.values()):
            log.log_batch([rec(oid=1000 * k + i) for i in range(lo, hi)])

    feed_each(0, 20)
    cluster.pump()                        # in flight, not committed
    assert cluster.migrate_slots(cluster.routing.slots_of(0), 1)
    assert cluster._migration is not None
    feed_each(20, 80)
    drain = cluster.routing.draining_mask()
    cursors, parked, reads = dict(cluster.cursors), cluster._parked_count, 0
    for pid, log in cluster.journals.items():
        while parked < cluster.park_cap:
            batch = log.read(cursors[pid], cluster.batch_size)
            if not batch:
                break
            cursors[pid] = batch.packed_index(len(batch) - 1) + 1
            parked += int(drain[fid_slots(*batch.tfid_cols(),
                                          DEFAULT_SLOTS)].sum())
            reads += 1
            if len(batch) < cluster.batch_size:
                break
    assert any(cursors[pid] <= log.last_index for pid, log in logs.items())
    calls0, slot_calls[:] = cluster.stats["slot_calls"], []
    cluster._route()
    assert cluster.cursors == cursors
    assert cluster._parked_count == parked >= cluster.park_cap
    assert len(slot_calls) == reads == cluster.stats["slot_calls"] - calls0
    expect = {(pid, i) for pid in logs for i in range(1, 81)}
    assert drain_until(cluster, stream, logs, expect) == expect
