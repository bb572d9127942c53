"""LCAP proxy behaviour (paper §III, §IV-B): aggregation from multiple
producers, consumer groups with load balancing, broadcast across groups,
collective upstream acknowledgement, at-least-once redelivery, ephemeral
readers, backpressure."""

import time

import pytest

from repro.core import records as R
from repro.core.llog import Llog
from repro.core.proxy import EPHEMERAL, Group, LcapProxy
from repro.core.reader import LocalReader
from repro.core.session import Subscription, connect
from repro.obs import MetricsRegistry
from repro.obs.spans import TRACER


def rec(t=R.CL_CREATE, oid=1, name=b"f", **kw):
    return R.ChangelogRecord(type=t, tfid=R.Fid(1, oid, 0),
                             pfid=R.Fid(1, 0, 0), name=name, **kw)


def mk_proxy(n_producers=2, **kw):
    logs = {f"mdt{i}": Llog(f"mdt{i}") for i in range(n_producers)}
    proxy = LcapProxy(logs, **kw)
    return proxy, logs


def feed(logs, n_each=10):
    for pid, log in logs.items():
        for i in range(n_each):
            log.log(rec(oid=i, name=f"{pid}-{i}".encode()))


def drain(reader, limit=10_000):
    got = []
    while True:
        batch = reader.fetch(256)
        if not batch:
            return got
        got.extend(batch)
        assert len(got) < limit


def test_aggregates_all_producers():
    proxy, logs = mk_proxy(3)
    feed(logs, 5)
    r = LocalReader(proxy, "g")
    proxy.pump()
    got = drain(r)
    assert len(got) == 15
    assert {pid for pid, _ in got} == {"mdt0", "mdt1", "mdt2"}


def test_group_load_balancing_spreads_records():
    """The stream is spread among instances of a single group (fig. 2)."""
    proxy, logs = mk_proxy(1)
    readers = [LocalReader(proxy, "g") for _ in range(4)]
    feed(logs, 100)
    proxy.pump()
    counts = [len(drain(r)) for r in readers]
    assert sum(counts) == 100
    assert all(c > 0 for c in counts)
    assert max(counts) - min(counts) <= 2   # least-loaded keeps it even


def test_each_group_sees_every_record():
    """If multiple groups co-exist, every record is delivered to each."""
    proxy, logs = mk_proxy(1)
    g1 = [LocalReader(proxy, "g1") for _ in range(2)]
    g2 = [LocalReader(proxy, "g2")]
    feed(logs, 20)
    proxy.pump()
    got1 = sum((drain(r) for r in g1), [])
    got2 = drain(g2[0])
    assert len(got1) == 20 and len(got2) == 20
    assert {r.index for _, r in got1} == {r.index for _, r in got2}


def test_upstream_ack_requires_every_group():
    """Records are acknowledged upstream only once acknowledged by every
    group (at-least-once)."""
    proxy, logs = mk_proxy(1)
    log = logs["mdt0"]
    r1 = LocalReader(proxy, "g1")
    r2 = LocalReader(proxy, "g2")
    feed(logs, 4)
    proxy.pump()
    for pid, r in drain(r1):
        r1.ack(pid, r.index)
    assert log.first_index == 1          # g2 has not acked
    for pid, r in drain(r2):
        r2.ack(pid, r.index)
    assert log.first_index == 5          # all groups acked -> trimmed


def test_out_of_order_batched_acks():
    proxy, logs = mk_proxy(1)
    log = logs["mdt0"]
    r = LocalReader(proxy, "g")
    feed(logs, 5)
    proxy.pump()
    got = drain(r)
    order = [2, 4, 1, 5, 3]              # delayed and batched (paper §II)
    for idx in order[:2]:
        r.ack("mdt0", idx)
    assert log.first_index == 1          # hole at 1
    r.ack("mdt0", 1)
    assert log.first_index == 3          # 1,2 contiguous
    r.ack("mdt0", 5)
    r.ack("mdt0", 3)
    assert log.first_index == 6


def test_at_least_once_redelivery_on_failure():
    """A dead consumer's unacked records are redelivered to the group."""
    proxy, logs = mk_proxy(1)
    a = LocalReader(proxy, "g")
    b = LocalReader(proxy, "g")
    feed(logs, 20)
    proxy.pump()
    got_a = drain(a)
    assert got_a                          # a holds in-flight records
    a.close(failed=True)                  # crash before acking
    got_b = drain(b)
    proxy.pump()
    got_b += drain(b)
    seen = {r.index for _, r in got_b}
    assert seen == set(range(1, 21))      # b eventually sees everything
    assert proxy.stats["redelivered"] >= len(got_a)
    for pid, r in got_b:
        b.ack(pid, r.index)
    assert logs["mdt0"].first_index == 21


def test_group_with_no_members_parks_records():
    proxy, logs = mk_proxy(1)
    proxy.groups.setdefault("g", Group("g"))
    feed(logs, 3)
    proxy.pump()
    # no member yet: records parked, nothing acked upstream
    assert logs["mdt0"].first_index == 1
    r = LocalReader(proxy, "g")
    got = drain(r)
    assert len(got) == 3                  # drained on subscribe


def test_ephemeral_reader_radio_semantics():
    """Ephemeral readers miss history, need no acks, and never block the
    upstream trim (paper §IV-B)."""
    proxy, logs = mk_proxy(1)
    log = logs["mdt0"]
    persistent = LocalReader(proxy, "g")
    feed(logs, 5)                         # history
    proxy.pump()
    eph = LocalReader(proxy, None, mode=EPHEMERAL)
    for i in range(5, 8):
        log.log(rec(oid=i))
    proxy.pump()
    got = drain(eph)
    assert [r.index for _, r in got] == [6, 7, 8]   # no history
    eph.ack("mdt0", 6)                    # a no-op, not an error
    for pid, r in drain(persistent):
        persistent.ack(pid, r.index)
    assert log.first_index == 9           # eph never blocks trimming
    eph.close()


def test_ephemeral_stops_receiving_after_close():
    proxy, logs = mk_proxy(1)
    LocalReader(proxy, "g")
    eph = LocalReader(proxy, None, mode=EPHEMERAL)
    feed(logs, 2)
    proxy.pump()
    assert len(drain(eph)) == 2
    eph.close()
    feed(logs, 2)
    proxy.pump()
    with pytest.raises(KeyError):
        proxy.fetch(eph.cid)


def test_remote_remap_strips_unrequested_fields():
    """The proxy strips fields the consumer did not express via flags."""
    proxy, logs = mk_proxy(1)
    narrow = LocalReader(proxy, "old", flags=0)
    wide = LocalReader(proxy, "new", flags=R.CLF_SUPPORTED)
    logs["mdt0"].log(rec(jobid=b"JOB", metrics=(3.5,)))
    proxy.pump()
    (_, r_old), = drain(narrow)
    (_, r_new), = drain(wide)
    assert r_old.jobid is None and r_old.metrics is None
    assert r_new.jobid == b"JOB" and r_new.metrics == (3.5,)


def test_local_remap_zero_fills_requested_fields():
    """A consumer requesting fields the producer never wrote sees them
    zero-filled (local remap)."""
    proxy, logs = mk_proxy(1)
    r = LocalReader(proxy, "g", flags=R.CLF_JOBID | R.CLF_SHARD)
    logs["mdt0"].log(rec())               # no extensions at all
    proxy.pump()
    (_, out), = drain(r)
    assert out.jobid == b"" and out.shard == (0, 0, 0, 0)


def test_backpressure_stops_dispatch_not_ingest_overflow():
    proxy, logs = mk_proxy(1, outbox_cap=8)
    r = LocalReader(proxy, "g")
    feed(logs, 64)
    proxy.pump()
    # dispatch halted at the cap; buffer holds the rest
    assert len(proxy.consumers[r.cid].outbox) <= 8
    drained = drain(r)
    proxy.pump()
    drained += drain(r)
    while True:
        proxy.pump()
        more = drain(r)
        if not more:
            break
        drained += more
    assert len(drained) == 64


def test_greedy_batched_ingest_counts():
    proxy, logs = mk_proxy(2, batch_size=16)
    feed(logs, 50)
    LocalReader(proxy, "g")
    proxy.pump()
    assert proxy.stats["ingested"] == 100
    assert proxy.cursors["mdt0"] == 51


def test_late_producer_registration():
    proxy, logs = mk_proxy(1)
    r = LocalReader(proxy, "g")
    extra = Llog("mdt9")
    proxy.add_producer("mdt9", extra)
    extra.log(rec(oid=1))
    feed(logs, 1)
    proxy.pump()
    got = drain(r)
    assert {pid for pid, _ in got} == {"mdt0", "mdt9"}


def test_fetch_and_ack_unknown_consumer_error_is_clear():
    """Satellite regression: unknown/unsubscribed consumer ids raise a
    KeyError that names the consumer, not an opaque dict lookup."""
    proxy, logs = mk_proxy(1)
    with pytest.raises(KeyError, match="unknown or unsubscribed.*nope"):
        proxy.fetch("nope")
    with pytest.raises(KeyError, match="unknown or unsubscribed.*nope"):
        proxy.ack("nope", "mdt0", 1)
    with pytest.raises(KeyError, match="unknown or unsubscribed.*nope"):
        proxy.fetch_batches("nope")
    with pytest.raises(KeyError, match="unknown or unsubscribed.*nope"):
        proxy.ack_batch("nope", "mdt0", [1])
    r = LocalReader(proxy, "g")
    r.close()
    with pytest.raises(KeyError, match="unknown or unsubscribed"):
        proxy.fetch(r.cid)


def test_batch_fetch_and_batch_ack_roundtrip():
    """fetch_batches returns per-producer RecordBatches; ack_batch
    acknowledges a whole batch and propagates the collective watermark."""
    proxy, logs = mk_proxy(2)
    r = LocalReader(proxy, "g")
    feed(logs, 10)
    proxy.pump()
    total = 0
    while True:
        batches = r.fetch_batches(64)
        if not batches:
            break
        for pid, batch in batches:
            assert isinstance(batch, R.RecordBatch)
            total += len(batch)
            r.ack_batch(pid, batch.indices())
    assert total == 20
    assert all(log.first_index == 11 for log in logs.values())


def test_proxy_restart_resumes_at_own_watermark_not_trim_point():
    """Bugfix regression: a restarted proxy must resume at the lcap
    reader's own acked watermark.  A slower co-registered reader holds
    the journal's trim point (first_index) back; resuming there
    re-ingests records the proxy already delivered and acked, and every
    group sees them twice."""
    log = Llog("mdt0")
    slow = log.register_reader("slow-audit")      # lags; holds the trim
    proxy1 = LcapProxy({"mdt0": log})
    r1 = LocalReader(proxy1, "g")
    for i in range(10):
        log.log(rec(oid=i))
    proxy1.pump()
    for pid, r in drain(r1):
        r1.ack(pid, r.index)
    assert log.first_index == 1                   # slow reader: no trim
    assert log.reader_position("lcap-mdt0") == 10

    # the proxy process dies and restarts against the same journal
    proxy2 = LcapProxy({"mdt0": log})
    assert proxy2.cursors["mdt0"] == 11           # resumed, not rewound
    r2 = LocalReader(proxy2, "g")
    proxy2.pump()
    assert drain(r2) == []                        # nothing re-ingested
    assert proxy2.stats["ingested"] == 0
    log.log(rec(oid=99))                          # new records still flow
    proxy2.pump()
    (_, nr), = drain(r2)
    assert nr.index == 11
    log.ack(slow, 11)                             # slow reader catches up
    r2.ack("mdt0", 11)
    assert log.first_index == 12


def test_restart_redelivers_backlog_the_first_incarnation_never_acked():
    """At-least-once across the *first* restart: a proxy that attached
    to a journal with existing records, delivered them, and died before
    any consumer ack must re-ingest them — its reader owes acks for the
    whole live backlog from the moment it attaches (Llog.attach_reader),
    not merely for records logged after registration."""
    log = Llog("mdt0")
    log.register_reader("holder")                 # arms logging
    for i in range(10):
        log.log(rec(oid=i))
    proxy1 = LcapProxy({"mdt0": log})             # fresh attach, backlog
    r1 = LocalReader(proxy1, "g")
    proxy1.pump()
    assert len(drain(r1)) == 10                   # delivered, NOT acked

    proxy2 = LcapProxy({"mdt0": log})             # proxy crashed
    assert proxy2.cursors["mdt0"] == 1            # owes the full backlog
    r2 = LocalReader(proxy2, "g")
    proxy2.pump()
    got = drain(r2)
    assert [r.index for _, r in got] == list(range(1, 11))
    for pid, r in got:
        r2.ack(pid, r.index)
    assert log.reader_position("lcap-mdt0") == 10


def test_ephemeral_gets_no_history_from_late_added_producer():
    """Bugfix regression (§IV-B): a producer added after an ephemeral
    consumer attached must not leak its journaled history — the
    connection point is stamped per producer at add_producer time."""
    proxy, logs = mk_proxy(1)
    LocalReader(proxy, "g")                       # arms dispatch
    eph = LocalReader(proxy, None, mode=EPHEMERAL)
    late = Llog("late")
    late.register_reader("hold")                  # arms logging pre-attach
    for i in range(5):
        late.log(rec(oid=i))                      # history before joining
    proxy.add_producer("late", late)
    proxy.pump()
    got = drain(eph)
    assert [pid for pid, _ in got] == []          # no leaked history
    late.log(rec(oid=9))
    feed(logs, 1)
    proxy.pump()
    got = drain(eph)
    assert {(pid, r.index) for pid, r in got} == {("late", 6), ("mdt0", 1)}


def test_backpressure_is_per_group_idle_group_keeps_draining():
    """Bugfix regression: one saturated persistent consumer must stall
    only its own group; the other groups keep draining."""
    proxy, logs = mk_proxy(1, outbox_cap=8)
    stuck = LocalReader(proxy, "stuck")           # never fetches
    live = LocalReader(proxy, "live")
    feed(logs, 100)
    for _ in range(30):
        proxy.pump()
    # the live group drained everything despite the saturated group
    got_live = drain(live)
    while True:
        proxy.pump()
        more = drain(live)
        if not more:
            break
        got_live += more
    assert len(got_live) == 100
    assert len(proxy.consumers[stuck.cid].outbox) >= 8   # stuck at cap
    # nothing was acked upstream yet: the stuck group still owes acks
    for pid, r in got_live:
        live.ack(pid, r.index)
    assert logs["mdt0"].first_index == 1
    # the stuck group recovers: parked records are redelivered in order
    got_stuck = []
    while True:
        more = drain(stuck)
        if not more:
            proxy.pump()
            more = drain(stuck)
            if not more:
                break
        got_stuck += more
        for pid, r in more:
            stuck.ack(pid, r.index)
    assert [r.index for _, r in got_stuck] == list(range(1, 101))
    assert logs["mdt0"].first_index == 101        # full collective trim


def test_ingest_rotates_producers_under_full_buffer():
    """Bugfix regression: with a buffer smaller than one producer's
    backlog, dict-order draining starved every later producer.  The
    rotation must interleave producers across pumps."""
    proxy, logs = mk_proxy(2, batch_size=8, max_buffer=8)
    r = LocalReader(proxy, "g")
    feed(logs, 64)
    seen_producers = set()
    for _ in range(4):                            # a few constrained pumps
        proxy.pump()
        for pid, rec_ in drain(r):
            seen_producers.add(pid)
            r.ack(pid, rec_.index)
    assert seen_producers == {"mdt0", "mdt1"}     # both flow early
    # and nothing is lost overall
    got = []
    for _ in range(100):
        proxy.pump()
        more = drain(r)
        for pid, rec_ in more:
            r.ack(pid, rec_.index)
        got += more
        if all(log.first_index == log.last_index + 1
               for log in logs.values()):
            break
    assert all(log.first_index == 65 for log in logs.values())


try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                       # pragma: no cover
    HAVE_HYPOTHESIS = False


if not HAVE_HYPOTHESIS:                   # pragma: no cover
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_property_exactly_once_per_group_and_full_trim():
        ...

else:
    @settings(max_examples=30, deadline=None)
    @given(
        n_producers=st.integers(1, 3),
        n_groups=st.integers(1, 3),
        members_per_group=st.integers(1, 3),
        n_records=st.integers(0, 40),
        fail_one=st.booleans(),
    )
    def test_property_exactly_once_per_group_and_full_trim(
            n_producers, n_groups, members_per_group, n_records, fail_one):
        """System invariants under random topologies: (1) every group sees
        every record exactly once (at-least-once collapses to exactly-once
        when consumers ack everything they fetch); (2) after all acks every
        journal is fully trimmed; (3) a mid-stream consumer failure never
        loses records."""
        proxy, logs = mk_proxy(n_producers)
        groups = {f"g{gi}": [LocalReader(proxy, f"g{gi}")
                             for _ in range(members_per_group)]
                  for gi in range(n_groups)}
        feed(logs, n_records)
        proxy.pump()
        if fail_one and n_records and members_per_group > 1:
            groups["g0"][0].close(failed=True)
            groups["g0"] = groups["g0"][1:]
        seen = {g: [] for g in groups}
        for _ in range(200):
            moved = 0
            for g, readers in groups.items():
                for r in readers:
                    for pid, rec in r.fetch(64):
                        seen[g].append((pid, rec.index))
                        r.ack(pid, rec.index)
                        moved += 1
            proxy.pump()
            proxy.flush_upstream()
            if not moved and all(len(s) >= n_producers * n_records
                                 for s in seen.values()):
                break
        expect = {(f"mdt{p}", i) for p in range(n_producers)
                  for i in range(1, n_records + 1)}
        for g, s in seen.items():
            assert sorted(s) == sorted(expect), g  # exactly once per group
        for log in logs.values():
            assert log.first_index == log.last_index + 1   # fully trimmed


# ------------------------------------------------- dispatch path, spans
def _spans(name, lo):
    return TRACER.select(name, lo)


def test_saturated_group_takes_the_per_record_loop_and_counts_it():
    """A batch that would overrun a member's outbox leaves the columnar
    path: the records the per-record loop handles are what
    ``dispatch_fallback_records`` and the ``proxy.dispatch.fallback``
    spans count; the last batch, which fits, goes columnar again."""
    proxy, logs = mk_proxy(1, outbox_cap=8)
    stream = connect(proxy).subscribe(Subscription(group="g",
                                                   auto_commit=False))
    logs["mdt0"].log_batch([rec(oid=i) for i in range(20)])
    lo = time.perf_counter()
    got = 0
    for _ in range(6):
        proxy.pump()
        got += sum(len(b) for _, b in stream.fetch(64))
        stream.commit()
    assert got == 20
    # 8 records fill the outbox, 8 more after the fetch, the last 4 fit
    assert proxy.stats["dispatch_fallback_batches"] == 2
    assert proxy.stats["dispatch_fallback_records"] == 16
    fb = _spans("proxy.dispatch.fallback", lo)
    assert list(fb["count"]) == [8, 8]
    assert _spans("proxy.dispatch", lo)["count"].sum() == 20
    waits = _spans("proxy.buffer_wait", lo)
    assert waits["count"].sum() == 20
    assert (waits["t1"] >= waits["t0"]).all()
    assert _spans("proxy.outbox_wait", lo)["count"].sum() == 20


def test_columnar_dispatch_leaves_the_fallback_counters_at_zero():
    proxy, logs = mk_proxy(2)
    reg = MetricsRegistry()
    proxy.attach_registry(reg)
    stream = connect(proxy).subscribe(Subscription(group="g"))
    feed(logs, 30)
    lo = time.perf_counter()
    proxy.pump()
    assert sum(len(b) for _, b in stream.fetch(256)) == 60
    assert proxy.stats["dispatch_fallback_batches"] == 0
    assert proxy.stats["dispatch_fallback_records"] == 0
    assert len(_spans("proxy.dispatch.fallback", lo)) == 0
    (pump,) = _spans("proxy.pump", lo)
    assert pump["count"] == 120                     # 60 in, 60 out
    (ingest,) = _spans("proxy.ingest", lo)
    assert ingest["count"] == 60 and ingest["parent"] == pump["seq"]
    snap = reg.snapshot()
    for key in ("batches", "records"):
        (sample,) = snap[f"lcap_proxy_dispatch_fallback_{key}_total"][
            "samples"]
        assert sample[1] == 0
    # the pump-latency histogram is fed from the proxy.pump span
    (hist,) = snap["lcap_pump_latency_seconds"]["samples"]
    assert hist[1]["count"] == 1
    assert hist[1]["sum"] == pytest.approx(
        (pump["t1"] - pump["t0"]) * 1e-9)
    spans = {lb["span"] for lb, _ in
             snap["lcap_span_records_total"]["samples"]}
    assert {"proxy.pump", "proxy.dispatch", "proxy.buffer_wait",
            "proxy.outbox_wait", "session.fetch"} <= spans
