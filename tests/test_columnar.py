"""Columnar hot path: decoded header columns, vectorized masks/hash/
fold, and bulk ack tracking agree bit-for-bit with the per-record
implementations they replaced.

Always-run tests drive seeded-random streams through both paths;
hypothesis property tests (skipped when hypothesis is absent, like
test_records.py) widen the input space.
"""

import random
import struct

import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                       # pragma: no cover
    HAVE_HYPOTHESIS = False

from repro.core import records as R
from repro.core.ack import AckTracker
from repro.core.cluster import fid_slot, fid_slots, batch_slots
from repro.core.history import Compactor
from repro.core.modules import (CancelCompensating, CoalesceHeartbeats,
                                ReorderByTarget, TypeFilter)

ALL_TYPES = sorted(R.TYPE_NAMES)


def rand_record(rng: random.Random, index: int,
                rtype: int = None) -> R.ChangelogRecord:
    """A random record; extension fields present per a random mask."""
    flags = rng.randrange(R.CLF_SUPPORTED + 1)
    rec = R.ChangelogRecord(
        type=rtype if rtype is not None else rng.choice(ALL_TYPES),
        index=index, prev=max(0, index - rng.randrange(4)),
        time=rng.randrange(1 << 62),
        tfid=R.Fid(rng.randrange(1 << 64), rng.randrange(1 << 32),
                   rng.randrange(1 << 32)),
        pfid=R.Fid(rng.randrange(1 << 64), rng.randrange(1 << 32),
                   rng.randrange(1 << 32)),
        name=bytes(rng.randrange(97, 123) for _ in range(rng.randrange(9))))
    if flags & R.CLF_RENAME:
        rec.sfid, rec.spfid, rec.sname = (R.Fid(1, 2, 3), R.Fid(4, 5, 6),
                                          b"old")
    if flags & R.CLF_JOBID:
        rec.jobid = b"job-%d" % index
    if flags & R.CLF_SHARD:
        rec.shard = (1, 2, 3, index & 0xFFFF)
    if flags & R.CLF_METRICS:
        rec.metrics = (float(index), -1.5)
    if flags & R.CLF_XATTR:
        rec.xattr = {"i": index}
    return rec


def rand_batch(rng: random.Random, n: int, **kw) -> R.RecordBatch:
    return R.RecordBatch.from_records(
        [rand_record(rng, i + 1, **kw) for i in range(n)])


# ---------------------------------------------------------------- decode
def test_header_columns_match_struct_decode():
    rng = random.Random(1)
    batch = rand_batch(rng, 200)
    idx, typ, fl, tm = (batch.indices_np(), batch.types_np(),
                        batch.flags_np(), batch.times_np())
    tseq, toid, tver = batch.tfid_cols()
    pseq, poid, pver = batch.pfid_cols()
    for i in range(len(batch)):
        buf = batch.packed(i)
        namelen, flags, rtype = struct.unpack_from("<HHH", buf, 0)
        index, prev, time = struct.unpack_from("<QQQ", buf, 8)
        ts, to, tv = struct.unpack_from("<QII", buf, 32)
        ps, po, pv = struct.unpack_from("<QII", buf, 48)
        assert (int(idx[i]), int(typ[i]), int(fl[i]), int(tm[i])) == \
            (index, rtype, flags, time)
        assert (int(tseq[i]), int(toid[i]), int(tver[i])) == (ts, to, tv)
        assert (int(pseq[i]), int(poid[i]), int(pver[i])) == (ps, po, pv)
        # per-record accessors read the same cached columns
        assert batch.packed_index(i) == index
        assert batch.packed_type(i) == rtype
        assert batch.packed_tfid(i) == (ts, to, tv)


def test_columns_survive_select_and_concat():
    rng = random.Random(2)
    batch = rand_batch(rng, 64)
    batch.header()                          # force the cache
    rows = [5, 3, 3, 60, 0]
    sub = batch.select(rows)
    assert sub.indices() == [batch.packed_index(i) for i in rows]
    both = R.RecordBatch.concat([sub, batch[10:12]])
    assert both.types() == ([batch.packed_type(i) for i in rows]
                            + [batch.packed_type(10), batch.packed_type(11)])
    assert both.keys() == ([batch.keys()[i] for i in rows]
                           + batch.keys()[10:12])


# ------------------------------------------------------------------ hash
def _edge_fids():
    return [(0, 0, 0), (1, 0, 0), ((1 << 64) - 1, (1 << 32) - 1,
                                   (1 << 32) - 1), (1 << 63, 1, 2)]


def test_fid_slots_matches_scalar():
    rng = random.Random(3)
    keys = [(rng.randrange(1 << 64), rng.randrange(1 << 32),
             rng.randrange(1 << 32)) for _ in range(2000)] + _edge_fids()
    seq = np.array([k[0] for k in keys], dtype=np.uint64)
    oid = np.array([k[1] for k in keys], dtype=np.uint32)
    ver = np.array([k[2] for k in keys], dtype=np.uint32)
    for n_slots in (1, 2, 63, 64, 97, 1024):
        want = [fid_slot(k, n_slots) for k in keys]
        assert fid_slots(seq, oid, ver, n_slots).tolist() == want


def test_batch_slots_matches_scalar_keys():
    rng = random.Random(4)
    batch = rand_batch(rng, 128)
    assert batch_slots(batch, 64).tolist() == \
        [fid_slot(k, 64) for k in batch.keys()]


def test_jax_fid_slots_matches_scalar():
    stream_ops = pytest.importorskip("repro.kernels.stream_ops")
    rng = random.Random(5)
    keys = [(rng.randrange(1 << 64), rng.randrange(1 << 32),
             rng.randrange(1 << 32)) for _ in range(512)] + _edge_fids()
    seq = np.array([k[0] for k in keys], dtype=np.uint64)
    oid = np.array([k[1] for k in keys], dtype=np.uint32)
    ver = np.array([k[2] for k in keys], dtype=np.uint32)
    for n_slots in (3, 64, 65535):
        want = [fid_slot(k, n_slots) for k in keys]
        assert stream_ops.fid_slots(seq, oid, ver, n_slots).tolist() == want
        assert stream_ops.fid_slots_pallas(seq, oid, ver, n_slots,
                                           interpret=True).tolist() == want


@pytest.mark.parametrize("n", [1, 1000, 32768, 32768 * 2 + 5])
def test_tiled_pallas_fid_slots_matches_numpy(n):
    """Pad rows and tile edges of the gridded kernel drop out: every
    record count gives numpy's slots, in order."""
    stream_ops = pytest.importorskip("repro.kernels.stream_ops")
    rng = np.random.default_rng(n)
    seq = rng.integers(0, 1 << 64, n, dtype=np.uint64)
    oid = rng.integers(0, 1 << 32, n, dtype=np.uint32)
    ver = rng.integers(0, 1 << 32, n, dtype=np.uint32)
    got = stream_ops.fid_slots_pallas(seq, oid, ver, 97, interpret=True)
    np.testing.assert_array_equal(got, fid_slots(seq, oid, ver, 97))


@pytest.mark.parametrize("n_slots", [64, 97])
def test_bucketed_jax_fid_slots_match_numpy_at_every_bucket_edge(n_slots):
    """The twin pads its columns to a bucket length and drops the pad
    rows: on either side of each edge it gives numpy's slots, in order,
    at the input's own length."""
    stream_ops = pytest.importorskip("repro.kernels.stream_ops")
    rng = np.random.default_rng(n_slots)
    for n in (1, 127, 128, 129, 1000, 1023, 1024, 1025, 5000):
        seq = rng.integers(0, 1 << 64, n, dtype=np.uint64)
        oid = rng.integers(0, 1 << 32, n, dtype=np.uint32)
        ver = rng.integers(0, 1 << 32, n, dtype=np.uint32)
        got = stream_ops.fid_slots(seq, oid, ver, n_slots)
        assert got.dtype == np.int64 and got.shape == (n,)
        np.testing.assert_array_equal(got, fid_slots(seq, oid, ver, n_slots))


def test_jax_fid_slots_compile_at_most_four_programs_up_to_1024():
    """Every length one journal read can give (1 .. 1024) runs on one
    of four compiled programs (128, 256, 512, 1024 records)."""
    stream_ops = pytest.importorskip("repro.kernels.stream_ops")
    z = np.zeros(1024, np.uint64)
    before = stream_ops._fid_slots_jit._cache_size()
    for n in range(1, 1025):
        stream_ops.fid_slots(z[:n], z[:n].astype(np.uint32),
                             z[:n].astype(np.uint32), 61)
    assert stream_ops._fid_slots_jit._cache_size() - before <= 4


def test_jax_routing_opt_in_raises_when_twin_cannot_import(monkeypatch):
    """With REPRO_JAX_ROUTING=1 the device twin is used, and a failed
    import of it is an error, never a silent switch to numpy."""
    import sys

    import repro.kernels
    from repro.core import cluster as cluster_mod
    stream_ops = pytest.importorskip("repro.kernels.stream_ops")
    monkeypatch.setenv("REPRO_JAX_ROUTING", "1")
    assert cluster_mod._resolve_jax_fid_slots() is stream_ops.fid_slots
    monkeypatch.delattr(repro.kernels, "stream_ops")
    monkeypatch.setitem(sys.modules, "repro.kernels.stream_ops", None)
    with pytest.raises(ImportError):
        cluster_mod._resolve_jax_fid_slots()


# --------------------------------------------------------------- project
def test_project_strips_like_per_record_remap():
    """The dispatch stamp: ``project(flags)`` strips exactly what a
    per-record ``remap(buf, src & flags)`` strips — and never
    zero-fills fields the record did not carry."""
    rng = random.Random(6)
    batch = rand_batch(rng, 100)
    for want in (0, R.CLF_JOBID, R.CLF_JOBID | R.CLF_SHARD,
                 R.CLF_SUPPORTED):
        out = batch.project(want)
        for i in range(len(batch)):
            src = batch.packed_flags(i)
            assert out.packed(i) == R.remap(batch.packed(i), src & want)
            assert out.packed_flags(i) == src & want    # no zero-fill
    # all-subset fast path: nothing to strip -> same object
    uniform = R.RecordBatch.from_records(
        [rand_record(rng, i + 1) for i in range(4)]).project(R.CLF_SUPPORTED)
    assert uniform.project(R.CLF_SUPPORTED) is uniform


def test_remap_zero_fills_where_project_does_not():
    buf = R.pack(R.ChangelogRecord(type=R.CL_CREATE, index=1,
                                   tfid=R.Fid(1, 2, 3), name=b"f"))
    batch = R.RecordBatch.from_packed([buf])
    stamped = batch.project(R.CLF_JOBID | R.CLF_SHARD)
    assert stamped.packed_flags(0) == 0              # strip-only
    widened = batch.remap(R.CLF_JOBID | R.CLF_SHARD)
    rec = R.unpack(widened.packed(0))
    assert rec.jobid == b"" and rec.shard == (0, 0, 0, 0)   # zero-filled


# --------------------------------------------------------------- modules
def _assert_same(out_batch, out_list):
    assert [bytes(b) for b in out_batch] == [R.pack(r) for r in out_list]


def _module_case(rng, n):
    """A stream that exercises every module: heartbeats, create/unlink
    pairs (some hardlinked), ckpt writes, renames."""
    recs = []
    for i in range(n):
        roll = rng.random()
        if roll < 0.25:
            rec = rand_record(rng, i + 1, rtype=R.CL_HEARTBEAT)
            rec.tfid = R.Fid(0, rng.randrange(4), 0)     # few hosts
        elif roll < 0.5:
            rec = rand_record(rng, i + 1, rtype=rng.choice(
                [R.CL_CREATE, R.CL_UNLINK, R.CL_MKDIR, R.CL_RMDIR,
                 R.CL_HARDLINK]))
            rec.tfid = R.Fid(7, rng.randrange(6), 0)     # few targets
        elif roll < 0.7:
            rec = rand_record(rng, i + 1, rtype=R.CL_CKPT_WRITE)
            rec.tfid = R.Fid(1, rng.randrange(3), rng.randrange(2))
        else:
            rec = rand_record(rng, i + 1)
        recs.append(rec)
    return recs


@pytest.mark.parametrize("seed", [7, 8, 9])
def test_modules_columnar_matches_list_path(seed):
    rng = random.Random(seed)
    recs = _module_case(rng, 120)
    modules = [TypeFilter(set(ALL_TYPES) - {R.CL_MARK}),
               CoalesceHeartbeats(), CancelCompensating(),
               ReorderByTarget()]
    for mod in modules:
        batch = R.RecordBatch.from_records([r for r in recs])
        _assert_same(mod(batch), mod(list(recs)))


def test_reorder_by_target_sorts_and_identity():
    rng = random.Random(10)
    batch = rand_batch(rng, 50)
    out = ReorderByTarget()(batch)
    ks = [(k, i) for k, i in zip(out.keys(), out.indices())]
    assert ks == sorted(ks)
    assert ReorderByTarget()(out) is out       # already sorted: no copy


# ------------------------------------------------------------------ fold
def _reference_compact(batch):
    """The pre-columnar Compactor.compact: per-key dict grouping, every
    key folded."""
    comp = Compactor()
    n = len(batch)
    types = batch.types()
    rows_by_key = {}
    for i, k in enumerate(batch.keys()):
        rows_by_key.setdefault(k, []).append(i)
    drop, replace = set(), {}
    for rows in rows_by_key.values():
        comp._compact_key(batch, types, rows, drop, replace)
    out = [replace.get(i, None) or batch.packed(i)
           for i in range(n) if i not in drop]
    stats = {k: v for k, v in comp.stats.items() if k not in
             ("records_in", "records_out")}
    return out, stats


def _fold_case(rng, n):
    recs = []
    for i in range(n):
        roll = rng.random()
        if roll < 0.45:
            rec = rand_record(rng, i + 1, rtype=rng.choice(
                [R.CL_CREATE, R.CL_UNLINK, R.CL_HARDLINK, R.CL_MKDIR,
                 R.CL_RMDIR]))
        elif roll < 0.7:
            rec = rand_record(rng, i + 1, rtype=rng.choice(
                [R.CL_SETATTR, R.CL_HEARTBEAT, R.CL_MARK]))
        elif roll < 0.85:
            rec = rand_record(rng, i + 1, rtype=R.CL_RENAME)
            rec.sfid, rec.spfid, rec.sname = (R.Fid(9, 9, 9),
                                              R.Fid(8, 8, 8),
                                              b"from-%d" % i)
        else:
            rec = rand_record(rng, i + 1)
        rec.tfid = R.Fid(3, rng.randrange(8), 0)         # collide targets
        recs.append(rec)
    return R.RecordBatch.from_records(recs)


@pytest.mark.parametrize("seed", [11, 12, 13, 14])
def test_compactor_fold_matches_reference(seed):
    rng = random.Random(seed)
    batch = _fold_case(rng, 160)
    want, want_stats = _reference_compact(batch)
    comp = Compactor()
    out = comp.compact(batch)
    assert [bytes(b) for b in out] == [bytes(b) for b in want]
    assert comp.stats["records_in"] == len(batch)
    assert comp.stats["records_out"] == len(want)
    for k, v in want_stats.items():
        assert comp.stats[k] == v, k


def test_compactor_hardlinked_lifetime_survives():
    """A hardlinked CREATE+UNLINK pair must NOT annihilate (the unlink
    may have removed only one name) — on both the segment pre-pass and
    the reference path."""
    def rec(i, t):
        return R.ChangelogRecord(type=t, index=i, tfid=R.Fid(1, 1, 1),
                                 name=b"f%d" % i)
    plain = R.RecordBatch.from_records(
        [rec(1, R.CL_CREATE), rec(2, R.CL_UNLINK)])
    assert len(Compactor().compact(plain)) == 0          # annihilated
    linked = R.RecordBatch.from_records(
        [rec(1, R.CL_CREATE), rec(2, R.CL_HARDLINK), rec(3, R.CL_UNLINK)])
    out = Compactor().compact(linked)
    assert out.indices() == [1, 2, 3]                    # kept whole
    want, _ = _reference_compact(linked)
    assert [bytes(b) for b in out] == [bytes(b) for b in want]


def test_compactor_boring_batch_is_identity():
    rng = random.Random(15)
    batch = R.RecordBatch.from_records(
        [rand_record(rng, i + 1, rtype=R.CL_CREATE) for i in range(32)])
    comp = Compactor()
    assert comp.compact(batch) is batch
    assert comp.stats["records_out"] == 32


# ------------------------------------------------------------------- ack
def _drive_trackers(rounds, rng):
    """Scalar-op tracker vs bulk-op tracker over the same stream."""
    scalar, bulk = AckTracker(), AckTracker()
    live = []
    nxt = 1
    for _ in range(rounds):
        burst = list(range(nxt, nxt + rng.randrange(1, 40)))
        nxt = burst[-1] + 1
        rng.shuffle(burst)
        for i in burst:
            scalar.deliver(i)
        assert bulk.deliver_many(burst + burst[:3]) == len(burst)
        live.extend(burst)
        assert scalar.in_flight == bulk.in_flight
        k = rng.randrange(0, len(live) + 1)
        rng.shuffle(live)
        acks, live = live[:k], live[k:]
        for i in acks:
            scalar.ack(i)
        if rng.random() < 0.5:
            bulk.ack_many(acks)
        else:
            bulk.ack_many(np.asarray(sorted(acks), dtype=np.int64)
                          if acks else [])
        assert scalar.watermark == bulk.watermark
        assert scalar.in_flight == bulk.in_flight
        if rng.random() < 0.2 and live:
            thr = rng.choice(live)
            assert scalar.ack_through(thr) == bulk.ack_through(thr)
            live = [i for i in live if i > thr]
            assert scalar.in_flight == bulk.in_flight
    # drain everything: both converge to the same final watermark
    for i in live:
        scalar.ack(i)
    bulk.ack_many(live)
    assert scalar.watermark == bulk.watermark == nxt - 1
    assert scalar.in_flight == bulk.in_flight == 0


@pytest.mark.parametrize("seed", [16, 17, 18])
def test_ack_tracker_bulk_matches_scalar(seed):
    _drive_trackers(60, random.Random(seed))


def test_ack_tracker_bulk_ignores_stale_and_duplicate():
    tr = AckTracker()
    assert tr.deliver_many([3, 1, 2, 2, 3]) == 3
    assert tr.ack_many([1, 2, 3]) == 3
    assert tr.deliver_many([3, 2, 1]) == 0        # all below watermark
    assert tr.in_flight == 0
    tr.deliver_many([5, 7])
    assert tr.ack_many([7]) == 3                  # hole at 5 blocks
    assert tr.ack_many([5]) == 7


# ----------------------------------------------------- hypothesis widening
if not HAVE_HYPOTHESIS:                   # pragma: no cover
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_property_fid_slots():
        ...

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_property_ack_bulk():
        ...

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_property_compactor_fold():
        ...

else:
    fid_ints = st.tuples(st.integers(0, 2**64 - 1),
                         st.integers(0, 2**32 - 1),
                         st.integers(0, 2**32 - 1))

    @settings(max_examples=100, deadline=None)
    @given(keys=st.lists(fid_ints, min_size=1, max_size=64),
           n_slots=st.integers(1, 4096))
    def test_property_fid_slots(keys, n_slots):
        seq = np.array([k[0] for k in keys], dtype=np.uint64)
        oid = np.array([k[1] for k in keys], dtype=np.uint32)
        ver = np.array([k[2] for k in keys], dtype=np.uint32)
        assert fid_slots(seq, oid, ver, n_slots).tolist() == \
            [fid_slot(k, n_slots) for k in keys]

    @settings(max_examples=50, deadline=None)
    @given(seed=st.integers(0, 2**32 - 1))
    def test_property_ack_bulk(seed):
        _drive_trackers(12, random.Random(seed))

    @settings(max_examples=50, deadline=None)
    @given(seed=st.integers(0, 2**32 - 1))
    def test_property_compactor_fold(seed):
        rng = random.Random(seed)
        batch = _fold_case(rng, rng.randrange(1, 80))
        want, _ = _reference_compact(batch)
        out = Compactor().compact(batch)
        assert [bytes(b) for b in out] == [bytes(b) for b in want]
