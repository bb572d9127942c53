"""Driver of a changelog-fabric cell: MDT journals (``Llog``) behind an
in-process sharded ``LcapCluster`` whose routing hash runs on the chip,
drained by one persistent consumer group through
``connect(cluster).subscribe(...)``.

One host thread plays every part, round after round: the load
generator appends the records that are due (open loop: the schedule
does not wait for the system) through ``Llog.log_batch``, the MDT's own
write path; ``cluster.pump()`` routes, dispatches and acks; each member
fetches and commits.

End to end: ``records_per_s`` counts the records the members fetched
(and committed) inside the window, over the window.
``delivery_p50_ms`` is the median, over every record due in the
window, of the time from its due time (its ``cr_time``) to the fetch
that handed it to a member; a record not fetched by the window's end
counts at its age then, and in ``failed``.  ``delivery_p95_ms``, the
95th percentile of the same sample, is a per-layer reading: a stall of
the host of a second or more, which a run meets now and then, moves it
by tens of per cent.

Once the window has closed the generator appends what is left of the
schedule and the rounds go on, without a clock, until every journal
is trimmed (or ``drain_timeout_s`` passes).  Then every record is
checked: fetched exactly once by the group, with the header it was
generated with; every slot the chip computed equal to the plain numpy
hash; every journal trimmed to the group's committed point.
"""

from __future__ import annotations

import os
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from . import mdtest

_C1, _C2 = 0xBF58476D1CE4E5B9, 0x94D049BB133111EB
_MIX = 0x9E3779B97F4A7C15


def fid_slots_ref(seq, oid, ver, n_slots: int) -> np.ndarray:
    """Slot of each target FID: the splitmix64 finaliser over
    ``seq*C1 ^ oid*C2 ^ ver*MIX``, modulo ``n_slots``, in plain wrapping
    uint64 numpy."""
    u = np.uint64
    with np.errstate(over="ignore"):
        z = (np.asarray(seq, u) * u(_C1) ^ np.asarray(oid, u) * u(_C2)
             ^ np.asarray(ver, u) * u(_MIX))
        z = (z ^ (z >> u(30))) * u(_C1)
        z = (z ^ (z >> u(27))) * u(_C2)
        return ((z ^ (z >> u(31))) % u(n_slots)).astype(np.int64)


class SlotRecorder:
    """Wraps the device routing twin; keeps every call's inputs and
    output for the check after the window."""

    def __init__(self, inner):
        self.inner = inner
        self.calls: List[Tuple] = []

    def __call__(self, seq, oid, ver, n_slots):
        out = self.inner(seq, oid, ver, n_slots)
        self.calls.append((np.array(seq), np.array(oid), np.array(ver),
                           n_slots, out))
        return out

    def mismatches(self) -> Tuple[int, int]:
        bad = total = 0
        for seq, oid, ver, n, out in self.calls:
            bad += int(np.count_nonzero(
                np.asarray(out) != fid_slots_ref(seq, oid, ver, n)))
            total += len(out)
        return bad, total


def warm_routing(kernel, batch_size: int, n_slots: int,
                 threads: int = 8) -> None:
    """Run the routing twin once at every batch length a journal read can
    give (1 .. batch_size), so that nothing compiles in the window.  The
    lengths compile (or load from the persistent cache) on a few threads
    at once; XLA compiles outside Python's lock."""
    from concurrent.futures import ThreadPoolExecutor

    z = np.zeros(batch_size, np.uint64)

    def one(n):
        return kernel(z[:n], z[:n].astype(np.uint32),
                      z[:n].astype(np.uint32), n_slots)

    with ThreadPoolExecutor(threads) as pool:
        for _ in pool.map(one, range(1, batch_size + 1)):
            pass


def plant(fab: "Fabric", fault: str) -> None:
    """Break the timed path underneath the driver, for the check's own
    tests: each fault must turn ``correct`` false.

    - ``drop_half``: each member hands on only the first half of every
      batch it fetched (half of the batch left out);
    - ``alter``: the first record fetched comes back with another type
      (an answer altered where it is produced);
    - ``bad_slot``: the chip's routing of the first batch is off by one
      slot;
    - ``no_ack``: members' commits go nowhere (the state never moves).
    """
    from repro.core import records as R

    if fault == "bad_slot":
        inner = fab.slots.inner

        def off_by_one(seq, oid, ver, n_slots, _first=[True]):
            out = inner(seq, oid, ver, n_slots)
            if _first[0] and len(out):
                _first[0] = False
                out = (out + 1) % n_slots
            return out

        fab.slots.inner = off_by_one
        return
    for m in fab.members:
        if fault == "no_ack":
            m.commit = lambda: 0
            continue
        fetch = m.fetch

        def broken(max_records=None, _fetch=fetch, _first=[True]):
            pairs = _fetch(max_records)
            if fault == "drop_half":
                return [(pid, b.select(np.arange(len(b) // 2)))
                        for pid, b in pairs]
            if fault == "alter" and pairs and _first[0]:
                _first[0] = False
                pid, b = pairs[0]
                recs = b.to_records()
                recs[0].type = R.CL_MKDIR if recs[0].type != R.CL_MKDIR \
                    else R.CL_CREATE
                pairs[0] = (pid, R.RecordBatch.from_records(recs))
            return pairs

        if fault not in ("drop_half", "alter"):
            raise ValueError(f"unknown fault {fault!r}")
        m.fetch = broken


class Fabric:
    """The system under test and the load, for one run."""

    def __init__(self, run, rate: Optional[float] = None):
        cfg, mix = run.config, run.mix
        self._env = {k: os.environ.get(k) for k in cfg.get("env", {})}
        os.environ.update(cfg.get("env", {}))
        from repro.core import cluster as CL
        from repro.core.llog import Llog
        from repro.core.session import Subscription, connect
        from repro.kernels import stream_ops

        self.run = run
        self.cfg, self.mix = cfg, mix
        self.rate = rate if rate is not None else mix["rate"]
        self.n_mdt = cfg["n_mdt"]
        self.warm_s = mix["warmup_s"]
        self.backlog_s = mix["backlog_s"]
        self.sch = mdtest.schedule(cfg, mix["phase"], self.rate,
                                   -self.backlog_s,
                                   self.warm_s + run.seconds, run.seed)
        self.rows = self.sch.per_mdt_rows(self.n_mdt)
        self.pids = [f"mdt{m}" for m in range(self.n_mdt)]
        self._stream_ops, self._CL = stream_ops, CL
        self._kernel = stream_ops.fid_slots
        warm_routing(self._kernel, cfg["batch_size"], cfg["n_slots"])
        self.slots = SlotRecorder(self._kernel)
        stream_ops.fid_slots = self.slots
        CL._reset_jax_probe()
        self.logs = {pid: Llog(pid) for pid in self.pids}
        self.cluster = CL.LcapCluster(self.logs, n_shards=cfg["n_shards"],
                                      n_slots=cfg["n_slots"],
                                      batch_size=cfg["batch_size"])
        self.session = connect(self.cluster)
        self.members = [self.session.subscribe(Subscription(
            group=cfg["group"], auto_commit=False,
            max_records=cfg["fetch_records"]))
            for _ in range(cfg["group_members"])]
        self.next_row = 0
        self.appended_at: List[Tuple[int, int, float]] = []  # (lo, hi, t)
        self.deliveries: List[Tuple[str, object, int]] = []
        self.fetched = 0
        self.origin_ns = 0
        self.origin = 0.0
        if run.fault:
            plant(self, run.fault)

    def close(self) -> None:
        for m in self.members:
            m.close()
        self._stream_ops.fid_slots = self._kernel
        self._CL._reset_jax_probe()
        for k, v in self._env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    # ------------------------------------------------------------- rounds
    def start_traffic(self) -> None:
        self.origin_ns = time.time_ns()
        self.origin = time.perf_counter()

    def append_due(self, until: float) -> int:
        """Append every scheduled record due before traffic time
        ``until`` (seconds)."""
        sch, spans = self.sch, self.run.spans
        hi = int(np.searchsorted(sch.due, until, side="right"))
        lo = self.next_row
        if hi <= lo:
            return 0
        rows = np.arange(lo, hi)
        by_mdt = np.argsort(sch.mdt[lo:hi], kind="stable")
        cuts = np.searchsorted(sch.mdt[lo:hi][by_mdt],
                               np.arange(self.n_mdt + 1))
        for m in range(self.n_mdt):
            mine = rows[by_mdt[cuts[m]:cuts[m + 1]]]
            if not len(mine):
                continue
            with spans.span("gen"):
                recs = mdtest.records(sch, mine, self.origin_ns)
            with spans.span("append"):
                self.logs[self.pids[m]].log_batch(recs)
        self.appended_at.append((lo, hi, time.perf_counter() - self.origin))
        self.next_row = hi
        return hi - lo

    def round(self, generate: bool = True) -> int:
        """One round; returns the records fetched."""
        if generate:
            self.append_due(time.perf_counter() - self.origin)
        spans = self.run.spans
        with spans.span("round"):
            self.cluster.pump()
        got = 0
        for m in self.members:
            with spans.span("deliver"):
                pairs = m.fetch()
                t = time.time_ns()
                m.commit()
            for pid, batch in pairs:
                self.deliveries.append((pid, batch, t))
                got += len(batch)
        self.fetched += got
        return got

    def backlog(self) -> int:
        """Records appended and not yet fetched by the group."""
        return self.next_row - self.fetched

    def dispatched(self) -> int:
        return sum(s.proxy.stats["dispatched"] for s in self.cluster.shards)

    def trimmed(self) -> int:
        return sum(log.first_index == log.last_index + 1
                   for log in self.logs.values())

    def drain(self, timeout: float) -> bool:
        self.append_due(np.inf)
        t_end = time.perf_counter() + timeout
        while time.perf_counter() < t_end:
            self.round(generate=False)
            if self.trimmed() == self.n_mdt and \
                    self.backlog() <= 0:
                return True
        return False

    # --------------------------------------------------------------- check
    def check(self) -> Dict[str, float]:
        """Counts of each kind of wrong answer (all compared against 0)."""
        sch = self.sch
        counts = [np.zeros(len(r) + 1, np.int64) for r in self.rows]
        first_ns = [np.full(len(r) + 1, -1, np.int64) for r in self.rows]
        header_bad = 0
        jobids = np.zeros((self.cfg["ranks"], 32), np.uint8)
        for r in range(self.cfg["ranks"]):
            jb = b"mdtest.%d" % r
            jobids[r, :len(jb)] = np.frombuffer(jb, np.uint8)
        for pid, batch, t in self.deliveries:
            m = self.pids.index(pid)
            idx = batch.indices_np().astype(np.int64)
            inside = (idx >= 1) & (idx <= len(self.rows[m]))
            header_bad += int(np.count_nonzero(~inside))
            idx = idx[inside]
            np.add.at(counts[m], idx, 1)
            fresh = first_ns[m][idx] < 0
            first_ns[m][idx[fresh]] = t
            g = self.rows[m][idx - 1]
            tseq, toid, tver = batch.tfid_cols()
            pseq, poid, _ = batch.pfid_cols()
            want_t = mdtest.due_ns(sch, g, self.origin_ns)
            ok = ((batch.types_np()[inside] == sch.rtype[g])
                  & (tseq[inside] == mdtest.MDT_SEQ0 + m)
                  & (toid[inside] == sch.oid[g]) & (tver[inside] == 0)
                  & (pseq[inside] == sch.pseq[g])
                  & (poid[inside] == sch.poid[g])
                  & (batch.times_np()[inside].astype(np.int64) == want_t)
                  & (batch.jobid_col()[inside] == jobids[sch.rank[g]]
                     ).all(axis=1))
            header_bad += int(np.count_nonzero(~ok))
        appended = [self.logs[p].last_index for p in self.pids]
        lost = sum(int(np.count_nonzero(c[1:a + 1] == 0))
                   for c, a in zip(counts, appended))
        lost += sum(len(r) - a for r, a in zip(self.rows, appended))
        dup = sum(int(np.count_nonzero(c > 1)) for c in counts)
        slot_bad, slot_total = self.slots.mismatches()
        routed = self.cluster.stats["routed"]
        self._first_ns = first_ns
        return {
            "lost": float(lost), "duplicated": float(dup),
            "header_mismatch": float(header_bad),
            "slot_mismatch": float(slot_bad + abs(slot_total - routed)),
            "untrimmed_journals": float(self.n_mdt - self.trimmed()),
            "failed_shards": float(self.cluster.stats["shards_failed"]),
        }

    def latencies(self, lo: float, hi: float) -> Tuple[np.ndarray, int]:
        """Delivery latency (s) of every record due in traffic time
        ``[lo, hi)``; one not fetched by ``hi`` counts at its age then.
        Returns (latencies, how many were not fetched by ``hi``)."""
        sch = self.sch
        lat, late = [], 0
        hi_ns = self.origin_ns + hi * 1e9
        for m, rows in enumerate(self.rows):
            due = sch.due[rows]
            sel = (due >= lo) & (due < hi)
            t = self._first_ns[m][1:][sel].astype(np.float64)
            due_ns = self.origin_ns + due[sel] * 1e9
            miss = (t < 0) | (t > hi_ns)
            late += int(np.count_nonzero(miss))
            t = np.where(miss, hi_ns, t)
            lat.append((t - due_ns) * 1e-9)
        return np.concatenate(lat), late

    def generator_lag(self, lo: float, hi: float) -> np.ndarray:
        """How late (s) each append ran against its records' due times,
        over the records due in ``[lo, hi)``."""
        out = []
        for a, b, t in self.appended_at:
            due = self.sch.due[a:b]
            sel = (due >= lo) & (due < hi)
            out.append(t - due[sel])
        return np.concatenate(out) if out else np.zeros(0)


def run(run, rate: Optional[float] = None, drain: bool = True) -> Dict:
    """One run of the cell (``rate`` and ``drain=False`` serve the knee
    sweep: another offered rate, and no drain after the window)."""
    cfg, mix = run.config, run.mix
    spans = run.spans
    fab = Fabric(run, rate)
    try:
        spans.wrap(fab.cluster, "_route", "route")
        for shard in fab.cluster.shards:
            spans.wrap(shard, "pump", "shard_pump")
        run.settle()
        fab.start_traffic()
        fab.append_due(0.0)                      # the backlog, if any
        while time.perf_counter() - fab.origin < fab.warm_s:
            fab.round()
        compiles0 = run.clock.count
        routed0 = fab.cluster.stats["routed"]
        dispatched0 = fab.dispatched()
        backlog_start = fab.backlog()
        run.start_window()
        w0 = time.perf_counter() - fab.origin
        fetched = 0
        ticks = [run.window[0]]
        # where the records not yet fetched wait, after each round:
        # unrouted in the journals, routed and not dispatched, dispatched
        # and not fetched
        waiting = []
        try:
            while ticks[-1] - run.window[0] < run.seconds:
                fetched += fab.round()
                ticks.append(time.perf_counter())
                routed_now = fab.cluster.stats["routed"]
                dispatched_now = fab.dispatched()
                waiting.append((fab.next_row - routed_now,
                                routed_now - dispatched_now,
                                dispatched_now - fab.fetched))
        finally:
            run.end_window()
        w1 = w0 + run.window_s
        window_compiles = run.clock.count - compiles0
        routed = fab.cluster.stats["routed"] - routed0
        dispatched = fab.dispatched() - dispatched0
        appended = sum(b - a for a, b, t in fab.appended_at if w0 <= t < w1)
        backlog_end = fab.backlog()
        spans.restore()
        memory_peak = run.memory_peak_bytes()
        t_check = time.perf_counter()
        drained = drain and fab.drain(mix["drain_timeout_s"])
        values = fab.check()
        values["undrained"] = 0.0 if drained else 1.0
        lat, late = fab.latencies(w0, w1)
        lag = fab.generator_lag(w0, w1)
    finally:
        fab.close()
    limits = {k: 0.0 for k in values}
    correct = all(values[k] <= limits[k] for k in limits)
    return {
        "correct": correct, "attempted": int(len(lat)), "failed": late,
        "end_to_end": {
            "setup_s": run.setup_s,
            "records_per_s": fetched / run.window_s,
            "delivery_p50_ms": float(np.percentile(lat, 50)) * 1e3,
            "delivery_p95_ms": float(np.percentile(lat, 95)) * 1e3,
        },
        "memory_peak_bytes": memory_peak,
        "check_s": time.perf_counter() - t_check,
        "readings": {
            "rounds": len(ticks) - 1,
            "longest_round_s": float(np.max(np.diff(ticks))),
            "round_ms": {f"p{q}": float(np.percentile(np.diff(ticks), q))
                         * 1e3 for q in (10, 50, 90)},
            "mean_waiting": dict(zip(("unrouted", "undispatched", "unfetched"),
                                     np.mean(waiting, axis=0).tolist())),
            "delivery_ms": {f"p{q}": float(np.percentile(lat, q)) * 1e3
                            for q in (50, 90, 95, 99)},
            "backlog_start": backlog_start, "backlog_end": backlog_end},
        "compared": {k: {"value": values[k], "limit": limits[k]}
                     for k in limits},
        "spans": spans, "records_fetched": fetched, "records_routed": routed,
        "records_dispatched": dispatched, "records_appended": appended,
        "window_compiles": window_compiles,
        "generator_lag_s": lag, "backlog_start": backlog_start,
        "backlog_end": backlog_end,
    }
