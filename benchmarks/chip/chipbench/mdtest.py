"""The metadata changelog stream of one IO500 ``mdtest`` phase, as a pure
function of the seed.

IO500 runs its mdtest phases one after another, each on its own:
mdtest-easy-write, later mdtest-hard-write, and the two delete phases
at the end.  A mix names one phase, and every record it logs belongs to
that phase:

- ``easy_write``: each rank creates empty files under a directory of
  its own (mdtest ``-u``), which lives on one MDT; one ``CREAT`` a file;
- ``hard_write``: every rank creates files in one shared directory,
  striped over all MDTs, so a file lands on the MDT its name hashes to;
  each gets a 3,901-byte write, which logs one write-side record
  (``CLOSE``) after its ``CREAT``;
- ``easy_delete``, ``hard_delete``: one ``UNLNK`` a file, the files laid
  out as the matching write phase left them.

File operations arrive as a Poisson process at the mix's rate, spread
over the ranks; a hard file's ``CLOSE`` follows its ``CREAT`` by a fixed
delay, so the whole stream stays Poisson at the rate.

``schedule`` draws every column with numpy; nothing is built per record
until ``records`` turns a due slice into ``ChangelogRecord`` objects.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List

import numpy as np

CL_CREATE, CL_UNLINK, CL_CLOSE = 1, 6, 11
PHASES = ("easy_write", "hard_write", "easy_delete", "hard_delete")
MDT_SEQ0 = 0x200000400          # FID sequence of MDT 0; MDT m uses +m
SHARED_DIR_OID = 1 << 24        # the mdtest-hard shared directory
RANK_DIR_OID0 = 1 << 25         # mdtest-easy: one directory per rank


@dataclass
class Schedule:
    """Columns of every record, sorted by due time (seconds from the
    start of the traffic; negative for a backlog logged before it)."""
    due: np.ndarray        # float64
    mdt: np.ndarray        # int64
    rtype: np.ndarray      # int64
    oid: np.ndarray        # int64, target FID object id (unique per MDT)
    poid: np.ndarray       # int64, parent FID object id
    pseq: np.ndarray       # int64, parent FID sequence
    rank: np.ndarray       # int64
    fileno: np.ndarray     # int64, the rank's file number

    def __len__(self) -> int:
        return len(self.due)

    def per_mdt_rows(self, n_mdt: int) -> List[np.ndarray]:
        """For each MDT, its rows in due order: the k-th row of MDT m is
        logged at journal index k + 1."""
        return [np.flatnonzero(self.mdt == m) for m in range(n_mdt)]


def schedule(config: Dict, phase: str, rate: float, start: float,
             end: float, seed: int) -> Schedule:
    """Every record of ``phase`` due in ``[start, end)`` at ``rate``
    records/s."""
    if phase not in PHASES:
        raise ValueError(f"unknown mdtest phase {phase!r}; known: {PHASES}")
    n_mdt, ranks = config["n_mdt"], config["ranks"]
    hard = phase.startswith("hard")
    write = phase.endswith("write")
    close_after = config["write_after_s"] if hard and write else 0.0
    file_rate = rate / (2.0 if hard and write else 1.0)
    rng = np.random.default_rng([seed, 0x6D64, PHASES.index(phase)])
    t0 = start - close_after
    n_files = int((end - t0) * file_rate * 1.1) + 64
    at = t0 + np.cumsum(rng.exponential(1.0 / file_rate, n_files))
    at = at[at < end]
    n = len(at)
    rank = rng.integers(0, ranks, n)
    # the rank's file number, in order per rank
    fileno = np.zeros(n, np.int64)
    order = np.argsort(rank, kind="stable")
    counts = np.bincount(rank, minlength=ranks)
    fileno[order] = np.arange(n) - np.repeat(np.cumsum(counts) - counts,
                                             counts)
    if hard:
        name_hash = (rank * 0x9E3779B1 + fileno * 0x85EBCA77) % (1 << 31)
        mdt = name_hash % n_mdt
        poid = np.full(n, SHARED_DIR_OID, np.int64)
        pseq = np.full(n, MDT_SEQ0, np.int64)
    else:
        mdt = rank % n_mdt
        poid = RANK_DIR_OID0 + rank
        pseq = MDT_SEQ0 + mdt
    oid = np.zeros(n, np.int64)
    for m in range(n_mdt):
        rows = np.flatnonzero(mdt == m)
        oid[rows] = 1 + np.arange(len(rows))

    src = np.arange(n)
    due = at
    rtype = np.full(n, CL_CREATE if write else CL_UNLINK, np.int64)
    if close_after:
        due = np.concatenate([at, at + close_after])
        rtype = np.concatenate([rtype, np.full(n, CL_CLOSE, np.int64)])
        src = np.concatenate([src, src])
    keep = (due >= start) & (due < end)
    due, rtype, src = due[keep], rtype[keep], src[keep]
    order = np.argsort(due, kind="stable")
    due, rtype, src = due[order], rtype[order], src[order]
    return Schedule(due=due, mdt=mdt[src], rtype=rtype, oid=oid[src],
                    poid=poid[src], pseq=pseq[src], rank=rank[src],
                    fileno=fileno[src])


def due_ns(sch: Schedule, rows: np.ndarray, origin_ns: int) -> np.ndarray:
    """Wall-clock due times (ns) of ``rows``, traffic starting at
    ``origin_ns``: the records' ``cr_time``."""
    return np.round(sch.due[rows] * 1e9).astype(np.int64) + np.int64(
        origin_ns)


def records(sch: Schedule, rows: np.ndarray, origin_ns: int):
    """``ChangelogRecord`` objects of ``rows``, each stamped with its
    due time as ``cr_time``."""
    from repro.core import records as R

    rec, fid = R.ChangelogRecord, R.Fid
    times = due_ns(sch, rows, origin_ns)
    cols = zip(times.tolist(), sch.rtype[rows].tolist(),
               sch.mdt[rows].tolist(), sch.oid[rows].tolist(),
               sch.pseq[rows].tolist(), sch.poid[rows].tolist(),
               sch.rank[rows].tolist(), sch.fileno[rows].tolist())
    return [rec(type=ty, time=t, tfid=fid(MDT_SEQ0 + m, oid, 0),
                pfid=fid(ps, po, 0), name=b"file.mdtest.%d.%d" % (rk, fn),
                jobid=b"mdtest.%d" % rk)
            for t, ty, m, oid, ps, po, rk, fn in cols]
