"""The mdtest changelog generator is a pure function of the seed, and its
due times follow the schedule the mix states."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import mdtest  # noqa: E402

CFG = {"n_mdt": 16, "ranks": 160, "write_after_s": 0.001}
SEED = 2 ** 33 + 12345          # larger than 32 signed bits hold


def _cols(s):
    return [s.due, s.mdt, s.rtype, s.oid, s.poid, s.pseq, s.rank, s.fileno]


def test_same_seed_same_columns_other_seed_other_columns():
    a = mdtest.schedule(CFG, "hard_write", 5000, -1.0, 4.0, SEED)
    b = mdtest.schedule(CFG, "hard_write", 5000, -1.0, 4.0, SEED)
    c = mdtest.schedule(CFG, "hard_write", 5000, -1.0, 4.0, SEED + 1)
    for x, y in zip(_cols(a), _cols(b)):
        np.testing.assert_array_equal(x, y)
    assert len(a) != len(c) or not np.array_equal(a.due, c.due)


@pytest.mark.parametrize("rate", [2000.0, 20000.0])
def test_due_times_follow_the_schedule(rate):
    start, end = -1.0, 5.0
    s = mdtest.schedule(CFG, "hard_write", rate, start, end, SEED)
    assert np.all(np.diff(s.due) >= 0)
    assert s.due[0] >= start and s.due[-1] < end
    # Poisson at ``rate``: the count within five standard deviations
    want = rate * (end - start)
    assert abs(len(s) - want) < 5 * np.sqrt(want)
    # the MDTs share the load evenly
    per = np.bincount(s.mdt, minlength=CFG["n_mdt"])
    assert per.min() > 0.8 * per.mean() and per.max() < 1.2 * per.mean()


@pytest.mark.parametrize("phase", mdtest.PHASES)
def test_each_phase_logs_only_its_own_records(phase):
    """A write phase creates (and, if hard, writes) each file; a delete
    phase unlinks; no phase logs another's records."""
    s = mdtest.schedule(CFG, phase, 4000, 0.0, 6.0, SEED)
    hard = phase.startswith("hard")
    want = ({mdtest.CL_CREATE, mdtest.CL_CLOSE} if phase == "hard_write"
            else {mdtest.CL_CREATE} if phase == "easy_write"
            else {mdtest.CL_UNLINK})
    assert set(np.unique(s.rtype).tolist()) == want
    if phase == "hard_write":
        key = s.rank * (1 << 32) + s.fileno
        t_create = {k: t for k, t, ty in zip(key, s.due, s.rtype)
                    if ty == mdtest.CL_CREATE}
        closes = [(k, t) for k, t, ty in zip(key, s.due, s.rtype)
                  if ty == mdtest.CL_CLOSE and k in t_create]
        assert len(closes) > 1000
        for k, t in closes:
            assert t - t_create[k] == pytest.approx(CFG["write_after_s"])
    # hard files share one parent; easy ranks keep theirs under their
    # own directory and MDT
    if hard:
        assert np.all(s.poid == mdtest.SHARED_DIR_OID)
    else:
        assert np.all(s.mdt == s.rank % CFG["n_mdt"])
        assert np.all(s.poid == mdtest.RANK_DIR_OID0 + s.rank)


def test_unknown_phase_is_refused():
    with pytest.raises(ValueError):
        mdtest.schedule(CFG, "mixed", 4000, 0.0, 1.0, SEED)


def test_a_file_keeps_one_fid_and_fids_are_unique_per_mdt():
    s = mdtest.schedule(CFG, "hard_write", 4000, 0.0, 6.0, SEED)
    key = s.rank * (1 << 32) + s.fileno
    fid = s.mdt * (1 << 40) + s.oid
    pairs = set(zip(key.tolist(), fid.tolist()))
    assert len(pairs) == len(set(key.tolist())) == len(set(fid.tolist()))
