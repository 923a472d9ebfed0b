"""Engine behaviour vs the dict oracle (paper semantics: newest-wins,
tombstones, range, cascaded merges). The hypothesis interleaving
property lives in test_slsm_props.py; deterministic randomized-schedule
equivalents live in test_engine.py."""
import numpy as np
import pytest

from repro.core import SLSM, SLSMParams
from repro.core.oracle import DictOracle

TINY = SLSMParams(R=3, Rn=8, eps=0.02, D=2, m=0.5, mu=4, max_levels=3,
                  max_range=512)


def _check_lookups(t, o, qs):
    v1, f1 = t.lookup(qs)
    v2, f2 = o.lookup(qs)
    np.testing.assert_array_equal(f1, f2)
    np.testing.assert_array_equal(v1[f1], v2[f2])
    v1s, f1s = t.lookup(qs, sparse=True)
    np.testing.assert_array_equal(f1s, f2)
    np.testing.assert_array_equal(v1s[f1s], v2[f2])


def test_newest_wins_update_in_place():
    """Paper 3.9.1: duplicate keys update in place in the active run."""
    t = SLSM(TINY)
    keys = np.zeros(64, np.int32) + 7
    vals = np.arange(64, dtype=np.int32)
    t.insert(keys, vals)
    v, f = t.lookup(np.asarray([7], np.int32))
    assert f[0] and v[0] == 63
    # dup-heavy stream must not have spilled: one distinct key
    assert t.n_levels == 0


def test_cascade_merge_and_depth():
    p = SLSMParams(R=2, Rn=8, eps=0.05, D=2, m=1.0, mu=4, max_levels=3,
                   max_range=4096)
    t, o = SLSM(p), DictOracle()
    rng = np.random.default_rng(3)
    for _ in range(40):
        ks = rng.integers(0, 120, 16).astype(np.int32)
        vs = rng.integers(0, 9, 16).astype(np.int32)
        t.insert(ks, vs)
        o.insert(ks, vs)
    assert t.n_levels >= 2  # cascade actually happened
    _check_lookups(t, o, np.arange(-2, 125, dtype=np.int32))


def test_tombstones_commit_at_deepest():
    p = SLSMParams(R=2, Rn=4, eps=0.05, D=2, m=1.0, mu=4, max_levels=3,
                   max_range=512)
    t = SLSM(p)
    ks = np.arange(16, dtype=np.int32)
    t.insert(ks, ks)
    t.delete(ks[:8])
    # force enough churn to push tombstones to the deepest level
    t.insert(ks + 100, ks)
    t.insert(ks + 200, ks)
    v, f = t.lookup(ks[:8])
    assert not f.any()
    v, f = t.lookup(ks[8:])
    assert f.all()


def test_range_truncation_bound():
    p = SLSMParams(R=4, Rn=64, eps=0.02, D=4, m=1.0, mu=32, max_levels=3,
                   max_range=512)
    t = SLSM(p)
    ks = np.arange(2000, dtype=np.int32)
    t.insert(ks, ks)
    k, v = t.range(0, 2000)
    assert len(k) == p.max_range  # static bound respected


def test_overflow_raises():
    p = SLSMParams(R=2, Rn=8, eps=0.05, D=2, m=1.0, mu=4, max_levels=2,
                   max_range=64)
    t = SLSM(p)
    with pytest.raises(RuntimeError, match="max_levels"):
        t.insert(np.arange(4000, dtype=np.int32),
                 np.arange(4000, dtype=np.int32))
    # the overflowing compaction consumed the (donated) state: the engine
    # stays unusable, with the same declared error, until restored
    with pytest.raises(RuntimeError, match="restore"):
        t.lookup(np.arange(4, dtype=np.int32))


def test_r_tradeoff_more_runs_fewer_merges():
    """Paper 3.1: higher R defers merges (fewer disk levels touched)."""
    rng = np.random.default_rng(0)
    ks = rng.integers(0, 2**20, 2000).astype(np.int32)
    vs = ks.copy()
    small = SLSM(SLSMParams(R=2, Rn=64, eps=0.01, D=4, m=1.0, mu=32,
                            max_levels=3, max_range=64))
    large = SLSM(SLSMParams(R=16, Rn=64, eps=0.01, D=4, m=1.0, mu=32,
                            max_levels=3, max_range=64))
    small.insert(ks, vs)
    large.insert(ks, vs)
    n_small = sum(int(lv.counts.sum()) for lv in small.state.levels)
    n_large = sum(int(lv.counts.sum()) for lv in large.state.levels)
    assert n_large < n_small  # more stays in memory with higher R
