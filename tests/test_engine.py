"""Engine-layer tests: backend dispatch, compaction policies, sharding,
and sparse-vs-dense read-path equivalence.

These use deterministic randomized schedules (seeded numpy) rather than
hypothesis, so they run everywhere — including environments where the
optional test deps are absent. The hypothesis interleaving property for
the single tree lives in test_slsm_props.py.
"""
import numpy as np
import pytest

from repro.core import SLSM, SLSMParams
from repro.core.oracle import DictOracle
from repro.engine import (LevelingPolicy, ShardedSLSM, TieringPolicy,
                          get_backend, shard_ids)

SMALL = SLSMParams(R=2, Rn=8, eps=0.02, D=2, m=1.0, mu=4, max_levels=3,
                   max_range=512, cand_factor=16)
KEY_SPACE = 200


def _random_schedule(t, o, seed, rounds=8, key_space=KEY_SPACE):
    """Randomized insert/delete stream driving seals, flushes, and
    cascaded merges on the tiny geometry (and the same ops on the
    oracle)."""
    rng = np.random.default_rng(seed)
    for _ in range(rounds):
        if rng.random() < 0.75:
            n = int(rng.integers(1, 40))
            ks = rng.integers(0, key_space, n).astype(np.int32)
            vs = rng.integers(-50, 50, n).astype(np.int32)
            t.insert(ks, vs)
            o.insert(ks, vs)
        else:
            n = int(rng.integers(1, 12))
            ks = rng.integers(0, key_space, n).astype(np.int32)
            t.delete(ks)
            o.delete(ks)
    return np.arange(-4, key_space + 4, dtype=np.int32)


# -- sparse vs dense read-path equivalence ----------------------------------

@pytest.mark.parametrize("seed", range(5))
def test_sparse_matches_dense_and_oracle(seed):
    """With sufficient cand_factor headroom the Bloom-compacted (sparse)
    disk search must agree with the dense path and the dict oracle across
    randomized insert/delete/merge schedules (total resident runs here is
    <= D * max_levels = 6 < cand_factor = 16, so the gate never
    overflows)."""
    t, o = SLSM(SMALL), DictOracle()
    qs = _random_schedule(t, o, seed)
    assert t.n_levels >= 1  # merges actually happened
    vd, fd = t.lookup(qs, sparse=False)
    vs_, fs = t.lookup(qs, sparse=True)
    vo, fo = o.lookup(qs)
    np.testing.assert_array_equal(fd, fo)
    np.testing.assert_array_equal(vd[fd], vo[fo])
    np.testing.assert_array_equal(fs, fo)
    np.testing.assert_array_equal(vs_[fs], vo[fo])


# -- backend dispatch --------------------------------------------------------

def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="backend"):
        SLSMParams(backend="cuda")
    with pytest.raises(ValueError, match="backend"):
        get_backend("cuda")


@pytest.mark.parametrize("seed", range(2))
def test_pallas_backend_matches_jnp(seed):
    """backend="pallas" routes Bloom probes, fence lookups, and merges
    through the kernels (interpret mode off-TPU) and must be observationally
    identical to the jnp reference."""
    pj = SMALL
    pp = SLSMParams(**{**pj.__dict__, "backend": "pallas"})
    tj, tp, o = SLSM(pj), SLSM(pp), DictOracle()
    rng = np.random.default_rng(seed)
    for _ in range(5):
        n = int(rng.integers(1, 32))
        ks = rng.integers(0, KEY_SPACE, n).astype(np.int32)
        vs = rng.integers(-50, 50, n).astype(np.int32)
        tj.insert(ks, vs)
        tp.insert(ks, vs)
        o.insert(ks, vs)
    dels = rng.integers(0, KEY_SPACE, 8).astype(np.int32)
    tj.delete(dels), tp.delete(dels), o.delete(dels)
    assert tp.n_levels >= 1  # kernel merge path exercised

    qs = np.arange(-4, KEY_SPACE + 4, dtype=np.int32)
    vj, fj = tj.lookup(qs)
    vp, fp = tp.lookup(qs)
    vo, fo = o.lookup(qs)
    np.testing.assert_array_equal(fj, fo)
    np.testing.assert_array_equal(fp, fo)
    np.testing.assert_array_equal(vj[fj], vo[fo])
    np.testing.assert_array_equal(vp[fp], vo[fo])

    kj, wj = tj.range(5, 150)
    kp, wp = tp.range(5, 150)
    np.testing.assert_array_equal(kj, kp)
    np.testing.assert_array_equal(wj, wp)


# -- compaction policies -----------------------------------------------------

def test_leveling_policy_matches_oracle_and_bounds_runs():
    p = SLSMParams(R=2, Rn=8, eps=0.05, D=2, m=1.0, mu=4, max_levels=4,
                   max_range=512)
    t, o = SLSM(p, policy=LevelingPolicy()), DictOracle()
    qs = _random_schedule(t, o, seed=3, rounds=10)
    v1, f1 = t.lookup(qs)
    v2, f2 = o.lookup(qs)
    np.testing.assert_array_equal(f1, f2)
    np.testing.assert_array_equal(v1[f1], v2[f2])
    k1, w1 = t.range(10, 180)
    k2, w2 = o.range(10, 180)
    np.testing.assert_array_equal(k1, k2)
    np.testing.assert_array_equal(w1, w2)
    # the policy's read-amplification promise: <= max_resident runs/level
    for lv in t.state.levels:
        assert int(lv.n_runs) <= 2


@pytest.mark.parametrize("geo,key_space", [
    (dict(D=3, m=0.5, max_levels=2), 80),    # ceil(m*D) < D
    (dict(D=3, m=1.0, max_levels=1), 50),    # deepest level fed by flushes
    (dict(D=3, m=1.0, max_levels=2), 120),
])
@pytest.mark.parametrize("policy", [TieringPolicy, LevelingPolicy])
def test_deepest_compaction_reads_only_occupiable_lanes(geo, key_space,
                                                         policy):
    """The deepest compaction merges slot 0 plus only the spill- (or
    flush-) sized head of every other slot (compaction.compaction_rows);
    repeated compactions under either policy stay oracle-exact."""
    p = SLSMParams(R=4 if geo["m"] < 1 else 3, Rn=8, eps=0.05, mu=4,
                   max_range=512, **geo)
    t, o = SLSM(p, policy=policy()), DictOracle()
    qs = _random_schedule(t, o, seed=5, rounds=40, key_space=key_space)
    assert t.stats["compactions"] >= 2
    v1, f1 = t.lookup(qs)
    v2, f2 = o.lookup(qs)
    np.testing.assert_array_equal(f1, f2)
    np.testing.assert_array_equal(v1[f1], v2[f2])
    k1, w1 = t.range(-5, key_space + 5)
    k2, w2 = o.range(-5, key_space + 5)
    np.testing.assert_array_equal(k1, k2)
    np.testing.assert_array_equal(w1, w2)


def test_leveling_policy_rejects_unsupported_geometry():
    # ceil(m*D) = 1 < max_resident: a spill could not fit the next level
    with pytest.raises(ValueError, match="LevelingPolicy"):
        SLSM(SLSMParams(R=3, Rn=8, D=2, m=0.5, mu=4), policy=LevelingPolicy())


def test_tiering_policy_is_default_paper_behaviour():
    t = SLSM(SMALL)
    assert isinstance(t.policy, TieringPolicy)
    assert t.policy.runs_to_spill(SMALL, SMALL.D) == SMALL.disk_runs_merged


# -- sharded engine ----------------------------------------------------------

def test_shard_routing_is_deterministic_and_covers_shards():
    keys = np.arange(4096, dtype=np.int32)
    sid = shard_ids(keys, 4)
    np.testing.assert_array_equal(sid, shard_ids(keys, 4))
    assert set(np.unique(sid)) == {0, 1, 2, 3}
    # hash routing should be roughly balanced on sequential keys
    counts = np.bincount(sid, minlength=4)
    assert counts.min() > len(keys) // 8


@pytest.mark.parametrize("seed", range(3))
def test_sharded_matches_oracle(seed):
    t, o = ShardedSLSM(SMALL, n_shards=4), DictOracle()
    rng = np.random.default_rng(seed)
    for _ in range(6):
        n = int(rng.integers(1, 120))
        ks = rng.integers(0, 500, n).astype(np.int32)
        vs = rng.integers(-50, 50, n).astype(np.int32)
        t.insert(ks, vs)
        o.insert(ks, vs)
        dels = rng.integers(0, 500, int(rng.integers(1, 16))).astype(np.int32)
        t.delete(dels)
        o.delete(dels)
    qs = np.arange(-4, 504, dtype=np.int32)
    v1, f1 = t.lookup(qs)
    v2, f2 = o.lookup(qs)
    np.testing.assert_array_equal(f1, f2)
    np.testing.assert_array_equal(v1[f1], v2[f2])
    k1, w1 = t.range(20, 480)
    k2, w2 = o.range(20, 480)
    np.testing.assert_array_equal(k1, k2)
    np.testing.assert_array_equal(w1, w2)


def test_sharded_cascade_reaches_disk_levels():
    """Enough volume to force every shard through flushes and level spills."""
    t, o = ShardedSLSM(SMALL, n_shards=4), DictOracle()
    rng = np.random.default_rng(7)
    # 600 keys over a 800-key space: every shard (~150 keys) overflows its
    # memory buffer (R*Rn = 16) several times over, without exceeding the
    # tiny geometry's declared total capacity
    ks = rng.integers(0, 800, 600).astype(np.int32)
    vs = rng.integers(0, 100, 600).astype(np.int32)
    t.insert(ks, vs)
    o.insert(ks, vs)
    occ = t.shard_occupancy()
    assert (occ > 0).all()
    disk = sum(int(lv.counts.sum()) for lv in t.state.levels)
    assert disk > 0  # flush/cascade actually ran
    qs = rng.integers(-10, 810, 512).astype(np.int32)
    v1, f1 = t.lookup(qs)
    v2, f2 = o.lookup(qs)
    np.testing.assert_array_equal(f1, f2)
    np.testing.assert_array_equal(v1[f1], v2[f2])


# -- range-query correctness under updates/deletes ---------------------------

def test_range_survives_overwrites_and_deletes():
    """Regression (ISSUE 3): per-structure range windows used to be cut to
    max_range BEFORE newest-wins dedup, so stale versions and tombstones
    occupying window slots silently evicted live keys even when the final
    count was far below max_range. Overwrite/delete a key range, then
    scan it: the survivors must all be visible."""
    p = SLSMParams(R=2, Rn=8, eps=0.02, D=2, m=1.0, mu=4, max_levels=3,
                   max_range=16)
    t, o = SLSM(p), DictOracle()
    keys = np.arange(0, 40, dtype=np.int32)
    t.insert(keys, keys)
    o.insert(keys, keys)
    # push the originals toward disk, then tombstone most of the range:
    # the deep run's first max_range slots are now all-stale
    t.delete(keys[:32])
    o.delete(keys[:32])
    k1, v1 = t.range(0, 80)
    k2, v2 = o.range(0, 80)
    assert len(k2) == 8 < p.max_range   # survivors fit well under the cap
    np.testing.assert_array_equal(k1, k2)
    np.testing.assert_array_equal(v1, v2)
    # same data, new values: overwrites must win without evicting anyone
    t.insert(keys[32:], keys[32:] * 10)
    o.insert(keys[32:], keys[32:] * 10)
    k1, v1, trunc = t.range(0, 80, return_truncated=True)
    k2, v2 = o.range(0, 80)
    assert not trunc
    np.testing.assert_array_equal(k1, k2)
    np.testing.assert_array_equal(v1, v2)


def test_range_truncation_flag_single_tree():
    p = SLSMParams(R=2, Rn=8, eps=0.02, D=2, m=1.0, mu=4, max_levels=3,
                   max_range=16)
    t = SLSM(p)
    keys = np.arange(0, 64, dtype=np.int32)
    t.insert(keys, keys)
    k, v, trunc = t.range(0, 64, return_truncated=True)
    assert trunc and len(k) == p.max_range
    np.testing.assert_array_equal(k, keys[:p.max_range])
    k, v, trunc = t.range(0, 10, return_truncated=True)
    assert not trunc and len(k) == 10


def test_sharded_range_parity_and_truncated_flags():
    """ShardedSLSM.range vs the single tree over hash-skewed keys: exact
    (and flag-free) while no shard truncates; per-shard flags light up
    exactly for the shards that hold more than max_range live keys."""
    p = SLSMParams(R=2, Rn=8, eps=0.02, D=2, m=1.0, mu=4, max_levels=3,
                   max_range=64)
    n_shards = 4
    # hash-skew: only keys routed to shards 0 and 1 (40 each, under the
    # per-shard max_range), so the other shards stay empty — the
    # imbalance the parity claim must survive without truncating
    pool = np.arange(0, 4000, dtype=np.int32)
    sid = shard_ids(pool, n_shards)
    skewed = np.concatenate([pool[sid == 0][:40], pool[sid == 1][:40]])
    s = ShardedSLSM(p, n_shards=n_shards)
    t = SLSM(SLSMParams(R=2, Rn=8, eps=0.02, D=2, m=1.0, mu=4, max_levels=3,
                        max_range=4096))   # wide enough to never truncate
    vals = (skewed * 3).astype(np.int32)
    s.insert(skewed, vals)
    t.insert(skewed, vals)
    lo, hi = int(pool[0]), int(pool[-1]) + 1
    ks, vs, trunc = s.range(lo, hi, return_truncated=True)
    kt, vt = t.range(lo, hi)
    assert trunc.shape == (n_shards,)
    assert not trunc.any()
    np.testing.assert_array_equal(ks, kt)
    np.testing.assert_array_equal(vs, vt)
    # force a truncating shard: more than max_range live keys on shard 0
    hot = pool[shard_ids(pool, n_shards) == 0][:p.max_range + 8]
    s2 = ShardedSLSM(p, n_shards=n_shards)
    s2.insert(hot, hot)
    _, _, trunc2 = s2.range(lo, hi, return_truncated=True)
    assert bool(trunc2[0])
    assert not trunc2[1:].any()


# -- reserved-sentinel rejection at the API boundary -------------------------

@pytest.mark.parametrize("engine", ["single", "sharded"])
def test_reserved_sentinels_rejected(engine):
    from repro.core.params import KEY_EMPTY
    t = (SLSM(SMALL) if engine == "single"
         else ShardedSLSM(SMALL, n_shards=2))
    ok_keys = np.asarray([1, 2], np.int32)
    with pytest.raises(ValueError, match="KEY_EMPTY"):
        t.insert(np.asarray([1, KEY_EMPTY], np.int32), ok_keys)
    with pytest.raises(ValueError, match="KEY_EMPTY"):
        t.delete(np.asarray([KEY_EMPTY], np.int32))
    with pytest.raises(ValueError, match="KEY_EMPTY"):
        t.lookup(np.asarray([KEY_EMPTY], np.int32))
    with pytest.raises(ValueError, match="KEY_EMPTY"):
        t.lookup_many(np.asarray([3, KEY_EMPTY], np.int32))
    # the regression the guard closes: a KEY_EMPTY lookup used to
    # false-positive against empty stage slots (seq 0 >= 0); and the
    # extreme-but-legal neighbour key must still work
    t.insert(np.asarray([KEY_EMPTY - 1], np.int32),
             np.asarray([77], np.int32))
    vals, found = t.lookup(np.asarray([KEY_EMPTY - 1], np.int32))
    assert found.all() and vals[0] == 77


@pytest.mark.parametrize("engine", ["single", "sharded"])
def test_full_int32_value_domain_round_trips(engine):
    """Regression (ISSUE 8): the legacy engine reserved TOMBSTONE
    (int32 min) as a value sentinel and rejected it at insert. The
    weighted record algebra carries deletes in the weight lane, so
    EVERY int32 is now a legal value — including the old sentinel and
    both domain extremes — and must round-trip through insert, lookup,
    delete, and re-insert."""
    t = (SLSM(SMALL) if engine == "single"
         else ShardedSLSM(SMALL, n_shards=2))
    lo, hi = np.iinfo(np.int32).min, np.iinfo(np.int32).max
    keys = np.asarray([10, 20, 30, 40], np.int32)
    vals = np.asarray([lo, lo + 1, hi, 0], np.int32)  # lo == old TOMBSTONE
    t.insert(keys, vals)
    got, found = t.lookup_many(keys)
    assert found.all()
    np.testing.assert_array_equal(np.asarray(got), vals)
    # extreme values survive delete + re-insert (newest-wins)
    t.delete(keys[:2])
    _, found = t.lookup_many(keys[:2])
    assert not np.asarray(found).any()
    t.insert(keys[:2], vals[2:])
    got, found = t.lookup_many(keys)
    assert np.asarray(found).all()
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray([hi, 0, hi, 0], np.int32))
    # range scans return the sentinel-valued rows too
    rk, rv = t.range(5, 45)
    np.testing.assert_array_equal(np.asarray(rk), keys)
    np.testing.assert_array_equal(np.asarray(rv),
                                  np.asarray([hi, 0, hi, 0], np.int32))


# -- seqno uniqueness across chunked inserts ---------------------------------

def _live_seqnos(state):
    out = [np.asarray(state.stage_seqs)[:int(state.stage_count)]]
    counts = np.asarray(state.buf_counts)
    for r in range(int(state.run_count)):
        out.append(np.asarray(state.buf_seqs)[r, :counts[r]])
    for lv in state.levels:
        lc = np.asarray(lv.counts)
        for d in range(int(lv.n_runs)):
            out.append(np.asarray(lv.seqs)[d, :lc[d]])
    return np.concatenate(out) if out else np.zeros(0, np.int64)


@pytest.mark.parametrize("seed", range(3))
def test_global_seqno_uniqueness_across_chunked_inserts(seed):
    """Regression (ISSUE 3): stage_append used to stamp seqnos on padded
    lanes while advancing next_seq only by n_valid, so pad-lane seqnos
    overlapped the next chunk's live range. Drive odd-sized (sub-Rn)
    chunks — every surviving seqno must be unique and < next_seq."""
    t = SLSM(SMALL)
    rng = np.random.default_rng(seed)
    total = 0
    for _ in range(12):
        n = int(rng.integers(1, SMALL.Rn))       # always a padded chunk
        ks = rng.integers(0, 500, n).astype(np.int32)
        vs = rng.integers(-50, 50, n).astype(np.int32)
        t.insert(ks, vs)
        total += n
        seqs = _live_seqnos(t.state)
        assert len(np.unique(seqs)) == len(seqs)
        assert int(t.state.next_seq) == total
        assert seqs.size == 0 or seqs.max() < total


# -- back-compat facade ------------------------------------------------------

def test_core_slsm_facade_exports():
    from repro.core import slsm
    for name in ("SLSM", "SLSMState", "LevelState", "init_state",
                 "lookup_batch", "range_query", "merge_buffer_to_level0",
                 "merge_level_down", "compact_last_level", "ShardedSLSM"):
        assert hasattr(slsm, name), name
    from repro.core import SLSM as core_slsm
    from repro.engine import SLSM as engine_slsm
    assert core_slsm is engine_slsm
