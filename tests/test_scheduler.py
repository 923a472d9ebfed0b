"""Merge scheduler tests: pacing semantics, drain-barrier equivalence,
program warm-up, and the stall-telemetry counters.

The load-bearing property (ISSUE 3's acceptance bar): a budgeted engine
must answer every lookup/range *identically* to a synchronous engine fed
the same ops — mid-backlog (reads are exact because pending-merge runs
stay visible until their step retires them) and after the drain()
barrier — on both drivers and both backends.
"""
import numpy as np
import pytest

from repro.core import SLSMParams
from repro.core.oracle import DictOracle
from repro.engine import (SLSM, LevelingPolicy, MergeScheduler, Occupancy,
                          ShardedSLSM, backlog_cost, pending_steps,
                          step_cost)
from repro.engine.compaction import TieringPolicy
from repro.engine.scheduler import COMPACT, FLUSH, SEAL, SPILL, occupancy_of

SMALL = dict(R=2, Rn=8, eps=0.02, D=2, m=1.0, mu=4, max_levels=3,
             max_range=512, cand_factor=16)


def _params(budget, **over):
    return SLSMParams(**{**SMALL, **over, "merge_budget": budget})


def _drive(t, o, seed, rounds=10, key_space=250):
    rng = np.random.default_rng(seed)
    for _ in range(rounds):
        n = int(rng.integers(1, 40))
        ks = rng.integers(0, key_space, n).astype(np.int32)
        vs = rng.integers(-50, 50, n).astype(np.int32)
        t.insert(ks, vs)
        o.insert(ks, vs)
        dels = rng.integers(0, key_space, int(rng.integers(1, 8))).astype(
            np.int32)
        t.delete(dels)
        o.delete(dels)
    return np.arange(-4, key_space + 4, dtype=np.int32)


# -- pending-step planner ---------------------------------------------------

def test_pending_steps_deepest_first_and_costed():
    p = _params(1)
    pol = TieringPolicy()
    occ = Occupancy(stage_count=p.Rn, run_count=p.R,
                    level_runs=(p.D, p.D, p.D))
    steps = pending_steps(p, pol, occ)
    assert [s.kind for s in steps] == [COMPACT, SPILL, SPILL, FLUSH, SEAL]
    assert [s.level for s in steps][:3] == [2, 1, 0]
    # per-step device-op cost: geometric in depth, seal cheapest
    costs = {(s.kind, s.level): s.cost for s in steps}
    assert costs[(SEAL, -1)] == p.Rn
    assert costs[(COMPACT, 2)] > costs[(SPILL, 1)] > costs[(SPILL, 0)]
    assert backlog_cost(steps) == sum(s.cost for s in steps)
    assert not pending_steps(p, pol, Occupancy(0, 0, (0, 0, 0)))


def test_step_cost_matches_level_geometry():
    p = _params(0)
    assert step_cost(FLUSH, -1, p) == p.runs_merged * p.Rn
    assert step_cost(SPILL, 0, p) == p.disk_runs_merged * p.level_cap(0)
    # slot 0 in D pieces plus D-1 spill-sized slots (m=1)
    assert step_cost(COMPACT, p.max_levels - 1, p) == (
        (2 * p.D - 1) * p.D * p.level_cap(p.max_levels - 2))


def test_negative_merge_budget_rejected():
    with pytest.raises(ValueError, match="merge_budget"):
        _params(-1)


# -- drain-barrier equivalence (the acceptance property) --------------------

@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("budget", [1, 2])
def test_budgeted_slsm_matches_sync_and_oracle(backend, budget):
    """Budgeted vs synchronous single tree, same op stream: lookups and
    ranges must be bit-identical mid-backlog and after drain()."""
    sync, o = SLSM(_params(0, backend=backend)), DictOracle()
    paced = SLSM(_params(budget, backend=backend))
    rng = np.random.default_rng(17)
    for _ in range(8):
        n = int(rng.integers(1, 40))
        ks = rng.integers(0, 250, n).astype(np.int32)
        vs = rng.integers(-50, 50, n).astype(np.int32)
        for t in (sync, paced):
            t.insert(ks, vs)
        o.insert(ks, vs)
        dels = rng.integers(0, 250, 4).astype(np.int32)
        for t in (sync, paced):
            t.delete(dels)
        o.delete(dels)
        # mid-backlog: reads are exact with merges still pending
        qs = np.arange(-4, 254, dtype=np.int32)
        vp, fp = paced.lookup(qs)
        vo, fo = o.lookup(qs)
        np.testing.assert_array_equal(fp, fo)
        np.testing.assert_array_equal(vp[fp], vo[fo])
    paced.drain()
    assert not paced.scheduler.backlog
    qs = np.arange(-4, 254, dtype=np.int32)
    vs_, fs = sync.lookup(qs)
    vp, fp = paced.lookup(qs)
    np.testing.assert_array_equal(fs, fp)
    np.testing.assert_array_equal(vs_, vp)
    ks_, ws = sync.range(0, 250)
    kp, wp = paced.range(0, 250)
    np.testing.assert_array_equal(ks_, kp)
    np.testing.assert_array_equal(ws, wp)
    # merges actually happened (the schedule differs; totals agree
    # wherever the policy makes them inevitable)
    assert paced.stats["flushes"] > 0 and paced.stats["spills"] > 0


@pytest.mark.parametrize("budget", [1, 2])
def test_budgeted_sharded_matches_sync_and_oracle(budget):
    sync, o = ShardedSLSM(_params(0), n_shards=4), DictOracle()
    paced = ShardedSLSM(_params(budget), n_shards=4)
    rng = np.random.default_rng(23)
    for _ in range(6):
        n = int(rng.integers(1, 120))
        ks = rng.integers(0, 500, n).astype(np.int32)
        vs = rng.integers(-50, 50, n).astype(np.int32)
        for t in (sync, paced):
            t.insert(ks, vs)
        o.insert(ks, vs)
        dels = rng.integers(0, 500, 8).astype(np.int32)
        for t in (sync, paced):
            t.delete(dels)
        o.delete(dels)
        qs = np.arange(-4, 504, dtype=np.int32)
        vp, fp = paced.lookup(qs)
        vo, fo = o.lookup(qs)
        np.testing.assert_array_equal(fp, fo)
        np.testing.assert_array_equal(vp[fp], vo[fo])
    paced.drain()
    qs = np.arange(-4, 504, dtype=np.int32)
    vs_, fs = sync.lookup(qs)
    vp, fp = paced.lookup(qs)
    np.testing.assert_array_equal(fs, fp)
    np.testing.assert_array_equal(vs_, vp)
    ks_, ws = sync.range(0, 500)
    kp, wp = paced.range(0, 500)
    np.testing.assert_array_equal(ks_, kp)
    np.testing.assert_array_equal(ws, wp)
    assert paced.stats["flushes"] > 0


def test_budgeted_leveling_policy_keeps_invariant():
    """Pacing must never violate the policy's occupancy bound: a step runs
    only when its destination can accept the output run."""
    p = SLSMParams(R=2, Rn=8, eps=0.05, D=2, m=1.0, mu=4, max_levels=4,
                   max_range=512, merge_budget=1)
    t, o = SLSM(p, policy=LevelingPolicy()), DictOracle()
    qs = _drive(t, o, seed=3)
    t.drain()
    v1, f1 = t.lookup(qs)
    v2, f2 = o.lookup(qs)
    np.testing.assert_array_equal(f1, f2)
    np.testing.assert_array_equal(v1[f1], v2[f2])
    for lv in t.state.levels:
        assert int(lv.n_runs) <= 2


# -- pacing + telemetry ------------------------------------------------------

def test_backlog_peak_recorded_and_drain_clears():
    t, o = SLSM(_params(1)), DictOracle()
    _drive(t, o, seed=5)
    assert t.stats["backlog_peak"] >= 1
    t.drain()
    assert not t.scheduler.backlog
    s, o2 = ShardedSLSM(_params(1), n_shards=2), DictOracle()
    _drive(s, o2, seed=5, key_space=400)
    assert s.stats["backlog_peak"] >= 1
    s.drain()
    assert all(not pending_steps(s.p, s.policy, occ)
               for occ in s._occupancies())


def test_sync_mode_is_default_and_drain_is_noop_shaped():
    t = SLSM(SLSMParams(**SMALL))
    assert t.p.merge_budget == 0
    o = DictOracle()
    qs = _drive(t, o, seed=9)
    before = t.lookup(qs)
    t.drain()   # legal in sync mode: retires whatever the legacy cascade
    after = t.lookup(qs)   # left resident; results must not change
    np.testing.assert_array_equal(before[0], after[0])
    np.testing.assert_array_equal(before[1], after[1])


# -- program warm-up ---------------------------------------------------------

@pytest.mark.parametrize("engine", ["single", "sharded"])
def test_warm_precompiles_without_changing_results(engine):
    if engine == "single":
        warmed, cold = SLSM(_params(1)), SLSM(_params(1))
    else:
        warmed = ShardedSLSM(_params(1), n_shards=2)
        cold = ShardedSLSM(_params(1), n_shards=2)
    warmed.warm()
    # warm() must not touch live state
    assert warmed.n_live == 0
    rng = np.random.default_rng(2)
    ks = rng.integers(0, 300, 200).astype(np.int32)
    vs = rng.integers(0, 100, 200).astype(np.int32)
    warmed.insert(ks, vs)
    cold.insert(ks, vs)
    qs = np.arange(0, 300, dtype=np.int32)
    vw, fw = warmed.lookup(qs)
    vc, fc = cold.lookup(qs)
    np.testing.assert_array_equal(fw, fc)
    np.testing.assert_array_equal(vw, vc)


def test_scheduler_backlog_property_reflects_occupancy():
    t = SLSM(_params(1))
    assert isinstance(t.scheduler, MergeScheduler)
    assert t.scheduler.backlog == []
    t.insert(np.arange(100, dtype=np.int32),
             np.arange(100, dtype=np.int32))
    # whatever is pending must be consistent with the planner
    assert t.scheduler.backlog == pending_steps(
        t.p, t.policy, occupancy_of(t.state))
