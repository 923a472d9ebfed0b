"""Merge scheduler tests: pacing semantics, drain-barrier equivalence,
program warm-up, and the stall-telemetry counters.

The load-bearing property (ISSUE 3's acceptance bar): a budgeted engine
must answer every lookup/range *identically* to a synchronous engine fed
the same ops — mid-backlog (reads are exact because pending-merge runs
stay visible until their step retires them) and after the drain()
barrier — on both drivers and both backends.
"""
import copy

import jax
import numpy as np
import pytest

from repro.core import SLSMParams, TuningPolicy
from repro.core.oracle import DictOracle
from repro.engine import (SLSM, LevelingPolicy, MergeScheduler, Occupancy,
                          ShardedSLSM, backlog_cost, pending_steps,
                          step_cost)
from repro.engine import replication as REPL
from repro.engine import wal as WAL
from repro.engine.compaction import TieringPolicy
from repro.engine.scheduler import COMPACT, FLUSH, SEAL, SPILL, occupancy_of
from repro.engine.tuner import READ

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


# -- the host occupancy mirror ---------------------------------------------

# room for the tuner's allocations (R=4) and for leveling (D >= 2); three
# levels, so flushes, spills and deepest compactions all run
MIRROR = dict(R=4, Rn=8, eps=1e-2, D=3, m=1.0, mu=4, max_levels=3,
              max_range=512, cand_factor=16)


def _mirror_store(budget, policy="tiering", tuned=False, durability=None):
    over = ({"tuning": TuningPolicy(mode="adaptive", interval=64,
                                    eps_floor=1e-3)} if tuned else {})
    p = SLSMParams(**{**MIRROR, **over, "merge_budget": budget})
    pol = LevelingPolicy() if policy == "leveling" else TieringPolicy()
    return SLSM(p, policy=pol, durability=durability)


def _check_mirror(store):
    """Wrap the store's scheduler: after every `on_chunk` and `drain` its
    occupancy comes from the mirror (no device read) and equals what the
    device holds; before every flush the memory runs it merges hold Rn
    records each (the flush's structural rows in)."""
    sched = store.scheduler

    def checked(entry):
        def run():
            entry()
            syncs = store.stats["host_syncs"]
            occ = sched.occupancy()
            assert store.stats["host_syncs"] == syncs
            assert occ == occupancy_of(store.state)
        return run

    def run_step(step, run=sched.run_step):
        if step.kind == FLUSH:
            mr = sched.p.runs_merged_eff
            assert int(store.state.buf_counts[:mr].sum()) == mr * sched.p.Rn
        run(step)

    sched.on_chunk = checked(sched.on_chunk)
    sched.drain = checked(sched.drain)
    sched.run_step = run_step


def _mirror_stream(seed, rounds=48, key_space=160):
    """Seeded calls of at most Rn records each: inserts with in-call
    duplicates and overwrites, deletes, lookups (few in the first half,
    many in the second, so an adaptive tuner switches allocation) and a
    drain every eighth round."""
    rng = np.random.default_rng(seed)
    rn = MIRROR["Rn"]
    for r in range(rounds):
        ks = rng.integers(0, key_space, 3 * rn).astype(np.int32)
        vs = rng.integers(-50, 50, 3 * rn).astype(np.int32)
        for off in range(0, ks.size, rn):
            yield "insert", ks[off:off + rn], vs[off:off + rn]
        yield "delete", rng.integers(0, key_space, rn // 2).astype(
            np.int32), None
        n_q = 8 if r < rounds // 2 else 256
        yield "lookup", rng.integers(0, key_space, n_q).astype(np.int32), None
        if r % 8 == 7:
            yield "drain", None, None


def _apply(t, kind, ks, vs):
    if kind == "insert":
        t.insert(ks, vs)
    elif kind == "delete":
        t.delete(ks)
    elif kind == "lookup":
        return t.lookup_many(ks)
    else:
        t.drain()


def _assert_oracle(t, o, key_space=160):
    qs = np.arange(-2, key_space + 2, dtype=np.int32)
    vt, ft = t.lookup_many(qs)
    vo, fo = o.lookup(qs)
    np.testing.assert_array_equal(ft, fo)
    np.testing.assert_array_equal(vt[ft], vo[fo])


@pytest.mark.parametrize("tuned", [False, True], ids=["static", "adaptive"])
@pytest.mark.parametrize("policy", ["tiering", "leveling"])
@pytest.mark.parametrize("budget", [0, 1, 2])
def test_occupancy_mirror_matches_device_and_a_rereading_twin(
        budget, policy, tuned):
    """The scheduler's host mirror equals the device's occupancy after
    every chunk and drain, and the tree it builds is bit for bit the tree
    of a twin whose mirror is rebuilt from the device before every call
    (its state reassigned, so the scheduler no longer recognizes it)."""
    store = _mirror_store(budget, policy, tuned)
    twin = _mirror_store(budget, policy, tuned)
    _check_mirror(store)
    oracle = DictOracle()
    passes = 0
    for kind, ks, vs in _mirror_stream(seed=11):
        twin.state = twin.state._replace()
        got = [_apply(t, kind, ks, vs) for t in (store, twin)]
        passes += kind != "lookup"
        if kind == "insert":
            oracle.insert(ks, vs)
        elif kind == "delete":
            oracle.delete(ks)
        elif kind == "lookup":
            vo, fo = oracle.lookup(ks)
            for v, f in got:
                np.testing.assert_array_equal(f, fo)
                np.testing.assert_array_equal(v[f], vo[fo])
    assert store.stats["spills"] > 0 and store.stats["compactions"] > 0
    if tuned:
        assert store.stats["retunes"] > 0
    assert store.stats["occupancy_resyncs"] == 0
    assert twin.stats["occupancy_resyncs"] == passes
    assert store.stats["host_syncs"] < twin.stats["host_syncs"]
    for a, b in zip(jax.tree_util.tree_leaves(store.state),
                    jax.tree_util.tree_leaves(twin.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    skip = ("host_syncs", "occupancy_resyncs")
    assert ({k: v for k, v in store.stats.items() if k not in skip}
            == {k: v for k, v in twin.stats.items() if k not in skip})
    _assert_oracle(store, oracle)


def _feed(engines, oracle, seed, calls=6):
    """Seeded insert and delete calls of up to 3 Rn records into every
    engine of `engines` and the oracle."""
    rng = np.random.default_rng(seed)
    for _ in range(calls):
        ks = rng.integers(0, 160, 3 * MIRROR["Rn"]).astype(np.int32)
        dels = rng.integers(0, 160, 4).astype(np.int32)
        for t in engines:
            t.insert(ks, ks + 1)
            t.delete(dels)
        oracle.insert(ks, ks + 1)
        oracle.delete(dels)


@pytest.mark.parametrize("path", ["run_tape", "restore", "apply_replicated",
                                  "promote_demote", "retune"])
def test_state_replaced_outside_the_scheduler_resyncs_the_mirror(
        tmp_path, path):
    """A state the scheduler did not install makes its mirror stale: the
    next scheduler pass rebuilds it once from the device, and answers
    stay exact. Promote and demote install no state and keep it."""
    o = DictOracle()
    if path == "run_tape":
        t = _mirror_store(1)
        _feed([t], o, seed=1)
        rn = MIRROR["Rn"]
        ks = np.arange(1000, 1000 + 3 * rn, dtype=np.int32)
        seals = t.run_tape([("write", ks[i:i + rn], ks[i:i + rn] + 1)
                            for i in range(0, ks.size, rn)])
        o.insert(ks, ks + 1)
        assert sum(seals) > 0   # sealed inside the scan, behind the scheduler
        cases = [(t, t, o, 1)]
    elif path == "restore":
        d = tmp_path / "db"
        t = _mirror_store(1, durability=WAL.Durability(d, fsync=False))
        _feed([t], o, seed=2)
        t.snapshot()
        _feed([t], o, seed=3, calls=2)   # the WAL tail past the snapshot
        t.durability.close()
        r = SLSM.restore(d)
        # the replay's first pass rebuilt the mirror of the adopted state
        assert r.stats["occupancy_resyncs"] == 1
        cases = [(r, r, o, 0)]
    elif path in ("apply_replicated", "promote_demote"):
        drv = _mirror_store(1, durability=WAL.Durability(
            tmp_path / "leader", fsync=False))
        leader = REPL.Leader(drv)
        _feed([drv], o, seed=5)
        drv.snapshot()
        fol = leader.add_follower(tmp_path / "follower")
        _feed([drv], o, seed=6)
        REPL.converge(leader, fol)
        # applying the shipped writes rebuilt the mirror of the state the
        # follower adopted from the leader's snapshot, once
        assert fol.drv.stats["occupancy_resyncs"] == 1
        assert drv.stats["occupancy_resyncs"] == 0
        if path == "apply_replicated":
            # the next writes reach the follower only through the leader
            class Shipped:
                def insert(self, ks, vs):
                    drv.insert(ks, vs)

                def delete(self, ks):
                    drv.delete(ks)
                    REPL.converge(leader, fol)

            cases = [(fol.drv, Shipped(), o, 0)]
        else:
            new = fol.promote()
            old = leader.demote()
            _assert_oracle(old, o)
            old.promote()
            cases = [(new, new, o, 0), (old, old, copy.deepcopy(o), 0)]
    else:   # retune: a decided allocation applied outside a RETUNE step
        t = _mirror_store(1, tuned=True)
        _feed([t], o, seed=7)
        t.tuner.target = READ
        t.apply_retune()
        cases = [(t, t, o, 1)]
    for i, (t, writer, oi, delta) in enumerate(cases):
        before = t.stats["occupancy_resyncs"]
        for seed in (20 + i, 30 + i):
            _feed([writer], oi, seed, calls=1)
            assert t.scheduler.occupancy() == occupancy_of(t.state)
            assert t.stats["occupancy_resyncs"] == before + delta
        _assert_oracle(t, oi)
