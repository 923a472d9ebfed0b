"""Serving-layer tests (ISSUE 6, DESIGN.md §11).

Load-bearing properties:
  * the oracle: a randomized interleaved op stream (insert / delete /
    lookup / range, multiple clients) served through the coalescing
    window + mixed-op tape is bitwise-equal — per ticket AND after the
    drain() barrier — to the same stream executed sequentially through
    the classic per-op driver calls, on both backends x both drivers;
    the per_request baseline mode satisfies the same oracle;
  * steady state never JITs: after `Server.warm()`, serving windows
    compile no tape interpreter;
  * the coalescer's hazard rule (only adjacent same-kind ops merge),
    capacity splitting, and scatter's result routing;
  * the WindowPolicy triggers and adaptive deadline, the Governor's
    credit accrual/cap/idle spend;
  * the closed-loop load generator and the stats() ledger (p999 +
    max-stall tail accounting the serving bench gates on);
  * the asyncio front-end round-trips a submit to its awaited result.
"""
import asyncio
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro.core.params import KEY_EMPTY, SLSMParams
from repro.engine import SLSM, ShardedSLSM
from repro.engine import tape as TP
from repro.serve import (AsyncServer, Governor, Server, WindowPolicy,
                         closed_loop, coalesce, scatter, sustained_at_slo)

# max_levels=4 (vs the usual 3): the per_request baseline and the
# governor push the same stream through real compactions, and the tiny
# geometry otherwise overflows its deepest level mid-test
SMALL = dict(R=2, Rn=8, eps=0.02, D=2, m=1.0, mu=4, max_levels=4,
             max_range=64)


def small_params(**over):
    return SLSMParams(**{**SMALL, **over})


# -- the request stream ------------------------------------------------------

def _stream(seed, n_requests=36, key_space=400):
    """Randomized interleaved multi-op request stream: a short
    insert-only warmup, then mixed inserts / deletes / lookups (with
    guaranteed-miss `key|1` probes) / range scans."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n_requests):
        kind = ("insert" if i < 4 else
                rng.choice(["insert", "insert", "lookup", "lookup",
                            "delete", "range"]))
        if kind == "insert":
            n = int(rng.integers(1, 7))
            ks = (rng.integers(0, key_space // 2, n) * 2).astype(np.int32)
            vs = rng.integers(-50, 50, n).astype(np.int32)
            reqs.append(("insert", ks, vs))
        elif kind == "delete":
            ks = (rng.integers(0, key_space // 2,
                               int(rng.integers(1, 4))) * 2).astype(np.int32)
            reqs.append(("delete", ks, None))
        elif kind == "lookup":
            n = int(rng.integers(1, 7))
            ks = (rng.integers(0, key_space // 2, n) * 2).astype(np.int32)
            ks = np.where(rng.random(n) < 0.3, ks | 1, ks).astype(np.int32)
            reqs.append(("lookup", ks, None))
        else:
            n = int(rng.integers(1, 3))
            lo = rng.integers(0, key_space, n).astype(np.int32)
            hi = (lo + rng.integers(1, 48, n)).astype(np.int32)
            reqs.append(("range", lo, hi))
    return reqs


def _serve_sequential(tree, reqs):
    """The oracle: the same stream, one classic driver call per request,
    in submission order."""
    out = []
    for kind, a, b in reqs:
        if kind == "insert":
            tree.insert(a, b)
            out.append(None)
        elif kind == "delete":
            tree.delete(a)
            out.append(None)
        elif kind == "lookup":
            out.append(tree.lookup_many(a))
        else:
            out.append(tree.range_many(np.stack([a, b], axis=1)))
    return out


def _assert_result_equal(got, want, msg=""):
    if want is None:
        assert got is None, msg
        return
    assert len(got) == len(want), msg
    for gi, wi in zip(got, want):
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi),
                                      err_msg=msg)


def _run_server_oracle(build, reqs, mode):
    """Drive a Server over `reqs` (pumping mid-stream at odd intervals)
    and check every ticket against the sequential oracle, then check
    the post-drain read state agrees too."""
    ref_tree = build()
    ref = _serve_sequential(ref_tree, reqs)
    srv = Server(build(), window=WindowPolicy(max_ops=24), mode=mode)
    tickets = []
    for i, (kind, a, b) in enumerate(reqs):
        tickets.append(srv.submit(f"client-{i % 3}", kind, a, b))
        if i % 7 == 6:
            srv.pump(force=True)
    srv.drain()
    for i, (t, r) in enumerate(zip(tickets, ref)):
        assert t.done
        _assert_result_equal(t.result, r, msg=f"request {i} ({t.kind})")
    # post-drain barrier: both trees answer identically everywhere
    ref_tree.drain()
    probe = np.arange(0, 400, 2, dtype=np.int32)
    _assert_result_equal(srv.tree.lookup_many(probe),
                         ref_tree.lookup_many(probe), msg="post-drain lookup")
    _assert_result_equal(srv.tree.range_many([(0, 400), (37, 203)]),
                         ref_tree.range_many([(0, 400), (37, 203)]),
                         msg="post-drain range")
    return srv


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("sharded", [False, True])
def test_serving_oracle_coalesced(backend, sharded):
    p = small_params(backend=backend)

    def build():
        return ShardedSLSM(p, n_shards=2) if sharded else SLSM(p)

    srv = _run_server_oracle(build, _stream(seed=7), "coalesced")
    # the coalescer actually fused: fewer dispatches than requests
    assert srv.counters["dispatches"] < srv.counters["requests"]


def test_serving_oracle_per_request():
    p = small_params()
    srv = _run_server_oracle(lambda: SLSM(p), _stream(seed=11),
                             "per_request")
    # the baseline pays one driver call per request
    assert srv.counters["dispatches"] >= srv.counters["requests"]


class _TapeCompiles:
    """Counts XLA compiles of the tape interpreters inside a `with`
    block (jax.monitoring's backend-compile event names the jitted
    function). `warm()` compiles ahead of time from shapes, so the first
    served window adds a dispatch-cache entry without compiling — the
    compile event, not the cache size, is what "never JITs" means."""

    NAMES = ("jit(tape_exec_impl)", "jit(_tape_exec_sharded)")

    def __init__(self):
        self.n = 0

    def _listen(self, event, duration_secs, **kw):
        if (event == "/jax/core/compile/backend_compile_duration"
                and kw.get("fun_name") in self.NAMES):
            self.n += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._listen)


def test_no_recompile_after_warm():
    """Steady-state serving never JITs: after warm(), windows reuse the
    precompiled tape grid on both drivers."""
    srv = Server(SLSM(small_params()))
    srv.warm()
    with _TapeCompiles() as compiles:
        for kind, a, b in _stream(seed=3, n_requests=24):
            srv.submit("c", kind, a, b)
            srv.pump(force=True)
        srv.drain()
    assert compiles.n == 0

    ssrv = Server(ShardedSLSM(small_params(), n_shards=2))
    ssrv.warm()
    with _TapeCompiles() as compiles:
        for kind, a, b in _stream(seed=4, n_requests=24):
            ssrv.submit("c", kind, a, b)
            ssrv.pump(force=True)
        ssrv.drain()
    assert compiles.n == 0


# -- coalescer ----------------------------------------------------------------

def _ticket(kind, keys, vals=None):
    keys = np.asarray(keys, np.int32)
    if vals is None:
        vals = np.zeros_like(keys)
    return SimpleNamespace(kind=kind, keys=keys,
                           vals=np.asarray(vals, np.int32))


def test_coalesce_hazard_ordering():
    """A write between two lookups is a hazard boundary: same-kind ops
    merge ONLY when adjacent, so chunk order = stream order."""
    p = small_params()
    tickets = [_ticket("lookup", [2, 4]), _ticket("insert", [6], [1]),
               _ticket("lookup", [6]), _ticket("lookup", [8])]
    chunks, places = coalesce(p, tickets)
    assert [c.kind for c in chunks] == ["lookup", "write", "lookup"]
    # the two adjacent lookups fused into the final chunk
    np.testing.assert_array_equal(chunks[2].keys, [6, 8])
    assert places[2] == [(2, 0, 1, 0)] and places[3] == [(2, 1, 1, 0)]


def test_coalesce_deletes_merge_with_inserts():
    """Deletes are weight -1 writes (DESIGN.md §13): adjacent
    insert+delete share one write chunk, the delete lanes carrying
    payload 0 and weight -1 beside the inserts' weight +1."""
    p = small_params()
    chunks, _ = coalesce(p, [_ticket("insert", [2, 4], [7, 8]),
                             _ticket("delete", [6])])
    assert len(chunks) == 1 and chunks[0].kind == "write"
    np.testing.assert_array_equal(chunks[0].keys, [2, 4, 6])
    np.testing.assert_array_equal(chunks[0].vals, [7, 8, 0])
    np.testing.assert_array_equal(chunks[0].wts, [1, 1, -1])


def test_coalesce_capacity_split_roundtrip():
    """A request larger than a slot's capacity splits across chunks;
    the placements reassemble it exactly and every chunk respects
    `chunk_capacity`."""
    p = small_params()     # Rn = 8 write/lookup lanes per slot
    keys = (np.arange(21, dtype=np.int32) + 1) * 2
    vals = np.arange(21, dtype=np.int32)
    chunks, places = coalesce(p, [_ticket("insert", keys, vals)])
    assert len(chunks) == 3
    assert all(len(c.keys) <= TP.chunk_capacity(p, c.kind) for c in chunks)
    got = np.concatenate([chunks[pl.chunk].keys[pl.lane:pl.lane + pl.n]
                          for pl in places[0]])
    np.testing.assert_array_equal(got, keys)
    assert [pl.off for pl in places[0]] == [0, 8, 16]


def test_scatter_routes_results():
    """scatter slices each chunk's result planes back onto the tickets
    that contributed the lanes (writes get None)."""
    p = small_params()
    tickets = [_ticket("insert", [2], [1]), _ticket("lookup", [4, 6]),
               _ticket("lookup", [8])]
    chunks, places = coalesce(p, tickets)
    assert [c.kind for c in chunks] == ["write", "lookup"]
    results = [1, (np.array([40, 60, 80]), np.array([True, False, True]))]
    scatter(tickets, places, results)
    assert tickets[0].result is None
    np.testing.assert_array_equal(tickets[1].result[0], [40, 60])
    np.testing.assert_array_equal(tickets[1].result[1], [True, False])
    np.testing.assert_array_equal(tickets[2].result[0], [80])
    np.testing.assert_array_equal(tickets[2].result[1], [True])


# -- window policy + governor -------------------------------------------------

def test_window_policy_triggers():
    wp = WindowPolicy(max_ops=16, wait_s=1e-3)
    assert not wp.should_close(0, 10.0)          # nothing pending
    assert wp.should_close(16, 0.0)              # size trigger
    assert not wp.should_close(1, 0.0)           # thin + fresh
    assert wp.should_close(1, 2e-3)              # time trigger


def test_window_policy_adapts():
    wp = WindowPolicy(max_ops=16, wait_s=1e-3)
    wp.closed(16)                                # full window -> wait up
    assert wp.wait_s > 1e-3
    wp = WindowPolicy(max_ops=16, wait_s=1e-3)
    wp.closed(1)                                 # thin timeout -> wait down
    assert wp.wait_s < 1e-3
    for _ in range(100):                         # clipped to the bounds
        wp.closed(0)
    assert wp.wait_s == pytest.approx(wp.min_wait_s)


class _FakeTree:
    """voluntary_steps stub with a bounded ready backlog."""

    def __init__(self, merge_budget=1, Rn=8, ready=100):
        self.p_active = SimpleNamespace(merge_budget=merge_budget, Rn=Rn)
        self.ready = ready
        self.ran = 0

    def voluntary_steps(self, budget):
        ran = min(budget, self.ready)
        self.ready -= ran
        self.ran += ran
        return ran


def test_governor_accrues_and_spends():
    """Credits accrue at merge_budget steps per Rn write ops; only whole
    steps are spent, fractions bank."""
    gov, tree = Governor(), _FakeTree(merge_budget=1, Rn=8)
    assert gov.window_done(tree, 4) == 0         # 0.5 credits banked
    assert gov.credits == pytest.approx(0.5)
    assert gov.window_done(tree, 4) == 1         # 1.0 -> one step
    assert gov.credits == pytest.approx(0.0)
    assert tree.ran == 1 and gov.steps_run == 1


def test_governor_credit_cap_and_idle():
    """A write burst cannot bank unbounded credits; idle pumps spend the
    free idle allowance."""
    gov = Governor(credit_cap=4.0)
    empty = _FakeTree(ready=0)
    gov.window_done(empty, 10_000)               # nothing ready to run
    assert gov.credits == pytest.approx(4.0)     # capped, stays banked
    busy = _FakeTree(ready=100)
    assert gov.window_done(busy, 0) == 4         # spent once work exists
    assert gov.idle(busy) == 1
    assert gov.idle_steps_run == 1 and gov.steps_run == 5


# -- load generator + accounting ----------------------------------------------

def test_closed_loop_and_stats():
    reqs = [SimpleNamespace(kind=k, keys=a, vals=b)
            for k, a, b in _stream(seed=5, n_requests=30)]
    srv = Server(SLSM(small_params()))
    srv.warm(full=False)
    pt = closed_loop(srv, reqs, concurrency=4)
    assert pt["clients"] == 4 and pt["requests"] == 30
    assert pt["ops"] == sum(r.keys.size for r in reqs)
    assert pt["ops_per_s"] > 0
    assert pt["max_stall_us"] >= pt["p999_us"] >= pt["p99_us"] > 0
    assert pt["dispatches"] <= pt["windows"] + 1
    srv.drain()
    st = srv.stats()
    assert set(st["clients"]) == {f"client-{c}" for c in range(4)}
    for ledger in list(st["clients"].values()) + [st["overall"]]:
        assert ledger["max_stall_us"] >= ledger["p999_us"] > 0
    assert st["counters"]["requests"] == 30
    assert st["governor"]["steps"] >= st["governor"]["idle_steps"] >= 0
    assert sustained_at_slo([pt], slo_p99_us=pt["p99_us"]) == pt["ops_per_s"]
    assert sustained_at_slo([pt], slo_p99_us=0.0) == 0.0


def test_submit_validates_at_the_boundary():
    srv = Server(SLSM(small_params()))
    with pytest.raises(ValueError):
        srv.submit("c", "upsert", [2])
    with pytest.raises(ValueError):
        srv.submit("c", "insert", [2, KEY_EMPTY], [1, 2])
    with pytest.raises(ValueError):
        srv.submit("c", "insert", [2, 4], [1])
    assert srv.pending == 0                      # nothing poisoned the window
    # the old reserved-value sentinel is now a legal payload (ISSUE 8)
    srv.submit("c", "insert", [2], [np.iinfo(np.int32).min])
    assert srv.pending == 1


def test_async_frontend_roundtrip():
    """The asyncio front-end resolves a submitted request to the same
    result the synchronous ticket carries."""
    srv = Server(SLSM(small_params()), window=WindowPolicy(max_ops=4))

    async def scenario():
        async with AsyncServer(srv, poll_s=1e-4) as front:
            await front.submit("a", "insert", np.int32([2, 4]),
                               np.int32([20, 40]))
            vals, found = await front.submit("a", "lookup",
                                             np.int32([2, 4, 5]))
            return np.asarray(vals), np.asarray(found)

    vals, found = asyncio.run(scenario())
    np.testing.assert_array_equal(found, [True, True, False])
    np.testing.assert_array_equal(vals[:2], [20, 40])
