"""The store's own profiler spans and its host-read counters.

A durable store at a tiny geometry runs inserts that seal, flush and
spill, a delete, a `lookup_many` and a `range_many` under a
`jax.profiler` trace; the spans must all be there, nested as the write
and read paths nest them, and tracing must change no answer and no
counter.
"""
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core.params import SLSMParams
from repro.engine import SLSM
from repro.engine import wal as WAL

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench_tpu import program_spans, xplane  # noqa: E402

# the benchmark tests' tiny geometry (tests/bench_tpu/bench_tpu_tiny.py)
P = SLSMParams(R=4, Rn=64, eps=1e-3, D=4, m=1.0, mu=64, max_levels=2,
               merge_budget=1, range_cand=64, max_range=64)
KEYS = (np.random.default_rng(3).permutation(4000)[:1500] * 2).astype(
    np.int32)

# span -> the program span that must enclose it (None: none may)
PARENT = {"slsm.write": None, "slsm.stage": "slsm.write",
          "slsm.schedule": "slsm.write", "slsm.step.seal": "slsm.schedule",
          "slsm.step.flush": "slsm.schedule",
          "slsm.step.spill": "slsm.schedule", "wal.append": "slsm.write",
          "wal.commit": "slsm.write", "wal.fsync": "wal.commit",
          "slsm.lookup_many": None, "slsm.range_many": None}
FETCH_PARENTS = {"slsm.lookup_many", "slsm.range_many"}


def durable_store(tmp_path, name: str) -> SLSM:
    return SLSM(P, durability=WAL.Durability(tmp_path / name, fsync=True))


def workload(store):
    """Writes that seal, flush and spill, a delete, then one batched
    lookup and one batched scan; returns the answers."""
    for off in range(0, KEYS.size, 300):
        part = KEYS[off:off + 300]
        store.insert(part, part + 1)
    store.delete(KEYS[:20])
    vals, found = store.lookup_many(KEYS[:100])
    k, v, c, t = store.range_many([(0, 1000), (5, 50), (2000, 2600)])
    return [vals, found, k, v, c, t]


def program_events(trace_dir):
    """(start, end, name) of the program's spans, on every host thread."""
    pd = xplane.load(trace_dir)
    out = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out += [(e.start_ns, e.end_ns, e.name) for e in line.events
                    if e.name.startswith(program_spans.PREFIXES)]
    return sorted(out, key=lambda x: (x[0], -x[1]))


def parents(events):
    """(name, name of the innermost enclosing program span or None)."""
    stack = []
    for s, e, name in events:
        while stack and stack[-1][0] <= s:
            stack.pop()
        yield name, (stack[-1][1] if stack else None)
        stack.append((e, name))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("tracing")
    plain = durable_store(tmp, "plain")
    want = workload(plain)
    store = durable_store(tmp, "traced")
    xplane.start(tmp / "trace")
    try:
        got = workload(store)
    finally:
        xplane.stop()
    yield plain, want, store, got, program_events(tmp / "trace")
    plain.durability.close()
    store.durability.close()


def test_every_span_appears_nested_as_the_paths_nest(traced):
    plain, _, _, _, events = traced
    assert plain.stats["seals"] and plain.stats["flushes"]
    assert plain.stats["spills"]
    seen = {}
    for name, parent in parents(events):
        seen[name] = seen.get(name, 0) + 1
        if name == "slsm.fetch":
            assert parent in FETCH_PARENTS
        else:
            assert PARENT[name] == parent, (name, parent)
    assert set(seen) == set(PARENT) | {"slsm.fetch"}
    # one write span per driver call, one stage and one schedule span per
    # chunk, one step span per step
    calls = -(-KEYS.size // 300) + 1
    assert seen["slsm.write"] == seen["wal.commit"] == calls
    assert seen["wal.fsync"] == seen["wal.append"] == calls
    assert seen["slsm.stage"] == seen["slsm.schedule"] == \
        plain.stats["chunks_staged"]
    for kind, counter in (("seal", "seals"), ("flush", "flushes"),
                          ("spill", "spills")):
        assert seen[f"slsm.step.{kind}"] == plain.stats[counter]
    assert seen["slsm.fetch"] == 2


def test_tracing_changes_no_answer_and_no_counter(traced):
    plain, want, store, got, _ = traced
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)
    assert dict(plain.stats) == dict(store.stats)
    assert plain.durability.stats()["wal_bytes"] == \
        store.durability.stats()["wal_bytes"]


def test_host_syncs_and_chunks_staged_count_exactly():
    """The scheduler keeps the occupancy on the host: a chunk reads only
    the stage count its `stage_append` left (1), whether it seals (a full
    chunk) or not (a part chunk). The batched lookup copies 2 answer
    planes, the batched scan 4. No step replaced the state behind the
    scheduler, so the mirror is never rebuilt."""
    store = SLSM(P)
    store.insert(KEYS[:64], KEYS[:64])
    assert (store.stats["host_syncs"], store.stats["chunks_staged"]) == \
        (1, 1)
    store.insert(KEYS[64:164], KEYS[64:164])
    assert (store.stats["host_syncs"], store.stats["chunks_staged"]) == \
        (3, 3)
    store.lookup_many(KEYS[:10])
    assert store.stats["host_syncs"] == 5
    store.range_many([(0, 100)])
    assert store.stats["host_syncs"] == 9
    assert store.stats["chunks_staged"] == 3 and store.stats["seals"] == 2
    assert store.stats["occupancy_resyncs"] == 0


@pytest.mark.parametrize("kind", ["insert", "delete"])
def test_every_write_call_counts_its_chunks(kind):
    store = SLSM(P)
    n = 3 * P.Rn + 1
    keys = KEYS[:n]
    if kind == "insert":
        store.insert(keys, keys)
    else:
        store.delete(keys)
    assert store.stats["chunks_staged"] == 4
    # three seals and no flush (R=4): one stage-count read a chunk
    assert store.stats["seals"] == 3 and store.stats["flushes"] == 0
    assert store.stats["host_syncs"] == 4
