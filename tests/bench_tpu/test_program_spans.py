"""The program's spans in a traced window (`bench_tpu/program_spans.py`)
and the reader of the engine's host-read counter, on synthetic traces
and on two recorded on a TPU v5e."""
import json

import pytest

from bench_tpu_tiny import ROOT
from bench_tpu import harness as H
from bench_tpu import program_spans as P
from bench_tpu import xplane

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
S = 1_000_000_000     # ns in a second


class _E:
    def __init__(self, name, start, end):
        self.name, self.start_ns, self.end_ns = name, start, end
        self.duration_ns = end - start


class _L:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _P:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def _spans_profile(call, spans, busy, starts=(0, 5), length=4):
    """A window of 10 s holding one `call` span of `length` s at each of
    `starts` (s), the program spans `spans` ((name, start s, end s),
    repeated in every call) and device programs over `busy` (s)."""
    events = [_E("bench.window", 0, 10 * S)]
    for c in starts:
        events.append(_E(call, round(c * S), round((c + length) * S)))
        events += [_E(n, round((c + a) * S), round((c + b) * S))
                   for n, a, b in spans]
    mods = [_E(f"jit_prog_impl({i})", round(s * S), round(t * S))
            for i, (s, t) in enumerate(busy)]
    host = _P("/host:CPU", [_L("python3", events)])
    dev = _P("/device:TPU:0", [_L("XLA Modules", mods),
                               _L("XLA Ops", list(mods))])
    return type("PD", (), {"planes": [host, dev]})()


# one insert call's program spans (s into the call): staging, the
# scheduler with a seal inside it, the group commit with its fsync
WRITE_SPANS = [("slsm.write", 0.5, 3.8), ("slsm.stage", 0.5, 1.0),
               ("slsm.schedule", 1.0, 3.0), ("slsm.step.seal", 2.0, 2.5),
               ("wal.append", 0.5, 0.5), ("wal.commit", 3.0, 3.5),
               ("wal.fsync", 3.1, 3.4)]
WRITE_BUSY = [(1.5, 2.2), (6.5, 7.2)]
SCAN_SPANS = [("slsm.range_many", 0.5, 3.5), ("slsm.fetch", 2.0, 3.5)]
SCAN_BUSY = [(1.0, 2.5), (6.0, 7.5)]


def _reduce(call, spans, busy, **kw):
    pd = _spans_profile(call, spans, busy, **kw)
    return P.reduce_profile(pd), xplane.reduce_profile(pd)


def test_program_spans_count_total_and_self_time():
    out, red = _reduce("bench.insert", WRITE_SPANS, WRITE_BUSY)
    got = {k: [n, round(t, 9), round(s, 9)]
           for k, (n, t, s) in out["program_spans"].items()}
    # a span's self time leaves out its children, not its grandchildren
    assert got == {"slsm.write": [2, 6.6, 0.6], "slsm.stage": [2, 1.0, 1.0],
                   "slsm.schedule": [2, 4.0, 3.0],
                   "slsm.step.seal": [2, 1.0, 1.0],
                   "wal.append": [2, 0.0, 0.0], "wal.commit": [2, 1.0, 0.4],
                   "wal.fsync": [2, 0.6, 0.6]}
    # the harness's own reduction still sees only the call spans
    assert red.spans == {"bench.insert": [2, pytest.approx(8.0)]}
    assert (out["window_s"], out["busy_s"]) == (red.window_s, red.busy_s)


@pytest.mark.parametrize("call,spans,busy,want", [
    # the first gap (0-1.5 s) crosses the window's start, the stage span
    # and the scheduler's own time; the second, two calls' tails and heads
    ("bench.insert", WRITE_SPANS, WRITE_BUSY,
     {"outside": 3.4, "slsm.stage": 1.0, "slsm.schedule": 2.0,
      "slsm.step.seal": 0.6, "wal.commit": 0.4, "wal.fsync": 0.6,
      "slsm.write": 0.6}),
    ("bench.range", SCAN_SPANS, SCAN_BUSY,
     {"outside": 4.0, "slsm.range_many": 1.0, "slsm.fetch": 2.0}),
    # a program with no spans of its own: every idle second is outside
    ("bench.range", [], SCAN_BUSY, {"outside": 7.0}),
])
def test_idle_is_split_over_the_innermost_program_span(call, spans, busy,
                                                       want):
    out, red = _reduce(call, spans, busy)
    assert {k: round(v, 9) for k, v in out["idle_in"].items()} == want
    assert sum(out["idle_in"].values()) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-9)


def test_program_spans_of_calls_the_trace_cut_are_left_out():
    # the device's events end inside the second of three calls: only
    # the first is kept whole
    spans = [("slsm.write", 0.5, 2.5), ("slsm.stage", 0.5, 1.0),
             ("slsm.schedule", 1.0, 2.5), ("slsm.step.seal", 2.0, 2.4)]
    out, red = _reduce("bench.insert", spans, [(1.5, 2.2), (3.7, 3.9)],
                       starts=(0, 3.2, 6.4), length=3)
    assert red.truncated and red.calls_kept == 1
    assert out["window_s"] == pytest.approx(3.0)
    assert {k: v[0] for k, v in out["program_spans"].items()} == \
        dict.fromkeys((name for name, _, _ in spans), 1)
    assert sum(out["idle_in"].values()) == pytest.approx(3.0 - 0.7)
    assert out["idle_in"]["outside"] == pytest.approx(0.5 + 0.5)


@pytest.mark.parametrize("call,spans,busy,key,want", [
    ("bench.insert", WRITE_SPANS, WRITE_BUSY, "sched.self_ms_per_span",
     1500.0),
    ("bench.insert", WRITE_SPANS, WRITE_BUSY, "wal.commit_ms_per_call",
     500.0),
    ("bench.insert", WRITE_SPANS, WRITE_BUSY, "device_idle.driver", 42.0),
    ("bench.insert", WRITE_SPANS, WRITE_BUSY, "device_idle.wal", 10.0),
    ("bench.range", SCAN_SPANS, SCAN_BUSY, "device_idle.fetch", 20.0),
])
def test_the_shares_and_times_derived_from_the_spans(call, spans, busy, key,
                                                     want):
    out, _ = _reduce(call, spans, busy)
    assert P.derived(out)[key] == pytest.approx(want)
    # a program without the spans (an older store) gives none of them
    bare, _ = _reduce(call, [], busy)
    assert P.derived(bare) == {}


def test_the_host_read_counter_reader():
    read = H.Registry.reader("sched.syncs_per_chunk")
    run = H.Run("ingest.s3-durable", {}, {})
    run.stats_before = {"host_syncs": 10, "chunks_staged": 1}
    run.stats_after = {"host_syncs": 100, "chunks_staged": 11}
    assert read(run) == pytest.approx(9.0)
    # a store without the counters reads nothing, and raises nothing
    run.stats_before, run.stats_after = {"writes": 0}, {"writes": 5}
    assert read(run) is None
    m, = [m for m in SPEC["per_layer"] if m["name"] == "sched.syncs_per_chunk"]
    assert m["workloads"] == ["ingest.s3-durable"]


@pytest.mark.parametrize("name,spans", [
    # before the program had spans: all of its idle time is outside them
    ("small", {}),
    # `bench_tpu/record_trace.py` on one TPU v5e, with the program's
    # spans: two inserts (one flushes), a lookup, a scan, a served window
    ("spans", {"slsm.write": 2, "slsm.stage": 4, "slsm.schedule": 4,
               "slsm.step.seal": 4, "slsm.step.flush": 1,
               "slsm.lookup_many": 1, "slsm.range_many": 1,
               "slsm.fetch": 2}),
])
def test_program_spans_of_a_recorded_trace(name, spans, capsys):
    path = ROOT / "bench_tpu" / "testdata" / f"{name}.xplane.pb.gz"
    red = xplane.reduce(path)
    assert P.main([str(path)]) == 0
    out = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert {k: v[0] for k, v in out["program_spans"].items()} == spans
    assert all(0 <= s <= t for _, t, s in out["program_spans"].values())
    idle = red.window_s - red.busy_s
    assert sum(out["idle_in"].values()) == pytest.approx(idle, rel=1e-6)
    assert set(out["idle_in"]) <= set(spans) | {"outside"}
    if not spans:
        assert out["idle_in"] == {"outside": pytest.approx(idle, rel=1e-9)}
        assert out["derived"] == {}
    else:
        assert 0 < out["derived"]["device_idle.fetch"] < 100
