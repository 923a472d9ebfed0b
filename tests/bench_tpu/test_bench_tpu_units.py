"""Units of the chip benchmark: generators, reference, registry, trace
reduction, the open loop's clocks, and the refusal to run without a
TPU. None of them needs a chip."""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from bench_tpu_tiny import CELLS, ROOT, TINY_BATCH, TINY_CONFIG, tiny_cell
from bench_tpu import harness as H
from bench_tpu import traffic as T
from bench_tpu import xplane
from bench_tpu.reference import Table, range_errors, truncation_errors

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_data_is_deterministic_per_seed():
    config, _ = tiny_cell("ingest.s3-durable")
    a, b = T.Data(config, 2**31 + 5), T.Data(config, 2**31 + 5)
    c = T.Data(config, 2**31 + 6)
    for (ka, x, y), (kb, u, v) in zip(a.calls, b.calls):
        assert ka == kb
        np.testing.assert_array_equal(x, u)
        np.testing.assert_array_equal(y, v)
    assert not np.array_equal(a.keys, c.keys)
    assert a.n_records == c.n_records == 1050
    assert np.unique(a.keys).size == TINY_CONFIG["preload_keys"]


@pytest.mark.parametrize("cell", CELLS)
def test_traffic_is_deterministic_per_seed(cell):
    config, traffic = tiny_cell(cell)
    data = T.Data(config, 99)
    if traffic["loop"] == "closed":
        def draw(s):
            out = T.closed_batch(data, traffic, T.stream(s, T.TRAFFIC, 2, 0))
            return out if isinstance(out, tuple) else (out,)
    else:
        def draw(s):
            r = T.open_requests(data, traffic, 2.0, T.stream(s, T.TRAFFIC))
            return r.due, r.kind, r.key, r.val
    for x, y in zip(draw(7), draw(7)):
        np.testing.assert_array_equal(x, y)
    assert any(not np.array_equal(x, y) for x, y in zip(draw(7), draw(8)))


def test_open_requests_have_a_fixed_count_and_mix():
    config, traffic = tiny_cell("serve-a.s3-durable")
    data = T.Data(config, 3)
    r = T.open_requests(data, dict(traffic, rate_per_s=1000), 10.0,
                        T.stream(3, T.TRAFFIC))
    assert len(r) == 10000
    assert np.all(np.diff(r.due) >= 0) and 0 <= r.due[0] <= r.due[-1] < 10
    reads = np.mean(r.kind == T.Requests.KINDS.index("read"))
    assert abs(reads - 0.5) < 0.03
    assert np.isin(r.key, data.keys).all()


def test_zipf_top_mass_matches_the_exact_distribution():
    from repro.bench.workloads import zipf_probs
    n, theta = 20000, 0.99
    np.testing.assert_allclose(T.zipf_probs(n, theta), zipf_probs(n, theta))
    ranks = T.Zipf(n, theta).sample(np.random.default_rng(0), 400_000)
    top = n // 100
    want = zipf_probs(n, theta)[:top].sum()
    assert abs(np.mean(ranks < top) - want) < 0.005
    assert ranks.min() >= 0 and ranks.max() < n


def test_lookup_batches_are_half_absent():
    config, traffic = tiny_cell("lookup.s3-volatile")
    data = T.Data(config, 4)
    ks = T.closed_batch(data, traffic, T.stream(4, T.TRAFFIC, 2, 0))
    odd = ks % 2 == 1
    assert odd.sum() == traffic["batch"] // 2
    assert np.isin(ks[~odd], data.keys).all()


def test_scan_windows_span_the_asked_records():
    config, traffic = tiny_cell("scan.s3-volatile")
    data = T.Data(config, 5)
    table = Table(data.space)
    table.write(data.keys, data.keys)
    w = T.closed_batch(data, dict(traffic, batch=2000),
                       T.stream(5, T.TRAFFIC, 2, 0))
    n = [table.range(lo, hi)[0].size for lo, hi in w.tolist()]
    assert 30 < np.mean(n) < 70     # 1-100 records, at the mean density


def test_reference_matches_a_dict_with_duplicates_in_a_call():
    rng = np.random.default_rng(0)
    space = T.KeySpace(300)
    table, model = Table(space), {}
    for _ in range(200):
        n = int(rng.integers(1, 40))
        ks = space.keys_at(rng.integers(0, 60, n))     # many duplicates
        ks[rng.random(n) < 0.1] |= 1                   # outside the space
        vs = rng.integers(-2**31, 2**31, n, dtype=np.int64).astype(np.int32)
        live = bool(rng.random() < 0.7)
        table.write(ks, vs, live=live)
        for k, v in zip(ks.tolist(), vs.tolist()):
            if live:
                model[k] = v
            else:
                model.pop(k, None)
        qs = np.concatenate([ks, space.keys_at(rng.integers(0, 300, 20))])
        gv, gf = table.lookup(qs)
        for q, v, f in zip(qs.tolist(), gv.tolist(), gf.tolist()):
            assert f == (q in model) and v == model.get(q, 0)
        lo = int(rng.integers(0, space.keys_at(60)))
        hi = lo + int(rng.integers(1, 20 * space.step))
        rk, rv = table.range(lo, hi)
        want = sorted((k, v) for k, v in model.items() if lo <= k < hi)
        assert rk.tolist() == [k for k, _ in want]
        assert rv.tolist() == [v for _, v in want]
        assert range_errors(table, lo, hi, rk, rv, rk.size, False) == 0
        if rk.size:
            assert range_errors(table, lo, hi, rk, rv, rk.size - 1,
                                False) == 1
            assert range_errors(table, lo, hi, rk, rv, rk.size - 1,
                                True) == 0


def test_every_name_is_found():
    reg = H.Registry()
    names = [w["name"] for w in SPEC["workloads"]]
    assert list(CELLS[:len(names)]) == names
    for w in SPEC["workloads"]:
        assert w["name"] == f"{w['traffic']}.{w['config']}"
        reg.config(w["config"])
        reg.traffic(w["traffic"])
        # every cell runs in the CPU tests at tiny sizes, as it is stated
        config, traffic = tiny_cell(w["name"])
        H.engine_settings(config)
        assert {k: config[k] for k in TINY_CONFIG} == TINY_CONFIG
        if traffic["loop"] == "closed":
            assert traffic["batch"] == TINY_BATCH[traffic["op"]]
        else:
            assert traffic["rate_per_s"] == 200
        e2e = reg.metrics(w["name"], "end_to_end")
        names = {m["name"] for m in e2e}
        assert "setup_s" in names and len(names) >= 2
        assert reg.metrics(w["name"], "per_layer")
    empty = H.Run("x", {}, {})
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        read = H.Registry.reader(m["name"])
        assert read(empty) is None
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
    assert H.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(H.BenchError):
        H.peaks("no such chip")


def test_benchmark_file_keeps_its_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    for p in SPEC["paths"]:
        assert (ROOT / p).is_dir()
    assert (ROOT / SPEC["command"][1]).is_file()
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert all(k in cfg for k in c["reduced"])
    layers = {}
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        layers.setdefault(m["layer"], m["layer"])
        for w in m["workloads"]:
            assert any(w in e.get("workloads", [w])
                       for e in SPEC["end_to_end"]
                       if e["name"] == m["moves"])
    for w in SPEC["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    fours = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert fours <= max(1, len(SPEC["workloads"]) // 2)


def test_open_loop_times_requests_from_when_they_were_due():
    class Clock:
        t = 100.0

        def __call__(self):
            return self.t

        def sleep(self, s):
            self.t += s

    clock = Clock()

    class Slow:
        """A server whose every served window takes 0.5 s."""

        def __init__(self):
            self.q = []

        @property
        def pending(self):
            return len(self.q)

        def poll(self):
            return True

        def submit(self, client, kind, keys, vals=None):
            t = type("Tk", (), {"done": False, "t_reply": None})()
            self.q.append(t)
            return t

        def pump(self):
            served, self.q = self.q, []
            clock.t += 0.5 if served else 0.0
            for t in served:
                t.done, t.t_reply = True, clock.t
            return len(served)

    reqs = T.Requests(np.array([0.0, 0.1, 0.2]), np.zeros(3, int),
                      np.zeros(3, np.int32), np.zeros(3, np.int32))
    t0, t1, tks, late, pumps = H.open_loop(
        Slow(), reqs, lambda name: __import__("contextlib").nullcontext(),
        clock=clock, sleep=clock.sleep)
    lat = [t.t_reply - (t0 + d) for t, d in zip(tks, reqs.due)]
    # the first waits one window; the other two arrive during it and are
    # submitted late, then wait for the second window
    np.testing.assert_allclose(lat, [0.5, 0.9, 0.8])
    np.testing.assert_allclose(late, [0.0, 0.4, 0.3])
    assert pumps == [0.5, 0.5] and t1 - t0 == pytest.approx(1.0)


def test_trace_reduction_of_a_recorded_trace():
    """`bench_tpu/record_trace.py` recorded this on one TPU v5e: two
    inserts, a lookup, a scan and a served window in their spans."""
    red = xplane.reduce(ROOT / "bench_tpu" / "testdata" /
                        "small.xplane.pb.gz")
    assert red.devices == 1 and not red.truncated
    assert 0 < red.ops_busy_s <= red.busy_s < red.window_s
    for prog, n in (("stage_append_impl", 4), ("seal_run_impl", 4),
                    ("lookup_many_impl", 1), ("range_many_impl", 1),
                    ("tape_exec_impl", 1)):
        secs, runs = red.program(prog)
        assert secs > 0 and runs == n, prog
    assert red.busy_s == pytest.approx(sum(red.program_s.values()))
    assert {k: v[0] for k, v in red.spans.items()} == {
        "bench.insert": 2, "bench.lookup": 1, "bench.range": 1,
        "bench.pump": 1}
    assert 0 < len(red.device_ops) <= 10 and 0 < len(red.idle_gaps) <= 10
    assert all(name.split("/", 1)[0] in red.program_s
               for name, _ in red.device_ops)
    idle = sum(s for _, s in red.idle_gaps)
    assert idle == pytest.approx(red.window_s - red.busy_s, rel=1e-6)


def test_union_merges_overlaps():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert xplane.program_name("jit_tape_exec_impl(12)") == "tape_exec_impl"


def test_the_command_refuses_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench_tpu" / "run.py"), "--workload",
         "ingest.s3-durable", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "refusing" in p.stderr


def test_a_scan_cut_short_within_its_budget_is_wrong():
    space = T.KeySpace(300)
    table = Table(space)
    ks = space.keys_at(np.arange(0, 40))
    table.write(ks, ks)
    table.write(ks[:10], ks[:10] + 1)           # 50 lanes, 40 keys
    lo, hi = int(ks[0]), int(ks[-1]) + 1
    assert table.records(lo, hi) == 50
    assert table.records(lo, int(ks[10])) == 20
    rk, rv = table.range(lo, hi)
    # a prefix flagged `truncated` passes the prefix check either way
    assert range_errors(table, lo, hi, rk, rv, 0, True) == 0
    # but may only be flagged past the budget
    assert truncation_errors(table, lo, hi, True, 50) == 1
    assert truncation_errors(table, lo, hi, True, 49) == 0
    assert truncation_errors(table, lo, hi, False, 50) == 0


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


def _profile(device_events, window=(0, 10_000_000_000)):
    host = _P("/host:CPU", [_L("python3", [_E("bench.window", *window)] + [
        _E("bench.lookup", s, s + 900_000_000)
        for s in range(0, 10_000_000_000, 1_000_000_000)])])
    mods = [_E(f"jit_lookup_many_impl({i})", s, t)
            for i, (s, t) in enumerate(device_events)]
    dev = _P("/device:TPU:0", [_L("XLA Modules", mods),
                               _L("XLA Ops", list(mods))])
    return type("PD", (), {"planes": [host, dev]})()


def test_a_trace_cut_short_keeps_only_what_it_covers():
    whole = [(s + 100_000_000, s + 800_000_000)
             for s in range(0, 10_000_000_000, 1_000_000_000)]
    red = xplane.reduce_profile(_profile(whole))
    assert not red.truncated and red.window_s == pytest.approx(10.0)
    assert red.busy_s == pytest.approx(7.0)
    assert red.program("lookup_many_impl") == (pytest.approx(7.0), 10)
    assert red.calls == red.calls_kept == 10
    # the buffers filled during the third call: the first two calls are
    # kept whole, from the window's start to the second call's end
    red = xplane.reduce_profile(_profile(whole[:3]))
    assert red.truncated and (red.calls, red.calls_kept) == (10, 2)
    assert red.window_s == pytest.approx(1.9)
    assert red.busy_s == pytest.approx(1.4)
    assert red.program("lookup_many_impl")[1] == 2
    assert red.spans["bench.lookup"][0] == 2
    assert red.idle_share == pytest.approx(0.5 / 1.9)


def test_idle_at_the_window_ends_is_not_taken_for_truncation():
    """Calls whose device work is all recorded, with the device idle for
    most of the first and last second: the whole window counts."""
    ends = [(s + 100_000_000, s + 800_000_000)
            for s in range(1_000_000_000, 9_000_000_000, 1_000_000_000)]
    ends = [(800_000_000, 850_000_000)] + ends + [(9_050_000_000,
                                                  9_100_000_000)]
    red = xplane.reduce_profile(_profile(ends))
    assert not red.truncated and red.calls_kept == 10
    assert red.window_s == pytest.approx(10.0)
    assert red.idle_share == pytest.approx(1 - 5.7 / 10)
