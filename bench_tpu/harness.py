"""The benchmark's harness: finds a cell's files by name, runs it, checks it.

`BENCHMARK.json` names every cell, configuration and metric. A cell's
configuration is the JSON file its entry names, its traffic mix is
``bench_tpu/traffic/<traffic>.json`` (read by `bench_tpu.traffic`), and
each metric is read by ``bench_tpu/metrics/<metric>.py``, whose
``read(run)`` returns a number or None. So a new cell needs new files
and a `BENCHMARK.json` entry, and no edit here.

A configuration file states the store it deploys (`engine_settings`).
Its top-level keys are:

* any field of `repro.core.params.SLSMParams` (``eps_per_level`` as a
  list, ``tuning`` as an object of `TuningPolicy` fields);
* ``policy``: the compaction policy, ``{"name": <CompactionPolicy.name>,
  ...its constructor's arguments}``, e.g. ``{"name": "leveling",
  "max_resident": 2}``; tiering where it is left out;
* ``durability``: null, or ``{"fsync": bool, "snapshot_every_bytes":
  n}`` for a group-commit write-ahead log;
* ``key_universe`` and ``preload_keys``, ``preload_overwrites``,
  ``preload_deletes``, ``preload_call`` (`bench_tpu.traffic.Data`);
* documentation that no run reads: ``published``, ``reduced``,
  ``assumed``, ``guarantees``, ``deployment``, ``reference``.

Any other key is refused (`BenchError`), so that a file cannot state a
store that the run does not build.

A run (`run_cell`): build the store, warm its programs, preload the
seed's data, warm the traffic, measure for the given seconds, then check
every answer of the window and a read-back of the written keys against
`bench_tpu.reference.Table`.
"""
from __future__ import annotations

import collections
import contextlib
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from bench_tpu import traffic as T
from bench_tpu.reference import Table, range_errors, truncation_errors

ROOT = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent

# lanes of one read-back `lookup_many` call (a warmed bucket)
READBACK_BATCH = 4096
# tape slot buckets warmed for served cells: every window of up to 512
# coalesced chunks runs a program that was compiled and traced in set-up
TAPE_WARM = (4, 16, 64, 128, 256, 512)
# top-level keys of a configuration file that are not `SLSMParams`
# fields: read by the harness and the generator, or documentation
HARNESS_KEYS = frozenset({
    "key_universe", "preload_keys", "preload_overwrites", "preload_deletes",
    "preload_call", "durability", "policy", "published", "reduced",
    "assumed", "guarantees", "deployment", "reference"})
# the numbers `correct` is decided by; every limit is 0 (exact answers)
CHECKS = ("wrong_answers", "truncated_answers", "wrong_readback",
          "unanswered", "writes_miscounted")


class BenchError(RuntimeError):
    """The benchmark cannot run as asked (unknown name, no chip)."""


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Registry:
    """`BENCHMARK.json` and the files it names, looked up by name."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.spec = load_json(self.root / "BENCHMARK.json")

    def _entry(self, kind: str, name: str) -> dict:
        for e in self.spec[kind]:
            if e["name"] == name:
                return e
        raise BenchError(f"no {kind} entry named {name!r} in BENCHMARK.json")

    def cell(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        config = load_json(self.root / self._entry("configs", name)["file"])
        engine_settings(config)
        return config

    def traffic(self, name: str) -> dict:
        return load_json(HERE / "traffic" / f"{name}.json")

    def metrics(self, cell: str, kind: str) -> list[dict]:
        """The `end_to_end` or `per_layer` metrics that `cell` reports."""
        e2e = {m["name"] for m in self.spec["end_to_end"]
               if cell in m.get("workloads", [cell])}
        out = []
        for m in self.spec[kind]:
            if "workloads" in m:
                if cell in m["workloads"]:
                    out.append(m)
            elif kind == "end_to_end" or m["moves"] in e2e:
                out.append(m)
        return out

    @staticmethod
    def reader(name: str):
        """``read(run)`` of ``bench_tpu/metrics/<name>.py``."""
        path = HERE / "metrics" / f"{name}.py"
        if not path.is_file():
            raise BenchError(f"no reader {path} for metric {name!r}")
        spec = importlib.util.spec_from_file_location(
            f"bench_tpu.metrics.{name.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


class Compiles:
    """Count of programs traced or compiled, from jax.monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.n = collections.Counter()
        self.names: set[str] = set()

    def listen(self, event, duration_secs, **kw) -> None:
        if event in self.EVENTS:
            self.n[event.rsplit("/", 1)[1]] += 1
            self.names.add(str(kw.get("fun_name", "?")))

    def total(self) -> int:
        return sum(self.n.values())


class Run:
    """What one run measured; the metric readers read it."""

    def __init__(self, cell: str, config: dict, traffic: dict):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.setup_s = None
        self.window_s = None
        self.work = collections.Counter()   # records, keys, scans, requests
        self.call_s: list[float] = []       # closed loop: each call
        self.latency_s: list[float] = []    # open loop: reply - due
        self.late_s: list[float] = []       # open loop: submit - due
        self.pump_s: list[float] = []       # open loop: pumps that served
        self.stats_before: dict = {}
        self.stats_after: dict = {}
        self.wal_bytes = None               # WAL bytes the window grew
        self.trace = None                   # bench_tpu.xplane.Reduced
        self.memory_peak = None             # bytes, from the device
        self.compiles_in_window = 0
        self.compiled_names: set[str] = set()
        self.attempted = 0
        self.chip_peaks = None              # peaks.json row of the device
        self.failed = 0
        self.error: str | None = None

    def stat_delta(self, key: str) -> int:
        return int(self.stats_after.get(key, 0)
                   - self.stats_before.get(key, 0))


def peaks(device_kind: str) -> dict:
    """The published peaks of `device_kind` (bench_tpu/peaks.json)."""
    table = load_json(HERE / "peaks.json")
    if device_kind not in table["devices"]:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"bench_tpu/peaks.json")
    return table["devices"][device_kind]


def engine_settings(config: dict):
    """(SLSMParams, compaction policy) that `config` states. Raises
    `BenchError` for a key that is neither an `SLSMParams` field nor in
    `HARNESS_KEYS`, and for a policy the engine does not have."""
    import dataclasses

    from repro.core.params import SLSMParams
    from repro.engine import CompactionPolicy, TieringPolicy
    from repro.engine import wal as WAL

    fields = {f.name for f in dataclasses.fields(SLSMParams)}
    unknown = sorted(set(config) - fields - HARNESS_KEYS)
    if unknown:
        raise BenchError(f"configuration keys {unknown} are neither "
                         f"SLSMParams fields nor read by the harness")
    params = WAL.params_from_dict(
        {k: v for k, v in config.items() if k in fields})
    spec = config.get("policy")
    if spec is None:
        return params, TieringPolicy()
    if not isinstance(spec, dict):
        raise BenchError(f"policy {spec!r} is not an object with a name")
    spec = dict(spec)
    name = spec.pop("name", None)
    known = {c.name: c for c in CompactionPolicy.__subclasses__()}
    if name not in known:
        raise BenchError(f"unknown compaction policy {name!r}; the engine "
                         f"has {sorted(known)}")
    try:
        policy = known[name](**spec)
    except TypeError as e:
        raise BenchError(f"compaction policy {name!r}: {e}") from e
    policy.validate(params)
    return params, policy


def build_store(config: dict, wal_dir: str | None, control: bool):
    """The system under test at `config` (or the control in its place)."""
    params, policy = engine_settings(config)
    space = T.KeySpace(config["key_universe"])
    if control:
        from bench_tpu.control import FirstWriteStore
        return FirstWriteStore(space, int(config["max_range"]))
    from repro.engine import SLSM
    from repro.engine import wal as WAL

    dur = config.get("durability")
    durability = None
    if dur is not None:
        durability = WAL.Durability(
            wal_dir, fsync=bool(dur["fsync"]),
            snapshot_every_bytes=int(dur["snapshot_every_bytes"]))
    return SLSM(params, policy=policy, durability=durability)


def build_server(store, control: bool):
    if control:
        from bench_tpu.control import FirstWriteServer
        return FirstWriteServer(store)
    from repro.serve import Server
    return Server(store)


def block(store) -> None:
    if store.state is not None:
        import jax
        jax.block_until_ready(store.state)


def warm_tapes(store, key: int, buckets) -> None:
    """Dispatch one read-only tape of each slot bucket. `warm_tape`
    compiles the tape programs from shapes, but the first real call of
    each still traces it (seconds at full size): served windows must not
    pay that."""
    lo = np.asarray([key], np.int32)
    for t in buckets:
        store.run_tape([("range", lo, lo + 1)] * t)


def apply_write(store, kind: str, keys, vals) -> None:
    if kind == "insert":
        store.insert(keys, vals)
    else:
        store.delete(keys)


# -- the two loops ----------------------------------------------------------

def closed_loop(store, data, traffic, seconds, rng_of, ops, span,
                run=None, clock=time.perf_counter):
    """Calls of one op, each after the previous returned, until `seconds`
    have passed (at least one call); `ops` gets one log entry per call
    and `run` each call's seconds. Returns (start, end) on `clock`."""
    op = traffic["op"]

    def one(i: int):
        with span("bench.gen"):
            args = T.closed_batch(data, traffic, rng_of(i))
        t = clock()
        if op == "insert":
            with span("bench.insert"):
                store.insert(*args)
                block(store)
            out = None
        elif op == "lookup":
            with span("bench.lookup"):
                out = store.lookup_many(args)
        else:
            with span("bench.range"):
                k, v, c, tr = store.range_many(args)
            out = [(k[j, :c[j]].copy(), v[j, :c[j]].copy(), int(c[j]),
                    bool(tr[j])) for j in range(len(c))]
        dt = clock() - t
        ops.append((op, args, out))
        return dt

    t0 = clock()
    i = 0
    while True:
        dt = one(i)
        i += 1
        if run is not None:
            run.call_s.append(dt)
        if clock() - t0 >= seconds:
            return t0, clock()


def submit(server, reqs, i: int, client: str):
    k = reqs.key[i:i + 1]
    if T.Requests.KINDS[reqs.kind[i]] == "read":
        return server.submit(client, "lookup", k)
    return server.submit(client, "insert", k, reqs.val[i:i + 1])


def open_loop(server, reqs, span, clock=time.perf_counter,
              sleep=time.sleep, clients: int = 64):
    """Submit each request when due, pump the server between arrivals.
    Returns (start, end, tickets, lateness of each submit, seconds of
    each pump that served a window)."""
    n = len(reqs)
    tickets = [None] * n
    late = np.zeros(n)
    pumps = []
    t0 = clock()
    due = t0 + reqs.due
    i = 0
    while True:
        now = clock()
        while i < n and due[i] <= now:
            tickets[i] = submit(server, reqs, i, f"client-{i % clients}")
            late[i] = clock() - due[i]
            i += 1
        if server.pending:
            if server.poll():
                t = clock()
                with span("bench.pump"):
                    served = server.pump()
                if served:
                    pumps.append(clock() - t)
            continue
        if i >= n:
            return t0, clock(), tickets, late, pumps
        with span("bench.idle"):
            server.pump()
        wait = due[i] - clock()
        if wait > 0:
            sleep(wait)


def ticket_ops(tickets, ops) -> int:
    """Append served tickets to `ops` in submission order; returns how
    many never got an answer."""
    missing = 0
    for t in tickets:
        if t is None or not t.done or t.error is not None:
            missing += 1
            continue
        if t.kind == "lookup":
            ops.append(("lookup", t.keys, t.result))
        else:
            ops.append((t.kind, t.keys, t.vals))
    return missing


# -- the check ----------------------------------------------------------------

def replay(table: Table, ops, scan_budget: int) -> tuple[int, int]:
    """Apply logged writes to `table` in order; count the answers of
    logged reads that disagree with it, and the scans cut short within
    `scan_budget` written records (`truncation_errors`)."""
    wrong = cut = 0
    for op, args, out in ops:
        if op == "insert":
            if isinstance(args, tuple):
                table.write(*args)
            else:
                table.write(args, out)
        elif op == "lookup":
            rv, rf = table.lookup(args)
            gv, gf = out
            wrong += int(np.sum((np.asarray(gf) != rf)
                                | (np.asarray(gv) != rv)))
        else:
            for (lo, hi), (k, v, c, tr) in zip(
                    np.asarray(args).tolist(), out):
                wrong += range_errors(table, lo, hi, k, v, c, tr)
                cut += truncation_errors(table, lo, hi, tr, scan_budget)
    return wrong, cut


def written_keys(ops) -> np.ndarray:
    parts = [np.asarray(a[0] if isinstance(a, tuple) else a).reshape(-1)
             for op, a, _ in ops if op == "insert"]
    return np.unique(np.concatenate(parts)) if parts else np.zeros(0,
                                                                  np.int32)


def readback(store, table: Table, keys: np.ndarray, limit: int,
             rng: np.random.Generator) -> tuple[int, int]:
    """Look `keys` up on the live store (a seeded sample of `limit` where
    there are more) and count those that disagree with `table`."""
    if keys.size > limit:
        keys = rng.choice(keys, limit, replace=False)
    wrong = 0
    for off in range(0, keys.size, READBACK_BATCH):
        part = keys[off:off + READBACK_BATCH]
        gv, gf = store.lookup_many(np.resize(part, READBACK_BATCH))
        rv, rf = table.lookup(part)
        wrong += int(np.sum((gf[:part.size] != rf)
                            | (gv[:part.size] != rv)))
    return wrong, int(keys.size)


# -- one run --------------------------------------------------------------

def run_cell(cell: str, config: dict, traffic: dict, seed: int,
             seconds: float, trace: bool, *, control: bool = False,
             t_start: float | None = None, device=None,
             clock=time.perf_counter):
    """One run of `cell`; returns (Run, checks). `device` is the JAX
    device whose memory is read (None: not read)."""
    import jax

    t_start = clock() if t_start is None else t_start
    run = Run(cell, config, traffic)
    compiles = Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles.listen)
    tmp = Path(tempfile.mkdtemp(prefix="bench_tpu_"))
    tracing = False
    try:
        data = T.Data(config, seed)
        durable = config.get("durability") is not None
        store = build_store(config, str(tmp / "wal") if durable else None,
                            control)
        open_ = traffic["loop"] == "open"
        buckets = {READBACK_BATCH}
        if traffic.get("op") == "lookup":
            buckets.add(max(16, 1 << (int(traffic["batch"]) - 1)
                            .bit_length()))
        store.warm(buckets=tuple(sorted(buckets)))
        server = build_server(store, control) if open_ else None
        if open_:
            store.warm_tape(buckets=TAPE_WARM)
        log(f"bench_tpu: set-up: built and warmed at "
            f"{clock() - t_start:.3f} s")
        for kind, k, v in data.calls:
            apply_write(store, kind, k, v)
        block(store)
        log(f"bench_tpu: set-up: preloaded {data.n_records} records at "
            f"{clock() - t_start:.3f} s")
        if open_ and not control:
            # served traffic spends maintenance in idle gaps: retire the
            # preload's deferred merge steps here, or the first idle gap
            # of the window runs a spill left over from set-up
            store.drain()
            warm_tapes(store, int(data.keys[0]), TAPE_WARM)
        ops: list = []       # warm-up and window, in order
        nothing = contextlib.nullcontext

        def spans(name):
            return jax.profiler.TraceAnnotation(name) if tracing else \
                nothing()

        if open_:
            warm = T.open_requests(data, traffic,
                                   float(traffic.get("warm_s", 2)),
                                   T.stream(seed, T.WARM))
            _, _, tks, _, _ = open_loop(server, warm, spans, clock)
            run.failed += ticket_ops(tks, ops)
            reqs = T.open_requests(data, traffic, seconds,
                                   T.stream(seed, T.TRAFFIC, 2))
        else:
            for i in range(int(traffic.get("warm_calls", 2))):
                closed_loop(store, data, traffic, 0.0,
                            lambda _i, i=i: T.stream(seed, T.WARM, i),
                            ops, spans)
        block(store)
        log(f"bench_tpu: set-up: warm traffic done at "
            f"{clock() - t_start:.3f} s")
        n_warm_ops = len(ops)
        run.stats_before = dict(store.stats)
        wal0 = (store.durability.stats()["wal_bytes"] if durable
                and store.durability is not None else None)
        if trace:
            from bench_tpu import xplane
            trace_dir = tmp / "trace"
            xplane.start(trace_dir)
            tracing = True
        n_comp0 = compiles.total()
        compiles.names.clear()
        run.setup_s = clock() - t_start
        try:
            with spans("bench.window"):
                if open_:
                    t0, t1, tks, late, pumps = open_loop(server, reqs, spans,
                                                         clock)
                    due = t0 + reqs.due
                    run.late_s = late.tolist()
                    run.pump_s = pumps
                    run.latency_s = [
                        (t.t_reply - d) if t is not None and t.done
                        else float("inf") for t, d in zip(tks, due)]
                    run.work["requests"] = len(reqs)
                else:
                    t0, t1 = closed_loop(
                        store, data, traffic, seconds,
                        lambda i: T.stream(seed, T.TRAFFIC, 2, i), ops,
                        spans, run)
                block(store)
                t1 = clock()
        except Exception as e:     # noqa: BLE001  (a run that fails is
            run.error = f"{type(e).__name__}: {e}"   # reported, not lost)
            run.failed += 1
            t0 = t1 = clock()
            tks = []
        finally:
            if tracing:
                xplane.stop()
                tracing = False
        run.window_s = t1 - t0
        run.compiles_in_window = compiles.total() - n_comp0
        run.compiled_names = set(compiles.names)
        if device is not None:
            stats = device.memory_stats() or {}
            run.memory_peak = stats.get("peak_bytes_in_use")
            run.chip_peaks = peaks(device.device_kind)
        if open_:
            run.failed += ticket_ops(tks, ops)
        window_ops = ops[n_warm_ops:]
        for op, a, _ in window_ops:
            n = (a[0] if isinstance(a, tuple) else a).shape[0]
            run.work[{"insert": "records", "lookup": "keys",
                      "range": "scans"}[op]] += n
        run.attempted = run.work["requests"] or (
            run.work["records"] + run.work["keys"] + run.work["scans"])
        if run.error is None:
            run.stats_after = dict(store.stats)
            if wal0 is not None:
                run.wal_bytes = store.durability.stats()["wal_bytes"] - wal0
        if trace:
            run.trace = xplane.reduce(trace_dir)
        # -- the check, after the window --------------------------------
        table = Table(data.space)
        for kind, k, v in data.calls:
            table.write(k, v, live=kind == "insert")
        checks = dict.fromkeys(CHECKS, 0)
        checks["unanswered"] = run.failed
        checks["wrong_answers"], checks["truncated_answers"] = replay(
            table, ops, min(int(config["range_cand"]),
                            int(config["max_range"])))
        if run.error is None:
            written = sum(
                (a[0] if isinstance(a, tuple) else a).shape[0]
                for op, a, _ in window_ops if op == "insert")
            checks["writes_miscounted"] = abs(
                run.stat_delta("writes") - written)
            wrong, run.work["readback"] = readback(
                store, table, written_keys(window_ops),
                int(traffic.get("readback_max", 1 << 18)),
                T.stream(seed, T.CHECK))
            checks["wrong_readback"] = wrong
        return run, checks
    finally:
        if tracing:
            from bench_tpu import xplane
            xplane.stop()
        jax.monitoring.unregister_event_duration_listener(compiles.listen)
        dur = getattr(locals().get("store"), "durability", None)
        if dur is not None:
            dur.close()
        shutil.rmtree(tmp, ignore_errors=True)
