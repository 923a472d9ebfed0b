"""Scenario executor: one `Scenario` in, one ``BENCH_<name>.json`` out.

Phases run in workload order — insert (merges included), delete, batched
lookups, per-query lookups, per-scan ranges, batched ranges — each timed
with ``block_until_ready`` per dispatch so the latency percentiles are
honest device-complete times, not async-dispatch times. The `shifting`
workload runs a two-phase mixed-op path instead (`_run_shifting`):
write-heavy inserts with a read trickle, then — with no drain in
between — read-heavy lookups with a write trickle, so adaptive engines
meet the flip mid-flight (DESIGN.md §9). The batched vs
per-query pair is the headline comparison: the same query stream served
by one fused multi-key dispatch per batch (`lookup_many`) vs one
dispatch per key — the speedup the batched read path exists for; the
range vs range_batched pair (`range_device` vs `range_many`, DESIGN.md
§10) is its scan-side sibling.

The `serving` workload runs a third path (`_run_serving`): the
closed-loop offered-load sweep of the continuous-batching server
(repro.serve) plus its per-request dispatch baseline, emitted as the
schema's ``metrics.serving`` block with the standard phases null
(DESIGN.md §11).

The Bloom false-positive rate is *measured*, not assumed: every disk
run's filter is probed with the workload's guaranteed-absent key stream
(inserted keys are even, probes are odd) and the admit rate is averaged
over runs — the quantity the paper's Figure 5 speedup is made of.

Documents are validated against `repro.bench.schema` before writing;
an invalid document is a bug and raises instead of polluting the
trajectory.
"""
from __future__ import annotations

import datetime
import json
import platform
import re
import struct
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.bench import schema as SCHEMA
from repro.bench.scenarios import PROFILES, Scenario
from repro.bench.workloads import Workload, make_workload
from repro.core import bloom as BL
from repro.engine import SLSM, LevelingPolicy, ShardedSLSM, TieringPolicy
from repro.engine import wal as WAL


def _phase(ops: int, wall_s: float, dispatch_times: List[float]) -> Dict:
    ts = np.asarray(dispatch_times if dispatch_times else [wall_s])
    return {
        "ops": int(ops),
        "wall_s": float(wall_s),
        "ops_per_s": float(ops / wall_s) if wall_s > 0 else 0.0,
        "p50_us": float(np.percentile(ts, 50) * 1e6),
        "p99_us": float(np.percentile(ts, 99) * 1e6),
        # stall telemetry (DESIGN.md §8): the tail the merge scheduler
        # flattens — p999 needs >=1000 dispatches to separate from max
        "p999_us": float(np.percentile(ts, 99.9) * 1e6),
        "max_stall_us": float(ts.max() * 1e6),
    }


def _timed(fn) -> float:
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return time.perf_counter() - t0


def build_engine(sc: Scenario, wal_dir: Optional[str] = None):
    """Instantiate the scenario's engine: single tree (with its compaction
    policy) or the vmapped sharded engine (tiering only, see sharded.py).
    `wal_dir` (durability scenarios) attaches a fsyncing WAL — every
    timed driver call then pays the real group-commit barrier."""
    p = sc.engine_params()
    dur = WAL.Durability(wal_dir) if wal_dir is not None else None
    if sc.n_shards > 1:
        if sc.policy != "tiering":
            raise ValueError(
                f"scenario {sc.name!r}: ShardedSLSM supports tiering only")
        return ShardedSLSM(p, n_shards=sc.n_shards, durability=dur)
    policy = {"tiering": TieringPolicy, "leveling": LevelingPolicy}[sc.policy]()
    return SLSM(p, policy=policy, durability=dur)


def _run_inserts(tree, w: Workload, chunk: int) -> Dict:
    """Chunked insert stream (merges included). `tree.warm()` has already
    precompiled the full maintenance program set (run_scenario calls it
    untimed — since the scheduler PR no merge program compiles inside the
    timed region; the old caveat about deep-level spill compiles landing
    mid-phase is gone). A prefix covering the first TWO buffer flushes
    (2*R*Rn elements) is additionally inserted untimed so the timed
    region starts with a populated tree — steady-state and comparable
    across scenarios regardless of execution order within one process.

    Returns (phase, steady_state): steady_state is False when the
    workload is too small to warm past both flushes for this geometry
    (the document is stamped so the trajectory can exclude such points).
    """
    p = tree.p
    warm_target = 2 * p.R * p.Rn + chunk
    warm = min(warm_target, 3 * len(w.keys) // 4)
    steady = warm >= warm_target
    if not steady:
        print(f"# warning: insert warmup capped at {warm} < {warm_target} "
              f"ops (R*Rn too large for n={len(w.keys)}); jit compiles "
              "land inside the timed insert phase "
              "(insert_steady_state=false)", file=sys.stderr)
    tree.insert(w.keys[:warm], w.vals[:warm])
    jax.block_until_ready(tree.state)
    times = []
    t0 = time.perf_counter()
    for off in range(warm, len(w.keys), chunk):
        times.append(_timed(lambda off=off: (
            tree.insert(w.keys[off:off + chunk], w.vals[off:off + chunk]),
            tree.state)[1]))
    return _phase(len(w.keys) - warm, time.perf_counter() - t0, times), steady


def _run_deletes(tree, w: Workload, chunk: int) -> Optional[Dict]:
    if len(w.deletes) == 0:
        return None
    times = []
    t0 = time.perf_counter()
    for off in range(0, len(w.deletes), chunk):
        times.append(_timed(lambda off=off: (
            tree.delete(w.deletes[off:off + chunk]), tree.state)[1]))
    return _phase(len(w.deletes), time.perf_counter() - t0, times)


def _run_lookups_batched(tree, lookups: np.ndarray, batch: int) -> Dict:
    # warm every padded shape the loop will hit (full batch + remainder)
    tree.lookup_many(lookups[:batch])
    tail = len(lookups) % batch
    if tail:
        tree.lookup_many(lookups[:tail])
    times = []
    t0 = time.perf_counter()
    for off in range(0, len(lookups), batch):
        times.append(_timed(
            lambda off=off: tree.lookup_many(lookups[off:off + batch])))
    return _phase(len(lookups), time.perf_counter() - t0, times)


def _run_lookups_per_query(tree, lookups: np.ndarray, sample: int) -> Dict:
    qs = lookups[:sample]
    tree.lookup(qs[:1])                        # warm the compile cache
    times = []
    t0 = time.perf_counter()
    for k in qs:
        times.append(_timed(lambda k=k: tree.lookup(np.asarray([k]))))
    return _phase(len(qs), time.perf_counter() - t0, times)


def _run_shifting(tree, w: Workload, prof: Dict) -> Tuple[Dict, Dict, bool]:
    """The two-phase shifting workload (DESIGN.md §9), no drain between.

    Phase 1 (write-heavy): the bulk insert stream in 4*Rn chunks with a
    lookup batch interleaved every few chunks — timed as the `insert`
    phase (dispatch times are the insert chunks; the read trickle rides
    inside the same wall clock, as it would in production). Phase 2
    (read-heavy): the zipf-hot lookup stream in `batch`-wide fused
    dispatches with a small insert chunk interleaved every few batches —
    timed as the `lookup_batched` phase. The engine is never drained
    between phases: an adaptive engine must detect the flip and retune
    mid-flight; a static one meets it with whatever structure it has.

    Returns (insert_phase, lookup_phase, steady) — per-query metrics are
    measured afterwards by the caller, like every other scenario.
    """
    p = tree.p
    n1 = int(w.meta["n_phase1"])
    nl1 = int(w.meta["n_lookups_phase1"])
    chunk = 4 * p.Rn
    # untimed warm prefix, as in _run_inserts (two flushes covered)
    warm_target = 2 * p.R * p.Rn + chunk
    warm = min(warm_target, 3 * n1 // 4)
    steady = warm >= warm_target
    tree.insert(w.keys[:warm], w.vals[:warm])
    jax.block_until_ready(tree.state)

    # phase 1: bulk inserts + a read trickle (every 4th chunk, one
    # `batch`-wide lookup — the same fused width phase 2 uses, so both
    # phases exercise only shapes tree.warm() precompiled)
    batch = prof["batch"]
    l1 = w.lookups[:nl1]
    li, times = 0, []
    t0 = time.perf_counter()
    for i, off in enumerate(range(warm, n1, chunk)):
        times.append(_timed(lambda off=off: (
            tree.insert(w.keys[off:off + chunk], w.vals[off:off + chunk]),
            tree.state)[1]))
        if i % 4 == 3 and li + batch <= nl1:
            tree.lookup_many(l1[li:li + batch])
            li += batch
    insert = _phase(n1 - warm, time.perf_counter() - t0, times)

    # phase 2: zipf-hot lookups + write trickle (every 8th batch, Rn keys)
    l2 = w.lookups[nl1:]
    ki, times = n1, []
    tree.lookup_many(l2[:batch])                 # warm the padded shapes
    tail = len(l2) % batch
    if tail:
        tree.lookup_many(l2[:tail])
    t0 = time.perf_counter()
    for i, off in enumerate(range(0, len(l2), batch)):
        times.append(_timed(
            lambda off=off: tree.lookup_many(l2[off:off + batch])))
        if i % 8 == 7 and ki < len(w.keys):
            tree.insert(w.keys[ki:ki + p.Rn], w.vals[ki:ki + p.Rn])
            ki += p.Rn
    lookup = _phase(len(l2), time.perf_counter() - t0, times)
    return insert, lookup, steady


# batched range scans dispatch in this many windows per fused call (the
# RANGE_BUCKETS grid covers it, so the shape is always warm)
RANGE_BATCH = 32

# the serving scenario's p99 SLO (enqueue->reply): sustained throughput
# is the best swept offered load whose p99 stays under this
SERVING_SLO_P99_US = 50_000.0


def _run_serving(sc: Scenario, w, prof: Dict) -> Tuple[Dict, Any]:
    """The closed-loop serving scenario (repro.serve, DESIGN.md §11).

    Offered-load sweep: one fresh engine + batching server per client
    count (`profile.serving_clients`), the SAME deterministic request
    stream re-partitioned across the clients, coalesced mixed-op-tape
    dispatch. Then the per-request baseline: the same stream at the top
    offered load, every request its own classic driver call. Returns
    ``(metrics.serving block, the last coalesced engine)`` — the engine
    feeds the document's maintenance/bloom sections.
    """
    from repro.serve import Server, closed_loop, sustained_at_slo

    sweep, tree, srv = [], None, None
    for c in prof["serving_clients"]:
        tree = build_engine(sc)
        srv = Server(tree)
        srv.warm()          # maintenance + read grid + tape interpreters
        sweep.append(closed_loop(srv, w.requests, c))
        srv.drain()
    coalesced = sweep[-1]
    top = prof["serving_clients"][-1]
    baseline_tree = build_engine(sc)
    baseline = Server(baseline_tree, mode="per_request")
    baseline.warm()
    per_request = closed_loop(baseline, w.requests, top)
    baseline.drain()
    gov = srv.stats()["governor"]
    block = {
        "sweep": sweep,
        "coalesced": coalesced,
        "per_request": per_request,
        "coalesced_speedup": (coalesced["ops_per_s"]
                              / max(per_request["ops_per_s"], 1e-12)),
        "slo_p99_us": SERVING_SLO_P99_US,
        "sustained_ops_at_slo": sustained_at_slo(sweep,
                                                 SERVING_SLO_P99_US),
        "governor": {"steps": int(gov["steps"]),
                     "idle_steps": int(gov["idle_steps"])},
    }
    return block, tree


def _run_ranges(tree, ranges: np.ndarray) -> Optional[Dict]:
    """Per-scan range phase: one device dispatch per window through the
    device-resident `range_device` — the timed cost is the scan engine
    itself, not a per-scan host `int(count)` round-trip (the sync the
    pre-engine driver paid on every scan)."""
    if len(ranges) == 0:
        return None
    tree.range_device(int(ranges[0, 0]), int(ranges[0, 1]))   # warm
    times = []
    t0 = time.perf_counter()
    for lo, hi in ranges:
        times.append(_timed(
            lambda lo=lo, hi=hi: tree.range_device(int(lo), int(hi))))
    return _phase(len(ranges), time.perf_counter() - t0, times)


def _run_ranges_batched(tree, ranges: np.ndarray
                        ) -> Tuple[Optional[Dict], Optional[Dict]]:
    """Batched range phase: the same windows served by fused
    `range_many` dispatches, RANGE_BATCH windows per call — the scan
    analogue of the batched-vs-per-query lookup comparison. Returns
    (phase, scan_stats) where scan_stats aggregates per-scan
    `keys_returned` and the truncated-scan count (the exactness
    telemetry of the candidate budget, DESIGN.md §10)."""
    if len(ranges) == 0:
        return None, None
    tree.range_many(ranges[:RANGE_BATCH])                     # warm
    tail = len(ranges) % RANGE_BATCH
    if tail:
        tree.range_many(ranges[:tail])
    # small profiles fit the whole window list in one fused call; repeat
    # the sweep so the phase always has a few timed dispatches (a single
    # sample would put any one-off hiccup straight into every percentile)
    n_batches = (len(ranges) + RANGE_BATCH - 1) // RANGE_BATCH
    reps = max(1, 4 // n_batches)
    times, counts, truncs = [], [], []
    t0 = time.perf_counter()
    for rep in range(reps):
        for off in range(0, len(ranges), RANGE_BATCH):
            def one(off=off, rep=rep):
                out = tree.range_many(ranges[off:off + RANGE_BATCH])
                if rep == 0:
                    counts.append(out[2])
                    truncs.append(out[3])
                return out
            times.append(_timed(one))
    phase = _phase(reps * len(ranges), time.perf_counter() - t0, times)
    counts = np.concatenate(counts)
    stats = {"keys_returned_mean": float(counts.mean()),
             "keys_returned_max": int(counts.max()),
             "scans_truncated": int(np.concatenate(truncs).sum())}
    return phase, stats


def _fresh_engine(tree, dur):
    """A fresh durable engine of the measured engine's own kind (the
    self-healing act builds its own small cluster)."""
    if isinstance(tree, ShardedSLSM):
        return ShardedSLSM(tree.p, n_shards=tree.S, durability=dur)
    return SLSM(tree.p, policy=tree.policy, durability=dur)


def _run_selfheal(tree, w: Workload) -> Dict[str, Any]:
    """The v9 self-healing keys of metrics.replication (DESIGN.md §15).

    A fresh quorum-ack cluster on the *real* clock: a segmented-WAL
    leader (`ack_mode="quorum", quorum=2`) with a short lease streams a
    write stream to two auto-promote followers, snapshots and prunes
    (``wal_pruned_bytes``), then is partitioned — not killed, its ends
    simply stop being pumped — and the measurement is the wall time
    until a follower's lease expires, the deterministic successor rule
    fires, and the automatically promoted engine answers its first read
    (``failover_auto_ms``). ``rpo_records`` counts quorum-acked writes
    the successor is missing — 0 by construction: an ack is only
    released once k followers hold the bytes."""
    from repro.engine import replication as R

    lease_s = 0.2
    with tempfile.TemporaryDirectory(prefix="bench_heal_") as td:
        d = Path(td)
        dur = WAL.Durability(d / "leader", fsync=False,
                             snapshot_every_bytes=1 << 30,
                             segment_bytes=2048)
        drv = _fresh_engine(tree, dur)
        leader = R.Leader(drv, ack_mode="quorum", quorum=2,
                          lease_s=lease_s)
        fols = [leader.add_follower(d / f"f{i}", auto_promote=True)
                for i in range(2)]
        keys = np.unique(w.keys[:1024].astype(np.int32))
        probe = keys[:256]
        for i in range(0, len(keys), 64):
            chunk = keys[i:i + 64]
            drv.insert(chunk, (chunk % 65536) * 3 + 1)
            leader.pump()
            for f in fols:
                f.pump()
        # the pruning leg: snapshot -> ack round-trip -> prune drops
        # every sealed segment below min(snapshot, follower acks)
        drv.snapshot()
        leader.pump()
        for f in fols:
            f.pump()
        leader.pump()               # drain the final acks + heartbeat
        leader.prune()
        pruned_bytes = int(dur.stats()["wal_pruned_bytes"])
        acked = int(leader.quorum_seqno())

        # partition (not kill): the leader's pump simply stops, so no
        # heartbeat renews the followers' leases — the real clock runs
        t_part = time.perf_counter()
        new_lead = None
        deadline = t_part + 60.0
        while new_lead is None and time.perf_counter() < deadline:
            for f in fols:
                f.pump()
                if f.new_leader is not None:
                    new_lead = f.new_leader
                    break
            time.sleep(lease_s / 40)
        if new_lead is None:
            raise RuntimeError("self-healing act: no automatic promotion "
                               f"within {deadline - t_part:.0f}s "
                               f"(lease_s={lease_s})")
        pv, pf = new_lead.drv.lookup_many(probe)
        jax.block_until_ready((pv, pf))
        failover_auto_ms = (time.perf_counter() - t_part) * 1e3
        rpo = max(0, acked - int(
            new_lead.drv.durability.writer.last_seqno))
        expiries = sum(f.counters["lease_expiries"] for f in fols)
        lv, lf = drv.lookup_many(probe)
        if not (np.array_equal(np.asarray(lf), np.asarray(pf))
                and np.array_equal(np.asarray(lv)[np.asarray(lf)],
                                   np.asarray(pv)[np.asarray(pf)])):
            raise RuntimeError("self-healing act: promoted successor "
                               "answers differ from the old leader's")
        for ld in (leader, new_lead):
            for h in list(ld.handles):
                ld.detach(h)
        drv.replication = None
        dur.close()
        for f in fols:
            f.drv.durability.close()
    return {"failover_auto_ms": float(failover_auto_ms),
            "rpo_records": int(rpo),
            "wal_pruned_bytes": pruned_bytes,
            "lease_expiries": int(expiries)}


def _run_replication(tree, n_followers: int, w: Workload
                     ) -> Dict[str, Any]:
    """The metrics.replication block (DESIGN.md §14).

    Attaches `n_followers` fresh in-process followers at the genesis
    cursor of the run's now-complete WAL — so the timed convergence
    loop streams the *entire* durable log through ship -> validate ->
    append-verbatim -> group-commit -> chunk-apply on every follower —
    then promotes one follower and times the failover: `promote()`
    (epoch bump, transport teardown) through its first answered read.
    Answer-exactness is checked against the leader on the workload's
    own key stream (found lanes bitwise + one range window). The v9
    self-healing keys (automatic lease failover, quorum-ack RPO, WAL
    pruning — DESIGN.md §15) come from `_run_selfheal`'s own small
    real-clock cluster and ride the same block."""
    from repro.engine import replication as R

    leader = R.Leader(tree)
    tree.durability.sync()
    # seed each follower with ONLY the leader's META header, so the
    # timed loop streams every post-genesis record over the wire (a
    # full `bootstrap` would copy the log and leave nothing to ship)
    meta_rec, _start, meta_end = WAL.record_offsets(
        tree.durability.wal_path)[0]
    header = tree.durability.wal_path.read_bytes()[:meta_end]
    ship_total = len(tree.durability.read_records()) - 1
    probe = np.unique(w.keys[:2048].astype(np.int32))
    with tempfile.TemporaryDirectory(prefix="bench_repl_") as d:
        fols = []
        for i in range(n_followers):
            fdir = Path(d) / f"f{i}"
            fdir.mkdir(parents=True)
            (fdir / "wal.log").write_bytes(header)
            link = R.QueueLink()
            fol = R.Follower(fdir, link.follower)
            fol.link = link
            leader.attach(link.leader,
                          R.Cursor(meta_end, meta_rec.seqno + 1,
                                   meta_rec.epoch))
            fols.append(fol)
        lag_peak = leader.stats()["follower_lag_records"]
        t0 = time.perf_counter()
        R.converge(leader, *fols)
        apply_wall = time.perf_counter() - t0
        st = leader.stats()
        applied = sum(f.counters["applied_records"] for f in fols)

        # failover: sever one follower's transport, promote, first read
        t0 = time.perf_counter()
        prom = fols[0].promote()
        pv, pf = prom.lookup_many(probe)
        jax.block_until_ready((pv, pf))
        failover_ms = (time.perf_counter() - t0) * 1e3
        lv, lf = tree.lookup_many(probe)
        lk, lvv = tree.range(int(probe[0]), int(probe[-1]) + 1)
        pk, pvv = prom.range(int(probe[0]), int(probe[-1]) + 1)
        f_np, pf_np = np.asarray(lf), np.asarray(pf)
        exact = bool(
            np.array_equal(f_np, pf_np)
            and np.array_equal(np.asarray(lv)[f_np], np.asarray(pv)[pf_np])
            and np.array_equal(np.asarray(lk), np.asarray(pk))
            and np.array_equal(np.asarray(lvv), np.asarray(pvv)))
        block = {
            "followers": int(n_followers),
            "shipped_records": int(st["shipped_records"]),
            "shipped_bytes": int(st["shipped_bytes"]),
            "lag_records_peak": int(lag_peak),
            "lag_records_final": int(st["follower_lag_records"]),
            "lag_bytes_final": int(st["follower_lag_bytes"]),
            "apply_ops_per_s": float(applied / max(apply_wall, 1e-12)),
            "failover_ms": float(failover_ms),
            "promoted_exact": exact,
            **_run_selfheal(tree, w),
        }
        for h in list(leader.handles):
            leader.detach(h)
        tree.replication = None
        for f in fols:
            f.drv.durability.close()
    if block["lag_records_final"] != 0 or applied < n_followers * ship_total:
        raise RuntimeError(
            f"replication did not drain: {block} (applied {applied} of "
            f"{n_followers}x{ship_total})")
    return block


def _measure_durability(tree) -> Dict[str, Any]:
    """The metrics.durability block of a WAL-on run (DESIGN.md §12).

    `restore()` is timed FIRST — before any snapshot exists — so
    restore_ms prices the worst case: a full replay-from-genesis of
    everything the run logged. Then one device-pytree snapshot is timed
    (the cost the serving governor hides in idle gaps). wal_bytes_per_op
    is log bytes per logged *element* (key+value), the durability tax
    per user write."""
    dur = tree.durability
    dur.sync()
    records = dur.read_records()
    n_elems = sum(struct.unpack_from("<I", r.payload, 0)[0]
                  for r in records if r.kind in WAL.WRITE_KINDS)
    t0 = time.perf_counter()
    restored = type(tree).restore(str(dur.dir))
    jax.block_until_ready(restored.state)
    restore_ms = (time.perf_counter() - t0) * 1e3
    replayed = int(restored.stats["replayed_records"])
    restored.durability.close()
    tree.snapshot()
    st = dur.stats()
    return {
        "wal_bytes": int(st["wal_bytes"]),
        "wal_records": int(st["wal_records"]),
        "wal_bytes_per_op": float(st["wal_bytes"] / max(1, n_elems)),
        "snapshot_ms": float(dur.last_snapshot_ms),
        "restore_ms": float(restore_ms),
        "replayed_chunks": replayed,
        "fsync": bool(dur.fsync),
    }


def measured_fp_rate(tree, absent: np.ndarray,
                     max_runs: int = 64) -> Tuple[float, int, int]:
    """Mean Bloom admit rate of the disk runs' filters on guaranteed-absent
    keys (the paper's eps, measured). Returns (rate, n_runs_probed,
    n_keys_probed); (0.0, 0, 0) when no disk runs exist yet."""
    p = getattr(tree, "p_active", tree.p)   # the live tuner allocation
    qs = jnp.asarray(absent[:2048].astype(np.int32))
    admit, runs = 0.0, 0
    for lvl, lv in enumerate(tree.state.levels):
        bits, _, kk = p.bloom_geometry(p.level_cap(lvl), p.level_eps(lvl))
        blooms, n_runs = np.asarray(lv.blooms), np.asarray(lv.n_runs)
        if blooms.ndim == 2:          # single tree: (D, words)
            blooms, n_runs = blooms[None], n_runs[None]
        for s in range(blooms.shape[0]):
            for d in range(int(n_runs[s])):
                if runs >= max_runs:
                    break
                pos = BL.bloom_probe(jnp.asarray(blooms[s, d]), qs, kk, bits)
                admit += float(np.asarray(pos).mean())
                runs += 1
    if runs == 0:
        return 0.0, 0, 0
    return admit / runs, runs, int(qs.shape[0])


def _env() -> Dict[str, str]:
    return {
        "jax": jax.__version__,
        "numpy": np.__version__,
        "python": platform.python_version(),
        "platform": jax.default_backend(),
        "timestamp": datetime.datetime.now(datetime.timezone.utc).isoformat(),
    }


def bench_filename(name: str) -> str:
    """``BENCH_<name>.json`` with the scenario name sanitized to a safe
    filename (the stable identity the trajectory is keyed on)."""
    return f"BENCH_{re.sub(r'[^A-Za-z0-9_.-]', '_', name)}.json"


def run_scenario(sc: Scenario, out_dir: str | Path,
                 profile: str = "default") -> Tuple[Path, Dict[str, Any]]:
    """Execute one scenario end-to-end and write its BENCH document.

    Returns (path, document). Raises RuntimeError if the produced
    document does not validate against the schema.
    """
    prof = PROFILES[profile]
    wargs = dict(sc.wargs)
    if sc.workload in ("range-scan", "delete-heavy", "shifting"):
        wargs.setdefault("n_ranges", prof["n_ranges"])
    n_ops = prof["serving_ops"] if sc.workload == "serving" else prof["n"]
    w = make_workload(sc.workload, n_ops, seed=sc.seed, **wargs)
    p = sc.engine_params()
    serving = None
    if sc.durability and w.kind == "serving":
        raise ValueError(f"scenario {sc.name!r}: the serving sweep builds "
                         "one engine per point; use a phase workload for "
                         "the durability axis")
    wal_ctx = (tempfile.TemporaryDirectory(prefix="bench_wal_")
               if sc.durability else None)
    wal_dir = wal_ctx.name if wal_ctx is not None else None

    if w.kind == "serving":
        # closed-loop serving: no standard phases (the schema's nullable
        # block); engines are built per sweep point inside _run_serving
        serving, tree = _run_serving(sc, w, prof)
        insert = batched = per_query = delete = None
        ranges = ranges_batched = range_stats = None
        insert_steady = True
        n_batched_lookups = prof["n_lookups"]
    elif w.kind == "shifting":
        tree = build_engine(sc, wal_dir)
        tree.warm()   # precompile all maintenance programs (untimed)
        # phased mixed-op stream, never drained mid-run: the adaptive
        # tuner must catch the write->read flip in flight (DESIGN.md §9)
        insert, batched, insert_steady = _run_shifting(tree, w, prof)
        nl1 = int(w.meta["n_lookups_phase1"])
        per_query = _run_lookups_per_query(
            tree, w.lookups[nl1:], prof["n_per_query"])
        delete = None
        ranges = _run_ranges(tree, w.ranges)
        ranges_batched, range_stats = _run_ranges_batched(tree, w.ranges)
        n_batched_lookups = len(w.lookups) - nl1
    else:
        tree = build_engine(sc, wal_dir)
        tree.warm()   # precompile all maintenance programs (untimed)
        insert, insert_steady = _run_inserts(tree, w, chunk=4 * p.Rn)
        delete = _run_deletes(tree, w, chunk=4 * p.Rn)
        if p.merge_budget > 0:
            # merge barrier (untimed): retire the deferred maintenance
            # backlog so the read phases run against a fully-merged tree,
            # comparable with synchronous-mode documents (reads are exact
            # either way — this only removes run-count variance from the
            # lookup timings)
            tree.drain()
            jax.block_until_ready(tree.state)
        lookups = w.lookups[:prof["n_lookups"]]
        batched = _run_lookups_batched(tree, lookups, prof["batch"])
        per_query = _run_lookups_per_query(tree, lookups,
                                           prof["n_per_query"])
        ranges = _run_ranges(tree, w.ranges)
        ranges_batched, range_stats = _run_ranges_batched(tree, w.ranges)
        n_batched_lookups = len(lookups)
    fp_rate, _, n_probed = measured_fp_rate(tree, w.absent)
    if sc.replication > 0 and not sc.durability:
        raise ValueError(f"scenario {sc.name!r}: replication requires a "
                         "durable leader (set durability=True)")
    # replication streams the finished log BEFORE _measure_durability
    # snapshots it (the followers must replay from genesis, not sync
    # from a snapshot)
    replication = (_run_replication(tree, sc.replication, w)
                   if sc.replication > 0 else None)
    durability = _measure_durability(tree) if sc.durability else None
    if wal_ctx is not None:
        tree.durability.close()
        wal_ctx.cleanup()

    doc: Dict[str, Any] = {
        "schema_version": SCHEMA.SCHEMA_VERSION,
        "name": sc.name,
        "workload": {"kind": w.kind, "n": w.n, "seed": sc.seed,
                     "args": {**wargs, **{k: v for k, v in w.meta.items()
                                          if isinstance(v, (int, float, str))}}},
        "engine": {"R": p.R, "Rn": p.Rn, "eps": p.eps, "D": p.D, "m": p.m,
                   "mu": p.mu, "max_levels": p.max_levels,
                   "max_range": p.max_range, "cand_factor": p.cand_factor,
                   "range_cand": 0 if p.range_cand is None else p.range_cand,
                   "backend": p.backend, "policy": sc.policy,
                   "n_shards": sc.n_shards, "merge_budget": p.merge_budget,
                   "tuning_mode": p.tuning.mode},
        "profile": {"name": profile, "batch": prof["batch"],
                    "n_lookups": n_batched_lookups,
                    "n_per_query": prof["n_per_query"],
                    "insert_steady_state": insert_steady},
        "metrics": {
            "insert": insert,
            "lookup_batched": batched,
            "lookup_per_query": per_query,
            "delete": delete,
            "range": ranges,
            "range_batched": ranges_batched,
            "range_stats": range_stats,
            "serving": serving,
            "batched_speedup": (None if batched is None else
                                batched["ops_per_s"]
                                / max(per_query["ops_per_s"], 1e-12)),
            "zset": dict({k: int(tree.stats[k]) for k in
                          ("rows_merged_in", "rows_merged_out",
                           "rows_annihilated")},
                         # payload bytes the Ghost gather skipped: 4 a
                         # row kept out of a merge's output
                         ghost_payload_bytes_skipped=4 * int(
                             tree.stats["rows_annihilated"])),
            "maintenance": {k: int(tree.stats[k]) for k in
                            ("seals", "flushes", "spills", "compactions",
                             "backlog_peak", "retunes")},
            "tuner": ({"active": tree.tuner.active,
                       "read_frac": float(tree.tuner.read_frac),
                       "budget_bytes": int(tree.tuner.budget_bytes),
                       "level_fp_observed": [
                           float(x) for x in tree.tuner.level_fp_observed]}
                      if tree.tuner.enabled else None),
            "bloom": {"eps_configured": p.eps,
                      "fp_rate_measured": fp_rate,
                      "n_probed": n_probed},
            "durability": durability,
            "replication": replication,
        },
        "env": _env(),
    }
    errs = SCHEMA.validate(doc)
    if errs:
        raise RuntimeError(
            f"scenario {sc.name!r} produced an invalid BENCH document:\n  "
            + "\n  ".join(errs))
    out_dir = Path(out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / bench_filename(sc.name)
    path.write_text(json.dumps(doc, indent=2, sort_keys=True) + "\n")
    return path, doc
