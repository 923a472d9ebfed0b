"""Host-side driver — the paper's insert/merge control flow (Algorithm 2).

`SLSM` owns the state pytree; *when* maintenance work happens is the
`repro.engine.scheduler.MergeScheduler`'s decision: with
`SLSMParams.merge_budget == 0` (default) the whole Do-Merge cascade runs
synchronously inside the insert chunk that triggers it (the paper's
behaviour, and the write-stall pathology that comes with it); with a
positive budget the cascade is paced one bounded step per chunk and
`drain()` is the completion barrier. Every data-touching op is a jitted
device computation dispatched through the ops backend selected by
`SLSMParams.backend`.
"""
from __future__ import annotations

import collections
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.params import KEY_EMPTY, SLSMParams
from repro.engine import tape as TP
from repro.engine import wal as WAL
from repro.engine.backend import get_backend
from repro.engine.batching import (ADAPTIVE_BUCKETS, RANGE_BUCKETS,
                                   TAPE_BUCKETS, adaptive_bucket,
                                   bucket_pow2, host_read, pad_to,
                                   range_bucket, range_many_host)
from repro.engine.compaction import (CompactionPolicy, LevelingPolicy,
                                     TieringPolicy)
from repro.engine.memtable import init_state, stage_append
from repro.engine.precompile import compile_programs, i32, state_shapes
from repro.engine.read_path import (aggregate_many, level_probe_stats,
                                    lookup_batch, lookup_many, range_many,
                                    range_query)
from repro.engine.scheduler import MergeScheduler, Occupancy
from repro.engine.tuner import READ, ReadModePolicy, Tuner, retune_filters

# fixed width of the tuner's sampled probe-telemetry dispatch: one shape
# -> one compiled level_probe_stats program per (allocation, structure)
PROBE_SAMPLE = 256

# WAL/snapshot fingerprints name compaction policies by kind string so
# restore() can rebuild the configured policy without pickling it
_POLICY_KINDS = {"tiering": TieringPolicy, "leveling": LevelingPolicy}


def _policy_kind(policy: CompactionPolicy) -> str:
    """Fingerprint name of a configured compaction policy (the inverse
    of the `_POLICY_KINDS` lookup restore() performs)."""
    for name, cls in _POLICY_KINDS.items():
        if type(policy) is cls:
            return name
    return type(policy).__name__.lower()


def reject_reserved(keys: np.ndarray, vals: np.ndarray | None = None,
                    op: str = "insert") -> None:
    """Reserved-sentinel guard at the public API boundary.

    KEY_EMPTY (INT32_MAX) is the engine's padding/empty-slot key;
    letting it in from user data would alias padding (silently dropped
    keys), and a lookup of KEY_EMPTY can false-positive against empty
    stage slots. Values are unrestricted: deletes are carried by the
    record's weight lane (DESIGN.md §13), not a reserved value, so
    every int32 — including the historical TOMBSTONE bit pattern — is a
    legal payload. Both drivers call this before touching device state.
    """
    del vals  # no reserved values under the weighted record algebra
    if keys.size and (keys == KEY_EMPTY).any():
        raise ValueError(
            f"{op}: key {int(KEY_EMPTY)} (KEY_EMPTY/INT32_MAX) is reserved "
            "as the engine's empty-slot sentinel and cannot be stored or "
            "queried")


class SLSM:
    """Host-side driver: owns the state pytree; the merge scheduler owns
    the maintenance schedule.

    `insert`/`delete`/`lookup`/`range` match the paper's API. The merge
    cascade (Do-Merge) is decomposed into bounded steps (scheduler.py):
    recursion depth and level occupancy are host decisions; every
    data-touching op is a jitted device computation.
    """

    def __init__(self, params: SLSMParams | None = None,
                 policy: CompactionPolicy | None = None,
                 durability=None):
        self.p = params or SLSMParams()
        get_backend(self.p.backend)  # fail fast on unknown backends
        self.policy = policy or TieringPolicy()
        self.policy.validate(self.p)
        self.state = init_state(self.p)
        # why `state` is gone, once a declared capacity error consumed it
        self.state_lost: str | None = None
        # p_active = the tuner's current allocation applied to p (same
        # physical geometry, possibly different effective filter/buffer/
        # fence view); == p forever under static tuning (DESIGN.md §9)
        self.p_active = self.p
        self.tuner = Tuner(self)
        self._read_policy = ReadModePolicy()
        self.scheduler = MergeScheduler(self)
        # a fresh state: nothing staged, no memory run, no level
        self.scheduler.track(Occupancy(0, 0, ()))
        # maintenance counters (the bench runner's merge-count trajectory);
        # backlog_peak = most pending merge steps ever observed at a chunk
        # boundary (0 in synchronous mode only if no step was ever
        # deferred); reads/writes feed the tuner's workload-mix signal;
        # host_syncs = blocking device-to-host reads of the driver and
        # its scheduler (batching.host_read), chunks_staged =
        # stage_append dispatches, occupancy_resyncs = rebuilds of the
        # scheduler's occupancy mirror from the device (after a state the
        # scheduler did not install: tape, restore, replayed retune)
        self.stats = collections.Counter(seals=0, flushes=0, spills=0,
                                         compactions=0, backlog_peak=0,
                                         retunes=0, reads=0, writes=0,
                                         rows_merged_in=0, rows_merged_out=0,
                                         rows_annihilated=0, host_syncs=0,
                                         chunks_staged=0,
                                         occupancy_resyncs=0)
        # durability surface (DESIGN.md §12): None (default) = volatile
        # engine, a path or wal.Durability = WAL every write op +
        # snapshot on demand; _replaying suppresses re-logging while
        # restore() replays the WAL tail through this same write path
        self._replaying = False
        self.durability = WAL.as_durability(durability)
        if self.durability is not None:
            self.durability.ensure_header(self._wal_meta())
        # replication hook (DESIGN.md §14): a replication.Leader /
        # .Follower claims this; repro.serve pumps it between windows.
        # fenced (DESIGN.md §15) = a deposed leader: writes raise until
        # a future promote() — the guard that keeps a partitioned old
        # leader from diverging from the cluster
        self.replication = None
        self.fenced = False

    @property
    def state(self):
        """The device state pytree. Raises once a deepest-level overflow
        consumed it (the compaction donates its input — see
        scheduler.MergeScheduler.run_step)."""
        if self._state is None:
            raise RuntimeError(self.state_lost)
        return self._state

    @state.setter
    def state(self, value) -> None:
        self._state = value

    # -- write path -------------------------------------------------------
    def _guard_writes(self) -> None:
        """Reject writes into a read-only engine: a fenced (deposed)
        leader or a replica follower (DESIGN.md §15). Replay and
        `apply_replicated` bypass this via ``_replaying``."""
        if self._replaying:
            return
        if self.fenced:
            raise RuntimeError(
                "write rejected: this engine was fenced (deposed leader) "
                "— demote() happened; rejoin via the new leader's "
                "bootstrap or promote() to lead again")
        if self.durability is not None and self.durability.replica:
            raise RuntimeError(
                "write rejected: replica engines are read-only until "
                "promote()")

    def insert(self, keys, vals) -> None:
        """Batched insert (paper Algorithm 1/2): stage in Rn-sized chunks;
        after each chunk the scheduler runs up to `merge_budget` voluntary
        merge steps plus whatever the next chunk structurally forces
        (everything, when merge_budget == 0 — the legacy synchronous
        cascade)."""
        keys = np.asarray(keys, np.int32).reshape(-1)
        vals = np.asarray(vals, np.int32).reshape(-1)
        assert keys.shape == vals.shape
        reject_reserved(keys, vals, op="insert")
        self._insert(keys, vals, np.ones_like(keys))

    def _insert(self, keys: np.ndarray, vals: np.ndarray,
                wts: np.ndarray) -> None:
        """Post-validation weighted write path (delete() enters here with
        weight -1 records). With durability on, the whole op is logged as
        one WAL record before any device state changes and
        group-committed before returning (one fsync per driver call, not
        per chunk — DESIGN.md §12).

        Spans: ``slsm.write`` around the call, ``slsm.stage`` around each
        chunk's padding, puts and `stage_append` dispatch, and the
        scheduler's ``slsm.schedule`` after it."""
        with jax.profiler.TraceAnnotation("slsm.write"):
            if len(keys) > 0:
                self._guard_writes()
            log = (self.durability is not None and not self._replaying
                   and len(keys) > 0)
            if log:
                self.durability.log_write(keys, vals, wts)
            self.stats["writes"] += len(keys)
            self.tuner.note_writes(len(keys))
            rn = self.p.Rn
            for off in range(0, len(keys), rn):
                with jax.profiler.TraceAnnotation("slsm.stage"):
                    ck, cv = keys[off:off + rn], vals[off:off + rn]
                    cw = wts[off:off + rn]
                    n = len(ck)
                    if n < rn:
                        ck = np.pad(ck, (0, rn - n), constant_values=KEY_EMPTY)
                        cv = np.pad(cv, (0, rn - n))
                        cw = np.pad(cw, (0, rn - n))
                    self.scheduler.staged(stage_append(
                        self.p_active, self.state, jnp.asarray(ck),
                        jnp.asarray(cv), jnp.asarray(cw), jnp.int32(n)))
                    self.stats["chunks_staged"] += 1
                self.scheduler.on_chunk()
            if log:
                self.durability.sync()

    def delete(self, keys) -> None:
        """Deletes are weight -1 records (paper 2.8 tombstones, recast as
        the Z-set retraction — DESIGN.md §13); a key's presence is the
        sign of its newest record's weight, and the pair physically
        vanishes (annihilates) when a merge creates the deepest data
        (paper 2.5)."""
        keys = np.asarray(keys, np.int32).reshape(-1)
        reject_reserved(keys, op="delete")
        self._insert(keys, np.zeros_like(keys), np.full_like(keys, -1))

    def drain(self) -> None:
        """Merge barrier: retire every pending maintenance step. After
        drain, a budgeted engine answers lookups/ranges identically to a
        synchronous one fed the same ops (reads are exact *without*
        draining too — pending-merge runs stay visible until their step
        retires them; drain only completes the deferred work)."""
        self.scheduler.drain()

    def warm(self, buckets: tuple = ADAPTIVE_BUCKETS) -> None:
        """Precompile the engine's full maintenance program set, so no
        insert chunk ever pays a first-use jit compile (the other — and
        at bench scale dominant — write-stall source besides cascade
        work; see MergeScheduler.programs). Optional; call before
        latency-sensitive serving.

        Also precompile the *read* programs (batched lookup per `bucket`,
        the single-key shape, the range-scan and aggregate grids —
        `RANGE_BUCKETS` batched widths plus the single-scan program) for
        every levels-structure the engine can grow into, so mid-stream
        level materialization never drops a compile into a live lookup
        or scan. With adaptive tuning the grid spans every preset
        allocation — a retune swaps jit-static params, and without this
        the first read after a switch would pay the compile the pacing
        budget cannot flatten — plus the probe-telemetry pass.

        Compiles from shapes alone, concurrently, and touches no device
        memory (`precompile.compile_programs`)."""
        progs = self.scheduler.programs()
        skip = self.tuner.enabled
        for pa in self._param_sets():
            for n_levels in range(self.p.max_levels + 1):
                st = state_shapes(pa, n_levels)
                for b in buckets:
                    progs.append((lookup_many,
                                  (pa, st, i32(b), i32(), False, skip)))
                progs.append((lookup_batch, (pa, st, i32(1), False, skip)))
                for b in RANGE_BUCKETS:
                    for fn in (range_many, aggregate_many):
                        progs.append((fn, (pa, st, i32(b), i32(b), i32())))
                progs.append((range_query, (pa, st, i32(), i32())))
                if skip:
                    progs.append((level_probe_stats,
                                  (pa, st, i32(PROBE_SAMPLE))))
        compile_programs(progs)

    def _param_sets(self) -> list:
        """Every static parameter set the engine can dispatch under: the
        configured one, or each preset allocation of the adaptive tuner
        (an allocation is a jit-static argument)."""
        if self.tuner.enabled:
            return [alloc.apply(self.p)
                    for alloc in self.tuner.presets.values()]
        return [self.p]

    # -- read path ----------------------------------------------------------
    def _on_reads(self, qs: np.ndarray) -> None:
        """Feed the tuner's workload signal: count the reads, stash the
        batch for write-boundary probe telemetry, and roll the
        controller (scheduler.on_read — decision-only; retunes and
        merges bind at the next write chunk or at drain(), so a lookup
        never absorbs maintenance work). Inert under static tuning."""
        self.stats["reads"] += qs.size
        t = self.tuner
        if not t.enabled:
            return
        t.note_reads(qs.size)
        t.last_queries = qs[:PROBE_SAMPLE].copy()
        self.scheduler.on_read()

    def lookup(self, keys, sparse: bool = False):
        """Point lookups (paper 2.7): newest-to-oldest across stage, memory
        runs, then Bloom/fence-gated disk levels. Compiles one program per
        distinct query-array shape — prefer `lookup_many` for mixed sizes."""
        qs_np = np.asarray(keys, np.int32).reshape(-1)
        reject_reserved(qs_np, op="lookup")
        self._on_reads(qs_np)
        qs = jnp.asarray(qs_np)
        vals, found = lookup_batch(self.p_active, self.state, qs, sparse,
                                   self.tuner.enabled)
        return host_read(vals, self.stats), host_read(found, self.stats)

    def lookup_many(self, keys, sparse: bool = False):
        """Batched multi-key fast path: all Q lookups in ONE device
        dispatch — a single fused Bloom-probe + fence-search pass per
        structure (paper 2.3/2.4) instead of one dispatch per query.
        Queries are padded to a power-of-two bucket so arbitrary Q reuses
        O(log Q) compiled programs. Same results as `lookup`. Runs in a
        ``slsm.lookup_many`` span, the answers' copy to the host in
        ``slsm.fetch``."""
        with jax.profiler.TraceAnnotation("slsm.lookup_many"):
            qs = np.asarray(keys, np.int32).reshape(-1)
            reject_reserved(qs, op="lookup_many")
            if qs.size == 0:
                return np.zeros(0, np.int32), np.zeros(0, bool)
            self._on_reads(qs)
            width = (adaptive_bucket(qs.size) if self.tuner.enabled
                     else bucket_pow2(qs.size))
            vals, found = lookup_many(self.p_active, self.state,
                                      jnp.asarray(pad_to(qs, width)),
                                      jnp.int32(qs.size), sparse,
                                      self.tuner.enabled)
            with jax.profiler.TraceAnnotation("slsm.fetch"):
                return (host_read(vals, self.stats)[:qs.size],
                        host_read(found, self.stats)[:qs.size])

    def range_device(self, lo: int, hi: int):
        """Device-resident range query [lo, hi) (paper 2.9): one jitted
        dispatch of the fence-pruned scan engine (DESIGN.md §10), no
        host round-trip. Returns jax arrays ``(keys (max_range,), vals,
        count, truncated)`` — rows KEY_EMPTY-padded past ``count`` —
        so latency-sensitive callers (the bench runner, `range_many`
        consumers) can chain or batch transfers instead of paying a
        per-scan sync."""
        return range_query(self.p_active, self.state, jnp.int32(lo),
                           jnp.int32(hi))

    def range(self, lo: int, hi: int, return_truncated: bool = False):
        """Range query [lo, hi) (paper 2.9): newest-wins, deleted keys
        (negative newest weight) dropped, key-sorted; truncated at
        `max_range` results. With
        `return_truncated`, also returns whether the result is only a
        prefix of the window (more than max_range live keys, or a
        `range_cand` budget overflow — the result is exact iff False).
        Convenience trim of `range_device` (this is where the one host
        sync happens)."""
        k, v, c, trunc = self.range_device(lo, hi)
        c = int(host_read(c, self.stats))
        out = host_read(k, self.stats)[:c], host_read(v, self.stats)[:c]
        return (out + (bool(host_read(trunc, self.stats)),)
                if return_truncated else out)

    def range_many(self, ranges):
        """Batched multi-scan fast path: all Q scans ``[(lo, hi), ...)``
        in ONE device dispatch of the fence-pruned scan engine — shared
        candidate gather, one fused merge-dedup pass (DESIGN.md §10) —
        instead of one dispatch (and one host sync) per scan. Scan
        counts are padded to the `RANGE_BUCKETS` grid so mixed batch
        sizes reuse a handful of compiled programs, mirroring
        `lookup_many`.

        Returns ``(keys (Q, max_range), vals, counts (Q,),
        truncated (Q,))`` as numpy arrays; row i holds ``counts[i]``
        key-sorted live pairs for window i (see `range` for the
        truncated-flag contract). Runs in a ``slsm.range_many`` span."""
        with jax.profiler.TraceAnnotation("slsm.range_many"):
            return range_many_host(
                lambda los, his, n: range_many(self.p_active, self.state,
                                               los, his, n),
                self.p.max_range, ranges, self.stats)

    def aggregate_many(self, ranges):
        """Batched windowed aggregates: ``count(lo, hi)`` and
        ``sum(lo, hi)`` over the live keys of each window ``[(lo, hi),
        ...)`` in ONE device dispatch (DESIGN.md §13). Rides the same
        fence-pruned candidate gather as `range_many` but reduces the
        merged survivor mask on-device instead of materializing rows, so
        a window's aggregate is exact past `max_range` — only a
        `range_cand` candidate-budget overflow (reported per-row in
        `truncated`) can clip it.

        Returns ``(counts (Q,), sums (Q,), truncated (Q,))`` as numpy
        arrays; sums use the engine's int32 wraparound arithmetic."""
        r = np.asarray(ranges, np.int32).reshape(-1, 2)
        q = r.shape[0]
        if q == 0:
            return (np.zeros(0, np.int32), np.zeros(0, np.int32),
                    np.zeros(0, bool))
        width = range_bucket(q)
        los = np.zeros(width, np.int32)
        his = np.zeros(width, np.int32)
        los[:q], his[:q] = r[:, 0], r[:, 1]
        c, s, t = aggregate_many(self.p_active, self.state,
                                 jnp.asarray(los), jnp.asarray(his),
                                 jnp.int32(q))
        return (host_read(c, self.stats)[:q], host_read(s, self.stats)[:q],
                host_read(t, self.stats)[:q])

    def count(self, lo: int, hi: int) -> int:
        """Live-key count over [lo, hi) (exact; one-window
        `aggregate_many`)."""
        c, _, _ = self.aggregate_many([(lo, hi)])
        return int(c[0])

    def sum(self, lo: int, hi: int) -> int:
        """Sum of live values over [lo, hi) (int32 wraparound; one-window
        `aggregate_many`)."""
        _, s, _ = self.aggregate_many([(lo, hi)])
        return int(s[0])

    # -- mixed-op tape (repro.engine.tape, DESIGN.md §11) -------------------
    def tape_write_capacity(self) -> int:
        """Max write keys the next `run_tape` call may carry, under the
        current occupancy: its headroom pass must be able to reserve one
        free run slot per in-scan seal the writes can force
        (`tape.tape_seal_bound`), and flushing can only push `run_count`
        down to ``run_count % runs_merged_eff``. Serving layers split
        windows that exceed this into multiple tapes."""
        p = self.p_active
        occ = self.scheduler.occupancy()
        rc, sc = occ.run_count, occ.stage_count
        # mirror ensure_stage_space(): pre-existing full stage seals first
        while sc >= p.Rn:
            if rc >= p.R:
                rc -= p.runs_merged_eff
            rc += 1
            sc -= p.Rn
        free = p.R - rc % p.runs_merged_eff
        return (free + 1) * p.Rn - 1 - sc

    def run_tape(self, chunks, sparse: bool = False):
        """Execute a coalesced mixed-op window as ONE device dispatch.

        `chunks` is a stream-ordered sequence of `tape.TapeChunk`s (or
        ``(kind, keys, vals)`` tuples): ``write`` chunks stage weighted
        records — `wts` lanes of +1 (insert) or -1 (delete), all +1 when
        omitted — ``lookup`` chunks carry point queries, ``range``
        chunks carry (lo, hi) window bounds. The
        whole window lowers to one `lax.scan` over tagged slots
        (`tape.tape_exec`), so a mixed stream pays one host->device
        launch and one device->host sync instead of one per op — the
        serving layer's steady-state data plane (DESIGN.md §11).

        Results are per-chunk, in order: writes -> in-scan seal count,
        lookups -> ``(vals, found)``, ranges -> ``(keys, vals, counts,
        truncated)`` — numpy, trimmed to each chunk's op count, and
        identical to what the per-op driver calls would have returned
        (same `_impl` ops in the same stream order; maintenance timing
        never changes read results — DESIGN.md §8).

        Headroom precondition (handled here, before each dispatch): the
        staging buffer absorbs every write slot and a free run slot
        exists for every seal the tape can trigger
        (`scheduler.ensure_stage_space` / `reserve_run_slots`). Windows
        whose writes exceed `tape_write_capacity` are segmented into
        multiple tapes at write boundaries (splitting a write chunk is
        stream-order-neutral), so steady-state serving usually stays at
        one dispatch per window and never fails on a heavy one.
        Flush/spill/compact/retune stay host steps *between* tapes (the
        maintenance governor's job), never inside one.
        """
        chunks = [c if isinstance(c, TP.TapeChunk) else TP.TapeChunk(*c)
                  for c in chunks]
        if not chunks:
            return []
        n_writes = n_reads = 0
        last_reads = None
        for ch in chunks:
            k = np.asarray(ch.keys, np.int32).reshape(-1)
            if ch.kind == "write":
                reject_reserved(k, op="tape write")
                n_writes += k.size
            elif ch.kind == "lookup":
                reject_reserved(k, op="tape lookup")
                n_reads += k.size
                last_reads = k
            elif ch.kind != "range":
                raise ValueError(f"unknown tape chunk kind {ch.kind!r}")
        if n_writes:
            self._guard_writes()
        # durability: one WAL record per write chunk (stream order is
        # preserved; segmentation below never reorders writes), group-
        # committed before this call returns — the serving layer stamps
        # replies only after run_tape returns, so every acked window is
        # durable (log-before-ack, DESIGN.md §12)
        log = self.durability is not None and not self._replaying
        if log:
            for ch in chunks:
                if ch.kind == "write":
                    k = np.asarray(ch.keys, np.int32).reshape(-1)
                    if k.size:
                        w = (np.ones_like(k) if ch.wts is None
                             else np.asarray(ch.wts, np.int32).reshape(-1))
                        self.durability.log_write(
                            k, np.asarray(ch.vals, np.int32).reshape(-1), w)
        results = [0] * len(chunks)
        # stream-ordered work list of (original chunk index, chunk);
        # oversized writes split across segments under the same index
        work = list(enumerate(chunks))
        while work:
            self.scheduler.ensure_stage_space()
            budget = self.tape_write_capacity()
            seg, seg_idx = [], []
            while work:
                i, ch = work[0]
                if ch.kind == "write":
                    k = np.asarray(ch.keys, np.int32).reshape(-1)
                    v = np.asarray(ch.vals, np.int32).reshape(-1)
                    w = (np.ones_like(k) if ch.wts is None
                         else np.asarray(ch.wts, np.int32).reshape(-1))
                    if budget <= 0:
                        break
                    if k.size > budget:
                        seg.append(TP.TapeChunk("write", k[:budget],
                                                v[:budget], w[:budget]))
                        seg_idx.append(i)
                        work[0] = (i, TP.TapeChunk("write", k[budget:],
                                                   v[budget:], w[budget:]))
                        budget = 0
                        continue
                    budget -= k.size
                seg.append(ch)
                seg_idx.append(i)
                work.pop(0)
            assert seg, "tape segmentation made no progress"
            seals = TP.tape_seal_bound(
                self.p_active, self.scheduler.occupancy().stage_count, seg)
            if seals:
                self.scheduler.reserve_run_slots(seals)
            ops, keys, vals, wts, nv = TP.build_tape(self.p_active, seg)
            self.state, ys = TP.tape_exec(
                self.p_active, self.state, jnp.asarray(ops),
                jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(wts),
                jnp.asarray(nv), sparse, self.tuner.enabled)
            for i, res in zip(seg_idx, TP.unpack_tape(self.p_active, seg, ys)):
                if chunks[i].kind == "write":
                    results[i] += res
                    self.stats["seals"] += res
                else:
                    results[i] = res
        self.stats["writes"] += n_writes
        self.stats["reads"] += n_reads
        if n_writes:
            self.tuner.note_writes(n_writes)
        if n_reads:
            self.tuner.note_reads(n_reads)
            if self.tuner.enabled and last_reads is not None:
                self.tuner.last_queries = last_reads[:PROBE_SAMPLE].copy()
        if log:
            self.durability.sync()
        return results

    def voluntary_steps(self, budget: int) -> int:
        """Roll the tuner's decision boundary, then run up to `budget`
        ready maintenance steps (scheduler.voluntary_steps; a decided
        RETUNE rides the backlog like any merge). The maintenance
        governor's uniform entry point (repro.serve) — identical
        signature on `ShardedSLSM` — for spending merge budget in idle
        gaps and at window boundaries instead of per insert chunk.
        Returns how many steps ran."""
        self.tuner.decide()
        return self.scheduler.voluntary_steps(budget)

    def warm_tape(self, buckets: tuple = TAPE_BUCKETS) -> None:
        """Precompile the mixed-op tape interpreter grid: one program
        per (allocation x levels-structure x slot bucket), like `warm`'s
        read grid — after this, steady-state serving windows never JIT
        (`run_tape` only ever dispatches these shapes). Call alongside
        `warm()` before latency-sensitive serving."""
        skip = self.tuner.enabled
        progs = []
        for pa in self._param_sets():
            for n_levels in range(self.p.max_levels + 1):
                st = state_shapes(pa, n_levels)
                for t in buckets:
                    progs.append((TP.tape_exec,
                                  (pa, st, i32(t), i32(t, pa.Rn),
                                   i32(t, pa.Rn), i32(t, pa.Rn), i32(t),
                                   False, skip)))
        compile_programs(progs)

    # -- tuner plumbing ----------------------------------------------------
    def sample_probe_stats(self) -> None:
        """Dispatch one per-level probe-telemetry pass over the most
        recent read batch (read_path.level_probe_stats). Called by the
        scheduler at write-chunk boundaries — alongside the maintenance
        work — so the instrumented dispatch never inflates a lookup's
        latency."""
        qs = self.tuner.last_queries
        if qs is None:
            return
        sample = np.full(PROBE_SAMPLE, KEY_EMPTY, np.int32)
        sample[:min(PROBE_SAMPLE, qs.size)] = qs[:PROBE_SAMPLE]
        c, h = level_probe_stats(self.p_active, self.state,
                                 jnp.asarray(sample))
        self.tuner.note_probe_stats(c, h)

    @property
    def policy_active(self):
        """Compaction policy under the current allocation: the configured
        policy, or the eager `ReadModePolicy` while the read-optimized
        allocation is active (fold structure down so the occupancy-masked
        read path probes less — DESIGN.md §9)."""
        if self.tuner.enabled and self.tuner.active == READ:
            return self._read_policy
        return self.policy

    def apply_retune(self) -> None:
        """The device half of a scheduler RETUNE step: swap the active
        parameter set to the tuner's target allocation and rebuild every
        resident Bloom filter under it in one jitted dispatch
        (tuner.retune_filters). Runs written afterwards pick up the new
        geometry at their own construction (levels.index_new_run). With
        durability on, the applied switch is WAL-logged and synced so a
        restored engine carries the same allocation trajectory (retunes
        are answer-invariant, so losing an unsynced one is harmless —
        DESIGN.md §9/§12)."""
        if self.durability is not None and not self._replaying:
            self.durability.log_retune(self.tuner.target)
        alloc = self.tuner.allocation(self.tuner.target)
        self.p_active = alloc.apply(self.p)
        self.state = retune_filters(self.p_active, self.state)
        self.tuner.applied()
        if self.durability is not None and not self._replaying:
            self.durability.sync()

    # -- durability (repro.engine.wal, DESIGN.md §12) -----------------------
    def _wal_meta(self) -> dict:
        """Engine fingerprint for the WAL's META record: enough to
        rebuild — and refuse to mix up — this engine configuration."""
        return {"driver": "slsm", "params": WAL.params_to_dict(self.p),
                "policy": _policy_kind(self.policy),
                "wal": WAL.WAL_FORMAT}

    def _snapshot_meta(self) -> dict:
        """Host-side state that rides a snapshot beside the pytree
        leaves: the engine fingerprint, the levels-structure depth the
        leaves were captured at, the tuner's controller position, and
        the stats counters at the watermark (replaying the WAL tail
        re-counts the rest, so restored totals match an uncrashed
        run)."""
        return {**self._wal_meta(), "n_levels": self.n_levels,
                "tuner": {"active": self.tuner.active,
                          "read_frac": float(self.tuner.read_frac)},
                "stats": {k: int(v) for k, v in self.stats.items()}}

    def snapshot(self):
        """Serialize the full device pytree (stage + runs + levels +
        filters, under the current allocation) as one atomic snapshot
        stamped with the WAL seqno watermark; restore() then only
        replays records past it. Returns the published directory.
        Requires a durability layer (the Governor triggers this in idle
        gaps — repro.serve)."""
        if self.durability is None:
            raise ValueError("snapshot() requires a durability layer: "
                             "construct with SLSM(..., durability=path)")
        return self.durability.snapshot(self)

    def _adopt_snapshot(self, leaves, meta: dict) -> None:
        """Install snapshot `leaves` as the live state pytree and adopt
        the host-side controller/stats position captured in `meta`.
        The physical geometry is params-determined (filters are sized at
        eps_floor — DESIGN.md §9), so a template built from the same
        params always matches the leaves' shapes."""
        template = init_state(self.p, int(meta["n_levels"]))
        treedef = jax.tree_util.tree_structure(template)
        self.state = jax.tree_util.tree_unflatten(
            treedef, [jnp.asarray(x) for x in leaves])
        for k, v in meta.get("stats", {}).items():
            self.stats[k] = int(v)
        t = meta.get("tuner")
        if t and self.tuner.enabled:
            name = t.get("active", self.tuner.active)
            self.tuner.active = self.tuner.target = name
            self.tuner.read_frac = float(t.get("read_frac",
                                               self.tuner.read_frac))
            self.p_active = self.tuner.allocation(name).apply(self.p)

    def _replay(self, records) -> None:
        """Re-apply a WAL tail through the existing chunk-apply programs
        (_insert / apply_retune) with re-logging suppressed. Replay is
        answer-exact, not bitwise-state-exact: maintenance may pace
        differently than the crashed run, but reads are exact at every
        point between merge steps (DESIGN.md §8), so every lookup/range
        afterwards matches an uncrashed engine fed the same records."""
        self._replaying = True
        try:
            n = 0
            for rec in records:
                if rec.kind in WAL.WRITE_KINDS:
                    k, v, w = WAL.decode_write(rec.payload, rec.kind)
                    self._insert(k, v, w)
                elif rec.kind == WAL.REC_RETUNE:
                    if self.tuner.enabled:
                        self.tuner.target = rec.payload.decode()
                        if self.tuner.pending:
                            self.apply_retune()
                            self.stats["retunes"] += 1
                else:
                    continue
                n += 1
            self.stats["replayed_records"] += n
        finally:
            self._replaying = False

    @classmethod
    def restore(cls, path, params: SLSMParams | None = None,
                policy: CompactionPolicy | None = None, durability=None):
        """Recover an engine from a durability directory: load the
        newest snapshot that passes verification (none is fine — replay
        then starts from genesis), replay every WAL record past its
        watermark, and return the live engine. A torn final WAL record
        is dropped cleanly (CRC framing rejects it as a unit — no
        partial apply). `params`/`policy` default to the fingerprint
        recorded in the snapshot/WAL META. Restore wall time and replay
        size are reported in ``stats()`` as ``restore_us`` /
        ``replayed_records``."""
        t0 = time.perf_counter()
        dur = WAL.as_durability(durability if durability is not None
                                else path)
        # decode the durable prefix BEFORE any writer truncates the tail
        records = dur.read_records()
        header = next((json.loads(r.payload.decode()) for r in records
                       if r.kind == WAL.REC_META), None)
        snap = WAL.load_latest_snapshot(dur.dir)
        meta = snap[2] if snap is not None else header
        if meta is None and params is None:
            raise ValueError(f"nothing to restore in {dur.dir}: no valid "
                             "snapshot and no readable WAL header")
        if params is None:
            params = WAL.params_from_dict(meta["params"])
        if policy is None and meta is not None:
            policy = _POLICY_KINDS.get(meta.get("policy", "tiering"),
                                       TieringPolicy)()
        drv = cls(params, policy, durability=dur)
        watermark = -1
        if snap is not None:
            num, leaves, smeta = snap
            drv._adopt_snapshot(leaves, smeta)
            watermark = num
        drv._replay([r for r in records if r.seqno > watermark])
        drv.stats["restore_us"] += int((time.perf_counter() - t0) * 1e6)
        return drv

    @classmethod
    def open_replica(cls, path, *, fsync: bool = False):
        """Open a replication follower over a bootstrapped directory
        (DESIGN.md §14): a plain `restore` of the leader's shipped
        snapshot + WAL tail, but with a *replica-mode* durability layer
        — the log is a verbatim copy of the leader's stream (extended
        only by ``Durability.append_frame``), so no local META record
        is ever injected into it. The returned engine is what
        `repro.engine.replication.Follower` drives."""
        return cls.restore(path, durability=WAL.Durability(
            path, fsync=fsync, replica=True))

    def apply_replicated(self, records) -> int:
        """Apply decoded leader WAL records through the same chunk-apply
        programs `restore` replays with (re-logging suppressed — the
        follower's durability layer appended the raw frames verbatim
        before this is called). Returns the records applied; the
        cumulative count rides ``stats['replayed_records']``."""
        before = self.stats["replayed_records"]
        self._replay(records)
        return self.stats["replayed_records"] - before

    def promote(self) -> "SLSM":
        """Failover: turn this replica into a writable leader. Bumps
        the WAL epoch (so stale pre-failover bytes the reused file may
        expose later are rejected by the prefix rule) and re-enables
        local logging; seqnos resume after the last applied record.
        Returns self. The transport-level half (dropping unacked
        buffered frames) lives in ``replication.Follower.promote``,
        which calls this."""
        if self.durability is None:
            raise ValueError("promote() requires a durability layer")
        self.durability.writer.bump_epoch()
        self.durability.replica = False
        self.fenced = False
        self.stats["promotions"] += 1
        return self

    def demote(self) -> "SLSM":
        """Fence this engine against writes (the deposed-leader exit,
        DESIGN.md §15): a leader that learned — via an ack at a higher
        epoch — that an automatic failover superseded it must stop
        accepting writes *immediately*, even mid-partition. Reads stay
        served (stale until rejoin); every write raises until a future
        `promote()`. Returns self."""
        self.fenced = True
        self.stats["demotions"] += 1
        return self

    # -- stats ----------------------------------------------------------------
    @property
    def n_live(self) -> int:
        """Resident elements across stage + memory runs + disk levels
        (duplicates and negative-weight delete records count until a
        merge annihilates them)."""
        n = int(self.state.stage_count) + int(self.state.buf_counts.sum())
        for lv in self.state.levels:
            n += int(lv.counts.sum())
        return n

    @property
    def n_levels(self) -> int:
        """Disk levels materialized so far (paper 2.4; grown lazily up to
        `max_levels`)."""
        return len(self.state.levels)
