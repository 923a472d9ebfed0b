#!/usr/bin/env python3
"""Chip smoke: the sLSM store's main path, end to end, on one TPU chip.

    python chip_smoke.py [--seed N]

Run from the root of a checkout on a machine whose first JAX device is a
TPU. Everything happens in this one process (a chip serves one process
at a time). Phases, in order:

  build   — `SLSM` at the one-chip paper geometry
            (`repro.configs.slsm_paper.one_chip_params`) with a
            group-commit WAL (`wal.Durability`, fsync on) in a temporary
            directory;
  warm    — `warm()` + `warm_tape()`: the maintenance, read and tape
            program grids compiled ahead of time (set-up time, not a
            speed claim; JAX's persistent compilation cache is placed
            by `repro.compile_cache.enable`);
  load    — 20,000,000 distinct keys from --seed in bulk `insert` calls,
            then overwrites and deletes of known subsets: enough for
            every maintenance step kind, one deepest-level compaction
            included;
  read    — `lookup_many` over present, overwritten, deleted and absent
            keys, `range_many` and `aggregate_many` over 32 windows
            (`truncated` checked), all against a plain-numpy
            last-write-wins table that shares no code with the engine;
  serve   — a few hundred mixed requests from several clients through
            `repro.serve.Server` (coalesced windows over the same tree),
            every reply checked against the table in submission order;
  idle    — the server's first empty pump: the governor's idle steps and
            the snapshot of the whole state the grown WAL calls for,
            then a few served writes past the snapshot's watermark;
  restore — the old engine's state freed, `SLSM.restore` from the
            durability directory (snapshot plus the replayed WAL tail),
            lookups and windows checked against the table again.

One line per phase goes to stdout, then the geometry with its cuts.
The last line is ``{"ok": true, "device": {...}}``; any failure raises
(non-zero exit) before it. Without a TPU the script exits 2 before
touching data and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

N_RECORDS = 20_000_000
N_OVERWRITE = 1_000_000
N_DELETE = 1_000_000
INSERT_BATCH = 1_000_000     # records per bulk insert call (one WAL record)
LOOKUP_BATCH = 4096          # lookup_many lanes per call (a warmed bucket)
N_LOOKUP_BATCHES = 25        # 102,400 point lookups
N_WINDOWS = 32               # range/aggregate windows (a warmed bucket)
N_WIDE = 4                   # of which this many overflow on purpose
SERVE_CLIENTS = 8
SERVE_ROUNDS = 40            # 320 requests
I32_MAX = np.iinfo(np.int32).max


class Mismatch(AssertionError):
    """An answer or a count disagreed with the reference."""


def check(ok, *what) -> None:
    """Raise `Mismatch` unless `ok` (kept under ``python -O``, unlike
    ``assert``)."""
    if not ok:
        raise Mismatch(*what)


class Reference:
    """Last-write-wins key -> value table in plain numpy.

    The bulk-loaded keys live in sorted arrays with a liveness mask;
    keys first written later go to a small dict. Shares no code with the
    engine, so agreement is evidence, not tautology."""

    def __init__(self, keys: np.ndarray, vals: np.ndarray):
        order = np.argsort(keys, kind="stable")
        self.keys = keys[order]
        self.vals = vals[order]
        self.live = np.ones(keys.size, bool)
        self.extra: dict[int, int | None] = {}   # key -> val, None = deleted

    def _base(self, ks: np.ndarray):
        i = np.searchsorted(self.keys, ks)
        ic = np.minimum(i, self.keys.size - 1)
        return ic, self.keys[ic] == ks

    def write(self, ks, vs, live: bool) -> None:
        """Apply writes in order (later lanes win over earlier ones)."""
        ks = np.asarray(ks, np.int32).reshape(-1)
        vs = np.asarray(vs, np.int32).reshape(-1)
        pos, hit = self._base(ks)
        for k, v, i, h in zip(ks.tolist(), vs.tolist(), pos.tolist(),
                              hit.tolist()):
            if h:
                self.vals[i], self.live[i] = (v if live else 0), live
            else:
                self.extra[k] = v if live else None

    def write_bulk(self, ks: np.ndarray, vs: np.ndarray, live: bool) -> None:
        """Vectorized `write` for distinct keys that are all bulk-loaded."""
        pos, hit = self._base(ks)
        check(hit.all() and np.unique(ks).size == ks.size, "bulk write keys")
        self.vals[pos] = vs if live else 0
        self.live[pos] = live

    def lookup(self, qs):
        qs = np.asarray(qs, np.int32).reshape(-1)
        pos, hit = self._base(qs)
        found = hit & self.live[pos]
        vals = np.where(found, self.vals[pos], 0).astype(np.int32)
        for j, q in enumerate(qs.tolist()):
            if q in self.extra:
                v = self.extra[q]
                found[j], vals[j] = v is not None, (0 if v is None else v)
        return vals, found

    def range(self, lo: int, hi: int):
        """Live (keys, vals) in [lo, hi), key-sorted."""
        a, b = np.searchsorted(self.keys, [lo, hi])
        m = self.live[a:b]
        ks, vs = self.keys[a:b][m], self.vals[a:b][m]
        more = sorted((k, v) for k, v in self.extra.items()
                      if lo <= k < hi and v is not None)
        if more:
            ks = np.concatenate([ks, np.asarray([k for k, _ in more],
                                                np.int32)])
            vs = np.concatenate([vs, np.asarray([v for _, v in more],
                                                np.int32)])
            o = np.argsort(ks, kind="stable")
            ks, vs = ks[o], vs[o]
        return ks, vs


def int32_sum(vs: np.ndarray) -> int:
    """Sum with the engine's int32 wraparound."""
    return int(np.asarray(vs, np.int64).sum().astype(np.int32))


def check_ranges(ref: Reference, windows, keys, vals, counts, truncated,
                 what: str) -> int:
    """Compare batched range rows with the table; returns the number of
    truncated rows. A truncated row must be a sorted prefix of the
    window's live keys; an untruncated one must be all of it."""
    n_trunc = 0
    for i, (lo, hi) in enumerate(windows):
        rk, rv = ref.range(lo, hi)
        c = int(counts[i])
        if truncated[i]:
            n_trunc += 1
            check(c <= rk.size, what, i, c, rk.size)
        else:
            check(c == rk.size, what, i, c, rk.size)
        np.testing.assert_array_equal(keys[i][:c], rk[:c], err_msg=what)
        np.testing.assert_array_equal(vals[i][:c], rv[:c], err_msg=what)
    return n_trunc


class Compiles:
    """Tally of XLA compiles (count, seconds, by jitted function) from
    jax.monitoring's backend-compile events: after `warm()` the engine's
    own programs should not appear, and each phase line says so."""

    def __init__(self):
        self.n, self.secs, self.names = 0, 0.0, set()

    def listen(self, event, duration_secs, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += duration_secs
            self.names.add(kw.get("fun_name", "?"))

    def take(self) -> str:
        out = (f"compiles {self.n} ({self.secs:.3f} s"
               + (f": {', '.join(sorted(self.names))})" if self.names
                  else ")"))
        self.n, self.secs, self.names = 0, 0.0, set()
        return out


def phase(name: str, t0: float, compiles: Compiles,
          detail: str = "") -> None:
    print(f"phase {name} {time.perf_counter() - t0:.3f} s | "
          f"{compiles.take()}" + (f" | {detail}" if detail else ""),
          flush=True)


def serve_round(srv, ref: Reference, rng, keys: np.ndarray, span: int,
                kinds=("insert", "delete", "lookup", "range"),
                p=(0.3, 0.1, 0.45, 0.15)):
    """One request per client through `srv` in one coalesced window;
    every reply is checked against `ref`, which then takes the round's
    writes in submission order. Returns (requests, ops, written keys)."""
    batch, touched = [], []
    for c in range(SERVE_CLIENTS):
        kind = str(rng.choice(kinds, p=p))
        if kind in ("insert", "delete"):
            n = int(rng.integers(1, 17))
            ks = np.where(rng.random(n) < 0.5,
                          keys[rng.integers(0, keys.size, n)],
                          keys[rng.integers(0, keys.size, n)] | 1)
            ks = np.unique(ks.astype(np.int32))
            vs = rng.integers(-2**31, 2**31 - 1, ks.size,
                              dtype=np.int64).astype(np.int32)
            touched.append(ks)
        elif kind == "lookup":
            n = int(rng.integers(16, 65))
            ks = keys[rng.integers(0, keys.size, n)]
            ks = np.where(rng.random(n) < 0.2, ks | 1, ks).astype(np.int32)
            vs = None
        else:
            n = int(rng.integers(1, 5))
            lo = (rng.choice(keys, n).astype(np.int64) - span // 2
                  ).clip(0, I32_MAX - 1 - span)
            ks, vs = lo.astype(np.int32), (lo + span).astype(np.int32)
        batch.append(srv.submit(f"client-{c}", kind, ks, vs))
    srv.pump(force=True)
    for t in batch:                           # submission order
        check(t.done and t.error is None, t.error)
        if t.kind == "insert":
            ref.write(t.keys, t.vals, live=True)
        elif t.kind == "delete":
            ref.write(t.keys, np.zeros_like(t.keys), live=False)
        elif t.kind == "lookup":
            gv, gf = t.result
            rv, rf = ref.lookup(t.keys)
            np.testing.assert_array_equal(gf, rf, err_msg="served")
            np.testing.assert_array_equal(gv, rv, err_msg="served")
        else:
            k_, v_, c_, tr_ = t.result
            check_ranges(ref, list(zip(t.keys.tolist(), t.vals.tolist())),
                         k_, v_, c_, tr_, "served range")
    return len(batch), sum(t.n_ops for t in batch), touched


def check_lookups(store, ref: Reference, qs: np.ndarray, what: str) -> int:
    """`lookup_many` over `qs` in warmed-bucket batches (the tail batch
    padded by repetition) against `ref`; returns how many were found."""
    n_found = 0
    for off in range(0, qs.size, LOOKUP_BATCH):
        part = qs[off:off + LOOKUP_BATCH]
        batch = np.resize(part, LOOKUP_BATCH)
        gv, gf = store.lookup_many(batch)
        rv, rf = ref.lookup(batch)
        np.testing.assert_array_equal(gf, rf, err_msg=what + " found")
        np.testing.assert_array_equal(gv, rv, err_msg=what + " vals")
        n_found += int(gf[:part.size].sum())
    return n_found


def run(params, seed: int, wal_dir: str) -> None:
    """Drive every phase at `params` with its durability directory in
    `wal_dir`; raises on any mismatch."""
    import jax

    from repro.engine import SLSM
    from repro.engine import wal as WAL
    from repro.serve import Server

    compiles = Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles.listen)
    rng = np.random.default_rng(seed)

    # -- build --------------------------------------------------------------
    t0 = time.perf_counter()
    store = SLSM(params, durability=WAL.Durability(wal_dir))
    phase("build", t0, compiles, f"wal {wal_dir}")

    # -- warm (set-up: compile time) ---------------------------------------
    t0 = time.perf_counter()
    store.warm(buckets=(LOOKUP_BATCH,))
    store.warm_tape()
    phase("warm", t0, compiles, "ahead-of-time compile of the "
          "maintenance, read and tape grids: set-up time")

    # -- load ---------------------------------------------------------------
    t0 = time.perf_counter()
    # distinct even keys: key|1 is then a guaranteed-absent probe, and
    # every key stays below the reserved KEY_EMPTY (INT32_MAX)
    keys = (rng.choice((I32_MAX - 3) // 2, size=N_RECORDS, replace=False)
            * 2).astype(np.int32)
    vals = rng.integers(-2**31, 2**31 - 1, N_RECORDS, dtype=np.int64
                        ).astype(np.int32)
    ref = Reference(keys, vals)
    t_gen = time.perf_counter() - t0
    for off in range(0, N_RECORDS, INSERT_BATCH):
        store.insert(keys[off:off + INSERT_BATCH],
                     vals[off:off + INSERT_BATCH])
    pick = rng.permutation(N_RECORDS)
    ow = keys[pick[:N_OVERWRITE]]
    ow_vals = rng.integers(-2**31, 2**31 - 1, ow.size, dtype=np.int64
                           ).astype(np.int32)
    dl = keys[pick[N_OVERWRITE:N_OVERWRITE + N_DELETE]]
    for off in range(0, ow.size, INSERT_BATCH):
        store.insert(ow[off:off + INSERT_BATCH],
                     ow_vals[off:off + INSERT_BATCH])
    for off in range(0, dl.size, INSERT_BATCH):
        store.delete(dl[off:off + INSERT_BATCH])
    t_ref = time.perf_counter()
    ref.write_bulk(ow, ow_vals, live=True)
    ref.write_bulk(dl, np.zeros_like(dl), live=False)
    t_gen += time.perf_counter() - t_ref
    st = store.stats
    n_written = N_RECORDS + ow.size + dl.size
    check(st["writes"] == n_written, st["writes"], n_written)
    for kind in ("seals", "flushes", "spills", "compactions"):
        check(st[kind] >= 1, kind, dict(st))
    phase("load", t0, compiles,
          f"{n_written} records ({N_RECORDS} inserts, {ow.size} "
          f"overwrites, {dl.size} deletes; of the phase, data and "
          f"reference {t_gen:.3f} s) seals={st['seals']} "
          f"flushes={st['flushes']} spills={st['spills']} "
          f"compactions={st['compactions']} "
          f"levels={store.n_levels} resident={store.n_live}")

    # -- read ---------------------------------------------------------------
    t0 = time.perf_counter()
    untouched = keys[pick[N_OVERWRITE + N_DELETE:]]

    def mixed_queries(q: int) -> np.ndarray:
        """Present, overwritten, deleted and absent (key|1) keys."""
        parts = [untouched[rng.integers(0, untouched.size, q // 2)],
                 ow[rng.integers(0, ow.size, q // 8)],
                 dl[rng.integers(0, dl.size, q // 8)]]
        absent = keys[rng.integers(0, N_RECORDS,
                                   q - sum(x.size for x in parts))] | 1
        return rng.permutation(np.concatenate(parts + [absent]))

    q = N_LOOKUP_BATCHES * LOOKUP_BATCH
    n_found = check_lookups(store, ref, mixed_queries(q), "lookup")
    # narrow windows hold ~100 live keys, inside the range_cand budget;
    # the wide ones overflow it (and max_range) on purpose
    span = int((I32_MAX // N_RECORDS) * 100)
    los = rng.choice(keys, N_WINDOWS).astype(np.int64) - span // 2
    widths = np.full(N_WINDOWS, span, np.int64)
    widths[:N_WIDE] = span * 1000
    windows = [(int(max(lo, 0)), int(min(lo + w, I32_MAX - 1)))
               for lo, w in zip(los, widths)]

    def check_windows(store, what: str):
        rk, rv, rc, rt = store.range_many(windows)
        n_trunc = check_ranges(ref, windows, rk, rv, rc, rt, what)
        check(not rt[N_WIDE:].any() and rt[:N_WIDE].all(), what, rt)
        ac, asum, at = store.aggregate_many(windows)
        np.testing.assert_array_equal(at, rt, err_msg=what + " truncated")
        for i, (lo, hi) in enumerate(windows):
            k_ref, v_ref = ref.range(lo, hi)
            c = int(ac[i])
            check(c == k_ref.size or (at[i] and c < k_ref.size), what, i, c)
            check(int(asum[i]) == int32_sum(v_ref[:c]), what, "sum", i)
        return n_trunc, int(rc.sum())

    n_trunc, n_rows = check_windows(store, "range_many")
    phase("read", t0, compiles,
          f"{q} lookups ({n_found} found), {N_WINDOWS} range + "
          f"{N_WINDOWS} aggregate windows ({n_trunc} truncated, "
          f"{n_rows} rows), all equal to the reference")

    # -- serve ----------------------------------------------------------------
    t0 = time.perf_counter()
    srv = Server(store)
    n_req = n_ops = 0
    touched = []
    for _ in range(SERVE_ROUNDS):
        r, o, w = serve_round(srv, ref, rng, keys, span)
        n_req, n_ops = n_req + r, n_ops + o
        touched += w
    srv.drain()
    tk = np.unique(np.concatenate(touched))
    check_lookups(store, ref, tk, "after drain")
    s = srv.stats()
    phase("serve", t0, compiles,
          f"{n_req} requests ({n_ops} ops) from {SERVE_CLIENTS} clients "
          f"in {s['counters']['windows']} windows, "
          f"{s['counters']['dispatches']} tape dispatches, "
          f"wal syncs {s['durability']['wal_syncs']}; every reply and "
          f"{tk.size} written keys after drain equal to the reference")

    # -- idle: the server's first idle gap snapshots the grown WAL ---------
    t0 = time.perf_counter()
    gov = srv.governor
    check(srv.pump() == 0, "idle pump served requests")
    check(gov.snapshots_run == 1, "no snapshot in the idle gap",
          gov.snapshots_run)
    (_, snap_dir), = WAL.list_snapshots(wal_dir)
    snap_bytes = sum(f.stat().st_size for f in snap_dir.iterdir())
    snap_s = store.durability.last_snapshot_ms / 1e3
    # writes past the snapshot's watermark: the WAL tail restore replays
    for _ in range(2):
        _, _, w = serve_round(srv, ref, rng, keys, span,
                              kinds=("insert", "delete"), p=(0.75, 0.25))
        touched += w
    srv.drain()
    phase("idle", t0, compiles,
          f"snapshot {snap_bytes} bytes in {snap_s:.3f} s, idle steps "
          f"{gov.idle_steps_run}, then {2 * SERVE_CLIENTS} write requests "
          f"past its watermark")

    # -- restore: the engine rebuilt from its durability directory --------
    t0 = time.perf_counter()
    store.durability.close()
    old = jax.tree_util.tree_leaves(store.state)
    del srv, store
    for x in old:       # a restart frees the old state; do it eagerly
        x.delete()
    del old
    store = SLSM.restore(wal_dir)
    st = store.stats
    check(st["replayed_records"] >= 1, "no WAL tail replayed", dict(st))
    check(st["writes"] == n_written + sum(w.size for w in touched),
          "restored writes", st["writes"])
    n_found = check_lookups(store, ref, np.concatenate(
        [mixed_queries(4 * LOOKUP_BATCH), np.unique(np.concatenate(
            touched))]), "restored lookup")
    n_trunc, n_rows = check_windows(store, "restored range_many")
    phase("restore", t0, compiles,
          f"restore {st['restore_us'] / 1e6:.3f} s, "
          f"{st['replayed_records']} WAL records replayed; lookups "
          f"({n_found} found) and {N_WINDOWS} range + {N_WINDOWS} "
          f"aggregate windows ({n_trunc} truncated, {n_rows} rows) "
          f"equal to the reference")
    store.durability.close()
    jax.monitoring.unregister_event_duration_listener(compiles.listen)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated data and requests")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        print(f"chip_smoke: refusing to run: JAX's first device is "
              f"{d0.platform!r} ({d0.device_kind}), not a TPU",
              file=sys.stderr)
        return 2
    print(f"device {d0.platform} {d0.device_kind} count={len(devices)}",
          flush=True)

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro import compile_cache
    from repro.configs.slsm_paper import ONE_CHIP_REDUCED, one_chip_params

    print(f"compile cache {compile_cache.enable()}", flush=True)
    params = one_chip_params()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_wal_") as wal_dir:
        run(params, args.seed, wal_dir)
    mem = d0.memory_stats() or {}
    print(f"device memory peak {mem.get('peak_bytes_in_use', 'n/a')} of "
          f"{mem.get('bytes_limit', 'n/a')} bytes", flush=True)
    geometry = {k: getattr(params, k) for k in
                ("R", "Rn", "eps", "D", "m", "mu", "max_levels",
                 "merge_budget", "range_cand", "max_range", "backend")}
    print("geometry " + json.dumps({"params": geometry,
                                    "records": N_RECORDS,
                                    "reduced": ONE_CHIP_REDUCED}),
          flush=True)
    print(json.dumps({"ok": True,
                      "device": {"platform": d0.platform,
                                 "kind": d0.device_kind,
                                 "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
