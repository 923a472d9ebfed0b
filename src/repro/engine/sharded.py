"""Vmapped multi-shard sLSM: S independent trees in one fused pytree.

The many-tenant serving shape: S complete sLSM trees live in one stacked
state pytree (every leaf gains a leading shard axis), and every device
op is the single-tree `_impl` op vmapped over that axis — one dispatch
drives all shards. The key space is hash-partitioned (the same Murmur3
finalizer the Bloom filters use), so shards never share keys and their
results merge trivially.

Control flow stays on the host, as in the single-tree driver: the host
reads the (S,) occupancy vectors and applies each maintenance op under a
per-shard select mask — shards whose mask is off get their state back
unchanged (the vmapped op's output for them is computed and discarded;
with S trees in one fused dispatch that is the price of lockstep, and it
is exactly the work a busy fleet does anyway).

Maintenance is scheduled per shard through the same step model the
single-tree driver uses (repro.engine.scheduler): after every lockstep
insert round, each shard runs up to `merge_budget` voluntary steps —
per-shard step masks, deepest level first — then the forced chain covers
whatever the next round structurally requires. With merge_budget == 0
only the forced chain runs: the legacy lockstep deepest-first cascade,
unchanged.

Two deliberate simplifications vs the single-tree driver:
  * all `max_levels` tiers are preallocated at init so every shard
    shares one pytree structure (no per-shard lazy growth);
  * annihilated records (weight sums <= 0, DESIGN.md §13) are dropped
    only at deepest-level compaction — always legal (paper 2.5/2.8);
    the per-shard "is the target the deepest occupied level"
    refinement would make `drop_annihilated` a traced per-shard value
    inside ops that specialize on it statically.

Compaction is the paper's tiering policy. Lookups use the dense read
path (the sparse path's candidate compaction does not vmap); queries are
routed host-side to their owner shard, looked up in one vmapped
dispatch, and scattered back.
"""
from __future__ import annotations

import collections
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.params import KEY_EMPTY, SLSMParams
from repro.engine import compaction as CP
from repro.engine import memtable as MT
from repro.engine import read_path as RP
from repro.engine import scheduler as SCH
from repro.engine import tape as TP
from repro.engine import tuner as TU
from repro.engine import wal as WAL
from repro.engine.backend import get_backend
from repro.engine.batching import (RANGE_BUCKETS, TAPE_BUCKETS, bucket_pow2,
                                   range_bucket, range_many_host,
                                   tape_bucket)
from repro.engine.engine import reject_reserved

I32 = jnp.int32

_GOLDEN = np.uint32(0x9E3779B9)   # bloom.SEED1 — same hash family
_C1 = np.uint32(0x85EBCA6B)
_C2 = np.uint32(0xC2B2AE35)


def _fmix32_np(x: np.ndarray) -> np.ndarray:
    """numpy mirror of repro.core.bloom.fmix32 (host-side routing hash)."""
    x = x.astype(np.uint32)
    x ^= x >> 16
    x = x * _C1
    x ^= x >> 13
    x = x * _C2
    x ^= x >> 16
    return x


def shard_ids(keys, n_shards: int) -> np.ndarray:
    """Owner shard of each key: fmix32(key ^ SEED1) mod S."""
    u = np.asarray(keys, np.int32).reshape(-1).view(np.uint32)
    return (_fmix32_np(u ^ _GOLDEN) % np.uint32(n_shards)).astype(np.int64)


# --------------------------------------------------------------------------
# vmapped device ops with per-shard select masks
# --------------------------------------------------------------------------

def _select(mask: jax.Array, new, old):
    """Per-shard pytree select: leaf[s] = new[s] if mask[s] else old[s]."""
    def sel(a, b):
        m = mask.reshape(mask.shape + (1,) * (a.ndim - 1))
        return jnp.where(m, a, b)
    return jax.tree.map(sel, new, old)


@functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
def _stage_append_sharded(p: SLSMParams, state, keys, vals, wts, n_valid):
    return jax.vmap(
        lambda st, k, v, w, n: MT.stage_append_impl(p, st, k, v, w, n)
    )(state, keys, vals, wts, n_valid)


@functools.partial(jax.jit, static_argnums=0)
def _seal_where(p: SLSMParams, state, mask):
    sealed = jax.vmap(lambda st: MT.seal_run_impl(p, st))(state)
    return _select(mask, sealed, state)


@functools.partial(jax.jit, static_argnums=0)
def _flush_where(p: SLSMParams, state, mask):
    new = jax.vmap(
        lambda st: CP.merge_buffer_to_level0_impl(p, st, False))(state)
    return _select(mask, new, state)


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def _merge_level_down_where(p: SLSMParams, state, level: int, n_merge: int,
                            mask):
    new = jax.vmap(
        lambda st: CP.merge_level_down_impl(p, st, level, n_merge, False)
    )(state)
    return _select(mask, new, state)


@functools.partial(jax.jit, static_argnums=0)
def _compact_last_where(p: SLSMParams, state, mask):
    new, raw = jax.vmap(lambda st: CP.compact_last_level_impl(p, st))(state)
    return _select(mask, new, state), raw


@functools.partial(jax.jit, static_argnums=(0, 3))
def _lookup_sharded(p: SLSMParams, state, qs, skip_empty: bool = False):
    """qs (S, Q): each shard looks up its own row (dense path).
    `skip_empty` passes the adaptive read path's occupancy gate through;
    under vmap it lowers to a select (see read_path._skip_if_empty), so
    it is semantics- and cost-neutral here — accepted for driver parity."""
    return jax.vmap(
        lambda st, q: RP.lookup_batch_impl(p, st, q, sparse=False,
                                           skip_empty=skip_empty)
    )(state, qs)


@functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
def _retune_filters_sharded(p: SLSMParams, state):
    """Rebuild every shard's resident filters under `p`'s (new) effective
    allocation — the vmapped device half of a RETUNE (tuner.retune_filters)."""
    return jax.vmap(lambda st: TU.retune_filters_impl(p, st))(state)


@functools.partial(jax.jit, static_argnums=0)
def _range_sharded(p: SLSMParams, state, lo, hi):
    return jax.vmap(lambda st: RP.range_query_impl(p, st, lo, hi))(state)


def _merge_shard_ranges(p: SLSMParams, k, v, c, tr):
    """Fold per-shard batched-scan results into global rows, on device.

    Inputs are the (S, Q, max_range) result planes of
    `read_path.range_many_impl` vmapped over shards (disjoint key sets,
    each row key-sorted): one `lax.sort` per scan merges them without a
    host round-trip. Shared by `_range_many_sharded` and the sharded
    mixed-op tape's range branch, so the merge contract cannot diverge."""
    mr = p.max_range
    s_n, q_n = k.shape[0], k.shape[1]
    kq = jnp.moveaxis(k, 0, 1).reshape(q_n, s_n * mr)
    vq = jnp.moveaxis(v, 0, 1).reshape(q_n, s_n * mr)
    kq, vq = jax.lax.sort((kq, vq), num_keys=1)
    total = c.sum(axis=0)
    return (kq[:, :mr], vq[:, :mr], jnp.minimum(total, mr),
            tr.any(axis=0) | (total > mr))


@functools.partial(jax.jit, static_argnums=0)
def _range_many_sharded(p: SLSMParams, state, los, his, n_valid):
    """Q scans against all S shards in one dispatch, merged on device.

    Every shard answers the whole scan batch through the fence-pruned
    engine (`read_path.range_many_impl` vmapped over the shard axis);
    the per-shard result rows — key-sorted, disjoint key sets — are then
    combined per scan with a single on-device sort (`_merge_shard_ranges`),
    so the global result never round-trips through host numpy. Returns
    the same ``(keys (Q, max_range), vals, counts, truncated)`` contract
    as the single-tree batched path, with ``truncated[i]`` true when any
    shard truncated scan i or the combined live count exceeds max_range."""
    k, v, c, tr = jax.vmap(
        lambda st: RP.range_many_impl(p, st, los, his, n_valid))(state)
    return _merge_shard_ranges(p, k, v, c, tr)


@functools.partial(jax.jit, static_argnums=0)
def _aggregate_many_sharded(p: SLSMParams, state, los, his, n_valid):
    """Q windowed aggregates against all S shards in one dispatch:
    every shard reduces its own live rows (`read_path.aggregate_many_impl`
    vmapped over the shard axis) and the disjoint per-shard partials fold
    by int32 addition — counts and wraparound sums are both associative,
    so the global aggregate needs no row merge at all. ``truncated[i]``
    is true when any shard's candidate gather overflowed for window i."""
    c, s, t = jax.vmap(
        lambda st: RP.aggregate_many_impl(p, st, los, his, n_valid))(state)
    return c.sum(axis=0), s.sum(axis=0), t.any(axis=0)


@functools.partial(jax.jit, static_argnums=(0, 7), donate_argnums=1)
def _tape_exec_sharded(p: SLSMParams, state, opcodes, keys, vals, wts,
                       n_valid, skip_empty: bool = False):
    """Sharded mixed-op tape: one `lax.scan` over T tagged slots, every
    branch the single-tree tape's op vmapped over the shard axis.

    xs are ``opcodes (T,)`` (one op kind per slot — the stream is
    global), ``keys/vals/wts (T, S, Rn)`` and ``n_valid (T, S)``
    host-routed per shard. WRITE slots append per shard and seal in-scan under a
    per-shard mask (compute-both + `_select`, the same lockstep price
    every masked maintenance op pays); LOOKUP slots answer each shard's
    routed lanes; RANGE slots broadcast their (lo, hi) lanes to every
    shard and fold the disjoint rows with `_merge_shard_ranges`. Host
    headroom preconditions are per shard (`ShardedSLSM.run_tape`)."""
    rb = TP.range_lanes(p)
    mr = p.max_range
    s_n, width = keys.shape[1], keys.shape[2]

    def zeros():
        return (jnp.zeros((s_n, width), I32),        # lookup vals
                jnp.zeros((s_n, width), bool),       # lookup found
                jnp.full((rb, mr), KEY_EMPTY, I32),  # range keys (merged)
                jnp.zeros((rb, mr), I32),            # range vals
                jnp.zeros((rb,), I32),               # range counts
                jnp.zeros((rb,), bool),              # range truncated
                jnp.zeros((), I32))                  # seals this slot

    def nop(st, k, v, w, n):
        return st, zeros()

    def write(st, k, v, w, n):
        new = jax.vmap(
            lambda s_, k_, v_, w_, n_: MT.stage_append_impl(p, s_, k_, v_,
                                                            w_, n_)
        )(st, k, v, w, n)
        mask = new.stage_count >= p.Rn
        sealed = jax.vmap(lambda s_: MT.seal_run_impl(p, s_))(new)
        out = zeros()
        return (_select(mask, sealed, new),
                out[:6] + (mask.sum(dtype=I32),))

    def lookup(st, k, v, w, n):
        lv, lf = jax.vmap(
            lambda s_, k_, n_: RP.lookup_many_impl(p, s_, k_, n_, False,
                                                   skip_empty)
        )(st, k, n)
        out = zeros()
        return st, (lv, lf) + out[2:]

    def range_(st, k, v, w, n):
        los, his, nr = k[0, :rb], v[0, :rb], n[0]
        kk, vv, cc, tt = jax.vmap(
            lambda s_: RP.range_many_impl(p, s_, los, his, nr))(st)
        rk, rv, rc, rt = _merge_shard_ranges(p, kk, vv, cc, tt)
        out = zeros()
        return st, out[:2] + (rk, rv, rc, rt) + out[6:]

    def body(st, xs):
        op, k, v, w, n = xs
        return jax.lax.switch(jnp.clip(op, 0, 3),
                              [nop, write, lookup, range_], st, k, v, w, n)

    return jax.lax.scan(body, state,
                        (opcodes.astype(I32), keys.astype(I32),
                         vals.astype(I32), wts.astype(I32),
                         n_valid.astype(I32)))


# --------------------------------------------------------------------------
# host driver
# --------------------------------------------------------------------------

class ShardedSLSM:
    """S hash-partitioned sLSM trees in one fused, vmapped state pytree."""

    def __init__(self, params: SLSMParams | None = None, n_shards: int = 4,
                 durability=None):
        if n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        self.p = params or SLSMParams()
        get_backend(self.p.backend)
        self.S = n_shards
        self.policy = CP.TieringPolicy()   # the only policy that vmaps
        base = MT.init_state(self.p, n_levels=self.p.max_levels)
        self.state = jax.tree.map(lambda x: jnp.stack([x] * n_shards), base)
        # the tuner's active allocation applied to p (== p under static
        # tuning); one allocation governs the whole fleet — the stacked
        # pytree runs every shard through the same static program, so a
        # retune is a lockstep swap + one vmapped filter rebuild
        self.p_active = self.p
        self.tuner = TU.Tuner(self)
        # maintenance counters, summed over shards (bench trajectory);
        # backlog_peak = most pending steps observed on any ONE shard
        self.stats = collections.Counter(seals=0, flushes=0, spills=0,
                                         compactions=0, backlog_peak=0,
                                         retunes=0, reads=0, writes=0,
                                         rows_merged_in=0, rows_merged_out=0,
                                         rows_annihilated=0)
        # durability surface (DESIGN.md §12): write ops are logged at the
        # driver boundary BEFORE shard routing, so single-tree and
        # sharded engines fed the same stream produce byte-identical
        # WALs (modulo the META fingerprint) — the recovery-parity tests
        # lean on that
        self._replaying = False
        self.durability = WAL.as_durability(durability)
        if self.durability is not None:
            self.durability.ensure_header(self._wal_meta())
        # replication hook (DESIGN.md §14): a replication.Leader /
        # .Follower claims this; repro.serve pumps it between windows.
        # fenced (DESIGN.md §15) = a deposed leader: writes raise until
        # a future promote()
        self.replication = None
        self.fenced = False

    # -- write path -------------------------------------------------------
    def _guard_writes(self) -> None:
        """Reject writes into a read-only engine: a fenced (deposed)
        leader or a replica follower (DESIGN.md §15) —
        `SLSM._guard_writes`'s contract. Replay and `apply_replicated`
        bypass this via ``_replaying``."""
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
        """Batched insert (paper Algorithm 1/2, vmapped): bucket by owner
        shard, then feed all shards in lockstep Rn-chunks; each round ends
        with the per-shard scheduler pass (budgeted voluntary steps, then
        the forced chain)."""
        keys = np.asarray(keys, np.int32).reshape(-1)
        vals = np.asarray(vals, np.int32).reshape(-1)
        assert keys.shape == vals.shape
        reject_reserved(keys, vals, op="insert")
        self._insert(keys, vals, np.ones_like(keys))

    def _insert(self, keys: np.ndarray, vals: np.ndarray,
                wts: np.ndarray) -> None:
        """Post-validation weighted write path (delete() enters here with
        weight -1 records). With durability on, the whole op is
        WAL-logged pre-routing as one record and group-committed before
        returning (one fsync per driver call — SLSM._insert's contract,
        byte-identical records)."""
        if len(keys) == 0:
            return
        self._guard_writes()
        log = self.durability is not None and not self._replaying
        if log:
            self.durability.log_write(keys, vals, wts)
        self.stats["writes"] += len(keys)
        self.tuner.note_writes(len(keys))
        sid = shard_ids(keys, self.S)
        buckets = [(keys[sid == s], vals[sid == s], wts[sid == s])
                   for s in range(self.S)]
        rn = self.p.Rn
        rounds = max((len(bk) + rn - 1) // rn for bk, _, _ in buckets)
        for r in range(rounds):
            ck = np.full((self.S, rn), KEY_EMPTY, np.int32)
            cv = np.zeros((self.S, rn), np.int32)
            cw = np.zeros((self.S, rn), np.int32)
            n = np.zeros((self.S,), np.int32)
            for s, (bk, bv, bw) in enumerate(buckets):
                seg = bk[r * rn:(r + 1) * rn]
                n[s] = len(seg)
                ck[s, :len(seg)] = seg
                cv[s, :len(seg)] = bv[r * rn:(r + 1) * rn]
                cw[s, :len(seg)] = bw[r * rn:(r + 1) * rn]
            self.state = _stage_append_sharded(
                self.p_active, self.state, jnp.asarray(ck), jnp.asarray(cv),
                jnp.asarray(cw), jnp.asarray(n))
            self._maintain()
        if log:
            self.durability.sync()

    def delete(self, keys) -> None:
        """Weight -1 records (paper 2.8 tombstones as Z-set retractions —
        DESIGN.md §13); annihilated at deepest-level compaction
        (paper 2.5)."""
        keys = np.asarray(keys, np.int32).reshape(-1)
        reject_reserved(keys, op="delete")
        self._insert(keys, np.zeros_like(keys), np.full_like(keys, -1))

    # -- merge scheduling (per-shard step masks over the vmapped ops) ------
    def _occupancies(self) -> list:
        """Per-shard occupancy snapshots for the scheduler's step logic."""
        stage = np.asarray(self.state.stage_count)
        runs = np.asarray(self.state.run_count)
        per_level = [np.asarray(lv.n_runs) for lv in self.state.levels]
        return [SCH.Occupancy(int(stage[s]), int(runs[s]),
                              tuple(int(lr[s]) for lr in per_level))
                for s in range(self.S)]

    def _book_merge(self, rows_in: int, rows_out: int) -> None:
        """Z-set merge telemetry over the masked shards of one step
        (mirrors `MergeScheduler._book_merge` — DESIGN.md §13): the
        in/out gap is dedup + annihilation, rows whose payloads the
        Ghost gather never touched (4 bytes each)."""
        st = self.stats
        st["rows_merged_in"] += rows_in
        st["rows_merged_out"] += rows_out
        st["rows_annihilated"] += rows_in - rows_out

    def _apply_step(self, kind: str, level: int, mask: np.ndarray) -> None:
        """Run one step kind for every masked shard in a single vmapped
        dispatch; unmasked shards pass through unchanged."""
        p, jm = self.p_active, jnp.asarray(mask)
        idx = np.flatnonzero(mask)
        if kind == SCH.SEAL:
            self.state = _seal_where(p, self.state, jm)
            self.stats["seals"] += int(mask.sum())
        elif kind == SCH.FLUSH:
            mr = p.runs_merged_eff
            rows_in = int(np.asarray(
                self.state.buf_counts)[idx, :mr].sum())
            slots = np.asarray(self.state.levels[0].n_runs)[idx]
            self.state = _flush_where(p, self.state, jm)
            self._book_merge(rows_in, int(np.asarray(
                self.state.levels[0].counts)[idx, slots].sum()))
            self.stats["flushes"] += int(mask.sum())
        elif kind == SCH.SPILL:
            nm = p.disk_runs_merged
            rows_in = int(np.asarray(
                self.state.levels[level].counts)[idx, :nm].sum())
            slots = np.asarray(self.state.levels[level + 1].n_runs)[idx]
            self.state = _merge_level_down_where(
                p, self.state, level, nm, jm)
            self._book_merge(rows_in, int(np.asarray(
                self.state.levels[level + 1].counts)[idx, slots].sum()))
            self.stats["spills"] += int(mask.sum())
        else:   # COMPACT
            last = p.max_levels - 1
            rows_in = int(np.asarray(self.state.levels[last].counts)[idx].sum())
            new_state, raw = _compact_last_where(p, self.state, jm)
            raws = np.asarray(raw)[mask]
            cap = p.level_cap(last)
            if (raws > cap).any():
                # raise before committing: the compacted state silently
                # truncates the overflowing run (same order as engine.py)
                raise RuntimeError(
                    f"sLSM deepest level overflow ({int(raws.max())} > {cap} "
                    f"live elements in a shard): increase max_levels beyond "
                    f"{p.max_levels}")
            self.state = new_state
            self._book_merge(rows_in, int(raws.sum()))
            self.stats["compactions"] += int(mask.sum())

    def _step_masks(self, kind: str, level: int, occs) -> np.ndarray:
        """(pending, ready) per-shard masks for one step kind."""
        p, policy = self.p_active, self.policy
        pend = np.array([SCH.step_pending(kind, level, o, p, policy)
                         for o in occs], dtype=bool)
        ready = np.array([SCH.step_ready(kind, level, o, p, policy)
                          for o in occs], dtype=bool)
        return pend, pend & ready

    def _apply_retune(self) -> None:
        """Lockstep allocation switch: swap the fleet's active params and
        rebuild every shard's filters in one vmapped dispatch. A retune
        is a *global static swap* (the stacked pytree runs one program),
        so unlike merges it cannot be per-shard masked — it applies at
        the round boundary that decided it, whatever the pacing budget.
        With durability on the applied switch is WAL-logged and synced
        (SLSM.apply_retune's contract)."""
        t = self.tuner
        log = self.durability is not None and not self._replaying
        if log:
            self.durability.log_retune(t.target)
        self.p_active = t.allocation(t.target).apply(self.p)
        self.state = _retune_filters_sharded(self.p_active, self.state)
        t.applied()
        self.stats["retunes"] += 1
        if log:
            self.durability.sync()

    def _maintain(self) -> None:
        """Per-round scheduler pass: tuner decision (adaptive mode),
        backlog telemetry, budgeted voluntary steps (merge_budget > 0),
        then the forced chain."""
        self.tuner.decide()
        if self.tuner.pending:
            self._apply_retune()
        occs = self._occupancies()
        p, policy = self.p_active, self.policy
        peak = max(len(SCH.pending_steps(p, policy, o)) for o in occs)
        self.stats["backlog_peak"] = max(self.stats["backlog_peak"], peak)
        if p.merge_budget > 0:
            self._voluntary_pass()
        self._forced_pass()

    def _voluntary_pass(self) -> None:
        """Up to merge_budget steps per shard, deepest-first: each masked
        vmapped op advances every shard with that step pending, ready, and
        budget left. One occupancy snapshot per applied op (the snapshot
        is a device->host sync on the insert hot path); the backlog is
        re-derived after each op, the same fixpoint semantics as the
        single-tree pass. Termination: every iteration that runs an op
        spends at least one unit of a finite budget."""
        budget = np.full(self.S, self.p_active.merge_budget, np.int64)
        while (budget > 0).any():
            occs = self._occupancies()
            ran = False
            for kind, level in SCH.step_order(self.p_active):
                _, ready = self._step_masks(kind, level, occs)
                mask = ready & (budget > 0)
                if mask.any():
                    self._apply_step(kind, level, mask)
                    budget[mask] -= 1
                    ran = True
                    break   # state changed: re-snapshot before the next op
            if not ran:
                return

    def _forced_pass(self) -> None:
        """Seal/flush/cascade every shard the next round structurally
        requires (the legacy lockstep Do-Merge — the whole of maintenance
        when merge_budget == 0)."""
        p = self.p_active
        while True:
            need_seal = np.asarray(self.state.stage_count) >= p.Rn
            if not need_seal.any():
                return
            need_flush = need_seal & (np.asarray(self.state.run_count) >= p.R)
            if need_flush.any():
                self._cascade(need_flush)
                self._apply_step(SCH.FLUSH, -1, need_flush)
            self._apply_step(SCH.SEAL, -1, need_seal)

    def _cascade(self, flush_mask: np.ndarray) -> None:
        """Forced deepest-first spill chain: shard s spills level l+1 only
        if its level-l spill is about to push a run into a full level l+1."""
        p = self.p_active
        spill, mask = [], flush_mask
        for lvl in range(p.max_levels):
            mask = mask & (np.asarray(self.state.levels[lvl].n_runs) >= p.D)
            spill.append(mask.copy())
        last = p.max_levels - 1
        if spill[last].any():
            self._apply_step(SCH.COMPACT, last, spill[last])
        for lvl in range(last - 1, -1, -1):
            if spill[lvl].any():
                self._apply_step(SCH.SPILL, lvl, spill[lvl])

    def warm(self) -> None:
        """Precompile the sharded maintenance program set (one program
        per step kind — the stacked pytree has a single structure, unlike
        the single tree's lazily grown levels) plus the range-scan
        program grid (`RANGE_BUCKETS` batched widths and the legacy
        per-shard scan), so no insert round or first scan pays a
        first-use jit compile. Masks are all-False: the vmapped ops still
        compile fully, the dummy state passes through unchanged. With
        adaptive tuning each preset allocation is its own static-param
        program set, so every preset (plus its retune rebuild) warms."""
        base = MT.init_state(self.p, n_levels=self.p.max_levels)
        if self.tuner.enabled:
            param_sets = [alloc.apply(self.p)
                          for alloc in self.tuner.presets.values()]
        else:
            param_sets = [self.p]

        def stacked():
            return jax.tree.map(lambda x: jnp.stack([x] * self.S), base)

        no = jnp.zeros((self.S,), bool)
        outs = []
        for p in param_sets:
            outs.append(_stage_append_sharded(  # donates: own dummy
                p, stacked(), jnp.zeros((self.S, p.Rn), jnp.int32),
                jnp.zeros((self.S, p.Rn), jnp.int32),
                jnp.zeros((self.S, p.Rn), jnp.int32),
                jnp.zeros((self.S,), jnp.int32)))
            if len(param_sets) > 1:             # donates: own dummy
                outs.append(_retune_filters_sharded(p, stacked()))
            dummy = stacked()
            outs.append(_seal_where(p, dummy, no))
            outs.append(_flush_where(p, dummy, no))
            for lvl in range(p.max_levels - 1):
                outs.append(_merge_level_down_where(p, dummy, lvl,
                                                    p.disk_runs_merged, no))
            outs.append(_compact_last_where(p, dummy, no))
            # the batched range-scan grid + the legacy per-shard program
            for b in RANGE_BUCKETS:
                z = jnp.zeros((b,), jnp.int32)
                outs.append(_range_many_sharded(p, dummy, z, z,
                                                jnp.int32(0)))
            outs.append(_range_sharded(p, dummy, jnp.int32(0), jnp.int32(0)))
        jax.block_until_ready(outs)

    def drain(self) -> None:
        """Merge barrier: retire every shard's pending steps (see
        SLSM.drain — reads are exact without draining; drain completes the
        deferred maintenance so budgeted and synchronous engines can be
        compared at rest)."""
        if self.tuner.pending:   # a decided switch drains like any step
            self._apply_retune()
        while True:
            occs = self._occupancies()
            pending_any = progressed = False
            for kind, level in SCH.step_order(self.p_active):
                pend, ready = self._step_masks(kind, level, occs)
                pending_any |= bool(pend.any())
                if ready.any():
                    self._apply_step(kind, level, ready)
                    progressed = True
                    break   # state changed: re-snapshot before the next op
            if not pending_any:
                return
            if not progressed:   # pragma: no cover — invariant violation
                raise RuntimeError("sharded merge drain stalled")

    def voluntary_steps(self, budget: int) -> int:
        """Run up to `budget` ready maintenance steps per shard,
        deepest-first, re-deriving the masks after each applied op (the
        `_voluntary_pass` fixpoint, with an explicit budget): the
        maintenance governor's entry point (repro.serve), mirroring
        `MergeScheduler.voluntary_steps` on the single tree. A pending
        tuner allocation switch applies first (the lockstep swap cannot
        be per-shard masked) and counts as one step. Returns the total
        steps applied across the fleet."""
        self.tuner.decide()
        ran = 0
        if self.tuner.pending and budget > 0:
            self._apply_retune()
            ran, budget = 1, budget - 1
        per_shard = np.full(self.S, budget, np.int64)
        while (per_shard > 0).any():
            occs = self._occupancies()
            progressed = False
            for kind, level in SCH.step_order(self.p_active):
                _, ready = self._step_masks(kind, level, occs)
                mask = ready & (per_shard > 0)
                if mask.any():
                    self._apply_step(kind, level, mask)
                    per_shard[mask] -= 1
                    ran += int(mask.sum())
                    progressed = True
                    break   # state changed: re-snapshot before the next op
            if not progressed:
                break
        return ran

    # -- read path ----------------------------------------------------------
    def _on_reads(self, n: int) -> None:
        """Tuner signal on the read path (adaptive mode): reads feed and
        roll the controller but never execute maintenance — decisions
        bind at the next insert round's `_maintain` (or at `drain()`),
        mirroring the single-tree rule (MergeScheduler.on_read). The
        sharded tuner observes fleet-global counts — one allocation
        governs all shards, so per-shard mixes fold into one signal."""
        self.stats["reads"] += n
        t = self.tuner
        if not t.enabled:
            return
        t.note_reads(n)
        t.decide()

    def lookup(self, keys):
        """Batched multi-key lookup (paper 2.7, vmapped): route each query
        to its owner shard host-side, answer every shard's row in ONE
        fused device dispatch (`read_path.lookup_batch_impl` vmapped over
        shards — one Bloom-probe/fence-search pass per run for all
        queries), scatter results back.

        The per-shard row width is padded to a power-of-two bucket, so
        mixed batch sizes reuse O(log Q) compiled programs instead of
        recompiling on every distinct max-queries-per-shard value."""
        qs = np.asarray(keys, np.int32).reshape(-1)
        reject_reserved(qs, op="lookup")
        nq = len(qs)
        if nq == 0:
            return np.zeros(0, np.int32), np.zeros(0, bool)
        self._on_reads(nq)
        sid = shard_ids(qs, self.S)
        counts = np.bincount(sid, minlength=self.S)
        qmax = bucket_pow2(int(counts.max()))
        routed = np.full((self.S, qmax), KEY_EMPTY, np.int32)
        # vectorized routing: stable-sort by shard, then each query's slot
        # is its rank within its shard (index minus the shard's start)
        order = np.argsort(sid, kind="stable")
        starts = np.zeros(self.S + 1, np.int64)
        np.cumsum(counts, out=starts[1:])
        pos = np.empty(nq, np.int64)
        pos[order] = np.arange(nq, dtype=np.int64) - starts[sid[order]]
        routed[sid, pos] = qs
        vals, found = _lookup_sharded(self.p_active, self.state,
                                      jnp.asarray(routed),
                                      self.tuner.enabled)
        vals, found = np.asarray(vals), np.asarray(found)
        return vals[sid, pos], found[sid, pos]

    def lookup_many(self, keys, sparse: bool = False):
        """Alias for `lookup` — the sharded read path is already the
        batched fast path (one fused dispatch for all Q queries); the name
        and signature match `SLSM.lookup_many` so drivers can switch
        engines. `sparse` is accepted for that interchangeability but
        always served by the dense path (exact; the sparse candidate
        compaction does not vmap — see module docstring)."""
        return self.lookup(keys)

    def range(self, lo: int, hi: int, return_truncated: bool = False):
        """Global range = concat of per-shard ranges (disjoint key sets),
        re-sorted by key. Each shard contributes a correct sorted prefix
        of its live window (bounded by max_range and, when finite, the
        `range_cand` candidate budget): results are exact while no shard
        truncates, and with `return_truncated` the (S,) per-shard
        truncation flags are returned so callers can tell (shard s's
        flag set means its contribution is only a prefix — it held more
        than max_range live keys in [lo, hi), or its scan overflowed the
        candidate budget)."""
        k, v, c, trunc = _range_sharded(self.p_active, self.state,
                                        jnp.int32(lo), jnp.int32(hi))
        k, v, c = np.asarray(k), np.asarray(v), np.asarray(c)
        ks = np.concatenate([k[s, :c[s]] for s in range(self.S)])
        vs = np.concatenate([v[s, :c[s]] for s in range(self.S)])
        order = np.argsort(ks, kind="stable")
        out = ks[order], vs[order]
        return out + (np.asarray(trunc),) if return_truncated else out

    def range_device(self, lo: int, hi: int):
        """Device-resident global range query: one fused dispatch over
        all shards with the per-shard results merged on device (no host
        argsort, no per-scan sync). Returns jax arrays ``(keys
        (max_range,), vals, count, truncated)`` — the single-tree
        `SLSM.range_device` contract, with `truncated` already folded
        across shards. The single scan rides the smallest warmed
        `RANGE_BUCKETS` lane width, so it never pays a first-use
        compile after `warm()`."""
        width = range_bucket(1)
        los = np.zeros(width, np.int32)
        his = np.zeros(width, np.int32)
        los[0], his[0] = lo, hi
        k, v, c, tr = _range_many_sharded(
            self.p_active, self.state, jnp.asarray(los), jnp.asarray(his),
            jnp.int32(1))
        return k[0], v[0], c[0], tr[0]

    def range_many(self, ranges):
        """Batched multi-scan fast path over the shard fleet: all Q
        scans answered by every shard in ONE vmapped dispatch, with the
        disjoint per-shard rows merged per scan on device
        (`_range_many_sharded`) — same numpy return contract as
        `SLSM.range_many` (one shared pad/trim driver), padded to the
        `RANGE_BUCKETS` grid."""
        return range_many_host(
            lambda los, his, n: _range_many_sharded(
                self.p_active, self.state, los, his, n),
            self.p.max_range, ranges)

    def aggregate_many(self, ranges):
        """Batched windowed aggregates over the shard fleet: every shard
        reduces its own live rows in ONE vmapped dispatch and the
        disjoint partial counts/sums fold by addition
        (`_aggregate_many_sharded`) — same numpy return contract as
        `SLSM.aggregate_many` (``counts, sums, truncated``), exact past
        `max_range`, int32-wraparound sums."""
        r = np.asarray(ranges, np.int32).reshape(-1, 2)
        q = r.shape[0]
        if q == 0:
            return (np.zeros(0, np.int32), np.zeros(0, np.int32),
                    np.zeros(0, bool))
        width = range_bucket(q)
        los = np.zeros(width, np.int32)
        his = np.zeros(width, np.int32)
        los[:q], his[:q] = r[:, 0], r[:, 1]
        c, s, t = _aggregate_many_sharded(self.p_active, self.state,
                                          jnp.asarray(los), jnp.asarray(his),
                                          jnp.int32(q))
        return np.asarray(c)[:q], np.asarray(s)[:q], np.asarray(t)[:q]

    def count(self, lo: int, hi: int) -> int:
        """Live-key count over [lo, hi) across all shards (exact;
        one-window `aggregate_many`)."""
        c, _, _ = self.aggregate_many([(lo, hi)])
        return int(c[0])

    def sum(self, lo: int, hi: int) -> int:
        """Sum of live values over [lo, hi) across all shards (int32
        wraparound; one-window `aggregate_many`)."""
        _, s, _ = self.aggregate_many([(lo, hi)])
        return int(s[0])

    # -- mixed-op tape (repro.engine.tape, DESIGN.md §11) -------------------
    def _route_lanes(self, keys, vals=None, wts=None):
        """Route one chunk's lanes to their owner shards. Returns
        ``(k (S, Rn), v (S, Rn), w (S, Rn), n (S,), sid, pos)`` — sid/pos
        are each input lane's (shard, rank-within-shard) coordinates, the
        scatter map for lookup results (same vectorized routing as
        `lookup`)."""
        rn = self.p.Rn
        qs = np.asarray(keys, np.int32).reshape(-1)
        sid = shard_ids(qs, self.S)
        counts = np.bincount(sid, minlength=self.S)
        order = np.argsort(sid, kind="stable")
        starts = np.zeros(self.S + 1, np.int64)
        np.cumsum(counts, out=starts[1:])
        pos = np.empty(len(qs), np.int64)
        pos[order] = np.arange(len(qs), dtype=np.int64) - starts[sid[order]]
        k = np.full((self.S, rn), KEY_EMPTY, np.int32)
        k[sid, pos] = qs
        v = np.zeros((self.S, rn), np.int32)
        if vals is not None:
            v[sid, pos] = np.asarray(vals, np.int32).reshape(-1)
        w = np.zeros((self.S, rn), np.int32)
        if wts is not None:
            w[sid, pos] = np.asarray(wts, np.int32).reshape(-1)
        return k, v, w, counts.astype(np.int32), sid, pos

    def tape_write_capacity(self) -> int:
        """Max write keys the next `run_tape` call may carry — the
        single-tree bound (`SLSM.tape_write_capacity`) evaluated per
        shard and min-folded, since routing may land every key on the
        worst shard."""
        p = self.p_active
        rcs = np.asarray(self.state.run_count)
        scs = np.asarray(self.state.stage_count)
        caps = []
        for s in range(self.S):
            rc, sc = int(rcs[s]), int(scs[s])
            while sc >= p.Rn:
                if rc >= p.R:
                    rc -= p.runs_merged_eff
                rc += 1
                sc -= p.Rn
            free = p.R - rc % p.runs_merged_eff
            caps.append((free + 1) * p.Rn - 1 - sc)
        return min(caps)

    def _reserve_run_slots(self, need: np.ndarray) -> None:
        """Per-shard headroom for the tape's in-scan seals: masked
        flushes (cascading first when level 0 is full) until every shard
        has >= need[s] free run slots. Mirrors
        `MergeScheduler.reserve_run_slots`, lockstep-masked."""
        p = self.p_active
        rm = p.runs_merged_eff
        while True:
            rc = np.asarray(self.state.run_count)
            short = (p.R - rc) < need
            if not short.any():
                return
            mask = short & (rc >= rm)
            if not mask.any():
                floors = rc % rm
                raise ValueError(
                    f"cannot reserve {need.max()} run slots on every "
                    f"shard: worst shard reaches {p.R - int(floors.max())} "
                    f"(R={p.R})")
            self._cascade(mask)
            self._apply_step(SCH.FLUSH, -1, mask)

    def run_tape(self, chunks):
        """Execute a coalesced mixed-op window as ONE vmapped device
        dispatch — the sharded form of `SLSM.run_tape` (same chunk
        kinds, same per-chunk result contract, same headroom and
        window-segmentation behaviour, with every precondition enforced
        per shard). Write and lookup lanes are host-routed to their
        owner shards; range slots are answered by every shard and
        merged on device (`_merge_shard_ranges`)."""
        chunks = [c if isinstance(c, TP.TapeChunk) else TP.TapeChunk(*c)
                  for c in chunks]
        if not chunks:
            return []
        n_writes = n_reads = 0
        for ch in chunks:
            k = np.asarray(ch.keys, np.int32).reshape(-1)
            if ch.kind == "write":
                reject_reserved(k, op="tape write")
                n_writes += k.size
            elif ch.kind == "lookup":
                reject_reserved(k, op="tape lookup")
                n_reads += k.size
            elif ch.kind != "range":
                raise ValueError(f"unknown tape chunk kind {ch.kind!r}")
        if n_writes:
            self._guard_writes()
        # one WAL record per write chunk, pre-routing, group-committed
        # before the window's results are returned (log-before-ack —
        # SLSM.run_tape's contract, byte-identical records)
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
        rb = TP.range_lanes(self.p_active)
        results = [0] * len(chunks)
        work = list(enumerate(chunks))
        while work:
            self._forced_pass()   # every shard's stage absorbs a chunk
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
            self._run_tape_segment(seg, seg_idx, rb, results)
        self.stats["writes"] += n_writes
        self.stats["reads"] += n_reads
        if n_writes:
            self.tuner.note_writes(n_writes)
        if n_reads:
            self.tuner.note_reads(n_reads)
        if log:
            self.durability.sync()
        return results

    def _run_tape_segment(self, seg, seg_idx, rb, results) -> None:
        """Pack, reserve, dispatch, and scatter back one tape segment."""
        p = self.p_active
        rn, t = p.Rn, len(seg)
        t_pad = tape_bucket(t)
        ops = np.zeros(t_pad, np.int32)
        keys = np.full((t_pad, self.S, rn), KEY_EMPTY, np.int32)
        vals = np.zeros((t_pad, self.S, rn), np.int32)
        wts = np.zeros((t_pad, self.S, rn), np.int32)
        nv = np.zeros((t_pad, self.S), np.int32)
        scatter = [None] * t
        seal_need = np.asarray(self.state.stage_count).astype(np.int64)
        for i, ch in enumerate(seg):
            if ch.kind == "range":
                los = np.asarray(ch.keys, np.int32).reshape(-1)
                his = np.asarray(ch.vals, np.int32).reshape(-1)
                if len(los) > rb:
                    raise ValueError(
                        f"range chunk of {len(los)} scans exceeds its "
                        f"per-slot capacity {rb}")
                ops[i] = TP.OP_RANGE
                keys[i, :, :len(los)] = los[None, :]
                vals[i, :, :len(his)] = his[None, :]
                nv[i, :] = len(los)
                continue
            if ch.kind == "write":
                cw = (np.ones(len(np.asarray(ch.keys).reshape(-1)), np.int32)
                      if ch.wts is None else ch.wts)
                k, v, w, n, sid, pos = self._route_lanes(ch.keys, ch.vals, cw)
            else:
                k, v, w, n, sid, pos = self._route_lanes(ch.keys)
            ops[i] = TP.OPCODES[ch.kind]
            keys[i], vals[i], wts[i], nv[i] = k, v, w, n
            scatter[i] = (sid, pos)
            if ch.kind == "write":
                seal_need += np.bincount(sid, minlength=self.S)
        need = (seal_need // rn).astype(np.int64)
        if need.any():
            self._reserve_run_slots(need)
        self.state, ys = _tape_exec_sharded(
            p, self.state, jnp.asarray(ops), jnp.asarray(keys),
            jnp.asarray(vals), jnp.asarray(wts), jnp.asarray(nv),
            self.tuner.enabled)
        lv, lf, rk, rv, rc, rt, sealed = (np.asarray(y) for y in ys)
        for i, ch in enumerate(seg):
            j = seg_idx[i]
            if ch.kind == "write":
                results[j] += int(sealed[i])
                self.stats["seals"] += int(sealed[i])
            elif ch.kind == "lookup":
                sid, pos = scatter[i]
                results[j] = (lv[i, sid, pos], lf[i, sid, pos])
            else:
                n = len(np.asarray(ch.keys).reshape(-1))
                results[j] = (rk[i, :n], rv[i, :n], rc[i, :n], rt[i, :n])

    def warm_tape(self, buckets: tuple = TAPE_BUCKETS) -> None:
        """Precompile the sharded tape interpreter grid (one program per
        allocation x slot bucket — the stacked pytree has a single
        structure), mirroring `SLSM.warm_tape`: after this, steady-state
        serving windows never JIT."""
        base = MT.init_state(self.p, n_levels=self.p.max_levels)
        if self.tuner.enabled:
            param_sets = [alloc.apply(self.p)
                          for alloc in self.tuner.presets.values()]
        else:
            param_sets = [self.p]
        skip = self.tuner.enabled
        outs = []
        for p in param_sets:
            for t in buckets:
                st = jax.tree.map(lambda x: jnp.stack([x] * self.S), base)
                outs.append(_tape_exec_sharded(
                    p, st, jnp.zeros((t,), jnp.int32),
                    jnp.full((t, self.S, p.Rn), KEY_EMPTY, jnp.int32),
                    jnp.zeros((t, self.S, p.Rn), jnp.int32),
                    jnp.zeros((t, self.S, p.Rn), jnp.int32),
                    jnp.zeros((t, self.S), jnp.int32), skip))
        jax.block_until_ready(outs)

    # -- durability (repro.engine.wal, DESIGN.md §12) -----------------------
    def _wal_meta(self) -> dict:
        """Engine fingerprint for the WAL's META record (driver kind,
        params, shard count) — verified on every reattach so a
        durability directory can never be replayed into a mismatched
        fleet."""
        return {"driver": "sharded",
                "params": WAL.params_to_dict(self.p),
                "policy": "tiering", "n_shards": self.S,
                "wal": WAL.WAL_FORMAT}

    def _snapshot_meta(self) -> dict:
        """Host-side state riding a snapshot beside the stacked pytree
        leaves (see SLSM._snapshot_meta; the levels structure is always
        fully preallocated here, so n_levels is max_levels)."""
        return {**self._wal_meta(), "n_levels": self.p.max_levels,
                "tuner": {"active": self.tuner.active,
                          "read_frac": float(self.tuner.read_frac)},
                "stats": {k: int(v) for k, v in self.stats.items()}}

    def snapshot(self):
        """Serialize the whole fleet's stacked pytree as one atomic
        snapshot stamped with the WAL seqno watermark (see
        SLSM.snapshot). Requires a durability layer."""
        if self.durability is None:
            raise ValueError("snapshot() requires a durability layer: "
                             "construct with ShardedSLSM(..., "
                             "durability=path)")
        return self.durability.snapshot(self)

    def _adopt_snapshot(self, leaves, meta: dict) -> None:
        """Install snapshot `leaves` as the live stacked state and adopt
        the controller/stats position captured in `meta` (see
        SLSM._adopt_snapshot; the stacked template is structure-fixed at
        init, so it always matches)."""
        base = MT.init_state(self.p, n_levels=self.p.max_levels)
        template = jax.tree.map(lambda x: jnp.stack([x] * self.S), base)
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
        with re-logging suppressed (see SLSM._replay: answer-exact by
        the scheduler invariant, not bitwise-state-exact)."""
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
                            self._apply_retune()
                else:
                    continue
                n += 1
            self.stats["replayed_records"] += n
        finally:
            self._replaying = False

    @classmethod
    def restore(cls, path, params: SLSMParams | None = None,
                n_shards: int | None = None, durability=None):
        """Recover a sharded fleet from a durability directory: newest
        valid snapshot + WAL-tail replay, exactly `SLSM.restore`'s
        contract (torn final record dropped cleanly; `params`/`n_shards`
        default to the recorded fingerprint; restore wall time and
        replay size reported as ``restore_us``/``replayed_records``)."""
        t0 = time.perf_counter()
        dur = WAL.as_durability(durability if durability is not None
                                else path)
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
        if n_shards is None:
            # a foreign (single-tree) fingerprint has no shard count; let
            # the constructor's ensure_header raise the clear mismatch
            n_shards = (int(meta.get("n_shards", 4))
                        if meta is not None else 4)
        drv = cls(params, n_shards, durability=dur)
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
        """Open a sharded replication follower over a bootstrapped
        directory — `SLSM.open_replica`'s contract: a plain `restore`
        under a replica-mode durability layer that never injects a
        local META record (the log is the leader's stream, verbatim).
        WAL records are pre-routing, so a sharded follower replays a
        sharded leader's stream byte-identically."""
        return cls.restore(path, durability=WAL.Durability(
            path, fsync=fsync, replica=True))

    def apply_replicated(self, records) -> int:
        """Apply decoded leader WAL records through the vmapped
        chunk-apply programs with re-logging suppressed (see
        `SLSM.apply_replicated`). Returns the records applied."""
        before = self.stats["replayed_records"]
        self._replay(records)
        return self.stats["replayed_records"] - before

    def promote(self) -> "ShardedSLSM":
        """Failover: turn this replica fleet into a writable leader —
        `SLSM.promote`'s contract (epoch bump + local logging
        re-enabled; seqnos resume after the last applied record)."""
        if self.durability is None:
            raise ValueError("promote() requires a durability layer")
        self.durability.writer.bump_epoch()
        self.durability.replica = False
        self.fenced = False
        self.stats["promotions"] += 1
        return self

    def demote(self) -> "ShardedSLSM":
        """Fence this fleet against writes (the deposed-leader exit,
        DESIGN.md §15) — `SLSM.demote`'s contract: reads stay served,
        writes raise until a future `promote()`. Returns self."""
        self.fenced = True
        self.stats["demotions"] += 1
        return self

    # -- stats ----------------------------------------------------------------
    @property
    def n_live(self) -> int:
        """Resident elements across all shards' stages, memory runs, and
        disk levels (duplicates and negative-weight delete records count
        until merges annihilate them) — the fleet-wide sibling of
        `SLSM.n_live`."""
        n = int(self.state.stage_count.sum()) + int(self.state.buf_counts.sum())
        for lv in self.state.levels:
            n += int(lv.counts.sum())
        return n

    def shard_occupancy(self) -> np.ndarray:
        """(S,) live elements per shard — routing-balance introspection."""
        per = np.asarray(self.state.stage_count).astype(np.int64)
        per = per + np.asarray(self.state.buf_counts).sum(axis=1)
        for lv in self.state.levels:
            per = per + np.asarray(lv.counts).sum(axis=1)
        return per
