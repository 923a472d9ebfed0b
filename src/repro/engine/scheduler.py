"""Incremental merge scheduler: the Do-Merge cascade as paced, bounded steps.

The paper's Do-Merge (Algorithm 2 / 2.5) is recursive: the insert that
fills the staging buffer pays for the seal, the flush, every level spill
the flush triggers, and — worst case — the deepest-level compaction, all
synchronously inside one insert chunk. That is the classic LSM write
stall (Luo & Carey, "On Performance Stability in LSM-based Storage
Systems"): p50 insert latency is one staged sort, p99 is the whole
cascade, two-plus orders of magnitude apart.

This module decomposes the cascade into four bounded-work step kinds —
each already a single jitted device op in `memtable`/`compaction`:

  seal     — stage -> one sealed memory run            (memtable.seal_run)
  flush    — ceil(m*R) memory runs -> one L0 run       (compaction.merge_buffer_to_level0)
  spill l  — ceil(m*D) runs of level l -> one l+1 run  (compaction.merge_level_down)
  compact  — all runs of the deepest level -> one run  (compaction.compact_last_level)

and paces them: after every staged insert chunk the scheduler executes up
to `SLSMParams.merge_budget` *voluntary* steps, deepest level first, then
runs whatever is structurally *forced* (the next chunk must fit the
staging buffer). With budget 0 the voluntary pass is empty and the forced
chain reproduces the legacy synchronous cascade exactly. With budget >= 1
a level that fills is retired during the many chunks of slack before the
next run arrives for it, so the forced chain almost never recurses and
the insert tail collapses to the cost of the single largest step.

Pacing invariants (DESIGN.md §8):
  * every step is one atomic state transition: a merge's source runs stay
    visible to the read path until the very dispatch that installs the
    merged output retires them, so reads are exact at every point between
    steps — no drain needed for correctness;
  * a step runs only when its destination has a free run slot under the
    compaction policy (`step_ready`), so pacing never violates the
    policy's occupancy bounds;
  * `drain()` is the barrier: it retires every pending step, after which
    budgeted and synchronous engines answer lookups/ranges identically
    (they may hold different — equally valid — resting structures);
  * voluntary work runs earlier than the synchronous schedule would, so
    a tree at its declared capacity can raise the deepest-level overflow
    RuntimeError a few chunks sooner than merge_budget=0 — the remedy is
    the same either way (increase max_levels).

The occupancy lives on the host. The scheduler keeps a mirror of it
(`MergeScheduler.occupancy`) and moves it by each step's structural
rule: a seal adds one memory run and takes Rn from the stage, a flush
moves `runs_merged_eff` memory runs into one level-0 run, a spill moves
`runs_to_spill` runs of level l into one run of level l+1, a compaction
leaves the deepest level one run. Only the stage count depends on the
data (`stage_append` dedups), so a staged chunk costs one device read
of it. The mirror is keyed on the state object it describes: a state
the scheduler or the stage loop did not install (a tape's in-scan
seals, a restore, a replayed retune) makes it stale, and the next read
rebuilds it from the device (`occupancy_of`, counted in
``stats["occupancy_resyncs"]``).

Annihilation stays the host decision it was in the synchronous cascade:
a step elides zero-sum (deleted) keys iff its output becomes the deepest
data *at the moment the step runs* (paper 2.5/2.8). Each merge step also
books the Z-set telemetry (rows in/out, annihilated rows) host-side: a
flush's rows in are its runs times Rn, and the rows a spill reads and a
merge writes are read from the device (a flush or spill every 50 or
more chunks at the paper's geometry).

The adaptive tuner (repro.engine.tuner, DESIGN.md §9) rides this same
machinery: a decided allocation switch surfaces as a fifth step kind,

  retune   — rebuild every resident filter under the new allocation
             (tuner.retune_filters) and swap the driver's active params

which is paced, drained, and telemetered exactly like a merge. With the
default static tuning policy no RETUNE step ever becomes pending and
the scheduler is bit-identical to its pre-tuner behaviour.
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.params import SLSMParams
from repro.engine.batching import host_read
from repro.engine.compaction import (CompactionPolicy, compact_last_level,
                                     compaction_rows, merge_buffer_to_level0,
                                     merge_level_down)
from repro.engine.levels import empty_level
from repro.engine.memtable import seal_run, stage_append
from repro.engine.precompile import i32, state_shapes

SEAL, FLUSH, SPILL, COMPACT = "seal", "flush", "spill", "compact"
RETUNE = "retune"


class Occupancy(NamedTuple):
    """Host-side occupancy of one tree: all the planner looks at. The
    scheduler's mirror holds ``stage_count=None`` between a staged chunk
    and the first read of it."""
    stage_count: Optional[int]
    run_count: int
    level_runs: Tuple[int, ...]   # n_runs per *materialized* level


def occupancy_of(state, stats=None) -> Occupancy:
    """Read a (single-tree) state pytree's occupancy from the device: 2 +
    len(levels) reads (`host_read`, counted in `stats`). The scheduler
    builds and rebuilds its mirror with it."""
    return Occupancy(int(host_read(state.stage_count, stats)),
                     int(host_read(state.run_count, stats)),
                     tuple(int(host_read(lv.n_runs, stats))
                           for lv in state.levels))


def step_order(p: SLSMParams) -> List[Tuple[str, int]]:
    """Canonical deepest-first step order: executing pending steps in this
    order propagates free space upward (a spill's destination is freed
    before the spill itself is attempted)."""
    order: List[Tuple[str, int]] = [(COMPACT, p.max_levels - 1)]
    order += [(SPILL, lvl) for lvl in range(p.max_levels - 2, -1, -1)]
    order += [(FLUSH, -1), (SEAL, -1)]
    return order


def step_pending(kind: str, level: int, occ: Occupancy, p: SLSMParams,
                 policy: CompactionPolicy) -> bool:
    """Does this step have work queued under the current occupancy?

    (RETUNE pendingness lives on the tuner, not the occupancy — it is
    injected by `pending_steps(..., retune=True)`.)"""
    if kind == SEAL:
        return occ.stage_count >= p.Rn
    if kind == FLUSH:
        # flush becomes *pending* at the tuner's effective buffer size;
        # only run_count >= R (physical slots exhausted) ever *forces* it
        return occ.run_count >= p.R_eff
    # spill/compact: the level must exist and the policy must want it moved
    if level >= len(occ.level_runs):
        return False
    return policy.needs_spill(p, occ.level_runs[level], level)


def step_ready(kind: str, level: int, occ: Occupancy, p: SLSMParams,
               policy: CompactionPolicy) -> bool:
    """Can this step run *now* without violating a policy bound — i.e. is
    its destination able to accept the output run? (The deepest-level
    compaction rewrites in place and is always ready.)"""
    if kind == SEAL:
        return occ.stage_count >= p.Rn and occ.run_count < p.R
    if kind == FLUSH:
        if occ.run_count < p.runs_merged_eff:
            return False
        return (len(occ.level_runs) == 0
                or not policy.needs_spill(p, occ.level_runs[0], 0))
    if kind in (COMPACT, RETUNE):
        return True
    dst = level + 1
    return (dst >= len(occ.level_runs)      # destination grown on demand
            or not policy.needs_spill(p, occ.level_runs[dst], dst))


def step_cost(kind: str, level: int, p: SLSMParams) -> int:
    """Device-op cost of one step, in elements touched by its merge — the
    uniform cost axis the pacing trades against (a seal is ~Rn, the
    deepest compaction reads (2D-1) * D * level_cap(last-1) lanes at
    m=1 — compaction.compaction_rows: orders of magnitude)."""
    if kind == SEAL:
        return p.Rn
    if kind == FLUSH:
        return p.runs_merged_eff * p.Rn
    if kind == COMPACT:
        rows, width = compaction_rows(p)
        return rows * width
    if kind == RETUNE:   # every resident filter is rebuilt from its keys
        return p.R * p.Rn + sum(p.D * p.level_cap(lvl)
                                for lvl in range(p.max_levels))
    return p.disk_runs_merged * p.level_cap(level)


class MergeStep(NamedTuple):
    """One bounded unit of Do-Merge work (uniform interface over the
    single-step ops in memtable.py / compaction.py)."""
    kind: str
    level: int     # source level for spill/compact; -1 for seal/flush
    cost: int      # elements touched (step_cost)

    def pending(self, occ: Occupancy, p, policy) -> bool:
        """Does this step have work queued under `occ`? (step_pending)"""
        return step_pending(self.kind, self.level, occ, p, policy)

    def ready(self, occ: Occupancy, p, policy) -> bool:
        """Can this step run now without violating a policy bound?
        (step_ready)"""
        return step_ready(self.kind, self.level, occ, p, policy)


def pending_steps(p: SLSMParams, policy: CompactionPolicy,
                  occ: Occupancy, retune: bool = False) -> List[MergeStep]:
    """The step backlog under `occ`, deepest-first (execution order).

    `retune` injects the tuner's pending allocation switch at the head
    of the backlog (its pendingness lives on the tuner, not in the
    occupancy): retiring it first means every subsequent merge in the
    same pass already builds filters at the new allocation."""
    steps = [MergeStep(kind, level, step_cost(kind, level, p))
             for kind, level in step_order(p)
             if step_pending(kind, level, occ, p, policy)]
    if retune:
        steps.insert(0, MergeStep(RETUNE, -1, step_cost(RETUNE, -1, p)))
    return steps


def backlog_cost(steps: Sequence[MergeStep]) -> int:
    """Total device-op cost of a backlog (telemetry)."""
    return sum(s.cost for s in steps)


def drop_annihilated_into(level_runs: Sequence[int],
                          target_level: int) -> bool:
    """Deletes commit (negative-weight records annihilate) when the merge
    output becomes the deepest data (paper 2.5/2.8): no run rests in
    `target_level` or below, by the occupancy at step-run time — exactly
    as the synchronous cascade evaluated it at recursion time."""
    return not any(level_runs[target_level:])


class MergeScheduler:
    """Single-tree scheduler: owns no array state — it executes steps
    against the driver's state pytree and keeps that state's occupancy
    on the host (`occupancy`).

    `on_chunk()` is the one entry point the insert path calls (after each
    staged Rn-chunk): voluntary budgeted steps first, forced chain after.
    `drain()` retires the whole backlog (the read-equivalence barrier).
    """

    def __init__(self, drv):
        self.drv = drv   # the SLSM driver: .p, .policy, .state, .stats
        # the occupancy mirror and the state object it describes: it holds
        # only while that object is still the driver's state
        self._occ_state = None
        self._occ: Optional[Occupancy] = None

    @property
    def p(self) -> SLSMParams:
        """The driver's *active* parameter set — the current tuner
        allocation's effective view (== drv.p under static tuning)."""
        return getattr(self.drv, "p_active", self.drv.p)

    @property
    def policy(self):
        """The driver's *active* compaction policy (the eager read-mode
        overlay while the tuner's read allocation is active; otherwise
        the configured policy)."""
        return getattr(self.drv, "policy_active", self.drv.policy)

    def _retune_pending(self) -> bool:
        tuner = getattr(self.drv, "tuner", None)
        return bool(tuner is not None and tuner.pending)

    # -- the occupancy mirror ----------------------------------------------

    def track(self, occ: Occupancy) -> None:
        """Make `occ` the mirror of the driver's current state."""
        self._occ_state, self._occ = self.drv.state, occ

    def _mirror(self) -> Occupancy:
        """The mirror of the driver's current state (its stage count may
        be None), rebuilt from the device when the state was replaced by
        anything but a step or `staged` — counted in the driver's
        ``occupancy_resyncs``."""
        drv = self.drv
        if self._occ_state is not drv.state:
            self.track(occupancy_of(drv.state, drv.stats))
            drv.stats["occupancy_resyncs"] += 1
        return self._occ

    def occupancy(self) -> Occupancy:
        """The driver state's occupancy, from the host mirror: the only
        device read it makes is the stage count after a staged chunk
        (one `host_read`), or a rebuild of a stale mirror."""
        occ = self._mirror()
        if occ.stage_count is None:
            occ = occ._replace(
                stage_count=self._read(self.drv.state.stage_count))
            self._occ = occ
        return occ

    def staged(self, state) -> None:
        """Install the state a `stage_append` returned. Only the stage
        count moved, by what the dedup left, so the mirror marks it
        unknown."""
        fresh = self._occ_state is self.drv.state
        self.drv.state = state
        if fresh:
            self.track(self._occ._replace(stage_count=None))

    # -- step execution (each is one jitted device dispatch) ---------------

    def _materialize(self, level: int) -> None:
        """Grow the levels pytree through `level` (host decision, lazy —
        the paper's unbounded level growth, bounded by max_levels)."""
        drv = self.drv
        while len(drv.state.levels) <= level:
            occ = self._mirror()
            drv.state = drv.state._replace(
                levels=drv.state.levels
                + (empty_level(self.p, len(drv.state.levels)),))
            self.track(occ._replace(level_runs=occ.level_runs + (0,)))

    def _book_merge(self, rows_in: int, rows_out: int) -> None:
        """Z-set merge telemetry (DESIGN.md §13): rows entering the merge
        vs. rows surviving it. The gap is dedup + annihilation — rows the
        weighted algebra kept out of the output, whose payloads the Ghost
        gather never touched (4 bytes of payload each)."""
        st = self.drv.stats
        st["rows_merged_in"] += rows_in
        st["rows_merged_out"] += rows_out
        st["rows_annihilated"] += rows_in - rows_out

    def _read(self, x) -> int:
        """One device scalar on the host (`host_read`, counted in the
        driver's ``host_syncs``)."""
        return int(host_read(x, self.drv.stats))

    def run_step(self, step: MergeStep) -> None:
        """Execute one step as a single jitted device dispatch (or, for
        RETUNE, the driver's filter-rebuild + active-params swap), bump
        the matching stats counter and move the occupancy mirror by the
        step's structural rule. The one place steps become state
        transitions — pacing, forcing, and draining all funnel through
        here; each runs in a ``slsm.step.<kind>`` span."""
        with jax.profiler.TraceAnnotation(f"slsm.step.{step.kind}"):
            drv, p = self.drv, self.p
            if step.kind == FLUSH:
                self._materialize(0)
            elif step.kind == SPILL:
                self._materialize(step.level + 1)
            occ = self.occupancy()
            runs = list(occ.level_runs)
            if step.kind == RETUNE:
                drv.apply_retune()
                drv.stats["retunes"] += 1
            elif step.kind == SEAL:
                drv.state = seal_run(p, drv.state)
                occ = occ._replace(stage_count=occ.stage_count - p.Rn,
                                   run_count=occ.run_count + 1)
                drv.stats["seals"] += 1
            elif step.kind == FLUSH:
                mr, slot = p.runs_merged_eff, runs[0]
                drv.state = merge_buffer_to_level0(
                    p, drv.state, drop_annihilated_into(runs, 0))
                # every memory run was sealed full: Rn records each
                self._book_merge(mr * p.Rn,
                                 self._read(drv.state.levels[0].counts[slot]))
                occ = occ._replace(run_count=occ.run_count - mr)
                runs[0] += 1
                drv.stats["flushes"] += 1
            elif step.kind == SPILL:
                lvl = step.level
                n_merge = self.policy.runs_to_spill(p, runs[lvl])
                rows_in = self._read(jnp.sum(
                    drv.state.levels[lvl].counts[:n_merge]))
                slot = runs[lvl + 1]
                drv.state = merge_level_down(
                    p, drv.state, lvl, n_merge,
                    drop_annihilated_into(runs, lvl + 1))
                rows_out = self._read(drv.state.levels[lvl + 1].counts[slot])
                self._book_merge(rows_in, rows_out)
                runs[lvl] -= n_merge
                runs[lvl + 1] += 1
                drv.stats["spills"] += 1
            else:   # COMPACT
                last = p.max_levels - 1
                rows_in = self._read(jnp.sum(drv.state.levels[last].counts))
                # the compaction donates the state (at deployment geometry a
                # second copy of it does not fit beside the merge), so an
                # overflow leaves nothing to roll back to: the engine drops
                # its state and every later call raises the same error
                drv.state, raw = compact_last_level(p, drv.state)
                raw, cap = self._read(raw), p.level_cap(last)
                if raw > cap:
                    drv.state = None
                    drv.state_lost = (
                        f"sLSM deepest level overflow ({raw} > "
                        f"{cap} live elements): increase max_levels beyond "
                        f"{p.max_levels}; this engine's state went into the "
                        f"overflowing compaction, so restore() it from its "
                        f"durability directory")
                    raise RuntimeError(drv.state_lost)
                self._book_merge(rows_in, raw)
                runs[last] = 1
                drv.stats["compactions"] += 1
            self.track(occ._replace(level_runs=tuple(runs)))

    # -- forced chain (== the legacy synchronous cascade) ------------------

    def force_space(self, level: int) -> None:
        """Guarantee `level` can accept one run, recursing deeper first —
        the legacy `_ensure_space`, expressed in steps. Only runs when
        pacing slack ran out (always, when merge_budget == 0)."""
        drv, p = self.drv, self.p
        if level >= p.max_levels:
            raise RuntimeError(
                "sLSM capacity exceeded: increase max_levels "
                f"(currently {p.max_levels})")
        if level >= len(drv.state.levels):
            self._materialize(level)
            return
        if not self.policy.needs_spill(
                p, self._mirror().level_runs[level], level):
            return
        if level == p.max_levels - 1:
            self.run_step(MergeStep(COMPACT, level,
                                    step_cost(COMPACT, level, p)))
        else:
            self.force_space(level + 1)
            self.run_step(MergeStep(SPILL, level, step_cost(SPILL, level, p)))

    # -- pacing entry points ----------------------------------------------

    def _next_ready(self):
        """Deepest pending step that is ready under the live occupancy
        (None if the backlog is empty or wholly blocked)."""
        p, policy = self.p, self.policy
        occ = self.occupancy()
        for step in pending_steps(p, policy, occ, self._retune_pending()):
            if step.ready(occ, p, policy):
                return step
        return None

    def on_chunk(self) -> None:
        """Voluntary budgeted steps, then whatever the next chunk forces.

        The backlog is re-derived after every applied step, so a step's
        consequences (a seal filling the buffer, a flush filling level 0)
        can be paid for inside the same chunk while budget remains — the
        same fixpoint semantics the sharded driver's masked pass uses, so
        equal budgets mean equal pacing on both drivers.

        The tuner (if adaptive) decides here, at the chunk boundary; a
        decided switch joins the backlog as a RETUNE step and is paid
        for out of the same voluntary budget as any merge. In
        synchronous mode (merge_budget == 0) the voluntary pass is
        empty, so a pending retune — like every other piece of
        maintenance in that mode — runs inline, immediately.

        Runs in a ``slsm.schedule`` span: the stage-count read, the
        planning and the steps (each in its own ``slsm.step.<kind>``)."""
        with jax.profiler.TraceAnnotation("slsm.schedule"):
            drv, p = self.drv, self.p
            tuner = getattr(drv, "tuner", None)
            if tuner is not None:
                tuner.decide()
                if tuner.take_probe_sample():
                    sampler = getattr(drv, "sample_probe_stats", None)
                    if sampler is not None:
                        sampler()
            backlog = pending_steps(p, self.policy, self.occupancy(),
                                    self._retune_pending())
            drv.stats["backlog_peak"] = max(drv.stats["backlog_peak"],
                                            len(backlog))
            budget = p.merge_budget
            # read-mode catch-up: while the read-optimized allocation is (or
            # is about to be) active, writes are a trickle and every one of
            # them is a chance to fold structure the read path then skips —
            # so the voluntary pass runs to quiescence instead of rationing.
            # Write-phase pacing (the whole point of merge_budget) is
            # untouched: catch-up applies only in/INTO read mode — a pending
            # switch to any other allocation stays budget-paced.
            catch_up = (budget > 0 and tuner is not None and tuner.enabled
                        and (tuner.active == "read"
                             or (tuner.pending and tuner.target == "read")))
            while budget > 0 or catch_up:
                step = self._next_ready()
                if step is None:
                    break
                self.run_step(step)
                budget -= 1
            if p.merge_budget == 0 and self._retune_pending():
                self.run_step(MergeStep(RETUNE, -1, step_cost(RETUNE, -1, p)))
            # forced: the staging buffer must fit the next Rn-chunk
            self.ensure_stage_space()

    def ensure_stage_space(self) -> None:
        """Forced chain: seal (flushing/cascading first when the buffer
        is out of run slots) until the staging buffer can absorb a full
        Rn-chunk — the structural precondition every insert chunk and
        every mixed-op tape dispatch relies on. This is `on_chunk`'s
        forced tail, callable standalone (the serving layer's headroom
        pass runs it between tapes)."""
        p = self.p
        while self.occupancy().stage_count >= p.Rn:
            if self.occupancy().run_count >= p.R:
                self.force_space(0)
                self.run_step(MergeStep(FLUSH, -1, step_cost(FLUSH, -1, p)))
            self.run_step(MergeStep(SEAL, -1, step_cost(SEAL, -1, p)))

    def reserve_run_slots(self, n: int) -> None:
        """Guarantee >= `n` free memory-run slots (flushing — and
        cascading, when level 0 is full — until they exist): the
        headroom a mixed-op tape needs before it can seal in-scan,
        where no host decision can intervene (tape.tape_seal_bound).

        A flush retires `runs_merged_eff` runs and needs that many
        resident, so the reachable floor from run_count rc is
        ``rc % runs_merged_eff``; raises ValueError when `n` exceeds
        ``R - that`` (the tape carries too many write keys — split it;
        `SLSM.tape_write_capacity` is the matching key budget)."""
        p = self.p
        floor = self._mirror().run_count % p.runs_merged_eff
        if n > p.R - floor:
            raise ValueError(
                f"cannot reserve {n} run slots: only {p.R - floor} "
                f"reachable (R={p.R}, {floor} unflushable resident runs)")
        while p.R - self._mirror().run_count < n:
            self.force_space(0)
            self.run_step(MergeStep(FLUSH, -1, step_cost(FLUSH, -1, p)))

    def voluntary_steps(self, budget: int) -> int:
        """Run up to `budget` ready steps, deepest-first, re-deriving the
        backlog after each (the same fixpoint semantics as `on_chunk`'s
        voluntary pass); returns how many ran. The maintenance governor's
        entry point (repro.serve): idle gaps and window boundaries spend
        accumulated budget here instead of pacing per insert chunk. A
        pending RETUNE rides the backlog like any merge."""
        ran = 0
        while ran < budget:
            step = self._next_ready()
            if step is None:
                break
            self.run_step(step)
            ran += 1
        return ran

    def on_read(self) -> None:
        """Decision boundary on the read path (adaptive tuning only —
        static engines never reach this, so their read path stays
        dispatch-for-dispatch identical to the pre-tuner engine).

        Reads only feed and roll the controller; they never *execute*
        maintenance — decisions bind at merge (write-chunk) boundaries,
        where `on_chunk` applies the RETUNE step and, in read mode,
        folds structure at catch-up pace. Keeping execution off the read
        path means a lookup's latency never absorbs a rebuild or merge:
        the read phase's trickle of writes is where that work lands.
        (`drain()` remains the barrier that applies everything,
        writes or not.)"""
        tuner = getattr(self.drv, "tuner", None)
        if tuner is None or not tuner.enabled:
            return
        tuner.decide()

    def drain(self) -> None:
        """Retire every pending step (the read-equivalence barrier).

        Deepest-ready-first until the backlog is empty; progress is
        guaranteed because a deeper step's execution is exactly what
        readies its shallower dependent. A pending allocation switch
        drains too: after drain() the engine is at rest *under its
        decided allocation*."""
        while True:
            backlog = pending_steps(self.p, self.policy, self.occupancy(),
                                    self._retune_pending())
            if not backlog:
                return
            step = self._next_ready()
            if step is None:   # pragma: no cover — invariant violation
                raise RuntimeError(
                    f"merge scheduler drain stalled with backlog {backlog}")
            self.run_step(step)

    @property
    def backlog(self) -> List[MergeStep]:
        """Current pending steps (introspection/telemetry)."""
        return pending_steps(self.p, self.policy, self.occupancy(),
                             self._retune_pending())

    # -- program warm-up ---------------------------------------------------

    def programs(self) -> list:
        """Every maintenance program this engine can dispatch, as
        ``(jitted_fn, abstract args)`` pairs for
        `precompile.compile_programs`.

        Static shapes make the set enumerable up front: each step op is
        jit-specialized on (params, levels-pytree structure, and for
        spills the static n_merge / annihilation flag), so the programs a
        run will ever need are exactly the combinations below. Without
        compiling them first, every first-use compile lands inside
        whichever insert chunk happens to trigger it: a stall the pacing
        budget cannot flatten, because it rides the very step dispatch
        that was paced. The jit cache is process-global, so same-param
        engines share the warmth.
        """
        from repro.engine.tuner import ReadModePolicy, retune_filters
        base, policy = self.drv.p, self.drv.policy
        tuner = getattr(self.drv, "tuner", None)
        # adaptive tuning: every preset is its own static-param program
        # set (the allocation is a jit-static argument), so warm each —
        # an allocation switch must not stall the chunk that pays for it;
        # the read-mode policy overlay adds its spill sizes to the set
        adaptive = tuner is not None and tuner.enabled
        if adaptive:
            param_sets = [alloc.apply(base)
                          for alloc in tuner.presets.values()]
            spill_sizes = sorted(set(policy.spill_sizes(base))
                                 | set(ReadModePolicy().spill_sizes(base)))
        else:
            param_sets = [base]
            spill_sizes = policy.spill_sizes(base)
        last = base.max_levels - 1
        progs = []
        for p in param_sets:
            rn = p.Rn
            for n_levels in range(p.max_levels + 1):
                st = state_shapes(p, n_levels)
                progs.append((stage_append,
                              (p, st, i32(rn), i32(rn), i32(rn), i32())))
                progs.append((seal_run, (p, st)))
                if len(param_sets) > 1:
                    progs.append((retune_filters, (p, st)))
                if n_levels == 0:
                    continue
                for drop in (True, False):
                    progs.append((merge_buffer_to_level0, (p, st, drop)))
                # spill of level l runs after its target l+1 materializes
                for lvl in range(min(n_levels - 1, last)):
                    for n_merge in spill_sizes:
                        for drop in (True, False):
                            progs.append((merge_level_down,
                                          (p, st, lvl, n_merge, drop)))
            progs.append((compact_last_level,
                          (p, state_shapes(p, p.max_levels))))
        return progs
