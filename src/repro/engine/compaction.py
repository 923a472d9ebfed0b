"""The Do-Merge cascade (paper Algorithm 2 / 2.5) as explicit policy + ops.

Device side: three jitted merge ops (buffer flush, level spill, deepest
compaction), all built on the backend-dispatched k-way merge — so the
paper's HeapMerge runs either as the XLA sort network or as the Pallas
merge-path tournament (`SLSMParams.backend`). Records are weighted
(DESIGN.md §13): merges move (key, weight, seq) lanes and gather
payloads only for surviving rows.

Host side: a `CompactionPolicy` decides *when* a level spills and *how
many* runs move — the axis along which real LSM systems specialize
(tiering vs leveling, cf. the Luo & Carey survey):

  TieringPolicy  — the paper's rule: wait until a level holds D runs,
                   then merge the ceil(m*D) oldest into the next level.
                   Lowest write amplification.
  LevelingPolicy — eager variant: merge a level's runs down as soon as
                   two coexist, keeping read amplification at ~1 run per
                   level at the cost of more merge work.

Annihilation stays a host decision (`scheduler.drop_annihilated_into`):
negative-weight records are elided only when a merge's output becomes
the deepest data (paper 2.5/2.8: deletes are committed there). *When*
these ops run is the merge scheduler's call (`repro.engine.scheduler`):
each op here is exactly one bounded `MergeStep`, dispatched either
synchronously (merge_budget=0) or paced across insert chunks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.params import KEY_EMPTY, SLSMParams
from repro.engine.backend import get_backend
from repro.engine.levels import (_KEY_MIN, empty_level, index_new_run,
                                 set_level_run, shift_level)
from repro.engine.memtable import SLSMState


# --------------------------------------------------------------------------
# host-driven merge policies
# --------------------------------------------------------------------------

class CompactionPolicy:
    """Decides when a disk level spills and how many runs move down."""

    name = "abstract"

    def validate(self, p: SLSMParams) -> None:
        """Raise if the parameter geometry cannot support this policy."""

    def needs_spill(self, p: SLSMParams, n_runs: int,
                    level: int = 0) -> bool:
        """Should a level holding `n_runs` runs be merged down? `level`
        lets depth-aware policies (the tuner's read-mode overlay) treat
        shallow and deep tiers differently; the paper's policies ignore
        it."""
        raise NotImplementedError

    def runs_to_spill(self, p: SLSMParams, n_runs: int) -> int:
        """How many of the level's oldest runs one spill moves down
        (jit-static: each distinct value is its own merge program)."""
        raise NotImplementedError

    def spill_sizes(self, p: SLSMParams) -> tuple:
        """Every distinct `runs_to_spill` value this policy can produce.

        The engine's warm() precompiles one spill program per
        (level, size, annihilation-flag) — `n_merge` is a jit-static
        argument, so each size is its own compiled program and an
        unwarmed size would stall the first insert chunk that needs it.
        """
        raise NotImplementedError


class TieringPolicy(CompactionPolicy):
    """The paper's policy (2.5): spill ceil(m*D) runs once a level is full."""

    name = "tiering"

    def needs_spill(self, p: SLSMParams, n_runs: int,
                    level: int = 0) -> bool:
        return n_runs >= p.D

    def runs_to_spill(self, p: SLSMParams, n_runs: int) -> int:
        """The paper's ceil(m*D) oldest runs (2.5), regardless of depth."""
        return p.disk_runs_merged

    def spill_sizes(self, p: SLSMParams) -> tuple:
        return (p.disk_runs_merged,)


class LevelingPolicy(CompactionPolicy):
    """Leveling variant: merge a level down as soon as `max_resident` runs
    coexist, so a level holds ~1 run at rest — fewer runs on the read
    path (each lookup probes at most `max_resident` runs per level)
    bought with more merge work, the classic tiering/leveling trade.
    Requires ceil(m*D) >= max_resident so a spill's output always fits
    one run of the next level."""

    name = "leveling"

    def __init__(self, max_resident: int = 2):
        if max_resident < 2:
            raise ValueError("max_resident must be >= 2")
        self.max_resident = max_resident

    def validate(self, p: SLSMParams) -> None:
        if p.D < self.max_resident:
            raise ValueError(
                f"LevelingPolicy(max_resident={self.max_resident}) needs "
                f"D >= {self.max_resident} run slots per level (D={p.D})")
        if p.disk_runs_merged < self.max_resident:
            raise ValueError(
                "LevelingPolicy needs ceil(m*D) >= max_resident so a spill "
                f"fits the next level's run capacity (ceil(m*D)="
                f"{p.disk_runs_merged}, max_resident={self.max_resident})")

    def needs_spill(self, p: SLSMParams, n_runs: int,
                    level: int = 0) -> bool:
        return n_runs >= self.max_resident

    def runs_to_spill(self, p: SLSMParams, n_runs: int) -> int:
        """All resident runs: a leveling spill leaves its level empty."""
        return n_runs

    def spill_sizes(self, p: SLSMParams) -> tuple:
        # a level spills at max_resident occupancy but can reach D runs
        # before the scheduler gets to it (forced chains, deferred steps)
        return tuple(range(self.max_resident, p.D + 1))


# --------------------------------------------------------------------------
# jitted merge ops (all k-way merges dispatch through the backend)
# --------------------------------------------------------------------------

def merge_buffer_to_level0_impl(p: SLSMParams, state: SLSMState,
                                drop_annihilated: bool) -> SLSMState:
    """Flush ceil(m*R_eff) oldest memory runs into disk level 0 (paper
    2.1/2.5). R_eff == R unless the tuner's write-buffer arm shrank the
    active buffer (DESIGN.md §9); level-0 capacity is sized from the
    physical R, so a smaller flush always fits."""
    be = get_backend(p.backend)
    mr = p.runs_merged_eff
    k, v, w, s, cnt = be.merge_runs(state.buf_keys[:mr], state.buf_vals[:mr],
                                    state.buf_wts[:mr], state.buf_seqs[:mr],
                                    drop_annihilated)
    k, v, w, s, filt, fences, mn, mx = index_new_run(p, 0, k, v, w, s, cnt)
    lv0 = set_level_run(state.levels[0], state.levels[0].n_runs,
                        k, v, w, s, cnt, filt, fences, mn, mx)

    def roll(a, fill):
        tail_shape = (mr,) + a.shape[1:]
        return jnp.concatenate([a[mr:], jnp.full(tail_shape, fill, a.dtype)])

    return state._replace(
        buf_keys=roll(state.buf_keys, KEY_EMPTY),
        buf_vals=roll(state.buf_vals, 0),
        buf_wts=roll(state.buf_wts, 0),
        buf_seqs=roll(state.buf_seqs, 0),
        buf_counts=roll(state.buf_counts, 0),
        buf_mins=roll(state.buf_mins, KEY_EMPTY),
        buf_maxs=roll(state.buf_maxs, _KEY_MIN),
        buf_blooms=roll(state.buf_blooms, 0),
        run_count=state.run_count - mr,
        levels=(lv0,) + state.levels[1:],
    )


merge_buffer_to_level0 = functools.partial(
    jax.jit, static_argnums=(0, 2), donate_argnums=1)(
        merge_buffer_to_level0_impl)


def merge_level_down_impl(p: SLSMParams, state: SLSMState, level: int,
                          n_merge: int, drop_annihilated: bool) -> SLSMState:
    """Merge the `n_merge` oldest runs of `level` into one run of `level+1`.

    `n_merge` is the policy's `runs_to_spill` (ceil(m*D) for tiering, the
    level's occupancy for leveling)."""
    be = get_backend(p.backend)
    src = state.levels[level]
    k, v, w, s, cnt = be.merge_runs(src.keys[:n_merge], src.vals[:n_merge],
                                    src.wts[:n_merge], src.seqs[:n_merge],
                                    drop_annihilated)
    k, v, w, s, filt, fences, mn, mx = index_new_run(p, level + 1,
                                                     k, v, w, s, cnt)
    dst = state.levels[level + 1]
    dst = set_level_run(dst, dst.n_runs, k, v, w, s, cnt, filt, fences,
                        mn, mx)
    src = shift_level(p, src, n_merge)
    levels = (state.levels[:level] + (src, dst)
              + state.levels[level + 2:])
    return state._replace(levels=levels)


merge_level_down = functools.partial(
    jax.jit, static_argnums=(0, 2, 3, 4), donate_argnums=1)(
        merge_level_down_impl)


def compaction_rows(p: SLSMParams) -> tuple:
    """``(rows, width)`` of the deepest compaction's merge input.

    Slot 0 of the deepest level may hold a whole earlier compaction
    (`level_cap` rows), but every other slot was filled by one spill
    from the level above — at most D * level_cap(last - 1) rows — or,
    when the deepest level is level 0, by one flush (at most
    runs_merged * Rn rows). So the merge reads slot 0 cut into sorted
    pieces of that width plus the first `width` lanes of each other
    slot: the rest of their capacity is always padding. At the one-chip
    paper geometry that is 31.5M lanes instead of D * level_cap =
    323.6M, which is what makes the compaction's temporaries small."""
    last = p.max_levels - 1
    cap = p.level_cap(last)
    width = (p.D * p.level_cap(last - 1) if last > 0
             else p.runs_merged * p.Rn)
    width = min(width, cap)
    return -(-cap // width) + p.D - 1, width


def compact_last_level_impl(p: SLSMParams, state: SLSMState):
    """In-place compaction of the deepest level: merge all D runs into slot 0.

    This is always the deepest data, so annihilation commits here (paper
    2.5: 'keys flagged for delete are not written ... at all' — the
    newest record's weight sums to <= 0 and the row is dropped).
    Returns (state, raw_count); the host raises if raw_count exceeds the
    deepest run capacity (the TPU analogue of running out of disk).
    Donates the state like the other steps, and merges only the lanes a
    run can occupy (`compaction_rows`): at deployment geometry a second
    state, or a merge over every slot's full capacity, does not fit one
    chip."""
    be = get_backend(p.backend)
    last = p.max_levels - 1
    lv = state.levels[last]
    rows, width = compaction_rows(p)
    head_pad = (rows - p.D + 1) * width - p.level_cap(last)

    def pieces(a, fill):
        head = jnp.concatenate([a[0], jnp.full((head_pad,), fill, a.dtype)])
        return jnp.concatenate([head.reshape(-1, width), a[1:, :width]])

    k, v, w, s, cnt = be.merge_runs(pieces(lv.keys, KEY_EMPTY),
                                    pieces(lv.vals, 0), pieces(lv.wts, 0),
                                    pieces(lv.seqs, 0), True)
    k, v, w, s, filt, fences, mn, mx = index_new_run(p, last, k, v, w, s, cnt)
    fresh = empty_level(p, last)
    fresh = set_level_run(fresh, 0, k, v, w, s,
                          jnp.minimum(cnt, p.level_cap(last)),
                          filt, fences, mn, mx)
    return state._replace(levels=state.levels[:last] + (fresh,)), cnt


compact_last_level = functools.partial(
    jax.jit, static_argnums=0, donate_argnums=1)(compact_last_level_impl)
