"""Sorted-run primitives: sort, weighted survivor dedup, k-way merge, fences.

TPU adaptation of the paper's run machinery, on the Z-set record algebra
(DESIGN.md §13): a record is ``(key, weight, seq | payload)`` with weight
+1 for an insert and -1 for a delete — structure-of-arrays, the payload
lane separate from the merge lanes.

  * a run is a dense sorted (keys, vals, wts, seqs) quad padded with
    KEY_EMPTY;
  * HeapMerge (paper 2.5, O(n log k) serial heap) becomes either
      - a `lax.sort` on (key, seq) — XLA's sort network, O(n log^2 n)
        comparisons but fully parallel; or
      - `merge_kway_ranked` — the rank-merge: every element's output slot is
        its own index plus its rank in every other run, computed with
        vectorized binary searches. O(n log k) *work*, data-independent
        control flow. Same asymptotics as the paper's heap, no heap.
  * weighted dedup: after a (key, seq)-ordered sort, the last element of
    every equal-key block carries the max seqno. Each op implicitly
    retracts its predecessor (an update is the Z-set -1/+1 pair fused
    into one record), so the per-key weight sum telescopes to the newest
    record's weight — presence is its sign, and the survivor mask is a
    shift-compare plus a sign test.
  * annihilation (zero-weight elision) happens only when merging into the
    deepest level (paper 2.5/2.8: deletes are "committed" there) —
    shallower merges keep the newest record per key even when its weight
    is negative, because it must still retract older copies below.
  * the Ghost property: merges move only the (key, weight, seq) lanes
    plus a provenance index through the sort/merge network; the payload
    lane is gathered once, at the end, for surviving rows only.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.params import KEY_EMPTY

# neutral "max key of an empty run" (min/max filters need a -inf)
_KEY_MIN = np.int32(np.iinfo(np.int32).min)


def sort_records(keys, vals, wts, seqs):
    """Lexicographic sort by (key, seq); vals/wts ride as payload.
    Sentinels sort to the end. Returns (keys, vals, wts, seqs).

    Every live record carries its own seqno, so (key, seq) pairs are
    unique and an unstable sort orders them exactly as a stable one
    would; only identical padding lanes can tie. The payload lanes ride
    a gather through the sorted source index instead of entering the
    sort: the TPU compiler's time for a sort grows with its operand
    count and with stability, and at deployment widths that time is
    what a cold engine start pays (DESIGN.md §13's Ghost shape)."""
    idx = jnp.arange(keys.shape[0], dtype=jnp.int32)
    keys, seqs, idx = jax.lax.sort((keys, seqs, idx), num_keys=2,
                                   is_stable=False)
    return keys, vals[idx], wts[idx], seqs


def survivor_mask(keys: jax.Array, wts: jax.Array,
                  drop_annihilated: bool) -> jax.Array:
    """Valid-mask over a (key, seq)-sorted run: keep the newest record of
    each key (the telescoped per-key weight sum); drop padding; when
    `drop_annihilated`, elide keys whose summed weight is <= 0 (deletes
    commit — the deepest-level merge)."""
    nxt = jnp.concatenate([keys[1:], jnp.full((1,), KEY_EMPTY, keys.dtype)])
    valid = (keys != KEY_EMPTY) & (keys != nxt)
    if drop_annihilated:
        valid &= wts > 0
    return valid


def compact(keys, vals, wts, seqs, valid):
    """Move the valid elements of a key-sorted run to the front; pad the
    rest. Returns (keys, vals, wts, seqs, count).

    Valid keys are unique (the survivor mask keeps one record per key),
    so re-sorting with every invalid lane's key set to KEY_EMPTY keeps
    the valid elements in their order — a stable partition bought with
    one unstable two-operand sort (see `sort_records` on why)."""
    order = _partition_order(keys, valid)
    ok = valid[order]
    keys = jnp.where(ok, keys[order], KEY_EMPTY)
    vals = jnp.where(ok, vals[order], 0)
    wts = jnp.where(ok, wts[order], 0)
    seqs = jnp.where(ok, seqs[order], 0)
    return keys, vals, wts, seqs, valid.sum(dtype=jnp.int32)


def _partition_order(keys, valid):
    """Gather order that moves the valid lanes of a key-sorted array with
    unique valid keys to the front, in key order."""
    masked = jnp.where(valid, keys, KEY_EMPTY)
    idx = jnp.arange(keys.shape[0], dtype=jnp.int32)
    _, order = jax.lax.sort((masked, idx), num_keys=1, is_stable=False)
    return order


def merge_runs(keys2d, vals2d, wts2d, seqs2d, drop_annihilated: bool):
    """Merge k sorted runs (k, cap) -> one compacted run (k*cap,).

    Sort-based path (XLA sort network) over the (key, seq, source-index)
    lanes only — weights and the payload lane never enter the sort.
    The per-key weight sum telescopes to the newest record (the sort is
    keyed on (key, seq) and dedup keeps the last copy — the paper's
    "highest-ranked run's value is written" rule, with run recency
    generalized to global seqnos); payloads are gathered through the
    surviving rows' source indices in one final pass (the Ghost
    property). Returns (keys, vals, wts, seqs, count).
    """
    k, s = keys2d.reshape(-1), seqs2d.reshape(-1)
    idx = jnp.arange(k.shape[0], dtype=jnp.int32)
    # (key, seq) is unique per live record: unstable == stable here
    k, s, idx = jax.lax.sort((k, s, idx), num_keys=2, is_stable=False)
    w = wts2d.reshape(-1)[idx]
    valid = survivor_mask(k, w, drop_annihilated)
    order = _partition_order(k, valid)
    ok = valid[order]
    keys = jnp.where(ok, k[order], KEY_EMPTY)
    wts = jnp.where(ok, w[order], 0)
    seqs = jnp.where(ok, s[order], 0)
    # payload gather — survivors only (annihilated rows never touch vals)
    vals = jnp.where(ok, vals2d.reshape(-1)[idx[order]], 0)
    return keys, vals, wts, seqs, valid.sum(dtype=jnp.int32)


def merge_two_ranked(ak, av, aw, as_, bk, bv, bw, bs):
    """Rank-merge of two sorted runs — the TPU HeapMerge step.

    out_pos(a[i]) = i + #{b[j] < a[i] by (key, seq)};  symmetrical for b.
    Both ranks come from two vectorized binary searches; the scatter is a
    permutation, so the result is sorted by (key, seq) and stable.
    Padding (KEY_EMPTY) naturally ranks to the tail.
    """
    n, mth = ak.shape[0], bk.shape[0]

    # rank = lexicographic lower_bound over (key, seq): runs are sorted by
    # (key, seq) — including intermediate tournament rounds, which may hold
    # duplicate keys — so a branch-free binary search with the pairwise
    # comparator is exact. O(n log m) work, fully lane-parallel.
    def rank_in(other_k, other_s, qk, qs):
        size = other_k.shape[0]
        steps = max(1, math.ceil(math.log2(size + 1)))
        lo = jnp.zeros(qk.shape, jnp.int32)
        hi = jnp.full(qk.shape, size, jnp.int32)

        def body(_, lohi):
            lo, hi = lohi
            mid = (lo + hi) // 2
            midc = jnp.clip(mid, 0, size - 1)
            ok_, os_mid = other_k[midc], other_s[midc]
            before = (ok_ < qk) | ((ok_ == qk) & (os_mid < qs))
            active = lo < hi
            new_lo = jnp.where(before, mid + 1, lo)
            new_hi = jnp.where(before, hi, mid)
            return (jnp.where(active, new_lo, lo),
                    jnp.where(active, new_hi, hi))

        lo, _ = jax.lax.fori_loop(0, steps, body, (lo, hi))
        return lo

    pa = jnp.arange(n, dtype=jnp.int32) + rank_in(bk, bs, ak, as_)
    pb = jnp.arange(mth, dtype=jnp.int32) + rank_in(ak, as_, bk, bs)
    total = n + mth
    ok = jnp.full((total,), KEY_EMPTY, ak.dtype).at[pa].set(ak).at[pb].set(bk)
    ov = jnp.zeros((total,), av.dtype).at[pa].set(av).at[pb].set(bv)
    ow = jnp.zeros((total,), aw.dtype).at[pa].set(aw).at[pb].set(bw)
    os_ = jnp.zeros((total,), as_.dtype).at[pa].set(as_).at[pb].set(bs)
    return ok, ov, ow, os_


def merge_kway_ranked(keys2d, vals2d, wts2d, seqs2d, drop_annihilated: bool):
    """Tournament of rank-merges: log2(k) parallel passes (paper-equivalent
    O(n log k) work). Used by benchmarks to compare against `merge_runs`."""
    runs = [(keys2d[i], vals2d[i], wts2d[i], seqs2d[i])
            for i in range(keys2d.shape[0])]
    while len(runs) > 1:
        nxt = []
        for i in range(0, len(runs) - 1, 2):
            nxt.append(merge_two_ranked(*runs[i], *runs[i + 1]))
        if len(runs) % 2:
            nxt.append(runs[-1])
        runs = nxt
    k, v, w, s = runs[0]
    valid = survivor_mask(k, w, drop_annihilated)
    return compact(k, v, w, s, valid)


def build_fences(keys: jax.Array, mu: int, n_fences: int) -> jax.Array:
    """Fence pointers (paper 2.4): the key at every mu-th slot."""
    idx = jnp.arange(n_fences, dtype=jnp.int32) * mu
    return keys[jnp.clip(idx, 0, keys.shape[0] - 1)]


def run_minmax(keys: jax.Array, count: jax.Array):
    """(min, max) key of a compacted sorted run (paper 2.3 max/min filter)."""
    mn = jnp.where(count > 0, keys[0], KEY_EMPTY)
    mx = jnp.where(count > 0, keys[jnp.maximum(count - 1, 0)], _KEY_MIN)
    return mn, mx
