"""Plain reference of the store's semantics: a last-write-wins table.

Shares no code with the store under test. Keys of the benchmark's key
universe live in dense arrays indexed by their position in it; any other
key goes to a dict. A write of weight +1 sets a key's value, a delete
(weight -1) removes it, and within one call the last lane of a key wins,
as later calls win over earlier ones.

It also counts every write lane ever applied to each key: no store can
hold more records of a key window, in all its structures together, than
were written into it, so where that count is within a scan's declared
budget the scan may not come back cut short (`truncation_errors`).
"""
from __future__ import annotations

import collections

import numpy as np


def last_lanes(idx: np.ndarray) -> np.ndarray:
    """Positions of the last occurrence of each distinct value of `idx`."""
    rev = idx[::-1]
    _, first_in_rev = np.unique(rev, return_index=True)
    return idx.size - 1 - first_in_rev


class Table:
    """Key -> value table over a `KeySpace` (see `bench_tpu.traffic`)."""

    def __init__(self, space):
        self.space = space
        self.vals = np.zeros(space.size, np.int32)
        self.live = np.zeros(space.size, bool)
        self.writes = np.zeros(space.size, np.int64)   # lanes ever written
        self.extra: dict[int, int] = {}
        self.extra_writes: collections.Counter = collections.Counter()

    def write(self, keys, vals, live: bool = True) -> None:
        """Apply one call's writes (inserts, or deletes with ``live=False``)
        in lane order."""
        keys = np.asarray(keys, np.int32).reshape(-1)
        vals = np.asarray(vals, np.int32).reshape(-1)
        idx, inside = self.space.index(keys)
        if not inside.all():
            for k, v in zip(keys[~inside].tolist(), vals[~inside].tolist()):
                self.extra_writes[k] += 1
                if live:
                    self.extra[k] = v
                else:
                    self.extra.pop(k, None)
            idx, vals = idx[inside], vals[inside]
        np.add.at(self.writes, idx, 1)
        last = last_lanes(idx)
        self.vals[idx[last]] = vals[last] if live else 0
        self.live[idx[last]] = live

    def lookup(self, keys):
        """(vals, found) for each key; vals are 0 where not found."""
        keys = np.asarray(keys, np.int32).reshape(-1)
        idx, inside = self.space.index(keys)
        safe = np.where(inside, idx, 0)
        found = inside & self.live[safe]
        vals = np.where(found, self.vals[safe], 0).astype(np.int32)
        if self.extra:
            for j in np.flatnonzero(~inside).tolist():
                v = self.extra.get(int(keys[j]))
                if v is not None:
                    found[j], vals[j] = True, v
        return vals, found

    def range(self, lo: int, hi: int):
        """Live (keys, vals) with lo <= key < hi, key-sorted."""
        a, b = self.space.index_bounds(lo, hi)
        sel = a + np.flatnonzero(self.live[a:b])
        ks, vs = self.space.keys_at(sel), self.vals[sel]
        more = sorted((k, v) for k, v in self.extra.items() if lo <= k < hi)
        if more:
            ks = np.concatenate([ks, np.asarray([k for k, _ in more],
                                                np.int32)])
            vs = np.concatenate([vs, np.asarray([v for _, v in more],
                                                np.int32)])
            order = np.argsort(ks, kind="stable")
            ks, vs = ks[order], vs[order]
        return ks, vs

    def records(self, lo: int, hi: int) -> int:
        """Write lanes ever applied to keys in [lo, hi)."""
        a, b = self.space.index_bounds(lo, hi)
        return int(self.writes[a:b].sum()) + sum(
            n for k, n in self.extra_writes.items() if lo <= k < hi)


def range_errors(table: Table, lo: int, hi: int, keys, vals, count: int,
                 truncated: bool) -> int:
    """1 if one scan's answer disagrees with `table`, else 0. A truncated
    answer must be a sorted prefix of the window's live keys; an
    untruncated one must be all of it."""
    rk, rv = table.range(lo, hi)
    if count > rk.size or (not truncated and count != rk.size):
        return 1
    same = (np.array_equal(np.asarray(keys)[:count], rk[:count])
            and np.array_equal(np.asarray(vals)[:count], rv[:count]))
    return 0 if same else 1


def truncation_errors(table: Table, lo: int, hi: int, truncated: bool,
                      budget: int) -> int:
    """1 if one scan came back cut short although it cannot have exceeded
    its budget: `truncated` is allowed only past ``max_range`` live keys
    or past ``range_cand`` candidate records over all structures, and
    both are at most the records ever written into the window, so a
    window of at most ``budget = min(range_cand, max_range)`` written
    records must come back whole."""
    return int(bool(truncated) and table.records(lo, hi) <= budget)
