"""The control: the plain reference in the store's place, one guarantee
broken.

The configurations state that the newest write of a key wins (paper
Section 2.7: newest-to-oldest lookup; deletes are newest-wins
tombstones). The control keeps the first live write instead: a write or
delete of a key that already holds a live value is dropped. It is what
a store that lost or reordered recency would answer. Run with
``bench_tpu/run.py --control``; the benchmark's own runs never use it,
and its `correct` has to come out false in every cell.
"""
from __future__ import annotations

import collections
import time

import numpy as np

from bench_tpu.reference import Table


class FirstWriteStore:
    """`SLSM`'s calls that the benchmark drives, answered by a `Table`
    that keeps the first live write of each key."""

    def __init__(self, space, max_range: int):
        self.table = Table(space)
        self.max_range = max_range
        self.stats = collections.Counter(writes=0)
        self.durability = None
        self.state = None

    def warm(self, buckets=()) -> None:
        pass

    def warm_tape(self, buckets=()) -> None:
        pass

    def insert(self, keys, vals) -> None:
        keys = np.asarray(keys, np.int32).reshape(-1)
        vals = np.asarray(vals, np.int32).reshape(-1)
        self.stats["writes"] += keys.size
        _, live = self.table.lookup(keys)
        self.table.write(keys[~live], vals[~live])

    def delete(self, keys) -> None:
        keys = np.asarray(keys, np.int32).reshape(-1)
        self.stats["writes"] += keys.size

    def lookup_many(self, keys):
        return self.table.lookup(keys)

    def range_many(self, ranges):
        r = np.asarray(ranges, np.int32).reshape(-1, 2)
        q, mr = r.shape[0], self.max_range
        keys = np.zeros((q, mr), np.int32)
        vals = np.zeros((q, mr), np.int32)
        counts = np.zeros(q, np.int32)
        trunc = np.zeros(q, bool)
        for i, (lo, hi) in enumerate(r.tolist()):
            ks, vs = self.table.range(lo, hi)
            c = min(ks.size, mr)
            keys[i, :c], vals[i, :c], counts[i] = ks[:c], vs[:c], c
            trunc[i] = ks.size > mr
        return keys, vals, counts, trunc


class Ticket:
    __slots__ = ("client", "kind", "keys", "vals", "t_enqueue", "t_reply",
                 "result", "error")

    def __init__(self, client, kind, keys, vals, t):
        self.client, self.kind, self.keys, self.vals = client, kind, keys, vals
        self.t_enqueue, self.t_reply = t, None
        self.result, self.error = None, None

    @property
    def done(self) -> bool:
        return self.t_reply is not None


class FirstWriteServer:
    """`Server`'s submit/pump over a `FirstWriteStore`: each pump serves
    everything pending, in submission order."""

    def __init__(self, store: FirstWriteStore):
        self.tree = store
        self._pending: list[Ticket] = []
        self.counters = collections.Counter()

    @property
    def pending(self) -> int:
        return len(self._pending)

    def poll(self) -> bool:
        return bool(self._pending)

    def submit(self, client, kind, keys, vals=None) -> Ticket:
        keys = np.asarray(keys, np.int32).reshape(-1)
        vals = (np.zeros_like(keys) if vals is None
                else np.asarray(vals, np.int32).reshape(-1))
        t = Ticket(client, kind, keys, vals, time.perf_counter())
        self._pending.append(t)
        return t

    def pump(self, force: bool = False) -> int:
        batch, self._pending = self._pending, []
        s = self.tree
        for t in batch:
            if t.kind == "insert":
                s.insert(t.keys, t.vals)
            else:
                t.result = s.lookup_many(t.keys)
        now = time.perf_counter()
        for t in batch:
            t.t_reply = now
        if batch:
            self.counters["windows"] += 1
        return len(batch)
