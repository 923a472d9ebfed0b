"""The benchmark's comparison catches a broken timed path.

Each case runs a cell at tiny sizes on the CPU with one fault planted in
the store's own calls (the chip check is skipped, the rest of the run is
the benchmark's), and sees `correct` come out false:

* unchanged: a write returns with the state unchanged;
* half: half of the batch is left out;
* altered: one answer (or one written value) is altered where it is
  produced;
* cut short: scans come back empty, flagged `truncated`, within their
  budget.

A single chip holds the whole tree, so no exchange between chips can be
left out.
"""
import numpy as np
import pytest

from bench_tpu_tiny import correct, run_tiny
from repro.engine import SLSM

_insert, _lookup, _range, _tape = (SLSM.insert, SLSM.lookup_many,
                                   SLSM.range_many, SLSM.run_tape)


def insert_unchanged(self, keys, vals):
    self.stats["writes"] += np.asarray(keys).size


def insert_half(self, keys, vals):
    keys, vals = np.asarray(keys), np.asarray(vals)
    h = keys.size // 2
    _insert(self, keys[:h], vals[:h])
    self.stats["writes"] += keys.size - h


def insert_altered(self, keys, vals):
    vals = np.array(vals, np.int32)
    vals[0] += 1
    _insert(self, keys, vals)


def lookup_half(self, keys, sparse=False):
    keys = np.asarray(keys)
    h = keys.size // 2
    v, f = _lookup(self, keys[:h])
    return (np.concatenate([v, np.zeros(keys.size - h, v.dtype)]),
            np.concatenate([f, np.zeros(keys.size - h, bool)]))


def lookup_altered(self, keys, sparse=False):
    v, f = _lookup(self, keys)
    v = v.copy()
    v[np.flatnonzero(f)[:1]] += 1
    return v, f


def range_half(self, ranges):
    k, v, c, t = _range(self, ranges)
    c = c.copy()
    c[c.size // 2:] = 0
    return k, v, c, t


def range_cut_short(self, ranges):
    k, v, c, t = _range(self, ranges)
    return k, v, np.zeros_like(c), np.ones_like(t)


def range_altered(self, ranges):
    k, v, c, t = _range(self, ranges)
    v = v.copy()
    i = np.flatnonzero(c)[:1]
    v[i, 0] += 1
    return k, v, c, t


def tape_unchanged(self, chunks, sparse=False):
    kept = [c for c in chunks if c[0] != "write"]
    out = iter(_tape(self, kept, sparse))
    self.stats["writes"] += sum(len(c[1]) for c in chunks
                                if c[0] == "write")
    return [0 if c[0] == "write" else next(out) for c in chunks]


def tape_half(self, chunks, sparse=False):
    h = len(chunks) // 2
    res = _tape(self, chunks[:h], sparse)
    self.stats["writes"] += sum(len(c[1]) for c in chunks[h:]
                                if c[0] == "write")
    for c in chunks[h:]:
        n = len(c[1])
        res.append(0 if c[0] == "write" else
                   (np.zeros(n, np.int32), np.zeros(n, bool)))
    return res


def tape_altered(self, chunks, sparse=False):
    res = _tape(self, chunks, sparse)
    for i, c in enumerate(chunks):
        if c[0] == "lookup":
            v, f = res[i]
            res[i] = (v + 1, f)
            break
    return res


FAULTS = [
    ("ingest.s3-durable", "insert", insert_unchanged),
    ("ingest.s3-durable", "insert", insert_half),
    ("ingest.s3-durable", "insert", insert_altered),
    ("lookup.s3-volatile", "lookup_many", lookup_half),
    ("lookup.s3-volatile", "lookup_many", lookup_altered),
    ("scan.s3-volatile", "range_many", range_half),
    ("scan.s3-volatile", "range_many", range_altered),
    ("scan.s3-volatile", "range_many", range_cut_short),
    ("serve-a.s3-durable", "run_tape", tape_unchanged),
    ("serve-a.s3-durable", "run_tape", tape_half),
    ("serve-a.s3-durable", "run_tape", tape_altered),
]


@pytest.mark.parametrize("cell,method,fault", FAULTS,
                         ids=[f.__name__ for _, _, f in FAULTS])
def test_fault_is_caught(monkeypatch, cell, method, fault):
    monkeypatch.setattr(SLSM, method, fault)
    run, checks = run_tiny(cell, seed=12)
    assert not correct(run, checks), checks
    if fault is range_cut_short:        # only the new count catches it
        assert checks["wrong_answers"] == 0 < checks["truncated_answers"]
