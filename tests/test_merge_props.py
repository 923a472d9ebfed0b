"""HeapMerge hypothesis sweep: sort-based, rank-based, and the Pallas
tournament agree with `oracle_merge` on arbitrary weighted run sets
(keys, vals, weights, seqs — DESIGN.md §13) — module degrades to a skip
when hypothesis is not installed."""
import pytest

pytest.importorskip("hypothesis")

import numpy as np
from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from repro.core import runs as RU
from repro.kernels.heap_merge import heap_merge_op
from test_merge import make_runs, oracle_merge


@settings(max_examples=25, deadline=None,
          suppress_health_check=list(HealthCheck))
@given(k=st.integers(2, 5), cap=st.sampled_from([16, 64, 96]),
       seed=st.integers(0, 10**6), drop=st.booleans())
def test_merge_paths_agree(k, cap, seed, drop):
    rng = np.random.default_rng(seed)
    K, V, W, S = make_runs(rng, k, cap)
    expect = oracle_merge(np.asarray(K), np.asarray(V), np.asarray(W),
                          np.asarray(S), drop)

    for fn in (RU.merge_runs, RU.merge_kway_ranked, heap_merge_op):
        mk, mv, mw, ms, cnt = fn(K, V, W, S, drop)
        got = list(zip(np.asarray(mk)[:int(cnt)].tolist(),
                       np.asarray(mv)[:int(cnt)].tolist(),
                       np.asarray(mw)[:int(cnt)].tolist(),
                       np.asarray(ms)[:int(cnt)].tolist()))
        assert got == expect, fn.__name__
