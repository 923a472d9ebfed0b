#!/usr/bin/env python3
"""Record the small device trace that the trace-reduction test reads.

    python3 bench_tpu/record_trace.py OUT.xplane.pb.gz

Runs on a TPU (refuses elsewhere): builds the store at a tiny geometry,
warms it, runs a few inserts (with a flush), a lookup, a range scan and
one served window once, then profiles the same calls again, each inside
the benchmark's own `TraceAnnotation` spans and all in its window span.
Writes the profiler's `.xplane.pb`, gzipped, to OUT and prints the
planes and lines it holds and their reduction (`bench_tpu/xplane.py`).
"""
from __future__ import annotations

import contextlib
import glob
import gzip
import os
import shutil
import sys
import tempfile

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv: list[str]) -> int:
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    out = argv[0]
    import jax

    d0 = jax.devices()[0]
    if d0.platform != "tpu":
        print(f"record_trace: needs a TPU, found {d0.platform}",
              file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from repro.core.params import SLSMParams
    from repro.engine import SLSM
    from repro.serve import Server

    from bench_tpu import xplane

    p = SLSMParams(R=4, Rn=64, eps=1e-3, D=4, m=1.0, mu=64, max_levels=2,
                   merge_budget=1, range_cand=64, max_range=64)
    store = SLSM(p)
    store.warm(buckets=(256,))
    store.warm_tape()
    rng = np.random.default_rng(1)
    keys = (rng.choice(1 << 20, 2048, replace=False) * 2).astype(np.int32)
    store.insert(keys[:1024], keys[:1024])
    srv = Server(store)

    def ops(lo: int, span):
        """Two inserts (the second flushes), a lookup, a scan and a served
        window of reads and writes, each in its `bench.*` span."""
        for i in range(2):
            part = keys[lo + 128 * i:lo + 128 * (i + 1)]
            with span("bench.insert"):
                store.insert(part, part)
                jax.block_until_ready(store.state)
        with span("bench.lookup"):
            store.lookup_many(keys[rng.integers(0, 2048, 256)])
        with span("bench.range"):
            store.range_many([(int(k), int(k) + 4096) for k in keys[:8]])
        for k in keys[lo:lo + 3]:
            srv.submit("c", "lookup", [int(k)])
            srv.submit("c", "insert", [int(k)], [7])
        with span("bench.pump"):
            srv.pump(force=True)

    ops(1024, lambda name: contextlib.nullcontext())   # traces, compiles
    tmp = tempfile.mkdtemp()
    try:
        xplane.start(tmp)
        with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
            ops(1536, jax.profiler.TraceAnnotation)
        xplane.stop()
        path, = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True)
        with open(path, "rb") as f, gzip.open(out, "wb") as g:
            shutil.copyfileobj(f, g)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"{out}: {os.path.getsize(out)} bytes")
    print(xplane.describe(out))
    print(xplane.reduce(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
