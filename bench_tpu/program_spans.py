#!/usr/bin/env python3
"""The store's own profiler spans in a traced window: where the device
waits, by the layer of the program that holds it.

    python3 bench_tpu/program_spans.py --workload <cell> --seed <n> \\
        --seconds <s>
    python3 bench_tpu/program_spans.py TRACE.xplane.pb[.gz]

The first form runs one traced window of a cell (`harness.run_cell`, as
``run.py --trace 1`` does) and keeps its profile; the second reads a
recorded one. Both print one JSON object: the kept window and busy time
(`bench_tpu/xplane.py`'s reduction, whose rules hold here too),
`program_spans` and `idle_in` below, and the shares and times they give
(`derived`).

The program's spans are the plain `TraceAnnotation`s named ``slsm.*``
and ``wal.*`` that `repro.engine` opens around its layers (the driver's
write call, staging, the scheduler and its steps, the write-ahead log,
the read calls and their result fetch), on the thread of the window:

* `program_spans`: ``{name: [count, total s, self s]}`` of the spans
  inside the kept window; self time leaves out what child program spans
  cover;
* `idle_in`: ``{name: s}``: each idle gap of the device, split by
  overlap over the innermost program span covering each part of it,
  ``"outside"`` where none does (the benchmark's own code); averaged
  over the devices as busy time is, it sums to the window less busy.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PREFIXES = ("slsm.", "wal.")    # name prefixes of the program's spans
OUTSIDE = "outside"


def reduce_profile(pd) -> dict:
    """The program's spans in a loaded trace (anything with
    `ProfileData`'s planes, lines and events)."""
    from bench_tpu import xplane

    red = xplane.reduce_profile(pd)
    host, = [pl for pl in pd.planes if pl.name == "/host:CPU"]
    (w0, w_end), thread = next(
        ((e.start_ns, e.end_ns), line) for line in host.lines
        for e in line.events if e.name == xplane.WINDOW_SPAN)
    w1 = w0 + red.window_s * 1e9    # the kept window's end
    spans = sorted(((e.start_ns, e.end_ns, e.name) for e in thread.events
                    if e.name.startswith(PREFIXES) and e.start_ns >= w0
                    and e.end_ns <= w1), key=lambda v: (v[0], -v[1]))
    pieces = _innermost(spans, w0, w1)
    idle: collections.Counter = collections.Counter()
    devices = 0
    for plane in pd.planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        lines = {ln.name: list(ln.events) for ln in plane.lines}

        def clip(name, lo, hi):
            return [(max(e.start_ns, lo), min(e.end_ns, hi))
                    for e in lines.get(name, ())
                    if e.end_ns > lo and e.start_ns < hi]

        if not (clip(xplane.MODULES_LINE, w0, w_end)
                or clip(xplane.OPS_LINE, w0, w_end)):
            continue
        devices += 1
        busy = xplane.union(clip(xplane.MODULES_LINE, w0, w1) or [
            (s, t) for s, t in clip(xplane.OPS_LINE, w0, w1) if t > s])
        edges = [w0] + [x for st in busy for x in st] + [w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        for label, secs in _split_gaps(gaps, pieces):
            idle[label] += secs
    return {"window_s": red.window_s, "busy_s": red.busy_s,
            "truncated": red.truncated, "calls_kept": red.calls_kept,
            "program_spans": _self_times(spans),
            "idle_in": {k: v / devices for k, v in idle.most_common()}}


def reduce(path) -> dict:
    from bench_tpu import xplane

    return reduce_profile(xplane.load(path))


def derived(out: dict) -> dict:
    """Shares of the window (%) and mean times (ms) of the spans that
    tell the write and scan paths' layers apart."""
    w, idle = out["window_s"], out["idle_in"]
    rows = out["program_spans"]
    res = {}
    if w > 0 and rows:
        for prefix, name in (("slsm.", "device_idle.driver"),
                             ("wal.", "device_idle.wal")):
            res[name] = 100.0 * sum(v for k, v in idle.items()
                                    if k.startswith(prefix)) / w
        res["device_idle.fetch"] = 100.0 * idle.get("slsm.fetch", 0.0) / w
    if "slsm.schedule" in rows:
        n, _, self_s = rows["slsm.schedule"]
        res["sched.self_ms_per_span"] = self_s / n * 1e3
    if "wal.commit" in rows:
        n, total, _ = rows["wal.commit"]
        res["wal.commit_ms_per_call"] = total / n * 1e3
    return res


def _self_times(spans) -> dict:
    """{name: [count, total s, self s]} of nested (start, end, name)
    spans sorted by start, outer first: a span's self time is its
    duration less what its child spans cover."""
    out: dict = {}
    stack: list = []    # (end, row) of the spans enclosing the next
    for s, e, name in spans:
        while stack and stack[-1][0] <= s:
            stack.pop()
        row = out.setdefault(name, [0, 0.0, 0.0])
        row[0] += 1
        row[1] += (e - s) * 1e-9
        row[2] += (e - s) * 1e-9
        if stack:
            stack[-1][1][2] -= (min(e, stack[-1][0]) - s) * 1e-9
        stack.append((e, row))
    return out


def _innermost(spans, w0, w1) -> list:
    """[w0, w1] cut into consecutive (start, end, label) pieces, each
    labelled by the innermost of the nested `spans` (as `_self_times`
    takes them) covering it, `OUTSIDE` where none does."""
    pieces: list = []
    stack: list = []    # (end, name) of the open spans
    at = w0

    def cut(upto) -> None:
        nonlocal at
        if upto > at:
            pieces.append((at, upto, stack[-1][1] if stack else OUTSIDE))
            at = upto

    for s, e, name in spans:
        while stack and stack[-1][0] <= s:
            cut(stack[-1][0])
            stack.pop()
        cut(s)
        stack.append((e, name))
    while stack:
        cut(stack[-1][0])
        stack.pop()
    cut(w1)
    return pieces


def _split_gaps(gaps, pieces):
    """(label, seconds) of each part of each sorted, disjoint gap, by the
    piece (`_innermost`) it overlaps."""
    j = 0
    for s, t in gaps:
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < t:
            a, b, label = pieces[k]
            yield label, (min(b, t) - max(a, s)) * 1e-9
            k += 1


def run_traced(workload: str, seed: int, seconds: float) -> dict:
    """One traced window of `workload`, reduced here beside the
    harness's own reduction of the same profile."""
    import jax

    from bench_tpu import harness as H
    from bench_tpu import xplane
    from repro import compile_cache

    compile_cache.enable()      # the programs `run.py` compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    reg = H.Registry()
    cell = reg.cell(workload)
    kept: dict = {}
    reduce_harness = xplane.reduce

    def keep(path):     # the harness deletes the trace once reduced
        pd = xplane.load(path)
        kept.update(reduce_profile(pd))
        return xplane.reduce_profile(pd)

    xplane.reduce = keep
    try:
        run, checks = H.run_cell(workload, reg.config(cell["config"]),
                                 reg.traffic(cell["traffic"]), seed,
                                 seconds, True)
    finally:
        xplane.reduce = reduce_harness
    kept["correct"] = run.error is None and all(
        v <= 0 for v in checks.values())
    kept["counters"] = {k: run.stat_delta(k)
                        for k in ("host_syncs", "chunks_staged")}
    return kept


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", nargs="?", help="a recorded .xplane.pb[.gz]")
    ap.add_argument("--workload", help="cell name")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    if args.trace:
        out = reduce(args.trace)
    elif args.workload and args.seed is not None and args.seconds:
        out = run_traced(args.workload, args.seed, args.seconds)
    else:
        ap.error("give a trace, or --workload, --seed and --seconds")
    out["derived"] = derived(out)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
