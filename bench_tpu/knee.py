#!/usr/bin/env python3
"""Find the knee of a served mix: the highest offered rate it sustains.

    python3 bench_tpu/knee.py --traffic serve-a --config s3-durable \\
        --seed N --rates 100,200,400 --step-seconds 15

The traffic file and the configuration are found by name, as a cell's
are, so the knee is found before the cell joins `BENCHMARK.json`.
On a TPU, in one process: set the cell up as `bench_tpu/run.py` does,
then offer each rate in turn for one step of open-loop Poisson arrivals
(the cell's own mix). For each step it prints the offered and completed
rate, how long the last reply came after the step's end (`overrun_s`),
the backlog (requests due but not yet answered) at the step's middle
and end, latency from the due time, and the mean served window. A step
sustains its rate when its backlog does not grow from the middle to the
end. The sweep stops after the first step whose overrun passes 20 s.
The knee is the highest rate that sustains; a cell's fixed rate is set
below it, once, by hand.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import tempfile

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def backlog(t_abs: float, due: np.ndarray, reply: np.ndarray) -> int:
    return int(np.sum(due <= t_abs) - np.sum(reply <= t_abs))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--traffic", required=True,
                    help="bench_tpu/traffic/<name>.json")
    ap.add_argument("--config", required=True,
                    help="configuration name in BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--step-seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    import jax

    from bench_tpu import harness as H
    from bench_tpu import traffic as T

    if jax.devices()[0].platform != "tpu":
        H.log("knee: refusing to run without a TPU")
        return 2
    from repro import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    reg = H.Registry()
    config = reg.config(args.config)
    traffic = reg.traffic(args.traffic)
    tmp = tempfile.mkdtemp(prefix="bench_tpu_knee_")
    try:
        data = T.Data(config, args.seed)
        store = H.build_store(config, os.path.join(tmp, "wal"), False)
        store.warm(buckets=(H.READBACK_BATCH,))
        store.warm_tape(buckets=H.TAPE_WARM)
        for kind, k, v in data.calls:
            H.apply_write(store, kind, k, v)
        store.drain()
        H.warm_tapes(store, int(data.keys[0]), H.TAPE_WARM)
        server = H.build_server(store, False)
        nothing = lambda name: contextlib.nullcontext()   # noqa: E731
        warm = T.open_requests(data, traffic, 3.0,
                               T.stream(args.seed, T.WARM))
        H.open_loop(server, warm, nothing)
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            reqs = T.open_requests(data, dict(traffic, rate_per_s=rate),
                                   args.step_seconds,
                                   T.stream(args.seed, T.TRAFFIC, 3, i))
            w0 = server.counters["windows"]
            t0, t1, tks, late, pumps = H.open_loop(server, reqs, nothing)
            due = t0 + reqs.due
            reply = np.array([t.t_reply for t in tks])
            lat = reply - due
            s = args.step_seconds
            row = {"offered_per_s": rate, "requests": len(reqs),
                   "completed_per_s": len(reqs) / (t1 - t0),
                   "overrun_s": (t1 - t0) - s,
                   "backlog_mid": backlog(t0 + s / 2, due, reply),
                   "backlog_end": backlog(t0 + s, due, reply),
                   "p50_ms": float(np.percentile(lat, 50)) * 1e3,
                   "p99_ms": float(np.percentile(lat, 99)) * 1e3,
                   "late_p99_ms": float(np.percentile(late, 99)) * 1e3,
                   "windows": server.counters["windows"] - w0,
                   "window_ms": float(np.mean(pumps)) * 1e3 if pumps else 0,
                   "requests_per_window": len(reqs) / max(1, len(pumps))}
            print("knee " + json.dumps(row), flush=True)
            if row["overrun_s"] > 20:
                break
    finally:
        dur = getattr(locals().get("store"), "durability", None)
        if dur is not None:
            dur.close()
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
