#!/usr/bin/env python3
"""Chip benchmark of the sLSM store: one run of one cell.

    python3 bench_tpu/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1> [--control]

Run from the root of a checkout on a machine with a TPU: it exits with
code 2, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for. A run builds the cell's configuration, warms its
programs, preloads the seed's data, warms the traffic, measures for
``--seconds``, then checks every answer of the window and a read-back
of the written keys against a plain reference (`bench_tpu.reference`).
The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones read from a profiler trace of the
window), `device`, with ``--trace 1`` `breakdown`, and last `checks`:
each number `correct` was decided by, with its limit. The same numbers
end standard error.

``--control`` puts the plain reference in the store's place with one
guarantee broken (`bench_tpu.control`); its `correct` must be false.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="run the control in the store's place")
    return ap.parse_args(argv)


def number(x):
    """A metric value as JSON takes it (None where not finite)."""
    x = float(x)
    return x if math.isfinite(x) else None


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    # JAX's persistent compilation cache: the environment's choice, else
    # a fixed directory inside the checkout (read when JAX is imported)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    from bench_tpu import harness as H

    try:
        reg = H.Registry()
        cell = reg.cell(args.workload)
        config = reg.config(cell["config"])
        traffic = reg.traffic(cell["traffic"])
    except (OSError, ValueError, KeyError, H.BenchError) as e:
        H.log(f"bench_tpu: {e}")
        return 2

    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu" or len(devices) < int(cell["chips"]):
        H.log(f"bench_tpu: refusing to run: the cell needs {cell['chips']} "
              f"TPU chip(s), JAX found {len(devices)} {d0.platform} "
              f"device(s) ({d0.device_kind})")
        return 2
    try:
        H.peaks(d0.device_kind)
    except H.BenchError as e:
        H.log(f"bench_tpu: {e}")
        return 2
    from repro import compile_cache

    cache = compile_cache.enable()
    # keep every program, however quick to compile, so that only a
    # checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    H.log(f"bench_tpu: {args.workload} seed {args.seed} on {len(devices)} "
          f"{d0.device_kind}; compile cache {cache}")

    run, checks = H.run_cell(args.workload, config, traffic, args.seed,
                             args.seconds, bool(args.trace),
                             control=args.control, t_start=T_START,
                             device=d0)
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in reg.metrics(args.workload, kind):
        v = H.Registry.reader(m["name"])(run)
        if v is not None and number(v) is not None:
            metrics[m["name"]] = {"value": number(v), "unit": m["unit"]}
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": run.memory_peak}
    out = {"correct": run.error is None and all(
               v <= 0 for v in checks.values()),
           "attempted": int(run.attempted),
           "failed": int(run.failed), "metrics": metrics, "device": device}
    if args.trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {"device_ops": run.trace.device_ops,
                            "idle_gaps": run.trace.idle_gaps}
    out["checks"] = {k: {"value": v, "limit": 0} for k, v in checks.items()}
    H.log(f"bench_tpu: window {run.window_s:.3f} s, work "
          f"{dict(run.work)}, compiles in the window "
          f"{run.compiles_in_window} {sorted(run.compiled_names)}")
    if run.call_s:
        n, c = len(run.call_s), run.call_s
        H.log(f"bench_tpu: {n} calls, {run.window_s / n * 1e3:.3f} ms a "
              f"cycle; a call's host ms: first 5 "
              f"{sum(c[:5]) / len(c[:5]) * 1e3:.3f}, median "
              f"{sorted(c)[n // 2] * 1e3:.3f}, last 5 "
              f"{sum(c[-5:]) / len(c[-5:]) * 1e3:.3f}")
    if run.trace is not None:
        tr = run.trace
        H.log(f"bench_tpu: trace kept {tr.calls_kept} of {tr.calls} calls "
              f"(truncated {tr.truncated}), {tr.window_s:.6f} s, busy "
              f"{tr.busy_s:.6f} s")
    if run.error:
        H.log(f"bench_tpu: the window failed: {run.error}")
    print(json.dumps(out), flush=True)
    for k, v in checks.items():
        H.log(f"check {k} {v} limit 0")
    return 0


if __name__ == "__main__":
    sys.exit(main())
