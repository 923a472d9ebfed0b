"""Benchmark entry point: workload scenarios (BENCH_*.json) + figure benches.

Scenario mode — the machine-readable perf trajectory (DESIGN.md §7):

    python -m benchmarks.run --scenario all --out .
    python -m benchmarks.run --scenario sweep-R,sweep-eps --out bench_out
    python -m benchmarks.run --scenario zipfian --profile smoke --out /tmp/b
    python -m benchmarks.run --check --out bench_out   # validate existing files
    python -m benchmarks.run --list

Each scenario emits one schema-versioned ``BENCH_<name>.json``
(`repro.bench.schema`) and prints a one-line summary including the
batched-vs-per-query lookup speedup.

Figure mode (legacy per-paper-figure CSV benches, Figs 2-12 + kernels):

    python -m benchmarks.run --fig fig05
    python -m benchmarks.run --fig all
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path


def _summary(doc: dict) -> str:
    m = doc["metrics"]
    if m.get("serving"):
        s = m["serving"]
        return (f"{doc['name']}: coalesced "
                f"{s['coalesced']['ops_per_s']:.0f} ops/s "
                f"(p99 {s['coalesced']['p99_us']:.0f}us) vs per-request "
                f"{s['per_request']['ops_per_s']:.0f} ops/s "
                f"({s['coalesced_speedup']:.1f}x) at "
                f"{s['coalesced']['clients']} clients, sustained@SLO "
                f"{s['sustained_ops_at_slo']:.0f} ops/s, "
                f"governor {s['governor']['steps']} steps")
    parts = [
        f"{doc['name']}:",
        f"insert {m['insert']['ops_per_s']:.0f} ops/s,",
        f"lookup batched {m['lookup_batched']['ops_per_s']:.0f} ops/s",
        f"vs per-query {m['lookup_per_query']['ops_per_s']:.0f} ops/s",
        f"({m['batched_speedup']:.1f}x),",
        f"merges s/f/s/c="
        f"{m['maintenance']['seals']}/{m['maintenance']['flushes']}/"
        f"{m['maintenance']['spills']}/{m['maintenance']['compactions']},",
        f"bloom fp {m['bloom']['fp_rate_measured']:.2e}",
    ]
    if m.get("tuner"):
        parts[-1] += ","
        parts.append(f"tuner {m['tuner']['active']} "
                     f"({m['maintenance']['retunes']} retunes)")
    if m["range"]:
        parts[-1] += ","
        parts.append(f"range p50 {m['range']['p50_us']:.0f}us")
    if m["delete"]:
        parts[-1] += ","
        parts.append(f"delete {m['delete']['ops_per_s']:.0f} ops/s")
    return " ".join(parts)


def run_scenarios(selector: str, out_dir: str, profile: str) -> None:
    from repro.bench.runner import run_scenario
    from repro.bench.scenarios import scenarios_for

    scenarios = scenarios_for(selector)
    print(f"# {len(scenarios)} scenario(s), profile={profile}, "
          f"out={out_dir}", file=sys.stderr)
    for sc in scenarios:
        t0 = time.perf_counter()
        path, doc = run_scenario(sc, out_dir, profile=profile)
        print(_summary(doc), flush=True)
        print(f"#   wrote {path} in {time.perf_counter()-t0:.1f}s",
              file=sys.stderr)
        # compiled executables accumulate memory mappings; a long
        # multi-scenario run (each scenario warms its own parameter
        # set, so there is no cross-scenario cache reuse to lose) can
        # hit the kernel's vm.max_map_count ceiling and segfault XLA's
        # next compile — release each scenario's programs before the
        # next one starts
        import jax
        jax.clear_caches()


def check_dir(out_dir: str) -> None:
    """Validate every BENCH_*.json in out_dir against the schema."""
    from repro.bench.schema import validate

    files = sorted(Path(out_dir).glob("BENCH_*.json"))
    if not files:
        sys.exit(f"no BENCH_*.json files found in {out_dir}")
    bad = 0
    for f in files:
        errs = validate(json.loads(f.read_text()))
        status = "ok" if not errs else "INVALID"
        print(f"{f.name}: {status}")
        for e in errs:
            print(f"  - {e}")
        bad += bool(errs)
    if bad:
        sys.exit(f"{bad}/{len(files)} documents failed schema validation")
    print(f"{len(files)} documents schema-valid "
          f"(schema_version pinned by repro.bench.schema)")


def list_scenarios() -> None:
    from repro.bench.scenarios import CANONICAL, SWEEPS

    print("canonical (--scenario all):")
    for sc in CANONICAL:
        print(f"  {sc.name:24s} workload={sc.workload}")
    for fam, group in sorted(SWEEPS.items()):
        print(f"{fam} (--scenario {fam}):")
        for sc in group:
            knobs = sc.params or {"policy": sc.policy,
                                  "n_shards": sc.n_shards}
            print(f"  {sc.name:24s} {knobs}")


def run_figs(fig: str) -> None:
    from benchmarks import figs

    fns = figs.ALL_FIGS
    if fig != "all":
        fns = [f for f in figs.ALL_FIGS if f.__name__.startswith(fig)]
        if not fns:
            sys.exit(f"unknown figure {fig}")
    print("name,us_per_call,derived")
    for fn in fns:
        t0 = time.perf_counter()
        for line in fn():
            print(line, flush=True)
        print(f"# {fn.__name__} took {time.perf_counter()-t0:.1f}s",
              file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scenario", default=None,
                    help="scenario selector: all | sweeps | sweep-R | "
                         "<name> | comma-separated mix")
    ap.add_argument("--out", default=".",
                    help="directory for BENCH_*.json files (scenario mode)")
    ap.add_argument("--profile", default="default",
                    choices=("smoke", "default", "full"),
                    help="workload sizing (smoke = CI-scale seconds)")
    ap.add_argument("--check", action="store_true",
                    help="validate BENCH_*.json in --out (combined with "
                         "--scenario: run first, then validate)")
    ap.add_argument("--list", action="store_true",
                    help="list scenario names and exit")
    ap.add_argument("--fig", default=None,
                    help="figure mode: e.g. fig05 | fig12 | kernels | all")
    args = ap.parse_args()

    from repro import compile_cache
    compile_cache.enable()
    if args.fig is not None and (args.scenario is not None or args.check
                                 or args.list):
        ap.error("--fig is figure mode; it cannot be combined with "
                 "--scenario/--check/--list")
    if args.list:
        list_scenarios()
        return
    if args.scenario is not None:
        run_scenarios(args.scenario, args.out, args.profile)
    if args.check:
        check_dir(args.out)        # after --scenario: run, then validate
    if args.scenario is None and not args.check:
        run_figs(args.fig or "all")


if __name__ == "__main__":
    main()
