"""Tiny sizes for running the benchmark's cells on the CPU in tests."""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench_tpu import harness as H  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
# every cell of the benchmark, in file order, and the served YCSB-A mix:
# out of the benchmark, it keeps the harness's open loop under test
CELLS = tuple(dict.fromkeys(
    [w["name"] for w in SPEC["workloads"]] + ["serve-a.s3-durable"]))

# the cells' geometry cut to a few hundred keys: the deepest level holds
# 4,096 keys, twice the universe, as the chip's holds twice its 8M
TINY_CONFIG = dict(R=4, Rn=64, eps=1e-3, D=4, m=1.0, mu=64, max_levels=2,
                   merge_budget=1, range_cand=64, max_range=64,
                   key_universe=2000, preload_keys=1000,
                   preload_overwrites=25, preload_deletes=25,
                   preload_call=256)
TINY_BATCH = {"insert": 500, "lookup": 256, "range": 8}


def tiny_cell(cell: str):
    """(config, traffic) of `cell` at tiny sizes: the benchmark's entry
    of that name, else ``<traffic>.<config>``. The configuration keeps
    every key that `TINY_CONFIG` does not set (its policy, its other
    engine fields)."""
    reg = H.Registry()
    try:
        w = reg.cell(cell)
        traffic_name, config_name = w["traffic"], w["config"]
    except H.BenchError:
        traffic_name, config_name = cell.split(".", 1)
    config = dict(reg.config(config_name), **TINY_CONFIG)
    traffic = dict(reg.traffic(traffic_name))
    if traffic["loop"] == "closed":
        if traffic["op"] not in TINY_BATCH:
            raise ValueError(f"no tiny batch for closed-loop op "
                             f"{traffic['op']!r} of {cell!r}")
        traffic["batch"] = TINY_BATCH[traffic["op"]]
        traffic["warm_calls"] = 1
    elif traffic["loop"] == "open":
        traffic["rate_per_s"] = 200
        traffic["warm_s"] = 0.5
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r} of {cell!r}")
    return config, traffic


def run_config(cell: str, config: dict, traffic: dict,
               seed: int = 2**31 + 7, control: bool = False,
               seconds: float = 0.5):
    """One CPU run of `cell` at `config` and `traffic`; returns (run,
    checks)."""
    saved = H.TAPE_WARM
    H.TAPE_WARM = (4, 16, 64)
    try:
        return H.run_cell(cell, config, traffic, seed, seconds, False,
                          control=control)
    finally:
        H.TAPE_WARM = saved


def run_tiny(cell: str, seed: int = 2**31 + 7, control: bool = False,
             seconds: float = 0.5):
    """One CPU run of `cell` at tiny sizes; returns (run, checks)."""
    return run_config(cell, *tiny_cell(cell), seed=seed, control=control,
                      seconds=seconds)


def correct(run, checks) -> bool:
    return run.error is None and all(v <= 0 for v in checks.values())
