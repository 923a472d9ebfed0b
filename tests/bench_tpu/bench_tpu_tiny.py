"""Tiny sizes for running the benchmark's cells on the CPU in tests."""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench_tpu import harness as H  # noqa: E402

CELLS = ("ingest.s3-durable", "lookup.s3-volatile", "scan.s3-volatile",
         "serve-a.s3-durable")

# the cells' geometry cut to a few hundred keys: the deepest level holds
# 4,096 keys, twice the universe, as the chip's holds twice its 8M
TINY_CONFIG = dict(R=4, Rn=64, eps=1e-3, D=4, m=1.0, mu=64, max_levels=2,
                   merge_budget=1, range_cand=64, max_range=64,
                   key_universe=2000, preload_keys=1000,
                   preload_overwrites=25, preload_deletes=25,
                   preload_call=256)
TINY_BATCH = {"insert": 500, "lookup": 256, "range": 8}


def tiny_cell(cell: str):
    """(config, traffic) of `cell` (``<traffic>.<config>``) at tiny
    sizes."""
    reg = H.Registry()
    traffic_name, config_name = cell.split(".", 1)
    config = dict(reg.config(config_name), **TINY_CONFIG)
    traffic = dict(reg.traffic(traffic_name))
    if traffic["loop"] == "closed":
        traffic["batch"] = TINY_BATCH[traffic["op"]]
        traffic["warm_calls"] = 1
    else:
        traffic["rate_per_s"] = 200
        traffic["warm_s"] = 0.5
    return config, traffic


def run_tiny(cell: str, seed: int = 2**31 + 7, control: bool = False,
             seconds: float = 0.5):
    """One CPU run of `cell` at tiny sizes; returns (run, checks)."""
    config, traffic = tiny_cell(cell)
    saved = H.TAPE_WARM
    H.TAPE_WARM = (4, 16, 64)
    try:
        return H.run_cell(cell, config, traffic, seed, seconds, False,
                          control=control)
    finally:
        H.TAPE_WARM = saved


def correct(run, checks) -> bool:
    return run.error is None and all(v <= 0 for v in checks.values())
