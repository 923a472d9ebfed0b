"""Batching policy: the pad/bucket grid every batched entry point shares.

Every batched op in the engine — `lookup_many`, `range_many`, the
staged insert chunks, and the serving layer's coalesced windows
(repro.serve) — compiles one program per padded lane width, so the
set of widths in circulation IS the compile-cache footprint. This
module is the single home for that policy:

  * `bucket_pow2`      — the generic power-of-two lane grid (O(log Q)
                         programs for arbitrary Q);
  * `ADAPTIVE_BUCKETS` — the coarse lookup grid adaptive engines use so
                         `warm()` can precompile every (preset x
                         structure x bucket) combination;
  * `RANGE_BUCKETS`    — the scan-count grid (coarse: each batched scan
                         program's width axis is the candidate buffer);
  * the pad helpers (`pad_to`, `pad_pow2`) that realize a bucket as a
    KEY_EMPTY-padded lane array;
  * `range_many_host`  — the shared pad/dispatch/trim driver for the
    batched range entry points of both engines;
  * `host_read`        — the one way the driver and its scheduler copy a
    device value to the host, counted in ``stats["host_syncs"]``.

Until PR 6 these lived as underscore-privates in `engine.py` and were
imported across modules (`sharded.py`) — promoting them makes the grid
a public contract the serving layer can warm against.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.params import KEY_EMPTY


def bucket_pow2(n: int, floor: int = 16) -> int:
    """Round a query count up to the next power-of-two bucket (>= floor).
    The one bucketing policy for every batched-lookup entry point: padded
    lane counts hit O(log Q) compiled programs instead of one per Q."""
    return max(floor, 1 << (n - 1).bit_length())


# adaptive engines quantize batched-lookup lanes to this coarse bucket
# set: every preset allocation is its own static-param read program, so
# the bucket set must stay small enough for warm() to precompile the
# whole (preset x structure x bucket) grid — a retune must never leave
# an unwarmed shape for a timed read to trip over
ADAPTIVE_BUCKETS = (256, 1024, 4096)

# batched range scans quantize to this bucket grid (every engine — the
# scan program's width axis is the candidate buffer, so the lane count
# stays coarse); warm() precompiles the whole grid per allocation
RANGE_BUCKETS = (8, 32)

# mixed-op tapes (repro.engine.tape) quantize their slot count to this
# grid: one lax.scan program per (params x structure x slot bucket), NOP
# slots padding the tail — the serving layer's window sizes all land on
# a handful of precompiled interpreters (SLSM.warm_tape)
TAPE_BUCKETS = (4, 16, 64)


def pad_to(qs: np.ndarray, width: int) -> np.ndarray:
    """Pad a query vector with KEY_EMPTY to `width` lanes."""
    out = np.full(width, KEY_EMPTY, np.int32)
    out[:len(qs)] = qs
    return out


def pad_pow2(qs: np.ndarray) -> np.ndarray:
    """Pad a query vector with KEY_EMPTY to its `bucket_pow2` width, so
    repeated mixed-size batches hit O(log Q) compiled programs."""
    return pad_to(qs, bucket_pow2(len(qs)))


def adaptive_bucket(n: int) -> int:
    """Smallest warmed adaptive bucket holding n lanes (pow2 past the
    largest, for callers exceeding the warmed grid)."""
    for b in ADAPTIVE_BUCKETS:
        if n <= b:
            return b
    return bucket_pow2(n)


def range_bucket(n: int) -> int:
    """Smallest warmed scan-count bucket holding n lanes (pow2 past the
    largest, for callers exceeding the warmed grid)."""
    for b in RANGE_BUCKETS:
        if n <= b:
            return b
    return bucket_pow2(n)


def tape_bucket(n: int) -> int:
    """Smallest warmed tape-slot bucket holding n slots (pow2 past the
    largest, for callers exceeding the warmed grid)."""
    for b in TAPE_BUCKETS:
        if n <= b:
            return b
    return bucket_pow2(n)


def host_read(x, stats=None) -> np.ndarray:
    """``np.asarray(x)``: one blocking device-to-host read (it waits for
    the program that computes `x`), counted in ``stats["host_syncs"]``
    when a stats counter is given."""
    if stats is not None:
        stats["host_syncs"] += 1
    return np.asarray(x)


def range_many_host(dispatch, max_range: int, ranges, stats=None):
    """Shared `range_many` driver for both engines: pad the scan list to
    the `RANGE_BUCKETS` grid, run the engine's jitted batched program
    ``dispatch(los, his, n_valid)``, trim back to the Q requested rows.
    One implementation so the bucket grid, padding dtype, and empty-batch
    contract cannot diverge between drivers. The four result copies
    (`host_read`, counted in `stats`) run in the ``slsm.fetch`` span."""
    r = np.asarray(ranges, np.int32).reshape(-1, 2)
    q = r.shape[0]
    if q == 0:
        return (np.zeros((0, max_range), np.int32),
                np.zeros((0, max_range), np.int32),
                np.zeros(0, np.int32), np.zeros(0, bool))
    width = range_bucket(q)
    los = np.zeros(width, np.int32)
    his = np.zeros(width, np.int32)
    los[:q], his[:q] = r[:, 0], r[:, 1]
    k, v, c, trunc = dispatch(jnp.asarray(los), jnp.asarray(his),
                              jnp.int32(q))
    with jax.profiler.TraceAnnotation("slsm.fetch"):
        return (host_read(k, stats)[:q], host_read(v, stats)[:q],
                host_read(c, stats)[:q], host_read(trunc, stats)[:q])
