"""Ahead-of-time compilation of the engine's program grid.

`SLSM.warm` / `warm_tape` (with `MergeScheduler.programs` for the
maintenance steps) enumerate every program a run can dispatch. They hand the list here,
as ``(jitted_fn, args)`` pairs whose array arguments are abstract
`jax.ShapeDtypeStruct`s, and this module lowers each one and compiles
the lot concurrently:

  * no device memory is touched — at the deployment geometry one state
    pytree is gigabytes, and a warm-up that materialized a dummy state
    per program would not fit beside the live one;
  * XLA compiles run outside the GIL, so a thread per core overlaps the
    TPU compiler's multi-second sort and scatter emitters (the cold
    start of a full-size engine is mostly those);
  * a compiled program lands in the same cache the jitted function's
    own dispatch reads, so the first real call with matching shapes
    runs without compiling (and, where a persistent compilation cache
    is configured, the next process finds it on disk).
"""
from __future__ import annotations

import functools
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Iterable, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.params import SLSMParams
from repro.engine.memtable import init_state

Program = Tuple[Callable, Sequence]


def state_shapes(p: SLSMParams, n_levels: int):
    """Abstract state pytree (shapes and dtypes only) with `n_levels`
    materialized disk levels — what `init_state` would allocate."""
    return jax.eval_shape(functools.partial(init_state, p, n_levels))


def i32(*shape: int) -> jax.ShapeDtypeStruct:
    """Abstract int32 array argument of `shape` (() for a scalar)."""
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def compile_programs(programs: Iterable[Program]) -> None:
    """Lower every ``(jitted_fn, args)`` pair and compile them on a
    thread pool (one thread per usable core). Duplicate signatures are
    harmless (the second lowering hits the cache)."""
    lowered = [fn.lower(*args) for fn, args in programs]
    if not lowered:
        return
    workers = min(len(lowered), len(os.sched_getaffinity(0)))
    with ThreadPoolExecutor(max_workers=workers) as pool:
        list(pool.map(lambda low: low.compile(), lowered))
