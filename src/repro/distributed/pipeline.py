"""Pipeline parallelism: GPipe schedule under shard_map + ppermute.

Stage s holds the params for layers [s*L/P, (s+1)*L/P); microbatches flow
stage-to-stage over `jax.lax.ppermute` (ICI neighbour hops on a TPU torus).
The schedule is the classic GPipe trapezoid: T = n_micro + n_stages - 1
ticks, bubble fraction (P-1)/(M+P-1).

This is the optional PP axis for depth-dominated configs; the dry-run
meshes use DP x TP (pipelining across pods would put activations on DCN).
Tested on host-device meshes in tests/test_distributed.py.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed import runtime as RT


def gpipe_forward(stage_fn, stage_params, x_micro, mesh, axis: str = "pipe"):
    """Run a GPipe forward pass.

    stage_fn: (stage_params_slice, x (mb, ...)) -> y (mb, ...)
    stage_params: pytree with leading axis == n_stages (sharded over `axis`)
    x_micro: (n_micro, mb, ...) microbatched input (replicated)
    Returns (n_micro, mb, ...) outputs (replicated).
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1
    fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

    def spmd(params_local, xs):
        # params_local leaves have leading dim 1 (this stage's slice)
        pl = jax.tree.map(lambda p: p[0], params_local)
        stage = jax.lax.axis_index(axis)
        # carries become device-varying after the first ppermute; mark them
        # varying from the start so the loop carry type is stable
        buf = jax.lax.pcast(jnp.zeros_like(xs[0]), axis, to="varying")
        outs = jax.lax.pcast(jnp.zeros_like(xs), axis, to="varying")

        def tick(t, carry):
            buf, outs = carry
            mb = t - stage
            active = (mb >= 0) & (mb < n_micro)
            mbc = jnp.clip(mb, 0, n_micro - 1)
            x_in = jnp.where(stage == 0, xs[mbc], buf)
            y = stage_fn(pl, x_in)
            y = jnp.where(active, y, buf)
            is_last = stage == n_stages - 1
            outs = jnp.where(
                active & is_last, outs.at[mbc].set(y), outs)
            buf_next = jax.lax.ppermute(y, axis, fwd_perm)
            return buf_next, outs

        _, outs = jax.lax.fori_loop(0, ticks, tick, (buf, outs))
        # only the last stage holds real outputs; broadcast via psum
        outs = jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, axis)

    return RT.shard_map(
        spmd, mesh=mesh,
        in_specs=(P(axis), P()), out_specs=P(),
    )(stage_params, x_micro)


def split_layers_into_stages(stacked_params, n_stages: int):
    """(L, ...) stacked layer params -> (n_stages, L/n_stages, ...)."""
    def resh(p):
        l = p.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return p.reshape(n_stages, l // n_stages, *p.shape[1:])
    return jax.tree.map(resh, stacked_params)


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
