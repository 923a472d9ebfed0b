"""Pure-jnp oracle for range_merge: per-row (key, seq) sort + the same
weighted survivor mask, computed after the fact. This is also the jnp
backend's production range-merge path (backend.py). Payloads ride a
post-sort gather through each row's source indices — the same Ghost
shape as the kernel, so both backends agree bitwise."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.params import KEY_EMPTY


def range_merge_ref(keys, vals, wts, seqs, offsets, drop_annihilated: bool):
    """Sort-based equivalent of `range_merge_op` (same output contract).

    `offsets` is accepted for interface parity and ignored: sorting each
    row by (key, seq) yields the same stream a segment merge does, since
    the rows hold the same multiset.
    """
    del offsets
    q, cand = keys.shape
    idx = jnp.broadcast_to(jnp.arange(cand, dtype=jnp.int32), (q, cand))
    # (key, seq) is unique per live candidate and the padding lanes are
    # identical, so the unstable sort orders rows as a stable one would
    # (core.runs.sort_records: fewer sort operands, faster TPU compiles)
    k, s, idx = jax.lax.sort(
        (keys.astype(jnp.int32), seqs.astype(jnp.int32), idx), num_keys=2,
        is_stable=False)
    w = jnp.take_along_axis(wts.astype(jnp.int32), idx, axis=1)
    nxt = jnp.concatenate(
        [k[:, 1:], jnp.full((k.shape[0], 1), KEY_EMPTY, k.dtype)], axis=1)
    keep = (k != KEY_EMPTY) & (k != nxt)
    if drop_annihilated:
        keep &= w > 0
    v = jnp.take_along_axis(vals.astype(jnp.int32), idx, axis=1)
    v = jnp.where(k == KEY_EMPTY, 0, v)
    return k, v, w, s, keep
