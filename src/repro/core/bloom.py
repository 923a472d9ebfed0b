"""Bloom filters (paper 2.3) — Murmur3-style double hashing, vectorized.

The paper pairs one filter per run (memory and disk), uses Murmur3 and the
double-hashing trick h_i = h1 + i*h2 so k probe positions cost two hashes.
We keep all of that; the bitset is a uint32 word array and insert/probe are
batched scatter/gather ops (TPU-native form of "bitset + test").
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

SEED1 = np.uint32(0x9E3779B9)
SEED2 = np.uint32(0x85EBCA77)

_C1 = np.uint32(0x85EBCA6B)
_C2 = np.uint32(0xC2B2AE35)


def fmix32(x: jax.Array) -> jax.Array:
    """Murmur3 32-bit finalizer (the avalanche core of Murmur3)."""
    x = x ^ (x >> np.uint32(16))
    x = x * _C1
    x = x ^ (x >> np.uint32(13))
    x = x * _C2
    x = x ^ (x >> np.uint32(16))
    return x


def _as_u32(keys: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(keys.astype(jnp.int32), jnp.uint32)


def probe_positions(keys: jax.Array, k: int, bits: int) -> jax.Array:
    """(..., k) uint32 bit positions via double hashing (paper 2.3)."""
    u = _as_u32(keys)
    h1 = fmix32(u ^ SEED1)
    h2 = fmix32(u ^ SEED2) | np.uint32(1)  # odd => full-period stride
    i = jnp.arange(k, dtype=jnp.uint32)
    pos = h1[..., None] + i * h2[..., None]
    return pos % np.uint32(bits)


# keys hashed per scatter when building a filter: a scatter of
# n * k positions costs the TPU a sort of them, so a deepest-level run
# (16M keys at the one-chip paper geometry) is built in slices of this
# many keys, keeping that sort's temporaries at tens of MiB
BUILD_CHUNK = 1 << 20


def bloom_build(keys: jax.Array, valid: jax.Array, words: int, k: int,
                bits: int | None = None) -> jax.Array:
    """Build a (words,) uint32 filter over `keys` where `valid`.

    `bits` is the *effective* filter size; default words*32 (the whole
    array). The adaptive tuner (DESIGN.md §9) sizes arrays physically for
    its densest allocation and passes the current allocation's smaller
    `bits` here — probe positions then stay inside [0, bits) and the
    tail words are never touched, so probe (with the same `bits`) and
    build agree. Keys are hashed `BUILD_CHUNK` at a time into the one
    bitset (setting a bit is idempotent, so the filter does not depend
    on the slicing; a run of at most `BUILD_CHUNK` keys is one slice)."""
    if bits is None:
        bits = words * 32
    assert bits <= words * 32, f"effective bits {bits} > {words} words"
    bits_phys = words * 32

    def set_bits(hot, ks, ok):
        pos = probe_positions(ks, k, bits).astype(jnp.int32)
        # invalid keys -> out-of-range position, dropped by the scatter
        pos = jnp.where(ok[..., None], pos, bits_phys)
        return hot.at[pos.reshape(-1)].set(True, mode="drop")

    n = keys.shape[0]
    chunk = max(1, min(n, BUILD_CHUNK))
    n_chunks = -(-n // chunk)
    pad = n_chunks * chunk - n
    ks = jnp.pad(keys, (0, pad)).reshape(n_chunks, chunk)
    ok = jnp.pad(valid, (0, pad)).reshape(n_chunks, chunk)
    hot = jax.lax.fori_loop(0, n_chunks,
                            lambda i, h: set_bits(h, ks[i], ok[i]),
                            jnp.zeros((bits_phys,), jnp.bool_))
    weights = jnp.left_shift(np.uint32(1), jnp.arange(32, dtype=jnp.uint32))
    return (hot.reshape(words, 32).astype(jnp.uint32) * weights).sum(
        axis=1, dtype=jnp.uint32
    )


def bloom_insert(filter_words: jax.Array, keys: jax.Array, valid: jax.Array,
                 k: int, bits: int | None = None) -> jax.Array:
    """OR new keys into an existing filter."""
    add = bloom_build(keys, valid, filter_words.shape[-1], k, bits)
    return filter_words | add


def bloom_probe(filter_words: jax.Array, keys: jax.Array, k: int,
                bits: int | None = None) -> jax.Array:
    """Membership test. No false negatives; false positives at rate ~eps.

    filter_words: (words,) uint32;  keys: (...,) int32  ->  (...,) bool
    `bits` = effective filter size (default: the whole array) — must
    match what `bloom_build` was given or probes read the wrong bits.
    """
    if bits is None:
        bits = filter_words.shape[-1] * 32
    pos = probe_positions(keys, k, bits).astype(jnp.int32)
    w = filter_words[pos // 32]
    bit = (w >> (pos % 32).astype(jnp.uint32)) & np.uint32(1)
    return jnp.all(bit == np.uint32(1), axis=-1)
