"""Bloom filter invariants (paper 2.3). Hypothesis property tests live
in test_bloom_props.py (skipped gracefully when hypothesis is absent)."""
import jax.numpy as jnp
import numpy as np

from repro.core.bloom import bloom_build, bloom_insert, bloom_probe
from repro.core.params import SLSMParams


def test_fp_rate_tracks_eps(rng):
    p = SLSMParams(eps=0.01)
    n = 4000
    bits, words, k = p.bloom_geometry(n)
    present = rng.choice(2**24, size=n, replace=False).astype(np.int32)
    filt = bloom_build(jnp.asarray(present), jnp.ones(n, bool), words, k)
    absent = (rng.choice(2**24, size=20000, replace=False)
              .astype(np.int64) + 2**24).astype(np.int32)
    fp = np.asarray(bloom_probe(filt, jnp.asarray(absent), k)).mean()
    assert fp < 5 * p.eps, fp  # within a small factor of the target


def test_insert_is_incremental_or(rng):
    a = rng.integers(0, 2**30, 100).astype(np.int32)
    b = rng.integers(0, 2**30, 100).astype(np.int32)
    both = bloom_build(jnp.asarray(np.concatenate([a, b])),
                       jnp.ones(200, bool), 64, 5)
    stepwise = bloom_build(jnp.asarray(a), jnp.ones(100, bool), 64, 5)
    stepwise = bloom_insert(stepwise, jnp.asarray(b), jnp.ones(100, bool), 5)
    np.testing.assert_array_equal(np.asarray(both), np.asarray(stepwise))


def test_invalid_keys_not_inserted():
    ks = jnp.asarray(np.asarray([5, 6, 7], np.int32))
    valid = jnp.asarray([True, False, True])
    filt = bloom_build(ks, valid, 64, 5)
    probe = np.asarray(bloom_probe(filt, ks, 5))
    assert probe[0] and probe[2]
    # key 6 was masked out; it may still collide, but with 64*32 bits and
    # 2 inserted keys the probability is negligible
    assert not probe[1]


def test_sliced_build_is_bit_identical(rng, monkeypatch):
    """A run hashed in many BUILD_CHUNK slices (the padded tail slice
    included) gives the same filter as the same run in one slice."""
    from repro.core import bloom as BL
    n, words, k = 1000, 600, 7
    ks = jnp.asarray(rng.integers(0, 2**30, n).astype(np.int32))
    valid = jnp.asarray(rng.random(n) < 0.8)
    whole = BL.bloom_build(ks, valid, words, k, bits=words * 32 - 40)
    monkeypatch.setattr(BL, "BUILD_CHUNK", 96)
    sliced = BL.bloom_build(ks, valid, words, k, bits=words * 32 - 40)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(sliced))
