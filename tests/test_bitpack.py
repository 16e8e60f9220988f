"""Bit-packing roundtrips: straddle, planar no-straddle, adaptive (DESIGN.md §2)."""

import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # property tests need the optional dev dep
from hypothesis import given, settings, strategies as st

from repro.core import bitpack


@pytest.mark.parametrize("bits", range(1, 9))
def test_straddle_roundtrip(bits, rng):
    c = rng.integers(0, 1 << bits, size=(5, 77)).astype(np.uint8)
    w = bitpack.pack_bits(jnp.asarray(c), bits)
    assert (np.asarray(w) == bitpack.pack_bits_np(c, bits)).all()
    assert (np.asarray(bitpack.unpack_bits(w, bits, 77)) == c).all()


@pytest.mark.parametrize("bits", range(1, 17))
def test_nostraddle_roundtrip(bits, rng):
    hi = 1 << min(bits, 8)
    c = rng.integers(0, hi, size=(3, 65, 24)).astype(np.uint8)
    w = bitpack.pack_planar(jnp.asarray(c), bits)
    u = bitpack.unpack_planar(w, bits, 65)
    assert (np.asarray(u) == c).all()
    # one column per word, floor(32/bits) rows per word
    assert w.shape == (3, bitpack.planar_rows(65, bits), 24)
    # word[r, d] holds row i*R + r at bit i*bits (the format's definition)
    R = w.shape[1]
    wn = np.asarray(w)
    for i in range(-(-65 // R)):
        rows = np.arange(i * R, min((i + 1) * R, 65))
        got = (wn[:, rows - i * R] >> (i * bits)) & ((1 << bits) - 1)
        assert (got == c[:, rows]).all()


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), bits=st.integers(1, 8),
       n=st.integers(1, 200))
def test_nostraddle_property(seed, bits, n):
    rng = np.random.default_rng(seed)
    c = rng.integers(0, 1 << bits, size=(2, n, 3)).astype(np.uint8)
    w = bitpack.pack_planar(jnp.asarray(c), bits)
    assert (np.asarray(bitpack.unpack_planar(w, bits, n)) == c).all()


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), maxval=st.integers(1, 255))
def test_adaptive_roundtrip(seed, maxval):
    rng = np.random.default_rng(seed)
    c = rng.integers(0, maxval + 1, size=(8, 64)).astype(np.uint8)
    ap = bitpack.pack_adaptive(jnp.asarray(c), capacity_words=8 * 64)
    u = bitpack.unpack_adaptive(ap)
    assert (np.asarray(u) == c).all()


def test_adaptive_bits_follow_range(rng):
    c = np.zeros((4, 64), np.uint8)
    c[1] = rng.integers(0, 2, (64,))
    c[2] = rng.integers(0, 14, (64,))
    c[3] = rng.integers(0, 200, (64,))
    c[3, 0] = 199
    ap = bitpack.pack_adaptive(jnp.asarray(c), capacity_words=1024)
    bits = np.asarray(ap.bits)
    assert bits[0] == 1 and bits[1] == 1
    assert bits[2] == int(np.ceil(np.log2(c[2].max() + 1)))
    assert bits[3] == 8
    # deterministic offsets = exclusive cumsum of word counts
    assert (np.asarray(ap.offsets) == np.concatenate(
        [[0], np.cumsum(np.asarray(ap.nwords))[:-1]])).all()


def test_packed_words_vs_nostraddle():
    # straddle is denser, planar no-straddle is gather-free; both bounded
    # (planar rounds the rows up to a whole word row per column)
    for bits in range(1, 9):
        dense = bitpack.packed_words(1000 * 8, bits)
        loose = bitpack.planar_rows(1000, bits) * 8
        assert dense <= loose <= dense + (1000 // (32 // bits) + 1) * 8
