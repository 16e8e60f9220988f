"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cache as C
from repro.core import huffman
from repro.kernels import fused_kv_attn, ops, ref
from repro.kernels.fused_kv_attn import fused_cache_attention_pallas
from repro.kernels.huffman_decode import (huffman_attn_scores_pallas,
                                          huffman_decode_pallas)
from repro.kernels.pack_encode import quant_pack_pallas


# Jitted oracle and prefill: one compile instead of op-by-op dispatch.
_ref_jit = jax.jit(ref.fused_cache_attention_ref,
                   static_argnames=("tile", "block_size", "scale"))
_prefill_jit = jax.jit(C.prefill, static_argnums=0)


def _kernel_args(c):
    return (c.k_store, c.k_min, c.k_step, c.v_store, c.v_min, c.v_step,
            c.k_buf, c.v_buf,
            jnp.minimum(c.n_flushed, c.spec.n_blocks), c.buf_len)


@pytest.mark.parametrize("layout", ["packed", "raw"])
@pytest.mark.parametrize("B,Hkv,G,S,D,T", [
    (1, 1, 1, 32, 16, 8),
    (2, 2, 3, 96, 32, 16),
    (1, 4, 2, 64, 64, 16),    # MXU-ish head_dim
    (2, 1, 8, 48, 24, 8),     # odd head_dim
    # 16-page compute blocks over 34 store blocks (not a multiple of 16);
    # 17 live blocks end one page into the second compute block
    (2, 1, 2, 1100, 16, 64),
])
def test_fused_cache_attention_sweep(B, Hkv, G, S, D, T, layout, rng):
    """Kernel (buffer tail folded in) vs the vmapped tile-decode oracle,
    through both the packed unpack decoder and the raw passthrough."""
    spec = C.CacheSpec(layout=layout, block_size=T, max_seq=2 * S,
                       rel_scale_k=0.05, rel_scale_v=0.15)
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(B, Hkv * G, D)).astype(np.float32))
    c = _prefill_jit(spec, k, v)
    tile = spec.impl.tile_decode(spec, D)
    kw = dict(tile=tile, block_size=T)
    out_r = _ref_jit(q, *_kernel_args(c), **kw)
    out_p = fused_cache_attention_pallas(q, *_kernel_args(c), **kw)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("layout", ["packed", "raw", "kivi", "huffman"])
def test_fused_paged_compute_blocks(layout, rng):
    """Paged page gather vs the oracle on the tables serving produces, with
    16-page compute blocks over 20 store blocks: row 0 has no live block,
    row 1's live blocks end mid compute block, row 2's shard-local table
    holds -1 inside its live range, row 3 shares row 1's first five arena
    pages (prefix sharing)."""
    from repro.core import pool

    T, D, S = 64, 16, 1240
    spec = C.CacheSpec(layout=layout, block_size=T, max_seq=20 * T,
                       rel_scale_k=0.05, rel_scale_v=0.15)
    k = jnp.asarray(rng.normal(size=(4, 1, S, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(4, 1, S, D)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(4, 2, D)).astype(np.float32))
    NB = spec.n_blocks
    tile = spec.impl.tile_decode(spec, D)
    assert NB == 20 and fused_kv_attn.pages_per_block(NB, T, D, tile) == 16
    pages = rng.permutation(4 * NB + 5)[:4 * NB].reshape(4, NB)
    c = jax.jit(pool.from_dense, static_argnums=1)(
        _prefill_jit(spec, k, v), 4 * NB + 5, pages)
    tab = np.asarray(c.page_tab).copy()
    tab[2, 1::3] = -1
    tab[3, :5] = tab[1, :5]
    args = list(_kernel_args(c))
    args[8] = jnp.asarray([0, 18, S // T, 18], jnp.int32)
    args += [jnp.asarray(tab)]
    kw = dict(tile=tile, block_size=T)
    out_r = _ref_jit(q, *args, **kw)
    out_p = fused_cache_attention_pallas(q, *args, **kw)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_decode_attention_dtypes(dtype, rng):
    spec = C.CacheSpec(layout="packed", block_size=8, max_seq=64)
    k = jnp.asarray(rng.normal(size=(1, 2, 32, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 2, 32, 16)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(1, 4, 16))).astype(dtype)
    c = C.prefill(spec, k, v)
    o1 = ops.cache_decode_attention(c, q, impl="pallas")
    o2 = ops.cache_decode_attention(c, q, impl="xla")
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), atol=2e-2)


def test_fused_matches_cache_attend_end_to_end(rng):
    spec = C.CacheSpec(layout="packed", block_size=16, max_seq=128)
    k = jnp.asarray(rng.normal(size=(2, 2, 72, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, 2, 72, 16)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(2, 4, 16)).astype(np.float32))
    c = C.prefill(spec, k, v)  # 4 full blocks + 8 in buffer
    assert (np.asarray(c.buf_len) == 8).all()
    out_kernel = ops.cache_decode_attention(c, q, impl="pallas")
    out_cache = C.attend(c, q)
    np.testing.assert_allclose(np.asarray(out_kernel), np.asarray(out_cache),
                               atol=5e-3)


def test_fused_empty_store_buffer_only(rng):
    """nb_valid == 0: everything comes from the raw buffer."""
    spec = C.CacheSpec(layout="packed", block_size=16, max_seq=64)
    k = jnp.asarray(rng.normal(size=(1, 2, 5, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 2, 5, 16)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(1, 2, 16)).astype(np.float32))
    c = C.prefill(spec, k, v)
    assert (np.asarray(c.n_flushed) == 0).all()
    out = ops.cache_decode_attention(c, q, impl="pallas")
    ref_out = C.reference_attend(k, v, q)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), atol=5e-3)


# ---------------------------------------------------------------------------
# Huffman kernels
# ---------------------------------------------------------------------------


def _encode_blocks(rng, NBLK, S, L, skew=3):
    codes = np.clip(np.round(rng.normal(8, skew, (NBLK, S, L))), 0, 30).astype(np.uint8)
    book = huffman.build_codebook(np.bincount(codes.reshape(-1), minlength=256))
    payloads, nbits = [], []
    for n in range(NBLK):
        w, nb = huffman.encode_block(codes[n], book)
        payloads.append(w)
        nbits.append(nb)
    W = max(len(w) for w in payloads)
    pay = np.zeros((NBLK, W), np.uint32)
    for n, w in enumerate(payloads):
        pay[n, : len(w)] = w
    return codes, book, pay, np.stack(nbits)


@pytest.mark.parametrize("NBLK,S,L", [(1, 4, 8), (3, 8, 16), (2, 16, 12)])
def test_huffman_decode_kernel_sweep(NBLK, S, L, rng):
    codes, book, pay, nbits = _encode_blocks(rng, NBLK, S, L)
    ch, isym, sym = book.as_device_tables()
    maxbits = int(nbits.sum(axis=1).max())
    dec = huffman_decode_pallas(jnp.asarray(pay), jnp.asarray(nbits),
                                ch, isym, sym, L, maxbits)
    assert (np.asarray(dec) == codes).all()


def test_huffman_fused_scores_kernel(rng):
    NBLK, S, D = 2, 8, 16
    codes, book, pay, nbits = _encode_blocks(rng, NBLK, S, D)
    ch, isym, sym = book.as_device_tables()
    maxbits = int(nbits.sum(axis=1).max())
    kmn = rng.normal(size=(NBLK, D)).astype(np.float32)
    kst = (0.05 * rng.uniform(1, 2, (NBLK, D))).astype(np.float32)
    q = rng.normal(size=(D,)).astype(np.float32)
    sc = huffman_attn_scores_pallas(
        jnp.asarray(pay), jnp.asarray(nbits), ch, isym, sym,
        jnp.asarray(kmn), jnp.asarray(kst), jnp.asarray(q), maxbits, scale=0.25)
    for n in range(NBLK):
        expect = ref.huffman_attn_scores_ref(
            jnp.asarray(pay[n]), jnp.asarray(nbits[n]), ch, isym, sym,
            jnp.asarray(kmn[n]), jnp.asarray(kst[n]), jnp.asarray(q), maxbits) * 0.25
        np.testing.assert_allclose(np.asarray(sc[n]), np.asarray(expect),
                                   atol=1e-4)


def test_decode_lut_entries_bounded(rng):
    """LUT invariants: consumed ∈ [1, 8], emitted entries reset to the root,
    probes ≤ 2 under the MAX_CODE_LEN limit.  (Deterministic — lives here
    rather than test_huffman.py so the production LUT decoder keeps tier-1
    coverage when the optional hypothesis dep gates that module away.)"""
    codes = np.clip(np.round(rng.normal(8, 6, (4096,))), 0, 255).astype(np.uint8)
    book = huffman.build_codebook(np.bincount(codes, minlength=256))
    lut = huffman.build_decode_lut(book)
    consumed = (lut >> 8) & 0xF
    emit = (lut >> 12) & 1
    nxt = lut >> 16
    assert consumed.min() >= 1 and consumed.max() <= huffman.LUT_CHUNK_BITS
    assert (nxt[emit == 1] == 0).all()
    assert (nxt < book.n_nodes).all()
    assert 1 <= book.decode_probes <= 2


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_lut_decode_matches_walk_deterministic(seed):
    """Deterministic LUT ≡ tree-walk equivalence (incl. a zero-bit padding
    stream and a truncated final stream) — the hypothesis-gated property
    test in test_huffman.py widens this sweep when the dep is present."""
    rng2 = np.random.default_rng(seed)
    skew = float(rng2.uniform(0.5, 30.0))
    S, L = int(rng2.integers(2, 10)), int(rng2.integers(2, 24))
    codes = np.clip(np.round(rng2.normal(8, skew, (S, L))), 0, 255).astype(np.uint8)
    book = huffman.build_codebook(np.bincount(codes.reshape(-1), minlength=256))
    w, nb = huffman.encode_block(codes, book)
    nb = np.insert(nb, S // 2, 0).astype(np.uint16)
    nb[-1] = nb[-1] // 2
    pay = jnp.asarray(np.concatenate([w, np.zeros(2, np.uint32)]))
    ch, isym, sym = book.as_device_tables()
    walk = huffman.decode_block_jax(pay, jnp.asarray(nb), ch, isym, sym,
                                    L, int(nb.max()))
    lut = huffman.decode_block_lut_jax(pay, jnp.asarray(nb),
                                       jnp.asarray(book.decode_lut()),
                                       L, book.decode_probes)
    assert (np.asarray(walk)[S // 2] == 0).all()
    assert (np.asarray(lut) == np.asarray(walk)).all()


# ---------------------------------------------------------------------------
# Store-stage kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("token_wise", [False, True])
@pytest.mark.parametrize("NBLK,T,D,bits", [(2, 8, 16, 5), (4, 16, 32, 3), (1, 16, 24, 8)])
def test_quant_pack_kernel_sweep(NBLK, T, D, bits, token_wise, rng):
    x = jnp.asarray(rng.normal(size=(NBLK, T, D)).astype(np.float32))
    w_p, mn_p, st_p = quant_pack_pallas(x, 0.05, bits, token_wise)
    w_r, mn_r, st_r = ref.quant_pack_ref(x, 0.05, bits, token_wise)
    assert (np.asarray(w_p) == np.asarray(w_r)).all()
    np.testing.assert_allclose(np.asarray(mn_p), np.asarray(mn_r), atol=1e-6)
    np.testing.assert_allclose(np.asarray(st_p), np.asarray(st_r), atol=1e-6)


def test_ops_quant_pack_wrapper(rng):
    x = jnp.asarray(rng.normal(size=(2, 8, 16)).astype(np.float32))
    for impl in ("pallas", "xla"):
        w, mn, st = ops.quant_pack(x, rel_scale=0.05, bits=5, token_wise=False,
                                   impl=impl)
        assert w.dtype == jnp.uint32
