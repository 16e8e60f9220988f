"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

Each function mirrors one kernel's semantics exactly, built only from jnp ops
already validated against numpy in ``repro.core``.  Kernel tests sweep shapes
and dtypes and ``assert_allclose`` kernel-vs-oracle.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core import bitpack

Array = jax.Array

NEG_INIT = -1e30  # finite "-inf" so flash combines never produce NaN


def dequant_k(codes: Array, k_min: Array, k_step: Array) -> Array:
    """codes [..., T, D], k_min/k_step [..., 1, D] (BlockQuant units)."""
    return (k_min.astype(jnp.float32)
            + codes.astype(jnp.float32) * k_step.astype(jnp.float32))


def dequant_v(codes: Array, v_min: Array, v_step: Array) -> Array:
    """codes [..., T, D], v_min/v_step [..., 1, T] (TokenQuant units)."""
    return (v_min[..., 0, :, None].astype(jnp.float32)
            + codes.astype(jnp.float32) * v_step[..., 0, :, None].astype(jnp.float32))


def fused_cache_attention_ref(
    q: Array,          # [B, Hq, D]
    k_store: Array,    # [B, Hkv, NB, *tile.k_tile]  (paged: [1, Hkv, P, ...])
    k_min: Array,      # [B, Hkv, NB, 1, D] (ignored when not tile.has_scales)
    k_step: Array,
    v_store: Array,    # [B, Hkv, NB, *tile.v_tile]
    v_min: Array,      # [B, Hkv, NB, 1, T]
    v_step: Array,
    k_buf: Array, v_buf: Array,  # [B, Hkv, T, D]
    nb_valid: Array,   # i32 [B] per-row valid block counts (scalar broadcasts)
    buf_len: Array,    # i32 [B] per-row buffer lengths (scalar broadcasts)
    page_tab: Array | None = None,  # i32 [B, NB] paged: slot -> arena page
    *,
    tile,              # layouts.FusedTileSpec — same decode the kernel runs
    block_size: int,
    scale: float | None = None,
) -> Array:
    """Oracle for the fused in-situ-decompression attention kernel.

    vmaps the layout's per-tile decode over (B, Hkv, NB) — deliberately
    materializing the dequantized store, because that is what makes it an
    oracle rather than a second implementation of the lazily-decoded paths.
    With ``page_tab`` the stores are a shared paged arena (DESIGN.md §10):
    each row's tiles are gathered through its page-table entries first —
    the same indirection the kernel performs in its index maps — and
    unassigned slots clamp to page 0 under the ``nb_valid`` mask.  Slots
    whose table entry is unassigned (< 0) are additionally masked
    regardless of ``nb_valid`` — the shard-local table semantics of
    DESIGN.md §12, where a shard sees ``-1`` for any block it does not
    host and must contribute nothing for it.
    Returns the normalized output [B, Hq, D] f32 (buffer tail included).
    """
    B, Hq, D = q.shape
    page_ok = None
    if page_tab is not None:
        P = k_store.shape[2]
        idx = jnp.clip(page_tab, 0, P - 1)  # [B, NB]
        page_ok = page_tab >= 0            # [B, NB]
        gather = lambda a: jnp.moveaxis(jnp.take(a[0], idx, axis=1), 1, 0)
        k_store, v_store = gather(k_store), gather(v_store)
        if tile.has_scales:
            k_min, k_step = gather(k_min), gather(k_step)
            v_min, v_step = gather(v_min), gather(v_step)
    Hkv, NB = k_store.shape[1], k_store.shape[2]
    G, T = Hq // Hkv, block_size
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    nbv = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(nb_valid, jnp.int32)), (B,))

    # Per-layer aux operands (block-invariant — e.g. huffman's decode LUTs)
    # are closed over un-vmapped, mirroring the kernel's constant index maps.
    aux = tuple(jnp.asarray(a) for a in tile.aux)

    def dec3(fn, store, mn, st):
        if tile.has_scales:
            f = jax.vmap(jax.vmap(jax.vmap(lambda t, m, s: fn(t, m, s, *aux))))
            return f(store, mn, st)
        f = jax.vmap(jax.vmap(jax.vmap(lambda t: fn(t, None, None, *aux))))
        return f(store)

    kd = dec3(tile.decode_k, k_store, k_min, k_step)  # [B,Hkv,NB,T,D] f32
    vd = dec3(tile.decode_v, v_store, v_min, v_step)
    qg = q.reshape(B, Hkv, G, D).astype(jnp.float32)
    s = jnp.einsum("bhgd,bhntd->bhgnt", qg, kd) * scale
    ok_b = jnp.arange(NB)[None, :] < nbv[:, None]  # [B, NB]
    if page_ok is not None:
        ok_b = ok_b & page_ok
    ok = ok_b[:, None, None, :, None]
    s = jnp.where(ok, s, NEG_INIT)
    s2 = s.reshape(B, Hkv, G, NB * T)
    m = jnp.max(s2, axis=-1)
    m = jnp.maximum(m, NEG_INIT)
    p = jnp.exp(s2 - m[..., None]) * jnp.repeat(ok[..., 0], T, -1)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhgnt,bhntd->bhgd", p.reshape(B, Hkv, G, NB, T), vd)
    return combine_with_buffer_ref(
        acc.reshape(B, Hq, D), m.reshape(B, Hq), l.reshape(B, Hq),
        q, k_buf, v_buf, buf_len, scale=scale)


def combine_with_buffer_ref(
    acc: Array, m: Array, l: Array,  # from the main (packed) part
    q: Array,                        # [B, Hq, D]
    k_buf: Array, v_buf: Array,      # [B, Hkv, T, D]
    buf_len: Array,                  # i32 [B] per-row (scalar broadcasts)
    scale: float | None = None,
):
    """Two-part softmax combine: packed-store partials + raw tail buffer."""
    B, Hq, D = q.shape
    Hkv, T = k_buf.shape[1], k_buf.shape[2]
    G = Hq // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    bl = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(buf_len, jnp.int32)), (B,))
    qg = q.reshape(B, Hkv, G, D).astype(jnp.float32)
    s = jnp.einsum("bhgd,bhtd->bhgt", qg, k_buf.astype(jnp.float32)) * scale
    ok = (jnp.arange(T)[None, :] < bl[:, None])[:, None, None, :]
    s = jnp.where(ok, s, NEG_INIT)
    mb = jnp.maximum(jnp.max(s, axis=-1), NEG_INIT)
    pb = jnp.exp(s - mb[..., None]) * ok
    lb = jnp.sum(pb, axis=-1)
    accb = jnp.einsum("bhgt,bhtd->bhgd", pb, v_buf.astype(jnp.float32))
    mb, lb, accb = mb.reshape(B, Hq), lb.reshape(B, Hq), accb.reshape(B, Hq, D)

    M = jnp.maximum(m, mb)
    a1 = jnp.exp(m - M)
    a2 = jnp.exp(mb - M)
    denom = l * a1 + lb * a2
    out = (acc * a1[..., None] + accb * a2[..., None]) / jnp.maximum(denom, 1e-30)[..., None]
    return out


def quant_pack_ref(x: Array, rel_scale: float, bits: int, token_wise: bool):
    """Oracle for the Store-stage kernel: quantize + planar no-straddle pack.

    x: [NBLK, T, D].  token_wise=False -> K BlockQuant (units: block×channel);
    True -> V TokenQuant (units: token).
    Returns (words u32 [NBLK, R, D] planar, mn [NBLK, 1, U], step
    [NBLK, 1, U]) with U = T for token_wise else D — the cache layouts'
    stored shapes.
    """
    xf = x.astype(jnp.float32)
    axes = (-1,) if token_wise else (-2,)
    mn = jnp.min(xf, axis=axes, keepdims=True)
    mx = jnp.max(xf, axis=axes, keepdims=True)
    step = rel_scale * (mx - mn)
    safe = jnp.where(step > 0, step, 1.0)
    codes = jnp.clip(jnp.round((xf - mn) / safe), 0, 2**bits - 1).astype(jnp.uint8)
    words = bitpack.pack_planar(codes, bits)
    row = lambda a: jnp.squeeze(a, axes)[:, None, :]
    return words, row(mn), row(step)


def huffman_decode_ref(payload, nbits, children, is_symbol, symbols, n_per_stream, max_stream_bits):
    """Oracle for the branchless-walk kernel: defer to the validated core impl."""
    from repro.core import huffman

    return huffman.decode_block_jax(
        payload, nbits, children, is_symbol, symbols, n_per_stream, max_stream_bits
    )


def huffman_attn_scores_ref(
    payload, nbits, children, is_symbol, symbols,
    k_min, k_step, q, max_stream_bits,
):
    """Oracle for the fused Huffman-decode + dot-product kernel.

    One stream per cached token (a [head_dim] K row).  Returns scores [S]:
    score_s = dequant(decode(stream_s)) · q.
    """
    D = q.shape[-1]
    codes = huffman_decode_ref(payload, nbits, children, is_symbol, symbols, D, max_stream_bits)
    kd = k_min[None, :].astype(jnp.float32) + codes.astype(jnp.float32) * k_step[None, :].astype(jnp.float32)
    return kd @ q.astype(jnp.float32)
