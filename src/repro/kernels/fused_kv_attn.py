"""Pallas TPU kernel: fused in-situ decompression + flash-decode attention.

The TPU realization of the paper's cache-resident decompression (§3.3.2):
compressed store tiles stream HBM→VMEM once per block; decoding (layout-owned
— see below), dequantization, and the attention matvec all happen inside the
kernel on VMEM/VREG data.  The decompressed K/V tiles are never written back
to HBM — exactly the paper's "decompressed data consumed in situ", with VMEM
playing the role of GPU shared memory and the MXU taking the dot products.

The per-tile decode is NOT hardcoded to one layout: the kernel is
parameterized by a ``repro.core.layouts.FusedTileSpec`` — the layout-owned
``tile_decode`` hook (DESIGN.md §9).  ``packed``/``kivi`` share the
planar no-straddle shift/mask unpack; ``raw`` plugs in a passthrough
decoder; and ``huffman`` (off-TPU only: Mosaic does not lower its LUT
gathers) decodes its ragged-payload slots via the tile spec's per-layer
``aux`` operands — block-invariant arrays (the canonical codebooks' chunked
direct-lookup LUTs) the kernel stages into VMEM with constant index maps
and appends to every decode call, while the per-stream u16 bit counts
arrive as part of the fixed worst-case-padded slot tile itself.

Grid: ``(B, Hkv, NB + 1)``.  TPU grids execute sequentially with the last
axis innermost, so VMEM scratch carries the flash-decoding running state
``(m, l, acc)`` across the block axis for each (batch, kv-head) pair.  The
extra final step folds the raw append buffer (the exact residual window) into
the same running softmax — masked per row by ``buf_len`` — and emits the
normalized output, so no separate XLA combine pass runs after the kernel.

Per-row ``nb_valid``/``buf_len`` arrive as scalar-prefetch args (indexed by
the batch grid axis before the body runs): every row of a continuous batch
attends at its own position, the contract the serving scheduler relies on.
Paged caches (DESIGN.md §10) add the per-row page table as a third
scalar-prefetch operand: the store BlockSpec index maps resolve logical
block ``n`` of row ``b`` to its physical arena page before the tile streams
HBM→VMEM, so the kernel body decodes pooled storage completely unchanged.

Tiling rule (DESIGN.md §9): every operand's block spans whole trailing
dims — ``q``/``out`` as ``[B, Hkv, G, D]`` (block ``(1, 1, G, D)``), store
tiles as the layout's 2-D ``k_tile``/``v_tile``, the quantization units as
``(1, D)`` / ``(1, T)`` rows — so Mosaic never sees a block dim that is
neither a multiple of (8, 128) nor the full array dim, whatever G, D, T or
the packed word count are.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import NEG_INIT
from repro.kernels.runtime import resolve_interpret

Array = jax.Array


def _kernel(
    nb_ref,        # scalar prefetch: i32 [B] per-row valid block counts
    bl_ref,        # scalar prefetch: i32 [B] per-row buffer lengths
    *refs,
    decode_k,
    decode_v,
    has_scales: bool,
    n_aux: int,
    block_size: int,
    head_dim: int,
    scale: float,
    nb_total: int,
    paged: bool = False,
):
    pt_ref = None
    if paged:
        # The page table rides as a third scalar-prefetch operand.  The
        # BlockSpec index maps consume it to resolve logical block n to its
        # arena page before the tile streams HBM→VMEM; the body reads it
        # once more for the validity guard below (shard-local tables mark
        # blocks this arena does not host with -1 — DESIGN.md §12 — and
        # those steps must contribute nothing, not decode a clamped page).
        pt_ref = refs[0]
        refs = refs[1:]
    # Per-layer aux operands (block-invariant, e.g. huffman's decode LUTs)
    # sit between the buffers and the output; their VMEM-resident values
    # are appended to every decode call — read inside the decode-step guard
    # only, so skipped steps and the buffer-combine step never load them.
    if n_aux:
        aux_refs = refs[-(4 + n_aux):-4]
        refs = refs[:-(4 + n_aux)] + refs[-4:]
    else:
        aux_refs = ()
    if has_scales:
        (q_ref, ks_ref, kmn_ref, kst_ref, vs_ref, vmn_ref, vst_ref,
         kbuf_ref, vbuf_ref, out_ref, acc_s, m_s, l_s) = refs
    else:
        (q_ref, ks_ref, vs_ref, kbuf_ref, vbuf_ref,
         out_ref, acc_s, m_s, l_s) = refs
        kmn_ref = kst_ref = vmn_ref = vst_ref = None
    b = pl.program_id(0)
    n = pl.program_id(2)
    T = block_size

    @pl.when(n == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)
        m_s[...] = jnp.full_like(m_s, NEG_INIT)
        l_s[...] = jnp.zeros_like(l_s)

    # Store blocks: each batch row of a continuous batch has its own number
    # of live blocks; steps past nb_valid[b] (and the final buffer step) skip.
    # Paged shards additionally skip blocks whose table entry is unassigned
    # (-1): their index map clamped to page 0, which holds some other row's
    # data, so the step must not touch the running softmax.
    live = n < nb_ref[b]
    if paged:
        live = live & (pt_ref[b, jnp.minimum(n, nb_total - 1)] >= 0)

    @pl.when(live)
    def _update():
        aux = tuple(r[...] for r in aux_refs)
        # --- decompress K in situ (VMEM), layout-owned decode ---
        kd = decode_k(ks_ref[0, 0, 0],
                      kmn_ref[0, 0, 0] if has_scales else None,
                      kst_ref[0, 0, 0] if has_scales else None,
                      *aux)  # [T, D]
        # --- scores on the MXU ---
        qg = q_ref[0, 0].astype(jnp.float32)  # [G, D]
        s = jax.lax.dot_general(qg, kd, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        # --- flash-decoding running softmax ---
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])  # [G, T]
        # --- decompress V in situ and accumulate ---
        vd = decode_v(vs_ref[0, 0, 0],
                      vmn_ref[0, 0, 0] if has_scales else None,
                      vst_ref[0, 0, 0] if has_scales else None,
                      *aux)  # [T, D]
        acc_s[...] = acc_s[...] * alpha[:, None] + jax.lax.dot(
            p, vd, preferred_element_type=jnp.float32)
        l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=1)
        m_s[...] = m_new

    # Final grid step: fold the raw buffer tail into the running softmax
    # (masked per row by buf_len) and emit the normalized output.
    @pl.when(n == nb_total)
    def _buffer_and_emit():
        qg = q_ref[0, 0].astype(jnp.float32)  # [G, D]
        kb = kbuf_ref[0, 0].astype(jnp.float32)  # [T, D]
        s = jax.lax.dot_general(qg, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        tpos = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
        ok = tpos < bl_ref[b]  # [1, T]
        s = jnp.where(ok, s, NEG_INIT)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None]) * ok  # [G, T]
        vb = vbuf_ref[0, 0].astype(jnp.float32)
        acc = acc_s[...] * alpha[:, None] + jax.lax.dot(
            p, vb, preferred_element_type=jnp.float32)
        l = l_s[...] * alpha + jnp.sum(p, axis=1)
        out_ref[0, 0] = acc / jnp.maximum(l, 1e-30)[:, None]


def fused_cache_attention_pallas(
    q: Array,
    k_store: Array, k_min: Array, k_step: Array,
    v_store: Array, v_min: Array, v_step: Array,
    k_buf: Array, v_buf: Array,
    nb_valid: Array,  # i32 [B] per-row valid block counts (scalar broadcasts)
    buf_len: Array,   # i32 [B] per-row buffer lengths (scalar broadcasts)
    page_tab: Array | None = None,  # i32 [B, NB] paged: slot -> arena page
    *,
    tile,             # layouts.FusedTileSpec (memoized — see fused_tile_spec)
    block_size: int,
    scale: float | None = None,
    interpret: bool | str = "auto",
) -> Array:
    """Full decode attention over (store ∥ buffer) -> [B, Hq, D] f32.

    With ``page_tab`` the stores are a shared paged arena (batch extent 1,
    ``P`` pages on the block axis — DESIGN.md §10): the table joins
    ``nb_valid``/``buf_len`` as a scalar-prefetch operand and every store
    BlockSpec index map resolves logical block ``n`` of row ``b`` to
    ``page_tab[b, n]`` before the tile streams HBM→VMEM — the kernel body
    (decode, flash softmax) is untouched by paging.  Unassigned entries
    (-1) clamp to page 0 in the index map and their grid steps skip via
    the body's validity guard — which also covers shard-local tables
    (DESIGN.md §12) where blocks below ``nb_valid`` may be ``-1`` because
    another shard hosts them.
    """
    B, Hq, D = q.shape
    paged = page_tab is not None
    Hkv = k_store.shape[1]
    NB = page_tab.shape[1] if paged else k_store.shape[2]
    P = k_store.shape[2]  # physical block extent (arena pages when paged)
    G, T = Hq // Hkv, block_size
    if scale is None:
        scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(
        _kernel,
        decode_k=tile.decode_k, decode_v=tile.decode_v,
        has_scales=tile.has_scales, n_aux=len(tile.aux),
        block_size=T, head_dim=D, scale=scale, nb_total=NB, paged=paged,
    )
    grid = (B, Hkv, NB + 1)

    # Index maps take the scalar-prefetch refs as trailing args; store tiles
    # clamp to the last block on the buffer step (loaded but unused).  The
    # paged variants get one extra trailing ref (the page table).
    in_specs = []
    inputs = []

    def fixed_map(*idx):
        return lambda b, h, n, *scalars: tuple(
            b if i == "b" else h if i == "h" else i for i in idx)

    in_specs.append(pl.BlockSpec((1, 1, G, D), fixed_map("b", "h", 0, 0)))
    inputs.append(q.reshape(B, Hkv, G, D))

    def add_store(arr, tile_shape):
        if arr.shape[3:] != tuple(tile_shape):
            raise ValueError(
                f"store operand {arr.shape} does not end in its tile "
                f"{tuple(tile_shape)}: a block must span whole trailing dims")
        r = len(tile_shape)
        if paged:
            def imap(b, h, n, nb, bl, pt, r=r):
                page = pt[b, jnp.minimum(n, NB - 1)]
                return (0, h, jnp.clip(page, 0, P - 1)) + (0,) * r
        else:
            def imap(b, h, n, nb, bl, r=r):
                return (b, h, jnp.minimum(n, NB - 1)) + (0,) * r
        in_specs.append(pl.BlockSpec((1, 1, 1) + tuple(tile_shape), imap))
        inputs.append(arr)

    add_store(k_store, tile.k_tile)
    if tile.has_scales:
        add_store(k_min, (1, D))
        add_store(k_step, (1, D))
    add_store(v_store, tile.v_tile)
    if tile.has_scales:
        add_store(v_min, (1, T))
        add_store(v_step, (1, T))
    for buf in (k_buf, v_buf):
        in_specs.append(pl.BlockSpec((1, 1, T, D), fixed_map("b", "h", 0, 0)))
        inputs.append(buf)
    for a in tile.aux:
        # Per-layer aux operand (e.g. a codebook LUT): block-invariant, one
        # whole-array tile staged into VMEM with a constant index map.
        arr = jnp.asarray(a)
        in_specs.append(pl.BlockSpec(arr.shape, fixed_map(*(0,) * arr.ndim)))
        inputs.append(arr)

    out_spec = pl.BlockSpec((1, 1, G, D), fixed_map("b", "h", 0, 0))
    scalars = [
        jnp.broadcast_to(jnp.atleast_1d(nb_valid), (B,)).astype(jnp.int32),
        jnp.broadcast_to(jnp.atleast_1d(buf_len), (B,)).astype(jnp.int32),
    ]
    if paged:
        scalars.append(page_tab.astype(jnp.int32))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=grid,
            in_specs=in_specs,
            out_specs=out_spec,
            scratch_shapes=[
                pltpu.VMEM((G, D), jnp.float32),
                pltpu.VMEM((G,), jnp.float32),
                pltpu.VMEM((G,), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(*scalars, *inputs).reshape(B, Hq, D)
