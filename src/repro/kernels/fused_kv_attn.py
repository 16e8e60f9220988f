"""Pallas TPU kernel: fused in-situ decompression + flash-decode attention.

The TPU realization of the paper's cache-resident decompression (§3.3.2):
compressed store pages stream HBM→VMEM once per decode step; decoding
(layout-owned — see below), dequantization, and the attention matvec all
happen inside the kernel on VMEM/VREG data.  The decompressed K/V tiles are
never written back to HBM — exactly the paper's "decompressed data consumed
in situ", with VMEM playing the role of GPU shared memory and the MXU taking
the dot products.

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

Grid: ``(B, Hkv)``, one step per (batch row, kv head).  Its body walks the
row's LIVE store blocks only, ``PP`` pages (one compute block) at a time,
in a ``fori_loop`` of ``ceil(nb_valid[b] / PP)`` iterations: the work of a
(row, head) follows its live KV, and one grid step's fixed cost is spread
over all of its pages.  ``PP`` comes from the shapes alone
(``pages_per_block``: 1,024 tokens' worth of pages, trimmed to a VMEM
budget); the serving scheduler calls the same function for its
``serve.attn.*`` block-fill counters.

The store operands (payload and scale arrays) stay in HBM (``pl.ANY``):
the body gathers each compute block's pages into a double-buffered VMEM
stage with one async DMA per page and operand, the page ids read from the
scalar-prefetched page table (dense caches use the block index itself).
Block ``j + 1``'s copies are in flight while block ``j`` is decoded and
attended.  Only live pages are copied — below ``nb_valid[b]`` and, paged,
with a table entry ``>= 0`` (shard-local tables mark pages another shard
hosts ``-1``, DESIGN.md §12); a dead page's stage slot is masked out of the
scores and zeroed in V, so stale or uninitialized VMEM never reaches the
output.  Inside a block each page is decoded by the layout's
``decode_k``/``decode_v`` exactly as one page per step used to be, the
decoded pages are stacked to ``[PP·T, D]``, and the flash-decoding running
softmax ``(m, l, acc)`` takes one update per compute block over
``[G, PP·T]`` scores.  After the loop the raw append buffer (the exact
residual window) folds into the same running softmax — masked per row by
``buf_len`` — and the normalized output is emitted, so no separate XLA
combine pass runs after the kernel.

Per-row ``nb_valid``/``buf_len`` (and, paged, the ``[B, NB]`` page table)
arrive as scalar-prefetch args: every row of a continuous batch attends at
its own position, the contract the serving scheduler relies on.

Tiling rule (DESIGN.md §9): every VMEM operand's block spans whole trailing
dims — ``q``/``out``/buffers as ``[B, Hkv, G|T, D]`` blocks
``(1, 1, G|T, D)``; each page DMA moves one whole store tile (the layout's
2-D ``k_tile``/``v_tile``, or a ``(1, D)`` / ``(1, T)`` scale row) into a
``(2, PP, *tile)`` stage — so Mosaic never sees a block dim that is neither
a multiple of (8, 128) nor the full array dim, whatever G, D, T or the
packed word count are.
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

# A compute block covers this many tokens' pages (16 pages at T = 64) ...
BLOCK_TOKENS = 1024
# ... unless its VMEM (double-buffered stages + decoded f32 K/V) would pass
# this budget, a quarter of the smallest default scoped-VMEM limit.
BLOCK_VMEM_BYTES = 4 << 20


def _vmem_tile_bytes(shape) -> int:
    """Upper bound of the VMEM bytes one 2-D tile takes: 32-bit words,
    rows padded to 8 sublanes and columns to 128 lanes."""
    r, c = shape
    return 4 * (-(-r // 8) * 8) * (-(-c // 128) * 128)


def pages_per_block(nb: int, block_size: int, head_dim: int, tile) -> int:
    """Pages ``PP`` one compute block of the kernel gathers and attends.

    Derived from the shapes the kernel sees — ``nb`` store blocks per row,
    ``block_size`` tokens per page, the layout's tiles — and nothing else:
    ``BLOCK_TOKENS`` worth of pages, at most ``nb``, cut down until the
    block's VMEM fits ``BLOCK_VMEM_BYTES``.
    """
    T, D = block_size, head_dim
    stage = _vmem_tile_bytes(tile.k_tile) + _vmem_tile_bytes(tile.v_tile)
    if tile.has_scales:
        stage += _vmem_tile_bytes((1, 2 * D + 2 * T))  # the f32 scale row
    per_page = 2 * stage + 2 * T * D * 4  # two stages + decoded f32 K and V
    pp = max(1, min(nb, BLOCK_TOKENS // T))
    while pp > 1 and pp * per_page > BLOCK_VMEM_BYTES:
        pp -= 1
    return pp


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
    pages: int,
    paged: bool = False,
):
    pt_ref = None
    if paged:
        # The page table rides as a third scalar-prefetch operand: the body
        # reads page ids from it for its DMAs and its validity guard.
        pt_ref, refs = refs[0], refs[1:]
    n_st = 3 if has_scales else 2
    q_ref, kbuf_ref, vbuf_ref = refs[:3]
    aux_refs = refs[3:3 + n_aux]
    stores = refs[3 + n_aux:3 + n_aux + n_st]          # HBM (pl.ANY)
    out_ref = refs[3 + n_aux + n_st]
    stages = refs[4 + n_aux + n_st:4 + n_aux + 2 * n_st]  # VMEM [2, PP, ...]
    sem, acc_s, m_s, l_s = refs[4 + n_aux + 2 * n_st:]
    b = pl.program_id(0)
    h = pl.program_id(1)
    T, PP, D = block_size, pages, head_dim
    nb = nb_ref[b]

    acc_s[...] = jnp.zeros_like(acc_s)
    m_s[...] = jnp.full_like(m_s, NEG_INIT)
    l_s[...] = jnp.zeros_like(l_s)
    qg = q_ref[0, 0].astype(jnp.float32)  # [G, D]

    def scores(k):  # [n, D] -> [G, n], on the MXU
        return jax.lax.dot_general(qg, k, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32) * scale

    def fold(s, ok, v):
        """One flash-decoding update of (m, l, acc): scores s [G, n], whose
        columns outside ok [1, n] are dead, against values v [n, D]."""
        s = jnp.where(ok, s, NEG_INIT)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None]) * ok
        acc_s[...] = acc_s[...] * alpha[:, None] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=1)
        m_s[...] = m_new

    def page(j, i):
        """(store index, live) of page i of compute block j of this row."""
        n = j * PP + i
        live = n < nb
        if paged:
            p = pt_ref[b, jnp.minimum(n, nb_total - 1)]
            return (0, h, jnp.maximum(p, 0)), live & (p >= 0)
        return (b, h, jnp.minimum(n, nb_total - 1)), live

    def dma(j, slot, op):
        """Start or wait (``op``) the copies of compute block j's live pages
        into stage ``slot``: one per page and store operand."""
        def one(i, carry):
            idx, live = page(j, i)

            @pl.when(live)
            def _():
                for src, dst in zip(stores, stages):
                    getattr(pltpu.make_async_copy(
                        src.at[idx], dst.at[slot, i], sem.at[slot]), op)()

            return carry

        jax.lax.fori_loop(0, PP, one, 0)

    n_blocks = (nb + PP - 1) // PP

    @pl.when(n_blocks > 0)
    def _prefetch_first():
        dma(0, 0, "start")

    def block(j, carry):
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < n_blocks)
        def _prefetch_next():
            dma(j + 1, 1 - slot, "start")

        dma(j, slot, "wait")
        lives = [page(j, i)[1] for i in range(PP)]

        @pl.when(functools.reduce(jnp.logical_or, lives))
        def _attend():
            aux = tuple(r[...] for r in aux_refs)

            def tiles(i, k):
                """decode_k (k) / decode_v args of page i: tile, min, step."""
                tile = stages[0 if k else 1][slot, i]
                if not has_scales:
                    return tile, None, None
                sc = stages[2].at[slot, i]  # [1, W] f32: kmn|kst|vmn|vst
                o, n = (0, D) if k else (2 * D, T)
                return tile, sc[:, o:o + n], sc[:, o + n:o + 2 * n]

            # Decompress the block's pages in situ (layout-owned decode per
            # page); a dead page's columns are masked and its V rows zeroed,
            # since its stage slot holds stale or uninitialized VMEM.
            kd = jnp.concatenate(
                [decode_k(*tiles(i, True), *aux) for i in range(PP)])
            vd = jnp.concatenate(
                [jnp.where(lives[i], decode_v(*tiles(i, False), *aux), 0.0)
                 for i in range(PP)])  # [PP*T, D]
            tpage = jax.lax.broadcasted_iota(jnp.int32, (1, PP * T), 1) // T
            ok = functools.reduce(
                jnp.logical_or, [(tpage == i) & lives[i] for i in range(PP)])
            fold(scores(kd), ok, vd)

        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)

    # Fold the raw buffer tail (masked per row by buf_len) into the running
    # softmax and emit the normalized output.
    tpos = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
    fold(scores(kbuf_ref[0, 0].astype(jnp.float32)), tpos < bl_ref[b],
         vbuf_ref[0, 0].astype(jnp.float32))
    out_ref[0, 0] = acc_s[...] / jnp.maximum(l_s[...], 1e-30)[:, None]


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
    ``nb_valid``/``buf_len`` as a scalar-prefetch operand and the body's
    page DMAs read logical block ``n`` of row ``b`` from arena page
    ``page_tab[b, n]`` — decode and flash softmax are untouched by paging.
    Unassigned entries (-1) are neither copied nor attended — which also
    covers shard-local tables (DESIGN.md §12) where blocks below
    ``nb_valid`` may be ``-1`` because another shard hosts them.
    ``nb_valid`` must not exceed the table's (or dense store's) ``NB``.
    """
    B, Hq, D = q.shape
    paged = page_tab is not None
    Hkv = k_store.shape[1]
    NB = page_tab.shape[1] if paged else k_store.shape[2]
    G, T = Hq // Hkv, block_size
    PP = pages_per_block(NB, T, D, tile)
    if scale is None:
        scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(
        _kernel,
        decode_k=tile.decode_k, decode_v=tile.decode_v,
        has_scales=tile.has_scales, n_aux=len(tile.aux),
        block_size=T, head_dim=D, scale=scale, nb_total=NB, pages=PP,
        paged=paged,
    )

    row_head = lambda shape: pl.BlockSpec(shape, lambda b, h, *_: (b, h, 0, 0))
    in_specs = [row_head((1, 1, G, D)), row_head((1, 1, T, D)),
                row_head((1, 1, T, D))]
    inputs = [q.reshape(B, Hkv, G, D), k_buf, v_buf]
    for a in tile.aux:
        # Per-layer aux operand (e.g. a codebook LUT): block-invariant, one
        # whole-array tile staged into VMEM with a constant index map.
        arr = jnp.asarray(a)
        in_specs.append(pl.BlockSpec(
            arr.shape, lambda b, h, *_, n=arr.ndim: (0,) * n))
        inputs.append(arr)

    for arr, tile_shape in ((k_store, tile.k_tile), (v_store, tile.v_tile)):
        if arr.shape[3:] != tuple(tile_shape):
            raise ValueError(
                f"store operand {arr.shape} does not end in its tile "
                f"{tuple(tile_shape)}: a page DMA must move whole trailing dims")
    stores = [k_store, v_store]
    stages = [pltpu.VMEM((2, PP) + tuple(tile.k_tile), k_store.dtype),
              pltpu.VMEM((2, PP) + tuple(tile.v_tile), v_store.dtype)]
    if tile.has_scales:
        # A page's four scale rows travel as ONE f32 row [kmn|kst|vmn|vst],
        # lane-padded to whole 128-lane tiles: Mosaic cannot DMA a single
        # bf16 row (two rows share a 32-bit sublane word), nor a row
        # narrower than its lane tile, and one copy per page beats four.
        # The bf16 -> f32 cast is exact: decode sees the same values.
        W = -(-(2 * D + 2 * T) // 128) * 128
        rows = jnp.concatenate(
            [a.astype(jnp.float32) for a in (k_min, k_step, v_min, v_step)],
            axis=-1)
        pad = [(0, 0)] * (rows.ndim - 1) + [(0, W - 2 * D - 2 * T)]
        stores.append(jnp.pad(rows, pad))
        stages.append(pltpu.VMEM((2, PP, 1, W), jnp.float32))
    # The stores stay in HBM; the body gathers their pages into the stages.
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * len(stores)
    inputs += stores

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
            grid=(B, Hkv),
            in_specs=in_specs,
            out_specs=row_head((1, 1, G, D)),
            scratch_shapes=stages + [
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((G, D), jnp.float32),
                pltpu.VMEM((G,), jnp.float32),
                pltpu.VMEM((G,), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(*scalars, *inputs).reshape(B, Hq, D)
