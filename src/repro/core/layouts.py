"""Pluggable cache-layout strategies (DESIGN.md §4).

The paper's central claim is that the cache layout and the compression
algorithm are co-designed *and swappable per workload* (§4.2's KVCompCache
integration point).  This module makes that a first-class API: every way of
storing a layer's KV blocks is a ``CacheLayout`` registered by name, and the
cache manager (``repro.core.cache``), the fused kernels
(``repro.kernels.ops``), the serving engine, and the dry-run cost model all
dispatch through the registry instead of string-comparing layout names.

A layout owns four responsibilities:

* ``init_store``      — allocate the six store arrays of a ``LayerKVCache``
                        (payload + per-unit quantization scales).
* ``write_blocks``    — the Store stage: quantize + encode whole compression
                        blocks into slots of the block ring (prefill bulk
                        writes and decode-time buffer flushes share this).
* ``decode_block`` /
  ``tile_decode``     — the Fetch stage hot paths (DESIGN.md §9):
                        ``decode_block`` lazily decodes ONE block for the
                        blockwise XLA attention scan (the portable floor every
                        layout gets by default); ``tile_decode`` hands the
                        fused Pallas kernel a per-VMEM-tile decoder so
                        fused-eligible layouts (``supports_fused``) run the
                        in-situ ``q·(m + s∘c)`` kernel.  ``attend_block`` is
                        the single dispatch point between them (via the
                        backend registry in ``repro.kernels.ops``).
* ``fetch``           — bulk reconstruction of dequantized
                        ``[B, H, NB, T, D]`` K/V blocks — reconstruction,
                        tests, and the ``attend_materialized`` oracle only;
                        never on the decode hot path.
* ``size_report`` / ``bytes_per_token`` — exact and analytic size accounting
                        (metadata included), shared by the codec reports and
                        the roofline model.

Built-in layouts: ``raw`` (bf16, exact), ``packed`` (error-bounded quantizer
+ planar no-straddle bit-packing), ``kivi`` (fixed-bit baseline), and
``huffman`` (the paper's maximal-ratio path promoted to a servable layout:
per-block Huffman streams with u16 per-stream bit counts, decoded by the
chunked direct-lookup decoder — in the blockwise XLA floor everywhere, and
in VMEM inside the fused kernel off-TPU only).  Register new ones with
``@register_layout("name")``.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitpack, huffman
from repro.obs.profiling import annotate

Array = jax.Array

RAW_BITS_PER_VALUE = 16  # KV caches are bf16/fp16 at rest


def bits_for_rel_scale(rel_scale: float) -> int:
    """Static bit width that covers every code of an error-bounded quantizer:
    max code = round(1/rel_scale)."""
    return max(1, math.ceil(math.log2(round(1.0 / rel_scale) + 1)))


# ---------------------------------------------------------------------------
# Size accounting (paper §3.3.2 ~1/128 metadata analysis)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RatioReport:
    """Exact size accounting for one compressed tensor."""

    n_values: int
    payload_bits: int
    scale_bits: int
    stream_meta_bits: int
    offset_meta_bits: int
    codebook_bits: int

    @property
    def total_bits(self) -> int:
        return (
            self.payload_bits
            + self.scale_bits
            + self.stream_meta_bits
            + self.offset_meta_bits
            + self.codebook_bits
        )

    @property
    def ratio(self) -> float:
        return self.n_values * RAW_BITS_PER_VALUE / max(self.total_bits, 1)

    @property
    def bits_per_value(self) -> float:
        return self.total_bits / max(self.n_values, 1)


def raw_ratio(q) -> RatioReport:
    """Uncompressed baseline: 16 bits/value, no metadata."""
    return RatioReport(
        n_values=int(q.codes.size),
        payload_bits=int(q.codes.size) * RAW_BITS_PER_VALUE,
        scale_bits=0,
        stream_meta_bits=0,
        offset_meta_bits=0,
        codebook_bits=0,
    )


def kivi_ratio(q, bits: int) -> RatioReport:
    """KIVI baseline: fixed b-bit payload + fp16 (min, step) per unit."""
    return RatioReport(
        n_values=int(q.codes.size),
        payload_bits=int(q.codes.size) * bits,
        scale_bits=q.meta_bits,
        stream_meta_bits=0,
        offset_meta_bits=0,
        codebook_bits=0,
    )


def huffman_ratio(q, book: huffman.CodeBook, streams_shape: tuple[int, int]) -> RatioReport:
    """KVComp Huffman path sizes from the histogram (exact expected bits)."""
    hist = np.bincount(np.asarray(q.codes).reshape(-1), minlength=huffman.N_SYMBOLS)
    payload = int((hist * book.lengths).sum())
    n_streams = int(np.prod(q.codes.shape)) // streams_shape[1]
    n_blocks = max(n_streams // streams_shape[0], 1)
    return RatioReport(
        n_values=int(q.codes.size),
        payload_bits=payload,
        scale_bits=q.meta_bits,
        stream_meta_bits=n_streams * 16,  # u16 bit count per stream (per-thread metadata)
        offset_meta_bits=n_blocks * 32,  # u32 offset per block (Block Offsets Array)
        codebook_bits=book.serialized_bits,
    )


def packed_ratio(q, block_codes: int) -> RatioReport:
    """TPU adaptive fixed-length path sizes."""
    codes = np.asarray(q.codes).reshape(-1, block_codes)
    mx = codes.max(axis=1).astype(np.int64)
    b = np.maximum(np.ceil(np.log2(mx + 1)), 1).astype(np.int64)
    payload = int((((block_codes * b) + 31) // 32 * 32).sum())
    n_blocks = codes.shape[0]
    return RatioReport(
        n_values=int(q.codes.size),
        payload_bits=payload,
        scale_bits=q.meta_bits,
        stream_meta_bits=n_blocks * 8,  # u8 width per block
        offset_meta_bits=n_blocks * 32,
        codebook_bits=0,
    )


# ---------------------------------------------------------------------------
# Shared quantization primitive (paper §3.1.1)
# ---------------------------------------------------------------------------


def quant_block_minmax(x: Array, rel_scale: float, bits: int,
                       unit_axes: tuple[int, ...], kivi: bool):
    """Quantize one buffer block. x: [..., T, D] (f32). Returns codes u8 +
    (min, step) with unit axes reduced."""
    mn = jnp.min(x, axis=unit_axes, keepdims=True)
    mx = jnp.max(x, axis=unit_axes, keepdims=True)
    if kivi:
        step = (mx - mn) / (2**bits - 1)
    else:
        step = rel_scale * (mx - mn)
    safe = jnp.where(step > 0, step, 1.0)
    codes = jnp.clip(jnp.round((x - mn) / safe), 0, 2**bits - 1).astype(jnp.uint8)
    return codes, jnp.squeeze(mn, unit_axes), jnp.squeeze(step, unit_axes)


def _init_units(B: int, H: int, NB: int, n: int, dtype):
    """Zeroed (min, step) for one tensor: one unit row per block, ``[B, H,
    NB, 1, n]`` (n = D for K, T for V), so a block's units form a
    whole-trailing-dims kernel tile."""
    return tuple(jnp.zeros((B, H, NB, 1, n), dtype) for _ in range(2))


def dequant_k(codes: Array, mn: Array, st: Array) -> Array:
    """codes [..., T, D]; BlockQuant units mn/st [..., 1, D] -> f32."""
    f32 = jnp.float32
    return mn.astype(f32) + codes.astype(f32) * st.astype(f32)


def dequant_v(codes: Array, mn: Array, st: Array) -> Array:
    """codes [..., T, D]; TokenQuant units mn/st [..., 1, T] -> f32 (the
    units are stored lane-dense and turned into a column here)."""
    f32 = jnp.float32
    col = lambda a: jnp.swapaxes(a.astype(f32), -1, -2)
    return col(mn) + codes.astype(f32) * col(st)


# ---------------------------------------------------------------------------
# The layout interface + registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False)
class FusedTileSpec:
    """Layout-owned decode hook for the fused Pallas attention kernel.

    The kernel (``repro.kernels.fused_kv_attn``) gathers store tiles
    HBM→VMEM, one DMA per page, and calls ``decode_k``/``decode_v`` once per
    page to reconstruct the dequantized ``[T, D]`` block in situ (DESIGN.md
    §9).  The decode callables
    must be kernel-safe (no captured host arrays, jnp ops only); they are also
    ``vmap``-ed over (B, H, NB) by the kernel's pure-jnp oracle, so one
    definition serves both paths.

    k_tile / v_tile : per-block store tile shape (what one page DMA moves):
        always 2-D and equal to the store's two trailing dims, e.g.
        ``(Rk, D)`` planar words or ``(T, D)`` raw values — the Mosaic
        tiling rule every kernel operand obeys (DESIGN.md §9).
    has_scales      : whether (min, step) arrays accompany the store; when
        False the decode callables receive ``None`` for both.
    decode_k(tile, mn, st, *aux) -> [T, D] f32 ; decode_v likewise (mn/st
        are the per-block BlockQuant ``[1, D]`` / TokenQuant ``[1, T]``
        units).
    aux             : per-LAYER operands (small numpy arrays, identical for
        every tile) the kernel stages into VMEM alongside the tiles and
        appends to each decode call — e.g. the huffman layout's flat
        chunked-decode LUTs.  Block-invariant: their BlockSpec index maps
        are constant, the oracle closes over them un-vmapped.

    Instances must be cached per (layout, spec, head_dim) — they carry
    closures (and aux arrays), and jit statics hash them by IDENTITY
    (``eq=False``: ndarray fields forbid structural hashing), so a fresh
    instance per call would defeat every jit cache (see ``fused_tile_spec``).
    """

    k_tile: tuple[int, ...]
    v_tile: tuple[int, ...]
    has_scales: bool
    decode_k: object
    decode_v: object
    aux: tuple = ()


@functools.lru_cache(maxsize=256)
def fused_tile_spec(layout_name: str, spec, head_dim: int) -> FusedTileSpec | None:
    """Stable (memoized) tile spec so jit caches keyed on it don't retrace.

    ``supports_fused`` is authoritative: a layout that clears it gets None
    even if it inherits a ``_tile_decode`` from a fused-capable base (a
    custom layout subclassing packed with a different slot encoding — the
    packed unpacker would silently misread its slots).
    """
    lay = get_layout(layout_name)
    if not lay.supports_fused:
        return None
    return lay._tile_decode(spec, head_dim)


class _BlockView:
    """One-block slice of a cache's six store arrays (duck-typed cache).

    ``decode_block``'s generic fallback feeds this to ``decompress_k``/``_v``
    so any layout that can decompress its full store automatically gets the
    blockwise lazily-dequantized attention path.  Arrays without a block axis
    (e.g. the raw layout's dummy scales) pass through untouched.
    """

    def __init__(self, cache, n):
        for f in ("k_store", "k_min", "k_step", "v_store", "v_min", "v_step"):
            a = getattr(cache, f)
            if a.ndim >= 4:
                # The barrier keeps downstream per-block converts glued to
                # the slice: without it XLA rewrites convert(slice(x)) to
                # slice(convert(x)) and hoists a full-store f32 copy out of
                # the attention scan — exactly the materialization the
                # blockwise path exists to avoid.
                a = jax.lax.optimization_barrier(
                    jax.lax.dynamic_slice_in_dim(a, n, 1, 2))
            setattr(self, f, a)
        self.head_dim = cache.head_dim


def scatter_slots(store: Array, slots: Array, vals: Array) -> Array:
    """Write per-row block payloads into block slots of a store array.

    store : [B, H, NB, ...] (dense ring) or [1, H, P, ...] (a paged arena
    shared by every row — DESIGN.md §10); slots : i32 [B, n] *physical*
    block indices (out-of-range = drop sentinel — that row writes nothing;
    paged callers translate logical ring slots through the page table
    first, see ``pool.lookup_slots``); vals : [B, H, n, ...].  Rows of a
    continuous batch flush at different times, so every row addresses its
    own slot.  Arena writes rely on the pool's no-alias invariant: live
    rows never share a page, so the scatter is collision-free.
    """
    B = slots.shape[0]
    if store.shape[0] == 1 and B > 1:
        # Shared arena: every row's blocks land in its own pages of the one
        # store.  (B == 1 degenerates to the dense branch, which writes
        # store[0, :, slot] — the identical arena update.)
        flat = slots.reshape(-1)  # [B*n]
        upd = jnp.moveaxis(vals, 1, 0).reshape(
            vals.shape[1], -1, *vals.shape[3:])  # [H, B*n, ...]
        return store[0].at[:, flat].set(upd, mode="drop")[None]
    bidx = jnp.arange(B)[:, None]  # broadcasts against slots [B, n]
    # Advanced indices at axes (0, 2) are separated by the H slice, so the
    # indexed dims move to the front: the update value is [B, n, H, ...].
    return store.at[bidx, :, slots].set(jnp.moveaxis(vals, 2, 1), mode="drop")


class CacheLayout:
    """Strategy interface for one way of storing a layer's KV blocks.

    Implementations are stateless singletons; every method receives the
    static ``CacheSpec`` (hashable, lives in the pytree aux) and operates on
    the six store arrays of a ``LayerKVCache`` (duck-typed — this module
    never imports the cache container, so registration stays cycle-free).
    """

    name: str = "?"
    # Eligible for the fused Pallas decode kernel (a Mosaic-legal tile_decode).
    supports_fused: bool = False
    # size_report needs a fitted huffman.CodeBook passed via ``book=``.
    needs_codebook: bool = False
    # Offline quantizer family: fixed-bit (KIVI) vs error-bounded steps.
    kivi_step: bool = False

    # -- static properties ----------------------------------------------------
    def bits_k(self, spec) -> int:
        raise NotImplementedError

    def bits_v(self, spec) -> int:
        raise NotImplementedError

    # -- cache storage --------------------------------------------------------
    def init_store(self, spec, batch: int, n_kv_heads: int, head_dim: int, dtype):
        """Allocate (k_store, k_min, k_step, v_store, v_min, v_step)."""
        raise NotImplementedError

    def write_blocks(self, spec, cache, slots: Array, kb: Array, vb: Array):
        """Store stage: write raw blocks kb/vb [B, H, n, T, D] into per-row
        ring slots [B, n] (out-of-range slot = drop sentinel for that row).
        Returns the six updated store arrays."""
        raise NotImplementedError

    def fetch(self, spec, cache):
        """Bulk Fetch: dequantized K and V [B, H, NB, T, D].

        Materializes the whole store — reconstruction/tests/benchmarks and
        the ``attend_materialized`` oracle only.  The decode hot path never
        calls this; it goes through ``decode_block`` (blockwise XLA scan) or
        ``tile_decode`` (fused Pallas kernel) instead.
        """
        return self.decompress_k(spec, cache), self.decompress_v(spec, cache)

    def decompress_k(self, spec, cache) -> Array:
        raise NotImplementedError

    def decompress_v(self, spec, cache) -> Array:
        raise NotImplementedError

    # -- decode attention hooks ----------------------------------------------
    def decode_block(self, spec, cache, n):
        """Lazily decode ONE store block for the blockwise attention scan.

        Returns ``(k_codes, k_mn, k_st, v_codes, v_mn, v_st)`` with
        ``k_codes``/``v_codes`` f32 ``[B, H, T, D]`` and per-block quant units
        ``k_mn``/``k_st`` ``[B, H, D]``, ``v_mn``/``v_st`` ``[B, H, T]``, under
        the dequantization convention ``x = mn + codes ∘ st``.  ``mn``/``st``
        of ``None`` mean the codes already ARE the dequantized values — the
        scan then skips the ``q·mn + (q∘st)·c`` fusion and dots directly.

        The generic fallback decompresses a one-block view, so any registered
        layout gets the blockwise path for free; quantizing layouts override
        it to return raw codes + scales and keep dequantization folded into
        the attention matvec.
        """
        view = _BlockView(cache, n)
        kd = self.decompress_k(spec, view)[:, :, 0].astype(jnp.float32)
        vd = self.decompress_v(spec, view)[:, :, 0].astype(jnp.float32)
        return kd, None, None, vd, None, None

    def decode_span(self, spec, cache, start, count: int):
        """Lazily decode ``count`` contiguous blocks ``[start, start+count)``
        for one step of the blockwise attention scan.

        Same contract as ``decode_block`` with a block axis C inserted:
        codes f32 ``[B, H, C, T, D]``, units ``[B, H, C, D]`` / ``[B, H, C, T]``
        (or ``None``).  The default stacks ``decode_block`` results; layouts
        whose store slices contiguously override it so one step decodes in
        ONE vectorized op instead of C small ones.
        """
        blocks = [self.decode_block(spec, cache, start + c) for c in range(count)]
        stk = lambda i: (None if blocks[0][i] is None
                         else jnp.stack([b[i] for b in blocks], axis=2))
        return tuple(stk(i) for i in range(6))

    def tile_decode(self, spec, head_dim: int) -> FusedTileSpec | None:
        """The fused Pallas kernel's per-tile decode hook (memoized).

        ``None`` means the layout cannot run in the fused kernel (no
        fixed-size tile formulation of its decode) and it falls back to
        the blockwise XLA scan.  ``supports_fused`` mirrors this statically.
        """
        return fused_tile_spec(self.name, spec, head_dim)

    def _tile_decode(self, spec, head_dim: int) -> FusedTileSpec | None:
        return None

    def attend_block(self, cache, q: Array, scale: float | None = None,
                     backend: str | None = None) -> Array:
        """Decode attention over (store ∥ buffer) — THE dispatch point.

        Routes through the attention-backend registry in
        ``repro.kernels.ops``: ``fused`` runs the Pallas in-situ-decompression
        kernel via ``tile_decode``; ``xla`` runs the blockwise
        lazily-dequantized scan via ``decode_block``.  ``backend=None``
        defers to the cache spec's ``attn_backend`` (default ``"auto"``)."""
        from repro.kernels import ops  # late: kernels import core

        return ops.decode_attention(cache, q, scale, backend=backend)

    # -- size accounting ------------------------------------------------------
    def size_report(self, q, *, block_size: int, head_dim: int,
                    kivi_bits: int = 2, book: huffman.CodeBook | None = None) -> RatioReport:
        """Exact accounting for a quantized tensor stored under this layout."""
        raise NotImplementedError

    def bytes_per_token(self, spec, n_kv_heads: int, head_dim: int) -> float:
        """Analytic HBM bytes per cached token per layer (payload + scales);
        feeds the dry-run roofline model."""
        raise NotImplementedError


_REGISTRY: dict[str, CacheLayout] = {}


def register_layout(name: str):
    """Class decorator: instantiate and register a layout under ``name``."""

    def deco(cls):
        inst = cls()
        inst.name = name
        _REGISTRY[name] = inst
        return cls

    return deco


def get_layout(name: str) -> CacheLayout:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown cache layout {name!r}; available: {available_layouts()}"
        ) from None


def available_layouts() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


# ---------------------------------------------------------------------------
# raw: bf16 blocks, no compression (the exactness baseline)
# ---------------------------------------------------------------------------


@register_layout("raw")
class RawLayout(CacheLayout):
    supports_fused = True  # passthrough tile decoder (see _tile_decode)

    def bits_k(self, spec) -> int:
        return RAW_BITS_PER_VALUE

    def bits_v(self, spec) -> int:
        return RAW_BITS_PER_VALUE

    def init_store(self, spec, batch, n_kv_heads, head_dim, dtype):
        B, H, T, D, NB = batch, n_kv_heads, spec.block_size, head_dim, spec.store_blocks
        k_store = jnp.zeros((B, H, NB, T, D), dtype)
        v_store = jnp.zeros((B, H, NB, T, D), dtype)
        dummy = jnp.zeros((1,), dtype)
        return k_store, dummy, dummy, v_store, dummy, dummy

    def write_blocks(self, spec, cache, slots, kb, vb):
        dt = cache.k_store.dtype
        k_store = scatter_slots(cache.k_store, slots, kb.astype(dt))
        v_store = scatter_slots(cache.v_store, slots, vb.astype(dt))
        return (k_store, cache.k_min, cache.k_step,
                v_store, cache.v_min, cache.v_step)

    def decompress_k(self, spec, cache):
        return cache.k_store

    def decompress_v(self, spec, cache):
        return cache.v_store

    def decode_span(self, spec, cache, start, count: int):
        # Values with no scales; the barrier keeps XLA from commuting the
        # downstream f32 convert above the slice and hoisting a full-store
        # copy out of the attention scan (see _BlockView).
        sl = lambda a: jax.lax.optimization_barrier(
            jax.lax.dynamic_slice_in_dim(a, start, count, 2))
        return sl(cache.k_store), None, None, sl(cache.v_store), None, None

    def _tile_decode(self, spec, head_dim):
        # Passthrough decoder: the raw layout rides the same fused kernel as
        # the quantized layouts (one uniform decode path, not a special
        # case); a tile is the [T, D] bf16 block itself, no scales.
        dec = lambda tile, mn, st: tile.astype(jnp.float32)
        shape = (spec.block_size, head_dim)
        return FusedTileSpec(k_tile=shape, v_tile=shape, has_scales=False,
                             decode_k=dec, decode_v=dec)

    def size_report(self, q, *, block_size, head_dim, kivi_bits=2, book=None):
        return raw_ratio(q)

    def bytes_per_token(self, spec, n_kv_heads, head_dim):
        return 2.0 * n_kv_heads * head_dim * 2  # K+V bf16


# ---------------------------------------------------------------------------
# packed: error-bounded quantizer + planar no-straddle bit-packing (the TPU path)
# ---------------------------------------------------------------------------


@register_layout("packed")
class PackedLayout(CacheLayout):
    supports_fused = True

    def bits_k(self, spec) -> int:
        return bits_for_rel_scale(spec.rel_scale_k)

    def bits_v(self, spec) -> int:
        return bits_for_rel_scale(spec.rel_scale_v)

    def init_store(self, spec, batch, n_kv_heads, head_dim, dtype):
        B, H, T, D, NB = batch, n_kv_heads, spec.block_size, head_dim, spec.store_blocks
        k_store = jnp.zeros((B, H, NB, bitpack.planar_rows(T, spec.bits_k), D),
                            jnp.uint32)
        v_store = jnp.zeros((B, H, NB, bitpack.planar_rows(T, spec.bits_v), D),
                            jnp.uint32)
        return (k_store, *_init_units(B, H, NB, D, dtype),
                v_store, *_init_units(B, H, NB, T, dtype))

    def quantize_blocks(self, spec, k: Array, v: Array):
        """Shared lossy stage for every quantizing layout: [B, H, NB, T, D]
        raw blocks -> (codes u8, min, step) per tensor.  K: BlockQuant —
        min/max over the block's T tokens, per channel; V: TokenQuant —
        min/max over D, per token.  Units come back in their stored
        ``[..., 1, D]`` / ``[..., 1, T]`` shapes."""
        kf = k.astype(jnp.float32)
        vf = v.astype(jnp.float32)
        k_codes, k_mn, k_st = quant_block_minmax(
            kf, spec.rel_scale_k, spec.bits_k, (-2,), self.kivi_step)
        v_codes, v_mn, v_st = quant_block_minmax(
            vf, spec.rel_scale_v, spec.bits_v, (-1,), self.kivi_step)
        row = lambda a: a[..., None, :]
        return k_codes, row(k_mn), row(k_st), v_codes, row(v_mn), row(v_st)

    def encode_codes(self, spec, codes: Array, tensor: str) -> Array:
        """Codes u8 ``[..., T, D]`` of K or V (``tensor``) -> stored slots."""
        bits = spec.bits_k if tensor == "k" else spec.bits_v
        return bitpack.pack_planar(codes, bits)

    def write_blocks(self, spec, cache, slots, kb, vb):
        k_codes, k_mn, k_st, v_codes, v_mn, v_st = self.quantize_blocks(spec, kb, vb)
        dt = jnp.bfloat16
        new = (self.encode_codes(spec, k_codes, "k"), k_mn.astype(dt), k_st.astype(dt),
               self.encode_codes(spec, v_codes, "v"), v_mn.astype(dt), v_st.astype(dt))
        old = (cache.k_store, cache.k_min, cache.k_step,
               cache.v_store, cache.v_min, cache.v_step)
        return tuple(scatter_slots(a, slots, x) for a, x in zip(old, new))

    def decode_codes(self, spec, store: Array, head_dim: int, tensor: str) -> Array:
        """Stored block slots of K or V (``tensor``) -> codes ``[..., T, D]``."""
        bits = spec.bits_k if tensor == "k" else spec.bits_v
        return bitpack.unpack_planar(store, bits, spec.block_size)

    def decompress_k(self, spec, cache):
        codes = self.decode_codes(spec, cache.k_store, cache.head_dim, "k")
        return dequant_k(codes, cache.k_min, cache.k_step).astype(jnp.bfloat16)

    def decompress_v(self, spec, cache):
        codes = self.decode_codes(spec, cache.v_store, cache.head_dim, "v")
        return dequant_v(codes, cache.v_min, cache.v_step).astype(jnp.bfloat16)

    def decode_block(self, spec, cache, n):
        # Raw codes + scales: dequantization stays folded into the attention
        # matvec via q·(mn + st∘c) = q·mn + (q∘st)·c (paper §3.3.2).
        out = self.decode_span(spec, cache, n, 1)
        return tuple(a[:, :, 0] for a in out)

    def decode_span(self, spec, cache, start, count: int):
        # One contiguous slice + one vectorized decode per tensor; the units
        # drop their stored unit axis for the scan's [B, H, C, D|T] contract.
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, start, count, 2)
        D = cache.head_dim
        kc = self.decode_codes(spec, sl(cache.k_store), D, "k")
        vc = self.decode_codes(spec, sl(cache.v_store), D, "v")
        unit = lambda a: sl(a)[..., 0, :]
        return (kc.astype(jnp.float32), unit(cache.k_min), unit(cache.k_step),
                vc.astype(jnp.float32), unit(cache.v_min), unit(cache.v_step))

    def _tile_decode(self, spec, head_dim):
        T, D = spec.block_size, head_dim
        bits_k, bits_v = spec.bits_k, spec.bits_v

        def dk(tile, mn, st):  # tile [Rk, D] u32, units [1, D]
            return dequant_k(bitpack.unpack_planar(tile, bits_k, T), mn, st)

        def dv(tile, mn, st):  # tile [Rv, D] u32, units [1, T]
            return dequant_v(bitpack.unpack_planar(tile, bits_v, T), mn, st)

        return FusedTileSpec(
            k_tile=(bitpack.planar_rows(T, bits_k), D),
            v_tile=(bitpack.planar_rows(T, bits_v), D),
            has_scales=True, decode_k=dk, decode_v=dv)

    def size_report(self, q, *, block_size, head_dim, kivi_bits=2, book=None):
        return packed_ratio(q, block_size * head_dim)

    def bytes_per_token(self, spec, n_kv_heads, head_dim):
        # Allocated planar words (no-straddle and row padding included).
        payload = (n_kv_heads * 4.0 * (spec.words_k(head_dim) + spec.words_v(head_dim))
                   / spec.block_size)
        # scales: K per (block, channel) 2x bf16; V per token 2x bf16
        meta = n_kv_heads * (2 * head_dim * 2 * 2 / spec.block_size + 2 * 2)
        return payload + meta


# ---------------------------------------------------------------------------
# kivi: fixed-bit asymmetric baseline (paper §4.1)
# ---------------------------------------------------------------------------


@register_layout("kivi")
class KiviLayout(PackedLayout):
    kivi_step = True  # step = (max−min)/(2^b − 1)

    def bits_k(self, spec) -> int:
        return spec.kivi_bits

    def bits_v(self, spec) -> int:
        return spec.kivi_bits

    def size_report(self, q, *, block_size, head_dim, kivi_bits=2, book=None):
        return kivi_ratio(q, kivi_bits)


# ---------------------------------------------------------------------------
# huffman: the paper's maximal-ratio path as a servable layout
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=64)
def default_codebook(n_codes: int) -> huffman.CodeBook:
    """Static prior codebook covering codes [0, n_codes).

    A servable layout needs a codebook available at trace time with static
    shapes, so the layout ships a deterministic prior (triangular, peaked at
    the code range's center — the error-bounded quantizer's codes of
    LLM-like data are bell-shaped, paper Fig. 3) instead of the offline
    codec's per-layer fitted histograms.  Coverage of the full code range
    guarantees losslessness for any input; fitted codebooks remain available
    through ``repro.core.codec.KVCompCodec`` (DESIGN.md §4).
    """
    hist = np.zeros(huffman.N_SYMBOLS, np.int64)
    c = np.arange(n_codes, dtype=np.float64)
    center = (n_codes - 1) / 2.0
    hist[:n_codes] = 1 + np.round(1000.0 * (n_codes - np.abs(c - center))).astype(np.int64)
    return huffman.build_codebook(hist)


def _pack_u16_pairs(nbits: Array) -> Array:
    """u16 [S] per-stream bit counts -> u32 [(S+1)//2] header words."""
    S = nbits.shape[0]
    nb = nbits.astype(jnp.uint32)
    if S % 2:
        nb = jnp.concatenate([nb, jnp.zeros((1,), jnp.uint32)])
    return nb[0::2] | (nb[1::2] << 16)


def _unpack_u16_pairs(hdr: Array, S: int) -> Array:
    lo = hdr & jnp.uint32(0xFFFF)
    hi = hdr >> 16
    return jnp.stack([lo, hi], axis=-1).reshape(-1)[:S].astype(jnp.uint16)


@register_layout("huffman")
class HuffmanLayout(PackedLayout):
    """Huffman-coded blocks behind the same six-array cache contract.

    Slot layout (per compression block, K and V alike): ``(T+1)//2`` header
    words holding the T per-stream u16 bit counts (one stream per token, D
    symbols each — the paper's per-thread metadata), followed by a
    worst-case-sized payload region (``T·D·max_code_len`` bits under the
    static prior codebook).  Quantization scales are stored exactly as in
    the packed layout, so ``q·(m + s∘c)`` algebra still applies after the
    entropy decode.  Allocated capacity is worst-case; ``size_report``
    accounts the *actual* entropy-coded bits (DESIGN.md §4).

    The payload is ragged INSIDE the slot, but the slot itself is a fixed
    worst-case-padded ``[1, W]`` tile — so the fused Pallas kernel streams
    whole slots HBM→VMEM like any other layout and ``tile_decode``
    re-derives the per-stream offsets from the header in VMEM.  Both the
    in-kernel decode and the blockwise XLA floor run the chunked
    direct-lookup decoder (``huffman.decode_block_lut_jax``): ≤ 2 LUT
    probes per symbol instead of one tree step per bit, with the canonical
    codebooks' flat LUTs riding along as the tile spec's per-layer ``aux``
    operands (DESIGN.md §9).  That decoder gathers per lane from the slot
    and the LUT, which Mosaic does not lower, so on a TPU the layout is not
    fused-capable and ``auto`` serves it through the blockwise scan.
    """

    needs_codebook = True

    @property
    def supports_fused(self) -> bool:
        # The one place the TPU/huffman decision lives (DESIGN.md §9): the
        # in-kernel LUT decode runs in interpret mode off-TPU only.
        from repro.kernels.runtime import on_tpu  # late: kernels import core

        return not on_tpu()

    # -- codebooks (static prior; see default_codebook) ----------------------
    def _n_codes(self, spec, bits: int, rel_scale: float) -> int:
        n = round(1.0 / rel_scale) + 1
        return int(min(n, 2**bits, huffman.N_SYMBOLS))

    def book_k(self, spec) -> huffman.CodeBook:
        return default_codebook(self._n_codes(spec, spec.bits_k, spec.rel_scale_k))

    def book_v(self, spec) -> huffman.CodeBook:
        return default_codebook(self._n_codes(spec, spec.bits_v, spec.rel_scale_v))

    def _slot_words(self, spec, head_dim: int, book: huffman.CodeBook) -> tuple[int, int]:
        """(header_words, payload_words) for one block's slot."""
        T = spec.block_size
        maxlen = int(book.lengths.max())
        hdr = (T + 1) // 2
        payload = (T * head_dim * maxlen + 31) // 32 + 1
        return hdr, payload

    def init_store(self, spec, batch, n_kv_heads, head_dim, dtype):
        B, H, T, D, NB = batch, n_kv_heads, spec.block_size, head_dim, spec.store_blocks
        wk = sum(self._slot_words(spec, D, self.book_k(spec)))
        wv = sum(self._slot_words(spec, D, self.book_v(spec)))
        k_store = jnp.zeros((B, H, NB, 1, wk), jnp.uint32)
        v_store = jnp.zeros((B, H, NB, 1, wv), jnp.uint32)
        return (k_store, *_init_units(B, H, NB, D, dtype),
                v_store, *_init_units(B, H, NB, T, dtype))

    def encode_codes(self, spec, codes: Array, tensor: str) -> Array:
        """codes u8 [B, H, n, T, D] -> slots u32 [B, H, n, 1, hdr+payload]."""
        book = self.book_k(spec) if tensor == "k" else self.book_v(spec)
        B, H, n, T, D = codes.shape
        hdr_w, pay_w = self._slot_words(spec, D, book)
        cl, ln = book.as_encode_tables()

        def enc(blk):  # [T, D]
            payload, nbits, _ = huffman.encode_block_jax(blk, cl, ln, pay_w)
            return jnp.concatenate([_pack_u16_pairs(nbits), payload])

        slots = jax.vmap(enc)(codes.reshape(B * H * n, T, D))
        return slots.reshape(B, H, n, 1, hdr_w + pay_w)

    def decode_codes(self, spec, store: Array, head_dim: int, tensor: str) -> Array:
        """slots u32 [..., 1, W] -> codes u8 [..., T, D].

        Chunked LUT decode (≤ 2 probes per symbol) — the same decoder the
        fused kernel runs per tile, here vmapped over every slot for the
        blockwise XLA floor.
        """
        book = self.book_k(spec) if tensor == "k" else self.book_v(spec)
        lead, W = store.shape[:-2], store.shape[-1]
        T, D = spec.block_size, head_dim
        hdr_w, _ = self._slot_words(spec, D, book)
        lut = jnp.asarray(book.decode_lut())
        probes = book.decode_probes

        def dec(slot):  # [hdr+payload]
            nbits = _unpack_u16_pairs(slot[:hdr_w], T)
            return huffman.decode_block_lut_jax(slot[hdr_w:], nbits, lut, D, probes)

        with annotate("huffman_lut_decode"):
            codes = jax.vmap(dec)(store.reshape(-1, W))
        return codes.reshape(*lead, T, D)

    def _tile_decode(self, spec, head_dim):
        # One tile = one whole worst-case-padded slot (header ∥ payload);
        # the ragged per-stream offsets are re-derived from the u16 header
        # INSIDE the kernel, and the canonical codebooks' flat LUTs ride as
        # per-layer aux operands the kernel stages into VMEM (DESIGN.md §9).
        T, D = spec.block_size, head_dim
        book_k, book_v = self.book_k(spec), self.book_v(spec)
        hk, pk = self._slot_words(spec, D, book_k)
        hv, pv = self._slot_words(spec, D, book_v)
        probes_k, probes_v = book_k.decode_probes, book_v.decode_probes

        def dk(tile, mn, st, lut_k, lut_v):  # tile [1, W]
            nbits = _unpack_u16_pairs(tile[0, :hk], T)
            codes = huffman.decode_block_lut_jax(
                tile[0, hk:], nbits, lut_k, D, probes_k)  # [T, D]
            return dequant_k(codes, mn, st)

        def dv(tile, mn, st, lut_k, lut_v):
            nbits = _unpack_u16_pairs(tile[0, :hv], T)
            codes = huffman.decode_block_lut_jax(
                tile[0, hv:], nbits, lut_v, D, probes_v)
            return dequant_v(codes, mn, st)

        return FusedTileSpec(k_tile=(1, hk + pk), v_tile=(1, hv + pv),
                             has_scales=True, decode_k=dk, decode_v=dv,
                             aux=(book_k.decode_lut(), book_v.decode_lut()))

    def size_report(self, q, *, block_size, head_dim, kivi_bits=2, book=None):
        assert book is not None, "huffman size_report needs a fitted codebook"
        return huffman_ratio(q, book, (block_size, head_dim))

    def bytes_per_token(self, spec, n_kv_heads, head_dim):
        # Allocated (worst-case slot) bytes — what HBM actually holds; the
        # entropy win shows up in size_report's expected-bits accounting.
        T = spec.block_size
        hk, pk = self._slot_words(spec, head_dim, self.book_k(spec))
        hv, pv = self._slot_words(spec, head_dim, self.book_v(spec))
        payload = n_kv_heads * 4.0 * (hk + pk + hv + pv) / T
        meta = n_kv_heads * (2 * head_dim * 2 * 2 / T + 2 * 2)
        return payload + meta
