"""Compressed KV-cache manager (paper §3.2.1, §3.2.3) — jit/pjit-friendly.

This is the serving-side realization of KVComp: a per-layer cache that keeps
its main storage *compressed* (block-quantized + encoded) and a small raw
append buffer.  Newly generated KV vectors accumulate in the buffer; when it
fills one compression block, the block is quantized, encoded, and written into
the store at a deterministic slot (the atomic-free Block Offsets Array of
DESIGN.md §2 degenerates to ``slot = n_flushed % NB`` because every layout
uses uniform per-block slot widths → offsets are affine in the block index).

How a block is encoded — and how it is fetched back — is owned entirely by
the ``CacheLayout`` strategy named in ``CacheSpec.layout`` (DESIGN.md §4);
this module holds only the layout-independent machinery: the ring of block
slots, the raw tail buffer, prefill/append scheduling, and the joint-softmax
attention over (store ∥ buffer).

Faithfulness notes
------------------
* The raw tail buffer doubles as KIVI's "residual window": the most recent
  ``block_size`` tokens are always exact.
* K uses BlockQuant (per block × head × channel min/max), V uses TokenQuant
  (per token × head) — the paper's granularities.
* Sliding-window models (Mixtral) evict whole blocks via a ring over the
  block axis — "block-aligned eviction composes with compression".
* Decode attention (``attend``) dispatches through the attention-backend
  registry (DESIGN.md §9): the ``fused`` backend streams compressed tiles
  into the Pallas kernel and decompresses in VMEM; the ``xla`` backend
  (``attend_blockwise``) scans the block axis decoding one block at a time
  and folds dequantization into the matvec with the *algebraic fusion*
  identity ``q·(m + s∘c) = (q·m) + (q∘s)·c``.  Neither builds a dequantized
  ``[B, Hkv, NB, T, D]`` intermediate — only the retired
  ``attend_materialized`` oracle does.

Lengths are **per row**: ``n_flushed`` and ``buf_len`` are ``i32 [B]``
vectors, so every batch row advances (appends, flushes, attends) at its own
sequence position — the contract the continuous-batching scheduler
(``repro.serve.scheduler``) relies on when requests join and leave slots
mid-flight.  Uniform batches are simply the special case where every row
holds the same value, and the structure still scans cleanly over layers.
"""

from __future__ import annotations

import dataclasses
import math
import os

import jax
import jax.numpy as jnp

from repro.core import bitpack, layouts, pool
from repro.obs.profiling import annotate

Array = jax.Array

NEG_INF = -1e9

# Re-exported: historical home of this helper (dryrun and tests import it).
bits_for_rel_scale = layouts.bits_for_rel_scale


@dataclasses.dataclass(frozen=True)
class CacheSpec:
    """Static (hashable — lives in the pytree aux) cache configuration.

    ``layout`` names a registered ``repro.core.layouts.CacheLayout``; bit
    widths and store shapes are delegated to it.  The optional overrides let
    a ``CompressionPolicy`` pin explicit storage widths per tensor.
    ``attn_backend`` selects the decode-attention backend
    (``repro.kernels.ops``): ``"auto"`` | ``"xla"`` | ``"fused"`` | any
    ``register_backend``-ed name.

    ``mode`` picks the storage container (DESIGN.md §10): ``"dense"`` gives
    every row its own ``n_blocks`` ring; ``"paged"`` stores blocks in one
    shared arena of ``pool_pages`` physical pages (store batch axis 1) that
    rows address through a per-row page table — the serving scheduler owns
    page allocation (``repro.core.pool``).
    """

    layout: str = "packed"  # any name in layouts.available_layouts()
    block_size: int = 64
    rel_scale_k: float = 0.05
    rel_scale_v: float = 0.15
    kivi_bits: int = 2
    max_seq: int = 4096
    window: int | None = None  # sliding-window size (tokens), None = full
    bits_k_override: int | None = None
    bits_v_override: int | None = None
    attn_backend: str = "auto"  # decode-attention backend (DESIGN.md §9)
    mode: str = "dense"  # "dense" | "paged" (shared-arena, page-indirect)
    pool_pages: int = 0  # paged: physical pages in the shared arena
    # Blockwise-scan tuning knobs (None = REPRO_BLOCKWISE_* env var, else the
    # module defaults BLOCKWISE_SPAN_TOKENS / BLOCKWISE_UNROLL_MAX below) —
    # the real-TPU tuning pass turns these instead of editing constants.
    span_tokens: int | None = None  # ~tokens decoded per scan step
    unroll_max: int | None = None   # unroll the span loop up to this many steps

    def __post_init__(self):
        if self.mode not in ("dense", "paged"):
            raise ValueError(f"mode must be dense|paged, got {self.mode!r}")
        for f in ("span_tokens", "unroll_max"):
            val = getattr(self, f)
            if val is not None and val < 1:
                raise ValueError(f"{f} must be >= 1 when set, got {val}")
        if self.mode == "paged" and self.pool_pages < 1:
            raise ValueError(
                f"paged mode needs pool_pages >= 1, got {self.pool_pages}")
        if self.window is not None and self.window % self.block_size:
            # A non-divisible window would make the ring silently retain
            # block_size-aligned spans shorter than the window claims.
            raise ValueError(
                f"block_size ({self.block_size}) must divide window "
                f"({self.window}): the sliding-window ring evicts whole "
                f"compression blocks")

    @property
    def impl(self) -> layouts.CacheLayout:
        return layouts.get_layout(self.layout)

    @property
    def paged(self) -> bool:
        return self.mode == "paged"

    @property
    def bits_k(self) -> int:
        if self.bits_k_override is not None:
            return self.bits_k_override
        return self.impl.bits_k(self)

    @property
    def bits_v(self) -> int:
        if self.bits_v_override is not None:
            return self.bits_v_override
        return self.impl.bits_v(self)

    @property
    def n_blocks(self) -> int:
        """Logical ring length: blocks addressable per row (page-table width
        in paged mode)."""
        span = self.max_seq if self.window is None else min(self.window, self.max_seq)
        return max(1, math.ceil(span / self.block_size))

    @property
    def store_blocks(self) -> int:
        """Physical extent of the store's block axis: the shared arena's
        page count in paged mode, the per-row ring length in dense mode."""
        return self.pool_pages if self.paged else self.n_blocks

    def words_k(self, head_dim: int) -> int:
        """u32 words of one packed K block (planar: rows × head_dim)."""
        return bitpack.planar_rows(self.block_size, self.bits_k) * head_dim

    def words_v(self, head_dim: int) -> int:
        return bitpack.planar_rows(self.block_size, self.bits_v) * head_dim


@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass
class LayerKVCache:
    """One layer's cache.  Leading dims: [B, Hkv, ...].

    Store shapes are layout-owned (see ``CacheLayout.init_store``); e.g. the
    packed layouts use
      k_store : u32 [B, Hkv, NB, Rk, D]       (planar bit-packed block codes)
      k_min/k_step : bf16 [B, Hkv, NB, 1, D]  (BlockQuant units)
      v_store : u32 [B, Hkv, NB, Rv, D]
      v_min/v_step : bf16 [B, Hkv, NB, 1, T]  (TokenQuant units; T = block_size)
    while the raw layout stores bf16 [B, Hkv, NB, T, D] blocks with dummy
    scales.  Shared, layout-independent:
      k_buf / v_buf : bf16 [B, Hkv, T, D] — raw append buffer (residual window)
      n_flushed : i32 [B] — per-row total blocks ever flushed (ring index)
      buf_len   : i32 [B] — per-row valid entries in the buffer
      page_tab  : i32 [B, NB] — paged mode only: logical slot -> physical
                  arena page (-1 unassigned); dense mode holds a [1] dummy.
                  In paged mode the six store arrays carry batch extent 1
                  (the shared arena) with ``spec.pool_pages`` on the block
                  axis, while buffers/lengths stay per-row (DESIGN.md §10).
    """

    k_store: Array
    k_min: Array
    k_step: Array
    v_store: Array
    v_min: Array
    v_step: Array
    k_buf: Array
    v_buf: Array
    n_flushed: Array
    buf_len: Array
    page_tab: Array
    spec: CacheSpec

    # -- pytree ---------------------------------------------------------------
    # Keys are part of the flatten so path-based sharding rules
    # (distributed.sharding.cache_shardings) can match leaves by name.
    _FIELDS = ("k_store", "k_min", "k_step", "v_store", "v_min", "v_step",
               "k_buf", "v_buf", "n_flushed", "buf_len", "page_tab")

    def tree_flatten_with_keys(self):
        leaves = [(jax.tree_util.GetAttrKey(f), getattr(self, f))
                  for f in self._FIELDS]
        return leaves, self.spec

    def tree_flatten(self):
        return tuple(getattr(self, f) for f in self._FIELDS), self.spec

    @classmethod
    def tree_unflatten(cls, spec, leaves):
        return cls(*leaves, spec=spec)

    # -- helpers ----------------------------------------------------------------
    def with_spec(self, spec: "CacheSpec") -> "LayerKVCache":
        """Same leaves under a different static spec.

        The sharded serving path (``repro.distributed.serve_shard``) uses
        this to rewrite ``attn_backend``/``pool_pages`` on views of a cache
        — e.g. each mesh shard attends its local page slice under a spec
        whose ``pool_pages`` is the per-shard arena extent.
        """
        return dataclasses.replace(self, spec=spec)

    @property
    def head_dim(self) -> int:
        return self.k_buf.shape[-1]

    @property
    def batch(self) -> int:
        return self.k_buf.shape[0]

    @property
    def total_len(self) -> Array:
        """Per-row tokens visible to attention (window-capped for SWA): [B]."""
        nb = jnp.minimum(self.n_flushed, self.spec.n_blocks)
        return nb * self.spec.block_size + self.buf_len


def init_layer_cache(spec: CacheSpec, batch: int, n_kv_heads: int, head_dim: int,
                     dtype=jnp.bfloat16) -> LayerKVCache:
    B, H, T, D = batch, n_kv_heads, spec.block_size, head_dim
    # Paged mode: the stores are ONE shared arena (batch extent 1, pool_pages
    # on the block axis — see spec.store_blocks); rows address it through
    # page_tab.  Buffers and length vectors stay per-row either way.
    k_store, k_min, k_step, v_store, v_min, v_step = spec.impl.init_store(
        spec, 1 if spec.paged else B, H, D, dtype)
    page_tab = (jnp.full((B, spec.n_blocks), -1, jnp.int32) if spec.paged
                else jnp.zeros((1,), jnp.int32))
    return LayerKVCache(
        k_store=k_store, k_min=k_min, k_step=k_step,
        v_store=v_store, v_min=v_min, v_step=v_step,
        k_buf=jnp.zeros((B, H, T, D), dtype),
        v_buf=jnp.zeros((B, H, T, D), dtype),
        n_flushed=jnp.zeros((B,), jnp.int32),
        buf_len=jnp.zeros((B,), jnp.int32),
        page_tab=page_tab,
        spec=spec,
    )


# ---------------------------------------------------------------------------
# Prefill: bulk-compress a prompt's KV (paper Store stage, prefill phase)
# ---------------------------------------------------------------------------


def prefill(spec: CacheSpec, k: Array, v: Array, dtype=jnp.bfloat16) -> LayerKVCache:
    """Build a cache from prompt KV [B, Hkv, S, D]; whole blocks are
    compressed, the remainder lands in the raw buffer."""
    if spec.paged:
        # Bulk prefill writes a private dense ring; paged arenas are
        # populated by the serving scheduler (solo dense prefill spliced via
        # pool.splice_row) or by pool.from_dense.  See DESIGN.md §10.
        raise ValueError(
            "prefill builds dense caches; compress under the dense twin of "
            "this spec and re-house it with repro.core.pool.from_dense")
    B, H, S, D = k.shape
    T, NB = spec.block_size, spec.n_blocks
    n_full = S // T
    cache = init_layer_cache(spec, B, H, D, dtype)
    # Window models only retain the last NB blocks.
    keep = min(n_full, NB)
    if n_full:
        kb = k[:, :, (n_full - keep) * T : n_full * T].reshape(B, H, keep, T, D)
        vb = v[:, :, (n_full - keep) * T : n_full * T].reshape(B, H, keep, T, D)
        slots = jnp.broadcast_to(
            ((jnp.arange(keep) + (n_full - keep)) % NB)[None], (B, keep))
        (cache.k_store, cache.k_min, cache.k_step,
         cache.v_store, cache.v_min, cache.v_step) = spec.impl.write_blocks(
            spec, cache, slots, kb, vb)
    rem = S - n_full * T
    if rem:
        cache.k_buf = cache.k_buf.at[:, :, :rem].set(k[:, :, n_full * T :].astype(dtype))
        cache.v_buf = cache.v_buf.at[:, :, :rem].set(v[:, :, n_full * T :].astype(dtype))
    cache.n_flushed = jnp.full((B,), n_full, jnp.int32)
    cache.buf_len = jnp.full((B,), rem, jnp.int32)
    return cache


# ---------------------------------------------------------------------------
# Decode-step append (paper §3.2.3 Natural Data Appending)
# ---------------------------------------------------------------------------


def append(cache: LayerKVCache, k_new: Array, v_new: Array) -> LayerKVCache:
    """Append one token's KV [B, Hkv, D]; flush a row's buffer into a
    compressed block when it fills.  Every row appends at its own ``buf_len``
    and flushes independently (rows of a continuous batch are at different
    positions).  Pure function — returns the updated cache."""
    spec = cache.spec
    T, NB = spec.block_size, spec.n_blocks
    dt = cache.k_buf.dtype
    pos = cache.buf_len  # [B]
    sel = jnp.arange(T)[None, :] == pos[:, None]  # [B, T] one-hot per row
    k_buf = jnp.where(sel[:, None, :, None], k_new[:, :, None, :].astype(dt),
                      cache.k_buf)
    v_buf = jnp.where(sel[:, None, :, None], v_new[:, :, None, :].astype(dt),
                      cache.v_buf)
    will_flush = (pos + 1) == T  # [B]

    B, H, _, D = k_buf.shape
    kb = k_buf[:, :, None]  # [B, H, 1, T, D]
    vb = v_buf[:, :, None]
    # NB = out-of-range drop sentinel for rows whose buffer did not fill.
    slots = jnp.where(will_flush, cache.n_flushed % NB, NB)[:, None]  # [B, 1]
    if spec.paged:
        # Page-indirect flush: logical ring slots translate through the page
        # table to physical arena pages (the scheduler assigned them before
        # this step); unassigned slots become the arena's drop sentinel, so
        # a retired row's garbage flush can never corrupt a reused page.
        slots = pool.lookup_slots(cache.page_tab, slots, NB, spec.pool_pages)
    staged = dataclasses.replace(cache, k_buf=k_buf, v_buf=v_buf)
    # Skip the encode entirely on the (T-1)/T steps where no row flushes —
    # every write would be dropped, and for entropy-coding layouts the dead
    # encode is the dominant per-token cost.
    (k_store, k_min, k_step, v_store, v_min, v_step) = jax.lax.cond(
        jnp.any(will_flush),
        lambda c: spec.impl.write_blocks(spec, c, slots, kb, vb),
        lambda c: (c.k_store, c.k_min, c.k_step, c.v_store, c.v_min, c.v_step),
        staged)
    return LayerKVCache(
        k_store=k_store, k_min=k_min, k_step=k_step,
        v_store=v_store, v_min=v_min, v_step=v_step,
        k_buf=k_buf, v_buf=v_buf,
        n_flushed=cache.n_flushed + will_flush.astype(jnp.int32),
        buf_len=jnp.where(will_flush, 0, pos + 1),
        page_tab=cache.page_tab,
        spec=spec,
    )


# ---------------------------------------------------------------------------
# Decode attention over the compressed cache (paper Fetch stage)
# ---------------------------------------------------------------------------


def attend(cache: LayerKVCache, q: Array, scale: float | None = None,
           backend: str | None = None) -> Array:
    """Single-token attention against the cache — the decode entry point.

    q : [B, H, D] with H = Hkv * G (GQA); returns [B, H, D].
    Dispatches through the layout's ``attend_block`` into the
    attention-backend registry (``repro.kernels.ops``): ``fused`` runs the
    Pallas in-situ-decompression kernel, ``xla`` the blockwise
    lazily-dequantized scan below.  ``backend=None`` defers to the cache
    spec's ``attn_backend`` (default ``"auto"``: fused on TPU for
    fused-capable layouts, blockwise elsewhere).  Neither path ever
    materializes a ``[B, Hkv, NB, T, D]`` dequantized intermediate.
    """
    return cache.spec.impl.attend_block(cache, q, scale, backend=backend)


BLOCKWISE_SPAN_TOKENS = 1024  # ~tokens decoded per scan step (peak-mem knob)
BLOCKWISE_UNROLL_MAX = 64     # unroll the span loop up to this many steps

ENV_SPAN_TOKENS = "REPRO_BLOCKWISE_SPAN_TOKENS"
ENV_UNROLL_MAX = "REPRO_BLOCKWISE_UNROLL_MAX"


def blockwise_knobs(spec: CacheSpec) -> tuple[int, int]:
    """Resolve the blockwise scan's (span_tokens, unroll_max).

    Same precedence as the attention-backend knob: an explicit ``CacheSpec``
    field wins (threaded from ``CompressionPolicy``/``ModelConfig``, per
    layer overridable), else the ``REPRO_BLOCKWISE_*`` env var (read at
    trace time — the real-TPU tuning pass sweeps these without code edits),
    else the module default.
    """

    def pick(field: int | None, env: str, default: int) -> int:
        if field is not None:
            return field
        raw = os.environ.get(env)
        if not raw:
            return default
        try:
            val = int(raw)
        except ValueError:
            raise ValueError(f"{env}={raw!r} is not an integer") from None
        if val < 1:  # same bound CacheSpec enforces on the field
            raise ValueError(f"{env} must be >= 1, got {val}")
        return val

    return (pick(spec.span_tokens, ENV_SPAN_TOKENS, BLOCKWISE_SPAN_TOKENS),
            pick(spec.unroll_max, ENV_UNROLL_MAX, BLOCKWISE_UNROLL_MAX))


def attend_blockwise(cache: LayerKVCache, q: Array,
                     scale: float | None = None,
                     span: int | None = None) -> Array:
    """The generic XLA decode path: a blockwise lazily-dequantized
    flash-decode scan (the ``"xla"`` attention backend).

    Running ``(m, l, acc)`` state walks the NB block axis in spans of a few
    blocks (``span`` blocks per step, sized so one step decodes about
    ``span_tokens`` tokens — enough matvec per step to amortize per-step
    overhead, while peak temporary state stays one span; see
    ``blockwise_knobs`` for how the spec/env/default resolve).  A span
    decodes lazily in one vectorized op through the layout's ``decode_span``
    and dequantization folds into the matvecs with the paper's algebraic
    fusion ``q·(mn + st∘c) = q·mn + q·(st∘c)`` (and its V-side mirror) —
    never the ``[B, Hkv, NB, T, D]`` store nor a ``[B, Hkv, G, NB*T+T]``
    logits concat.  Up to ``unroll_max`` steps the loop unrolls
    (XLA fuses each span chain and reuses one span's buffers — measurably
    faster than both lax.scan and the materializing attend on CPU); past
    that (very long contexts) it switches to ``lax.scan`` to keep the HLO
    bounded.  The raw buffer tail merges via the same two-part softmax
    combine the fused kernel path uses.  Any registered layout gets this
    path for free (huffman LUT-decodes one span per step).
    """
    from repro.kernels import ref as kref  # shared combine; late: kernels import core

    spec = cache.spec
    B, Hq, D = q.shape
    Hkv = cache.k_buf.shape[1]
    G = Hq // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, Hkv, G, D).astype(jnp.float32)
    with annotate("blockwise_span_scan"):
        m, l, acc = _store_scan(cache, qg, scale, span)
    out = kref.combine_with_buffer_ref(
        acc.reshape(B, Hq, D), m.reshape(B, Hq), l.reshape(B, Hq),
        q, cache.k_buf, cache.v_buf, cache.buf_len, scale=scale)
    return out.astype(q.dtype)


def _store_scan(cache: LayerKVCache, qg: Array, scale: float,
                span: int | None = None):
    """The blockwise flash-decode scan over the FLUSHED store only: running
    ``(m, l, acc)`` softmax state per grouped query, without the raw-buffer
    combine (callers merge their own tail — ``attend_blockwise`` the buffer,
    ``attend_chunk`` the chunk's intra-causal raw scores).

    ``qg``: f32 ``[B, Hkv, G', D]`` — generic in the grouped-query axis, so
    the chunked-prefill path folds its ``C`` chunk positions into
    ``G' = C * G`` and reuses this scan unchanged (every flushed block is
    strictly in the past of every chunk token, so all G' queries see the
    same mask).  Returns ``(m [B,Hkv,G'], l [B,Hkv,G'], acc [B,Hkv,G',D])``
    with ``m = NEG_INIT, l = 0`` rows where nothing is flushed.
    """
    from repro.kernels import ref as kref  # shared constants; late import

    spec = cache.spec
    B, Hkv, G, D = qg.shape
    T, NB = spec.block_size, spec.n_blocks
    span_tokens, unroll_max = blockwise_knobs(spec)
    if span is None:
        span = max(1, span_tokens // T)
    span = min(span, NB)
    n_steps = -(-NB // span)
    nb_valid = jnp.minimum(cache.n_flushed, NB)  # [B]
    impl = spec.impl
    f32 = jnp.float32

    def body(carry, n0):
        m, l, acc = carry
        # One contiguous span [start, start+span) decodes in one vectorized
        # layout op.  The last (ragged) span clamps its window back; blocks
        # before n0 in the clamped window were already consumed, so the mask
        # drops them alongside not-yet-flushed slots.
        start = jnp.minimum(n0, NB - span)
        if spec.paged:
            # Gather the span's pages out of the shared arena into a dense
            # per-row view; the layout decodes it unchanged from block 0.
            kc, k_mn, k_st, vc, v_mn, v_st = impl.decode_span(
                spec, pool.span_view(cache, start, span), 0, span)
        else:
            kc, k_mn, k_st, vc, v_mn, v_st = impl.decode_span(
                spec, cache, start, span)
        has_scales = k_mn is not None
        # q·(mn + st∘c) = q·mn + q·(st∘c): the rank-1 mn term stays separate
        # (dequantized values are never formed); the step scales fold into
        # the CODES so the whole span contracts in one [G,D]x[C·T,D] matvec.
        if has_scales:
            kc = kc * k_st.astype(f32)[:, :, :, None, :]  # st∘c  [B,H,C,T,D]
        s = jnp.einsum("bhgd,bhxd->bhgx", qg,
                       kc.astype(f32).reshape(B, Hkv, span * T, D)
                       ).reshape(B, Hkv, G, span, T)
        if has_scales:
            s = s + jnp.einsum("bhgd,bhcd->bhgc", qg,
                               k_mn.astype(f32))[..., None]
        s = s * scale
        # flushed blocks are whole: per-(row, block) all-or-nothing masks
        idx = start + jnp.arange(span)  # [C]
        ok = (idx[None, :] >= n0) & (idx[None, :] < nb_valid[:, None])  # [B,C]
        if spec.paged:
            # Unassigned table entries (-1) gathered a clamped page above;
            # mask them out regardless of nb_valid — the shard-local table
            # semantics of DESIGN.md §12, where blocks hosted by another
            # shard are marked -1 and must contribute nothing.
            pg = jax.lax.dynamic_slice_in_dim(cache.page_tab, start, span, 1)
            ok = ok & (pg >= 0)
        okx = ok[:, None, None, :, None]
        s = jnp.where(okx, s, kref.NEG_INIT)
        s2 = s.reshape(B, Hkv, G, span * T)
        m_new = jnp.maximum(m, jnp.max(s2, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = (jnp.exp(s - m_new[..., None, None]) * okx)  # [B,H,G,C,T]
        l_new = l * alpha + jnp.sum(p, axis=(-2, -1))
        # V mirror: Σ p·(mn + st∘c) = (p·mn) + ((p∘st)·c)
        if has_scales:
            pv = p * v_st.astype(f32)[:, :, None]  # p∘st  [B,H,G,C,T]
            upd = (jnp.einsum("bhgct,bhct->bhg", p, v_mn.astype(f32))[..., None]
                   + jnp.einsum("bhgx,bhxd->bhgd",
                                pv.reshape(B, Hkv, G, span * T),
                                vc.astype(f32).reshape(B, Hkv, span * T, D)))
        else:
            upd = jnp.einsum("bhgx,bhxd->bhgd",
                             p.reshape(B, Hkv, G, span * T),
                             vc.astype(f32).reshape(B, Hkv, span * T, D))
        acc_new = acc * alpha[..., None] + upd
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hkv, G), kref.NEG_INIT, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G), jnp.float32)
    acc0 = jnp.zeros((B, Hkv, G, D), jnp.float32)
    if n_steps <= unroll_max:
        carry = (m0, l0, acc0)
        for i in range(n_steps):
            carry, _ = body(carry, i * span)
        return carry
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0),
                                  jnp.arange(n_steps) * span)
    return m, l, acc


# ---------------------------------------------------------------------------
# Block-chunked prefill (DESIGN.md §11/§13) — since chunked admission became
# the scheduler default, this is the path EVERY served prompt takes: solo
# admission drains all chunks at once, chunked admission splices them
# between decode steps, and both reduce to the same per-block computation.
# ---------------------------------------------------------------------------


def attend_chunk(cache: LayerKVCache, q: Array, k_new: Array, v_new: Array,
                 scale: float | None = None) -> Array:
    """Attention for one block-chunked prefill step: ``C`` new tokens attend
    the flushed compressed store plus the chunk's own raw K/V causally.

    ``q``: ``[B, C, Hq, D]``; ``k_new``/``v_new``: ``[B, Hkv, C, D]``.
    Chunks start at block boundaries (the raw buffer is empty), so each
    token's visible set is exactly what the decode path would give it: all
    flushed blocks through the store (lazily dequantized — the lossy side)
    plus the raw tokens of its own partial block (the exact side, self
    included).  The store partials come from the same ``_store_scan`` the
    decode backend runs, with the chunk axis folded into the grouped-query
    axis (``G' = C*G`` — every flushed block is strictly past every chunk
    token), then merge with the intra-chunk causal scores by the usual
    two-part online-softmax combine.  Per-block output is therefore a pure
    function of (params, pages so far, block tokens): resuming at block
    ``j`` from cached pages is bit-identical to chunking from token 0.
    """
    from repro.kernels import ref as kref  # shared constants; late import

    B, C, Hq, D = q.shape
    Hkv = k_new.shape[1]
    G = Hq // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    f32 = jnp.float32
    # [B, C, Hq, D] -> [B, Hkv, C, G, D]; fold (C, G) for the store scan.
    qf = q.astype(f32).reshape(B, C, Hkv, G, D).transpose(0, 2, 1, 3, 4)
    m, l, acc = _store_scan(cache, qf.reshape(B, Hkv, C * G, D), scale)
    m = m.reshape(B, Hkv, C, G)
    l = l.reshape(B, Hkv, C, G)
    acc = acc.reshape(B, Hkv, C, G, D)
    # Intra-chunk causal raw scores (self included — the chunk counterpart
    # of decode's append-before-attend buffer visibility).
    s = jnp.einsum("bhcgd,bhxd->bhcgx", qf, k_new.astype(f32)) * scale
    causal = jnp.arange(C)[:, None] >= jnp.arange(C)[None, :]  # [C(q), C(k)]
    mask = causal[None, None, :, None, :]
    s = jnp.where(mask, s, kref.NEG_INIT)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None]) * mask
    l_new = l * alpha + jnp.sum(p, axis=-1)
    acc_new = (acc * alpha[..., None]
               + jnp.einsum("bhcgx,bhxd->bhcgd", p, v_new.astype(f32)))
    out = acc_new / jnp.maximum(l_new, 1e-30)[..., None]  # [B,Hkv,C,G,D]
    return out.transpose(0, 2, 1, 3, 4).reshape(B, C, Hq, D).astype(q.dtype)


def append_chunk(cache: LayerKVCache, k_new: Array, v_new: Array) -> LayerKVCache:
    """Append one chunk's KV ``[B, Hkv, C, D]`` at a block boundary (the
    raw buffer must be empty — the chunked-prefill invariant).  A full
    chunk (``C == block_size``) compresses straight through the layout's
    ``write_blocks`` and leaves the buffer empty for the next chunk; a
    final partial chunk lands in the raw buffer, exactly where a token-wise
    decode of the same suffix would have left it."""
    spec = cache.spec
    T, NB = spec.block_size, spec.n_blocks
    C = k_new.shape[2]
    dt = cache.k_buf.dtype
    if not 1 <= C <= T:
        raise ValueError(f"chunk of {C} tokens vs block_size {T}")
    if C == T:
        slots = (cache.n_flushed % NB)[:, None]  # [B, 1]
        if spec.paged:
            slots = pool.lookup_slots(cache.page_tab, slots, NB, spec.pool_pages)
        kb = k_new[:, :, None].astype(dt)  # [B, H, 1, T, D]
        vb = v_new[:, :, None].astype(dt)
        (k_store, k_min, k_step, v_store, v_min, v_step) = spec.impl.write_blocks(
            spec, cache, slots, kb, vb)
        return dataclasses.replace(
            cache, k_store=k_store, k_min=k_min, k_step=k_step,
            v_store=v_store, v_min=v_min, v_step=v_step,
            n_flushed=cache.n_flushed + 1)
    return dataclasses.replace(
        cache,
        k_buf=cache.k_buf.at[:, :, :C].set(k_new.astype(dt)),
        v_buf=cache.v_buf.at[:, :, :C].set(v_new.astype(dt)),
        buf_len=jnp.full_like(cache.buf_len, C))


def attend_materialized(cache: LayerKVCache, q: Array,
                        scale: float | None = None) -> Array:
    """The retired materializing attend — kept as the oracle/baseline.

    Dequantizes the whole store via ``fetch`` into a ``[B, Hkv, NB, T, D]``
    intermediate and runs one joint softmax over (store ∥ buffer).  Exact
    same math as the pre-backend-registry production path; lives on for the
    backend-parity tests and as ``benchmarks/decode_path.py``'s baseline.
    Never dispatched to by the serving decode path.
    """
    cache = pool.to_dense(cache)  # paged: gather pages into a private ring
    spec = cache.spec
    B, Hq, D = q.shape
    Hkv = cache.k_buf.shape[1]
    G = Hq // Hkv
    T, NB = spec.block_size, spec.n_blocks
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, Hkv, G, D).astype(jnp.float32)

    k_deq, v_deq = spec.impl.fetch(spec, cache)  # [B,Hkv,NB,T,D]
    k_deq = k_deq.astype(jnp.float32)
    v_deq = v_deq.astype(jnp.float32)
    s_main = jnp.einsum("bhgd,bhntd->bhgnt", qg, k_deq) * scale
    nb_valid = jnp.minimum(cache.n_flushed, NB)  # [B]
    # ring: any slot < nb_valid is live — per row
    block_ok = jnp.arange(NB)[None, :] < nb_valid[:, None]  # [B, NB]
    s_main = jnp.where(block_ok[:, None, None, :, None], s_main, NEG_INF)

    kb = cache.k_buf.astype(jnp.float32)
    vb = cache.v_buf.astype(jnp.float32)
    s_buf = jnp.einsum("bhgd,bhtd->bhgt", qg, kb) * scale
    buf_ok = jnp.arange(T)[None, :] < cache.buf_len[:, None]  # [B, T]
    s_buf = jnp.where(buf_ok[:, None, None, :], s_buf, NEG_INF)

    logits = jnp.concatenate([s_main.reshape(B, Hkv, G, NB * T), s_buf], axis=-1)
    w = jax.nn.softmax(logits, axis=-1)
    w_main = w[..., : NB * T].reshape(B, Hkv, G, NB, T)
    w_buf = w[..., NB * T :]
    out = jnp.einsum("bhgnt,bhntd->bhgd", w_main, v_deq)
    out = out + jnp.einsum("bhgt,bhtd->bhgd", w_buf, vb)
    return out.reshape(B, Hq, D).astype(q.dtype)


def reference_attend(k: Array, v: Array, q: Array, scale: float | None = None,
                     window: int | None = None) -> Array:
    """Oracle: exact attention over raw [B,Hkv,S,D] caches (for tests)."""
    B, Hq, D = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    G = Hq // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, Hkv, G, D).astype(jnp.float32)
    s = jnp.einsum("bhgd,bhsd->bhgs", qg, k.astype(jnp.float32)) * scale
    if window is not None and S > window:
        keep = jnp.arange(S) >= (S - window)
        s = jnp.where(keep[None, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bhsd->bhgd", w, v.astype(jnp.float32))
    return out.reshape(B, Hq, D).astype(q.dtype)
