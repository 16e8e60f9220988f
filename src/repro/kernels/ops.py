"""Public jit'd kernel wrappers + the attention-backend registry.

Two orthogonal selection axes (DESIGN.md §9):

* **backend** — which decode-attention algorithm serves a cache:
    * ``"fused"`` — the Pallas in-situ-decompression kernel
      (``repro.kernels.fused_kv_attn``), parameterized by the layout's
      ``tile_decode`` hook; requires ``CacheLayout.supports_fused``.
    * ``"xla"``   — the blockwise lazily-dequantized flash-decode scan
      (``repro.core.cache.attend_blockwise``); works for every layout and is
      the portable floor.
    * ``"auto"``  — fused on real TPU for fused-capable layouts, xla
      elsewhere.
  New backends register with ``@register_backend("name")`` (same pattern as
  the cache-layout registry).  The ``REPRO_ATTN_BACKEND`` env var overrides
  the selection at trace time — the CI matrix uses it to keep both paths
  green on CPU.

* **impl** — within the fused backend, which code path executes:
  ``"pallas"`` (interpret mode off-TPU, compiled Mosaic on real TPU) or
  ``"xla"`` (the vmapped pure-jnp oracle in ``repro.kernels.ref``);
  ``"auto"`` picks pallas on TPU and the oracle elsewhere.

The dispatch entry is ``decode_attention`` — what every
``CacheLayout.attend_block`` routes through, making it the single point the
model decode path, the serving scheduler, and the api facade all share.
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.fused_kv_attn import fused_cache_attention_pallas
from repro.kernels.runtime import resolve_impl, resolve_interpret  # noqa: F401  (re-export)
from repro.obs.profiling import annotate

Array = jax.Array


# ---------------------------------------------------------------------------
# Attention-backend registry
# ---------------------------------------------------------------------------


_BACKENDS: dict[str, object] = {}

ENV_BACKEND = "REPRO_ATTN_BACKEND"


def register_backend(name: str):
    """Function decorator: register ``fn(cache, q, scale) -> [B, Hq, D]`` as
    a decode-attention backend under ``name``."""

    def deco(fn):
        _BACKENDS[name] = fn
        return fn

    return deco


def available_backends() -> tuple[str, ...]:
    return tuple(sorted(_BACKENDS))


def resolve_backend(backend: str | None, layout) -> str:
    """Collapse (requested backend, env override, layout capability, host
    platform) to a registered backend name.

    ``REPRO_ATTN_BACKEND`` (read at trace time) replaces an ``auto``
    selection — explicit requests win, so the CI matrix steers every
    default-configured path without defeating tests that pin a backend.
    ``auto`` resolves to fused on real TPU for fused-capable layouts and to
    the blockwise scan elsewhere; steered to ``fused``, it still serves a
    layout the kernel cannot take through the scan.  An explicit ``fused``
    request against a layout without ``supports_fused`` (huffman on a TPU,
    custom layouts without a tile decoder) raises rather than quietly
    serving another path.
    """
    from repro.kernels.runtime import on_tpu

    name = backend or "auto"
    if name == "auto":
        name = os.environ.get(ENV_BACKEND) or "auto"
        if name in ("auto", "fused") and not layout.supports_fused:
            name = "xla"
    if name == "auto":
        name = "fused" if on_tpu() else "xla"
    if name == "fused" and not layout.supports_fused:
        raise ValueError(
            f"attention backend 'fused' cannot serve layout {layout.name!r} "
            f"here (supports_fused is False); request 'auto' or 'xla'")
    if name not in _BACKENDS:
        raise ValueError(
            f"unknown attention backend {name!r}; available: {available_backends()}")
    return name


def decode_attention(cache, q: Array, scale: float | None = None,
                     backend: str | None = None) -> Array:
    """Decode attention over (store ∥ buffer) — the registry dispatch point.

    ``backend=None`` defers to ``cache.spec.attn_backend`` (itself
    ``"auto"`` unless a CompressionPolicy/ModelConfig pinned it).
    """
    name = resolve_backend(backend if backend is not None else cache.spec.attn_backend,
                           cache.spec.impl)
    return _BACKENDS[name](cache, q, scale)


@register_backend("xla")
def _xla_backend(cache, q: Array, scale: float | None = None) -> Array:
    from repro.core import cache as kvcache  # late: core imports this module

    return kvcache.attend_blockwise(cache, q, scale)


@register_backend("fused")
def _fused_backend(cache, q: Array, scale: float | None = None) -> Array:
    return cache_decode_attention(cache, q, scale=scale)


# ---------------------------------------------------------------------------
# Fused-kernel wrappers
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("tile", "block_size", "scale", "impl", "interpret"),
)
def fused_cache_attention(
    q: Array,
    k_store: Array, k_min: Array, k_step: Array,
    v_store: Array, v_min: Array, v_step: Array,
    k_buf: Array, v_buf: Array,
    nb_valid: Array, buf_len: Array,
    page_tab: Array | None = None,
    *,
    tile,  # layouts.FusedTileSpec (memoized — hashable static arg)
    block_size: int,
    scale: float | None = None,
    impl: str = "auto",
    interpret: bool | str = "auto",
) -> Array:
    """Full decode attention over (store ∥ buffer) -> [B, Hq, D].

    ``impl="pallas"`` runs the single fused kernel (buffer tail folded into
    its softmax combine); ``impl="xla"`` runs the vmapped oracle.  A
    ``page_tab`` (i32 [B, NB]) marks the stores as a shared paged arena
    (DESIGN.md §10): both impls gather K/V tiles through the table —
    the kernel in its scalar-prefetch index maps, the oracle by an explicit
    per-row gather.
    """
    impl = resolve_impl(impl)
    D = q.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    kw = dict(tile=tile, block_size=block_size, scale=scale)
    # Profiling attribution (DESIGN.md §14): device profiles tag this whole
    # fused in-situ-decompression attention as one named compression stage.
    with annotate("fused_attention"):
        if impl == "pallas":
            out = fused_cache_attention_pallas(
                q, k_store, k_min, k_step, v_store, v_min, v_step,
                k_buf, v_buf, nb_valid, buf_len, page_tab,
                interpret=interpret, **kw)
        else:
            out = ref.fused_cache_attention_ref(
                q, k_store, k_min, k_step, v_store, v_min, v_step,
                k_buf, v_buf, nb_valid, buf_len, page_tab, **kw)
    return out.astype(q.dtype)


def cache_decode_attention(cache, q: Array, scale: float | None = None,
                           impl: str = "auto", interpret: bool | str = "auto"):
    """Fused decode attention straight from a LayerKVCache (the ``"fused"``
    backend body).

    Only layouts whose ``tile_decode`` returns a plan (``supports_fused``)
    can enter the kernel; the backend resolver routes everything else to the
    blockwise ``repro.core.cache.attend_blockwise`` path first.
    """
    spec = cache.spec
    tile = spec.impl.tile_decode(spec, cache.head_dim)
    if tile is None:
        raise ValueError(
            f"fused kernel requires a fused-capable layout "
            f"(got {spec.layout!r}; see layouts.CacheLayout.tile_decode)")
    return fused_cache_attention(
        q,
        cache.k_store, cache.k_min, cache.k_step,
        cache.v_store, cache.v_min, cache.v_step,
        cache.k_buf, cache.v_buf,
        jnp.minimum(cache.n_flushed, spec.n_blocks), cache.buf_len,
        cache.page_tab if spec.paged else None,
        tile=tile, block_size=spec.block_size, scale=scale,
        impl=impl, interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Store-stage kernel wrapper
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("rel_scale", "bits", "token_wise", "impl", "interpret"))
def quant_pack(
    x: Array, *, rel_scale: float, bits: int, token_wise: bool,
    impl: str = "auto", interpret: bool | str = "auto",
):
    """Store-stage compression of [NBLK, T, D] raw blocks."""
    impl = resolve_impl(impl)
    with annotate("pack_encode"):
        if impl == "pallas":
            from repro.kernels.pack_encode import quant_pack_pallas

            return quant_pack_pallas(x, rel_scale, bits, token_wise,
                                     interpret=interpret)
        return ref.quant_pack_ref(x, rel_scale, bits, token_wise)
