"""Public KVComp facade — the one import for compressing, serving, and
sizing KV caches.

    from repro import api
    from repro.core.policy import CompressionPolicy, TensorPolicy, LayerOverride

    policy = CompressionPolicy(layout="packed")      # or kivi / huffman / raw
    cache  = api.compress(k, v, policy=policy)       # Store (prefill bulk)
    out    = api.attend(cache, q)                    # Fetch (fused algebra)
    k2, v2 = api.decompress(cache)                   # reconstruct
    report = api.estimate_ratio(k, v, policy=policy) # exact size accounting

    server = api.serve(cfg, params, max_slots=8)     # continuous batching
    handle = server.submit(api.Request(prompt, max_new_tokens=64))
    for tok in handle.tokens(): ...                  # streaming
    result = handle.result()                         # or block for the rest

Everything dispatches through the ``CacheLayout`` registry
(``repro.core.layouts``): any layout registered with
``@register_layout(name)`` — including the four built-ins raw / packed /
kivi / huffman — is servable through this module with no other code aware
of it.  Examples and benchmarks consume this facade rather than reaching
into the internals.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro import obs  # noqa: F401  (observability facade: DESIGN.md §14)
from repro.core import cache as kvcache
from repro.core import huffman, layouts, quant
from repro.core.policy import CompressionPolicy, LayerOverride, TensorPolicy  # noqa: F401
from repro.kernels import ops as kernel_ops
from repro.serve.scheduler import Handle, Request, Server, ServerConfig  # noqa: F401

__all__ = [
    "CompressionPolicy", "TensorPolicy", "LayerOverride",
    "available_layouts", "register_layout", "make_spec", "make_cache",
    "available_backends", "register_backend",
    "compress", "decompress", "append", "attend", "estimate_ratio",
    "serve", "Server", "ServerConfig", "Request", "Handle", "obs",
]

register_layout = layouts.register_layout
register_backend = kernel_ops.register_backend


def serve(cfg, params, *, max_slots: int = 8, max_seq: int = 4096,
          pad_id: int = 0, policy: str = "fcfs",
          attn_backend: str | None = None,
          cache_mode: str | None = None,
          pool_hbm_bytes: int | None = None,
          prefix_cache: str = "off",
          mesh=None,
          prefill_mode: str = "chunked",
          prefill_chunk_tokens: int | None = None,
          trace: str = "off",
          max_requeues: int = 32,
          max_pending: int | None = None,
          backpressure: str = "reject",
          default_deadline_s: float | None = None,
          faults=None,
          audit_every: int = 0,
          q_chunk: int = 512, kv_chunk: int = 512) -> Server:
    """Launch a continuous-batching server over ``cfg``'s cache policy.

    Returns a ``repro.serve.scheduler.Server``: ``submit(Request) -> Handle``
    with ``handle.result()`` / streaming ``handle.tokens()``; requests join
    and leave decode slots mid-flight at their own per-row positions.
    ``policy`` picks the admission order ("fcfs" or "ljf"; DESIGN.md §8).
    ``attn_backend`` overrides the decode-attention backend (DESIGN.md §9;
    None keeps ``cfg.attn_backend`` — "auto" runs the fused
    in-situ-decompression kernel on TPU, the blockwise scan elsewhere).
    ``cache_mode`` overrides ``cfg.cache_mode`` (DESIGN.md §10): "paged"
    pools compressed blocks in shared per-layer arenas sized by
    ``pool_hbm_bytes`` and admits by memory pressure — slots oversubscribe
    the dense reservation by the compression ratio, preempting + requeueing
    the youngest request if the pool runs dry (tokens are unaffected);
    ``server.stats()`` reports live pool occupancy.
    ``prefix_cache="on"`` (paged mode only; DESIGN.md §11) shares
    block-aligned prompt prefixes across requests through a radix index
    over refcounted compressed pages — admission splices cached page ids
    and prefills only the divergent suffix, preempted requests resume from
    cached pages, and ``server.stats()["prefix"]`` reports hit-rate /
    reuse / copy-on-write counters ("noshare" runs the same chunked
    admission path without sharing — the accounting baseline).
    ``mesh`` (DESIGN.md §12) serves across devices: a jax Mesh with
    ("data", "model") axes — ``repro.launch.mesh.make_serve_mesh("dp,tp")``
    builds one — shards decode slots, page tables, and the paged arena's
    page axis over "data" and KV heads over "model", with parameters
    replicated so greedy outputs stay bit-identical to the single-device
    server; ``server.stats()["shards"]`` reports per-shard page pressure.
    ``prefill_mode`` (DESIGN.md §13) picks the admission style: "chunked"
    (the default) splits every prompt into block-multiple chunks spliced
    between decode steps — at most ``prefill_chunk_tokens`` prompt tokens
    (default ``8 * block_size``; must be a positive multiple of the cache
    block size) ride alongside the live decode batch per step, so a long
    prompt no longer stalls in-flight streams, and in paged mode each
    chunk's KV encodes straight into pooled pages (peak admission memory
    O(chunk), not O(prompt)); "solo" restores the blocking full-length
    prefill. Greedy outputs are bit-identical either way;
    ``server.stats()["prefill"]`` reports chunks in flight and tokens
    co-scheduled with decode.
    ``trace`` (DESIGN.md §14) turns on the ring-buffered scheduler event
    trace ("events" records every scheduling decision);
    ``server.trace.write_chrome(path)`` — or
    ``server.shutdown(trace_out=...)`` — exports it as Perfetto-loadable
    Chrome trace-event JSON, and ``server.metrics`` is the typed registry
    behind ``server.stats()`` (its ``step`` block times each step's host
    phases, always on).
    Request-lifecycle hardening (DESIGN.md §15): failures are isolated —
    pool exhaustion with nothing reclaimable requeues the affected request
    up to ``max_requeues`` times (the same budget caps preemption storms,
    with the oldest request always protected) and then fails ONLY that
    request (``Result.finish_reason == "error"`` with ``Result.error``
    naming the cause; other streams are bit-identical to an undisturbed
    run).  ``Handle.cancel()`` and ``Request.deadline_s`` /
    ``default_deadline_s`` retire requests in any state ("cancelled" /
    "deadline"); ``max_pending`` bounds the admission queue, with
    ``backpressure`` picking "reject" (submit raises ``QueueFull``) or
    "block" (submit drives the server until the queue drains).
    ``faults`` takes a ``repro.serve.faults.FaultPlan`` for deterministic
    seeded fault injection at the named scheduler sites, and
    ``audit_every=N`` cross-checks the server's pool/page-table/index
    bookkeeping every N steps (``repro.serve.faults.InvariantAuditor``),
    raising on the first violation.
    """
    return Server(cfg, params,
                  ServerConfig(max_slots=max_slots, max_seq=max_seq,
                               pad_id=pad_id, policy=policy,
                               attn_backend=attn_backend,
                               cache_mode=cache_mode,
                               pool_hbm_bytes=pool_hbm_bytes,
                               prefix_cache=prefix_cache,
                               mesh=mesh,
                               prefill_mode=prefill_mode,
                               prefill_chunk_tokens=prefill_chunk_tokens,
                               trace=trace,
                               max_requeues=max_requeues,
                               max_pending=max_pending,
                               backpressure=backpressure,
                               default_deadline_s=default_deadline_s,
                               faults=faults,
                               audit_every=audit_every),
                  q_chunk=q_chunk, kv_chunk=kv_chunk)


def available_layouts() -> tuple[str, ...]:
    """Names of every registered cache layout."""
    return layouts.available_layouts()


def available_backends() -> tuple[str, ...]:
    """Names of every registered decode-attention backend."""
    return kernel_ops.available_backends()


def _policy(policy: CompressionPolicy | None) -> CompressionPolicy:
    return policy if policy is not None else CompressionPolicy()


def make_spec(policy: CompressionPolicy | None = None, *, layer: int = 0,
              max_seq: int = 4096, window: int | None = None) -> kvcache.CacheSpec:
    """Resolve a (possibly per-layer-overridden) policy to one CacheSpec."""
    return _policy(policy).spec_for_layer(layer, max_seq=max_seq, window=window)


def make_cache(batch: int, n_kv_heads: int, head_dim: int, *,
               policy: CompressionPolicy | None = None, layer: int = 0,
               max_seq: int = 4096, window: int | None = None,
               dtype=jnp.bfloat16) -> kvcache.LayerKVCache:
    """An empty, servable layer cache under the policy's layout."""
    spec = make_spec(policy, layer=layer, max_seq=max_seq, window=window)
    return kvcache.init_layer_cache(spec, batch, n_kv_heads, head_dim, dtype)


def compress(k, v, *, policy: CompressionPolicy | None = None, layer: int = 0,
             max_seq: int | None = None, window: int | None = None,
             dtype=jnp.bfloat16) -> kvcache.LayerKVCache:
    """Bulk-compress prompt KV [B, Hkv, S, D] into a layer cache (Store)."""
    S = k.shape[2]
    spec = make_spec(policy, layer=layer,
                     max_seq=max_seq if max_seq is not None else S,
                     window=window)
    return kvcache.prefill(spec, k, v, dtype)


def decompress(cache: kvcache.LayerKVCache):
    """Reconstruct (k, v) [B, Hkv, S, D] from a cache — decoded store blocks
    followed by the exact raw-buffer tail.  Host-side convenience: the cache
    lengths must be concrete (outside jit).  Paged caches are first gathered
    back into a private dense ring (``repro.core.pool.to_dense``)."""
    from repro.core import pool as blockpool

    cache = blockpool.to_dense(cache)
    spec = cache.spec
    k_deq, v_deq = spec.impl.fetch(spec, cache)
    B, H, NB, T, D = k_deq.shape
    nf = np.asarray(cache.n_flushed)
    bl = np.asarray(cache.buf_len)
    if not ((nf == nf[0]).all() and (bl == bl[0]).all()):
        raise ValueError(
            "decompress needs uniform per-row lengths (rows of a continuous "
            f"batch are at different positions: n_flushed={nf.tolist()}, "
            f"buf_len={bl.tolist()}); decompress rows individually instead")
    nb = int(nf[0])
    if nb > NB:
        raise ValueError("cache has evicted blocks; only the last "
                         f"{NB * T} store tokens are reconstructible")
    buf = int(bl[0])
    k = jnp.concatenate(
        [k_deq.reshape(B, H, NB * T, D)[:, :, : nb * T], cache.k_buf[:, :, :buf]],
        axis=2)
    v = jnp.concatenate(
        [v_deq.reshape(B, H, NB * T, D)[:, :, : nb * T], cache.v_buf[:, :, :buf]],
        axis=2)
    return k, v


def append(cache: kvcache.LayerKVCache, k_new, v_new) -> kvcache.LayerKVCache:
    """Append one token's KV [B, Hkv, D] (compress-on-block-overflow)."""
    return kvcache.append(cache, k_new, v_new)


def attend(cache: kvcache.LayerKVCache, q, scale: float | None = None,
           backend: str | None = None):
    """Single-token decode attention over (store ∥ buffer) -> [B, Hq, D].

    Dispatches through the attention-backend registry; ``backend=None``
    defers to the cache spec (``"auto"``: fused Pallas kernel on TPU for
    fused-capable layouts, blockwise lazily-dequantized scan elsewhere).
    """
    return kvcache.attend(cache, q, scale, backend=backend)


def estimate_ratio(k=None, v=None, *, policy: CompressionPolicy | None = None,
                   layer: int = 0, which: str = "both") -> dict:
    """Exact compression-ratio accounting of this policy on real tensors.

    Quantizes K (BlockQuant) and/or V (TokenQuant) under the resolved layer
    policy, fits Huffman codebooks where the layout needs them, and returns
    per-tensor ``RatioReport``s plus the combined ratio — the collapse of
    the old ``KVCompCodec.report_k``/``report_v`` duplication into the
    layout objects.  ``which`` ∈ {"k", "v", "both"} limits the work when a
    caller sweeps only one tensor.
    """
    if which not in ("k", "v", "both"):
        raise ValueError(f"which must be k|v|both, got {which!r}")
    if (which in ("k", "both") and k is None) or \
            (which in ("v", "both") and v is None):
        raise ValueError(f"which={which!r} needs the corresponding tensor(s)")
    ref = k if k is not None else v
    spec = make_spec(policy, layer=layer, max_seq=int(ref.shape[0]))
    lay = spec.impl
    head_dim = int(ref.shape[-1])

    def report(q):
        book = None
        if lay.needs_codebook:
            book = huffman.build_codebook(np.asarray(huffman.histogram(q.codes)))
        return lay.size_report(q, block_size=spec.block_size, head_dim=head_dim,
                               kivi_bits=spec.bits_k, book=book)

    out = {"layout": spec.layout}
    if which in ("k", "both"):
        qk = (quant.kivi_quantize_k(k, spec.bits_k, 32) if lay.kivi_step
              else quant.quantize_k_block(k, spec.rel_scale_k, spec.block_size))
        out["k"] = report(qk)
    if which in ("v", "both"):
        qv = (quant.kivi_quantize_v(v, spec.bits_v) if lay.kivi_step
              else quant.quantize_v_token(v, spec.rel_scale_v))
        out["v"] = report(qv)
    reports = [out[t] for t in ("k", "v") if t in out]
    total_bits = sum(r.total_bits for r in reports)
    n = sum(r.n_values for r in reports)
    out["ratio"] = n * layouts.RAW_BITS_PER_VALUE / max(total_bits, 1)
    return out
