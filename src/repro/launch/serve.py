"""Serving launcher: continuous-batching generation with the compressed KV
cache.

    python -m repro.launch.serve --arch yi_6b --layout packed --requests 8
    python -m repro.launch.serve --arch yi_6b --layout raw   # baseline
    python -m repro.launch.serve --size published --max-seq 2048   # on a TPU

Requests get heterogeneous prompt lengths and token budgets and are pushed
through the ``api.serve`` Server — slots admit, decode at per-row positions,
retire, and are reused mid-flight.  Prints the device, per-request results
and the per-layout cache memory.  ``--size smoke`` (the default, sized for
the CPU) serves the architecture's reduced config; ``--size published``
serves its published widths with only the depth cut (to
``PUBLISHED_LAYERS``), with random bf16 weights.  Exits 1 when any request ends in ``error``.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import jax
import numpy as np

from repro import api
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_serve_mesh
from repro.models import layers as L
from repro.models import model as M
from repro.models import registry

# Published-width depth: yi_6b's bf16 weights are ~1.05 GB of embeddings
# plus ~0.35 GB per layer, so 8 layers (~3.8 GB) leave a 16 GB chip ample
# room for a 4-slot 2k-token cache and the compiled step.
PUBLISHED_LAYERS = 8


def model_config(arch: str, size: str = "smoke"):
    """The served ModelConfig: the arch's smoke config, or its published
    config at full width with only ``n_layers`` cut to ``PUBLISHED_LAYERS``."""
    if size == "smoke":
        return registry.get_smoke_config(arch)
    cfg = registry.get_config(arch)
    return dataclasses.replace(cfg, n_layers=min(PUBLISHED_LAYERS, cfg.n_layers))


def describe_cut(arch: str, cfg) -> str:
    """One line naming the served model and how it was cut."""
    full = registry.get_config(arch)
    widths = (f"d_model {cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads}, "
              f"head_dim {cfg.resolved_head_dim}, d_ff {cfg.d_ff}, "
              f"vocab {cfg.vocab_size}, cache_block {cfg.cache_block}")
    if cfg.d_model != full.d_model:
        return f"model {cfg.name}: smoke config ({widths}, {cfg.n_layers} layers)"
    return (f"model {cfg.name}: published widths ({widths}); depth cut "
            f"{full.n_layers} -> {cfg.n_layers} layers; random {cfg.dtype} weights")


def init_params(cfg, seed: int = 0):
    """Random weights in the config's own dtype (bf16 for published
    configs), from a fixed seed, generated on the device in one program."""
    init = jax.jit(lambda key: M.init_params(cfg, key, L.dtype_of(cfg.dtype))[0])
    return init(jax.random.PRNGKey(seed))


def device_info() -> dict:
    """The device a run used, as JAX reports it."""
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def workload(cfg, n_requests: int, prompt_len: int, new_tokens: int,
             shared_prefix: bool = False, seed: int = 0) -> list:
    """Heterogeneous requests: prompts from half to full ``prompt_len``,
    budgets from half to full ``new_tokens``; with ``shared_prefix`` they
    share a system prompt of half ``prompt_len``."""
    rng = np.random.default_rng(seed)
    shared = (rng.integers(0, cfg.vocab_size, prompt_len // 2).astype(np.int32)
              if shared_prefix else np.zeros(0, np.int32))
    span = max(n_requests - 1, 1)
    reqs = []
    for i in range(n_requests):
        plen = max(4, prompt_len - (i * prompt_len // 2) // span)
        n_new = max(2, new_tokens - (i * new_tokens // 2) // span)
        tail = rng.integers(0, cfg.vocab_size,
                            max(plen - len(shared), 1)).astype(np.int32)
        reqs.append(api.Request(prompt=np.concatenate([shared, tail]),
                                max_new_tokens=n_new))
    return reqs


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi_6b")
    ap.add_argument("--size", default="smoke", choices=["smoke", "published"],
                    help="smoke: the arch's reduced CPU-sized config; "
                         f"published: its published widths, depth cut to "
                         f"{PUBLISHED_LAYERS} layers, random bf16 weights")
    ap.add_argument("--layout", default="packed",
                    choices=list(api.available_layouts()))
    ap.add_argument("--backend", default=None,
                    choices=list(api.available_backends()) + ["auto"],
                    help="decode-attention backend (default: the model "
                         "config's attn_backend — auto: fused kernel on TPU, "
                         "blockwise scan elsewhere)")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=96)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--cache-mode", default="dense", choices=["dense", "paged"],
                    help="paged: pool compressed blocks in a shared arena "
                         "and admit by memory pressure (DESIGN.md §10)")
    ap.add_argument("--pool-bytes", type=int, default=None,
                    help="paged: byte budget for the block pool (default: "
                         "the dense-equivalent footprint of --max-slots)")
    ap.add_argument("--prefix-cache", default="off",
                    choices=["off", "on", "noshare"],
                    help="paged: share block-aligned prompt prefixes through "
                         "the refcounted page index (DESIGN.md §11); noshare "
                         "runs the same chunked admission without sharing")
    ap.add_argument("--span-tokens", type=int, default=None,
                    help="blockwise-scan span width in tokens (mirrors "
                         "REPRO_BLOCKWISE_SPAN_TOKENS; default: model config)")
    ap.add_argument("--unroll-max", type=int, default=None,
                    help="max spans unrolled before the scan falls back to "
                         "lax.scan (mirrors REPRO_BLOCKWISE_UNROLL_MAX; "
                         "default: model config)")
    ap.add_argument("--prefill-mode", default="chunked",
                    choices=["chunked", "solo"],
                    help="chunked (default): splice admission prefills "
                         "between decode steps in --prefill-chunk budgets "
                         "(DESIGN.md §13); solo: drain the whole prompt at "
                         "admission, stalling live decoders")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="per-step chunked-prefill token budget; must be a "
                         "positive multiple of the cache block_size "
                         "(default: 8 blocks)")
    ap.add_argument("--mesh", default=None,
                    help="dp,tp serving mesh (DESIGN.md §12), e.g. 2,2 — "
                         "shards slots and the paged arena over dp and KV "
                         "heads over tp; on CPU export "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N "
                         "first")
    ap.add_argument("--trace", default="off",
                    choices=["off", "events", "full"],
                    help="scheduler event trace (DESIGN.md §14): events "
                         "records every scheduling decision in a ring "
                         "buffer, full adds decode dispatch spans; off "
                         "keeps the hot path event-free")
    ap.add_argument("--trace-out", default=None,
                    help="write the event trace as Chrome trace-event JSON "
                         "(open in Perfetto; needs --trace events|full)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the metrics snapshot as JSON here, plus a "
                         ".prom Prometheus-text sibling")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request wall-clock deadline in seconds "
                         "(DESIGN.md §15); requests past it retire with "
                         "finish_reason='deadline'")
    ap.add_argument("--max-requeues", type=int, default=32,
                    help="preemption/requeue budget per request; over "
                         "budget a (non-oldest) request fails in isolation "
                         "instead of requeueing (DESIGN.md §15)")
    ap.add_argument("--max-pending", type=int, default=None,
                    help="bound the admission queue; submits past the "
                         "bound apply --backpressure")
    ap.add_argument("--backpressure", default="reject",
                    choices=["reject", "block"],
                    help="full-queue policy: reject raises, block drives "
                         "the server until the queue drains")
    ap.add_argument("--audit-every", type=int, default=0,
                    help="cross-check pool/page-table/prefix-index "
                         "invariants every N steps (0 = off; DESIGN.md §15)")
    ap.add_argument("--fault", action="append", default=[],
                    metavar="SITE[:PROB]",
                    help="inject deterministic faults at a named site "
                         "(repeatable; prob defaults to 1.0), e.g. "
                         "--fault reclaim_sweep:0.05 — sites: "
                         "pool_alloc, reclaim_sweep, prefix_evict, "
                         "prefix_insert, chunk_prefill, decode_dispatch")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the --fault schedule (replayable)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = model_config(args.arch, args.size)
    cfg = dataclasses.replace(cfg, cache_layout=args.layout)
    if args.span_tokens is not None:
        cfg = dataclasses.replace(cfg, cache_span_tokens=args.span_tokens)
    if args.unroll_max is not None:
        cfg = dataclasses.replace(cfg, cache_unroll_max=args.unroll_max)
    print(f"device={device_info()}")
    print(describe_cut(args.arch, cfg))
    params = init_params(cfg)
    mesh = make_serve_mesh(args.mesh) if args.mesh else None
    faults = None
    if args.fault:
        from repro.serve.faults import FaultPlan
        rates = {}
        for spec in args.fault:
            site, _, prob = spec.partition(":")
            rates[site] = float(prob) if prob else 1.0
        faults = FaultPlan(seed=args.fault_seed, rates=rates)
    server = api.serve(cfg, params, max_slots=args.max_slots,
                       max_seq=args.max_seq, attn_backend=args.backend,
                       cache_mode=args.cache_mode,
                       pool_hbm_bytes=args.pool_bytes,
                       prefix_cache=args.prefix_cache,
                       prefill_mode=args.prefill_mode,
                       prefill_chunk_tokens=args.prefill_chunk,
                       mesh=mesh, trace=args.trace,
                       max_requeues=args.max_requeues,
                       max_pending=args.max_pending,
                       backpressure=args.backpressure,
                       default_deadline_s=args.deadline,
                       faults=faults,
                       audit_every=args.audit_every)
    # With the prefix cache enabled, requests share a system-prompt prefix
    # (half of --prompt-len) so the printed hit-rate exercises real reuse.
    handles = [server.submit(r) for r in workload(
        cfg, args.requests, args.prompt_len, args.new_tokens,
        shared_prefix=args.prefix_cache != "off")]
    t0 = time.monotonic()
    try:
        server.run()
    except KeyboardInterrupt:
        # Ctrl-C must not lose the run's telemetry: print the final
        # snapshot and run the shutdown exports before exiting with the
        # conventional interrupt status.
        wall = time.monotonic() - t0
        print(f"\ninterrupted after {wall:.1f}s: active={server.active} "
              f"prefilling={server.prefilling} pending={server.pending}")
        print(api.obs.format_snapshot(server.stats()))
        server.shutdown(metrics_out=args.metrics_out,
                        trace_out=args.trace_out)
        raise SystemExit(130)
    wall = time.monotonic() - t0
    results = [h.result() for h in handles]
    total = sum(len(r.tokens) for r in results)
    rep = server.memory_report()
    print(f"layout={args.layout} mode={args.cache_mode} "
          f"requests={len(results)} slots={args.max_slots} tokens={total} "
          f"throughput={total / wall:.1f} tok/s "
          f"kv_cache_bytes={rep['kv_bytes']:,}")
    # One schema, one printer: stats() is the registry snapshot and
    # format_snapshot is the shared renderer (DESIGN.md §14) — the old
    # hand-rolled section printers drifted between launchers.
    print(api.obs.format_snapshot(server.stats()))
    if args.metrics_out or args.trace_out:
        server.shutdown(metrics_out=args.metrics_out,
                        trace_out=args.trace_out)
    for i, r in enumerate(results[:4]):
        # ttft_s is None for token-less (failed/cancelled/expired) requests
        ttft = f"{r.ttft_s * 1e3:.0f}ms" if r.ttft_s is not None else "-"
        print(f"  req{i}: prompt_len={r.prompt_len} n_tokens={len(r.tokens)} "
              f"queue={r.queue_wait_s * 1e3:.0f}ms "
              f"ttft={ttft} "
              f"prefill={r.prefill_s * 1e3:.0f}ms gen={r.gen_s * 1e3:.0f}ms "
              f"finish={r.finish_reason} tokens={r.tokens[:8].tolist()}…")
    failed = [i for i, r in enumerate(results) if r.finish_reason == "error"]
    if failed:
        for i in failed:
            print(f"req{i} failed: {results[i].error}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
