"""Prefix-reuse bench: shared-prompt serving with the compressed-page
prefix cache on vs off (DESIGN.md §11).

The workload is N requests that share one long block-aligned system prompt
and diverge only in a short unique suffix — the multi-tenant chat shape the
prefix cache is built for.  Both runs use the SAME paged configuration and
the SAME block-chunked admission numerics; the only difference is whether
the radix index may splice cached page ids into a new row:

  * ``prefix_cache="on"``      — admission looks up the shared prefix and
    prefills only the divergent suffix;
  * ``prefix_cache="noshare"`` — identical chunked admission with the index
    disabled (every request prefills its full prompt).

Because both modes chunk the forced tokens identically, greedy outputs are
bit-identical by construction — the bench asserts it, so the reported
savings are at EQUAL outputs, not merely similar ones.  Records per mode:

  * ``tok_s``                — aggregate decode throughput,
  * ``prefill_tokens``       — tokens actually pushed through prefill,
  * ``reused_tokens``        — tokens spliced from cached pages,
  * ``prefill_flops``        — analytic FLOPs from the model dims: linear
    cost ``2 * param_count`` per prefill token plus attention cost
    ``4 * n_layers * n_heads * head_dim`` per attended (q, kv) pair
    (the scheduler counts the pairs exactly).

Writes ``BENCH_prefix.json``.  ``--require-savings`` exits non-zero unless
sharing saves >= 2x prefill FLOPs at bit-identical tokens (the CI gate).

    PYTHONPATH=src python benchmarks/prefix_reuse.py --smoke
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time
from pathlib import Path

import jax
import numpy as np

from repro import obs
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import device_info
from repro.models import model as M
from repro.models import registry
from repro.serve.scheduler import Request, Server, ServerConfig


def make_workload(rng, vocab: int, n_requests: int, shared_len: int,
                  suffix_len: int, new_tokens: int) -> list[Request]:
    """One shared system prompt, unique per-request suffixes."""
    shared = rng.integers(0, vocab, shared_len).astype(np.int32)
    reqs = []
    for i in range(n_requests):
        suffix = rng.integers(0, vocab, suffix_len).astype(np.int32)
        reqs.append(Request(prompt=np.concatenate([shared, suffix]),
                            max_new_tokens=new_tokens))
    return reqs


def prefill_flops(cfg, prefix_stats: dict) -> int:
    """Analytic prefill FLOPs from the scheduler's exact host counters."""
    linear = 2 * cfg.param_count() * prefix_stats["prefill_tokens"]
    attn = (4 * cfg.n_layers * cfg.n_heads * cfg.resolved_head_dim
            * prefix_stats["prefill_attn_pairs"])
    return linear + attn


def run_mode(cfg, params, reqs, mode: str, max_slots: int, max_seq: int,
             pool_bytes: int | None) -> tuple[dict, list[np.ndarray]]:
    server = Server(cfg, params,
                    ServerConfig(max_slots=max_slots, max_seq=max_seq,
                                 cache_mode="paged",
                                 pool_hbm_bytes=pool_bytes,
                                 prefix_cache=mode),
                    q_chunk=32, kv_chunk=32)
    handles = [server.submit(r) for r in reqs]
    t0 = time.monotonic()
    server.run()
    wall = time.monotonic() - t0
    outs = [np.asarray(h.result().tokens) for h in handles]
    toks = sum(len(o) for o in outs)
    st = server.stats()
    px = st["prefix"]
    entry = {"tokens": toks, "wall_s": wall, "tok_s": toks / wall,
             "prefill_tokens": px["prefill_tokens"],
             "prefill_attn_pairs": px["prefill_attn_pairs"],
             "reused_tokens": px["reused_tokens"],
             "hit_rate": px["hit_rate"] if mode == "on" else 0.0,
             "prefill_flops": prefill_flops(cfg, px),
             "preemptions": st["preemptions"],
             "metrics": obs.bench_columns(server)}
    return entry, outs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi_6b")
    ap.add_argument("--layout", default="packed")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--shared-blocks", type=int, default=12,
                    help="shared system-prompt length in cache blocks")
    ap.add_argument("--suffix-len", type=int, default=6,
                    help="unique per-request prompt suffix (tokens)")
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (small model, short workload)")
    ap.add_argument("--require-savings", action="store_true",
                    help="exit non-zero unless sharing saves >= 2x prefill "
                         "FLOPs at bit-identical greedy tokens (CI gate)")
    ap.add_argument("--out", default="BENCH_prefix.json")
    args = ap.parse_args()
    enable_compile_cache()
    if args.smoke:
        args.requests = min(args.requests, 6)
        args.shared_blocks = min(args.shared_blocks, 8)
        args.new_tokens = min(args.new_tokens, 6)

    cfg0 = registry.get_smoke_config(args.arch)
    cfg = dataclasses.replace(cfg0, cache_layout=args.layout, cache_block=8)
    params, _ = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    shared_len = args.shared_blocks * 8
    reqs = make_workload(rng, cfg.vocab_size, args.requests, shared_len,
                         args.suffix_len, args.new_tokens)

    bench = {"arch": args.arch, "layout": args.layout,
             "workload": {"requests": len(reqs),
                          "shared_prefix_tokens": shared_len,
                          "suffix_tokens": args.suffix_len,
                          "new_tokens": args.new_tokens},
             "modes": {}}
    outs = {}
    # Pool left at its dense-equivalent default: ample, so no preemption
    # perturbs the wall-clock comparison.
    for mode in ("noshare", "on"):
        entry, outs[mode] = run_mode(cfg, params, reqs, mode,
                                     args.max_slots, args.max_seq, None)
        bench["modes"][mode] = entry
        print(f"[{mode:8s}] prefill_tokens={entry['prefill_tokens']:5d}  "
              f"reused_tokens={entry['reused_tokens']:5d}  "
              f"prefill_flops={entry['prefill_flops']:.3e}  "
              f"decode {entry['tok_s']:6.1f} tok/s")

    identical = (len(outs["on"]) == len(outs["noshare"]) and
                 all(a.shape == b.shape and bool((a == b).all())
                     for a, b in zip(outs["on"], outs["noshare"])))
    saved = (bench["modes"]["noshare"]["prefill_flops"]
             / max(bench["modes"]["on"]["prefill_flops"], 1))
    bench["bit_identical"] = identical
    bench["prefill_flops_saved_x"] = saved
    # registry-sourced columns for run.py's CSV (the sharing leg)
    bench["metrics"] = bench["modes"]["on"]["metrics"]
    print(f"bit_identical={identical}  prefill_flops_saved=x{saved:.2f}")

    bench["device"] = device_info()
    Path(args.out).write_text(json.dumps(bench, indent=2))
    print(f"wrote {args.out}")
    if args.require_savings:
        if not identical:
            raise SystemExit(
                "greedy outputs differ between prefix_cache=on and noshare "
                "— sharing must not change tokens")
        if saved < 2.0:
            raise SystemExit(
                f"prefix sharing saved only x{saved:.2f} prefill FLOPs "
                "(gate requires >= x2.0)")


if __name__ == "__main__":
    main()
