"""Pool-pressure capacity bench: paged vs dense admission under one byte
budget (DESIGN.md §10).

Dense mode reserves ``n_blocks`` ring blocks per slot up front, so a byte
budget admits ``budget // (n_blocks * page_bytes)`` concurrent requests no
matter how little of the ring each request uses.  The paged pool admits by
actual post-compression occupancy, so the same budget holds more concurrent
requests — the footprint-to-throughput coupling the paper's compression
ratio buys.  For each budget (in dense-reservation units) and layout this
bench runs the same heterogeneous workload through both modes and records:

  * ``admitted_peak``   — max simultaneously live requests,
  * ``tok_s``           — aggregate decode throughput,
  * ``preemptions`` / pool high-water (paged).

Writes ``BENCH_pool.json``.  ``--require-capacity-win`` exits non-zero
unless, at every budget, the paged server admits STRICTLY more concurrent
requests than dense for a compressing layout (the CI gate).

    PYTHONPATH=src python benchmarks/pool_pressure.py --smoke
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
from repro.core import pool as blockpool
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import device_info
from repro.models import model as M
from repro.models import registry
from repro.serve.scheduler import Request, Server, ServerConfig


def make_workload(rng, vocab: int, n_requests: int, prompt_len: int,
                  new_tokens: int) -> list[Request]:
    """Short-lived heterogeneous requests: each needs a small fraction of a
    full dense ring, which is exactly the traffic a paged pool packs."""
    reqs = []
    for i in range(n_requests):
        plen = max(4, prompt_len - (i * prompt_len // 2) // max(n_requests - 1, 1))
        n_new = max(2, new_tokens - ((i * 7) % new_tokens) // 2)
        reqs.append(Request(prompt=rng.integers(0, vocab, plen).astype(np.int32),
                            max_new_tokens=n_new))
    return reqs


def run_mode(cfg, params, reqs, mode: str, budget: int, max_slots: int,
             max_seq: int) -> dict:
    server = Server(cfg, params,
                    ServerConfig(max_slots=max_slots, max_seq=max_seq,
                                 policy="ljf", cache_mode=mode,
                                 pool_hbm_bytes=budget if mode == "paged" else None),
                    q_chunk=32, kv_chunk=32)
    handles = [server.submit(r) for r in reqs]
    peak = 0
    t0 = time.monotonic()
    while server.step():
        peak = max(peak, server.active)
    wall = time.monotonic() - t0
    toks = sum(len(h.result().tokens) for h in handles)
    out = {"admitted_peak": peak, "tokens": toks, "wall_s": wall,
           "tok_s": toks / wall,
           "metrics": obs.bench_columns(server)}
    st = server.stats()
    if "pool" in st:
        out["preemptions"] = st["preemptions"]
        out["pool_pages"] = st["pool"]["pages_total"]
        out["pool_high_water_pages"] = st["pool"]["high_water_pages"]
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi_6b")
    ap.add_argument("--layouts", default="packed,raw")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--new-tokens", type=int, default=10)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--budgets", default="2,3",
                    help="byte budgets in dense-reservation units")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (small model, short workload)")
    ap.add_argument("--require-capacity-win", action="store_true",
                    help="exit non-zero unless paged admits strictly more "
                         "concurrent requests than dense at every budget "
                         "for a compressing layout (CI gate)")
    ap.add_argument("--out", default="BENCH_pool.json")
    args = ap.parse_args()
    enable_compile_cache()
    if args.smoke:
        args.requests = min(args.requests, 8)
        args.budgets = "2"

    cfg0 = registry.get_smoke_config(args.arch)
    params, _ = M.init_params(cfg0, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = make_workload(rng, cfg0.vocab_size, args.requests,
                         args.prompt_len, args.new_tokens)

    bench = {"arch": args.arch,
             "workload": {"requests": len(reqs),
                          "prompt_lens": [len(r.prompt) for r in reqs],
                          "max_new_tokens": [r.max_new_tokens for r in reqs]},
             "layouts": {}}
    compressing_wins = []
    for layout in args.layouts.split(","):
        cfg = dataclasses.replace(cfg0, cache_layout=layout, cache_block=8)
        specs = M.cache_specs(cfg, args.max_seq)
        page_b = sum(blockpool.page_nbytes(s, cfg.n_kv_heads,
                                           cfg.resolved_head_dim)
                     for s in specs)
        reservation_b = specs[0].n_blocks * page_b  # one dense slot's bytes
        entry = {"page_bytes": page_b, "dense_reservation_bytes": reservation_b,
                 "budgets": {}}
        for units in (int(u) for u in args.budgets.split(",")):
            budget = units * reservation_b
            dense_slots = budget // reservation_b
            dense = run_mode(cfg, params, reqs, "dense", budget,
                             max_slots=dense_slots, max_seq=args.max_seq)
            paged = run_mode(cfg, params, reqs, "paged", budget,
                             max_slots=len(reqs), max_seq=args.max_seq)
            ratio = paged["admitted_peak"] / max(dense["admitted_peak"], 1)
            entry["budgets"][f"{units}x"] = {
                "budget_bytes": budget, "dense": dense, "paged": paged,
                "capacity_ratio": ratio,
                "tok_s_ratio": paged["tok_s"] / dense["tok_s"],
            }
            if layout != "raw":
                compressing_wins.append(
                    (layout, units, paged["admitted_peak"],
                     dense["admitted_peak"]))
            print(f"[{layout:8s} {units}x] budget={budget:>9,}B  "
                  f"dense admits {dense['admitted_peak']:2d} "
                  f"@ {dense['tok_s']:6.1f} tok/s  "
                  f"paged admits {paged['admitted_peak']:2d} "
                  f"@ {paged['tok_s']:6.1f} tok/s  "
                  f"capacity x{ratio:.2f}  "
                  f"preempt={paged.get('preemptions', 0)}")
        bench["layouts"][layout] = entry
        # registry-sourced columns for run.py's CSV (last paged leg)
        bench["metrics"] = paged["metrics"]

    bench["device"] = device_info()
    Path(args.out).write_text(json.dumps(bench, indent=2))
    print(f"wrote {args.out}")
    if args.require_capacity_win:
        losses = [(lay, u, p, d) for lay, u, p, d in compressing_wins
                  if p <= d]
        if losses:
            raise SystemExit(
                "paged admission did not beat dense reservation at the same "
                f"byte budget: {losses}")


if __name__ == "__main__":
    main()
