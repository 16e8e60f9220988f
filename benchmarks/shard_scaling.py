"""Shard-scaling capacity bench: serving on a 1/2/4-device mesh at EQUAL
per-device byte budget (DESIGN.md §12).

Each device count N runs the same heterogeneous paged workload through
``api.serve`` on a pure-data ``(N, 1)`` mesh whose pool holds
``N x per_device_budget`` bytes — i.e. every configuration gives each
device the same arena slice, and what scales is how many requests the
fleet admits concurrently plus the aggregate decode rate.  Because the
scheduler pins every row's pages to the row's own data shard, the mesh adds
capacity without changing a single output token (the §12 bit-identity
parity tests assert exactly that).

Device counts are simulated: each N runs in a child process launched with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the flag must be
set before jax initializes, hence the subprocess).  That flag only splits
the CPU host platform, so on a TPU host the child refuses rather than
measure one chip under a 1/2/4-device label; the sharded path on real
chips is ``python chip_smoke.py --chips 4``.

Writes ``BENCH_shard.json``.  ``--require-capacity-win`` exits non-zero
unless the largest mesh admits at least 2x the concurrent requests of the
single device at the same per-device budget (the CI gate).

    PYTHONPATH=src python benchmarks/shard_scaling.py --smoke
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

SRC = str(Path(__file__).resolve().parents[1] / "src")


def child_main(args) -> None:
    """One device count: build the mesh, serve the workload, print JSON."""
    import dataclasses

    import jax
    import numpy as np

    from repro import obs
    from repro.core import pool as blockpool
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_serve_mesh
    from repro.launch.serve import device_info
    from repro.models import model as M
    from repro.models import registry
    from repro.serve.scheduler import Request, Server, ServerConfig

    if jax.default_backend() != "cpu":
        raise SystemExit(
            f"shard_scaling simulates device counts on the CPU host platform "
            f"(--xla_force_host_platform_device_count) but JAX found a "
            f"{jax.default_backend()!r} backend; it does not measure chips. "
            f"Run the sharded path on real chips with: python chip_smoke.py "
            f"--chips 4")
    enable_compile_cache()
    n = args.child
    cfg = dataclasses.replace(registry.get_smoke_config(args.arch),
                              cache_layout=args.layout, cache_block=8)
    params, _ = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(args.requests):
        plen = max(4, args.prompt_len
                   - (i * args.prompt_len // 2) // max(args.requests - 1, 1))
        n_new = max(2, args.new_tokens - ((i * 7) % args.new_tokens) // 2)
        reqs.append(Request(prompt=rng.integers(0, cfg.vocab_size,
                                                plen).astype(np.int32),
                            max_new_tokens=n_new))

    specs = M.cache_specs(cfg, args.max_seq)
    page_b = sum(blockpool.page_nbytes(s, cfg.n_kv_heads,
                                       cfg.resolved_head_dim) for s in specs)
    reservation_b = specs[0].n_blocks * page_b
    per_device = args.budget_units * reservation_b
    max_slots = ((args.requests + n - 1) // n) * n
    server = Server(cfg, params,
                    ServerConfig(max_slots=max_slots, max_seq=args.max_seq,
                                 policy="ljf", cache_mode="paged",
                                 pool_hbm_bytes=per_device * n,
                                 mesh=make_serve_mesh(f"{n},1")),
                    q_chunk=32, kv_chunk=32)
    handles = [server.submit(r) for r in reqs]
    peak = 0
    t0 = time.monotonic()
    while server.step():
        peak = max(peak, server.active)
    wall = time.monotonic() - t0
    toks = sum(len(h.result().tokens) for h in handles)
    st = server.stats()
    out = {
        "devices": n,
        "admitted_peak": peak,
        "tokens": toks,
        "wall_s": wall,
        "tok_s": toks / wall,
        "pool_pages": st["pool"]["pages_total"],
        "pool_high_water_pages": st["pool"]["high_water_pages"],
        "preemptions": st["preemptions"],
        "per_device_budget_bytes": per_device,
        "shards": st["shards"]["per_shard"],
        "metrics": obs.bench_columns(server),
        "device": device_info(),
    }
    print(json.dumps(out))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi_6b")
    ap.add_argument("--layout", default="packed")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--budget-units", type=int, default=1,
                    help="per-device pool budget in dense-reservation units")
    ap.add_argument("--device-counts", default="1,2,4")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (short workload)")
    ap.add_argument("--require-capacity-win", action="store_true",
                    help="exit non-zero unless the largest mesh admits >= 2x "
                         "the single device's concurrent requests at equal "
                         "per-device budget (CI gate)")
    ap.add_argument("--out", default="BENCH_shard.json")
    ap.add_argument("--child", type=int, default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.smoke:
        args.requests = min(args.requests, 10)
        args.new_tokens = min(args.new_tokens, 6)
    if args.child:
        child_main(args)
        return

    counts = [int(c) for c in args.device_counts.split(",")]
    bench = {"arch": args.arch, "layout": args.layout,
             "workload": {"requests": args.requests,
                          "prompt_len": args.prompt_len,
                          "new_tokens": args.new_tokens},
             "budget_units_per_device": args.budget_units,
             "counts": {}}
    for n in counts:
        env = dict(os.environ, PYTHONPATH=SRC,
                   XLA_FLAGS=f"--xla_force_host_platform_device_count={n}")
        argv = [sys.executable, os.path.abspath(__file__), "--child", str(n),
                "--arch", args.arch, "--layout", args.layout,
                "--requests", str(args.requests),
                "--prompt-len", str(args.prompt_len),
                "--new-tokens", str(args.new_tokens),
                "--max-seq", str(args.max_seq),
                "--budget-units", str(args.budget_units)]
        r = subprocess.run(argv, capture_output=True, text=True, env=env,
                           timeout=900)
        if r.returncode != 0:
            raise SystemExit(
                f"device count {n} failed:\n{r.stderr[-3000:]}")
        res = json.loads(r.stdout.strip().splitlines()[-1])
        bench["counts"][str(n)] = res
        print(f"[mesh {n},1] pool={res['pool_pages']:3d} pages  "
              f"admits {res['admitted_peak']:2d}/{args.requests} "
              f"@ {res['tok_s']:6.1f} tok/s  "
              f"high-water {res['pool_high_water_pages']} "
              f"preempt={res['preemptions']}")

    first, last = bench["counts"][str(counts[0])], bench["counts"][str(counts[-1])]
    bench["capacity_ratio"] = (last["admitted_peak"]
                               / max(first["admitted_peak"], 1))
    bench["tok_s_ratio"] = last["tok_s"] / first["tok_s"]
    # registry-sourced columns for run.py's CSV (largest mesh's run)
    bench["metrics"] = last["metrics"]
    bench["device"] = last["device"]
    Path(args.out).write_text(json.dumps(bench, indent=2))
    print(f"wrote {args.out}  capacity x{bench['capacity_ratio']:.2f} "
          f"({counts[0]} -> {counts[-1]} devices)")
    if args.require_capacity_win and bench["capacity_ratio"] < 2.0:
        raise SystemExit(
            f"{counts[-1]}-device mesh admitted only "
            f"x{bench['capacity_ratio']:.2f} the single device's concurrent "
            "requests at equal per-device budget (need >= 2x)")


if __name__ == "__main__":
    main()
