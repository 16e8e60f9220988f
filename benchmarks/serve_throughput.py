"""Continuous-batching serve throughput: Server vs the legacy bucket engine.

One heterogeneous workload (mixed prompt lengths, mixed token budgets) runs
through both serving paths per cache layout:

  * ``Server`` — slot scheduler, per-row decode positions, requests join and
    leave mid-flight (no lockstep padding waste);
  * ``LockstepEngine`` — the pre-scheduler bucket batcher: groups padded to a
    length grid decode for ``max(max_new_tokens)`` steps each.

Both paths run once for jit warmup and once measured, on the same compiled
closures, so the comparison is steady-state scheduling efficiency rather
than compile time.  Writes ``BENCH_serve.json`` with aggregate tok/s,
latency decomposition (queue wait / TTFT / inter-token p50+p99 — per-token
timestamps from ``Result.token_times``), and live kv-cache bytes per
layout — the serving numbers behind the paper's "throughput-critical
inference systems" claim (§5).

A second, mixed long-prompt/short-decode leg (DESIGN.md §13) replays the
tail-latency scenario chunked admission exists for: one long prompt lands
mid-stream over a pool of short decoders, once under ``prefill_mode=
"chunked"`` and once under ``"solo"``.  ``--require-p99-win`` gates the
result (CI): chunked admission must cut the short decoders' p99
inter-token latency at least 2x vs solo at >= 0.9x the aggregate tok/s,
with bit-identical greedy outputs.

    PYTHONPATH=src python benchmarks/serve_throughput.py --smoke
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
from repro.serve.engine import EngineConfig, LockstepEngine, Request
from repro.serve.scheduler import Server, ServerConfig


def make_workload(rng, vocab: int, n_requests: int, base_prompt: int,
                  base_new: int) -> list[Request]:
    """Heterogeneous mix — the traffic continuous batching exists for:
    prompt lengths spread base/6 .. base (several length buckets, unevenly
    filled) and budgets base/6 .. base scattered so every bucket group holds
    at least one long-running request (maximal lockstep masking waste)."""
    n1 = max(n_requests - 1, 1)
    ks = rng.permutation(n_requests)  # scatter budgets across the length order
    reqs = []
    for i in range(n_requests):
        plen = max(4, base_prompt - (base_prompt - base_prompt // 6) * i // n1)
        n_new = max(2, base_new - (base_new - base_new // 6) * int(ks[i]) // n1)
        reqs.append(Request(prompt=rng.integers(0, vocab, plen).astype(np.int32),
                            max_new_tokens=n_new))
    return reqs


def _latency_block(results) -> dict:
    """Latency decomposition from per-token timestamps: queue wait split
    out of the old conflated mean latency, TTFT and inter-token gaps as
    p50/p99 (the serving tail the chunked-admission gate watches)."""
    # ttft_s is None for token-less results (failed/cancelled before the
    # first token); exclude them rather than report a fictitious 0.0
    ttfts = [r.ttft_s for r in results if r.ttft_s is not None] or [0.0]
    gaps = np.concatenate([np.diff(r.token_times) for r in results
                           if len(r.token_times) > 1] or [np.zeros(1)])
    return {
        "queue_wait_s": float(np.mean([r.queue_wait_s for r in results])),
        "mean_latency_s": float(np.mean([r.prefill_s + r.gen_s
                                         for r in results])),
        "ttft_p50_s": float(np.percentile(ttfts, 50)),
        "ttft_p99_s": float(np.percentile(ttfts, 99)),
        "itl_p50_s": float(np.percentile(gaps, 50)),
        "itl_p99_s": float(np.percentile(gaps, 99)),
    }


def run_server(server: Server, reqs: list[Request]) -> dict:
    handles = [server.submit(r) for r in reqs]
    t0 = time.monotonic()
    server.run()
    wall = time.monotonic() - t0
    results = [h.result() for h in handles]
    toks = sum(len(r.tokens) for r in results)
    return {"wall_s": wall, "tokens": toks, "tok_s": toks / wall,
            **_latency_block(results)}


def run_mixed(cfg, params, mode: str, shorts: list[Request],
              long_req: Request, *, slots: int, max_seq: int,
              chunk_tokens: int, pre_steps: int = 3,
              repeats: int = 3) -> dict:
    """One long prompt arriving mid-stream over a pool of short decoders,
    under ``prefill_mode=mode`` on the paged pool (the fused
    encode-to-page admission path).  The short decoders' inter-token gaps
    are the measurement: solo admission freezes them for the long
    prompt's whole prefill, chunked admission bounds every stall at
    ``prefill_chunk_tokens``."""
    server = Server(cfg, params,
                    ServerConfig(max_slots=slots, max_seq=max_seq,
                                 cache_mode="paged", prefill_mode=mode,
                                 prefill_chunk_tokens=chunk_tokens),
                    q_chunk=32, kv_chunk=32)

    def once():
        hs = [server.submit(r) for r in shorts]
        t0 = time.monotonic()
        for _ in range(pre_steps):   # the decoders are mid-stream...
            server.step()
        hl = server.submit(long_req)  # ...when the long prompt lands
        server.run()
        wall = time.monotonic() - t0
        return hs, hl, wall

    once()  # jit warmup on the same compiled closures
    # median-of-repeats: the short decoders' p99 inter-token gap is a tail
    # statistic, exactly what single-shot CPU walls scatter the most
    runs = sorted((once() for _ in range(repeats)), key=lambda r: r[2])
    hs, hl, wall = runs[len(runs) // 2]
    short_res = [h.result() for h in hs]
    long_res = hl.result()
    toks = sum(len(r.tokens) for r in short_res) + len(long_res.tokens)
    return {"wall_s": wall, "tokens": toks, "tok_s": toks / wall,
            "long_ttft_s": long_res.ttft_s,
            "long_queue_wait_s": long_res.queue_wait_s,
            "stalled_decode_steps":
                server.stats()["prefill"]["stalled_decode_steps"],
            "coscheduled_tokens":
                server.stats()["prefill"]["coscheduled_tokens"],
            **{f"short_{k}": v for k, v in _latency_block(short_res).items()},
            "outputs": [r.tokens.tolist()
                        for r in short_res] + [long_res.tokens.tolist()]}


def run_lockstep(engine: LockstepEngine, reqs: list[Request]) -> dict:
    t0 = time.monotonic()
    results = engine.generate(reqs)
    wall = time.monotonic() - t0
    toks = sum(len(r.tokens) for r in results)
    return {"wall_s": wall, "tokens": toks, "tok_s": toks / wall}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi_6b")
    ap.add_argument("--layouts", default="raw,packed")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (small model, short workload)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--require-speedup", action="store_true",
                    help="exit non-zero unless the server beats the legacy "
                         "bucket engine on every layout (CI gate)")
    ap.add_argument("--long-prompt", type=int, default=8192,
                    help="long-prompt length for the mixed leg (8-32k "
                         "nominal; --smoke shrinks it)")
    ap.add_argument("--require-p99-win", action="store_true",
                    help="exit non-zero unless chunked admission cuts the "
                         "mixed leg's p99 inter-token latency >=2x vs solo "
                         "at >=0.9x aggregate tok/s (CI gate)")
    ap.add_argument("--trace", default="off",
                    choices=("off", "events", "full"),
                    help="scheduler event-trace level for the Server runs "
                         "(DESIGN.md §14); 'off' keeps the hot path "
                         "event-free")
    ap.add_argument("--trace-out", default=None,
                    help="write the last layout server's Chrome trace-event "
                         "JSON here (needs --trace events|full)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the last layout server's metrics snapshot "
                         "(JSON + .prom exposition sibling) here")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a jax.profiler device trace of the "
                         "measured layout runs into this directory")
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args()
    enable_compile_cache()
    if args.smoke:
        # small counts, same traffic shape as the default: prompts spanning
        # several length buckets (fragmented legacy groups), a deep scattered
        # decode-budget spread (lockstep masking waste), and queue depth
        # beyond the slot count (continuous refill)
        args.requests = min(args.requests, 10)
        args.prompt_len = min(args.prompt_len, 48)
        args.new_tokens = min(args.new_tokens, 32)
        args.max_seq = min(args.max_seq, 128)

    cfg0 = registry.get_smoke_config(args.arch)
    params, _ = M.init_params(cfg0, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = make_workload(rng, cfg0.vocab_size, args.requests,
                         args.prompt_len, args.new_tokens)
    assert len(reqs) >= 8 or args.requests < 8

    bench = {"arch": args.arch,
             "workload": {"requests": len(reqs),
                          "prompt_lens": [len(r.prompt) for r in reqs],
                          "max_new_tokens": [r.max_new_tokens for r in reqs]},
             "slots": args.slots, "layouts": {}}
    server = None
    with obs.trace_capture(args.profile_dir):
        for layout in args.layouts.split(","):
            cfg = dataclasses.replace(cfg0, cache_layout=layout)
            server = Server(cfg, params,
                            ServerConfig(max_slots=args.slots,
                                         max_seq=args.max_seq,
                                         policy="ljf", trace=args.trace),
                            q_chunk=32, kv_chunk=32)
            legacy = LockstepEngine(cfg, params,
                                    EngineConfig(bucket=32,
                                                 max_batch=args.slots,
                                                 max_seq=args.max_seq),
                                    q_chunk=32, kv_chunk=32)
            run_server(server, reqs)  # jit warmup (same compiled closures)
            run_lockstep(legacy, reqs)
            # interleaved repeats + median: CPU walls at this scale are
            # noisy, and alternating the engines exposes both to the same
            # drift
            srv_runs, old_runs = [], []
            for _ in range(args.repeats):
                srv_runs.append(run_server(server, reqs))
                old_runs.append(run_lockstep(legacy, reqs))
            srv = sorted(srv_runs, key=lambda r: r["tok_s"])[args.repeats // 2]
            old = sorted(old_runs, key=lambda r: r["tok_s"])[args.repeats // 2]
            srv["kv_cache_bytes"] = server.memory_report()["kv_bytes"]
            entry = {"server": srv, "legacy_bucket": old,
                     "speedup": srv["tok_s"] / old["tok_s"]}
            bench["layouts"][layout] = entry
            print(f"[{layout:8s}] server {srv['tok_s']:7.1f} tok/s  "
                  f"legacy {old['tok_s']:7.1f} tok/s  "
                  f"speedup {entry['speedup']:.2f}x  "
                  f"kv_cache {srv['kv_cache_bytes']:,}B")
    # Registry-sourced columns (last layout's server): what run.py splices
    # into its CSV rows and the CI artifacts expose.
    bench["metrics"] = obs.bench_columns(server)
    if args.metrics_out or args.trace_out:
        server.shutdown(metrics_out=args.metrics_out,
                        trace_out=args.trace_out)

    walls = [(v["server"]["wall_s"], v["legacy_bucket"]["wall_s"],
              v["server"]["tokens"]) for v in bench["layouts"].values()]
    agg = (sum(t for _, _, t in walls) / sum(s for s, _, _ in walls)) / \
          (sum(t for _, _, t in walls) / sum(l for _, l, _ in walls))
    bench["aggregate_speedup"] = agg

    # -- mixed long-prompt/short-decode leg (chunked vs solo admission) -----
    mix_cfg = dataclasses.replace(cfg0, cache_layout="packed")
    T = M.cache_specs(mix_cfg, 1)[0].block_size
    long_len = (min(args.long_prompt, 30 * T) if args.smoke
                else args.long_prompt)
    long_len -= long_len % T  # block-multiple keeps the chunk count exact
    mix_seq = long_len + 4 * T + 16
    rng2 = np.random.default_rng(1)
    shorts = [Request(prompt=rng2.integers(0, mix_cfg.vocab_size,
                                           8 + 2 * i).astype(np.int32),
                      max_new_tokens=40) for i in range(args.slots - 1)]
    long_req = Request(prompt=rng2.integers(0, mix_cfg.vocab_size,
                                            long_len).astype(np.int32),
                       max_new_tokens=8)
    legs = {mode: run_mixed(mix_cfg, params, mode, shorts, long_req,
                            slots=args.slots, max_seq=mix_seq,
                            chunk_tokens=2 * T)
            for mode in ("chunked", "solo")}
    match = legs["chunked"].pop("outputs") == legs["solo"].pop("outputs")
    p99_ratio = (legs["solo"]["short_itl_p99_s"]
                 / max(legs["chunked"]["short_itl_p99_s"], 1e-9))
    tok_ratio = legs["chunked"]["tok_s"] / legs["solo"]["tok_s"]
    bench["mixed_long_prompt"] = {
        "long_prompt_len": long_len, "chunk_tokens": 2 * T,
        "short_requests": len(shorts), "bit_identical": match,
        "p99_itl_improvement": p99_ratio, "tok_s_ratio": tok_ratio,
        **{mode: leg for mode, leg in legs.items()},
    }
    print(f"[mixed   ] long={long_len} tok: p99 ITL "
          f"{legs['solo']['short_itl_p99_s'] * 1e3:.1f}ms solo -> "
          f"{legs['chunked']['short_itl_p99_s'] * 1e3:.1f}ms chunked "
          f"({p99_ratio:.2f}x better) at {tok_ratio:.2f}x tok/s, "
          f"bit_identical={match}")

    bench["device"] = device_info()
    Path(args.out).write_text(json.dumps(bench, indent=2))
    print(f"aggregate speedup {agg:.2f}x; wrote {args.out}")
    if args.require_speedup and agg <= 1.0:
        raise SystemExit(
            f"server did not beat the legacy bucket engine in aggregate "
            f"({agg:.2f}x): " +
            str({k: round(v['speedup'], 2) for k, v in bench['layouts'].items()}))
    if args.require_p99_win and not (
            match and p99_ratio >= 2.0 and tok_ratio >= 0.9):
        raise SystemExit(
            "chunked admission failed the mixed-leg gate: "
            f"p99 ITL improvement {p99_ratio:.2f}x (need >=2), tok/s ratio "
            f"{tok_ratio:.2f} (need >=0.9), bit_identical={match}")


if __name__ == "__main__":
    main()
