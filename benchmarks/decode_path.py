"""Decode-path microbench: materializing attend vs blockwise scan vs fused.

The paper's core serving claim is that decompression COST, not ratio,
decides end-to-end decode throughput — the Fetch stage must consume
compressed blocks in situ instead of reconstructing the cache in HBM.  This
bench pins that down for one layer's decode attention across cache layouts
and sequence lengths:

  * ``materialized`` — the retired production path
    (``core.cache.attend_materialized``): dequantize the WHOLE store to a
    ``[B, Hkv, NB, T, D]`` intermediate, one joint softmax.  Survives only
    as this baseline/oracle.
  * ``blockwise``    — the ``"xla"`` backend (``attend_blockwise``): running
    (m, l, acc) scan over the block axis, one lazily-decoded block at a
    time, dequant folded into the matvec.
  * ``fused``        — the ``"fused"`` backend through
    ``kernels.ops.cache_decode_attention`` (Pallas kernel on TPU; its
    vmapped tile-decode oracle elsewhere — the recorded ``impl`` says which
    ran).

Per cell it reports attention steps/s → tok/s (steps × batch / wall) and the
compiled peak temp memory (``memory_analysis().temp_size_in_bytes`` — the
materialized intermediate shows up here).  Writes ``BENCH_decode.json``;
``--require-win`` gates CI on (a) the production path (blockwise off-TPU)
matching or beating the materializing baseline per decode-cheap layout in
geomean, and (b) the huffman FUSED leg existing (``supports_fused`` — the
maximal-ratio layout must serve through the fused backend, DESIGN.md §9)
and staying within ``FUSED_GATE_MIN`` of huffman-blockwise at the longest
context, with one remeasure before failing.  The band, not strict >= 1.0:
on idle hardware the fused leg wins the long-context cell (x1.2-1.4
recorded in BENCH_decode.json; the pre-LUT deficit was x0.95 with decode
~10x slower overall), but the CPU oracle's wide one-pass decode is
bimodal under box state (+-2x observed), while a genuine decode
regression — say the one-tree-step-per-BIT walk sneaking back — lands far
below the band.  The definitive fused-vs-blockwise numbers are the
real-TPU bench pass's to claim (ROADMAP).  Huffman's (a) is reported but
not gated for the same variance reason: its two one-pass decode paths
(materialized, fused-CPU-oracle) and the span-chunked scan trade places
with context length and box load.

    PYTHONPATH=src python benchmarks/decode_path.py --smoke --require-win
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cache as C
from repro.kernels import ops
from repro.kernels.runtime import on_tpu
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import device_info


def build_cache(rng, layout: str, B: int, Hkv: int, D: int, S: int,
                block: int) -> C.LayerKVCache:
    spec = C.CacheSpec(layout=layout, block_size=block, max_seq=S)
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    return C.prefill(spec, k, v)


def peak_temp_bytes(fn, *args) -> int | None:
    try:
        ma = jax.jit(fn).lower(*args).compile().memory_analysis()
        return int(ma.temp_size_in_bytes)
    except Exception:
        return None  # backends without memory_analysis support


def bench_paths(fns: dict, cache, q, steps: int, repeats: int) -> dict:
    """Measure all paths with interleaved repeats (every repeat times each
    path back to back, so host-speed drift hits them equally) and take the
    per-path median wall."""
    jfns = {n: jax.jit(fn) for n, fn in fns.items()}
    for jfn in jfns.values():
        jfn(cache, q).block_until_ready()  # compile + warmup
    walls = {n: [] for n in fns}
    for _ in range(repeats):
        for n, jfn in jfns.items():
            t0 = time.monotonic()
            for _ in range(steps):
                out = jfn(cache, q)
            out.block_until_ready()
            walls[n].append(time.monotonic() - t0)
    B = q.shape[0]
    out = {}
    for n, ws in walls.items():
        wall = sorted(ws)[len(ws) // 2]
        out[n] = {"wall_s": wall, "steps": steps, "tok_s": steps * B / wall,
                  "peak_temp_bytes": peak_temp_bytes(fns[n], cache, q)}
    return out


PATHS = {
    "materialized": lambda c, q: C.attend_materialized(c, q),
    "blockwise": lambda c, q: C.attend_blockwise(c, q),
    "fused": lambda c, q: ops.cache_decode_attention(c, q),
}

# --require-win floor for huffman fused-vs-blockwise at the longest context
# (see module docstring: wins on idle hardware, band absorbs the recorded
# +-2x box-state bimodality of the CPU oracle's one-pass decode).
FUSED_GATE_MIN = 0.6


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layouts", default="raw,packed,kivi,huffman")
    ap.add_argument("--seq-lens", default="1024,4096")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--kv-heads", type=int, default=2)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--gqa", type=int, default=4)
    ap.add_argument("--block", type=int, default=64)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized grid (compressed layouts, short run)")
    ap.add_argument("--require-win", action="store_true",
                    help="exit non-zero unless, per decode-cheap layout, the "
                         "production path (blockwise off-TPU, fused on TPU) "
                         ">= the materializing baseline tok/s in geomean "
                         "over the seq-len grid, AND huffman serves a fused "
                         "leg within FUSED_GATE_MIN of blockwise at the "
                         "longest context (see module docstring)")
    ap.add_argument("--out", default="BENCH_decode.json")
    args = ap.parse_args()
    enable_compile_cache()
    if args.smoke:
        # CI gate runs the production layout of the paper's TPU path plus
        # the maximal-ratio huffman layout (its fused-vs-blockwise win is
        # gated); the full grid (default args) additionally reports raw/kivi.
        args.layouts = "packed,huffman"
        args.seq_lens = "1024,4096"
        args.steps = 5

    production = "fused" if on_tpu() else "blockwise"
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(
        size=(args.batch, args.kv_heads * args.gqa, args.head_dim)
        ).astype(np.float32))

    bench = {"batch": args.batch, "kv_heads": args.kv_heads,
             "head_dim": args.head_dim, "gqa": args.gqa, "block": args.block,
             "production_path": production,
             "fused_impl": ops.resolve_impl("auto"), "cells": []}
    speedups: dict[str, list[float]] = {}
    fused_ratios: dict[str, list[float]] = {}
    for layout in args.layouts.split(","):
        for S in (int(s) for s in args.seq_lens.split(",")):
            cache = build_cache(rng, layout, args.batch, args.kv_heads,
                                args.head_dim, S, args.block)
            fns = {n: f for n, f in PATHS.items()
                   if n != "fused" or cache.spec.impl.supports_fused}
            cell = {"layout": layout, "seq_len": S,
                    "paths": bench_paths(fns, cache, q, args.steps,
                                         args.repeats)}
            prod = cell["paths"].get(production) or cell["paths"]["blockwise"]
            base = cell["paths"]["materialized"]
            cell["production_speedup"] = prod["tok_s"] / base["tok_s"]
            mem = (base["peak_temp_bytes"] / prod["peak_temp_bytes"]
                   if prod["peak_temp_bytes"] else None)
            cell["production_mem_reduction"] = mem
            if "fused" in cell["paths"]:
                cell["fused_vs_blockwise"] = (
                    cell["paths"]["fused"]["tok_s"]
                    / cell["paths"]["blockwise"]["tok_s"])
                fused_ratios.setdefault(layout, []).append(
                    cell["fused_vs_blockwise"])
            bench["cells"].append(cell)
            speedups.setdefault(layout, []).append(cell["production_speedup"])
            print(f"[{layout:8s} S={S:5d}] " + "  ".join(
                f"{n} {p['tok_s']:9.1f} tok/s"
                + (f" temp {p['peak_temp_bytes']:>11,}B"
                   if p["peak_temp_bytes"] is not None else "")
                for n, p in cell["paths"].items())
                + f"  prod x{cell['production_speedup']:.2f}")

    geomean = lambda xs: float(np.exp(np.mean(np.log(xs))))
    bench["layout_geomean_speedup"] = {
        l: geomean(xs) for l, xs in speedups.items()}
    bench["layout_geomean_fused_vs_blockwise"] = {
        l: geomean(xs) for l, xs in fused_ratios.items()}
    bench["device"] = device_info()
    Path(args.out).write_text(json.dumps(bench, indent=2))
    print("per-layout geomean production speedup: " + "  ".join(
        f"{l} x{x:.2f}" for l, x in bench["layout_geomean_speedup"].items()))
    print("per-layout geomean fused-vs-blockwise: " + "  ".join(
        f"{l} x{x:.2f}"
        for l, x in bench["layout_geomean_fused_vs_blockwise"].items()))
    print(f"wrote {args.out}")
    if args.require_win:
        losses = {l: x for l, x in bench["layout_geomean_speedup"].items()
                  if x < 1.0 and l != "huffman"}  # see module docstring (b)
        if losses:
            raise SystemExit(
                "production decode path lost to the materializing baseline on: "
                + ", ".join(f"{l} ({x:.2f}x)" for l, x in losses.items()))
        # The maximal-ratio layout must serve through the fused backend
        # (before PR 5 it silently fell back to the blockwise scan) and its
        # in-kernel decode must stay in the same league as blockwise at
        # long context — see module docstring for the FUSED_GATE_MIN band.
        hf_all = [c for c in bench["cells"] if c["layout"] == "huffman"]
        hf_cells = [c for c in hf_all if "fused_vs_blockwise" in c]
        if hf_all and not hf_cells:
            raise SystemExit(
                "huffman has no fused leg: the layout lost supports_fused")
        if hf_cells:
            longest = max(hf_cells, key=lambda c: c["seq_len"])
            S, ratio = longest["seq_len"], longest["fused_vs_blockwise"]
            if ratio < FUSED_GATE_MIN:
                # Transient-load guard: the decisive ratio is a wall-clock
                # measurement; remeasure the one cell before failing, so a
                # loaded runner doesn't red the pipeline while a real
                # regression still fails twice.
                cache = build_cache(rng, "huffman", args.batch, args.kv_heads,
                                    args.head_dim, S, args.block)
                paths = bench_paths(
                    {n: PATHS[n] for n in ("blockwise", "fused")},
                    cache, q, args.steps, args.repeats)
                retry = paths["fused"]["tok_s"] / paths["blockwise"]["tok_s"]
                print(f"huffman-fused gate retry at S={S}: x{retry:.2f} "
                      f"(first run x{ratio:.2f})")
                ratio = max(ratio, retry)
            if ratio < FUSED_GATE_MIN:
                raise SystemExit(
                    f"huffman-fused fell below x{FUSED_GATE_MIN} of "
                    f"huffman-blockwise at S={S} ({ratio:.2f}x, twice)")


if __name__ == "__main__":
    main()
