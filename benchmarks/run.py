"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV plus the registry-sourced serving
columns (``repro.obs.BENCH_COLUMNS``: TTFT/ITL p50+p99, preemptions,
copy-on-write breaks — read from each serving suite's ``BENCH_*.json``
``"metrics"`` block; figure suites leave them empty).

    PYTHONPATH=src python -m benchmarks.run                # everything
    PYTHONPATH=src python -m benchmarks.run --only ratio_k # one figure
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

from repro.launch.compile_cache import enable_compile_cache
from repro.obs import BENCH_COLUMNS

SUITES = [
    ("accuracy_sweep", "paper Fig. 5/6: accuracy vs rel quant scale"),
    ("ratio_k", "paper Fig. 7: K ratio vs KIVI/ChannelQuant"),
    ("ratio_v", "paper Fig. 8: V ratio vs ctx length"),
    ("fused_vs_multi", "paper Fig. 9: fused vs multi-kernel"),
    ("fused_vs_matvec", "paper Fig. 10/11: fused vs plain matvec"),
    ("roofline", "dry-run roofline table"),
    ("serve_throughput", "continuous-batching serving throughput, chunked-prefill"
     " p99 inter-token latency (mixed long-prompt leg)"),
    ("decode_path", "decode-path latency breakdown"),
    ("pool_pressure", "paged-pool capacity vs dense reservation (§10)"),
    ("prefix_reuse", "prefix-cache prefill savings, on vs noshare (§11)"),
    ("shard_scaling", "mesh capacity at equal per-device budget (§12)"),
]


# Which BENCH_*.json each script suite writes — where its registry-sourced
# CSV columns come from (obs.bench_columns embedded under "metrics").
BENCH_JSON = {
    "serve_throughput": "BENCH_serve.json",
    "decode_path": "BENCH_decode.json",
    "pool_pressure": "BENCH_pool.json",
    "prefix_reuse": "BENCH_prefix.json",
    "shard_scaling": "BENCH_shard.json",
}


def metric_cols(mod_name: str) -> str:
    """The trailing CSV cells for one suite row: values from the suite's
    ``BENCH_*.json`` ``"metrics"`` block in ``BENCH_COLUMNS`` order, empty
    cells when the suite has no serving registry behind it."""
    path = BENCH_JSON.get(mod_name)
    if path and os.path.exists(path):
        m = json.loads(open(path).read()).get("metrics") or {}
    else:
        m = {}

    def cell(k):
        v = m.get(k)
        if v is None:
            return ""
        return str(v) if isinstance(v, int) else f"{v:.6g}"

    return "".join("," + cell(k) for k in BENCH_COLUMNS)


def run_one(mod_name: str) -> int:
    """Run one suite in-process (used by the per-suite subprocess).

    Two suite shapes: figure modules expose a ``run()`` generator of
    ``(name, us, derived)`` rows; serving suites are argparse scripts
    (``main()`` + ``--smoke``) that write their own ``BENCH_*.json`` — those
    run under ``--smoke`` and report one pass/fail CSV row here.
    """
    enable_compile_cache()
    empty = "," * len(BENCH_COLUMNS)
    mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
    if hasattr(mod, "run"):
        for name, us, derived in mod.run():
            print(f"{name},{us:.1f},{derived}{empty}", flush=True)
        return 0
    argv, sys.argv = sys.argv, [f"benchmarks/{mod_name}.py", "--smoke"]
    try:
        t0 = time.time()
        mod.main()
        print(f"{mod_name},{(time.time() - t0) * 1e6:.1f},smoke_ok"
              f"{metric_cols(mod_name)}", flush=True)
    finally:
        sys.argv = argv
    return 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names")
    ap.add_argument("--in-process", action="store_true",
                    help="run suites in this process (default: one fresh "
                         "subprocess per suite — jitted-executable caches "
                         "otherwise accumulate past this container's RAM)")
    args = ap.parse_args()
    want = set(args.only.split(",")) if args.only else None

    print("name,us_per_call,derived," + ",".join(BENCH_COLUMNS), flush=True)
    failures = 0
    for mod_name, desc in SUITES:
        if want and mod_name not in want:
            continue
        t0 = time.time()
        if args.in_process:
            try:
                run_one(mod_name)
            except Exception as e:  # noqa: BLE001
                failures += 1
                print(f"{mod_name}_FAILED,0,{type(e).__name__}:{e}"
                      + "," * len(BENCH_COLUMNS), flush=True)
        else:
            # One child per suite, and only the child touches a device: this
            # parent imports jax (through repro.obs) but never initialises a
            # backend, so on a TPU host the chip stays free for the child.
            code = (
                "from benchmarks.run import run_one; "
                f"run_one({mod_name!r})"
            )
            env = dict(os.environ)
            env.setdefault("PYTHONPATH", "src")
            r = subprocess.run([sys.executable, "-c", code], env=env,
                               stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                               text=True)
            sys.stdout.write("\n".join(
                l for l in r.stdout.splitlines() if "," in l and not l.startswith("#")
            ) + ("\n" if r.stdout else ""))
            sys.stdout.flush()
            if r.returncode != 0:
                failures += 1
                print(f"{mod_name}_FAILED,0,subprocess_exit_{r.returncode}"
                      + "," * len(BENCH_COLUMNS), flush=True)
        print(f"# {mod_name} ({desc}) took {time.time() - t0:.1f}s",
              file=sys.stderr, flush=True)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
