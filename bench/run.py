"""Run one cell of the benchmark once.

    python3 bench/run.py --workload yi_6b.longdoc --seed 7 --seconds 30 \\
        --trace 0

Loads the cell named in ``BENCHMARK.json``, makes its weights and traffic
from ``--seed``, warms every program the window uses (set-up), measures for
``--seconds`` seconds, checks what was served against the plain reference,
and prints one JSON line as the last line of standard output.  With
``--trace 0`` its metrics are the cell's end-to-end metrics; with
``--trace 1`` the window is profiled and the metrics are the cell's
per-layer metrics.  The numbers compared for ``correct`` are printed beside
their limits as the last lines of standard error and under ``check`` in the
line.

It runs on a TPU only: it exits non-zero without a result line when JAX
finds no TPU or fewer chips than the cell asks for, or when the program
(``src/repro``) is not in the checkout.  JAX's compilation cache is kept at
``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` says
otherwise.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="also copy the traced window's .xplane.pb here")
    return ap.parse_args(argv)


def setup_paths():
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"run.py: the program is missing ({src / 'repro'}); "
                         "run from a checkout of the repository")
    for p in (str(BENCH), str(src)):
        if p not in sys.path:
            sys.path.insert(0, p)


def enable_cache():
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def report(line: dict):
    for name, v in line["check"].items():
        print(f"check {name}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


def run(args, require_tpu=True, peaks=None, bench_dir=BENCH) -> dict:
    from kvbench import cell as cell_mod
    from kvbench import spec

    c = spec.load_cell(args.workload, bench_dir)
    runner = cell_mod.Runner(c, args.seed, args.seconds, bool(args.trace),
                             T_START, require_tpu=require_tpu, peaks=peaks,
                             keep_trace=getattr(args, "keep_trace", None))
    return runner.run()


def main(argv=None) -> int:
    args = parse(argv)
    setup_paths()
    enable_cache()
    from kvbench.cell import Fail
    from kvbench.spec import SpecError

    try:
        line = run(args)
    except (Fail, SpecError) as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 1
    report(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
