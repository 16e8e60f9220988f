"""Readings that a cell's correctness limit is set from.

    python3 bench/calibrate.py --workload yi_6b.longdoc --seconds 10 \\
        --seeds 11 12 13 --control-seeds 11 12 13

For each seed, in one process: the cell's set-up and a window of
``--seconds`` at the cell's own load, then the reference over the checked
sample.  It prints, per seed, the mean and the widest reference gap of the
served tokens (``run.py`` compares the mean) and, for the control seeds,
the same of the tokens that the reference computed in a lower precision
(int8 and fp8, see ``kvbench.reference``) ranks first at the same
positions -- the reference put in the program's place.  One JSON object
per line.  The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--controls", nargs="*", default=["int8", "fp8"])
    args = ap.parse_args(argv)
    bench_run.setup_paths()
    bench_run.enable_cache()
    from kvbench import cell as cell_mod
    from kvbench import check, spec

    c = spec.load_cell(args.workload)
    n_pick = check.check_params(c)["requests"]
    ref = c.architecture.logits_at
    for seed in args.seeds:
        t0 = time.monotonic()
        runner = cell_mod.Runner(c, seed, args.seconds, False, t0)
        runner.serve_window()
        runner.free()
        picks = check.sample(runner.records, n_pick, seed)
        kv = check.kv_of(c.config)
        g = check.served_gaps(ref, runner.params, runner.arch, picks, kv)
        out = {"seed": seed, "program_mean": float(g.mean()),
               "program_max": float(g.max()), "tokens": int(g.size),
               "failed": sum(r.result.finish_reason == "error"
                             for r in runner.records),
               "finished": sum(r.result.finish_reason == "length"
                               for r in runner.records),
               "setup_s": runner.setup_s}
        if seed in args.control_seeds:
            for prec in args.controls:
                cg = check.control_gaps(ref, runner.params, runner.arch,
                                        picks, prec, kv)
                out[f"{prec}_mean"] = float(cg.mean())
                out[f"{prec}_max"] = float(cg.max())
        out["seconds"] = time.monotonic() - t0
        print(json.dumps(out), flush=True)
        del runner
    return 0


if __name__ == "__main__":
    sys.exit(main())
