"""Find the knee of an open-loop cell: the highest arrival rate the server
sustains without a growing queue.

    python3 bench/sweep.py --workload yi_6b.chat --seconds 20 --rates 4 6 8 10

One process, one set-up; then, for each rate, the cell's traffic at that
rate for ``--seconds`` (after the mix's own ``warm_s``).  Per rate it
prints one JSON line: requests due and completed in the window, the time
to first token (from the due time) of the first and the second half of the
requests due in the window, and the queue left at the close.  A rate is
sustained while the second half's TTFT does not grow past the first's and
the queue stays short.  The benchmark's own runs never sweep; the cell's
rate is fixed in its traffic file from one such sweep.
"""

from __future__ import annotations

import argparse
import dataclasses
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
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench_run.setup_paths()
    bench_run.enable_cache()
    import numpy as np

    from kvbench import cell as cell_mod
    from kvbench import spec, stats
    from kvbench import traffic as traffic_mod

    c = spec.load_cell(args.workload)
    runner = cell_mod.Runner(c, args.seed, args.seconds, False,
                             time.monotonic())
    runner.setup()
    for rate in args.rates:
        mix = dict(c.traffic, rate_per_s=rate)
        runner.cell = dataclasses.replace(c, traffic=mix)
        runner.traffic = traffic_mod.generate(mix, runner.arch.vocab,
                                              args.seed, args.seconds)
        runner.records = []
        runner.run_open()
        pending = runner.server.pending
        runner.collect()
        due = [r for r in runner.records
               if runner.t_open <= r.due < runner.t_close]
        half = len(due) // 2

        def ttft(rs):
            v = [r.result.token_times[0] - r.due for r in rs
                 if r.result.token_times]
            return (stats.percentile(v, 50), stats.percentile(v, 95)) \
                if v else (None, None)

        done = sum(r.result.finish_reason == "length" for r in due)
        out = {"rate": rate, "due": len(due), "completed": done,
               "ttft_first_half_p50_p95": ttft(due[:half]),
               "ttft_second_half_p50_p95": ttft(due[half:]),
               "pending_at_close": pending,
               "itl_p95_ms": 1e3 * stats.percentile(np.concatenate([
                   stats.gaps_in(r.result.token_times, runner.t_open,
                                 runner.t_close) for r in runner.records]),
                   95),
               "out_tok_s": sum(int(np.sum(
                   (np.asarray(r.result.token_times) >= runner.t_open)
                   & (np.asarray(r.result.token_times) <= runner.t_close)))
                   for r in runner.records) / args.seconds}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
