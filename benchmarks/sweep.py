"""Find the highest block rate a steady cell sustains: one set-up, then
one open-loop window a rate.  The builder runs this once on the chip
and writes a rate below the answer into `benchmarks/cells/<cell>.json`
(four fifths, or lower where the median there is part queueing: PERF.md
section 4 has both readings); the benchmark's own runs never search
for a rate.

    python benchmarks/sweep.py --workload <name> --seed <n> --seconds <s> --rates 4,6,8

A rate is sustained when the backlog does not grow through the window:
the last third's median latency is no worse than the first third's by
more than a half, and the last block finishes within two intervals and
one block's service of its due time.
"""

import argparse
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def main(argv=None) -> int:
    from benchlib import engine, openloop, stats

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = engine.Cell(os.path.dirname(HERE), args.workload, args.seed, args.seconds, False)
    cell.setup()
    try:
        cell.warm_up()
        for rate in (float(r) for r in args.rates.split(",")):
            cell.rate = rate
            cell.latencies_s, cell.lateness_s, cell.run_sizes = [], [], []
            n = openloop.Schedule(rate, 0.0).count_within(args.seconds)
            cell._open_loop(n, record=True)
            ms = [1e3 * x for x in cell.latencies_s]
            third = max(1, len(ms) // 3)
            first, last = statistics.median(ms[:third]), statistics.median(ms[-third:])
            sizes = {}
            for s in cell.run_sizes:
                sizes[s] = sizes.get(s, 0) + 1
            print(json.dumps({
                "rate_blocks_per_s": rate, "blocks": len(ms),
                "p50_ms": stats.median(ms), "p95_ms": stats.percentile(ms, 95),
                "max_ms": max(ms), "first_third_p50_ms": first, "last_third_p50_ms": last,
                "last_block_ms": ms[-1], "blocks_per_store_call": sizes,
                "late_max_ms": 1e3 * max(cell.lateness_s),
                "sustained": last <= 1.5 * first and ms[-1] <= 2e3 / rate + stats.median(ms),
            }), flush=True)
    finally:
        cell.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
