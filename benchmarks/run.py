"""Run one cell of the benchmark once.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Earlier lines (`# tag: {...}`) carry what is worth reading; the last
line of standard output is the contract's one JSON object.  Exits
non-zero and prints no result where JAX finds no TPU, the native
library is missing, or the program is not in the checkout.
"""

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchlib import engine
    from benchlib.manifest import ManifestError

    try:
        line = engine.run_cell(
            ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
            t_start=_T0,
        )
    except (engine.Refused, ManifestError) as e:
        print(f"benchmarks/run.py: {e}; nothing measured", file=sys.stderr)
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
