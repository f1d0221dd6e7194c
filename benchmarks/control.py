"""Run a cell with one of its guarantees broken inside the program, to
show that the comparison which decides `correct` fails it.

    python benchmarks/control.py --workload <name> --seeds <n,n,n> --seconds <s> --control <name>

A control patches the program's own class where the program decides
the thing, before the cell is built, so the harness and its timed path
are the ones every run uses.  The benchmark's own runs never come
here; the builder runs this on the chip at the cell's own size, on
three seeds or more a cell (one process, one run a seed), and PERF.md
records what it read.  Exits 0 when every run's `correct` came out
false, 1 when a broken path passed.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def accept_all_signatures():
    """A provider that checks no signature: every mask is all true.
    Breaks "every creator signature and every endorsement signature is
    checked"; the planted corruptions then pass, and their writes land
    in the state."""
    from fabric_tpu.csp.tpu.provider import TPUCSP

    TPUCSP.verify_batch_async = lambda self, items: (lambda: [True] * len(items))


def skip_mvcc():
    """The ledger's MVCC check forgets the block's own earlier writes:
    a transaction that read a key before an earlier transaction of the
    block wrote it is committed.  Breaks "MVCC"; the flags and the
    state (the later writer's value and version) both differ."""
    from fabric_tpu.ledger.txmgmt import MVCCValidator

    inner = MVCCValidator._committed_version
    MVCCValidator._committed_version = (
        lambda self, ns, key, updates, cache=None: inner(self, ns, key, {}, cache)
    )


CONTROLS = {"accept_all_signatures": accept_all_signatures, "skip_mvcc": skip_mvcc}


def main(argv=None) -> int:
    from benchlib import engine

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", required=True, choices=sorted(CONTROLS))
    args = ap.parse_args(argv)
    CONTROLS[args.control]()
    passed = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        line = engine.run_cell(ROOT, args.workload, seed, args.seconds, False)
        print(json.dumps({"control": args.control, "seed": seed, "correct": line["correct"],
                          "attempted": line["attempted"], "failed": line["failed"]}), flush=True)
        passed += line["correct"] is not False
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
