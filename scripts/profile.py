#!/usr/bin/env python
"""profscope CLI — profile the canned commit workload and print ONE
bench-style JSON line.

Drives the faultfuzz commit workload (endorsed blocks -> validate ->
commit over a fresh on-disk ledger) under an armed tracelens recorder
and the profscope sampler, then prints one line of JSON:
the top hot frames (collapsed-stack leaf attribution), per-role lock
wait totals, per-span CPU attribution (self_cpu_ms), workpool
queue-wait vs run-time, and the speedscope artifact path.

Usage:
  python scripts/profile.py [--blocks B] [--hz N] [--out PATH]

The artifact loads directly in https://www.speedscope.app (or feeds
any collapsed-stack flamegraph tool via otherData.collapsed).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _commit_workload(root: str, blocks: int) -> int:
    """The tracing-parity commit workload: canned per-block writes
    through endorse -> commit on a fresh ledger; returns final height."""
    from fabric_tpu.devtools import faultfuzz
    from fabric_tpu.ledger import LedgerProvider

    provider = LedgerProvider(root)
    ledger = provider.open(faultfuzz.CHANNEL)
    writes = faultfuzz.workload_writes(blocks)
    try:
        for n in range(blocks + 2):
            ledger.commit(
                faultfuzz._endorsed_block(ledger, n, writes[n])
            )
        return ledger.height
    finally:
        provider.close()


def _top_frames(collapsed: list[str], limit: int) -> list[dict]:
    """Leaf-frame attribution over the collapsed-stack aggregate:
    'a;b;c N' charges N samples to leaf frame c."""
    totals: dict[str, int] = {}
    for row in collapsed:
        stack, _, count = row.rpartition(" ")
        leaf = stack.rsplit(";", 1)[-1]
        totals[leaf] = totals.get(leaf, 0) + int(count)
    ranked = sorted(totals.items(), key=lambda kv: (-kv[1], kv[0]))
    return [
        {"frame": frame, "samples": n} for frame, n in ranked[:limit]
    ]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--blocks", type=int, default=6,
                    help="canned workload blocks (default 6)")
    ap.add_argument("--hz", type=float, default=200.0,
                    help="sampling rate (default 200 Hz)")
    ap.add_argument("--out", default=".faultfuzz/profscope.json",
                    metavar="PATH",
                    help="speedscope artifact path "
                         "(default .faultfuzz/profscope.json)")
    ap.add_argument("--top", type=int, default=8,
                    help="hot frames in the JSON line (default 8)")
    args = ap.parse_args()

    from fabric_tpu.common import profile, tracing, workpool

    t0 = time.perf_counter()
    root = tempfile.mkdtemp(prefix="profscope-")
    try:
        # tracing first: the sampler attributes CPU to live spans
        with tracing.scope():
            with profile.scope(interval_s=1.0 / max(args.hz, 1.0)):
                height = _commit_workload(root, args.blocks)
                doc = profile.export("profscope.cli")
        path = profile.dump_to(args.out, doc)
    finally:
        shutil.rmtree(root, ignore_errors=True)
        workpool.shutdown()

    od = doc["otherData"]
    line = {
        "experiment": "profscope",
        "blocks": args.blocks,
        "final_height": height,
        "hz": args.hz,
        "samples": od["samples"],
        "duration_s": od["duration_s"],
        "top_frames": _top_frames(od["collapsed"], args.top),
        "lock_wait_ms": {
            role: round(rec["wait_s"] * 1e3, 3)
            for role, rec in sorted(od["locks"].items())
        },
        "self_cpu_ms": od["self_cpu_ms"],
        "workpool": od["workpool"],
        "artifact": path,
        "seconds": round(time.perf_counter() - t0, 4),
    }
    print(json.dumps(line, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
