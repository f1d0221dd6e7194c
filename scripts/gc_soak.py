"""Does a peer's memory grow with every block?  Drives passes of one
of the benchmark's deployments through `Committer.store_stream`, a
fresh on-disk ledger a pass, as `benchmarks/` does, and prints the
resident size, the collector's counters and the per-tx objects still
tracked every few passes (PERF.md, Findings PR 26).

    python scripts/gc_soak.py [--config majority5-1000tx] [--passes 60]
                              [--every 10] [--blocks 8] [--sw]

One JSON line every `--every` passes.  The provider is the one
`sampleconfig/core.yaml` selects (the TPU; `--sw` takes the host
provider, for a rehearsal where there is no chip).  It reads sizes and
counts, never a rate.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "benchmarks")):
    if p not in sys.path:
        sys.path.insert(0, p)

PER_TX = ("_PlanPending", "_TxWork", "RwsetFootprint")


def _resident_bytes() -> int:
    with open("/proc/self/statm", "r", encoding="ascii") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", default="majority5-1000tx")
    ap.add_argument("--passes", type=int, default=60)
    ap.add_argument("--every", type=int, default=10)
    ap.add_argument("--blocks", type=int, default=8)
    ap.add_argument("--block-txs", type=int, default=0)
    ap.add_argument("--sw", action="store_true")
    args = ap.parse_args(argv)

    from benchlib import generator

    from fabric_tpu.common.channelconfig import bundle_from_genesis
    from fabric_tpu.ledger import LedgerProvider
    from fabric_tpu.peer.committer import Committer
    from fabric_tpu.peer.txvalidator import TxValidator
    from fabric_tpu.protos.common import common_pb2

    with open(os.path.join(ROOT, "benchmarks", "configs", f"{args.config}.json")) as f:
        cfg = json.load(f)
    deployment = dict(cfg["deployment"])
    if args.block_txs:
        deployment["block_txs"] = args.block_txs
    world = generator.build_world(26, deployment, cfg["planted"], args.blocks)
    if args.sw:
        from fabric_tpu.csp import SWCSP

        csp = SWCSP()
    else:
        from fabric_tpu.common.config import Config
        from fabric_tpu.csp import csp_from_config

        csp = csp_from_config(Config.load(
            "core", "CORE", path=os.path.join(ROOT, "sampleconfig", "core.yaml")
        ))
    bundle = bundle_from_genesis(world.genesis, csp)
    workdir = tempfile.mkdtemp(prefix="tpu-fabric-soak-")
    try:
        for n in range(1, args.passes + 1):
            path = os.path.join(workdir, f"ledger{n}")
            provider = LedgerProvider(path)
            ledger = provider.create(world.genesis)
            committer = Committer(
                TxValidator(generator.CHANNEL, ledger, bundle, csp), ledger
            )
            blocks = [common_pb2.Block.FromString(b) for b in world.blocks]
            for _flags in committer.store_stream(iter(blocks)):
                pass
            provider.close()
            shutil.rmtree(path, ignore_errors=True)
            del provider, ledger, committer, blocks
            if n % args.every == 0 or n == args.passes:
                tracked: dict = {}
                for o in gc.get_objects():
                    name = type(o).__name__
                    if name in PER_TX:
                        tracked[name] = tracked.get(name, 0) + 1
                print(json.dumps({
                    "passes": n,
                    "txs": n * args.blocks * int(deployment["block_txs"]),
                    "resident_bytes": _resident_bytes(),
                    "collections": [s["collections"] for s in gc.get_stats()],
                    "frozen_objects": gc.get_freeze_count(),
                    "threshold": gc.get_threshold(),
                    "per_tx_objects_tracked": tracked,
                }), flush=True)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
        from fabric_tpu import node

        node.quiesce(csp)
    return 0


if __name__ == "__main__":
    sys.exit(main())
