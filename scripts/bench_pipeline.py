"""Block-validation pipeline benchmark (BASELINE.json configs #3/#4):
VALIDATED tx/s (no commit in the timed loop — bench.py owns the
committed-tx/s headline via Committer.store_stream) and per-block
validate latency for 1000-tx blocks at
1-of-1 and 3-of-5 endorsement, TPU batched verify vs host sw verify.

Prints one JSON line per configuration (bench.py stays the single-line
headline; this is the measurement matrix).
"""

from __future__ import annotations

import json
import os
import time



def _build_world(n_orgs: int):
    import sys

    sys.path.insert(
        0,
        os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tests"),
    )
    from orgfix import make_org

    from fabric_tpu.common import configtx_builder as ctx
    from fabric_tpu.msp import msp_config_from_ca

    orgs = [make_org(f"Org{i+1}MSP") for i in range(n_orgs)]
    oorg = make_org("OrdererMSP")
    app = ctx.application_group(
        {
            f"Org{i+1}": ctx.org_group(
                o.mspid, msp_config_from_ca(o.ca, o.mspid)
            )
            for i, o in enumerate(orgs)
        }
    )
    ordg = ctx.orderer_group(
        {"O": ctx.org_group("OrdererMSP", msp_config_from_ca(oorg.ca, "OrdererMSP"))},
        consensus_type="solo",
    )
    genesis = ctx.genesis_block("benchch", ctx.channel_group(app, ordg))
    return orgs, genesis


def _make_blocks(orgs, genesis, csp, n_txs: int, endorsers: int,
                 n_blocks: int = 1, on_endorsed=None):
    """`n_blocks` blocks of distinct endorsed txs (each endorsed by
    `endorsers` orgs).  `on_endorsed(bno, i, responses)`, when given,
    sees each transaction's proposal responses before the client
    assembles and signs the envelope: chip_smoke.py corrupts an
    endorsement signature there (done afterwards, the edit would also
    break the creator's signature over the payload)."""
    from fabric_tpu import protoutil
    from fabric_tpu.common.channelconfig import bundle_from_genesis
    from fabric_tpu.ledger import LedgerProvider
    from fabric_tpu.peer.endorser import Endorser
    from fabric_tpu.protos.common import common_pb2
    from fabric_tpu.protos.peer import proposal_pb2

    provider = LedgerProvider(None)
    ledger = provider.create(genesis)
    bundle = bundle_from_genesis(genesis, csp)

    def cc(sim, args):
        sim.set_state("benchcc", args[0].decode(), args[1])
        return 200, "", b""

    ends = [
        Endorser("benchch", ledger, bundle,
                 o.signer(f"peer{i}", role_ou="peer"), {"benchcc": cc}, csp)
        for i, o in enumerate(orgs[:endorsers])
    ]
    client = orgs[0].signer("client", role_ou="client")
    blocks = []
    for bno in range(n_blocks):
        envs = []
        for i in range(n_txs):
            prop, _ = protoutil.create_chaincode_proposal(
                client.serialize(), "benchch", "benchcc",
                [b"k%d-%d" % (bno, i), b"v%d" % i],
            )
            signed = proposal_pb2.SignedProposal(
                proposal_bytes=prop.SerializeToString(),
                signature=client.sign(prop.SerializeToString()),
            )
            resps = [e.process_proposal(signed) for e in ends]
            if on_endorsed is not None:
                on_endorsed(bno, i, resps)
            envs.append(protoutil.create_signed_tx(prop, client, resps))
        blk = common_pb2.Block()
        blk.header.number = 1 + bno
        blk.data.data.extend(e.SerializeToString() for e in envs)
        while len(blk.metadata.metadata) < 3:
            blk.metadata.metadata.append(b"")
        blocks.append(blk)
    return ledger, bundle, blocks


def bench_config(name: str, n_orgs: int, endorsers: int, n_txs: int,
                 repeats: int = 3):
    from fabric_tpu.csp import SWCSP
    from fabric_tpu.csp.tpu.provider import TPUCSP
    from fabric_tpu.peer.txvalidator import TxValidator
    from fabric_tpu.protos.common import common_pb2

    sw = SWCSP()
    n_blocks = 4
    orgs, genesis = _build_world(n_orgs)
    ledger, bundle, blocks = _make_blocks(
        orgs, genesis, sw, n_txs, endorsers, n_blocks
    )

    def copies(k):
        out = []
        for j in range(k):
            b = common_pb2.Block()
            b.CopyFrom(blocks[j % n_blocks])
            out.append(b)
        return out

    out = {"config": name, "txs": n_txs, "endorsements_per_tx": endorsers}
    for label, csp in (("sw", sw), ("tpu", TPUCSP(min_device_batch=1))):
        validator = TxValidator("benchch", ledger, bundle, csp)
        best = float("inf")
        for _ in range(repeats):
            (b,) = copies(1)
            t0 = time.perf_counter()
            flags = validator.validate(b)
            best = min(best, time.perf_counter() - t0)
            assert all(f == 0 for f in flags), "txs must validate"
        out[f"{label}_block_validate_s"] = round(best, 4)
        out[f"{label}_validated_tx_s"] = round(n_txs / best, 1)
        # steady-state throughput: a stream of distinct blocks through
        # the pipelined validator (collect(k+1) overlaps device
        # verify(k)); fresh validator per run so the pipeline's
        # duplicate-txid window starts empty.
        stream_best = float("inf")
        for _ in range(repeats):
            v2 = TxValidator("benchch", ledger, bundle, csp)
            bs = copies(n_blocks)
            t0 = time.perf_counter()
            for flags in v2.validate_pipeline(iter(bs), depth=3):
                assert all(f == 0 for f in flags)
            stream_best = min(stream_best, time.perf_counter() - t0)
        out[f"{label}_pipelined_tx_s"] = round(n_blocks * n_txs / stream_best, 1)
    out["speedup"] = round(
        out["tpu_validated_tx_s"] / out["sw_validated_tx_s"], 2
    )
    out["pipelined_speedup"] = round(
        out["tpu_pipelined_tx_s"] / out["sw_pipelined_tx_s"], 2
    )
    print(json.dumps(out))


def main():
    bench_config("1000tx_1of1", 1, 1, 1000)
    bench_config("1000tx_3of5", 5, 3, 1000)


if __name__ == "__main__":
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    main()
