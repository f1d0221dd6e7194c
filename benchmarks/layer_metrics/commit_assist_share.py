"""Committer and ledger (`peer/committer.py`, `ledger/kvledger.py`):
share of the window's committed blocks whose `block_append` span says
`assisted`: the block came to `KVLedger.commit` with the validator's
txids and envelope bytes (a `CommitAssist`), so the commit decoded no
envelope again.  `store_stream` has always handed one over; a lone
block through `store_block` does since PR 35.  The genesis block of
each fresh ledger (block 0, under `bench.make_ledger`) is committed by
the ledger provider with no validator before it and is not counted.  A
program whose `block_append` does not say `assisted` gives nothing to
read."""

from benchlib import spans


def read(obs):
    appends = [e["args"]["assisted"] for e in spans.named(obs, "block_append")
               if "assisted" in e["args"] and e["args"].get("block")]
    if not appends:
        return None
    return 100.0 * sum(1 for a in appends if a) / len(appends)
