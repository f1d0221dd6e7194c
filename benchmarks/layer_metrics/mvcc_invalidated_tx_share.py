"""Committer and ledger (`ledger/txmgmt.py` `MVCCValidator`): the
transactions MVCC invalidated (`read_conflicts` + `phantom_conflicts` on
the window's `mvcc` spans) over all the window's transactions (`txs`
on its `block` roots), %.  Every one of them reached MVCC with its
signatures verified and its policy met, so where every transaction
carries the same lanes, as in `smallbank-100k-zipf` (4 each), it is
also the share of the device's lanes verified for nothing.
`committed_tx_per_s` counts those transactions too: this share beside
it says how many were kept.  A program whose `mvcc` spans lack the
counts gives nothing to read.

`# mvcc` prints beside it, a block: the transactions that came in
valid, and those invalidated, by kind."""

from benchlib import spans


def read(obs):
    counted = [e["args"] for e in spans.named(obs, "mvcc") if "read_conflicts" in e["args"]]
    txs = sum(e["args"].get("txs", 0) for e in spans.named(obs, "block"))
    if not counted or not txs:
        return None
    n = len(counted)
    reads = sum(a["read_conflicts"] for a in counted)
    phantoms = sum(a["phantom_conflicts"] for a in counted)
    spans.say("mvcc", {
        "blocks": n,
        "valid_in_per_block": sum(a["valid_in"] for a in counted) / n,
        "read_conflicts_per_block": reads / n,
        "phantom_conflicts_per_block": phantoms / n,
    })
    return 100.0 * (reads + phantoms) / txs
