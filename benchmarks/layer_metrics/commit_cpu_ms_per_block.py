"""Committer and ledger (`peer/committer.py`, `ledger/kvledger.py`):
thread CPU of the top-level commit stages per block (`tdur`, PR 37),
the stages `commit_ms_per_block` sums.  The difference to that wall is
what the committer waited: for the interpreter's lock the validator
holds, and inside `block_append`, `fsync` and `kv_txn` for the disk;
the two are not told apart."""

from benchlib import cpuspans

STAGES = ("mvcc", "block_append", "pvt", "state", "history", "fsync", "kv_txn")


def read(obs):
    return cpuspans.cpu_ms_per_block(obs, *STAGES)
