"""Committer and ledger (`peer/committer.py`, `ledger/kvledger.py`):
the top-level commit stages per block.  `mvcc_*` and `kv_*` are splits
of `mvcc` and `kv_txn` and are not added again."""

STAGES = ("mvcc", "block_append", "pvt", "state", "history", "fsync", "kv_txn")


def read(obs):
    if not obs["blocks"]:
        return None
    s = obs["commit_stage_seconds"]
    return 1e3 * sum(s.get(k, 0.0) for k in STAGES) / obs["blocks"]
