"""Committer and ledger (`peer/committer.py` `store_stream`,
`ledger/kvledger.py`): mean `blocks` of the window's `fsync` spans: the
blocks a commit group held when it was flushed (one fsync of the block
files and one KV transaction a group).  A group closes at
`store_stream`'s depth or when the commit queue runs empty, so with
blocks of uneven size the group's size follows timing, and with it the
fsyncs and KV transactions a block."""

from benchlib import spans


def read(obs):
    groups = [e["args"]["blocks"] for e in spans.named(obs, "fsync")
              if isinstance(e["args"].get("blocks"), int)]
    if not groups:
        return None
    return sum(groups) / len(groups)
