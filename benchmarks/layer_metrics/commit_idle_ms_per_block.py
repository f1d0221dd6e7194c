"""Committer and ledger (`peer/committer.py`): how long the committer
thread of `store_stream` waited for the validator's next block
(`commit.idle`), per block.  Read with
`validator_backpressure_ms_per_block`: the side that waits less sets
the stream's pace."""

from benchlib import spans


def read(obs):
    idle = spans.named(obs, "commit.idle")
    if not obs["blocks"] or not idle:
        return None
    return spans.total_ms(idle) / obs["blocks"]
