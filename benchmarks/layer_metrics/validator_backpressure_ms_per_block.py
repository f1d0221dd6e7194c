"""Validator (`peer/txvalidator.py` as `store_stream` drives it): how
long the main thread waited for the committer, per block: in
`commit_q.put` with the queue full (`commit.backpressure`) and at the
stream's tail for the last flags (`commit.await_flags`).  Read with
`commit_idle_ms_per_block`."""

from benchlib import spans


def read(obs):
    waits = spans.named(obs, "commit.backpressure", "commit.await_flags")
    if not obs["blocks"] or not waits:
        return None
    return spans.total_ms(waits) / obs["blocks"]
