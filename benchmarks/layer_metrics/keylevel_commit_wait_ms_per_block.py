"""Validator (`peer/txvalidator.py`): the wall of the window's
`policy.await_commit` spans over its blocks: how long a block whose
key-level decisions depend on an earlier block stood, its lanes
verified, until that block's commit had landed.  A traced window
without one reads 0.0 (a time).  A program without the span is told
from that by its `policy` spans, which then lack `deferred` (both came
together, PR 40); it gives nothing to read."""

from benchlib import spans


def read(obs):
    if not obs["blocks"] or not any(
            "deferred" in e["args"] for e in spans.named(obs, "policy")):
        return None
    return spans.total_ms(spans.named(obs, "policy.await_commit")) / obs["blocks"]
