"""Validator (`peer/txvalidator.py`): of the window's transactions, the
share whose creator the block's memo did not hold, so that it was
deserialised and validated afresh (`creator_validations` on the
window's `collect` spans over `block_txs` a block)."""

from benchlib import spans


def read(obs):
    collects = [e for e in spans.named(obs, "collect") if "creator_validations" in e["args"]]
    if not collects or not obs["block_txs"]:
        return None
    fresh = sum(e["args"]["creator_validations"] for e in collects)
    return 100.0 * fresh / (len(collects) * obs["block_txs"])
