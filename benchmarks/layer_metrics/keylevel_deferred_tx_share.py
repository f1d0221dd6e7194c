"""Validator (`peer/txvalidator.py`): the transactions whose
endorsement policies were resolved only once an earlier block's commit
had landed (`deferred` on the window's `policy` spans) over all the
window's transactions (`txs` on its `block` roots), %: the share of
the traffic the dependency between neighbouring blocks touches, and the
reading that the mechanism was engaged.  A program whose `policy` spans
lack `deferred` gives nothing to read."""

from benchlib import spans


def read(obs):
    deferred = [e["args"]["deferred"] for e in spans.named(obs, "policy")
                if "deferred" in e["args"]]
    txs = sum(e["args"].get("txs", 0) for e in spans.named(obs, "block"))
    if not deferred or not txs:
        return None
    return 100.0 * sum(deferred) / txs
