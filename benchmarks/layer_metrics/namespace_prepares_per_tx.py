"""Validator (`peer/txvalidator.py`): validation-plugin prepares a
transaction: `namespace_prepares` on the window's `collect` spans (one
for every transaction that reached the policy stage's preparation and
one more for each further namespace it writes, upstream's
`plugindispatcher/dispatcher.go` wrNamespace loop) over the window's
transactions (`txs` on its `block` roots).  A little under 1 where every
transaction writes its own chaincode alone (the few a block refused
before their policies were prepared take none); what a share of
chaincode-to-chaincode transactions adds.  A program whose `collect`
spans lack `namespace_prepares` gives nothing to read."""

from benchlib import spans


def read(obs):
    prepares = [e["args"]["namespace_prepares"] for e in spans.named(obs, "collect")
                if "namespace_prepares" in e["args"]]
    txs = sum(e["args"].get("txs", 0) for e in spans.named(obs, "block"))
    if not prepares or not txs:
        return None
    return sum(prepares) / txs
