"""Validator (`peer/txvalidator.py`): of the committed state-metadata
lookups its plugins asked in the window (`keylevel_reads` on the
`collect` spans, `deferred_reads` on the `policy` spans), the share the
block's bulk read of its stage had fetched already, %: those that did
not go to the ledger one by one (`keylevel_point_reads`,
`deferred_point_reads`).  The engagement reading of the one-read-a-stage
path: near 100 where it runs, and the point reads say what it missed (a
lane parsed inline, the Python collector, a commit that landed between
a block's check of the state and its first lookup).  A program whose
spans lack the point-read counts (before PR 41), and a window in which
nothing was asked, give nothing to read.

`# keylevel_bulk` prints beside it, a block: the pairs the bulk reads
of both stages fetched, and the lookups that missed them."""

from benchlib import spans


def read(obs):
    collects = [e["args"] for e in spans.named(obs, "collect")
                if "keylevel_point_reads" in e["args"]]
    if not collects:
        return None
    policies = [e["args"] for e in spans.named(obs, "policy")
                if "deferred_point_reads" in e["args"]]
    asked = (sum(a["keylevel_reads"] for a in collects)
             + sum(a["deferred_reads"] for a in policies))
    if not asked:
        return None
    missed = (sum(a["keylevel_point_reads"] for a in collects)
              + sum(a["deferred_point_reads"] for a in policies))
    n = len(collects)
    spans.say("keylevel_bulk", {
        "blocks": n,
        "bulk_keys_in_collect_per_block": sum(a["keylevel_bulk_keys"] for a in collects) / n,
        "bulk_keys_deferred_per_block": sum(a["deferred_bulk_keys"] for a in policies) / n,
        "point_reads_in_collect_per_block": sum(a["keylevel_point_reads"] for a in collects) / n,
        "point_reads_deferred_per_block": sum(a["deferred_point_reads"] for a in policies) / n,
    })
    return 100.0 * (asked - missed) / asked
