"""Validator (`peer/txvalidator.py`): the wall of the committed
state-metadata lookups that find a written key's VALIDATION_PARAMETER,
over blocks (since PR 41 the bulk read a stage plus any point reads, as
`keylevel_bulk_hit_share` counts them): `keylevel_ms` summed over the
window's `collect` spans
(the transactions decided while their block is collected) and
`deferred_ms` over its `policy` spans (those decided once an earlier
block's commit had landed).  A program whose `collect` spans lack
`keylevel_ms` gives nothing to read.

`# keylevel` prints beside it, a block: the lookups of both stages, the
distinct parameters a collect met, and the endorsement-plan cache's
hits, misses (plans built) and clears of both stages."""

from benchlib import spans


def read(obs):
    collects = [e["args"] for e in spans.named(obs, "collect") if "keylevel_ms" in e["args"]]
    if not collects:
        return None
    n = len(collects)
    policies = [e["args"] for e in spans.named(obs, "policy")]
    spans.say("keylevel", {
        "blocks": n,
        "lookups_in_collect_per_block": sum(a["keylevel_reads"] for a in collects) / n,
        "lookups_deferred_per_block": sum(a.get("deferred_reads", 0) for a in policies) / n,
        "distinct_parameters_per_block": sum(a["keylevel_policies"] for a in collects) / n,
        "plan_hits_per_block": sum(a.get("plan_hits", 0) for a in collects + policies) / n,
        "plan_misses_per_block": sum(a.get("plan_misses", 0) for a in collects + policies) / n,
        "plan_clears_per_block": sum(a.get("plan_clears", 0) for a in collects + policies) / n,
    })
    return (sum(a["keylevel_ms"] for a in collects)
            + sum(a.get("deferred_ms", 0.0) for a in policies)) / n
