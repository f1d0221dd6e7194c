"""Validator (`peer/txvalidator.py`): of the window's endorsement-plan
lookups (`BuiltinV20Plugin._plan_pending`: one a transaction and written
namespace), the share that found no plan for its (policies, ordered
distinct endorsers) and built one, %: `plan_misses` over `plan_hits` +
`plan_misses`, summed over the window's `collect` spans and, for the
decisions a block deferred, its `policy` spans.  Near 0 where a
channel's transactions repeat a few endorser sets (one miss a pass in
`majority5-1000tx.catchup`); large where they outnumber the cache's cap
and every overflow empties it.  A program whose spans lack the counts
gives nothing to read.

`# plans` prints beside it, a block: lookups, misses, clears, the
namespaces a definition decided, and the wall of building plans."""

from benchlib import spans


def read(obs):
    counted = [e["args"] for e in spans.named(obs, "collect", "policy")
               if "plan_misses" in e["args"]]
    lookups = sum(a["plan_hits"] + a["plan_misses"] for a in counted)
    if not lookups:
        return None
    misses = sum(a["plan_misses"] for a in counted)
    collects = [e["args"] for e in spans.named(obs, "collect")]
    n = len(collects)
    spans.say("plans", {
        "blocks": n,
        "lookups_per_block": lookups / n,
        "misses_per_block": misses / n,
        "clears_per_block": sum(a["plan_clears"] for a in counted) / n,
        "build_ms_per_block": sum(a.get("plan_build_ms", 0.0) for a in counted) / n,
        "definitions_resolved_per_block": sum(
            a.get("definitions_resolved", 0) for a in collects) / n,
    })
    return 100.0 * misses / lookups
