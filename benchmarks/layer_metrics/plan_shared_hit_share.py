"""Validator (`peer/txvalidator.py`): of the window's endorsement-plan
lookups (`plan_hits` + `plan_misses`, as `plan_miss_share.py` sums them:
over the window's `collect` spans and, for the decisions a block
deferred, its `policy` spans), the share that found a plan which OTHER
identities had built (`plan_shared_hits`), %.  A plan is kept by what
its policies can observe of each endorser (the principals it satisfies),
so the second peer of an organisation finds the plan the first one
built: these are the hits a key of identities would have missed.  The
engagement reading of that sharing: large where a channel's clients draw
their endorsers among several peers an organisation, 0 where one peer an
organisation endorses everything or a principal (an OU, an identity)
tells an organisation's peers apart.  A program whose spans lack the
count (before PR 53) gives nothing to read.

`# plan_sharing` prints beside it, a block."""

from benchlib import spans


def read(obs):
    counted = [e["args"] for e in spans.named(obs, "collect", "policy")
               if "plan_shared_hits" in e["args"]]
    lookups = sum(a["plan_hits"] + a["plan_misses"] for a in counted)
    if not lookups:
        return None
    shared = sum(a["plan_shared_hits"] for a in counted)
    n = len(spans.named(obs, "collect"))
    spans.say("plan_sharing", {
        "blocks": n,
        "lookups_per_block": lookups / n,
        "shared_hits_per_block": shared / n,
        "own_hits_per_block": (sum(a["plan_hits"] for a in counted) - shared) / n,
        "misses_per_block": sum(a["plan_misses"] for a in counted) / n,
    })
    return 100.0 * shared / lookups
