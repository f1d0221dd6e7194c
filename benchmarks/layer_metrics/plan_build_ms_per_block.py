"""Validator (`peer/txvalidator.py`): the wall of building endorsement
plans, over blocks: `plan_build_ms` (the time inside
`BuiltinV20Plugin._plan`'s construction of an `EndorsementPlan`: the
endorsers deserialised, every policy prepared against sentinel digests)
summed over the window's `collect` spans and, for the decisions a block
deferred, its `policy` spans.  What a plan-cache miss costs before its
first `decide`; it lies inside `collect_ms_per_block`.  A program whose
spans lack `plan_build_ms` gives nothing to read."""

from benchlib import spans


def read(obs):
    timed = [e["args"]["plan_build_ms"] for e in spans.named(obs, "collect", "policy")
             if "plan_build_ms" in e["args"]]
    blocks = len(spans.named(obs, "collect"))
    if not timed or not blocks:
        return None
    return sum(timed) / blocks
