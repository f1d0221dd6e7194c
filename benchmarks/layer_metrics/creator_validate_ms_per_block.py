"""Validator (`peer/txvalidator.py`): the wall a block's `collect`
spent deserialising and validating creators it did not remember
(`creator_ms` on the window's `collect` spans), over blocks.  The
hits of the block's memo are `# creators` beside it."""

from benchlib import spans


def read(obs):
    collects = [e for e in spans.named(obs, "collect") if "creator_ms" in e["args"]]
    if not collects:
        return None
    n = len(collects)
    spans.say("creators", {
        "distinct_per_block": sum(e["args"]["creators"] for e in collects) / n,
        "validations_per_block": sum(e["args"]["creator_validations"] for e in collects) / n,
        "blocks": n,
    })
    return sum(e["args"]["creator_ms"] for e in collects) / n
