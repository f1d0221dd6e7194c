"""Validator (`peer/txvalidator.py`): the times a block the
endorsement-plan cache ran over its cap and was emptied whole
(`plan_clears` summed over the window's `collect` and `policy` spans,
over blocks; `validator_plan_cache_total{outcome="cleared"}` on
/metrics).  0 wherever a channel's (policies, ordered endorsers) sets
fit the cache; every clear rebuilds what the next transactions ask for.
A program whose spans lack `plan_clears` gives nothing to read."""

from benchlib import spans


def read(obs):
    clears = [e["args"]["plan_clears"] for e in spans.named(obs, "collect", "policy")
              if "plan_clears" in e["args"]]
    blocks = len(spans.named(obs, "collect"))
    if not clears or not blocks:
        return None
    return sum(clears) / blocks
