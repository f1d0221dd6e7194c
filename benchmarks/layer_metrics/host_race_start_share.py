"""CSP provider: share of the window's `tpu.collect` spans whose
deadline expired, so that the consumer started verifying on the host
beside the chip (`raced`); how many of those races the host won is
printed beside it.  `device_lane_share` says what the race sealed;
this says how often it ran at all."""

from benchlib import spans


def read(obs):
    collects = spans.named(obs, "tpu.collect")
    if not collects or not any("raced" in e["args"] for e in collects):
        return None
    raced = [e for e in collects if e["args"].get("raced")]
    spans.say("host_races", {
        "collects": len(collects), "raced": len(raced),
        "race_won": sum(1 for e in raced if e["args"].get("race_won")),
        "sole": sum(1 for e in collects if e["args"].get("sole")),
    })
    return 100.0 * len(raced) / len(collects)
