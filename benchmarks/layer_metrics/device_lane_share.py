"""CSP provider: share of the lanes submitted at or above
`min_device_batch` whose mask the device sealed (`lane_tally()`):
what is left went to the host race, failover or the breaker."""


def read(obs):
    t = obs["lanes_sealed_by"]
    big = sum(v for k, v in t.items() if k != "small")
    return 100.0 * t.get("device", 0) / big if big else None
