"""CSP provider: share of the window's signature lanes that came in
batches under `min_device_batch` and were verified on the host, on the
validator's thread (`lane_tally()["small"]` over all the lanes sealed
in the window).  `small_batch_ms_per_block` says what they cost."""


def read(obs):
    t = obs["lanes_sealed_by"]
    lanes = sum(t.values())
    return 100.0 * t.get("small", 0) / lanes if lanes else None
