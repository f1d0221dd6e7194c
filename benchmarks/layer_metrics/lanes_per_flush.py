"""CSP provider: median lanes of a device dispatch in the window (the
`lanes` of `tpu.dispatch`, counted at the provider's `_dispatch`)."""

import statistics


def read(obs):
    lanes = obs["flush_lanes"]
    return float(statistics.median(lanes)) if lanes else None
