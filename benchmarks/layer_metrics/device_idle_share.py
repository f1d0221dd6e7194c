"""Device: 1 - (union of device-operation intervals) / (traced window)."""


def read(obs):
    dt = obs.get("device_trace")
    if not dt or dt["busy_s"] <= 0 or dt["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - dt["busy_s"] / dt["window_s"])
