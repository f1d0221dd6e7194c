"""Kernels (`csp/tpu/pallas_ec.py`): device time of the kernel's events
in the profiler's trace over the bucket lanes dispatched in the window
(padding included: the kernel computes every lane of its bucket)."""


def read(obs):
    dt = obs.get("device_trace")
    lanes = sum(b for bs in obs["flush_buckets"] for b in bs)
    if not dt or not dt["kernel_events"] or not lanes:
        return None
    return 1e9 * dt["kernel_s"] / lanes
