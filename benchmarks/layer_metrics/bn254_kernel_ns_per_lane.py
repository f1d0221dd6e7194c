"""Kernels (`csp/tpu/pallas_bn254.py`): device time of the operations
whose name holds `pallas_bn254` in the profiler's trace, over the
bucket lanes the window's `idemix.enqueue` spans launched (padding
included: the kernel runs every lane of its bucket).  Per lane, not
per pairing: the kernel named `pallas_bn254_pairing` computes G1
commitments, the pairings are the host's.  A traced window that
launched lanes and shows no such operation reads 0.0; one that
launched none gives nothing to read."""

from benchlib import spans
from kernel_counts import pallas_bn254 as counts


def device_seconds(obs) -> float:
    ops = (obs.get("device_trace") or {}).get("ops") or {}
    return sum(s for name, s in ops.items() if counts.PATTERN in name)


def bucket_lanes(obs) -> int:
    return sum(e["args"].get("bucket", 0) for e in spans.named(obs, "idemix.enqueue"))


def read(obs):
    lanes = bucket_lanes(obs)
    if not obs.get("device_trace") or not lanes:
        return None
    return 1e9 * device_seconds(obs) / lanes
