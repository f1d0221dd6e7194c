"""Kernels (`csp/tpu/pallas_bn254.py`): the memory side of the BN254
kernel's roofline: the bytes a lane moves across HBM (inputs, the
issuer key's tables once a launch, Jacobian outputs; counted in
`benchmarks/kernel_counts/pallas_bn254.py`) over the kernel's device
time and the chip's published HBM bandwidth (`peaks.json`).  It is the
only side with a published peak; it reads far under 100% and says the
kernel is bound elsewhere (16-bit-limb arithmetic on the VPU, whose
count is printed beside it as `# bn254_kernel_counts`)."""

import json
import os

from benchlib import spans
from kernel_counts import pallas_bn254 as counts

_PEAKS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "peaks.json")


def read(obs):
    launches = spans.named(obs, "idemix.enqueue")
    lanes = sum(e["args"].get("bucket", 0) for e in launches)
    ops = (obs.get("device_trace") or {}).get("ops") or {}
    secs = sum(s for name, s in ops.items() if counts.PATTERN in name)
    if not lanes or secs <= 0:
        return None
    with open(_PEAKS, encoding="utf-8") as f:
        peak = json.load(f)["devices"]["TPU v5 lite"]["hbm_bytes_per_s"]
    moved = sum(
        e["args"]["bucket"] * counts.hbm_bytes_per_lane(e["args"]["bucket"])
        for e in launches if e["args"].get("bucket")
    )
    spans.say("bn254_kernel_counts", {
        "hbm_bytes_per_lane": moved / lanes,
        "limb_multiplies_per_lane": counts.limb_multiplies_per_lane(),
        "limb_multiplies_per_s": counts.limb_multiplies_per_lane() * lanes / secs,
        "device_s": secs, "bucket_lanes": lanes,
    })
    return 100.0 * moved / secs / peak
