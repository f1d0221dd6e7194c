"""CSP provider (`csp/tpu/provider.py`): of the lanes the window
enqueued (`tpu.enqueue{lanes, kernel}`), the share that went to the
key-table kernel, whose per-lane key is an index into a table resident
on the device; the rest carried 64 bytes of key a lane to the
per-lane-key kernel.  `# enqueued_lanes_by_kernel` prints the split."""

from benchlib import spans

TABLE_KERNEL = "pallas_ec_p256_verify_ktab"


def read(obs):
    by_kernel: dict = {}
    for e in spans.named(obs, "tpu.enqueue"):
        kernel = e["args"].get("kernel")
        if kernel is not None:
            by_kernel[kernel] = by_kernel.get(kernel, 0) + e["args"]["lanes"]
    total = sum(by_kernel.values())
    if not total:
        return None
    spans.say("enqueued_lanes_by_kernel", by_kernel)
    return 100.0 * by_kernel.get(TABLE_KERNEL, 0) / total
