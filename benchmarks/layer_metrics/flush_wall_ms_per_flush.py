"""CSP provider: mean wall of a `tpu.flush` span, from the dispatch's
start to the mask sealed (by the device, the host race or a failure
path): host dispatch, transfer, the kernel at its bucket, the waiter's
copy back.  `verify_wait_ms_per_block` is what is left of it for a
block to wait; this is the whole of it."""

from benchlib import spans


def read(obs):
    flushes = spans.named(obs, "tpu.flush")
    if not flushes:
        return None
    return spans.total_ms(flushes) / len(flushes)
