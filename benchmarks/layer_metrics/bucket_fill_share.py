"""CSP provider: lanes submitted over lanes computed, summed over the
window's `tpu.enqueue` spans (`lanes` over `bucket`): the kernel runs
every lane of its bucket, so what is missing to 100% is device time
spent on padding."""

from benchlib import spans


def read(obs):
    chunks = spans.named(obs, "tpu.enqueue")
    computed = sum(e["args"].get("bucket", 0) for e in chunks)
    if not computed:
        return None
    return 100.0 * sum(e["args"].get("lanes", 0) for e in chunks) / computed
