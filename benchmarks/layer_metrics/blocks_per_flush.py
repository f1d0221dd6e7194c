"""CSP provider (`csp/tpu/provider.py`): mean `segments` of the
window's `tpu.flush` spans: the `verify_batch_async` batches a flush
took in, a block each under `store_stream` (a block whose lanes are
under `min_device_batch` is verified on the host and is in no flush).
`store_stream`'s depth would put three in every flush; what is missing
to three went to the host as small batches or stood alone at a pass's
end.  `# flush_makeup` prints the flushes by their buckets with their
mean lanes and segments.  A program whose `tpu.flush` does not say
`segments` gives nothing to read."""

from benchlib import spans


def read(obs):
    flushes = [e for e in spans.named(obs, "tpu.flush") if "segments" in e["args"]]
    if not flushes:
        return None
    by_bucket: dict = {}
    for e in flushes:
        key = "+".join(str(b) for b in e["args"].get("buckets") or ("none",))
        by_bucket.setdefault(key, []).append(e["args"])
    spans.say("flush_makeup", {
        bucket: {
            "flushes": len(rows),
            "mean_lanes": sum(a.get("lanes", 0) for a in rows) / len(rows),
            "mean_segments": sum(a["segments"] for a in rows) / len(rows),
        }
        for bucket, rows in sorted(by_bucket.items(), key=lambda kv: (len(kv[0]), kv[0]))
    })
    return sum(e["args"]["segments"] for e in flushes) / len(flushes)
