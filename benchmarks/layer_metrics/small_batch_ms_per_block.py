"""CSP provider, on the validator's thread: the wall of the window's
`tpu.small` spans (the host verification of a batch under
`min_device_batch`, inside `verify_batch_async`, which is inside
`collect`) over the window's blocks.  A traced window in which no batch
was small reads 0.0: a time, not a share of a peak.  A program that
does not have the span is told from that by its `block` root spans,
which then lack `txs` (both came together); it gives nothing to read.

`# block_classes` prints beside it where the window's time is by the
size of the block: blocks, transactions, and the summed wall of the
stage spans under each `block` root (`collect`; `verify_wait`; `policy`;
the commit stages, a group's `fsync` and `kv_txn` charged to the block
at its boundary, as the ledger records them), by class of
`block{txs}`: under 8, 8 to 199, 200 to 479, 480 and over (full: cut by
the count or the byte rule)."""

from benchlib import spans

CLASSES = (("under_8", 0, 8), ("8_to_199", 8, 200), ("200_to_479", 200, 480),
           ("480_and_over", 480, None))
COMMIT = ("mvcc", "block_append", "pvt", "state", "history", "fsync", "kv_txn")


def _class_of(txs: int) -> str:
    return next(name for name, _low, high in CLASSES if high is None or txs < high)


def read(obs):
    roots = {
        e["args"].get("span"): e["args"]["txs"]
        for e in spans.named(obs, "block") if "txs" in e["args"]
    }
    if not roots or not obs["blocks"]:
        return None
    table = {name: {"blocks": 0, "txs": 0, "collect_ms": 0.0, "verify_wait_ms": 0.0,
                    "policy_ms": 0.0, "commit_ms": 0.0} for name, _l, _h in CLASSES}
    for txs in roots.values():
        row = table[_class_of(txs)]
        row["blocks"] += 1
        row["txs"] += txs
    for e in spans.named(obs, "collect", "verify_wait", "policy", *COMMIT):
        txs = roots.get(e["args"].get("parent"))
        if txs is not None:
            column = "commit_ms" if e["name"] in COMMIT else e["name"] + "_ms"
            table[_class_of(txs)][column] += e["dur"] / 1e3
    spans.say("block_classes", table)
    return spans.total_ms(spans.named(obs, "tpu.small")) / obs["blocks"]
