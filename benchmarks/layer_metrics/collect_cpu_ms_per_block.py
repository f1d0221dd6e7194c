"""Validator (`peer/txvalidator.py`): thread CPU of the window's
`collect` spans per block (`tdur`, PR 37).  Beside
`collect_ms_per_block` it is the stage's work, and the difference is
what `collect` waited: for the interpreter's lock that the committer
and the flush workers share with it, or for a CPU.  Native code that
dropped the lock (the marshal, the chain signatures) counts as CPU.

Beside it `# oncpu_split_ms_per_block`: per thread and span name, for
every `cat="stage"` span and the flush's own (`tpu.*` below), wall, CPU
and off-CPU milliseconds a block.  The waiting spans (`commit.idle`,
`commit.backpressure`, `commit.await_flags`, `verify_wait`,
`tpu.device_wait`) are the control: their CPU must read near nothing."""

from benchlib import cpuspans, spans

FLUSH = ("tpu.dispatch", "tpu.marshal", "tpu.keytable", "tpu.enqueue",
         "tpu.small", "tpu.device_wait")


def read(obs):
    value = cpuspans.cpu_ms_per_block(obs, "collect")
    if value is None:
        return None
    n = obs["blocks"]
    split: dict = {}
    for e in cpuspans.timed(obs["spans"]):
        if e.get("ph") == "X" and (e.get("cat") == "stage" or e["name"] in FLUSH):
            row = split.setdefault(e["tid"], {}).setdefault(
                e["name"], {"wall": 0.0, "cpu": 0.0})
            row["wall"] += e["dur"] / 1e3 / n
            row["cpu"] += e["tdur"] / 1e3 / n
    for rows in split.values():
        for row in rows.values():
            row["off_cpu"] = row["wall"] - row["cpu"]
    spans.say("oncpu_split_ms_per_block", split)
    return value
