"""Validator (`peer/txvalidator.py`): the wall of the window's
`verify_wait` spans over the window's `tpu.flush` count: how much of a
flush the validator stands waiting for with nothing to overlap it.
`store_stream` flushes when it finishes the oldest of its three blocks
in flight, a moment after the flush was enqueued, so the first block of
every three waits for most of the flush's wall
(`flush_wall_ms_per_flush`) and the two behind it for little; with
1000-tx blocks that wait is a fifth of a block's `collect`, with 80-tx
blocks it is several blocks' worth."""

from benchlib import spans


def read(obs):
    flushes = spans.named(obs, "tpu.flush")
    waits = spans.named(obs, "verify_wait")
    if not flushes or not waits:
        return None
    return spans.total_ms(waits) / len(flushes)
