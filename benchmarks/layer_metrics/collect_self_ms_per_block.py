"""Validator (`peer/txvalidator.py`): the `collect` spans' own time per
block: what is left of them after the parts the provider's
`tpu.dispatch` covered on the same thread (a flush that filled inside
`verify_batch_async`: marshal, key table, padding, the kernel enqueue)
and the parts a `gc.pause` covered on any thread (a collection stops
every thread).  The covered parts are printed beside it: the three add
up to the `collect` spans' total, which is `collect_ms_per_block`."""

from benchlib import spans


def split(obs):
    """(self, under tpu.dispatch, under gc.pause only) in ms, summed."""
    pauses = [spans.interval(e) for e in spans.named(obs, "gc.pause")]
    dispatch_by_thread: dict = {}
    for e in spans.named(obs, "tpu.dispatch"):
        dispatch_by_thread.setdefault(e["tid"], []).append(spans.interval(e))
    own = in_dispatch = in_pause = 0.0
    for c in spans.named(obs, "collect"):
        a, b = spans.interval(c)
        mine = dispatch_by_thread.get(c["tid"], [])
        d = spans.overlap_us(a, b, mine)
        both = spans.overlap_us(a, b, mine + pauses)
        own += (b - a) - both
        in_dispatch += d
        in_pause += both - d
    return own / 1e3, in_dispatch / 1e3, in_pause / 1e3


def read(obs):
    if not obs["blocks"] or not spans.named(obs, "collect"):
        return None
    own, in_dispatch, in_pause = split(obs)
    n = obs["blocks"]
    spans.say("collect_split_ms_per_block", {
        "self": own / n, "under_tpu_dispatch": in_dispatch / n,
        "under_gc_pause": in_pause / n,
        "collect_spans": (own + in_dispatch + in_pause) / n,
    })
    return own / n
