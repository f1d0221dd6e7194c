"""Host runtime: the generation-2 `gc.pause` spans' time inside the
harness's timed spans (`bench.store_stream`, `bench.drain_run`), per
block.  In a catch-up cell it counts what `gc_pause_ms_per_block`
counts from the harness's own callback, from the program's span; in a
steady cell it is the only reading of the pauses.  A program without
the span (or a window without one collection) gives nothing to read."""

from benchlib import spans

TIMED = ("bench.store_stream", "bench.drain_run")


def read(obs):
    pauses = [
        e for e in spans.named(obs, "gc.pause")
        if e["args"].get("generation") == 2
    ]
    timed = [spans.interval(e) for e in spans.named(obs, *TIMED)]
    if not obs["blocks"] or not timed or not spans.named(obs, "gc.pause"):
        return None
    inside = sum(spans.overlap_us(*spans.interval(e), timed) for e in pauses)
    spans.say("gc_pause_spans", {
        "generation2": len(pauses), "all": len(spans.named(obs, "gc.pause")),
        "generation2_ms": spans.total_ms(pauses), "inside_timed_ms": inside / 1e3,
    })
    return inside / 1e3 / obs["blocks"]
