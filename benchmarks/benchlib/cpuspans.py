"""What the CPU-time readers share.  Since PR 37 an armed tracelens
span that begins and ends on one thread carries the trace-event
format's thread clock beside its wall: `tts` and `tdur`, microseconds
of `time.thread_time_ns()`; `dur - tdur` is what the thread spent off
the CPU (the interpreter's lock, a queue, the disk, the scheduler).  A
root that bounds a piece of work (a detached one, which has no thread
of its own, or a `cat="bench"` one) carries `args.proc_cpu_us`: the
whole process's CPU between its ends, every thread counted.  A program
whose spans lack the fields (the parent of
that PR) gives these readers nothing to read.
"""

from __future__ import annotations

from benchlib import spans


def timed(events) -> list:
    """The events that carry a thread duration."""
    return [e for e in events if "tdur" in e]


def cpu_ms_per_block(obs: dict, *names: str):
    """Thread CPU of the window's spans called one of `names`, in
    milliseconds a block; None where there is nothing to read."""
    events = timed(spans.named(obs, *names))
    if not obs.get("blocks") or not events:
        return None
    return sum(e["tdur"] for e in events) / 1e3 / obs["blocks"]
