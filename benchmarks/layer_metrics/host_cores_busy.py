"""Host runtime (CPython): cores the process used while it had a block
in hand: the process's CPU (`proc_cpu_us`, PR 37: every thread counted,
XLA's and the native calls' too) over the wall of the window's
`bench.store_stream` / `bench.drain_run` spans.  Near 1.0 with two
threads each a third off the CPU is the interpreter's lock; well under
1.0 is a machine that does not schedule the process.  `# host_cpu`
prints the sums, the timed spans' own thread's CPU among them.

In an open-loop cell `# stalled_blocks` stands beside it: at most eight
lone-block `bench.drain_run` spans (one block's validation and commit)
whose wall stands 60 ms or more over the window's median, each with its
block, the process's CPU over it, its own thread's, and the stage span
that was open longest in it, wall and thread CPU.  Process CPU near
nothing over a stall: the process was not scheduled, or every thread
sat in the kernel.  A stall's worth of process CPU with the stage's own
flat: another thread held the lock."""

import statistics

from benchlib import spans

TIMED = ("bench.store_stream", "bench.drain_run")
STALL_US = 60_000
AT_MOST = 8


def _longest_stage(obs, a, b):
    """The stage span open longest inside [a, b]."""
    best = None
    for e in obs["spans"]:
        if e.get("ph") != "X" or e.get("cat") != "stage":
            continue
        open_us = min(b, e["ts"] + e["dur"]) - max(a, e["ts"])
        if open_us > 0 and (best is None or open_us > best[0]):
            best = (open_us, e)
    if best is None:
        return None
    e = best[1]
    return {"name": e["name"], "tid": e["tid"], "wall_ms": e["dur"] / 1e3,
            "cpu_ms": e["tdur"] / 1e3 if "tdur" in e else None}


def _stalled(obs, runs):
    if not runs:
        return {"median_ms": None, "stalled": 0, "longest": []}
    median = statistics.median(e["dur"] for e in runs)
    late = sorted((e for e in runs if e["dur"] >= median + STALL_US),
                  key=lambda e: -e["dur"])
    blocks = spans.named(obs, "block")
    rows = []
    for e in late[:AT_MOST]:
        a, b = spans.interval(e)
        rows.append({
            # the run's one block is the `block` span that began inside it
            "block": next((r["args"].get("block") for r in blocks if a <= r["ts"] <= b), None),
            "wall_ms": e["dur"] / 1e3,
            "proc_cpu_ms": e["args"]["proc_cpu_us"] / 1e3,
            "thread_cpu_ms": e["tdur"] / 1e3 if "tdur" in e else None,
            "longest_stage": _longest_stage(obs, a, b),
        })
    return {"median_ms": median / 1e3, "stalled": len(late), "longest": rows}


def read(obs):
    runs = [e for e in spans.named(obs, *TIMED) if "proc_cpu_us" in e["args"]]
    wall = sum(e["dur"] for e in runs)
    if not wall:
        return None
    cpu = sum(e["args"]["proc_cpu_us"] for e in runs)
    # the clocks held to each other: a run's own thread is one of the
    # process's, so its CPU cannot pass the process's by more than a tick
    over = [e["tdur"] - e["args"]["proc_cpu_us"] for e in runs if "tdur" in e]
    spans.say("host_cpu", {
        "runs": len(runs), "wall_s": wall / 1e6,
        "proc_cpu_s": cpu / 1e6,
        "own_thread_cpu_s": sum(e.get("tdur", 0) for e in runs) / 1e6,
        "own_thread_over_process_us_max": max(over, default=None),
    })
    if obs.get("mode") == "open_loop":
        spans.say("stalled_blocks", _stalled(
            obs, [e for e in runs if e["args"].get("blocks") == 1]))
    return cpu / wall
