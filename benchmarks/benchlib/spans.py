"""What the span readers share: picking tracelens events out of
`obs["spans"]` (Chrome trace events: `ts` and `dur` in microseconds on
the host's monotonic clock, `tid` the thread's name, `args` the span's
attributes with `span` and `parent` ids) and interval arithmetic on
them.  A program that lacks a span gives a reader nothing to read: the
reader returns None and the metric is left out of the line.
"""

from __future__ import annotations


def named(obs: dict, *names: str) -> list:
    """The finished spans called one of `names`, in recorded order."""
    return [
        e for e in obs.get("spans") or ()
        if e.get("ph") == "X" and e.get("name") in names
    ]


def interval(e: dict) -> tuple:
    return e["ts"], e["ts"] + e["dur"]


def total_ms(events) -> float:
    return sum(e["dur"] for e in events) / 1e3


def union(intervals) -> list:
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        elif b > a:
            out.append([a, b])
    return out


def overlap_us(a: float, b: float, intervals) -> float:
    """Microseconds of [a, b] covered by the union of `intervals`."""
    return sum(
        max(0, min(b, y) - max(a, x)) for x, y in union(intervals)
    )


def by_parent(obs: dict, *names: str) -> dict:
    """The spans called one of `names`, keyed by their parent's id."""
    out: dict = {}
    for e in named(obs, *names):
        out.setdefault(e["args"].get("parent"), []).append(e)
    return out


def say(tag: str, record) -> None:
    """What stands beside a metric's number, as an earlier line."""
    from benchlib.engine import say as line

    line(tag, record)
