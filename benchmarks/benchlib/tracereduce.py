"""From the JAX profiler's trace to device metrics.

`load_xplane` turns an `.xplane.pb` into a plain document

    {"planes": [{"name": ..., "lines": [{"name": ..., "events":
        [[name, start_ns, duration_ns], ...]}]}]}

and everything else works on that document, so the reduction is tested
on a small recorded one (tests/bench/data/trace_small.json).  Times in
the document are nanoseconds on the profiler's clock, which starts near
zero at `start_trace`; `anchor_offset_s` ties it to the host's
monotonic clock through a `TraceAnnotation` the harness emits at a
known instant, so that the program's host spans can be laid over the
device's idle gaps.
"""

from __future__ import annotations

# lines of a device plane that hold something other than single
# operations (whole modules, steps, annotations): counting them would
# double the busy time
_NOT_OP_LINES = ("XLA Modules", "Steps", "XLA TraceMe", "Framework", "Source")


def load_xplane(path: str) -> dict:
    import jax.profiler

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        # of a host plane only the harness's own annotations are kept:
        # its threads can hold millions of runtime events
        host = not plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            lines.append({
                "name": line.name,
                "events": [
                    [_short(e.name), float(e.start_ns), float(e.duration_ns)]
                    for e in line.events
                    if not host or e.name.startswith("bench.")
                ],
            })
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _short(name: str) -> str:
    """`%tpu_custom_call.1 = u32[...] custom-call(...)` -> `tpu_custom_call.1`:
    the profiler names a device operation by its whole HLO line."""
    return name.split(" = ", 1)[0].lstrip("%")


def device_planes(doc: dict) -> list:
    return [
        p for p in doc["planes"]
        if p["name"].startswith("/device:") and "host" not in p["name"].lower()
    ]


def op_events(plane: dict) -> list:
    """[name, start_ns, duration_ns] of single device operations: the
    "XLA Ops" line where the plane has one, else every line that is
    not a roll-up."""
    named = [l for l in plane["lines"] if l["name"] == "XLA Ops"]
    lines = named or [
        l for l in plane["lines"]
        if not any(l["name"].startswith(x) for x in _NOT_OP_LINES)
    ]
    return [e for l in lines for e in l["events"] if e[2] > 0]


def _clipped(events, w0: float, w1: float):
    for name, start, dur in events:
        a, b = max(start, w0), min(start + dur, w1)
        if b > a:
            yield name, a, b


def busy_intervals(events, w0: float, w1: float) -> list:
    """The union of the operations' intervals inside [w0, w1]."""
    out: list = []
    for _name, a, b in sorted(_clipped(events, w0, w1), key=lambda t: t[1]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def busy_seconds(events, w0: float, w1: float) -> float:
    return sum(b - a for a, b in busy_intervals(events, w0, w1)) / 1e9


def idle_gaps(events, w0: float, w1: float) -> list:
    """[(start_ns, end_ns)] in which no operation ran, inside [w0, w1]."""
    gaps, at = [], w0
    for a, b in busy_intervals(events, w0, w1):
        if a > at:
            gaps.append((at, a))
        at = b
    if w1 > at:
        gaps.append((at, w1))
    return gaps


def op_totals(events, w0: float, w1: float) -> dict:
    """Seconds by operation name inside the window."""
    out: dict = {}
    for name, a, b in _clipped(events, w0, w1):
        out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return out


def kernel_seconds(events, patterns, w0: float, w1: float) -> tuple:
    """(seconds, events) of the operations whose name contains one of
    `patterns`, inside the window."""
    secs, n = 0.0, 0
    for name, a, b in _clipped(events, w0, w1):
        if any(p in name for p in patterns):
            secs += (b - a) / 1e9
            n += 1
    return secs, n


def find_annotations(doc: dict, name: str) -> list:
    """Start times (ns) of the host annotations called `name`."""
    out = []
    for p in doc["planes"]:
        if not p["name"].startswith("/host:"):
            continue
        for l in p["lines"]:
            out.extend(e[1] for e in l["events"] if e[0] == name)
    return sorted(out)


def anchor_offset_s(doc: dict, name: str, monotonic_s: list) -> float | None:
    """Seconds to ADD to a host monotonic time to get the profiler's
    clock, from annotations emitted at the known monotonic instants
    `monotonic_s` (in the order emitted); None where none was found."""
    seen = find_annotations(doc, name)
    pairs = list(zip(seen, monotonic_s))
    if not pairs:
        return None
    return sum(ns / 1e9 - m for ns, m in pairs) / len(pairs)


def attribute_gaps(gaps, spans, top: int = 10) -> list:
    """Idle seconds by what the host was doing: `gaps` are (start_s,
    end_s) and `spans` are (name, start_s, end_s, thread) on one clock.
    Each gap is cut at the span boundaries inside it, and each piece
    goes to a label `<harness span>/<stage>_<stage>`: the innermost
    open `bench.*` span, then the innermost open span of every thread,
    sorted.  Returns the `top` labels as [[label, seconds], ...]."""
    totals: dict = {}
    for g0, g1 in gaps:
        near = [s for s in spans if s[1] < g1 and s[2] > g0]
        cuts = sorted({g0, g1, *(
            t for s in near for t in (s[1], s[2]) if g0 < t < g1
        )})
        for a, b in zip(cuts, cuts[1:]):
            open_ = [s for s in near if s[1] <= a and s[2] >= b]
            label = _label(open_)
            totals[label] = totals.get(label, 0.0) + (b - a)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])
    return [[k, v] for k, v in ranked[:top]]


def _label(open_spans) -> str:
    bench = [s for s in open_spans if s[0].startswith("bench.")]
    inner_by_thread: dict = {}
    for s in open_spans:
        if s[0].startswith("bench."):
            continue
        cur = inner_by_thread.get(s[3])
        if cur is None or s[1] >= cur[1]:
            inner_by_thread[s[3]] = s
    stages = "_".join(sorted({s[0] for s in inner_by_thread.values()}))
    outer = max(bench, key=lambda s: s[1])[0] if bench else ""
    if outer and stages:
        return f"{outer}/{stages}"
    return outer or stages or "(no span open)"


def reduce_device(doc: dict, w0: float, w1: float, kernel_patterns) -> dict:
    """Busy seconds (averaged over the device planes that ran anything),
    operation totals, the kernel's seconds and event count, and the
    idle gaps of the busiest plane, inside [w0, w1] ns."""
    planes = [(p["name"], op_events(p)) for p in device_planes(doc)]
    used = [(n, ev) for n, ev in planes if busy_seconds(ev, w0, w1) > 0]
    if not used:
        return {"planes": [n for n, _ in planes], "busy_s": 0.0,
                "window_s": (w1 - w0) / 1e9, "ops": {}, "kernel_s": 0.0,
                "kernel_events": 0, "gaps": []}
    busy = [busy_seconds(ev, w0, w1) for _n, ev in used]
    ops: dict = {}
    ksecs, kn = 0.0, 0
    for _n, ev in used:
        for name, s in op_totals(ev, w0, w1).items():
            ops[name] = ops.get(name, 0.0) + s
        s, n = kernel_seconds(ev, kernel_patterns, w0, w1)
        ksecs, kn = ksecs + s, kn + n
    busiest = used[busy.index(max(busy))][1]
    return {
        "planes": [n for n, _ in used],
        "busy_s": sum(busy) / len(busy),
        "window_s": (w1 - w0) / 1e9,
        "ops": ops,
        "kernel_s": ksecs,
        "kernel_events": kn,
        "gaps": idle_gaps(busiest, w0, w1),
    }
