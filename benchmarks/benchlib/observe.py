"""What a run records about itself, so that a run that stands out can
be explained from its records: generation-2 collections, compilations,
kernel buckets, and the machine it ran on.  Nothing here switches
anything off: the cell has what a deployed peer has, and counts it.
"""

from __future__ import annotations

import gc
import os
import time


class GCWatch:
    """The harness's own `gc.callbacks` entry.  Counts collections by
    generation and sums the pause of generation-2 collections, apart
    for those inside a timed region (`timed` is set by the harness)."""

    def __init__(self):
        self.counts = [0, 0, 0]
        self.gen2_pause_s = 0.0
        self.gen2_pause_timed_s = 0.0
        self.gen2_timed = 0
        self.timed = False
        self._t0 = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
            return
        gen = info.get("generation", 0)
        self.counts[gen] += 1
        if gen == 2:
            dt = time.perf_counter() - self._t0
            self.gen2_pause_s += dt
            if self.timed:
                self.gen2_pause_timed_s += dt
                self.gen2_timed += 1

    def install(self) -> "GCWatch":
        gc.callbacks.append(self)
        return self

    def remove(self) -> None:
        if self in gc.callbacks:
            gc.callbacks.remove(self)

    def snapshot(self) -> dict:
        return {
            "collections": list(self.counts),
            "gen2_pause_s": self.gen2_pause_s,
            "gen2_in_timed": self.gen2_timed,
            "gen2_pause_timed_s": self.gen2_pause_timed_s,
        }


class CompileWatch:
    """Counts what JAX traces, lowers and compiles, by its own
    monitoring events (`/jax/core/compile/...`).  A program found in
    the persistent cache still traces and lowers, so any event of the
    family inside the window means a shape the warm-up did not cover."""

    PREFIX = "/jax/core/compile/"

    def __init__(self):
        self.events: dict[str, int] = {}
        self.seconds: dict[str, float] = {}

    def _on_duration(self, name: str, secs: float, **_kw) -> None:
        if name.startswith(self.PREFIX):
            key = name[len(self.PREFIX):]
            self.events[key] = self.events.get(key, 0) + 1
            self.seconds[key] = self.seconds.get(key, 0.0) + secs

    def install(self) -> "CompileWatch":
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def total(self) -> int:
        return sum(self.events.values())

    def snapshot(self) -> dict:
        return {"events": dict(self.events), "seconds": dict(self.seconds)}


class BucketWatch:
    """Every device dispatch's lanes and the kernel buckets they pad
    to, seen from outside: the provider's `_dispatch` is wrapped on the
    instance (one list append a flush).  Tracelens has the same in
    `tpu.dispatch{lanes}`, but end-to-end runs keep tracing off."""

    def __init__(self, csp):
        from fabric_tpu.csp.tpu.provider import _chunk_plan

        self.flushes: list[tuple[int, tuple]] = []   # (lanes, buckets)
        # wall of the process's first dispatch: trace, lower and the
        # compile or its cache load happen inside it
        self.first_wall_s: float | None = None
        inner = csp._dispatch

        def counted(items):
            n = len(items)
            self.flushes.append((n, tuple(
                b for _take, b in _chunk_plan(n, csp._max_chunk, min_bucket=256)
            )))
            if self.first_wall_s is not None:
                return inner(items)
            t0 = time.perf_counter()
            try:
                return inner(items)
            finally:
                self.first_wall_s = time.perf_counter() - t0

        csp._dispatch = counted

    def mark(self) -> int:
        return len(self.flushes)

    def buckets(self, start: int = 0, end: int | None = None) -> set:
        return {b for _n, bs in self.flushes[start:end] for b in bs}

    def lanes(self, start: int = 0, end: int | None = None) -> list:
        return [n for n, _bs in self.flushes[start:end]]


# an fsync costs nothing on these
MEMORY_FILESYSTEMS = ("tmpfs", "ramfs", "devtmpfs")


def filesystem_of(path: str) -> dict:
    """Type and free space of the filesystem that holds `path`: on
    tmpfs an fsync is free, and durability's cost is then not measured."""
    real = os.path.realpath(path)
    best, fstype = "", "unknown"
    try:
        with open("/proc/mounts", encoding="utf-8") as f:
            for line in f:
                parts = line.split()
                if len(parts) >= 3:
                    mnt = parts[1]
                    under = real == mnt or real.startswith(mnt.rstrip("/") + "/")
                    if under and len(mnt) >= len(best):
                        best, fstype = mnt, parts[2]
    except OSError:
        pass
    st = os.statvfs(real)
    return {"mount": best, "type": fstype, "free_bytes": st.f_bavail * st.f_frsize}


def machine() -> dict:
    try:
        load = list(os.getloadavg())
    except OSError:
        load = []
    try:
        usable = len(os.sched_getaffinity(0))
    except AttributeError:
        usable = os.cpu_count()
    return {"loadavg": load, "cpus": os.cpu_count(), "cpus_usable": usable}


def knobs_set() -> dict:
    """Every FABRIC_TPU_*, CORE_* and JAX/XLA variable found in the
    environment.  The harness sets none; it prints those it finds."""
    return {
        k: v for k, v in sorted(os.environ.items())
        if k.startswith(("FABRIC_TPU_", "CORE_", "JAX_", "XLA_", "LIBTPU_", "TPU_"))
    }
