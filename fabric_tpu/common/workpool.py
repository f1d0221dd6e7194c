"""Process-wide bounded host work pool for the commit path's parallel
stages.

The validate->commit pipeline has three host-side loops whose per-item
work is dominated by C-extension calls (protobuf decode, SHA-256,
identity deserialization): the validator's per-tx collect, the MVCC
per-namespace write-set prepare, and the native-collect footprint
prefetch.  Each of them fans out over ONE shared bounded executor —
a single pool keeps the process's host-thread budget fixed no matter
how many validators/ledgers exist, mirroring the reference's single
per-peer validation worker pool (core/committer/txvalidator
validationWorkersSemaphore, validator.go:180).

The pool is created lazily through ``lockwatch.tracked_executor`` so
every worker registers with the threadwatch drain gate — a session that
spins the pool up MUST call :func:`shutdown` before exit (`node.quiesce`,
the multichip dryrun, and tests/conftest.py all do), otherwise the idle
workers are reported as leaked threads, by design.

Stage fan-out widths are env knobs (``0``/``false``/``off`` disables a
stage's parallelism per the tree-wide convention):

``FABRIC_TPU_COLLECT_POOL``
    validator per-tx collect fan-out (default: auto, see _auto_width)
``FABRIC_TPU_MVCC_POOL``
    MVCC per-namespace prepare fan-out (default: auto)

Widths are CHUNK counts, not thread counts: a stage splits its items
into ``width`` contiguous chunks and submits each to the shared
executor, so results merge back in deterministic chunk order and the
executor's worker cap bounds real concurrency.
"""

from __future__ import annotations

import os
import threading

from fabric_tpu.common import profile, tracing
from fabric_tpu.devtools import clockskew, knob_registry

_FALSY = ("0", "false", "off", "no")

# the shared executor and the width it was created with; both move only
# under _pool_lock (declared in devtools/guards.py)
_pool = None
_pool_lock = threading.Lock()

# observability: an optional WorkpoolMetrics bundle (queue depth /
# in-flight / saturation gauges, wired by operations.System) and the
# count of chunks in flight that it and the /healthz checker read; both
# under one lock
_metrics = None
_stats_lock = threading.Lock()
_in_flight = 0


def set_metrics(metrics) -> None:
    """Attach a common.metrics.WorkpoolMetrics bundle: run_chunked then
    keeps its queue-depth / in-flight / saturation gauges current."""
    global _metrics
    with _stats_lock:
        _metrics = metrics


def _note_submit(pool, n_chunks: int) -> None:
    global _in_flight
    with _stats_lock:
        _in_flight += n_chunks
        m = _metrics
        inflight = _in_flight
    if m is not None:
        m.in_flight.set(inflight)
        q = getattr(pool, "_work_queue", None)
        if q is not None:
            m.queue_depth.set(q.qsize())
        workers = getattr(pool, "_max_workers", 0) or 1
        m.saturation.set(min(1.0, inflight / workers))


def _note_done(n_chunks: int) -> None:
    global _in_flight
    with _stats_lock:
        _in_flight = max(0, _in_flight - n_chunks)
        m = _metrics
        inflight = _in_flight
    if m is not None:
        m.in_flight.set(inflight)


def _auto_width() -> int:
    cpus = os.cpu_count() or 4
    return min(8, max(2, cpus // 3))


def stage_width(env: str) -> int:
    """Fan-out width for a stage: its env knob, else auto; 0 = stage
    runs serial (the knob's falsy spellings all map to 0)."""
    raw = knob_registry.raw(env).strip().lower()
    if not raw:
        return _auto_width()
    if raw in _FALSY:
        return 0
    try:
        n = int(raw)
    except ValueError:
        raise ValueError(
            f"{env}={raw!r} is not an integer fan-out width "
            "(0 disables the stage's parallelism)"
        ) from None
    return max(0, n)


def default_pool():
    """The shared bounded executor, created on first use.  Sized to the
    widest auto width so chunked stages can saturate it; never resized
    (widths above the worker cap just queue, preserving determinism).

    Registered with threadwatch as kind="service": the pool is a
    run-until-stopped facility whose stop path is :func:`shutdown`,
    and its idle workers must not read as leaked bounded jobs to
    mid-session ``drain_threads`` sweeps.  (:class:`scoped_pool`
    registers as "worker" instead — a test pool that outlives its
    scope IS a leak and fails the session.)"""
    global _pool
    with _pool_lock:
        if _pool is None:
            from fabric_tpu.devtools.lockwatch import tracked_executor

            _pool = tracked_executor(
                max_workers=max(_auto_width(), 4),
                name="fabric-workpool",
                kind="service",
            )
        return _pool


def saturation() -> tuple[int, int, int]:
    """Instantaneous pool pressure: ``(in_flight chunks, worker cap,
    executor queue depth)``.  All zeros while the shared pool has never
    been created — probing must not spin it up."""
    with _pool_lock:
        pool = _pool
    if pool is None:
        return 0, 0, 0
    workers = getattr(pool, "_max_workers", 0) or 0
    q = getattr(pool, "_work_queue", None)
    depth = q.qsize() if q is not None else 0
    with _stats_lock:
        inflight = _in_flight
    return inflight, workers, depth


def health_checker():
    """A /healthz checker (``operations.System.register_checker``) that
    fails while fan-outs are queuing behind each other: more chunks in
    flight than the pool has workers AND tasks actually waiting in the
    executor queue.  Transient full utilization (in_flight == workers,
    empty queue) stays healthy — that is the pool doing its job."""

    def check() -> bool:
        inflight, workers, depth = saturation()
        if workers and inflight > workers and depth > 0:
            raise RuntimeError(
                f"workpool saturated: {inflight} chunks in flight over "
                f"{workers} workers, {depth} queued"
            )
        return True

    return check


def shutdown(wait: bool = True) -> None:
    """Shut the shared executor down (idempotent).  Every entry point
    that may have spun it up calls this on the way out — under
    threadwatch an un-shut pool fails the session's drain gate."""
    global _pool
    with _pool_lock:
        pool, _pool = _pool, None
    if pool is not None:
        pool.shutdown(wait=wait)


class scoped_pool:
    """A dedicated tracked executor with deterministic lifetime — the
    parity tests sweep explicit pool sizes through this so the shared
    default pool's width never leaks into what a test measures::

        with scoped_pool(3) as pool:
            validator = TxValidator(..., collect_pool=pool)
    """

    def __init__(self, max_workers: int, name: str = "scoped-pool"):
        from fabric_tpu.devtools.lockwatch import tracked_executor

        self._pool = tracked_executor(
            max_workers=max_workers, name=name, kind="worker"
        )

    def __enter__(self):
        return self._pool

    def __exit__(self, *exc):
        self._pool.shutdown(wait=True)
        return False


def run_chunked(pool, fn, items, width: int):
    """Fan ``fn`` over ``items`` in ``width`` contiguous chunks on
    ``pool`` and return the per-item results in input order.

    ``fn`` receives ``(chunk_start_index, [item, ...])`` and returns a
    list of per-item results.  Deterministic by construction: chunk
    boundaries depend only on ``len(items)`` and ``width``, and results
    concatenate in chunk order.  A worker exception (BaseException
    included — faultline's FaultCrash models process death) propagates
    to the caller in chunk order."""
    n = len(items)
    if n == 0:
        return []
    width = min(width, n)
    if width <= 1:
        return fn(0, items)
    ctx = tracing.current() if tracing.enabled() else None
    if ctx is not None:
        # the caller's span flows INTO the pooled work: every chunk runs
        # under a child span, so spans opened inside (collect.tx /
        # mvcc.ns_prepare stages) parent across the thread hop
        caller_fn = fn

        def fn(off, chunk, _fn=caller_fn, _ctx=ctx):
            with tracing.attached(_ctx):
                with tracing.span(
                    "workpool.chunk", offset=off, items=len(chunk),
                ):
                    return _fn(off, chunk)

    if profile.enabled():
        # profscope queue-wait vs run-time attribution: all chunks are
        # submitted within the loop below, so one submit timestamp
        # serves every chunk; the wrapper wraps OUTSIDE the tracing
        # wrapper so run time covers the chunk span too
        submitted_fn = fn
        t_submit = clockskew.monotonic()

        def fn(off, chunk, _fn=submitted_fn, _ts=t_submit):
            t_start = clockskew.monotonic()
            try:
                return _fn(off, chunk)
            finally:
                profile.note_chunk(
                    t_start - _ts, clockskew.monotonic() - t_start
                )

    per = (n + width - 1) // width
    futures = [
        pool.submit(fn, off, items[off:off + per])
        for off in range(0, n, per)
    ]
    _note_submit(pool, len(futures))
    out: list = []
    try:
        for f in futures:
            out.extend(f.result())
    except BaseException:
        for f in futures:
            f.cancel()
        # settle every in-flight chunk before re-raising: a worker
        # still running after this call returned could hit a faultline
        # point after the caller's plan was disarmed, or outlive a
        # test's lockwatch scope — the fan-out must be fully quiesced
        # on every exit path
        from concurrent.futures import wait as _wait

        _wait(futures)
        _note_done(len(futures))
        raise
    _note_done(len(futures))
    return out


__all__ = [
    "default_pool",
    "scoped_pool",
    "shutdown",
    "stage_width",
    "run_chunked",
    "set_metrics",
    "saturation",
    "health_checker",
]
