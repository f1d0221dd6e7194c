"""tracelens — zero-overhead-when-disabled end-to-end span tracing.

The stage histograms (``commit_stage_seconds``,
``validator_block_stage_duration``) answer "how long does stage X take
in aggregate" but not the questions the commit-path work keeps raising:
which stage sat on the CRITICAL PATH of one slow block, whether
``verify_wait`` overlapped the TPU dispatch or serialized behind it,
and what the pipeline was doing in the seconds before a chaos-oracle
failure.  This module answers those with causally-linked spans, in the
same seam style as faultline/clockskew:

- :func:`span`/:func:`begin` are a module-global load and an ``is
  None`` test when ``FABRIC_TPU_TRACE`` is unset — they return one
  shared no-op object, allocate nothing, and no ring buffer ever
  exists.  Traced and untraced commits are byte-identical (spans only
  observe timing; tests/test_tracing.py pins both contracts).
- Armed, every finished span lands in a process-wide bounded
  ring-buffer **flight recorder** (old spans fall off; the recorder is
  a black box for "what just happened", not a full trace store).
- Span/trace IDs come from a seeded process counter and timestamps
  from the ``clockskew`` provider, so virtual-clock tests produce
  byte-identical traces and same-seed chaos campaigns replay to
  identical span sequences.
- Trace context crosses async hops explicitly: :func:`wire_token`/
  :func:`from_wire` carry it inside RPC frames, :func:`current` +
  :func:`attached` carry it onto committer/workpool/raft-sender
  threads.

Export is Chrome trace-event JSON (``chrome://tracing`` / Perfetto
load it directly): the operations endpoint serves the flight recorder
at ``GET /traces``, and faultfuzz drops a dump next to every repro
artifact.  The reader of record for performance work is the benchmark:
``benchmarks/run.py --trace 1`` arms tracelens for the measured window,
keeps every event in ``obs["spans"]`` for the per-layer readers
(``benchmarks/layer_metrics/``) and lays the ``stage`` and ``bench``
spans over the device's idle gaps.

A span's ``dur`` is wall time, and the pipeline's threads share one
interpreter lock, so an armed span also says how long its thread was
on a CPU: ``tts``/``tdur`` (the trace-event format's thread clock and
thread duration, microseconds of ``time.thread_time_ns()``) on every
span that begins and ends on one thread, and ``args.proc_cpu_us``
(``time.process_time_ns()``, every thread of the process counted) on
the roots that bound a piece of work: a detached root, which has no
thread of its own (a peer's ``block``, an orderer's ``raft.block``), and
the ``cat="bench"`` roots a harness times with.  No other span pays for
the second clock.
``dur - tdur`` is the time the thread waited: for the lock, a queue,
the disk or the scheduler.  Spans that follow each other within
microseconds share one reading of the thread's clock where a reading
is dear (``_thread_cpu_ns``): their ``tdur`` tile, and a single span is
off by a few reads' worth of wall at most.  Under a virtual clock the
fields are left out, so seeded documents stay byte-identical.

Generation-2 collections stop every thread, so an armed process also
records them: :func:`arm` installs the process's one ``gc.callbacks``
entry and each long collection becomes a ``gc.pause`` span on the
thread it ran on (see :func:`watch_gc`).
"""

from __future__ import annotations

import collections
import contextlib
import gc
import json
import os
import threading
import time

from fabric_tpu.devtools import clockskew, knob_registry

_ENV = "FABRIC_TPU_TRACE"
_FALSY = ("", "0", "false", "off", "no")

DEFAULT_CAPACITY = 8192


class SpanContext:
    """The carryable half of a span: (trace_id, span_id).  This is what
    crosses threads and wires — never the Span object itself."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: int, span_id: int):
        self.trace_id = trace_id
        self.span_id = span_id

    def __repr__(self) -> str:
        return f"SpanContext({self.trace_id:x}.{self.span_id:x})"

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, SpanContext)
            and other.trace_id == self.trace_id
            and other.span_id == self.span_id
        )

    def __hash__(self) -> int:
        return hash((self.trace_id, self.span_id))


class FlightRecorder:
    """Process-wide bounded ring buffer of finished span / instant
    events (Chrome trace-event dicts).  Old events fall off the front —
    the recorder answers "what was the pipeline doing just now", like a
    cockpit flight recorder, not "everything since boot"."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.capacity = int(capacity)
        self._buf: collections.deque = collections.deque(
            maxlen=self.capacity
        )
        self._lock = threading.Lock()
        # monotonically increasing per-event cursor: `GET
        # /traces?since=<id>` streams only what landed after a previous
        # poll (netbench polls live nodes incrementally instead of
        # re-downloading the whole recorder each time)
        self._seq = 0

    def record(self, event: dict) -> None:
        with self._lock:
            self._seq += 1
            event["id"] = self._seq
            self._buf.append(event)

    def snapshot(self, since: int | None = None) -> list[dict]:
        with self._lock:
            if since is None:
                return list(self._buf)
            return [ev for ev in self._buf if ev.get("id", 0) > since]

    def snapshot_with_cursor(
        self, since: int | None = None
    ) -> tuple[list[dict], int]:
        """(events after ``since``, cursor) taken under ONE lock — a
        cursor read after a separate snapshot() would advertise events
        recorded in between without containing them, and an incremental
        poller would skip them forever.  A ``since`` AHEAD of the
        current cursor means the recorder was cleared since the caller
        last polled: the stale cursor is invalid, so the full buffer is
        returned and the caller resyncs on the fresh cursor."""
        with self._lock:
            if since is not None and since > self._seq:
                since = None  # stale cursor from before a clear()
            events = (
                list(self._buf) if since is None
                else [ev for ev in self._buf if ev.get("id", 0) > since]
            )
            return events, self._seq

    @property
    def last_event_id(self) -> int:
        with self._lock:
            return self._seq

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self._seq = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._buf)


# the armed recorder; None = tracing disarmed.  EVERY entry point's
# fast path tests only this global (the faultline `_plan` pattern).
_recorder: FlightRecorder | None = None
_state_lock = threading.Lock()

# deterministic id source: a plain counter, reset by reset_ids() so a
# chaos campaign's per-plan traces replay to identical sequences
_ids = [0]
_ids_lock = threading.Lock()

# armed-path consultations — stays 0 while tracing has never been
# armed, which is the zero-overhead acceptance probe
_lookups = [0]

_tls = threading.local()  # .stack: list[Span | _Remote]

# Cross-thread view of the per-thread span stacks, keyed by thread
# ident: the SAME list objects as _tls.stack, so the profscope sampler
# can read another thread's innermost span under the GIL without that
# thread's cooperation.  Only ever populated from _stack(), which runs
# exclusively on armed paths — the disarmed zero-overhead pin holds.
_stacks_by_thread: dict[int, list] = {}


# A read of the thread's CPU clock is a system call: 0.4 us on Linux,
# 6 us on a sandboxed kernel (whose clock then ticks at 10 ms), where
# the wall clock costs 0.09.  Stages follow each other within
# microseconds, so a span that begins or ends within a few reads' worth
# of wall after its thread's last reading shares that reading: where the
# clock is cheap next to nothing is shared and every span is exact,
# where it is dear neighbours tile (one's end IS the next one's start),
# nothing is counted twice, and a span is off by the window at most.
_CPU_REUSE_READS = 8
_cpu_reuse_s: float | None = None     # the window, measured at first use


def _thread_cpu_ns(wall: float) -> int:
    global _cpu_reuse_s
    if _cpu_reuse_s is None:
        t0 = time.perf_counter()
        for _ in range(16):
            time.thread_time_ns()
        _cpu_reuse_s = _CPU_REUSE_READS * (time.perf_counter() - t0) / 16
    last = getattr(_tls, "cpu", None)
    if last is not None and 0 <= wall - last[0] < _cpu_reuse_s:
        return last[1]
    now = time.thread_time_ns()
    _tls.cpu = (wall, now)
    return now


def _stack() -> list:
    s = getattr(_tls, "stack", None)
    if s is None:
        s = _tls.stack = []
        _stacks_by_thread[threading.get_ident()] = s
    return s


def active_span_of(tid: int) -> "Span | None":
    """Innermost live span on thread ``tid``, or None.  A cross-thread
    read for samplers: list snapshot + attribute reads are GIL-atomic,
    and a span that ended between reads reports ``_ended`` and is
    skipped — worst case a sample lands on the parent span, never on a
    corrupt one."""
    stack = _stacks_by_thread.get(tid)
    if not stack:
        return None
    for item in reversed(list(stack)):
        if isinstance(item, Span) and not item._ended:
            return item
    return None


def _next_id() -> int:
    with _ids_lock:
        _ids[0] += 1
        return _ids[0]


class _Remote:
    """Stack marker for a context attached from another thread/process
    hop: parents spans opened in this scope without being a span."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, ctx: SpanContext):
        self.trace_id = ctx.trace_id
        self.span_id = ctx.span_id


class Span:
    """A live span.  Use as a context manager (exception-safe) or via
    explicit :meth:`end`.  ``end`` repairs the thread-local stack: any
    child a crash left open is closed at the same instant and marked
    ``abandoned`` so an injected FaultCrash mid-stage cannot corrupt
    later spans' parenting."""

    __slots__ = (
        "name", "trace_id", "span_id", "parent_id", "attrs", "cat",
        "start", "_tid", "_detached", "_ended", "_tcpu", "_pcpu",
    )

    def __init__(self, name: str, trace_id: int, span_id: int,
                 parent_id: int | None, cat: str, attrs: dict,
                 detached: bool):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.cat = cat
        self.attrs = attrs
        # CPU clocks (module docstring), real clock only: the thread's
        # unless detached, the process's on a root that is detached or
        # a harness's.  Read so that the process's interval contains
        # the wall's and the thread's; the thread's passes the wall's
        # by one read at most.
        real = clockskew.installed() is None
        self._pcpu = (
            time.process_time_ns()
            if real and parent_id is None and (detached or cat == "bench")
            else None
        )
        self.start = clockskew.monotonic()
        self._tcpu = (
            _thread_cpu_ns(self.start) if real and not detached else None
        )
        self._tid = threading.current_thread().name
        self._detached = detached
        self._ended = False

    @property
    def ctx(self) -> SpanContext:
        return SpanContext(self.trace_id, self.span_id)

    def annotate(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> bool:
        self.end()
        return False

    def end(self) -> None:
        if self._ended:
            return
        rec = _recorder
        end_ts = clockskew.monotonic()
        tcpu = _thread_cpu_ns(end_ts) if self._tcpu is not None else None
        pcpu = time.process_time_ns() if self._pcpu is not None else None
        if not self._detached:
            stack = _stack()
            # repair: close any child an exception left open above us
            while stack:
                top = stack.pop()
                if top is self:
                    break
                if isinstance(top, Span) and not top._ended:
                    top._ended = True
                    top.attrs["abandoned"] = True
                    if rec is not None:
                        # closed at an end that is not its own: no CPU
                        rec.record(top._event(end_ts))
        self._ended = True
        if rec is not None:
            rec.record(self._event(end_ts, tcpu, pcpu))

    def _event(self, end_ts: float, tcpu: int | None = None,
               pcpu: int | None = None) -> dict:
        args = {
            "trace": f"{self.trace_id:x}",
            "span": f"{self.span_id:x}",
        }
        if self.parent_id is not None:
            args["parent"] = f"{self.parent_id:x}"
        args.update(self.attrs)
        if pcpu is not None:
            args["proc_cpu_us"] = max(0, round((pcpu - self._pcpu) / 1e3))
        # round, not truncate: 0.01s on a virtual clock must be exactly
        # 10000µs, or determinism tests chase float dust
        ts = round(self.start * 1e6)
        event = {
            "ph": "X",
            "name": self.name,
            "cat": self.cat,
            "ts": ts,
            "dur": max(0, round(end_ts * 1e6) - ts),
            "pid": 0,
            "tid": self._tid,
            "args": args,
        }
        if tcpu is not None:
            # the trace-event format's own thread clock: Perfetto and
            # chrome://tracing show it as the slice's CPU duration
            tts = round(self._tcpu / 1e3)
            event["tts"] = tts
            event["tdur"] = max(0, round(tcpu / 1e3) - tts)
        return event


class _Noop:
    """The shared disarmed span/scope: every method is a no-op and
    every entry point returns THIS singleton — no allocation on the
    disarmed path, pinned by test_tracing."""

    __slots__ = ()
    ctx = None

    def __enter__(self) -> "_Noop":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def annotate(self, **attrs) -> None:
        pass

    def end(self) -> None:
        pass


_NOOP = _Noop()


# -- span entry points --------------------------------------------------------


def begin(name: str, parent: SpanContext | None = None,
          detach: bool = False, cat: str = "span", **attrs):
    """Open a span.  Disarmed: returns the shared no-op.  Armed: the
    parent is `parent` if given, else the innermost span/attached
    context on this thread; a parentless span roots a new trace.
    ``detach=True`` keeps the span OFF the thread-local stack (for
    per-block roots whose children start on other threads/iterations) —
    children then attach via ``attached(span.ctx)`` or ``parent=``."""
    if _recorder is None:
        return _NOOP
    _lookups[0] += 1
    parent_id = None
    trace_id = None
    if parent is not None:
        trace_id = parent.trace_id
        parent_id = parent.span_id
    else:
        stack = _stack()
        if stack:
            top = stack[-1]
            trace_id = top.trace_id
            parent_id = top.span_id
    span_id = _next_id()
    if trace_id is None:
        trace_id = span_id  # root: the trace is named after its root
    sp = Span(name, trace_id, span_id, parent_id, cat, attrs, detach)
    if not detach:
        _stack().append(sp)
    return sp


# `with tracing.span(...)` reads better at call sites; same function.
span = begin


def instant(name: str, **attrs) -> None:
    """Record a zero-duration marker event (faultline trips, lockwatch
    violations, chaos-oracle annotations) parented to the innermost
    active span.  Disarmed: a global load + None test."""
    rec = _recorder
    if rec is None:
        return
    _lookups[0] += 1
    args = dict(attrs)
    stack = _stack()
    if stack:
        top = stack[-1]
        args["trace"] = f"{top.trace_id:x}"
        args["parent"] = f"{top.span_id:x}"
    rec.record({
        "ph": "i",
        "name": name,
        "cat": "mark",
        "ts": round(clockskew.monotonic() * 1e6),
        "pid": 0,
        "tid": threading.current_thread().name,
        "s": "p",
        "args": args,
    })


def annotate(**attrs) -> None:
    """Merge attrs into the innermost active span (no-op when disarmed
    or no span is open)."""
    if _recorder is None:
        return
    stack = _stack()
    if stack and isinstance(stack[-1], Span):
        stack[-1].attrs.update(attrs)


def current() -> SpanContext | None:
    """The innermost active span context on this thread, carryable to
    another thread via :func:`attached`."""
    if _recorder is None:
        return None
    stack = _stack()
    if not stack:
        return None
    top = stack[-1]
    return SpanContext(top.trace_id, top.span_id)


class _Attach:
    __slots__ = ("_ctx",)

    def __init__(self, ctx: SpanContext):
        self._ctx = ctx

    def __enter__(self):
        _stack().append(_Remote(self._ctx))
        return self._ctx

    def __exit__(self, *exc) -> bool:
        stack = _stack()
        if stack:
            stack.pop()
        return False


def attached(ctx: SpanContext | None):
    """Adopt a context carried from another thread/hop for a scope:
    spans opened inside parent to it.  ``attached(None)`` (and the
    disarmed path) is the shared no-op."""
    if _recorder is None or ctx is None:
        return _NOOP
    return _Attach(ctx)


# -- wire propagation ---------------------------------------------------------


def wire_token() -> str | None:
    """The active context as a compact wire token (``trace.span`` hex),
    or None when tracing is disarmed / no span is active — callers emit
    byte-identical frames in that case."""
    ctx = current()
    if ctx is None:
        return None
    return f"{ctx.trace_id:x}.{ctx.span_id:x}"


def from_wire(token: str) -> SpanContext | None:
    """Parse a :func:`wire_token`; malformed tokens are None (a traced
    peer must never be able to crash an untraced server)."""
    try:
        t, _, s = token.partition(".")
        return SpanContext(int(t, 16), int(s, 16))
    except ValueError:
        return None


# Binary-frame piggyback (the gossip TCP transport; the RPC transport
# carries the same token inside its str method field): a traced sender
# prefixes the frame with b"\x01<token>\x01".  Serialized protobuf
# frames always start with a field-tag byte (never 0x01), so receivers
# can ALWAYS strip; untraced senders emit byte-identical frames.  Kept
# HERE beside wire_token/from_wire so the token format has one owner.
FRAME_MARK = b"\x01"


def frame_with_token(data: bytes, ctx: SpanContext | None) -> bytes:
    """Prefix a binary frame with the context's wire token (the frame
    unchanged when ``ctx`` is None — the untraced path)."""
    if ctx is None:
        return data
    token = f"{ctx.trace_id:x}.{ctx.span_id:x}"
    return FRAME_MARK + token.encode("ascii") + FRAME_MARK + data


def split_frame_token(frame: bytes) -> tuple[bytes, SpanContext | None]:
    """(payload, SpanContext | None) — strips the optional trace
    prefix; malformed prefixes fall back to the raw frame so a traced
    peer can never wedge an untraced server."""
    if not frame.startswith(FRAME_MARK):
        return frame, None
    end = frame.find(FRAME_MARK, 1)
    if end < 0:
        return frame, None
    try:
        token = frame[1:end].decode("ascii")
    except UnicodeDecodeError:
        return frame, None
    return frame[end + 1:], from_wire(token)


# -- gc pauses ----------------------------------------------------------------

# The process's ONE `gc.callbacks` entry.  It is installed on first
# need: by arm(), which wants a span for every collection that can
# explain an idle gap, or by common.metrics.ProcessMetrics, which
# wants the summed pause for its gauge.  Where neither asked, nothing
# is installed.  A collection stops every thread of the process, so a
# pause belongs to no block's trace: the event carries no span id (the
# seeded id counter is left alone, and span_sequence() skips it).
#
# Which collections get a span: every one of generation 1 or older,
# and a generation-0 one of at least GC_SPAN_MIN_S.  Since the start-up
# heap is frozen and the young generations are collected where the
# pipeline runs empty (common/gcpolicy.py), a full walk of the heap is
# off the block path and the generation-1 collection is the usual one
# there: it is what an operator reads in a trace to see that the
# collector is alive and cheap.
GC_SPAN_MIN_S = 1e-3  # a generation-0 pause shorter than this only counts

_gc_keep = False  # ProcessMetrics reads the total for the life of the process
# the recorder arm() made: pauses are kept only while IT is the armed
# one, so a scope() (the seeded tests' entry) records none
_gc_rec: FlightRecorder | None = None
_gc_t0: float | None = None
_gc_pause_total = [0.0]
# The callback runs between two bytecodes of whatever thread crossed
# the collector's threshold, possibly one that holds the recorder's
# lock: it takes no lock and only appends here (atomic under the GIL);
# export() moves the events into the recorder.  arm() sizes this to
# its recorder, so no pause is dropped that the ring would have kept.
_gc_pending: collections.deque = collections.deque(maxlen=DEFAULT_CAPACITY)


def _on_gc(phase: str, info: dict) -> None:
    global _gc_t0
    if phase == "start":
        _gc_t0 = time.monotonic()
        return
    t0, _gc_t0 = _gc_t0, None
    if t0 is None:
        return
    dt = time.monotonic() - t0
    _gc_pause_total[0] += dt
    rec = _gc_rec
    # a virtual clock has no place for a wall-clock pause
    if rec is None or rec is not _recorder or clockskew.installed() is not None:
        return
    gen = info.get("generation", 0)
    if gen < 1 and dt < GC_SPAN_MIN_S:
        return
    ts = round(t0 * 1e6)
    dur = max(0, round((t0 + dt) * 1e6) - ts)
    _gc_pending.append({
        "ph": "X",
        "name": "gc.pause",
        # "stage": the benchmark lays these over the device's idle gaps
        "cat": "stage",
        "ts": ts,
        "dur": dur,
        # the collector runs on the thread that tripped it: all CPU
        "tts": max(0, round(time.thread_time_ns() / 1e3) - dur),
        "tdur": dur,
        "pid": 0,
        "tid": threading.current_thread().name,
        "args": {
            "generation": gen,
            "collected": info.get("collected", 0),
            "uncollectable": info.get("uncollectable", 0),
        },
    })


def watch_gc(keep: bool = True) -> None:
    """Install the process's gc callback (idempotent).  ``keep`` pins
    it for the life of the process (the ProcessMetrics gauge reads a
    running total); arm() installs it unpinned and disarm() takes it
    out again."""
    global _gc_keep
    with _state_lock:
        _gc_keep = _gc_keep or keep
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)


def gc_pause_seconds() -> float:
    """Seconds spent inside cyclic collections since the callback was
    installed (every generation; a float add is atomic enough for a
    monotone scrape-time read)."""
    return _gc_pause_total[0]


def _drain_gc(rec: FlightRecorder) -> None:
    if rec is _gc_rec:
        while _gc_pending:
            rec.record(_gc_pending.popleft())


# -- lifecycle ----------------------------------------------------------------


def enabled() -> bool:
    return _recorder is not None


def recorder() -> FlightRecorder | None:
    return _recorder


def lookup_count() -> int:
    """Armed-path consultations so far — provably 0 while tracing has
    never been armed (the zero-overhead acceptance probe)."""
    return _lookups[0]


def arm(capacity: int = DEFAULT_CAPACITY) -> FlightRecorder:
    """Arm tracing process-wide (idempotent per capacity: re-arming
    replaces the recorder), and record ``gc.pause`` spans while armed."""
    global _recorder, _gc_rec, _gc_pending
    watch_gc(keep=False)
    with _state_lock:
        _gc_pending = collections.deque(maxlen=capacity)
        _recorder = _gc_rec = FlightRecorder(capacity)
        return _recorder


def disarm() -> None:
    global _recorder, _gc_rec
    with _state_lock:
        _recorder = _gc_rec = None
        _gc_pending.clear()
        if not _gc_keep and _on_gc in gc.callbacks:
            gc.callbacks.remove(_on_gc)


def reset_ids(start: int = 0) -> None:
    """Reset the deterministic id counter — a same-seed chaos plan run
    then replays to an identical span sequence."""
    with _ids_lock:
        _ids[0] = int(start)


def reset() -> None:
    """Clear the recorder and the id counter (armed runs that need
    per-pass / per-plan reproducible traces: bench passes, fuzz plans)."""
    rec = _recorder
    if rec is not None:
        rec.clear()
        _gc_pending.clear()
    reset_ids()


@contextlib.contextmanager
def scope(capacity: int = DEFAULT_CAPACITY):
    """Arm tracing for a lexical scope (tests), restoring the previous
    recorder — and the previous id counter — on exit, so a traced test
    leaves the disarmed world exactly as it found it."""
    global _recorder
    with _state_lock:
        prev, _recorder = _recorder, FlightRecorder(capacity)
    with _ids_lock:
        prev_ids = _ids[0]
        _ids[0] = 0
    try:
        yield _recorder
    finally:
        with _state_lock:
            _recorder = prev
        with _ids_lock:
            _ids[0] = prev_ids


# -- export -------------------------------------------------------------------


def export(rec: FlightRecorder | None = None,
           since: int | None = None) -> dict:
    """The flight recorder as a Chrome trace-event document
    (object form: chrome://tracing and Perfetto load it directly).
    ``since`` is an event-id cursor: only events recorded AFTER it are
    included, and ``otherData.last_event_id`` is the cursor for the
    next incremental poll (``GET /traces?since=``); a cursor from
    before a recorder reset is detected (it is ahead of the fresh
    cursor) and answered with the full buffer so the poller resyncs."""
    rec = rec if rec is not None else _recorder
    if rec is not None:
        _drain_gc(rec)
        events, cursor = rec.snapshot_with_cursor(since)
    else:
        events, cursor = [], 0
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "armed": _recorder is not None,
            "source": "fabric_tpu.tracelens",
            "last_event_id": cursor,
        },
    }


def dump_doc(path: str, doc: dict) -> str:
    """Write an already-exported trace document as the canonical
    artifact format (one serialization owned here — faultfuzz repro
    traces and chaos replay dumps route through this)."""
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
        # the dump is usually written on the way down from a failure —
        # push it to the OS now so a crash right after still leaves a
        # complete artifact (this module is a reviewed chaos seam:
        # fabriclint's blocking-io propagation stops here)
        f.flush()
    return path


def dump_to(path: str, rec: FlightRecorder | None = None) -> str:
    """Write :func:`export` as JSON (the chaos-repro trace artifact)."""
    return dump_doc(path, export(rec))


def span_sequence(doc: dict) -> list[tuple]:
    """The determinism view of a trace: (name, trace, span, parent)
    per event in recorded order, timestamps stripped — what same-seed
    campaign runs must reproduce byte-identically.  ``gc.*`` events are
    left out: when the collector runs is not the seed's to decide."""
    out = []
    for ev in doc.get("traceEvents", []):
        if str(ev.get("name", "")).startswith("gc."):
            continue
        args = ev.get("args", {})
        out.append((
            ev.get("name"), args.get("trace"), args.get("span"),
            args.get("parent"),
        ))
    return out


# -- env arming ---------------------------------------------------------------


def _init_from_env() -> None:
    raw = knob_registry.raw(_ENV).strip().lower()
    if raw in _FALSY:
        return
    try:
        cap = int(raw)
    except ValueError:
        cap = DEFAULT_CAPACITY
    arm(cap if cap > 1 else DEFAULT_CAPACITY)


_init_from_env()


__all__ = [
    "SpanContext",
    "FlightRecorder",
    "Span",
    "span",
    "begin",
    "instant",
    "annotate",
    "current",
    "attached",
    "wire_token",
    "from_wire",
    "frame_with_token",
    "split_frame_token",
    "FRAME_MARK",
    "active_span_of",
    "enabled",
    "recorder",
    "lookup_count",
    "arm",
    "disarm",
    "watch_gc",
    "gc_pause_seconds",
    "reset",
    "reset_ids",
    "scope",
    "export",
    "dump_doc",
    "dump_to",
    "span_sequence",
    "DEFAULT_CAPACITY",
]
