"""TPU CSP provider: the `bccsp/tpu` seam.

The sibling the reference never had (BASELINE.json north star): same SPI as
the `sw` provider (bccsp/sw/impl.go dispatch surface), but `verify_batch`
executes as one kernel launch over the whole batch instead of per-item
host calls.  (`hash_batch` is hashlib: host SHA-NI wins at every size.)

Key management and signing delegate to the host `sw` provider — the
reference's hot path is *verification* at commit time (SURVEY.md §3.4:
N_txs x (1 creator + K endorsers) ECDSA verifies per block); signing is
one-per-proposal on the endorser and stays host-side.

Static-shape discipline (SURVEY.md §7 hard part (1)): batches are padded to
bucket sizes (powers of two) so XLA compiles once per bucket; oversized
batches are chunked.  Per-item failure semantics are preserved end to end:
host prechecks mark items invalid without throwing, and the kernel returns
a per-lane mask (hard part (4)).
"""

from __future__ import annotations

import contextlib
import hashlib
import os
import threading
import time
from typing import Sequence

import numpy as np

from fabric_tpu.common import gcpolicy, tracing
from fabric_tpu.common.flogging import must_get_logger
from fabric_tpu.csp import api
from fabric_tpu.devtools import faultline, knob_registry
from fabric_tpu.devtools.lockwatch import guarded, named_rlock, spawn_thread

_logger = must_get_logger("csp.tpu")
from fabric_tpu.csp.api import (
    CSP,
    ECDSAP256PrivateKey,
    ECDSAP256PublicKey,
    Key,
    VerifyBatchItem,
)

# Guarded like fabric_tpu/csp/__init__: the provider itself only needs
# SWCSP for the default host oracle — a caller that supplies its own
# `sw` object (the chaos/degraded-mode tests run one on minimal hosts)
# can use the full device path without the `cryptography` package.
from fabric_tpu.csp.idemix_provider import IdemixCSP

try:
    from fabric_tpu.csp.sw import SWCSP
except ModuleNotFoundError as _exc:  # pragma: no cover - minimal hosts
    if (_exc.name or "").split(".")[0] != "cryptography":
        raise
    SWCSP = None  # type: ignore[assignment]

_BATCH_BUCKETS = (32, 128, 512, 2048, 4096, 8192, 32768)  # single dispatch
# for big batches: per-call overhead beats chunk-pipelining wins
_MAX_CHUNK = 8192  # largest single kernel execution
# a flush of more than one and at most two of these runs as chunks of
# this size (a 1000-tx block at 3-of-5 is 4000 sigs: 2048 + 1952), so
# that whoever collects such a batch alone can hand the first chunk to
# the device while it collects the rest (`TPUCSP.early_chunk`)
_EARLY_CHUNK = 2048

# Who can seal a verified lane's mask (TPUCSP.lane_tally keys, the
# `sealed_by` label of csp_tpu_lanes_total): the device; the host race
# past a stall deadline; the host oracle after a device error at
# dispatch or collect; the open breaker; a batch below
# min_device_batch; the host_fraction tail.
LANE_SEALERS = (
    "device", "host_race", "failover", "breaker", "small", "host_fraction",
)


# (kernel, bucket) pairs this process has enqueued: the first enqueue
# of a pair traces, lowers and compiles (or loads) inside its
# `tpu.enqueue` span, which is then marked `cold`
_enqueued: set = set()

# JAX reports what it traces, lowers and compiles as duration events;
# one listener for the process, registered with the first TPUCSP, turns
# them into a counter on the CSPMetrics bound last and, while tracelens
# is armed, into `jax.compile` instants (inside a cold `tpu.enqueue`
# they say what it spent; under load they are a recompile)
_COMPILE_EVENTS = "/jax/core/compile/"
_compile_lock = threading.Lock()
_compile_listening = False
_compile_metrics: list = [None]


def _on_compile_event(name: str, secs: float, **_kw) -> None:
    if not name.startswith(_COMPILE_EVENTS):
        return
    event = name[len(_COMPILE_EVENTS):]
    metrics = _compile_metrics[0]
    if metrics is not None:
        metrics.compile_events.With("event", event).add()
    # tracing one kernel reports thousands of sub-millisecond nested
    # traces: they count, the ring keeps the ones that took time
    if secs >= 1e-3:
        tracing.instant("jax.compile", event=event, secs=secs)


def _watch_compiles(metrics) -> None:
    global _compile_listening
    import jax.monitoring

    with _compile_lock:
        if metrics is not None:
            _compile_metrics[0] = metrics
        if not _compile_listening:
            jax.monitoring.register_event_duration_secs_listener(
                _on_compile_event
            )
            _compile_listening = True


def _bucket(n: int, buckets) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def _chunk_plan(
    n: int, max_chunk: int = _MAX_CHUNK, min_bucket: int = 0
) -> list[tuple[int, int]]:
    """(lanes, padded_bucket) per kernel execution.  Full chunks run at
    max_chunk; the tail pads to its own bucket instead of inflating the
    whole batch to the next power of two.  A flush of 2,049-4,096 lanes
    is cut at _EARLY_CHUNK instead, whoever sends it and however it is
    dispatched: [(2048, 2048), (n - 2048, its own bucket)], so the 4096
    bucket is named for no n up to max_chunk and a process that hands
    the first chunk over early (`TPUCSP.early_chunk`) uses the shapes
    of one that does not.  min_bucket floors the pad size — the Pallas
    paths pass the kernel block (256) so every chunk is a whole number
    of grid blocks and device placement never falls back to a
    host-side pad."""
    if _EARLY_CHUNK < n <= 2 * _EARLY_CHUNK:
        max_chunk = min(max_chunk, _EARLY_CHUNK)
    out = []
    left = n
    while left > 0:
        take = min(left, max_chunk)
        out.append((take, max(_bucket(take, _BATCH_BUCKETS), min_bucket)))
        left -= take
    return out


class _KeyTable:
    """Persistent unique-public-key table for the dedup kernel variant.

    Blocks reuse the same handful of endorser/client keys, so instead of
    an np.unique pass per batch (argsort over (B, 16) words) the
    provider maintains one SKI-keyed table across batches and emits only
    a u32 index per lane.  The packed (8, KEYTAB) word arrays are
    device_put once and the SAME device buffers ride every subsequent
    verify call — zero re-upload until a new key appears.  On overflow
    the table resets to the current batch's keys; if a single batch
    holds more than KEYTAB distinct keys the caller falls back to the
    per-batch np.unique layout (which itself degrades to per-lane keys).

    That suits a channel with a handful of clients.  Where every user
    holds an enrolment certificate of its own, a two-block flush holds
    some 900 distinct keys, and the table is all or nothing: `assign`
    walks the lanes to the 257th key, clears the table, walks again,
    fails, and `dedup_keys` then runs np.unique over the whole batch to
    hand it over unchanged — three passes a flush to learn "a key a
    lane", and the device's resident copy thrown away each time
    (benchmarks/configs/manyclients-10k.json is the cell that times
    it).  `last_outcome` says how the last batch came out: `resident`
    (no new key), `grown`, `reset` (cleared and refilled) or `per_lane`.
    """

    def __init__(self):
        from fabric_tpu.csp.tpu.pallas_ec import KEYTAB

        self.cap = KEYTAB
        self._idx: dict[bytes, int] = {}
        self._ktabx = np.zeros((8, self.cap), np.uint32)
        self._ktaby = np.zeros((8, self.cap), np.uint32)
        self._dev: tuple | None = None
        self.uploads = 0  # device copies made (one per new key per device)
        self.last_outcome = "resident"

    @staticmethod
    def _words(be32: bytes) -> np.ndarray:
        # 32B big-endian -> 8 little-endian-ordered u32 words
        return np.frombuffer(be32, ">u4")[::-1].astype(np.uint32)

    def _add(self, key) -> int | None:
        j = len(self._idx)
        if j >= self.cap:
            return None
        self._idx[key.ski()] = j
        self._ktabx[:, j] = self._words(key.x_bytes)
        self._ktaby[:, j] = self._words(key.y_bytes)
        self._dev = None  # invalidate every device's cached copy
        return j

    def assign(self, keys) -> np.ndarray | None:
        """Per-lane table indexes for `keys`, or None when even a fresh
        table cannot hold this batch's distinct keys."""
        for attempt in (0, 1):
            held = len(self._idx)
            kidx = np.empty(len(keys), np.uint32)
            ok = True
            for i, k in enumerate(keys):
                j = self._idx.get(k.ski())
                if j is None:
                    j = self._add(k)
                    if j is None:
                        ok = False
                        break
                kidx[i] = j
            if ok:
                self.last_outcome = (
                    "reset" if attempt
                    else "grown" if len(self._idx) > held
                    else "resident"
                )
                return kidx
            # overflow: reset to this batch's working set and retry once
            self._idx.clear()
            self._ktabx[:] = 0
            self._ktaby[:] = 0
            self._dev = None
        self.last_outcome = "per_lane"
        return None

    def device_tables(self, device=None):
        """(ktabx, ktaby) as cached on-device jax arrays, one copy per
        target device (multi-chip dispatch places chunks round-robin)."""
        import jax

        if self._dev is None:
            self._dev = {}
        key = device
        if key not in self._dev:
            self.uploads += 1
            self._dev[key] = (
                jax.device_put(self._ktabx.copy(), device),
                jax.device_put(self._ktaby.copy(), device),
            )
        return self._dev[key]


# Process-wide MEASURED host verification rate (sigs/s), fed by real
# host verifies (_FlushResult._host_verify).  Deadline budgets reserve
# host-race time from what this host actually delivers under its
# current load — a configuration hint can be 20-40% optimistic on a
# contended box, which is exactly the margin a ~450ms latency budget
# cannot afford to lose.
_host_rate_lock = threading.Lock()
_host_rate_ewma: list = [None]


def _note_host_rate(lanes: int, secs: float) -> None:
    if secs <= 0:
        return
    rate = lanes / secs
    with _host_rate_lock:
        cur = _host_rate_ewma[0]
        _host_rate_ewma[0] = rate if cur is None else 0.7 * cur + 0.3 * rate


def _measured_host_rate(default: float) -> float:
    with _host_rate_lock:
        r = _host_rate_ewma[0]
    return r if r else default


def _host_verify_batch(sw: SWCSP, items) -> list[bool]:
    """Host verification preferring the native libcrypto batch
    (native/ecverify.cc) — GIL-free and a multiple of the
    python-per-signature rate on hosts with a fast libcrypto; the
    python engine is the fallback oracle.  Feeds the process-wide
    measured host rate (deadline budgeting reserves race time from
    OBSERVED speed, not the configuration hint)."""
    if not items:
        return []
    from fabric_tpu import native

    t0 = time.perf_counter()
    mask = native.ecdsa_verify_host(items)
    if mask is None:
        mask = sw.verify_batch(items)
    if len(items) >= 256:
        _note_host_rate(len(items), time.perf_counter() - t0)
    return mask


def _knob_int(name: str, default: int) -> int:
    """A registered int knob's value, `default` when unset or
    unparsable (the breaker tolerates garbage rather than refusing to
    start a node over a tuning knob)."""
    raw = knob_registry.raw(name).strip()
    try:
        return int(raw)
    except ValueError:
        return default


class _Breaker:
    """Degraded-mode circuit breaker over the device path (the chaos
    tentpole's hardening half).  `threshold` CONSECUTIVE device-path
    failures — dispatch raising, a flush waiter's collect dying — open
    it; while open, verify_batch routes straight to the host oracle, NO
    queuing, and every `probe_every`-th held verify call first sends a
    tiny probe batch through the device: a probe the DEVICE completes
    closes the breaker and traffic returns.  Knobs: constructor
    arguments, else FABRIC_TPU_BREAKER_THRESHOLD /
    FABRIC_TPU_BREAKER_PROBE_EVERY.  State + trip/probe counts surface
    through a common.metrics.CSPMetrics on /metrics."""

    def __init__(self, threshold: int | None = None,
                 probe_every: int | None = None, metrics=None):
        self.threshold = (
            threshold if threshold is not None
            else _knob_int("FABRIC_TPU_BREAKER_THRESHOLD", 3)
        )
        self.probe_every = (
            probe_every if probe_every is not None
            else _knob_int("FABRIC_TPU_BREAKER_PROBE_EVERY", 8)
        )
        self._lock = threading.Lock()
        self._consecutive = 0
        self._held = 0  # host-served calls since the last probe
        self.open = False
        self.trips = 0
        self.metrics = metrics

    def set_metrics(self, metrics) -> None:
        self.metrics = metrics
        if metrics is not None:
            metrics.breaker_state.set(1 if self.open else 0)

    def record(self, ok: bool) -> None:
        """One device-path outcome (any thread)."""
        with self._lock:
            if ok:
                self._consecutive = 0
                return
            self._consecutive += 1
            if self.metrics is not None:
                self.metrics.device_failures.add()
            if not self.open and self._consecutive >= self.threshold:
                self.open = True
                self.trips += 1
                self._held = 0
                if self.metrics is not None:
                    self.metrics.breaker_state.set(1)
                    self.metrics.breaker_trips.add()
                _logger.warning(
                    "TPU circuit breaker OPEN after %d consecutive "
                    "device failures; verify routed to the host "
                    "path (probe every %d calls)",
                    self._consecutive, self.probe_every,
                )

    def probe_due(self) -> bool:
        """Count one host-served call while open; True when it is this
        call's turn to probe the device."""
        with self._lock:
            if not self.open:
                return False
            self._held += 1
            if self._held >= self.probe_every:
                self._held = 0
                return True
            return False

    def note_probe(self, ok: bool) -> None:
        if self.metrics is not None:
            self.metrics.probes.With(
                "result", "ok" if ok else "fail"
            ).add()

    def close(self) -> None:
        with self._lock:
            was_open = self.open
            self.open = False
            self._consecutive = 0
            if self.metrics is not None:
                self.metrics.breaker_state.set(0)
        if was_open:
            _logger.warning(
                "TPU circuit breaker CLOSED: recovery probe completed "
                "on the device; resuming device dispatch"
            )


class _ProbeKey:
    """Minimal P-256 public-key duck type for the breaker probe: the
    device marshallers and the host oracles only touch the coordinate
    views and the SKI, none of which need the `cryptography` package."""

    def __init__(self, x: int, y: int):
        self.x = x
        self.y = y
        self.x_bytes = x.to_bytes(32, "big")
        self.y_bytes = y.to_bytes(32, "big")
        self._ski = hashlib.sha256(
            b"\x04" + self.x_bytes + self.y_bytes
        ).digest()

    def ski(self) -> bytes:
        return self._ski

    def public_key(self) -> "_ProbeKey":
        return self

    @property
    def is_private(self) -> bool:
        return False


class _FlushResult:
    """One flushed (coalesced) device dispatch: lazy per-chunk
    collectors plus a consumption count so the provider can drop the
    materialized mask once every enqueued segment has read its slice.

    A dedicated WAITER THREAD blocks on the device result the moment
    the flush is dispatched (`start_background`), GIL released, so the
    mask is materialized (device->host copy, lane unpacking) and the
    wall/outcome feedback recorded while the main thread collects block
    k+2 and the committer thread persists block k.  On the directly
    attached v5e the execution itself does NOT need the parked thread:
    a flush dispatched with no waiter and left alone finishes on its
    own and then collects in a fraction of its steady wall
    (chip_smoke.py leg A repeats the check; figures in PERF.md
    "Bring-up").  Materialization is memoized once (`_seal`), so the
    waiter, any number of consuming segments, and a deadline-triggered
    host race all land safely on the one shared mask.

    DEADLINE FALLBACK (p99 control): a stalled or contended device can
    make a flush take many times its usual wall time.  A consumer that
    passes `deadline` seconds waits that long for the waiter, then
    starts verifying the flush's own items on the host in mini-batches,
    polling for device completion in between — whichever side finishes
    first supplies the mask, so a stalled chip costs at most deadline +
    full-host-verify instead of an unbounded chip wait.  Late device
    results are simply discarded.

    Whoever seals the mask reports its lanes once through `on_sealed`
    (the provider's lane tally): "device", "host_race", "failover"
    (device error, host oracle answered) and `host_kind` for the host
    tail.  The same writer ends the flush's detached `tpu.flush` span
    (`span`, handed over by the provider) with `sealed_by`."""

    # host mini-batch between device-completion polls: sized so a poll
    # happens every ~20-100ms — larger when the native batch verifier
    # is in play (its per-call key setup amortizes over the chunk)
    _RACE_STEP = 192
    _RACE_STEP_NATIVE = 1024

    def __init__(self, pending, total_lanes: int,
                 host_items=(), sw: SWCSP | None = None,
                 device_items=None, deadline: float | None = None,
                 on_device_wall=None, on_device_outcome=None,
                 on_sealed=None, host_kind: str = "host_fraction",
                 on_race=None, buckets=()):
        self._pending = pending  # [(collect, kept_lanes)]
        self.buckets = tuple(buckets)  # the padded size of each chunk
        self.span = None  # tracelens `tpu.flush`, dispatch begun -> sealed
        self._mask: list[bool] | None = None
        self._exc: Exception | None = None
        self._outstanding = total_lanes
        # optional tail verified on the host inside the waiter (kept for
        # explicit host_fraction configs; the degraded no-device path
        # also rides this)
        self._host_items = host_items
        self._sw = sw
        # per-lane items of the DEVICE portion, in lane order — the
        # host-race fallback needs them to re-verify independently
        self._device_items = device_items
        self.deadline = deadline
        # deadline-calibration feedback: called (lanes, seconds) when
        # the DEVICE supplied the mask (provider EWMA, see _dispatch)
        self._on_device_wall = on_device_wall
        # circuit-breaker feedback: called (ok: bool) once per flush
        # that had a device portion — True when the device materialized
        # its chunks, False when the device path died mid-flight
        self._on_device_outcome = on_device_outcome
        # lane-tally feedback: called (kind, lanes) by the ONE writer
        # that wins the seal; `host_kind` names the host tail's share
        self._on_sealed = on_sealed
        self._host_kind = host_kind
        # called (won: bool) on the consumer's thread when its deadline
        # expired and the host race ran
        self._on_race = on_race
        # True once the device (not the host fallback) produced the
        # device lanes' mask — the breaker probe's success criterion
        self.device_ok = False
        self._n_device_lanes = len(device_items) if device_items else 0
        self._t0 = time.perf_counter()
        self._seal_lock = threading.Lock()
        self._wait_lock = threading.Lock()
        self._done = threading.Event()
        # set by TPUCSP.drain(): the provider is shutting down, so this
        # flush's wall must not feed the lane-wall EWMA (a drain-time
        # wall measures teardown contention, not chip speed) and its
        # waiter is about to be joined
        self.cancelled = False
        self._waiter: threading.Thread | None = None

    def start_background(self) -> None:
        self._waiter = spawn_thread(
            target=self._wait_device, name="tpu-flush-waiter",
            kind="worker",
        )
        self._waiter.start()

    def _seal(self, mask: list | None, exc: Exception | None = None,
              by: str = "error") -> bool:
        """First writer wins; every consumer wakes.  Drops the input
        references (device collectors, item lists) either way — a flush
        coalesces thousands of VerifyBatchItems and the late loser of a
        host/device race must not pin them (nor device output buffers)
        for the rest of the result's lifetime.  Returns True when THIS
        writer won (its mask/exc is the flush's result)."""
        with self._seal_lock:
            won = self._mask is None and self._exc is None
            if won:
                self._mask = mask
                self._exc = exc
        if won and self.span is not None:
            self.span.annotate(sealed_by=by)
            self.span.end()
        self._pending = ()
        self._host_items = ()
        self._device_items = None
        self._done.set()
        return won

    def _note_sealed(self, kind: str, lanes: int, host_lanes: int) -> None:
        """Report the winning seal: `lanes` device-portion lanes to
        `kind`, the host tail's to its own kind."""
        if self._on_sealed is None:
            return
        if lanes:
            self._on_sealed(kind, lanes)
        if host_lanes:
            self._on_sealed(self._host_kind, host_lanes)

    def _wait_device(self) -> None:
        """Materialize the device result (waiter thread or any direct
        caller); idempotent.  Snapshots the input references up front —
        a concurrently sealing host race clears them (see _seal)."""
        with self._wait_lock:
            if self._done.is_set():
                return
            pending, host_items = self._pending, self._host_items
            device_items = self._device_items
            device_phase = False
            ctx = None if self.span is None else self.span.ctx
            try:
                # host tail FIRST: it runs while the device crunches
                # (that overlap is the whole point of host_fraction);
                # the result order stays device-lanes-then-host-lanes
                host_mask = (
                    self._host_verify(host_items) if host_items else []
                )
                device_phase = True
                if pending:
                    # the device-loss injection seam: a DeviceUnavailable
                    # raised here exercises the mid-flush failover below
                    faultline.point(
                        "tpu.collect", lanes=self._n_device_lanes
                    )
                out: list[bool] = []
                for collect, keep in pending:
                    # pallas chunks hand back a lazy collector; the XLA
                    # fallback hands back the device array itself
                    with tracing.attached(ctx), tracing.span(
                        "tpu.device_wait", lanes=keep,
                    ):
                        mask = (
                            collect() if callable(collect)
                            else np.asarray(collect)
                        )
                    out.extend(bool(v) for v in mask[:keep])
                if pending:
                    self.device_ok = True
                    if self._on_device_outcome is not None:
                        self._on_device_outcome(True)
                out.extend(host_mask)
            except Exception as e:
                # feed the breaker only for DEVICE-phase failures: a
                # host-tail verify dying must not open the breaker and
                # route everything onto the very path that just failed
                if (
                    pending
                    and device_phase
                    and self._on_device_outcome is not None
                ):
                    self._on_device_outcome(False)
                if device_items is not None and self._sw is not None:
                    # device path died mid-flight: the host oracle can
                    # still answer (same degradation _flush_locked
                    # applies to dispatch-time failures)
                    try:
                        out = list(self._host_verify(device_items))
                        out.extend(self._host_verify(host_items))
                        if self._seal(out, by="failover"):
                            self._note_sealed(
                                "failover", len(device_items),
                                len(host_items),
                            )
                        return
                    except Exception as e2:
                        e = e2
                self._seal(None, e)
                return
            won = self._seal(
                out,
                by="device" if self._n_device_lanes else self._host_kind,
            )
            if won:
                self._note_sealed(
                    "device", self._n_device_lanes, len(host_items)
                )
            if (
                won
                and self._on_device_wall is not None
                and self._n_device_lanes
                and not host_items
                and not self.cancelled
            ):
                # feed the provider's flush-wall EWMA — only from walls
                # the device actually produced (a host-race win says
                # nothing about chip speed), only for pure-device
                # flushes (with a host tail the wall includes the
                # tail's serial verify and would inflate the per-lane
                # estimate toward the anchor cap), and only when THIS
                # device result sealed the flush: losing the seal means
                # the host race already answered because the device
                # stalled past its deadline, and feeding that stalled
                # wall would drag the EWMA toward worst-case walls
                self._on_device_wall(
                    self._n_device_lanes, time.perf_counter() - self._t0
                )

    def _host_verify(self, items):
        """Host verification (native libcrypto preferred, python
        fallback) — see the module-level _host_verify_batch."""
        return _host_verify_batch(self._sw, items)

    def _host_race(self) -> bool:
        """Deadline expired: verify this flush's items on the host,
        checking for (and yielding to) device completion between
        mini-batches.  True when the host supplied the mask."""
        device_items, host_items = self._device_items, self._host_items
        if device_items is None:
            return False  # sealed concurrently: use the device mask
        from fabric_tpu import native

        step = (
            self._RACE_STEP_NATIVE
            if native.available()
            else self._RACE_STEP
        )
        items = list(device_items) + list(host_items)
        out: list[bool] = []
        won = True
        for off in range(0, len(items), step):
            if self._done.is_set():
                won = False  # device finished after all — use it
                break
            out.extend(self._host_verify(items[off:off + step]))
        won = won and self._seal(out, by="host_race")
        if won:
            self._note_sealed(
                "host_race", len(device_items), len(host_items)
            )
        if self._on_race is not None:
            self._on_race(won)
        return won

    def collect(self, deadline: float | None = None) -> list[bool]:
        if self._mask is None and self._exc is None:
            deadline = self.deadline if deadline is None else deadline
            if (
                deadline is not None
                and self._device_items is not None
                and self._sw is not None
                and not self._done.wait(deadline)
            ):
                self._host_race()
            if not self._done.is_set():
                self._wait_device()
            self._done.wait()
        if self._exc is not None:
            raise self._exc
        return self._mask

    def consume(self, lanes: int) -> bool:
        """Mark `lanes` result lanes as read; True once all are."""
        self._outstanding -= lanes
        return self._outstanding <= 0


class TPUCSP(CSP):
    """Batched JAX/XLA crypto provider (ECDSA-P256 verify, Idemix)."""

    def __init__(
        self,
        sw: SWCSP | None = None,
        min_device_batch: int = 16,
        coalesce_lanes: int = 6144,
        host_fraction: float = 0.0,
        max_chunk: int = _MAX_CHUNK,
        stall_factor: float | None = 1.0,
        host_rate_hint: float = 9000.0,
        breaker_threshold: int | None = None,
        breaker_probe_every: int | None = None,
        metrics=None,
    ):
        if sw is None:
            if SWCSP is None:
                raise ImportError(
                    "TPUCSP's default host oracle (SWCSP) requires the "
                    "'cryptography' package; pass an explicit `sw` "
                    "provider on hosts without it"
                )
            sw = SWCSP()
        self._sw = sw
        # degraded-mode circuit breaker: consecutive device failures
        # flip every verify to the host oracle (no device queuing)
        # until a periodic probe batch sees the device recover
        self._breaker = _Breaker(
            breaker_threshold, breaker_probe_every, metrics
        )
        self._probe_cache: list | None = None
        # Below this size, host verify wins on latency (device dispatch
        # overhead); the sw provider is also the fallback oracle.
        self._min_device_batch = min_device_batch
        self._key_table = _KeyTable()
        # -- cross-call coalescing (TPU path): every kernel execution
        # carries a fixed scheduling/program cost, so async batches are
        # buffered and flushed together — either when `coalesce_lanes`
        # lanes are pending (keeps dispatch eager enough to overlap the
        # caller's next collect phase) or when the first collector is
        # invoked (correctness).  Callers that pipeline blocks get ~2
        # blocks per execution for free.
        self._coalesce = max(1, coalesce_lanes)
        # fraction of each flush verified host-side in the waiter thread.
        # Default 0: flushes pad to power-of-two kernel buckets, so
        # shaving a sub-bucket tail saves no device time at all, and the
        # pipelined callers need the host core for collect/commit work.
        # Chip-stall protection is the collector's deadline fallback,
        # not a pre-committed split.
        self._host_fraction = host_fraction
        # -- stall deadline (p99 control): a consumer that finds its
        # flush unfinished at the deadline starts racing the chip with
        # host verification (see _FlushResult).  The deadline is a
        # PER-BLOCK LATENCY BUDGET: 1.5x the EWMA-predicted flush wall
        # (per-lane rate learned from completed device flushes, floor
        # 0.15 s), CAPPED by the host anchor
        # `stall_factor * lanes / host_rate` — the cap keeps a
        # chronically slow device from normalizing its own slowness
        # into ever-longer deadlines: per-flush wall stays near 2x the
        # pure-host cost in the worst window, and in ordinary windows
        # the EWMA keeps the race trigger tight enough that a single
        # stalled flush costs ~deadline + host-verify, not the anchor.
        # None switches the race off (chip_smoke.py leg A: a mask can
        # then come only from the device or a counted failure path).
        self._stall_factor = stall_factor
        self._host_rate = host_rate_hint
        self._lane_wall_ewma: float | None = None  # s/lane, device flushes
        self._ewma_lock = threading.Lock()
        # the coalescing lane state behind this lock is racecheck's
        # declared-guard territory (devtools/guards.py): created through
        # the lockwatch seam so tier-1 cross-checks the guard at runtime
        self._pend_lock = named_rlock("csp.tpu.pend")
        self._pend_batches: list = []  # list[Sequence[VerifyBatchItem]]
        self._pend_lanes = 0
        self._flushed: dict[int, object] = {}  # gen -> _FlushResult
        # every dispatched flush, kept until its waiter thread exits —
        # drain() joins these so NO tpu-flush-waiter can still be parked
        # inside an XLA kernel when the interpreter exits (the rc=134
        # "FATAL: exception not rethrown" teardown abort)
        self._inflight: list = []
        self._gen = 0
        self._max_chunk = max_chunk
        # -- multi-device sharding (SURVEY.md §2.9): chunks place
        # round-robin across every visible device — verification is
        # embarrassingly parallel, so data-parallel placement with no
        # collectives is the idiomatic mesh layout, and each device
        # crunches its chunk while the host marshals the next.
        self.last_dispatch_devices: tuple = ()
        # -- lane tally: who sealed each verified lane's mask.  Written
        # from callers, consumers (host race) and tpu-flush-waiter
        # threads, so every access goes through _tally_lock.
        self._metrics = metrics
        self._tally_lock = threading.Lock()
        self._lane_tally = dict.fromkeys(LANE_SEALERS, 0)
        # flushes so far, the batches (a block each under store_stream)
        # they took in, and the flushes that took in one alone; written
        # under _pend_lock by _flush_locked (flush_tally)
        self._flush_tally = {"flushes": 0, "segments": 0, "lone": 0}
        # the second kernel's provider (BN254: a channel's Idemix
        # credential proofs and pseudonym signatures); an IdemixMSP
        # built with this CSP verifies through it, and drain() and
        # close() join its flush workers with this provider's waiters
        self.idemix = IdemixCSP(metrics=metrics)
        _watch_compiles(metrics)

    # -- lifecycle ---------------------------------------------------------

    @staticmethod
    def device_info() -> dict:
        """The accelerator as JAX reports it — every entry point that
        claims a device run (chip_smoke.py, benchmarks/run.py, `peer node
        start` on this provider) prints this, so a host run cannot pass for
        one.  Initializes the backend; raises where JAX cannot."""
        import jax

        devices = jax.devices()
        return {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        }

    def set_metrics(self, metrics) -> None:
        """Bind a common.metrics.CSPMetrics (e.g. from
        operations.System.csp_metrics()) so breaker state/trips,
        device failures and the lane tally surface on /metrics."""
        self._breaker.set_metrics(metrics)
        self._metrics = metrics
        self.idemix.set_metrics(metrics)
        _watch_compiles(metrics)

    def lane_tally(self) -> dict[str, int]:
        """Lanes verified so far, keyed by who sealed their mask
        (LANE_SEALERS).  The values add up to the lanes submitted to
        verify_batch/verify_batch_async whose collectors completed; a
        run that verified on the chip alone shows everything under
        "device"."""
        with self._tally_lock:
            return dict(self._lane_tally)

    def flush_tally(self) -> dict[str, int]:
        """Device flushes so far: `flushes`, the `segments` they took
        in (verify_batch_async batches: a block each while a peer
        streams blocks) and the `lone` flushes that took in one batch
        alone.  segments / flushes is blocks a flush, as
        csp_tpu_flush_segments_total over csp_tpu_dispatches_total is
        on /metrics; a stream whose flushes are mostly `lone` overlaps
        no block's verification with another's."""
        with self._pend_lock:
            return dict(self._flush_tally)

    def _note_sealed(self, kind: str, lanes: int) -> None:
        with self._tally_lock:
            self._lane_tally[kind] += lanes
        if self._metrics is not None:
            self._metrics.lanes.With("sealed_by", kind).add(lanes)

    def _note_race(self, won: bool) -> None:
        """A consumer's deadline expired and it raced the chip on the
        host; runs on that consumer's thread, inside its `tpu.collect`
        span."""
        tracing.annotate(raced=True, race_won=won)
        if self._metrics is not None:
            self._metrics.host_races.With(
                "outcome", "won" if won else "lost"
            ).add()

    @contextlib.contextmanager
    def _enqueue_span(self, kernel: str, lanes: int, bucket: int, dev):
        """Count one chunk's enqueue by bucket and span it."""
        cold = (kernel, bucket) not in _enqueued
        _enqueued.add((kernel, bucket))
        if self._metrics is not None:
            self._metrics.dispatches.With("bucket", str(bucket)).add()
        with tracing.span(
            "tpu.enqueue", lanes=lanes, bucket=bucket, kernel=kernel,
            device=0 if dev is None else dev.id, cold=cold,
        ):
            yield
        if cold:
            # trace-and-lower left a heap behind that lives as long as
            # the process: keep every later collection off it
            gcpolicy.absorb()

    @property
    def breaker(self) -> "_Breaker":
        """The degraded-mode circuit breaker (tests/diagnostics)."""
        return self._breaker

    @property
    def breaker_open(self) -> bool:
        """True while verify is served by the host oracle."""
        return self._breaker.open

    def health_checker(self):
        """A /healthz checker: the node still SERVES while degraded
        (the host oracle answers), but an open breaker is exactly what
        an operator's health rollup should surface — netscope's health
        timeline reads the failure reason from ?detail=1."""

        def check() -> bool:
            if self._breaker.open:
                raise RuntimeError(
                    "TPU degraded: circuit breaker open after "
                    f"{self._breaker.trips} trip(s); verify "
                    "served by the host oracle"
                )
            return True

        return check

    def drain(self, timeout: float | None = 60.0) -> bool:
        """Quiesce the provider: flush anything still buffered (so no
        collector can dangle) and JOIN every in-flight flush waiter.

        This is the missing lifecycle API behind the MULTICHIP rc=134
        regression: a `tpu-flush-waiter` daemon thread still blocked in
        an XLA kernel at interpreter exit gets pthread-killed, the
        forced unwind crosses XLA's catch(...), and glibc aborts with
        "FATAL: exception not rethrown".  Callers (benchmarks/run.py,
        the multichip dryrun, node shutdown) drain before exiting instead
        of papering over the abort with os._exit(0).

        Every in-flight flush is marked cancelled first so a wall
        completed during teardown never feeds the lane-wall EWMA.
        Returns True when every waiter finished inside `timeout`
        (None = wait indefinitely); False leaves the stragglers
        running — the caller can report and decide, but should NOT
        exit the interpreter under them.

        The join loop re-snapshots until it finds nothing alive: a
        dispatch racing the first snapshot (another thread calling
        verify_batch while we drain) is caught — and cancelled — by
        the next pass, so the close() guarantee holds without freezing
        concurrent verifiers out of the provider."""
        deadline = (
            None if timeout is None else time.monotonic() + timeout
        )
        if not self.idemix.drain(timeout):
            return False
        while True:
            with self._pend_lock:
                if self._pend_batches:
                    self._flush_locked()
                for res in self._inflight:
                    res.cancelled = True
                live = [
                    r for r in self._inflight
                    if r._waiter is not None and r._waiter.is_alive()
                ]
                if not live:
                    self._inflight = []
                    return True
            for res in live:
                th = res._waiter
                if deadline is None:
                    th.join()
                else:
                    th.join(max(0.0, deadline - time.monotonic()))
                    if th.is_alive():
                        with self._pend_lock:
                            self._inflight = [
                                r for r in self._inflight
                                if r._waiter is not None
                                and r._waiter.is_alive()
                            ]
                        return False

    def close(self) -> None:
        """drain() with the indefinite wait: the provider guarantees no
        worker thread survives close()."""
        self.drain(timeout=None)

    # -- key management / signing: host side ------------------------------

    def key_gen(self) -> ECDSAP256PrivateKey:
        return self._sw.key_gen()

    def key_import(self, raw: bytes, private: bool = False) -> Key:
        return self._sw.key_import(raw, private)

    def get_key(self, ski: bytes) -> Key:
        return self._sw.get_key(ski)

    def sign(self, key: Key, digest: bytes) -> bytes:
        return self._sw.sign(key, digest)

    # -- hashing -----------------------------------------------------------

    def hash(self, msg: bytes) -> bytes:
        return hashlib.sha256(msg).digest()

    def hash_batch(self, msgs: Sequence[bytes]) -> list[bytes]:
        # the host at every size (README "Deliberate cuts")
        return [hashlib.sha256(m).digest() for m in msgs]

    # -- verification ------------------------------------------------------

    def verify(self, key: Key, signature: bytes, digest: bytes) -> bool:
        return self._sw.verify(key, signature, digest)

    def verify_batch(self, items: Sequence[VerifyBatchItem]) -> list[bool]:
        return self.verify_batch_async(items)()

    def early_chunk(self, lanes: int) -> int | None:
        """Where a batch of `lanes` that is collected alone is cut: the
        lanes of the chunk plan's first chunk when the plan has more
        than one, else None.  The validator of a lone block hands that
        many over with `flush=True` as soon as it holds them, and the
        rest at the end of its collect, so the device runs the first
        chunk while the host collects the second (a flush that was one
        dispatch of these same chunks at the end).  None too while this
        process has not enqueued the first chunk's bucket yet: that
        enqueue traces and lowers the kernel shape for seconds, which
        is no time to take out of the middle of a collect, so the first
        such batch goes out whole and warms the shape at its end."""
        plan = _chunk_plan(lanes, self._max_chunk, min_bucket=256)
        if len(plan) < 2:
            return None
        take, bucket = plan[0]
        if not any(b == bucket for _kernel, b in _enqueued):
            return None
        return take

    def verify_batch_async(self, items: Sequence[VerifyBatchItem],
                           flush: bool = False):
        """Enqueue a batch, return its collector.

        Batches are COALESCED across calls: every kernel execution pays
        a fixed scheduling/program cost on top of its per-lane time, so
        consecutive async batches (e.g. the pipelined txvalidator's
        per-block dispatches) are buffered and flushed as one device
        call — when `coalesce_lanes` lanes are pending, or at the first
        collector invocation.  The device still executes asynchronously
        after the flush, so pipelined callers keep their host/device
        overlap while paying the fixed cost once per ~2 blocks.

        `flush=True` dispatches what is pending, this batch included,
        before it returns: for a caller with nothing behind it to hide
        the flush (a lone block's first `early_chunk`), which goes on
        collecting while the device runs.  Each flush is a generation
        of its own, and a collector returns its own segment's mask."""
        if len(items) < self._min_device_batch:
            # too small for the device: verified here, on the caller's
            # thread (the validator's `collect`), to the same rule; the
            # span says so, for without it the time is `collect`'s
            with tracing.span("tpu.small", lanes=len(items)):
                result = self._sw.verify_batch(items)
            self._note_sealed("small", len(items))
            if self._metrics is not None:
                self._metrics.small_batches.add()
            return lambda: result
        if self._breaker_gate():
            # degraded mode: the device is failing, so serve from the
            # host oracle with NO device queuing (the gate already ran
            # this call's recovery probe if it was due)
            mask = _host_verify_batch(self._sw, list(items))
            self._note_sealed("breaker", len(items))
            return lambda: mask
        with self._pend_lock:
            gen = self._gen
            seg_start = self._pend_lanes
            self._pend_batches.append(items)
            self._pend_lanes += len(items)
            if flush or self._pend_lanes >= self._coalesce:
                self._flush_locked(early=flush)
        n = len(items)

        memo: list = []

        def collector():
            with self._pend_lock:
                # memo check under the lock: two first-calls racing
                # would otherwise double-consume the flush and pop the
                # generation out from under its other segments
                if memo:  # idempotent: repeat calls see the same mask
                    return memo[0]
                res = self._flushed.get(gen)
                if res is None:
                    self._flush_locked()
                    res = self._flushed[gen]
                # sole-flush consumer (serial per-block validate — the
                # p99 path): nothing else is in flight, the host is
                # idle, so the tighter ABSOLUTE latency budget applies
                sole = len(self._flushed) <= 1 and not self._pend_batches
            deadline = None
            if sole and res.deadline is not None:
                deadline = self._sole_deadline_for(res._n_device_lanes)
            budget = res.deadline if deadline is None else deadline
            with tracing.span(
                "tpu.collect", batch=gen, lanes=n,
                device_lanes=res._n_device_lanes, sole=sole,
                deadline_ms=None if budget is None else budget * 1e3,
                raced=False,  # _note_race rewrites it
            ):
                mask = res.collect(deadline)
                if tracing.enabled():
                    # the two measurements the deadlines follow
                    with self._ewma_lock:
                        wall = self._lane_wall_ewma
                    tracing.annotate(
                        lane_wall_ewma_us=None if wall is None else wall * 1e6,
                        host_rate_ewma=_host_rate_ewma[0],
                    )
            out = mask[seg_start:seg_start + n]
            with self._pend_lock:
                if memo:  # lost a race after collect: keep first result
                    return memo[0]
                memo.append(out)
                if res.consume(n):
                    self._flushed.pop(gen, None)
            return out

        return collector

    def _flush_locked(self, early: bool = False) -> None:
        """Dispatch every pending batch as one chunked device call and
        advance the generation.  Caller holds _pend_lock.  `early`: a
        caller asked for it (`verify_batch_async(flush=True)`) while it
        still collects what the next flush will carry."""
        guarded(self, "_pend_batches", by="csp.tpu.pend")
        items: list = []
        segments = self._pend_batches
        for b in segments:
            items.extend(b)
        self._pend_batches = []
        self._pend_lanes = 0
        gen = self._gen
        self._gen += 1
        tally = self._flush_tally
        tally["flushes"] += 1
        tally["segments"] += len(segments)
        if len(segments) == 1:
            tally["lone"] += 1
        if self._metrics is not None:
            self._metrics.flush_segments.add(len(segments))
            if early:
                self._metrics.early_flushes.add()
        # dispatch begun -> mask sealed, ended by whoever seals it; it
        # shares `batch` with tpu.dispatch and the segments' tpu.collect.
        # `segments`: the verify_batch_async batches it took in (a block
        # each under store_stream), `segment_lanes` their lanes in order
        fspan = tracing.begin(
            "tpu.flush", detach=True, batch=gen, lanes=len(items),
            segments=len(segments), early=early,
        )
        if tracing.enabled():
            fspan.annotate(segment_lanes=[len(b) for b in segments])
        try:
            with tracing.span(
                "tpu.dispatch", parent=fspan.ctx, batch=gen,
                lanes=len(items),
            ):
                res = self._dispatch(items)
            res.span = fspan
            fspan.annotate(
                buckets=list(res.buckets),
                deadline_ms=(
                    None if res.deadline is None else res.deadline * 1e3
                ),
            )
            # park a waiter on the device result NOW, so the mask is
            # materialized off the caller's thread (see _FlushResult)
            res.start_background()
        except Exception:
            # a failed dispatch must not strand the other coalesced
            # batches' collectors (their items are already dequeued):
            # degrade the whole flush to the host oracle, lazily — and
            # loudly: a Mosaic compile error lands here too
            self._breaker.record(False)
            _logger.warning(
                "device dispatch of %d lanes failed; serving the flush "
                "from the host oracle", len(items), exc_info=True,
            )
            res = _FlushResult(
                [], len(items), host_items=items, sw=self._sw,
                on_sealed=self._note_sealed, host_kind="failover",
            )
            res.span = fspan
        self._flushed[gen] = res
        self._inflight = [
            r for r in self._inflight
            if r._waiter is not None and r._waiter.is_alive()
        ]
        self._inflight.append(res)

    # Fixed known-good P-256 probe vector (key/signature precomputed for
    # digest = SHA-256("faultline-breaker-probe")): the recovery probe
    # must work with ANY host oracle, including minimal hosts where the
    # sw provider (and thus key_gen/sign) is unavailable.
    _PROBE_QX = 0x46464CED59A558637321A8AB0D957C71C46162990C1311469A8FC24032FEC1E3
    _PROBE_QY = 0xDE57524FDD4A8DBC03E77BE70FAA656B2F12A7B34BA3CCAADBC042640104E4ED
    _PROBE_R = 0x2C63F9FD69C2C999966BDF5ACEB3E114A42C852AB7AF88870E7D29CB4C5AC471
    _PROBE_S = 0x767B9BC011A2EC87635DFEAB8334A15995113A67176CA4D02F706D316C9EB86F

    def _probe_items(self) -> list:
        """A tiny cached known-good batch for breaker recovery probes
        (one fixed public key + signature, duplicated to two lanes)."""
        if self._probe_cache is None:
            key = _ProbeKey(self._PROBE_QX, self._PROBE_QY)
            digest = self.hash(b"faultline-breaker-probe")
            sig = api.marshal_ecdsa_signature(self._PROBE_R, self._PROBE_S)
            item = VerifyBatchItem(key, digest, sig)
            self._probe_cache = [item, item]
        return self._probe_cache

    def _breaker_gate(self) -> bool:
        """Degraded-mode routing decision: while the breaker is open,
        run the periodic recovery probe when due; True when this call
        must be served by the host path (still open afterwards)."""
        if not self._breaker.open:
            return False
        if self._breaker.probe_due():
            ok = self._probe_device()
            self._breaker.note_probe(ok)
            if ok:
                self._breaker.close()
        return self._breaker.open

    def _probe_device(self) -> bool:
        """One probe batch straight through the device path, collected
        synchronously; True only when the DEVICE (not the host
        fallback) produced an all-valid mask."""
        try:
            res = self._dispatch(list(self._probe_items()))
        except Exception:
            return False
        res._on_sealed = None  # the provider's own lanes, not submitted work
        res._wait_device()
        try:
            mask = res.collect()
        except Exception:
            return False
        return res.device_ok and all(mask)

    def _dispatch(self, items) -> "_FlushResult":
        import jax

        faultline.point("tpu.dispatch", lanes=len(items))

        # local_devices: on a multi-host pod, jax.devices() includes
        # devices other processes own; device_put to those raises
        devices = jax.local_devices()
        used: list = []

        def place(i: int):
            """Round-robin target for chunk i; None = default device.
            Pallas chunks are always padded to whole kernel blocks
            (min_bucket=256 in their _chunk_plan), so placement never
            triggers a host-side pad in verify_packed."""
            if len(devices) <= 1:
                return None
            dev = devices[i % len(devices)]
            used.append(dev)
            return dev

        # Hybrid split (both backends): a tail of the flush verifies on
        # the host DURING the device wait (see _FlushResult.collect) —
        # sized so host time stays under the device execution's fixed
        # cost.  The virtual-mesh dryrun leans on this to keep its
        # device leg small while still exercising real mesh dispatch.
        host_items: Sequence[VerifyBatchItem] = ()
        if self._host_fraction > 0 and len(items) >= 2048:
            h = int(len(items) * self._host_fraction)
            if h:
                host_items = items[len(items) - h:]
                items = items[:len(items) - h]

        if jax.default_backend() != "tpu":
            # The fused kernel is TPU-only (Mosaic); other backends get
            # the portable XLA kernel (interpreted Pallas would be
            # orders of magnitude slower on CPU test runs).  Dispatch is
            # async here too (JAX queues the computation); only the
            # np.asarray conversion blocks, and it lives in the
            # collector so pipelined callers keep their overlap.
            from fabric_tpu.csp.tpu import ec

            pending, buckets = [], []
            with tracing.span("tpu.marshal", lanes=len(items)):
                chunks = list(self._tuple_chunks(items))
            for i, (chunk, keep) in enumerate(chunks):
                with tracing.span("tpu.marshal", lanes=keep):
                    prep = ec.prepare_batch(chunk)
                dev = place(i)
                buckets.append(len(chunk))
                with self._enqueue_span(
                    "xla_p256_verify", keep, len(chunk), dev
                ):
                    if dev is not None:
                        prep = {
                            k: jax.device_put(v, dev)
                            for k, v in prep.items()
                        }
                    pending.append((ec.verify_prepared(**prep), keep))
            self.last_dispatch_devices = tuple(dict.fromkeys(used))
            return _FlushResult(
                pending, len(items) + len(host_items),
                host_items=host_items, sw=self._sw,
                device_items=list(items),
                on_device_outcome=self._breaker.record,
                on_sealed=self._note_sealed,
                on_race=self._note_race, buckets=buckets,
            )

        from fabric_tpu.csp.tpu import pallas_ec

        # Chunked pipeline over the fused Pallas kernel: every chunk is
        # dispatched (host prep + async device call) before any result is
        # collected, so host packing and the host->device hop of chunk
        # k+1 overlap chunk k's device time.  Host prep runs in the C++
        # marshaller when available (DER + prechecks + batch inversion +
        # packing in one pass), else the numpy path.
        with tracing.span("tpu.marshal", lanes=len(items)):
            packed_all = self._marshal_native(items)
        pending, buckets = [], []
        if packed_all is not None:
            plan = _chunk_plan(len(items), self._max_chunk, min_bucket=256)
            with tracing.span("tpu.keytable") as kspan:
                # persistent SKI-keyed table: per-lane keys collapse to
                # a u32 index, and the table buffers stay resident on
                # device across blocks (uploaded again only when a new
                # key shows up); chunks slice only the per-lane arrays
                # (the shared ktab rides along by reference)
                kidx = self._key_table.assign(
                    [
                        it.key.public_key()
                        if isinstance(it.key, ECDSAP256PrivateKey)
                        else it.key
                        for it in items
                    ]
                )
                use_table = kidx is not None
                if use_table:
                    packed_all = {
                        k: v
                        for k, v in packed_all.items()
                        if k not in ("qx", "qy")
                    }
                    packed_all["kidx"] = kidx
                    # one resident copy per device the chunks go to
                    uploads = self._key_table.uploads
                    targets = (
                        [None] if len(devices) <= 1
                        else devices[:len(plan)]
                    )
                    for dev in targets:
                        self._key_table.device_tables(dev)
                    kspan.annotate(
                        uploaded=self._key_table.uploads != uploads
                    )
                    if tracing.enabled():
                        kspan.annotate(distinct=int(np.count_nonzero(
                            np.bincount(kidx, minlength=self._key_table.cap)
                        )))
                else:
                    seen: dict = {}
                    packed_all = pallas_ec.dedup_keys(packed_all, seen)
                    kspan.annotate(**seen)
                outcome = self._key_table.last_outcome
                kspan.annotate(outcome=outcome)
                if self._metrics is not None:
                    self._metrics.keytable_flushes.With(
                        "outcome", outcome
                    ).add()
            shared = ("ktabx", "ktaby")
            kernel = (
                "pallas_ec_p256_verify_ktab" if "kidx" in packed_all
                else "pallas_ec_p256_verify"
            )
            off = 0
            for i, (take, bsz) in enumerate(plan):
                dev = place(i)
                buckets.append(bsz)
                with self._enqueue_span(kernel, take, bsz, dev):
                    sl = {}
                    for k, v in packed_all.items():
                        if k in shared:
                            sl[k] = v
                        elif v.ndim == 2:
                            sl[k] = v[:, off:off + take]
                        else:
                            sl[k] = v[off:off + take]
                    off += take
                    if take < bsz:
                        # zero-pad (valid=False lanes) to the bucket
                        # size so every chunk reuses the same compiled
                        # kernel shape
                        sl = {
                            k: (v if k in shared else np.concatenate(
                                [v, np.zeros(
                                    v.shape[:-1] + (bsz - take,), v.dtype
                                )],
                                axis=-1,
                            ))
                            for k, v in sl.items()
                        }
                    if dev is not None:
                        # cand1_ok/valid stay host-side: verify_packed
                        # np.asarray's them into its flags stack anyway
                        host_side = ("cand1_ok", "valid")
                        sl = {
                            k: (
                                v
                                if k in shared or k in host_side
                                else jax.device_put(v, dev)
                            )
                            for k, v in sl.items()
                        }
                    if use_table:
                        sl["ktabx"], sl["ktaby"] = (
                            self._key_table.device_tables(dev)
                        )
                    pending.append((pallas_ec.verify_packed(sl), take))
        else:
            with tracing.span("tpu.marshal", lanes=len(items)):
                chunks = list(self._tuple_chunks(items, min_bucket=256))
            for i, (chunk, keep) in enumerate(chunks):
                with tracing.span("tpu.marshal", lanes=keep):
                    packed = pallas_ec.dedup_keys(
                        pallas_ec.prepare_packed(chunk)
                    )
                dev = place(i)
                buckets.append(len(chunk))
                with self._enqueue_span(
                    "pallas_ec_p256_verify_ktab" if "kidx" in packed
                    else "pallas_ec_p256_verify",
                    keep, len(chunk), dev,
                ):
                    if dev is not None:
                        packed = {
                            k: jax.device_put(v, dev)
                            for k, v in packed.items()
                        }
                    pending.append((pallas_ec.verify_packed(packed), keep))
        self.last_dispatch_devices = tuple(dict.fromkeys(used))
        return _FlushResult(
            pending, len(items) + len(host_items),
            host_items=host_items, sw=self._sw,
            device_items=list(items),
            deadline=self._deadline_for(len(items)),
            on_device_wall=self._note_device_wall,
            on_device_outcome=self._breaker.record,
            on_sealed=self._note_sealed,
            on_race=self._note_race, buckets=buckets,
        )

    def _note_device_wall(self, lanes: int, wall: float) -> None:
        """EWMA of per-lane device flush wall (dispatch -> mask),
        fed only by flushes the DEVICE completed."""
        if lanes <= 0 or wall <= 0:
            return
        per_lane = wall / lanes
        with self._ewma_lock:
            cur = self._lane_wall_ewma
            self._lane_wall_ewma = (
                per_lane if cur is None else 0.7 * cur + 0.3 * per_lane
            )

    def _deadline_for(self, lanes: int) -> float | None:
        """Per-flush latency budget: 1.5x the EWMA-predicted wall,
        floored at 0.15 s, capped by the host anchor (see __init__)."""
        if self._stall_factor is None:
            return None
        anchor = max(
            0.2,
            self._stall_factor * lanes / _measured_host_rate(self._host_rate),
        )
        with self._ewma_lock:
            per_lane = self._lane_wall_ewma
        if per_lane is None:
            return anchor
        return max(0.15, min(1.5 * per_lane * lanes, anchor))

    # absolute per-block latency budget for the SOLE-flush case: the
    # serial consumer (per-block validate latency, the p99 metric) has
    # an idle host, so racing early is free — budget the deadline so
    # deadline + host-race stays under ~420 ms even in a chip window
    # whose ORDINARY flush wall would push the pipelined EWMA deadline
    # past it.  The race reserve uses the MEASURED host rate; the floor
    # is low because a too-early race on this path costs only one
    # wasted poll chunk of an otherwise idle host.
    _SOLE_BUDGET_S = 0.42

    def _sole_deadline_for(self, lanes: int) -> float | None:
        base = self._deadline_for(lanes)
        if base is None:
            return None
        race_est = lanes / _measured_host_rate(self._host_rate)
        return max(0.05, min(base, self._SOLE_BUDGET_S - race_est))

    def _tuple_chunks(self, items, min_bucket: int = 0):
        """(padded tuple chunk, kept lanes) pairs for the non-native
        prep paths (Python-side DER parse)."""
        tuples = []
        for it in items:
            key = it.key
            if isinstance(key, ECDSAP256PrivateKey):
                key = key.public_key()
            try:
                r, s = api.unmarshal_ecdsa_signature(it.signature)
            except ValueError:
                r, s = -1, -1  # prepare marks the lane invalid
            tuples.append((key.x, key.y, it.digest, r, s))
        off = 0
        for take, bsz in _chunk_plan(len(tuples), self._max_chunk, min_bucket):
            chunk = tuples[off:off + take]
            off += take
            chunk = chunk + [
                (api.P256_GX, api.P256_GY, b"", -1, -1)
            ] * (bsz - take)
            yield chunk, take

    @staticmethod
    def _marshal_native(items) -> dict | None:
        from fabric_tpu import native

        if not native.available():
            return None
        xs, ys, digs, sigs, offs = [], [], [], [], [0]
        bad_digest = []
        for i, it in enumerate(items):
            key = it.key
            if isinstance(key, ECDSAP256PrivateKey):
                key = key.public_key()
            xs.append(key.x_bytes)
            ys.append(key.y_bytes)
            if len(it.digest) == 32:
                digs.append(it.digest)
            else:
                digs.append(b"\0" * 32)
                bad_digest.append(i)
            sigs.append(it.signature)
            offs.append(offs[-1] + len(it.signature))
        packed = native.marshal_batch(
            b"".join(xs), b"".join(ys), b"".join(digs), b"".join(sigs),
            np.asarray(offs, np.int32),
        )
        if packed is not None and bad_digest:
            packed["valid"][bad_digest] = False
        return packed


__all__ = ["TPUCSP", "LANE_SEALERS"]
