"""Metrics provider SPI + prometheus-text / statsd-line / disabled impls.

Reference: common/metrics — provider SPI (provider.go: Counter/Gauge/
Histogram created from *Opts, each supporting With(label pairs)),
prometheus provider (prometheus/provider.go:20-48), statsd provider
(statsd/provider.go with go-kit), disabled provider, and the gendoc
metric catalog.  The operations server (fabric_tpu/common/operations.py)
scrapes `PrometheusRegistry.expose()` for its /metrics endpoint.
"""

from __future__ import annotations

import dataclasses
import math
import threading
from typing import Sequence

from fabric_tpu.common import tracing


@dataclasses.dataclass(frozen=True)
class CounterOpts:
    namespace: str = ""
    subsystem: str = ""
    name: str = ""
    help: str = ""
    label_names: tuple[str, ...] = ()
    statsd_format: str = ""


@dataclasses.dataclass(frozen=True)
class GaugeOpts:
    namespace: str = ""
    subsystem: str = ""
    name: str = ""
    help: str = ""
    label_names: tuple[str, ...] = ()
    statsd_format: str = ""


@dataclasses.dataclass(frozen=True)
class HistogramOpts:
    namespace: str = ""
    subsystem: str = ""
    name: str = ""
    help: str = ""
    label_names: tuple[str, ...] = ()
    buckets: tuple[float, ...] = (
        0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10,
    )
    statsd_format: str = ""


def _fqname(opts) -> str:
    return "_".join(p for p in (opts.namespace, opts.subsystem, opts.name) if p)


def _label_key(
    label_names: Sequence[str], label_values: Sequence[str]
) -> tuple[tuple[str, str], ...]:
    if len(label_values) % 2 == 0 and not label_names:
        # With("name", "value", ...) pairs form
        it = iter(label_values)
        return tuple(sorted(zip(it, it)))
    raise ValueError("labels must be alternating name/value pairs")


class _Metric:
    """Base: holds per-labelset series."""

    def __init__(self, opts, registry):
        self.opts = opts
        self.name = _fqname(opts)
        self._series: dict[tuple, float] = {}
        self._lock = threading.Lock()
        self._labels: tuple[tuple[str, str], ...] = ()
        if registry is not None:
            registry._register(self)

    def with_labels(self, *pairs: str) -> "_Metric":
        c = type(self).__new__(type(self))
        c.opts = self.opts
        c.name = self.name
        c._series = self._series
        c._lock = self._lock
        it = iter(pairs)
        c._labels = tuple(sorted(self._labels + tuple(zip(it, it))))
        return c

    # go-kit naming
    With = with_labels


class Counter(_Metric):
    def add(self, delta: float = 1.0) -> None:
        with self._lock:
            self._series[self._labels] = (
                self._series.get(self._labels, 0.0) + delta
            )


class Gauge(_Metric):
    def set(self, value: float) -> None:
        with self._lock:
            self._series[self._labels] = value

    def add(self, delta: float) -> None:
        with self._lock:
            self._series[self._labels] = (
                self._series.get(self._labels, 0.0) + delta
            )


class Histogram(_Metric):
    def __init__(self, opts, registry):
        super().__init__(opts, registry)
        self._obs: dict[tuple, list] = {}

    def with_labels(self, *pairs: str) -> "Histogram":
        c = super().with_labels(*pairs)
        c._obs = self._obs
        return c

    With = with_labels

    def observe(self, value: float) -> None:
        with self._lock:
            rec = self._obs.setdefault(
                self._labels, [0, 0.0, [0] * len(self.opts.buckets)]
            )
            rec[0] += 1
            rec[1] += value
            # per-bucket counts are NON-cumulative here; expose()
            # cumulates once.  (The old form incremented every bucket
            # >= value AND re-cumulated at exposition, so a rendered
            # _bucket count could exceed _count — non-monotonic output
            # that a strict scraper rejects.)
            for i, b in enumerate(self.opts.buckets):
                if value <= b:
                    rec[2][i] += 1
                    break


class PrometheusRegistry:
    """Collects metrics and renders the prometheus text format for the
    operations endpoint."""

    def __init__(self):
        self._metrics: list[_Metric] = []
        self._collectors: list = []
        self._lock = threading.Lock()

    def _register(self, m: _Metric) -> None:
        with self._lock:
            self._metrics.append(m)

    def register_collector(self, fn) -> None:
        """Register a zero-arg callable invoked at the top of every
        expose() — the prometheus Collector idiom for values that are
        READ at scrape time rather than observed as they change
        (process CPU/RSS/fds, GC totals).  A collector that raises is
        skipped for that scrape, never fails the endpoint."""
        with self._lock:
            self._collectors.append(fn)

    @staticmethod
    def _escape_label_value(v) -> str:
        """Prometheus text-format label-value escaping: backslash,
        double quote, and newline (exposition format spec) — a label
        value carrying any of them must not corrupt the line framing
        the netscope parser (and any real scraper) relies on."""
        return (
            str(v)
            .replace("\\", "\\\\")
            .replace('"', '\\"')
            .replace("\n", "\\n")
        )

    @classmethod
    def _fmt_labels(cls, labels) -> str:
        if not labels:
            return ""
        inner = ",".join(
            f'{k}="{cls._escape_label_value(v)}"' for k, v in labels
        )
        return "{" + inner + "}"

    def expose(self) -> str:
        lines: list[str] = []
        with self._lock:
            metrics = list(self._metrics)
            collectors = list(self._collectors)
        for fn in collectors:
            try:
                fn()
            except Exception:
                pass
        for m in metrics:
            kind = (
                "counter" if isinstance(m, Counter)
                else "histogram" if isinstance(m, Histogram)
                else "gauge"
            )
            if m.opts.help:
                lines.append(f"# HELP {m.name} {m.opts.help}")
            lines.append(f"# TYPE {m.name} {kind}")
            if isinstance(m, Histogram):
                for labels, (count, total, buckets) in sorted(
                    m._obs.items()
                ):
                    cum = 0
                    for b, n in zip(m.opts.buckets, buckets):
                        cum += n
                        lb = dict(labels)
                        lb["le"] = (
                            f"{b:g}" if not math.isinf(b) else "+Inf"
                        )
                        lines.append(
                            f"{m.name}_bucket"
                            f"{self._fmt_labels(sorted(lb.items()))} {cum}"
                        )
                    inf = dict(labels)
                    inf["le"] = "+Inf"
                    lines.append(
                        f"{m.name}_bucket"
                        f"{self._fmt_labels(sorted(inf.items()))} {count}"
                    )
                    lines.append(
                        f"{m.name}_sum{self._fmt_labels(labels)} {total:g}"
                    )
                    lines.append(
                        f"{m.name}_count{self._fmt_labels(labels)} {count}"
                    )
            else:
                for labels, v in sorted(m._series.items()):
                    lines.append(
                        f"{m.name}{self._fmt_labels(labels)} {v:g}"
                    )
        return "\n".join(lines) + "\n"


class PrometheusProvider:
    """Reference prometheus/provider.go: NewCounter/NewGauge/NewHistogram."""

    def __init__(self, registry: PrometheusRegistry | None = None):
        self.registry = registry or PrometheusRegistry()

    def new_counter(self, opts: CounterOpts) -> Counter:
        return Counter(opts, self.registry)

    def new_gauge(self, opts: GaugeOpts) -> Gauge:
        return Gauge(opts, self.registry)

    def new_histogram(self, opts: HistogramOpts) -> Histogram:
        return Histogram(opts, self.registry)


class StatsdProvider:
    """Emits statsd lines through a supplied `send(line: str)` callable
    (reference statsd/provider.go; the gokit statsd emitter is replaced by
    the callable so tests/deployments choose the socket)."""

    def __init__(self, send, prefix: str = ""):
        self._send = send
        self._prefix = prefix

    def _name(self, opts, labels=()) -> str:
        base = _fqname(opts)
        if self._prefix:
            base = f"{self._prefix}.{base}"
        fmt = opts.statsd_format
        if fmt:
            for k, v in labels:
                fmt = fmt.replace("%{" + k + "}", v)
            return f"{base}.{fmt}" if fmt else base
        if labels:
            base += "." + ".".join(v for _, v in labels)
        return base.replace("_", ".")

    def new_counter(self, opts: CounterOpts):
        return _StatsdCounter(self, opts)

    def new_gauge(self, opts: GaugeOpts):
        return _StatsdGauge(self, opts)

    def new_histogram(self, opts: HistogramOpts):
        return _StatsdHistogram(self, opts)


class _StatsdMetric:
    def __init__(self, provider, opts, labels=()):
        self._p = provider
        self.opts = opts
        self._labels = labels

    def with_labels(self, *pairs):
        it = iter(pairs)
        return type(self)(
            self._p, self.opts, self._labels + tuple(zip(it, it))
        )

    With = with_labels


class _StatsdCounter(_StatsdMetric):
    def add(self, delta: float = 1.0) -> None:
        self._p._send(
            f"{self._p._name(self.opts, self._labels)}:{delta:g}|c"
        )


class _StatsdGauge(_StatsdMetric):
    def set(self, value: float) -> None:
        self._p._send(
            f"{self._p._name(self.opts, self._labels)}:{value:g}|g"
        )

    def add(self, delta: float) -> None:
        sign = "+" if delta >= 0 else ""
        self._p._send(
            f"{self._p._name(self.opts, self._labels)}:{sign}{delta:g}|g"
        )


class _StatsdHistogram(_StatsdMetric):
    def observe(self, value: float) -> None:
        self._p._send(
            f"{self._p._name(self.opts, self._labels)}:{value:g}|ms"
        )


class DisabledProvider:
    """No-op provider (reference disabled/provider.go)."""

    def new_counter(self, opts):
        return _Noop()

    def new_gauge(self, opts):
        return _Noop()

    def new_histogram(self, opts):
        return _Noop()


class _Noop:
    def with_labels(self, *p):
        return self

    With = with_labels

    def add(self, *_):
        pass

    def set(self, *_):
        pass

    def observe(self, *_):
        pass


class SnapshotMetrics:
    """Channel-snapshot workload metrics (the gendoc-catalog role for
    the new subsystem): generation latency, bytes pushed through the
    CSP hash_batch path with its observed throughput, and the pending-
    request gauge.  Built from any metrics provider; the operations
    System exposes a prometheus-registered instance
    (common/operations.py snapshot_metrics())."""

    def __init__(self, provider):
        self.generation_duration = provider.new_histogram(HistogramOpts(
            namespace="snapshot",
            name="generation_duration",
            help="Seconds to generate one channel snapshot.",
            statsd_format="%{channel}",
        ))
        self.bytes_hashed = provider.new_counter(CounterOpts(
            namespace="snapshot",
            name="bytes_hashed",
            help="Total snapshot bytes digested through the CSP "
                 "hash_batch path.",
            statsd_format="%{channel}",
        ))
        self.hash_mb_per_s = provider.new_gauge(GaugeOpts(
            namespace="snapshot",
            name="hash_batch_mb_per_s",
            help="hash_batch throughput observed during the last "
                 "snapshot export (MB/s).",
            statsd_format="%{channel}",
        ))
        self.pending_requests = provider.new_gauge(GaugeOpts(
            namespace="snapshot",
            name="pending_requests",
            help="Number of pending snapshot requests.",
            statsd_format="%{channel}",
        ))


class ValidateMetrics:
    """Per-stage block-validate timing: host collect (parse + identity
    + policy prepare, possibly fanned out over the work pool), the wait
    on the device verify batch, and the host policy finish — the
    validate-side counterpart of CommitMetrics, so the /metrics reader
    can see which side of the validate->commit pipeline owns the p99.
    `creators` lies inside `collect`: what of it went to deserialising
    and validating the creators the block's memo did not hold.
    `await_commit` stands between `verify_wait` and `policy`, and only
    for a pipelined block some of whose key-level endorsement decisions
    depend on an earlier block's commit: how long it waited for that
    commit to land (peer/txvalidator.py `_KeyWindow`).

    Key-level (state-based) endorsement has three counters beside
    them: the committed state-metadata lookups the validator made, the
    decisions it deferred to the policy stage because a block in flight
    could still change a key's VALIDATION_PARAMETER, and the
    endorsement-plan cache's outcomes (`cleared`: the cache ran over
    its cap and was emptied).  Beside them the endorsement signatures
    that failed in transactions whose policy was met without them."""

    STAGES = ("collect", "creators", "verify_wait", "await_commit", "policy")

    def __init__(self, provider):
        self.stage_duration = provider.new_histogram(HistogramOpts(
            namespace="validator",
            subsystem="block",
            name="stage_duration",
            help="Seconds spent in one validate stage for one block "
                 "(collect, creators inside it, verify_wait, "
                 "await_commit, policy).",
            buckets=(
                0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                0.1, 0.25, 0.5, 1.0, 2.5,
            ),
            statsd_format="%{channel}.%{stage}",
        ))
        self.keylevel_lookups = provider.new_counter(CounterOpts(
            namespace="validator",
            subsystem="keylevel",
            name="lookups_total",
            help="Committed state-metadata lookups made to find a "
                 "written key's VALIDATION_PARAMETER.",
            label_names=("channel",),
            statsd_format="%{channel}",
        ))
        self.keylevel_point_reads = provider.new_counter(CounterOpts(
            namespace="validator",
            subsystem="keylevel",
            name="point_reads_total",
            help="Of those lookups, the ones a block's bulk read of its "
                 "written keys' state metadata did not cover: each went "
                 "to the ledger by itself.",
            label_names=("channel",),
            statsd_format="%{channel}",
        ))
        self.keylevel_deferred = provider.new_counter(CounterOpts(
            namespace="validator",
            subsystem="keylevel",
            name="deferred_total",
            help="Transactions whose endorsement policies were resolved "
                 "only once an earlier block's commit had landed, "
                 "because that block could change a written key's "
                 "VALIDATION_PARAMETER.",
            label_names=("channel",),
            statsd_format="%{channel}",
        ))
        self.plan_cache = provider.new_counter(CounterOpts(
            namespace="validator",
            subsystem="plan",
            name="cache_total",
            help="Endorsement-plan cache outcomes: hit, miss (a plan "
                 "built), shared (of the hits, those on a plan that "
                 "other identities of the same principal class built), "
                 "cleared (the young generation ran over its cap and "
                 "the plans unused since the last overflow were "
                 "dropped).",
            label_names=("outcome",),
            statsd_format="%{outcome}",
        ))
        self.tolerated_bad_endorsements = provider.new_counter(CounterOpts(
            namespace="validator",
            subsystem="tolerated",
            name="bad_endorsements_total",
            help="Endorsement signatures that failed verification in "
                 "transactions that stayed VALID because their "
                 "endorsement policy was met without them.",
            label_names=("channel",),
            statsd_format="%{channel}",
        ))


class CommitMetrics:
    """Per-stage ledger-commit pipeline timing (the group-commit
    tentpole's instrumentation): one histogram labeled (channel, stage)
    over the stages mvcc / block_append / pvt / state / history (per
    block) and fsync / kv_txn (per group boundary), plus how many
    blocks each fsync+txn boundary made durable — the breakdown an
    operator reads off /metrics and benchmarks/run.py off
    `commit_stage_seconds`."""

    STAGES = (
        "mvcc", "block_append", "pvt", "state", "history",
        "fsync", "kv_txn",
    )

    def __init__(self, provider):
        self.stage_duration = provider.new_histogram(HistogramOpts(
            namespace="ledger",
            subsystem="commit",
            name="stage_duration",
            help="Seconds spent in one commit-pipeline stage for one "
                 "block (mvcc/block_append/pvt/state/history) or one "
                 "group boundary (fsync/kv_txn).",
            buckets=(
                0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                0.1, 0.25, 0.5, 1.0, 2.5,
            ),
            statsd_format="%{channel}.%{stage}",
        ))
        self.blocks_per_sync = provider.new_histogram(HistogramOpts(
            namespace="ledger",
            subsystem="commit",
            name="blocks_per_sync",
            help="Blocks made durable by one group-commit fsync+txn "
                 "boundary (1 = no coalescing).",
            buckets=(1, 2, 3, 4, 6, 8, 12, 16, 24, 32),
            statsd_format="%{channel}",
        ))


class CSPMetrics:
    """TPU-CSP degraded-mode instrumentation (the faultline tentpole's
    hardening half): the circuit breaker's state and trip counts, raw
    device-path failures, recovery probes, and the lane tally (who
    sealed each verified lane's mask) — the signals an operator watches
    to know how much of the node's verification the chip really did."""

    def __init__(self, provider):
        self.breaker_state = provider.new_gauge(GaugeOpts(
            namespace="csp",
            subsystem="tpu",
            name="breaker_state",
            help="1 while the TPU degraded-mode circuit breaker is open "
                 "(verify served by the host path, no device "
                 "queuing), 0 when closed.",
        ))
        self.breaker_trips = provider.new_counter(CounterOpts(
            namespace="csp",
            subsystem="tpu",
            name="breaker_trips_total",
            help="Times the breaker opened after consecutive device "
                 "failures.",
        ))
        self.device_failures = provider.new_counter(CounterOpts(
            namespace="csp",
            subsystem="tpu",
            name="device_failures_total",
            help="Device-path failures observed by the TPU provider "
                 "(dispatch or collect).",
        ))
        self.probes = provider.new_counter(CounterOpts(
            namespace="csp",
            subsystem="tpu",
            name="breaker_probes_total",
            help="Recovery probe batches sent while the breaker was "
                 "open, labeled by result.",
            statsd_format="%{result}",
        ))
        self.lanes = provider.new_counter(CounterOpts(
            namespace="csp",
            subsystem="tpu",
            name="lanes_total",
            help="Signature lanes verified, labeled by who sealed the "
                 "mask: device, host_race, failover, breaker, small, "
                 "host_fraction.",
            statsd_format="%{sealed_by}",
        ))
        self.dispatches = provider.new_counter(CounterOpts(
            namespace="csp",
            subsystem="tpu",
            name="dispatches_total",
            help="Kernel executions enqueued, labeled by the bucket "
                 "(padded lanes) each ran at.",
            statsd_format="%{bucket}",
        ))
        self.host_races = provider.new_counter(CounterOpts(
            namespace="csp",
            subsystem="tpu",
            name="host_races_total",
            help="Host races started because a flush's deadline "
                 "expired, labeled by outcome: won (the host sealed "
                 "the mask) or lost (the device finished first).",
            statsd_format="%{outcome}",
        ))
        self.compile_events = provider.new_counter(CounterOpts(
            namespace="csp",
            subsystem="tpu",
            name="compile_events_total",
            help="JAX trace, lower and compile events in this process, "
                 "labeled by event; growth under load is a recompile.",
            statsd_format="%{event}",
        ))
        self.idemix_items = provider.new_counter(CounterOpts(
            namespace="csp",
            subsystem="idemix",
            name="items_total",
            help="Idemix items verified, labeled by kind (proof: a "
                 "credential proof; nym: a pseudonym signature) and by "
                 "the path that computed their commitments: pallas "
                 "(the BN254 kernel), xla (the scan fallback) or host.",
            statsd_format="%{kind}.%{path}",
        ))
        self.idemix_fallbacks = provider.new_counter(CounterOpts(
            namespace="csp",
            subsystem="idemix",
            name="fallbacks_total",
            help="Idemix batches verified elsewhere than the Pallas "
                 "BN254 kernel, labeled by reason: below_crossover, "
                 "no_tpu, forced_host, device_error, pallas_to_xla.",
            statsd_format="%{reason}",
        ))
        self.idemix_batches = provider.new_counter(CounterOpts(
            namespace="csp",
            subsystem="idemix",
            name="batches_total",
            help="Idemix batches launched on the device, labeled by "
                 "the bucket (padded lanes) each ran at.",
            statsd_format="%{bucket}",
        ))
        self.idemix_pairing_checks = provider.new_counter(CounterOpts(
            namespace="csp",
            subsystem="idemix",
            name="pairing_checks_total",
            help="Pairing checks of Idemix credential proofs, labeled "
                 "by stage: combined (one a batch), and, after a "
                 "combined check failed, subset (the bisection's) and "
                 "item (one proof's own).  Growth under subset or item "
                 "means forged credentials are being submitted.",
            statsd_format="%{stage}",
        ))
        self.keytable_flushes = provider.new_counter(CounterOpts(
            namespace="csp",
            subsystem="tpu",
            name="keytable_flushes_total",
            help="Flushes by what became of their public keys: "
                 "resident (every key already in the device's table), "
                 "grown (new keys added), reset (table cleared and "
                 "refilled), per_lane (more distinct keys than the "
                 "table holds: 64 bytes of key a lane go to the "
                 "per-lane-key kernel).",
            statsd_format="%{outcome}",
        ))
        self.idemix_msm_terms = provider.new_counter(CounterOpts(
            namespace="csp",
            subsystem="idemix",
            name="msm_terms_total",
            help="Point-and-scalar terms of the weighted G1 sums behind "
                 "those checks, labeled by engine: bucket (one "
                 "multi-scalar multiplication a sum) or window (a "
                 "scalar multiplication a term: sums under the native "
                 "threshold).  A block of n sound proofs adds 2n to "
                 "bucket.",
            statsd_format="%{engine}",
        ))
        self.flush_segments = provider.new_counter(CounterOpts(
            namespace="csp",
            subsystem="tpu",
            name="flush_segments_total",
            help="Batches (verify_batch_async calls: a block each while "
                 "a peer streams blocks) that device flushes took in.  "
                 "Over the sum of dispatches_total it is blocks a "
                 "flush: near 1 on a peer that keeps up, 2-3 on one "
                 "catching up.",
        ))
        self.small_batches = provider.new_counter(CounterOpts(
            namespace="csp",
            subsystem="tpu",
            name="small_batches_total",
            help="Batches under bccsp.tpu.minDeviceBatch, verified on "
                 "the host on the caller's thread to the same rule; "
                 "lanes_total{sealed_by=\"small\"} counts their lanes.  "
                 "Growth means blocks of a handful of transactions: a "
                 "lightly loaded channel cut by BatchTimeout.",
        ))
        self.early_flushes = provider.new_counter(CounterOpts(
            namespace="csp",
            subsystem="tpu",
            name="early_flushes_total",
            help="Device flushes a caller asked for while it still "
                 "collected the rest of its batch: the first 2,048 "
                 "lanes of a block validated alone that holds more.  "
                 "One a block on a peer that keeps up with a channel "
                 "of full blocks, none while it streams a backlog.",
        ))
        self.breaker_state.set(0)


class MSPMetrics:
    """The caching MSP's three LRUs (msp/cache.py, upstream
    msp/cache/cache.go's sizes): who was asked for, and what fell out.
    A channel with a handful of identities shows hits and no eviction;
    one whose blocks carry more distinct creators than a cache holds
    shows misses and evictions growing together, block after block.
    And the chain signatures behind the misses (msp/msp.py): `batch`
    where a block's creators were decided in one native call, `single`
    where OpenSSL checked one in place; and who read the certificates
    of a block's creators that missed."""

    def __init__(self, provider):
        self.cache_requests = provider.new_counter(CounterOpts(
            namespace="msp",
            subsystem="cache",
            name="requests_total",
            help="Lookups in the caching MSP, labeled by cache "
                 "(deserialize, validate, principal) and outcome: hit, "
                 "miss, or expired (a validate entry older than its "
                 "60 s, checked again).",
            statsd_format="%{cache}.%{outcome}",
        ))
        self.cache_evictions = provider.new_counter(CounterOpts(
            namespace="msp",
            subsystem="cache",
            name="evictions_total",
            help="Entries the caching MSP dropped because a cache was "
                 "full, labeled by cache.",
            statsd_format="%{cache}",
        ))
        self.chain_signatures = provider.new_counter(CounterOpts(
            namespace="msp",
            name="chain_signatures_total",
            help="Certificate chain signatures the X.509 MSPs checked, "
                 "labeled by path: batch (a block's creators, one "
                 "native call without the interpreter's lock) or "
                 "single (one OpenSSL call in place: another curve or "
                 "algorithm, several issuer candidates, an "
                 "intermediate's own hop, an identity validated alone).",
            statsd_format="%{path}",
        ))
        self.creator_parses = provider.new_counter(CounterOpts(
            namespace="msp",
            name="creator_parses_total",
            help="Certificates of block creators the caching MSP's batch "
                 "door read because its deserialize cache did not hold "
                 "them, labeled by path: native (one call without the "
                 "interpreter's lock reads a crowded block's) or python "
                 "(one at a time: a small block, a certificate the "
                 "native reader handed back, no native library).",
            statsd_format="%{path}",
        ))


class WorkpoolMetrics:
    """Shared host-work-pool observability (the PR 9 pool had none):
    how deep the executor's queue is, how many run_chunked chunks are
    in flight, and how saturated the worker set is — the signals that
    say whether FABRIC_TPU_COLLECT_POOL/_MVCC_POOL widths are starving
    or flooding the one process-wide pool."""

    def __init__(self, provider):
        self.queue_depth = provider.new_gauge(GaugeOpts(
            namespace="workpool",
            name="queue_depth",
            help="Tasks waiting in the shared host work pool's "
                 "executor queue at the last fan-out.",
        ))
        self.in_flight = provider.new_gauge(GaugeOpts(
            namespace="workpool",
            name="in_flight_chunks",
            help="run_chunked chunks currently submitted and not yet "
                 "collected.",
        ))
        self.saturation = provider.new_gauge(GaugeOpts(
            namespace="workpool",
            name="worker_saturation",
            help="In-flight chunks over the pool's worker cap, capped "
                 "at 1.0 — sustained 1.0 means fan-outs queue behind "
                 "each other.",
        ))


class RaftMetrics:
    """Raft cluster-comm instrumentation: the silent-loss counters the
    transport used to drop into the void.  `send_dropped` counts
    StepRequests discarded on a full outbound queue (raft retransmits,
    so an occasional drop is benign — sustained growth means a peer is
    down or a link is saturated); `dials` counts outbound connection
    attempts, so reconnect storms are visible next to the backoff."""

    def __init__(self, provider):
        self.send_dropped = provider.new_counter(CounterOpts(
            namespace="raft",
            name="send_dropped_total",
            help="StepRequests dropped because a peer's outbound queue "
                 "was full.",
            statsd_format="%{dest}",
        ))
        self.dials = provider.new_counter(CounterOpts(
            namespace="raft",
            name="dial_total",
            help="Outbound link connection attempts, labeled by "
                 "destination node.",
            statsd_format="%{dest}",
        ))
        # netscope gap closure: the consensus-state signals the
        # telemetry plane reads per scrape round
        self.term = provider.new_gauge(GaugeOpts(
            namespace="raft",
            name="term",
            help="This node's current raft term.",
        ))
        self.leader_changes = provider.new_counter(CounterOpts(
            namespace="raft",
            name="leader_changes_total",
            help="Observed leadership transitions (any leader -> a "
                 "different nonzero leader).",
        ))
        self.committed_index = provider.new_gauge(GaugeOpts(
            namespace="raft",
            name="last_committed_index",
            help="Last raft log index known committed on this node.",
        ))
        self.queue_depth = provider.new_gauge(GaugeOpts(
            namespace="raft",
            name="outbound_queue_depth",
            help="Depth of the per-peer outbound send queue at the "
                 "last enqueue, labeled by destination node.",
            statsd_format="%{dest}",
        ))
        self.wal_append = provider.new_histogram(HistogramOpts(
            namespace="raft",
            subsystem="wal",
            name="append_seconds",
            help="Seconds writing one WAL record batch (pre-fsync).",
            buckets=(
                0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.025, 0.05, 0.1, 0.25,
            ),
        ))
        self.wal_fsync = provider.new_histogram(HistogramOpts(
            namespace="raft",
            subsystem="wal",
            name="fsync_seconds",
            help="Seconds in one WAL fsync.",
            buckets=(
                0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.025, 0.05, 0.1, 0.25,
            ),
        ))


class GossipMetrics:
    """Gossip-plane instrumentation (a netscope gap closure: the gossip
    stack had NO metrics): message flow in/out, the state-transfer
    request/served-block counters that make catch-up visible, and the
    membership gauge the health rollup reads."""

    def __init__(self, provider):
        self.messages_received = provider.new_counter(CounterOpts(
            namespace="gossip",
            name="messages_received_total",
            help="Verified inbound gossip messages dispatched to "
                 "subscribers, labeled by content kind.",
            statsd_format="%{content}",
        ))
        self.messages_sent = provider.new_counter(CounterOpts(
            namespace="gossip",
            name="messages_sent_total",
            help="Outbound gossip messages signed and handed to a "
                 "transport.",
        ))
        self.state_requests_sent = provider.new_counter(CounterOpts(
            namespace="gossip",
            name="state_requests_sent_total",
            help="Anti-entropy state-transfer requests sent while "
                 "behind a peer's advertised height.",
        ))
        self.state_requests_served = provider.new_counter(CounterOpts(
            namespace="gossip",
            name="state_requests_served_total",
            help="Inbound state-transfer requests answered with at "
                 "least one block.",
        ))
        self.state_blocks_served = provider.new_counter(CounterOpts(
            namespace="gossip",
            name="state_blocks_served_total",
            help="Blocks shipped in state-transfer responses.",
        ))
        self.membership = provider.new_gauge(GaugeOpts(
            namespace="gossip",
            name="membership_size",
            help="Alive peers known to discovery at the last tick "
                 "(excluding self).",
        ))


class DeliverMetrics:
    """Deliver-client instrumentation (netscope gap closure): blocks
    pulled from the ordering service, reconnect episodes, and the
    cumulative backoff the client has waited out — a climbing
    reconnect counter with a flat block counter is the silent-wedge
    signature the stall detector confirms from the outside."""

    def __init__(self, provider):
        self.blocks = provider.new_counter(CounterOpts(
            namespace="deliver",
            name="blocks_total",
            help="Blocks verified and handed to the sink.",
            statsd_format="%{channel}",
        ))
        self.reconnects = provider.new_counter(CounterOpts(
            namespace="deliver",
            name="reconnects_total",
            help="Reconnect/rotation episodes (a stream ended or "
                 "failed and the client moved to the next endpoint).",
            statsd_format="%{channel}",
        ))
        self.backoff_seconds = provider.new_counter(CounterOpts(
            namespace="deliver",
            name="backoff_seconds_total",
            help="Cumulative seconds the client has spent in "
                 "reconnect backoff.",
            statsd_format="%{channel}",
        ))


class GatewayMetrics:
    """Gateway submission front-end instrumentation: admission queue
    depth and the adaptive in-flight window (the backpressure pair —
    depth pinned at the window with zero resolutions is the
    stuck-gateway signature), dedup hits, backpressure rejections,
    orderer failover episodes, per-status resolution counters, and the
    submit→commit latency histogram netscope's SLO rollup reads."""

    def __init__(self, provider):
        self.queue_depth = provider.new_gauge(GaugeOpts(
            namespace="gateway",
            name="queue_depth",
            help="Envelopes accepted but not yet written to an "
                 "orderer broadcast stream.",
            statsd_format="%{channel}",
        ))
        self.in_flight = provider.new_gauge(GaugeOpts(
            namespace="gateway",
            name="in_flight",
            help="Accepted txids not yet resolved to a commit status.",
            statsd_format="%{channel}",
        ))
        self.window = provider.new_gauge(GaugeOpts(
            namespace="gateway",
            name="window",
            help="Current admission window (max unresolved txids), "
                 "adapted to the deliver-observed commit rate.",
            statsd_format="%{channel}",
        ))
        self.dedup_hits = provider.new_counter(CounterOpts(
            namespace="gateway",
            name="dedup_hits_total",
            help="Resubmissions answered idempotently from the txid "
                 "dedup map.",
            statsd_format="%{channel}",
        ))
        self.rejections = provider.new_counter(CounterOpts(
            namespace="gateway",
            name="rejections_total",
            help="Submissions rejected with retry-after because the "
                 "admission window was full.",
            statsd_format="%{channel}",
        ))
        self.failovers = provider.new_counter(CounterOpts(
            namespace="gateway",
            name="failovers_total",
            help="Orderer stream failover episodes (connection loss "
                 "-> rotation + in-flight resubmission).",
            statsd_format="%{channel}",
        ))
        self.resolved = provider.new_counter(CounterOpts(
            namespace="gateway",
            name="resolved_total",
            help="Txids resolved to a definitive commit status, by "
                 "status (VALID/INVALID/TIMEOUT).",
            statsd_format="%{channel}.%{status}",
        ))
        self.submit_to_commit_seconds = provider.new_histogram(HistogramOpts(
            namespace="gateway",
            name="submit_to_commit_seconds",
            help="Latency from gateway admission to commit-status "
                 "resolution via the deliver tail.",
            statsd_format="%{channel}",
        ))


class LedgerMetrics:
    """Per-channel ledger progress (netscope gap closure): the height
    and durability-watermark gauges the telemetry plane derives
    cross-peer commit lag from, plus committed block/tx counters for
    sustained-throughput SLO rollups."""

    def __init__(self, provider):
        self.height = provider.new_gauge(GaugeOpts(
            namespace="ledger",
            name="height",
            help="Committed block height (next block number), per "
                 "channel.",
            statsd_format="%{channel}",
        ))
        self.durable_height = provider.new_gauge(GaugeOpts(
            namespace="ledger",
            name="durable_height",
            help="Durability watermark: every block at or below it has "
                 "its block file fsynced and its KV txn committed.",
            statsd_format="%{channel}",
        ))
        self.blocks_committed = provider.new_counter(CounterOpts(
            namespace="ledger",
            name="blocks_committed_total",
            help="Blocks committed since process start, per channel.",
            statsd_format="%{channel}",
        ))
        self.commit_assist = provider.new_counter(CounterOpts(
            namespace="ledger",
            name="commit_assist_total",
            help="Blocks committed by what came with them: full = the "
                 "validator's txids and envelope bytes (no envelope is "
                 "decoded again), none = the ledger parsed the block "
                 "itself (genesis, recovery, a caller without a "
                 "validator).",
            statsd_format="%{channel}.%{assist}",
        ))
        self.transactions = provider.new_counter(CounterOpts(
            namespace="ledger",
            name="transactions_total",
            help="VALID transactions committed since process start, "
                 "per channel.",
            statsd_format="%{channel}",
        ))
        self.mvcc_invalidated = provider.new_counter(CounterOpts(
            namespace="ledger",
            name="mvcc_invalidated_total",
            help="Transactions that came to MVCC valid and were "
                 "invalidated there, by reason: read = a read key's "
                 "committed version differs, phantom = a range query's "
                 "result does.",
            statsd_format="%{channel}.%{reason}",
        ))
        self.preload_rows = provider.new_counter(CounterOpts(
            namespace="ledger",
            name="preload_rows_total",
            help="Distinct keys a block's one MVCC bulk read asked the "
                 "state for, by outcome: found = the row is there, "
                 "missing = the state holds no such row.",
            statsd_format="%{channel}.%{outcome}",
        ))
        self.kv_txn_rows = provider.new_counter(CounterOpts(
            namespace="ledger",
            name="kv_txn_rows_total",
            help="Rows (puts and deletes of index, state, history and "
                 "private data together) written by the commit groups' "
                 "KV transactions, per channel.",
            statsd_format="%{channel}",
        ))


class LockMetrics:
    """Lock-contention observability (profscope, PR 15): per-role
    acquire-wait and hold-time histograms — the runtime complement to
    fabriclint's static lock-order graph.  Fed by
    ``profile.note_lock_wait/note_lock_hold`` (lockwatch's watched and
    profiled lock wrappers) only while profiling is armed, so a
    disarmed node's /metrics is unchanged."""

    # lock waits live in the microsecond..second range, far below the
    # default request buckets
    _BUCKETS = (
        1e-6, 1e-5, 1e-4, 1e-3, 5e-3, 0.025, 0.1, 0.5, 2.0,
    )

    def __init__(self, provider):
        self.wait = provider.new_histogram(HistogramOpts(
            namespace="lock",
            name="wait_seconds",
            help="Seconds a thread spent blocked acquiring the lock "
                 "with this role (profscope armed only).",
            buckets=self._BUCKETS,
            statsd_format="%{role}",
        ))
        self.hold = provider.new_histogram(HistogramOpts(
            namespace="lock",
            name="hold_seconds",
            help="Seconds the lock with this role was held, outermost "
                 "acquire to final release (profscope armed only).",
            buckets=self._BUCKETS,
            statsd_format="%{role}",
        ))


class ProcessMetrics:
    """Standard process-level gauges (the prometheus client-library
    conventions) so netscope series can correlate node saturation with
    commit lag: CPU seconds, RSS, open fds, GC collections and pause
    time.  Values are read at scrape time — register :meth:`collect`
    with ``PrometheusRegistry.register_collector``."""

    def __init__(self, provider):
        self.cpu_seconds = provider.new_gauge(GaugeOpts(
            name="process_cpu_seconds_total",
            help="Total user+system CPU seconds of this process "
                 "(monotone; exposed as a scrape-time gauge).",
        ))
        self.rss_bytes = provider.new_gauge(GaugeOpts(
            name="process_resident_memory_bytes",
            help="Resident set size in bytes.",
        ))
        self.open_fds = provider.new_gauge(GaugeOpts(
            name="process_open_fds",
            help="Open file descriptors.",
        ))
        self.gc_collections = provider.new_gauge(GaugeOpts(
            name="process_gc_collections_total",
            help="Cyclic GC collections since process start, per "
                 "generation.",
        ))
        self.gc_pause_seconds = provider.new_gauge(GaugeOpts(
            name="process_gc_pause_seconds_total",
            help="Cumulative seconds spent inside cyclic GC "
                 "collections (gc callback timing).",
        ))
        self.gc_frozen_objects = provider.new_gauge(GaugeOpts(
            name="process_gc_frozen_objects",
            help="Objects in the collector's permanent generation "
                 "(gc.freeze): the start-up heap no collection walks "
                 "any more; 0 until common.gcpolicy.settle() has run.",
        ))
        # the process has ONE gc callback and tracelens owns it: armed,
        # the same entry also records `gc.pause` spans
        tracing.watch_gc()

    def collect(self) -> None:
        import gc
        import os

        t = os.times()
        self.cpu_seconds.set(t.user + t.system)
        try:
            with open("/proc/self/statm", "r", encoding="ascii") as f:
                pages = int(f.read().split()[1])
            self.rss_bytes.set(pages * (os.sysconf("SC_PAGE_SIZE")))
        except (OSError, ValueError, IndexError):
            pass
        try:
            self.open_fds.set(len(os.listdir("/proc/self/fd")))
        except OSError:
            pass
        for gen, st in enumerate(gc.get_stats()):
            self.gc_collections.With(
                "generation", str(gen)
            ).set(st.get("collections", 0))
        self.gc_pause_seconds.set(tracing.gc_pause_seconds())
        self.gc_frozen_objects.set(gc.get_freeze_count())


__all__ = [
    "CounterOpts",
    "GaugeOpts",
    "HistogramOpts",
    "Counter",
    "Gauge",
    "Histogram",
    "PrometheusProvider",
    "PrometheusRegistry",
    "StatsdProvider",
    "DisabledProvider",
    "SnapshotMetrics",
    "CommitMetrics",
    "CSPMetrics",
    "RaftMetrics",
    "WorkpoolMetrics",
    "GossipMetrics",
    "DeliverMetrics",
    "GatewayMetrics",
    "LedgerMetrics",
    "LockMetrics",
    "ProcessMetrics",
]
