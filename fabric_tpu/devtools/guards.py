"""Reviewed guarded-by declarations for fabriclint's racecheck rule.

Each entry pins a shared field to the lock ROLE that must be held at
every access reachable from a thread entry point.  Declarations beat
majority inference: they are the reviewed concurrency contract for the
hot structures (the commit pipeline, the snapshot manager, the TPU
CSP's coalescing lane state, gossip membership), so a refactor that
quietly drops the lock around one access fails the lint gate even if
it also shifts the statistical majority.

Role spellings
--------------
* a ``lockwatch`` role string (``named_lock("kvledger.commit_lock")``)
  for locks created through the lockwatch seam — the runtime
  ``lockwatch.guarded(obj, field, by=role)`` assertions use the same
  strings, so the static map and the dynamic cross-check can never
  drift apart;
* the member's own qname (``fabric_tpu.csp.tpu.provider.TPUCSP.
  _ewma_lock``) as a pseudo-role for plain ``threading`` primitives.

Fields NOT listed here still get a guard when a strict majority of
their access sites hold one lock (see ``dataflow.Project._racecheck``);
this table exists for the structures where "majority" is not a strong
enough word for the invariant.

Since fabriclint v4 the racecheck engine also models happens-before
edges (thread start/join, Event set->wait, Queue put->get, workpool
submit->result): a field whose every access is publication-ordered
needs NO entry here (it resolves as ``hb-publish`` in the guard map),
and an entry whose every access becomes HB-proven — with at least one
access genuinely thread-reachable — is flagged STALE so this table
only shrinks.  Declare a guard when the invariant is the reviewed
contract (locks); let publication idioms be proven, not declared.

v5 sharpened both sides of that bargain: events and accesses are
ordered by CFG dominance/reachability rather than line position (a
back edge that carries a write after a previous iteration's start is
a finding, a start that dominates every access path is a proof), and
the lockset consulted at each access is the flow-sensitive must-hold
meet over paths — so a conditional acquire or early-return release
can neither fake a guard here nor hide from one.
"""

from __future__ import annotations

DECLARED_GUARDS: dict[str, str] = {
    # -- commit pipeline (PR 2 group commit) -------------------------------
    # the open CommitGroup and the durability watermark only move under
    # the commit lock; a thread reading them lock-free would see a
    # half-flushed group boundary
    "fabric_tpu.ledger.kvledger.KVLedger._active_group":
        "kvledger.commit_lock",
    "fabric_tpu.ledger.kvledger.KVLedger._durable_height":
        "kvledger.commit_lock",
    "fabric_tpu.ledger.kvledger.KVLedger._durable_hash":
        "kvledger.commit_lock",
    # -- snapshot manager (PR 1/2) -----------------------------------------
    "fabric_tpu.ledger.snapshot.SnapshotManager._pending":
        "snapshot.manager",
    "fabric_tpu.ledger.snapshot.SnapshotManager._inflight":
        "snapshot.idle",
    "fabric_tpu.ledger.snapshot.SnapshotManager._spawn_seq":
        "snapshot.idle",
    "fabric_tpu.ledger.snapshot.SnapshotManager._ack_seq":
        "snapshot.idle",
    # -- TPU CSP coalescing lane state (PR 2/6) ----------------------------
    "fabric_tpu.csp.tpu.provider.TPUCSP._pend_batches": "csp.tpu.pend",
    "fabric_tpu.csp.tpu.provider.TPUCSP._pend_lanes": "csp.tpu.pend",
    "fabric_tpu.csp.tpu.provider.TPUCSP._flushed": "csp.tpu.pend",
    "fabric_tpu.csp.tpu.provider.TPUCSP._inflight": "csp.tpu.pend",
    "fabric_tpu.csp.tpu.provider.TPUCSP._gen": "csp.tpu.pend",
    "fabric_tpu.csp.tpu.provider.TPUCSP._lane_wall_ewma":
        "fabric_tpu.csp.tpu.provider.TPUCSP._ewma_lock",
    # who-sealed-it lane tally: bumped by callers, host-race consumers
    # and tpu-flush-waiter threads alike
    "fabric_tpu.csp.tpu.provider.TPUCSP._lane_tally":
        "fabric_tpu.csp.tpu.provider.TPUCSP._tally_lock",
    # process-wide measured host verify rate (module global)
    "fabric_tpu.csp.tpu.provider._host_rate_ewma":
        "fabric_tpu.csp.tpu.provider._host_rate_lock",
    # -- shared host work pool (PR 9 parallel collect/prepare) -------------
    # the lazily-created process-wide executor singleton: creation and
    # teardown race between first users and shutdown callers
    "fabric_tpu.common.workpool._pool":
        "fabric_tpu.common.workpool._pool_lock",
    # -- gossip membership --------------------------------------------------
    "fabric_tpu.gossip.discovery.DiscoveryCore._peers":
        "gossip.discovery.members",
    "fabric_tpu.gossip.discovery.DiscoveryCore._tick":
        "gossip.discovery.members",
    "fabric_tpu.gossip.discovery.DiscoveryCore._seq":
        "gossip.discovery.members",
    # -- netscope telemetry collector (PR 12) -------------------------------
    # the scraper thread ingests rounds while the harness thread reads
    # series/marks events/writes artifacts; every shared structure
    # moves under one state lock
    "fabric_tpu.devtools.netscope.Netscope._series": "netscope.state",
    "fabric_tpu.devtools.netscope.Netscope._health": "netscope.state",
    "fabric_tpu.devtools.netscope.Netscope._events": "netscope.state",
    "fabric_tpu.devtools.netscope.Netscope._trace_events":
        "netscope.state",
    "fabric_tpu.devtools.netscope.Netscope._trace_cursor":
        "netscope.state",
    "fabric_tpu.devtools.netscope.Netscope._stalls": "netscope.state",
    "fabric_tpu.devtools.netscope.Netscope._height_window":
        "netscope.state",
    # -- profscope profiling plane (PR 15) ----------------------------------
    # the sampler service thread folds sweeps into the aggregates while
    # feed points (lockwatch contention, workpool chunks) write from
    # arbitrary threads and export() snapshots from the harness thread;
    # everything shared moves under the profiler's own plain lock (a
    # plain primitive on purpose: a watched lock here would recurse
    # through the very note_lock_wait hook it feeds)
    "fabric_tpu.common.profile.Profiler._stacks":
        "fabric_tpu.common.profile.Profiler._lock",
    "fabric_tpu.common.profile.Profiler._spans":
        "fabric_tpu.common.profile.Profiler._lock",
    "fabric_tpu.common.profile.Profiler._locks":
        "fabric_tpu.common.profile.Profiler._lock",
    "fabric_tpu.common.profile.Profiler._chunks":
        "fabric_tpu.common.profile.Profiler._lock",
    "fabric_tpu.common.profile.Profiler._samples":
        "fabric_tpu.common.profile.Profiler._lock",
    "fabric_tpu.common.profile.Profiler._dropped":
        "fabric_tpu.common.profile.Profiler._lock",
    "fabric_tpu.common.profile.Profiler._t0":
        "fabric_tpu.common.profile.Profiler._lock",
}

__all__ = ["DECLARED_GUARDS"]
