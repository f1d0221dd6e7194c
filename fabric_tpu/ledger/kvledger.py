"""The peer ledger: block store + state DB + history DB orchestration.

Reference: core/ledger/kvledger/kv_ledger.go:447-530 CommitLegacy
(ValidateAndPrepare -> block store -> state DB -> history DB), provider in
kv_ledger_provider.go, recovery-on-open (state/history DBs replay blocks
newer than their savepoints), ledgermgmt/ledger_mgmt.go lifecycle.
"""

from __future__ import annotations

import os
import threading
import time

from fabric_tpu.common import tracing
from fabric_tpu.devtools import faultline, knob_registry
from fabric_tpu.devtools.lockwatch import guarded, named_rlock
from fabric_tpu.ledger.blkstorage import BlockStore, BlockStoreError
from fabric_tpu.ledger.history import HistoryDB
from fabric_tpu.ledger.kvstore import (
    KVStore,
    WriteBatchCollector,
    open_store_root,
)
from fabric_tpu.ledger.statedb import Height, VersionedDB
from fabric_tpu.ledger.txmgmt import (
    MVCCValidator,
    TxSimulator,
    VALID,
    hash_ns,
    key_hash,
    pvt_ns,
)
from fabric_tpu.protos.common import common_pb2
from fabric_tpu.protos.ledger.rwset import rwset_pb2
from fabric_tpu.protos.ledger.rwset.kvrwset import kv_rwset_pb2
from fabric_tpu import protoutil


import dataclasses


@dataclasses.dataclass
class CommitAssist:
    """Everything the validator already learned about a block that the
    commit path would otherwise re-derive: per-tx marshaled rwsets (no
    envelope re-walk), per-tx decoded RwsetFootprints (no rwset
    re-unmarshal in MVCC/history), per-tx txids (no envelope parse in the
    block-store index), and the materialized envelope byte list (the
    store splice-serializes the block from these instead of re-encoding
    the whole message).  The reference re-unmarshals at every one of
    those stages (validator.go, validateAndPrepareBatch, blockindex.go).

    Both of the committer's entry points hand one over with every block:
    Committer.store_stream (from TxValidator.validate_pipeline) and
    Committer.store_block (from TxValidator.validate, as the
    private-data coordinator's store_block does).  A position the
    validator could not parse holds None and is parsed here."""

    rwsets: list  # per-tx marshaled TxReadWriteSet | None
    footprints: list  # per-tx RwsetFootprint | None
    txids: list  # per-tx txid str | None
    env_bytes: list | None = None  # the block's envelope byte strings
    # the validator's per-block trace root (tracing.SpanContext | None):
    # the committer thread attaches it so commit-stage spans join the
    # block's trace across the pipeline hop
    trace_ctx: object | None = None


@dataclasses.dataclass
class CommitGroup:
    """In-flight group-commit state: a WriteBatchCollector buffering
    every KV mutation (block index + pvt + state + history + savepoints)
    destined for ONE atomic base transaction, an overlay-aware state
    view so MVCC of block k+1 sees block k's buffered writes, and the
    bookkeeping the flush boundary needs (which block files to fsync,
    which committed heights to hand the snapshot auto-trigger).  Created
    by KVLedger.begin_commit_group, reusable across flushes."""

    collector: WriteBatchCollector
    state: VersionedDB  # rebased view over the collector
    mvcc: MVCCValidator
    blocks: int = 0
    dirty_files: set = dataclasses.field(default_factory=set)
    snap_notify: list = dataclasses.field(default_factory=list)
    # set when a buffered block has a pending snapshot request: the
    # streaming committer flushes at this block so the export height is
    # exactly the requested height (deterministic across peers)
    boundary_hint: bool = False


def extract_rwsets(block: common_pb2.Block) -> list[bytes | None]:
    """Per-tx marshaled TxReadWriteSet for endorser txs (None otherwise)."""
    out: list[bytes | None] = []
    for i in range(len(block.data.data)):
        raw = None
        try:
            env = protoutil.extract_envelope(block, i)
            payload = common_pb2.Payload.FromString(env.payload)
            chdr = common_pb2.ChannelHeader.FromString(payload.header.channel_header)
            if chdr.type == common_pb2.ENDORSER_TRANSACTION:
                _, action = protoutil.get_action_from_envelope(env)
                raw = action.results
        except Exception:
            raw = None
        out.append(raw)
    return out


def _history_writes(
    rwsets: list[bytes | None],
    flags: list[int],
    footprints: list | None = None,
):
    """Per-tx (ns, key) write lists for the history index (valid txs
    only).  When the validator's decoded footprints ride along, the
    public write keys are read straight off them — no re-unmarshal."""
    writes_per_tx: list[list[tuple[str, str]]] = [[] for _ in flags]
    for tx_num, raw in enumerate(rwsets):
        if flags[tx_num] != VALID or raw is None:
            continue
        fp = footprints[tx_num] if footprints is not None else None
        if fp is not None:
            out = writes_per_tx[tx_num]
            for ns, kvrw, _colls in fp.parsed:
                out.extend((ns, w.key) for w in kvrw.writes)
            continue
        try:
            txrw = rwset_pb2.TxReadWriteSet.FromString(raw)
            for nsrw in txrw.ns_rwset:
                kvrw = kv_rwset_pb2.KVRWSet.FromString(nsrw.rwset)
                writes_per_tx[tx_num].extend(
                    (nsrw.namespace, w.key) for w in kvrw.writes
                )
        except Exception:
            # fabriclint: allow[exception-discipline] a malformed rwset
            # contributes no history writes; MVCC already flagged the tx
            continue
    return writes_per_tx


class KVLedger:
    """One channel's ledger (reference ledger.PeerLedger,
    core/ledger/ledger_interface.go:142).  Owns the block store, state DB,
    history DB, and the private-data store — the reference's kvledger also
    commits block + pvtdata together (kv_ledger.go commitToPvtAndBlockStore)
    so that restart recovery can replay cleartext private writes."""

    def __init__(
        self,
        ledger_id: str,
        block_store: BlockStore,
        kv: KVStore,
        btl_policy=None,
        metrics=None,
        ledger_metrics=None,
    ):
        from fabric_tpu.ledger.confighistory import ConfigHistoryMgr
        from fabric_tpu.ledger.pvtdatastorage import PvtDataStore

        self.ledger_id = ledger_id
        self._kv = kv
        self._blocks = block_store
        self._state = VersionedDB(kv, f"statedb/{ledger_id}")
        self._history = HistoryDB(kv, f"historydb/{ledger_id}")
        self._mvcc = MVCCValidator(self._state)
        self.pvt_store = PvtDataStore(kv, ledger_id, btl_policy=btl_policy)
        self.config_history = ConfigHistoryMgr(kv, ledger_id)
        # SnapshotManager wired by the provider after construction (it
        # needs the ledger); commit() notifies it per committed block
        self.snapshots = None
        # Per-stage commit timing: cumulative wall seconds per pipeline
        # stage (CommitMetrics.STAGES keys), always maintained
        # (benchmarks/run.py reads them); `metrics` (a
        # common.metrics.CommitMetrics) also gets per-observation
        # histograms for /metrics.
        self._metrics = metrics
        # `ledger_metrics` (common.metrics.LedgerMetrics): the
        # per-channel height / durable_height gauges + block/tx
        # counters the netscope telemetry plane derives cross-peer
        # commit lag and sustained throughput from
        self._lmetrics = ledger_metrics
        self.commit_stage_seconds: dict[str, float] = {}
        # Serializes state mutation against snapshot export: commits are
        # already single-threaded per ledger (one committer), but an
        # admin RPC can request an on-demand snapshot concurrently — the
        # export takes this lock so it never reads a half-committed
        # block.  RLock because the commit-time auto-trigger generates
        # while the committing thread already holds it.  Created through
        # the lockwatch seam: under FABRIC_TPU_LOCKWATCH (tier-1) every
        # acquisition feeds the runtime lock-order watchdog.
        self.commit_lock = named_rlock("kvledger.commit_lock")
        # the CommitGroup currently holding buffered (unflushed) blocks,
        # if any — commits through any OTHER group are rejected while it
        # is open (their collectors would disagree about the checkpoint)
        self._active_group: CommitGroup | None = None
        self._recover()
        # Durability watermark: the height (and last block hash) as of
        # the last group boundary — everything at or below it has its
        # block file fsynced AND its KV transaction committed.  During
        # an open group, self.height runs ahead of this; snapshot
        # exports and the auto-trigger only ever observe the watermark.
        self._durable_height = self._blocks.height
        self._durable_hash = self._blocks.last_block_hash
        self._publish_heights()

    def _publish_heights(self) -> None:
        lm = self._lmetrics
        if lm is not None:
            lm.height.With("channel", self.ledger_id).set(
                self._blocks.height
            )
            lm.durable_height.With("channel", self.ledger_id).set(
                self._durable_height
            )

    def set_btl_policy(self, btl_policy) -> None:
        self.pvt_store._btl = btl_policy or (lambda ns, coll: 0)

    # -- recovery (reference recoverDBs / syncStateAndHistoryDBWithBlockstore)

    @staticmethod
    def _recovery_group_size() -> int:
        """Blocks replayed per recovery KV transaction
        (FABRIC_TPU_RECOVERY_GROUP, default 32; values below 1 restore
        the old per-block-txn behavior)."""
        raw = knob_registry.raw("FABRIC_TPU_RECOVERY_GROUP").strip()
        if not raw:
            return 32
        try:
            return max(1, int(raw))
        except ValueError:
            raise ValueError(
                f"FABRIC_TPU_RECOVERY_GROUP={raw!r} is not an integer "
                "group size"
            ) from None

    def _recover(self) -> None:
        """Replay blocks newer than the state savepoint THROUGH the same
        WriteBatchCollector group-commit seam live commits use: one KV
        transaction per replayed group instead of four-plus per block
        (the pre-batched path), with the rebased overlay making each
        block's MVCC re-application see its predecessors' buffered
        writes.  Crash-safe at every boundary: the savepoint rides each
        group's atomic flush, so a crash mid-recovery resumes from the
        last flushed group and the replay is idempotent."""
        height = self._blocks.height
        sp = self._state.savepoint()
        first = 0 if sp is None else sp.block_num + 1
        if first >= height:
            return
        # guard-style fault point: a "skip" rule leaves the ledger at
        # the block store's height without the state of the blocks past
        # the savepoint, which the invariants oracle must catch
        if not faultline.guard(
            "ledger.recovery_replay", first=first, height=height,
        ):
            return
        group_size = self._recovery_group_size()
        collector = WriteBatchCollector(self._kv)
        state = self._state.rebased(collector)
        mvcc = MVCCValidator(state)
        buffered = 0
        for num in range(first, height):
            block = self._blocks.get_block_by_number(num)
            self._apply_state_updates(
                block, self.pvt_store.get_pvt_data_by_block(num),
                mvcc=mvcc, state=state, into=collector,
            )
            buffered += 1
            if buffered >= group_size:
                collector.flush()
                state.invalidate_caches()
                buffered = 0
        if buffered:
            collector.flush()
        # the base store changed underneath the main view's caches
        self._state.invalidate_caches()

    def _apply_state_updates(
        self, block: common_pb2.Block,
        pvt_data: dict[int, bytes] | None = None,
        *, mvcc=None, state=None, into=None,
    ) -> None:
        """Replay one block's state/history effects.  `mvcc`/`state`
        default to the live DBs (per-block commit); recovery passes a
        collector-rebased pair plus `into` so a whole replay group lands
        in one KV transaction."""
        mvcc = mvcc if mvcc is not None else self._mvcc
        state = state if state is not None else self._state
        flags = list(protoutil.tx_filter(block))
        rwsets = extract_rwsets(block)
        # replay trusts the recorded validation flags; MVCC re-application
        # is deterministic because only VALID txs contribute writes
        batch = mvcc.validate_and_prepare(
            block.header.number, rwsets, flags, pvt_data
        )
        # a replayed block whose group KV txn died with a crash lost its
        # cleartext pvt writes (pvt store + state are one atomic txn):
        # record every endorsed-cleartext collection with no stored data
        # as MISSING so the reconciler re-fetches instead of the loss
        # staying silent (may over-report collections this peer was
        # never eligible for; reconciliation of those is a no-op)
        missing = self._lost_pvt(rwsets, flags, pvt_data or {})
        if missing:
            self.pvt_store.commit(
                block.header.number, {}, missing, into=into
            )
        state.apply_updates(batch, Height(block.header.number, len(flags)))
        self._history.commit(
            block.header.number, _history_writes(rwsets, flags), into=into
        )

    @staticmethod
    def _lost_pvt(rwsets, flags, pvt_data) -> list[tuple[int, str, str]]:
        """[(tx, ns, coll)] where the rwset endorsed a cleartext private
        rwset (non-empty pvt_rwset_hash) but no cleartext survives."""
        out: list[tuple[int, str, str]] = []
        for tx_num, raw in enumerate(rwsets):
            if flags[tx_num] != VALID or raw is None or pvt_data.get(tx_num):
                continue
            try:
                txrw = rwset_pb2.TxReadWriteSet.FromString(raw)
            except Exception:
                # fabriclint: allow[exception-discipline] unparsable rwset ->
                # no endorsed collections -> nothing can be missing
                continue
            for nsrw in txrw.ns_rwset:
                for ch in nsrw.collection_hashed_rwset:
                    if ch.pvt_rwset_hash:
                        out.append(
                            (tx_num, nsrw.namespace, ch.collection_name)
                        )
        return out

    # -- commit path (reference kv_ledger.go:447 CommitLegacy) -------------

    def begin_commit_group(self) -> CommitGroup:
        """Start a group commit: blocks committed with this group buffer
        every KV mutation in one shared collector (and skip per-block
        fsyncs); commit_group_flush lands the whole group with one
        block-file fsync + one all-or-nothing KV transaction.  Reusable
        after each flush."""
        collector = WriteBatchCollector(self._kv)
        view = self._state.rebased(collector)
        return CommitGroup(
            collector=collector, state=view, mvcc=MVCCValidator(view)
        )

    def commit(
        self,
        block: common_pb2.Block,
        pvt_data: dict[int, bytes] | None = None,
        missing_pvt: list[tuple[int, str, str]] | None = None,
        rwsets: list[bytes | None] | None = None,
        assist: CommitAssist | None = None,
        group: CommitGroup | None = None,
    ) -> None:
        """MVCC-validate (updating the tx filter), persist block + private
        data, apply state + history.  Signature/policy flags must already
        be set by the txvalidator; this adds the MVCC codes.  pvt_data maps
        tx index -> marshaled TxPvtReadWriteSet (cleartext private writes
        this peer is eligible for); missing_pvt records eligible-but-absent
        collections for the reconciler.  `rwsets` may carry the per-tx
        marshaled TxReadWriteSets the validator already extracted
        (Committer.store_stream) — the commit then skips re-walking
        every envelope; a full `assist` additionally skips the rwset
        re-unmarshal (MVCC + history read the decoded footprints), the
        txid envelope parse in the block index, and the whole-block
        re-serialization (splice from the envelope bytes).

        Without `group`, the block is flushed immediately — still as ONE
        block-file fsync + ONE atomic KV transaction carrying the block
        index, pvt store, state (with savepoint) and history together
        (the pre-group code paid one fsync plus four-plus independent
        KV transactions here).  With `group`, the block lands in the
        group's buffers and only becomes durable/visible at the next
        commit_group_flush."""
        if self.snapshots is not None:
            # a background snapshot export pinned to the last flush
            # height must win the commit lock before state advances
            self.snapshots.wait_generation_turn()
        with self.commit_lock:
            g = group if group is not None else self.begin_commit_group()
            if self._active_group is not None and g is not self._active_group:
                # a DIFFERENT group holds buffered blocks: its index/
                # checkpoint advance lives only in its collector, so a
                # fresh collector would read the stale base checkpoint
                # and index this block at already-occupied offsets
                raise BlockStoreError(
                    "another commit group holds unflushed blocks for "
                    f"ledger {self.ledger_id!r}"
                )
            try:
                # fabriclint: allow[lock-discipline] the faultline stage
                # points inside may inject delays under the commit lock BY
                # DESIGN (chaos latency testing); with no plan armed they
                # are zero-overhead no-ops
                self._commit_into(
                    block, pvt_data, missing_pvt, rwsets, assist, g
                )
            except BaseException as exc:
                # a failure after add_block would otherwise leave the
                # live block store advanced (file appended, height
                # bumped) with its index writes stranded in the
                # abandoned collector — unwind the WHOLE group (its
                # blocks were never acknowledged).  An injected
                # FaultCrash models PROCESS DEATH: no unwind runs, so
                # the chaos tests' reopen exercises the real recovery
                # path, not the graceful rollback.
                if not faultline.is_crash(exc):
                    self._rollback_group(g)
                raise
            if group is None:
                self._flush_group(g)

    def commit_group_flush(self, group: CommitGroup) -> None:
        """Land an open group: fsync the touched block files FIRST, then
        commit the group's single KV transaction (index + pvt + state +
        history + savepoints) — the same block-file-first recovery
        invariant as per-block commits, paid once per group.  Finally
        fire the deferred snapshot auto-triggers; the durability
        watermark advances so exports only see fully-synced heights."""
        if self.snapshots is not None:
            self.snapshots.wait_generation_turn()
        with self.commit_lock:
            self._flush_group(group)

    def _commit_into(
        self, block, pvt_data, missing_pvt, rwsets, assist,
        group: CommitGroup,
    ) -> None:
        t = time.perf_counter
        flags = list(protoutil.tx_filter(block))
        footprints = txids = env_bytes = None
        if assist is not None and len(assist.rwsets) == len(flags):
            rwsets = assist.rwsets
            footprints = assist.footprints
            txids = assist.txids
            env_bytes = assist.env_bytes
        if rwsets is None or len(rwsets) != len(flags):
            # only a caller without a validator comes this far (the
            # genesis block, recovery replay, devtools): a walk of every
            # envelope that no stage clock or span below covers
            rwsets = extract_rwsets(block)
        # the block store is handed the block's txids and envelope bytes
        # (it parses only a position whose txid is None)
        assisted = txids is not None and env_bytes is not None
        num = block.header.number
        t0 = t()
        # group.mvcc reads through the collector overlay, so a block
        # sees the buffered writes of earlier blocks in its group.
        # Stage spans join the validator's per-block trace when the
        # committer thread attached the CommitAssist context; the
        # stage-boundary fault points stay INSIDE each span so injected
        # trips annotate the stage they landed in.
        with tracing.span("mvcc", cat="stage", block=num) as msp:
            batch = group.mvcc.validate_and_prepare(
                num, rwsets, flags, pvt_data,
                footprints=footprints,
            )
            counts = group.mvcc.last_counts
            msp.annotate(
                valid_in=counts["valid_in"],
                read_conflicts=counts["read_conflicts"],
                phantom_conflicts=counts["phantom_conflicts"],
            )
            protoutil.set_tx_filter(block, flags)
            # stage-boundary fault points: an injected crash lands AFTER
            # the named stage's work (the any-stage crash matrix in
            # tests/test_chaos_commit.py drives every one of these)
            faultline.point("commit.stage", stage="mvcc", block=num)
        t1 = t()
        with tracing.span(
            "block_append", cat="stage", block=num, assisted=assisted,
        ):
            file_idx = self._blocks.add_block(
                block, txids=txids, env_bytes=env_bytes,
                into=group.collector, sync=False,
            )
            if file_idx is not None:
                group.dirty_files.add(file_idx)
            faultline.point(
                "commit.stage", stage="block_append", block=num
            )
        t2 = t()
        # Pvt store and state ride the SAME atomic KV transaction (with
        # the savepoint), so recovery never sees state ahead of the pvt
        # store; a crash losing the whole txn loses both together, and
        # _recover's replay records reconciler missing-data entries for
        # cleartext that went down with an unflushed group.
        with tracing.span("pvt", cat="stage", block=num):
            self.pvt_store.commit(
                num, pvt_data or {}, missing_pvt,
                into=group.collector,
            )
            faultline.point("commit.stage", stage="pvt", block=num)
        t3 = t()
        with tracing.span("state", cat="stage", block=num):
            group.state.apply_updates(batch, Height(num, len(flags)))
            faultline.point("commit.stage", stage="state", block=num)
        t4 = t()
        with tracing.span("history", cat="stage", block=num):
            self._history.commit(
                num, _history_writes(rwsets, flags, footprints),
                into=group.collector,
            )
            faultline.point("commit.stage", stage="history", block=num)
        t5 = t()
        group.blocks += 1
        group.snap_notify.append(block.header.number)
        self._active_group = group
        lm = self._lmetrics
        if lm is not None:
            lm.height.With("channel", self.ledger_id).set(
                self._blocks.height
            )
            lm.blocks_committed.With("channel", self.ledger_id).add()
            lm.commit_assist.With(
                "channel", self.ledger_id,
                "assist", "full" if assisted else "none",
            ).add()
            lm.transactions.With("channel", self.ledger_id).add(
                sum(1 for f in flags if f == 0)  # VALID
            )
            for reason in ("read", "phantom"):
                n = counts[reason + "_conflicts"]
                if n:
                    lm.mvcc_invalidated.With(
                        "channel", self.ledger_id, "reason", reason
                    ).add(n)
            found = counts["rows_found"]
            for outcome, n in (("found", found),
                               ("missing", counts["keys_asked"] - found)):
                if n:
                    lm.preload_rows.With(
                        "channel", self.ledger_id, "outcome", outcome
                    ).add(n)
        if self.snapshots is not None and self.snapshots.has_pending_request(
            block.header.number
        ):
            group.boundary_hint = True
        sub = getattr(group.mvcc, "last_stage_seconds", None) or {}
        self._observe_stages(
            mvcc=t1 - t0, block_append=t2 - t1, pvt=t3 - t2,
            state=t4 - t3, history=t5 - t4,
            # the mvcc stage's own split (preload / serial check /
            # write-set prepare) so the next optimisation round can see
            # where the remaining commit-path host time lives
            mvcc_preload=sub.get("preload", 0.0),
            mvcc_check=sub.get("check", 0.0),
            mvcc_prepare=sub.get("prepare", 0.0),
        )

    def _flush_group(self, group: CommitGroup) -> None:
        # static guard (devtools/guards.py) cross-checked at runtime:
        # the open group and durability watermark move only under the
        # commit lock
        guarded(self, "_active_group", by="kvledger.commit_lock")
        if group.blocks:
            # flush spans are attributed to the group's boundary block
            # so the bench critical-path summary can charge the fsync/
            # kv_txn wall time to the block whose flush paid it
            boundary = (
                group.snap_notify[-1] if group.snap_notify else None
            )
            t0 = time.perf_counter()
            try:
                with tracing.span(
                    "fsync", cat="stage", block=boundary,
                    blocks=group.blocks,
                ):
                    self._blocks.sync_files(group.dirty_files)
                    faultline.point("commit.stage", stage="fsync")
                t1 = time.perf_counter()
                rows = group.collector.pending
                with tracing.span(
                    "kv_txn", cat="stage", block=boundary,
                    blocks=group.blocks, rows=rows,
                    clustered=self._kv.clustered,
                    mmap_bytes=self._kv.mmap_bytes,
                ):
                    group.collector.flush()
                    faultline.point("commit.stage", stage="kv_txn")
            except BaseException as exc:
                # roll the WHOLE group back so the live ledger stays
                # consistent with committed storage: the buffered index
                # data is gone, so the unindexed file appends go with it
                # and height/hash return to the durable watermark.  The
                # group's blocks were never acknowledged; callers may
                # re-commit them into a fresh (or this, now-empty) group.
                # An injected FaultCrash (simulated process death) skips
                # the unwind — reopen must run real recovery instead.
                if not faultline.is_crash(exc):
                    self._rollback_group(group)
                raise
            t2 = time.perf_counter()
            self._observe_stages(fsync=t1 - t0, kv_txn=t2 - t1)
            if self._metrics is not None:
                self._metrics.blocks_per_sync.With(
                    "channel", self.ledger_id
                ).observe(group.blocks)
            if self._lmetrics is not None:
                self._lmetrics.kv_txn_rows.With(
                    "channel", self.ledger_id
                ).add(rows)
            # the base store changed under the main view's caches
            self._state.invalidate_caches()
            self._durable_height = self._blocks.height
            self._durable_hash = self._blocks.last_block_hash
            self._publish_heights()
        notify, group.snap_notify = group.snap_notify, []
        group.blocks = 0
        group.dirty_files.clear()
        group.boundary_hint = False
        if self._active_group is group:
            self._active_group = None
        if self.snapshots is not None:
            for num in notify:
                self.snapshots.on_block_committed(num)

    def _rollback_group(self, group: CommitGroup) -> None:
        """Discard a group's buffered KV writes, truncate its unindexed
        file appends, and restore block-store height/hash to committed
        state — the all-or-nothing unwind for any group failure."""
        group.collector.discard()
        self._blocks.truncate_to_checkpoint()
        group.blocks = 0
        group.dirty_files.clear()
        group.snap_notify.clear()
        group.boundary_hint = False
        group.state.invalidate_caches()
        if self._active_group is group:
            self._active_group = None
        self._publish_heights()

    def _observe_stages(self, **stages: float) -> None:
        acc = self.commit_stage_seconds
        for name, dt in stages.items():
            acc[name] = acc.get(name, 0.0) + dt
            if self._metrics is not None:
                self._metrics.stage_duration.With(
                    "channel", self.ledger_id, "stage", name
                ).observe(dt)

    @property
    def durable_height(self) -> int:
        """Height as of the last flushed group boundary — block files
        fsynced and the KV transaction committed up to here."""
        return self._durable_height

    @property
    def durable_block_hash(self) -> bytes:
        return self._durable_hash

    def commit_old_pvt_data(
        self, block_num: int, tx_num: int, pvt_bytes: bytes
    ) -> None:
        """Apply reconciled private data from an old block (reference
        CommitPvtDataOfOldBlocks): persist in the pvt store and update the
        private state for keys whose hashed version still points at
        (block_num, tx_num) — anything newer means the value is stale and
        only the store copy is kept."""
        from fabric_tpu.ledger.txmgmt import key_hash as _kh
        from fabric_tpu.protos.ledger.rwset import rwset_pb2 as _rw
        from fabric_tpu.protos.ledger.rwset.kvrwset import (
            kv_rwset_pb2 as _kvrw,
        )

        self.pvt_store.resolve_missing(block_num, tx_num, pvt_bytes)
        h = Height(block_num, tx_num)
        batch: dict[str, dict] = {}
        txpvt = _rw.TxPvtReadWriteSet.FromString(pvt_bytes)
        for nsp in txpvt.ns_pvt_rwset:
            for cp in nsp.collection_pvt_rwset:
                hns = hash_ns(nsp.namespace, cp.collection_name)
                pns = pvt_ns(nsp.namespace, cp.collection_name)
                kvrw = _kvrw.KVRWSet.FromString(cp.rwset)
                for w in kvrw.writes:
                    hv = self._state.get_version(
                        hns, _kh(w.key).hex()
                    )
                    if hv != h:
                        continue  # stale: overwritten since
                    from fabric_tpu.ledger.statedb import VersionedValue

                    batch.setdefault(pns, {})[w.key] = (
                        None if w.is_delete else VersionedValue(w.value, h)
                    )
        if batch:
            self._state.apply_updates(batch, None)

    # -- queries -----------------------------------------------------------

    @property
    def block_store(self):
        """Read access to the underlying block store (qscc's query
        surface — GetBlockByHash/GetTransactionByID/GetBlockByTxID ride
        the store's indexes directly, reference core/scc/qscc/query.go)."""
        return self._blocks

    @property
    def state_db(self):
        """Read access to the versioned state DB (the snapshot exporter
        streams its raw records; everything else should go through the
        query executor / simulator)."""
        return self._state

    @property
    def height(self) -> int:
        return self._blocks.height

    def get_blockchain_info(self):
        return self._blocks.info()

    def get_block_by_number(self, num: int):
        return self._blocks.get_block_by_number(num)

    def get_block_by_hash(self, h: bytes):
        return self._blocks.get_block_by_hash(h)

    def get_tx_by_id(self, txid: str):
        return self._blocks.get_tx_by_id(txid)

    def get_tx_validation_code(self, txid: str):
        return self._blocks.get_tx_validation_code(txid)

    def tx_id_exists(self, txid: str) -> bool:
        # presence probe, not a location lookup: txids imported from a
        # snapshot have no block location but still count as duplicates
        return bool(self._blocks.tx_ids_exist([txid]))

    def tx_ids_exist(self, txids) -> set[str]:
        """Bulk duplicate-txid probe (one index round-trip)."""
        return self._blocks.tx_ids_exist(txids)

    def may_have_state_metadata(self, ns: str) -> bool:
        """False guarantees no key in `ns` (public or derived hashed
        namespace) carries state metadata — the validator's key-level
        endorsement fast path."""
        return self._state.may_have_metadata(ns)

    def holds_state_metadata(self) -> bool:
        """False guarantees that NO namespace of the committed state
        carries metadata: `may_have_state_metadata` is False for every
        one (the validator asks this once a block)."""
        return self._state.holds_metadata()

    def define_index(self, ns: str, field: str) -> None:
        """Create (and backfill) a rich-query index on a dotted JSON
        field of a namespace — the statecouchdb index-definition
        equivalent (statecouchdb.go:53); chaincode deployments feed
        this from META-INF/statedb/indexes/*.json."""
        self._state.define_index(ns, field)

    def new_tx_simulator(self) -> TxSimulator:
        return TxSimulator(self._state)

    def new_query_executor(self) -> "QueryExecutor":
        """Read-only executor (reference ledger.QueryExecutor,
        core/ledger/ledger_interface.go:214)."""
        return QueryExecutor(self._state)

    def get_state(self, ns: str, key: str) -> bytes | None:
        return self.new_query_executor().get_state(ns, key)

    def get_state_range(self, ns: str, start: str, end: str):
        return self.new_query_executor().get_state_range(ns, start, end)

    def get_private_data(self, ns: str, coll: str, key: str) -> bytes | None:
        return self.new_query_executor().get_private_data(ns, coll, key)

    def get_private_data_hash(self, ns: str, coll: str, key: str):
        return self.new_query_executor().get_private_data_hash(ns, coll, key)

    def get_state_metadata(self, ns: str, key: str) -> dict[str, bytes]:
        return self.new_query_executor().get_state_metadata(ns, key)

    def get_state_metadata_many(self, pairs) -> dict:
        return self.new_query_executor().get_state_metadata_many(pairs)

    def get_history_for_key(self, ns: str, key: str):
        return self._history.get_history_for_key(ns, key)


class QueryExecutor:
    """Read-only state access handed to SCCs/endorser queries (reference
    QueryExecutor ledger_interface.go:214: GetState/GetStateRange/
    GetPrivateData*).  No read recording — never part of a transaction."""

    def __init__(self, state: VersionedDB):
        self._state = state

    def get_state(self, ns: str, key: str) -> bytes | None:
        vv = self._state.get_state(ns, key)
        return vv.value if vv else None

    def get_state_multiple(self, ns: str, keys) -> list[bytes | None]:
        return [
            vv.value if vv else None
            for vv in self._state.get_state_multiple(ns, keys)
        ]

    def get_state_range(self, ns: str, start: str, end: str):
        for key, vv in self._state.get_state_range(ns, start, end):
            yield key, vv.value

    def get_private_data(self, ns: str, coll: str, key: str) -> bytes | None:
        vv = self._state.get_state(pvt_ns(ns, coll), key)
        return vv.value if vv else None

    def get_private_data_hash(self, ns: str, coll: str, key: str):
        vv = self._state.get_state(hash_ns(ns, coll), key_hash(key).hex())
        return vv.value if vv else None

    def get_state_metadata(self, ns: str, key: str) -> dict[str, bytes]:
        """Decoded metadata entries of a key, matching the simulator's
        get_state_metadata; `ns` may be a derived hashed namespace."""
        from fabric_tpu.ledger.txmgmt import decode_metadata

        if not self._state.may_have_metadata(ns):
            return {}  # namespace never stored metadata: skip the store
        vv = self._state.get_state(ns, key)
        return decode_metadata(vv.metadata) if vv else {}

    def get_state_metadata_many(self, pairs) -> dict:
        """`get_state_metadata` of every (ns, key) pair, as ONE read of
        the same committed view: {(ns, key): entries}, with an entry for
        every pair asked for ({} for a key that is absent or carries
        none).  Pairs of a namespace that never stored metadata are
        answered without the store, the rest in one `get_state_many`;
        equal metadata is decoded once, so its keys share one dict:
        the answer is for reading."""
        from fabric_tpu.ledger.txmgmt import decode_metadata

        out: dict = {}
        may: dict[str, bool] = {}
        ask: list = []
        for pair in pairs:
            ns = pair[0]
            m = may.get(ns)
            if m is None:
                m = may[ns] = self._state.may_have_metadata(ns)
            if m:
                ask.append(pair)
            else:
                out[pair] = {}
        if ask:
            decoded: dict[bytes, dict] = {}
            for pair, vv in self._state.get_state_many(ask).items():
                raw = vv.metadata if vv else b""
                entries = decoded.get(raw)
                if entries is None:
                    entries = decoded[raw] = decode_metadata(raw)
                out[pair] = entries
        return out

    def done(self) -> None:
        pass


class LedgerProvider:
    """Opens/creates per-channel ledgers under one root (reference
    kv_ledger_provider.go + ledgermgmt).  `csp`/`metrics` feed the
    snapshot subsystem: per-file digests of generated snapshots go
    through csp.hash_batch; `snapshots_dir` defaults to
    <root>/snapshots."""

    def __init__(self, root_dir: str | None = None, csp=None, metrics=None,
                 snapshots_dir: str | None = None, commit_metrics=None,
                 ledger_metrics=None):
        self._root = root_dir
        self._csp = csp
        self._metrics = metrics
        self._commit_metrics = commit_metrics
        self._ledger_metrics = ledger_metrics
        if snapshots_dir is None and root_dir is not None:
            snapshots_dir = os.path.join(root_dir, "snapshots")
        self._snapshots_dir = snapshots_dir
        if root_dir is not None:
            os.makedirs(root_dir, exist_ok=True)
        self._kv = open_store_root(root_dir)
        self._ledgers: dict[str, KVLedger] = {}

    def create(self, genesis_block: common_pb2.Block) -> KVLedger:
        """Create from a genesis block (ledger id = channel id inside)."""
        env = protoutil.extract_envelope(genesis_block, 0)
        payload = common_pb2.Payload.FromString(env.payload)
        chdr = common_pb2.ChannelHeader.FromString(payload.header.channel_header)
        ledger = self.open(chdr.channel_id)
        if ledger.height == 0:
            ledger.commit(genesis_block)
        return ledger

    def open(self, ledger_id: str) -> KVLedger:
        if ledger_id in self._ledgers:
            return self._ledgers[ledger_id]
        from fabric_tpu.ledger import snapshot as snap

        # a crashed join-by-snapshot leaves the stores holding an
        # arbitrary prefix of the snapshot (bootstrap info without
        # state, or state without config history) — refuse LOUDLY
        # instead of opening a channel whose reads would silently
        # disagree with the chain it claims to be at
        if snap.import_marker(self._kv, ledger_id) == \
                snap.IMPORT_IN_PROGRESS:
            raise snap.SnapshotError(
                f"channel {ledger_id!r} has a half-finished snapshot "
                "import (the importing process crashed); run "
                "discard_failed_import() and re-join from the snapshot"
            )
        block_dir = (
            None if self._root is None else os.path.join(self._root, ledger_id, "chains")
        )
        store = BlockStore(block_dir, self._kv, name=ledger_id)
        ledger = KVLedger(
            ledger_id, store, self._kv, metrics=self._commit_metrics,
            ledger_metrics=self._ledger_metrics,
        )
        self._wire_snapshots(ledger)
        self._ledgers[ledger_id] = ledger
        return ledger

    def _wire_snapshots(self, ledger: KVLedger) -> None:
        from fabric_tpu.ledger.snapshot import SnapshotManager

        ledger.snapshots = SnapshotManager(
            ledger, self._snapshots_dir, self._kv,
            csp=self._csp, metrics=self._metrics,
        )

    def create_from_snapshot(self, snapshot_dir: str) -> KVLedger:
        """Bootstrap a BLOCKLESS channel ledger from a verified snapshot
        (reference kv_ledger_provider.go CreateFromSnapshot): the block
        store records the bootstrap height + last block hash so commit
        resumes at the snapshot height, the state DB is bulk-loaded with
        its savepoint at the snapshot, and deliver-based catch-up
        (height_fn) naturally starts there.  Verification recomputes
        every file digest through csp.hash_batch and refuses tampered
        snapshots."""
        from fabric_tpu.ledger import snapshot as snap

        meta = snap.verify_snapshot(snapshot_dir, csp=self._csp)
        ledger_id = meta["channel_id"]
        if ledger_id in self._ledgers:
            raise snap.SnapshotError(
                f"ledger {ledger_id!r} already exists"
            )
        if snap.import_marker(self._kv, ledger_id) == \
                snap.IMPORT_IN_PROGRESS:
            raise snap.SnapshotError(
                f"channel {ledger_id!r} has a half-finished snapshot "
                "import; run discard_failed_import() before re-joining"
            )
        block_dir = (
            None if self._root is None
            else os.path.join(self._root, ledger_id, "chains")
        )
        store = BlockStore(block_dir, self._kv, name=ledger_id)
        if store.height:
            raise snap.SnapshotError(
                f"channel {ledger_id!r} already has {store.height} blocks"
            )
        snap.import_snapshot(meta, snapshot_dir, store, self._kv, ledger_id)
        ledger = KVLedger(
            ledger_id, store, self._kv, metrics=self._commit_metrics,
            ledger_metrics=self._ledger_metrics,
        )
        self._wire_snapshots(ledger)
        self._ledgers[ledger_id] = ledger
        return ledger

    # every per-channel namespace mounted on the shared KV store — the
    # discard sweep below must cover ALL of them, or a retried import
    # would land on residue (bookkeeping is a two-level namespace:
    # bookkeeping/<lid>/<category>)
    _CHANNEL_NAMESPACES = (
        "blkindex/{lid}", "statedb/{lid}", "historydb/{lid}",
        "pvtdata/{lid}", "confighistory/{lid}", "transient/{lid}",
        "bookkeeping/{lid}/", "snapimport/{lid}",
    )

    def discard_failed_import(self, ledger_id: str) -> int:
        """Clear the debris of a CRASHED snapshot import so the channel
        can re-join (the recovery path the half-import refusal points
        operators at).  Deliberately narrow: refuses unless the
        channel's import marker is IMPORT_IN_PROGRESS — this is a
        crashed-import cleanup, not a general channel-delete.  Sweeps
        every per-channel namespace off the shared KV store (the marker
        goes LAST, so a crash mid-discard leaves the channel still
        refused, and the discard itself is re-runnable) and removes the
        channel's block-file directory.  Returns the number of KV keys
        deleted."""
        from fabric_tpu.ledger import snapshot as snap
        from fabric_tpu.ledger.kvstore import NamedDB, wipe_prefix

        if snap.import_marker(self._kv, ledger_id) != \
                snap.IMPORT_IN_PROGRESS:
            raise snap.SnapshotError(
                f"channel {ledger_id!r} has no half-finished snapshot "
                "import to discard"
            )
        deleted = 0
        marker_prefix = (
            f"snapimport/{ledger_id}".encode() + NamedDB._SEP
        )
        for ns in self._CHANNEL_NAMESPACES:
            name = ns.format(lid=ledger_id)
            # bookkeeping/<lid>/ spans its categories' namespaces, so
            # the raw name (sans separator) is the scan prefix there
            prefix = name.encode() if name.endswith("/") else (
                name.encode() + NamedDB._SEP
            )
            if prefix == marker_prefix:
                continue  # the marker falls last, below
            deleted += wipe_prefix(self._kv, prefix)
        if self._root is not None:
            chain_dir = os.path.join(self._root, ledger_id)
            if os.path.isdir(chain_dir):
                import shutil

                shutil.rmtree(chain_dir)
        NamedDB(self._kv, f"snapimport/{ledger_id}").delete(b"state")
        return deleted

    @property
    def kv(self):
        """The provider's shared index KVStore — side stores that live
        next to the ledgers (transient store) mount namespaces on it."""
        return self._kv

    @property
    def snapshots_root(self) -> str | None:
        """The completed/in_progress snapshot tree this provider's
        ledgers export into — the directory admin.SnapshotFetch serves
        remote join-by-snapshot from."""
        return self._snapshots_dir

    def list(self) -> list[str]:
        return sorted(self._ledgers)

    def close(self) -> None:
        for led in self._ledgers.values():
            led._blocks.close()
        self._kv.close()


__all__ = [
    "KVLedger",
    "LedgerProvider",
    "QueryExecutor",
    "CommitGroup",
    "extract_rwsets",
]
