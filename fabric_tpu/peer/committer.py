"""Commit orchestration: validate -> commit -> notify.

Reference: gossip/privdata/coordinator.go:149 StoreBlock (txvalidator ->
pvtdata assembly -> CommitLegacy) + core/committer/committer_impl.go.
Private-data fetching slots in between validate and commit when the
pvtdata subsystem lands.

`store_stream` is the TPU-first throughput path: the validator pipeline
overlaps host collect with device verify across blocks, and a dedicated
committer thread overlaps MVCC+persist of block k with collect of
k+1/k+2 (the reference serializes validate -> commit per block inside
StoreBlock; deliver clients therefore see commit latency on the
validation critical path)."""

from __future__ import annotations

import collections
import queue
import threading
import time

from fabric_tpu.common import gcpolicy, tracing
from fabric_tpu.devtools.lockwatch import spawn_thread


def validate_for_commit(validator, block):
    """Validate a lone block (sets its sig/policy flags) and hand back
    what the validation learned of it: the CommitAssist that
    store_stream's pipeline hands KVLedger.commit with every block, so
    a lone block's commit re-decodes no envelope either.  None from a
    validator that keeps none; the ledger then parses for itself."""
    validator.validate(block)
    take = getattr(validator, "take_assist", None)
    return None if take is None else take()


class Committer:
    def __init__(self, validator, ledger, metrics=None):
        self._validator = validator
        self._ledger = ledger
        self._listeners: list = []
        self._lock = threading.Lock()
        self.metrics = metrics
        # whoever builds a committer is about to validate and commit
        # blocks, and has imported what that takes: the process is warm
        # (idempotent, process-wide; see common/gcpolicy.py)
        gcpolicy.settle()

    def add_commit_listener(self, fn) -> None:
        self._listeners.append(fn)

    def get_block_by_number(self, num: int):
        """Committed-block reader for gossip state transfer
        (gossip/state.py _read_committed serves state_requests from it
        once blocks age out of the gossip message store)."""
        return self._ledger.get_block_by_number(num)

    def store_block(self, block) -> list[int]:
        """The per-block pipeline; returns final validation flags."""
        t0 = time.perf_counter()
        assist = validate_for_commit(self._validator, block)
        t_validate = time.perf_counter() - t0
        # the commit stages join the block's trace through
        # CommitAssist.trace_ctx, as they do in store_stream
        with self._lock, tracing.attached(
            getattr(assist, "trace_ctx", None)
        ):
            # MVCC + persist (updates flags again)
            self._ledger.commit(block, assist=assist)
        # nothing of the block's parse is in hand when pipeline_empty()
        # collects below (held, a full collection walks its footprints)
        del assist
        if self.metrics is not None:
            self.metrics.observe(
                "validate_duration", t_validate, channel=self._validator.channel_id
            )
            self.metrics.observe(
                "commit_duration",
                time.perf_counter() - t0,
                channel=self._validator.channel_id,
            )
        from fabric_tpu import protoutil

        flags = list(protoutil.tx_filter(block))
        for fn in self._listeners:
            fn(block, flags)
        gcpolicy.pipeline_empty()  # a lone block, committed
        return flags

    def store_stream(self, blocks, depth: int = 3):
        """Pipelined validate+commit over a block stream; yields each
        block's final (post-MVCC) flags in order.

        Three overlapped stages: host collect (validator), device
        verify (CSP async), and MVCC+persist (this method's committer
        thread).  The flags are those of `store_block`, one block at a
        time, at any depth: where a transaction's key-level
        endorsement policy depends on an earlier block of the stream,
        the validator decides it once that block's commit has landed
        here (`validate_pipeline`; the release below tells it), and a
        commit that fails ends its wait with the failure.

        Group commit: the committer thread buffers up to `depth` blocks
        into one CommitGroup (one shared KV transaction + unsynced
        block-file appends) and flushes at the group boundary — one
        fsync + one KV txn for the whole group.  The boundary triggers
        when `depth` blocks are buffered OR the commit queue drains
        (so a validator-bound stream still goes durable block by block
        and adds no latency).  Listener callbacks, dedup-window
        releases, and yielded flags all wait for the flush: nothing is
        announced before it is durable."""
        from fabric_tpu import protoutil

        pending: collections.deque = collections.deque()
        releases: collections.deque = collections.deque()
        rwsets_q: collections.deque = collections.deque()

        def tee(it):
            for b in it:
                pending.append(b)
                yield b

        commit_q: queue.Queue = queue.Queue(maxsize=depth)
        done_q: queue.Queue = queue.Queue()

        # the newest release the validator handed out: through it the
        # committer thread tells a validator that waits for a commit
        # (key-level endorsement) that the commit will not come
        newest: list = [None]

        def give_up(exc):
            abort = getattr(newest[0], "abort", None)
            if abort is not None:
                abort(exc)

        def commit_loop():
            try:
                commit_blocks()
            except BaseException as e:
                # whatever ends this thread ends the stream: neither the
                # consumer (on done_q) nor the validator (on a commit)
                # is left waiting, and the consumer raises what it was
                died = RuntimeError(f"the committer thread died: {e!r}")
                died.__cause__ = e
                give_up(died)
                done_q.put(died)

        def commit_blocks():
            failed = False
            group = self._ledger.begin_commit_group()
            grouped: list = []  # (block, release_txids) awaiting flush

            def announce():
                # post-flush callbacks run OUTSIDE self._lock (as the
                # per-block path always did): a listener re-entering
                # the Committer must not deadlock, and slow listeners
                # must not serialize against other commit entrypoints
                for blk, release in grouped:
                    # the ledger index now durably holds these txids:
                    # safe to close the validator's in-flight dedup
                    # window
                    release()
                    flags = list(protoutil.tx_filter(blk))
                    for fn in self._listeners:
                        fn(blk, flags)
                    done_q.put(flags)
                grouped.clear()

            while True:
                # whoever waits longer sets the pace: this thread here
                # (validator-bound), the main thread in
                # commit.backpressure / commit.await_flags below
                with tracing.span(
                    "commit.idle", cat="stage", depth=commit_q.qsize(),
                ):
                    item = commit_q.get()
                if item is None:
                    if not failed and grouped:
                        try:
                            with self._lock:
                                self._ledger.commit_group_flush(group)
                            announce()
                        except Exception as e:
                            give_up(e)
                            done_q.put(e)
                    return
                if failed:
                    continue  # drain without committing past a failure
                blk, release_txids, assist = item
                try:
                    flushed = False
                    with self._lock, tracing.attached(
                        getattr(assist, "trace_ctx", None)
                    ):
                        self._ledger.commit(blk, assist=assist, group=group)
                        grouped.append((blk, release_txids))
                        # boundary_hint: a buffered block carries a
                        # pending snapshot request — flush HERE so the
                        # export height is exactly the requested one
                        if (
                            len(grouped) >= depth
                            or commit_q.empty()
                            or getattr(group, "boundary_hint", False)
                        ):
                            self._ledger.commit_group_flush(group)
                            flushed = True
                    if flushed:
                        announce()
                except Exception as e:  # surfaced to the consumer
                    # (a raising LISTENER counts too — the thread must
                    # post the error, never die leaving the consumer
                    # blocked on done_q); nothing further commits onto
                    # suspect state
                    failed = True
                    give_up(e)
                    done_q.put(e)

        th = spawn_thread(
            target=commit_loop, name="committer-stream", kind="worker"
        )
        th.start()
        n_in = n_out = 0
        try:
            for _flags in self._validator.validate_pipeline(
                tee(blocks), depth=depth, release=releases.append,
                rwsets_out=rwsets_q.append,
            ):
                assist = rwsets_q.popleft()
                newest[0] = landed = releases.popleft()
                with tracing.span(
                    "commit.backpressure", cat="stage",
                    parent=getattr(assist, "trace_ctx", None),
                    depth=commit_q.qsize(),
                ):
                    commit_q.put((pending.popleft(), landed, assist))
                n_in += 1
                while not done_q.empty():
                    r = done_q.get()
                    if isinstance(r, Exception):
                        raise r
                    n_out += 1
                    yield r
            while n_out < n_in:
                with tracing.span(
                    "commit.await_flags", cat="stage", depth=n_in - n_out,
                ):
                    r = done_q.get()
                if isinstance(r, Exception):
                    raise r
                n_out += 1
                yield r
        finally:
            commit_q.put(None)
            th.join()
            gcpolicy.pipeline_empty()  # the stream's last flags are out

    @property
    def height(self) -> int:
        """DURABLE chain height — gossip state transfer keys payload
        dedup and peer advertisement off this, and a buffered group's
        blocks are neither readable nor guaranteed to survive (a flush
        failure rolls them back), so they must not be advertised or
        used to drop incoming copies."""
        return getattr(self._ledger, "durable_height", self._ledger.height)


__all__ = ["Committer", "validate_for_commit"]
