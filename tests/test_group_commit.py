"""Group-commit ledger pipeline tests (ISSUE 2 tentpole): one atomic KV
transaction + one coalesced fsync per commit group on the measured path,
overlay-visible MVCC across a group's buffered blocks, crash recovery at
both torn points (after the block-file append but before the KV txn, and
at a group boundary with unsynced tail blocks), the durability watermark
snapshot exports observe, and the per-stage commit timing breakdown
(reference kv_ledger.go:447 CommitLegacy + blockfile recovery)."""

import os

import pytest

from fabric_tpu import protoutil
from fabric_tpu.ledger import LedgerProvider, blkstorage
from fabric_tpu.ledger.kvstore import (
    MemKVStore,
    SqliteKVStore,
    WriteBatchCollector,
)
from fabric_tpu.ledger.statedb import Height, VersionedDB, VersionedValue
from fabric_tpu.ledger.txmgmt import VALID

from test_ledger import _endorsed_block, _sim_rwset


def _write_block(ledger, num, items):
    """An endorser block writing [(ns, key, value)] via this ledger's
    own simulator (reads recorded against committed state)."""
    sim = ledger.new_tx_simulator()
    for ns, k, v in items:
        sim.set_state(ns, k, v)
    return _endorsed_block(
        num, ledger.block_store.last_block_hash,
        [sim.get_tx_simulation_results()],
    )


class _Counts:
    """Count base-store KV transactions (every SqliteKVStore write
    entrypoint is one sqlite txn) and block-file data barriers (the
    segment writer's fdatasync; segment prealloc/roll metadata fsyncs
    are NOT commit-path barriers and are counted separately)."""

    def __init__(self, monkeypatch):
        self.txns = 0
        self.fsyncs = 0
        self.meta_fsyncs = 0
        real_wb = SqliteKVStore.write_batch
        real_wba = SqliteKVStore.write_batch_if_absent
        real_fdatasync = blkstorage.os.fdatasync
        real_fsync = blkstorage.os.fsync

        def wb(store, puts, deletes=()):
            self.txns += 1
            return real_wb(store, puts, deletes)

        def wba(store, puts):
            self.txns += 1
            return real_wba(store, puts)

        def fds(fd):
            self.fsyncs += 1
            return real_fdatasync(fd)

        def fs(fd):
            self.meta_fsyncs += 1
            return real_fsync(fd)

        monkeypatch.setattr(SqliteKVStore, "write_batch", wb)
        monkeypatch.setattr(SqliteKVStore, "write_batch_if_absent", wba)
        monkeypatch.setattr(blkstorage.os, "fdatasync", fds)
        monkeypatch.setattr(blkstorage.os, "fsync", fs)

    def reset(self):
        self.txns = self.fsyncs = self.meta_fsyncs = 0


def test_write_batch_collector_contract():
    base = MemKVStore()
    base.write_batch({b"a": b"1", b"c": b"3", b"d": b"4"})
    c = WriteBatchCollector(base)
    c.write_batch({b"b": b"2", b"c": b"30"}, [b"d"])
    # overlay-aware reads
    assert c.get(b"a") == b"1"
    assert c.get(b"b") == b"2"
    assert c.get(b"c") == b"30"
    assert c.get(b"d") is None
    assert c.get_many([b"a", b"b", b"c", b"d"]) == {
        b"a": b"1", b"b": b"2", b"c": b"30",
    }
    # merged ordered iteration
    assert [(k, v) for k, v in c.iterate()] == [
        (b"a", b"1"), (b"b", b"2"), (b"c", b"30"),
    ]
    assert [k for k, _ in c.iterate(b"b", b"c")] == [b"b"]
    # first-wins insert-if-absent sees the overlay
    c.write_batch_if_absent({b"b": b"XX", b"e": b"5"})
    assert c.get(b"b") == b"2" and c.get(b"e") == b"5"
    # nothing reached the base yet; flush lands everything at once
    assert base.get(b"b") is None and base.get(b"d") == b"4"
    assert c.pending == 4
    c.flush()
    assert c.pending == 0
    assert base.get(b"b") == b"2"
    assert base.get(b"c") == b"30"
    assert base.get(b"d") is None
    assert base.get(b"e") == b"5"


def test_single_commit_one_txn_one_fsync(tmp_path, monkeypatch):
    provider = LedgerProvider(str(tmp_path))
    ledger = provider.open("gc")
    ledger.commit(_write_block(ledger, 0, [("cc", "k0", b"v0")]))
    counts = _Counts(monkeypatch)
    ledger.commit(_write_block(ledger, 1, [("cc", "k1", b"v1")]))
    # block index + pvt + state(+savepoint) + history in ONE sqlite txn,
    # one block-file fsync (the pre-group path paid 1 fsync + 5 txns)
    assert counts.txns == 1
    assert counts.fsyncs == 1
    assert ledger.get_state("cc", "k1") == b"v1"
    assert ledger.get_history_for_key("cc", "k1") == [(1, 0)]
    assert ledger.durable_height == ledger.height == 2
    provider.close()


def test_group_commit_one_txn_one_fsync_per_group(tmp_path, monkeypatch):
    provider = LedgerProvider(str(tmp_path))
    ledger = provider.open("gc")
    ledger.commit(_write_block(ledger, 0, [("cc", "k", b"v0")]))
    counts = _Counts(monkeypatch)

    # block 1 overwrites k; block 2 READS k at block 1's version — only
    # visible through the group's overlay — then writes again
    group = ledger.begin_commit_group()
    blk1 = _write_block(ledger, 1, [("cc", "k", b"v1")])
    ledger.commit(blk1, group=group)
    scratch = VersionedDB(MemKVStore())
    scratch.apply_updates(
        {"cc": {"k": VersionedValue(b"v1", Height(1, 0))}}, None
    )
    rw2 = _sim_rwset(scratch, reads=[("cc", "k")], writes=[("cc", "k", b"v2")])
    blk2 = _endorsed_block(2, ledger.block_store.last_block_hash, [rw2])
    ledger.commit(blk2, group=group)
    blk3 = _write_block(ledger, 3, [("cc", "k3", b"v3")])
    ledger.commit(blk3, group=group)

    # nothing durable or base-visible before the boundary
    assert counts.txns == 0 and counts.fsyncs == 0
    assert ledger.height == 4
    assert ledger.durable_height == 1
    assert ledger.get_state("cc", "k") == b"v0"

    ledger.commit_group_flush(group)
    assert counts.txns == 1 and counts.fsyncs == 1
    assert list(protoutil.tx_filter(blk2)) == [VALID]
    assert ledger.durable_height == 4
    assert ledger.get_state("cc", "k") == b"v2"
    assert ledger.get_state("cc", "k3") == b"v3"
    assert ledger.get_history_for_key("cc", "k") == [(0, 0), (1, 0), (2, 0)]
    assert ledger.get_tx_validation_code("tx-2-0") == VALID
    provider.close()


def test_crash_after_append_before_kv_txn(tmp_path):
    """Torn point A: the block file holds the record but the group's KV
    transaction (index + state + savepoint) never landed — _recover must
    re-index the trailing block and replay state to a consistent
    height."""
    provider = LedgerProvider(str(tmp_path))
    ledger = provider.open("gc")
    ledger.commit(_write_block(ledger, 0, [("cc", "a", b"0")]))
    ledger.commit(_write_block(ledger, 1, [("cc", "b", b"1")]))
    group = ledger.begin_commit_group()
    ledger.commit(
        _write_block(ledger, 2, [("cc", "c", b"2")]), group=group
    )
    # "crash": the collector (and its buffered index/savepoint) is
    # simply dropped; only the unsynced file append survives
    provider.close()

    provider2 = LedgerProvider(str(tmp_path))
    led2 = provider2.open("gc")
    assert led2.height == 3
    assert led2.get_state("cc", "c") == b"2"
    assert led2.get_state("cc", "b") == b"1"
    assert led2.get_tx_validation_code("tx-2-0") == VALID
    assert led2.state_db.savepoint() == Height(2, 1)
    assert led2.durable_height == 3
    provider2.close()


def test_crash_with_unsynced_tail_at_group_boundary(tmp_path):
    """Torn point B: one group flushed (durable), a second group's tail
    appended but never flushed — recovery replays the tail from the file
    scan on top of the flushed savepoint."""
    provider = LedgerProvider(str(tmp_path))
    ledger = provider.open("gc")
    ledger.commit(_write_block(ledger, 0, [("cc", "a", b"0")]))
    g1 = ledger.begin_commit_group()
    ledger.commit(_write_block(ledger, 1, [("cc", "b", b"1")]), group=g1)
    ledger.commit(_write_block(ledger, 2, [("cc", "c", b"2")]), group=g1)
    ledger.commit_group_flush(g1)
    g2 = ledger.begin_commit_group()
    ledger.commit(_write_block(ledger, 3, [("cc", "d", b"3")]), group=g2)
    ledger.commit(_write_block(ledger, 4, [("cc", "e", b"4")]), group=g2)
    provider.close()  # g2 never flushed

    provider2 = LedgerProvider(str(tmp_path))
    led2 = provider2.open("gc")
    assert led2.height == 5
    for key, val in (("b", b"1"), ("c", b"2"), ("d", b"3"), ("e", b"4")):
        assert led2.get_state("cc", key) == val
    assert led2.state_db.savepoint() == Height(4, 1)
    assert led2.get_history_for_key("cc", "e") == [(4, 0)]
    provider2.close()


def test_flush_failure_rolls_group_back(tmp_path, monkeypatch):
    """A group flush that cannot land its KV transaction must roll the
    WHOLE group back — height/hash return to the durable watermark, the
    unindexed file appends are truncated away, and the same blocks can
    be re-committed cleanly afterward."""
    provider = LedgerProvider(str(tmp_path))
    ledger = provider.open("gc")
    ledger.commit(_write_block(ledger, 0, [("cc", "a", b"0")]))

    blk1 = _write_block(ledger, 1, [("cc", "b", b"1")])
    blk2 = _write_block(ledger, 2, [("cc", "c", b"2")])
    group = ledger.begin_commit_group()
    ledger.commit(blk1, group=group)
    ledger.commit(blk2, group=group)

    real_wb = SqliteKVStore.write_batch
    def boom(store, puts, deletes=()):
        raise OSError("disk full")
    monkeypatch.setattr(SqliteKVStore, "write_batch", boom)
    with pytest.raises(OSError, match="disk full"):
        ledger.commit_group_flush(group)
    monkeypatch.setattr(SqliteKVStore, "write_batch", real_wb)

    # live object consistent with committed storage again
    assert ledger.height == ledger.durable_height == 1
    assert ledger.get_state("cc", "b") is None
    # the rolled-back blocks re-commit cleanly (fresh copies: flags and
    # last-hash links are rebuilt by the new commit)
    ledger.commit(_write_block(ledger, 1, [("cc", "b", b"1")]))
    ledger.commit(_write_block(ledger, 2, [("cc", "c", b"2")]))
    assert ledger.get_state("cc", "c") == b"2"
    provider.close()

    provider2 = LedgerProvider(str(tmp_path))
    led2 = provider2.open("gc")
    assert led2.height == 3
    assert led2.get_state("cc", "b") == b"1"
    provider2.close()


def test_commit_failure_mid_group_rolls_back(tmp_path, monkeypatch):
    """An exception AFTER the block-file append (history stage here)
    must unwind the whole group — otherwise the live store advertises a
    height whose index writes died with the collector."""
    provider = LedgerProvider(str(tmp_path))
    ledger = provider.open("gc")
    ledger.commit(_write_block(ledger, 0, [("cc", "a", b"0")]))
    group = ledger.begin_commit_group()
    ledger.commit(_write_block(ledger, 1, [("cc", "b", b"1")]), group=group)

    real = ledger._history.commit
    def boom(*a, **k):
        raise RuntimeError("history exploded")
    monkeypatch.setattr(ledger._history, "commit", boom)
    with pytest.raises(RuntimeError, match="history exploded"):
        ledger.commit(
            _write_block(ledger, 2, [("cc", "c", b"2")]), group=group
        )
    monkeypatch.setattr(ledger._history, "commit", real)

    assert ledger.height == ledger.durable_height == 1
    # the unwound blocks re-commit cleanly
    ledger.commit(_write_block(ledger, 1, [("cc", "b", b"1")]))
    ledger.commit(_write_block(ledger, 2, [("cc", "c", b"2")]))
    assert ledger.get_state("cc", "c") == b"2"
    provider.close()


def test_recovery_stops_at_mid_file_damage(tmp_path):
    """Unsynced group appends mean a crash can tear a NON-tail record
    (writeback order is not guaranteed): recovery must replay the
    contiguous prefix and drop everything from the damage on — never
    fail to open, never index garbage."""
    provider = LedgerProvider(str(tmp_path))
    ledger = provider.open("gc")
    ledger.commit(_write_block(ledger, 0, [("cc", "a", b"0")]))
    group = ledger.begin_commit_group()
    for n, key in ((1, "b"), (2, "c"), (3, "d")):
        ledger.commit(
            _write_block(ledger, n, [("cc", key, b"%d" % n)]), group=group
        )
    provider.close()  # crash: group never flushed

    # locate block 2's record (third in the file) and zero its payload
    import struct
    path = os.path.join(str(tmp_path), "gc", "chains", "blocks_000000.dat")
    with open(path, "rb") as f:
        data = f.read()
    off = 0
    for _ in range(2):  # skip records of blocks 0 and 1
        (n,) = struct.unpack(">I", data[off:off + 4])
        off += 4 + n
    (n,) = struct.unpack(">I", data[off:off + 4])
    with open(path, "r+b") as f:
        f.seek(off + 4)
        f.write(b"\x00" * n)  # the hole the crashed writeback left

    provider2 = LedgerProvider(str(tmp_path))
    led2 = provider2.open("gc")
    assert led2.height == 2  # blocks 0-1 replayed; 2-3 dropped
    assert led2.get_state("cc", "b") == b"1"
    assert led2.get_state("cc", "c") is None
    # the chain continues cleanly from the recovered height
    led2.commit(_write_block(led2, 2, [("cc", "c2", b"x")]))
    assert led2.get_state("cc", "c2") == b"x"
    provider2.close()


def test_raising_listener_surfaces_instead_of_hanging(tmp_path):
    """A commit listener that raises must surface through store_stream
    as an exception — not kill the commit thread and leave the consumer
    blocked on the results queue forever."""
    from fabric_tpu.peer.committer import Committer

    provider = LedgerProvider(str(tmp_path))
    ledger = provider.open("gc")
    ledger.commit(_write_block(ledger, 0, [("cc", "k", b"v")]))
    blocks = [
        _write_block(ledger, n, [("cc", f"s{n}", b"v")]) for n in (1, 2, 3)
    ]
    committer = Committer(_PassthroughValidator(), ledger)
    committer.add_commit_listener(
        lambda blk, flags: (_ for _ in ()).throw(RuntimeError("bad hook"))
    )
    with pytest.raises(RuntimeError, match="bad hook"):
        list(committer.store_stream(iter(blocks), depth=2))
    provider.close()


def test_snapshot_export_observes_durable_watermark(tmp_path):
    """An export racing an open group must see only flushed heights —
    the in-memory height runs ahead of what is readable/crash-safe."""
    provider = LedgerProvider(str(tmp_path))
    ledger = provider.open("gc")
    for n in range(3):
        ledger.commit(_write_block(ledger, n, [("cc", f"k{n}", b"x")]))
    group = ledger.begin_commit_group()
    ledger.commit(_write_block(ledger, 3, [("cc", "k3", b"x")]), group=group)
    assert ledger.height == 4 and ledger.durable_height == 3
    res = ledger.snapshots.submit_request(0)  # snapshot "now"
    assert res["block_number"] == 2  # durable last block, not the tail
    from fabric_tpu.ledger.snapshot import load_metadata

    meta = load_metadata(res["snapshot_dir"])
    assert meta["last_block_number"] == 2
    ledger.commit_group_flush(group)
    assert ledger.durable_height == 4
    provider.close()


class _PassthroughValidator:
    """Committer test double: hands every block straight through with
    its existing flags (no crypto stack in this container)."""

    channel_id = "gc"

    def validate_pipeline(self, blocks, depth=2, release=None,
                          rwsets_out=None):
        for blk in blocks:
            release(lambda: None)
            rwsets_out(None)
            yield list(protoutil.tx_filter(blk))


def test_store_stream_coalesces_fsyncs(tmp_path, monkeypatch):
    from fabric_tpu.peer.committer import Committer

    provider = LedgerProvider(str(tmp_path))
    ledger = provider.open("gc")
    ledger.commit(_write_block(ledger, 0, [("cc", "k", b"v0")]))
    n_blocks = 6
    blocks = [
        _write_block(ledger, n, [("cc", f"s{n}", b"v")])
        for n in range(1, n_blocks + 1)
    ]
    # blocks built against pre-stream state on purpose: no reads, only
    # blind writes, so they are VALID in any commit order
    counts = _Counts(monkeypatch)
    committer = Committer(_PassthroughValidator(), ledger)
    seen: list = []
    committer.add_commit_listener(
        lambda blk, flags: seen.append(blk.header.number)
    )
    flags = list(committer.store_stream(iter(blocks), depth=3))
    assert len(flags) == n_blocks and all(f == [VALID] for f in flags)
    assert seen == list(range(1, n_blocks + 1))
    # one KV txn per fsync boundary, coalesced across the stream: never
    # more than one boundary per block, at least one for the whole run
    assert counts.txns == counts.fsyncs
    assert 1 <= counts.fsyncs <= n_blocks
    assert ledger.durable_height == ledger.height == n_blocks + 1
    for n in range(1, n_blocks + 1):
        assert ledger.get_state("cc", f"s{n}") == b"v"
    provider.close()


def test_stream_snapshot_trigger_exact_height(tmp_path):
    """A pending snapshot request forces a group boundary at exactly the
    requested block, and the next commit waits for the export to take
    the lock — the snapshot height is deterministic, not a race with
    the stream (peers generating from the same request agree)."""
    from fabric_tpu.peer.committer import Committer
    from fabric_tpu.ledger.snapshot import load_metadata

    provider = LedgerProvider(str(tmp_path))
    ledger = provider.open("gc")
    ledger.commit(_write_block(ledger, 0, [("cc", "k", b"v")]))
    ledger.snapshots.submit_request(3)
    blocks = [
        _write_block(ledger, n, [("cc", f"s{n}", b"v")])
        for n in range(1, 7)
    ]
    committer = Committer(_PassthroughValidator(), ledger)
    flags = list(committer.store_stream(iter(blocks), depth=6))
    assert len(flags) == 6
    assert ledger.snapshots.wait_idle()
    snap_dir = os.path.join(
        str(tmp_path), "snapshots", "completed", "gc", "3"
    )
    assert os.path.isdir(snap_dir)
    assert load_metadata(snap_dir)["last_block_number"] == 3
    provider.close()


def test_snapshot_request_for_buffered_height_rejected(tmp_path):
    """A request for a height already BUFFERED in an open commit group
    is refused: its flush-at-requested-height hint has passed, so the
    export could only run at the group's later flush height — silently
    wrong.  Future heights stay accepted mid-group."""
    from fabric_tpu.ledger.snapshot import SnapshotError, load_metadata

    provider = LedgerProvider(str(tmp_path))
    ledger = provider.open("gc")
    ledger.commit(_write_block(ledger, 0, [("cc", "a", b"0")]))
    group = ledger.begin_commit_group()
    ledger.commit(_write_block(ledger, 1, [("cc", "b", b"1")]), group=group)
    ledger.commit(_write_block(ledger, 2, [("cc", "c", b"2")]), group=group)
    with pytest.raises(SnapshotError, match="buffered in an open commit"):
        ledger.snapshots.submit_request(1)
    # block 0 is durable: an immediate request still works mid-group
    res0 = ledger.snapshots.submit_request(0)
    assert res0["block_number"] == 0 and res0["snapshot_dir"]
    # a future height is recorded and exported at exactly that height
    assert ledger.snapshots.submit_request(4)["snapshot_dir"] is None
    ledger.commit_group_flush(group)
    for n in (3, 4, 5):
        ledger.commit(_write_block(ledger, n, [("cc", f"s{n}", b"v")]))
    assert ledger.snapshots.wait_idle()
    snap4 = os.path.join(str(tmp_path), "snapshots", "completed", "gc", "4")
    assert load_metadata(snap4)["last_block_number"] == 4
    provider.close()


def test_second_group_rejected_while_one_is_open(tmp_path):
    """A commit through a different (or no) group while another group
    holds buffered blocks must be rejected — its fresh collector would
    read the stale base checkpoint and corrupt the block index."""
    from fabric_tpu.ledger import BlockStoreError

    provider = LedgerProvider(str(tmp_path))
    ledger = provider.open("gc")
    ledger.commit(_write_block(ledger, 0, [("cc", "k", b"v")]))
    group = ledger.begin_commit_group()
    ledger.commit(_write_block(ledger, 1, [("cc", "b", b"1")]), group=group)
    blk2 = _write_block(ledger, 2, [("cc", "c", b"2")])
    with pytest.raises(BlockStoreError, match="unflushed blocks"):
        ledger.commit(blk2)  # no group: implicit fresh collector
    ledger.commit_group_flush(group)
    ledger.commit(_write_block(ledger, 2, [("cc", "c", b"2")]))
    assert ledger.get_state("cc", "c") == b"2"
    provider.close()


def test_commit_stage_breakdown_and_metrics(tmp_path):
    from fabric_tpu.common.metrics import CommitMetrics, PrometheusProvider

    prov = PrometheusProvider()
    provider = LedgerProvider(
        str(tmp_path), commit_metrics=CommitMetrics(prov)
    )
    ledger = provider.open("gc")
    for n in range(2):
        ledger.commit(_write_block(ledger, n, [("cc", f"k{n}", b"v")]))
    # every pipeline stage accumulated wall time (commit_ms_per_block of
    # benchmarks/run.py sums exactly these)
    assert set(CommitMetrics.STAGES) <= set(ledger.commit_stage_seconds)
    assert all(v >= 0 for v in ledger.commit_stage_seconds.values())
    exposed = prov.registry.expose()
    assert "ledger_commit_stage_duration_bucket" in exposed
    for stage in CommitMetrics.STAGES:
        assert f'stage="{stage}"' in exposed
    assert "ledger_commit_blocks_per_sync_count" in exposed
    provider.close()
