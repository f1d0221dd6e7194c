"""The one statedb engine behind `open_store_root` and the
preallocated-segment block writer (what is left of ISSUE 17's storage
engine v2: the namespace-sharded store was withdrawn in PR 51, every
world having one chaincode namespace).

The contracts pinned here:

* **one engine** — a root opens as the one `SqliteKVStore` on
  `index.sqlite`; a directory the sharded engine left is REFUSED with
  the way out, never read as a ledger without its state;
* **recovery idempotence** — reopening after a crash is a fixed point:
  a second reopen changes nothing, on both layouts of the file (a fresh
  clustered one, an older build's rowid one);
* **snapshot portability** — an export from a ledger on a rowid file
  imports into a fresh clustered store byte-identically (the snapshot
  stream is the canonical form, not the file's layout; it is also the
  way across from a layout this build no longer opens);
* **segment hygiene** — a clean preallocated (zero) tail is NOT
  recovery damage; sealed segments are trimmed to data size; records
  larger than a segment still land and replay.
"""

import os
import struct

import pytest

from fabric_tpu.devtools import faultline, invariants
from fabric_tpu.ledger import LedgerProvider
from fabric_tpu.ledger.blkstorage import DEFAULT_SEGMENT, segment_size
from fabric_tpu.ledger.kvstore import SqliteKVStore, open_store_root

from test_group_commit import _write_block
from test_kvstore_layout import _old_ledger_root


WORKLOAD = [
    [("cc", "a", b"0"), ("qscc", "q", b"config")],
    [("cc", "b", b"1"), ("lscc", "l", b"deploy")],
    [("cc\x00pvt\x00col", "p", b"private"), ("cc", "c", b"2")],
    [("basic", "k", b"3"), ("qscc", "q", b"config2")],
]


# -- one engine ---------------------------------------------------------------


def test_a_plain_root_opens_as_the_one_sqlite_store(tmp_path):
    kv = open_store_root(str(tmp_path))
    try:
        assert type(kv) is SqliteKVStore and kv.clustered is True
        kv.write_batch({b"statedb/ch\x00\xff\x02cc\x00k": b"v"})
        assert kv.get(b"statedb/ch\x00\xff\x02cc\x00k") == b"v"
    finally:
        kv.close()
    assert sorted(
        f for f in os.listdir(str(tmp_path)) if f.endswith(".sqlite")
    ) == ["index.sqlite"]


def test_a_sharded_directory_is_refused_not_misread(tmp_path):
    """What the withdrawn engine left (`state_00.sqlite` beside
    `index.sqlite`) holds its state rows where this build does not
    look: the open says so, names the way out, and touches nothing."""
    provider = LedgerProvider(str(tmp_path))
    ledger = provider.open("v2")
    ledger.commit(_write_block(ledger, 0, WORKLOAD[0]))
    provider.close()
    (tmp_path / "state_00.sqlite").write_bytes(b"")
    before = sorted(os.listdir(str(tmp_path)))
    for opener in (open_store_root, LedgerProvider):
        with pytest.raises(ValueError, match="sharded statedb") as err:
            opener(str(tmp_path))
        assert "PR 51" in str(err.value)
        assert "join the channel by snapshot" in str(err.value)
    assert sorted(os.listdir(str(tmp_path))) == before


# -- recovery idempotence -----------------------------------------------------


@pytest.mark.parametrize("old", [False, True], ids=["clustered", "rowid"])
def test_recovery_is_idempotent(tmp_path, old):
    """Crash between the block-file fsync and the KV transaction, then
    reopen TWICE: the second reopen is a no-op (same digest, same
    height) — recovery is a fixed point on both layouts."""
    root = _old_ledger_root(tmp_path) if old else str(tmp_path)
    provider = LedgerProvider(root)
    assert provider.kv.clustered is (not old)
    ledger = provider.open("v2")
    ledger.commit(_write_block(ledger, 0, WORKLOAD[0]))
    blk1 = _write_block(ledger, 1, WORKLOAD[1])
    with faultline.use_plan({"seed": 1, "faults": [
        {"point": "kvstore.txn", "action": "crash"},
    ]}):
        with pytest.raises(faultline.FaultCrash):
            ledger.commit(blk1)
        assert faultline.trips()
    provider.close()

    snaps = []
    for _ in range(2):
        p2 = LedgerProvider(root)
        led2 = p2.open("v2")
        snaps.append((invariants.state_digest(led2), led2.height,
                      led2.durable_height))
        assert invariants.check_ledger(led2) == []
        assert p2.kv.clustered is (not old)
        p2.close()
    assert snaps[0] == snaps[1]
    assert snaps[0][1] == 2  # the block record was durable: replayed


# -- snapshot portability -----------------------------------------------------


def test_snapshot_from_a_rowid_file_imports_into_a_clustered_store(
    tmp_path,
):
    """Export from a ledger on an older build's rowid file, import into
    a fresh root: the destination is clustered, its raw state export is
    the source's byte for byte, and it keeps committing."""
    (tmp_path / "src").mkdir()
    provider = LedgerProvider(_old_ledger_root(tmp_path / "src"))
    ledger = provider.open("v2")
    for n, items in enumerate(WORKLOAD):
        ledger.commit(_write_block(ledger, n, items))
    assert provider.kv.clustered is False
    export_dir = ledger.snapshots.generate()
    src_digest = invariants.state_digest(ledger)
    src_records = list(ledger.state_db.export_records())
    provider.close()

    dst = LedgerProvider(str(tmp_path / "dst"))
    try:
        led2 = dst.create_from_snapshot(export_dir)
        assert dst.kv.clustered is True
        assert invariants.check_import_state(led2, export_dir) == []
        assert list(led2.state_db.export_records()) == src_records
        assert invariants.state_digest(led2) == src_digest
        led2.commit(_write_block(led2, led2.height,
                                 [("cc", "post", b"import")]))
        assert led2.get_state("cc", "post") == b"import"
    finally:
        dst.close()


# -- segment hygiene ----------------------------------------------------------


def test_clean_prealloc_tail_is_not_recovery_damage(tmp_path):
    """The block file is preallocated past its data: the zero tail must
    read as CLEAN on reopen (no truncation, no lost blocks) — the
    whole point of paying prealloc is not re-extending per append."""
    provider = LedgerProvider(str(tmp_path))
    ledger = provider.open("v2")
    ledger.commit(_write_block(ledger, 0, [("cc", "a", b"0")]))
    ledger.commit(_write_block(ledger, 1, [("cc", "b", b"1")]))
    provider.close()

    path = os.path.join(str(tmp_path), "v2", "chains",
                        "blocks_000000.dat")
    size = os.path.getsize(path)
    assert size == segment_size(None) == DEFAULT_SEGMENT

    p2 = LedgerProvider(str(tmp_path))
    led2 = p2.open("v2")
    assert led2.height == 2
    assert led2.get_state("cc", "b") == b"1"
    # recovery did NOT shrink the preallocated tail
    assert os.path.getsize(path) == size
    p2.close()


def test_segment_roll_seals_to_data_size(tmp_path, monkeypatch):
    """A full segment is sealed (trimmed to its data) before the writer
    advances; the live tail segment keeps its preallocation."""
    monkeypatch.setenv("FABRIC_TPU_STORE_SEGMENT", "4k")
    provider = LedgerProvider(str(tmp_path))
    ledger = provider.open("v2")
    big = b"x" * 3000
    for n in range(3):
        ledger.commit(_write_block(ledger, n, [("cc", f"k{n}", big)]))
    chains = os.path.join(str(tmp_path), "v2", "chains")
    files = sorted(f for f in os.listdir(chains) if f.endswith(".dat"))
    assert len(files) == 3
    for sealed in files[:-1]:
        sz = os.path.getsize(os.path.join(chains, sealed))
        assert sz < 4096, f"{sealed} was not trimmed ({sz})"
    assert os.path.getsize(os.path.join(chains, files[-1])) == 4096
    provider.close()

    p2 = LedgerProvider(str(tmp_path))
    led2 = p2.open("v2")
    assert led2.height == 3
    for n in range(3):
        assert led2.get_state("cc", f"k{n}") == big
    p2.close()


def test_oversized_record_extends_past_segment(tmp_path, monkeypatch):
    """A record larger than the whole segment still lands (the file
    just grows past its preallocation) and replays on reopen — the
    segment floor is a hint, never a cap."""
    monkeypatch.setenv("FABRIC_TPU_STORE_SEGMENT", "4096")
    provider = LedgerProvider(str(tmp_path))
    ledger = provider.open("v2")
    huge = b"y" * 10_000
    ledger.commit(_write_block(ledger, 0, [("cc", "huge", huge)]))
    provider.close()

    p2 = LedgerProvider(str(tmp_path))
    led2 = p2.open("v2")
    assert led2.height == 1
    assert led2.get_state("cc", "huge") == huge
    led2.commit(_write_block(led2, 1, [("cc", "next", b"n")]))
    assert led2.height == 2
    p2.close()


def test_torn_tail_in_prealloc_zone_is_erased(tmp_path, monkeypatch):
    """Garbage AFTER the committed data but INSIDE the preallocated
    zone (a torn header whose length field promises bytes that never
    made it) is recognized as damage — erased back to zeros, committed
    blocks intact, and the next append lands over it."""
    monkeypatch.setenv("FABRIC_TPU_STORE_SEGMENT", "65536")
    provider = LedgerProvider(str(tmp_path))
    ledger = provider.open("v2")
    ledger.commit(_write_block(ledger, 0, [("cc", "a", b"0")]))
    provider.close()

    path = os.path.join(str(tmp_path), "v2", "chains",
                        "blocks_000000.dat")
    with open(path, "rb") as f:
        data = f.read()
    (n,) = struct.unpack(">I", data[:4])
    tail = 4 + n
    with open(path, "r+b") as f:  # a torn header: promises 500 bytes
        f.seek(tail)
        f.write(struct.pack(">I", 500) + b"GARBAGE")

    p2 = LedgerProvider(str(tmp_path))
    led2 = p2.open("v2")
    assert led2.height == 1
    assert led2.get_state("cc", "a") == b"0"
    led2.commit(_write_block(led2, 1, [("cc", "b", b"1")]))
    assert led2.height == 2
    p2.close()

    p3 = LedgerProvider(str(tmp_path))
    led3 = p3.open("v2")
    assert led3.height == 2
    assert led3.get_state("cc", "b") == b"1"
    p3.close()


def test_skipped_recovery_truncate_guard_is_defense_in_depth(
    tmp_path, monkeypatch
):
    """A faultfuzz "skip" at the ``blkstorage.recovery_truncate`` guard
    deletes the torn-tail erase — and recovery must STILL be correct,
    because the scan never trusts bytes past the checkpoint and the
    next in-segment append overwrites from the checkpoint offset.  The
    guard is defense in depth, not a correctness crutch; this pinned
    plan is also what proves the seam armable to chaos-coverage."""
    monkeypatch.setenv("FABRIC_TPU_STORE_SEGMENT", "65536")
    provider = LedgerProvider(str(tmp_path))
    ledger = provider.open("v2")
    ledger.commit(_write_block(ledger, 0, [("cc", "a", b"0")]))
    provider.close()

    path = os.path.join(str(tmp_path), "v2", "chains",
                        "blocks_000000.dat")
    with open(path, "rb") as f:
        data = f.read()
    (n,) = struct.unpack(">I", data[:4])
    tail = 4 + n
    with open(path, "r+b") as f:  # a torn header: promises 500 bytes
        f.seek(tail)
        f.write(struct.pack(">I", 500) + b"GARBAGE")

    with faultline.use_plan({"seed": 1, "faults": [
        {"point": "blkstorage.recovery_truncate", "action": "skip"},
    ]}):
        p2 = LedgerProvider(str(tmp_path))
        led2 = p2.open("v2")
        assert faultline.trips(), "the skip rule never fired"
        # torn bytes were NOT erased, yet recovery ignores them
        assert led2.height == 1
        assert led2.get_state("cc", "a") == b"0"
        led2.commit(_write_block(led2, 1, [("cc", "b", b"1")]))
        p2.close()

    p3 = LedgerProvider(str(tmp_path))
    led3 = p3.open("v2")
    assert led3.height == 2
    assert led3.get_state("cc", "b") == b"1"
    p3.close()


def test_segment_size_knob_parsing(monkeypatch):
    monkeypatch.delenv("FABRIC_TPU_STORE_SEGMENT", raising=False)
    assert segment_size(None) == DEFAULT_SEGMENT
    monkeypatch.setenv("FABRIC_TPU_STORE_SEGMENT", "64k")
    assert segment_size(None) == 64 * 1024
    monkeypatch.setenv("FABRIC_TPU_STORE_SEGMENT", "8m")
    assert segment_size(None) == 8 * 1024 * 1024
    monkeypatch.setenv("FABRIC_TPU_STORE_SEGMENT", "17")
    assert segment_size(None) == 4096  # floor
    assert segment_size(1 << 20) == 1 << 20  # explicit override
    monkeypatch.setenv("FABRIC_TPU_STORE_SEGMENT", "banana")
    with pytest.raises(ValueError):
        segment_size(None)
