"""Chaos commit matrix (ISSUE 6 tentpole): an injected CRASH at every
commit-pipeline stage (mvcc / block_append / pvt / state / history /
fsync / kv_txn — plus the kvstore-txn boundary and a torn mid-record
file append), followed by a reopen, must recover to a consistent height
with no torn state.  PR 2's tests exercised exactly two hand-picked
torn points; faultline generalizes them into an any-stage matrix.

A faultline "crash" raises FaultCrash (a BaseException): the ledger's
rollback seams deliberately SKIP their unwind for it, so what is on
disk at the reopen is exactly what a killed process would have left —
the recovery scan, not the graceful rollback, is what these tests
exercise."""

import os
import struct

import pytest

from fabric_tpu.devtools import faultline, invariants
from fabric_tpu.ledger import LedgerProvider
from fabric_tpu.ledger.statedb import Height

from test_ledger import _endorsed_block
from test_group_commit import _write_block


def _crash_plan(point: str, ctx: dict | None = None, **extra) -> dict:
    fault = {"point": point, "action": "crash", **extra}
    if ctx:
        fault["ctx"] = ctx
    return {"seed": 1, "faults": [fault]}


def _assert_consistent(led, height: int, keys: dict) -> None:
    """The recovery invariants: advertised height matches the block
    store AND the state savepoint, every block below it is readable
    with its index entries, the block-file-first invariant holds (no
    index entry can point past file content — a readable block at
    every indexed height proves it), and expected state matches."""
    assert led.height == height
    assert led.durable_height == height
    sp = led.state_db.savepoint()
    if height > 0:
        assert sp is not None and sp.block_num == height - 1
        for num in range(height):
            blk = led.get_block_by_number(num)
            assert blk is not None and blk.header.number == num
        # the hash chain is intact through the recovered tail
        assert led.block_store.last_block_hash
    for (ns, key), want in keys.items():
        assert led.get_state(ns, key) == want, (ns, key)


STAGE_POINTS = [
    ("commit.stage", {"stage": "mvcc"}),
    ("commit.stage", {"stage": "block_append"}),
    ("commit.stage", {"stage": "pvt"}),
    ("commit.stage", {"stage": "state"}),
    ("commit.stage", {"stage": "history"}),
    ("commit.stage", {"stage": "fsync"}),
    ("commit.stage", {"stage": "kv_txn"}),
    ("kvstore.txn", None),
    ("blkstorage.fsync", None),
]


@pytest.mark.parametrize(
    "point,ctx", STAGE_POINTS,
    ids=[(ctx or {}).get("stage", p) for p, ctx in STAGE_POINTS],
)
def test_crash_at_every_commit_stage_recovers(tmp_path, point, ctx):
    """One ungrouped commit traverses every stage; a crash at stage X
    leaves block 2 either fully absent (crash before its record could
    reach the file) or replayable from the file scan — never a torn
    ledger.  The chain then continues cleanly from the recovered
    height."""
    provider = LedgerProvider(str(tmp_path))
    ledger = provider.open("chaos")
    ledger.commit(_write_block(ledger, 0, [("cc", "a", b"0")]))
    ledger.commit(_write_block(ledger, 1, [("cc", "b", b"1")]))

    blk2 = _write_block(ledger, 2, [("cc", "c", b"2")])
    with faultline.use_plan(_crash_plan(point, ctx)):
        with pytest.raises(faultline.FaultCrash):
            ledger.commit(blk2)
        assert faultline.trips(), "the plan never fired"
    provider.close()  # the "dead" process's fds

    # before the block_append stage point, block 2's record never
    # reached the file; from block_append on, the tail scan replays it
    survived = not (point == "commit.stage" and ctx["stage"] == "mvcc")
    expect_h = 3 if survived else 2
    keys = {("cc", "a"): b"0", ("cc", "b"): b"1",
            ("cc", "c"): b"2" if survived else None}

    provider2 = LedgerProvider(str(tmp_path))
    led2 = provider2.open("chaos")
    _assert_consistent(led2, expect_h, keys)
    # and the chain continues from wherever recovery landed
    led2.commit(_write_block(led2, expect_h, [("cc", "next", b"n")]))
    assert led2.get_state("cc", "next") == b"n"
    assert led2.state_db.savepoint() == Height(expect_h, 1)
    provider2.close()


@pytest.mark.parametrize(
    "stage", ["block_append", "fsync", "kv_txn"],
)
def test_group_crash_at_flush_stage_recovers_all_buffered(tmp_path, stage):
    """A multi-block group crashed at a flush-path stage: every
    appended record (durable or not — same filesystem view) replays on
    reopen; a crash after kv_txn changes nothing observable."""
    provider = LedgerProvider(str(tmp_path))
    ledger = provider.open("chaos")
    ledger.commit(_write_block(ledger, 0, [("cc", "a", b"0")]))
    group = ledger.begin_commit_group()
    blk1 = _write_block(ledger, 1, [("cc", "b", b"1")])
    blk2 = _write_block(ledger, 2, [("cc", "c", b"2")])
    plan = _crash_plan(
        "commit.stage", {"stage": stage} if stage != "block_append" else
        {"stage": stage, "block": 2},
    )
    with faultline.use_plan(plan):
        with pytest.raises(faultline.FaultCrash):
            ledger.commit(blk1, group=group)
            ledger.commit(blk2, group=group)
            ledger.commit_group_flush(group)
        assert faultline.trips()
    provider.close()

    provider2 = LedgerProvider(str(tmp_path))
    led2 = provider2.open("chaos")
    _assert_consistent(led2, 3, {
        ("cc", "a"): b"0", ("cc", "b"): b"1", ("cc", "c"): b"2",
    })
    assert led2.get_history_for_key("cc", "c") == [(2, 0)]
    provider2.close()


def test_torn_file_append_truncated_on_reopen(tmp_path):
    """torn-write-then-crash at the block-file append: a strict prefix
    of block 2's record lands on disk; the recovery scan must truncate
    it away and the same block must re-commit cleanly."""
    provider = LedgerProvider(str(tmp_path))
    ledger = provider.open("chaos")
    ledger.commit(_write_block(ledger, 0, [("cc", "a", b"0")]))
    ledger.commit(_write_block(ledger, 1, [("cc", "b", b"1")]))
    blk2 = _write_block(ledger, 2, [("cc", "c", b"2")])
    plan = {"seed": 3, "faults": [{
        "point": "blkstorage.file_append", "action": "torn",
        "cut": 0.5, "ctx": {"block": 2},
    }]}
    with faultline.use_plan(plan):
        with pytest.raises(faultline.FaultCrash, match="torn write"):
            ledger.commit(blk2)
        # label filter: under FABRIC_TPU_SOAK the pre-plan commits leave
        # background delay trips in the ledger
        [trip] = [t for t in faultline.trips() if t["plan"] != "soak"]
        assert trip["point"] == "blkstorage.file_append"
    provider.close()

    # the torn prefix is really on disk (strictly shorter than a full
    # record: length header promises more bytes than exist)
    path = os.path.join(str(tmp_path), "chaos", "chains",
                        "blocks_000000.dat")
    with open(path, "rb") as f:
        data = f.read()
    off = 0
    for _ in range(2):  # complete records of blocks 0 and 1
        (n,) = struct.unpack(">I", data[off:off + 4])
        off += 4 + n
    assert off < len(data), "no torn tail was written"

    provider2 = LedgerProvider(str(tmp_path))
    led2 = provider2.open("chaos")
    _assert_consistent(led2, 2, {
        ("cc", "a"): b"0", ("cc", "b"): b"1", ("cc", "c"): None,
    })
    led2.commit(_write_block(led2, 2, [("cc", "c", b"2")]))
    assert led2.get_state("cc", "c") == b"2"
    provider2.close()


def test_crash_before_any_write_loses_nothing(tmp_path):
    """A raise-style fault (graceful failure, NOT a crash) at the
    kvstore txn rolls the group back and the caller retries — the
    PR 2 rollback path still works with injected failures."""
    provider = LedgerProvider(str(tmp_path))
    ledger = provider.open("chaos")
    ledger.commit(_write_block(ledger, 0, [("cc", "a", b"0")]))
    blk1 = _write_block(ledger, 1, [("cc", "b", b"1")])
    with faultline.use_plan({"faults": [{
        "point": "kvstore.txn", "action": "raise", "error": "OSError",
        "message": "injected disk full",
    }]}):
        with pytest.raises(OSError, match="injected disk full"):
            ledger.commit(blk1)
        assert faultline.trips()
    # graceful rollback ran: live state matches durable storage
    assert ledger.height == ledger.durable_height == 1
    ledger.commit(_write_block(ledger, 1, [("cc", "b", b"1")]))
    assert ledger.get_state("cc", "b") == b"1"
    provider.close()


def test_pinned_parallel_prepare_crash_plan(tmp_path, monkeypatch):
    """Pinned seeded plan over the PR 9 parallel-stage seam: a crash
    inside the fanned-out MVCC namespace prepare (mvcc.ns_prepare,
    targeted at one namespace's group so the trip is deterministic even
    with pool workers racing) aborts the commit before anything reaches
    disk; reopen recovers cleanly and the same block re-commits.  Two
    runs yield identical trip ledgers."""
    monkeypatch.setenv("FABRIC_TPU_MVCC_POOL", "3")
    plan = {"seed": 9, "faults": [{
        "point": "mvcc.ns_prepare", "ctx": {"ns": "ns1"},
        "action": "crash",
    }]}

    def run(sub: str) -> list[dict]:
        provider = LedgerProvider(str(tmp_path / sub))
        ledger = provider.open("chaos")
        ledger.commit(_write_block(ledger, 0, [("ns0", "a", b"0")]))
        # 3 namespaces x 15 writes: past the prepare fan-out threshold
        items = [
            (f"ns{j}", f"k{i}", b"v")
            for j in range(3) for i in range(15)
        ]
        blk = _write_block(ledger, 1, items)
        with faultline.use_plan(plan):
            with pytest.raises(faultline.FaultCrash):
                ledger.commit(blk)
            observed = [
                t for t in faultline.trips() if t["plan"] != "soak"
            ]
        assert observed and all(
            t["point"] == "mvcc.ns_prepare" and t["ctx"]["ns"] == "ns1"
            for t in observed
        )
        provider.close()

        # the crash hit BEFORE the block-append stage: nothing reached
        # disk, recovery lands at height 1, the block re-commits
        provider2 = LedgerProvider(str(tmp_path / sub))
        led2 = provider2.open("chaos")
        _assert_consistent(led2, 1, {("ns0", "a"): b"0",
                                     ("ns1", "k0"): None})
        led2.commit(_write_block(led2, 1, items))
        assert led2.get_state("ns1", "k0") == b"v"
        assert led2.height == 2
        provider2.close()
        return observed

    first, second = run("r1"), run("r2")
    assert first == second


# -- a graceful failure on the flush path --------------------------------------


@pytest.mark.parametrize("stage", ["fsync", "kv_txn"])
def test_graceful_raise_at_a_flush_stage_needs_no_reopen(tmp_path, stage):
    """A raise-style fault (a failure the process survives, NOT a
    crash) at the two stage points of the flush path.  After `fsync`
    the block record is durable and the group's one KV transaction has
    not begun: the ledger rolls the group back (buffered rows dropped,
    the file cut to its checkpoint) and the same block commits again.
    After `kv_txn` the transaction has landed, and it is the commit
    point: the block stays, the caller's error notwithstanding, and the
    chain goes on from it.  Neither needs a reopen, and a reopen agrees
    with the live ledger."""
    provider = LedgerProvider(str(tmp_path))
    ledger = provider.open("chaos")
    ledger.commit(_write_block(ledger, 0, [("cc", "a", b"0")]))
    blk1 = _write_block(ledger, 1, [("cc", "b", b"1")])
    with faultline.use_plan({"faults": [{
        "point": "commit.stage", "ctx": {"stage": stage},
        "action": "raise", "error": "OSError",
        "message": "injected disk full",
    }]}):
        with pytest.raises(OSError, match="injected disk full"):
            ledger.commit(blk1)
        assert faultline.trips()
    landed = stage == "kv_txn"
    assert ledger.height == (2 if landed else 1)
    assert ledger.get_state("cc", "b") == (b"1" if landed else None)
    if not landed:
        assert ledger.durable_height == 1
        ledger.commit(_write_block(ledger, 1, [("cc", "b", b"1")]))
    ledger.commit(_write_block(ledger, 2, [("cc", "next", b"n")]))
    keys = {("cc", "a"): b"0", ("cc", "b"): b"1", ("cc", "next"): b"n"}
    _assert_consistent(ledger, 3, keys)
    assert invariants.check_ledger(ledger) == []
    provider.close()

    provider2 = LedgerProvider(str(tmp_path))
    _assert_consistent(provider2.open("chaos"), 3, keys)
    provider2.close()


# literal plan rules (not a name parametrized through _crash_plan):
# these pins are what the chaos-coverage faultmap cross-check counts
# as arming the two segment-lifecycle seams
SEGMENT_LIFECYCLE_PLANS = [
    {"seed": 1, "faults": [
        {"point": "blkstorage.segment_prealloc", "action": "crash"},
    ]},
    {"seed": 1, "faults": [
        {"point": "blkstorage.segment_roll", "action": "crash"},
    ]},
]


@pytest.mark.parametrize(
    "plan", SEGMENT_LIFECYCLE_PLANS,
    ids=[p["faults"][0]["point"] for p in SEGMENT_LIFECYCLE_PLANS],
)
def test_crash_at_segment_lifecycle_points_recovers(
    tmp_path, plan, monkeypatch
):
    """The preallocated-segment writer's metadata seams: a crash while
    preallocating the next segment (before its rename publishes it) or
    while sealing a full one must leave the committed chain fully
    replayable — segment lifecycle is bookkeeping, never data loss.  A
    tiny segment floor forces a roll on the second block."""
    monkeypatch.setenv("FABRIC_TPU_STORE_SEGMENT", "4096")
    provider = LedgerProvider(str(tmp_path))
    ledger = provider.open("chaos")
    big = b"x" * 3000  # ~3KB payload: two records cannot share 4KB
    ledger.commit(_write_block(ledger, 0, [("cc", "a", big)]))

    blk1 = _write_block(ledger, 1, [("cc", "b", big)])
    with faultline.use_plan(plan):
        with pytest.raises(faultline.FaultCrash):
            ledger.commit(blk1)
        assert faultline.trips(), "the plan never fired"
    provider.close()

    provider2 = LedgerProvider(str(tmp_path))
    led2 = provider2.open("chaos")
    # block 1 never reached the (unpublished or mid-seal) segment —
    # recovery lands at height 1 and the same block re-commits into a
    # freshly preallocated segment
    _assert_consistent(led2, 1, {("cc", "a"): big, ("cc", "b"): None})
    led2.commit(_write_block(led2, 1, [("cc", "b", big)]))
    assert led2.get_state("cc", "b") == big
    assert led2.height == 2
    provider2.close()


def test_same_seed_same_trip_ledger_across_runs(tmp_path):
    """Determinism acceptance: the same plan over the same workload
    yields an IDENTICAL trip ledger across two runs — seeded
    probability triggers included."""
    plan = {"seed": 42, "faults": [
        {"point": "commit.stage", "ctx": {"stage": "history"},
         "action": "delay", "delay_s": 0.0, "prob": 0.5, "count": 100},
        {"point": "kvstore.txn", "action": "delay", "delay_s": 0.0,
         "every": 2, "count": 100},
    ]}

    def run(sub: str) -> list[dict]:
        provider = LedgerProvider(str(tmp_path / sub))
        ledger = provider.open("det")
        with faultline.use_plan(plan):
            for n in range(8):
                ledger.commit(
                    _write_block(ledger, n, [("cc", f"k{n}", b"v")])
                )
            observed = [
                t for t in faultline.trips() if t["plan"] != "soak"
            ]
        provider.close()
        return observed

    first, second = run("r1"), run("r2")
    assert first == second
    assert first, "the probabilistic rule never fired in 8 commits"
