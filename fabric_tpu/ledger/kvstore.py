"""Key-value store SPI + implementations.

Equivalent of the reference's common/ledger/util/leveldbhelper (a shared
goleveldb wrapper with db-name prefixing, batches and range iterators).
goleveldb has no Python counterpart in this image, so the durable backend
is sqlite (WAL mode, ordered BLOB keys give the same range-scan
contract); an in-memory impl serves tests and ephemeral ledgers.
"""

from __future__ import annotations

import bisect
import heapq
import os
import sqlite3
import struct
import threading
import time
import zlib
from typing import Iterator

from fabric_tpu.common.flogging import must_get_logger
from fabric_tpu.devtools import faultline, knob_registry
from fabric_tpu.devtools.lockwatch import guarded, named_lock, named_rlock


class KVStore:
    """Ordered byte-key store. Iteration is over a half-open [start, end)
    range in lexicographic key order, like leveldb iterators."""

    # what a paged backend found at open (SqliteKVStore); a store
    # without pages says neither
    clustered = False
    mmap_bytes = 0

    def get(self, key: bytes) -> bytes | None:
        raise NotImplementedError

    def get_many(self, keys) -> dict[bytes, bytes]:
        """Present keys -> values (absent keys omitted).  Backends
        override with one round-trip; the default loops."""
        out = {}
        for k in keys:
            v = self.get(k)
            if v is not None:
                out[k] = v
        return out

    def write_batch(self, puts: dict[bytes, bytes], deletes=()) -> None:
        raise NotImplementedError

    def write_batch_if_absent(self, puts: dict[bytes, bytes]) -> None:
        """Insert keys that do not exist yet; existing keys keep their
        value (leveldb has no native merge operator either — the
        reference reads before writing for first-wins indexes; backends
        here do it in one INSERT OR IGNORE round-trip)."""
        existing = self.get_many(list(puts))
        self.write_batch({k: v for k, v in puts.items() if k not in existing})

    def put(self, key: bytes, value: bytes) -> None:
        self.write_batch({key: value})

    def delete(self, key: bytes) -> None:
        self.write_batch({}, [key])

    def iterate(self, start: bytes = b"", end: bytes | None = None) -> Iterator[tuple[bytes, bytes]]:
        raise NotImplementedError

    def close(self) -> None:
        pass


class MemKVStore(KVStore):
    def __init__(self) -> None:
        self._data: dict[bytes, bytes] = {}
        self._keys: list[bytes] = []
        self._lock = threading.RLock()

    def get(self, key: bytes) -> bytes | None:
        with self._lock:
            return self._data.get(key)

    def write_batch(self, puts, deletes=()) -> None:
        with self._lock:
            for k, v in puts.items():
                if k not in self._data:
                    bisect.insort(self._keys, k)
                self._data[k] = v
            for k in deletes:
                if k in self._data:
                    del self._data[k]
                    i = bisect.bisect_left(self._keys, k)
                    if i < len(self._keys) and self._keys[i] == k:
                        self._keys.pop(i)

    def iterate(self, start: bytes = b"", end: bytes | None = None):
        with self._lock:
            i = bisect.bisect_left(self._keys, start)
            keys = []
            while i < len(self._keys):
                k = self._keys[i]
                if end is not None and k >= end:
                    break
                keys.append(k)
                i += 1
            snapshot = [(k, self._data[k]) for k in keys]
        yield from snapshot


_SQLITE_SYNC_LEVELS = ("OFF", "NORMAL", "FULL", "EXTRA")

# what PRAGMA mmap_size is asked for: above any build's
# SQLITE_MAX_MMAP_SIZE, so what is granted is that build's ceiling
_MMAP_ASK = 1 << 62

_logger = must_get_logger("ledger.kvstore")


def _sqlite_sync_level(override: str | None) -> str:
    """PRAGMA synchronous level: ctor override, else
    FABRIC_TPU_SQLITE_SYNC, else NORMAL — the default the chaos-commit
    crash matrix and faultfuzz campaigns run against (in WAL mode,
    NORMAL can lose the last transactions on POWER loss but never
    corrupts, and the block-file-first invariant makes lost KV txns
    replayable from the file scan; FULL/EXTRA trade throughput for
    power-loss durability, OFF is bench-sweep-only)."""
    raw = (
        override
        if override is not None
        else knob_registry.raw("FABRIC_TPU_SQLITE_SYNC")
    ).strip().upper()
    if not raw:
        return "NORMAL"
    if raw not in _SQLITE_SYNC_LEVELS:
        raise ValueError(
            f"FABRIC_TPU_SQLITE_SYNC={raw!r}: expected one of "
            f"{'/'.join(_SQLITE_SYNC_LEVELS)}"
        )
    return raw


def _sqlite_wal_checkpoint(override: int | None) -> int:
    """wal_autocheckpoint page threshold: ctor override, else
    FABRIC_TPU_WAL_CHECKPOINT, else sqlite's stock 1000.  Larger values
    move checkpoint I/O off the commit path at the cost of a longer WAL
    (recovery still replays it fully); 0 disables auto-checkpointing
    entirely (operator-driven checkpoints only)."""
    if override is not None:
        return max(0, int(override))
    raw = knob_registry.raw("FABRIC_TPU_WAL_CHECKPOINT").strip()
    if not raw:
        return 1000
    try:
        return max(0, int(raw))
    except ValueError:
        raise ValueError(
            f"FABRIC_TPU_WAL_CHECKPOINT={raw!r} is not an integer page "
            "count (0 disables auto-checkpointing)"
        ) from None


class SqliteKVStore(KVStore):
    """Durable backend. One table of BLOB key/value; WAL journaling gives
    atomic batch commits (the recovery property blkstorage/kvledger rely
    on, reference blockfile checkpoints + leveldb atomicity).

    The page path (what a row costs in pages, what a page costs to
    reach; sqlite's 2 MB page cache is left as it is):

    - a fresh file's `kv` is `WITHOUT ROWID`: a row is one entry of the
      key's own B-tree, not a rowid-table row plus an autoindex entry
      that repeats the (long, prefixed) key — half the file, half the
      leaves a write dirties, one descent a read.  A file an older
      build made keeps the rowid layout it has and keeps working (every
      statement here runs on either; `clustered` says which was found);
    - reads go through mapped memory (`PRAGMA mmap_size`, asked at the
      build's ceiling; `mmap_bytes` is what was granted, 0 = plain
      `pread`): a miss of the page cache is a memory read, not a system
      call and a copy.  sqlite never WRITES through the map, so the
      WAL, `synchronous` and checkpoint paths are what they were.  The
      one operational consequence: an I/O error on a mapped page ends
      the process with a signal (SIGBUS) where a `pread` would have
      returned an error — a process death like any other to the
      block-file-first recovery;
    - a transaction's rows reach sqlite in key order, so neighbouring
      keys land on a leaf while the cache still holds it.

    Durability knobs (the chaos crash matrix pins the default's
    safety):
    `synchronous`/`FABRIC_TPU_SQLITE_SYNC` and
    `wal_autocheckpoint`/`FABRIC_TPU_WAL_CHECKPOINT` — see
    _sqlite_sync_level/_sqlite_wal_checkpoint."""

    def __init__(self, path: str, synchronous: str | None = None,
                 wal_autocheckpoint: int | None = None):
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self._conn = sqlite3.connect(path, check_same_thread=False)
        self._conn.execute("PRAGMA journal_mode=WAL")
        self.sync_level = _sqlite_sync_level(synchronous)
        self._conn.execute(f"PRAGMA synchronous={self.sync_level}")
        self.wal_autocheckpoint = _sqlite_wal_checkpoint(wal_autocheckpoint)
        self._conn.execute(
            f"PRAGMA wal_autocheckpoint={self.wal_autocheckpoint:d}"
        )
        self._conn.execute(
            "CREATE TABLE IF NOT EXISTS kv "
            "(k BLOB PRIMARY KEY, v BLOB NOT NULL) WITHOUT ROWID"
        )
        self._conn.commit()
        # observed, not configured: a rowid table carries its key in
        # sqlite_autoindex_kv_1, a WITHOUT ROWID table has no such index
        self.clustered = self._conn.execute(
            "SELECT 1 FROM sqlite_master WHERE name = 'sqlite_autoindex_kv_1'"
        ).fetchone() is None
        # the pragma answers with what it granted (no row at all from a
        # build without mmap); address space, not memory
        granted = self._conn.execute(
            f"PRAGMA mmap_size={_MMAP_ASK:d}"
        ).fetchone()
        self.mmap_bytes = granted[0] if granted else 0
        _logger.info(
            "kvstore %s: clustered=%s mmap_bytes=%d",
            path, self.clustered, self.mmap_bytes,
        )
        self._lock = threading.RLock()

    def get(self, key: bytes) -> bytes | None:
        with self._lock:
            row = self._conn.execute("SELECT v FROM kv WHERE k = ?", (key,)).fetchone()
        return None if row is None else row[0]

    def get_many(self, keys) -> dict[bytes, bytes]:
        keys = list(keys)
        out: dict[bytes, bytes] = {}
        with self._lock:
            for off in range(0, len(keys), 500):  # sqlite variable limit
                chunk = keys[off:off + 500]
                rows = self._conn.execute(
                    "SELECT k, v FROM kv WHERE k IN (%s)"
                    % ",".join("?" * len(chunk)),
                    chunk,
                ).fetchall()
                out.update(rows)
        return out

    def write_batch(self, puts, deletes=()) -> None:
        # fault point BEFORE the transaction: an injected crash here
        # models process death between the block-file fsync and the KV
        # txn (sqlite's own atomicity covers mid-txn death)
        faultline.point("kvstore.txn", puts=len(puts))
        with self._lock:
            with self._conn:
                self._conn.executemany(
                    "INSERT INTO kv(k, v) VALUES(?, ?) "
                    "ON CONFLICT(k) DO UPDATE SET v = excluded.v",
                    sorted(puts.items()),
                )
                self._conn.executemany(
                    "DELETE FROM kv WHERE k = ?", [(k,) for k in deletes]
                )

    def write_batch_if_absent(self, puts) -> None:
        # a dict's keys are unique, so key order changes no winner: the
        # one conflict left is with a row the table already holds
        with self._lock:
            with self._conn:
                self._conn.executemany(
                    "INSERT OR IGNORE INTO kv(k, v) VALUES(?, ?)",
                    sorted(puts.items()),
                )

    def iterate(self, start: bytes = b"", end: bytes | None = None):
        with self._lock:
            if end is None:
                rows = self._conn.execute(
                    "SELECT k, v FROM kv WHERE k >= ? ORDER BY k", (start,)
                ).fetchall()
            else:
                rows = self._conn.execute(
                    "SELECT k, v FROM kv WHERE k >= ? AND k < ? ORDER BY k",
                    (start, end),
                ).fetchall()
        yield from rows

    def close(self) -> None:
        self._conn.close()


class WriteBatchCollector(KVStore):
    """Buffers every mutation destined for `base` so one whole commit —
    state + history + pvt store + block index + savepoints — lands in a
    SINGLE base write_batch: on the sqlite backend that is exactly one
    transaction (the group-commit seam; the reference accumulates a
    leveldbhelper UpdateBatch per store but still pays one WriteBatch
    per store per block).  Reads are overlay-aware (read-your-writes),
    so MVCC validation of block k+1 in a group sees block k's buffered
    writes; flush() is all-or-nothing."""

    def __init__(self, base: KVStore):
        self._base = base
        self._puts: dict[bytes, bytes] = {}
        self._dels: set[bytes] = set()

    def get(self, key: bytes) -> bytes | None:
        if key in self._puts:
            return self._puts[key]
        if key in self._dels:
            return None
        return self._base.get(key)

    def get_many(self, keys) -> dict[bytes, bytes]:
        out: dict[bytes, bytes] = {}
        missing: list[bytes] = []
        for k in keys:
            if k in self._puts:
                out[k] = self._puts[k]
            elif k not in self._dels:
                missing.append(k)
        if missing:
            out.update(self._base.get_many(missing))
        return out

    def write_batch(self, puts, deletes=()) -> None:
        for k, v in puts.items():
            self._dels.discard(k)
            self._puts[k] = v
        for k in deletes:
            self._puts.pop(k, None)
            self._dels.add(k)

    # write_batch_if_absent: the KVStore default (get_many + filtered
    # write_batch) is already correct here because get_many sees the
    # overlay — first-wins holds across the buffered blocks of a group
    # as well as against committed state.

    def iterate(self, start: bytes = b"", end: bytes | None = None):
        """Merge the overlay into the base's ordered scan (the pvt
        store's expiry purge range-reads mid-commit)."""
        ov = iter(sorted(
            k for k in self._puts
            if k >= start and (end is None or k < end)
        ))
        ok = next(ov, None)
        for k, v in self._base.iterate(start, end):
            while ok is not None and ok < k:
                yield ok, self._puts[ok]
                ok = next(ov, None)
            if ok == k:
                yield k, self._puts[k]
                ok = next(ov, None)
                continue
            if k in self._dels:
                continue
            yield k, v
        while ok is not None:
            yield ok, self._puts[ok]
            ok = next(ov, None)

    @property
    def pending(self) -> int:
        return len(self._puts) + len(self._dels)

    def flush(self) -> None:
        """Commit everything buffered to the base store in one
        write_batch (one sqlite transaction), then reset."""
        if self._puts or self._dels:
            self._base.write_batch(self._puts, sorted(self._dels))
        self._puts = {}
        self._dels = set()

    def discard(self) -> None:
        """Drop everything buffered without touching the base store —
        the group-commit failure rollback."""
        self._puts = {}
        self._dels = set()


class NamedDB(KVStore):
    """A prefixed view over a shared store — the reference's
    leveldbhelper.Provider GetDBHandle(dbName) pattern."""

    _SEP = b"\x00\xff"

    def __init__(self, base: KVStore, name: str):
        self._base = base
        self._prefix = name.encode() + self._SEP

    def rebase(self, base: KVStore) -> "NamedDB":
        """The same namespace view over a different base — how commit
        hands each store a WriteBatchCollector without re-deriving the
        prefix from a name."""
        c = NamedDB.__new__(NamedDB)
        c._base = base
        c._prefix = self._prefix
        return c

    def _k(self, key: bytes) -> bytes:
        return self._prefix + key

    def get(self, key: bytes) -> bytes | None:
        return self._base.get(self._k(key))

    def get_many(self, keys) -> dict[bytes, bytes]:
        plen = len(self._prefix)
        got = self._base.get_many([self._k(k) for k in keys])
        return {k[plen:]: v for k, v in got.items()}

    def write_batch(self, puts, deletes=()) -> None:
        self._base.write_batch(
            {self._k(k): v for k, v in puts.items()}, [self._k(k) for k in deletes]
        )

    def write_batch_if_absent(self, puts) -> None:
        self._base.write_batch_if_absent(
            {self._k(k): v for k, v in puts.items()}
        )

    def iterate(self, start: bytes = b"", end: bytes | None = None):
        pend = self._prefix + end if end is not None else _prefix_end(self._prefix)
        for k, v in self._base.iterate(self._prefix + start, pend):
            yield k[len(self._prefix):], v


def _prefix_end(prefix: bytes) -> bytes | None:
    """Smallest key greater than every key with this prefix."""
    p = bytearray(prefix)
    while p:
        if p[-1] != 0xFF:
            p[-1] += 1
            return bytes(p)
        p.pop()
    return None


def wipe_prefix(store: KVStore, prefix: bytes) -> int:
    """Delete every key under `prefix` in one batch; returns the count.
    THE range-delete helper — ledger admin repair ops and the crashed-
    import discard both sweep namespaces through it, so the 0xFF-carry
    end-key logic lives in exactly one place."""
    keys = [k for k, _ in store.iterate(prefix, _prefix_end(prefix))]
    if keys:
        store.write_batch({}, deletes=keys)
    return len(keys)


def open_kvstore(path: str | None) -> KVStore:
    """None/':memory:' -> MemKVStore, else sqlite at path."""
    if path in (None, ":memory:"):
        return MemKVStore()
    return SqliteKVStore(path)


# -- storage engine v2: namespace-sharded store, two-phase group flush -------
#
# One sqlite file means one WAL and one fsync stream for every namespace a
# peer commits to.  The sharded store splits the STATE portion of the key
# space (``statedb/<lid>`` ``\x02`` entries — the bulk of every commit's
# bytes) across N shard files routed by top-level chaincode namespace,
# while everything whose atomicity defines the crash contract (state
# savepoints, block index + checkpoint, history, pvt store, metadata
# namespaces) stays in the coordinator file.  A group flush becomes two
# phases: every touched shard STAGES its mutations in a local
# pending-table transaction tagged with the flush epoch, then ONE
# coordinator transaction (carrying the savepoint/index/history writes
# plus the epoch record) commits the whole flush — reopen rolls prepared-
# but-uncommitted shards back and committed-but-unapplied shards forward,
# so the one-atomic-txn-per-block contract survives sharding.

_STATEDB_RAW_PREFIX = b"statedb/"
# coordinator-file metadata; \x00-leading raw keys sort below every
# NamedDB namespace so no prefixed view or wipe sweep can reach them
_SHARD_COUNT_KEY = b"\x00storev2\x00shards"
_EPOCH_KEY = b"\x00storev2\x00epoch"

_MAX_SHARDS = 64


def store_shards(override: int | None = None) -> int:
    """FABRIC_TPU_STORE_SHARDS: statedb shard-file count.  Default 1
    keeps the single-file seed layout (plain SqliteKVStore, no epoch
    machinery); values > 1 enable the namespace-sharded two-phase-flush
    engine.  The count is pinned into the coordinator file at creation —
    a reopen under a different knob value keeps the persisted width, so
    key routing can never drift across restarts."""
    if override is not None:
        return max(1, min(int(override), _MAX_SHARDS))
    raw = knob_registry.raw("FABRIC_TPU_STORE_SHARDS").strip()
    if not raw:
        return 1
    try:
        n = int(raw)
    except ValueError:
        raise ValueError(
            f"FABRIC_TPU_STORE_SHARDS={raw!r} is not an integer shard "
            "count (1 = single-file layout)"
        ) from None
    return max(1, min(n, _MAX_SHARDS))


def shard_of_namespace(ns: str, n: int) -> int:
    """Shard index a namespace's state entries route to.  Derived
    namespaces (``cc\\x00pvt\\x00coll`` / ``cc\\x00hash\\x00coll``, see
    txmgmt.pvt_ns/hash_ns) ride with their parent chaincode so one
    chaincode's public + private state shares a shard/WAL."""
    top = ns.split("\x00", 1)[0]
    return zlib.crc32(top.encode()) % n


def state_shard(key: bytes, n: int) -> int | None:
    """Shard index for a raw store key, or None for coordinator keys.
    Only ``statedb/<lid>`` ``\x02`` state entries shard; savepoints
    (``\x01``), indexes (``\x03``/``\x04``), metadata (``\x05``) and
    every non-statedb namespace stay coordinated — they are the
    atomicity anchors of the commit."""
    if n <= 1 or not key.startswith(_STATEDB_RAW_PREFIX):
        return None
    sep = key.find(NamedDB._SEP, len(_STATEDB_RAW_PREFIX))
    if sep < 0:
        return None
    inner = key[sep + len(NamedDB._SEP):]
    if not inner.startswith(b"\x02"):
        return None
    nul = inner.find(b"\x00", 1)
    ns = inner[1:nul] if nul > 0 else inner[1:]
    return zlib.crc32(ns) % n


class _ShardStore(SqliteKVStore):
    """One statedb shard: the plain sqlite kv table plus a PENDING
    staging table and the shard-local epoch mark the two-phase flush
    stages into.  Pending rows are invisible to every read until
    apply_pending() folds them into kv (NULL value = delete marker)."""

    def __init__(self, path: str, synchronous: str | None = None,
                 wal_autocheckpoint: int | None = None):
        super().__init__(path, synchronous, wal_autocheckpoint)
        self._conn.execute(
            "CREATE TABLE IF NOT EXISTS pending (k BLOB PRIMARY KEY, v BLOB)"
        )
        self._conn.execute(
            "CREATE TABLE IF NOT EXISTS shardmeta "
            "(mk TEXT PRIMARY KEY, mv INTEGER NOT NULL)"
        )
        self._conn.commit()
        # lockwatch role: every shard file's connection lock shares one
        # role (no two shard locks ever nest on a thread — the fan-out
        # holds at most one per worker), ordered under the flush lock
        self._lock = named_rlock("kvstore.shard")

    def stage_pending(self, puts, deletes, epoch: int) -> None:
        """Phase-1 prepare: replace the pending table with this flush's
        mutations and mark the shard's epoch, in one local txn.  The
        leading DELETE makes prepare idempotent AND sweeps any stale
        pending left by a crashed-then-rolled-back earlier flush."""
        with self._lock:
            with self._conn:
                self._conn.execute("DELETE FROM pending")
                self._conn.executemany(
                    "INSERT INTO pending(k, v) VALUES(?, ?)",
                    list(puts.items()),
                )
                # deletes win over same-key puts, matching write_batch
                self._conn.executemany(
                    "INSERT OR REPLACE INTO pending(k, v) VALUES(?, NULL)",
                    [(k,) for k in deletes],
                )
                self._conn.execute(
                    "INSERT INTO shardmeta(mk, mv) "
                    "VALUES('pending_epoch', ?) "
                    "ON CONFLICT(mk) DO UPDATE SET mv = excluded.mv",
                    (epoch,),
                )

    def pending_epoch(self) -> int | None:
        """Epoch of the staged-but-unapplied flush, None when clean."""
        with self._lock:
            row = self._conn.execute(
                "SELECT mv FROM shardmeta WHERE mk = 'pending_epoch'"
            ).fetchone()
        return None if row is None else row[0]

    def apply_pending(self) -> None:
        """Phase-3 apply (also reopen roll-forward): fold pending into
        kv and clear the stage, in one local txn — atomic, so a crash
        mid-apply re-applies idempotently on the next open."""
        with self._lock:
            with self._conn:
                self._conn.execute(
                    "INSERT INTO kv(k, v) "
                    "SELECT k, v FROM pending WHERE v IS NOT NULL "
                    "ON CONFLICT(k) DO UPDATE SET v = excluded.v"
                )
                self._conn.execute(
                    "DELETE FROM kv WHERE k IN "
                    "(SELECT k FROM pending WHERE v IS NULL)"
                )
                self._conn.execute("DELETE FROM pending")
                self._conn.execute(
                    "DELETE FROM shardmeta WHERE mk = 'pending_epoch'"
                )

    def drop_pending(self) -> None:
        """Reopen roll-back: discard a prepared-but-never-committed
        stage (the coordinator's epoch record never landed)."""
        with self._lock:
            with self._conn:
                self._conn.execute("DELETE FROM pending")
                self._conn.execute(
                    "DELETE FROM shardmeta WHERE mk = 'pending_epoch'"
                )


class ShardedKVStore(KVStore):
    """The KVStore SPI over one coordinator file + N statedb shard
    files.  Reads route per key; iteration heap-merges the per-file
    ordered scans (routing is deterministic and disjoint, so the merge
    is exactly the single-file key order — snapshot export, state
    digests and range reads are byte-identical at every shard width).
    write_batch with shard-routed mutations runs the two-phase group
    flush; batches that touch no shard (index-only writes, recovery
    bookkeeping) commit straight to the coordinator exactly like the
    single-file engine."""

    def __init__(self, root_dir: str, shards: int | None = None,
                 synchronous: str | None = None,
                 wal_autocheckpoint: int | None = None):
        self._coord = SqliteKVStore(
            os.path.join(root_dir, "index.sqlite"),
            synchronous, wal_autocheckpoint,
        )
        raw = self._coord.get(_SHARD_COUNT_KEY)
        if raw is not None:
            # the persisted width wins: routing must never drift
            n = struct.unpack(">I", raw)[0]
        else:
            n = max(2, store_shards(shards))
            self._coord.put(_SHARD_COUNT_KEY, struct.pack(">I", n))
        self.shards = n
        self._stores = [
            _ShardStore(
                os.path.join(root_dir, f"state_{i:02d}.sqlite"),
                synchronous, wal_autocheckpoint,
            )
            for i in range(n)
        ]
        # the layout every file has: one older file makes the answer no
        files = [self._coord, *self._stores]
        self.clustered = all(f.clustered for f in files)
        self.mmap_bytes = min(f.mmap_bytes for f in files)
        # serializes two-phase flushes and guards the epoch counter
        self._lock = named_lock("kvstore.shard_flush")
        # per-phase wall splits of the LAST two-phase flush; kvledger
        # folds them into commit_stage_seconds after each group flush
        self.last_stage_seconds: dict[str, float] = {}
        self._epoch = 0
        with self._lock:
            raw = self._coord.get(_EPOCH_KEY)
            self._epoch = 0 if raw is None else struct.unpack(">Q", raw)[0]
            self._recover_pending()

    # -- reopen recovery ---------------------------------------------------

    def _recover_pending(self) -> None:
        """Resolve staged flushes left by a crash: a shard whose pending
        epoch matches the coordinator's committed epoch lost only its
        apply phase — roll FORWARD (the flush was acknowledged by the
        coordinator txn).  Any other pending epoch was prepared but
        never committed — roll back.  Both arms are idempotent, so a
        crash during recovery just re-runs it."""
        for i, s in enumerate(self._stores):
            pe = s.pending_epoch()
            if pe is None:
                continue
            if pe == self._epoch:
                # guard-style fault point: a faultfuzz "skip" rule
                # deletes this roll-forward, leaving writes the
                # coordinator's savepoint already acknowledges missing
                # from the shard — the lost-committed-state corruption
                # the invariants oracle must catch (the storage-v2
                # seeded-violation acceptance case)
                if faultline.guard(
                    "store.shard_recover", shard=i, epoch=pe,
                ):
                    s.apply_pending()
            else:
                s.drop_pending()

    # -- reads -------------------------------------------------------------

    def _store_for(self, key: bytes) -> KVStore:
        i = state_shard(key, self.shards)
        return self._coord if i is None else self._stores[i]

    def get(self, key: bytes) -> bytes | None:
        return self._store_for(key).get(key)

    def get_many(self, keys) -> dict[bytes, bytes]:
        groups: dict[int | None, list[bytes]] = {}
        for k in keys:
            groups.setdefault(state_shard(k, self.shards), []).append(k)
        out: dict[bytes, bytes] = {}
        for i, ks in groups.items():
            store = self._coord if i is None else self._stores[i]
            out.update(store.get_many(ks))
        return out

    def iterate(self, start: bytes = b"", end: bytes | None = None):
        # routing is disjoint: the heap-merge of per-file ordered scans
        # IS the global key order (each file scan releases its lock
        # before yielding, so the lazy merge never nests shard locks)
        return heapq.merge(
            self._coord.iterate(start, end),
            *(s.iterate(start, end) for s in self._stores),
        )

    # -- writes ------------------------------------------------------------

    def _partition(self, puts, deletes):
        shard_puts: dict[int, dict[bytes, bytes]] = {}
        shard_dels: dict[int, list[bytes]] = {}
        coord_puts: dict[bytes, bytes] = {}
        coord_dels: list[bytes] = []
        for k, v in puts.items():
            i = state_shard(k, self.shards)
            if i is None:
                coord_puts[k] = v
            else:
                shard_puts.setdefault(i, {})[k] = v
        for k in deletes:
            i = state_shard(k, self.shards)
            if i is None:
                coord_dels.append(k)
            else:
                shard_dels.setdefault(i, []).append(k)
        return shard_puts, shard_dels, coord_puts, coord_dels

    @staticmethod
    def _fanout_width(n_shards: int) -> int:
        """Chunk fan-out for the prepare/apply phases on the shared
        workpool (FABRIC_TPU_STORE_POOL, default auto, 0 = serial).
        Width never changes RESULTS — partitioning is deterministic and
        shard key sets are disjoint — only wall time."""
        from fabric_tpu.common import workpool

        return min(workpool.stage_width("FABRIC_TPU_STORE_POOL"), n_shards)

    def write_batch(self, puts, deletes=()) -> None:
        shard_puts, shard_dels, coord_puts, coord_dels = self._partition(
            puts, deletes
        )
        if not shard_puts and not shard_dels:
            # coordinator-only batch: no two-phase machinery, and no
            # stale phase splits left for the caller to re-observe
            self.last_stage_seconds = {}
            self._coord.write_batch(coord_puts, coord_dels)
            return
        from fabric_tpu.common import workpool

        t = time.perf_counter
        with self._lock:
            guarded(self, "_epoch", by="kvstore.shard_flush")
            epoch = self._epoch + 1
            touched = sorted(set(shard_puts) | set(shard_dels))
            wall: dict[str, float] = {}

            def _prep(off, items):
                out = []
                for i in items:
                    t0 = t()
                    p = shard_puts.get(i, {})
                    faultline.point(
                        "store.shard_flush", stage="prepare", shard=i,
                        epoch=epoch, puts=len(p),
                    )
                    self._stores[i].stage_pending(
                        p, shard_dels.get(i, ()), epoch
                    )
                    out.append((i, t() - t0))
                return out

            def _apply(off, items):
                out = []
                for i in items:
                    t0 = t()
                    faultline.point(
                        "store.shard_flush", stage="apply", shard=i,
                        epoch=epoch,
                    )
                    self._stores[i].apply_pending()
                    out.append((i, t() - t0))
                return out

            width = self._fanout_width(len(touched))
            pool = workpool.default_pool() if width > 1 else None
            t0 = t()
            # phase 1: stage every touched shard (parallel fan-out)
            for i, dt in workpool.run_chunked(
                pool, _prep, touched, max(width, 1)
            ):
                wall[f"shard{i}"] = wall.get(f"shard{i}", 0.0) + dt
            t1 = t()
            # phase 2: THE commit point — coordinator mutations (index,
            # savepoint, history, pvt) plus the epoch record in ONE
            # sqlite txn; a crash on either side of it resolves cleanly
            # at reopen (_recover_pending)
            faultline.point(
                "store.shard_flush", stage="commit", epoch=epoch,
                shards=len(touched),
            )
            coord_puts[_EPOCH_KEY] = struct.pack(">Q", epoch)
            self._coord.write_batch(coord_puts, coord_dels)
            self._epoch = epoch
            t2 = t()
            # phase 3: fold each shard's stage into its kv table
            for i, dt in workpool.run_chunked(
                pool, _apply, touched, max(width, 1)
            ):
                wall[f"shard{i}"] = wall.get(f"shard{i}", 0.0) + dt
            t3 = t()
            wall["prepare"] = t1 - t0
            wall["commit"] = t2 - t1
            wall["apply"] = t3 - t2
            self.last_stage_seconds = wall

    def write_batch_if_absent(self, puts) -> None:
        shard_puts, _, coord_puts, _ = self._partition(puts, ())
        if coord_puts:
            self._coord.write_batch_if_absent(coord_puts)
        for i in sorted(shard_puts):
            self._stores[i].write_batch_if_absent(shard_puts[i])

    def close(self) -> None:
        self._coord.close()
        for s in self._stores:
            s.close()


def open_store_root(root_dir: str | None,
                    shards: int | None = None) -> KVStore:
    """The provider's root store.  None -> MemKVStore; the single
    sqlite file (seed layout) unless FABRIC_TPU_STORE_SHARDS asks for
    more or shard files already exist on disk — an existing sharded
    layout always reopens sharded, whatever the knob says now."""
    if root_dir is None:
        return MemKVStore()
    n = store_shards(shards)
    if n <= 1 and not os.path.exists(
        os.path.join(root_dir, "state_00.sqlite")
    ):
        return SqliteKVStore(os.path.join(root_dir, "index.sqlite"))
    return ShardedKVStore(root_dir, shards=n)


__all__ = [
    "KVStore",
    "MemKVStore",
    "SqliteKVStore",
    "ShardedKVStore",
    "NamedDB",
    "WriteBatchCollector",
    "open_kvstore",
    "open_store_root",
    "store_shards",
    "shard_of_namespace",
    "state_shard",
    "wipe_prefix",
]
