"""Key-value store SPI + implementations.

Equivalent of the reference's common/ledger/util/leveldbhelper (a shared
goleveldb wrapper with db-name prefixing, batches and range iterators).
goleveldb has no Python counterpart in this image, so the durable backend
is sqlite (WAL mode, ordered BLOB keys give the same range-scan
contract); an in-memory impl serves tests and ephemeral ledgers.
"""

from __future__ import annotations

import bisect
import os
import sqlite3
import threading
from typing import Iterator

from fabric_tpu.common.flogging import must_get_logger
from fabric_tpu.devtools import faultline, knob_registry


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

# PRAGMA wal_autocheckpoint, in pages: sqlite's stock threshold
_WAL_AUTOCHECKPOINT = 1000

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

    The durability knob (the chaos crash matrix pins the default's
    safety): `synchronous`/`FABRIC_TPU_SQLITE_SYNC`, see
    _sqlite_sync_level.  The WAL is checkpointed at sqlite's stock
    1000 pages."""

    def __init__(self, path: str, synchronous: str | None = None):
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self._conn = sqlite3.connect(path, check_same_thread=False)
        self._conn.execute("PRAGMA journal_mode=WAL")
        self.sync_level = _sqlite_sync_level(synchronous)
        self._conn.execute(f"PRAGMA synchronous={self.sync_level}")
        self._conn.execute(
            f"PRAGMA wal_autocheckpoint={_WAL_AUTOCHECKPOINT:d}"
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


def open_store_root(root_dir: str | None) -> KVStore:
    """The provider's root store.  None -> MemKVStore, else the one
    sqlite file `index.sqlite` under `root_dir`."""
    if root_dir is None:
        return MemKVStore()
    if os.path.exists(os.path.join(root_dir, "state_00.sqlite")):
        # what the namespace-sharded engine (withdrawn in PR 51) left:
        # state rows in state_NN.sqlite beside index.sqlite.  Opening
        # index.sqlite alone would serve a ledger without its state.
        raise ValueError(
            f"{root_dir} holds a sharded statedb (state_00.sqlite), a "
            "layout dropped in PR 51: start this peer on an empty "
            "directory and join the channel by snapshot"
        )
    return SqliteKVStore(os.path.join(root_dir, "index.sqlite"))


__all__ = [
    "KVStore",
    "MemKVStore",
    "SqliteKVStore",
    "NamedDB",
    "WriteBatchCollector",
    "open_kvstore",
    "open_store_root",
    "wipe_prefix",
]
