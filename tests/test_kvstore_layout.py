"""The store's page path (PR 48, `ledger/kvstore.py` `SqliteKVStore`):
a fresh file's `kv` is one `WITHOUT ROWID` B-tree, reads go through
mapped memory, a transaction's rows reach sqlite in key order — and a
file an older build made keeps the rowid layout it has and keeps
working.  One contract over the in-memory store, a fresh file, an
old-schema file and a file the pragma granted no map; then what each
layout looks like from outside, and a ledger directory of the old
layout through `KVLedger`: open, read, commit, recover."""

import io
import sqlite3

import pytest

from fabric_tpu.common import flogging, tracing
from fabric_tpu.ledger import LedgerProvider, kvstore
from fabric_tpu.ledger.kvstore import MemKVStore, SqliteKVStore
from fabric_tpu.ledger.statedb import Height

from test_group_commit import _write_block

# what `SqliteKVStore` created before PR 48
OLD_SCHEMA = "CREATE TABLE kv (k BLOB PRIMARY KEY, v BLOB NOT NULL)"


def _old_file(path):
    """A store file as an older build left it: WAL, the rowid table."""
    db = sqlite3.connect(path)
    db.execute("PRAGMA journal_mode=WAL")
    db.execute(OLD_SCHEMA)
    db.commit()
    db.close()
    return path


def _open(tmp_path, old):
    """A store on a fresh file, or on one an older build left."""
    path = str(tmp_path / "kv.sqlite")
    return SqliteKVStore(_old_file(path) if old else path)


def _schema(path):
    db = sqlite3.connect(path)
    try:
        return sorted(db.execute("SELECT type, name, sql FROM sqlite_master"))
    finally:
        db.close()


class _Kind:
    """One backend of the contract: `open()` gives the store, again
    after a `close()` (the in-memory store is its own reopen)."""

    def __init__(self, name, tmp_path, monkeypatch):
        self.name = name
        self.path = str(tmp_path / "kv.sqlite")
        self._mem = MemKVStore()
        if name == "old_schema":
            _old_file(self.path)
        if name == "unmapped":
            # a build whose pragma grants nothing: the store runs on pread
            monkeypatch.setattr(kvstore, "_MMAP_ASK", 0)

    def open(self):
        return self._mem if self.name == "mem" else SqliteKVStore(self.path)


@pytest.fixture(params=["mem", "fresh", "old_schema", "unmapped"])
def kind(request, tmp_path, monkeypatch):
    return _Kind(request.param, tmp_path, monkeypatch)


@pytest.fixture
def store(kind):
    s = kind.open()
    yield s
    s.close()


# -- the contract, on every backend ------------------------------------------


def test_get_put_delete(store):
    assert store.get(b"a") is None
    store.put(b"a", b"1")
    store.put(b"a", b"2")
    assert store.get(b"a") == b"2"
    store.delete(b"a")
    store.delete(b"never")
    assert store.get(b"a") is None


def test_get_many_over_the_variable_limit_with_absent_keys(store):
    rows = {b"key/%05d" % i: b"v%d" % i for i in range(0, 2400, 2)}
    store.write_batch(rows)
    asked = [b"key/%05d" % i for i in range(2400)]   # every other one absent
    assert len(asked) > 500
    assert store.get_many(asked) == rows
    assert store.get_many(iter(asked[:7])) == {k: rows[k] for k in asked[:7:2]}
    assert store.get_many([]) == {}
    assert store.get_many([b"nobody", b"nothing"]) == {}


def test_write_batch_puts_overwrite_and_deletes_win(store):
    store.write_batch({b"a": b"1", b"b": b"2", b"c": b"3"})
    # unsorted on purpose; a key both put and deleted ends deleted
    store.write_batch({b"c": b"30", b"a": b"10", b"d": b"4"}, deletes=[b"b", b"d", b"nobody"])
    assert list(store.iterate()) == [(b"a", b"10"), (b"c", b"30")]
    store.write_batch({}, deletes=[b"a"])
    store.write_batch({})
    assert list(store.iterate()) == [(b"c", b"30")]


def test_write_batch_if_absent_first_wins(store):
    store.write_batch({b"m": b"first", b"z": b"first"})
    # existing keys keep their value whatever order the batch names them in
    store.write_batch_if_absent({b"z": b"late", b"a": b"new", b"m": b"late", b"q": b"new"})
    assert dict(store.iterate()) == {b"a": b"new", b"m": b"first", b"q": b"new", b"z": b"first"}
    # and a key a batch brought in is first from then on
    store.write_batch_if_absent({b"q": b"later", b"a": b"later", b"b": b"new"})
    assert store.get_many([b"a", b"b", b"q"]) == {b"a": b"new", b"b": b"new", b"q": b"new"}
    store.write_batch_if_absent({})


def test_iterate_is_in_key_order_over_half_open_bounds(store):
    keys = [b"", b"\x00", b"\x00\xff", b"a", b"a\x00", b"ab", b"a\xff", b"b", b"\xff", b"\xff\xff"]
    store.write_batch({k: k[::-1] for k in reversed(keys)})
    assert [k for k, _ in store.iterate()] == keys
    assert list(store.iterate()) == [(k, k[::-1]) for k in keys]
    assert [k for k, _ in store.iterate(b"a", b"b")] == [b"a", b"a\x00", b"ab", b"a\xff"]
    assert [k for k, _ in store.iterate(b"a\x00")] == keys[4:]
    assert [k for k, _ in store.iterate(b"", b"a")] == keys[:3]
    assert [k for k, _ in store.iterate(b"aa", b"aa")] == []
    assert [k for k, _ in store.iterate(b"b", b"a")] == []
    assert [k for k, _ in store.iterate(b"\xff\xff\x00")] == []


def test_empty_values_and_values_over_a_page(store):
    big = bytes(range(256)) * 80          # 20 KiB: an overflow chain of 4 KiB pages
    bigger = b"\x07" * 70_000
    store.write_batch({b"empty": b"", b"big": big, b"k" * 3000: b"long key", b"bigger": bigger})
    assert store.get(b"empty") == b""
    assert store.get(b"big") == big
    assert store.get(b"k" * 3000) == b"long key"
    assert store.get_many([b"bigger", b"empty", b"gone"]) == {b"bigger": bigger, b"empty": b""}
    store.write_batch({b"big": b"small now", b"empty": big})
    assert dict(store.iterate(b"big", b"f")) == {b"big": b"small now", b"bigger": bigger,
                                                 b"empty": big}


def test_what_was_written_is_there_after_close_and_reopen(kind):
    s = kind.open()
    rows = {b"r/%04d" % i: b"x" * (i % 97) for i in range(1500)}
    s.write_batch(rows)
    s.write_batch_if_absent({b"r/0001": b"late", b"s": b"new"})
    s.write_batch({}, deletes=[b"r/0000"])
    s.close()
    again = kind.open()
    try:
        want = {**rows, b"s": b"new"}
        del want[b"r/0000"]
        assert dict(again.iterate()) == want
        assert again.get_many(list(rows)[:600]) == {k: want[k] for k in list(rows)[1:600]}
        assert (again.clustered, again.mmap_bytes > 0) == {
            "mem": (False, False), "fresh": (True, True), "old_schema": (False, True),
            "unmapped": (True, False)}[kind.name]
    finally:
        again.close()


# -- what each layout is, from outside -----------------------------------------


def test_a_fresh_file_holds_one_without_rowid_table_and_no_autoindex(tmp_path):
    path = str(tmp_path / "kv.sqlite")
    s = SqliteKVStore(path)
    s.put(b"k", b"v")
    assert s.clustered is True
    s.close()
    # one row in all: the table, and no `sqlite_autoindex_kv_1` beside it
    ((kind_, name, sql),) = _schema(path)
    assert (kind_, name) == ("table", "kv")
    assert sql.upper().endswith("WITHOUT ROWID")


def test_an_older_builds_file_keeps_its_schema_and_still_maps(tmp_path):
    path = _old_file(str(tmp_path / "kv.sqlite"))
    before = _schema(path)
    assert ("index", "sqlite_autoindex_kv_1", None) in before
    s = SqliteKVStore(path)
    s.write_batch({b"k%d" % i: b"v" for i in range(100)})
    assert s.clustered is False
    assert s.mmap_bytes > 0
    s.close()
    assert _schema(path) == before
    # and a second open of it says the same: nothing migrated it
    s = SqliteKVStore(path)
    assert s.clustered is False and s.get(b"k7") == b"v"
    s.close()


@pytest.mark.parametrize("old", [False, True])
def test_mmap_bytes_is_what_the_pragma_answers(tmp_path, old):
    s = _open(tmp_path, old)
    try:
        (granted,) = s._conn.execute("PRAGMA mmap_size").fetchone()
        assert s.mmap_bytes == granted > 0
        # the ceiling of this build, not a size somebody chose
        (ceiling,) = [int(o.split("=")[1], 0) for (o,) in
                      s._conn.execute("PRAGMA compile_options") if o.startswith("MAX_MMAP_SIZE=")]
        assert granted == ceiling
    finally:
        s.close()


def test_the_closed_levers_are_what_they_were(tmp_path):
    """The page cache, page size and temp store are sqlite's own (what
    a bare connection of this build has); `synchronous` and
    `wal_autocheckpoint` are the store's documented defaults."""
    s = SqliteKVStore(str(tmp_path / "kv.sqlite"))
    bare = sqlite3.connect(str(tmp_path / "bare.sqlite"))
    try:
        ask = lambda db, pragma: db.execute(f"PRAGMA {pragma}").fetchone()[0]
        for pragma in ("cache_size", "page_size", "temp_store"):
            assert ask(s._conn, pragma) == ask(bare, pragma), pragma
        assert ask(s._conn, "synchronous") == 1          # NORMAL
        assert ask(s._conn, "wal_autocheckpoint") == 1000
        assert ask(s._conn, "journal_mode") == "wal"
    finally:
        bare.close()
        s.close()


class _Spy:
    """Stands where the store's connection stands and keeps the rows
    of every `executemany` in the order sqlite was handed them."""

    def __init__(self, conn):
        self._conn = conn
        self.batches = []

    def executemany(self, sql, rows):
        rows = list(rows)
        self.batches.append((sql, rows))
        return self._conn.executemany(sql, rows)

    def __enter__(self):
        return self._conn.__enter__()

    def __exit__(self, *exc):
        return self._conn.__exit__(*exc)

    def __getattr__(self, name):
        return getattr(self._conn, name)


@pytest.mark.parametrize("old", [False, True])
def test_a_batch_reaches_sqlite_in_key_order(tmp_path, old):
    s = _open(tmp_path, old)
    spy = s._conn = _Spy(s._conn)
    try:
        puts = {b"statedb/ch\x00\xff\x02cc\x00k%03d" % ((i * 37) % 101): b"%d" % i
                for i in range(101)}
        puts[b"blkindex/ch\x00\xfft" + b"f" * 64] = b"loc"
        puts[b"historydb/ch\x00\xffcc\x00k001"] = b""
        assert list(puts) != sorted(puts)
        s.write_batch(puts, deletes=[b"zz", b"aa"])
        s.write_batch_if_absent(dict(reversed(list(puts.items()))))
        (_, upserts), (_, deletes), (sql, inserts) = spy.batches
        assert [k for k, _ in upserts] == sorted(puts)
        assert dict(upserts) == puts
        assert deletes == [(b"zz",), (b"aa",)]
        assert sql.startswith("INSERT OR IGNORE") and [k for k, _ in inserts] == sorted(puts)
        assert dict(s.iterate()) == puts
    finally:
        s.close()


def test_every_open_logs_the_layout_it_found(tmp_path):
    registry = flogging.global_registry()
    said = io.StringIO()
    stream = registry._handler.stream
    registry.set_writer(said)
    try:
        SqliteKVStore(str(tmp_path / "new.sqlite")).close()
        SqliteKVStore(_old_file(str(tmp_path / "old.sqlite"))).close()
    finally:
        registry.set_writer(stream)
    lines = [line for line in said.getvalue().splitlines() if "ledger.kvstore" in line]
    assert len(lines) == 2, lines
    assert "new.sqlite: clustered=True mmap_bytes=" in lines[0]
    assert "old.sqlite: clustered=False mmap_bytes=" in lines[1]
    assert not lines[0].endswith("mmap_bytes=0")


# -- a ledger directory of the old layout ------------------------------------------


def _old_ledger_root(tmp_path):
    """The directory a provider of an older build leaves: its
    `index.sqlite` already made, under the rowid schema."""
    _old_file(str(tmp_path / "index.sqlite"))
    return str(tmp_path)


@pytest.mark.parametrize("old", [False, True])
def test_a_ledger_directory_opens_reads_commits_and_recovers(tmp_path, old):
    root = _old_ledger_root(tmp_path) if old else str(tmp_path)
    provider = LedgerProvider(root)
    assert provider._kv.clustered is (not old)
    ledger = provider.open("ch")
    ledger.commit(_write_block(ledger, 0, [("cc", "a", b"0"), ("cc", "big", b"\x01" * 9000)]))
    ledger.commit(_write_block(ledger, 1, [("cc", "b", b"1"), ("cc", "a", b"1")]))
    provider.close()

    # reopen: reads what was committed, commits on top
    provider = LedgerProvider(root)
    assert provider._kv.clustered is (not old)
    ledger = provider.open("ch")
    assert ledger.height == 2
    assert ledger.get_state("cc", "a") == b"1"
    assert ledger.get_state("cc", "big") == b"\x01" * 9000
    assert ledger.get_history_for_key("cc", "a") == [(0, 0), (1, 0)]
    ledger.commit(_write_block(ledger, 2, [("cc", "c", b"2")]))
    # a group whose KV transaction never lands: the block file has the
    # record, the store has not (process death between the two)
    group = ledger.begin_commit_group()
    ledger.commit(_write_block(ledger, 3, [("cc", "d", b"3"), ("cc", "a", b"3")]), group=group)
    provider.close()

    # recover: the trailing block is re-indexed and its state replayed
    provider = LedgerProvider(root)
    try:
        ledger = provider.open("ch")
        assert ledger.height == ledger.durable_height == 4
        assert ledger.state_db.savepoint() == Height(3, 1)
        assert [ledger.get_state("cc", k) for k in "abcd"] == [b"3", b"1", b"2", b"3"]
        assert ledger.get_history_for_key("cc", "a") == [(0, 0), (1, 0), (3, 0)]
        assert provider._kv.clustered is (not old)
    finally:
        provider.close()
    assert (("index", "sqlite_autoindex_kv_1", None)
            in _schema(str(tmp_path / "index.sqlite"))) is old


@pytest.mark.parametrize("old", [False, True])
def test_the_kv_txn_span_says_the_layout_it_wrote_to(tmp_path, old):
    root = _old_ledger_root(tmp_path) if old else str(tmp_path)
    with tracing.scope() as rec:
        provider = LedgerProvider(root)
        try:
            ledger = provider.open("ch")
            ledger.commit(_write_block(ledger, 0, [("cc", "a", b"0")]))
            granted = provider._kv.mmap_bytes
        finally:
            provider.close()
        (kv,) = [e for e in tracing.export(rec)["traceEvents"] if e["name"] == "kv_txn"]
    assert kv["args"]["rows"] >= 1
    assert kv["args"]["clustered"] is (not old)
    assert kv["args"]["mmap_bytes"] == granted > 0


def test_a_store_without_pages_says_neither_on_the_span():
    with tracing.scope() as rec:
        provider = LedgerProvider(None)
        try:
            ledger = provider.open("ch")
            ledger.commit(_write_block(ledger, 0, [("cc", "a", b"0")]))
        finally:
            provider.close()
        (kv,) = [e for e in tracing.export(rec)["traceEvents"] if e["name"] == "kv_txn"]
    assert (kv["args"]["clustered"], kv["args"]["mmap_bytes"]) == (False, 0)
