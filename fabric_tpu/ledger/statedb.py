"""Versioned state database.

Reference SPI: core/ledger/kvledger/txmgmt/statedb/statedb.go:29
(VersionedDB: GetState/GetStateMultipleKeys/GetStateRangeScanIterator/
ApplyUpdates with a savepoint height).  Backend here is the KVStore SPI
(stateleveldb equivalent).

Field indexes (the CouchDB-backend performance surface —
statecouchdb.go:53 index-backed Mango queries): an index on (ns, field)
materializes order-preserving entries

    \x03 ns \x00 field \x00 enc(value) \x00 key   ->  b""

in the SAME ordered KV store, so an indexed selector runs as a range
scan instead of a full-namespace document scan, on every backend
(sqlite or memory), atomically maintained inside ApplyUpdates' one
write batch.  `enc` is a type-tagged order-preserving encoding (null <
bool < number < string); richquery's planner rechecks every candidate
document, so the index only ever has to be a superset filter.
"""

from __future__ import annotations

import dataclasses
import json
import struct
import threading

from fabric_tpu.ledger.kvstore import KVStore, NamedDB


@dataclasses.dataclass(frozen=True, order=True)
class Height:
    """Commit height (block, tx) — the MVCC version (reference
    txmgmt/version/version.go)."""

    block_num: int
    tx_num: int

    def pack(self) -> bytes:
        return struct.pack(">QQ", self.block_num, self.tx_num)

    @classmethod
    def unpack(cls, raw: bytes) -> "Height":
        b, t = struct.unpack(">QQ", raw)
        return cls(b, t)


@dataclasses.dataclass
class VersionedValue:
    value: bytes
    version: Height
    metadata: bytes = b""


_NS_SEP = b"\x00"
_SAVEPOINT_KEY = b"\x01savepoint"
_IDX_PREFIX = b"\x03"
_IDX_DEF_PREFIX = b"\x04"
_META_NS_KEY = b"\x05metans"


def _state_key(ns: str, key: str) -> bytes:
    return b"\x02" + ns.encode() + _NS_SEP + key.encode()


def _esc(raw: bytes) -> bytes:
    """Order-preserving escape so \\x00 can terminate components."""
    return raw.replace(b"\x00", b"\x00\xff")


def encode_scalar(v) -> bytes | None:
    """Type-tagged order-preserving encoding of a JSON scalar; None for
    non-indexable values (objects/arrays)."""
    if v is None:
        return b"\x01"
    if isinstance(v, bool):
        return b"\x02" + (b"\x01" if v else b"\x00")
    if isinstance(v, (int, float)):
        f = float(v)
        if f == 0.0:
            f = 0.0  # normalize -0.0: Python == equates them, keys must too
        bits = struct.unpack(">Q", struct.pack(">d", f))[0]
        # IEEE754 total-order trick: flip sign bit for positives,
        # invert everything for negatives
        bits = bits ^ 0x8000000000000000 if bits < 1 << 63 else ~bits & (1 << 64) - 1
        return b"\x03" + struct.pack(">Q", bits)
    if isinstance(v, str):
        return b"\x04" + _esc(v.encode("utf-8"))
    return None


# Separator inside a COMPOUND index's field spec ("color\x1fsize") —
# the unit-separator control char never appears in JSON field paths.
INDEX_SPEC_SEP = "\x1f"


def encode_composite(values) -> bytes | None:
    """Order-preserving concatenation of scalar encodings for a
    compound index entry; None when any component is non-indexable.
    String components carry a \\x00 terminator (their escaped content
    never holds a bare \\x00), which both delimits them and keeps the
    concatenation ordered componentwise: a longer string's next content
    byte is always > the terminator, so ("ab", y) < ("abc", x) for
    every y, x — matching tuple comparison."""
    parts = []
    for v in values:
        e = encode_scalar(v)
        if e is None:
            return None
        if e[:1] == b"\x04":
            e += b"\x00"
        parts.append(e)
    return b"".join(parts)


def _idx_entry_state_key(rest: bytes, n_components: int = 1) -> str | None:
    """Parse `enc \\x00 statekey` (the tail of an index entry after the
    ns/field prefix) and return the state key.  The encoding length is
    recovered from its type tag — number encodings and state keys (e.g.
    composite keys) may legitimately contain \\x00 bytes, so a plain
    split would misparse.  `n_components` > 1 parses a compound entry
    (encode_composite: terminated strings)."""
    pos = 0
    for _ in range(n_components):
        tag = rest[pos:pos + 1]
        if tag == b"\x01":
            ln = 1
        elif tag == b"\x02":
            ln = 2
        elif tag == b"\x03":
            ln = 9
        elif tag == b"\x04":  # escaped string: ends at the first bare \x00
            i = pos + 1
            while True:
                j = rest.find(b"\x00", i)
                if j < 0:
                    return None
                if rest[j + 1:j + 2] == b"\xff":
                    i = j + 2
                    continue
                break
            ln = j - pos
            if n_components > 1:
                ln += 1  # composite strings include their terminator
        else:
            return None
        pos += ln
    if rest[pos:pos + 1] != b"\x00":
        return None
    try:
        return rest[pos + 1:].decode()
    except UnicodeDecodeError:
        return None


def _idx_key(ns: str, field: str, enc: bytes, key: str) -> bytes:
    return (
        _IDX_PREFIX + _esc(ns.encode()) + b"\x00" + _esc(field.encode())
        + b"\x00" + enc + b"\x00" + key.encode()
    )


def _idx_prefix(ns: str, field: str, enc: bytes = b"") -> bytes:
    base = _IDX_PREFIX + _esc(ns.encode()) + b"\x00" + _esc(field.encode()) + b"\x00"
    return base + enc


def _doc_field(value: bytes, path: str):
    """Extract a dotted field from a JSON document value; (None, False)
    when the value is not JSON or the path is absent."""
    try:
        doc = json.loads(value.decode("utf-8"))
    except Exception:
        # fabriclint: allow[exception-discipline] (None, False) is the
        # documented "no indexable field" sentinel for non-JSON values
        return None, False
    if not isinstance(doc, dict):
        return None, False
    cur = doc
    for part in path.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None, False
        cur = cur[part]
    return cur, True


def _encode_value(vv: VersionedValue) -> bytes:
    return (
        vv.version.pack()
        + struct.pack(">I", len(vv.metadata))
        + vv.metadata
        + vv.value
    )


def _decode_value(raw: bytes) -> VersionedValue:
    version = Height.unpack(raw[:16])
    (mlen,) = struct.unpack(">I", raw[16:20])
    metadata = raw[20 : 20 + mlen]
    return VersionedValue(raw[20 + mlen :], version, metadata)


class VersionedDB:
    """KV-backed versioned state (reference stateleveldb.VersionedDB),
    with optional per-(ns, field) rich-query indexes."""

    def __init__(self, store: KVStore, name: str = "statedb"):
        self._db = NamedDB(store, name)
        self._indexes: dict[str, set[str]] | None = None  # lazy-loaded
        self._meta_ns: set[str] | bool | None = None  # lazy; True = unknown
        # loading the set (a store read, then the assignment) against
        # dropping it: a validator's thread reads it while a committer's
        # flushes a group underneath, and a load that straddled the
        # flush must not leave the older set cached behind it
        self._meta_lock = threading.Lock()

    def rebased(self, base: KVStore) -> "VersionedDB":
        """The same versioned namespace over a different base store —
        the commit path hands this a WriteBatchCollector so
        apply_updates buffers into the group's single KV transaction,
        and reads (MVCC preloads, index maintenance) see the writes of
        earlier blocks in the same group.  The index-definition cache is
        shared with the parent (definitions only ever grow); the
        metadata-namespace cache is NOT — the view reloads it through
        the overlay so a group's own metadata flags stay visible."""
        c = VersionedDB.__new__(VersionedDB)
        c._db = self._db.rebase(base)
        c._indexes = self._load_indexes()
        c._meta_ns = None
        c._meta_lock = threading.Lock()
        return c

    # -- metadata presence fast path ---------------------------------------

    def _load_meta_ns(self):
        """Namespaces that have EVER stored key metadata (validation
        parameters / SBE).  Most workloads have none, and the committed-
        metadata lookup sits on the per-tx validation hot path — when a
        namespace is not in this set, get_state_metadata can answer {}
        without touching the store.  Monotone (never un-flagged), so it
        can only over-report, never under-report.  Legacy DBs written
        before this key existed stay permanently conservative."""
        m = self._meta_ns
        if m is None:
            with self._meta_lock:
                m = self._meta_ns
                if m is None:
                    raw = self._db.get(_META_NS_KEY)
                    if raw is not None:
                        m = set(json.loads(raw.decode()))
                    elif self._db.get(_SAVEPOINT_KEY) is not None:
                        m = True  # pre-existing DB: unknown history
                    else:
                        m = set()
                    self._meta_ns = m
        return m

    def _drop_meta_ns(self) -> None:
        with self._meta_lock:
            self._meta_ns = None

    def invalidate_caches(self) -> None:
        """Drop caches derived from the backing store — call after the
        store changed underneath this view (a WriteBatchCollector flush
        from a commit group, an out-of-band writer).  Index DEFINITIONS
        are deliberately kept: they only ever grow, and group commits
        never add them."""
        self._drop_meta_ns()

    def may_have_metadata(self, ns: str) -> bool:
        """False guarantees no key under `ns` carries metadata.

        The set is cached for read speed and re-loaded from the store at
        every apply_updates (see there), so metadata written through a
        DIFFERENT VersionedDB over the same backing store (offline
        repair tooling) becomes visible at the next commit boundary.
        What THIS ledger's commits flag is visible as soon as the
        commit (a group: its flush) has landed: the pipelined validator
        counts on that for the first metadata a namespace ever gets.
        Hot callers (the per-tx key-level endorsement fast path) should
        memoize per block, as TxValidator does."""
        m = self._load_meta_ns()
        return True if m is True else ns in m

    def holds_metadata(self) -> bool:
        """False guarantees `may_have_metadata` is False for every
        namespace: no key of the state carries metadata."""
        return bool(self._load_meta_ns())

    # -- index definitions -------------------------------------------------

    def _load_indexes(self) -> dict[str, set[str]]:
        if self._indexes is None:
            out: dict[str, set[str]] = {}
            end = _IDX_DEF_PREFIX + b"\xff"
            for k, _ in self._db.iterate(_IDX_DEF_PREFIX, end):
                ns_b, field_b = k[len(_IDX_DEF_PREFIX):].split(b"\x00", 1)
                out.setdefault(ns_b.decode(), set()).add(field_b.decode())
            self._indexes = out
        return self._indexes

    def indexes_for(self, ns: str) -> set[str]:
        return self._load_indexes().get(ns, set())

    def indexed_namespaces(self) -> set[str]:
        """Namespaces that have at least one index definition (snapshot
        export records the definitions so import can re-backfill)."""
        return set(self._load_indexes())

    def define_index(self, ns: str, field) -> None:
        """Create (and backfill) an index on a dotted JSON field — or,
        given a list/tuple of fields, a COMPOUND index over them (the
        statecouchdb multi-field index equivalent).  A document enters
        a compound index only when EVERY field is present with a
        scalar value — safe, because the planner only uses the index
        for conditions that require presence of scalars, so unindexed
        documents cannot match.  Idempotent."""
        if isinstance(field, (list, tuple)):
            fields_in = list(field)
            for f in fields_in:
                if INDEX_SPEC_SEP in f:
                    # a field NAME carrying the spec separator would be
                    # silently re-parsed as a compound spec and the
                    # index would under-select — refuse loudly
                    raise ValueError(
                        f"index field {f!r} contains the reserved "
                        "separator \\x1f"
                    )
        else:
            # a separator-joined STRING is the canonical spec form the
            # rest of the API trades in (indexes_for/index_scan), so
            # `define_index(ns, s) for s in src.indexes_for(ns)` —
            # the offline re-index pattern — round-trips compounds
            fields_in = field.split(INDEX_SPEC_SEP)
        spec = INDEX_SPEC_SEP.join(fields_in)
        if spec in self.indexes_for(ns):
            return
        fields = spec.split(INDEX_SPEC_SEP)
        puts = {_IDX_DEF_PREFIX + ns.encode() + b"\x00" + spec.encode(): b""}
        for key, vv in self.get_state_range(ns, "", ""):
            enc = self._index_encoding(vv.value, fields)
            if enc is not None:
                puts[_idx_key(ns, spec, enc, key)] = b""
        self._db.write_batch(puts, [])
        self._load_indexes().setdefault(ns, set()).add(spec)

    @staticmethod
    def _index_encoding(value: bytes, fields: list[str]) -> bytes | None:
        """The entry encoding of one document under an index spec, or
        None when the document does not belong in the index."""
        vals = []
        for f in fields:
            v, present = _doc_field(value, f)
            if not present:
                return None
            vals.append(v)
        if len(fields) == 1:
            return encode_scalar(vals[0])
        return encode_composite(vals)

    # -- index scans (planner entry points) --------------------------------

    def index_scan(self, ns: str, field: str, lo: bytes | None,
                   hi: bytes | None):
        """Yield state keys whose indexed encoding is in [lo, hi]
        (inclusive; None = open end).  `field` is the index spec
        (compound specs are INDEX_SPEC_SEP-joined); encodings come from
        encode_scalar / encode_composite; the caller rechecks each
        document."""
        start = _idx_prefix(ns, field, lo if lo is not None else b"")
        if hi is None:
            end = _idx_prefix(ns, field) + b"\xfe\xff"
        else:
            end = _idx_prefix(ns, field, hi) + b"\x01"
        plen = len(_idx_prefix(ns, field))
        n_comp = field.count(INDEX_SPEC_SEP) + 1
        for k, _ in self._db.iterate(start, end):
            key = _idx_entry_state_key(k[plen:], n_comp)
            if key is not None:
                yield key

    def _index_mutations(self, batch: dict, puts: dict, deletes: list) -> None:
        """Maintain index entries for namespaces with indexes: remove the
        old value's entries, add the new value's — inside the same
        atomic write batch as the state update."""
        idx = self._load_indexes()
        dels: set[bytes] = set()
        for ns, kvs in batch.items():
            specs = idx.get(ns)
            if not specs:
                continue
            split = {s: s.split(INDEX_SPEC_SEP) for s in specs}
            for key, vv in kvs.items():
                old = self.get_state(ns, key)
                for spec, fields in split.items():
                    if old is not None:
                        oenc = self._index_encoding(old.value, fields)
                        if oenc is not None:
                            dels.add(_idx_key(ns, spec, oenc, key))
                    if vv is not None:
                        nenc = self._index_encoding(vv.value, fields)
                        if nenc is not None:
                            puts[_idx_key(ns, spec, nenc, key)] = b""
        # an unchanged encoding would be deleted after being re-put
        # (write_batch applies puts before deletes) — drop those
        deletes.extend(dels - puts.keys())

    def get_state(self, ns: str, key: str) -> VersionedValue | None:
        raw = self._db.get(_state_key(ns, key))
        return None if raw is None else _decode_value(raw)

    def get_version(self, ns: str, key: str) -> Height | None:
        vv = self.get_state(ns, key)
        return None if vv is None else vv.version

    def get_state_multiple(self, ns: str, keys) -> list[VersionedValue | None]:
        return [self.get_state(ns, k) for k in keys]

    def get_state_many(self, pairs) -> dict:
        """Bulk point lookup: {(ns, key): VersionedValue | None} with an
        entry for EVERY requested pair (absent keys map to None, so a
        hit in the result distinguishes known-absent from not-probed) in
        one store round-trip — the commit path's bulk MVCC preload."""
        pairs = list(dict.fromkeys(pairs))
        raw_keys = [_state_key(ns, k) for ns, k in pairs]
        got = self._db.get_many(raw_keys)
        return {
            pair: (_decode_value(got[rk]) if rk in got else None)
            for pair, rk in zip(pairs, raw_keys)
        }

    def get_state_range(self, ns: str, start_key: str, end_key: str):
        """Iterate (key, VersionedValue) over [start, end); empty end = open."""
        start = _state_key(ns, start_key)
        if end_key:
            end = _state_key(ns, end_key)
        else:
            end = b"\x02" + ns.encode() + b"\x01"  # past the \x00 separator
        prefix_len = len(b"\x02" + ns.encode() + _NS_SEP)
        for k, v in self._db.iterate(start, end):
            yield k[prefix_len:].decode(), _decode_value(v)

    def apply_updates(self, batch: dict, height: Height | None) -> None:
        """batch: {ns: {key: VersionedValue | None}} (None = delete).
        Atomic with the savepoint write (reference ApplyUpdates)."""
        puts: dict[bytes, bytes] = {}
        deletes: list[bytes] = []
        self._index_mutations(batch, puts, deletes)  # reads OLD state
        # re-read the meta-ns set from the store (not the read cache):
        # the persisted key below must MERGE with flags an out-of-band
        # writer (a second VersionedDB over this store) may have added
        # since we last loaded — rewriting a stale cached set would
        # un-flag their namespaces and silently skip SBE checks.
        # ASSUMPTION: commits against one store are SERIALIZED (one
        # committer per ledger — kvledger holds the commit lock, as the
        # reference does).  Two VersionedDB instances committing
        # CONCURRENTLY could still interleave this load with the other's
        # write_batch and drop a freshly-added flag; the re-read narrows
        # that window, it does not close it.  Concurrent committers
        # would need the merge under the store's write lock.
        self._drop_meta_ns()
        meta_ns = self._load_meta_ns()
        for ns, kvs in batch.items():
            for key, vv in kvs.items():
                if vv is None:
                    deletes.append(_state_key(ns, key))
                else:
                    puts[_state_key(ns, key)] = _encode_value(vv)
                    if vv.metadata and meta_ns is not True:
                        meta_ns.add(ns)
        if meta_ns is not True:
            # ALWAYS persisted (even when empty): a store this code has
            # committed to must carry the key, otherwise the next
            # _load_meta_ns would see savepoint-without-key and flip to
            # the permanently-conservative legacy mode — which disabled
            # the per-tx key-level-endorsement fast path for every
            # ledger right after its genesis commit
            puts[_META_NS_KEY] = json.dumps(
                sorted(meta_ns), sort_keys=True
            ).encode()
        if height is not None:
            puts[_SAVEPOINT_KEY] = height.pack()
        self._db.write_batch(puts, deletes)
        # drop the metadata-namespace cache so the next reader re-loads
        # it from the store: one cheap get per commit buys visibility of
        # out-of-band writers (a second VersionedDB over this store)
        self._drop_meta_ns()

    def savepoint(self) -> Height | None:
        raw = self._db.get(_SAVEPOINT_KEY)
        return None if raw is None else Height.unpack(raw)

    # -- snapshot export / import ------------------------------------------

    def export_records(self):
        """Every state entry as a raw (key, value) pair in key order —
        the deterministic stream channel snapshots are built from.  Keys
        keep the full internal `\\x02 ns \\x00 key` encoding so import
        re-writes them verbatim (no decode/re-encode drift); index
        entries, definitions, and housekeeping keys are excluded."""
        return self._db.iterate(b"\x02", b"\x03")

    @staticmethod
    def split_state_key(raw_key: bytes) -> tuple[str, str]:
        """(ns, key) of a raw entry key from export_records.  Derived
        private/hashed namespaces embed \\x00 separators
        ('cc\\x00hash\\x00coll' — see txmgmt.hash_ns/pvt_ns), so that
        fixed shape is recognized before the plain ns/key split."""
        s = raw_key[1:]
        parts = s.split(b"\x00")
        if len(parts) >= 4 and parts[1] in (b"pvt", b"hash"):
            ns, key = b"\x00".join(parts[:3]), b"\x00".join(parts[3:])
        else:
            ns, _, key = s.partition(b"\x00")
        return ns.decode(), key.decode()

    def import_records(self, records, savepoint: Height,
                       batch_size: int = 10000) -> int:
        """Bulk-load raw state records (a snapshot's export stream) into
        an EMPTY state DB and set the savepoint, recomputing the
        metadata-presence namespace set on the way through (so the
        key-level-endorsement fast path stays exact on a restored
        ledger).  Returns the record count."""
        if self._db.get(_SAVEPOINT_KEY) is not None:
            raise ValueError("cannot import a snapshot into a non-empty state DB")
        meta_ns: set[str] = set()
        puts: dict[bytes, bytes] = {}
        count = 0
        for k, v in records:
            puts[k] = v
            count += 1
            if _decode_value(v).metadata:
                meta_ns.add(self.split_state_key(k)[0])
            if len(puts) >= batch_size:
                self._db.write_batch(puts, [])
                puts = {}
        puts[_META_NS_KEY] = json.dumps(
            sorted(meta_ns), sort_keys=True
        ).encode()
        puts[_SAVEPOINT_KEY] = savepoint.pack()
        self._db.write_batch(puts, [])
        self._drop_meta_ns()
        return count


__all__ = [
    "Height", "VersionedValue", "VersionedDB", "encode_scalar",
    "encode_composite", "INDEX_SPEC_SEP",
]
