"""Transaction management: simulation (rwset building) + MVCC validation.

Reference surface: core/ledger/kvledger/txmgmt —
  * rwsetutil: TxReadWriteSet build/parse (rwsetutil/rwset_builder.go)
  * validation: validateAndPrepareBatch / validateKVRead / validateRangeQuery
    (validation/validator.go:82-260)
  * lockbased_txmgr: the simulator handed to the endorser.

The MVCC pass itself is host work (string keys, variable shapes — not
device-friendly); the TPU win upstream is that by the time blocks reach
MVCC, all signature checks already ran as one batch.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time

from fabric_tpu.common import tracing, workpool
from fabric_tpu.common.hashing import sha256 as _sha256
from fabric_tpu.devtools import faultline
from fabric_tpu.ledger.statedb import Height, VersionedDB, VersionedValue
from fabric_tpu.protos.ledger.rwset import rwset_pb2
from fabric_tpu.protos.ledger.rwset.kvrwset import kv_rwset_pb2
from fabric_tpu.protos.peer import transaction_pb2

VALID = transaction_pb2.VALID
MVCC_READ_CONFLICT = transaction_pb2.MVCC_READ_CONFLICT
PHANTOM_READ_CONFLICT = transaction_pb2.PHANTOM_READ_CONFLICT
BAD_RWSET = transaction_pb2.BAD_RWSET


# Private collections live in the same VersionedDB under derived namespaces
# (the reference keeps a composite public/hashed/private DB,
# core/ledger/kvledger/txmgmt/privacyenabledstate/db.go; we derive
# sub-namespaces instead — '\x00' can't appear in chaincode names).
def pvt_ns(ns: str, coll: str) -> str:
    return f"{ns}\x00pvt\x00{coll}"


def hash_ns(ns: str, coll: str) -> str:
    return f"{ns}\x00hash\x00{coll}"


def key_hash(key: str) -> bytes:
    return _sha256(key.encode())


def value_hash(value: bytes) -> bytes:
    return _sha256(value)


# State metadata is a named-entry map; the key-level endorsement policy
# lives under the entry name VALIDATION_PARAMETER (reference
# core/ledger/kvledger/txmgmt/statemetadata + pkg/statebased).
VALIDATION_PARAMETER = "VALIDATION_PARAMETER"


def encode_metadata(entries: dict[str, bytes]) -> bytes:
    from fabric_tpu.protos.peer import chaincode_shim_pb2 as _shim

    res = _shim.StateMetadataResult()
    for name in sorted(entries):
        res.entries.add(metakey=name, value=entries[name])
    return res.SerializeToString()


def decode_metadata(raw: bytes) -> dict[str, bytes]:
    from fabric_tpu.protos.peer import chaincode_shim_pb2 as _shim

    if not raw:
        return {}
    res = _shim.StateMetadataResult.FromString(raw)
    return {e.metakey: bytes(e.value) for e in res.entries}


def _version_proto(h: Height | None):
    if h is None:
        return None
    return kv_rwset_pb2.Version(block_num=h.block_num, tx_num=h.tx_num)


def _height_of(v: kv_rwset_pb2.Version | None) -> Height | None:
    if v is None:
        return None
    return Height(v.block_num, v.tx_num)


class TxSimulator:
    """Collects a read-write set while chaincode reads/writes state
    (reference TxSimulator, core/ledger/ledger_interface.go:270)."""

    def __init__(self, db: VersionedDB):
        self._db = db
        self._reads: dict[tuple[str, str], Height | None] = {}
        self._writes: dict[tuple[str, str], bytes | None] = {}
        self._range_queries: list[kv_rwset_pb2.RangeQueryInfo] = []
        # Private data (reference TxSimulator Get/Set/DeletePrivateData,
        # ledger_interface.go:270): reads are recorded against the *hashed*
        # key-space (what committers without the collection validate), and
        # writes split into a hashed write (public) + the cleartext write
        # (distributed separately via the transient store / gossip).
        self._pvt_reads: dict[tuple[str, str, str], Height | None] = {}
        self._pvt_writes: dict[tuple[str, str, str], bytes | None] = {}
        # Metadata writes: full-entry-map replacement per key (reference
        # SetStateMetadata semantics are per-entry; we merge at write time
        # against the committed map so the rwset carries the final map).
        self._meta_writes: dict[tuple[str, str], dict[str, bytes]] = {}
        self._pvt_meta_writes: dict[
            tuple[str, str, str], dict[str, bytes]
        ] = {}
        self._done = False

    def get_state(self, ns: str, key: str) -> bytes | None:
        if (ns, key) in self._writes:
            return self._writes[(ns, key)]
        vv = self._db.get_state(ns, key)
        self._reads.setdefault((ns, key), vv.version if vv else None)
        return vv.value if vv else None

    def set_state(self, ns: str, key: str, value: bytes) -> None:
        self._writes[(ns, key)] = value

    def delete_state(self, ns: str, key: str) -> None:
        self._writes[(ns, key)] = None

    def get_state_metadata(self, ns: str, key: str) -> dict[str, bytes]:
        """Committed metadata entries of a key (reference
        GetStateMetadata); records NO read — metadata is validated by the
        key-level validator, not MVCC."""
        if (ns, key) in self._meta_writes:
            return dict(self._meta_writes[(ns, key)])
        vv = self._db.get_state(ns, key)
        return decode_metadata(vv.metadata) if vv else {}

    def set_state_metadata(
        self, ns: str, key: str, entries: dict[str, bytes]
    ) -> None:
        """Merge entries into the key's metadata (reference
        SetStateMetadata is per-entry upsert)."""
        cur = self.get_state_metadata(ns, key)
        cur.update(entries)
        self._meta_writes[(ns, key)] = cur

    def delete_state_metadata(self, ns: str, key: str, name: str) -> None:
        cur = self.get_state_metadata(ns, key)
        cur.pop(name, None)
        self._meta_writes[(ns, key)] = cur

    def get_private_data_metadata(
        self, ns: str, coll: str, key: str
    ) -> dict[str, bytes]:
        if (ns, coll, key) in self._pvt_meta_writes:
            return dict(self._pvt_meta_writes[(ns, coll, key)])
        vv = self._db.get_state(hash_ns(ns, coll), key_hash(key).hex())
        return decode_metadata(vv.metadata) if vv else {}

    def set_private_data_metadata(
        self, ns: str, coll: str, key: str, entries: dict[str, bytes]
    ) -> None:
        cur = self.get_private_data_metadata(ns, coll, key)
        cur.update(entries)
        self._pvt_meta_writes[(ns, coll, key)] = cur

    def get_private_data(self, ns: str, coll: str, key: str) -> bytes | None:
        if (ns, coll, key) in self._pvt_writes:
            return self._pvt_writes[(ns, coll, key)]
        # The hashed key-space is keyed by hex(sha256(key)) — the version
        # recorded here is what committers outside the collection validate.
        hv = self._db.get_state(hash_ns(ns, coll), key_hash(key).hex())
        self._pvt_reads.setdefault(
            (ns, coll, key), hv.version if hv else None
        )
        vv = self._db.get_state(pvt_ns(ns, coll), key)
        return vv.value if vv else None

    def set_private_data(self, ns: str, coll: str, key: str, value: bytes):
        self._pvt_writes[(ns, coll, key)] = value

    def delete_private_data(self, ns: str, coll: str, key: str) -> None:
        self._pvt_writes[(ns, coll, key)] = None

    def get_private_data_hash(self, ns: str, coll: str, key: str):
        """Hash-only read: allowed even for peers outside the collection
        (reference GetPrivateDataHash); does NOT record a read."""
        vv = self._db.get_state(hash_ns(ns, coll), key_hash(key).hex())
        return vv.value if vv else None

    def get_private_data_range(self, ns: str, coll: str, start: str, end: str):
        """[(key, value)] over the private key-space.  Like the reference,
        private range queries record no phantom-protection info."""
        return [
            (key, vv.value)
            for key, vv in self._db.get_state_range(pvt_ns(ns, coll), start, end)
        ]

    def get_query_result(self, ns: str, query: str):
        """Rich JSON-selector query (reference GetQueryResult via the
        CouchDB backend).  Every RETURNED key is recorded in the read set
        for MVCC version checks (reference queryHelper adds each result
        to the rwset); only phantoms go unprotected, matching the
        reference's couchdb caveat."""
        from fabric_tpu.ledger.richquery import (
            execute_query,
            execute_query_indexed,
        )

        if hasattr(self._db, "indexes_for"):
            got = execute_query_indexed(self._db, ns, query)
            if got is not None:
                out = []
                for key, value, version in got:
                    self._reads.setdefault((ns, key), version)
                    out.append((key, value))
                return out

        versions = {}

        def pairs():
            for key, vv in self._db.get_state_range(ns, "", ""):
                versions[key] = vv.version
                yield key, vv.value

        out = execute_query(pairs(), query)
        for key, _ in out:
            self._reads.setdefault((ns, key), versions[key])
        return out

    def get_private_data_query_result(self, ns: str, coll: str, query: str):
        from fabric_tpu.ledger.richquery import execute_query

        pairs = (
            (key, vv.value)
            for key, vv in self._db.get_state_range(pvt_ns(ns, coll), "", "")
        )
        return execute_query(pairs, query)

    def get_state_range(self, ns: str, start: str, end: str):
        """Returns [(key, value)] and records the range query for phantom
        detection at validation time."""
        rqi = kv_rwset_pb2.RangeQueryInfo(start_key=start, end_key=end, itr_exhausted=True)
        out = []
        for key, vv in self._db.get_state_range(ns, start, end):
            rqi.raw_reads.kv_reads.append(
                kv_rwset_pb2.KVRead(key=key, version=_version_proto(vv.version))
            )
            out.append((key, vv.value))
        self._range_queries.append((ns, rqi))
        return out

    def _pvt_collection_rwsets(self) -> dict[str, dict[str, bytes]]:
        """{ns: {coll: serialized private KVRWSet}} for namespaces with
        private writes."""
        per_coll: dict[tuple[str, str], kv_rwset_pb2.KVRWSet] = {}
        for (ns, coll, key), value in sorted(self._pvt_writes.items()):
            per_coll.setdefault((ns, coll), kv_rwset_pb2.KVRWSet()).writes.append(
                kv_rwset_pb2.KVWrite(
                    key=key, is_delete=value is None, value=value or b""
                )
            )
        out: dict[str, dict[str, bytes]] = {}
        for (ns, coll), kvrw in per_coll.items():
            out.setdefault(ns, {})[coll] = kvrw.SerializeToString()
        return out

    def get_tx_simulation_results(self) -> bytes:
        """Marshaled rwset.TxReadWriteSet: public reads/writes plus, per
        collection touched, the hashed rwset + hash of the private rwset
        (reference rwsetutil/rwset_builder.go GetTxSimulationResults)."""
        self._done = True
        by_ns: dict[str, kv_rwset_pb2.KVRWSet] = {}

        def ns_set(ns: str) -> kv_rwset_pb2.KVRWSet:
            return by_ns.setdefault(ns, kv_rwset_pb2.KVRWSet())

        for (ns, key), ver in sorted(self._reads.items()):
            ns_set(ns).reads.append(
                kv_rwset_pb2.KVRead(key=key, version=_version_proto(ver))
            )
        for item in self._range_queries:
            ns, rqi = item
            ns_set(ns).range_queries_info.append(rqi)
        for (ns, key), value in sorted(self._writes.items()):
            ns_set(ns).writes.append(
                kv_rwset_pb2.KVWrite(
                    key=key, is_delete=value is None, value=value or b""
                )
            )
        for (ns, key), entries in sorted(self._meta_writes.items()):
            mw = kv_rwset_pb2.KVMetadataWrite(key=key)
            for name in sorted(entries):
                mw.entries.add(name=name, value=entries[name])
            ns_set(ns).metadata_writes.append(mw)

        # Hashed r/w sets per (ns, collection).
        hashed: dict[tuple[str, str], kv_rwset_pb2.HashedRWSet] = {}

        def coll_set(ns: str, coll: str) -> kv_rwset_pb2.HashedRWSet:
            return hashed.setdefault((ns, coll), kv_rwset_pb2.HashedRWSet())

        for (ns, coll, key), ver in sorted(self._pvt_reads.items()):
            coll_set(ns, coll).hashed_reads.append(
                kv_rwset_pb2.KVReadHash(
                    key_hash=key_hash(key), version=_version_proto(ver)
                )
            )
        for (ns, coll, key), value in sorted(self._pvt_writes.items()):
            coll_set(ns, coll).hashed_writes.append(
                kv_rwset_pb2.KVWriteHash(
                    key_hash=key_hash(key),
                    is_delete=value is None,
                    value_hash=value_hash(value) if value is not None else b"",
                )
            )
        for (ns, coll, key), entries in sorted(
            self._pvt_meta_writes.items()
        ):
            mw = kv_rwset_pb2.KVMetadataWriteHash(key_hash=key_hash(key))
            for name in sorted(entries):
                mw.entries.add(name=name, value=entries[name])
            coll_set(ns, coll).metadata_writes.append(mw)

        pvt = self._pvt_collection_rwsets()
        namespaces = sorted(
            set(by_ns) | {ns for ns, _ in hashed}
        )
        txrw = rwset_pb2.TxReadWriteSet(data_model=rwset_pb2.TxReadWriteSet.KV)
        for ns in namespaces:
            nsrw = rwset_pb2.NsReadWriteSet(
                namespace=ns,
                rwset=by_ns.get(ns, kv_rwset_pb2.KVRWSet()).SerializeToString(),
            )
            for (hns, coll), hrw in sorted(hashed.items()):
                if hns != ns:
                    continue
                pvt_bytes = pvt.get(ns, {}).get(coll)
                nsrw.collection_hashed_rwset.append(
                    rwset_pb2.CollectionHashedReadWriteSet(
                        collection_name=coll,
                        hashed_rwset=hrw.SerializeToString(),
                        pvt_rwset_hash=(
                            _sha256(pvt_bytes)
                            if pvt_bytes is not None
                            else b""
                        ),
                    )
                )
            txrw.ns_rwset.append(nsrw)
        return txrw.SerializeToString()

    def get_pvt_simulation_results(self) -> bytes | None:
        """Marshaled rwset.TxPvtReadWriteSet with the cleartext private
        writes, or None if the tx touched no collections.  Never embedded
        in the transaction — distributed via transient store + gossip."""
        pvt = self._pvt_collection_rwsets()
        if not pvt:
            return None
        txpvt = rwset_pb2.TxPvtReadWriteSet(
            data_model=rwset_pb2.TxReadWriteSet.KV
        )
        for ns in sorted(pvt):
            nsp = rwset_pb2.NsPvtReadWriteSet(namespace=ns)
            for coll in sorted(pvt[ns]):
                nsp.collection_pvt_rwset.append(
                    rwset_pb2.CollectionPvtReadWriteSet(
                        collection_name=coll, rwset=pvt[ns][coll]
                    )
                )
            txpvt.ns_pvt_rwset.append(nsp)
        return txpvt.SerializeToString()


@dataclasses.dataclass
class _TxUpdates:
    writes: dict[tuple[str, str], bytes | None]


# a block below this many write operations prepares serially even when
# a fan-out width is configured — chunking overhead would dominate
_PARALLEL_MIN_WRITES = 32


# what MVCCValidator counts of every block it validates, in this order
MVCC_COUNTS = ("keys_asked", "rows_found", "valid_in",
               "read_conflicts", "phantom_conflicts")


class MvccTally:
    """What MVCC asked of the state and what it refused, from process
    start: the sums of `MVCC_COUNTS` over every block an MVCCValidator
    validated (`keys_asked`: the distinct (namespace, key) pairs the
    block's one bulk preload asked the store for; `rows_found`: those
    that are rows; `valid_in`: the transactions that came in VALID with
    a read-write set; `read_conflicts` / `phantom_conflicts`: those MVCC
    invalidated, by kind), and of the last RECENT blocks their number
    and their five counts, oldest first, so that whoever knows how many
    blocks a stretch committed (a benchmark's window:
    benchmarks/conditions/smallbank-shape.py) reads that stretch alone.
    An operator reads the same on /metrics (ledger_preload_rows_total,
    ledger_mvcc_invalidated_total)."""

    RECENT = 16384

    def __init__(self):
        self._lock = threading.Lock()
        self._totals = [0] * len(MVCC_COUNTS)
        self._blocks = 0
        self._recent: collections.deque = collections.deque(maxlen=self.RECENT)

    def block_done(self, num: int, counts: tuple) -> None:
        with self._lock:
            self._blocks += 1
            self._totals = [a + b for a, b in zip(self._totals, counts)]
            self._recent.append((num, *counts))

    def snapshot(self) -> dict:
        with self._lock:
            return {"blocks": self._blocks, **dict(zip(MVCC_COUNTS, self._totals)),
                    "recent_blocks": list(self._recent)}


_MVCC = MvccTally()


def mvcc_tally() -> dict:
    """See MvccTally."""
    return _MVCC.snapshot()


class MVCCValidator:
    """Block-level MVCC validation building the state update batch
    (reference validation/validator.go:82 validateAndPrepareBatch).

    Structured as two passes so the write-set prepare can fan out:

    1. **check** (always serial, commit order): read/range/hashed-read
       conflict detection and the in-block version bookkeeping
       (``updated_versions``) — the pass whose outputs feed later txs'
       conflict checks, so it is inherently ordered.
    2. **prepare** (parallelizable per top-level namespace): building
       the ``{ns: {key: VersionedValue|None}}`` batch, including
       metadata retention and cleartext-private application.  Namespaces
       are disjoint batch keys (derived hash/pvt namespaces embed their
       parent), so per-namespace workers never share output, and the
       merge re-assembles the batch in the exact first-encounter
       namespace order the serial loop would have produced — flags and
       batch contents are byte-identical to serial at every fan-out
       width (pinned by tests/test_parallel_commit.py).

    `fanout` chunks the namespace groups across `pool` (default: the
    process workpool); None reads FABRIC_TPU_MVCC_POOL, 0 keeps prepare
    serial.  The bulk version preload fans out per namespace under the
    same width."""

    def __init__(self, db: VersionedDB, pool=None, fanout: int | None = None):
        self._db = db
        self._pool = pool
        if fanout is None:
            fanout = workpool.stage_width("FABRIC_TPU_MVCC_POOL")
        self._fanout = max(0, fanout)
        # per-call stage wall seconds {preload, check, prepare} — the
        # ledger folds these into commit_stage_seconds/os /metrics as
        # mvcc_preload/mvcc_check/mvcc_prepare
        self.last_stage_seconds: dict[str, float] = {}
        # the last block's MVCC_COUNTS by name: the ledger puts them on
        # its `mvcc` span and on /metrics (MvccTally has the sums)
        self.last_counts: dict[str, int] = {}
        # blocks whose prepare actually fanned out (smoke-test probe)
        self.parallel_prepare_blocks = 0

    def _committed_version(
        self, ns: str, key: str, updates: dict, cache: dict | None = None
    ) -> Height | None:
        if (ns, key) in updates:
            return updates[(ns, key)]
        if cache is not None and (ns, key) in cache:
            vv = cache[(ns, key)]
            return None if vv is None else vv.version
        return self._db.get_version(ns, key)

    def _preload(self, parsed_per_tx: list) -> dict:
        """Bulk-load the block's whole point read/version set — every
        read key, hashed read, and (only in namespaces that may carry
        metadata) every write key, whose committed metadata a value-only
        write must retain — in ONE get_state_many round-trip instead of
        a store probe per key (the reference pays a leveldb get per
        read, validator.go validateKVRead).  Range queries are not
        preloaded; they fall back to scans.  The result maps every
        harvested (ns, key) to VersionedValue | None, so a cache entry
        of None means known-absent, not not-probed."""
        keys: list[tuple[str, str]] = []
        may_meta: dict[str, bool] = {}

        def meta(ns: str) -> bool:
            # _existing_metadata short-circuits on may_have_metadata,
            # so metadata-free namespaces (the common case) need no
            # write-key preload at all
            got = may_meta.get(ns)
            if got is None:
                got = may_meta[ns] = self._db.may_have_metadata(ns)
            return got

        for parsed in parsed_per_tx:
            if not parsed:
                continue
            for ns, kvrw, colls in parsed:
                keys.extend((ns, r.key) for r in kvrw.reads)
                if meta(ns):
                    keys.extend((ns, w.key) for w in kvrw.writes)
                    keys.extend(
                        (ns, mw.key) for mw in kvrw.metadata_writes
                    )
                for coll, hrw, _ in colls:
                    hns = hash_ns(ns, coll)
                    keys.extend(
                        (hns, bytes(hr.key_hash).hex())
                        for hr in hrw.hashed_reads
                    )
                    if meta(hns):
                        keys.extend(
                            (hns, bytes(hw.key_hash).hex())
                            for hw in hrw.hashed_writes
                        )
                        keys.extend(
                            (hns, bytes(mw.key_hash).hex())
                            for mw in hrw.metadata_writes
                        )
        if not keys:
            return {}
        width = self._fanout
        if width > 1 and len(keys) >= 2 * _PARALLEL_MIN_WRITES:
            by_ns: dict[str, list] = {}
            for pair in keys:
                by_ns.setdefault(pair[0], []).append(pair)
            if len(by_ns) >= 2:
                # per-namespace version preload: each group is one
                # get_state_many round-trip; the merged cache is the
                # same mapping the single round-trip would produce
                # (namespace is part of every key, so groups are
                # disjoint)
                def _load(off, chunk):
                    out = []
                    for pairs in chunk:
                        faultline.point(
                            "mvcc.ns_prepare", stage="preload",
                            ns=pairs[0][0],
                        )
                        out.append(self._db.get_state_many(pairs))
                    return out

                maps = workpool.run_chunked(
                    self._pool or workpool.default_pool(),
                    _load, list(by_ns.values()),
                    min(width, len(by_ns)),
                )
                merged: dict = {}
                for m in maps:
                    merged.update(m)
                return merged
        return self._db.get_state_many(keys)

    def validate_and_prepare(
        self,
        block_num: int,
        rwsets: list[bytes | None],
        flags: list[int],
        pvt_data: dict[int, bytes] | None = None,
        footprints: list | None = None,
    ) -> dict:
        """rwsets[i]: marshaled TxReadWriteSet of tx i (None = not an
        endorser tx or already invalid).  Mutates `flags` with MVCC codes;
        returns the state update batch {ns: {key: VersionedValue|None}}.

        pvt_data maps tx_num -> marshaled TxPvtReadWriteSet for txs whose
        cleartext private writes this peer holds; cleartext writes apply
        only when their hash matches the endorsed pvt_rwset_hash (reference
        coordinator verifies hashes before commit,
        gossip/privdata/coordinator.go).

        footprints[i], when given, is the validator's RwsetFootprint for
        tx i: its `.parsed` [(ns, KVRWSet, [(coll, HashedRWSet, hash)])]
        is exactly this method's decode, so the rwset wire format is
        walked once per tx per lifecycle instead of once per stage (the
        reference re-unmarshals in validateAndPrepareBatch,
        validation/validator.go:82).

        Matches the reference's serial-in-commit-order semantics: a tx sees
        conflicts against committed state AND the writes of earlier valid
        txs in the same block."""
        pvt_data = pvt_data or {}
        # decode pass: adopt the validator's footprints or unmarshal
        # once per tx, so the whole block's read set can be harvested
        # for ONE bulk version preload before any validation runs
        parsed_per_tx: list = [None] * len(rwsets)
        for tx_num, raw in enumerate(rwsets):
            if flags[tx_num] != VALID or raw is None:
                continue
            fp = footprints[tx_num] if footprints is not None else None
            if fp is not None:
                parsed_per_tx[tx_num] = fp.parsed
                continue
            try:
                txrw = rwset_pb2.TxReadWriteSet.FromString(raw)
                parsed_per_tx[tx_num] = [
                    (
                        nsrw.namespace,
                        kv_rwset_pb2.KVRWSet.FromString(nsrw.rwset),
                        [
                            (
                                ch.collection_name,
                                kv_rwset_pb2.HashedRWSet.FromString(
                                    ch.hashed_rwset
                                ),
                                bytes(ch.pvt_rwset_hash),
                            )
                            for ch in nsrw.collection_hashed_rwset
                        ],
                    )
                    for nsrw in txrw.ns_rwset
                ]
            except Exception:
                flags[tx_num] = BAD_RWSET
        t = time.perf_counter
        t0 = t()
        # the one bulk read of the rows the block names: a span of its
        # own, so that a state past the store's cache shows where it costs
        with tracing.span("mvcc.preload", cat="stage", block=block_num) as psp:
            cache = self._preload(parsed_per_tx)
            found = len(cache) - list(cache.values()).count(None)
            psp.annotate(keys_asked=len(cache), rows_found=found)
        t1 = t()
        valid_in = read_conflicts = phantom_conflicts = 0

        # -- pass 1: serial conflict checks + version bookkeeping -------
        # updated_versions carries every in-block write's version (None
        # for deletes) — the state later txs' conflict checks read —
        # and doubles as the "was this key written earlier in the
        # block" oracle the metadata-write bookkeeping needs.  Work for
        # pass 2 is grouped by TOP-LEVEL namespace (derived hash/pvt
        # namespaces ride with their parent), and ns_order records the
        # exact batch-key first-encounter order of the serial loop.
        updated_versions: dict[tuple[str, str], Height] = {}
        ns_order: list[str] = []
        ns_owner: dict[str, str] = {}
        groupwork: dict[str, list] = {}
        all_items: list = []  # every group item in global (tx, entry)
        # order — the collision fallback's single serial group
        collided = [False]
        n_writes = 0

        def order(ns: str, owner: str) -> None:
            # `owner` is the TOP-LEVEL group key (the parsed entry's
            # namespace) recorded explicitly — never re-derived from the
            # namespace string, because an adversarial rwset may name a
            # top-level namespace that itself contains the \x00 the
            # derived hash/pvt encodings use.  If two different groups
            # ever claim one output namespace (a literal namespace
            # colliding with another namespace's derived hash/pvt
            # encoding — only constructible by an adversarial rwset),
            # the groups are NOT disjoint and pass 2 falls back to one
            # serial group over all items, which reproduces the old
            # single-batch-dict semantics exactly.
            if ns not in ns_owner:
                ns_owner[ns] = owner
                ns_order.append(ns)
            elif ns_owner[ns] != owner:
                collided[0] = True

        for tx_num, parsed in enumerate(parsed_per_tx):
            if parsed is None or flags[tx_num] != VALID:
                continue
            valid_in += 1
            code = VALID
            for ns, kvrw, colls in parsed:
                for read in kvrw.reads:
                    want = _height_of(read.version) if read.HasField("version") else None
                    have = self._committed_version(
                        ns, read.key, updated_versions, cache
                    )
                    if want != have:
                        code = MVCC_READ_CONFLICT
                        break
                if code != VALID:
                    break
                for rqi in kvrw.range_queries_info:
                    if not self._validate_range_query(ns, rqi, updated_versions):
                        code = PHANTOM_READ_CONFLICT
                        break
                if code != VALID:
                    break
                for coll, hrw, _ in colls:
                    hns = hash_ns(ns, coll)
                    for hread in hrw.hashed_reads:
                        want = (
                            _height_of(hread.version)
                            if hread.HasField("version")
                            else None
                        )
                        have = self._committed_version(
                            hns, bytes(hread.key_hash).hex(),
                            updated_versions, cache,
                        )
                        if want != have:
                            code = MVCC_READ_CONFLICT
                            break
                    if code != VALID:
                        break
                if code != VALID:
                    break
            flags[tx_num] = code
            if code != VALID:
                if code == PHANTOM_READ_CONFLICT:
                    phantom_conflicts += 1
                else:
                    read_conflicts += 1
                continue
            h = Height(block_num, tx_num)
            pvt_by_coll = self._parse_pvt(pvt_data.get(tx_num))
            # cleartext authenticity is decided HERE, once: only
            # collections whose supplied cleartext hashes to the
            # endorsed pvt_rwset_hash survive into pvt_ok — pass 2
            # applies them without re-hashing, forged/absent supplies
            # are treated as missing (an empty endorsed hash means NO
            # cleartext was endorsed, so any supply is forged)
            pvt_ok: dict = {}
            for ns, kvrw, colls in parsed:
                order(ns, ns)
                item = (h, ns, kvrw, colls, pvt_ok)
                groupwork.setdefault(ns, []).append(item)
                all_items.append(item)
                for w in kvrw.writes:
                    n_writes += 1
                    updated_versions[(ns, w.key)] = (
                        None if w.is_delete else h  # type: ignore[assignment]
                    )
                for mw in kvrw.metadata_writes:
                    n_writes += 1
                    self._meta_write_version(
                        ns, mw.key, h, updated_versions, cache
                    )
                for coll, hrw, expected_hash in colls:
                    hns = hash_ns(ns, coll)
                    order(hns, ns)
                    for hw in hrw.hashed_writes:
                        n_writes += 1
                        updated_versions[(hns, bytes(hw.key_hash).hex())] = (
                            None if hw.is_delete else h  # type: ignore[assignment]
                        )
                    for mw in hrw.metadata_writes:
                        n_writes += 1
                        self._meta_write_version(
                            hns, bytes(mw.key_hash).hex(), h,
                            updated_versions, cache,
                        )
                    clear = pvt_by_coll.get((ns, coll))
                    if clear is not None and expected_hash and \
                            _sha256(clear[0]) == expected_hash:
                        pvt_ok[(ns, coll)] = clear
                        order(pvt_ns(ns, coll), ns)
        t2 = t()

        # -- pass 2: write-set prepare, fanned out per namespace --------
        if collided[0]:
            # non-disjoint groups (see order()): one serial group over
            # all items in global order — the old single-dict semantics
            groups = [("", all_items)]
        else:
            groups = [(ns, items) for ns, items in groupwork.items()]
        width = self._fanout
        if (
            width > 1 and len(groups) >= 2
            and n_writes >= _PARALLEL_MIN_WRITES
        ):
            # warm the metadata-namespace cache once on this thread so
            # pool workers only ever read it
            self._db.may_have_metadata("")
            width = min(width, len(groups))
            self.parallel_prepare_blocks += 1
        else:
            width = 0
        pool = None
        if width:
            pool = self._pool or workpool.default_pool()

        def _prep(off, chunk, _cache=cache):
            return self._prepare_groups(chunk, _cache)

        maps = workpool.run_chunked(pool, _prep, groups, width or 1)
        batch: dict[str, dict[str, VersionedValue | None]] = {}
        if collided[0]:
            single = maps[0]
            for ns in ns_order:
                batch[ns] = single.get(ns, {})
        else:
            # each namespace (top-level or derived) resolves to the
            # group pass 1 recorded as its owner
            by_group = {
                gns: m for (gns, _items), m in zip(groups, maps)
            }
            for ns in ns_order:
                batch[ns] = by_group[ns_owner[ns]].get(ns, {})
        self.last_stage_seconds = {
            "preload": t1 - t0, "check": t2 - t1, "prepare": t() - t2,
        }
        counts = (len(cache), found, valid_in, read_conflicts,
                  phantom_conflicts)
        self.last_counts = dict(zip(MVCC_COUNTS, counts))
        _MVCC.block_done(block_num, counts)
        return batch

    def _prepare_groups(self, groups: list, cache: dict) -> list[dict]:
        """Pass-2 worker: build the batch dicts for a chunk of namespace
        groups.  Each group's items arrive in commit order, outputs are
        keyed by exact namespace strings (parent + derived), and no two
        groups share an output key — so any interleaving of workers
        merges to the same batch."""
        out = []
        for ns_top, items in groups:
            faultline.point(
                "mvcc.ns_prepare", stage="prepare", ns=ns_top,
                txs=len(items),
            )
            m: dict[str, dict] = {}
            for h, ns, kvrw, colls, pvt_by_coll in items:
                self._build_ns_writes(
                    ns, kvrw, colls, h, pvt_by_coll, m, cache
                )
            out.append(m)
        return out

    def _build_ns_writes(self, ns, kvrw, colls, h, pvt_by_coll, out,
                         cache) -> None:
        """Apply one tx's writes for one parsed namespace entry into the
        per-group batch maps — the exact write-application the serial
        loop performed, minus the version bookkeeping pass 1 already
        did."""
        ns_batch = out.setdefault(ns, {})
        for w in kvrw.writes:
            if w.is_delete:
                ns_batch[w.key] = None
            else:
                # A value-only write RETAINS existing metadata
                # (key-level endorsement policies survive plain
                # puts — reference tx_ops metadata merge).
                ns_batch[w.key] = VersionedValue(
                    w.value, h,
                    self._existing_metadata(ns, w.key, ns_batch, cache),
                )
        for mw in kvrw.metadata_writes:
            self._apply_metadata_write(
                ns, mw.key,
                {e.name: bytes(e.value) for e in mw.entries},
                ns_batch, h, cache,
            )
        for coll, hrw, expected_hash in colls:
            hns = hash_ns(ns, coll)
            h_batch = out.setdefault(hns, {})
            for hw in hrw.hashed_writes:
                hkey = bytes(hw.key_hash).hex()
                if hw.is_delete:
                    h_batch[hkey] = None
                else:
                    h_batch[hkey] = VersionedValue(
                        bytes(hw.value_hash), h,
                        self._existing_metadata(hns, hkey, h_batch, cache),
                    )
            for mw in hrw.metadata_writes:
                self._apply_metadata_write(
                    hns, bytes(mw.key_hash).hex(),
                    {e.name: bytes(e.value) for e in mw.entries},
                    h_batch, h, cache,
                )
            # Cleartext private writes: pvt_by_coll is pass 1's
            # ALREADY-AUTHENTICATED map (only entries whose cleartext
            # hashed to the endorsed pvt_rwset_hash survive), so the
            # worker applies without re-hashing; forged/absent supplies
            # were dropped there.
            clear = pvt_by_coll.get((ns, coll))
            if clear is None:
                continue
            _raw_kvrw, clear_kvrw = clear
            p_batch = out.setdefault(pvt_ns(ns, coll), {})
            for w in clear_kvrw.writes:
                if w.is_delete:
                    p_batch[w.key] = None
                else:
                    p_batch[w.key] = VersionedValue(w.value, h)

    def _meta_write_version(self, ns, key, h, updated_versions, cache) -> None:
        """Pass-1 version bookkeeping of a metadata write: it bumps the
        key's version only when the key EXISTS (earlier in-block write
        that was not a delete, else committed state) — mirroring
        _apply_metadata_write's early returns."""
        if (ns, key) in updated_versions:
            if updated_versions[(ns, key)] is None:
                return  # deleted earlier in the block: metadata no-op
        else:
            if cache is not None and (ns, key) in cache:
                vv = cache[(ns, key)]
            else:
                vv = self._db.get_state(ns, key)
                if cache is not None:
                    # stash so the pass-2 worker's _apply_metadata_write
                    # hits the cache instead of re-probing the store
                    cache[(ns, key)] = vv
            if vv is None:
                return  # key absent: metadata write is a no-op
        updated_versions[(ns, key)] = h

    def _existing_metadata(
        self, ns: str, key: str, ns_batch: dict, cache: dict | None = None
    ) -> bytes:
        """Current metadata of a key: in-block overlay first, then
        committed state (preload cache before a point probe); empty for
        new/deleted keys."""
        if key in ns_batch:
            base = ns_batch[key]
            return base.metadata if base is not None else b""
        if not self._db.may_have_metadata(ns):
            return b""  # namespace never stored metadata: skip the store
        if cache is not None and (ns, key) in cache:
            vv = cache[(ns, key)]
        else:
            vv = self._db.get_state(ns, key)
        return vv.metadata if vv is not None else b""

    def _apply_metadata_write(
        self, ns: str, key: str, entries: dict[str, bytes],
        ns_batch: dict, h: Height, cache: dict | None = None,
    ) -> None:
        """Replace a key's metadata map, keeping its value; a metadata
        write on a non-existent/deleted key is a no-op (reference
        statemetadata semantics).  Version bookkeeping lives in pass 1
        (_meta_write_version) — this is pure batch construction."""
        if key in ns_batch:
            base = ns_batch[key]
            if base is None:
                return
            ns_batch[key] = VersionedValue(base.value, h, encode_metadata(entries))
        else:
            if cache is not None and (ns, key) in cache:
                vv = cache[(ns, key)]
            else:
                vv = self._db.get_state(ns, key)
            if vv is None:
                return
            ns_batch[key] = VersionedValue(vv.value, h, encode_metadata(entries))

    @staticmethod
    def _parse_pvt(raw: bytes | None):
        """{(ns, coll): (raw_kvrwset_bytes, parsed KVRWSet)}"""
        out: dict[tuple[str, str], tuple[bytes, kv_rwset_pb2.KVRWSet]] = {}
        if not raw:
            return out
        try:
            txpvt = rwset_pb2.TxPvtReadWriteSet.FromString(raw)
            for nsp in txpvt.ns_pvt_rwset:
                for cp in nsp.collection_pvt_rwset:
                    out[(nsp.namespace, cp.collection_name)] = (
                        bytes(cp.rwset),
                        kv_rwset_pb2.KVRWSet.FromString(cp.rwset),
                    )
        except Exception:
            # fabriclint: allow[exception-discipline] unparsable supplied pvt
            # cleartext contributes no writes; the hashed-namespace comparison
            # independently flags the gap as missing data
            return {}
        return out

    def _validate_range_query(self, ns: str, rqi, updated_versions) -> bool:
        """Re-scan and compare against recorded raw reads (reference
        validateRangeQuery; the Merkle-summary variant is not implemented —
        simulators here always record raw reads)."""
        if rqi.WhichOneof("reads_info") == "reads_merkle_hashes":
            return False
        current: list[tuple[str, Height | None]] = []
        seen = set()
        for key, vv in self._db.get_state_range(ns, rqi.start_key, rqi.end_key):
            ver = updated_versions.get((ns, key), vv.version)
            if ver is not None:
                current.append((key, ver))
                seen.add(key)
        # keys created by earlier txs in this block inside the range are
        # phantoms too
        for (uns, ukey), uver in updated_versions.items():
            if uns != ns or ukey in seen or uver is None:
                continue
            if rqi.start_key <= ukey and (not rqi.end_key or ukey < rqi.end_key):
                current.append((ukey, uver))
        current.sort()
        recorded = [
            (r.key, _height_of(r.version) if r.HasField("version") else None)
            for r in rqi.raw_reads.kv_reads
        ]
        return current == recorded


__all__ = [
    "TxSimulator",
    "MVCCValidator",
    "VALID",
    "MVCC_READ_CONFLICT",
    "PHANTOM_READ_CONFLICT",
    "BAD_RWSET",
    "pvt_ns",
    "hash_ns",
    "key_hash",
    "value_hash",
    "VALIDATION_PARAMETER",
    "encode_metadata",
    "decode_metadata",
]
