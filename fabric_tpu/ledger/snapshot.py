"""Channel snapshots & join-by-snapshot: TPU-hashed ledger checkpoints.

Reference: core/ledger/kvledger/snapshot.go + snapshot_mgmt.go (generate
at commit, request bookkeeping), core/ledger/kvledger/kv_ledger_provider.go
CreateFromSnapshot, internal/peer/snapshot (CLI surface).  A snapshot is a
directory of deterministic, ordered export files

    public_state.data          raw (key, value) records of the public
                               state namespaces, in state-key order
    private_state_hashes.data  the derived hashed-collection namespaces
                               (key hashes + value hashes; cleartext
                               private data is NEVER exported — a
                               restored peer reconciles it later)
    txids.data                 every committed txid (duplicate-tx guard)
    confighistory.data         collection-config history entries
    channel_config.block       the channel's config block (lets a peer
                               with no blocks build its channel bundle)
    _snapshot_signable_metadata.json
                               channel id, last block number/hash, and
                               per-file SHA-256 digests

The per-file digests are computed through the CSP `hash_batch` seam
(fabric_tpu/csp/api.py) — one batched call for all files, which every
provider in the tree answers with hashlib.  `verify_snapshot` recomputes
the digests on import and refuses a tampered directory.

Request lifecycle (reference snapshot_mgmt.go): requests are persisted
under the ledger's bookkeeping/snapshot-request namespace (submit /
cancel / list-pending) and the ledger triggers generation automatically
when it commits the requested block number.  Generated snapshots land in

    <snapshots_root>/completed/<ledger_id>/<last_block_number>/

written via an in_progress staging directory + atomic rename so a crash
never leaves a half-written "completed" snapshot.
"""

from __future__ import annotations

import json
import os
import shutil
import struct
import threading
import time

from fabric_tpu.common import tracing
from fabric_tpu.devtools import faultline
from fabric_tpu.devtools.lockwatch import (
    guarded,
    named_condition,
    named_lock,
    spawn_thread,
)
from fabric_tpu.ledger.bookkeeping import (
    SNAPSHOT_REQUEST,
    BookkeepingProvider,
)
from fabric_tpu.ledger.confighistory import ConfigHistoryMgr
from fabric_tpu.ledger.kvstore import KVStore, NamedDB
from fabric_tpu.ledger.pvtdatastorage import PvtDataStore
from fabric_tpu.ledger.txmgmt import key_hash
from fabric_tpu.ledger.statedb import Height, VersionedDB

SNAPSHOT_FORMAT_VERSION = 1

METADATA_FILE = "_snapshot_signable_metadata.json"
PUBLIC_STATE_FILE = "public_state.data"
PVT_HASHES_FILE = "private_state_hashes.data"
TXIDS_FILE = "txids.data"
CONFIG_HISTORY_FILE = "confighistory.data"
CONFIG_BLOCK_FILE = "channel_config.block"

# the data files whose digests enter the signable metadata, in the fixed
# order they are hashed (sorted, so the metadata is deterministic)
DATA_FILES = (
    CONFIG_BLOCK_FILE,
    CONFIG_HISTORY_FILE,
    PVT_HASHES_FILE,
    PUBLIC_STATE_FILE,
    TXIDS_FILE,
)

_LEN = struct.Struct(">I")


class SnapshotError(Exception):
    pass


class SnapshotExistsError(SnapshotError):
    """A snapshot for this (channel, height) already exists on disk —
    benign for the background auto-trigger: two requests satisfied by
    the same commit group both export at the same durable height, and
    the loser's request is answered by the winner's snapshot."""


# -- record files ------------------------------------------------------------
#
# All .data files share one trivially deterministic format: a sequence of
# length-prefixed (key, value) byte-string pairs in the order the source
# store iterates them (lexicographic key order everywhere).


def _write_record(f, k: bytes, v: bytes) -> None:
    f.write(_LEN.pack(len(k)))
    f.write(k)
    f.write(_LEN.pack(len(v)))
    f.write(v)


def write_records(path: str, records) -> tuple[int, int]:
    """Write (key, value) pairs; returns (record_count, byte_count)."""
    count = size = 0
    with open(path, "wb") as f:
        for k, v in records:
            _write_record(f, k, v)
            count += 1
            size += 8 + len(k) + len(v)
    return count, size


def read_records(path: str):
    """Yield the (key, value) pairs of a record file; raises
    SnapshotError on a truncated or malformed file."""
    with open(path, "rb") as f:
        while True:
            hdr = f.read(_LEN.size)
            if not hdr:
                return
            if len(hdr) < _LEN.size:
                raise SnapshotError(f"truncated record file {path!r}")
            (klen,) = _LEN.unpack(hdr)
            k = f.read(klen)
            vhdr = f.read(_LEN.size)
            if len(k) < klen or len(vhdr) < _LEN.size:
                raise SnapshotError(f"truncated record file {path!r}")
            (vlen,) = _LEN.unpack(vhdr)
            v = f.read(vlen)
            if len(v) < vlen:
                raise SnapshotError(f"truncated record file {path!r}")
            yield k, v


# -- request bookkeeping -----------------------------------------------------


class SnapshotRequestBookkeeper:
    """Durable pending snapshot requests (reference snapshot_mgmt.go
    snapshotRequestBookkeeper): one key per requested block number under
    the ledger's bookkeeping/<ledger>/snapshot-request namespace, so
    pending requests survive a peer restart."""

    def __init__(self, db):
        self._db = db

    @staticmethod
    def _key(block_number: int) -> bytes:
        return b"%016x" % block_number

    def submit(self, block_number: int) -> None:
        if self.has(block_number):
            raise SnapshotError(
                f"snapshot request for block {block_number} already pending"
            )
        self._db.put(self._key(block_number), b"")

    def cancel(self, block_number: int) -> None:
        if not self.has(block_number):
            raise SnapshotError(
                f"no pending snapshot request for block {block_number}"
            )
        self._db.delete(self._key(block_number))

    def has(self, block_number: int) -> bool:
        return self._db.get(self._key(block_number)) is not None

    def list_pending(self) -> list[int]:
        return [int(k, 16) for k, _ in self._db.iterate(b"", None)]


# -- generation --------------------------------------------------------------


def _metadata_path(snapshot_dir: str) -> str:
    return os.path.join(snapshot_dir, METADATA_FILE)


def load_metadata(snapshot_dir: str) -> dict:
    path = _metadata_path(snapshot_dir)
    if not os.path.isfile(path):
        raise SnapshotError(f"no snapshot metadata at {path!r}")
    with open(path, "rb") as f:
        return json.loads(f.read().decode("utf-8"))


def _hash_files(snapshot_dir: str, names, csp=None, metrics=None,
                channel: str = ""):
    """Per-file SHA-256 digests through the CSP hash_batch seam — ONE
    batched call covers every file.  When the csp package itself is
    unavailable (hosts without `cryptography`), the common.hashing seam
    produces the identical digests."""
    if csp is None:
        try:
            from fabric_tpu.csp.factory import get_default

            csp = get_default()
        except ImportError:
            csp = None
    blobs = []
    for name in names:
        path = os.path.join(snapshot_dir, name)
        if not os.path.isfile(path):
            raise SnapshotError(f"snapshot file {name!r} is missing")
        with open(path, "rb") as f:
            blobs.append(f.read())
    t0 = time.perf_counter()
    if csp is not None:
        digests = csp.hash_batch(blobs)
    else:
        from fabric_tpu.common.hashing import sha256_many

        digests = sha256_many(blobs)
    dt = time.perf_counter() - t0
    total = sum(len(b) for b in blobs)
    if metrics is not None:
        metrics.bytes_hashed.With("channel", channel).add(total)
        if dt > 0:
            metrics.hash_mb_per_s.With("channel", channel).set(
                total / dt / 1e6
            )
    return {name: d.hex() for name, d in zip(names, digests)}


def generate_snapshot(
    ledger, snapshots_root: str, csp=None, metrics=None
) -> str:
    """Export the ledger into <snapshots_root>/completed/<id>/<height-1>
    and return the snapshot directory.  Deterministic: same ledger state
    -> byte-identical files -> identical signable metadata.  The whole
    export runs under one trace span (per-stage progress lands as
    instant marks at the faultline stage points), so a trace shows
    whether an export overlapped or serialized behind the next commit."""
    with tracing.span(
        "snapshot.export", cat="stage",
        channel=getattr(ledger, "ledger_id", ""),
        block=max(0, getattr(ledger, "durable_height", ledger.height) - 1),
    ):
        return _generate_snapshot(ledger, snapshots_root, csp, metrics)


def _generate_snapshot(
    ledger, snapshots_root: str, csp=None, metrics=None
) -> str:
    if not snapshots_root:
        raise SnapshotError("ledger provider has no snapshots directory")
    # export the DURABLE height: under group commit the in-memory
    # height can run ahead of the last flushed fsync+txn boundary, and
    # only the flushed prefix is readable (and crash-safe) here
    height = getattr(ledger, "durable_height", ledger.height)
    if height == 0:
        raise SnapshotError("cannot snapshot an empty ledger")
    t0 = time.perf_counter()
    lid = ledger.ledger_id
    last_num = height - 1
    final_dir = os.path.join(snapshots_root, "completed", lid, str(last_num))
    if os.path.exists(final_dir):
        raise SnapshotExistsError(
            f"snapshot for {lid!r} at block {last_num} already exists"
        )
    work = os.path.join(snapshots_root, "in_progress", f"{lid}-{last_num}")
    if os.path.isdir(work):
        shutil.rmtree(work)  # a crashed previous attempt
    os.makedirs(work)

    store = ledger.block_store
    state: VersionedDB = ledger.state_db

    # state: ONE ordered pass routing each record to the public or
    # hashed-collection file; cleartext private namespaces are skipped
    # (the reference never exports them either — a restored peer
    # reconciles cleartext from collection peers).  The ns/key split is
    # heuristic (a public KEY may itself embed '\x00pvt\x00'-shaped
    # bytes — the statedb key encoding cannot distinguish that from a
    # collection namespace), so a pvt-classified record is only DROPPED
    # when its hashed counterpart exists: every genuinely-private
    # committed write also committed a hash-namespace entry
    # (txmgmt validate_and_prepare), while a look-alike public key has
    # none and must ride the public file.  Misrouting between the two
    # EXPORTED files is harmless — import re-writes raw records
    # verbatim from both.
    with open(os.path.join(work, PUBLIC_STATE_FILE), "wb") as pub_f, \
            open(os.path.join(work, PVT_HASHES_FILE), "wb") as hash_f:
        for raw_key, raw_val in state.export_records():
            ns, key = VersionedDB.split_state_key(raw_key)
            parts = ns.split("\x00")
            if len(parts) == 3 and parts[1] == "pvt":
                hashed_ns = f"{parts[0]}\x00hash\x00{parts[2]}"
                khash = key_hash(key).hex()
                if state.get_state(hashed_ns, khash) is not None:
                    continue  # confirmed cleartext private: never export
            out = hash_f if len(parts) == 3 and parts[1] == "hash" else pub_f
            _write_record(out, raw_key, raw_val)
    # export stage fault points (ROADMAP faultline gap): a crash at any
    # of these leaves only the in_progress/ staging directory — the
    # atomic-rename contract says completed/ never holds a partial
    # snapshot, which the faultfuzz oracle verifies
    faultline.point("snapshot.export.stage", stage="state", channel=lid)
    tracing.instant("snapshot.stage", stage="state", channel=lid)
    write_records(
        os.path.join(work, TXIDS_FILE),
        ((t.encode(), b"") for t in store.export_txids()),
    )
    faultline.point("snapshot.export.stage", stage="txids", channel=lid)
    tracing.instant("snapshot.stage", stage="txids", channel=lid)
    write_records(
        os.path.join(work, CONFIG_HISTORY_FILE),
        ledger.config_history.export_entries(),
    )
    faultline.point(
        "snapshot.export.stage", stage="confighistory", channel=lid
    )
    tracing.instant("snapshot.stage", stage="confighistory", channel=lid)
    cfg_raw = store.config_block_bytes()
    if cfg_raw is None:
        blk0 = store.get_block_by_number(0)
        if blk0 is None:
            raise SnapshotError(
                f"ledger {lid!r} has neither a config block nor block 0"
            )
        cfg_raw = blk0.SerializeToString()
    with open(os.path.join(work, CONFIG_BLOCK_FILE), "wb") as f:
        f.write(cfg_raw)
    faultline.point(
        "snapshot.export.stage", stage="config_block", channel=lid
    )
    tracing.instant("snapshot.stage", stage="config_block", channel=lid)

    files = _hash_files(work, DATA_FILES, csp, metrics, channel=lid)
    faultline.point("snapshot.export.stage", stage="hash", channel=lid)
    tracing.instant("snapshot.stage", stage="hash", channel=lid)
    last_blk = store.get_block_by_number(last_num)
    sp = state.savepoint()
    last_hash = getattr(ledger, "durable_block_hash", None)
    if last_hash is None:
        last_hash = store.last_block_hash
    meta = {
        "version": SNAPSHOT_FORMAT_VERSION,
        "channel_id": lid,
        "last_block_number": last_num,
        "last_block_hash": last_hash.hex(),
        # informational for external auditors signing/checking the
        # metadata against the source chain (the reference's signable
        # metadata carries it too); import does not consume it
        "previous_block_hash": (
            last_blk.header.previous_hash.hex() if last_blk is not None
            else ""
        ),
        "state_savepoint": [sp.block_num, sp.tx_num] if sp else None,
        "index_defs": {
            ns: sorted(state.indexes_for(ns))
            for ns in sorted(state.indexed_namespaces())
        },
        "files": files,
    }
    with open(_metadata_path(work), "wb") as f:
        # torn-manifest seam: a "torn" rule writes a strict prefix of
        # the signable metadata and crashes — verify_snapshot must then
        # refuse the staging directory (truncated JSON, missing digests)
        faultline.write(
            "snapshot.manifest", f,
            json.dumps(meta, sort_keys=True, indent=2).encode(),
            channel=lid,
        )

    faultline.point("snapshot.export.stage", stage="rename", channel=lid)
    tracing.instant("snapshot.stage", stage="rename", channel=lid)
    os.makedirs(os.path.dirname(final_dir), exist_ok=True)
    os.replace(work, final_dir)
    if metrics is not None:
        metrics.generation_duration.With("channel", lid).observe(
            time.perf_counter() - t0
        )
    return final_dir


# -- verification + import ---------------------------------------------------


def verify_snapshot(snapshot_dir: str, csp=None) -> dict:
    """Recompute every data file's digest (through hash_batch) and check
    it against the signable metadata; returns the metadata.  Raises
    SnapshotError on any mismatch or missing file."""
    meta = load_metadata(snapshot_dir)
    if meta.get("version") != SNAPSHOT_FORMAT_VERSION:
        raise SnapshotError(
            f"unsupported snapshot format version {meta.get('version')!r}"
        )
    expected = meta.get("files") or {}
    # a digest for EVERY data file must be present — otherwise editing
    # the metadata to drop an entry would exempt that file from the
    # tamper check entirely
    missing = [n for n in DATA_FILES if n not in expected]
    if missing:
        raise SnapshotError(
            "snapshot metadata lists no digest for: " + ", ".join(missing)
        )
    names = sorted(expected)
    actual = _hash_files(snapshot_dir, names, csp)
    bad = [n for n in names if actual[n] != expected[n]]
    if bad:
        raise SnapshotError(
            "snapshot file hash mismatch (tampered or corrupt): "
            + ", ".join(bad)
        )
    return meta


IMPORT_IN_PROGRESS = b"in_progress"
IMPORT_DONE = b"done"


def import_marker(kv: KVStore, ledger_id: str) -> bytes | None:
    """The channel's snapshot-import completion marker: None (never
    imported), IMPORT_IN_PROGRESS (a crashed half-import — the stores
    hold an arbitrary prefix of the snapshot and must NOT be served),
    or IMPORT_DONE."""
    return NamedDB(kv, f"snapimport/{ledger_id}").get(b"state")


def import_snapshot(
    meta: dict, snapshot_dir: str, store, kv: KVStore, ledger_id: str
) -> None:
    """Populate an EMPTY channel's stores from a verified snapshot:
    block-store bootstrap info + txid index, state DB (public + hashed,
    savepoint at the snapshot height so recovery replays nothing),
    config history, and the pvt store's bootstrap marker.  The caller
    then constructs the KVLedger over the same stores.

    Crash safety: an IMPORT_IN_PROGRESS marker lands FIRST and flips to
    IMPORT_DONE only after every store is populated — a crash anywhere
    between (the faultline stage points below inject exactly those)
    leaves the marker mid-flight, and LedgerProvider.open refuses to
    serve the half-imported channel instead of silently opening partial
    state."""
    marker = NamedDB(kv, f"snapimport/{ledger_id}")
    marker.put(b"state", IMPORT_IN_PROGRESS)
    last_num = int(meta["last_block_number"])
    with open(os.path.join(snapshot_dir, CONFIG_BLOCK_FILE), "rb") as f:
        cfg_raw = f.read()
    store.bootstrap(
        last_num, bytes.fromhex(meta["last_block_hash"]), config_block=cfg_raw
    )
    faultline.point(
        "snapshot.import.stage", stage="bootstrap", channel=ledger_id
    )
    store.import_snapshot_txids(
        k.decode() for k, _ in read_records(
            os.path.join(snapshot_dir, TXIDS_FILE)
        )
    )
    faultline.point(
        "snapshot.import.stage", stage="txids", channel=ledger_id
    )

    def state_records():
        yield from read_records(os.path.join(snapshot_dir, PUBLIC_STATE_FILE))
        yield from read_records(os.path.join(snapshot_dir, PVT_HASHES_FILE))

    sp = meta.get("state_savepoint")
    savepoint = Height(sp[0], sp[1]) if sp else Height(last_num, 0)
    state = VersionedDB(kv, f"statedb/{ledger_id}")
    state.import_records(state_records(), savepoint)
    faultline.point(
        "snapshot.import.stage", stage="state", channel=ledger_id
    )
    for ns, specs in (meta.get("index_defs") or {}).items():
        for spec in specs:
            state.define_index(ns, spec)
    ConfigHistoryMgr(kv, ledger_id).import_entries(
        read_records(os.path.join(snapshot_dir, CONFIG_HISTORY_FILE))
    )
    faultline.point(
        "snapshot.import.stage", stage="confighistory", channel=ledger_id
    )
    PvtDataStore(kv, ledger_id).init_bootstrap_height(last_num + 1)
    marker.put(b"state", IMPORT_DONE)


# -- manager -----------------------------------------------------------------


class SnapshotManager:
    """Per-ledger snapshot front end: request bookkeeping + commit-time
    auto-trigger + on-demand generation (reference snapshot_mgmt.go's
    snapshotMgr, owned by the kvledger)."""

    def __init__(self, ledger, snapshots_root: str | None, kv: KVStore,
                 csp=None, metrics=None):
        self._ledger = ledger
        self._root = snapshots_root
        self._csp = csp
        self.metrics = metrics
        self._requests = SnapshotRequestBookkeeper(
            BookkeepingProvider(kv).get_kv(ledger.ledger_id, SNAPSHOT_REQUEST)
        )
        # watched under FABRIC_TPU_LOCKWATCH: canonical order is
        # ledger.commit_lock FIRST, then this manager lock
        self._lock = named_lock("snapshot.manager")
        # background auto-trigger generations in flight (wait_idle),
        # plus a spawn/ack handshake: _spawn_seq counts generations
        # handed to background threads, _ack_seq counts those that have
        # ACQUIRED the ledger commit lock — commits wait for the two to
        # match so a pinned export runs before state advances past its
        # height (the reference blocks commits during generation too)
        self._idle = named_condition("snapshot.idle")
        self._inflight = 0
        self._spawn_seq = 0
        self._ack_seq = 0
        # in-memory mirror of the durable pending-request set: the
        # per-block boundary-hint probe on the commit hot path must not
        # pay a KV get
        self._pending = set(self._requests.list_pending())
        self._update_gauge()

    # -- requests ----------------------------------------------------------

    def _update_gauge(self) -> None:
        if self.metrics is not None:
            self.metrics.pending_requests.With(
                "channel", self._ledger.ledger_id
            ).set(len(self._requests.list_pending()))

    def submit_request(self, block_number: int = 0) -> dict:
        """Request a snapshot at `block_number` (0 = the last committed
        block, generated immediately).  A request at the last committed
        block also generates immediately; a future block is recorded and
        auto-triggers when the ledger commits it (reference
        SubmitSnapshotRequest semantics).

        Lock order everywhere is ledger.commit_lock -> manager lock (the
        commit-time trigger enters with commit_lock already held), so an
        RPC-thread generate can never deadlock against a commit — and
        the export always sees a fully committed block, never a torn
        one."""
        with self._ledger.commit_lock:
            with self._lock:
                # anchor on the DURABLE height: an open commit group's
                # buffered tail is neither readable nor crash-safe, so
                # "the last committed block" means the watermark
                last = getattr(
                    self._ledger, "durable_height", self._ledger.height
                ) - 1
                if block_number == 0:
                    if last < 0:
                        raise SnapshotError("ledger has no committed blocks")
                    block_number = last
                if block_number < last:
                    raise SnapshotError(
                        f"requested block {block_number} is already "
                        f"committed (last committed block is {last})"
                    )
                if block_number == last:
                    path = self._generate()
                    return {
                        "block_number": block_number, "snapshot_dir": path
                    }
                if block_number < self._ledger.height:
                    # already buffered in an OPEN commit group: the
                    # stream's flush-at-requested-height hint for this
                    # block has passed, so the export could only run at
                    # the group's (later) flush height — silently
                    # exporting at the wrong height would break the
                    # deterministic-height guarantee, so refuse instead
                    raise SnapshotError(
                        f"requested block {block_number} is already "
                        f"buffered in an open commit group (last durable "
                        f"block is {last}); request block 0 for the last "
                        f"durable block, or a block >= "
                        f"{self._ledger.height}"
                    )
                self._requests.submit(block_number)
                self._pending.add(block_number)
                self._update_gauge()
                return {"block_number": block_number, "snapshot_dir": None}

    def cancel_request(self, block_number: int) -> None:
        with self._lock:
            self._requests.cancel(block_number)
            self._pending.discard(block_number)
            self._update_gauge()

    def has_pending_request(self, block_number: int) -> bool:
        """O(1) in-memory probe — the commit path's per-block
        boundary-hint check."""
        return block_number in self._pending

    def list_pending(self) -> list[int]:
        return self._requests.list_pending()

    # -- generation --------------------------------------------------------

    def on_block_committed(self, block_number: int) -> None:
        """KVLedger's group flush calls this for each block made durable
        (commit_lock held); a matching pending request hands generation
        to a BACKGROUND thread — the commit thread only dequeues the
        request, so the export no longer runs inline on the committer
        (the reference generates in a background goroutine the same
        way).  Height determinism is preserved by three pieces: the
        streaming committer flushes AT a requested block (CommitGroup.
        boundary_hint), submit_request refuses heights already buffered
        in an open group (whose hint has passed), and
        wait_generation_turn makes the next commit wait until the
        export thread holds the commit lock — so the snapshot is taken
        at exactly the requested height, as the synchronous path
        guaranteed (and peers generating from the same request agree
        byte-for-byte).  A generation failure is logged
        and the request dropped — the commit itself must never fail
        because a snapshot could not be written (reference logs and
        continues the same way).  Tests and operators can wait_idle()
        for the export to finish."""
        with self._lock:
            guarded(self, "_pending", by="snapshot.manager")
            if not self._requests.has(block_number):
                return
            self._requests.cancel(block_number)
            self._pending.discard(block_number)
            self._update_gauge()
        with self._idle:
            guarded(self, "_spawn_seq", by="snapshot.idle")
            self._inflight += 1
            self._spawn_seq += 1
        spawn_thread(
            target=self._bg_generate, args=(block_number,),
            name=f"snapshot-gen-{self._ledger.ledger_id}", kind="worker",
        ).start()

    def wait_generation_turn(self, timeout: float = 30.0) -> None:
        """Block until every spawned background generation has acquired
        the ledger commit lock.  KVLedger calls this at each commit/
        flush entry (BEFORE taking the commit lock itself), so an export
        pinned to the triggering flush's height always runs before state
        can advance past it — the export height is deterministic, not a
        race.  Times out rather than wedging commits if a generation
        thread dies before acquiring."""
        deadline = time.monotonic() + timeout
        with self._idle:
            while self._ack_seq < self._spawn_seq:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return
                self._idle.wait(remaining)

    def _bg_generate(self, block_number: int) -> None:
        try:
            with self._ledger.commit_lock:
                with self._idle:
                    self._ack_seq += 1
                    self._idle.notify_all()
                with self._lock:
                    self._generate()
        except SnapshotExistsError:
            # several requests satisfied by one commit group race to
            # export the same durable height: the winner's snapshot
            # answers every one of them
            pass
        except Exception as exc:
            from fabric_tpu.common.flogging import must_get_logger

            must_get_logger("ledger.snapshot").warning(
                "snapshot generation at block %d failed for %r: %s",
                block_number, self._ledger.ledger_id, exc,
            )
        finally:
            with self._idle:
                self._inflight -= 1
                self._idle.notify_all()

    def wait_idle(self, timeout: float = 30.0) -> bool:
        """Block until no background auto-trigger generation is in
        flight; False on timeout."""
        deadline = time.monotonic() + timeout
        with self._idle:
            while self._inflight:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._idle.wait(remaining)
        return True

    def generate(self) -> str:
        """Generate a snapshot at the current committed height."""
        with self._ledger.commit_lock:
            with self._lock:
                return self._generate()

    def _generate(self) -> str:
        return generate_snapshot(
            self._ledger, self._root, csp=self._csp, metrics=self.metrics
        )


# -- snapshot serving (remote fetch) ------------------------------------------
#
# join_by_snapshot used to require the snapshot directory on SHARED disk.
# These helpers stream a COMPLETED snapshot directory over any frame
# transport (the peer's admin.SnapshotFetch RPC): each frame is a JSON
# header line (file name + eof marker) followed by a raw chunk.  The
# receiver rebuilds the directory; integrity needs no transport trust —
# verify-on-import recomputes every file digest, so a torn or tampered
# stream is refused at join time (pinned by the torn-stream test via the
# snapshot.fetch.chunk faultline seam).

FETCH_CHUNK = 1 << 20


def completed_snapshot_dir(snapshots_root: str, ledger_id: str,
                           block_number: int) -> str:
    """The canonical completed/<lid>/<height> path; raises when absent."""
    path = os.path.join(
        snapshots_root, "completed", ledger_id, str(int(block_number))
    )
    if not os.path.isdir(path):
        raise SnapshotError(
            f"no completed snapshot for {ledger_id!r} at height "
            f"{block_number}"
        )
    return path


def list_completed(snapshots_root: str, ledger_id: str) -> list[int]:
    """Completed snapshot heights for a channel, ascending."""
    ldir = os.path.join(snapshots_root, "completed", ledger_id)
    if not os.path.isdir(ldir):
        return []
    return sorted(int(h) for h in os.listdir(ldir) if h.isdigit())


def stream_snapshot_dir(snapshot_dir: str):
    """Yield the frames of a completed snapshot directory: per chunk, a
    JSON header line + raw bytes.  The first frame is the manifest."""
    names = sorted(
        n for n in os.listdir(snapshot_dir)
        if os.path.isfile(os.path.join(snapshot_dir, n))
    )
    yield json.dumps(
        {"manifest": names, "snapshot": os.path.basename(snapshot_dir)},
        sort_keys=True,
    ).encode() + b"\n"
    for name in names:
        path = os.path.join(snapshot_dir, name)
        index = 0
        with open(path, "rb") as f:
            while True:
                chunk = f.read(FETCH_CHUNK)
                eof = len(chunk) < FETCH_CHUNK
                # torn-stream seam: an armed plan raising here cuts the
                # transfer mid-file; the receiver is left with a partial
                # directory that verify-on-import must refuse
                faultline.point(
                    "snapshot.fetch.chunk", file=name, index=index
                )
                header = json.dumps(
                    {"name": name, "eof": eof}, sort_keys=True
                ).encode() + b"\n"
                yield header + chunk
                index += 1
                if eof:
                    break


def receive_snapshot_stream(frames, dest_dir: str) -> str:
    """Rebuild a streamed snapshot directory under ``dest_dir``; returns
    the directory holding the received files.  Verification is the
    CALLER's job (create_from_snapshot / verify_snapshot) — a transport
    error mid-stream leaves a partial directory those refuse."""
    os.makedirs(dest_dir, exist_ok=True)
    open_files: dict[str, object] = {}
    try:
        it = iter(frames)
        first = next(it, None)
        if first is None:
            raise SnapshotError("empty snapshot stream")
        manifest = json.loads(first.split(b"\n", 1)[0].decode("utf-8"))
        if "manifest" not in manifest:
            raise SnapshotError("snapshot stream missing its manifest")
        for frame in it:
            header_line, chunk = frame.split(b"\n", 1)
            header = json.loads(header_line.decode("utf-8"))
            name = os.path.basename(header["name"])  # no path escapes
            f = open_files.get(name)
            if f is None:
                f = open_files[name] = open(
                    os.path.join(dest_dir, name), "wb"
                )
            f.write(chunk)
            if header.get("eof"):
                open_files.pop(name).close()
    finally:
        for f in open_files.values():
            f.close()
    return dest_dir


def fetch_snapshot(client, channel_id: str, block_number: int,
                   dest_dir: str) -> str:
    """Client half of ``admin.SnapshotFetch``: stream a remote peer's
    completed snapshot into ``dest_dir`` (``client`` is an RPCClient —
    or anything with .stream(method, body))."""
    body = json.dumps(
        {"channel": channel_id, "block_number": int(block_number)},
        sort_keys=True,
    ).encode()
    return receive_snapshot_stream(
        client.stream("admin.SnapshotFetch", body), dest_dir
    )


__all__ = [
    "SnapshotError",
    "SnapshotExistsError",
    "SnapshotManager",
    "SnapshotRequestBookkeeper",
    "generate_snapshot",
    "verify_snapshot",
    "import_snapshot",
    "import_marker",
    "IMPORT_IN_PROGRESS",
    "IMPORT_DONE",
    "load_metadata",
    "read_records",
    "write_records",
    "METADATA_FILE",
    "PUBLIC_STATE_FILE",
    "PVT_HASHES_FILE",
    "TXIDS_FILE",
    "CONFIG_HISTORY_FILE",
    "CONFIG_BLOCK_FILE",
    "DATA_FILES",
    "SNAPSHOT_FORMAT_VERSION",
    "completed_snapshot_dir",
    "list_completed",
    "stream_snapshot_dir",
    "receive_snapshot_stream",
    "fetch_snapshot",
    "FETCH_CHUNK",
]
