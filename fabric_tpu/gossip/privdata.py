"""Private-data gossip flows: distribute, fetch, coordinate.

Reference package gossip/privdata:
  distributor.go:138  DistributePrivateData — endorsement-time push of
                      cleartext collection rwsets to eligible peers
  pull.go / fetcher.go — commit-time pull of missing collection rwsets
  coordinator.go:149  StoreBlock — validate, assemble private data
                      (transient store first, then pull), commit, purge
  reconcile.go        — background fetch of data missed at commit time

All flows ride the existing gossip comm layer using the wire messages
PrivateDataMessage / PrivateDataRequest / PrivateDataResponse
(fabric_tpu/protos/gossip/message.proto).
"""

from __future__ import annotations

import threading
import time

from fabric_tpu.common import gcpolicy
from fabric_tpu.common.hashing import sha256 as _sha256
from fabric_tpu.peer.committer import validate_for_commit
from fabric_tpu.protos.common import common_pb2
from fabric_tpu.protos.gossip import message_pb2 as gpb
from fabric_tpu.protos.ledger.rwset import rwset_pb2
from fabric_tpu import protoutil


def _collection_rwsets(pvt_bytes: bytes):
    """Yield (ns, coll, raw_kvrwset) triples from a TxPvtReadWriteSet."""
    txpvt = rwset_pb2.TxPvtReadWriteSet.FromString(pvt_bytes)
    for nsp in txpvt.ns_pvt_rwset:
        for cp in nsp.collection_pvt_rwset:
            yield nsp.namespace, cp.collection_name, bytes(cp.rwset)


def assemble_tx_pvt(colls: dict[tuple[str, str], bytes]) -> bytes | None:
    """Inverse of _collection_rwsets: {(ns, coll): raw} -> serialized
    TxPvtReadWriteSet."""
    if not colls:
        return None
    txpvt = rwset_pb2.TxPvtReadWriteSet(data_model=rwset_pb2.TxReadWriteSet.KV)
    by_ns: dict[str, dict[str, bytes]] = {}
    for (ns, coll), raw in colls.items():
        by_ns.setdefault(ns, {})[coll] = raw
    for ns in sorted(by_ns):
        nsp = txpvt.ns_pvt_rwset.add()
        nsp.namespace = ns
        for coll in sorted(by_ns[ns]):
            cp = nsp.collection_pvt_rwset.add()
            cp.collection_name = coll
            cp.rwset = by_ns[ns][coll]
    return txpvt.SerializeToString()


def block_pvt_requirements(block: common_pb2.Block):
    """Per-tx private-data requirements from the public hashed rwsets:
    {tx_num: (txid, {(ns, coll): expected_hash})}."""
    from fabric_tpu.ledger.kvledger import extract_rwsets

    out: dict[int, tuple[str, dict[tuple[str, str], bytes]]] = {}
    rwsets = extract_rwsets(block)
    for tx_num, raw in enumerate(rwsets):
        if raw is None:
            continue
        try:
            env = protoutil.extract_envelope(block, tx_num)
            payload = common_pb2.Payload.FromString(env.payload)
            chdr = common_pb2.ChannelHeader.FromString(
                payload.header.channel_header
            )
            txid = chdr.tx_id
            txrw = rwset_pb2.TxReadWriteSet.FromString(raw)
        except Exception:
            continue
        needed: dict[tuple[str, str], bytes] = {}
        for nsrw in txrw.ns_rwset:
            for ch in nsrw.collection_hashed_rwset:
                needed[(nsrw.namespace, ch.collection_name)] = bytes(
                    ch.pvt_rwset_hash
                )
        if needed:
            out[tx_num] = (txid, needed)
    return out


class PrivDataDistributor:
    """Endorsement-time push (reference distributor.go:138): send each
    collection's cleartext rwset to up to maximum_peer_count eligible
    peers (best effort beyond required_peer_count)."""

    def __init__(self, comm, collection_store, membership):
        """membership() -> [(endpoint, serialized_identity)]."""
        self._comm = comm
        self._collections = collection_store
        self._membership = membership

    def distribute(
        self, channel: str, txid: str, block_seq: int, pvt_bytes: bytes
    ) -> dict[tuple[str, str], int]:
        """Returns {(ns, coll): n_peers_sent}; raises if a collection's
        required_peer_count cannot be met (the reference fails the
        endorsement in that case)."""
        sent: dict[tuple[str, str], int] = {}
        for ns, coll, raw in _collection_rwsets(pvt_bytes):
            conf = self._collections.collection(ns, coll)
            eligible = [
                ep
                for ep, ident in self._membership()
                if conf.is_member(ident)
            ]
            targets = eligible[: max(conf.maximum_peer_count, 0)]
            if len(targets) < conf.required_peer_count:
                raise RuntimeError(
                    f"collection {ns}/{coll}: only {len(targets)} eligible "
                    f"peers, need {conf.required_peer_count}"
                )
            msg = gpb.GossipMessage(
                channel=channel.encode(),
                private_data=gpb.PrivateDataMessage(
                    channel=channel,
                    tx_id=txid,
                    namespace=ns,
                    collection=coll,
                    block_seq=block_seq,
                    rwset=raw,
                ),
            )
            for ep in targets:
                self._comm.send(ep, msg)
            sent[(ns, coll)] = len(targets)
        return sent


class PrivDataHandler:
    """Receives pushes into the transient store and serves pull requests
    from local stores (reference gossip/privdata pull.go handlers)."""

    def __init__(self, comm, transient_store, pvtdata_store,
                 collection_store, ledger_height, channel: str | None = None):
        """`channel`: when set, pushes and pull requests for OTHER
        channels are ignored — a node serving several channels mounts
        one handler per channel on the shared comm, and each must only
        touch its own transient/pvt stores."""
        self._comm = comm
        self._transient = transient_store
        self._pvtstore = pvtdata_store
        self._collections = collection_store
        self._height = ledger_height  # callable -> int
        self._channel = channel
        self._pending: list[tuple[dict, threading.Event, set]] = []
        self._lock = threading.Lock()
        comm.subscribe(self._on_message)

    # -- inbound -----------------------------------------------------------

    def _on_message(self, rm) -> None:
        msg = rm.msg
        which = msg.WhichOneof("content")
        if self._channel is not None:
            if which == "private_data":
                ch = msg.private_data.channel
            elif which == "private_req":
                ch = msg.private_req.channel
            elif which == "private_res":
                # responses carry the channel on the outer message
                # (_serve echoes req.channel there)
                ch = bytes(msg.channel).decode("utf-8", "replace")
            else:
                ch = None
            if ch is not None and ch != self._channel:
                return
        if which == "private_data":
            pd = msg.private_data
            self._transient.persist(
                pd.tx_id,
                pd.block_seq,
                assemble_tx_pvt(
                    {(pd.namespace, pd.collection): bytes(pd.rwset)}
                ),
            )
        elif which == "private_req":
            self._serve(rm)
        elif which == "private_res":
            self._absorb_response(msg.private_res)

    def _serve(self, rm) -> None:
        """Serve a pull request — ONLY for collections the requester is
        eligible for (reference pull.go filters via the collection
        AccessFilter; without this check any gossip peer could exfiltrate
        cleartext private data)."""
        req = rm.msg.private_req
        requester = self._comm.identity_of(rm.sender_pki)
        res = gpb.PrivateDataResponse()
        for dig in req.digests:
            if requester is None or not self._collections.is_eligible(
                dig.namespace, dig.collection, requester
            ):
                continue
            raw = self._lookup(dig.tx_id, dig.namespace, dig.collection,
                               req.block_seq)
            if raw is None:
                continue
            el = res.elements.add()
            el.channel = req.channel
            el.tx_id = dig.tx_id
            el.namespace = dig.namespace
            el.collection = dig.collection
            el.block_seq = req.block_seq
            el.rwset = raw
        rm.respond(
            gpb.GossipMessage(
                channel=req.channel.encode(), private_res=res
            )
        )

    def _lookup(self, txid: str, ns: str, coll: str, block_seq: int):
        for _, pvt_bytes in self._transient.get_tx_pvt_rwsets(txid):
            for n, c, raw in _collection_rwsets(pvt_bytes):
                if (n, c) == (ns, coll):
                    return raw
        # Committed data: scan the block's stored pvt data for the txid.
        stored = self._pvtstore.get_pvt_data_by_block(block_seq)
        for raw_tx in stored.values():
            for n, c, raw in _collection_rwsets(raw_tx):
                if (n, c) == (ns, coll):
                    return raw
        return None

    def _absorb_response(self, res) -> None:
        with self._lock:
            for el in res.elements:
                key = (el.tx_id, el.namespace, el.collection)
                for results, event, wanted in self._pending:
                    if key in wanted and key not in results:
                        results[key] = bytes(el.rwset)
                        if set(results) >= wanted:
                            event.set()

    # -- outbound fetch ----------------------------------------------------

    def fetch(
        self,
        channel: str,
        block_seq: int,
        digests: list[tuple[str, str, str]],
        endpoints: list[str],
        timeout_s: float = 2.0,
    ) -> dict[tuple[str, str, str], bytes]:
        """Ask peers for [(txid, ns, coll)]; returns whatever arrived in
        time (reference fetcher.go fetch with per-peer retries)."""
        if not digests or not endpoints:
            return {}
        req = gpb.PrivateDataRequest(channel=channel, block_seq=block_seq)
        for txid, ns, coll in digests:
            d = req.digests.add()
            d.tx_id = txid
            d.namespace = ns
            d.collection = coll
        results: dict[tuple[str, str, str], bytes] = {}
        event = threading.Event()
        wanted = set(digests)
        entry = (results, event, wanted)
        with self._lock:
            self._pending.append(entry)
        try:
            msg = gpb.GossipMessage(
                channel=channel.encode(), private_req=req
            )
            deadline = time.monotonic() + timeout_s
            for ep in endpoints:
                self._comm.send(ep, msg)
                if event.wait(
                    min(0.5, max(0.0, deadline - time.monotonic()))
                ):
                    break
                if time.monotonic() >= deadline:
                    break
            return dict(results)
        finally:
            with self._lock:
                self._pending.remove(entry)


class PrivDataCoordinator:
    """The commit orchestrator (reference coordinator.go:149 StoreBlock):
    validate -> assemble private data -> commit -> purge."""

    def __init__(
        self,
        validator,
        ledger,
        transient_store,
        collection_store,
        self_identity: bytes,
        fetcher: PrivDataHandler | None = None,
        fetch_endpoints=None,  # callable -> [endpoint]
        transient_block_retention: int = 1000,
    ):
        self._validator = validator
        self._ledger = ledger
        self._transient = transient_store
        self._collections = collection_store
        self._self_identity = self_identity
        self._fetcher = fetcher
        self._fetch_endpoints = fetch_endpoints or (lambda: [])
        self._retention = transient_block_retention
        self._listeners: list = []
        self._lock = threading.Lock()

    def add_commit_listener(self, fn) -> None:
        self._listeners.append(fn)

    def set_fetcher(self, fetcher, fetch_endpoints) -> None:
        """Late-bind the gossip pull path (a node wires the coordinator
        at channel creation but gossip may come up afterwards)."""
        self._fetcher = fetcher
        self._fetch_endpoints = fetch_endpoints

    @property
    def height(self) -> int:
        return self._ledger.height

    def get_block_by_number(self, num: int):
        """Committed-block reader for gossip state transfer: a peer
        serving a state_request reads past the store's TTL window from
        the ledger (gossip/state.py _read_committed)."""
        return self._ledger.get_block_by_number(num)

    def store_block(self, block) -> list[int]:
        assist = validate_for_commit(self._validator, block)
        flags = list(protoutil.tx_filter(block))
        reqs = block_pvt_requirements(block)
        pvt_data: dict[int, bytes] = {}
        missing: list[tuple[int, str, str]] = []
        to_fetch: dict[int, list[tuple[str, str, str]]] = {}
        collected: dict[int, dict[tuple[str, str], bytes]] = {}
        txids: list[str] = []
        from fabric_tpu.ledger.txmgmt import VALID

        for tx_num, (txid, needed) in reqs.items():
            if flags[tx_num] != VALID:
                continue
            txids.append(txid)
            colls: dict[tuple[str, str], bytes] = {}
            for (ns, coll), expected in needed.items():
                if not self._collections.is_eligible(
                    ns, coll, self._self_identity
                ):
                    continue  # not our data: not "missing" either
                raw = self._from_transient(txid, ns, coll, expected)
                if raw is not None:
                    colls[(ns, coll)] = raw
                else:
                    to_fetch.setdefault(tx_num, []).append((txid, ns, coll))
            collected[tx_num] = colls

        if to_fetch and self._fetcher is not None:
            digests = [d for ds in to_fetch.values() for d in ds]
            fetched = self._fetcher.fetch(
                self._validator.channel_id,
                block.header.number,
                digests,
                self._fetch_endpoints(),
            )
            for tx_num, ds in to_fetch.items():
                _, needed = reqs[tx_num]
                for txid_, ns, coll in ds:
                    raw = fetched.get((txid_, ns, coll))
                    if raw is not None and self._hash_ok(
                        raw, needed[(ns, coll)]
                    ):
                        collected[tx_num][(ns, coll)] = raw

        for tx_num, (txid, needed) in reqs.items():
            if flags[tx_num] != VALID:
                continue
            colls = collected.get(tx_num, {})
            for (ns, coll) in needed:
                if (ns, coll) not in colls and self._collections.is_eligible(
                    ns, coll, self._self_identity
                ):
                    missing.append((tx_num, ns, coll))
            assembled = assemble_tx_pvt(colls)
            if assembled is not None:
                pvt_data[tx_num] = assembled

        with self._lock:
            # The ledger persists block + pvt data + missing records
            # together (kvledger owns the pvt store so restart recovery
            # replays cleartext writes).
            self._ledger.commit(block, pvt_data, missing, assist=assist)
        self._transient.purge_by_txids(txids)
        if block.header.number % self._retention == 0:
            floor = max(0, block.header.number - self._retention)
            self._transient.purge_below_height(floor)
        final_flags = list(protoutil.tx_filter(block))
        for fn in self._listeners:
            fn(block, final_flags)
        gcpolicy.pipeline_empty()  # as Committer.store_block
        return final_flags

    def _from_transient(self, txid, ns, coll, expected_hash):
        for _, pvt_bytes in self._transient.get_tx_pvt_rwsets(txid):
            for n, c, raw in _collection_rwsets(pvt_bytes):
                if (n, c) == (ns, coll) and self._hash_ok(raw, expected_hash):
                    return raw
        return None

    @staticmethod
    def _hash_ok(raw: bytes, expected: bytes) -> bool:
        # No endorsed hash -> no endorsed cleartext rwset: reject supply.
        return bool(expected) and _sha256(raw) == expected


class Reconciler:
    """Background repair of missing private data (reference
    reconcile.go): query the ledger's missing list, pull from peers,
    verify against the block's endorsed pvt hashes, commit as old-block
    private data (pvt store + non-stale state updates)."""

    def __init__(self, ledger, fetcher: PrivDataHandler,
                 channel: str, fetch_endpoints, batch_size: int = 10):
        self._ledger = ledger
        self._fetcher = fetcher
        self._channel = channel
        self._endpoints = fetch_endpoints
        self._batch = batch_size

    def reconcile_once(self) -> int:
        """Returns how many (block, tx, ns, coll) entries were repaired."""
        work = self._ledger.pvt_store.get_missing(max_blocks=self._batch)
        repaired = 0
        by_block: dict[int, list[tuple[int, str, str]]] = {}
        for block_num, tx, ns, coll in work:
            by_block.setdefault(block_num, []).append((tx, ns, coll))
        for block_num, entries in by_block.items():
            block = self._ledger.get_block_by_number(block_num)
            if block is None:
                continue
            reqs = block_pvt_requirements(block)
            digests = []
            expected: dict[tuple[int, str, str], tuple[str, bytes]] = {}
            for tx, ns, coll in entries:
                if tx not in reqs:
                    continue
                txid, needed = reqs[tx]
                exp = needed.get((ns, coll))
                if not exp:
                    continue
                digests.append((txid, ns, coll))
                expected[(tx, ns, coll)] = (txid, exp)
            if not digests:
                continue
            fetched = self._fetcher.fetch(
                self._channel, block_num, digests, self._endpoints()
            )
            for (tx, ns, coll), (txid, exp) in expected.items():
                raw = fetched.get((txid, ns, coll))
                if raw is None or _sha256(raw) != exp:
                    continue  # absent or forged: leave as missing
                self._ledger.commit_old_pvt_data(
                    block_num, tx, assemble_tx_pvt({(ns, coll): raw})
                )
                repaired += 1
        return repaired


__all__ = [
    "PrivDataDistributor",
    "PrivDataHandler",
    "PrivDataCoordinator",
    "Reconciler",
    "assemble_tx_pvt",
    "block_pvt_requirements",
]
