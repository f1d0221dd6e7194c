"""Private-data subsystem tests.

Coverage mirrors the reference's gossip/privdata + core/transientstore +
core/ledger/pvtdatastorage test strategy: store semantics (persist/purge,
BTL expiry, missing-data tracking), collection eligibility, and the
distribute -> transient -> coordinator -> commit -> reconcile loop across
two in-proc gossip peers.
"""

import hashlib

from fabric_tpu.common.privdata import (
    CollectionStore,
    collection_package,
    static_collection,
)
from fabric_tpu.gossip.comm import InProcGossipComm, InProcGossipNet
from fabric_tpu.gossip.privdata import (
    PrivDataCoordinator,
    PrivDataDistributor,
    PrivDataHandler,
    Reconciler,
    assemble_tx_pvt,
    block_pvt_requirements,
)
from fabric_tpu.ledger.kvstore import MemKVStore
from fabric_tpu.ledger.pvtdatastorage import PvtDataStore
from fabric_tpu.ledger.transientstore import TransientStore
from fabric_tpu.protos.ledger.rwset import rwset_pb2
from fabric_tpu.protos.ledger.rwset.kvrwset import kv_rwset_pb2


def _kvrw(writes: dict[str, bytes]) -> bytes:
    kv = kv_rwset_pb2.KVRWSet()
    for k, v in sorted(writes.items()):
        kv.writes.append(kv_rwset_pb2.KVWrite(key=k, value=v))
    return kv.SerializeToString()


class FakeDeserializer:
    """Maps serialized identity b'id:<msp>' -> object with mspid; principal
    check passes when msp ids match (stand-in for the MSP manager)."""

    class _Ident:
        def __init__(self, mspid):
            self.mspid = mspid

    def deserialize_identity(self, serialized: bytes):
        return self._Ident(serialized.decode().split(":", 1)[1])

    def satisfies_principal(self, ident, principal) -> None:
        from fabric_tpu.protos.msp import msp_principal_pb2

        role = msp_principal_pb2.MSPRole.FromString(principal.principal)
        if role.msp_identifier != ident.mspid:
            raise ValueError("wrong msp")


def _collection_store() -> CollectionStore:
    cs = CollectionStore(FakeDeserializer())
    cs.set_collections(
        "mycc",
        collection_package(
            static_collection("collA", ["Org1"], required_peer_count=0,
                              maximum_peer_count=3, block_to_live=2),
            static_collection("collB", ["Org2"]),
        ).SerializeToString(),
    )
    return cs


class TestTransientStore:
    def test_persist_get_purge(self):
        ts = TransientStore(MemKVStore(), "ch")
        ts.persist("tx1", 5, b"payload-a")
        ts.persist("tx1", 6, b"payload-b")
        ts.persist("tx2", 7, b"payload-c")
        got = ts.get_tx_pvt_rwsets("tx1")
        assert sorted(h for h, _ in got) == [5, 6]
        ts.purge_by_txids(["tx1"])
        assert ts.get_tx_pvt_rwsets("tx1") == []
        assert ts.min_height() == 7
        ts.purge_below_height(8)
        assert ts.min_height() is None


class TestPvtDataStore:
    def test_commit_query_and_btl_expiry(self):
        btl = lambda ns, coll: 2 if coll == "collA" else 0
        ps = PvtDataStore(MemKVStore(), "ch", btl_policy=btl)
        pvt = assemble_tx_pvt(
            {("mycc", "collA"): _kvrw({"k": b"v"}),
             ("mycc", "collB"): _kvrw({"x": b"y"})}
        )
        ps.commit(1, {0: pvt})
        assert 0 in ps.get_pvt_data_by_block(1)
        # BTL=2 -> expires when block 1+2+1=4 commits.
        ps.commit(2, {}); ps.commit(3, {})
        assert b"collA" in ps.get_pvt_data_by_block(1)[0]
        ps.commit(4, {})
        remaining = ps.get_pvt_data_by_block(1)[0]
        assert b"collA" not in remaining and b"collB" in remaining

    def test_missing_tracking_and_resolve(self):
        ps = PvtDataStore(MemKVStore(), "ch")
        ps.commit(1, {}, missing=[(0, "mycc", "collA")])
        assert ps.get_missing() == [(1, 0, "mycc", "collA")]
        ps.resolve_missing(
            1, 0, assemble_tx_pvt({("mycc", "collA"): _kvrw({"k": b"v"})})
        )
        assert ps.get_missing() == []
        assert b"collA" in ps.get_pvt_data_by_block(1)[0]


class TestCollectionStore:
    def test_eligibility(self):
        cs = _collection_store()
        assert cs.is_eligible("mycc", "collA", b"id:Org1")
        assert not cs.is_eligible("mycc", "collA", b"id:Org2")
        assert cs.is_eligible("mycc", "collB", b"id:Org2")
        assert not cs.is_eligible("mycc", "nope", b"id:Org1")
        assert cs.btl_policy()("mycc", "collA") == 2
        assert cs.collection("mycc", "collA").member_orgs() == ["Org1"]


class _FakeValidator:
    channel_id = "ch"

    def validate(self, block):
        return list(block.metadata.metadata[2]) if block.metadata.metadata else []


class _FakeLedger:
    """Ledger stand-in with a real PvtDataStore (the coordinator and
    reconciler contract: commit(block, pvt, missing, assist), pvt_store,
    get_block_by_number, commit_old_pvt_data)."""

    def __init__(self, btl_policy=None):
        self.committed = []
        self.height = 0
        self.blocks = {}
        self.pvt_store = PvtDataStore(MemKVStore(), "ch", btl_policy)

    def commit(self, block, pvt_data=None, missing_pvt=None, assist=None):
        self.committed.append((block.header.number, dict(pvt_data or {})))
        self.blocks[block.header.number] = block
        self.pvt_store.commit(
            block.header.number, pvt_data or {}, missing_pvt
        )
        self.height = block.header.number + 1

    def get_block_by_number(self, num):
        return self.blocks.get(num)

    def commit_old_pvt_data(self, block_num, tx_num, pvt_bytes):
        self.pvt_store.resolve_missing(block_num, tx_num, pvt_bytes)


def _block_with_pvt_tx(txid: str, colls: dict[tuple[str, str], bytes]):
    """Build a minimal block whose single tx carries hashed rwsets
    matching `colls`."""
    from fabric_tpu import protoutil
    from fabric_tpu.protos.common import common_pb2
    from fabric_tpu.protos.peer import proposal_response_pb2, transaction_pb2
    from fabric_tpu.protos.peer import proposal_pb2

    txrw = rwset_pb2.TxReadWriteSet(data_model=rwset_pb2.TxReadWriteSet.KV)
    by_ns = {}
    for (ns, coll), raw in colls.items():
        by_ns.setdefault(ns, []).append((coll, raw))
    for ns, items in sorted(by_ns.items()):
        nsrw = txrw.ns_rwset.add()
        nsrw.namespace = ns
        nsrw.rwset = kv_rwset_pb2.KVRWSet().SerializeToString()
        for coll, raw in sorted(items):
            ch = nsrw.collection_hashed_rwset.add()
            ch.collection_name = coll
            ch.hashed_rwset = kv_rwset_pb2.HashedRWSet().SerializeToString()
            ch.pvt_rwset_hash = hashlib.sha256(raw).digest()

    ccp = proposal_pb2.ChaincodeAction(results=txrw.SerializeToString())
    prp = proposal_response_pb2.ProposalResponsePayload(
        extension=ccp.SerializeToString()
    )
    cap = transaction_pb2.ChaincodeActionPayload()
    cap.action.proposal_response_payload = prp.SerializeToString()
    tx = transaction_pb2.Transaction()
    ta = tx.actions.add()
    ta.payload = cap.SerializeToString()
    chdr = common_pb2.ChannelHeader(
        type=common_pb2.ENDORSER_TRANSACTION, channel_id="ch", tx_id=txid
    )
    payload = common_pb2.Payload(
        header=common_pb2.Header(
            channel_header=chdr.SerializeToString(),
            signature_header=common_pb2.SignatureHeader().SerializeToString(),
        ),
        data=tx.SerializeToString(),
    )
    env = common_pb2.Envelope(payload=payload.SerializeToString())
    block = common_pb2.Block()
    block.header.number = 1
    block.data.data.append(env.SerializeToString())
    protoutil.set_tx_filter(block, [0])
    return block


class TestEndToEndFlow:
    def _make_peer(self, net, name, mspid):
        ident = f"id:{mspid}".encode()
        comm = InProcGossipComm(name, net, ident)
        kv = MemKVStore()
        cs = _collection_store()
        ts = TransientStore(kv, "ch")
        ledger = _FakeLedger(btl_policy=cs.btl_policy())
        handler = PrivDataHandler(comm, ts, ledger.pvt_store, cs, lambda: 10)
        return dict(comm=comm, ident=ident, cs=cs, ts=ts,
                    ledger=ledger, ps=ledger.pvt_store, handler=handler)

    def test_distribute_coordinate_fetch(self):
        net = InProcGossipNet()
        p1 = self._make_peer(net, "p1", "Org1")  # endorser, eligible
        p2 = self._make_peer(net, "p2", "Org1")  # committer, eligible
        p3 = self._make_peer(net, "p3", "Org2")  # not eligible for collA

        raw = _kvrw({"k": b"secret"})
        pvt = assemble_tx_pvt({("mycc", "collA"): raw})
        membership = lambda: [("p2", p2["ident"]), ("p3", p3["ident"])]
        dist = PrivDataDistributor(p1["comm"], p1["cs"], membership)
        sent = dist.distribute("ch", "tx-1", 1, pvt)
        assert sent[("mycc", "collA")] == 1  # only p2 eligible
        # Push landed in p2's transient store.
        assert p2["ts"].get_tx_pvt_rwsets("tx-1")

        # p2 commits the block: data comes from its transient store.
        block = _block_with_pvt_tx("tx-1", {("mycc", "collA"): raw})
        coord2 = PrivDataCoordinator(
            _FakeValidator(), p2["ledger"], p2["ts"], p2["cs"],
            p2["ident"], fetcher=p2["handler"], fetch_endpoints=lambda: [],
        )
        coord2.store_block(block)
        _, pvt_committed = p2["ledger"].committed[0]
        assert 0 in pvt_committed
        assert b"secret" in pvt_committed[0]
        assert p2["ps"].get_missing() == []
        # Transient purged after commit.
        assert p2["ts"].get_tx_pvt_rwsets("tx-1") == []

        # p3 (ineligible): commits without the data, nothing missing.
        coord3 = PrivDataCoordinator(
            _FakeValidator(), p3["ledger"], p3["ts"], p3["cs"],
            p3["ident"], fetcher=p3["handler"], fetch_endpoints=lambda: [],
        )
        coord3.store_block(_block_with_pvt_tx("tx-1", {("mycc", "collA"): raw}))
        assert p3["ledger"].committed[0][1] == {}
        assert p3["ps"].get_missing() == []

        # p4: eligible but never got the push — fetches from p2 at commit.
        p4 = self._make_peer(net, "p4", "Org1")
        coord4 = PrivDataCoordinator(
            _FakeValidator(), p4["ledger"], p4["ts"], p4["cs"],
            p4["ident"], fetcher=p4["handler"],
            fetch_endpoints=lambda: ["p2"],
        )
        coord4.store_block(_block_with_pvt_tx("tx-1", {("mycc", "collA"): raw}))
        assert 0 in p4["ledger"].committed[0][1]
        assert b"secret" in p4["ledger"].committed[0][1][0]

        # p5: eligible, no data, no reachable peers -> recorded missing,
        # then reconciled once p2 is reachable.
        p5 = self._make_peer(net, "p5", "Org1")
        coord5 = PrivDataCoordinator(
            _FakeValidator(), p5["ledger"], p5["ts"], p5["cs"],
            p5["ident"], fetcher=p5["handler"], fetch_endpoints=lambda: [],
        )
        coord5.store_block(_block_with_pvt_tx("tx-1", {("mycc", "collA"): raw}))
        assert p5["ps"].get_missing() == [(1, 0, "mycc", "collA")]
        rec = Reconciler(
            p5["ledger"], p5["handler"], "ch", lambda: ["p2"]
        )
        assert rec.reconcile_once() == 1
        assert p5["ps"].get_missing() == []
        assert b"secret" in p5["ps"].get_pvt_data_by_block(1)[0]

        # Confidentiality: an INELIGIBLE peer (Org2) asking p2 for collA
        # must get nothing back, even though p2 holds the data.
        stolen = p3["handler"].fetch(
            "ch", 1, [("tx-1", "mycc", "collA")], ["p2"], timeout_s=0.3
        )
        assert stolen == {}


def test_block_pvt_requirements_extraction():
    raw = _kvrw({"k": b"v"})
    block = _block_with_pvt_tx("tx-9", {("mycc", "collA"): raw})
    reqs = block_pvt_requirements(block)
    assert list(reqs) == [0]
    txid, needed = reqs[0]
    assert txid == "tx-9"
    assert needed == {("mycc", "collA"): hashlib.sha256(raw).digest()}
