"""Commit-path v2 parity suite (ISSUE 9 tentpole): the parallel collect
and parallel MVCC prepare stages must be BYTE-IDENTICAL to their serial
counterparts — same flags, same _ItemSink item order and dedup indices,
same MVCC batch contents and namespace order — at every tested pool
width, and the batched recovery replay must reach exactly the state the
per-block replay reached.

Runs WITHOUT the `cryptography` package: a stdlib-only fake MSP/CSP
world (deterministic hash-derived keys and signatures) drives the real
TxValidator through both the native-assisted and pure-Python collect
paths, so the parity pins hold in minimal containers too."""

from __future__ import annotations

import pytest

from fabric_tpu import native, protoutil
from fabric_tpu.common import workpool
from fabric_tpu.common.hashing import sha256 as _sha256
from fabric_tpu.csp.api import VerifyBatchItem
from fabric_tpu.devtools import faultline, invariants, lockwatch
from fabric_tpu.ledger import LedgerProvider
from fabric_tpu.ledger.kvstore import MemKVStore
from fabric_tpu.ledger.statedb import Height, VersionedDB, VersionedValue
from fabric_tpu.ledger.txmgmt import (
    MVCCValidator,
    TxSimulator,
    VALID,
    MVCC_READ_CONFLICT,
)
from fabric_tpu.peer.committer import Committer
from fabric_tpu.peer.txvalidator import TxValidator
from fabric_tpu.protos.common import common_pb2
from fabric_tpu.protos.peer import (
    proposal_pb2,
    proposal_response_pb2,
    transaction_pb2,
)

V = transaction_pb2
CHANNEL = "ppch"


# -- stdlib-only fake crypto world -------------------------------------------


class _FakeKey:
    """Hash-derived public key with the .x/.y ints _ItemSink's dedup
    key and the device marshaling layer expect."""

    __slots__ = ("x", "y")

    def __init__(self, x: int, y: int):
        self.x = x
        self.y = y

    def __eq__(self, other):
        return (self.x, self.y) == (other.x, other.y)

    def __hash__(self):
        return hash((self.x, self.y))


def _key_of(ident_bytes: bytes) -> _FakeKey:
    h = _sha256(b"key:" + ident_bytes)
    return _FakeKey(
        int.from_bytes(h[:16], "big"), int.from_bytes(h[16:], "big")
    )


def _sign(ident_bytes: bytes, digest: bytes) -> bytes:
    k = _key_of(ident_bytes)
    return _sha256(b"sig:%d:%d:" % (k.x, k.y) + digest)


class _FakeIdentity:
    def __init__(self, raw: bytes):
        self.raw = raw
        self.public_key = _key_of(raw)

    def verification_item(self, msg: bytes, sig: bytes) -> VerifyBatchItem:
        return VerifyBatchItem(self.public_key, _sha256(msg), sig)


class _FakeMSPManager:
    """deserialize_identity/validate over raw identity bytes; bytes
    starting with b'badid' refuse to deserialize (the invalid-creator
    lane)."""

    def deserialize_identity(self, raw: bytes) -> _FakeIdentity:
        if bytes(raw).startswith(b"badid"):
            raise ValueError("unknown identity")
        return _FakeIdentity(bytes(raw))

    def validate(self, ident: _FakeIdentity) -> None:
        pass


class _FakePending:
    def __init__(self, items: list, k: int):
        self.items = items
        self._k = k

    def finish(self, mask) -> bool:
        return sum(bool(m) for m in mask) >= self._k


class _FakePolicy:
    """k-of-n endorsement policy with the SignaturePolicy two-phase
    interface (prepare -> pending.items / finish(mask))."""

    def __init__(self, k: int):
        self._k = k

    def prepare(self, signed) -> _FakePending:
        items = [
            VerifyBatchItem(
                _key_of(bytes(sd.identity)),
                sd.digest if sd.digest is not None else _sha256(sd.data),
                sd.signature,
            )
            for sd in signed
        ]
        return _FakePending(items, self._k)


class _FakePolicyManager:
    def __init__(self, k: int = 2):
        self._policy = _FakePolicy(k)

    def get_policy(self, name: str) -> _FakePolicy:
        return self._policy


class _FakeBundle:
    def __init__(self, k: int = 2):
        self.policy_manager = _FakePolicyManager(k)
        self.msp_manager = _FakeMSPManager()


class _FakeCSP:
    """Deterministic verify/hash backend: a signature is valid iff it is
    _sign(identity, digest) for the item's hash-derived key.  Records
    every verify batch so tests can compare _ItemSink contents (order +
    dedup) across collect configurations."""

    def __init__(self):
        self.batches: list[list[VerifyBatchItem]] = []

    def hash_batch(self, msgs):
        return [_sha256(m) for m in msgs]

    def _mask(self, items):
        return [
            bytes(it.signature)
            == _sha256(b"sig:%d:%d:" % (it.key.x, it.key.y) + bytes(it.digest))
            for it in items
        ]

    def verify_batch_async(self, items):
        items = list(items)
        self.batches.append(items)
        mask = self._mask(items)
        return lambda: mask

    def verify_batch(self, items):
        return self.verify_batch_async(items)()


_ENDORSERS = (b"end:org1", b"end:org2", b"end:org3")
_CREATORS = (b"cre:alice", b"cre:bob", b"cre:carol")


def _make_tx(
    key: str,
    value: bytes = b"v",
    cc: str = "ppcc",
    channel: str = CHANNEL,
    creator: bytes = _CREATORS[0],
    endorsers=_ENDORSERS,
    nonce: bytes | None = None,
    txid: str | None = None,
    tx_type: int = common_pb2.ENDORSER_TRANSACTION,
    bad_creator_sig: bool = False,
    tampered_endorsements: int = 0,
    rwset_override: bytes | None = None,
    bad_proposal_hash: bool = False,
    no_endorsements: bool = False,
) -> bytes:
    """One fully well-formed endorser envelope over the fake world, with
    targeted mutations for each failure stage."""
    if rwset_override is not None:
        rwset = rwset_override
    else:
        sim = TxSimulator(VersionedDB(MemKVStore()))
        sim.set_state(cc, key, value)
        rwset = sim.get_tx_simulation_results()
    nonce = nonce if nonce is not None else _sha256(b"nonce:" + key.encode())
    txid = txid if txid is not None else protoutil.compute_tx_id(nonce, creator)
    ext = proposal_pb2.ChaincodeHeaderExtension()
    ext.chaincode_id.name = cc
    chdr = protoutil.make_channel_header(
        tx_type, channel, tx_id=txid,
        extension=ext.SerializeToString(), timestamp=0,
    )
    shdr = protoutil.make_signature_header(creator, nonce)
    chdr_b = chdr.SerializeToString()
    shdr_b = shdr.SerializeToString()
    ccpp_b = proposal_pb2.ChaincodeProposalPayload(
        input=b"input:" + key.encode()
    ).SerializeToString()

    action = proposal_pb2.ChaincodeAction(results=rwset)
    action.chaincode_id.name = cc
    phash = protoutil.proposal_hash2(chdr_b, shdr_b, ccpp_b)
    if bad_proposal_hash:
        phash = b"\x00" * 32
    prp = proposal_response_pb2.ProposalResponsePayload(
        proposal_hash=phash, extension=action.SerializeToString()
    )
    prp_b = prp.SerializeToString()
    endos = []
    if not no_endorsements:
        for j, eb in enumerate(endorsers):
            sig = _sign(eb, _sha256(prp_b + eb))
            if j < tampered_endorsements:
                sig = b"tampered-signature"
            endos.append(
                proposal_response_pb2.Endorsement(endorser=eb, signature=sig)
            )
    cap = transaction_pb2.ChaincodeActionPayload(
        chaincode_proposal_payload=ccpp_b,
        action=transaction_pb2.ChaincodeEndorsedAction(
            proposal_response_payload=prp_b, endorsements=endos
        ),
    )
    tx = transaction_pb2.Transaction(
        actions=[
            transaction_pb2.TransactionAction(payload=cap.SerializeToString())
        ]
    )
    payload_b = common_pb2.Payload(
        header=common_pb2.Header(
            channel_header=chdr_b, signature_header=shdr_b
        ),
        data=tx.SerializeToString(),
    ).SerializeToString()
    env_sig = (
        b"bad-creator-signature"
        if bad_creator_sig
        else _sign(creator, _sha256(payload_b))
    )
    return common_pb2.Envelope(
        payload=payload_b, signature=env_sig
    ).SerializeToString()


def _block_of(env_bytes: list[bytes], num: int = 0,
              prev: bytes = b"") -> common_pb2.Block:
    blk = common_pb2.Block()
    blk.header.number = num
    blk.header.previous_hash = prev
    blk.data.data.extend(env_bytes)
    blk.header.data_hash = protoutil.block_data_hash(blk.data)
    protoutil.init_block_metadata(blk)
    protoutil.set_tx_filter(blk, bytearray(len(env_bytes)))
    return blk


def _copy(blk: common_pb2.Block) -> common_pb2.Block:
    c = common_pb2.Block()
    c.CopyFrom(blk)
    return c


def _mixed_block() -> tuple[common_pb2.Block, dict[int, int]]:
    """A block mixing ~40 valid txs with one lane per failure stage;
    returns (block, {tx index: expected flag})."""
    envs: list[bytes] = []
    expect: dict[int, int] = {}

    def add(env: bytes, flag: int) -> None:
        expect[len(envs)] = flag
        envs.append(env)

    for i in range(40):
        add(
            _make_tx(
                f"k{i}", creator=_CREATORS[i % 3],
                endorsers=_ENDORSERS if i % 4 else _ENDORSERS[:2],
            ),
            V.VALID,
        )
    add(_make_tx("badident", creator=b"badid:x"), V.BAD_CREATOR_SIGNATURE)
    add(_make_tx("badsig", bad_creator_sig=True), V.BAD_CREATOR_SIGNATURE)
    # 1 of 3 endorsements tampered still meets the 2-of-3 policy
    add(_make_tx("tam1", tampered_endorsements=1), V.VALID)
    add(
        _make_tx("tam2", tampered_endorsements=2),
        V.ENDORSEMENT_POLICY_FAILURE,
    )
    dup_nonce = _sha256(b"nonce:dup")
    add(_make_tx("dupA", nonce=dup_nonce), V.VALID)
    add(_make_tx("dupB", nonce=dup_nonce), V.DUPLICATE_TXID)
    add(
        _make_tx("badrw", rwset_override=b"\xff\xff\xff\xff"),
        V.BAD_RWSET,
    )
    add(_make_tx("wrongch", channel="otherch"), V.BAD_CHANNEL_HEADER)
    add(_make_tx("badph", bad_proposal_hash=True), V.BAD_RESPONSE_PAYLOAD)
    add(
        _make_tx("noendo", no_endorsements=True),
        V.ENDORSEMENT_POLICY_FAILURE,
    )
    add(
        _make_tx("badtxid", txid="not-the-binding"), V.BAD_PROPOSAL_TXID
    )
    add(
        _make_tx("badtype", tx_type=common_pb2.MESSAGE), V.UNKNOWN_TX_TYPE
    )
    return _block_of(envs), expect


def _collect_outcome(blk: common_pb2.Block, width: int, pool=None):
    """(flags, verify items, per-tx sink index lists) of one validate
    run at the given collect width."""
    csp = _FakeCSP()
    ledger = LedgerProvider(None).open(CHANNEL)
    v = TxValidator(
        CHANNEL, ledger, _FakeBundle(), csp,
        collect_width=width, collect_pool=pool,
    )
    started = v._start_block(_copy(blk), set())
    block, flags0, works, collect, _envs, bspan = started
    flags = v._finish_block(block, flags0, works, collect, bspan)
    items = csp.batches[0] if csp.batches else []
    index_map = [
        (w.creator_item, [ix for _p, idxs in w.pendings for ix in idxs])
        for w in works
    ]
    return flags, items, index_map, v


# -- collect parity -----------------------------------------------------------


@pytest.mark.parametrize("use_native", [True, False],
                         ids=["native", "pure-python"])
def test_parallel_collect_parity(monkeypatch, use_native):
    """Serial vs parallel collect: identical flags, identical verify-
    item order, identical dedup index assignments at every pool width —
    on both the native-assisted and pure-Python collect paths."""
    if use_native and not native.available():
        pytest.skip("native library unavailable")
    if not use_native:
        monkeypatch.setattr(native, "available", lambda: False)
    blk, expect = _mixed_block()
    base_flags, base_items, base_idx, v0 = _collect_outcome(blk, width=0)
    assert v0.parallel_collect_blocks == 0
    assert base_items, "the mixed block must produce verify items"
    for i, flag in expect.items():
        assert base_flags[i] == flag, (
            f"tx {i}: expected flag {flag}, got {base_flags[i]}"
        )
    for width in (2, 3, 8):
        with workpool.scoped_pool(width, name=f"parity-{width}") as pool:
            flags, items, idx, v = _collect_outcome(
                blk, width=width, pool=pool
            )
        assert v.parallel_collect_blocks == 1, f"width {width} stayed serial"
        assert flags == base_flags, f"width {width} flags diverged"
        assert items == base_items, f"width {width} sink items diverged"
        assert idx == base_idx, f"width {width} dedup indices diverged"


def test_small_block_stays_serial():
    """Blocks under the fan-out threshold must not pay pool overhead."""
    blk = _block_of([_make_tx("only")])
    flags, _items, _idx, v = _collect_outcome(blk, width=8)
    assert flags == [V.VALID]
    assert v.parallel_collect_blocks == 0


def test_collect_tx_chaos_seam(monkeypatch):
    """collect.tx is armable inside the (pooled) collect stage: a
    ctx-free raise rule aborts validation deterministically, and a
    plain delay leaves flags untouched — with the pool active."""
    blk, _expect = _mixed_block()
    with workpool.scoped_pool(3, name="chaos-collect") as pool:
        csp = _FakeCSP()
        ledger = LedgerProvider(None).open(CHANNEL)
        v = TxValidator(
            CHANNEL, ledger, _FakeBundle(), csp,
            collect_width=3, collect_pool=pool,
        )
        with faultline.use_plan({"seed": 5, "faults": [{
            "point": "collect.tx", "action": "raise",
            "error": "OSError", "message": "injected collect fault",
            "nth": 7,
        }]}):
            with pytest.raises(OSError, match="injected collect fault"):
                v.validate(_copy(blk))
            assert any(
                t["point"] == "collect.tx" for t in faultline.trips()
                if t["plan"] != "soak"
            )
        # delays must not change the outcome
        base_flags, base_items, base_idx, _v = _collect_outcome(blk, 0)
        with faultline.use_plan({"seed": 6, "faults": [{
            "point": "collect.tx", "action": "delay", "delay_s": 0.0,
            "every": 9, "count": 50,
        }]}):
            flags, items, idx, _v2 = _collect_outcome(blk, 3, pool=pool)
            assert (flags, items, idx) == (base_flags, base_items, base_idx)


# -- MVCC prepare parity ------------------------------------------------------


def _seeded_db() -> VersionedDB:
    db = VersionedDB(MemKVStore())
    h = Height(1, 0)
    batch: dict = {}
    for ns in ("cc0", "cc1", "cc2"):
        batch[ns] = {
            f"base{i}": VersionedValue(b"b%d" % i, h) for i in range(6)
        }
    # cc2 carries key metadata so the metadata-retention path (and the
    # may_have_metadata-gated write-key preload) is exercised
    from fabric_tpu.ledger.txmgmt import encode_metadata

    batch["cc2"]["base0"] = VersionedValue(
        b"m0", h, encode_metadata({"VALIDATION_PARAMETER": b"pol"})
    )
    db.apply_updates(batch, Height(1, 1))
    return db


def _mvcc_workload(db: VersionedDB):
    """(rwsets, pvt_data) spanning 3 namespaces, in-block conflicts,
    deletes, metadata writes, ranges, and private collections."""
    rwsets: list = []

    def sim() -> TxSimulator:
        return TxSimulator(db)

    # three fat write-only txs (past the fan-out threshold together)
    for t in range(3):
        s = sim()
        for ns in ("cc0", "cc1", "cc2"):
            for i in range(8):
                s.set_state(ns, f"w{t}-{i}", b"x%d" % t)
        rwsets.append(s.get_tx_simulation_results())
    # reads: one consistent, one conflicting with tx0's in-block write
    s = sim()
    s.get_state("cc0", "base0")
    s.set_state("cc1", "r-ok", b"1")
    rwsets.append(s.get_tx_simulation_results())
    s = sim()
    s.get_state("cc0", "w0-0")  # version None committed; tx0 wrote it
    s.set_state("cc0", "r-bad", b"2")
    rwsets.append(s.get_tx_simulation_results())
    # deletes + rewrite, metadata writes on live and absent keys
    s = sim()
    s.delete_state("cc0", "base1")
    s.set_state("cc0", "base2", b"rewritten")
    s.set_state_metadata("cc2", "base1", {"OWNER": b"org1"})
    s.set_state_metadata("cc2", "missing", {"OWNER": b"org2"})
    rwsets.append(s.get_tx_simulation_results())
    # range query over cc1 (phantom-protected)
    s = sim()
    s.get_state_range("cc1", "base0", "base9")
    s.set_state("cc1", "rq", b"3")
    rwsets.append(s.get_tx_simulation_results())
    # private collection: authentic cleartext for tx7, forged for tx8
    s = sim()
    s.set_private_data("cc1", "collA", "p1", b"secret")
    rwsets.append(s.get_tx_simulation_results())
    pvt_good = s.get_pvt_simulation_results()
    s = sim()
    s.set_private_data("cc2", "collB", "p2", b"secret2")
    rwsets.append(s.get_tx_simulation_results())
    pvt_data = {7: pvt_good, 8: b"\x0a\x03bad"}
    return rwsets, pvt_data


def test_parallel_mvcc_prepare_parity():
    """Serial vs fanned-out MVCC prepare: identical flags, identical
    batch contents AND identical namespace insertion order at every
    fan-out width."""
    db = _seeded_db()
    rwsets, pvt_data = _mvcc_workload(db)
    flags0 = [VALID] * len(rwsets)
    serial = MVCCValidator(db, fanout=0)
    base_batch = serial.validate_and_prepare(
        2, list(rwsets), flags0, dict(pvt_data)
    )
    assert serial.parallel_prepare_blocks == 0
    assert flags0[4] == MVCC_READ_CONFLICT  # the in-block stale read
    assert flags0.count(VALID) == len(rwsets) - 1
    # the authentic cleartext landed, the forged one did not
    assert "cc1\x00pvt\x00collA" in base_batch
    assert "cc2\x00pvt\x00collB" not in base_batch
    for width in (2, 3, 8):
        with workpool.scoped_pool(width, name=f"mvcc-{width}") as pool:
            mv = MVCCValidator(db, pool=pool, fanout=width)
            flags = [VALID] * len(rwsets)
            batch = mv.validate_and_prepare(
                2, list(rwsets), flags, dict(pvt_data)
            )
        assert mv.parallel_prepare_blocks == 1, f"width {width} stayed serial"
        assert flags == flags0, f"width {width} flags diverged"
        assert batch == base_batch, f"width {width} batch diverged"
        assert list(batch) == list(base_batch), (
            f"width {width} namespace order diverged"
        )


def test_mvcc_prepare_chaos_seam():
    """mvcc.ns_prepare fires inside the fanned-out prepare; a raise
    rule targeted at one namespace aborts the whole prepare."""
    db = _seeded_db()
    rwsets, pvt_data = _mvcc_workload(db)
    with workpool.scoped_pool(3, name="chaos-mvcc") as pool:
        mv = MVCCValidator(db, pool=pool, fanout=3)
        with faultline.use_plan({"seed": 11, "faults": [{
            "point": "mvcc.ns_prepare", "ctx": {"ns": "cc1"},
            "action": "raise", "error": "OSError",
            "message": "injected prepare fault",
        }]}):
            with pytest.raises(OSError, match="injected prepare fault"):
                mv.validate_and_prepare(
                    2, list(rwsets), [VALID] * len(rwsets), dict(pvt_data)
                )
            trips = [
                t for t in faultline.trips() if t["plan"] != "soak"
            ]
            assert trips and trips[0]["point"] == "mvcc.ns_prepare"
            assert trips[0]["ctx"]["ns"] == "cc1"


# -- batched recovery replay --------------------------------------------------


def _committed_blocks(ledger, n_blocks: int):
    """Commit `n_blocks` multi-namespace blocks per-block; returns the
    writes_by_block model for the invariant oracle."""
    from test_group_commit import _write_block

    model = []
    for num in range(n_blocks):
        items = [
            (ns, f"b{num}-{i}", b"v%d" % num)
            for ns in ("cca", "ccb")
            for i in range(3)
        ]
        ledger.commit(_write_block(ledger, num, items))
        model.append(items)
    return model


@pytest.mark.parametrize("group_size", ["1", "3", "32"])
def test_recovery_replay_equivalence(tmp_path, monkeypatch, group_size):
    """Replay through the WriteBatchCollector group seam reaches the
    same state/history/durable_height as the per-block path at every
    replay group size, judged by the invariant oracle."""
    from test_group_commit import _write_block

    # reference directory: everything committed and flushed per block
    ref_provider = LedgerProvider(str(tmp_path / "ref"))
    ref = ref_provider.open("rec")
    model = _committed_blocks(ref, 3)
    for num in (3, 4, 5, 6):
        items = [
            (ns, f"b{num}-{i}", b"v%d" % num)
            for ns in ("cca", "ccb")
            for i in range(3)
        ]
        ref.commit(_write_block(ref, num, items))
        model.append(items)

    # replay directory: blocks 3..6 land in a group that never flushes
    # (simulated crash) — reopen must replay them through the batched
    # seam
    root = str(tmp_path / f"replay{group_size}")
    provider = LedgerProvider(root)
    led = provider.open("rec")
    _committed_blocks(led, 3)
    group = led.begin_commit_group()
    for num in (3, 4, 5, 6):
        items = [
            (ns, f"b{num}-{i}", b"v%d" % num)
            for ns in ("cca", "ccb")
            for i in range(3)
        ]
        led.commit(_write_block(led, num, items), group=group)
    provider.close()  # crash: group never flushed

    monkeypatch.setenv("FABRIC_TPU_RECOVERY_GROUP", group_size)
    provider2 = LedgerProvider(root)
    led2 = provider2.open("rec")
    violations = invariants.check_ledger(led2, writes_by_block=model)
    assert not violations, [str(x) for x in violations]
    assert led2.height == ref.height == 7
    assert led2.durable_height == 7
    assert led2.state_db.savepoint() == ref.state_db.savepoint()
    for num, items in enumerate(model):
        for ns, key, val in items:
            assert led2.get_state(ns, key) == ref.get_state(ns, key) == val
            assert led2.get_history_for_key(ns, key) == \
                ref.get_history_for_key(ns, key)
    # and the chain continues cleanly from the recovered height
    led2.commit(_write_block(led2, 7, [("cca", "post", b"p")]))
    assert led2.get_state("cca", "post") == b"p"
    provider2.close()
    ref_provider.close()


def test_recovery_replay_coalesces_kv_txns(tmp_path, monkeypatch):
    """The batched replay pays ~one KV transaction per replay group —
    strictly fewer than the per-block-group path over the same tail."""
    from test_group_commit import _write_block
    from fabric_tpu.ledger.kvstore import SqliteKVStore

    def build(root):
        provider = LedgerProvider(root)
        led = provider.open("rec")
        led.commit(_write_block(led, 0, [("cc", "k0", b"v")]))
        group = led.begin_commit_group()
        for num in range(1, 9):
            led.commit(
                _write_block(led, num, [("cc", f"k{num}", b"v")]),
                group=group,
            )
        provider.close()

    def reopen_txns(root, group_size):
        monkeypatch.setenv("FABRIC_TPU_RECOVERY_GROUP", group_size)
        counter = [0]
        real = SqliteKVStore.write_batch

        def wb(store, puts, deletes=()):
            counter[0] += 1
            return real(store, puts, deletes)

        monkeypatch.setattr(SqliteKVStore, "write_batch", wb)
        provider = LedgerProvider(root)
        led = provider.open("rec")
        assert led.height == 9
        assert led.get_state("cc", "k8") == b"v"
        monkeypatch.setattr(SqliteKVStore, "write_batch", real)
        provider.close()
        return counter[0]

    build(str(tmp_path / "a"))
    build(str(tmp_path / "b"))
    per_block = reopen_txns(str(tmp_path / "a"), "1")
    batched = reopen_txns(str(tmp_path / "b"), "32")
    assert batched < per_block, (batched, per_block)


def test_mvcc_adversarial_nul_namespaces():
    """An adversarial rwset may NAME a top-level namespace containing
    the \\x00 separators the derived hash/pvt encodings use.  The
    per-namespace grouping must neither crash nor drop such writes —
    and when a literal namespace COLLIDES with another namespace's
    derived encoding, the prepare must fall back to the old
    single-dict semantics (both writers land in one merged batch dict,
    in tx order) at every fan-out width."""
    db = VersionedDB(MemKVStore())
    evil = "evil\x00hash\x00c"  # literal ns == hash_ns("evil", "c")

    def workload():
        rwsets = []
        s = TxSimulator(db)
        for i in range(20):
            s.set_state(evil, f"lit{i}", b"L")
            s.set_state("cc0", f"pad{i}", b"p")
        rwsets.append(s.get_tx_simulation_results())
        # the colliding derived namespace: private writes in
        # ("evil", "c") hash into the SAME namespace string
        s = TxSimulator(db)
        s.set_private_data("evil", "c", "p1", b"secret")
        for i in range(20):
            s.set_state("cc1", f"q{i}", b"q")
        rwsets.append(s.get_tx_simulation_results())
        return rwsets

    rwsets = workload()
    flags0 = [VALID, VALID]
    serial = MVCCValidator(db, fanout=0)
    base = serial.validate_and_prepare(5, list(rwsets), flags0)
    assert flags0 == [VALID, VALID]
    # the literal writes survived, alongside the hashed write of the
    # colliding derived namespace, in ONE batch dict
    assert base[evil]["lit0"].value == b"L"
    assert base[evil]["lit19"].value == b"L"
    from fabric_tpu.ledger.txmgmt import key_hash

    assert key_hash("p1").hex() in base[evil]
    for width in (2, 4):
        with workpool.scoped_pool(width, name=f"nul-{width}") as pool:
            mv = MVCCValidator(db, pool=pool, fanout=width)
            flags = [VALID, VALID]
            batch = mv.validate_and_prepare(5, list(rwsets), flags)
        assert flags == flags0
        assert batch == base, f"width {width} diverged on NUL namespaces"
        assert list(batch) == list(base)


def test_serial_duplicate_txid_skips_expensive_tail(monkeypatch):
    """The serial collect path must flag a duplicate txid WITHOUT
    paying the transaction-decode/hash/footprint tail (the old
    single-pass behavior); flags still match the parallel path, where
    the dup verdict lands at integration."""
    import fabric_tpu.peer.validation_plugins as vp

    dup_nonce = _sha256(b"nonce:serial-dup")
    envs = [
        _make_tx("sd-a", nonce=dup_nonce),
        _make_tx("sd-b", nonce=dup_nonce),
    ]
    blk = _block_of(envs)
    calls = []
    real = vp.parse_footprint
    monkeypatch.setattr(
        vp, "parse_footprint",
        lambda raw: calls.append(1) or real(raw),
    )
    import fabric_tpu.peer.txvalidator as txv

    monkeypatch.setattr(txv, "parse_footprint", vp.parse_footprint)
    monkeypatch.setattr(native, "available", lambda: False)
    csp = _FakeCSP()
    ledger = LedgerProvider(None).open(CHANNEL)
    v = TxValidator(CHANNEL, ledger, _FakeBundle(), csp, collect_width=0)
    flags = v.validate(_copy(blk))
    assert flags == [V.VALID, V.DUPLICATE_TXID]
    assert len(calls) == 1, "the duplicate's rwset was still parsed"


def test_mvcc_metadata_write_semantics_after_restructure():
    """Hand-computed pins for the pass-1/pass-2 split (not just
    serial-vs-parallel): a metadata write on a live key keeps its value
    and bumps its version; on an in-block-deleted or absent key it is a
    no-op (no version bump — a later read at the committed version
    stays VALID); a value-only write retains committed metadata."""
    from fabric_tpu.ledger.txmgmt import decode_metadata, encode_metadata

    db = VersionedDB(MemKVStore())
    h1 = Height(1, 0)
    db.apply_updates({"cc": {
        "live": VersionedValue(b"v", h1),
        "meta": VersionedValue(b"v", h1, encode_metadata({"A": b"1"})),
        "dele": VersionedValue(b"v", h1),
    }}, Height(1, 1))

    s = TxSimulator(db)
    s.set_state_metadata("cc", "live", {"OWNER": b"org1"})
    rw0 = s.get_tx_simulation_results()
    s = TxSimulator(db)
    s.delete_state("cc", "dele")
    rw1 = s.get_tx_simulation_results()
    s = TxSimulator(db)
    s.set_state_metadata("cc", "dele", {"OWNER": b"org2"})  # deleted: no-op
    s.set_state_metadata("cc", "absent", {"OWNER": b"org3"})  # absent: no-op
    rw2 = s.get_tx_simulation_results()
    s = TxSimulator(db)
    s.set_state("cc", "meta", b"v2")  # value-only: metadata retained
    rw3 = s.get_tx_simulation_results()
    # reads the committed version of 'dele'/'absent' AFTER the metadata
    # no-ops: must stay VALID (a spurious version bump would conflict)
    s = TxSimulator(db)
    s.get_state("cc", "dele")
    s.get_state("cc", "absent")
    s.set_state("cc", "tail", b"t")
    rw4 = s.get_tx_simulation_results()

    flags = [VALID] * 5
    batch = MVCCValidator(db, fanout=0).validate_and_prepare(
        2, [rw0, rw1, rw2, rw3, rw4], flags
    )
    # tx1 deleted 'dele' in-block, so tx4's committed-version read of it
    # conflicts; the metadata no-ops must NOT have bumped 'absent'
    assert flags == [VALID, VALID, VALID, VALID, MVCC_READ_CONFLICT]
    assert decode_metadata(batch["cc"]["live"].metadata) == {
        "OWNER": b"org1"
    }
    assert batch["cc"]["live"].value == b"v"
    assert batch["cc"]["live"].version == Height(2, 0)
    assert batch["cc"]["dele"] is None
    assert "absent" not in batch["cc"]
    assert decode_metadata(batch["cc"]["meta"].metadata) == {"A": b"1"}
    assert batch["cc"]["meta"].value == b"v2"

    # without the in-block delete, the metadata write on the LIVE
    # 'dele' key is a real version bump (a later committed-version read
    # of it must conflict), while the no-op on 'absent' still bumps
    # nothing (a read of it stays VALID)
    s = TxSimulator(db)
    s.get_state("cc", "absent")
    s.set_state("cc", "tail2", b"t")
    rw5 = s.get_tx_simulation_results()
    flags2 = [VALID] * 3
    batch2 = MVCCValidator(db, fanout=0).validate_and_prepare(
        2, [rw2, rw4, rw5], flags2
    )
    assert flags2 == [VALID, MVCC_READ_CONFLICT, VALID]
    assert decode_metadata(batch2["cc"]["dele"].metadata) == {
        "OWNER": b"org2"
    }
    assert "absent" not in batch2["cc"]
    assert "tail2" in batch2["cc"]


# -- sqlite durability knob ---------------------------------------------------


def test_sqlite_durability_knob(tmp_path, monkeypatch):
    """FABRIC_TPU_SQLITE_SYNC reaches the PRAGMA; the ctor arg overrides
    env; an invalid value refuses loudly."""
    from fabric_tpu.ledger.kvstore import SqliteKVStore

    def sync(store):
        return store._conn.execute("PRAGMA synchronous").fetchone()[0]

    s = SqliteKVStore(str(tmp_path / "default.db"))
    assert (sync(s), s.sync_level) == (1, "NORMAL")
    s.close()

    monkeypatch.setenv("FABRIC_TPU_SQLITE_SYNC", "full")
    s = SqliteKVStore(str(tmp_path / "env.db"))
    assert (sync(s), s.sync_level) == (2, "FULL")
    s.close()

    s = SqliteKVStore(str(tmp_path / "ctor.db"), synchronous="OFF")
    assert sync(s) == 0
    s.close()

    monkeypatch.setenv("FABRIC_TPU_SQLITE_SYNC", "sometimes")
    with pytest.raises(ValueError, match="FABRIC_TPU_SQLITE_SYNC"):
        SqliteKVStore(str(tmp_path / "bad.db"))


# -- tier-1 smoke: 50-tx pipelined stream, parallel stages on ----------------


def test_smoke_parallel_stream_50tx_depth2():
    """A tiny pipelined validate+commit stream (50 txs, depth 2) with
    parallel collect AND parallel MVCC prepare enabled: green invariant
    oracle, clean lockwatch/threadwatch ledgers, and both stages
    actually fanned out."""
    envs = []
    model = []
    n_txs = 50
    for i in range(n_txs):
        ns = "ppcc" if i % 2 else "ppcc2"
        envs.append(
            _make_tx(f"s{i}", cc=ns, creator=_CREATORS[i % 3])
        )
        model.append((ns, f"s{i}", b"v"))
    with workpool.scoped_pool(2, name="smoke") as pool:
        csp = _FakeCSP()
        provider = LedgerProvider(None)
        ledger = provider.open(CHANNEL)
        validator = TxValidator(
            CHANNEL, ledger, _FakeBundle(), csp,
            collect_width=2, collect_pool=pool,
        )
        # thread the scoped pool through the ledger's commit groups so
        # the MVCC prepare fans out on it too
        import fabric_tpu.ledger.txmgmt as txmgmt

        real_init = txmgmt.MVCCValidator.__init__
        prepared = []

        def init(self, db, p=None, fanout=None):
            real_init(self, db, pool=pool, fanout=2)
            prepared.append(self)

        txmgmt.MVCCValidator.__init__ = init
        try:
            committer = Committer(validator, ledger)
            blk = _block_of(envs, num=0)
            flags = list(committer.store_stream(iter([blk]), depth=2))
        finally:
            txmgmt.MVCCValidator.__init__ = real_init
    assert flags == [[V.VALID] * n_txs]
    assert validator.parallel_collect_blocks >= 1
    assert any(m.parallel_prepare_blocks for m in prepared)
    assert ledger.height == 1
    violations = invariants.check_ledger(
        ledger, writes_by_block=[model]
    )
    assert not violations, [str(x) for x in violations]
    assert not lockwatch.violations
    assert not lockwatch.thread_violations
