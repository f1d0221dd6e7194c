"""A block's key-level parameters come in one read (`_KeyLevelMemo`,
`KVLedger.get_state_metadata_many`): the validator fetches the written
keys' committed state metadata once a stage and answers its plugins'
lookups from memory.  Here, on the CPU at a small size: the flags are
those of a validator that reads a key a lookup (the memo forced to
miss), lone and pipelined at every depth, on the chains of
`test_keylevel_stream.py` and on a randomised one with deletes,
collection keys, an unparseable parameter and in-block rewrites; a
spy on the ledger sees one bulk read a stage, no read by itself on the
native path and no pending pair in a collect's read; a block's policy
stage reads its own block's memo whatever was collected since; a
ledger without metadata takes no pre-pass and no read; faithful mode
still reads a key a lookup; and the bulk read answers as the point
read does, pair by pair.

No number of a CPU run is a device number: the tests read counts, flags
and verdicts, never a time."""

import threading

import pytest

from test_keylevel_stream import (  # noqa: F401 - the fixtures of the stream's chains
    N_BLOCKS, NS, POLICY, VALID, _Peer, chain, hand, held, kl, majority, man, serial)

from fabric_tpu import native
from fabric_tpu.common import tracing
from fabric_tpu.common.hashing import sha256 as _sha
from fabric_tpu.ledger.txmgmt import VALIDATION_PARAMETER
from fabric_tpu.peer import txvalidator
from fabric_tpu.peer.txvalidator import TxValidator, _KeyLevelMemo, _KeyWindow

OTHER = "othercc"       # a second namespace some transactions write too
COLL = "secrets"


# -- transactions the world's `Tx` does not make ------------------------------


def _raw_tx(hand, endorsers, fill) -> bytes:
    """An envelope whose TxReadWriteSet `fill(results)` builds, endorsed
    by the peers of `endorsers`."""
    from fabric_tpu import protoutil
    from fabric_tpu.protos.ledger.rwset import rwset_pb2

    net, rng = hand.net, hand.rng
    results = rwset_pb2.TxReadWriteSet(data_model=rwset_pb2.TxReadWriteSet.KV)
    fill(results)
    prop, _txid = protoutil.create_chaincode_proposal(
        net.client.serialize(), "benchch", NS, [b"raw"], nonce=rng.randbytes(24))
    resps = [
        protoutil.create_proposal_response(
            prop, results=results.SerializeToString(), events=b"", response=net._ok,
            chaincode_id=net._cc_id, endorser_signer=net.peers[i])
        for i in endorsers
    ]
    return protoutil.create_signed_tx(prop, net.client, resps).SerializeToString()


def _public(ns, writes=(), deletes=(), params=()):
    """`fill` for public writes [(key, value)], deletes [key] and
    parameters [(key, raw bytes)] in `ns`."""
    from fabric_tpu.protos.ledger.rwset.kvrwset import kv_rwset_pb2

    def fill(results):
        kv = kv_rwset_pb2.KVRWSet()
        for key, value in writes:
            kv.writes.add(key=key, value=value)
        for key in deletes:
            kv.writes.add(key=key, is_delete=True)
        for key, raw in params:
            kv.metadata_writes.add(key=key).entries.add(name=VALIDATION_PARAMETER, value=raw)
        results.ns_rwset.add(namespace=ns, rwset=kv.SerializeToString())
    return fill


def _private(writes=(), params=(), public=()):
    """`fill` for hashed writes [(key, value)] and hashed parameters
    [(key, raw bytes)] of the collection, beside public writes."""
    from fabric_tpu.protos.ledger.rwset.kvrwset import kv_rwset_pb2

    def fill(results):
        kv = kv_rwset_pb2.KVRWSet()
        for key, value in public:
            kv.writes.add(key=key, value=value)
        nsrw = results.ns_rwset.add(namespace=NS, rwset=kv.SerializeToString())
        h = kv_rwset_pb2.HashedRWSet()
        for key, value in writes:
            h.hashed_writes.add(key_hash=_sha(key.encode()), value_hash=_sha(value))
        for key, raw in params:
            h.metadata_writes.add(key_hash=_sha(key.encode())).entries.add(
                name=VALIDATION_PARAMETER, value=raw)
        ch = nsrw.collection_hashed_rwset.add(collection_name=COLL)
        ch.hashed_rwset = h.SerializeToString()
        ch.pvt_rwset_hash = _sha(b"")
    return fill


def _raw_block(hand, *envs) -> bytes:
    from fabric_tpu.protos.common import common_pb2

    hand.number += 1
    blk = common_pb2.Block()
    blk.header.number = hand.number
    blk.data.data.extend(envs)
    while len(blk.metadata.metadata) < 3:
        blk.metadata.metadata.append(b"")
    return blk.SerializeToString()


MAJORITY = (0, 1, 2)


def _randomised(hand, blocks=7, block_txs=14) -> tuple:
    """(setup, stream): assets with owners, keys of a collection with
    owners, a key whose parameter does not parse, a second namespace;
    then blocks of updates, transfers, deletes, re-creations, strangers'
    writes, stale reads and in-block rewrites drawn from a seeded
    generator, so that neighbouring blocks meet on the same few keys."""
    rng, net = hand.rng, hand.net
    env = lambda tx: net.envelope(rng, tx)[0]  # noqa: E731
    assets = [f"a{i}" for i in range(8)]
    secrets = [f"s{i}" for i in range(4)]
    owner = {k: (rng.randrange(5),) for k in assets + secrets}
    setup = [
        _raw_block(hand, *[env(hand.create(k, owner[k])) for k in assets]),
        _raw_block(
            hand,
            *[_raw_tx(hand, MAJORITY, _private(
                writes=[(k, b"made")], params=[(k, net.parameter(owner[k]))]))
              for k in secrets],
            _raw_tx(hand, MAJORITY, _public(NS, writes=[("broken", b"made")],
                                            params=[("broken", b"\xff not a policy")])),
            _raw_tx(hand, MAJORITY, _public(OTHER, writes=[("o0", b"made")],
                                            params=[("o0", net.parameter((4,)))]))),
    ]

    def one() -> list:
        k = rng.choice(assets)
        s = rng.choice(secrets)
        who = lambda key: owner[key] if rng.random() < 0.75 else (rng.randrange(5),)  # noqa: E731
        kind = rng.choice(["update"] * 3 + ["transfer"] * 3 + ["delete", "recreate", "secret",
                          "secret_transfer", "broken", "other", "both", "pair", "versioned"])
        if kind == "update":
            return [env(hand.tx(k, who(k)))]
        if kind == "versioned":     # reads the version the key was made at: MVCC's, once rewritten
            return [env(hand.tx(k, who(k), read=(1, assets.index(k))))]
        if kind == "transfer":
            return [env(hand.tx(k, who(k), new_owners=(rng.randrange(5),)))]
        if kind == "delete":
            return [_raw_tx(hand, who(k), _public(NS, deletes=[k]))]
        if kind == "recreate":
            return [env(hand.create(k, (rng.randrange(5),)))]
        if kind == "secret":
            return [_raw_tx(hand, who(s), _private(writes=[(s, rng.randbytes(4))]))]
        if kind == "secret_transfer":
            return [_raw_tx(hand, who(s), _private(
                writes=[(s, b"sold")], params=[(s, net.parameter((rng.randrange(5),)))]))]
        if kind == "broken":
            return [_raw_tx(hand, rng.choice([MAJORITY, (0, 1, 2, 3, 4)]),
                            _public(NS, writes=[("broken", rng.randbytes(4))]))]
        if kind == "other":
            return [_raw_tx(hand, rng.choice([(4,), MAJORITY]),
                            _public(OTHER, writes=[("o0", rng.randbytes(4))]))]
        if kind == "both":      # a public key and a key of the collection, an owner each
            return [_raw_tx(hand, tuple(set(who(k) + who(s))), _private(
                writes=[(s, b"both")], public=[(k, b"both")]))]
        # an in-block rewrite: whoever writes the key after the transfer is refused
        return [env(hand.tx(k, owner[k], new_owners=(rng.randrange(5),))),
                env(hand.tx(k, who(k)))]

    stream = []
    for _b in range(blocks):
        envs: list = []
        while len(envs) < block_txs:
            envs.extend(one())
        stream.append(_raw_block(hand, *envs))
        # who owns what the generator cannot know without a validator:
        # `who` then draws strangers and owners alike, which is the point
        for key in owner:
            if rng.random() < 0.3:
                owner[key] = (rng.randrange(5),)
    return setup, stream


def _kind_1(hand):
    return [], [hand.block(hand.create("asset", (0,), value=b"made")),
                hand.block(hand.tx("asset", (0,), read=(hand.number, 0), value=b"by-owner"))]


def _owned(hand):
    return [hand.block(hand.create("asset", (0,), value=b"made"))]


def _kind_2(hand):
    return _owned(hand), [
        hand.block(hand.tx("asset", (0,), read=(1, 0), new_owners=(1,), value=b"sold")),
        hand.block(hand.tx("asset", (1,), read=(2, 0), value=b"by-new-owner"))]


def _kind_3(hand):
    return _owned(hand), [
        hand.block(hand.tx("asset", (0,), read=(1, 0), new_owners=(1,), value=b"sold")),
        hand.block(hand.tx("asset", (0,), read=(2, 0), value=b"by-old-owner"))]


def _three_blocks(hand):
    return _owned(hand), [
        hand.block(hand.tx("asset", (0,), new_owners=(1,), value=b"sold")),
        hand.block(hand.tx("asset", (0,), value=b"by-old-owner")),
        hand.block(hand.tx("asset", (1,), value=b"by-new-owner"))]


def _mvcc_refused(hand):
    return _owned(hand), [
        hand.block(hand.tx("asset", (0,), read=(1, 0), value=b"kept"),
                   hand.tx("other", (0, 1, 2), value=b"x"),
                   hand.tx("asset", (0,), read=(1, 0), new_owners=(1,), value=b"never")),
        hand.block(hand.tx("asset", (0,), read=(2, 0), value=b"by-owner"),
                   hand.tx("other2", (0, 1, 2), value=b"y"),
                   hand.tx("asset", (1,), read=(2, 0), value=b"by-stranger"))]


def _deleted(hand):
    return _owned(hand), [
        _raw_block(hand, _raw_tx(hand, (0,), _public(NS, deletes=["asset"]))),
        hand.block(hand.tx("asset", (0,), value=b"by-old-owner-alone")),
        hand.block(hand.tx("asset", (2, 3, 4), value=b"by-a-majority"))]


HAND_CHAINS = {
    "kind_1": _kind_1, "kind_2": _kind_2, "kind_3": _kind_3, "three_blocks": _three_blocks,
    "mvcc_refused": _mvcc_refused, "deleted": _deleted, "randomised": _randomised,
}


@pytest.fixture
def point_reads_only(monkeypatch):
    """The validator before the bulk read: the memo holds nothing, so
    every lookup is a read of the ledger by itself."""
    def apply():
        monkeypatch.setattr(_KeyLevelMemo, "fill", lambda self, pairs: None)
        monkeypatch.setattr(_KeyLevelMemo, "_settled", lambda self, pair: False)
    return apply


def _full_state(peer) -> dict:
    """Every namespace the chains write, the collection's hashes too."""
    from fabric_tpu.ledger.txmgmt import hash_ns

    out = {}
    for ns in (NS, OTHER, hash_ns(NS, COLL)):
        for key, vv in peer.ledger._state.get_state_range(ns, "", ""):
            out[ns, key] = (vv.value, (vv.version.block_num, vv.version.tx_num), vv.metadata)
    return out


def _runs(peer_of, setup, stream):
    """The stream lone (a block at a time) and pipelined at depths 1-3:
    [(flags, state)]."""
    out = []
    for how in ("lone", 1, 2, 3):
        peer = peer_of()
        peer.serial(setup)
        flags = peer.serial(stream) if how == "lone" else peer.stream(stream, how)
        out.append((flags, _full_state(peer)))
    return out


@pytest.mark.parametrize("name", sorted(HAND_CHAINS))
def test_flags_with_the_bulk_read_are_those_of_a_read_a_key(hand, point_reads_only, name):
    setup, stream = HAND_CHAINS[name](hand)
    bulk = _runs(hand.peer, setup, stream)
    point_reads_only()
    point = _runs(hand.peer, setup, stream)
    want = point[0]                                 # lone, a read a key: the parent's serial peer
    for got in bulk + point[1:]:
        assert got[0] == want[0]
        assert got[1] == want[1]
    if name == "randomised":
        seen = {f for fl in want[0] for f in fl}
        assert {VALID, POLICY} <= seen and len(seen) >= 3, seen
        share = sum(f == VALID for fl in want[0] for f in fl) / sum(map(len, want[0]))
        assert 0.2 < share < 0.9                    # neither kind of flag is rare
        assert any(k[0] != NS and v[2] for k, v in want[1].items())     # a collection's parameter


def test_the_worlds_chain_is_decided_alike_with_and_without_the_bulk_read(
        chain, serial, point_reads_only):
    world, _dep = chain
    peer_of = lambda: _Peer(world.genesis, world.channel)  # noqa: E731
    bulk = _runs(peer_of, [], world.blocks)
    point_reads_only()
    point = _runs(peer_of, [], world.blocks)
    for flags, _state in bulk + point:
        assert flags == serial[0]
    assert len({repr(sorted(s.items())) for _f, s in bulk + point}) == 1


# -- what the ledger sees ------------------------------------------------------


class _Spy:
    """Records every read of committed state metadata a validator makes
    of its ledger, with the stage and the block that made it, and the
    pending view each block's collect took."""

    def __init__(self, monkeypatch, peer):
        self.bulk: list = []         # (stage, block, pairs)
        self.point: list = []        # (stage, block, pair)
        self.pending: dict = {}      # block -> its window's view, as taken
        self._at = threading.local()
        ledger, spy = peer.ledger, self
        many, one = ledger.get_state_metadata_many, ledger.get_state_metadata

        def spy_many(pairs):
            pairs = list(pairs)
            spy.bulk.append(spy._where() + (pairs,))
            return many(pairs)

        def spy_one(ns, key):
            spy.point.append(spy._where() + ((ns, key),))
            return one(ns, key)

        ledger.get_state_metadata_many, ledger.get_state_metadata = spy_many, spy_one
        start, finish = TxValidator._start_block_traced, TxValidator._finish_block_traced
        pending = _KeyWindow.pending

        def spy_start(v, block, *a, **kw):
            spy._at.where = ("collect", block.header.number)
            try:
                return start(v, block, *a, **kw)
            finally:
                spy._at.where = None

        def spy_finish(v, block, *a, **kw):
            spy._at.where = ("policy", block.header.number)
            try:
                return finish(v, block, *a, **kw)
            finally:
                spy._at.where = None

        def spy_pending(window):
            view = pending(window)
            spy.pending[spy._where()[1]] = dict(view or {})
            return view

        monkeypatch.setattr(TxValidator, "_start_block_traced", spy_start)
        monkeypatch.setattr(TxValidator, "_finish_block_traced", spy_finish)
        monkeypatch.setattr(_KeyWindow, "pending", spy_pending)

    def _where(self) -> tuple:
        return getattr(self._at, "where", None) or ("elsewhere", None)

    def bulk_calls(self, stage) -> dict:
        out: dict = {}
        for st, block, pairs in self.bulk:
            if st == stage:
                out.setdefault(block, []).append(pairs)
        return out


needs_native = pytest.mark.skipif(
    not native.available(), reason="the native collect walker is not built here")


@needs_native
@pytest.mark.parametrize("depth", [1, 2, 3])
def test_a_stage_makes_one_bulk_read_and_none_of_a_pending_pair(
        chain, serial, monkeypatch, depth):
    world, _dep = chain
    peer = _Peer(world.genesis, world.channel)
    spy = _Spy(monkeypatch, peer)
    with tracing.scope() as rec:
        assert peer.stream(world.blocks, depth) == serial[0]
        events = tracing.export(rec)["traceEvents"]
    assert all(st in ("collect", "policy") for st, _b, _p in spy.bulk)
    collects, policies = spy.bulk_calls("collect"), spy.bulk_calls("policy")
    assert all(len(calls) == 1 for calls in collects.values())
    assert all(len(calls) == 1 for calls in policies.values())
    assert spy.point == []                              # the native path reads no key by itself
    # a block collected before any metadata had landed has nothing to
    # read; the last work blocks are collected after the create blocks landed
    assert collects and max(collects) == N_BLOCKS
    for block, (pairs,) in collects.items():
        assert len(set(pairs)) == len(pairs) > 0
        assert not set(pairs) & set(spy.pending[block]), block
    # what a block's collect left out as pending is what its policy
    # read (at depth 1 too: the block before is still at its commit)
    assert policies and any(spy.pending.values())
    for block, (pairs,) in policies.items():
        assert len(set(pairs)) == len(pairs) > 0
        assert set(pairs) <= set(spy.pending[block]), block
    # the spans say the same, a block
    args = lambda name: {e["args"]["block"]: e["args"] for e in events  # noqa: E731
                         if e.get("ph") == "X" and e["name"] == name}
    for block, a in args("collect").items():
        assert a["keylevel_bulk_keys"] == sum(map(len, collects.get(block, [])))
        assert a["keylevel_point_reads"] == 0
        assert bool(a["keylevel_reads"]) <= bool(a["keylevel_bulk_keys"])
    for block, a in args("policy").items():
        if a["deferred"]:
            assert a["deferred_bulk_keys"] == sum(map(len, policies.get(block, [])))
            assert a["deferred_point_reads"] == 0
            assert 0 < a["deferred_reads"]
        else:
            assert "deferred_bulk_keys" not in a


def test_without_the_walker_a_lookup_is_a_read_and_is_remembered(hand, monkeypatch):
    """The Python collector takes no pre-pass: its lookups fall through
    to the ledger, once a key a block."""
    setup, stream = _kind_2(hand)
    again = hand.block(hand.tx("asset", (1,), value=b"one"), hand.tx("other", MAJORITY),
                       hand.tx("asset", (1,), value=b"two"))
    monkeypatch.setattr(native, "available", lambda: False)
    peer = hand.peer()
    peer.serial(setup)
    spy = _Spy(monkeypatch, peer)
    assert peer.serial(stream + [again]) == [[VALID], [VALID], [VALID, VALID, VALID]]
    assert spy.bulk == []
    assert [(b, p) for _st, b, p in spy.point] == [
        (2, (NS, "asset")), (3, (NS, "asset")), (4, (NS, "asset")), (4, (NS, "other"))]


@needs_native
def test_a_blocks_policy_stage_reads_its_own_blocks_memo(hand, monkeypatch):
    """Depth 3, the caller committing as it pulls flags: block k's
    policy stage runs after the collects of k+1 and k+2.  The transfer
    planted in k-1 decides k's transactions under the new owner, from
    k's memo: no other block's, and no read of a key by itself."""
    setup = _owned(hand) + [hand.block(hand.create("bystander", (2,)))]
    stream = [
        hand.block(hand.tx("asset", (0,), new_owners=(1,), value=b"sold")),             # k-1
        hand.block(hand.tx("asset", (1,), value=b"by-new-owner"),                       # k
                   hand.tx("bystander", (2,), value=b"k")),
        hand.block(hand.tx("asset", (0,), value=b"by-old-owner"),                       # k+1
                   hand.tx("bystander", (2,), value=b"k+1")),
        hand.block(hand.tx("bystander", (2,), value=b"k+2")),                           # k+2
    ]
    k = hand.number - 2
    peer = hand.peer()
    peer.serial(setup)
    spy = _Spy(monkeypatch, peer)
    order: list = []
    start, finish = TxValidator._start_block_traced, TxValidator._finish_block_traced
    monkeypatch.setattr(TxValidator, "_start_block_traced", lambda v, b, *a, **kw: (
        order.append(("collect", b.header.number)), start(v, b, *a, **kw))[1])
    monkeypatch.setattr(TxValidator, "_finish_block_traced", lambda v, b, *a, **kw: (
        order.append(("policy", b.header.number)), finish(v, b, *a, **kw))[1])
    assert peer.pipeline(stream, 3) == [[VALID], [VALID, VALID], [POLICY, VALID], [VALID]]
    assert order.index(("policy", k)) > order.index(("collect", k + 2))
    assert spy.point == []
    # k's collect left the asset out (k-1 was in flight) and read the
    # bystander; k's policy read the asset, once k-1 had landed
    assert spy.bulk_calls("collect")[k] == [[(NS, "bystander")]]
    assert spy.bulk_calls("policy")[k] == [[(NS, "asset")]]
    assert spy.pending[k] == {(NS, "asset"): k - 1}
    assert peer.state()["asset"][0] == b"by-new-owner"


# -- who stays out --------------------------------------------------------------


@needs_native
def test_a_ledger_without_metadata_takes_no_pre_pass_and_no_read(majority, monkeypatch):
    peer = _Peer(majority.genesis, majority.channel)
    assert peer.ledger.holds_state_metadata() is False
    spy = _Spy(monkeypatch, peer)
    gathered: list = []
    pairs_of = txvalidator._metadata_pairs
    monkeypatch.setattr(txvalidator, "_metadata_pairs",
                        lambda *a, **kw: gathered.append(1) or pairs_of(*a, **kw))
    # the glue loop parses each footprint itself, in its turn: a parse
    # and then that transaction's prepare, never all the parses first
    trail: list = []
    parse, prepare = txvalidator.parse_footprint, TxValidator._prepare_namespaces
    monkeypatch.setattr(txvalidator, "parse_footprint",
                        lambda raw: trail.append("parse") or parse(raw))

    def spy_prepare(v, w, signed, cc_id, rwset_bytes, sink, footprint=None):
        assert footprint is None                    # `prefetched` stayed None
        trail.append("prepare")
        return prepare(v, w, signed, cc_id, rwset_bytes, sink, footprint=footprint)

    monkeypatch.setattr(TxValidator, "_prepare_namespaces", spy_prepare)
    with tracing.scope() as rec:
        assert peer.stream(majority.blocks) == [list(p) for p in majority.planted]
        events = tracing.export(rec)["traceEvents"]
    assert spy.bulk == [] and spy.point == [] and gathered == []
    assert trail and trail == ["prepare", "parse"] * (len(trail) // 2)
    assert peer.validator.parallel_collect_blocks == 0
    collects = [e["args"] for e in events if e.get("name") == "collect"]
    assert len(collects) == 4
    assert all(a["keylevel_bulk_keys"] == 0 and a["keylevel_point_reads"] == 0
               and a["keylevel_reads"] == 0 for a in collects)
    policies = [e["args"] for e in events if e.get("name") == "policy"]
    assert all(a == {**a, "deferred": 0} and "deferred_bulk_keys" not in a for a in policies)


def test_faithful_mode_still_reads_a_key_a_lookup(hand, monkeypatch):
    """Upstream's cost model: a GetStateMetadata a written key a
    transaction, the same key again included."""
    from fabric_tpu.common.channelconfig import bundle_from_genesis

    setup, stream = _kind_2(hand)
    again = hand.block(hand.tx("asset", (1,), value=b"one"), hand.tx("other", MAJORITY),
                       hand.tx("asset", (1,), value=b"two"))
    peer = hand.peer()
    peer.serial(setup)
    peer.validator = TxValidator(
        "benchch", peer.ledger, bundle_from_genesis(hand.net.genesis, peer.csp), peer.csp,
        faithful=True)
    from fabric_tpu.peer.committer import Committer

    peer.committer = Committer(peer.validator, peer.ledger)
    spy = _Spy(monkeypatch, peer)
    before = txvalidator.keylevel_tally()["lookups"]
    assert peer.stream(stream + [again]) == [[VALID], [VALID], [VALID, VALID, VALID]]
    assert spy.bulk == []
    # block 3 waits for the transfer and reads in its policy stage;
    # block 4 reads its three keys, the asset twice
    assert sorted((b, p) for _st, b, p in spy.point) == sorted([
        (2, (NS, "asset")), (3, (NS, "asset")),
        (4, (NS, "asset")), (4, (NS, "other")), (4, (NS, "asset"))])
    assert txvalidator.keylevel_tally()["lookups"] - before == len(spy.point)


# -- the ledger's read -------------------------------------------------------------


def test_the_bulk_read_answers_as_the_point_read_pair_by_pair(hand):
    from fabric_tpu.ledger.txmgmt import hash_ns

    setup, stream = _randomised(hand, blocks=2)
    peer = hand.peer()
    peer.serial(setup + stream)
    ledger = peer.ledger
    hns = hash_ns(NS, COLL)
    held_pairs = list(_full_state(peer))
    pairs = held_pairs + [(NS, "never-written"), (hns, "00" * 32), ("nobody", "a0"),
                          (hash_ns(NS, "no-such-collection"), "00" * 32), (OTHER, "a0")]
    assert not ledger.may_have_state_metadata("nobody")
    got = ledger.get_state_metadata_many(pairs + pairs[:3])         # a pair twice is one entry
    assert set(got) == set(pairs) and len(got) == len(pairs)
    for ns, key in pairs:
        assert got[ns, key] == ledger.get_state_metadata(ns, key), (ns, key)
    with_parameter = [p for p in pairs if got[p].get(VALIDATION_PARAMETER)]
    assert {p[0] for p in with_parameter} == {NS, OTHER, hns}
    assert got[NS, "never-written"] == got["nobody", "a0"] == {}
    assert got[NS, "broken"] == {VALIDATION_PARAMETER: b"\xff not a policy"}
    assert ledger.get_state_metadata_many([]) == {}
    # a namespace that never stored metadata is answered without the store
    reads: list = []
    many = ledger._state.get_state_many
    ledger._state.get_state_many = lambda ps: reads.append(list(ps)) or many(ps)
    try:
        assert ledger.get_state_metadata_many([("nobody", "a0"), (NS, "a0")]) \
            == {("nobody", "a0"): {}, (NS, "a0"): ledger.get_state_metadata(NS, "a0")}
        assert ledger.get_state_metadata_many([("nobody", "a0")]) == {("nobody", "a0"): {}}
    finally:
        del ledger._state.get_state_many
    assert reads == [[(NS, "a0")]]
    # the query executor's, over the same committed view
    assert ledger.new_query_executor().get_state_metadata_many(pairs) == got
