"""Key-level (state-based) endorsement on the peer's normal, pipelined
path (`benchmarks/configs/keylevel-5org-1000tx.json`): a block's
policies are those a validator that commits every block before it
validates the next would apply, at any depth of `store_stream` and
`validate_pipeline`.  Here, on the CPU at a small size: a seeded chain
of the benchmark's own world (`benchmarks/worlds/x509-keylevel.py`)
against `store_block` one block at a time, to the flag and to the state
(values, versions AND metadata); each kind of wrong flag the pipeline
used to give, alone in a two-block stream; what an MVCC-refused
metadata write must not do; that no signature waits for a commit; that
a failed commit ends the validator's wait; that a channel without
key-level policies defers nothing; and what the tracing and the
counters say.

No number of a CPU run is a device number: the tests read counts, flags
and verdicts, never a time."""

import json
import os
import random
import sys
import threading

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from fabric_tpu.common import tracing  # noqa: E402
from fabric_tpu.csp import SWCSP  # noqa: E402

SEED = 2**31 + 40
N_BLOCKS = 8
BLOCK_TXS = 24
NS = "benchcc"
VALID, POLICY = 0, 10


# -- the benchmark's world, small ---------------------------------------------


@pytest.fixture(scope="module")
def man():
    from benchlib.manifest import Manifest

    return Manifest(ROOT)


@pytest.fixture(scope="module")
def held():
    with open(os.path.join(BENCH, "configs", "keylevel-5org-1000tx.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def kl(man, held):
    """The world's module: its `Net` and `Tx` build the hand-made chains."""
    man.world(held)
    return sys.modules["bench_worlds_x509_keylevel"]


@pytest.fixture(scope="module")
def chain(man, held):
    dep = dict(held["deployment"], block_txs=BLOCK_TXS)
    world = man.world(held)(SEED, dep, held["planted"], N_BLOCKS)
    return world, dep


def _blocks(raw):
    from fabric_tpu.protos.common import common_pb2

    return [common_pb2.Block.FromString(b) for b in raw]


class _Peer:
    """A ledger with the validator and committer a peer holds for it."""

    def __init__(self, genesis, channel, csp=None, metrics=None):
        from fabric_tpu.common.channelconfig import bundle_from_genesis
        from fabric_tpu.ledger import LedgerProvider
        from fabric_tpu.peer.committer import Committer
        from fabric_tpu.peer.txvalidator import TxValidator

        self.csp = csp or SWCSP()
        self.ledger = LedgerProvider(None).create(genesis)
        self.validator = TxValidator(
            channel, self.ledger, bundle_from_genesis(genesis, self.csp), self.csp,
            metrics=metrics)
        self.committer = Committer(self.validator, self.ledger)

    def state(self) -> dict:
        """(key) -> (value, version, metadata)."""
        return {
            key: (vv.value, (vv.version.block_num, vv.version.tx_num), vv.metadata)
            for key, vv in self.ledger._state.get_state_range(NS, "", "")
        }

    def serial(self, raw) -> list:
        return [list(self.committer.store_block(b)) for b in _blocks(raw)]

    def stream(self, raw, depth=3) -> list:
        return [list(f) for f in self.committer.store_stream(iter(_blocks(raw)), depth=depth)]

    def pipeline(self, raw, depth) -> list:
        """`validate_pipeline` as a caller that commits each block
        before it pulls the next flags drives it."""
        fed, assists, out = [], [], []

        def tee():
            for b in _blocks(raw):
                fed.append(b)
                yield b

        for k, _flags in enumerate(self.validator.validate_pipeline(
                tee(), depth=depth, rwsets_out=assists.append)):
            self.ledger.commit(fed[k], assist=assists[k])
            from fabric_tpu import protoutil

            out.append(list(protoutil.tx_filter(fed[k])))
        return out


@pytest.fixture(scope="module")
def serial(chain):
    world, _dep = chain
    peer = _Peer(world.genesis, world.channel)
    return peer.serial(world.blocks), peer.state()


def test_the_small_chain_is_of_the_cells_kind(chain, serial, man, held):
    world, dep = chain
    flags, state = serial
    assert world.block_kinds == ["create"] * 2 + ["work"] * 6
    # every class is planted wherever it is due, and the serial peer,
    # the generator's truth and the plain reference agree
    for due, heldc in zip(world.due_classes, world.planted_classes):
        assert all(heldc[c] >= 1 for c in due)
    assert world.due_classes[-1] == list(sys.modules["bench_worlds_x509_keylevel"].CLASSES)
    assert flags == [list(p) for p in world.planted]
    ref_flags, ref_states = man.reference(held)(world.public, dep, world.blocks)
    assert [list(f) for f in ref_flags] == flags
    assert {(NS, k): v[:2] for k, v in state.items()} == ref_states[-1] == world.expected_state()
    assert {0, 4, 10, 11} <= {f for fl in flags for f in fl}
    # neighbours meet: transactions that depend on the two blocks before
    assert all(d > 0 for d in world.dependent[2:])
    assert any(v[2] for v in state.values())           # parameters are in the state


@pytest.mark.parametrize("depth", [1, 2, 3])
@pytest.mark.parametrize("entry", ["store_stream", "validate_pipeline"])
def test_a_pipeline_decides_as_the_serial_validator_at_any_depth(chain, serial, entry, depth):
    world, _dep = chain
    peer = _Peer(world.genesis, world.channel)
    got = peer.stream(world.blocks, depth) if entry == "store_stream" \
        else peer.pipeline(world.blocks, depth)
    want_flags, want_state = serial
    assert got == want_flags
    assert peer.state() == want_state


# -- hand-made chains: each kind of wrong flag alone --------------------------


class _Chain:
    """Blocks made by hand on the world's own network."""

    def __init__(self, kl, held):
        self.kl = kl
        self.rng = random.Random("keylevel-stream-tests")
        self.net = kl.Net(self.rng, dict(held["deployment"], block_txs=4))
        self.number = 0

    def block(self, *txs) -> bytes:
        self.number += 1
        raw, _lanes = self.net.block(self.rng, self.number, list(txs))
        return raw

    def tx(self, key, endorsers, read=None, new_owners=None, value=None):
        return self.kl.Tx(key=key, value=value or self.rng.randbytes(8),
                          endorsers=tuple(endorsers),
                          read=self.kl.BLIND if read is None else read,
                          new_owners=None if new_owners is None else tuple(new_owners))

    def create(self, key, owners, value=None):
        return self.tx(key, (0, 1, 2), new_owners=owners, value=value)

    def peer(self, **kw) -> _Peer:
        return _Peer(self.net.genesis, "benchch", **kw)


@pytest.fixture
def hand(kl, held):
    return _Chain(kl, held)


@pytest.fixture
def stale(monkeypatch):
    """The pipeline as it was: no key is ever pending."""
    from fabric_tpu.peer.txvalidator import _KeyWindow

    def apply():
        monkeypatch.setattr(_KeyWindow, "pending", lambda self: None)
    return apply


@pytest.mark.parametrize("first_metadata", [True, False],
                         ids=["the_namespaces_first_metadata", "a_namespace_with_metadata"])
def test_an_asset_created_in_k_is_updated_in_k_plus_1_by_its_owner_alone(
        hand, stale, first_metadata):
    """Kind 1: under the old parameter the key had none, so the
    chaincode's 3 of 5 decided, and the owner's sound update was refused."""
    setup = [] if first_metadata else [hand.block(hand.create("other", (3,)))]
    two = [hand.block(hand.create("asset", (0,), value=b"made")),
           hand.block(hand.tx("asset", (0,), read=(hand.number, 0), value=b"by-owner"))]
    for decide, want in ((None, [[VALID], [VALID]]), (stale, [[VALID], [POLICY]])):
        if decide is not None:
            decide()
        peer = hand.peer()
        peer.serial(setup)
        assert peer.ledger.may_have_state_metadata(NS) is (not first_metadata)
        assert peer.stream(two) == want
        assert peer.state()["asset"][0] == (b"by-owner" if want[1] == [VALID] else b"made")


def _owned_by_org1(hand):
    return [hand.block(hand.create("asset", (0,), value=b"made"))]


def test_an_asset_transferred_in_k_is_updated_in_k_plus_1_by_its_new_owner(hand, stale):
    """Kind 2: decided under the old parameter, the new owner was refused."""
    setup = _owned_by_org1(hand)
    two = [hand.block(hand.tx("asset", (0,), read=(1, 0), new_owners=(1,), value=b"sold")),
           hand.block(hand.tx("asset", (1,), read=(2, 0), value=b"by-new-owner"))]
    for decide, want in ((None, [[VALID], [VALID]]), (stale, [[VALID], [POLICY]])):
        if decide is not None:
            decide()
        peer = hand.peer()
        peer.serial(setup)
        assert peer.stream(two) == want
        assert peer.state()["asset"][0] == (b"by-new-owner" if want[1] == [VALID] else b"sold")


def test_an_asset_transferred_in_k_is_not_written_in_k_plus_1_by_its_old_owner(hand, stale):
    """Kind 3: read version correct, so MVCC does not save it; decided
    under the old parameter it was ACCEPTED, and a write the channel's
    rules forbid landed in the state."""
    setup = _owned_by_org1(hand)
    two = [hand.block(hand.tx("asset", (0,), read=(1, 0), new_owners=(1,), value=b"sold")),
           hand.block(hand.tx("asset", (0,), read=(2, 0), value=b"by-old-owner"))]
    for decide, want in ((None, [[VALID], [POLICY]]), (stale, [[VALID], [VALID]])):
        if decide is not None:
            decide()
        peer = hand.peer()
        peer.serial(setup)
        assert peer.stream(two) == want
        assert peer.state()["asset"][0] == (b"sold" if want[1] == [POLICY] else b"by-old-owner")


def test_the_issues_three_blocks_end_by_new_owner(hand):
    """ISSUE 40's own example: Org1 hands the key to Org2, then a blind
    write by Org1 alone, then one by Org2 alone."""
    setup = _owned_by_org1(hand)
    three = [hand.block(hand.tx("asset", (0,), new_owners=(1,), value=b"sold")),
             hand.block(hand.tx("asset", (0,), value=b"by-old-owner")),
             hand.block(hand.tx("asset", (1,), value=b"by-new-owner"))]
    peer = hand.peer()
    peer.serial(setup)
    assert peer.stream(three) == [[VALID], [POLICY], [VALID]]
    assert peer.state()["asset"][0] == b"by-new-owner"


@pytest.mark.parametrize("entry", ["store_stream", "validate_pipeline"])
def test_a_metadata_write_that_mvcc_refuses_decides_nothing(hand, entry):
    """Block k's VSCC verdict is not the truth: its transfer passes its
    policy and fails MVCC, so the parameter never lands, and block k+1
    is decided under the owner the asset still has."""
    setup = _owned_by_org1(hand)
    two = [hand.block(hand.tx("asset", (0,), read=(1, 0), value=b"kept"),
                      hand.tx("other", (0, 1, 2), value=b"x"),
                      hand.tx("asset", (0,), read=(1, 0), new_owners=(1,), value=b"never")),
           hand.block(hand.tx("asset", (0,), read=(2, 0), value=b"by-owner"),
                      hand.tx("other2", (0, 1, 2), value=b"y"),
                      hand.tx("asset", (1,), read=(2, 0), value=b"by-stranger"))]
    peer = hand.peer()
    peer.serial(setup)
    got = peer.stream(two) if entry == "store_stream" else peer.pipeline(two, 3)
    assert got == [[VALID, VALID, 11], [VALID, VALID, POLICY]]
    assert peer.state()["asset"][0] == b"by-owner"


def test_a_delete_takes_the_parameter_with_the_key(hand):
    """A key deleted in k and written again in k+1 has no parameter:
    the chaincode's policy decides, as after a commit."""
    setup = _owned_by_org1(hand)
    two = [_delete_block(hand),
           hand.block(hand.tx("asset", (0,), value=b"by-old-owner-alone")),
           hand.block(hand.tx("asset", (2, 3, 4), value=b"by-a-majority"))]
    want = None
    for run in ("serial", "stream"):
        peer = hand.peer()
        peer.serial(setup)
        got = peer.serial(two) if run == "serial" else peer.stream(two)
        want = want or got
        assert got == want == [[VALID], [POLICY], [VALID]]
        assert peer.state()["asset"][0] == b"by-a-majority"


def _delete_block(hand) -> bytes:
    """The next block: one transaction that deletes the asset, endorsed
    by its owner (the world's `Tx` makes no delete)."""
    from fabric_tpu import protoutil
    from fabric_tpu.protos.common import common_pb2
    from fabric_tpu.protos.ledger.rwset import rwset_pb2
    from fabric_tpu.protos.ledger.rwset.kvrwset import kv_rwset_pb2
    from fabric_tpu.protos.peer import chaincode_pb2, proposal_pb2

    kv = kv_rwset_pb2.KVRWSet()
    kv.writes.add(key="asset", is_delete=True)
    results = rwset_pb2.TxReadWriteSet(data_model=rwset_pb2.TxReadWriteSet.KV)
    results.ns_rwset.add(namespace=NS, rwset=kv.SerializeToString())
    net = hand.net
    prop, _txid = protoutil.create_chaincode_proposal(
        net.client.serialize(), "benchch", NS, [b"asset"], nonce=hand.rng.randbytes(24))
    resp = protoutil.create_proposal_response(
        prop, results=results.SerializeToString(), events=b"",
        response=proposal_pb2.Response(status=200),
        chaincode_id=chaincode_pb2.ChaincodeID(name=NS), endorser_signer=net.peers[0])
    env = protoutil.create_signed_tx(prop, net.client, [resp])
    hand.number += 1
    blk = common_pb2.Block()
    blk.header.number = hand.number
    blk.data.data.append(env.SerializeToString())
    while len(blk.metadata.metadata) < 3:
        blk.metadata.metadata.append(b"")
    return blk.SerializeToString()


# -- no signature waits; no wait hangs ----------------------------------------


class _Recording(SWCSP):
    """Says when each block's lanes were handed to the provider."""

    def __init__(self):
        super().__init__()
        self.batches: list = []

    def verify_batch_async(self, items, flush=False):
        self.batches.append(len(items))
        return super().verify_batch_async(items, flush)


def _in_thread(fn):
    out: dict = {}

    def run():
        try:
            out["value"] = fn()
        except BaseException as e:      # noqa: BLE001 - handed to the test
            out["error"] = e

    from fabric_tpu.devtools.lockwatch import spawn_thread

    th = spawn_thread(target=run, name="keylevel-stream-test", kind="worker")
    th.start()
    return th, out


def _wait_for(predicate, seconds=20.0) -> bool:
    import time

    end = time.monotonic() + seconds
    while time.monotonic() < end:
        if predicate():
            return True
        time.sleep(0.01)
    return predicate()


def test_no_signature_waits_for_a_commit(hand):
    """With the committer held at a gate, block k+1's lanes are at the
    provider before block k's commit is released, and its flags are
    not yielded."""
    from fabric_tpu.peer.txvalidator import keylevel_tally

    setup = _owned_by_org1(hand)
    two = [hand.block(hand.tx("asset", (0,), read=(1, 0), new_owners=(1,), value=b"sold")),
           hand.block(hand.tx("asset", (1,), read=(2, 0), value=b"by-new-owner"))]
    csp = _Recording()
    peer = hand.peer(csp=csp)
    peer.serial(setup)
    del csp.batches[:]
    gate, entered = threading.Event(), threading.Event()
    commit = peer.ledger.commit

    def held_commit(block, **kw):
        entered.set()
        assert gate.wait(30)
        return commit(block, **kw)

    peer.ledger.commit = held_commit
    yielded: list = []
    before = keylevel_tally()

    def drive():
        for flags in peer.committer.store_stream(iter(_blocks(two))):
            yielded.append(list(flags))

    th, out = _in_thread(drive)
    try:
        assert entered.wait(20)                      # block k is at its commit
        assert _wait_for(lambda: len(csp.batches) == 2)
        # both blocks' lanes (a creator and an endorsement each) are
        # with the provider; block k's commit has not begun, so nothing
        # is announced and block k+1 is not decided
        assert csp.batches == [2, 2] and yielded == []
        assert not _wait_for(lambda: bool(yielded), seconds=0.3)
    finally:
        gate.set()
        th.join(30)
    assert not th.is_alive() and "error" not in out
    assert yielded == [[VALID], [VALID]]
    after = keylevel_tally()
    assert after["deferred"] - before["deferred"] == 1
    assert after["waits"] - before["waits"] == 1
    assert after["recent_blocks"][-2:] == [(2, 0), (3, 1)]


def test_a_commit_that_fails_ends_the_validators_wait(hand):
    setup = _owned_by_org1(hand)
    two = [hand.block(hand.tx("asset", (0,), read=(1, 0), new_owners=(1,), value=b"sold")),
           hand.block(hand.tx("asset", (1,), read=(2, 0), value=b"by-new-owner"))]
    peer = hand.peer()
    peer.serial(setup)

    def failing_commit(block, **kw):
        raise RuntimeError("the disk is gone")

    peer.ledger.commit = failing_commit
    th, out = _in_thread(lambda: list(peer.committer.store_stream(iter(_blocks(two)))))
    th.join(30)
    assert not th.is_alive(), "the validator still waits for a commit that failed"
    assert isinstance(out.get("error"), RuntimeError) and "the disk is gone" in str(out["error"])


def test_a_committer_thread_that_dies_ends_the_stream(hand):
    """Not an Exception: the thread dies, and neither the validator
    (on the commit) nor the consumer (on the flags) waits for ever."""
    from fabric_tpu.devtools import faultline

    setup = _owned_by_org1(hand)
    two = [hand.block(hand.tx("asset", (0,), read=(1, 0), new_owners=(1,), value=b"sold")),
           hand.block(hand.tx("asset", (1,), read=(2, 0), value=b"by-new-owner"))]
    peer = hand.peer()
    peer.serial(setup)

    def dying_commit(block, **kw):
        raise faultline.FaultCrash("the process is gone")

    peer.ledger.commit = dying_commit
    th, out = _in_thread(lambda: list(peer.committer.store_stream(iter(_blocks(two)))))
    th.join(30)
    assert not th.is_alive()
    assert isinstance(out.get("error"), RuntimeError)
    assert isinstance(out["error"].__cause__, faultline.FaultCrash)


# -- a channel without key-level policies -------------------------------------


@pytest.fixture(scope="module")
def majority(man):
    with open(os.path.join(BENCH, "configs", "majority5-1000tx.json")) as f:
        cfg = json.load(f)
    dep = dict(cfg["deployment"], block_txs=12)
    return man.world(cfg)(SEED, dep, cfg["planted"], 4)


def test_a_majority5_stream_defers_nothing_and_takes_no_wait(majority):
    from fabric_tpu.peer.txvalidator import keylevel_tally

    peer = _Peer(majority.genesis, majority.channel)
    before = keylevel_tally()
    with tracing.scope() as rec:
        got = peer.stream(majority.blocks)
        events = tracing.export(rec)["traceEvents"]
    assert got == [list(p) for p in majority.planted]
    after = keylevel_tally()
    assert {k: after[k] - before[k] for k in ("lookups", "deferred", "waits")} \
        == {"lookups": 0, "deferred": 0, "waits": 0}
    assert after["recent_blocks"][-4:] == [(1, 0), (2, 0), (3, 0), (4, 0)]
    names = {e.get("name") for e in events}
    assert "policy.await_commit" not in names
    policies = [e["args"] for e in events if e.get("name") == "policy"]
    assert len(policies) == 4 and all(a["deferred"] == 0 for a in policies)
    collects = [e["args"] for e in events if e.get("name") == "collect"]
    assert all(a["keylevel_reads"] == 0 and a["keylevel_policies"] == 0 for a in collects)
    assert "await_commit" not in peer.validator.validate_stage_seconds


# -- tracing and counters ------------------------------------------------------


def test_disarmed_the_new_sites_consult_nothing(chain):
    """Off, a site is a global load and an `is None` test: the armed
    path's counter stays where it was through a whole pass, deferred
    decisions and waits included."""
    world, _dep = chain
    peer = _Peer(world.genesis, world.channel)
    assert not tracing.enabled()
    before = tracing.lookup_count()
    peer.stream(world.blocks)
    assert tracing.lookup_count() == before
    assert peer.validator.validate_stage_seconds.get("await_commit", 0.0) > 0.0


def test_the_spans_and_the_counters_say_what_the_stream_held(chain, serial):
    from fabric_tpu.common.operations import System
    from fabric_tpu.peer.txvalidator import keylevel_tally

    world, _dep = chain
    ops = System()
    peer = _Peer(world.genesis, world.channel, metrics=ops.validate_metrics())
    text = ops.metrics_provider.registry.expose()
    before = keylevel_tally()
    with tracing.scope() as rec:
        assert peer.stream(world.blocks) == serial[0]
        events = tracing.export(rec)["traceEvents"]
    after = keylevel_tally()
    spans = {}
    for e in events:
        if e.get("ph") == "X":
            spans.setdefault(e["name"], []).append(e)
    by_block = lambda name: {e["args"]["block"]: e["args"] for e in spans[name]}  # noqa: E731
    collects, policies, waits = by_block("collect"), by_block("policy"), \
        by_block("policy.await_commit")
    # a create block reads no metadata (the namespace has none, or its
    # keys are new): nothing deferred, no wait
    assert policies[1]["deferred"] == 0 and 1 not in waits
    deferred = {b: a["deferred"] for b, a in policies.items()}
    # at depth 3 the two blocks before are in flight for sure when a
    # block is collected; how many more are depends on how far the
    # commits lag, so the world's count is the floor, block by block
    for b in range(1, N_BLOCKS + 1):
        assert world.dependent[b - 1] <= deferred[b] <= BLOCK_TXS, b
    assert sorted(waits) == sorted(b for b, n in deferred.items() if n)
    for b, a in waits.items():
        assert a["txs"] == deferred[b] and 1 <= a["waits_on"] < b
        # a lookup a deferred transaction (it writes one key), less those
        # refused before their policy (a creator's signature, the in-block rule)
        assert 0 < policies[b]["deferred_reads"] <= deferred[b]
    # a span a block, never a span a key; the wait is a stage span on
    # the validator's thread, so the device's idle gaps carry its name
    assert len(spans["policy.await_commit"]) == len(waits)
    assert all(e["cat"] == "stage" and e["tid"] == spans["policy"][0]["tid"]
               for e in spans["policy.await_commit"])
    lookups = sum(a["keylevel_reads"] for a in collects.values()) \
        + sum(a.get("deferred_reads", 0) for a in policies.values())
    assert lookups > 0 and all(a["keylevel_ms"] >= 0.0 for a in collects.values())
    # one bulk read a stage fetched what they asked for; a lookup it did
    # not cover went to the ledger by itself (none, with the native walker)
    stages = [(a["keylevel_reads"], a["keylevel_bulk_keys"], a["keylevel_point_reads"])
              for a in collects.values()]
    stages += [(a["deferred_reads"], a["deferred_bulk_keys"], a["deferred_point_reads"])
               for a in policies.values() if a["deferred"]]
    assert all(0 <= point <= reads and (bulk or point or not reads)
               for reads, bulk, point in stages)
    point_reads = sum(point for _reads, _bulk, point in stages)
    assert sum(bulk for _reads, bulk, _point in stages) > 0 or point_reads == lookups
    assert max(a["keylevel_policies"] for a in collects.values()) >= 2
    assert sum(a["plan_misses"] for a in collects.values()) >= 2
    assert sum(a["plan_hits"] for a in collects.values()) > 0
    # the process's tally and the peer's page say the same
    assert after["lookups"] - before["lookups"] == lookups
    assert after["deferred"] - before["deferred"] == sum(deferred.values())
    assert after["waits"] - before["waits"] == len(waits)
    assert after["recent_blocks"][-N_BLOCKS:] == sorted(deferred.items())
    for name in ("validator_keylevel_lookups_total", "validator_keylevel_deferred_total",
                 "validator_keylevel_point_reads_total", "validator_plan_cache_total"):
        assert name in text                       # on the page from the start
    text = ops.metrics_provider.registry.expose()
    assert f'validator_keylevel_lookups_total{{channel="benchch"}} {lookups}' in text
    if point_reads:
        assert (f'validator_keylevel_point_reads_total{{channel="benchch"}} '
                f'{point_reads}') in text
    assert (f'validator_keylevel_deferred_total{{channel="benchch"}} '
            f'{sum(deferred.values())}') in text
    assert 'validator_plan_cache_total{outcome="hit"}' in text
    assert 'validator_plan_cache_total{outcome="miss"}' in text
    assert 'stage="await_commit"' in text
