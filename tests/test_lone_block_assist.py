"""A lone block is committed with what its validation already learned:
`Committer.store_block` hands `KVLedger.commit` the `CommitAssist` that
`store_stream` hands it with every block.  Here, on the CPU at a small
size, for the `x509-majority` world at 1-of-1 and 3-of-5: whatever a
block holds (planted bad creators, bad endorsements and conflicting
pairs, a CONFIG transaction, duplicate txids, envelopes mangled so that
the native collect hands them to `_collect_tx`), `store_block` leaves
the flags, the block file's bytes, the txid index, the history and the
state that `validate` and a bare `ledger.commit(block)` leave on a twin
ledger; a healthy block through `store_block` is parsed by neither
`extract_rwsets` nor `BlockStore._parse_txid`; the private-data
coordinator hands the same over; and the span and the counter say which
blocks came assisted, at no cost while tracing is off.

No number of a CPU run is a device number: the tests read counts, flags
and bytes, never a time."""

import os
import random
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from fabric_tpu import protoutil  # noqa: E402
from fabric_tpu.common import tracing  # noqa: E402
from fabric_tpu.csp import SWCSP  # noqa: E402
from fabric_tpu.ledger import LedgerProvider, blkstorage, kvledger  # noqa: E402
from fabric_tpu.peer.committer import Committer  # noqa: E402
from fabric_tpu.peer.txvalidator import TxValidator  # noqa: E402
from fabric_tpu.protos.common import common_pb2  # noqa: E402

SEED = 2**31 + 35
BLOCK_TXS = 16
N_BLOCKS = 2
PLANTED = {"bad_creator_per_block": 2, "bad_endorsement_per_block": 2,
           "conflict_pairs_per_block": 2}
HEALTHY = dict.fromkeys(PLANTED, 0)
WORLDS = {
    "1of1": {"orgs": 1, "endorsers_per_tx": 1},
    "3of5": {"orgs": 5, "endorsers_per_tx": 3},
}
VALID, BAD_CREATOR, DUPLICATE_TXID, POLICY_FAILURE, MVCC_CONFLICT = 0, 4, 9, 10, 11


def _world(kind: str, planted: dict):
    from benchlib.generator import build_world

    dep = dict(WORLDS[kind], block_txs=BLOCK_TXS, value_bytes=32)
    return build_world(SEED, dep, planted, N_BLOCKS)


@pytest.fixture(scope="module", params=sorted(WORLDS))
def world(request):
    return _world(request.param, PLANTED)


@pytest.fixture(scope="module", params=sorted(WORLDS))
def healthy(request):
    return _world(request.param, HEALTHY)


class Peer:
    """A ledger on disk with its validator and committer."""

    def __init__(self, world, root, python_collect=False, ledger_metrics=None):
        from fabric_tpu.common.channelconfig import bundle_from_genesis

        csp = SWCSP()
        self.root = str(root)
        self.provider = LedgerProvider(self.root, ledger_metrics=ledger_metrics)
        self.ledger = self.provider.create(world.genesis)
        self.validator = TxValidator(
            world.channel, self.ledger, bundle_from_genesis(world.genesis, csp), csp
        )
        if python_collect:
            self.validator._collect_native = lambda *a, **k: False
        self.committer = Committer(self.validator, self.ledger)

    def store_block(self, raw: bytes) -> list:
        return self.committer.store_block(common_pb2.Block.FromString(raw))

    def bare_commit(self, raw: bytes) -> list:
        """What `store_block` did before: the validator's flags, then a
        commit that is handed nothing."""
        block = common_pb2.Block.FromString(raw)
        self.validator.validate(block)
        self.ledger.commit(block)
        return list(protoutil.tx_filter(block))

    def block_files(self) -> dict:
        out = {}
        chains = os.path.join(self.root, self.ledger.ledger_id, "chains")
        for dirpath, _dirs, files in os.walk(chains):
            for name in files:
                path = os.path.join(dirpath, name)
                with open(path, "rb") as f:
                    out[os.path.relpath(path, chains)] = f.read()
        return out

    def answers(self, world, raws) -> dict:
        """What a client can ask the ledger about these blocks."""
        ledger = self.ledger
        txids = sorted({
            t for raw in raws
            for e in common_pb2.Block.FromString(raw).data.data
            for t in [blkstorage.BlockStore._parse_txid(e)] if t
        })
        state = {
            (ns, key): (vv.value, (vv.version.block_num, vv.version.tx_num))
            for ns in world.namespaces
            for key, vv in ledger._state.get_state_range(ns, "", "")
        }
        keys = sorted({(ns, key) for wrote in world.writes for key, _v in wrote
                       for ns in world.namespaces})
        return {
            "height": ledger.height,
            "tx_by_id": {t: ledger.get_tx_by_id(t).SerializeToString() for t in txids},
            "tx_code": {t: ledger.get_tx_validation_code(t) for t in txids},
            "blocks": [ledger.get_block_by_number(n).SerializeToString()
                       for n in range(ledger.height)],
            "history": {k: ledger.get_history_for_key(*k) for k in keys},
            "state": state,
        }

    def close(self):
        self.provider.close()


# -- what a block may hold ----------------------------------------------------


def _config_envelope(world) -> bytes:
    signer = world.orgs[0].signer(random.Random(35), "admin", "client")
    creator, nonce = signer.serialize(), bytes(range(24))
    chdr = protoutil.make_channel_header(
        common_pb2.CONFIG, world.channel,
        tx_id=protoutil.compute_tx_id(nonce, creator), timestamp=0,
    )
    payload = protoutil.make_payload_bytes(
        chdr, protoutil.make_signature_header(creator, nonce), b"a config update"
    )
    return protoutil.make_envelope(payload, signer).SerializeToString()


def _with_envelopes(raw: bytes, change) -> bytes:
    block = common_pb2.Block.FromString(raw)
    envs = list(block.data.data)
    change(envs)
    del block.data.data[:]
    block.data.data.extend(envs)
    return block.SerializeToString()


def _second_block(world, what: str) -> tuple:
    """(the second block's bytes, {position: the flag it must get})."""
    first = list(common_pb2.Block.FromString(world.blocks[0]).data.data)
    raw = world.blocks[1]
    n = BLOCK_TXS
    if what == "planted":
        return raw, dict(enumerate(world.planted[1]))
    if what == "config_tx":
        return (_with_envelopes(raw, lambda envs: envs.insert(5, _config_envelope(world))),
                {5: VALID})
    if what == "duplicate_txid":
        # one the ledger holds already, and one twice in this block
        def change(envs):
            envs.append(first[0])
            envs.append(next(e for e, f in zip(envs, world.planted[1]) if f == VALID))
        return _with_envelopes(raw, change), {n: DUPLICATE_TXID, n + 1: DUPLICATE_TXID}
    if what == "mangled_envelope":
        def change(envs):
            good = envs[2]
            envs[2] = good[:-9]                       # cut short
            envs.append(b"\xff\x01 not an envelope")
            env = common_pb2.Envelope.FromString(good)
            env.payload = env.payload[: len(env.payload) // 2]
            envs.append(env.SerializeToString())      # an envelope, no payload
        return _with_envelopes(raw, change), {}
    raise AssertionError(what)


@pytest.mark.parametrize("collect", ["native", "python"])
@pytest.mark.parametrize(
    "what", ["planted", "config_tx", "duplicate_txid", "mangled_envelope"])
def test_store_block_leaves_what_the_unassisted_commit_leaves(world, tmp_path, what, collect):
    second, expected = _second_block(world, what)
    raws = [world.blocks[0], second]
    a = Peer(world, tmp_path / "assisted", python_collect=collect == "python")
    b = Peer(world, tmp_path / "bare", python_collect=collect == "python")
    try:
        for number, raw in enumerate(raws, start=1):
            got = a.store_block(raw)
            assert got == b.bare_commit(raw), f"block {number}"
            if number == 1:
                assert got == world.planted[0]
        assert {i: got[i] for i in expected} == expected
        if what == "mangled_envelope":
            assert VALID not in (got[2], got[-2], got[-1])
        assert {BAD_CREATOR, POLICY_FAILURE, MVCC_CONFLICT} <= set(got)
        files = a.block_files()
        assert files and files == b.block_files()
        assert a.answers(world, raws) == b.answers(world, raws)
    finally:
        a.close()
        b.close()


# -- the mechanism ------------------------------------------------------------


@pytest.fixture
def parses(monkeypatch):
    """How often the ledger parsed envelopes for itself."""
    calls = {"extract_rwsets": 0, "_parse_txid": 0}
    extract, parse = kvledger.extract_rwsets, blkstorage.BlockStore._parse_txid

    def counted_extract(block):
        calls["extract_rwsets"] += 1
        return extract(block)

    def counted_parse(raw_env):
        calls["_parse_txid"] += 1
        return parse(raw_env)

    monkeypatch.setattr(kvledger, "extract_rwsets", counted_extract)
    monkeypatch.setattr(blkstorage.BlockStore, "_parse_txid", staticmethod(counted_parse))
    return calls


@pytest.mark.parametrize("entry", ["store_block", "bare_commit"])
def test_a_healthy_lone_block_is_parsed_by_the_validator_alone(healthy, tmp_path, parses, entry):
    peer = Peer(healthy, tmp_path / "ledger")
    try:
        parses.update(extract_rwsets=0, _parse_txid=0)     # the genesis block's
        for raw in healthy.blocks:
            assert getattr(peer, entry)(raw) == [VALID] * BLOCK_TXS
        if entry == "store_block":
            assert parses == {"extract_rwsets": 0, "_parse_txid": 0}
            # handed over once: nothing of a committed block stays behind
            assert peer.validator.take_assist() is None
        else:
            assert parses == {"extract_rwsets": N_BLOCKS, "_parse_txid": N_BLOCKS * BLOCK_TXS}
    finally:
        peer.close()


def test_an_assist_is_one_blocks_and_is_handed_over_once(healthy, tmp_path, monkeypatch):
    peer = Peer(healthy, tmp_path / "ledger")
    try:
        block = common_pb2.Block.FromString(healthy.blocks[0])
        peer.validator.validate(block)
        assist = peer.validator.take_assist()
        assert peer.validator.take_assist() is None
        assert assist.env_bytes == list(block.data.data)
        assert len(assist.rwsets) == len(assist.footprints) == len(assist.txids) == BLOCK_TXS
        assert None not in assist.rwsets + assist.footprints + assist.txids
        # a validation that raises leaves no earlier block's assist behind
        peer.validator.validate(block)

        def verify_fails(*_a, **_k):
            raise RuntimeError("the device is gone")

        monkeypatch.setattr(peer.validator, "_finish_block", verify_fails)
        with pytest.raises(RuntimeError):
            peer.validator.validate(block)
        assert peer.validator.take_assist() is None
    finally:
        peer.close()


def test_no_assist_is_in_hand_when_the_lone_block_is_collected(healthy, tmp_path, monkeypatch):
    """`store_block` ends with `gcpolicy.pipeline_empty()`: the block's
    footprints are gone by then, or every collection walks them."""
    import weakref

    from fabric_tpu.peer import committer

    seen, alive_at_collection = [], []
    validate_for_commit = committer.validate_for_commit

    def watched(validator, block):
        assist = validate_for_commit(validator, block)
        seen.append(weakref.ref(assist))
        return assist

    monkeypatch.setattr(committer, "validate_for_commit", watched)
    monkeypatch.setattr(committer.gcpolicy, "pipeline_empty",
                        lambda: alive_at_collection.append(seen[-1]() is not None))
    peer = Peer(healthy, tmp_path / "ledger")
    try:
        for raw in healthy.blocks:
            peer.store_block(raw)
        assert alive_at_collection == [False] * N_BLOCKS
    finally:
        peer.close()


def test_the_private_data_coordinator_hands_the_same_over(healthy, tmp_path, parses):
    from fabric_tpu.common.privdata import CollectionStore
    from fabric_tpu.gossip.privdata import PrivDataCoordinator
    from fabric_tpu.ledger.kvstore import MemKVStore
    from fabric_tpu.ledger.transientstore import TransientStore

    a, b = Peer(healthy, tmp_path / "coordinator"), Peer(healthy, tmp_path / "bare")
    try:
        coordinator = PrivDataCoordinator(
            a.validator, a.ledger, TransientStore(MemKVStore(), healthy.channel),
            CollectionStore(None), b"nobody",
        )
        parses.update(extract_rwsets=0, _parse_txid=0)
        for raw in healthy.blocks:
            assert coordinator.store_block(common_pb2.Block.FromString(raw)) \
                == [VALID] * BLOCK_TXS
        # block_pvt_requirements walks each block once, before the commit:
        # the ledger itself parses nothing
        assert parses == {"extract_rwsets": N_BLOCKS, "_parse_txid": 0}
        for raw in healthy.blocks:
            b.bare_commit(raw)
        assert a.block_files() == b.block_files()
        assert a.answers(healthy, healthy.blocks) == b.answers(healthy, healthy.blocks)
    finally:
        a.close()
        b.close()


# -- what says that it engaged ------------------------------------------------


def test_the_span_and_the_counter_say_which_blocks_came_assisted(healthy, tmp_path):
    from fabric_tpu.common.metrics import LedgerMetrics, PrometheusProvider

    metrics = PrometheusProvider()
    with tracing.scope() as rec:
        peer = Peer(healthy, tmp_path / "ledger", ledger_metrics=LedgerMetrics(metrics))
        try:
            peer.store_block(healthy.blocks[0])
            peer.bare_commit(healthy.blocks[1])
        finally:
            peer.close()
        events = tracing.export(rec)["traceEvents"]
    appends = {e["args"]["block"]: e for e in events if e["name"] == "block_append"}
    assert {n: e["args"]["assisted"] for n, e in appends.items()} \
        == {0: False, 1: True, 2: False}
    # the lone block's commit stages still join its validator's trace
    roots = {e["args"]["block"]: e["args"]["span"] for e in events if e["name"] == "block"}
    assert appends[1]["args"]["parent"] == roots[1]
    text = metrics.registry.expose()
    channel = healthy.channel
    assert f'ledger_commit_assist_total{{assist="full",channel="{channel}"}} 1' in text
    assert f'ledger_commit_assist_total{{assist="none",channel="{channel}"}} 2' in text
    assert f'ledger_blocks_committed_total{{channel="{channel}"}} 3' in text


def test_tracing_off_the_assisted_commit_consults_nothing(healthy, tmp_path):
    """The `assisted` attribute rides the `block_append` site that was
    there: disarmed, a lone block's validate and commit reach no armed
    path (as `test_manyclients.py` pins the collect's sites)."""
    assert not tracing.enabled()
    peer = Peer(healthy, tmp_path / "ledger")
    try:
        before = tracing.lookup_count()
        assert peer.store_block(healthy.blocks[0]) == [VALID] * BLOCK_TXS
        assert tracing.lookup_count() == before
    finally:
        peer.close()
