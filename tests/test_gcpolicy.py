"""PR 26: a block's objects die young.  The MSP cache's negative
outcomes carry no traceback from one call to the next, nothing of a
pass stays reachable once its ledger is closed, and the process-wide
collector policy (common/gcpolicy.py) is idempotent and leaves the
collector enabled.  Counts and identities only: no time is read.
"""

from __future__ import annotations

import datetime
import gc
import json
import os
import sys
import weakref

import pytest

from orgfix import make_org

from fabric_tpu.common import gcpolicy
from fabric_tpu.msp import MSPError, MSPManager, SigningIdentity
from fabric_tpu.msp.cache import CachedMSP
from fabric_tpu.protos.msp import msp_principal_pb2 as mp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HITS = 1000


class _Local:
    """Something a calling frame holds while the cache raises."""


def _tb_len(exc: BaseException) -> int:
    n, tb = 0, exc.__traceback__
    while tb is not None:
        n, tb = n + 1, tb.tb_next
    return n


def _cached_failures():
    """(name, call) per cached negative outcome: a peer of Org2 tested
    against Org1's MEMBER principal, and an expired certificate."""
    org1, org2 = make_org("Org1MSP"), make_org("Org2MSP")
    cached = CachedMSP(MSPManager([org1.msp, org2.msp]))
    peer2 = cached.deserialize_identity(org2.signer("peer0").serialize())
    principal = mp.MSPPrincipal(
        principal_classification=mp.MSPPrincipal.ROLE,
        principal=mp.MSPRole(
            msp_identifier="Org1MSP", role=mp.MSPRole.MEMBER
        ).SerializeToString(),
    )
    past = datetime.datetime.now(datetime.timezone.utc) - datetime.timedelta(days=1)
    pair = org1.ca.issue("old", ous=["peer"], not_after=past)
    expired = SigningIdentity.from_pem("Org1MSP", pair.cert_pem, pair.key_pem, org1.csp)
    return {
        "satisfies_principal": lambda: cached.satisfies_principal(peer2, principal),
        "validate": lambda: cached.validate(expired),
    }


@pytest.mark.parametrize("name", ["satisfies_principal", "validate"])
def test_a_cached_failure_is_raised_fresh_on_every_hit(name):
    call = _cached_failures()[name]
    refs, seen, lengths = [], set(), []

    def caller():
        local = _Local()
        refs.append(weakref.ref(local))
        try:
            call()
        except MSPError as exc:
            seen.add((type(exc), str(exc)))
            lengths.append(_tb_len(exc))
            return
        raise AssertionError("the cached failure did not raise")

    caller()                       # the miss: the inner MSP raises
    assert refs[-1]() is None
    for _ in range(HITS):
        caller()
    # the same type and message every time, and a traceback that holds
    # the frames of ONE call: had the cached object been raised again
    # its chain would have grown by two entries a hit
    assert len(seen) == 1
    assert max(lengths[1:]) == min(lengths[1:]) <= lengths[0]
    with pytest.raises(MSPError) as one:
        call()
    with pytest.raises(MSPError) as other:
        call()
    assert one.value is not other.value
    # what the calling frame held died with the frame, with no help
    # from the cyclic collector
    assert all(r() is None for r in refs)


def test_an_error_type_that_cannot_be_copied_is_not_cached():
    class Odd(Exception):
        def __init__(self, a, b):
            super().__init__(f"{a}{b}")

    class Inner:
        calls = 0

        def satisfies_principal(self, identity, principal):
            Inner.calls += 1
            raise Odd("no", "pe")

    org = make_org()
    ident = org.signer("peer0")
    principal = mp.MSPPrincipal(principal=b"x")
    cached = CachedMSP(Inner())
    for _ in range(3):
        with pytest.raises(Odd, match="nope"):
            cached.satisfies_principal(ident, principal)
    assert Inner.calls == 3


# -- nothing of a pass outlives its ledger -----------------------------------

PER_TX = ("_PlanPending", "_TxWork", "RwsetFootprint")


def _world(config: str, block_txs: int, n_blocks: int):
    """The benchmark's own generator at a tiny size: the deployments of
    `benchmarks/configs/` are the ones PERF.md's count was made on."""
    bench = os.path.join(ROOT, "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from benchlib import generator

    with open(os.path.join(bench, "configs", f"{config}.json")) as f:
        cfg = json.load(f)
    deployment = dict(cfg["deployment"], block_txs=block_txs)
    return generator.build_world(26, deployment, cfg["planted"], n_blocks)


class _Peer:
    """A fresh on-disk ledger with the validator and committer a peer
    holds for it, and the world's blocks parsed anew."""

    def __init__(self, world, path, csp, bundle):
        from benchlib import generator

        from fabric_tpu.ledger import LedgerProvider
        from fabric_tpu.peer.committer import Committer
        from fabric_tpu.peer.txvalidator import TxValidator
        from fabric_tpu.protos.common import common_pb2

        self.provider = LedgerProvider(str(path))
        self.ledger = self.provider.create(world.genesis)
        self.committer = Committer(
            TxValidator(generator.CHANNEL, self.ledger, bundle, csp), self.ledger
        )
        self.blocks = [common_pb2.Block.FromString(b) for b in world.blocks]


def _sw_bundle(world):
    from fabric_tpu.common.channelconfig import bundle_from_genesis
    from fabric_tpu.csp import SWCSP

    csp = SWCSP()
    return csp, bundle_from_genesis(world.genesis, csp)


@pytest.mark.parametrize("config", ["majority5-1000tx", "solo1-500tx"])
def test_no_per_tx_object_is_reachable_after_three_passes(config, tmp_path):
    """PERF.md Findings PR 23 (1), the count made by hand: 1,800 of
    each after 3 passes of 4 x 200 tx in the 3-of-5 world, none in
    1-of-1.  The cause was the cached exception's traceback."""
    world = _world(config, block_txs=24, n_blocks=3)
    csp, bundle = _sw_bundle(world)

    def one_pass(n: int) -> int:
        peer = _Peer(world, tmp_path / f"ledger{n}", csp, bundle)
        out = sum(1 for _ in peer.committer.store_stream(iter(peer.blocks)))
        peer.provider.close()
        return out

    assert [one_pass(n) for n in range(3)] == [3, 3, 3]
    # a frozen object is in no generation gc.get_objects() lists: thaw
    # what the policy froze meanwhile, so that nothing hides there
    gc.unfreeze()
    gc.collect()
    left = {}
    for o in gc.get_objects():
        name = type(o).__name__
        if name in PER_TX:
            left[name] = left.get(name, 0) + 1
    assert left == {}


# -- the collector policy -----------------------------------------------------


def test_the_collector_policy_is_idempotent_and_leaves_collection_on(monkeypatch):
    monkeypatch.setattr(gcpolicy, "_settled", False)
    saved = gc.get_threshold()
    gc.unfreeze()
    try:
        assert gcpolicy.settle() is True
        assert gc.isenabled()
        frozen = gc.get_freeze_count()
        assert frozen > 0
        assert gc.get_threshold() == (gcpolicy.GEN0_THRESHOLD, 10, 10)
        # a second call neither freezes again nor touches the thresholds
        gc.set_threshold(1234, 5, 6)
        held = [[i] for i in range(1000)]       # tracked, alive, unfrozen
        assert gcpolicy.settle() is False
        assert gc.get_freeze_count() == frozen
        assert gc.get_threshold() == (1234, 5, 6)
        # absorb() is the explicit second freeze (a cold kernel shape)
        gcpolicy.absorb()
        assert gc.get_freeze_count() >= frozen + len(held)
        assert gc.isenabled()
    finally:
        gc.set_threshold(*saved)
        gc.unfreeze()


def test_absorb_is_nothing_in_a_process_that_has_not_settled(monkeypatch):
    monkeypatch.setattr(gcpolicy, "_settled", False)
    gc.unfreeze()
    gcpolicy.absorb()
    assert gc.get_freeze_count() == 0


def test_an_empty_pipeline_collects_young_and_every_tenth_time_all(monkeypatch):
    """`pipeline_empty()` is where the young generations turn over: a
    generation-1 collection, a full one every GEN2_THRESHOLD-th time
    (CPython's ratio), nothing at all before `settle()`."""
    seen = []

    def watch(phase, info):
        if phase == "stop":
            seen.append(info["generation"])

    monkeypatch.setattr(gcpolicy, "_settled", False)
    gc.callbacks.append(watch)
    try:
        gcpolicy.pipeline_empty()
        assert seen == []
        monkeypatch.setattr(gcpolicy, "_settled", True)
        gc.collect()                      # the counts start from zero
        del seen[:]
        for _ in range(2 * gcpolicy.GEN2_THRESHOLD + 2):
            gcpolicy.pipeline_empty()
    finally:
        gc.callbacks.remove(watch)
    ours = [g for g in seen if g >= 1]    # (a young one may have joined)
    assert ours.count(2) == 2 and ours.count(1) == 2 * gcpolicy.GEN2_THRESHOLD
    assert ours[gcpolicy.GEN2_THRESHOLD] == 2
    # a cycle made and dropped between blocks is gone after the call
    class Node:
        pass

    a, b = Node(), Node()
    a.other, b.other = b, a
    ref = weakref.ref(a)
    del a, b
    gcpolicy.pipeline_empty()
    assert ref() is None


def test_store_block_and_store_stream_end_on_an_empty_pipeline(monkeypatch, tmp_path):
    world = _world("solo1-500tx", block_txs=12, n_blocks=3)
    peer = _Peer(world, tmp_path / "ledger", *_sw_bundle(world))
    calls = []
    monkeypatch.setattr(gcpolicy, "pipeline_empty", lambda: calls.append(peer.ledger.height))
    peer.committer.store_block(peer.blocks[0])
    assert calls == [2]
    assert sum(1 for _ in peer.committer.store_stream(iter(peer.blocks[1:]))) == 2
    assert calls == [2, 4]                # once a stream, after its last block
    peer.provider.close()


def test_the_first_committer_settles_the_process(monkeypatch):
    from fabric_tpu.peer.committer import Committer

    monkeypatch.setattr(gcpolicy, "_settled", False)
    saved = gc.get_threshold()
    try:
        Committer(validator=None, ledger=None)
        assert gc.get_freeze_count() > 0
        assert gc.get_threshold()[0] == gcpolicy.GEN0_THRESHOLD
    finally:
        gc.set_threshold(*saved)
        gc.unfreeze()


def test_the_frozen_heap_shows_on_the_process_metrics():
    from fabric_tpu.common import tracing
    from fabric_tpu.common.metrics import ProcessMetrics, PrometheusProvider

    had = tracing._on_gc in gc.callbacks
    keep = tracing._gc_keep
    prov = PrometheusProvider()
    pm = ProcessMetrics(prov)
    try:
        gc.freeze()
        before = gc.get_freeze_count()
        pm.collect()
        after = gc.get_freeze_count()
        text = prov.registry.expose()
        line = next(
            ln for ln in text.splitlines()
            if ln.startswith("process_gc_frozen_objects")
        )
        # a frozen object can still die by its reference count (another
        # thread of the worker, a temporary of the call itself), so the
        # count may fall between two reads and never rises
        assert before >= float(line.split()[-1]) >= after > 0
    finally:
        gc.unfreeze()
        tracing._gc_keep = keep
        if not had and tracing._on_gc in gc.callbacks:
            gc.callbacks.remove(tracing._on_gc)


def test_nothing_disables_the_collector():
    """`gc.disable()` appears nowhere in the program."""
    needle = "gc." + "disable("
    hits = []
    for base, _dirs, files in os.walk(os.path.join(ROOT, "fabric_tpu")):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(base, name)
                with open(path, encoding="utf-8") as f:
                    if needle in f.read():
                        hits.append(path)
    assert hits == []
