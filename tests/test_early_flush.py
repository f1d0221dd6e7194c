"""A lone block's first 2,048 lanes are on the device while the rest is
still collected: a flush of 2,049-4,096 lanes is planned as chunks of
2,048 (`_chunk_plan`), the provider says where a batch collected alone
is cut (`early_chunk`) and takes a chunk now (`verify_batch_async(...,
flush=True)`), and `TxValidator.validate` hands the first chunk over as
soon as its sink holds it.  Here, on the CPU: the plan; the provider's
generations and their masks; a lone 1000-tx 3-of-5 block with faults on
both sides of the cut against the unsplit validator and the pure-Python
collector; the blocks that are never cut; the host providers; and what
the spans and the counter say, at no cost while tracing is off.

The blocks are the benchmark's own (`benchlib/generator.py`).  The
provider's path is the one a CPU takes (`_dispatch`'s XLA branch: plan,
chunks, enqueue spans, waiter, collectors), with OpenSSL standing in
for the kernel behind `ec.verify_prepared`: the XLA scan takes a minute
a 2,048-lane chunk on a CPU (`tests/test_csp_tpu.py` holds the kernel
itself to the rule at small sizes).  No number of a CPU run is a device
number: the tests read counts, flags and masks, never a time."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from fabric_tpu.common import tracing  # noqa: E402
from fabric_tpu.csp import SWCSP, api  # noqa: E402
from fabric_tpu.csp.api import VerifyBatchItem  # noqa: E402
from fabric_tpu.csp.tpu import provider  # noqa: E402
from fabric_tpu.csp.tpu.provider import TPUCSP, _chunk_plan  # noqa: E402
from fabric_tpu.peer.txvalidator import TxValidator, _ItemSink  # noqa: E402
from fabric_tpu.protos.common import common_pb2  # noqa: E402

SEED = 2**31 + 38
BAD_CREATOR, DUPLICATE_TXID, POLICY_FAILURE = 4, 9, 10
CUT = 2048
LANES_3OF5 = 4           # a creator and three endorsements a transaction
CUT_TX = CUT // LANES_3OF5
PLANTED = {"bad_creator_per_block": 6, "bad_endorsement_per_block": 6,
           "conflict_pairs_per_block": 2}


# -- the plan ------------------------------------------------------------------


@pytest.mark.parametrize("lanes,plan", [
    (2048, [(2048, 2048)]),
    (2049, [(2048, 2048), (1, 256)]),
    (3000, [(2048, 2048), (952, 2048)]),
    (4000, [(2048, 2048), (1952, 2048)]),
    (4096, [(2048, 2048), (2048, 2048)]),
    (4097, [(4097, 8192)]),
    (8000, [(8000, 8192)]),
    (8193, [(8192, 8192), (1, 256)]),
])
def test_a_flush_of_2049_to_4096_lanes_is_planned_as_chunks_of_2048(lanes, plan):
    assert _chunk_plan(lanes, provider._MAX_CHUNK, min_bucket=256) == plan


def test_no_flush_up_to_the_largest_chunk_names_the_4096_bucket():
    """The set of shapes a process dispatches stays closed whoever
    sends a flush: four buckets for every size up to 8,192 lanes, every
    lane in exactly one chunk."""
    seen = set()
    for n in range(1, provider._MAX_CHUNK + 1):
        plan = _chunk_plan(n, provider._MAX_CHUNK, min_bucket=256)
        assert sum(take for take, _b in plan) == n
        assert all(take <= bucket for take, bucket in plan)
        seen.update(bucket for _take, bucket in plan)
    assert seen == {256, 512, 2048, 8192}


def test_a_smaller_largest_chunk_plans_as_it_did():
    assert _chunk_plan(3000, 1024) == [(1024, 2048), (1024, 2048), (952, 2048)]
    assert _chunk_plan(300, 128) == [(128, 128), (128, 128), (44, 128)]


@pytest.fixture
def warm(monkeypatch):
    """A process that has enqueued the 2048 and 8192 buckets before
    (its own record, `provider._enqueued`, not the test run's)."""
    monkeypatch.setattr(provider, "_enqueued",
                        {("xla_p256_verify", 2048), ("xla_p256_verify", 8192)})


@pytest.fixture
def cold(monkeypatch):
    monkeypatch.setattr(provider, "_enqueued", set())


@pytest.mark.parametrize("lanes,first", [
    (16, None), (1000, None), (2048, None), (2049, 2048), (4000, 2048), (4096, 2048),
    (4097, None), (8000, None), (8193, 8192),
])
def test_the_provider_says_where_a_batch_collected_alone_is_cut(lanes, first, warm):
    csp = TPUCSP(stall_factor=None)
    try:
        assert csp.early_chunk(lanes) == first
    finally:
        csp.close()


def test_the_provider_cuts_nothing_before_the_chunks_shape_is_warm(cold, host_kernel):
    """The first enqueue of a bucket traces and lowers its kernel for
    seconds: not inside a collect.  The batch that meets the shape cold
    goes out whole, and the next one is cut."""
    csp = TPUCSP(stall_factor=None)
    try:
        assert csp.early_chunk(4000) is None
        assert csp.verify_batch(_signed(2100, corrupt={5})) == [i != 5 for i in range(2100)]
        assert csp.early_chunk(4000) == CUT
    finally:
        csp.close()


# -- the provider ----------------------------------------------------------------


@pytest.fixture
def host_kernel(monkeypatch):
    """OpenSSL behind `ec.verify_prepared`, to the kernel's rule (strict
    DER and low-S are `prepare_batch`'s and `_tuple_chunks`' already: a
    lane they refuse comes here with r = -1)."""
    from cryptography.hazmat.primitives.asymmetric import ec as cec

    from fabric_tpu.csp.tpu import ec

    sw = SWCSP()
    keys: dict = {}

    def prepare_batch(chunk):
        # arrays, as the kernel's are: on the tests' virtual mesh a
        # chunk is put on its device before it is enqueued
        sound = [r > 0 and len(digest) == 32 for _x, _y, digest, r, _s in chunk]
        words = [
            b"".join(v.to_bytes(32, "big") for v in (x, y, r, s)) + digest
            if ok else bytes(160)
            for ok, (x, y, digest, r, s) in zip(sound, chunk)
        ]
        return {"words": np.frombuffer(b"".join(words), np.uint8).reshape(-1, 160),
                "sound": np.asarray(sound)}

    def verify_prepared(words, sound):
        out = []
        for row, ok in zip(np.asarray(words), np.asarray(sound)):
            if not ok:
                out.append(False)
                continue
            raw = row.tobytes()
            x, y, r, s = (int.from_bytes(raw[i:i + 32], "big") for i in range(0, 128, 32))
            key = keys.get((x, y))
            if key is None:
                key = keys[x, y] = api.ECDSAP256PublicKey(
                    cec.EllipticCurvePublicNumbers(x, y, cec.SECP256R1()).public_key()
                )
            out.append(sw.verify(key, api.marshal_ecdsa_signature(r, s), raw[128:]))
        return np.asarray(out)

    monkeypatch.setattr(ec, "prepare_batch", prepare_batch)
    monkeypatch.setattr(ec, "verify_prepared", verify_prepared)


def _signed(n: int, corrupt=()):
    sw = SWCSP()
    key = sw.key_gen()
    items = []
    for i in range(n):
        digest = sw.hash(b"early-flush-%d" % i)
        sig = sw.sign(key, digest)
        if i in corrupt:
            sig = sig[:-1] + bytes([sig[-1] ^ 1])
        items.append(VerifyBatchItem(key.public_key(), digest, sig))
    return items


def _dispatches(csp) -> list:
    """Every `_dispatch` call's lanes, as the harness's `BucketWatch`
    counts them."""
    seen: list = []
    inner = csp._dispatch

    def counted(items):
        seen.append(len(items))
        return inner(items)

    csp._dispatch = counted
    return seen


def test_flush_dispatches_at_once_and_every_collector_returns_its_own_segment(host_kernel):
    from fabric_tpu.common.metrics import CSPMetrics, PrometheusProvider

    prov = PrometheusProvider()
    csp = TPUCSP(stall_factor=None, metrics=CSPMetrics(prov))
    seen = _dispatches(csp)
    bad = {3, 25, 50, 69}
    items = _signed(70, corrupt=bad)
    try:
        with tracing.scope() as rec:
            first = csp.verify_batch_async(items[:20])
            assert seen == []                       # buffered, as it was
            second = csp.verify_batch_async(items[20:44], flush=True)
            assert seen == [44]                     # out before it returned
            third = csp.verify_batch_async(items[44:])
            assert seen == [44]
            # the later generation's collector first: each finds its own
            masks = {3: third(), 1: first(), 2: second()}
            assert seen == [44, 26]
            events = tracing.export(rec)["traceEvents"]
    finally:
        csp.close()
    want = [i not in bad for i in range(70)]
    assert masks[1] == want[:20] and masks[2] == want[20:44] and masks[3] == want[44:]
    flushes = sorted((e["args"] for e in events if e["name"] == "tpu.flush"),
                     key=lambda a: a["batch"])
    assert [(a["lanes"], a["segment_lanes"], a["early"]) for a in flushes] \
        == [(44, [20, 24], True), (26, [26], False)]
    assert csp.lane_tally()["device"] == 70
    text = prov.registry.expose()
    assert "csp_tpu_early_flushes_total 1" in text
    assert "csp_tpu_flush_segments_total 3" in text


def test_a_failed_early_dispatch_degrades_to_the_host_oracle_as_a_failed_flush_does(host_kernel):
    csp = TPUCSP(stall_factor=None)
    inner, calls = csp._dispatch, []

    def failing_once(items):
        calls.append(len(items))
        if len(calls) == 1:
            raise RuntimeError("no device for this one")
        return inner(items)

    csp._dispatch = failing_once
    items = _signed(60, corrupt={7, 41})
    try:
        early = csp.verify_batch_async(items[:32], flush=True)
        rest = csp.verify_batch_async(items[32:])
        assert rest() == [i != 41 for i in range(32, 60)]
        assert early() == [i != 7 for i in range(32)]
    finally:
        csp.close()
    assert calls == [32, 28]
    assert csp.lane_tally()["failover"] == 32 and csp.lane_tally()["device"] == 28


def test_a_batch_under_min_device_batch_is_verified_on_the_host_flush_or_not(host_kernel):
    csp = TPUCSP(stall_factor=None)
    seen = _dispatches(csp)
    try:
        assert csp.verify_batch_async(_signed(15, corrupt={2}), flush=True)() \
            == [i != 2 for i in range(15)]
    finally:
        csp.close()
    assert seen == [] and csp.lane_tally()["small"] == 15


# -- the sink --------------------------------------------------------------------


class _CountingCSP:
    """Records what it is handed; item i verifies unless it is `bad`."""

    def __init__(self, bad=()):
        self.calls: list = []
        self.bad = set(bad)

    def verify_batch_async(self, items, flush=False):
        self.calls.append(([it.digest for it in items], flush))
        mask = [it.digest not in self.bad for it in items]
        return lambda: mask


def _item(i: int):
    key = type("K", (), {"x": 1, "y": 2})
    return VerifyBatchItem(key, b"d%d" % i, b"s")


def test_the_sinks_indices_stay_valid_across_the_cut():
    csp = _CountingCSP(bad={b"d1", b"d5"})
    sink = _ItemSink()
    idx = sink.add_many([_item(i) for i in range(4)])
    sink.hand_early(csp, 3)
    assert csp.calls == [([b"d0", b"d1", b"d2"], True)]
    idx += sink.add_many([_item(4), _item(1), _item(5), _item(3)])
    # a later duplicate of an early item is the early index, one of the
    # tail's the tail's
    assert idx == [0, 1, 2, 3, 4, 1, 5, 3] and sink.early_lanes == 3
    mask = sink.hand_over(csp)()
    assert csp.calls[1] == ([b"d3", b"d4", b"d5"], False)
    assert mask == [True, False, True, True, True, False]


def test_a_sink_that_hands_nothing_early_hands_over_one_batch():
    csp = _CountingCSP()
    sink = _ItemSink()
    assert sink.hand_over(csp)() == [] and csp.calls == []
    sink.add_many([_item(i) for i in range(3)])
    assert sink.hand_over(csp)() == [True] * 3
    assert csp.calls == [([b"d0", b"d1", b"d2"], False)] and sink.early_lanes == 0
    # and one whose early chunk was everything it came to hold
    whole = _ItemSink()
    whole.add_many([_item(i) for i in range(3)])
    whole.hand_early(csp, 3)
    assert whole.hand_over(csp)() == [True] * 3 and len(csp.calls) == 2


# -- the validator -----------------------------------------------------------------


def _world(orgs: int, endorsers: int, txs: int, n_blocks: int, planted=PLANTED):
    from benchlib.generator import build_world

    dep = {"orgs": orgs, "endorsers_per_tx": endorsers, "block_txs": txs, "value_bytes": 32}
    return build_world(SEED, dep, planted, n_blocks)


@pytest.fixture(scope="module")
def majority():
    """Two 1000-tx 3-of-5 blocks; in the first, transaction 930 is
    replaced by a copy of transaction 7, so its creator's item is an
    early one met again after the cut."""
    world = _world(5, 3, 1000, 2)
    block = common_pb2.Block.FromString(world.blocks[0])
    block.data.data[930] = block.data.data[7]
    return world, block.SerializeToString()


def _validator(world, csp, python_collect=False):
    from fabric_tpu.common.channelconfig import bundle_from_genesis
    from fabric_tpu.ledger import LedgerProvider

    ledger = LedgerProvider(None).create(world.genesis)
    validator = TxValidator(world.channel, ledger, bundle_from_genesis(world.genesis, csp), csp)
    if python_collect:
        validator._collect_native = lambda *a, **k: False
    return validator


def _flushes(events) -> list:
    flushes = sorted((e["args"] for e in events if e["name"] == "tpu.flush"),
                     key=lambda a: a["batch"])
    return [(a["lanes"], a["buckets"], a["early"]) for a in flushes]


def test_a_lone_block_is_cut_at_the_providers_chunk_and_flags_what_the_unsplit_validators_flag(
        majority, host_kernel, cold):
    world, raw = majority
    planted = world.planted[0]
    for flag in (BAD_CREATOR, POLICY_FAILURE):
        where = [i for i, f in enumerate(planted) if f == flag and i != 930]
        assert min(where) < CUT_TX < max(where)        # faults on both sides of the cut
    assert planted[7] == 0
    csp = TPUCSP(stall_factor=None)
    seen = _dispatches(csp)
    try:
        validator = _validator(world, csp)
        # the process meets the 2048 bucket with this block: whole
        first = validator.validate(common_pb2.Block.FromString(raw))
        assert validator.early_flush_blocks == 0 and seen == [3996]
        del seen[:]
        validator = _validator(world, csp)
        with tracing.scope() as rec:
            flags = validator.validate(common_pb2.Block.FromString(raw))
            events = tracing.export(rec)["traceEvents"]
        assert flags == first
        assert validator.early_flush_blocks == 1
        # transaction 930 brought one lane (its creator's, the early
        # one again) and no endorsement: 3,996 lanes in two flushes
        assert seen == [CUT, 3996 - CUT]
        assert _flushes(events) == [(CUT, [2048], True), (3996 - CUT, [2048], False)]
        (collect,) = [e["args"] for e in events if e["name"] == "collect"]
        assert collect["early_lanes"] == CUT
        assert csp.lane_tally()["device"] == 2 * 3996

        python = _validator(world, csp, python_collect=True)
        assert python.validate(common_pb2.Block.FromString(raw)) == flags
        assert python.early_flush_blocks == 0 and seen[2:] == [3996]
        faithful = TxValidator(world.channel, python._ledger, python._bundle, csp,
                               faithful=True)
        assert faithful.validate(common_pb2.Block.FromString(raw)) == flags
        assert faithful.early_flush_blocks == 0
    finally:
        csp.close()
    unsplit = _validator(world, SWCSP())
    assert unsplit.validate(common_pb2.Block.FromString(raw)) == flags
    assert unsplit.early_flush_blocks == 0
    # and they are the flags the generator planted, less what the
    # commit decides later (the conflicting pairs), with the copy a
    # duplicate of its original
    want = [0 if f == 11 else f for f in planted]
    want[930] = DUPLICATE_TXID
    assert flags == want


def test_a_block_of_one_chunk_and_the_blocks_of_a_pipeline_are_never_cut(majority, host_kernel, warm):
    world, _raw = majority
    small = _world(1, 1, 500, 1)
    csp = TPUCSP(stall_factor=None)
    seen = _dispatches(csp)
    try:
        lone = _validator(small, csp)
        flags = lone.validate(common_pb2.Block.FromString(small.blocks[0]))
        assert flags == [0 if f == 11 else f for f in small.planted[0]]
        assert lone.early_flush_blocks == 0 and seen == [1000]

        del seen[:]
        piped = _validator(world, csp)
        blocks = [common_pb2.Block.FromString(b) for b in world.blocks]
        with tracing.scope() as rec:
            got = list(piped.validate_pipeline(iter(blocks)))
            events = tracing.export(rec)["traceEvents"]
        assert got == [[0 if f == 11 else f for f in row] for row in world.planted]
        # two blocks a flush, one chunk at 8192, as before
        assert piped.early_flush_blocks == 0 and seen == [8000]
        assert _flushes(events) == [(8000, [8192], False)]
        assert [e["args"]["early_lanes"] for e in events if e["name"] == "collect"] == [0, 0]
    finally:
        csp.close()


def test_a_lone_block_through_store_block_is_cut_too(majority, host_kernel, warm, tmp_path):
    """`Committer.store_block` is what `gossip/state.py` `_drain` calls
    for a block that came alone; `store_stream` is a pipeline."""
    from fabric_tpu.common.channelconfig import bundle_from_genesis
    from fabric_tpu.ledger import LedgerProvider
    from fabric_tpu.peer.committer import Committer

    world, _raw = majority
    csp = TPUCSP(stall_factor=None)
    seen = _dispatches(csp)
    provider_ = LedgerProvider(str(tmp_path / "ledger"))
    try:
        ledger = provider_.create(world.genesis)
        validator = TxValidator(world.channel, ledger,
                                bundle_from_genesis(world.genesis, csp), csp)
        committer = Committer(validator, ledger)
        flags = committer.store_block(common_pb2.Block.FromString(world.blocks[0]))
        assert list(flags) == world.planted[0]
        assert validator.early_flush_blocks == 1 and seen == [CUT, 4000 - CUT]
    finally:
        csp.close()
        provider_.close()


# -- the host providers ---------------------------------------------------------------


def test_a_host_provider_and_the_custody_wrapper_take_the_argument_and_cut_nothing(host_kernel, warm):
    from fabric_tpu.csp.custody import CustodyCSP

    items = _signed(20, corrupt={11})
    want = [i != 11 for i in range(20)]
    sw = SWCSP()
    assert sw.early_chunk(4000) is None
    assert sw.verify_batch_async(items, flush=True)() == want
    # no call reaches the daemon: verification is the local provider's
    over_sw = CustodyCSP(("127.0.0.1", 1), b"t" * 16)
    assert over_sw.early_chunk(4000) is None
    assert over_sw.verify_batch_async(items, flush=True)() == want
    tpu = TPUCSP(stall_factor=None)
    seen = _dispatches(tpu)
    try:
        over_tpu = CustodyCSP(("127.0.0.1", 1), b"t" * 16, verify_csp=tpu)
        assert over_tpu.early_chunk(4000) == CUT and over_tpu.early_chunk(1000) is None
        collector = over_tpu.verify_batch_async(items, flush=True)
        assert seen == [20]
        assert collector() == want
    finally:
        tpu.close()


# -- what says that it engaged, and what it costs when nobody looks ------------------


def test_tracing_off_the_early_flush_consults_nothing(majority, host_kernel, warm):
    """`collect{early_lanes}` and `tpu.flush{early}` ride sites that
    were there: disarmed, a lone block that is cut reaches no armed
    path (as `test_lone_block_assist.py` and `test_timeoutcut.py` pin
    their sites)."""
    world, raw = majority
    assert not tracing.enabled()
    csp = TPUCSP(stall_factor=None)
    try:
        validator = _validator(world, csp)
        before = tracing.lookup_count()
        validator.validate(common_pb2.Block.FromString(raw))
        csp.drain()
        assert tracing.lookup_count() == before
        assert validator.early_flush_blocks == 1
    finally:
        csp.close()


def test_the_counter_stands_on_a_peers_metrics_page(majority, host_kernel, warm):
    from fabric_tpu.common.operations import System

    world, raw = majority
    ops = System()
    csp = TPUCSP(stall_factor=None)
    csp.set_metrics(ops.csp_metrics())
    try:
        assert "csp_tpu_early_flushes_total" in ops.metrics_provider.registry.expose()
        validator = _validator(world, csp)
        validator.validate(common_pb2.Block.FromString(raw))
        list(validator.validate_pipeline(
            iter([common_pb2.Block.FromString(b) for b in world.blocks])
        ))
    finally:
        csp.close()
    text = ops.metrics_provider.registry.expose()
    assert "csp_tpu_early_flushes_total 1" in text
    assert 'csp_tpu_dispatches_total{bucket="2048"} 2' in text
    assert 'csp_tpu_dispatches_total{bucket="8192"} 1' in text
    assert 'csp_tpu_dispatches_total{bucket="4096"}' not in text
