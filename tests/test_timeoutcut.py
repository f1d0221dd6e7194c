"""A channel whose blocks the orderer's BatchTimeout cuts
(`benchmarks/configs/timeoutcut-2s.json`): blocks of one transaction
to a full batch, side by side.  Here, on the CPU at a small size: such
a chain validates and commits to the plain reference's flags and state
through `store_stream` and block by block through `store_block`, on the
TPU provider with `min_device_batch` 16, on both collect paths; a batch
of 15 lanes is verified on the host and one of 16 on the device, each
to the rule; the world's cut is the consenter's rule recomputed plainly
from its own arrival times; and what the tracing and the counters say
of a pass is what the pass held.  The blocks are the benchmark's own
(`benchmarks/worlds/x509-timeoutcut.py`).

No number of a CPU run is a device number: the tests read counts, flags
and verdicts, never a time."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from fabric_tpu.common import tracing  # noqa: E402
from fabric_tpu.csp import SWCSP  # noqa: E402
from fabric_tpu.csp.api import VerifyBatchItem  # noqa: E402
from fabric_tpu.csp.tpu.provider import TPUCSP  # noqa: E402

SEED = 2**31 + 34
N_BLOCKS = 12
MIN_DEVICE_BATCH = 16
LANES_PER_TX = 2          # one organisation: a creator and one endorsement
# a tiny channel of the same kind: MaxMessageCount 12, a cycle of
# 0.4 / 3 / 20 transactions a second
TINY = {
    "orgs": 1, "endorsers_per_tx": 1, "block_txs": 12, "envelope_bytes": 2724,
    "orderer_batch": {"batch_timeout_s": 2.0, "max_message_count": 12,
                      "preferred_max_bytes": 2 * 1024 * 1024,
                      "absolute_max_bytes": 10 * 1024 * 1024},
    "load": {"arrival_seed": 2**31 + 34,
             "cycle": [{"tx_per_s": 0.4, "seconds": 8}, {"tx_per_s": 3, "seconds": 8},
                       {"tx_per_s": 20, "seconds": 2}]},
}


@pytest.fixture(scope="module")
def chain():
    """(manifest, configuration, deployment, world, reference flags,
    reference states) of one tiny pass."""
    from benchlib.manifest import Manifest

    man = Manifest(ROOT)
    with open(os.path.join(BENCH, "configs", "timeoutcut-2s.json")) as f:
        held = json.load(f)
    dep = dict(held["deployment"], **TINY)
    world = man.world(held)(SEED, dep, held["planted"], N_BLOCKS)
    flags, states = man.reference(held)(world.public, dep, world.blocks)
    return man, held, dep, world, [list(f) for f in flags], states


def _blocks(world):
    from fabric_tpu.protos.common import common_pb2

    return [common_pb2.Block.FromString(b) for b in world.blocks]


def _peer(world, csp, python_collect):
    from fabric_tpu.common.channelconfig import bundle_from_genesis
    from fabric_tpu.ledger import LedgerProvider
    from fabric_tpu.peer.committer import Committer
    from fabric_tpu.peer.txvalidator import TxValidator

    ledger = LedgerProvider(None).create(world.genesis)
    v = TxValidator(world.channel, ledger, bundle_from_genesis(world.genesis, csp), csp)
    if python_collect:
        v._collect_native = lambda *a, **k: False
    return ledger, Committer(v, ledger)


def _state(world, ledger) -> dict:
    return {
        (ns, key): (vv.value, (vv.version.block_num, vv.version.tx_num))
        for ns in world.namespaces
        for key, vv in ledger._state.get_state_range(ns, "", "")
    }


@pytest.fixture(scope="module")
def csp():
    provider = TPUCSP(min_device_batch=MIN_DEVICE_BATCH, stall_factor=None)
    yield provider
    provider.close()


# -- the chain against the plain reference -----------------------------------


def test_the_tiny_pass_is_of_the_cells_kind(chain):
    """One-transaction blocks, blocks at MaxMessageCount, blocks on both
    sides of `min_device_batch`, and every kind of planted fault."""
    _man, held, _dep, world, ref_flags, _states = chain
    sizes = world.txs_per_block
    assert len(sizes) == N_BLOCKS and 1 in sizes and 12 in sizes
    assert [by for by, n in zip(world.cut_by, sizes) if n == 12] \
        == ["count"] * sizes.count(12)
    lanes = world.lanes_by_block
    assert lanes == [LANES_PER_TX * n for n in sizes]
    assert any(n < MIN_DEVICE_BATCH for n in lanes) and any(n >= MIN_DEVICE_BATCH for n in lanes)
    assert [list(p) for p in world.planted] == ref_flags
    flat = [f for flags in ref_flags for f in flags]
    assert {0, 4, 10, 11} <= set(flat)
    # what is planted is what the configuration says, block by block
    for number, flags in enumerate(ref_flags, start=1):
        bad = sorted(f for f in flags if f)
        if len(flags) >= held["planted"]["full_set_from_txs"]:
            assert bad == [4, 10, 11]
        elif len(flags) >= 2:
            assert bad == [(4, 10, 11)[number % 3]]
        else:
            assert bad == ([4] if number % 3 == 0 else [])
    # a small block's fault sits in a batch too small for the device
    assert any(f for flags, n in zip(ref_flags, lanes) if n < MIN_DEVICE_BATCH for f in flags)


@pytest.mark.parametrize("python_collect", [False, True], ids=["native", "python"])
@pytest.mark.parametrize("entry", ["store_stream", "store_block"])
def test_the_chain_commits_to_the_references_flags_and_state(chain, csp, entry, python_collect):
    from fabric_tpu import native

    if not python_collect and not native.available():
        pytest.skip(f"no native collector: {native.load_error()}")
    _man, _held, _dep, world, ref_flags, ref_states = chain
    ledger, committer = _peer(world, csp, python_collect)
    before = csp.lane_tally()
    if entry == "store_stream":
        got = [list(f) for f in committer.store_stream(iter(_blocks(world)))]
    else:
        got = [list(committer.store_block(b)) for b in _blocks(world)]
    assert got == ref_flags
    assert ledger.height == 1 + N_BLOCKS
    assert _state(world, ledger) == ref_states[-1] == world.expected_state()
    # every lane was verified, the small blocks' on the host
    after = csp.lane_tally()
    sealed = {k: after[k] - before[k] for k in after}
    small = sum(n for n in world.lanes_by_block if n < MIN_DEVICE_BATCH)
    assert sealed["small"] == small and sealed["device"] == sum(world.lanes_by_block) - small
    assert sealed["failover"] == sealed["breaker"] == 0


# -- one lane on each side of `min_device_batch` ------------------------------


def _signed(n: int, corrupt: int):
    sw = SWCSP()
    key = sw.key_gen()
    items = []
    for i in range(n):
        digest = sw.hash(b"timeoutcut-%d" % i)
        sig = sw.sign(key, digest)
        if i == corrupt:
            sig = sig[:-1] + bytes([sig[-1] ^ 1])
        items.append(VerifyBatchItem(key.public_key(), digest, sig))
    return items


@pytest.mark.parametrize("lanes,where", [(MIN_DEVICE_BATCH - 1, "host"), (MIN_DEVICE_BATCH, "device")])
def test_a_batch_on_either_side_of_min_device_batch_is_verified_to_the_rule(lanes, where):
    from fabric_tpu.common.metrics import CSPMetrics, PrometheusProvider

    prov = PrometheusProvider()
    csp = TPUCSP(min_device_batch=MIN_DEVICE_BATCH, stall_factor=None, metrics=CSPMetrics(prov))
    items = _signed(lanes, corrupt=lanes - 1)
    try:
        with tracing.scope() as rec:
            mask = csp.verify_batch_async(items)()
            events = tracing.export(rec)["traceEvents"]
    finally:
        csp.close()
    assert mask == [True] * (lanes - 1) + [False]
    small = [e["args"] for e in events if e.get("name") == "tpu.small"]
    flushes = [e["args"] for e in events if e.get("name") == "tpu.flush"]
    text = prov.registry.expose()
    if where == "host":
        assert [a["lanes"] for a in small] == [lanes] and not flushes
        assert csp.lane_tally()["small"] == lanes and csp.lane_tally()["device"] == 0
        assert "csp_tpu_small_batches_total 1" in text
        assert f'csp_tpu_lanes_total{{sealed_by="small"}} {lanes}' in text
    else:
        assert not small
        assert [(a["segments"], a["segment_lanes"], a["lanes"]) for a in flushes] \
            == [(1, [lanes], lanes)]
        assert csp.lane_tally()["device"] == lanes and csp.lane_tally()["small"] == 0
        assert "csp_tpu_flush_segments_total 1" in text
        assert "csp_tpu_small_batches_total 1" not in text


# -- the cut -----------------------------------------------------------------


def _recut(times, timeout_s, max_count, message_bytes, preferred):
    """The solo consenter's rule, plainly: (sizes, cut at, by what) of
    every block that `times` closes."""
    out, pending, deadline, held = [], [], None, 0
    for t in times:
        if deadline is not None and deadline <= t:
            if pending:
                out.append((len(pending), deadline, "timeout"))
            pending, held, deadline = [], 0, None
        if pending and held + message_bytes > preferred:
            out.append((len(pending), t, "bytes"))
            pending, held = [], 0
        pending.append(t)
        held += message_bytes
        if len(pending) >= max_count:
            out.append((len(pending), t, "count"))
            pending, held = [], 0
        if not pending:
            deadline = None
        elif deadline is None:
            deadline = t + timeout_s
    return out


def test_the_worlds_cut_is_the_consenters_rule_over_its_own_arrival_times(chain):
    _man, _held, dep, world, _f, _s = chain
    times = [t for arrived in world.arrivals_s for t in arrived]
    assert times == sorted(times) and len(times) == sum(world.txs_per_block)
    batch = dep["orderer_batch"]
    # one arrival more, past the last cut, lets the last timer fire
    again = _recut(times + [world.cut_at_s[-1] + 1e-9], batch["batch_timeout_s"],
                   dep["block_txs"], dep["envelope_bytes"], batch["preferred_max_bytes"])
    assert again[:N_BLOCKS] == list(zip(world.txs_per_block, world.cut_at_s, world.cut_by))
    # a timer cut comes BatchTimeout after the block's first arrival,
    # whatever arrived since; a count cut at the arrival that filled it
    for arrived, at, by in zip(world.arrivals_s, world.cut_at_s, world.cut_by):
        assert at == (arrived[0] + batch["batch_timeout_s"] if by == "timeout" else arrived[-1])
        assert arrived[-1] <= at
    # the genesis block carries the batch settings the cut was made under
    from fabric_tpu.common.channelconfig import bundle_from_genesis

    oc = bundle_from_genesis(world.genesis, SWCSP()).orderer_config
    assert (oc.max_message_count, oc.batch_timeout_s, oc.preferred_max_bytes) \
        == (12, 2.0, 2 * 1024 * 1024)


def test_the_byte_rule_cuts_a_burst_of_this_networks_envelopes_before_the_count():
    """Upstream's defaults meeting a three-endorsement transaction:
    483 envelopes of 4,333 bytes fill PreferredMaxBytes, so
    MaxMessageCount 500 is never reached; and the timer that was running
    when the byte rule cut is left running, as upstream leaves it."""
    from benchlib.manifest import Manifest

    man = Manifest(ROOT)
    with open(os.path.join(BENCH, "configs", "timeoutcut-2s.json")) as f:
        dep = json.load(f)["deployment"]
    mod = sys.modules[man.world({"name": "x", "world": "x509-timeoutcut"}).__module__]
    burst = [i / 1000.0 for i in range(1200)]          # 1,000 a second
    cut = mod.cut_from_arrivals(iter(burst + [10.0]), 3, dep["orderer_batch"],
                                dep["block_txs"], dep["envelope_bytes"])
    assert [(len(arrived), by) for arrived, _at, by in cut] \
        == [(483, "bytes"), (483, "bytes"), (234, "timeout")]
    assert cut[2][1] == burst[0] + 2.0     # the first message's timer, never re-armed
    # a byte less an envelope and 484 fit: the cut stands within 20
    # bytes of the limit, which is why the world shows the cutter one
    # stated length
    shorter = mod.cut_from_arrivals(iter(burst + [10.0]), 1, dep["orderer_batch"],
                                    dep["block_txs"], dep["envelope_bytes"] - 1)
    assert len(shorter[0][0]) == 484


def test_a_timer_that_fires_on_nothing_pending_cuts_no_block(chain):
    man = chain[0]
    mod = sys.modules[man.world({"name": "x", "world": "x509-timeoutcut"}).__module__]
    consenter = mod.Consenter(mod._cutter(TINY["orderer_batch"], 12), 2.0)
    assert consenter.message(1.0, b"m") == [] and consenter.deadline == 3.0
    consenter.cutter.cut()                 # as a config message takes what is pending
    assert consenter.advance(3.5) == [] and consenter.deadline is None
    # and the next message arms it afresh
    assert consenter.message(4.0, b"n") == [] and consenter.deadline == 6.0
    assert [(len(b), at, by) for b, at, by in consenter.advance(6.0)] == [(1, 6.0, "timeout")]


# -- what the tracing and the counters say of a pass -------------------------


def _flush_makeup(lanes, depth=3):
    """`store_stream`'s flushes by block order alone: a block under
    `min_device_batch` is in none; the oldest of `depth` blocks in
    flight, when finished, flushes what is pending."""
    out, pending, flushed, in_flight = [], [], set(), []

    def finish(b):
        if lanes[b] >= MIN_DEVICE_BATCH and b not in flushed and pending:
            out.append([lanes[x] for x in pending])
            flushed.update(pending)
            pending.clear()

    for b, n in enumerate(lanes):
        if n >= MIN_DEVICE_BATCH:
            pending.append(b)
        in_flight.append(b)
        if len(in_flight) >= depth:
            finish(in_flight.pop(0))
    while in_flight:
        finish(in_flight.pop(0))
    return out


def test_the_spans_say_what_the_pass_held(chain, csp):
    from fabric_tpu import native

    if not native.available():
        pytest.skip(f"no native collector: {native.load_error()}")
    world = chain[3]
    _ledger, committer = _peer(world, csp, python_collect=False)
    with tracing.scope() as rec:
        list(committer.store_stream(iter(_blocks(world))))
        csp.drain()
        events = tracing.export(rec)["traceEvents"]
    roots = [e["args"] for e in events if e.get("name") == "block" and e.get("ph") == "X"]
    assert sorted((a["block"], a["txs"]) for a in roots) \
        == list(enumerate(world.txs_per_block, start=1))
    lanes = world.lanes_by_block
    small = [e["args"]["lanes"] for e in events if e.get("name") == "tpu.small"]
    assert small == [n for n in lanes if n < MIN_DEVICE_BATCH]
    flushes = sorted((e["args"] for e in events if e.get("name") == "tpu.flush"),
                     key=lambda a: a["batch"])
    assert [a["segment_lanes"] for a in flushes] == _flush_makeup(lanes)
    assert all(a["segments"] == len(a["segment_lanes"])
               and a["lanes"] == sum(a["segment_lanes"]) for a in flushes)
    # the small batches' spans lie inside `collect`, on its thread
    collects = [e for e in events if e.get("name") == "collect"]
    for e in (e for e in events if e.get("name") == "tpu.small"):
        assert any(c["tid"] == e["tid"] and c["ts"] <= e["ts"]
                   and e["ts"] + e["dur"] <= c["ts"] + c["dur"] for c in collects)
    # a commit group's fsync says how many blocks it held
    groups = [e["args"]["blocks"] for e in events if e.get("name") == "fsync"]
    assert sum(groups) == N_BLOCKS and all(1 <= g <= 3 for g in groups)


def test_disarmed_the_new_sites_consult_nothing(chain, csp):
    """Off, a site is a global load and an `is None` test: the armed
    path's counter stays where it was through a whole pass, small
    batches and flushes of several blocks included."""
    world = chain[3]
    _ledger, committer = _peer(world, csp, python_collect=False)
    assert not tracing.enabled()
    before = tracing.lookup_count()
    list(committer.store_stream(iter(_blocks(world))))
    csp.drain()
    assert tracing.lookup_count() == before


def test_the_two_counters_stand_on_a_peers_metrics_page(chain):
    """`operations.System.csp_metrics()` is what `peer node start` binds
    to its provider: the page shows both counters from the start, and
    after a pass what the pass held."""
    from fabric_tpu.common.operations import System

    world = chain[3]
    ops = System()
    csp = TPUCSP(min_device_batch=MIN_DEVICE_BATCH, stall_factor=None)
    csp.set_metrics(ops.csp_metrics())
    try:
        text = ops.metrics_provider.registry.expose()
        assert "csp_tpu_flush_segments_total" in text and "csp_tpu_small_batches_total" in text
        _ledger, committer = _peer(world, csp, python_collect=False)
        list(committer.store_stream(iter(_blocks(world))))
        csp.drain()
    finally:
        csp.close()
    text = ops.metrics_provider.registry.expose()
    lanes = world.lanes_by_block
    n_small = sum(1 for n in lanes if n < MIN_DEVICE_BATCH)
    assert f"csp_tpu_small_batches_total {n_small}" in text
    assert f"csp_tpu_flush_segments_total {N_BLOCKS - n_small}" in text
    assert (f'csp_tpu_lanes_total{{sealed_by="small"}} '
            f'{sum(n for n in lanes if n < MIN_DEVICE_BATCH)}') in text
