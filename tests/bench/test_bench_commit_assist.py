"""`commit_assist_share` (`benchmarks/layer_metrics/commit_assist_share.py`):
on hand-made `block_append` spans against answers counted by hand, on
what the parent's program recorded (no `assisted` to read: nothing), and
on the spans a small peer records here when blocks come to its ledger
each way: through `store_block`, through `store_stream`, and with no
validator before them."""

import pytest

from benchlib.manifest import Manifest

from conftest import ROOT

METRIC = "commit_assist_share.steady"


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


def append(block, assisted=None, **more):
    args = {"block": block, "span": 10 + block, "parent": 1, **more}
    if assisted is not None:
        args["assisted"] = assisted
    return {"name": "block_append", "ph": "X", "cat": "stage", "ts": 1000 * block,
            "dur": 400, "tid": "MainThread", "args": args}


def test_the_metric_is_declared_for_the_steady_cells_alone(man):
    entry = {m["name"]: m for m in man.doc["per_layer"]}[METRIC]
    assert entry == {
        "name": METRIC, "unit": "%", "better": "higher", "source": "program_span",
        "layer": "committer and ledger (peer/committer.py, ledger/kvledger.py)",
        "moves": "block_commit_p50_ms",
        "workloads": ["solo1-500tx.steady", "majority5-1000tx.steady"],
    }
    reporting = next(m for m in man.doc["end_to_end"] if m["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(reporting["workloads"])


@pytest.mark.parametrize("assisted, share", [
    ([True, True, True, True], 100.0),
    ([True, False, True, True], 75.0),
    ([False, False], 0.0),          # traced and unassisted is a reading, not nothing
])
def test_the_share_of_the_windows_blocks_that_came_assisted(man, assisted, share):
    # a fresh ledger's genesis block (block 0) has no validator before it
    # in any program, and is not counted
    events = [append(0, False)] + [append(1 + i, a) for i, a in enumerate(assisted)]
    events.append(dict(append(9, True), name="mvcc"))
    value = man.reader(METRIC)({"spans": events})
    assert value == share and isinstance(value, float)


@pytest.mark.parametrize("spans", [
    None,                                           # an untraced run
    [],
    [append(0, False)],                             # nothing but a genesis block
    [append(0), append(1), append(2)],              # the parent's spans
], ids=["untraced", "empty", "genesis_alone", "no_attribute"])
def test_a_window_without_the_attribute_gives_nothing_to_read(man, spans):
    assert man.reader(METRIC)({"spans": spans}) is None


def test_on_the_spans_a_peer_records(man, tmp_path):
    from benchlib.generator import build_world
    from fabric_tpu.common import tracing
    from fabric_tpu.common.channelconfig import bundle_from_genesis
    from fabric_tpu.csp import SWCSP
    from fabric_tpu.ledger import LedgerProvider
    from fabric_tpu.peer.committer import Committer
    from fabric_tpu.peer.txvalidator import TxValidator
    from fabric_tpu.protos.common import common_pb2

    dep = {"orgs": 1, "endorsers_per_tx": 1, "block_txs": 8, "value_bytes": 32}
    planted = {"bad_creator_per_block": 1, "bad_endorsement_per_block": 1,
               "conflict_pairs_per_block": 1}
    world = build_world(2**31 + 35, dep, planted, 4)
    blocks = [common_pb2.Block.FromString(b) for b in world.blocks]
    csp = SWCSP()
    with tracing.scope() as rec:
        provider = LedgerProvider(str(tmp_path))
        try:
            ledger = provider.create(world.genesis)
            validator = TxValidator(
                world.channel, ledger, bundle_from_genesis(world.genesis, csp), csp)
            committer = Committer(validator, ledger)
            committer.store_block(blocks[0])
            assert len(list(committer.store_stream(iter(blocks[1:3])))) == 2
            share_of_the_committers = man.reader(METRIC)(
                {"spans": tracing.export(rec)["traceEvents"]})
            validator.validate(blocks[3])
            ledger.commit(blocks[3])                # as store_block did before
        finally:
            provider.close()
        events = tracing.export(rec)["traceEvents"]
    assert share_of_the_committers == 100.0
    assert man.reader(METRIC)({"spans": events}) == 75.0
