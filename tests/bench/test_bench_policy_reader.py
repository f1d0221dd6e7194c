"""`policy_ms_per_block` (`benchmarks/layer_metrics/policy_ms_per_block.py`),
the validator's third stage clock: against an answer by hand, with
nothing to read, as `BENCHMARK.json` declares it, and on the stage
clocks a small peer keeps, where `collect`, `verify_wait` and `policy`
are the validator's whole wall."""

import pytest

from benchlib.manifest import Manifest

from conftest import ROOT

MOVES = {"catchup": "committed_tx_per_s", "steady": "block_commit_p50_ms"}


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


@pytest.mark.parametrize("suffix", sorted(MOVES))
def test_the_policy_stage_over_the_windows_blocks(man, suffix):
    read = man.reader(f"policy_ms_per_block.{suffix}")
    assert read.__module__ == "bench_layer_metrics_policy_ms_per_block"
    obs = {"blocks": 8, "validate_stage_seconds": {"collect": 0.7, "verify_wait": 0.1,
                                                   "policy": 0.024}}
    assert read(obs) == pytest.approx(3.0)
    # a window whose blocks carried nothing for a policy to decide took no time
    assert read({"blocks": 8, "validate_stage_seconds": {"collect": 0.7}}) == 0.0


@pytest.mark.parametrize("suffix", sorted(MOVES))
def test_a_window_without_a_block_gives_nothing_to_read(man, suffix):
    assert man.reader(f"policy_ms_per_block.{suffix}")(
        {"blocks": 0, "validate_stage_seconds": {}}) is None


@pytest.mark.parametrize("suffix", sorted(MOVES))
def test_it_is_declared_beside_the_validators_two_other_clocks(man, suffix):
    declared = {m["name"]: m for m in man.doc["per_layer"]}
    entry = declared[f"policy_ms_per_block.{suffix}"]
    beside = declared[f"collect_ms_per_block.{suffix}"]
    assert entry == dict(beside, name=entry["name"])
    assert entry["moves"] == MOVES[suffix] and entry["source"] == "program_span"
    assert entry["layer"] == "validator (peer/txvalidator.py)"
    assert declared[f"verify_wait_ms_per_block.{suffix}"]["workloads"] == entry["workloads"]


def test_on_the_stage_clocks_a_peer_keeps(man, tmp_path):
    import time

    from benchlib.generator import build_world
    from fabric_tpu.common.channelconfig import bundle_from_genesis
    from fabric_tpu.csp import SWCSP
    from fabric_tpu.ledger import LedgerProvider
    from fabric_tpu.peer.committer import Committer
    from fabric_tpu.peer.txvalidator import TxValidator
    from fabric_tpu.protos.common import common_pb2

    dep = {"orgs": 3, "endorsers_per_tx": 2, "block_txs": 8, "value_bytes": 32}
    planted = {"bad_creator_per_block": 1, "bad_endorsement_per_block": 1,
               "conflict_pairs_per_block": 1}
    world = build_world(2**31 + 39, dep, planted, 3)
    blocks = [common_pb2.Block.FromString(b) for b in world.blocks]
    csp = SWCSP()
    provider = LedgerProvider(str(tmp_path))
    try:
        ledger = provider.create(world.genesis)
        validator = TxValidator(
            world.channel, ledger, bundle_from_genesis(world.genesis, csp), csp)
        committer = Committer(validator, ledger)
        t0 = time.perf_counter()
        committer.store_block(blocks[0])
        assert len(list(committer.store_stream(iter(blocks[1:])))) == 2
        wall = time.perf_counter() - t0
        stages = dict(validator.validate_stage_seconds)
    finally:
        provider.close()
    assert {"collect", "verify_wait", "policy"} <= set(stages)
    obs = {"blocks": 3, "validate_stage_seconds": stages}
    value = man.reader("policy_ms_per_block.catchup")(obs)
    assert isinstance(value, float) and 0.0 < value < 1e3 * wall / 3
    three = sum(man.reader(f"{name}_ms_per_block.catchup")(obs)
                for name in ("collect", "verify_wait", "policy"))
    assert three <= 1e3 * wall / 3
