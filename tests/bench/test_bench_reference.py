"""The plain reference against what the generator planted, at a tiny
size: corrupted creator and endorsement signatures, conflicting pairs,
the final state; the same seed gives the same inputs; and the
reference shares no code with the validator it judges."""

import ast
import os

import pytest

from benchlib import generator

from conftest import BENCH

DEPLOY = {"orgs": 5, "block_txs": 24, "endorsers_per_tx": 3, "value_bytes": 16}
PLANTED = {"bad_creator_per_block": 3, "bad_endorsement_per_block": 3,
           "conflict_pairs_per_block": 2}
SEED = 2**31 + 12345     # seeds are larger than 32 signed bits hold


@pytest.fixture(scope="module")
def world():
    return generator.build_world(SEED, DEPLOY, PLANTED, 3)


def test_reference_flags_exactly_what_was_planted(world):
    from reference import validator

    flags, state = validator.run_reference(world.ca_certs_pem, DEPLOY["orgs"], world.blocks)
    assert flags == world.planted
    for row in flags:
        assert row.count(generator.BAD_CREATOR_SIGNATURE) == 3
        assert row.count(generator.ENDORSEMENT_POLICY_FAILURE) == 3
        assert row.count(generator.MVCC_READ_CONFLICT) == 2
        assert row.count(generator.VALID) == DEPLOY["block_txs"] - 8
    assert state == generator.planted_state(world)
    assert len(state) == 3 * (DEPLOY["block_txs"] - 8)
    # "accept everything" is wrong in every block
    assert all(any(f != generator.VALID for f in row) for row in flags)


def test_two_of_five_orgs_do_not_satisfy_majority():
    from reference import validator

    two = dict(DEPLOY, endorsers_per_tx=2, block_txs=10)
    w = generator.build_world(SEED, two, dict(PLANTED, conflict_pairs_per_block=0,
                                             bad_creator_per_block=0,
                                             bad_endorsement_per_block=0), 1)
    flags, state = validator.run_reference(w.ca_certs_pem, 5, w.blocks)
    assert flags == [[generator.ENDORSEMENT_POLICY_FAILURE] * 10] and state == {}


def test_an_identity_of_an_unknown_ca_counts_for_no_org(world):
    from reference import validator

    other = generator.build_world(SEED + 1, DEPLOY, PLANTED, 1)
    flags, _ = validator.run_reference(other.ca_certs_pem, 5, world.blocks[:1])
    assert set(flags[0]) == {generator.BAD_CREATOR_SIGNATURE}


def test_the_seed_fixes_the_inputs(world):
    again = generator.build_world(SEED, DEPLOY, PLANTED, 3)
    assert again.planted == world.planted and again.writes == world.writes
    assert again.ca_certs_pem.keys() == world.ca_certs_pem.keys()
    keys = lambda w: [o.ca.key.private_numbers().private_value for o in w.orgs]  # noqa: E731
    assert keys(again) == keys(world)
    other = generator.build_world(SEED + 1, DEPLOY, PLANTED, 3)
    assert other.writes != world.writes and keys(other) != keys(world)


def test_the_reference_imports_nothing_of_the_code_under_test():
    with open(os.path.join(BENCH, "reference", "validator.py")) as f:
        tree = ast.parse(f.read())
    mods = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            mods.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            mods.add(node.module or "")
    ours = {m for m in mods if m.startswith("fabric_tpu")}
    assert ours and all(m.startswith("fabric_tpu.protos") for m in ours), ours
