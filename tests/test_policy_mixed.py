"""Mixed chaincodes and nested endorsement policies on the peer's normal
path (`benchmarks/configs/mixedcc-8cc-5org-1000tx.json`), on the CPU at
a small size and seeded.  Four properties, a parametrised test each:

- for random rule trees of depth up to 3 over five organisations of two
  peers each, random endorser sequences (duplicates, permutations, both
  peers of an organisation) and random masks, three evaluators give one
  verdict: `SignaturePolicy.prepare().finish`, `EndorsementPlan.decide`
  through `_PlanPending` (what a block's transactions go through), and
  the plain reference's own walk of the tree
  (`benchmarks/reference/x509-mixedcc.py`, written from cauthdsl's
  description and importing none of the program);
- a block whose endorsement-plan cache clears mid-block yields the flags
  of a validator that keeps no plans;
- a transaction that writes two namespaces fails when either
  chaincode's policy is unmet;
- `tolerated_bad_lanes` (the `policy` span, `tolerated_tally()`) counts
  the corrupted endorsements the world planted in transactions that
  stay VALID.

No number of a CPU run is a device number: the tests read counts, flags
and verdicts, never a time."""

import json
import os
import random
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from fabric_tpu.common import tracing  # noqa: E402
from fabric_tpu.csp import SWCSP  # noqa: E402

SEED = 2**31 + 52
BLOCK_TXS = 60
VALID, POLICY = 0, 10
ROLES = ("peer", "peer", "peer", "member", "client")


@pytest.fixture(scope="module")
def man():
    from benchlib.manifest import Manifest

    return Manifest(ROOT)


@pytest.fixture(scope="module")
def held():
    with open(os.path.join(BENCH, "configs", "mixedcc-8cc-5org-1000tx.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def mixed(man, held):
    """The world's module: its `Net` and `Tx` build the hand-made blocks."""
    man.world(held)
    return sys.modules["bench_worlds_x509_mixedcc"]


@pytest.fixture(scope="module")
def reference(man, held):
    man.reference(held)
    return sys.modules["bench_reference_x509_mixedcc"]


def _world(man, held, seed, block_txs=BLOCK_TXS, n_blocks=2):
    dep = dict(held["deployment"], block_txs=block_txs)
    return man.world(held)(seed, dep, held["planted"], n_blocks)


def _blocks(raw):
    from fabric_tpu.protos.common import common_pb2

    return [common_pb2.Block.FromString(b) for b in raw]


def _validator(world, plans=True, definitions=True, metrics=None):
    from fabric_tpu.common.channelconfig import bundle_from_genesis
    from fabric_tpu.ledger import LedgerProvider
    from fabric_tpu.peer.txvalidator import TxValidator
    from fabric_tpu.peer.validation_plugins import PluginRegistry

    csp = SWCSP()
    ledger = LedgerProvider(None).create(world.genesis)
    return TxValidator(
        world.channel, ledger, bundle_from_genesis(world.genesis, csp), csp,
        definition_provider=world.definition_provider if definitions else None,
        plugin_registry=PluginRegistry(plans=plans), metrics=metrics)


# -- three evaluators, one verdict --------------------------------------------


@pytest.fixture(scope="module")
def net(mixed, held):
    """A channel of the deployment's shape and its identities."""
    from fabric_tpu.common.channelconfig import bundle_from_genesis

    network = mixed.Net(random.Random("policy-mixed"), dict(held["deployment"], block_txs=4))
    bundle = bundle_from_genesis(network.genesis, SWCSP())
    serialized = [[p.serialize() for p in peers] for peers in network.peers]
    return network, bundle, serialized


def _random_envelope(rng: random.Random, n_orgs: int):
    """A rule tree of depth up to 3 over `OrgNMSP.<role>` principals;
    now and then an `n` no rule list can meet, or 0."""
    from fabric_tpu.policies import policydsl, signature_policy as sp
    from fabric_tpu.protos.common import policies_pb2

    principals, index = [], {}

    def leaf():
        spec = f"Org{rng.randrange(n_orgs) + 1}MSP.{rng.choice(ROLES)}"
        if spec not in index:
            index[spec] = len(principals)
            principals.append(policydsl.from_string(f"OR('{spec}')").identities[0])
        return sp.signed_by(index[spec])

    def tree(depth):
        if depth == 0 or (depth < 3 and rng.random() < 0.35):
            return leaf()
        rules = [tree(depth - 1) for _ in range(rng.randint(1, 4))]
        n = rng.randint(1, len(rules))
        if rng.random() < 0.08:
            n = rng.choice((0, len(rules) + 1))
        return sp.n_out_of(n, rules)

    return policies_pb2.SignaturePolicyEnvelope(
        version=0, rule=tree(3), identities=principals)


def _random_endorsers(rng: random.Random, n_orgs: int, per_org: int) -> list:
    """(org, peer) in the order of the endorsements: a draw of the
    identities, some of them twice."""
    everyone = [(o, k) for o in range(n_orgs) for k in range(per_org)]
    picked = rng.sample(everyone, rng.randint(1, len(everyone)))
    picked += [rng.choice(picked) for _ in range(rng.randint(0, 3))]
    rng.shuffle(picked)
    return picked


@pytest.mark.parametrize("case", range(48))
def test_three_evaluators_of_a_nested_rule_give_one_verdict(case, net, reference):
    from fabric_tpu.peer.validation_plugins import (
        BuiltinV20Plugin,
        PolicyProvider,
        ValidationContext,
    )
    from fabric_tpu.policies.signature_policy import SignaturePolicy
    from fabric_tpu.protoutil import SignedData

    network, bundle, serialized = net
    rng = random.Random(f"policy-mixed:{case}")
    n_orgs, per_org = len(serialized), len(serialized[0])
    envelope = _random_envelope(rng, n_orgs)
    policy = SignaturePolicy(envelope, bundle.msp_manager)
    plugin = BuiltinV20Plugin()
    provider = PolicyProvider(bundle.policy_manager, bundle.msp_manager)
    for _round in range(6):
        endorsers = _random_endorsers(rng, n_orgs, per_org)
        signed = [SignedData(b"", serialized[o][k], b"sig", digest=bytes(32))
                  for o, k in endorsers]
        distinct = list(dict.fromkeys(endorsers))
        mask = [rng.random() < 0.75 for _ in distinct]
        # 1. the policy by itself: an item a distinct identity
        pending = policy.prepare(signed)
        assert len(pending.items) == len(distinct)
        direct = pending.finish(mask)
        # 2. through the plan a block's transactions share
        ctx = ValidationContext(
            channel_id="benchch", namespace="cc", tx_pos=-1, endorsements=signed,
            rwset_bytes=None, policy_provider=provider, state_metadata=lambda ns, key: {})
        planned = plugin._plan_pending(ctx, [policy])
        assert len(planned.items) == len(distinct)
        through_plan = planned.finish(mask)
        # 3. the plain reference's walk, over (mspid, OUs) or None
        idents = [(network.orgs[o].mspid, {"peer"}) if ok else None
                  for (o, _k), ok in zip(distinct, mask)]
        plain = reference.envelope_met(envelope, idents)
        assert direct == through_plan == plain, (case, endorsers, mask, str(envelope.rule))
    assert plugin.plan_misses >= 1 and plugin.plan_build_s > 0.0


# -- the plan cache clears mid-block ------------------------------------------


@pytest.mark.parametrize("seed", [SEED, SEED + 1, SEED + 2])
def test_a_plan_cache_that_clears_mid_block_changes_no_flag(seed, man, held, monkeypatch):
    from fabric_tpu.peer.validation_plugins import BuiltinV20Plugin

    world = _world(man, held, seed)
    monkeypatch.setattr(BuiltinV20Plugin, "_PLAN_CAP", 7)
    with_plans, without = _validator(world), _validator(world, plans=False)
    plugin = with_plans._registry.plugin("vscc")
    for raw, planted in zip(_blocks(world.blocks), world.planted):
        clears = plugin.plan_clears
        kept = with_plans.validate(raw)
        assert plugin.plan_clears - clears >= 2      # the cache ran over inside this block
        fresh = without.validate(_blocks([raw.SerializeToString()])[0])
        # before MVCC: a conflict's second is still VALID here
        want = [VALID if f == 11 else f for f in planted]
        assert list(kept) == list(fresh) == want
    assert without._registry.plugin("vscc").plan_hits == 0


# -- two namespaces, two policies ---------------------------------------------


# cc1: OutOf(2, Org1, Org2, Org3); cc3: OR(Org1, AND(Org2, Org3)); organisations by index
@pytest.mark.parametrize("endorsers,flag", [
    ([(0, 0), (1, 1)], VALID),              # Org1 + Org2: both met
    ([(2, 0), (1, 0)], VALID),              # Org2 + Org3: cc3 by its AND
    ([(0, 1)], POLICY),                     # Org1 alone: cc3 met, cc1 not
    ([(1, 0), (3, 0)], POLICY),             # Org2 + Org4: neither
    ([(1, 0), (1, 1), (3, 1)], POLICY),     # both peers of Org2: one organisation once
    ([(0, 0), (0, 0), (4, 0)], POLICY),     # one identity twice: cc1 still wants another
], ids=["both_met", "both_met_by_and", "first_unmet", "neither", "same_org_twice",
        "duplicate_endorser"])
def test_a_two_namespace_transaction_needs_both_policies(endorsers, flag, mixed, held, man):
    from fabric_tpu.protos.common import common_pb2

    world = _world(man, held, SEED, block_txs=12, n_blocks=1)
    rng = random.Random("two-namespaces")
    network = mixed.Net(rng, dict(held["deployment"], block_txs=4))
    names = list(world.namespaces)
    cc1, cc3 = names.index("cc1"), names.index("cc3")
    block = common_pb2.Block()
    block.header.number = 1
    for namespaces in ((cc1, cc3), (cc3, cc1)):     # whichever of them is invoked
        tx = mixed.Tx(namespaces=namespaces, endorsers=endorsers, key=f"k{namespaces[0]}",
                      values=(b"a", b"b"))
        block.data.data.append(network.envelope(rng, tx, names))
    while len(block.metadata.metadata) < 3:
        block.metadata.metadata.append(b"")
    # this network's own genesis: its CAs issued these endorsers
    own = type("W", (), {"genesis": network.genesis, "channel": world.channel,
                         "definition_provider": world.definition_provider})
    validator = _validator(own)
    assert list(validator.validate(block)) == [flag, flag]
    # and under the channel's default alone both want three organisations
    assert list(_validator(own, definitions=False).validate(
        common_pb2.Block.FromString(block.SerializeToString()))) == [POLICY, POLICY]


# -- tolerated lanes ------------------------------------------------------------


@pytest.mark.parametrize("seed", [SEED + 10, SEED + 11, SEED + 12])
def test_tolerated_bad_lanes_count_what_the_world_planted(seed, man, held):
    from fabric_tpu.peer.txvalidator import tolerated_tally

    from fabric_tpu.common.operations import System

    world = _world(man, held, seed)
    assert all(n >= 1 for n in world.tolerated_lanes)
    ops = System()
    validator = _validator(world, metrics=ops.validate_metrics())
    # on the page from the start
    assert "validator_tolerated_bad_endorsements_total" in ops.metrics_provider.registry.expose()
    before = tolerated_tally()
    tracing.arm(1 << 12)
    try:
        for raw in _blocks(world.blocks):
            validator.validate(raw)
        events = tracing.export()["traceEvents"]
    finally:
        tracing.disarm()
    after = tolerated_tally()
    n = len(world.blocks)
    assert after["recent_blocks"][-n:] == [
        (1 + b, lanes) for b, lanes in enumerate(world.tolerated_lanes)]
    assert after["tolerated_bad_lanes"] - before["tolerated_bad_lanes"] \
        == sum(world.tolerated_lanes)
    assert (f'validator_tolerated_bad_endorsements_total{{channel="{world.channel}"}} '
            f'{sum(world.tolerated_lanes)}') in ops.metrics_provider.registry.expose()
    policy = [e["args"] for e in events if e.get("ph") == "X" and e["name"] == "policy"]
    assert [a["tolerated_bad_lanes"] for a in policy] == world.tolerated_lanes
    collect = [e["args"] for e in events if e.get("ph") == "X" and e["name"] == "collect"]
    # a prepare a transaction and written namespace
    assert [a["namespace_prepares"] for a in collect] \
        == [sum(len(t.namespaces) for t in txs) for txs in world.txs]
    # cc0 alone has no definition
    plain = [sum(1 for t in txs for c in t.namespaces if world.namespaces[c] == "cc0")
             for txs in world.txs]
    assert [a["definitions_resolved"] for a in collect] \
        == [a["namespace_prepares"] - p for a, p in zip(collect, plain)]
    assert all(a["plan_build_ms"] > 0.0 for a in collect)
