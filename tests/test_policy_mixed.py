"""Mixed chaincodes and nested endorsement policies on the peer's normal
path (`benchmarks/configs/mixedcc-8cc-5org-1000tx.json`), on the CPU at
a small size and seeded.  Five properties, a parametrised test each
(the second, six):

- for random rule trees of depth up to 3 over five organisations of two
  peers each, random endorser sequences (duplicates, permutations, both
  peers of an organisation) and random masks, three evaluators give one
  verdict: `SignaturePolicy.prepare().finish`, `EndorsementPlan.decide`
  through `_PlanPending` (what a block's transactions go through), and
  the plain reference's own walk of the tree
  (`benchmarks/reference/x509-mixedcc.py`, written from cauthdsl's
  description and importing none of the program);
- a plan is shared by what its policies can see of an endorser, not by
  who the endorser is: endorser sets of one class (both peers of an
  organisation under `OrgNMSP.<role>` principals, every order) find one
  plan and its three verdicts agree; a principal that splits an
  organisation's peers (an IDENTITY, an OU one of them lacks) splits
  their plans; a shared plan verifies under the transaction's own key;
  a policy object that cannot list its principals is keyed by identity;
  a new policy object finds neither old plans nor old classes;
- a block whose endorsement-plan cache overflows mid-block yields the
  flags of a validator that keeps no plans, and an overflow drops only
  plans nobody asked for;
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


# -- a plan is shared by the class of its endorsers ---------------------------


def _context(provider, signed):
    from fabric_tpu.peer.validation_plugins import ValidationContext

    return ValidationContext(
        channel_id="benchch", namespace="cc", tx_pos=-1, endorsements=signed,
        rwset_bytes=None, policy_provider=provider, state_metadata=lambda ns, key: {})


def _unsigned(serialized, endorsers):
    from fabric_tpu.protoutil import SignedData

    return [SignedData(b"", serialized[o][k], b"sig", digest=bytes(32)) for o, k in endorsers]


def _orders_and_peers(rng: random.Random, n_orgs: int, per_org: int):
    """Endorser sets that differ in the order of their organisations
    and in WHICH peer of each signed: every order of a draw of one to
    three organisations, under every choice of their peers."""
    import itertools

    orgs = rng.sample(range(n_orgs), rng.randint(1, 3))
    for order in itertools.permutations(orgs):
        for peers in itertools.product(range(per_org), repeat=len(order)):
            yield list(zip(order, peers))


@pytest.mark.parametrize("case", range(48))
def test_endorser_sets_of_one_class_share_a_plan_and_its_verdicts(case, net, reference):
    """ONE plugin, the random trees of the test above (`OrgNMSP.<role>`
    principals, which both peers of an organisation answer alike): a
    plan an order of organisations at most, whoever of them signed, and
    the verdict through it is the policy's own and the reference's."""
    from fabric_tpu.peer.validation_plugins import BuiltinV20Plugin, PolicyProvider
    from fabric_tpu.policies.signature_policy import SignaturePolicy

    network, bundle, serialized = net
    rng = random.Random(f"policy-shared:{case}")
    n_orgs, per_org = len(serialized), len(serialized[0])
    envelope = _random_envelope(rng, n_orgs)
    policy = SignaturePolicy(envelope, bundle.msp_manager)
    plugin = BuiltinV20Plugin()
    provider = PolicyProvider(bundle.policy_manager, bundle.msp_manager)
    orders, lookups = set(), 0
    for _round in range(3):
        for endorsers in _orders_and_peers(rng, n_orgs, per_org):
            signed = _unsigned(serialized, endorsers)
            mask = [rng.random() < 0.75 for _ in endorsers]
            direct = policy.prepare(signed).finish(mask)
            planned = plugin._plan_pending(_context(provider, signed), [policy])
            assert len(planned.items) == len(endorsers)
            idents = [(network.orgs[o].mspid, {"peer"}) if ok else None
                      for (o, _k), ok in zip(endorsers, mask)]
            assert direct == planned.finish(mask) == reference.envelope_met(envelope, idents), \
                (case, endorsers, mask, str(envelope.rule))
            orders.add(tuple(o for o, _k in endorsers))
            lookups += 1
    assert plugin.plan_hits + plugin.plan_misses == lookups
    # organisations the tree does not name look alike too: fewer still
    assert 1 <= plugin.plan_misses <= len(orders)
    assert plugin.plan_shared_hits > 0
    assert plugin.plan_shared_hits <= plugin.plan_hits


@pytest.fixture(scope="module")
def auditors(net):
    """The channel's identities with the second peer of each
    organisation issued again, by the same CA, with the OU `audit`
    beside `peer`."""
    from benchlib.generator import _seeded_key
    from fabric_tpu.common.crypto import CertKeyPair
    from fabric_tpu.msp import SigningIdentity

    network, _bundle, serialized = net
    rng = random.Random("policy-mixed-auditors")
    out = []
    for o, org in enumerate(network.orgs):
        key = _seeded_key(rng)
        cert = org.ca.issue_for_public_key(
            f"peer1.org{o + 1}", key.public_key(), ous=["peer", "audit"])
        pair = CertKeyPair(cert, key)
        audited = SigningIdentity.from_pem(org.mspid, pair.cert_pem, pair.key_pem, org.csp)
        out.append([serialized[o][0], audited.serialize()])
    return out


def _splitting_envelope(rng: random.Random, kind: str, orgs: list, network, serialized):
    """A rule tree of depth up to 2 whose leaves, for every organisation
    of `orgs`, include one that only ONE of its two peers satisfies: an
    IDENTITY principal naming the first, or the OU `audit` that only the
    second holds; `OrgNMSP.peer` leaves stand among them.  Beside the
    envelope, each principal as the reference's walk is told it."""
    from fabric_tpu.policies import policydsl, signature_policy as sp
    from fabric_tpu.protos.common import policies_pb2
    from fabric_tpu.protos.msp import msp_principal_pb2 as mp

    principals, told, leaves = [], [], []
    for o in orgs:
        mspid = network.orgs[o].mspid
        if kind == "identity":
            principals.append(mp.MSPPrincipal(
                principal_classification=mp.MSPPrincipal.IDENTITY,
                principal=serialized[o][0]))
            told.append(("identity", (o, 0)))
        else:
            principals.append(mp.MSPPrincipal(
                principal_classification=mp.MSPPrincipal.ORGANIZATION_UNIT,
                principal=mp.OrganizationUnit(
                    msp_identifier=mspid,
                    organizational_unit_identifier="audit").SerializeToString()))
            told.append(("ou", mspid, "audit"))
        leaves.append(sp.signed_by(len(principals) - 1))
        if rng.random() < 0.5:
            principals.append(policydsl.from_string(f"OR('{mspid}.peer')").identities[0])
            told.append(("ou", mspid, "peer"))
            leaves.append(sp.signed_by(len(principals) - 1))
    rng.shuffle(leaves)
    cut = rng.randint(1, len(leaves))
    rules = [sp.n_out_of(rng.randint(1, cut), leaves[:cut])] + leaves[cut:]
    return policies_pb2.SignaturePolicyEnvelope(
        version=0, rule=sp.n_out_of(rng.randint(1, len(rules)), rules),
        identities=principals), told


@pytest.mark.parametrize("kind", ["identity", "ou"])
@pytest.mark.parametrize("case", range(12))
def test_a_principal_that_splits_an_organisation_splits_its_plans(
        case, kind, net, auditors, reference, monkeypatch):
    """The same three verdicts where a principal tells an organisation's
    two peers apart: they get different classes, and no endorser set
    finds a plan that other identities built."""
    from fabric_tpu.peer.validation_plugins import BuiltinV20Plugin, PolicyProvider
    from fabric_tpu.policies.signature_policy import SignaturePolicy

    network, bundle, _serialized = net
    serialized = auditors
    rng = random.Random(f"policy-split:{kind}:{case}")
    n_orgs, per_org = len(serialized), len(serialized[0])
    orgs = rng.sample(range(n_orgs), 3)
    envelope, told = _splitting_envelope(rng, kind, orgs, network, serialized)
    policy = SignaturePolicy(envelope, bundle.msp_manager)
    plugin = BuiltinV20Plugin()
    provider = PolicyProvider(bundle.policy_manager, bundle.msp_manager)

    # the reference's walk, its leaf told what this test's principals mean
    def satisfies(ident, principal):
        if principal[0] == "identity":
            return ident[2] == principal[1]
        return ident[0] == principal[1] and principal[2] in ident[1]

    monkeypatch.setattr(reference, "satisfies", satisfies)
    for o in orgs:
        (first, _k0), (second, _k1) = plugin._learn(
            (policy,), tuple(serialized[o]), bundle.msp_manager)
        assert first != second and isinstance(first, int) and isinstance(second, int)
    def person(o, k):
        return (network.orgs[o].mspid, {"peer", "audit"} if k else {"peer"}, (o, k))

    plans, looks = {}, set()
    for _round in range(3):
        for endorsers in _orders_and_peers(random.Random(rng.random()), 3, per_org):
            endorsers = [(orgs[i], k) for i, k in endorsers]
            signed = _unsigned(serialized, endorsers)
            mask = [rng.random() < 0.8 for _ in endorsers]
            direct = policy.prepare(signed).finish(mask)
            planned = plugin._plan_pending(_context(provider, signed), [policy])
            idents = [person(o, k) if ok else None for (o, k), ok in zip(endorsers, mask)]
            plain = reference.holds(envelope.rule, told, idents, [False] * len(idents))
            assert direct == planned.finish(mask) == plain, \
                (case, kind, endorsers, mask, str(envelope.rule))
            assert plans.setdefault(tuple(endorsers), planned._plan) is planned._plan
            # what the tree can see of these endorsers, by this test's own reading
            looks.add(tuple(tuple(satisfies(person(o, k), t) for t in told)
                            for o, k in endorsers))
    # a plan a sequence of looks (a peer that satisfies nothing is anybody's like) ...
    assert plugin.plan_misses == len(looks) == len({id(p) for p in plans.values()})
    # ... and never one for an endorser set and the same with an organisation's OTHER peer
    for endorsers, plan in plans.items():
        for pos, (o, k) in enumerate(endorsers):
            other = endorsers[:pos] + ((o, 1 - k),) + endorsers[pos + 1:]
            assert plans[other] is not plan


def test_a_shared_plan_verifies_under_the_transactions_own_key(net):
    """The plan was built for peer A; peer B's endorsement finds it, and
    its lane carries B's key: B's sound signature passes, and A's
    signature under B's identity does not."""
    import hashlib

    from fabric_tpu.peer.validation_plugins import BuiltinV20Plugin, PolicyProvider
    from fabric_tpu.policies import policydsl
    from fabric_tpu.policies.signature_policy import SignaturePolicy
    from fabric_tpu.protoutil import SignedData

    network, bundle, serialized = net
    csp = SWCSP()
    policy = SignaturePolicy(policydsl.from_string("AND('Org2MSP.peer')"), bundle.msp_manager)
    plugin = BuiltinV20Plugin()
    provider = PolicyProvider(bundle.policy_manager, bundle.msp_manager)
    a, b = network.peers[1]
    message = b"a proposal response and its endorser"
    digest = hashlib.sha256(message).digest()

    def decided(identity: bytes, signature: bytes):
        signed = [SignedData(message, identity, signature, digest=digest)]
        pending = plugin._plan_pending(_context(provider, signed), [policy])
        [item] = pending.items
        mask = csp.verify_batch(pending.items)
        return item.key, list(mask), pending.finish(mask)

    key_a, mask, verdict = decided(serialized[1][0], a.sign(message))
    assert (mask, verdict) == ([True], True) and plugin.plan_misses == 1
    key_b, mask, verdict = decided(serialized[1][1], b.sign(message))
    assert (mask, verdict) == ([True], True)
    assert (plugin.plan_misses, plugin.plan_hits, plugin.plan_shared_hits) == (1, 1, 1)
    assert key_b is not key_a
    assert key_b is bundle.msp_manager.deserialize_identity(serialized[1][1]).public_key
    # A's signature presented under B's identity: B's key refuses it
    key, mask, verdict = decided(serialized[1][1], a.sign(message))
    assert key is key_b and (mask, verdict) == ([False], False)
    assert (plugin.plan_misses, plugin.plan_shared_hits) == (1, 2)
    # an endorser that does not deserialize has no lane, whoever built the plan
    signed = [SignedData(message, b"not an identity", b"sig", digest=digest),
              SignedData(message, serialized[1][0], a.sign(message), digest=digest)]
    pending = plugin._plan_pending(_context(provider, signed), [policy])
    assert len(pending.items) == 1 and pending.finish(csp.verify_batch(pending.items)) is True
    signed[0] = SignedData(message, b"nor is this", b"sig", digest=digest)
    signed[1] = SignedData(message, serialized[1][1], b.sign(message), digest=digest)
    misses = plugin.plan_misses
    pending = plugin._plan_pending(_context(provider, signed), [policy])
    assert plugin.plan_misses == misses
    assert len(pending.items) == 1 and pending.finish(csp.verify_batch(pending.items)) is True


class _Opaque:
    """A policy object of somebody else's: it prepares and finishes, and
    says nothing of the principals it asks about."""

    def __init__(self, inner):
        self._inner = inner

    def prepare(self, signed_data):
        return self._inner.prepare(signed_data)


def test_a_policy_that_cannot_list_its_principals_is_keyed_by_identity(net):
    from fabric_tpu.peer.validation_plugins import BuiltinV20Plugin, PolicyProvider
    from fabric_tpu.policies import policydsl
    from fabric_tpu.policies.manager import ImplicitMetaPolicy
    from fabric_tpu.policies.signature_policy import SignaturePolicy, principals_of
    from fabric_tpu.protos.common import policies_pb2

    _network, bundle, serialized = net
    inner = SignaturePolicy(
        policydsl.from_string("OutOf(2, 'Org1MSP.peer', 'Org2MSP.peer', 'Org3MSP.peer')"),
        bundle.msp_manager)
    provider = PolicyProvider(bundle.policy_manager, bundle.msp_manager)
    opaque = _Opaque(inner)
    assert principals_of([opaque]) is None and len(principals_of([inner])) == 3
    # a meta policy over one is as silent, and a reject asks nothing
    meta = ImplicitMetaPolicy([inner, opaque], policies_pb2.ImplicitMetaPolicy.ANY)
    assert principals_of([meta]) is None and principals_of([inner, opaque]) is None
    assert len(principals_of([ImplicitMetaPolicy([inner, inner], 0)])) == 6
    assert principals_of([bundle.policy_manager.get_policy("/Channel/Nowhere")]) == []
    for policies in ([opaque], [inner, opaque], [meta]):
        plugin = BuiltinV20Plugin()
        sets = set()
        for endorsers in _orders_and_peers(random.Random("opaque"), 3, 2):
            signed = _unsigned(serialized, endorsers)
            for mask in ([True] * len(endorsers), [False] + [True] * (len(endorsers) - 1)):
                planned = plugin._plan_pending(_context(provider, signed), policies)
                # a meta policy's pending holds an item a sub-policy and identity
                direct = [p.prepare(signed) for p in policies]
                assert planned.finish(mask) == all(
                    d.finish(mask * (len(d.items) // len(mask))) for d in direct)
            sets.add(tuple(endorsers))
        assert plugin.plan_misses == len(sets) and plugin.plan_shared_hits == 0
        assert plugin.plan_hits == len(sets)
        classes = {c for seen in plugin._seen.values() for c, _key in seen.values()}
        assert classes <= {i for org in serialized for i in org}


def test_a_new_policy_object_finds_neither_old_plans_nor_old_classes(net):
    """A channel-config update builds a new bundle: new MSPs, new policy
    objects.  Nothing learnt under the old ones answers for the new."""
    from fabric_tpu.common.channelconfig import bundle_from_genesis
    from fabric_tpu.peer.validation_plugins import BuiltinV20Plugin, PolicyProvider

    network, bundle, serialized = net
    plugin = BuiltinV20Plugin()
    endorsers = [(0, 0), (1, 0), (2, 0)]
    signed = _unsigned(serialized, endorsers)

    def lookup(b):
        provider = PolicyProvider(b.policy_manager, b.msp_manager)
        policy = provider.default_policy()
        plugin._plan_pending(_context(provider, signed), [policy])
        return policy

    old = lookup(bundle)
    lookup(bundle)
    assert (plugin.plan_misses, plugin.plan_hits) == (1, 1)
    updated = bundle_from_genesis(network.genesis, SWCSP())
    assert (updated.policy_manager.get_policy("/Channel/Application/Endorsement")
            is not old)
    asked = []
    inner = updated.msp_manager.satisfies_principal

    def satisfies_principal(ident, principal):
        asked.append(ident)
        return inner(ident, principal)

    updated.msp_manager.satisfies_principal = satisfies_principal
    new = lookup(updated)
    assert (plugin.plan_misses, plugin.plan_hits, plugin.plan_shared_hits) == (2, 1, 0)
    # every endorser was asked again, of the NEW deserializer
    assert len({id(i) for i in asked}) == len(endorsers)
    assert set(plugin._seen) == {(old,), (new,)}
    assert all(len(seen) == len(endorsers) for seen in plugin._seen.values())
    asked.clear()
    lookup(updated)
    assert not asked and plugin.plan_hits == 2


# -- the plan cache overflows mid-block ---------------------------------------


@pytest.mark.parametrize("seed", [SEED, SEED + 1, SEED + 2])
def test_a_plan_cache_that_clears_mid_block_changes_no_flag(seed, man, held, net, monkeypatch):
    from fabric_tpu.peer.validation_plugins import BuiltinV20Plugin, PolicyProvider

    world = _world(man, held, seed)
    cap = 7
    monkeypatch.setattr(BuiltinV20Plugin, "_PLAN_CAP", cap)
    with_plans, without = _validator(world), _validator(world, plans=False)
    plugin = with_plans._registry.plugin("vscc")
    for raw, planted in zip(_blocks(world.blocks), world.planted):
        clears = plugin.plan_clears
        kept = with_plans.validate(raw)
        # the cache ran over inside this block, and neither generation passed the bound
        assert plugin.plan_clears > clears
        assert 0 < len(plugin._plans) <= cap and 0 < len(plugin._old_plans) <= cap
        fresh = without.validate(_blocks([raw.SerializeToString()])[0])
        # before MVCC: a conflict's second is still VALID here
        want = [VALID if f == 11 else f for f in planted]
        assert list(kept) == list(fresh) == want
    assert without._registry.plugin("vscc").plan_hits == 0

    # an overflow drops the plans nobody asked for, never one in use:
    # one endorser set asked for between every two others, five
    # generations of them, is built once
    import itertools

    _network, bundle, serialized = net
    provider = PolicyProvider(bundle.policy_manager, bundle.msp_manager)
    policy = provider.default_policy()
    orders = [o for n in (3, 4) for o in itertools.permutations(range(len(serialized)), n)]
    random.Random(seed).shuffle(orders)
    hot, cold = orders[0], orders[1:1 + 5 * cap]

    def ask(order) -> int:
        misses = plugin.plan_misses
        plugin._plan_pending(
            _context(provider, _unsigned(serialized, [(o, 0) for o in order])), [policy])
        assert len(plugin._plans) <= cap and len(plugin._old_plans) <= cap
        return plugin.plan_misses - misses

    assert ask(hot) == 1
    clears = plugin.plan_clears
    for order in cold:
        assert ask(order) == 1 and ask(hot) == 0
    assert plugin.plan_clears - clears >= 4
    # and the first of the others went with its generation
    assert ask(cold[0]) == 1


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
