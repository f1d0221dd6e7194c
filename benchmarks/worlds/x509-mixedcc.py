"""The world of `mixedcc-8cc-5org-1000tx`: the five-organisation
MAJORITY channel of `x509-majority` (`benchlib/generator.py`: its
seeded CAs, `Org.signer`, the corrupted signatures) hosting EIGHT
chaincodes, each under the endorsement policy its definition gives, as
Fabric's `docs/source/endorsement-policies.rst` writes them ("Endorsement
policy syntax": `AND`, `OR`, `OutOf`, nested; "Multiple ways to specify
endorsement policies": by definition, by the channel's default, by a
reference to a channel policy), with two endorsing peers an organisation
(fabric-samples `crypto-config.yaml`, `Template.Count: 2`) and the
endorsers of a transaction drawn as a client draws them from discovery's
endorsement descriptor (`docs/source/discovery-overview.rst`: a layout is
a quantity of peers a group, the client picks a layout and peers of each
group).

The policies are the configuration's (`deployment["policies"]`): a
signature policy is parsed by the program's `policies/policydsl.
from_string` and wrapped as the `ApplicationPolicy` a committed
definition carries; a `policy_reference` becomes the
`channel_config_policy_reference` arm; a chaincode without either has no
definition and falls to the channel's default.  The world hands the
engine a `definition_provider` that answers `validation_info` from a
dict, as a peer's lifecycle cache would.

A transaction invokes a chaincode drawn by Zipfian popularity over the
configuration's rank (`zipf_constant`), writes one fresh key of it, and
with `two_namespace_share` also one key of a second chaincode drawn the
same way (a chaincode-to-chaincode call, `docs/source/chaincode4ade.rst`):
both namespaces stand in its read-write set and each is decided under
its own policy.  Its endorsers are one of its policy's MINIMAL satisfying
layouts, drawn uniformly among them (of two namespaces: the union of one
of each), a peer of each organisation drawn uniformly of its
`peers_per_org` (both, where the layout wants two of one organisation);
with `over_endorsed_share` one more endorsement, of an organisation
outside the layout; the endorsements in a seeded random order.

A layout is found from the policy itself (`_Policy.layouts`): of the
multisets of organisations (0 to `peers_per_org` of each) those that
satisfy the rule and lose it with any one endorsement taken away.  The
rule is evaluated as upstream's `common/cauthdsl/cauthdsl.go` evaluates
it (`_Policy.met`: identities deduplicated, taken in the order of the
endorsements, an identity standing for one principal only, a sub-rule
that fails consuming none): the world's own few lines, so that the flags
it plants are a third opinion beside the program's and the plain
reference's.

Planted, so that every wrong short cut is wrong in every block
(`planted` of the configuration; a block too small for all of them, a
test's, takes one of each in this order and then the rest, as far as
five sixths of its transactions go):

    bad_creator                     a corrupted creator signature:
                                    BAD_CREATOR_SIGNATURE
    bad_endorsement_breaks_policy   one corrupted endorsement of a
                                    minimally endorsed transaction:
                                    ENDORSEMENT_POLICY_FAILURE
    bad_endorsement_policy_still_met  the EXTRA endorsement of an
                                    over-endorsed transaction corrupted:
                                    VALID, with a false bit in its mask
    wrong_orgs                      every signature sound, a layout of
                                    ANOTHER chaincode's policy that does
                                    not meet this one's: failure
    same_org_twice                  both peers of one organisation in
                                    the place of two organisations: failure
    duplicate_endorser              one identity's endorsement twice in
                                    the place of two identities': counted
                                    once, failure
    greedy_order                    the chaincode whose rule names one
                                    organisation in two sub-rules (`cc6`)
                                    endorsed by ONE peer of it where the
                                    layout wants both: the second sub-rule
                                    finds it used: failure
    second_namespace_unmet          a two-namespace transaction endorsed
                                    for its first chaincode alone, which
                                    does not meet the second's policy:
                                    failure
    conflict_pairs                  two transactions that read one absent
                                    key and write it: the second
                                    MVCC_READ_CONFLICT

What the world keeps for the condition `mixedcc-shape`, the
configuration's file and the run's lines: per block the planted classes
and those due, the chaincodes drawn and those due (a chaincode is due
in a block that would hold `DUE_AT_LEAST` of its transactions by its
popularity), the endorsement lanes of VALID transactions that carry a
corrupted signature (`tolerated_lanes`), the signature lanes, and the
distinct endorsement-plan keys of the pass (`plan_keys`: a namespace's
policy and the ordered distinct endorser identities, the default policy
one policy under whichever chaincode).

What `--seed` fixes: every key, value, nonce, draw, layout, peer, order
and planted place; what stays random, as in the accepted worlds: ECDSA
signature nonces, certificate serial numbers and validity instants.
Nothing here touches JAX.

The world is built only for a program that counts what the condition
reads (`peer.txvalidator.tolerated_tally`): a checkout without it is
refused before anything is measured.
"""

from __future__ import annotations

import dataclasses
import itertools
import random

from benchlib.generator import (
    BAD_CREATOR_SIGNATURE,
    CHANNEL,
    ENDORSEMENT_POLICY_FAILURE,
    MVCC_READ_CONFLICT,
    VALID,
    Org,
    _flip_last_byte,
    _seeded_ca,
)
from benchlib.manifest import ManifestError

DEFAULT_POLICY = "/Channel/Application/Endorsement"
DUE_AT_LEAST = 8        # expected transactions of a chaincode in a block that owes it

# the planted classes, in the order a small block takes them, and the
# transactions one of each takes
CLASSES = ("bad_creator", "bad_endorsement_breaks_policy", "bad_endorsement_policy_still_met",
           "wrong_orgs", "same_org_twice", "duplicate_endorser", "greedy_order",
           "second_namespace_unmet", "conflict_pairs")
_TXS_OF = {c: 2 if c == "conflict_pairs" else 1 for c in CLASSES}


class _Policy:
    """One chaincode's rule over organisation indices, evaluated as
    cauthdsl evaluates it.  `tree` is ("signed_by", org) or ("n_out_of",
    n, [tree, ...]); the channel's default (ImplicitMeta MAJORITY over
    the organisations' `OrgN.peer` rules) is a majority of leaves."""

    def __init__(self, tree: tuple, n_orgs: int, peers_per_org: int):
        self.tree = tree
        self._n_orgs, self._per_org = n_orgs, peers_per_org
        self._layouts = None

    def met(self, endorsers) -> bool:
        """`endorsers`: (org, peer) of every SOUND endorsement, in the
        transaction's order; an identity counts once."""
        idents = list(dict.fromkeys(endorsers))
        return self._rule(self.tree, idents, [False] * len(idents))

    def _rule(self, node, idents, used) -> bool:
        if node[0] == "signed_by":
            for pos, (org, _peer) in enumerate(idents):
                if not used[pos] and org == node[1]:
                    used[pos] = True
                    return True
            return False
        verified = 0
        for sub in node[2]:
            trial = list(used)
            if self._rule(sub, idents, trial):
                verified += 1
                used[:] = trial
        return verified >= node[1]

    def layouts(self) -> list:
        """The minimal satisfying layouts: tuples of a quantity an
        organisation.  Which peer of an organisation signs, and in what
        order, changes nothing: a leaf asks for the organisation."""
        if self._layouts is None:
            def ok(q):
                return self.met([(o, k) for o, n in enumerate(q) for k in range(n)])

            self._layouts = [
                q for q in itertools.product(range(self._per_org + 1), repeat=self._n_orgs)
                if ok(q) and not any(
                    ok(q[:o] + (q[o] - 1,) + q[o + 1:]) for o in range(self._n_orgs) if q[o])
            ]
        return self._layouts


def _tree_of(envelope, org_of: dict) -> tuple:
    """The rule of a `SignaturePolicyEnvelope` over organisation
    indices; its principals are `OrgNMSP.peer` roles."""
    from fabric_tpu.protos.msp import msp_principal_pb2 as mp

    orgs = []
    for p in envelope.identities:
        role = mp.MSPRole.FromString(p.principal)
        if p.principal_classification != mp.MSPPrincipal.ROLE or role.role != mp.MSPRole.PEER:
            raise ManifestError("the world's policies name `OrgNMSP.peer` principals only")
        orgs.append(org_of[role.msp_identifier])

    def walk(rule):
        if rule.WhichOneof("Type") == "signed_by":
            return ("signed_by", orgs[rule.signed_by])
        return ("n_out_of", rule.n_out_of.n, [walk(r) for r in rule.n_out_of.rules])

    return walk(envelope.rule)


@dataclasses.dataclass
class Tx:
    """One transaction as the generator means it."""

    namespaces: tuple              # chaincode ranks: the invoked one, then the second
    endorsers: list                # (org, peer) in the envelope's order
    key: str
    values: tuple                  # a value a namespace
    bad: frozenset = frozenset()   # positions in `endorsers` whose signature is corrupted
    bad_creator: bool = False
    reads_absent: bool = False     # reads its key (absent) before writing it: a conflict pair
    kind: str = "ordinary"


@dataclasses.dataclass
class World:
    """The contract of `benchlib/manifest.py`, and what this kind keeps
    for itself (see the module's docstring)."""

    genesis: object
    blocks: list
    planted: list
    lanes_per_block: int            # of the first block
    public: dict
    definition_provider: object
    namespaces: tuple
    state: dict                     # (namespace, key) -> (value, (block, tx))
    txs: list                       # per block: the `Tx` of every transaction
    lanes_by_block: list
    planted_classes: list           # per block: class -> how many it holds
    due_classes: list               # per block: the classes it has room for
    chaincodes_drawn: list          # per block: namespace -> transactions that write it
    due_chaincodes: list            # per block: the namespaces it owes
    tolerated_lanes: list           # per block: corrupted lanes of VALID transactions
    plan_keys: int                  # distinct (policy, ordered endorsers) of the pass
    plan_keys_by_orgs: int          # of them, distinct by (policy, ordered organisations)
    layouts: dict                   # namespace -> its minimal layouts
    channel: str = CHANNEL

    def expected_state(self) -> dict:
        return dict(self.state)


class Definitions:
    """What a peer's lifecycle gives its validator, from a dict: the
    committed definitions as a lifecycle cache would hold them."""

    def __init__(self, parameters: dict):
        self._parameters = parameters      # namespace -> ApplicationPolicy bytes

    def validation_info(self, namespace: str):
        raw = self._parameters.get(namespace)
        return None if raw is None else ("vscc", raw)


class Net:
    """The channel: the organisations with a CA each, `peers_per_org`
    endorsing peers an organisation, one client of the first, the
    genesis block."""

    def __init__(self, rng: random.Random, deployment: dict):
        from fabric_tpu.common import configtx_builder as ctx
        from fabric_tpu.csp import SWCSP
        from fabric_tpu.msp import msp_config_from_ca
        from fabric_tpu.protos.peer import proposal_pb2

        sw = SWCSP()
        n_orgs = int(deployment["orgs"])
        self.orgs = [
            Org(f"Org{i + 1}MSP",
                _seeded_ca(rng, f"ca.org{i + 1}msp.example.com", f"Org{i + 1}MSP"), sw)
            for i in range(n_orgs)
        ]
        oorg = Org("OrdererMSP",
                   _seeded_ca(rng, "ca.orderermsp.example.com", "OrdererMSP"), sw)
        app = ctx.application_group({
            f"Org{i + 1}": ctx.org_group(o.mspid, msp_config_from_ca(o.ca, o.mspid))
            for i, o in enumerate(self.orgs)
        })
        ordg = ctx.orderer_group(
            {"O": ctx.org_group("OrdererMSP", msp_config_from_ca(oorg.ca, "OrdererMSP"))},
            consensus_type=deployment.get("orderer", "solo"),
            max_message_count=int(deployment["block_txs"]),
        )
        self.genesis = ctx.genesis_block(CHANNEL, ctx.channel_group(app, ordg))
        self.client = self.orgs[0].signer(rng, "client", "client")
        self.peers = [
            [o.signer(rng, f"peer{k}.org{i + 1}", "peer")
             for k in range(int(deployment["peers_per_org"]))]
            for i, o in enumerate(self.orgs)
        ]
        self._creator = self.client.serialize()
        self._ok = proposal_pb2.Response(status=200)

    def envelope(self, rng: random.Random, tx: Tx, names: list) -> bytes:
        from fabric_tpu import protoutil
        from fabric_tpu.protos.ledger.rwset import rwset_pb2
        from fabric_tpu.protos.ledger.rwset.kvrwset import kv_rwset_pb2
        from fabric_tpu.protos.peer import chaincode_pb2

        results = rwset_pb2.TxReadWriteSet(data_model=rwset_pb2.TxReadWriteSet.KV)
        written = sorted(zip((names[c] for c in tx.namespaces), tx.values))
        for ns, value in written:
            kv = kv_rwset_pb2.KVRWSet()
            if tx.reads_absent:
                kv.reads.add(key=tx.key)
            kv.writes.add(key=tx.key, value=value)
            results.ns_rwset.add(namespace=ns, rwset=kv.SerializeToString())
        invoked = names[tx.namespaces[0]]
        prop, _txid = protoutil.create_chaincode_proposal(
            self._creator, CHANNEL, invoked, [tx.key.encode(), tx.values[0]],
            nonce=rng.randbytes(24),
        )
        resps = [
            protoutil.create_proposal_response(
                prop, results=results.SerializeToString(), events=b"", response=self._ok,
                chaincode_id=chaincode_pb2.ChaincodeID(name=invoked),
                endorser_signer=self.peers[org][peer],
            )
            for org, peer in tx.endorsers
        ]
        for pos in tx.bad:
            e = resps[pos].endorsement
            e.signature = _flip_last_byte(e.signature)
        env = protoutil.create_signed_tx(prop, self.client, resps)
        if tx.bad_creator:
            env.signature = _flip_last_byte(env.signature)
        return env.SerializeToString()


def _planted_counts(planted: dict, n_txs: int) -> dict:
    """How many of each class a block of `n_txs` takes: all that is
    asked for where they fit in five sixths of it, else one of each in
    order and then the rest."""
    asked = {c: int(planted[c]) for c in CLASSES}
    room = (5 * n_txs) // 6
    took = dict.fromkeys(CLASSES, 0)
    for _round in range(max(asked.values(), default=0)):
        for c in CLASSES:
            if took[c] < asked[c] and room >= _TXS_OF[c]:
                took[c] += 1
                room -= _TXS_OF[c]
    return took


def build_world(seed: int, deployment: dict, planted: dict, n_blocks: int) -> World:
    try:
        from fabric_tpu.peer.txvalidator import tolerated_tally  # noqa: F401
    except ImportError as e:
        raise ManifestError(
            "this checkout's validator keeps no count of the refused lanes of valid "
            "transactions (peer.txvalidator.tolerated_tally), which the condition of "
            f"mixedcc-8cc-5org-1000tx reads ({e})"
        ) from e
    from fabric_tpu.policies import policydsl
    from fabric_tpu.protos.common import common_pb2
    from fabric_tpu.protos.peer import collection_pb2

    rng = random.Random(f"fabric-bench-mixedcc:{int(seed)}")
    n_orgs = int(deployment["orgs"])
    per_org = int(deployment["peers_per_org"])
    n_txs = int(deployment["block_txs"])
    value_bytes = int(deployment["value_bytes"])
    zipf = float(deployment["zipf_constant"])
    over_share = float(deployment["over_endorsed_share"])
    two_share = float(deployment["two_namespace_share"])

    net = Net(rng, deployment)
    org_of = {o.mspid: i for i, o in enumerate(net.orgs)}
    majority = ("n_out_of", n_orgs // 2 + 1, [("signed_by", o) for o in range(n_orgs)])

    # the chaincodes by rank: name, rule, and the definition a peer holds
    names, policies, parameters = [], [], {}
    for entry in deployment["policies"]:
        name = entry["chaincode"]
        if entry.get("policy"):
            envelope = policydsl.from_string(entry["policy"])
            tree = _tree_of(envelope, org_of)
            parameters[name] = collection_pb2.ApplicationPolicy(
                signature_policy=envelope).SerializeToString()
        else:
            tree = majority
            reference = entry.get("policy_reference")
            if reference is not None:
                if reference != DEFAULT_POLICY:
                    raise ManifestError(f"the world knows one channel policy, {DEFAULT_POLICY}")
                parameters[name] = collection_pb2.ApplicationPolicy(
                    channel_config_policy_reference=reference).SerializeToString()
        names.append(name)
        policies.append(_Policy(tree, n_orgs, per_org))
    if len(names) != int(deployment["chaincodes"]) or len(set(names)) != len(names):
        raise ManifestError("`chaincodes` and the distinct entries of `policies` differ")
    ranks = range(len(names))
    weights = [1.0 / (r + 1) ** zipf for r in ranks]
    shares = [w / sum(weights) for w in weights]
    # the rule that names one organisation under two sub-rules: its
    # layout with two of that organisation is what `greedy_order` cuts
    twice = [(c, q) for c in ranks for q in policies[c].layouts() if max(q) > 1]

    def chaincode(other=None) -> int:
        while True:
            c = rng.choices(ranks, weights)[0]
            if c != other:
                return c

    def people(layout) -> list:
        """A peer of each organisation of the layout (`q` of them where
        it wants `q`), in the order of the organisations."""
        return [(o, k) for o, q in enumerate(layout) for k in sorted(rng.sample(range(per_org), q))]

    def union(a, b) -> tuple:
        return tuple(max(x, y) for x, y in zip(a, b))

    def outsider(layout):
        """One more endorser, of an organisation the layout leaves out."""
        out = [o for o, q in enumerate(layout) if not q]
        return (rng.choice(out), rng.randrange(per_org)) if out else None

    def shuffled(endorsers) -> list:
        endorsers = list(endorsers)
        rng.shuffle(endorsers)
        return endorsers

    serial = itertools.count()

    def tx(namespaces, endorsers, **more) -> Tx:
        return Tx(namespaces=tuple(namespaces), endorsers=endorsers,
                  key=f"k{next(serial):06d}-{rng.getrandbits(40):010x}",
                  values=tuple(rng.randbytes(value_bytes) for _ in namespaces), **more)

    def layout_of(namespaces) -> tuple:
        layout = (0,) * n_orgs
        for c in namespaces:
            layout = union(layout, rng.choice(policies[c].layouts()))
        return layout

    def ordinary(over=None, two=None, **more) -> Tx:
        c = chaincode()
        two = rng.random() < two_share if two is None else two
        namespaces = (c, chaincode(other=c)) if two else (c,)
        layout = layout_of(namespaces)
        endorsers = people(layout)
        over = rng.random() < over_share if over is None else over
        extra = outsider(layout) if over else None
        if extra is not None:
            endorsers.append(extra)
        return tx(namespaces, shuffled(endorsers), **more)

    def breaks_policy() -> Tx:
        t = ordinary(over=False, kind="bad_endorsement_breaks_policy")
        t.bad = frozenset((rng.randrange(len(t.endorsers)),))
        return t

    def still_met() -> Tx:
        while True:
            c = chaincode()
            layout = layout_of((c,))
            extra = outsider(layout)
            if extra is not None:
                endorsers = shuffled(people(layout) + [extra])
                return tx((c,), endorsers, bad=frozenset((endorsers.index(extra),)),
                          kind="bad_endorsement_policy_still_met")

    def wrong_orgs() -> Tx:
        while True:
            c, other = chaincode(), chaincode()
            endorsers = shuffled(people(rng.choice(policies[other].layouts())))
            if other != c and not policies[c].met(endorsers):
                return tx((c,), endorsers, kind="wrong_orgs")

    def same_org_twice() -> Tx:
        while True:
            c = chaincode()
            layout = rng.choice(policies[c].layouts())
            single = [o for o, q in enumerate(layout) if q == 1]
            if len(single) < 2 or per_org < 2:
                continue
            keep, drop = rng.sample(single, 2)
            endorsers = [e for e in people(layout) if e[0] != drop]
            mine = next(e for e in endorsers if e[0] == keep)
            endorsers.append((keep, rng.choice([k for k in range(per_org) if k != mine[1]])))
            if not policies[c].met(endorsers):
                return tx((c,), shuffled(endorsers), kind="same_org_twice")

    def duplicate_endorser() -> Tx:
        while True:
            c = chaincode()
            endorsers = people(rng.choice(policies[c].layouts()))
            if len(endorsers) < 2:
                continue
            endorsers.pop(rng.randrange(len(endorsers)))
            endorsers.append(rng.choice(endorsers))
            if not policies[c].met(endorsers):
                return tx((c,), shuffled(endorsers), kind="duplicate_endorser")

    def greedy_order() -> Tx:
        c, layout = rng.choice(twice)
        o = next(o for o, q in enumerate(layout) if q > 1)
        endorsers = people(layout[:o] + (1,) + layout[o + 1:])
        if policies[c].met(endorsers):
            raise ManifestError(f"{names[c]}: one peer of organisation {o + 1} meets its rule")
        return tx((c,), shuffled(endorsers), kind="greedy_order")

    def second_namespace_unmet() -> Tx:
        while True:
            c = chaincode()
            second = chaincode(other=c)
            endorsers = shuffled(people(rng.choice(policies[c].layouts())))
            if not policies[second].met(endorsers):
                return tx((c, second), endorsers, kind="second_namespace_unmet")

    makers = {
        "bad_creator": lambda: (ordinary(bad_creator=True, kind="bad_creator"),),
        "bad_endorsement_breaks_policy": lambda: (breaks_policy(),),
        "bad_endorsement_policy_still_met": lambda: (still_met(),),
        "wrong_orgs": lambda: (wrong_orgs(),),
        "same_org_twice": lambda: (same_org_twice(),),
        "duplicate_endorser": lambda: (duplicate_endorser(),),
        "greedy_order": lambda: (greedy_order(),),
        "second_namespace_unmet": lambda: (second_namespace_unmet(),),
    }

    def conflict_pair() -> tuple:
        first = ordinary(over=False, two=False, reads_absent=True, kind="conflict_first")
        second = tx(first.namespaces, shuffled(people(layout_of(first.namespaces))),
                    reads_absent=True, kind="conflict_second")
        second.key = first.key
        return first, second

    makers["conflict_pairs"] = conflict_pair
    if not twice:
        makers["greedy_order"] = None       # no rule of the configuration can hold one

    state: dict = {}
    plan_keys, plan_keys_by_orgs = set(), set()
    blocks, flags_all, txs_all, lanes, classes_all, due_all = [], [], [], [], [], []
    drawn_all, owed_all, tolerated_all = [], [], []
    for bno in range(n_blocks):
        number = 1 + bno
        took = _planted_counts(planted, n_txs)
        due = [c for c in CLASSES if took[c]]
        slots: list = [None] * n_txs
        free = list(range(n_txs))
        rng.shuffle(free)
        for c in CLASSES:
            make = makers[c]
            if make is None:
                took[c] = 0         # due, and missing: the condition says so
                continue
            for _ in range(took[c]):
                made = make()
                for i, t in zip(sorted(free.pop() for _ in made), made):
                    slots[i] = t
        for i in free:
            slots[i] = ordinary()

        flags, written, tolerated, n_lanes = [], set(), 0, 0
        drawn = dict.fromkeys(names, 0)
        for i, t in enumerate(slots):
            # an identity counts once, by its first endorsement
            first: dict = {}
            for pos, e in enumerate(t.endorsers):
                first.setdefault(e, pos)
            idents = list(first)
            sound = [e for e, pos in first.items() if pos not in t.bad]
            n_lanes += 1 + len(idents)
            for c in t.namespaces:
                drawn[names[c]] += 1
                # the default is ONE policy under whichever chaincode
                pol = "default" if policies[c].tree is majority else names[c]
                plan_keys.add((pol, tuple(idents)))
                plan_keys_by_orgs.add((pol, tuple(o for o, _k in idents)))
            if t.bad_creator:
                flag = BAD_CREATOR_SIGNATURE
            elif not all(policies[c].met(sound) for c in t.namespaces):
                flag = ENDORSEMENT_POLICY_FAILURE
            elif t.reads_absent and any((names[c], t.key) in written for c in t.namespaces):
                flag = MVCC_READ_CONFLICT
            else:
                flag = VALID
                tolerated += len(idents) - len(sound)
                for c, value in zip(t.namespaces, t.values):
                    written.add((names[c], t.key))
                    state[names[c], t.key] = (value, (number, i))
            flags.append(flag)

        blk = common_pb2.Block()
        blk.header.number = number
        blk.data.data.extend(net.envelope(rng, t, names) for t in slots)
        while len(blk.metadata.metadata) < 3:
            blk.metadata.metadata.append(b"")
        blocks.append(blk.SerializeToString())
        flags_all.append(flags)
        txs_all.append(slots)
        lanes.append(n_lanes)
        classes_all.append(took)
        due_all.append(due)
        drawn_all.append(drawn)
        owed_all.append([names[c] for c in ranks if shares[c] * n_txs >= DUE_AT_LEAST])
        tolerated_all.append(tolerated)
    public = {"ca_certs_pem": {o.mspid: o.ca.cert_pem for o in net.orgs},
              "definitions": dict(parameters)}
    return World(
        genesis=net.genesis, blocks=blocks, planted=flags_all, lanes_per_block=lanes[0],
        public=public, definition_provider=Definitions(parameters), namespaces=tuple(names),
        state=state, txs=txs_all, lanes_by_block=lanes, planted_classes=classes_all,
        due_classes=due_all, chaincodes_drawn=drawn_all, due_chaincodes=owed_all,
        tolerated_lanes=tolerated_all, plan_keys=len(plan_keys),
        plan_keys_by_orgs=len(plan_keys_by_orgs),
        layouts={names[c]: policies[c].layouts() for c in ranks},
    )
