"""The world of `keylevel-5org-1000tx`: the five-organisation MAJORITY
channel of `x509-majority` (`benchlib/generator.py`: its CAs,
organisations, client and peers) whose assets carry key-level
(state-based) endorsement policies, the owner-endorses-its-own-asset
pattern of Fabric's `docs/source/endorsement-policies.rst`, "Setting
key-level endorsement policies".

An asset is a key of chaincode `benchcc`.  It is CREATED under the
chaincode's policy (the channel's default MAJORITY: 3 of 5 endorse) by a
transaction that writes its value and its `VALIDATION_PARAMETER`: a
`KeyEndorsementPolicy` envelope (`chaincode/statebased.py`, upstream's
`pkg/statebased`), N-of-N over `OrgN.peer`, naming ONE organisation for
`single_owner_share` of the assets and TWO for the rest (15 distinct
parameters over five organisations).  From then on the asset is written
only with the endorsement of its owners' peers and nobody else's:

    update     read the key at its committed version, write its value;
               endorsed by the current owners (1 or 2 endorsements)
    transfer   the same, and a metadata write that sets the parameter
               to the buyer's organisation(s), drawn as owners are drawn
               among the parameters other than the current one;
               endorsed by the CURRENT owners

A pass is `create_blocks_share` create blocks (a quarter: 4 of 16),
then work blocks of `transfer_share` transfers and updates for the
rest.  A work block's assets are drawn WITHOUT replacement by Zipfian
popularity over the assets ranked by creation (constant
`ZIPF_CONSTANT`, YCSB's default), so the hot assets return in nearly
every block and a transfer in block k is usually followed in k+1 by a
transaction on the same asset: the neighbourhood a pipelined validator
has to get right.  (A world too small to hold a block's worth of assets,
a test's, fills its work blocks with late creates.)

The generator keeps the SERIAL truth (`_Truth`: the state, versions and
owners as a validator that commits each block before it validates the
next leaves them), so every honest transaction is endorsed by the
owners the block before left, reads the version it left, and is VALID
under it.  Planted, so that every wrong short cut is wrong in every
block (`planted` of the configuration), in every work block:

    previous_owner_per_block   a transaction endorsed by the PREVIOUS
                               owners of an asset the block before
                               transferred to owners the previous ones
                               do not cover; read version correct, so
                               MVCC does not catch it:
                               ENDORSEMENT_POLICY_FAILURE, and VALID for
                               a validator that decides it under the
                               parameter of two blocks ago
    in_block_pairs_per_block   a transfer, then later in the block an
                               update of the same asset by its owners
                               as committed: the second is
                               ENDORSEMENT_POLICY_FAILURE (upstream's
                               ValidationParameterUpdatedError)
    conflict_pairs_per_block   two updates of one asset reading the same
                               version: the second MVCC_READ_CONFLICT
    bad_creator_per_block      a corrupted creator signature
    bad_endorsement_per_block  one corrupted endorsement signature, which
                               breaks the N-of-N (in a create block, the
                               3 of 5)

and in every create block the last three.  A block smaller than what is
planted (a test's) takes one of each kind in that order, then the rest,
as far as two thirds of its transactions go.

What the world keeps for the condition `keylevel-shape`, the
configuration's file and the run's lines: per block its kind, its
signature lanes, what was planted of each class and what was due, and
the DEPENDENT transactions: those that write an asset whose parameter a
valid transaction of one of the two blocks before wrote (`dependent`;
`dependent_deep`: of the three before).  At `store_stream`'s default
depth the two blocks before are always still in flight when a block is
collected, so the program defers at least the first count; how many
more depends on how far the commits lag behind (the third before
usually, a fourth after the create blocks, whose validator never
waits).

What `--seed` fixes: every key, owner, buyer, value, nonce, draw and
planted place; what stays random, as in the accepted worlds: ECDSA
signature nonces, certificate serial numbers and validity instants.
Nothing here touches JAX.

The world is built only for a program that can give the deployment's
guarantee: it asks the program for the count the condition will read
(`peer.txvalidator.keylevel_tally`), and a checkout without it is
refused before anything is measured (it would decide a pipelined
block's keys under stale parameters, and read `correct: false` after a
whole run: PERF.md section 9, PR 40).
"""

from __future__ import annotations

import dataclasses
import itertools
import random

from benchlib.generator import (
    BAD_CREATOR_SIGNATURE,
    CHAINCODE,
    CHANNEL,
    ENDORSEMENT_POLICY_FAILURE,
    MVCC_READ_CONFLICT,
    VALID,
    Org,
    _flip_last_byte,
    _seeded_ca,
)
from benchlib.manifest import ManifestError

ZIPF_CONSTANT = 0.99
VALIDATION_PARAMETER = "VALIDATION_PARAMETER"
BLIND = "blind"         # a write that reads nothing

# the planted classes, in the order a small block takes them
CLASSES = ("previous_owner", "in_block_pair", "conflict_pair", "bad_creator",
           "bad_endorsement")
_TXS_OF = {"previous_owner": 1, "in_block_pair": 2, "conflict_pair": 2,
           "bad_creator": 1, "bad_endorsement": 1}
_CREATE_CLASSES = CLASSES[2:]


def parameter(mspids) -> bytes:
    """Upstream's `KeyEndorsementPolicy`: N-of-N over `OrgN.peer`."""
    from fabric_tpu.chaincode.statebased import ROLE_PEER, KeyEndorsementPolicy

    pol = KeyEndorsementPolicy()
    pol.add_orgs(ROLE_PEER, *mspids)
    return pol.policy()


@dataclasses.dataclass
class Tx:
    """One transaction as the generator means it; `Net.envelope` signs
    it, `_Truth.apply` says what a serial validator makes of it."""

    key: str
    value: bytes
    endorsers: tuple               # organisation indices
    read: object = BLIND           # BLIND, None (absent) or (block, tx)
    read_key: str | None = None    # the key it reads, where that is not the one it writes
    new_owners: tuple | None = None     # organisation indices of the parameter it sets
    bad_creator: bool = False
    bad_endorsement: bool = False
    kind: str = "update"


class Net:
    """The channel: five organisations with a CA each, one peer an
    organisation, one client of the first, the genesis block."""

    def __init__(self, rng: random.Random, deployment: dict):
        from fabric_tpu.common import configtx_builder as ctx
        from fabric_tpu.csp import SWCSP
        from fabric_tpu.msp import msp_config_from_ca
        from fabric_tpu.protos.peer import chaincode_pb2, proposal_pb2

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
        self.peers = [o.signer(rng, f"peer{i}", "peer") for i, o in enumerate(self.orgs)]
        self.public = {"ca_certs_pem": {o.mspid: o.ca.cert_pem for o in self.orgs}}
        self._creator = self.client.serialize()
        self._cc_id = chaincode_pb2.ChaincodeID(name=CHAINCODE)
        self._ok = proposal_pb2.Response(status=200)
        self._parameters: dict = {}

    def parameter(self, owners: tuple) -> bytes:
        raw = self._parameters.get(owners)
        if raw is None:
            raw = self._parameters[owners] = parameter(
                [self.orgs[i].mspid for i in owners])
        return raw

    def envelope(self, rng: random.Random, tx: Tx) -> tuple:
        """(serialized Envelope, signatures it carries)."""
        from fabric_tpu import protoutil
        from fabric_tpu.protos.ledger.rwset import rwset_pb2
        from fabric_tpu.protos.ledger.rwset.kvrwset import kv_rwset_pb2

        kv = kv_rwset_pb2.KVRWSet()
        if tx.read != BLIND:
            read = kv.reads.add(key=tx.read_key or tx.key)
            if tx.read is not None:
                read.version.block_num, read.version.tx_num = tx.read
        kv.writes.add(key=tx.key, value=tx.value)
        if tx.new_owners is not None:
            mw = kv.metadata_writes.add(key=tx.key)
            mw.entries.add(name=VALIDATION_PARAMETER, value=self.parameter(tx.new_owners))
        results = rwset_pb2.TxReadWriteSet(data_model=rwset_pb2.TxReadWriteSet.KV)
        results.ns_rwset.add(namespace=CHAINCODE, rwset=kv.SerializeToString())
        prop, _txid = protoutil.create_chaincode_proposal(
            self._creator, CHANNEL, CHAINCODE, [tx.key.encode(), tx.value],
            nonce=rng.randbytes(24),
        )
        resps = [
            protoutil.create_proposal_response(
                prop, results=results.SerializeToString(), events=b"", response=self._ok,
                chaincode_id=self._cc_id, endorser_signer=self.peers[i],
            )
            for i in tx.endorsers
        ]
        if tx.bad_endorsement:
            e = resps[rng.randrange(len(resps))].endorsement
            e.signature = _flip_last_byte(e.signature)
        env = protoutil.create_signed_tx(prop, self.client, resps)
        if tx.bad_creator:
            env.signature = _flip_last_byte(env.signature)
        return env.SerializeToString(), 1 + len(resps)

    def block(self, rng: random.Random, number: int, txs: list) -> tuple:
        """(serialized Block `number`, signatures it carries)."""
        from fabric_tpu.protos.common import common_pb2

        made = [self.envelope(rng, tx) for tx in txs]
        blk = common_pb2.Block()
        blk.header.number = number
        blk.data.data.extend(env for env, _n in made)
        while len(blk.metadata.metadata) < 3:
            blk.metadata.metadata.append(b"")
        return blk.SerializeToString(), sum(n for _env, n in made)


class _Truth:
    """What a validator that commits each block before it validates the
    next makes of the transactions, in the order of its checks: creator
    signature, the policy of the written key (its parameter as the
    blocks before left it, N-of-N; the chaincode's majority for a key
    without one; refused outright once an earlier valid transaction of
    the block rewrote the parameter), then MVCC."""

    def __init__(self, n_orgs: int):
        self._majority = n_orgs // 2 + 1
        self.value: dict = {}        # key -> (value, (block, tx))
        self.owners: dict = {}       # key -> organisation indices of its parameter
        self.wrote_parameter: dict = {}     # key -> the block whose valid tx wrote it last
        self.previous_owners: dict = {}     # key -> owners before that write

    def apply(self, number: int, txs: list) -> list:
        flags, rewritten = [], set()
        for i, tx in enumerate(txs):
            flags.append(self._one(number, i, tx, rewritten))
        return flags

    def _one(self, number: int, i: int, tx: Tx, rewritten: set) -> int:
        if tx.bad_creator:
            return BAD_CREATOR_SIGNATURE
        if tx.key in rewritten:
            return ENDORSEMENT_POLICY_FAILURE
        sound = set(tx.endorsers) if not tx.bad_endorsement else None
        owners = self.owners.get(tx.key)
        if sound is None:
            # which endorsement is corrupted is the envelope's draw: the
            # generator only plants it where any one of them is needed
            need = len(owners) if owners is not None else self._majority
            if len(set(tx.endorsers)) - 1 < need:
                return ENDORSEMENT_POLICY_FAILURE
            raise ValueError("a corrupted endorsement that breaks nothing")
        if owners is not None:
            if not set(owners) <= sound:
                return ENDORSEMENT_POLICY_FAILURE
        elif len(sound) < self._majority:
            return ENDORSEMENT_POLICY_FAILURE
        if tx.new_owners is not None:
            rewritten.add(tx.key)       # valid by its policy: MVCC comes after
        if tx.read != BLIND:
            have = self.value.get(tx.read_key or tx.key)
            if (have[1] if have else None) != tx.read:
                return MVCC_READ_CONFLICT
        self.value[tx.key] = (tx.value, (number, i))
        if tx.new_owners is not None:
            self.previous_owners[tx.key] = owners
            self.owners[tx.key] = tx.new_owners
            self.wrote_parameter[tx.key] = number
        return VALID

    def version(self, key: str):
        have = self.value.get(key)
        return have[1] if have else None


@dataclasses.dataclass
class World:
    """The contract of `benchlib/manifest.py`, and what this kind keeps
    for itself (see the module's docstring)."""

    genesis: object
    blocks: list
    planted: list
    lanes_per_block: int          # of the first block (a create block: 4 a transaction)
    public: dict
    state: dict                   # (namespace, key) -> (value, (block, tx)), the serial state
    block_kinds: list             # "create" | "work", per block
    lanes_by_block: list
    planted_classes: list         # per block: class -> transactions planted
    due_classes: list             # per block: the classes the block can hold at all
    dependent: list               # per block: see the docstring
    dependent_deep: list
    txs: list                     # per block: the `Tx` of every transaction
    assets: int
    parameters: int               # distinct parameters written
    channel: str = CHANNEL
    namespaces: tuple = (CHAINCODE,)

    def expected_state(self) -> dict:
        return dict(self.state)


def _draw_owners(rng: random.Random, n_orgs: int, single_share: float) -> tuple:
    if n_orgs < 2 or rng.random() < single_share:
        return (rng.randrange(n_orgs),)
    return tuple(sorted(rng.sample(range(n_orgs), 2)))


def _planted_counts(planted: dict, classes: tuple, n_txs: int) -> dict:
    """How many of each class a block of `n_txs` takes: all that is
    asked for where they fit in two thirds of it, else one of each in
    order and then the rest."""
    asked = {c: int(planted[c + ("s" if c.endswith("pair") else "") + "_per_block"])
             for c in classes}
    room = (2 * n_txs) // 3
    took = dict.fromkeys(classes, 0)
    for _round in range(max(asked.values(), default=0)):
        for c in classes:
            if took[c] < asked[c] and room >= _TXS_OF[c]:
                took[c] += 1
                room -= _TXS_OF[c]
    return took


def _zipf_draw(rng: random.Random, n_assets: int, k: int) -> list:
    """`k` distinct ranks of `n_assets`, rank r with weight
    1 / (r + 1) ** ZIPF_CONSTANT, without replacement (Efraimidis and
    Spirakis: the k largest of u ** (1 / w)), in a shuffled order."""
    keyed = sorted(
        ((rng.random() ** ((r + 1) ** ZIPF_CONSTANT), r) for r in range(n_assets)),
        reverse=True,
    )
    ranks = [r for _u, r in keyed[:k]]
    rng.shuffle(ranks)
    return ranks


def build_world(seed: int, deployment: dict, planted: dict, n_blocks: int) -> World:
    try:
        from fabric_tpu.peer.txvalidator import keylevel_tally  # noqa: F401
    except ImportError as e:
        raise ManifestError(
            "this checkout's validator keeps no count of deferred key-level decisions "
            "(peer.txvalidator.keylevel_tally): it decides a pipelined block's keys under "
            "the parameters of blocks before the one before, and cannot give the "
            f"guarantee keylevel-5org-1000tx is for ({e})"
        ) from e

    rng = random.Random(f"fabric-bench-keylevel:{int(seed)}")
    n_orgs = int(deployment["orgs"])
    n_txs = int(deployment["block_txs"])
    value_bytes = int(deployment["value_bytes"])
    single_share = float(deployment["single_owner_share"])
    transfer_share = float(deployment["transfer_share"])
    n_create = max(1, round(n_blocks * float(deployment["create_blocks_share"])))
    create_endorsers = int(deployment["endorsers_per_create"])

    net = Net(rng, deployment)
    truth = _Truth(n_orgs)
    assets: list = []            # keys by rank: creation order
    asset_set: set = set()

    serial = itertools.count()

    def create(**more) -> Tx:
        more.setdefault("kind", "create")
        return Tx(key=f"a{next(serial):05d}-{rng.getrandbits(40):010x}",
                  value=rng.randbytes(value_bytes),
                  endorsers=tuple(sorted(rng.sample(range(n_orgs), create_endorsers))),
                  new_owners=_draw_owners(rng, n_orgs, single_share), **more)

    def update(key: str, **more) -> Tx:
        return Tx(key=key, value=rng.randbytes(value_bytes), endorsers=truth.owners[key],
                  read=truth.version(key), **more)

    def transfer(key: str, **more) -> Tx:
        have = truth.owners[key]
        new = _draw_owners(rng, n_orgs, single_share)
        while new == have:
            new = _draw_owners(rng, n_orgs, single_share)
        more.setdefault("kind", "transfer")
        return update(key, new_owners=new, **more)

    blocks, flags_all, kinds, lanes, classes_all, due_all, txs_all = [], [], [], [], [], [], []
    dependent, dependent_deep = [], []
    parameters: set = set()
    for bno in range(n_blocks):
        number = 1 + bno
        is_create = bno < n_create
        classes = _CREATE_CLASSES if is_create else CLASSES
        took = _planted_counts(planted, classes, n_txs)
        slots: list = [None] * n_txs
        free = list(range(n_txs))
        rng.shuffle(free)

        def place(*txs):
            """Into free slots, in the order given."""
            at = sorted(free.pop() for _ in txs)
            for i, tx in zip(at, txs):
                slots[i] = tx

        if is_create:
            for _ in range(took["conflict_pair"]):
                # the second read the first's key while it was absent
                # (a pair of creates of ONE key would meet the in-block
                # rule first: the first writes its parameter)
                first = create(read=None, kind="conflict_first")
                place(first, create(read=None, read_key=first.key, kind="conflict_second"))
            for _ in range(took["bad_creator"]):
                place(create(bad_creator=True, kind="bad_creator"))
            for _ in range(took["bad_endorsement"]):
                place(create(bad_endorsement=True, kind="bad_endorsement"))
            for i in free:
                slots[i] = create()
            due = list(classes)
        else:
            # the previous owners' transactions first: they need assets
            # the block before transferred away from owners who do not
            # cover the new ones
            handed = [
                k for k in assets
                if truth.wrote_parameter.get(k) == number - 1
                and truth.previous_owners.get(k) is not None
                and not set(truth.owners[k]) <= set(truth.previous_owners[k])
            ]
            rng.shuffle(handed)
            n_prev = min(took["previous_owner"], len(handed))
            stale = handed[:n_prev]
            took["previous_owner"] = n_prev
            pairs = took["in_block_pair"] + took["conflict_pair"]
            distinct = min(n_txs - pairs, len(assets))
            drawn = [assets[r] for r in _zipf_draw(rng, len(assets), distinct)]
            # an asset once a block: a stale one takes the place of one drawn
            rest = [k for k in drawn if k not in stale][:distinct - n_prev]
            for k in stale:
                place(Tx(key=k, value=rng.randbytes(value_bytes),
                         endorsers=truth.previous_owners[k], read=truth.version(k),
                         kind="previous_owner"))
            planted_here = {
                "in_block_pair": lambda k: (transfer(k, kind="in_block_transfer"),
                                            update(k, kind="in_block_second")),
                "conflict_pair": lambda k: (update(k, kind="conflict_first"),
                                            update(k, kind="conflict_second")),
                "bad_creator": lambda k: (update(k, bad_creator=True, kind="bad_creator"),),
                "bad_endorsement": lambda k: (
                    update(k, bad_endorsement=True, kind="bad_endorsement"),),
            }
            for c, make in planted_here.items():
                want, took[c] = took[c], 0
                while took[c] < want and rest:
                    place(*make(rest.pop()))
                    took[c] += 1
            for k in rest:
                slots[free.pop()] = transfer(k) if rng.random() < transfer_share else update(k)
            for i in free:          # a world too small for the block: late creates
                slots[i] = create()
            due = [c for c in classes if c != "previous_owner" or handed]

        # who depends on the blocks before, by the truth before this block
        def wrote_within(tx, depth):
            wrote = truth.wrote_parameter.get(tx.key)
            return wrote is not None and number - depth <= wrote < number

        dependent.append(sum(1 for tx in slots if wrote_within(tx, 2)))
        dependent_deep.append(sum(1 for tx in slots if wrote_within(tx, 3)))

        flags = truth.apply(number, slots)
        created = [tx.key for tx, flag in zip(slots, flags)
                   if flag == VALID and tx.new_owners is not None and tx.key not in asset_set]
        raw, n_lanes = net.block(rng, number, slots)
        blocks.append(raw)
        flags_all.append(flags)
        kinds.append("create" if is_create else "work")
        lanes.append(n_lanes)
        classes_all.append(dict(took))
        due_all.append(due)
        txs_all.append(slots)
        parameters.update(tx.new_owners for tx, flag in zip(slots, flags)
                          if flag == VALID and tx.new_owners is not None)
        for key in created:
            asset_set.add(key)
            assets.append(key)
    return World(
        genesis=net.genesis, blocks=blocks, planted=flags_all, lanes_per_block=lanes[0],
        public=net.public,
        state={(CHAINCODE, k): v for k, v in truth.value.items()},
        block_kinds=kinds, lanes_by_block=lanes, planted_classes=classes_all,
        due_classes=due_all, dependent=dependent, dependent_deep=dependent_deep,
        txs=txs_all, assets=len(assets), parameters=len(parameters),
    )
