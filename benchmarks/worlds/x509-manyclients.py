"""The world of `manyclients-10k`: the five-organisation MAJORITY
channel of `x509-majority` (`benchlib/generator.py`) with one thing
changed, the creator.  `client_identities` clients are enrolled, an
equal share an organisation (rank r belongs to organisation r mod
`orgs`), each with a P-256 key of its own and a certificate of its
organisation's CA carrying the OU `client`, as Fabric CA issues one
enrolment certificate a user.  Every transaction's creator is one draw
from a Zipfian distribution (constant `ZIPF_CONSTANT`, YCSB's default)
over the clients ranked, so a block of 1,000 transactions carries some
500 distinct creators, most of them strangers to the block before.
Certificates are issued only for the clients a run draws: the others
never reach the peer.  Endorsers, policy, the one write a transaction
and the three accepted kinds of planted fault are the generator's.

Planted beside them, so that "skip the identity checks" is wrong in
every block (`planted` of the configuration), each by a creator that
signs its transaction correctly and is refused for its certificate
alone (BAD_CREATOR_SIGNATURE, as upstream's checkSignatureFromCreator
gives an identity that does not validate):

    rogue_ca_creators_per_block    a certificate signed by another key
                                   under the organisation CA's subject
                                   name and key identifier: only the
                                   chain signature fails
    expired_creators_per_block     `not_valid_after` a day ago
    revoked_creators_per_block     its serial number is on the
                                   organisation's CRL, which the genesis
                                   block's MSP configuration carries
    no_role_ou_creators_per_block  no role OU (NodeOUs are on: an
                                   identity that is no client, peer,
                                   admin or orderer is no identity)

A block smaller than twice what is planted (a test's) takes the kinds
in that order, then the generator's three, as far as half its
transactions go.

What `--seed` fixes: every CA's and every identity's key, which client
signs which transaction, keys, values, nonces and every planted place.
What stays random, as in the accepted worlds: ECDSA signature nonces,
certificate serial numbers and validity instants.  Nothing here touches
JAX.

For the condition `manyclients-shape` and for the run's `# compared:`
lines the world keeps how many distinct creators every block, every
two neighbouring blocks and the whole pass carry.
"""

from __future__ import annotations

import dataclasses
import datetime
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
    _seeded_key,
)

ZIPF_CONSTANT = 0.99


@dataclasses.dataclass
class World:
    """The contract of `benchlib/manifest.py`, and what this kind keeps
    for itself: `writes`, and the distinct creators of each block, of
    each two neighbouring blocks and of the pass."""

    genesis: object
    blocks: list
    planted: list
    writes: list
    lanes_per_block: int
    public: dict                  # CA certificates and CRLs, by MSP id
    creators_per_block: list      # distinct creators of block b
    creators_per_two_blocks: list  # distinct creators of blocks b and b + 1
    creators_per_pass: int
    certificates_issued: int
    channel: str = CHANNEL
    namespaces: tuple = (CHAINCODE,)

    def expected_state(self) -> dict:
        state: dict = {}
        for bno, (want, wrote) in enumerate(zip(self.planted, self.writes)):
            for i, (flag, (key, value)) in enumerate(zip(want, wrote)):
                if flag == VALID:
                    state[CHAINCODE, key] = (value, (1 + bno, i))
        return state


def _signer(org: Org, key, cert):
    from fabric_tpu.common.crypto import CertKeyPair
    from fabric_tpu.msp import SigningIdentity

    pair = CertKeyPair(cert, key)
    return SigningIdentity.from_pem(org.mspid, pair.cert_pem, pair.key_pem, org.csp)


def _rogue_ca(ca, rng: random.Random):
    """The organisation CA's name and certificate over another key:
    what it issues names the true CA as issuer (subject and key
    identifier) and verifies under a key the channel does not trust."""
    from fabric_tpu.common import crypto

    rogue = crypto.CA.__new__(crypto.CA)
    rogue.key = _seeded_key(rng)
    rogue.org = ca.org
    rogue.cert = ca.cert
    rogue.parent = None
    rogue._revoked = []
    return rogue


class _Clients:
    """The enrolled clients by rank, each made when first drawn.  A
    client's key comes from the seed and its rank alone, so which
    clients a run draws does not change any of them."""

    def __init__(self, seed: int, orgs: list, enrolled: int):
        self._seed, self._orgs = int(seed), orgs
        weights = [1.0 / (r + 1) ** ZIPF_CONSTANT for r in range(enrolled)]
        self._cum = list(itertools.accumulate(weights))
        self._ranks = range(enrolled)
        self._made: dict = {}

    def draw(self, rng: random.Random, k: int) -> list:
        return rng.choices(self._ranks, cum_weights=self._cum, k=k)

    def signer(self, rank: int):
        s = self._made.get(rank)
        if s is None:
            org = self._orgs[rank % len(self._orgs)]
            key = _seeded_key(random.Random(f"fabric-bench-client:{self._seed}:{rank}"))
            cert = org.ca.issue_for_public_key(
                f"user{rank}", key.public_key(), ous=["client"]
            )
            s = self._made[rank] = _signer(org, key, cert)
        return s

    def __len__(self) -> int:
        return len(self._made)


def build_world(seed: int, deployment: dict, planted: dict, n_blocks: int) -> World:
    from fabric_tpu import protoutil
    from fabric_tpu.common import configtx_builder as ctx
    from fabric_tpu.csp import SWCSP
    from fabric_tpu.ledger import LedgerProvider
    from fabric_tpu.msp import msp_config_from_ca
    from fabric_tpu.protos.common import common_pb2
    from fabric_tpu.protos.peer import chaincode_pb2, proposal_pb2

    rng = random.Random(f"fabric-bench-manyclients:{int(seed)}")
    sw = SWCSP()
    n_orgs = int(deployment["orgs"])
    n_txs = int(deployment["block_txs"])
    endorsers = int(deployment["endorsers_per_tx"])
    value_bytes = int(deployment["value_bytes"])

    orgs = [
        Org(f"Org{i + 1}MSP",
            _seeded_ca(rng, f"ca.org{i + 1}msp.example.com", f"Org{i + 1}MSP"), sw)
        for i in range(n_orgs)
    ]
    oorg = Org("OrdererMSP",
               _seeded_ca(rng, "ca.orderermsp.example.com", "OrdererMSP"), sw)
    rogues = [_rogue_ca(o.ca, rng) for o in orgs]

    n_rogue = int(planted["rogue_ca_creators_per_block"])
    n_expired = int(planted["expired_creators_per_block"])
    n_revoked = int(planted["revoked_creators_per_block"])
    n_no_role = int(planted["no_role_ou_creators_per_block"])
    n_bad_c = int(planted["bad_creator_per_block"])
    n_bad_e = int(planted["bad_endorsement_per_block"])
    n_conf = int(planted["conflict_pairs_per_block"])

    # the refused creators of every block, made before the genesis
    # block, whose MSP configurations carry the CRLs with the revoked
    # ones' serial numbers
    yesterday = datetime.datetime.now(datetime.timezone.utc) - datetime.timedelta(days=1)
    n_refused = n_rogue + n_expired + n_revoked + n_no_role
    refused_all = []
    for bno in range(n_blocks):
        made = []
        for j in range(n_refused):
            k = (bno * n_refused + j) % n_orgs
            org, key, name = orgs[k], _seeded_key(rng), f"refused{bno}-{j}"
            if j < n_rogue:
                cert = rogues[k].issue_for_public_key(name, key.public_key(), ous=["client"])
            elif j < n_rogue + n_expired:
                cert = org.ca.issue_for_public_key(
                    name, key.public_key(), ous=["client"], not_after=yesterday)
            elif j < n_rogue + n_expired + n_revoked:
                cert = org.ca.issue_for_public_key(name, key.public_key(), ous=["client"])
                org.ca.revoke(cert)
            else:
                cert = org.ca.issue_for_public_key(name, key.public_key(), ous=["department1"])
            made.append(_signer(org, key, cert))
        refused_all.append(made)
    crls = {o.mspid: o.ca.gen_crl() for o in orgs}

    app = ctx.application_group({
        f"Org{i + 1}": ctx.org_group(
            o.mspid, msp_config_from_ca(o.ca, o.mspid, crls=[crls[o.mspid]]))
        for i, o in enumerate(orgs)
    })
    ordg = ctx.orderer_group(
        {"O": ctx.org_group("OrdererMSP", msp_config_from_ca(oorg.ca, "OrdererMSP"))},
        consensus_type=deployment.get("orderer", "solo"),
        max_message_count=n_txs,
    )
    genesis = ctx.genesis_block(CHANNEL, ctx.channel_group(app, ordg))

    clients = _Clients(seed, orgs, int(deployment["client_identities"]))
    peers = [o.signer(rng, f"peer{i}", "peer") for i, o in enumerate(orgs[:endorsers])]
    cc_id = chaincode_pb2.ChaincodeID(name=CHAINCODE)
    ok = proposal_pb2.Response(status=200)
    sim_ledger = LedgerProvider(None).create(genesis)

    wanted = n_refused + n_bad_c + n_bad_e + 2 * n_conf
    blocks, flags_all, writes_all, seen = [], [], [], []
    for bno in range(n_blocks):
        cut = iter(rng.sample(range(n_txs), min(wanted, n_txs // 2)))
        refused = dict(zip(itertools.islice(cut, n_refused), refused_all[bno]))
        bad_creator = set(itertools.islice(cut, n_bad_c))
        bad_endorse = set(itertools.islice(cut, n_bad_e))
        pairs = list(cut)
        shares: dict[int, int] = {}
        readers: set[int] = set()
        for a, b in zip(pairs[0::2], pairs[1::2]):
            first, second = min(a, b), max(a, b)
            shares[second] = first
            readers.update((first, second))
        want = [VALID] * n_txs
        keys = [f"k{bno}-{i}-{rng.getrandbits(40):010x}" for i in range(n_txs)]
        ranks = clients.draw(rng, n_txs)
        envs, wrote, creators = [], [], set()
        for i in range(n_txs):
            key = keys[shares.get(i, i)]
            value = rng.randbytes(value_bytes)
            client = refused.get(i) or clients.signer(ranks[i])
            creator = client.serialize()
            creators.add(creator)
            prop, _txid = protoutil.create_chaincode_proposal(
                creator, CHANNEL, CHAINCODE, [key.encode(), value],
                nonce=rng.randbytes(24),
            )
            sim = sim_ledger.new_tx_simulator()
            if i in readers:
                sim.get_state(CHAINCODE, key)
            sim.set_state(CHAINCODE, key, value)
            results = sim.get_tx_simulation_results()
            resps = [
                protoutil.create_proposal_response(
                    prop, results=results, events=b"", response=ok,
                    chaincode_id=cc_id, endorser_signer=p,
                )
                for p in peers
            ]
            if i in bad_endorse:
                e = resps[rng.randrange(len(resps))].endorsement
                e.signature = _flip_last_byte(e.signature)
                want[i] = ENDORSEMENT_POLICY_FAILURE
            env = protoutil.create_signed_tx(prop, client, resps)
            if i in bad_creator:
                env.signature = _flip_last_byte(env.signature)
            if i in bad_creator or i in refused:
                want[i] = BAD_CREATOR_SIGNATURE
            elif i in shares and want[i] == VALID:
                want[i] = MVCC_READ_CONFLICT
            envs.append(env.SerializeToString())
            wrote.append((key, value))
        blk = common_pb2.Block()
        blk.header.number = 1 + bno
        blk.data.data.extend(envs)
        while len(blk.metadata.metadata) < 3:
            blk.metadata.metadata.append(b"")
        blocks.append(blk.SerializeToString())
        flags_all.append(want)
        writes_all.append(wrote)
        seen.append(creators)
    return World(
        genesis=genesis, blocks=blocks, planted=flags_all, writes=writes_all,
        lanes_per_block=n_txs * (1 + endorsers),
        public={"ca_certs_pem": {o.mspid: o.ca.cert_pem for o in orgs}, "crls_pem": crls},
        creators_per_block=[len(s) for s in seen],
        creators_per_two_blocks=[len(a | b) for a, b in zip(seen, seen[1:])],
        creators_per_pass=len(set().union(*seen)),
        certificates_issued=len(clients) + n_blocks * n_refused,
    )
