"""The world of `idemix-nym128`: a channel whose CLIENTS sign with
Identity Mixer credentials (Hyperledger Fabric docs/source/idemix.rst:
an organisation with `msptype: idemix`; Idemix is for clients only,
peers and orderers stay X.509; only OU and Role are disclosed).

One X.509 peer organisation (`Org1MSP`, endorsement `Org1MSP.peer`,
1-of-1), the solo orderer's organisation, and one Idemix client
organisation (`IdemixOrgMSP`) with one issuer.  `enrolled_users`
credentials over the four attributes (OU, Role, EnrollmentID,
RevocationHandle) are issued from the seed; every transaction is
signed by one of them under a FRESH pseudonym, so every creator of
every block is one the peer has never seen: the identity's association
proof (disclosing OU and Role, binding the pseudonym) and the
envelope's pseudonym signature are new work each time.  The
transaction itself is the accepted worlds' (`benchlib/generator.py`):
one endorsement, one write of `value_bytes` into chaincode `benchcc`.

Planted, all from the seed (`planted` of the configuration):

    bad_proof_per_block          a response of the association proof is
                                 off by one: the Fiat-Shamir re-hash fails
    bad_nym_signature_per_block  the envelope is signed over another payload
    ou_mismatch_per_block        the identity claims an OU its proof does
                                 not disclose (refused when deserialised)
    bad_endorsement_per_block    the peer's ECDSA signature is corrupted
    conflict_pairs_per_block     two transactions read and write one key
    rogue_issuer_proofs_per_pass in ONE block of the pass (its first), a
                                 proof from a credential of a rogue issuer:
                                 the same bases, another secret key, so every
                                 Schnorr relation holds and only the pairing
                                 fails

The first four kinds of creator fault give BAD_CREATOR_SIGNATURE, as
upstream's checkSignatureFromCreator does.  The world uses the
program's issuer and signer (`fabric_tpu.idemix`, `msp/idemixmsp.py`)
with `rng` seeded, so the same seed gives the same bytes, proofs
included; only the endorser's ECDSA nonces, the X.509 serial numbers
and with them nothing that changes the work stay random.  Nothing here
touches JAX.
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

IDEMIX_MSPID = "IdemixOrgMSP"
CLIENT_OU = "org2.clients"


@dataclasses.dataclass
class World:
    """The contract of `benchlib/manifest.py`, and what this kind keeps
    for itself: `writes`, and per block how many creators the peer
    refuses when it deserialises them (no item reaches the device for
    those: the condition `idemix-on-device` counts with it)."""

    genesis: object
    blocks: list
    planted: list
    writes: list
    lanes_per_block: int
    public: dict
    refused_at_deserialise: list
    rogue_block: int              # index of the block with the rogue issuer's proof
    channel: str = CHANNEL
    namespaces: tuple = (CHAINCODE,)

    def expected_state(self) -> dict:
        state: dict = {}
        for bno, (want, wrote) in enumerate(zip(self.planted, self.writes)):
            for i, (flag, (key, value)) in enumerate(zip(want, wrote)):
                if flag == VALID:
                    state[CHAINCODE, key] = (value, (1 + bno, i))
        return state


class _Anon:
    """One transaction's anonymous client: a fresh pseudonym of an
    enrolled user, with the faults the generator plants."""

    def __init__(self, user, ipk, rng, bad_proof=False, forged_ou=False):
        from fabric_tpu.msp.idemixmsp import ROLE_MEMBER, IdemixSigningIdentity
        from fabric_tpu.protos.msp import identities_pb2

        sk, cred = user
        self._inner = IdemixSigningIdentity(
            IDEMIX_MSPID, sk, cred, ipk, CLIENT_OU, ROLE_MEMBER, rng=rng
        )
        self._serialized = self._inner.serialize()
        if bad_proof or forged_ou:
            proof = self._inner.proof
            if bad_proof:
                proof = dataclasses.replace(proof, responses={
                    **proof.responses,
                    "sprime": (proof.responses["sprime"] + 1) % _order(),
                })
            nym = self._inner.nym
            self._serialized = identities_pb2.SerializedIdentity(
                mspid=IDEMIX_MSPID,
                id_bytes=identities_pb2.SerializedIdemixIdentity(
                    nym_x=nym[0].to_bytes(32, "big"),
                    nym_y=nym[1].to_bytes(32, "big"),
                    ou=(b"org2.forged" if forged_ou else CLIENT_OU.encode()),
                    role=ROLE_MEMBER.to_bytes(4, "big"),
                    proof=proof.to_bytes(),
                ).SerializeToString(),
            ).SerializeToString()

    def serialize(self) -> bytes:
        return self._serialized

    def sign(self, msg: bytes) -> bytes:
        return self._inner.sign(msg)


def _order() -> int:
    from fabric_tpu.idemix import bn254 as bn

    return bn.R


def _enrol(issuer, rng, enrollment_id: str, handle: int):
    """(sk, credential) of one user, as Fabric CA's Idemix enrolment
    gives it: request, issue, check."""
    from fabric_tpu.idemix import bn254 as bn
    from fabric_tpu.idemix.credential import (
        attribute_to_scalar,
        new_cred_request,
        new_credential,
    )
    from fabric_tpu.msp.idemixmsp import ROLE_MEMBER

    sk = bn.rand_zr(rng)
    req = new_cred_request(sk, rng.randbytes(16), issuer.ipk, rng=rng)
    cred = new_credential(issuer, req, [
        attribute_to_scalar(CLIENT_OU),
        attribute_to_scalar(ROLE_MEMBER),
        attribute_to_scalar(enrollment_id),
        attribute_to_scalar(handle),
    ], rng=rng)
    cred.ver(sk, issuer.ipk)
    return sk, cred


def _rogue_issuer(issuer, rng):
    """An issuer with the channel issuer's bases and another secret
    key: its credentials satisfy every Schnorr relation of a
    presentation against the channel's public key, and fail only
    e(A', W) == e(Abar, g2)."""
    from fabric_tpu.idemix import bn254 as bn
    from fabric_tpu.idemix.issuer import IssuerKey

    x = bn.rand_zr(rng)
    ipk = dataclasses.replace(issuer.ipk, w=bn.g2_mul(bn.G2_GEN, x))
    return IssuerKey(isk=x, ipk=ipk)


def build_world(seed: int, deployment: dict, planted: dict, n_blocks: int) -> World:
    from fabric_tpu import protoutil
    from fabric_tpu.common import configtx_builder as ctx
    from fabric_tpu.csp import SWCSP
    from fabric_tpu.ledger import LedgerProvider
    from fabric_tpu.msp import msp_config_from_ca
    from fabric_tpu.msp.idemixmsp import generate_issuer, idemix_msp_config
    from fabric_tpu.protos.common import common_pb2
    from fabric_tpu.protos.peer import chaincode_pb2, proposal_pb2

    rng = random.Random(f"fabric-bench-idemix:{int(seed)}")
    sw = SWCSP()
    n_txs = int(deployment["block_txs"])
    value_bytes = int(deployment["value_bytes"])
    if int(deployment["orgs"]) != 1 or int(deployment["endorsers_per_tx"]) != 1:
        raise ValueError("this world has one X.509 peer organisation, 1-of-1")

    org1 = Org("Org1MSP", _seeded_ca(rng, "ca.org1msp.example.com", "Org1MSP"), sw)
    oorg = Org("OrdererMSP",
               _seeded_ca(rng, "ca.orderermsp.example.com", "OrdererMSP"), sw)
    issuer = generate_issuer(rng=rng)
    rogue = _rogue_issuer(issuer, rng)
    app = ctx.application_group({
        "Org1": ctx.org_group(org1.mspid, msp_config_from_ca(org1.ca, org1.mspid)),
        # msptype idemix: members may write (Writers = ANY over the
        # orgs' Writers, '<mspid>.member'), nobody endorses
        "IdemixOrg": ctx.org_group(
            IDEMIX_MSPID, idemix_msp_config(issuer, IDEMIX_MSPID)
        ),
    })
    ordg = ctx.orderer_group(
        {"O": ctx.org_group("OrdererMSP", msp_config_from_ca(oorg.ca, "OrdererMSP"))},
        consensus_type=deployment.get("orderer", "solo"),
        max_message_count=n_txs,
    )
    genesis = ctx.genesis_block(CHANNEL, ctx.channel_group(app, ordg))

    users = [
        _enrol(issuer, rng, f"user{u}", u)
        for u in range(int(deployment["enrolled_users"]))
    ]
    outsider = _enrol(rogue, rng, "outsider", 0)
    peer = org1.signer(rng, "peer0", "peer")
    cc_id = chaincode_pb2.ChaincodeID(name=CHAINCODE)
    ok = proposal_pb2.Response(status=200)
    sim_ledger = LedgerProvider(None).create(genesis)

    n_bad_p = int(planted["bad_proof_per_block"])
    n_bad_n = int(planted["bad_nym_signature_per_block"])
    n_ou = int(planted["ou_mismatch_per_block"])
    n_bad_e = int(planted["bad_endorsement_per_block"])
    n_conf = int(planted["conflict_pairs_per_block"])
    n_rogue = int(planted["rogue_issuer_proofs_per_pass"])
    # WHICH transaction of the block carries the rogue issuer's proof is
    # the seed's; the block is the pass's first.  The seed chose the
    # block too at first, and its place in store_stream's pipeline split
    # the cell's rate in two (PERF.md, Findings of PR 28): a pass is
    # replayed for the whole window, so one seed measured one place.
    rogue_block = 0

    blocks, flags_all, writes_all, refused = [], [], [], []
    for bno in range(n_blocks):
        here_rogue = n_rogue if bno == rogue_block else 0
        wanted = n_bad_p + n_bad_n + n_ou + n_bad_e + here_rogue + 2 * n_conf
        # a block smaller than what is planted (a test's) takes the
        # kinds in this order as far as its transactions go
        cut = iter(rng.sample(range(n_txs), min(wanted, n_txs)))
        bad_proof = set(itertools.islice(cut, n_bad_p))
        bad_nym = set(itertools.islice(cut, n_bad_n))
        forged_ou = set(itertools.islice(cut, n_ou))
        bad_endorse = set(itertools.islice(cut, n_bad_e))
        from_rogue = set(itertools.islice(cut, here_rogue))
        pairs = list(cut)
        shares: dict[int, int] = {}
        readers: set[int] = set()
        for a, b in zip(pairs[0::2], pairs[1::2]):
            first, second = min(a, b), max(a, b)
            shares[second] = first
            readers.update((first, second))
        want = [VALID] * n_txs
        keys = [f"k{bno}-{i}-{rng.getrandbits(40):010x}" for i in range(n_txs)]
        envs, wrote = [], []
        for i in range(n_txs):
            key = keys[shares.get(i, i)]
            value = rng.randbytes(value_bytes)
            client = _Anon(
                outsider if i in from_rogue else users[rng.randrange(len(users))],
                issuer.ipk, rng,
                bad_proof=i in bad_proof, forged_ou=i in forged_ou,
            )
            prop, _txid = protoutil.create_chaincode_proposal(
                client.serialize(), CHANNEL, CHAINCODE, [key.encode(), value],
                nonce=rng.randbytes(24),
            )
            sim = sim_ledger.new_tx_simulator()
            if i in readers:
                sim.get_state(CHAINCODE, key)
            sim.set_state(CHAINCODE, key, value)
            resp = protoutil.create_proposal_response(
                prop, results=sim.get_tx_simulation_results(), events=b"",
                response=ok, chaincode_id=cc_id, endorser_signer=peer,
            )
            if i in bad_endorse:
                resp.endorsement.signature = _flip_last_byte(resp.endorsement.signature)
                want[i] = ENDORSEMENT_POLICY_FAILURE
            env = protoutil.create_signed_tx(prop, client, [resp])
            if i in bad_nym:
                env.signature = client.sign(env.payload + b"\x00another")
            if i in bad_proof or i in bad_nym or i in forged_ou or i in from_rogue:
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
        refused.append(len(forged_ou))
    return World(
        genesis=genesis, blocks=blocks, planted=flags_all, writes=writes_all,
        # per transaction: one association proof, one pseudonym
        # signature (BN254), one endorsement signature (P-256)
        lanes_per_block=3 * n_txs,
        public={
            "ca_certs_pem": {org1.mspid: org1.ca.cert_pem},
            "idemix_issuers": {IDEMIX_MSPID: issuer.ipk.to_dict()},
        },
        refused_at_deserialise=refused, rogue_block=rogue_block,
    )
