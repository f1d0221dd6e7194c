"""validate_pipeline: ordered flags identical to sequential validate,
with duplicate-txid detection spanning in-flight blocks."""

from __future__ import annotations

import pytest

from orgfix import make_org

from fabric_tpu import protoutil
from fabric_tpu.common import configtx_builder as ctx
from fabric_tpu.common.channelconfig import bundle_from_genesis
from fabric_tpu.ledger import LedgerProvider
from fabric_tpu.msp import msp_config_from_ca
from fabric_tpu.peer.endorser import Endorser
from fabric_tpu.peer.txvalidator import TxValidator
from fabric_tpu.protos.common import common_pb2
from fabric_tpu.protos.peer import proposal_pb2, transaction_pb2

V = transaction_pb2


def _cc(sim, args):
    sim.set_state("pipecc", args[0].decode(), args[1])
    return 200, "", b""


@pytest.fixture(scope="module")
def world():
    org = make_org("Org1MSP")
    oorg = make_org("OrdererMSP")
    app = ctx.application_group(
        {"Org1": ctx.org_group("Org1MSP", msp_config_from_ca(org.ca, "Org1MSP"))}
    )
    ordg = ctx.orderer_group(
        {"O": ctx.org_group("OrdererMSP", msp_config_from_ca(oorg.ca, "OrdererMSP"))},
        consensus_type="solo",
    )
    genesis = ctx.genesis_block("pipech", ctx.channel_group(app, ordg))
    provider = LedgerProvider(None)
    ledger = provider.create(genesis)
    bundle = bundle_from_genesis(genesis, org.csp)
    endorser = Endorser(
        "pipech", ledger, bundle, org.signer("peer0", role_ou="peer"),
        {"pipecc": _cc}, org.csp,
    )
    client = org.signer("user1", role_ou="client")
    return org, ledger, bundle, endorser, client


def _tx(endorser, client, key: bytes, val: bytes):
    prop, txid = protoutil.create_chaincode_proposal(
        client.serialize(), "pipech", "pipecc", [key, val]
    )
    signed = proposal_pb2.SignedProposal(
        proposal_bytes=prop.SerializeToString(),
        signature=client.sign(prop.SerializeToString()),
    )
    resp = endorser.process_proposal(signed)
    return protoutil.create_signed_tx(prop, client, [resp])


def _block(num: int, envs) -> common_pb2.Block:
    blk = common_pb2.Block()
    blk.header.number = num
    blk.data.data.extend(e.SerializeToString() for e in envs)
    while len(blk.metadata.metadata) < 3:
        blk.metadata.metadata.append(b"")
    return blk


def test_pipeline_matches_sequential(world):
    org, ledger, bundle, endorser, client = world
    blocks = []
    for b in range(3):
        envs = []
        for i in range(4):
            env = _tx(endorser, client, b"k%d-%d" % (b, i), b"v")
            if i == 2:  # tamper one creator signature per block
                env = common_pb2.Envelope(
                    payload=env.payload, signature=env.signature[:-2] + b"xx"
                )
            envs.append(env)
        blocks.append(_block(b + 1, envs))

    def copies():
        out = []
        for blk in blocks:
            c = common_pb2.Block()
            c.CopyFrom(blk)
            out.append(c)
        return out

    seq = [
        TxValidator("pipech", ledger, bundle, org.csp).validate(b)
        for b in copies()
    ]
    piped = list(
        TxValidator("pipech", ledger, bundle, org.csp).validate_pipeline(
            copies(), depth=2
        )
    )
    assert piped == seq
    for flags in piped:
        assert flags[2] == V.BAD_CREATOR_SIGNATURE
        assert [flags[0], flags[1], flags[3]] == [V.VALID] * 3


def test_pipeline_catches_cross_block_duplicate_txid(world):
    org, ledger, bundle, endorser, client = world
    env = _tx(endorser, client, b"dupkey", b"v")
    b1 = _block(10, [env])
    b2 = _block(11, [env])  # same envelope (same txid) in the next block
    piped = list(
        TxValidator("pipech", ledger, bundle, org.csp).validate_pipeline(
            [b1, b2], depth=2
        )
    )
    assert piped[0] == [V.VALID]
    assert piped[1] == [V.DUPLICATE_TXID]


# -- anonymous (Idemix) creators on the same path --------------------------
#
# A channel with one X.509 peer organisation and one Idemix client
# organisation.  An Idemix creator's credential proof and pseudonym
# signature are deferred to the block's one batched Idemix verify, as
# an X.509 creator's signature is deferred to the ECDSA batch.

import dataclasses
import random

from fabric_tpu.csp.idemix_provider import for_csp
from fabric_tpu.msp.idemixmsp import (
    ROLE_MEMBER,
    IdemixMSPError,
    IdemixSigningIdentity,
    generate_issuer,
    idemix_msp_config,
    issue_signer_config,
)
from fabric_tpu.idemix import bn254 as bn
from fabric_tpu.idemix.credential import Credential
from fabric_tpu.idemix.issuer import IssuerKey
from fabric_tpu.protos.msp import identities_pb2
from fabric_tpu.protos.peer import chaincode_pb2

RNG = random.Random(28)
IDEMIX_ORG = "IdemixOrgMSP"


def _user(issuer, ou="ou1"):
    sc = issue_signer_config(issuer, IDEMIX_ORG, ou=ou, role=ROLE_MEMBER,
                             enrollment_id="alice", rng=RNG)
    return int.from_bytes(sc.sk, "big"), Credential.from_bytes(sc.cred)


class _Anon:
    """A fresh pseudonym of a user, with one planted fault."""

    def __init__(self, user, ipk, fault=None):
        sk, cred = user
        self.inner = IdemixSigningIdentity(
            IDEMIX_ORG, sk, cred, ipk, "ou1", ROLE_MEMBER, rng=RNG
        )
        self.fault = fault
        proof = self.inner.proof
        if fault == "bad_proof":
            proof = dataclasses.replace(proof, responses={
                **proof.responses, "sk": (proof.responses["sk"] + 1) % bn.R})
        nym = self.inner.nym
        self._serialized = identities_pb2.SerializedIdentity(
            mspid=IDEMIX_ORG,
            id_bytes=identities_pb2.SerializedIdemixIdentity(
                nym_x=nym[0].to_bytes(32, "big"), nym_y=nym[1].to_bytes(32, "big"),
                ou=b"ou-forged" if fault == "forged_ou" else b"ou1",
                role=ROLE_MEMBER.to_bytes(4, "big"), proof=proof.to_bytes(),
            ).SerializeToString(),
        ).SerializeToString()

    def serialize(self):
        return self._serialized

    def sign(self, msg):
        if self.fault == "bad_nym":
            return self.inner.sign(msg + b"another")
        if self.fault == "garbage_sig":
            return b"not json"
        return self.inner.sign(msg)


@pytest.fixture(scope="module")
def idemix_world():
    org = make_org("Org1MSP")
    oorg = make_org("OrdererMSP")
    issuer = generate_issuer(rng=RNG)
    # same bases, another secret key: only the pairing tells
    x = bn.rand_zr(RNG)
    rogue = IssuerKey(isk=x, ipk=dataclasses.replace(
        issuer.ipk, w=bn.g2_mul(bn.G2_GEN, x)))
    app = ctx.application_group({
        "Org1": ctx.org_group("Org1MSP", msp_config_from_ca(org.ca, "Org1MSP")),
        "IdemixOrg": ctx.org_group(IDEMIX_ORG, idemix_msp_config(issuer, IDEMIX_ORG)),
    })
    ordg = ctx.orderer_group(
        {"O": ctx.org_group("OrdererMSP", msp_config_from_ca(oorg.ca, "OrdererMSP"))},
        consensus_type="solo",
    )
    genesis = ctx.genesis_block("pipech", ctx.channel_group(app, ordg))
    ledger = LedgerProvider(None).create(genesis)
    bundle = bundle_from_genesis(genesis, org.csp)
    return {
        "org": org, "ledger": ledger, "bundle": bundle, "genesis": genesis,
        "peer": org.signer("peer0", role_ou="peer"),
        "x509_client": org.signer("user1", role_ou="client"),
        "ipk": issuer.ipk, "user": _user(issuer), "outsider": _user(rogue),
    }


def _anon_tx(w, client, key: bytes, val: bytes = b"v", spoil_endorsement=False):
    """An endorsed transaction of `client`, endorsed as the generator
    of the benchmark endorses (no proposal round trip)."""
    prop, _txid = protoutil.create_chaincode_proposal(
        client.serialize(), "pipech", "pipecc", [key, val]
    )
    sim = w["ledger"].new_tx_simulator()
    sim.set_state("pipecc", key.decode(), val)
    resp = protoutil.create_proposal_response(
        prop, results=sim.get_tx_simulation_results(), events=b"",
        response=proposal_pb2.Response(status=200),
        chaincode_id=chaincode_pb2.ChaincodeID(name="pipecc"),
        endorser_signer=w["peer"],
    )
    if spoil_endorsement:
        e = resp.endorsement
        e.signature = e.signature[:-1] + bytes([e.signature[-1] ^ 1])
    return protoutil.create_signed_tx(prop, client, [resp])


FAULTS = [None, "bad_proof", "bad_nym", "forged_ou", "rogue_issuer", "garbage_sig"]


def _faulty(w, fault):
    if fault == "rogue_issuer":
        return _Anon(w["outsider"], w["ipk"])
    return _Anon(w["user"], w["ipk"], fault)


def test_a_genesis_block_with_an_idemix_org_builds_a_bundle(idemix_world):
    from fabric_tpu.msp.idemixmsp import IdemixMSP

    bundle = idemix_world["bundle"]
    msp = bundle.msp_manager.get_msp(IDEMIX_ORG)
    assert isinstance(msp, IdemixMSP) and msp.ipk == idemix_world["ipk"]
    assert set(bundle.application_config.orgs) == {"Org1", "IdemixOrg"}
    assert bundle.application_config.orgs["IdemixOrg"].mspid == IDEMIX_ORG
    # clients only: the org has Writers and no Endorsement policy, so the
    # channel's MAJORITY Endorsement counts Org1 alone
    group = bundle.config.channel_group.groups["Application"].groups["IdemixOrg"]
    assert set(group.policies) == {"Readers", "Writers", "Admins"}
    # the MSP verifies through the provider beside the bundle's CSP
    assert msp._idemix is for_csp(idemix_world["org"].csp)


@pytest.mark.parametrize("fault", FAULTS)
def test_deferred_verification_gives_the_eager_verdict(idemix_world, fault):
    """`deserialize_creator` + the batched items against the eager
    `deserialize_identity` + `verify`, for every planted kind."""
    w = idemix_world
    mgr = w["bundle"].msp_manager
    msp = mgr.get_msp(IDEMIX_ORG)
    client = _faulty(w, fault)
    msg = b"the envelope's payload"
    sig = client.sign(msg)
    try:
        eager = msp.verify(mgr._inner.deserialize_identity(client.serialize()), msg, sig)
    except IdemixMSPError:
        eager = False
    try:
        ident = mgr.deserialize_creator(client.serialize())
    except IdemixMSPError:
        deferred = False
        assert fault == "forged_ou"      # a cheap check: refused at once
    else:
        assert ident.proof_deferred and ident.anonymous
        deferred = all(msp.verify_items_async(ident.deferred_items(msg, sig))())
    assert deferred == eager == (fault is None)
    # single-use identities stay out of the caches
    assert mgr._deserialize.get(client.serialize()) == (None, False)


def _mixed_block(w, num):
    clients = [w["x509_client"]] + [_faulty(w, f) for f in FAULTS] + [w["x509_client"]]
    envs = [_anon_tx(w, c, b"mk%d-%d" % (num, i)) for i, c in enumerate(clients)]
    # and one X.509 creator whose signature is tampered
    envs[-1] = common_pb2.Envelope(
        payload=envs[-1].payload, signature=envs[-1].signature[:-2] + b"xx")
    want = [V.VALID, V.VALID] + [V.BAD_CREATOR_SIGNATURE] * (len(FAULTS) - 1) \
        + [V.BAD_CREATOR_SIGNATURE]
    return _block(num, envs), want


def test_a_block_mixing_x509_and_idemix_creators(idemix_world):
    w = idemix_world
    blk, want = _mixed_block(w, 1)
    v = TxValidator("pipech", w["ledger"], w["bundle"], w["org"].csp)
    assert v.validate(blk) == want


def test_native_and_python_collect_agree_on_idemix_creators(idemix_world, monkeypatch):
    from fabric_tpu import native

    if not native.available():
        pytest.skip(f"no native collector: {native.load_error()}")
    w = idemix_world
    blk, want = _mixed_block(w, 2)
    py = common_pb2.Block()
    py.CopyFrom(blk)
    v = TxValidator("pipech", w["ledger"], w["bundle"], w["org"].csp)
    assert v.validate(blk) == want
    monkeypatch.setattr(native, "available", lambda: False)
    v2 = TxValidator("pipech", w["ledger"], w["bundle"], w["org"].csp)
    assert v2.validate(py) == want


def test_bad_creator_signature_takes_precedence_for_idemix_creators(idemix_world):
    """A failed proof or pseudonym signature wins over the endorsement
    policy's failure of the same transaction, as the X.509 creator
    mask does."""
    w = idemix_world
    envs = [
        _anon_tx(w, _faulty(w, fault), b"pk%d" % i, spoil_endorsement=True)
        for i, fault in enumerate(("bad_proof", "bad_nym", "rogue_issuer", None))
    ]
    v = TxValidator("pipech", w["ledger"], w["bundle"], w["org"].csp)
    assert v.validate(_block(3, envs)) == \
        [V.BAD_CREATOR_SIGNATURE] * 3 + [V.ENDORSEMENT_POLICY_FAILURE]
