"""Idemix MSP provider tests (reference msp/idemixmsp.go coverage:
config setup, serialize/deserialize roundtrip, signing, principals)."""

import random

import pytest

from fabric_tpu.msp.idemixmsp import (
    ROLE_ADMIN,
    ROLE_MEMBER,
    IdemixMSP,
    IdemixMSPError,
    generate_issuer,
    idemix_msp_config,
    issue_signer_config,
)
from fabric_tpu.protos.msp import msp_principal_pb2

RNG = random.Random(7)


@pytest.fixture(scope="module")
def msp():
    issuer = generate_issuer(rng=RNG)
    signer = issue_signer_config(
        issuer, "IdemixOrg", ou="ou1", role=ROLE_MEMBER,
        enrollment_id="alice", rng=RNG,
    )
    conf = idemix_msp_config(issuer, "IdemixOrg", signer)
    return IdemixMSP.from_config(conf)


def test_sign_verify(msp):
    ident = msp.get_default_signing_identity()
    sig = ident.sign(b"tx-payload")
    assert msp.verify(ident, b"tx-payload", sig)
    assert not msp.verify(ident, b"other", sig)
    assert not msp.verify(ident, b"tx-payload", b"garbage")


def test_deserialize_roundtrip_is_anonymous(msp):
    ident = msp.get_default_signing_identity()
    back = msp.deserialize_identity(ident.serialize())
    assert back.ou == "ou1"
    assert back.role == ROLE_MEMBER
    assert back.nym == ident.nym
    msp.validate(back)
    # Anonymity surface: the serialized identity reveals OU/role only —
    # no enrollment id anywhere in the bytes.
    assert b"alice" not in ident.serialize()


def test_deserialize_rejects_claimed_ou_lie(msp):
    from fabric_tpu.protos.msp import identities_pb2

    sid = identities_pb2.SerializedIdentity.FromString(
        msp.get_default_signing_identity().serialize()
    )
    sii = identities_pb2.SerializedIdemixIdentity.FromString(sid.id_bytes)
    sii.ou = b"ou-forged"
    sid.id_bytes = sii.SerializeToString()
    with pytest.raises(IdemixMSPError):
        msp.deserialize_identity(sid.SerializeToString())


def test_satisfies_principal(msp):
    ident = msp.get_default_signing_identity()
    member = msp_principal_pb2.MSPPrincipal(
        principal_classification=msp_principal_pb2.MSPPrincipal.ROLE,
        principal=msp_principal_pb2.MSPRole(
            msp_identifier="IdemixOrg", role=msp_principal_pb2.MSPRole.MEMBER
        ).SerializeToString(),
    )
    msp.satisfies_principal(ident, member)

    admin = msp_principal_pb2.MSPPrincipal(
        principal_classification=msp_principal_pb2.MSPPrincipal.ROLE,
        principal=msp_principal_pb2.MSPRole(
            msp_identifier="IdemixOrg", role=msp_principal_pb2.MSPRole.ADMIN
        ).SerializeToString(),
    )
    with pytest.raises(IdemixMSPError):
        msp.satisfies_principal(ident, admin)

    ou_ok = msp_principal_pb2.MSPPrincipal(
        principal_classification=msp_principal_pb2.MSPPrincipal.ORGANIZATION_UNIT,
        principal=msp_principal_pb2.OrganizationUnit(
            msp_identifier="IdemixOrg", organizational_unit_identifier="ou1"
        ).SerializeToString(),
    )
    msp.satisfies_principal(ident, ou_ok)


def test_admin_identity():
    issuer = generate_issuer(rng=RNG)
    signer = issue_signer_config(
        issuer, "Org", ou="ou1", role=ROLE_ADMIN, enrollment_id="boss",
        rng=RNG,
    )
    msp = IdemixMSP.from_config(idemix_msp_config(issuer, "Org", signer))
    ident = msp.get_default_signing_identity()
    assert ident.is_admin
    admin = msp_principal_pb2.MSPPrincipal(
        principal_classification=msp_principal_pb2.MSPPrincipal.ROLE,
        principal=msp_principal_pb2.MSPRole(
            msp_identifier="Org", role=msp_principal_pb2.MSPRole.ADMIN
        ).SerializeToString(),
    )
    msp.satisfies_principal(ident, admin)


# -- an `msptype: idemix` organisation reaches a running peer ---------------


def test_a_configtx_profile_with_an_msptype_idemix_org_loads_as_a_bundle(tmp_path):
    """idemixgen ca-keygen -> configtx.yaml as docs/source/idemix.rst
    writes it (lower-case keys, `msptype: idemix`) -> configtxgen ->
    bundle_from_genesis."""
    import yaml

    from fabric_tpu.cmd import configtxgen, idemixgen
    from fabric_tpu.common.channelconfig import bundle_from_genesis
    from fabric_tpu.common.crypto import CA
    from fabric_tpu.msp import write_msp_dir
    from fabric_tpu.protos.common import common_pb2

    root = str(tmp_path)
    for name in ("org1", "orderer"):
        write_msp_dir(f"{root}/{name}/msp", CA(f"ca.{name}.example.com", name))
    assert idemixgen.main(["ca-keygen", "--output", f"{root}/idemix-config"]) == 0
    doc = {
        "Organizations": [
            {"Name": "Org1", "ID": "Org1MSP", "MSPDir": "org1/msp"},
            {"Name": "Orderer", "ID": "OrdererMSP", "MSPDir": "orderer/msp"},
            {"name": "idemixMSP1", "id": "idemixMSPID1", "msptype": "idemix",
             "mspdir": "idemix-config"},
        ],
        "Profiles": {"Anon": {
            "Orderer": {"OrdererType": "solo", "Organizations": ["Orderer"]},
            "Application": {"Organizations": ["Org1", "idemixMSP1"]},
        }},
    }
    with open(f"{root}/configtx.yaml", "w") as f:
        yaml.safe_dump(doc, f)
    assert configtxgen.main([
        "-profile", "Anon", "-channelID", "anonch", "-configPath", root,
        "-outputBlock", f"{root}/genesis.block",
    ]) == 0
    with open(f"{root}/genesis.block", "rb") as f:
        genesis = common_pb2.Block.FromString(f.read())
    bundle = bundle_from_genesis(genesis)
    msp = bundle.msp_manager.get_msp("idemixMSPID1")
    assert isinstance(msp, IdemixMSP)
    assert bundle.application_config.orgs["idemixMSP1"].mspid == "idemixMSPID1"
    assert bundle.orderer_config.org_mspids == ["OrdererMSP"]
    # a member of the idemix org satisfies the channel's Writers
    assert {m.mspid for m in bundle.msp_manager.msps()} == \
        {"Org1MSP", "OrdererMSP", "idemixMSPID1"}


def test_an_idemix_org_without_its_public_key_is_refused_by_configtxgen(tmp_path):
    import yaml

    from fabric_tpu.cmd import configtxgen

    root = str(tmp_path)
    doc = {"Organizations": [{"Name": "I", "ID": "IMSP", "MSPType": "idemix",
                              "MSPDir": "nowhere"}],
           "Profiles": {"P": {"Application": {"Organizations": ["I"]}}}}
    with open(f"{root}/configtx.yaml", "w") as f:
        yaml.safe_dump(doc, f)
    with pytest.raises(SystemExit, match="IssuerPublicKey"):
        configtxgen.main(["-profile", "P", "-configPath", root,
                          "-outputBlock", f"{root}/g.block"])


def test_deserialize_deferred_runs_the_cheap_checks_and_leaves_the_proof(msp):
    ident = msp.get_default_signing_identity()
    deferred = msp.deserialize_deferred(ident.serialize())
    assert deferred.proof_deferred and deferred.msp is msp
    assert (deferred.nym, deferred.ou, deferred.role) == (ident.nym, "ou1", ROLE_MEMBER)
    proof_item, nym_item = deferred.deferred_items(b"payload", ident.sign(b"payload"))
    assert msp.verify_items_async([proof_item, nym_item])() == [True, True]
    # eager deserialisation keeps its meaning: verified when it returns
    assert not msp.deserialize_identity(ident.serialize()).proof_deferred
    # a signature that is no signature is an item that fails, not an error
    _p, garbage = deferred.deferred_items(b"payload", b"garbage")
    assert garbage.sig is None
    assert msp.verify_items_async([garbage])() == [False]
