"""A block's creators validated as one batch (`CachedMSP.
deserialize_creators`: the chain signatures of the X.509 identities in
one `native.ecdsa_verify_host` call, the verdict brought to
`MSP.validate` ahead) give, identity for identity, what the same
creators give one at a time through `deserialize_creator`; with the
native verifier there and with it gone."""

import datetime
import random

import pytest
from cryptography import x509
from cryptography.hazmat.primitives import hashes, serialization
from cryptography.hazmat.primitives.asymmetric import ec
from cryptography.hazmat.primitives.asymmetric.utils import decode_dss_signature
from cryptography.x509.oid import NameOID

from fabric_tpu import native
from fabric_tpu.common.crypto import CA
from fabric_tpu.csp import SWCSP
from fabric_tpu.csp.api import P256_HALF_N as HALF_N
from fabric_tpu.msp import MSP, MSPManager, msp_config_from_ca
from fabric_tpu.msp import msp as msp_mod
from fabric_tpu.msp.cache import CachedMSP
from fabric_tpu.msp.idemixmsp import (
    ROLE_MEMBER,
    IdemixMSP,
    generate_issuer,
    idemix_msp_config,
    issue_signer_config,
)
from fabric_tpu.protos.msp import identities_pb2, msp_config_pb2
from orgfix import undecodable_issuer

FILLERS = 2 * msp_mod._NATIVE_BATCH_MIN


def _creator(mspid: str, cert: x509.Certificate) -> bytes:
    return identities_pb2.SerializedIdentity(
        mspid=mspid, id_bytes=cert.public_bytes(serialization.Encoding.PEM),
    ).SerializeToString()


def _rogue(ca: CA) -> CA:
    """Another key under the CA's own certificate: what it issues names
    the CA's subject and key identifier and fails the signature alone."""
    rogue = CA.__new__(CA)
    rogue.key = ec.generate_private_key(ec.SECP256R1())
    rogue.org, rogue.cert, rogue.parent, rogue._revoked = ca.org, ca.cert, None, []
    return rogue


def _issue_with_s(ca: CA, high: bool) -> x509.Certificate:
    for i in range(200):
        cert = ca.issue(f"s{i}", ous=["client"]).cert
        if (decode_dss_signature(cert.signature)[1] > HALF_N) == high:
            return cert
    raise AssertionError("no such signature in 200 draws")


def _p384_org(mspid: str):
    """An organisation whose CA holds a P-384 key (its certificates are
    still ECDSA with SHA-256): the native verifier takes P-256 alone."""
    key = ec.generate_private_key(ec.SECP384R1())
    name = x509.Name([x509.NameAttribute(NameOID.COMMON_NAME, "ca.p384")])
    now = datetime.datetime.now(datetime.timezone.utc)
    ca_cert = (
        x509.CertificateBuilder().subject_name(name).issuer_name(name)
        .public_key(key.public_key()).serial_number(x509.random_serial_number())
        .not_valid_before(now - datetime.timedelta(minutes=5))
        .not_valid_after(now + datetime.timedelta(days=30))
        .add_extension(x509.BasicConstraints(ca=True, path_length=None), critical=True)
        .sign(key, hashes.SHA256())
    )
    leaf = (
        x509.CertificateBuilder()
        .subject_name(x509.Name([
            x509.NameAttribute(NameOID.COMMON_NAME, "user"),
            x509.NameAttribute(NameOID.ORGANIZATIONAL_UNIT_NAME, "client"),
        ]))
        .issuer_name(name)
        .public_key(ec.generate_private_key(ec.SECP256R1()).public_key())
        .serial_number(x509.random_serial_number())
        .not_valid_before(now - datetime.timedelta(minutes=5))
        .not_valid_after(now + datetime.timedelta(days=30))
        .sign(key, hashes.SHA256())
    )
    fconf = msp_config_pb2.FabricMSPConfig(
        name=mspid, root_certs=[ca_cert.public_bytes(serialization.Encoding.PEM)],
    )
    conf = msp_config_pb2.MSPConfig(type=0, config=fconf.SerializeToString())
    return conf, leaf


class _Channel:
    """Three X.509 organisations and an Idemix one, and a block's worth
    of creators: name -> (creator bytes, accepted?, X.509 chain
    signature the native batch can decide?)."""

    def __init__(self):
        rng = random.Random(33)
        ca1 = CA("ca.org1", "Org1MSP")
        ica1 = ca1.new_intermediate("ica.org1")
        revoked = ca1.issue("revoked", ous=["client"])
        ca1.revoke(revoked.cert)
        past = datetime.datetime.now(datetime.timezone.utc) - datetime.timedelta(days=1)
        # Org3 trusts two roots of one subject: the CA and the other key
        # under its name (several candidates: the single check's)
        ca3 = CA("ca.org3", "Org3MSP")
        twin3 = CA("ca.org3", "Org3MSP")
        ca2 = CA("ca.org2", "Org2MSP")
        p384_conf, p384_leaf = _p384_org("Org4MSP")
        issuer = generate_issuer(rng=rng)
        anon = issue_signer_config(
            issuer, "IdemixOrg", ou="ou1", role=ROLE_MEMBER,
            enrollment_id="alice", rng=rng,
        )
        idemix_conf = idemix_msp_config(issuer, "IdemixOrg", anon)
        conf3 = msp_config_pb2.FabricMSPConfig.FromString(
            msp_config_from_ca(ca3, "Org3MSP").config
        )
        conf3.root_certs.append(twin3.cert_pem)
        self._confs = [
            msp_config_from_ca(ca1, "Org1MSP", intermediates=[ica1], crls=[ca1.gen_crl()]),
            msp_config_from_ca(ca2, "Org2MSP"),
            msp_config_pb2.MSPConfig(type=0, config=conf3.SerializeToString()),
            p384_conf,
        ]
        self._idemix_conf = idemix_conf
        org1 = lambda cert: _creator("Org1MSP", cert)  # noqa: E731
        self.creators = {
            "a sound client": (org1(ca1.issue("u", ous=["client"]).cert), True, True),
            "signed by another key under the CA's subject and key identifier":
                (org1(_rogue(ca1).issue("r", ous=["client"]).cert), False, True),
            "expired": (org1(ca1.issue("e", ous=["client"], not_after=past).cert), False, True),
            "revoked": (org1(revoked.cert), False, True),
            "no role OU": (org1(ca1.issue("n", ous=[]).cert), False, True),
            "a CA signature with high S": (org1(_issue_with_s(ca1, True)), True, True),
            "a CA signature with low S": (org1(_issue_with_s(ca1, False)), True, True),
            "an unknown issuer":
                (org1(CA("ca.elsewhere", "Org1MSP").issue("x", ous=["client"]).cert), False, False),
            "a chain through an intermediate CA":
                (org1(ica1.issue("i", ous=["client"]).cert), True, True),
            "an intermediate CA's rogue twin":
                (org1(_rogue(ica1).issue("ir", ous=["client"]).cert), False, True),
            "a CA whose key is not P-256": (_creator("Org4MSP", p384_leaf), True, False),
            "a creator of another MSP":
                (_creator("Org2MSP", ca2.issue("o", ous=["client"]).cert), True, True),
            "a certificate of another MSP's CA under this MSP's name":
                (org1(ca2.issue("w", ous=["client"]).cert), False, False),
            "two trusted roots of one subject, the second signed":
                (_creator("Org3MSP", twin3.issue("t", ous=["client"]).cert), True, False),
            "an Idemix creator":
                (IdemixMSP.from_config(idemix_conf).get_default_signing_identity().serialize(),
                 True, False),
            "an MSP the channel does not know":
                (_creator("NopeMSP", ca1.issue("k", ous=["client"]).cert), False, False),
            "no identity at all": (b"\x00garbage", False, False),
            # it loads, and reading its issuer raises: refused alone,
            # by the batch as by the single door
            "an issuer name that does not decode":
                (undecodable_issuer(org1(ca1.issue("b", ous=["client"]).cert)), False, False),
        }
        for i in range(FILLERS):
            self.creators[f"filler {i}"] = (
                org1(ca1.issue(f"f{i}", ous=["client"]).cert), True, True)

    def manager(self) -> CachedMSP:
        csp = SWCSP()
        msps = [MSP.from_config(c, csp) for c in self._confs]
        msps.append(IdemixMSP.from_config(self._idemix_conf, csp))
        return CachedMSP(MSPManager(msps))


@pytest.fixture(scope="module")
def channel():
    return _Channel()


@pytest.fixture(scope="module", params=["native", "no native verifier"])
def verdicts(request, channel):
    """name -> (the batch's identity or None, the single door's), and
    what the batch's one native call decided."""
    mp = pytest.MonkeyPatch()
    if request.param == "native":
        if native.ecdsa_verify_host([]) is None:
            pytest.skip(f"no native verifier: {native.load_error()}")
    else:
        mp.setattr(native, "ecdsa_verify_host", lambda items: None)
    try:
        names = list(channel.creators)
        raws = [channel.creators[n][0] for n in names]
        batch, decided = channel.manager().deserialize_creators(raws)
        one_by_one = channel.manager()
        single = []
        for raw in raws:
            try:
                single.append(one_by_one.deserialize_creator(raw))
            except Exception:
                single.append(None)
    finally:
        mp.undo()
    return request.param, dict(zip(names, zip(batch, single))), decided


@pytest.mark.parametrize("name", [
    "a sound client",
    "signed by another key under the CA's subject and key identifier",
    "expired", "revoked", "no role OU",
    "a CA signature with high S", "a CA signature with low S",
    "an unknown issuer", "a chain through an intermediate CA",
    "an intermediate CA's rogue twin", "a CA whose key is not P-256",
    "a creator of another MSP",
    "a certificate of another MSP's CA under this MSP's name",
    "two trusted roots of one subject, the second signed",
    "an Idemix creator", "an MSP the channel does not know",
    "no identity at all", "an issuer name that does not decode",
])
def test_the_batch_and_the_single_door_agree(channel, verdicts, name):
    _mode, got, _decided = verdicts
    batch, single = got[name]
    accepted = channel.creators[name][1]
    assert (single is not None) == accepted
    assert (batch is not None) == accepted
    if accepted:
        assert type(batch) is type(single)
        assert batch.serialize() == single.serialize()
        assert getattr(batch, "proof_deferred", False) == getattr(
            single, "proof_deferred", False)
        # the verdict brought ahead was taken by the validate it was for
        assert getattr(batch, "chain_verdict", None) is None


def test_the_native_call_decides_every_qualifying_signature_or_none(channel, verdicts):
    mode, got, decided = verdicts
    qualifying = sum(q for _raw, _ok, q in channel.creators.values())
    assert qualifying >= msp_mod._NATIVE_BATCH_MIN
    assert decided == (qualifying if mode == "native" else 0)
    assert all(b is not None and s is not None
               for n, (b, s) in got.items() if n.startswith("filler"))


def test_a_native_verifier_that_raises_decides_nothing_and_refuses_no_one(channel, monkeypatch):
    def broken(items):
        raise OSError("libcrypto went away")

    monkeypatch.setattr(native, "ecdsa_verify_host", broken)
    raws = [raw for raw, _ok, _q in channel.creators.values()]
    idents, decided = channel.manager().deserialize_creators(raws)
    assert decided == 0
    assert [i is not None for i in idents] == [ok for _raw, ok, _q in channel.creators.values()]


def test_a_batch_under_the_size_takes_openssl_in_place(channel, monkeypatch):
    calls = []
    real = native.ecdsa_verify_host
    monkeypatch.setattr(native, "ecdsa_verify_host",
                        lambda items: calls.append(len(items)) or real(items))
    few = [channel.creators[f"filler {i}"][0]
           for i in range(msp_mod._NATIVE_BATCH_MIN - 1)]
    idents, decided = channel.manager().deserialize_creators(few)
    assert decided == 0 and calls == [] and all(i is not None for i in idents)
    enough = few + [channel.creators["a sound client"][0]]
    idents, decided = channel.manager().deserialize_creators(enough)
    assert decided == len(enough) and calls == [len(enough)]
    assert all(i is not None for i in idents)


def test_the_batch_keeps_the_caches_counts_and_finds_their_entries(channel):
    mgr = channel.manager()
    raws = [raw for raw, _ok, _q in channel.creators.values()][:40]
    first, _ = mgr.deserialize_creators(raws)
    mid = mgr.tally()["requests"]
    again, decided = mgr.deserialize_creators(raws)
    after = mgr.tally()["requests"]
    # the second block finds every X.509 identity it deserialised and
    # every verdict, the refusals too: nothing is left to prove
    assert decided == 0
    assert [i is None for i in again] == [i is None for i in first]
    assert after["validate"]["miss"] == mid["validate"]["miss"]
    assert after["validate"]["hit"] > mid["validate"]["hit"]
    assert after["deserialize"]["hit"] - mid["deserialize"]["hit"] >= 35
