"""Shared test fixture: in-memory organizations with CAs and identities
(the role cryptogen-generated fixtures play in the reference's tests)."""

from __future__ import annotations

import dataclasses

from fabric_tpu.common.crypto import CA, CertKeyPair
from fabric_tpu.csp import SWCSP
from fabric_tpu.msp import MSP, SigningIdentity, msp_config_from_ca


@dataclasses.dataclass
class Org:
    mspid: str
    ca: CA
    msp: MSP
    csp: SWCSP

    def signer(self, name: str, role_ou: str = "peer") -> SigningIdentity:
        pair = self.ca.issue(name, ous=[role_ou])
        return SigningIdentity.from_pem(self.mspid, pair.cert_pem, pair.key_pem, self.csp)

    def issue(self, name: str, ous: list[str]) -> CertKeyPair:
        return self.ca.issue(name, ous=ous)


def make_org(mspid: str = "Org1MSP", node_ous: bool = True, admins=None) -> Org:
    csp = SWCSP()
    ca = CA(f"ca.{mspid.lower()}.example.com", mspid)
    conf = msp_config_from_ca(ca, mspid, node_ous=node_ous, admins=admins or [])
    msp = MSP.from_config(conf, csp)
    return Org(mspid, ca, msp, csp)


def undecodable_issuer(creator: bytes) -> bytes:
    """The serialized identity with the last byte of its certificate's
    issuer name made a byte no string type decodes.  Such a certificate
    loads (cryptography parses a Name when it is first read) and raises
    ValueError from `.issuer` ever after."""
    from cryptography import x509
    from cryptography.hazmat.primitives.serialization import Encoding

    from fabric_tpu.protos.msp import identities_pb2

    sid = identities_pb2.SerializedIdentity.FromString(creator)
    cert = x509.load_pem_x509_certificate(sid.id_bytes)
    der, issuer = cert.public_bytes(Encoding.DER), cert.issuer.public_bytes()
    end = der.index(issuer) + len(issuer)
    broken = x509.load_der_x509_certificate(der[:end - 1] + b"\xff" + der[end:])
    try:
        broken.issuer
    except ValueError:
        sid.id_bytes = broken.public_bytes(Encoding.PEM)
        return sid.SerializeToString()
    raise AssertionError("the issuer still decodes")
