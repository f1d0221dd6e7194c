"""X.509 identities (reference msp/identities.go).

`Identity.verify` is the single-signature call the reference issues per
endorsement (msp/identities.go:169-196: hash then bccsp.Verify).  The TPU
build adds `verification_item` so callers can *collect* instead of verify —
the whole block's items go to one `CSP.verify_batch` call (SURVEY.md §3.4).
"""

from __future__ import annotations

import functools

from cryptography import x509
from cryptography.hazmat.primitives import serialization
from cryptography.x509.oid import NameOID

from fabric_tpu.common.hashing import sha256 as _sha256
from fabric_tpu.csp import api as csp_api
from fabric_tpu.csp.api import ECDSAP256PublicKey, VerifyBatchItem
from fabric_tpu.protos.msp import identities_pb2


def cert_pubkey(cert: x509.Certificate) -> ECDSAP256PublicKey:
    """The certificate's key as the provider's marshal reads it, taken
    from the certificate once: no export to DER and parse back."""
    return ECDSAP256PublicKey(cert.public_key())


def cert_ous(cert: x509.Certificate) -> list[str]:
    return [
        a.value
        for a in cert.subject.get_attributes_for_oid(NameOID.ORGANIZATIONAL_UNIT_NAME)
    ]


class Identity:
    """A deserialized, not-necessarily-valid identity bound to its MSP."""

    # (issuer bytes, the MSP's trusted certificate with that subject,
    # the verdict): this certificate's signature under its one issuer
    # candidate, decided ahead in a block's native batch
    # (`msp.prove_chains`) for the `MSP.validate` that follows, which
    # takes it once; None: validate checks the signature itself
    chain_verdict = None

    def __init__(self, mspid: str, cert: x509.Certificate, csp):
        self.mspid = mspid
        self.cert = cert
        self._csp = csp
        self.public_key = cert_pubkey(cert)
        self.ous = cert_ous(cert)

    @functools.cached_property
    def id(self) -> tuple[str, str]:
        # IdentityIdentifier: (mspid, hash of the raw cert) — reference
        # msp/mspimpl.go getIdentityFromConf.
        der = self.cert.public_bytes(serialization.Encoding.DER)
        return (self.mspid, _sha256(der).hex())

    def serialize(self) -> bytes:
        # memoized: the hot path (policy evaluation, cache keys) calls
        # this per endorsement and certs are immutable
        cached = getattr(self, "_serialized", None)
        if cached is None:
            cached = identities_pb2.SerializedIdentity(
                mspid=self.mspid,
                id_bytes=self.cert.public_bytes(serialization.Encoding.PEM),
            ).SerializeToString()
            self._serialized = cached
        return cached

    def expires_at(self):
        return self.cert.not_valid_after_utc

    # -- verification ------------------------------------------------------

    def verify(self, msg: bytes, sig: bytes) -> bool:
        """Hash + verify (single call; hot paths use verification_item)."""
        return self._csp.verify(self.public_key, sig, self._csp.hash(msg))

    def verification_item(self, msg: bytes, sig: bytes) -> VerifyBatchItem:
        """Deferred-verification triple for CSP.verify_batch."""
        return VerifyBatchItem(self.public_key, _sha256(msg), sig)


class SigningIdentity(Identity):
    def __init__(self, mspid: str, cert: x509.Certificate, private_key, csp):
        super().__init__(mspid, cert, csp)
        self._key = private_key  # csp_api.ECDSAP256PrivateKey

    def sign(self, msg: bytes) -> bytes:
        return self._csp.sign(self._key, self._csp.hash(msg))

    @classmethod
    def from_pem(cls, mspid: str, cert_pem: bytes, key_pem: bytes, csp):
        cert = x509.load_pem_x509_certificates(cert_pem)[0]
        key = csp_api.ECDSAP256PrivateKey.from_pem(key_pem)
        return cls(mspid, cert, key, csp)


__all__ = ["Identity", "SigningIdentity", "cert_pubkey", "cert_ous"]
