"""X.509 identities (reference msp/identities.go).

`Identity.verify` is the single-signature call the reference issues per
endorsement (msp/identities.go:169-196: hash then bccsp.Verify).  The TPU
build adds `verification_item` so callers can *collect* instead of verify —
the whole block's items go to one `CSP.verify_batch` call (SURVEY.md §3.4).
"""

from __future__ import annotations

import datetime
import functools

from cryptography import x509
from cryptography.hazmat.primitives import serialization
from cryptography.x509.oid import NameOID

from fabric_tpu.common.hashing import sha256 as _sha256
from fabric_tpu.csp import api as csp_api
from fabric_tpu.csp.api import ECDSAP256PublicKey, VerifyBatchItem
from fabric_tpu.protos.msp import identities_pb2


_EPOCH = datetime.datetime(1970, 1, 1, tzinfo=datetime.timezone.utc)


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
    """A deserialized, not-necessarily-valid identity bound to its MSP.

    Made from a `cryptography` certificate (`Identity(mspid, cert,
    csp)`), or from the fields the native reader read of one
    (`from_fields`: a crowded block's creators, msp.read_identities).
    Either way it answers the same questions: `MSP.validate` reads
    `issuer_bytes`, `der`, `not_before` / `not_after`, `serial` and
    `ous`, the marshal reads `public_key`, the caches `serialize()`.
    The first kind takes them from the certificate when first asked,
    the second has them in hand and loads the certificate (`cert`)
    when a caller first wants it."""

    # (issuer bytes, the MSP's trusted certificate with that subject,
    # the verdict): this certificate's signature under its one issuer
    # candidate, decided ahead in a block's native batch
    # (`msp.prove_chains`) for the `MSP.validate` that follows, which
    # takes it once; None: validate checks the signature itself
    chain_verdict = None
    # (SHA-256 of the TBS bytes, DER of (r, min(s, n - s))) of a
    # certificate the native reader qualified (ecdsa-with-SHA256, strict
    # DER, r and s in range): what the native verifier takes of it.
    # None: `MSP._chain_item` reads them from the certificate
    chain_signature = None

    def __init__(self, mspid: str, cert: x509.Certificate, csp):
        self.mspid = mspid
        self.cert = cert
        self._csp = csp
        self.public_key = cert_pubkey(cert)
        self.ous = cert_ous(cert)

    @classmethod
    def from_fields(cls, mspid: str, fields, serialized: bytes, csp) -> "Identity":
        """The identity of a certificate `native.x509_read` qualified
        (`fields`, an `X509Fields`), which came as `serialized` and, the
        reader having held both wrappings to their canonical forms,
        serializes to the same bytes."""
        self = cls.__new__(cls)
        self.mspid = mspid
        self._csp = csp
        self._serialized = serialized
        self.public_key = ECDSAP256PublicKey.from_coordinates(
            fields.x_bytes, fields.y_bytes
        )
        self.ous = [ou.decode() for ou in fields.ous]
        self.der = fields.der
        self.issuer_bytes = fields.issuer
        self.serial = int.from_bytes(fields.serial, "big")
        self.not_before = _EPOCH + datetime.timedelta(seconds=fields.not_before)
        self.not_after = _EPOCH + datetime.timedelta(seconds=fields.not_after)
        self.chain_signature = (fields.tbs_digest, fields.low_s_signature)
        return self

    # what follows is in the instance of an identity `from_fields` made,
    # but for `cert`; one made from a certificate reads each when first
    # asked (an issuer Name that does not decode raises here, as ever)

    @functools.cached_property
    def cert(self) -> x509.Certificate:
        return x509.load_der_x509_certificate(self.der)

    @functools.cached_property
    def der(self) -> bytes:
        return self.cert.public_bytes(serialization.Encoding.DER)

    @functools.cached_property
    def issuer_bytes(self) -> bytes:
        return self.cert.issuer.public_bytes()

    @functools.cached_property
    def serial(self) -> int:
        return self.cert.serial_number

    @functools.cached_property
    def not_before(self) -> datetime.datetime:
        return self.cert.not_valid_before_utc

    @functools.cached_property
    def not_after(self) -> datetime.datetime:
        return self.cert.not_valid_after_utc

    @functools.cached_property
    def id(self) -> tuple[str, str]:
        # IdentityIdentifier: (mspid, hash of the raw cert) — reference
        # msp/mspimpl.go getIdentityFromConf.
        return (self.mspid, _sha256(self.der).hex())

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
        return self.not_after

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
