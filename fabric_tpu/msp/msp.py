"""The X.509 membership service provider.

Reference surface: msp/msp.go interfaces, msp/mspimpl.go (Setup :248,
Validate :317, DeserializeIdentity :384, SatisfiesPrincipal :429) with the
setup/validate split of mspimplsetup.go / mspimplvalidate.go.

Differences from the reference are deliberate simplifications recorded
here: chain building walks issuer->subject with signature checks per hop
(cryptography exposes no full RFC 5280 path builder); OU certifier
identifiers compare against the chain's root/intermediate certs' hashes.
"""

from __future__ import annotations

import datetime

from cryptography import x509
from cryptography.hazmat.primitives import serialization
from cryptography.hazmat.primitives.asymmetric import ec

from fabric_tpu.csp import factory as csp_factory
from fabric_tpu.msp.identity import Identity, SigningIdentity
from fabric_tpu.protos.msp import msp_principal_pb2
from fabric_tpu.protos.msp import identities_pb2, msp_config_pb2

FABRIC = 0  # MSPConfig.type for the X.509 provider
IDEMIX = 1


class MSPError(Exception):
    pass


def _load_pem_cert(pem: bytes) -> x509.Certificate:
    certs = x509.load_pem_x509_certificates(pem)
    if len(certs) != 1:
        raise MSPError("expected exactly one certificate in PEM")
    return certs[0]


def _verify_issued(issuer: x509.Certificate, cert: x509.Certificate) -> bool:
    if cert.issuer != issuer.subject:
        return False
    pub = issuer.public_key()
    try:
        pub.verify(
            cert.signature, cert.tbs_certificate_bytes,
            ec.ECDSA(cert.signature_hash_algorithm),
        )
        return True
    except Exception:
        return False


class MSP:
    """One organization's membership rules (an X.509 trust domain)."""

    def __init__(self, mspid: str, csp=None):
        self.mspid = mspid
        self.csp = csp or csp_factory.get_default()
        self.root_certs: list[x509.Certificate] = []
        self.intermediate_certs: list[x509.Certificate] = []
        self.admins: list[bytes] = []  # DER of admin certs
        self.crls: list[x509.CertificateRevocationList] = []
        self.node_ous_enabled = False
        self.ou_roles: dict[str, str] = {}  # OU string -> role name
        self.signer: SigningIdentity | None = None

    # -- setup (reference mspimplsetup.go) --------------------------------

    @classmethod
    def from_config(cls, conf: msp_config_pb2.MSPConfig, csp=None) -> "MSP":
        if conf.type != FABRIC:
            raise MSPError(f"unsupported MSP type {conf.type} for X.509 MSP")
        fconf = msp_config_pb2.FabricMSPConfig.FromString(conf.config)
        msp = cls(fconf.name, csp)
        msp._setup(fconf)
        return msp

    def _setup(self, fconf: msp_config_pb2.FabricMSPConfig) -> None:
        if not fconf.root_certs:
            raise MSPError("expected at least one CA certificate")
        self.root_certs = [_load_pem_cert(c) for c in fconf.root_certs]
        self.intermediate_certs = [
            _load_pem_cert(c) for c in fconf.intermediate_certs
        ]
        self.admins = [
            _load_pem_cert(c).public_bytes(serialization.Encoding.DER)
            for c in fconf.admins
        ]
        self.crls = [x509.load_pem_x509_crl(c) for c in fconf.revocation_list]
        if fconf.HasField("fabric_node_ous") and fconf.fabric_node_ous.enable:
            self.node_ous_enabled = True
            nou = fconf.fabric_node_ous
            for role, ident in (
                ("client", nou.client_ou_identifier),
                ("peer", nou.peer_ou_identifier),
                ("admin", nou.admin_ou_identifier),
                ("orderer", nou.orderer_ou_identifier),
            ):
                if ident.organizational_unit_identifier:
                    self.ou_roles[ident.organizational_unit_identifier] = role
        if fconf.HasField("signing_identity") and fconf.signing_identity.public_signer:
            cert = _load_pem_cert(fconf.signing_identity.public_signer)
            key_pem = fconf.signing_identity.private_signer.key_material
            from fabric_tpu.csp.api import ECDSAP256PrivateKey

            key = ECDSAP256PrivateKey.from_pem(key_pem)
            self.signer = SigningIdentity(self.mspid, cert, key, self.csp)

    # -- identity plumbing -------------------------------------------------

    def deserialize_identity(self, serialized: bytes) -> Identity:
        sid = identities_pb2.SerializedIdentity.FromString(serialized)
        if sid.mspid != self.mspid:
            raise MSPError(f"expected MSP ID {self.mspid}, got {sid.mspid}")
        cert = _load_pem_cert(sid.id_bytes)
        return Identity(self.mspid, cert, self.csp)

    def get_default_signing_identity(self) -> SigningIdentity:
        if self.signer is None:
            raise MSPError(f"MSP {self.mspid} has no signing identity")
        return self.signer

    # -- validation (reference mspimplvalidate.go) ------------------------

    def _chain(self, cert: x509.Certificate) -> list[x509.Certificate]:
        """Build [leaf, intermediates..., root]; raises if no trusted path."""
        by_subject: dict[bytes, list[x509.Certificate]] = {}
        for c in self.intermediate_certs:
            by_subject.setdefault(c.subject.public_bytes(), []).append(c)
        roots_by_subject: dict[bytes, list[x509.Certificate]] = {}
        for c in self.root_certs:
            roots_by_subject.setdefault(c.subject.public_bytes(), []).append(c)

        chain = [cert]
        current = cert
        for _ in range(10):  # path length bound
            issuer_key = current.issuer.public_bytes()
            for root in roots_by_subject.get(issuer_key, []):
                if _verify_issued(root, current):
                    chain.append(root)
                    return chain
            advanced = False
            for inter in by_subject.get(issuer_key, []):
                if inter in chain:
                    continue
                if _verify_issued(inter, current):
                    chain.append(inter)
                    current = inter
                    advanced = True
                    break
            if not advanced:
                break
        raise MSPError("could not build certification chain to a trusted root")

    def validate(self, identity: Identity) -> None:
        """Raises MSPError when invalid: untrusted chain, expired, revoked,
        or (with NodeOUs) not classifiable into exactly one role."""
        chain = self._chain(identity.cert)
        now = datetime.datetime.now(datetime.timezone.utc)
        for c in chain:
            if now < c.not_valid_before_utc or now > c.not_valid_after_utc:
                raise MSPError("certificate outside its validity period")
        # CRL check: any cert of the chain revoked by a CRL signed by its
        # issuer invalidates the identity (reference validateCertAgainstChain)
        for crl in self.crls:
            for c in chain[:-1]:
                entry = crl.get_revoked_certificate_by_serial_number(c.serial_number)
                if entry is not None:
                    raise MSPError("certificate has been revoked")
        if self.node_ous_enabled:
            roles = {self.ou_roles[ou] for ou in identity.ous if ou in self.ou_roles}
            if len(roles) != 1:
                raise MSPError(
                    "NodeOUs enabled: identity must carry exactly one of the "
                    f"role OUs, found {sorted(roles)}"
                )

    def is_valid(self, identity: Identity) -> bool:
        try:
            self.validate(identity)
            return True
        except MSPError:
            return False

    def _role_of(self, identity: Identity) -> str | None:
        roles = {self.ou_roles[ou] for ou in identity.ous if ou in self.ou_roles}
        return next(iter(roles)) if len(roles) == 1 else None

    def _is_admin(self, identity: Identity) -> bool:
        der = identity.cert.public_bytes(serialization.Encoding.DER)
        if der in self.admins:
            return True
        return self.node_ous_enabled and self._role_of(identity) == "admin"

    # -- principals (reference mspimpl.go:429 satisfiesPrincipalInternal) --

    def satisfies_principal(
        self, identity: Identity, principal: msp_principal_pb2.MSPPrincipal
    ) -> None:
        """Raises MSPError when the identity does NOT satisfy the principal."""
        cls = principal.principal_classification
        P = msp_principal_pb2.MSPPrincipal
        if cls == P.ROLE:
            role = msp_principal_pb2.MSPRole.FromString(principal.principal)
            if role.msp_identifier != self.mspid:
                raise MSPError(
                    f"principal is for MSP {role.msp_identifier}, identity is {self.mspid}"
                )
            self.validate(identity)
            R = msp_principal_pb2.MSPRole
            if role.role == R.MEMBER:
                return
            if role.role == R.ADMIN:
                if self._is_admin(identity):
                    return
                raise MSPError("identity is not an admin")
            if role.role in (R.CLIENT, R.PEER, R.ORDERER):
                want = {R.CLIENT: "client", R.PEER: "peer", R.ORDERER: "orderer"}[role.role]
                if self.node_ous_enabled and self._role_of(identity) == want:
                    return
                raise MSPError(f"identity is not a {want}")
            raise MSPError(f"invalid MSP role type {role.role}")
        if cls == P.IDENTITY:
            if principal.principal == identity.serialize():
                return
            raise MSPError("identity does not match IDENTITY principal")
        if cls == P.ORGANIZATION_UNIT:
            ou = msp_principal_pb2.OrganizationUnit.FromString(principal.principal)
            if ou.msp_identifier != self.mspid:
                raise MSPError("OU principal is for a different MSP")
            self.validate(identity)
            if ou.organizational_unit_identifier in identity.ous:
                return
            raise MSPError("identity lacks the required OU")
        if cls == P.ANONYMITY:
            anon = msp_principal_pb2.MSPIdentityAnonymity.FromString(principal.principal)
            if anon.anonymity_type == msp_principal_pb2.MSPIdentityAnonymity.NOMINAL:
                return
            raise MSPError("X.509 identities cannot be anonymous")
        if cls == P.COMBINED:
            comb = msp_principal_pb2.CombinedPrincipal.FromString(principal.principal)
            if not comb.principals:
                raise MSPError("empty combined principal")
            for sub in comb.principals:
                self.satisfies_principal(identity, sub)
            return
        raise MSPError(f"unknown principal classification {cls}")


def msp_config_name(conf: msp_config_pb2.MSPConfig) -> str:
    """The MSP id an MSPConfig of either type carries."""
    if conf.type == IDEMIX:
        return msp_config_pb2.IdemixMSPConfig.FromString(conf.config).name
    return msp_config_pb2.FabricMSPConfig.FromString(conf.config).name


def msp_from_config(conf: msp_config_pb2.MSPConfig, csp=None):
    """The MSP of a channel organisation, by `MSPConfig.type`: X.509
    (FABRIC) or Idemix (reference msp/factory.go New + Setup)."""
    if conf.type == IDEMIX:
        from fabric_tpu.msp.idemixmsp import IdemixMSP

        return IdemixMSP.from_config(conf, csp)
    return MSP.from_config(conf, csp)


class MSPManager:
    """Per-channel MSP set: routes deserialization by mspid (reference
    msp/mspmgrimpl.go)."""

    def __init__(self, msps: list[MSP] | None = None):
        self._msps: dict[str, MSP] = {}
        for m in msps or []:
            self._msps[m.mspid] = m

    def add(self, msp: MSP) -> None:
        self._msps[msp.mspid] = msp

    def get_msp(self, mspid: str) -> MSP:
        try:
            return self._msps[mspid]
        except KeyError:
            raise MSPError(f"MSP {mspid} is unknown") from None

    def msps(self) -> list[MSP]:
        return list(self._msps.values())

    def deserialize_identity(self, serialized: bytes) -> Identity:
        sid = identities_pb2.SerializedIdentity.FromString(serialized)
        return self.get_msp(sid.mspid).deserialize_identity(serialized)

    def deserialize_deferred(self, serialized: bytes):
        """An identity of an MSP that can leave its expensive proof
        owing (`IdemixMSP.deserialize_deferred`), or None: the identity
        belongs to an MSP that cannot (X.509), and the caller
        deserializes and validates it as ever."""
        if not any(
            hasattr(m, "deserialize_deferred") for m in self._msps.values()
        ):
            return None
        sid = identities_pb2.SerializedIdentity.FromString(serialized)
        deferred = getattr(
            self.get_msp(sid.mspid), "deserialize_deferred", None
        )
        return None if deferred is None else deferred(serialized)

    def satisfies_principal(self, identity, principal) -> None:
        self.get_msp(identity.mspid).satisfies_principal(identity, principal)

    def validate(self, identity) -> None:
        self.get_msp(identity.mspid).validate(identity)


__all__ = [
    "MSP", "MSPManager", "MSPError", "FABRIC", "IDEMIX",
    "msp_config_name", "msp_from_config",
]
