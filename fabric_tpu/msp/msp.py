"""The X.509 membership service provider.

Reference surface: msp/msp.go interfaces, msp/mspimpl.go (Setup :248,
Validate :317, DeserializeIdentity :384, SatisfiesPrincipal :429) with the
setup/validate split of mspimplsetup.go / mspimplvalidate.go.

Differences from the reference are deliberate simplifications recorded
here: chain building walks issuer->subject with signature checks per hop
(cryptography exposes no full RFC 5280 path builder); OU certifier
identifiers compare against the chain's root/intermediate certs' hashes.

What chain building asks of the MSP's own certificates is the same for
every identity and is prepared once, in `_setup` (`_Trusted`, the index
by subject bytes).  The signature of an identity's certificate under
its issuer is checked either here, one OpenSSL call through the Python
wrapper, or ahead of `validate` for many identities in one native call
that holds no interpreter lock (`prove_chains`): the verdict is the
same, and `validate` is the one place that accepts or refuses.  The
certificates of a crowded block's creators are read the same way, in
one native call beside that one (`read_identities`): the reader
qualifies a certificate or hands it back to `deserialize_identity`, and
what it qualified reaches `validate` as fields in place of a
`cryptography` object.
"""

from __future__ import annotations

import datetime

from cryptography import x509
from cryptography.hazmat.primitives import serialization
from cryptography.hazmat.primitives.asymmetric import ec
from cryptography.x509.oid import SignatureAlgorithmOID

from fabric_tpu.common.hashing import sha256 as _sha256
from fabric_tpu.csp import factory as csp_factory
from fabric_tpu.csp.api import (
    P256_N,
    ECDSAP256PublicKey,
    VerifyBatchItem,
    is_low_s,
    marshal_ecdsa_signature,
    unmarshal_ecdsa_signature,
)
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


# under this many chain signatures the native call's fixed cost (every
# issuer key set up anew a call, ~0.15 ms; the arrays) outweighs what
# it saves a signature, so OpenSSL checks in place: on the chip's host
# a call of 1 costs 207 us, of 4 106 each, of 16 80, level with the
# wrapper's 78 (PERF.md, PR 33).  What the threshold is worth: a block
# with one creator, as five of the benchmark's six cells have, pays
# 0.08 ms for its chain signature and not 0.21
_NATIVE_BATCH_MIN = 16


class _Trusted:
    """A root or intermediate certificate of an MSP with what chain
    building reads of it an identity, taken from it once: its key, as
    the OpenSSL object for the single check and as the coordinates the
    native verifier takes (None: not P-256), its validity window, its
    serial number, its DER (an identity that IS this certificate does
    not chain through it), and for an intermediate its own way up to a
    root."""

    __slots__ = ("cert", "der", "root", "public_key", "p256", "not_before",
                 "not_after", "serial", "path")

    def __init__(self, cert: x509.Certificate, root: bool):
        self.cert = cert
        self.der = cert.public_bytes(serialization.Encoding.DER)
        self.root = root
        try:
            self.public_key = cert.public_key()
        except Exception:
            self.public_key = None  # a key OpenSSL cannot load signs nothing
        try:
            self.p256 = ECDSAP256PublicKey(self.public_key)
        except Exception:
            self.p256 = None
        self.not_before = cert.not_valid_before_utc
        self.not_after = cert.not_valid_after_utc
        self.serial = cert.serial_number
        # [self, ..., root], () where no trusted way leads up from
        # here, None until an identity first asks (`MSP._path`)
        self.path: list | tuple | None = None


# common.metrics.MSPMetrics.chain_signatures or None, process-wide:
# `msp.cache.set_metrics` binds it with the caches' counters
chain_signatures = None


def _count_chain_signatures(path: str, n: int = 1) -> None:
    counter = chain_signatures
    if counter is not None:
        counter.With("path", path).add(n)


def _signed_by(ca: _Trusted, cert: x509.Certificate) -> bool:
    """One chain signature, by OpenSSL through the Python wrapper.
    The caller found `ca` by the certificate's issuer bytes."""
    _count_chain_signatures("single")
    try:
        ca.public_key.verify(
            cert.signature, cert.tbs_certificate_bytes,
            ec.ECDSA(cert.signature_hash_algorithm),
        )
        return True
    except Exception:
        return False


def prove_chains(pairs) -> int:
    """For (MSP, identity) pairs about to be validated: the chain
    signature of every identity that qualifies (`MSP._chain_item`),
    all in ONE `native.ecdsa_verify_host` call, which holds no
    interpreter lock while it runs; each verdict is left on its
    identity for the `MSP.validate` that follows.  Returns how many
    the call decided: 0 where fewer than `_NATIVE_BATCH_MIN` qualify
    or the native library or libcrypto is missing, and `validate`
    then checks each signature itself, as it does for every identity
    that did not qualify."""
    owing = []
    for msp, identity in pairs:
        try:
            item = msp._chain_item(identity)
        except Exception:
            # a field of the certificate that does not decode (they are
            # parsed when first read): `validate` meets it again, in
            # its caller's guard, and refuses this identity alone
            item = None
        if item is not None:
            owing.append((identity, item))
    if len(owing) < _NATIVE_BATCH_MIN:
        return 0
    from fabric_tpu import native

    try:
        mask = native.ecdsa_verify_host([item[2] for _, item in owing])
    except Exception:
        mask = None  # nothing decided: each validate checks its own
    if mask is None:
        return 0
    for (identity, (issuer, ca, _)), ok in zip(owing, mask):
        identity.chain_verdict = (issuer, ca, ok)
    _count_chain_signatures("batch", len(owing))
    return len(owing)


def read_identities(msps: dict, serialized) -> list:
    """For each serialized identity of a crowded block (at least
    `_NATIVE_BATCH_MIN` of them, as for the chain batch) the `Identity`
    of its MSP in `msps` (MSP id -> MSP), built from the fields ONE
    `native.x509_read` call read of them all without the interpreter's
    lock; or None: the reader handed the certificate back (another
    curve, algorithm or encoding: native/x509.cc has the list), its MSP
    is not an X.509 one of these, the block is small, or the native
    library or libcrypto is missing.  The caller takes a None through
    `deserialize_identity`, which accepts or refuses it as ever.
    Nothing is decided here: a qualified certificate is one that door
    would have read to the same fields."""
    idents: list = [None] * len(serialized)
    if len(serialized) < _NATIVE_BATCH_MIN:
        return idents
    from fabric_tpu import native

    try:
        read = native.x509_read(serialized, wrapped=True)
    except Exception:
        read = None  # nothing read: each goes through its own door
    for i, fields in enumerate(read or ()):
        if isinstance(fields, int):
            continue  # handed back: the status says by which rule
        mspid = fields.mspid.decode()  # printable ASCII, by the reader's rule
        msp = msps.get(mspid)
        if isinstance(msp, MSP):
            idents[i] = Identity.from_fields(
                mspid, fields, bytes(serialized[i]), msp.csp
            )
    return idents


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
        # subject bytes -> the roots, then the intermediates, with that
        # subject (`_setup`; a config update builds new MSPs)
        self._issuers: dict[bytes, list[_Trusted]] = {}

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
        self._issuers = {}
        for certs, root in (
            (self.root_certs, True), (self.intermediate_certs, False),
        ):
            for c in certs:
                self._issuers.setdefault(
                    c.subject.public_bytes(), []
                ).append(_Trusted(c, root))
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

    def read_identities(self, serialized) -> list:
        return read_identities({self.mspid: self}, serialized)

    def get_default_signing_identity(self) -> SigningIdentity:
        if self.signer is None:
            raise MSPError(f"MSP {self.mspid} has no signing identity")
        return self.signer

    # -- validation (reference mspimplvalidate.go) ------------------------

    def _path(self, ca: _Trusted) -> list | tuple:
        """[ca, ..., root] for an intermediate: its own signature under
        its issuer and every hop above, checked once an MSP and then
        known; () where no trusted way leads up."""
        if ca.path is None:
            path, current = [ca], ca
            for _ in range(9):  # path length bound: ten hops with the leaf's
                advanced = False
                for up in self._issuers.get(current.cert.issuer.public_bytes(), ()):
                    if not up.root and any(up.cert == c.cert for c in path):
                        continue
                    if _signed_by(up, current.cert):
                        path.append(up)
                        current, advanced = up, True
                        break
                if not advanced or current.root:
                    break
            ca.path = path if current.root else ()
        return ca.path

    def _chain(self, identity: Identity) -> list[_Trusted]:
        """The trusted certificates above the identity's, [intermediates
        ..., root]; raises if no trusted path.  The issuer is found by
        the raw issuer bytes, roots before intermediates, as the
        reference compares RawIssuer with RawSubject."""
        ahead = identity.chain_verdict
        if ahead is None:
            issuer = identity.issuer_bytes
        else:
            issuer, identity.chain_verdict = ahead[0], None
        for ca in self._issuers.get(issuer, ()):
            if not ca.root and ca.der == identity.der:
                continue
            if ahead is not None and ahead[1] is ca:
                signed = ahead[2]
            else:
                signed = _signed_by(ca, identity.cert)
            if not signed:
                continue
            if ca.root:
                return [ca]
            path = self._path(ca)
            if path:
                return path
            break
        raise MSPError("could not build certification chain to a trusted root")

    def _chain_item(self, identity: Identity):
        """(issuer bytes, the issuer, the native verifier's item) where
        this identity's chain signature is one the native batch decides
        exactly as `_signed_by` would: one issuer candidate, holding a
        P-256 key, ECDSA with SHA-256, (r, s) in range and in strict
        DER.  None otherwise, and `_chain` checks it in place.  A
        certificate's signature is valid with either S (Go's
        x509.CheckSignature and OpenSSL accept both) while the native
        verifier holds every signature to Fabric's low-S rule for
        transactions, so it is handed (r, min(s, n - s)), which
        verifies exactly when (r, s) does."""
        issuer = identity.issuer_bytes
        candidates = self._issuers.get(issuer, ())
        if len(candidates) != 1:
            return None
        ca = candidates[0]
        if ca.p256 is None or (not ca.root and ca.der == identity.der):
            return None
        laid_out = identity.chain_signature
        if laid_out is not None:
            # the native reader qualified this certificate by the rules
            # below and laid (digest, (r, min(s, n - s))) out
            return issuer, ca, VerifyBatchItem(ca.p256, *laid_out)
        cert = identity.cert
        if cert.signature_algorithm_oid != SignatureAlgorithmOID.ECDSA_WITH_SHA256:
            return None
        sig = cert.signature
        try:
            r, s = unmarshal_ecdsa_signature(sig)  # strict DER
        except ValueError:
            return None
        if r >= P256_N or s >= P256_N:
            return None
        if not is_low_s(s):
            sig = marshal_ecdsa_signature(r, P256_N - s)
        return issuer, ca, VerifyBatchItem(
            ca.p256, _sha256(cert.tbs_certificate_bytes), sig
        )

    def prove_chains(self, identities) -> int:
        return prove_chains([(self, i) for i in identities])

    def validate(self, identity: Identity) -> None:
        """Raises MSPError when invalid: untrusted chain, expired, revoked,
        or (with NodeOUs) not classifiable into exactly one role."""
        chain = self._chain(identity)
        now = datetime.datetime.now(datetime.timezone.utc)
        if now < identity.not_before or now > identity.not_after:
            raise MSPError("certificate outside its validity period")
        for ca in chain:
            if now < ca.not_before or now > ca.not_after:
                raise MSPError("certificate outside its validity period")
        # CRL check: any cert of the chain revoked by a CRL signed by its
        # issuer invalidates the identity (reference validateCertAgainstChain)
        if self.crls:
            serials = [identity.serial] + [ca.serial for ca in chain[:-1]]
            for crl in self.crls:
                for serial in serials:
                    if crl.get_revoked_certificate_by_serial_number(serial) is not None:
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
        if identity.der in self.admins:
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

    def read_identities(self, serialized) -> list:
        """A crowded block's identities of any of the channel's X.509
        MSPs, their certificates read in one native call; None for
        each that goes through `deserialize_identity` as ever."""
        return read_identities(self._msps, serialized)

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

    def prove_chains(self, identities) -> int:
        """The chain signatures of identities of any of the channel's
        MSPs, ahead of their `validate`, in one native call."""
        pairs = [(self._msps.get(i.mspid), i) for i in identities]
        return prove_chains([p for p in pairs if isinstance(p[0], MSP)])


__all__ = [
    "MSP", "MSPManager", "MSPError", "FABRIC", "IDEMIX",
    "msp_config_name", "msp_from_config",
]
