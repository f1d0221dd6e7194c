"""Idemix MSP provider (reference msp/idemixmsp.go, msp/idemix_roles.go).

An MSP whose identities are anonymous credentials instead of X.509 certs.
A serialized idemix identity (`SerializedIdemixIdentity`, wire-compatible
with the reference: msp/idemixmsp.go DeserializeIdentity) carries:

    nym_x/nym_y — the pseudonym (fresh per identity)
    ou          — disclosed organizational unit
    role        — disclosed role (MEMBER/ADMIN encoded as in idemix_roles.go)
    proof       — an idemix presentation Signature disclosing exactly
                  (OU, Role) and binding the nym to the hidden sk

Per-message signing then uses nym signatures against the same pseudonym.

The attribute layout matches the reference's 4-attribute convention
(msp/idemixmsp.go:  AttributeIndexOU=0, AttributeIndexRole=1,
AttributeIndexEnrollmentId=2, AttributeIndexRevocationHandle=3).
"""

from __future__ import annotations

import dataclasses

from fabric_tpu.csp.idemix_provider import (
    IdemixNymItem,
    IdemixVerifyItem,
    for_csp,
)
from fabric_tpu.idemix import bn254 as bn
from fabric_tpu.idemix import nymsignature, revocation as idemix_revocation
from fabric_tpu.idemix import signature as idemix_signature
from fabric_tpu.idemix.credential import (
    Credential,
    attribute_to_scalar,
    new_cred_request,
    new_credential,
)
from fabric_tpu.idemix.issuer import IssuerKey, IssuerPublicKey
from fabric_tpu.protos.msp import identities_pb2, msp_config_pb2
from fabric_tpu.protos.msp import msp_principal_pb2

ATTR_OU = 0
ATTR_ROLE = 1
ATTR_ENROLLMENT_ID = 2
ATTR_REVOCATION_HANDLE = 3
ATTR_NAMES = ["OU", "Role", "EnrollmentID", "RevocationHandle"]

ROLE_MEMBER = 1
ROLE_ADMIN = 2

IDEMIX = 1  # ProviderType (reference msp/msp.go ProviderType IDEMIX)


class IdemixMSPError(Exception):
    pass


def _parse_nym_signature(sig: bytes) -> nymsignature.NymSignature | None:
    """The wire form `IdemixSigningIdentity.sign` writes; None for
    anything else."""
    import json

    try:
        d = json.loads(sig)
        return nymsignature.NymSignature(
            challenge=int(d["c"]),
            z_sk=int(d["z_sk"]),
            z_rnym=int(d["z_rnym"]),
        )
    except (ValueError, KeyError, TypeError):
        return None


@dataclasses.dataclass
class IdemixIdentity:
    """A deserialized anonymous identity.  From `deserialize_identity`
    it is verified; from `deserialize_deferred` its credential proof is
    still owed (`proof_deferred`), and `deferred_items` hands it over
    with the pseudonym signature to be verified in a batch."""

    mspid: str
    nym: tuple
    ou: str
    role: int
    proof: idemix_signature.Signature
    _serialized: bytes = b""
    proof_deferred: bool = False
    msp: object | None = None      # the IdemixMSP that deserialized it

    # single-use: a fresh pseudonym a transaction, so caches keep out
    anonymous = True

    def serialize(self) -> bytes:
        return self._serialized

    def deferred_items(self, msg: bytes, sig: bytes) -> tuple:
        """(credential-proof item, pseudonym-signature item over `msg`)
        for `IdemixMSP.verify_items_async`: what an X.509 creator's
        `verification_item` is to the ECDSA batch.  The creator's
        signature stands when both verify."""
        return (
            IdemixVerifyItem(self.proof, b""),
            IdemixNymItem(_parse_nym_signature(sig), self.nym, msg),
        )

    def get_identifier(self) -> str:
        import hashlib

        # fabriclint: allow[csp-seam] pseudonym fingerprint over a BN254
        # G1 point — idemix credential domain, not the P-256 seam
        return hashlib.sha256(bn.g1_to_bytes(self.nym)).hexdigest()

    @property
    def is_admin(self) -> bool:
        return self.role == ROLE_ADMIN


class IdemixSigningIdentity(IdemixIdentity):
    """Holds the user secret + credential; signs with nym signatures."""

    def __init__(
        self,
        mspid: str,
        sk: int,
        cred: Credential,
        ipk: IssuerPublicKey,
        ou: str,
        role: int,
        rng=None,
    ):
        nym, r_nym = idemix_signature.make_nym(sk, ipk, rng)
        proof = idemix_signature.new_signature(
            cred,
            sk,
            ipk,
            msg=b"",
            disclosure=[True, True, False, False],
            nym=nym,
            r_nym=r_nym,
            rng=rng,
        )
        serialized = identities_pb2.SerializedIdentity(
            mspid=mspid,
            id_bytes=identities_pb2.SerializedIdemixIdentity(
                nym_x=nym[0].to_bytes(32, "big"),
                nym_y=nym[1].to_bytes(32, "big"),
                ou=ou.encode(),
                role=role.to_bytes(4, "big"),
                proof=proof.to_bytes(),
            ).SerializeToString(),
        ).SerializeToString()
        super().__init__(
            mspid=mspid, nym=nym, ou=ou, role=role, proof=proof,
            _serialized=serialized,
        )
        self._sk = sk
        self._r_nym = r_nym
        self._ipk = ipk
        self._rng = rng

    def sign(self, msg: bytes) -> bytes:
        sig = nymsignature.new_nym_signature(
            self._sk, self.nym, self._r_nym, self._ipk, msg, rng=self._rng
        )
        import json

        return json.dumps(
            {"c": sig.challenge, "z_sk": sig.z_sk, "z_rnym": sig.z_rnym}
        ).encode()


class IdemixMSP:
    """MSP interface over idemix credentials (reference msp/idemixmsp.go
    Setup/DeserializeIdentity/Validate/SatisfiesPrincipal)."""

    provider_type = IDEMIX

    def __init__(self, mspid: str, ipk: IssuerPublicKey,
                 revocation_pk=None, epoch: int = 0, csp=None):
        """`csp` is the node's CSP: batched verification goes through
        the Idemix provider beside it (`idemix_provider.for_csp`: a
        `TPUCSP`'s own, else a host-only one)."""
        ipk.check()
        if ipk.attr_names != ATTR_NAMES:
            raise IdemixMSPError(
                f"issuer key must carry attributes {ATTR_NAMES}"
            )
        self.mspid = mspid
        self.ipk = ipk
        self.revocation_pk = revocation_pk
        self.epoch = epoch
        self._idemix = for_csp(csp)
        self._signer: IdemixSigningIdentity | None = None

    # -- config -------------------------------------------------------------

    @classmethod
    def from_config(cls, conf: msp_config_pb2.MSPConfig,
                    csp=None) -> "IdemixMSP":
        if conf.type != IDEMIX:
            raise IdemixMSPError("not an idemix MSP config")
        ic = msp_config_pb2.IdemixMSPConfig.FromString(conf.config)
        ipk = IssuerPublicKey.from_dict(__import__("json").loads(ic.ipk))
        msp = cls(ic.name, ipk, epoch=ic.epoch, csp=csp)
        if ic.signer:
            sc = msp_config_pb2.IdemixMSPSignerConfig.FromString(ic.signer)
            msp._signer = IdemixSigningIdentity(
                ic.name,
                int.from_bytes(sc.sk, "big"),
                Credential.from_bytes(sc.cred),
                ipk,
                sc.organizational_unit_identifier,
                sc.role,
            )
        return msp

    def get_default_signing_identity(self) -> IdemixSigningIdentity:
        if self._signer is None:
            raise IdemixMSPError("no signing identity configured")
        return self._signer

    # -- identity lifecycle -------------------------------------------------

    def deserialize_identity(self, serialized: bytes,
                             defer: bool = False) -> IdemixIdentity:
        sid = identities_pb2.SerializedIdentity.FromString(serialized)
        if sid.mspid != self.mspid:
            raise IdemixMSPError(
                f"expected MSP ID {self.mspid}, got {sid.mspid}"
            )
        return self._deserialize_inner(sid.id_bytes, serialized, defer)

    def deserialize_deferred(self, serialized: bytes) -> IdemixIdentity:
        """`deserialize_identity` with the credential proof left owing:
        the cheap, exact checks run now (wire shape, points on the
        curve, disclosure [OU, Role], claimed OU and role equal to the
        disclosed attributes, proof bound to the pseudonym), the Schnorr
        recomputation and the pairings wait for a batch.  For the block
        validator, whose every creator is new; whoever takes the
        identity owes `deferred_items` to `verify_items_async`."""
        return self.deserialize_identity(serialized, defer=True)

    def verify_items_async(self, items):
        """One asynchronous batched verify of `deferred_items` against
        this MSP's issuer key: the collector of
        `IdemixCSP.verify_batch_async`."""
        return self._idemix.verify_batch_async(items, self.ipk)

    def _deserialize_inner(
        self, id_bytes: bytes, serialized: bytes, defer: bool = False
    ) -> IdemixIdentity:
        sii = identities_pb2.SerializedIdemixIdentity.FromString(id_bytes)
        try:
            nym = (
                int.from_bytes(sii.nym_x, "big"),
                int.from_bytes(sii.nym_y, "big"),
            )
            proof = idemix_signature.Signature.from_bytes(sii.proof)
        except Exception as exc:  # wire bytes are untrusted: any shape error
            raise IdemixMSPError(f"malformed idemix identity: {exc}") from exc
        if not bn.g1_is_on_curve(nym):
            raise IdemixMSPError("idemix identity: nym not on curve")
        ou = sii.ou.decode()
        role = int.from_bytes(sii.role, "big")
        # The proof must disclose exactly OU and Role, match the claimed
        # values, and bind the nym (reference idemixmsp.go Validate).
        if proof.disclosure != [True, True, False, False]:
            raise IdemixMSPError("idemix identity: wrong disclosure")
        if proof.nym != nym:
            raise IdemixMSPError("idemix identity: proof not bound to nym")
        if proof.disclosed_attrs.get(ATTR_OU) != attribute_to_scalar(ou):
            raise IdemixMSPError("idemix identity: OU mismatch")
        if proof.disclosed_attrs.get(ATTR_ROLE) != attribute_to_scalar(role):
            raise IdemixMSPError("idemix identity: role mismatch")
        if not defer and not idemix_signature.verify(proof, self.ipk, b""):
            raise IdemixMSPError("idemix identity: credential proof invalid")
        return IdemixIdentity(
            mspid=self.mspid, nym=nym, ou=ou, role=role, proof=proof,
            _serialized=serialized, proof_deferred=defer, msp=self,
        )

    def validate(self, identity: IdemixIdentity) -> None:
        if identity.mspid != self.mspid:
            raise IdemixMSPError("identity from a different MSP")
        # deserialize_identity already verified the proof.

    # -- verification -------------------------------------------------------

    def verify(self, identity: IdemixIdentity, msg: bytes, sig: bytes) -> bool:
        nsig = _parse_nym_signature(sig)
        if nsig is None:
            return False
        return nymsignature.verify_nym(nsig, identity.nym, self.ipk, msg)

    def satisfies_principal(self, identity: IdemixIdentity, principal) -> None:
        """Reference idemixmsp.go SatisfiesPrincipal: ROLE (member/admin),
        ORGANIZATION_UNIT, IDENTITY-by-bytes."""
        pc = msp_principal_pb2.MSPPrincipal.Classification
        if principal.principal_classification == pc.ROLE:
            role = msp_principal_pb2.MSPRole.FromString(principal.principal)
            if role.msp_identifier != self.mspid:
                raise IdemixMSPError("role principal for a different MSP")
            if role.role == msp_principal_pb2.MSPRole.MEMBER:
                return
            if role.role == msp_principal_pb2.MSPRole.ADMIN:
                if not identity.is_admin:
                    raise IdemixMSPError("identity is not an admin")
                return
            raise IdemixMSPError(f"unsupported idemix role {role.role}")
        if principal.principal_classification == pc.ORGANIZATION_UNIT:
            ou = msp_principal_pb2.OrganizationUnit.FromString(
                principal.principal
            )
            if ou.msp_identifier != self.mspid:
                raise IdemixMSPError("OU principal for a different MSP")
            if ou.organizational_unit_identifier != identity.ou:
                raise IdemixMSPError("OU mismatch")
            return
        if principal.principal_classification == pc.IDENTITY:
            if bytes(principal.principal) != identity.serialize():
                raise IdemixMSPError("identity bytes mismatch")
            return
        raise IdemixMSPError(
            f"unsupported principal class {principal.principal_classification}"
        )


# ---------------------------------------------------------------------------
# Config generation (the idemixgen surface, reference cmd/idemixgen)
# ---------------------------------------------------------------------------


def generate_issuer(rng=None) -> IssuerKey:
    return IssuerKey.generate(ATTR_NAMES, rng=rng)


def issue_signer_config(
    issuer: IssuerKey,
    mspid: str,
    ou: str,
    role: int,
    enrollment_id: str,
    revocation_handle: int = 0,
    rng=None,
) -> msp_config_pb2.IdemixMSPSignerConfig:
    """Run the request->issue flow and emit a signer config (reference
    idemixgen's signerconfig output)."""
    sk = bn.rand_zr(rng)
    req = new_cred_request(sk, b"idemixgen", issuer.ipk, rng=rng)
    attrs = [
        attribute_to_scalar(ou),
        attribute_to_scalar(role),
        attribute_to_scalar(enrollment_id),
        attribute_to_scalar(revocation_handle),
    ]
    cred = new_credential(issuer, req, attrs, rng=rng)
    cred.ver(sk, issuer.ipk)
    return msp_config_pb2.IdemixMSPSignerConfig(
        cred=cred.to_bytes(),
        sk=sk.to_bytes(32, "big"),
        organizational_unit_identifier=ou,
        role=role,
        enrollment_id=enrollment_id.encode(),
    )


def idemix_msp_config(
    issuer: IssuerKey,
    mspid: str,
    signer: msp_config_pb2.IdemixMSPSignerConfig | None = None,
    epoch: int = 0,
) -> msp_config_pb2.MSPConfig:
    import json

    ic = msp_config_pb2.IdemixMSPConfig(
        name=mspid,
        ipk=json.dumps(issuer.ipk.to_dict()).encode(),
        epoch=epoch,
    )
    if signer is not None:
        ic.signer = signer.SerializeToString()
    return msp_config_pb2.MSPConfig(type=IDEMIX, config=ic.SerializeToString())


ISSUER_PUBLIC_KEY_FILE = "IssuerPublicKey"


def write_issuer_public_key(issuer: IssuerKey, out_dir: str) -> str:
    """`<out_dir>/msp/IssuerPublicKey`, as upstream idemixgen lays it
    out; this tree's JSON form of the key."""
    import json
    import os

    msp_dir = os.path.join(out_dir, "msp")
    os.makedirs(msp_dir, exist_ok=True)
    path = os.path.join(msp_dir, ISSUER_PUBLIC_KEY_FILE)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(issuer.ipk.to_dict(), f)
    return path


def load_idemix_msp_dir(path: str, mspid: str) -> msp_config_pb2.MSPConfig:
    """The verifier's MSPConfig of an Idemix organisation from
    idemixgen's output directory (`msp/IssuerPublicKey`; the file
    directly under `path` is accepted too).  Raises OSError when the
    key is not there."""
    import os

    for candidate in (
        os.path.join(path, "msp", ISSUER_PUBLIC_KEY_FILE),
        os.path.join(path, ISSUER_PUBLIC_KEY_FILE),
    ):
        if os.path.exists(candidate):
            with open(candidate, "rb") as f:
                ipk = f.read()
            return msp_config_pb2.MSPConfig(
                type=IDEMIX,
                config=msp_config_pb2.IdemixMSPConfig(
                    name=mspid, ipk=ipk,
                ).SerializeToString(),
            )
    raise FileNotFoundError(
        f"no {ISSUER_PUBLIC_KEY_FILE} under {path!r} or its msp/"
    )


__all__ = [
    "IdemixMSP",
    "IdemixIdentity",
    "IdemixSigningIdentity",
    "IdemixMSPError",
    "generate_issuer",
    "issue_signer_config",
    "idemix_msp_config",
    "load_idemix_msp_dir",
    "write_issuer_public_key",
    "ROLE_MEMBER",
    "ROLE_ADMIN",
    "IDEMIX",
]
