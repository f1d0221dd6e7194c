"""The plain reference of `manyclients-10k`: the serial
validate-and-commit of `reference/x509-majority.py` (a copy: a
reference stands alone), with an identity held to what the
configuration's guarantees state, one identity at a time, as Fabric's
msp/mspimplvalidate.go holds it.

It decodes with the protobufs alone and imports nothing of
`peer/txvalidator.py`, `msp/`, `policies/`, `csp/` or `ledger/`.  Each
signature is checked with `cryptography`'s OpenSSL verify, one at a
time.  An identity counts for its organisation when its certificate

    - names that organisation's CA certificate as issuer and verifies
      under its key (both from the world's `public`, as a peer takes
      them from the channel configuration): the chain signature;
    - stands, with the CA's, inside its validity window by this
      machine's clock;
    - has a serial number that is not on the organisation's CRL (from
      `public` too; the CRL's own signature is checked under the CA's
      key when it is loaded);
    - carries exactly one of the four role OUs (`client`, `peer`,
      `admin`, `orderer`): NodeOUs are on in every organisation.

A creator may be of any role; an endorsement counts when its identity's
role is `peer`.  The channel's `MAJORITY Endorsement` policy is decided
by counting the distinct organisations with a valid `peer`
endorsement; MVCC is a dict of versions, keyed by (namespace, key).
The memo of identities is this file's own business (an identity's
bytes decide its verdict within a run); the verdicts are not.

Fabric's order of checks (core/committer/txvalidator/v20): creator
signature, then the endorsement policy, then at commit the reads
against committed versions, a transaction seeing the valid writes of
earlier transactions in its block.
"""

from __future__ import annotations

import datetime

from cryptography import x509
from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives import hashes
from cryptography.hazmat.primitives.asymmetric import ec
from cryptography.hazmat.primitives.asymmetric.utils import decode_dss_signature

from fabric_tpu.protos.common import common_pb2
from fabric_tpu.protos.ledger.rwset import rwset_pb2
from fabric_tpu.protos.ledger.rwset.kvrwset import kv_rwset_pb2
from fabric_tpu.protos.msp import identities_pb2
from fabric_tpu.protos.peer import proposal_pb2, proposal_response_pb2, transaction_pb2

VALID = 0
BAD_CREATOR_SIGNATURE = 4
ENDORSEMENT_POLICY_FAILURE = 10
MVCC_READ_CONFLICT = 11

ROLE_OUS = ("client", "peer", "admin", "orderer")

_P256_HALF_ORDER = (
    0xFFFFFFFF00000000FFFFFFFFFFFFFFFFBCE6FAADA7179E84F3B9CAC2FC632551 >> 1
)


class Reference:
    """Serial validator and state over one chain of blocks."""

    def __init__(self, ca_certs_pem: dict, crls_pem: dict, n_orgs: int):
        self._cas = {
            mspid: x509.load_pem_x509_certificate(pem)
            for mspid, pem in ca_certs_pem.items()
        }
        self._revoked = {}                    # mspid -> serial numbers on its CRL
        for mspid, pem in crls_pem.items():
            crl = x509.load_pem_x509_crl(pem)
            ca = self._cas[mspid]
            if crl.issuer != ca.subject or not crl.is_signature_valid(ca.public_key()):
                raise ValueError(f"the CRL of {mspid} is not its CA's")
            self._revoked[mspid] = {r.serial_number for r in crl}
        self._need = n_orgs // 2 + 1          # ImplicitMeta MAJORITY
        self._idents: dict = {}               # serialized identity -> (mspid, key, role) | None
        self.state: dict = {}                 # (namespace, key) -> (value, (block, tx))

    def _identity(self, serialized: bytes):
        hit = self._idents.get(serialized, False)
        if hit is not False:
            return hit
        try:
            out = self._validated(serialized)
        except (ValueError, InvalidSignature):
            out = None
        self._idents[serialized] = out
        return out

    def _validated(self, serialized: bytes):
        """(mspid, public key, role) of an identity that passes the four
        checks, else None (or the error of what failed to parse or verify)."""
        sid = identities_pb2.SerializedIdentity.FromString(serialized)
        cert = x509.load_pem_x509_certificate(sid.id_bytes)
        ca = self._cas.get(sid.mspid)
        if ca is None or cert.issuer != ca.subject:
            return None
        ca.public_key().verify(
            cert.signature, cert.tbs_certificate_bytes,
            ec.ECDSA(cert.signature_hash_algorithm),
        )
        now = datetime.datetime.now(datetime.timezone.utc)
        for c in (cert, ca):
            if not c.not_valid_before_utc <= now <= c.not_valid_after_utc:
                return None
        if cert.serial_number in self._revoked.get(sid.mspid, ()):
            return None
        roles = {
            a.value for a in cert.subject
            if a.oid == x509.NameOID.ORGANIZATIONAL_UNIT_NAME and a.value in ROLE_OUS
        }
        if len(roles) != 1:
            return None
        return sid.mspid, cert.public_key(), roles.pop()

    @staticmethod
    def _verify(key, signature: bytes, message: bytes) -> bool:
        try:
            _r, s = decode_dss_signature(signature)
            if s > _P256_HALF_ORDER:      # Fabric accepts low-S only
                return False
            key.verify(signature, message, ec.ECDSA(hashes.SHA256()))
            return True
        except (ValueError, InvalidSignature):
            return False

    def _validate_tx(self, env_bytes: bytes):
        """(flag, reads, writes) of one envelope, before MVCC."""
        env = common_pb2.Envelope.FromString(env_bytes)
        payload = common_pb2.Payload.FromString(env.payload)
        shdr = common_pb2.SignatureHeader.FromString(payload.header.signature_header)
        creator = self._identity(shdr.creator)
        if creator is None or not self._verify(creator[1], env.signature, env.payload):
            return BAD_CREATOR_SIGNATURE, (), ()
        tx = transaction_pb2.Transaction.FromString(payload.data)
        cap = transaction_pb2.ChaincodeActionPayload.FromString(tx.actions[0].payload)
        prp = cap.action.proposal_response_payload
        orgs = set()
        for e in cap.action.endorsements:
            ident = self._identity(e.endorser)
            if ident is None or ident[2] != "peer":
                continue
            if self._verify(ident[1], e.signature, prp + e.endorser):
                orgs.add(ident[0])
        if len(orgs) < self._need:
            return ENDORSEMENT_POLICY_FAILURE, (), ()
        action = proposal_pb2.ChaincodeAction.FromString(
            proposal_response_pb2.ProposalResponsePayload.FromString(prp).extension
        )
        reads, writes = [], []
        for ns in rwset_pb2.TxReadWriteSet.FromString(action.results).ns_rwset:
            kv = kv_rwset_pb2.KVRWSet.FromString(ns.rwset)
            for r in kv.reads:
                ver = (
                    (r.version.block_num, r.version.tx_num)
                    if r.HasField("version") else None
                )
                reads.append(((ns.namespace, r.key), ver))
            for w in kv.writes:
                writes.append(((ns.namespace, w.key), None if w.is_delete else w.value))
        return VALID, reads, writes

    def apply_block(self, block_bytes: bytes) -> list:
        """Validate and commit one block; the final per-tx flags."""
        block = common_pb2.Block.FromString(block_bytes)
        num = block.header.number
        flags = []
        for i, env_bytes in enumerate(block.data.data):
            flag, reads, writes = self._validate_tx(env_bytes)
            if flag == VALID:
                for key, ver in reads:
                    have = self.state.get(key)
                    if (have[1] if have else None) != ver:
                        flag = MVCC_READ_CONFLICT
                        break
            if flag == VALID:
                for key, value in writes:
                    if value is None:
                        self.state.pop(key, None)
                    else:
                        self.state[key] = (value, (num, i))
            flags.append(flag)
        return flags


def run(public: dict, deployment: dict, blocks: list):
    """(per-block flags, the state after each block) of a fresh chain."""
    ref = Reference(public["ca_certs_pem"], public["crls_pem"], int(deployment["orgs"]))
    flags, states = [], []
    for b in blocks:
        flags.append(ref.apply_block(b))
        states.append(dict(ref.state))
    return flags, states
