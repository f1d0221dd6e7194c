"""The plain reference of `smallbank-100k-zipf`: a serial
validate-and-commit over a chain that starts populated, with the same
semantics as the peer's and none of its code.

A copy of its own (not an import of `x509-majority.py`): it decodes
with the protobufs alone and imports nothing of `peer/`, `policies/`,
`csp/` or `ledger/`.  Each signature is checked with `cryptography`'s
OpenSSL verify, one at a time; an identity counts for its organisation
when its certificate verifies under that organisation's CA certificate
(taken from the world's `public`, as a peer takes it from the channel
configuration) and carries the role's OU; the channel's `MAJORITY
Endorsement` policy is decided by counting the distinct organisations
with a valid `peer` endorsement: 3 of 5; MVCC is a dict of (value,
version), keyed by (namespace, key).

Fabric's order of checks (core/committer/txvalidator/v20): creator
signature, then the endorsement policy, then at commit the reads
against committed versions, a transaction seeing the valid writes of
earlier transactions in its block.

It replays the set-up blocks first, as any block (each of their
transactions has to come out VALID: a ledger that starts otherwise is
not the deployment's), and answers as the reference of a populated
world answers (`benchlib/manifest.py`): per measured block the flags,
the state the set-up left, and per measured block the rows it changed.
The chaincode is not run again: what a transaction read and wrote is in
its read-write set, which three organisations signed.
"""

from __future__ import annotations

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

_P256_HALF_ORDER = (
    0xFFFFFFFF00000000FFFFFFFFFFFFFFFFBCE6FAADA7179E84F3B9CAC2FC632551 >> 1
)


class Reference:
    """Serial validator and state over one chain of blocks."""

    def __init__(self, ca_certs_pem: dict, n_orgs: int):
        self._cas = {
            mspid: x509.load_pem_x509_certificate(pem)
            for mspid, pem in ca_certs_pem.items()
        }
        self._need = n_orgs // 2 + 1          # ImplicitMeta MAJORITY
        self._idents: dict = {}               # serialized identity -> (mspid, key, ous) | None
        self.state: dict = {}                 # (namespace, key) -> (value, (block, tx))

    def _identity(self, serialized: bytes):
        hit = self._idents.get(serialized, False)
        if hit is not False:
            return hit
        out = None
        try:
            sid = identities_pb2.SerializedIdentity.FromString(serialized)
            cert = x509.load_pem_x509_certificate(sid.id_bytes)
            ca = self._cas.get(sid.mspid)
            if ca is not None:
                ca.public_key().verify(
                    cert.signature, cert.tbs_certificate_bytes,
                    ec.ECDSA(cert.signature_hash_algorithm),
                )
                ous = {
                    a.value for a in cert.subject
                    if a.oid == x509.NameOID.ORGANIZATIONAL_UNIT_NAME
                }
                out = (sid.mspid, cert.public_key(), ous)
        except (ValueError, InvalidSignature):
            out = None
        self._idents[serialized] = out
        return out

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
            if ident is None or "peer" not in ident[2]:
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

    def apply_block(self, block_bytes: bytes) -> tuple:
        """Validate and commit one block: the final per-tx flags, and
        the rows it changed, (namespace, key) -> (value, version) as it
        left them or None for a row it deleted."""
        block = common_pb2.Block.FromString(block_bytes)
        num = block.header.number
        flags, changed = [], {}
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
                    changed[key] = self.state.get(key)
            flags.append(flag)
        return flags, changed


def run(public: dict, deployment: dict, blocks: list, setup_blocks: list):
    """(per measured block the flags, the state after the last of
    `setup_blocks`, per measured block the rows that changed)."""
    ref = Reference(public["ca_certs_pem"], int(deployment["orgs"]))
    for b in setup_blocks:
        flags, _changed = ref.apply_block(b)
        if set(flags) != {VALID}:
            raise ValueError(f"a set-up block holds a transaction that is not valid: {flags}")
    base = dict(ref.state)
    answers = [ref.apply_block(b) for b in blocks]
    return [flags for flags, _c in answers], base, [changed for _f, changed in answers]
