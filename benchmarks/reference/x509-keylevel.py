"""The plain reference of `keylevel-5org-1000tx`: a serial
validate-and-commit, one block at a time, with key-level (state-based)
endorsement as Fabric's `statebased/validator_keylevel.go` and
`vpmanagerimpl.go` give it, and none of the peer's code.

A copy of its own (not an import of `x509-majority.py`): it decodes
with the protobufs alone and imports nothing of `peer/`, `policies/`,
`csp/`, `ledger/` or `chaincode/`.  Each signature is checked with
`cryptography`'s OpenSSL verify, one at a time; an identity counts for
its organisation when its certificate verifies under that
organisation's CA certificate (from the world's `public`) and, for a
`peer` principal, carries the OU `peer`.

Per transaction, in Fabric's order (core/committer/txvalidator/v20):

1. the creator's signature;
2. the endorsement policy of every written namespace: each key of a
   value write or a metadata write is decided by ITS parameter, the
   `SignaturePolicyEnvelope` bytes a plain dict holds for (namespace,
   key) as the blocks BEFORE this one left it; the keys without one by
   the chaincode's policy, the channel's `MAJORITY Endorsement`, once;
   a transaction that writes nothing still needs the majority.  A key
   whose parameter an earlier VALID transaction of the same block
   rewrote refuses the transaction outright (upstream's
   `ValidationParameterUpdatedError`): ENDORSEMENT_POLICY_FAILURE;
3. at commit, MVCC: the reads against the versions as the earlier valid
   transactions of the block left them.  A valid transaction's value
   writes and metadata writes are applied: a value write keeps the
   key's parameter, a metadata write sets it and bumps the key's
   version, on an absent key it is a no-op, and a delete takes the
   parameter with the key.

Departures from upstream, each deliberate:

- a parameter is evaluated by walking its rule over ORGANISATIONS: a
  `signed_by` is met when a valid endorsement comes from an identity of
  the principal's MSP with the principal's role, and an `n_out_of` when
  n of its rules are; upstream's cauthdsl also spends each signature at
  most once in a rule.  For N-of-N over distinct organisations, the one
  shape `KeyEndorsementPolicy` builds, the two agree;
- principals other than ROLE (PEER or MEMBER) refuse the transaction,
  as an unparseable parameter does upstream;
- upstream keeps validation parameters in the state database's
  metadata; here they are a dict beside the state, and only
  `VALIDATION_PARAMETER` is kept of a metadata write's entries;
- no collections, no range queries, no lifecycle: the deployment has
  none.
"""

from __future__ import annotations

from cryptography import x509
from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives import hashes
from cryptography.hazmat.primitives.asymmetric import ec
from cryptography.hazmat.primitives.asymmetric.utils import decode_dss_signature

from fabric_tpu.protos.common import common_pb2, policies_pb2
from fabric_tpu.protos.ledger.rwset import rwset_pb2
from fabric_tpu.protos.ledger.rwset.kvrwset import kv_rwset_pb2
from fabric_tpu.protos.msp import identities_pb2, msp_principal_pb2
from fabric_tpu.protos.peer import proposal_pb2, proposal_response_pb2, transaction_pb2

VALID = 0
BAD_CREATOR_SIGNATURE = 4
ENDORSEMENT_POLICY_FAILURE = 10
MVCC_READ_CONFLICT = 11

VALIDATION_PARAMETER = "VALIDATION_PARAMETER"

_P256_HALF_ORDER = (
    0xFFFFFFFF00000000FFFFFFFFFFFFFFFFBCE6FAADA7179E84F3B9CAC2FC632551 >> 1
)


class Reference:
    """Serial validator, state and parameters over one chain of blocks."""

    def __init__(self, ca_certs_pem: dict, n_orgs: int):
        self._cas = {
            mspid: x509.load_pem_x509_certificate(pem)
            for mspid, pem in ca_certs_pem.items()
        }
        self._majority = n_orgs // 2 + 1      # ImplicitMeta MAJORITY
        self._idents: dict = {}               # serialized identity -> (mspid, key, ous) | None
        self.state: dict = {}                 # (namespace, key) -> (value, (block, tx))
        self.parameters: dict = {}            # (namespace, key) -> SignaturePolicyEnvelope bytes

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

    # -- policies --------------------------------------------------------

    def _meets(self, raw: bytes, endorsed: list) -> bool:
        """Whether the valid endorsements `endorsed` ((mspid, OUs) each)
        meet the parameter `raw`."""
        try:
            env = policies_pb2.SignaturePolicyEnvelope.FromString(raw)
            principals = []
            for p in env.identities:
                if p.principal_classification != msp_principal_pb2.MSPPrincipal.ROLE:
                    return False
                role = msp_principal_pb2.MSPRole.FromString(p.principal)
                if role.role not in (msp_principal_pb2.MSPRole.PEER,
                                     msp_principal_pb2.MSPRole.MEMBER):
                    return False
                principals.append((role.msp_identifier,
                                   role.role == msp_principal_pb2.MSPRole.PEER))
        except ValueError:
            return False
        if not principals:
            return False

        def met(rule) -> bool:
            which = rule.WhichOneof("Type")
            if which == "signed_by":
                if not 0 <= rule.signed_by < len(principals):
                    return False
                mspid, peer = principals[rule.signed_by]
                return any(m == mspid and (not peer or "peer" in ous) for m, ous in endorsed)
            if which == "n_out_of":
                return sum(1 for r in rule.n_out_of.rules if met(r)) >= rule.n_out_of.n
            return False

        return met(env.rule)

    def _majority_met(self, endorsed: list) -> bool:
        return len({m for m, ous in endorsed if "peer" in ous}) >= self._majority

    # -- one transaction ---------------------------------------------------

    def _validate_tx(self, env_bytes: bytes, rewritten: set):
        """(flag, reads, value writes, metadata writes) of one envelope,
        before MVCC."""
        nothing = (), (), ()
        env = common_pb2.Envelope.FromString(env_bytes)
        payload = common_pb2.Payload.FromString(env.payload)
        shdr = common_pb2.SignatureHeader.FromString(payload.header.signature_header)
        creator = self._identity(shdr.creator)
        if creator is None or not self._verify(creator[1], env.signature, env.payload):
            return (BAD_CREATOR_SIGNATURE, *nothing)
        tx = transaction_pb2.Transaction.FromString(payload.data)
        cap = transaction_pb2.ChaincodeActionPayload.FromString(tx.actions[0].payload)
        prp = cap.action.proposal_response_payload
        endorsed, seen = [], set()
        for e in cap.action.endorsements:
            if e.endorser in seen:
                continue              # an identity counts once
            seen.add(e.endorser)
            ident = self._identity(e.endorser)
            if ident is not None and self._verify(ident[1], e.signature, prp + e.endorser):
                endorsed.append((ident[0], ident[2]))
        action = proposal_pb2.ChaincodeAction.FromString(
            proposal_response_pb2.ProposalResponsePayload.FromString(prp).extension
        )
        reads, writes, metas = [], [], []
        ok, wrote_anything = True, False
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
            for mw in kv.metadata_writes:
                metas.append(((ns.namespace, mw.key),
                              {e.name: bytes(e.value) for e in mw.entries}))
            keys = {(ns.namespace, w.key) for w in kv.writes} \
                | {(ns.namespace, mw.key) for mw in kv.metadata_writes}
            if not keys:
                continue
            wrote_anything = True
            if keys & rewritten:
                ok = False            # the in-block rule
            need_majority = False
            for key in sorted(keys):
                raw = self.parameters.get(key)
                if raw is None:
                    need_majority = True
                elif not self._meets(raw, endorsed):
                    ok = False
            if need_majority and not self._majority_met(endorsed):
                ok = False
        if not wrote_anything and not self._majority_met(endorsed):
            ok = False
        if not ok:
            return (ENDORSEMENT_POLICY_FAILURE, *nothing)
        return VALID, reads, writes, metas

    def apply_block(self, block_bytes: bytes) -> list:
        """Validate and commit one block; the final per-tx flags."""
        block = common_pb2.Block.FromString(block_bytes)
        num = block.header.number
        # every policy of the block reads the parameters as the block
        # BEFORE left them; the block's own land in `later`
        flags, rewritten, later = [], set(), []
        for i, env_bytes in enumerate(block.data.data):
            flag, reads, writes, metas = self._validate_tx(env_bytes, rewritten)
            if flag == VALID:
                rewritten.update(key for key, _entries in metas)
            later.append((reads, writes, metas))
            flags.append(flag)
        for i, (reads, writes, metas) in enumerate(later):
            if flags[i] != VALID:
                continue
            for key, ver in reads:
                have = self.state.get(key)
                if (have[1] if have else None) != ver:
                    flags[i] = MVCC_READ_CONFLICT
                    break
            if flags[i] != VALID:
                continue
            for key, value in writes:
                if value is None:
                    self.state.pop(key, None)
                    self.parameters.pop(key, None)
                else:
                    self.state[key] = (value, (num, i))
            for key, entries in metas:
                have = self.state.get(key)
                if have is None:
                    continue          # a metadata write on an absent key is a no-op
                self.state[key] = (have[0], (num, i))
                raw = entries.get(VALIDATION_PARAMETER)
                if raw:
                    self.parameters[key] = raw
                else:
                    self.parameters.pop(key, None)
        return flags


def run(public: dict, deployment: dict, blocks: list):
    """(per-block flags, the state after each block) of a fresh chain."""
    ref = Reference(public["ca_certs_pem"], int(deployment["orgs"]))
    flags, states = [], []
    for b in blocks:
        flags.append(ref.apply_block(b))
        states.append(dict(ref.state))
    return flags, states
