"""The plain reference of `mixedcc-8cc-5org-1000tx`: a serial
validate-and-commit, one transaction and one signature at a time, in
which every namespace a transaction writes is decided under that
chaincode's own endorsement policy, evaluated as Fabric's
`common/cauthdsl/cauthdsl.go` evaluates a signature policy, and none of
the peer's code.

A copy of its own (not an import of `x509-majority.py`): it decodes
with the protobufs alone and imports nothing of `peer/`, `policies/`,
`csp/`, `ledger/` or `chaincode/`.  Each signature is checked with
`cryptography`'s OpenSSL verify, one at a time.  What it knows of the
channel it takes from the world's `public`, as a verifier outside the
program would from the channel's configuration and its committed
definitions: the organisations' CA certificates, and per chaincode the
`ApplicationPolicy` bytes of its definition (none for a chaincode
without one).

Per transaction, in Fabric's order (core/committer/txvalidator/v20):

1. the creator's signature;
2. the endorsement policy of the invoked chaincode and of every other
   namespace the read-write set writes (upstream
   `plugindispatcher/dispatcher.go:158-218`), each under its own policy:
   the definition's signature policy; the channel's
   `/Channel/Application/Endorsement` where the definition refers to it
   or where there is no definition.  One unmet policy:
   ENDORSEMENT_POLICY_FAILURE;
3. at commit, MVCC: the reads against the versions as the earlier valid
   transactions of the block left them.

A signature policy is evaluated from cauthdsl's description:

- the endorsements are deduplicated by their identity's bytes, the
  first of an identity standing (`policies.SignatureSetToValidIdentities`),
  and an identity whose signature fails, or whose certificate does not
  verify under its organisation's CA, is out;
- `signed_by(i)` takes the FIRST identity, in the order of the
  endorsements, that is not yet `used` and satisfies principal i, and
  marks it used: an identity stands for one principal only;
- `n_out_of(n, rules)` evaluates EVERY rule in order, each against a
  copy of `used` that is kept only if the rule holds (a sub-rule that
  fails consumes nothing), and holds when n or more did.

Departures from upstream, each deliberate:

- a principal is satisfied by its `MSPRole` alone: the identity's
  certificate verifies under the CA of the principal's organisation and,
  for PEER, CLIENT, ADMIN and ORDERER, carries that OU (`member`: any
  certificate of the organisation).  Upstream's `SatisfiesPrincipal`
  also checks validity dates and revocation; the deployment has neither
  an expired nor a revoked certificate.  Principals that are not a role
  satisfy nobody;
- the channel's Endorsement policy is not read from the configuration
  block: it is resolved to what this deployment's channel writes, "a
  majority of the organisations' `OrgNMSP.peer` rules", each
  organisation's rule evaluated by itself over all the valid identities
  (an ImplicitMeta policy keeps no `used` marks between its sub-policies);
- a definition whose bytes do not parse, and a reference to any other
  channel policy, refuse the transaction (upstream falls to an implicit
  deny);
- no collections, no key-level parameters, no range queries: the
  deployment has none.
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
from fabric_tpu.protos.msp import identities_pb2, msp_principal_pb2
from fabric_tpu.protos.peer import (
    collection_pb2,
    proposal_pb2,
    proposal_response_pb2,
    transaction_pb2,
)

VALID = 0
BAD_CREATOR_SIGNATURE = 4
ENDORSEMENT_POLICY_FAILURE = 10
MVCC_READ_CONFLICT = 11

CHANNEL_ENDORSEMENT = "/Channel/Application/Endorsement"

_P256_HALF_ORDER = (
    0xFFFFFFFF00000000FFFFFFFFFFFFFFFFBCE6FAADA7179E84F3B9CAC2FC632551 >> 1
)

_ROLE_OU = {
    msp_principal_pb2.MSPRole.PEER: "peer",
    msp_principal_pb2.MSPRole.CLIENT: "client",
    msp_principal_pb2.MSPRole.ADMIN: "admin",
    msp_principal_pb2.MSPRole.ORDERER: "orderer",
}


def satisfies(ident, principal) -> bool:
    """`ident`: (mspid, OUs) of a valid identity; `principal`: (mspid,
    role) of an `MSPRole`, or None for a principal of another class."""
    if principal is None or ident[0] != principal[0]:
        return False
    if principal[1] == msp_principal_pb2.MSPRole.MEMBER:
        return True
    return _ROLE_OU.get(principal[1]) in ident[1]


def holds(rule, principals: list, idents: list, used: list) -> bool:
    """cauthdsl's closure over `idents`, the deduplicated identities in
    the order of their endorsements ((mspid, OUs), or None for one whose
    signature or certificate failed); `used` runs beside it."""
    which = rule.WhichOneof("Type")
    if which == "signed_by":
        if not 0 <= rule.signed_by < len(principals):
            return False
        principal = principals[rule.signed_by]
        for pos, ident in enumerate(idents):
            if used[pos] or ident is None:
                continue
            if satisfies(ident, principal):
                used[pos] = True
                return True
        return False
    if which == "n_out_of":
        verified = 0
        for sub in rule.n_out_of.rules:
            trial = list(used)
            if holds(sub, principals, idents, trial):
                verified += 1
                used[:] = trial
        return verified >= rule.n_out_of.n
    return False


def envelope_met(envelope, idents: list) -> bool:
    """Whether `idents` meet a `SignaturePolicyEnvelope`."""
    principals = []
    for p in envelope.identities:
        if p.principal_classification != msp_principal_pb2.MSPPrincipal.ROLE:
            principals.append(None)
            continue
        role = msp_principal_pb2.MSPRole.FromString(p.principal)
        principals.append((role.msp_identifier, role.role))
    return holds(envelope.rule, principals, idents, [False] * len(idents))


class Reference:
    """Serial validator and state over one chain of blocks."""

    def __init__(self, ca_certs_pem: dict, definitions: dict):
        self._cas = {
            mspid: x509.load_pem_x509_certificate(pem)
            for mspid, pem in ca_certs_pem.items()
        }
        self._orgs = sorted(self._cas)
        self._majority = len(self._orgs) // 2 + 1      # ImplicitMeta MAJORITY
        self._definitions = definitions       # namespace -> ApplicationPolicy bytes
        self._policies: dict = {}             # namespace -> envelope | CHANNEL_ENDORSEMENT | None
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

    # -- policies --------------------------------------------------------

    def _policy_of(self, namespace: str):
        """The namespace's policy: its definition's envelope,
        CHANNEL_ENDORSEMENT, or None for one that can never be met."""
        if namespace in self._policies:
            return self._policies[namespace]
        raw = self._definitions.get(namespace)
        policy = CHANNEL_ENDORSEMENT
        if raw:
            policy = None
            try:
                ap = collection_pb2.ApplicationPolicy.FromString(raw)
            except ValueError:
                ap = None
            which = ap.WhichOneof("type") if ap is not None else None
            if which == "signature_policy":
                policy = ap.signature_policy
            elif which == "channel_config_policy_reference" \
                    and ap.channel_config_policy_reference == CHANNEL_ENDORSEMENT:
                policy = CHANNEL_ENDORSEMENT
        self._policies[namespace] = policy
        return policy

    def _channel_endorsement_met(self, idents: list) -> bool:
        """A majority of the organisations' `OrgNMSP.peer` rules."""
        met = sum(
            1 for mspid in self._orgs
            if any(ident is not None
                   and satisfies(ident, (mspid, msp_principal_pb2.MSPRole.PEER))
                   for ident in idents)
        )
        return met >= self._majority

    def _met(self, namespace: str, idents: list) -> bool:
        policy = self._policy_of(namespace)
        if policy is None:
            return False
        if policy is CHANNEL_ENDORSEMENT:
            return self._channel_endorsement_met(idents)
        return envelope_met(policy, idents)

    # -- one transaction ---------------------------------------------------

    def _validate_tx(self, env_bytes: bytes):
        """(flag, reads, writes) of one envelope, before MVCC."""
        env = common_pb2.Envelope.FromString(env_bytes)
        payload = common_pb2.Payload.FromString(env.payload)
        shdr = common_pb2.SignatureHeader.FromString(payload.header.signature_header)
        creator = self._identity(shdr.creator)
        if creator is None or not self._verify(creator[1], env.signature, env.payload):
            return BAD_CREATOR_SIGNATURE, (), ()
        chdr = common_pb2.ChannelHeader.FromString(payload.header.channel_header)
        invoked = proposal_pb2.ChaincodeHeaderExtension.FromString(
            chdr.extension).chaincode_id.name
        tx = transaction_pb2.Transaction.FromString(payload.data)
        cap = transaction_pb2.ChaincodeActionPayload.FromString(tx.actions[0].payload)
        prp = cap.action.proposal_response_payload
        # the identities in the order of their endorsements, each once
        idents, seen = [], set()
        for e in cap.action.endorsements:
            if e.endorser in seen:
                continue
            seen.add(e.endorser)
            ident = self._identity(e.endorser)
            if ident is not None and self._verify(ident[1], e.signature, prp + e.endorser):
                idents.append((ident[0], ident[2]))
            else:
                idents.append(None)
        action = proposal_pb2.ChaincodeAction.FromString(
            proposal_response_pb2.ProposalResponsePayload.FromString(prp).extension
        )
        reads, writes = [], []
        namespaces = [invoked]
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
            if kv.writes and ns.namespace not in namespaces:
                namespaces.append(ns.namespace)
        if not all(self._met(ns, idents) for ns in namespaces):
            return ENDORSEMENT_POLICY_FAILURE, (), ()
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
    ref = Reference(public["ca_certs_pem"], public["definitions"])
    flags, states = [], []
    for b in blocks:
        flags.append(ref.apply_block(b))
        states.append(dict(ref.state))
    return flags, states
