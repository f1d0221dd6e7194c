"""Signature-policy compilation & evaluation (cauthdsl equivalent).

Reference: common/cauthdsl/cauthdsl.go:24-92 (compile to a closure with the
`used[]` de-duplication trick) and common/policies/policy.go:365-402
(SignatureSetToValidIdentities: verify each signature once, dedup
identities, then run the closure over *valid identities only*).

TPU-first split (SURVEY.md §7 step 3): the reference interleaves signature
verification with policy evaluation per transaction; here the two phases
are explicit so a whole block's signatures batch into one device call:

  1. `prepare(signed_data)` -> PendingEvaluation: deserializes/dedups
     identities and exposes `items` (VerifyBatchItems) WITHOUT verifying.
  2. the caller batches items from many policies into CSP.verify_batch.
  3. `PendingEvaluation.finish(mask)` runs the compiled combinatoric
     closure over the identities whose signatures verified.

`evaluate_signed_data` composes all three for single-policy callers (e.g.
the orderer's sig filter).
"""

from __future__ import annotations

import dataclasses

from fabric_tpu.csp.api import VerifyBatchItem
from fabric_tpu.protos.common import policies_pb2
from fabric_tpu.protoutil import SignedData


class PolicyError(Exception):
    pass


def _compile(policy: policies_pb2.SignaturePolicy, identities, deserializer):
    """SignaturePolicy tree -> closure(valid_identities, used) -> bool.

    `valid_identities` is a list of (identity, index) whose signatures
    verified; `used` is a parallel bool list implementing the reference's
    rule that one signature cannot satisfy two leaves (cauthdsl.go:40-60)."""
    which = policy.WhichOneof("Type")
    if which == "signed_by":
        idx = policy.signed_by
        if idx < 0 or idx >= len(identities):
            raise PolicyError(f"identity index {idx} out of range")
        principal = identities[idx]

        def signed_by(valid, used):
            for pos, ident in enumerate(valid):
                if used[pos] or ident is None:
                    continue
                try:
                    deserializer.satisfies_principal(ident, principal)
                except Exception:
                    # fabriclint: allow[exception-discipline] principal
                    # mismatch is the expected per-lane outcome, not an error
                    continue
                used[pos] = True
                return True
            return False

        return signed_by
    if which == "n_out_of":
        n = policy.n_out_of.n
        subs = [_compile(r, identities, deserializer) for r in policy.n_out_of.rules]

        def n_out_of(valid, used):
            verified = 0
            for sub in subs:
                # speculative evaluation against a copy of `used`; commit
                # only on success (the reference's buf/copy dance)
                trial = list(used)
                if sub(valid, trial):
                    verified += 1
                    used[:] = trial
            return verified >= n

        return n_out_of
    raise PolicyError(f"unknown signature policy type {which!r}")


@dataclasses.dataclass
class PendingEvaluation:
    """Deferred policy evaluation: feed `items` to verify_batch, then call
    `finish` with the per-item validity mask."""

    items: list  # VerifyBatchItem per *deduped* signed-data entry
    _closure: object
    _identities: list  # deserialized identity per item (None if bad)

    def finish(self, mask) -> bool:
        if len(mask) != len(self.items):
            raise PolicyError("mask length mismatch")
        valid = [
            ident if ok and ident is not None else None
            for ident, ok in zip(self._identities, mask)
        ]
        used = [False] * len(valid)
        return self._closure(valid, used)


class SignaturePolicy:
    """A compiled SignaturePolicyEnvelope bound to an identity deserializer
    (implements the `policies.Policy` protocol)."""

    def __init__(self, envelope: policies_pb2.SignaturePolicyEnvelope, deserializer):
        if envelope.version != 0:
            raise PolicyError(f"unsupported policy version {envelope.version}")
        self._envelope = envelope
        self._deserializer = deserializer
        self._closure = _compile(envelope.rule, list(envelope.identities), deserializer)

    def principals(self) -> list:
        """[(deserializer, principal)]: every question the compiled
        closure can ask of an identity (`satisfies_principal`), and who
        answers it.  Two identities with the same answers are one to
        this policy."""
        return [(self._deserializer, p) for p in self._envelope.identities]

    def prepare(self, signed_data: list[SignedData]) -> PendingEvaluation:
        """Deserialize + dedup identities; no signature verification here.

        Dedup matches the reference (policy.go:381-388): repeated identity
        bytes contribute a single entry — and a single verify item."""
        seen: dict[bytes, int] = {}
        items, idents = [], []
        for sd in signed_data:
            if sd.identity in seen:
                continue
            seen[sd.identity] = len(items)
            ident = None
            try:
                ident = self._deserializer.deserialize_identity(sd.identity)
            except Exception:
                # fabriclint: allow[exception-discipline] lane stays None and
                # gets an unsatisfiable dummy item (alignment sentinel below)
                pass
            idents.append(ident)
            if ident is None:
                # keep lane alignment; a lane that cannot deserialize can
                # never verify.  Use an unsatisfiable dummy item.
                items.append(_dummy_item())
            elif sd.digest is not None:
                items.append(
                    VerifyBatchItem(ident.public_key, sd.digest, sd.signature)
                )
            else:
                items.append(ident.verification_item(sd.data, sd.signature))
        return PendingEvaluation(items, self._closure, idents)

    def evaluate_signed_data(self, signed_data: list[SignedData], csp) -> bool:
        """One-shot path (reference policy.EvaluateSignedData,
        common/cauthdsl/policy.go:87-95)."""
        pending = self.prepare(signed_data)
        mask = csp.verify_batch(pending.items)
        return pending.finish(mask)


def principals_of(policies) -> list | None:
    """Every (deserializer, principal) the policy objects may ask an
    identity about, from their `principals()`; None where one of them
    has no such method: all that is known of what it asks is the
    identity."""
    asked: list = []
    for policy in policies:
        listed = getattr(policy, "principals", None)
        part = None if listed is None else listed()
        if part is None:
            return None
        asked.extend(part)
    return asked


_DUMMY = None


def _dummy_item():
    """A VerifyBatchItem that always fails verification (malformed DER)."""
    global _DUMMY
    if _DUMMY is None:
        from fabric_tpu.csp.api import ECDSAP256PrivateKey, VerifyBatchItem

        key = ECDSAP256PrivateKey.generate().public_key()
        _DUMMY = VerifyBatchItem(key, b"\x00" * 32, b"\x30\x00")
    return _DUMMY


# ---------------------------------------------------------------------------
# Convenience policy constructors (reference common/policydsl builders).
# ---------------------------------------------------------------------------


def signed_by(index: int) -> policies_pb2.SignaturePolicy:
    return policies_pb2.SignaturePolicy(signed_by=index)


def n_out_of(n: int, rules) -> policies_pb2.SignaturePolicy:
    return policies_pb2.SignaturePolicy(
        n_out_of=policies_pb2.SignaturePolicy.NOutOf(n=n, rules=list(rules))
    )


def signed_by_msp_role(mspid: str, role) -> "policies_pb2.SignaturePolicyEnvelope":
    from fabric_tpu.protos.msp import msp_principal_pb2 as mp

    principal = mp.MSPPrincipal(
        principal_classification=mp.MSPPrincipal.ROLE,
        principal=mp.MSPRole(msp_identifier=mspid, role=role).SerializeToString(),
    )
    return policies_pb2.SignaturePolicyEnvelope(
        version=0, rule=signed_by(0), identities=[principal]
    )


def signed_by_any_member(mspids) -> policies_pb2.SignaturePolicyEnvelope:
    """1-of-N member policy across the given MSPs (reference
    policydsl SignedByAnyMember)."""
    from fabric_tpu.protos.msp import msp_principal_pb2 as mp

    identities = []
    rules = []
    for i, mspid in enumerate(mspids):
        identities.append(
            mp.MSPPrincipal(
                principal_classification=mp.MSPPrincipal.ROLE,
                principal=mp.MSPRole(
                    msp_identifier=mspid, role=mp.MSPRole.MEMBER
                ).SerializeToString(),
            )
        )
        rules.append(signed_by(i))
    return policies_pb2.SignaturePolicyEnvelope(
        version=0, rule=n_out_of(1, rules), identities=identities
    )


__all__ = [
    "PolicyError",
    "SignaturePolicy",
    "PendingEvaluation",
    "principals_of",
    "signed_by",
    "n_out_of",
    "signed_by_msp_role",
    "signed_by_any_member",
]
