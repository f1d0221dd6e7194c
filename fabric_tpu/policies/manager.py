"""Hierarchical policy manager + implicit meta policies.

Reference: common/policies/policy.go:152 (Manager: path-addressed policy
namespace `/Channel/Application/Writers`), implicitmeta.go (ANY/ALL/
MAJORITY over the equally-named policy of each sub-group).

Every policy object implements the same two-phase protocol as
SignaturePolicy (`prepare` -> PendingEvaluation with batchable items) so a
caller can batch across policies — including across the sub-policies an
implicit meta policy fans out to.
"""

from __future__ import annotations

from fabric_tpu.protos.common import configtx_pb2, policies_pb2
from fabric_tpu.protoutil import SignedData
from fabric_tpu.policies.signature_policy import (
    PendingEvaluation,
    PolicyError,
    SignaturePolicy,
    principals_of,
)

# Reserved policy names (reference common/policies/policy.go)
CHANNEL_READERS = "Readers"
CHANNEL_WRITERS = "Writers"
CHANNEL_ADMINS = "Admins"
BLOCK_VALIDATION = "BlockValidation"


class _MetaPending:
    def __init__(self, pendings: list[PendingEvaluation], threshold: int):
        self._pendings = pendings
        self._threshold = threshold
        self.items = [it for p in pendings for it in p.items]

    def finish(self, mask) -> bool:
        if len(mask) != len(self.items):
            raise PolicyError("mask length mismatch")
        satisfied = 0
        off = 0
        for p in self._pendings:
            n = len(p.items)
            if p.finish(mask[off : off + n]):
                satisfied += 1
            off += n
        return satisfied >= self._threshold


class ImplicitMetaPolicy:
    """ANY/ALL/MAJORITY of the same-named policy across sub-managers."""

    def __init__(self, sub_policies: list, rule: int):
        self._subs = sub_policies
        R = policies_pb2.ImplicitMetaPolicy
        if rule == R.ANY:
            self._threshold = min(1, len(sub_policies))
        elif rule == R.ALL:
            self._threshold = len(sub_policies)
        elif rule == R.MAJORITY:
            self._threshold = len(sub_policies) // 2 + 1
        else:
            raise PolicyError(f"unknown implicit meta rule {rule}")

    def principals(self) -> list | None:
        """Those of the sub-policies (`SignaturePolicy.principals`);
        None where one of them cannot list its own."""
        return principals_of(self._subs)

    def prepare(self, signed_data: list[SignedData]):
        return _MetaPending([p.prepare(signed_data) for p in self._subs], self._threshold)

    def evaluate_signed_data(self, signed_data: list[SignedData], csp) -> bool:
        pending = self.prepare(signed_data)
        mask = csp.verify_batch(pending.items)
        return pending.finish(mask)


class RejectPolicy:
    """Stand-in for unparsable/absent policies: always rejects (the
    reference routes unknown policies to an implicit deny).  `reason`
    records WHY the deny exists — an unparsable policy and a missing
    path are different operator problems, and a silent always-False
    object made them indistinguishable."""

    def __init__(self, name: str, reason: str = ""):
        self.name = name
        self.reason = reason or f"policy {name!r} is not defined"

    def principals(self) -> list:
        return []  # it asks nothing of anybody

    def prepare(self, signed_data):
        return _MetaPending([], 1)

    def evaluate_signed_data(self, signed_data, csp) -> bool:
        return False


class Manager:
    """A node in the policy namespace tree."""

    def __init__(self, path: str, policies: dict, sub_managers: dict):
        self.path = path
        self._policies = policies
        self._subs = sub_managers

    def manager(self, relpath: list[str]) -> "Manager | None":
        m = self
        for seg in relpath:
            m = m._subs.get(seg)
            if m is None:
                return None
        return m

    def get_policy(self, name: str):
        """Accepts relative names ("Writers"), absolute paths
        ("/Channel/Application/Writers"), and slashed relative paths."""
        if name.startswith("/"):
            segs = [s for s in name.split("/") if s]
            # absolute paths are rooted at the channel manager; tolerate a
            # leading "Channel" segment matching this manager's root
            m = self
            if segs and segs[0] == "Channel" and self.path in ("Channel", ""):
                segs = segs[1:]
            for seg in segs[:-1]:
                m = m._subs.get(seg)
                if m is None:
                    return RejectPolicy(name)
            return m._policies.get(segs[-1], RejectPolicy(name)) if segs else RejectPolicy(name)
        if "/" in name:
            segs = [s for s in name.split("/") if s]
            m = self.manager(segs[:-1])
            if m is None:
                return RejectPolicy(name)
            return m._policies.get(segs[-1], RejectPolicy(name))
        return self._policies.get(name, RejectPolicy(name))


def manager_from_config_group(
    path: str, group: configtx_pb2.ConfigGroup, deserializer
) -> Manager:
    """Build the manager tree from a channel config group (reference
    NewManagerImpl walking ConfigGroup.policies/groups)."""
    subs = {
        name: manager_from_config_group(f"{path}/{name}" if path else name, g, deserializer)
        for name, g in group.groups.items()
    }
    policies: dict[str, object] = {}
    metas: list[tuple[str, policies_pb2.ImplicitMetaPolicy]] = []
    for name, cfg_policy in group.policies.items():
        pol = cfg_policy.policy
        if pol.type == policies_pb2.Policy.SIGNATURE:
            try:
                env = policies_pb2.SignaturePolicyEnvelope.FromString(pol.value)
                policies[name] = SignaturePolicy(env, deserializer)
            except Exception as exc:
                # structured deny: the config carried a SIGNATURE policy
                # that does not parse — evaluations fail closed AND the
                # reject records what broke (reference logs + implicit
                # deny for unknown policy types)
                policies[name] = RejectPolicy(
                    name, reason=f"unparsable SIGNATURE policy: {exc}"
                )
        elif pol.type == policies_pb2.Policy.IMPLICIT_META:
            metas.append((name, policies_pb2.ImplicitMetaPolicy.FromString(pol.value)))
        else:
            policies[name] = RejectPolicy(
                name, reason=f"unsupported policy type {pol.type}"
            )
    # implicit metas resolve against sub-managers' policies after they exist
    for name, meta in metas:
        sub_pols = []
        for sm in subs.values():
            p = sm._policies.get(meta.sub_policy)
            if p is not None and not isinstance(p, RejectPolicy):
                sub_pols.append(p)
        if sub_pols:
            policies[name] = ImplicitMetaPolicy(sub_pols, meta.rule)
        else:
            policies[name] = RejectPolicy(
                name,
                reason=f"implicit meta policy over {meta.sub_policy!r} "
                       f"resolved zero sub-policies",
            )
    return Manager(path, policies, subs)


__all__ = [
    "Manager",
    "ImplicitMetaPolicy",
    "RejectPolicy",
    "manager_from_config_group",
    "CHANNEL_READERS",
    "CHANNEL_WRITERS",
    "CHANNEL_ADMINS",
    "BLOCK_VALIDATION",
]
