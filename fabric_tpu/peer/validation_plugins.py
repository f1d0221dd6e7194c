"""Pluggable transaction-validation framework + builtin v2.0 plugin with
key-level (state-based) endorsement.

Reference surface:
  core/handlers/validation/api/**        — the Validate(block, ns, txPos,
                                           actionPos, ctx) plugin SPI
  core/committer/txvalidator/plugin/     — plugin name -> factory mapping
  core/handlers/validation/builtin/v20/  — the default "vscc" plugin
  core/committer/txvalidator/v20/plugindispatcher/dispatcher.go:158-218
                                         — per-written-namespace dispatch
  core/common/validation/statebased/     — key-level endorsement
                                           (validator_keylevel.go:36-141,
                                           evaluator v20.go:105-150)

TPU-first twist: the reference plugin verifies endorsement signatures
inline; here a plugin's `prepare` returns a `PendingValidation` whose
`items` join the block-wide `verify_batch` device call and whose
`finish(mask)` applies the policy combinatorics on the host — the same
two-phase split the signature-policy engine uses (SURVEY.md §7 step 3).

Key-level policy semantics (reference baseEvaluator.checkSBAndCCEP):
every key the tx writes (value or metadata, public or collection) is
checked against its key-level VALIDATION_PARAMETER when one is set; an
unparseable parameter fails the tx.  Keys without one fall back to the
collection-level endorsement policy (collection writes, when the
collection defines one) and otherwise to the chaincode-level policy,
each such fallback policy evaluated at most once.  A tx that writes
nothing in the namespace is still checked against the chaincode policy
(FAB-9473, CheckCCEPIfNoEPChecked).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Sequence

from fabric_tpu.common.flogging import must_get_logger
from fabric_tpu.csp.api import VerifyBatchItem
from fabric_tpu.ledger.txmgmt import VALIDATION_PARAMETER, hash_ns
from fabric_tpu.policies.signature_policy import SignaturePolicy, principals_of
from fabric_tpu.protos.ledger.rwset import rwset_pb2
from fabric_tpu.protos.ledger.rwset.kvrwset import kv_rwset_pb2
from fabric_tpu.protos.common import policies_pb2
from fabric_tpu.protos.peer import collection_pb2
from fabric_tpu.protoutil import SignedData


_logger = must_get_logger("peer.validation")


class IllegalWritesetError(Exception):
    """Duplicate namespace in the tx rwset (reference dispatcher.go:174
    -> TxValidationCode_ILLEGAL_WRITESET)."""


@dataclasses.dataclass
class RwsetFootprint:
    """One parse of a TxReadWriteSet, shared between the validator's
    ordering logic and the plugins (avoids re-decoding per phase)."""

    touched: frozenset  # {(ns_or_hashns, key)} the tx writes or re-metas
    meta_writes: dict  # {(ns_or_hashns, key): {entry: value}}
    deletes: list  # [(ns_or_hashns, key)] the tx deletes: a key's
    #                metadata goes with it, so a delete changes the key's
    #                VALIDATION_PARAMETER as a metadata write does
    per_ns: dict  # ns -> {"pub": [key], "meta": [key],
    #                      "coll": [(coll, hashns, hkey)],
    #                      "coll_meta": [(coll, hashns, hkey)],
    #                      "writes": bool}
    parsed: list = dataclasses.field(default_factory=list)
    # the SAME decode the MVCC validator and history index need later:
    # [(ns, KVRWSet, [(coll, HashedRWSet, pvt_rwset_hash)])] — handed down
    # the commit path so each tx's rwset wire format is walked exactly
    # once per lifecycle (the reference re-unmarshals it in the
    # dispatcher, in validateAndPrepareBatch AND in the history db,
    # rwsetutil/rwset_proto_util.go callers)


def parse_footprint(rwset_bytes: bytes | None) -> RwsetFootprint:
    # Hot path: one call per tx per block (the largest single collect
    # cost when it was profiled), so the common shape
    # — one namespace, a few public writes, no collections — runs on
    # list comprehensions and batch extends, not per-item loop bodies.
    touched: list = []
    deletes: list = []
    meta: dict[tuple[str, str], dict[str, bytes]] = {}
    per_ns: dict[str, dict] = {}
    parsed: list = []
    if rwset_bytes:
        txrw = rwset_pb2.TxReadWriteSet.FromString(rwset_bytes)
        for nsrw in txrw.ns_rwset:
            ns = nsrw.namespace
            if ns in per_ns:
                raise IllegalWritesetError(
                    f"duplicate namespace {ns!r} in txRWSet"
                )
            kvrw = kv_rwset_pb2.KVRWSet.FromString(nsrw.rwset)
            colls: list = []
            parsed.append((ns, kvrw, colls))
            # ONE walk of the writes for their keys and their deletes: a
            # second iteration makes every KVWrite's wrapper again
            pub = []
            for w in kvrw.writes:
                pub.append(w.key)
                if w.is_delete:
                    deletes.append((ns, w.key))
            mkeys = [mw.key for mw in kvrw.metadata_writes]
            entry = per_ns[ns] = {
                "pub": pub, "meta": mkeys, "coll": [], "coll_meta": [],
                "writes": bool(pub or mkeys),
            }
            if pub:
                touched.extend((ns, k) for k in pub)
            if mkeys:
                touched.extend((ns, k) for k in mkeys)
                for mw in kvrw.metadata_writes:
                    meta[(ns, mw.key)] = {
                        e.name: bytes(e.value) for e in mw.entries
                    }
            if not nsrw.collection_hashed_rwset:
                continue
            seen_colls: set[str] = set()
            for ch in nsrw.collection_hashed_rwset:
                cname = ch.collection_name
                if cname in seen_colls:
                    raise IllegalWritesetError(
                        f"duplicate collection {cname!r} in "
                        f"namespace {ns!r}"
                    )
                seen_colls.add(cname)
                hns = hash_ns(ns, cname)
                hrw = kv_rwset_pb2.HashedRWSet.FromString(ch.hashed_rwset)
                colls.append((cname, hrw, bytes(ch.pvt_rwset_hash)))
                hkeys = []
                for hw in hrw.hashed_writes:
                    hkey = bytes(hw.key_hash).hex()
                    hkeys.append(hkey)
                    if hw.is_delete:
                        deletes.append((hns, hkey))
                if hkeys:
                    touched.extend((hns, k) for k in hkeys)
                    entry["coll"].extend((cname, hns, k) for k in hkeys)
                    entry["writes"] = True
                for mw in hrw.metadata_writes:
                    hkey = bytes(mw.key_hash).hex()
                    touched.append((hns, hkey))
                    entry["coll_meta"].append((cname, hns, hkey))
                    entry["writes"] = True
                    meta[(hns, hkey)] = {
                        e.name: bytes(e.value) for e in mw.entries
                    }
    return RwsetFootprint(frozenset(touched), meta, deletes, per_ns, parsed)


@dataclasses.dataclass
class ValidationContext:
    """Everything a plugin may consult for one (tx, namespace) action."""

    channel_id: str
    namespace: str
    tx_pos: int
    endorsements: list[SignedData]
    rwset_bytes: bytes | None
    policy_provider: "PolicyProvider"
    state_metadata: Callable[[str, str], dict[str, bytes]]
    # (ns_or_hashns, key) -> committed metadata entries
    footprint: RwsetFootprint | None = None
    ns_has_metadata: Callable[[str], bool] | None = None
    # committed-state oracle: False guarantees NO key in the namespace
    # carries metadata, letting the plugin skip the per-written-key
    # VALIDATION_PARAMETER lookups wholesale (the reference pays a
    # GetStateMetadata fetch per written key per tx,
    # statebased/vpmanagerimpl.go:293); None = unknown, look keys up
    pending: Callable[[tuple], int | None] | None = None
    # the pipelined validator's: the (ns_or_hashns, key) pair -> the
    # newest block still in flight (collected, its commit not yet
    # readable) that may change the key's VALIDATION_PARAMETER, None for
    # a key no such block touches.  The committed metadata of such a key
    # is not yet what a validator that commits every block before it
    # validates the next would read, so an action that writes one is
    # not decided in `prepare`: see DeferredValidation.  None = nothing
    # in flight changes any parameter (a lone block; every block of a
    # channel without key-level policies)


class PendingValidation:
    """Two-phase result: `items` join the block batch; `finish(mask)`
    returns True when the action validates."""

    waits_on: int | None = None
    # the block whose commit `finish` has to find landed: only a
    # DeferredValidation has one

    def __init__(self, pendings: list, items: list):
        self._pendings = pendings  # [(PendingEvaluation, (start, end))]
        self.items = items

    def finish(self, mask: Sequence[bool]) -> bool:
        return all(
            p.finish(mask[start:end]) for p, (start, end) in self._pendings
        )


class _FailPending(PendingValidation):
    """Structured always-fail result: carries WHY the action can never
    validate (the reason also goes to the validation logger), so a
    rejected tx is attributable instead of a silent False."""

    def __init__(self, reason: str):
        super().__init__([], [])
        self.reason = reason
        _logger.warning("validation action rejected: %s", reason)

    def finish(self, mask) -> bool:
        return False


class PolicyProvider:
    """Resolves policy references for a channel: inline signature
    policies, channel-policy references, and the per-chaincode default
    (reference plugindispatcher/plugin_validator.go policy fetching).

    Parsed policies are memoized by their raw bytes: every tx carrying
    the same chaincode-level validation parameter or key-level
    VALIDATION_PARAMETER resolves to the SAME compiled SignaturePolicy
    object, so downstream per-(policy, endorser-set) caches hit across
    txs and blocks."""

    _MEMO_CAP = 512

    def __init__(self, policy_manager, deserializer, definition_provider=None):
        self._pm = policy_manager
        self._deserializer = deserializer
        self._definitions = definition_provider
        self._app_memo: dict[bytes, object] = {}
        self._sig_memo: dict[bytes, object] = {}
        self._ns_memo: dict[str, object] = {}

    @property
    def deserializer(self):
        return self._deserializer

    def begin_block(self) -> None:
        """Reset per-block memos.  Chaincode-level policy resolution is
        stable within one block but may change between blocks (a
        lifecycle commit lands a new definition), so the validator calls
        this at every block start."""
        self._ns_memo.clear()

    def default_policy(self):
        return self._pm.get_policy("/Channel/Application/Endorsement")

    def chaincode_policy(self, namespace: str):
        """The chaincode-level endorsement policy from the committed
        definition's validation parameter, else the channel default.
        Memoized per block (see begin_block)."""
        if namespace in self._ns_memo:
            return self._ns_memo[namespace]
        pol = self._resolve_chaincode_policy(namespace)
        self._ns_memo[namespace] = pol
        return pol

    def _resolve_chaincode_policy(self, namespace: str):
        if self._definitions is not None:
            info = self._definitions.validation_info(namespace)
            if info is not None:
                _, param = info
                pol = self.from_application_policy_bytes(param)
                if pol is not None:
                    return pol
        return self.default_policy()

    def collection_policy(self, namespace: str, collection: str):
        """The collection-level endorsement policy from the committed
        definition's collection config, or None when the collection
        defines none (reference v20.go fetchCollEP +
        CollectionValidationInfo)."""
        if self._definitions is None:
            return None
        getter = getattr(self._definitions, "collection_config", None)
        if getter is None:
            return None
        conf = getter(namespace, collection)
        if conf is None or not conf.HasField("endorsement_policy"):
            return None
        return self.from_application_policy_bytes(
            conf.endorsement_policy.SerializeToString()
        )

    def from_application_policy_bytes(self, raw: bytes):
        """Parse an ApplicationPolicy (inline signature policy or channel
        policy reference) — the chaincode-level validation parameter
        encoding; None when empty/unparseable."""
        if not raw:
            return None
        if raw in self._app_memo:
            return self._app_memo[raw]
        pol = self._parse_application_policy(raw)
        if len(self._app_memo) >= self._MEMO_CAP:
            self._app_memo.clear()
        self._app_memo[raw] = pol
        return pol

    def _parse_application_policy(self, raw: bytes):
        # parse and lookup fail differently: a proto decode error means
        # bad BYTES, a reference-resolution error means bad channel
        # CONFIG — the operator must be pointed at the right one
        try:
            ap = collection_pb2.ApplicationPolicy.FromString(raw)
        except Exception as exc:
            # None is the documented "no usable policy" sentinel the
            # callers fall back on — but the parse failure itself must
            # be attributable, not swallowed
            _logger.warning(
                "unparsable ApplicationPolicy (%d bytes): %s",
                len(raw), exc,
            )
            return None
        which = ap.WhichOneof("type")
        try:
            if which == "signature_policy":
                return SignaturePolicy(
                    ap.signature_policy, self._deserializer
                )
            if which == "channel_config_policy_reference":
                return self._pm.get_policy(
                    ap.channel_config_policy_reference
                )
        except Exception as exc:
            _logger.warning(
                "ApplicationPolicy %s could not be resolved: %s",
                which, exc,
            )
        return None

    def from_signature_policy_bytes(self, raw: bytes):
        """Parse a bare SignaturePolicyEnvelope — the KEY-LEVEL
        (state-based) policy encoding, distinct from ApplicationPolicy
        (the two are not wire-distinguishable, so each context uses its
        own parser, as in the reference)."""
        if not raw:
            return None
        if raw in self._sig_memo:
            return self._sig_memo[raw]
        pol = self._parse_signature_policy(raw)
        if len(self._sig_memo) >= self._MEMO_CAP:
            self._sig_memo.clear()
        self._sig_memo[raw] = pol
        return pol

    def _parse_signature_policy(self, raw: bytes):
        try:
            env = policies_pb2.SignaturePolicyEnvelope.FromString(raw)
            if env.rule.ByteSize() or env.identities:
                return SignaturePolicy(env, self._deserializer)
        except Exception as exc:
            _logger.warning(
                "unparsable SignaturePolicyEnvelope (%d bytes): %s",
                len(raw), exc,
            )
        return None


def _endorser(identity: bytes, questions: list | None, deserializer) -> tuple:
    """(class, public key) of one endorser: see `BuiltinV20Plugin._learn`.
    `questions` are the (deserializer, principal) pairs of a policy set
    (`principals_of`), None where a policy cannot list its own.  The
    class is a number: bit 0 the lane, bit i the answer to question i
    (an identity the policy's deserializer does not read satisfies
    nothing, as in `prepare`)."""
    read: dict = {}

    def read_by(asker):
        if id(asker) not in read:
            try:
                read[id(asker)] = asker.deserialize_identity(identity)
            except Exception:
                # no lane, and to a policy an identity it never sees
                read[id(asker)] = None
        return read[id(asker)]

    ident = read_by(deserializer)
    public_key = None if ident is None else ident.public_key
    if questions is None:
        return identity, public_key
    cls = int(ident is not None)
    for bit, (asker, principal) in enumerate(questions, 1):
        ident = read_by(asker)
        if ident is None:
            continue
        try:
            asker.satisfies_principal(ident, principal)
        except Exception:
            # fabriclint: allow[exception-discipline] principal mismatch
            # is the expected answer, not an error
            continue
        cls |= 1 << bit
    return cls, public_key


class EndorsementPlan:
    """Amortized policy combinatorics for one (policy set, ordered unique
    endorser set).

    Within a block — and across blocks — most txs repeat the same
    chaincode policy against the same endorsing orgs; only the digests
    and signatures differ per tx.  The reference re-runs identity
    deserialization, principal matching, and the cauthdsl closure for
    every tx (common/policies/policy.go:365 + cauthdsl.go:40-92).  A
    plan does all of that ONCE: it prepares every policy against
    sentinel digests to learn which item lane maps to which endorser,
    and memoizes `decide(bits)` — the pure function from per-endorser
    verify outcomes to the policy verdict.  Per tx, validation is then
    k VerifyBatchItem constructions plus one dict lookup.

    A plan holds no key: it is shared by every endorser set whose
    members, place by place, look alike to its policies (see
    `BuiltinV20Plugin._learn`), and a transaction's items carry its own
    endorsers' keys.  `built_for` is the set that built it."""

    def __init__(self, policies, endorser_bytes: tuple):
        self.built_for = endorser_bytes
        self.width = len(endorser_bytes)
        # Sentinel digests (1-based: the all-zero digest is the dummy
        # item for identities that fail to deserialize) recover the
        # item-lane -> endorser-index mapping from each policy's prepare.
        sentinels = {}
        signed = []
        for j, eb in enumerate(endorser_bytes):
            d = (j + 1).to_bytes(32, "big")
            sentinels[d] = j
            signed.append(SignedData(b"", eb, b"", digest=d))
        self._pendings = []
        for pol in policies:
            p = pol.prepare(signed)
            mapping = [sentinels.get(bytes(it.digest), -1) for it in p.items]
            self._pendings.append((p, mapping))
        self._decisions: dict[tuple, bool] = {}

    def decide(self, bits: tuple) -> bool:
        r = self._decisions.get(bits)
        if r is None:
            r = all(
                p.finish([bits[j] if j >= 0 else False for j in mapping])
                for p, mapping in self._pendings
            )
            self._decisions[bits] = r
        return r


class _PlanPending(PendingValidation):
    """Per-tx pending bound to a shared EndorsementPlan: `items` carry
    this tx's keys/digests/signatures for the endorsers that
    deserialize; `finish` folds the mask into the plan's memoized
    decision."""

    def __init__(self, plan: EndorsementPlan, lanes: list, items: list):
        self._plan = plan
        self._lanes = lanes  # endorser index per item position
        self.items = items

    def finish(self, mask) -> bool:
        bits = [False] * self._plan.width
        for pos, j in enumerate(self._lanes):
            bits[j] = bool(mask[pos])
        return self._plan.decide(tuple(bits))


class DeferredValidation(PendingValidation):
    """An action that writes a key whose VALIDATION_PARAMETER a block
    still in flight may change (`ValidationContext.pending`): WHICH
    policies decide it is not known while its block is collected.  Its
    signatures do not wait for that: `items` are a lane for each
    distinct endorser that deserializes, as `_plan_pending` makes them
    whatever its policies turn out to be, and they join the block's
    batch with everybody else's.  `finish` resolves the policies
    against the committed state, so the validator calls it only once
    block `waits_on` has landed (its commit durable and readable); the
    verdict is then the plan's, from the same mask."""

    def __init__(self, plugin: "BuiltinV20Plugin", ctx: ValidationContext,
                 waits_on: int, endorsers: tuple, lanes: list, items: list):
        self._plugin = plugin
        self._ctx = ctx
        self.waits_on = waits_on
        self._endorsers = endorsers  # distinct endorser identities, in order
        self._lanes = lanes  # endorser index per item position
        self.items = items

    def finish(self, mask) -> bool:
        ctx = self._ctx
        policies = self._plugin._policies(ctx)
        if isinstance(policies, _FailPending):
            return False
        plan = self._plugin._plan(
            policies, self._endorsers, ctx.policy_provider.deserializer
        )
        return _PlanPending(plan, self._lanes, self.items).finish(mask)


class BuiltinV20Plugin:
    """The default endorsement-policy plugin ("vscc"), key-level aware.
    Evaluates the single namespace in `ctx.namespace`; the validator
    dispatches one prepare per written namespace, as the reference
    dispatcher does.

    A plan is kept under (policies, the class of each distinct endorser
    in the endorsements' order): what the policies can observe of the
    endorsers (`_learn`), not who they are.  Plans live in two
    generations of at most `_PLAN_CAP` each: one asked for in the old
    generation moves to the young one; when the young one is full the
    old one is dropped and the young one takes its place.  So a plan in
    use survives every overflow, and one nobody asked for while a whole
    generation filled goes."""

    # plans a generation.  A plan of three or four endorsers weighs 2 KB
    # (a signature policy) to 5 KB (the channel's default over five
    # organisations) with its decisions (CHANGES.md, PR 53): a thousand
    # alive, the most, are 2-5 MB of a channel's validator
    _PLAN_CAP = 512
    # endorsers remembered (`_seen`, over all policy sets): run over, it
    # starts afresh; what it held is asked a question a principal again
    # and no plan is lost
    _SEEN_CAP = 4096

    def __init__(self, plans: bool = True):
        self._use_plans = plans
        # (policies, classes) -> plan: the young generation, the old
        self._plans: dict[tuple, EndorsementPlan] = {}
        self._old_plans: dict[tuple, EndorsementPlan] = {}
        # policies -> {endorser identity: (class, public key)}.  It
        # hangs on the policy objects as the plans do: a channel-config
        # update makes new ones, which find neither
        self._seen: dict[tuple, dict] = {}
        self._seen_count = 0
        # the plan cache's outcomes since this plugin was built: a plan
        # found, a plan built, of the plans found those that OTHER
        # identities built (an identity key would have built one more),
        # and the overflows that dropped plans: the young generation
        # was full and the old one, the plans nobody had asked for
        # since the overflow before, was let go (the validator reads
        # the four a block: collect{plan_hits, plan_misses,
        # plan_clears, plan_shared_hits},
        # validator_plan_cache_total{outcome}), and the seconds spent
        # learning endorsers' classes and building plans
        # (collect{plan_build_ms}): what a miss costs before its first
        # `decide`
        self.plan_hits = 0
        self.plan_misses = 0
        self.plan_clears = 0
        self.plan_shared_hits = 0
        self.plan_build_s = 0.0

    def _learn(self, pols: tuple, endorsers: tuple, deserializer) -> list:
        """(class, public key) of each endorser, remembered a policy
        set.  The class is what the policies can observe of the
        endorser, found by asking: whether it deserializes (its lane:
        the key is None where it does not), and the answer of each
        policy's OWN deserializer to every principal the policy lists.
        Two endorsers of one class are one to `prepare` and to the
        compiled closures, whoever they are; an OU, an identity or a
        combined principal splits them where the policy would.  Under
        a policy object that cannot list its principals the class is
        the identity itself."""
        t0 = time.perf_counter()
        seen = self._seen.setdefault(pols, {})
        questions = principals_of(pols)
        known = []
        for identity in endorsers:
            entry = seen.get(identity)
            if entry is None:
                if self._seen_count >= self._SEEN_CAP:
                    self._seen.clear()
                    self._seen_count = 0
                    seen = self._seen[pols] = {}
                entry = seen[identity] = _endorser(
                    identity, questions, deserializer
                )
                self._seen_count += 1
            known.append(entry)
        self.plan_build_s += time.perf_counter() - t0
        return known

    def _find(self, pols: tuple, endorsers: tuple, deserializer) -> tuple:
        """(the plan of the distinct endorsers' classes under `pols`,
        the (class, public key) of each): the plan from either
        generation, built where neither holds it; one that cannot be
        built raises."""
        try:
            seen = self._seen[pols]
            known = [seen[identity] for identity in endorsers]
        except KeyError:
            # a stranger among them: ask
            known = self._learn(pols, endorsers, deserializer)
        key = (pols, tuple([c for c, _k in known]))
        plan = self._plans.get(key)
        if plan is None:
            plan = self._old_plans.get(key)
            built = plan is None
            if built:
                plan = self._build(pols, endorsers)
            if len(self._plans) >= self._PLAN_CAP:
                if self._old_plans:
                    self.plan_clears += 1
                self._old_plans = self._plans
                self._plans = {}
            self._plans[key] = plan
            if built:
                self.plan_misses += 1
                return plan, known
        self.plan_hits += 1
        if plan.built_for != endorsers:
            self.plan_shared_hits += 1
        return plan, known

    def _plan(self, policies, endorsers: tuple, deserializer) -> EndorsementPlan:
        """The plan of (policies, distinct endorsers), from the cache
        where plans are kept; a plan that cannot be built raises."""
        if not self._use_plans:
            return self._build(policies, endorsers)
        return self._find(tuple(policies), endorsers, deserializer)[0]

    def _build(self, policies, endorsers: tuple) -> EndorsementPlan:
        t0 = time.perf_counter()
        plan = EndorsementPlan(policies, endorsers)
        self.plan_build_s += time.perf_counter() - t0
        return plan

    def _plan_pending(self, ctx: ValidationContext, policies) -> PendingValidation | None:
        """Plan-cached fast path; None when an endorsement lacks a
        precomputed digest (the generic per-tx path handles it)."""
        ends = ctx.endorsements
        if not self._use_plans or not ends:
            return None
        uniq: dict[bytes, SignedData] = {}
        for sd in ends:
            if sd.digest is None:
                return None
            if sd.identity not in uniq:
                uniq[sd.identity] = sd
        try:
            plan, known = self._find(
                tuple(policies), tuple(uniq), ctx.policy_provider.deserializer
            )
        except Exception as exc:
            # fall back to the per-tx generic path; the plan build
            # failure is logged so a policy that can never be
            # amortized is visible, not silently slow
            _logger.warning(
                "endorsement-plan build failed for %r (falling back "
                "to per-tx evaluation): %s", ctx.namespace, exc,
            )
            return None
        lanes, items = [], []
        for j, sd in enumerate(uniq.values()):
            public_key = known[j][1]
            if public_key is not None:
                lanes.append(j)
                items.append(
                    VerifyBatchItem(public_key, sd.digest, sd.signature)
                )
        return _PlanPending(plan, lanes, items)

    def _defer(self, ctx: ValidationContext, waits_on: int) -> DeferredValidation:
        """The action's lanes from its endorsements alone; its policies
        once block `waits_on` has landed."""
        uniq: dict[bytes, SignedData] = {}
        for sd in ctx.endorsements:
            if sd.identity not in uniq:
                uniq[sd.identity] = sd
        deserializer = ctx.policy_provider.deserializer
        lanes, items = [], []
        for j, sd in enumerate(uniq.values()):
            try:
                ident = deserializer.deserialize_identity(sd.identity)
            except Exception:
                # fabriclint: allow[exception-discipline] no lane: the
                # plan holds None for this endorser and its bit stays False
                continue
            lanes.append(j)
            if sd.digest is not None:
                items.append(
                    VerifyBatchItem(ident.public_key, sd.digest, sd.signature)
                )
            else:
                items.append(ident.verification_item(sd.data, sd.signature))
        return DeferredValidation(
            self,
            # at `finish` the block's own memo of the namespaces that
            # hold metadata is as stale as its window: look keys up
            dataclasses.replace(ctx, pending=None, ns_has_metadata=None),
            waits_on, tuple(uniq), lanes, items,
        )

    def prepare(self, ctx: ValidationContext) -> PendingValidation:
        policies = self._policies(ctx)
        if not isinstance(policies, list):
            return policies  # a _FailPending, or a DeferredValidation

        planned = self._plan_pending(ctx, policies)
        if planned is not None:
            return planned

        items: list = []
        pendings = []
        for pol in policies:
            pending = pol.prepare(ctx.endorsements)
            start = len(items)
            items.extend(pending.items)
            pendings.append((pending, (start, len(items))))
        return PendingValidation(pendings, items)

    def _policies(self, ctx: ValidationContext):
        """The policies that decide the action, each once: its keys'
        VALIDATION_PARAMETERs as committed, and the fallbacks of the
        keys without one.  A _FailPending where the action can never
        validate; a DeferredValidation where a key's parameter is not
        yet what it will be when the blocks before this one have
        landed."""
        try:
            fp = ctx.footprint or parse_footprint(ctx.rwset_bytes)
        except Exception as exc:
            return _FailPending(
                f"tx rwset for namespace {ctx.namespace!r} does not "
                f"parse: {exc}"
            )
        entry = fp.per_ns.get(
            ctx.namespace,
            {"pub": [], "meta": [], "coll": [], "coll_meta": [],
             "writes": False},
        )
        # Dedupe: a key counted once even when both written and
        # metadata-written; identical key-level policies evaluated once.
        pub_keys = set(entry["pub"]) | set(entry["meta"])
        coll_keys = set(entry["coll"]) | set(entry["coll_meta"])

        pending = ctx.pending
        if pending is not None:
            ns0 = ctx.namespace
            waits = [pending((ns0, k)) for k in pub_keys]
            waits.extend(pending((ns, key)) for _c, ns, key in coll_keys)
            waits = [b for b in waits if b is not None]
            if waits:
                return self._defer(ctx, max(waits))

        policies_by_bytes: dict[bytes, object] = {}
        fallbacks: dict[str, object] = {}  # "" = ccEP, else collection

        def resolve_fallback(coll: str) -> None:
            """Mirrors CheckCCEPIfNotChecked: cache the collection policy
            when the collection defines one, else the chaincode policy
            (each evaluated at most once)."""
            if coll and coll not in fallbacks:
                fallbacks[coll] = ctx.policy_provider.collection_policy(
                    ctx.namespace, coll
                )
            if coll and fallbacks.get(coll) is not None:
                return
            if "" not in fallbacks:
                fallbacks[""] = ctx.policy_provider.chaincode_policy(
                    ctx.namespace
                )

        # Namespaces whose committed state holds no metadata at all can
        # skip the per-key lookups: every key falls back, and the
        # fallback resolution is memoized, so the whole loop collapses
        # to one resolve per (namespace, collection).
        has_meta = ctx.ns_has_metadata
        check: list[tuple[str, str, str]] = []
        if pub_keys:
            if has_meta is not None and not has_meta(ctx.namespace):
                resolve_fallback("")
            else:
                check.extend(
                    ("", ctx.namespace, k) for k in sorted(pub_keys)
                )
        if coll_keys:
            skip_ns: dict[str, bool] = {}
            for coll, ns, key in sorted(coll_keys):
                sk = skip_ns.get(ns)
                if sk is None:
                    sk = has_meta is not None and not has_meta(ns)
                    skip_ns[ns] = sk
                if sk:
                    resolve_fallback(coll)
                else:
                    check.append((coll, ns, key))
        for coll, ns, key in check:
            raw = ctx.state_metadata(ns, key).get(VALIDATION_PARAMETER)
            if not raw:
                resolve_fallback(coll)
                continue
            if raw not in policies_by_bytes:
                pol = ctx.policy_provider.from_signature_policy_bytes(raw)
                if pol is None:
                    # unmarshalable key-level policy invalidates the tx
                    # (reference policyErr on Evaluate of broken vp)
                    return _FailPending(
                        f"key-level VALIDATION_PARAMETER on "
                        f"({ns!r}, {key!r}) does not parse as a "
                        f"SignaturePolicyEnvelope"
                    )
                policies_by_bytes[raw] = pol

        policies = list(policies_by_bytes.values())
        policies.extend(p for p in fallbacks.values() if p is not None)
        if not entry["writes"] and not policies:
            # no writes at all: the chaincode policy must still hold
            policies.append(
                ctx.policy_provider.chaincode_policy(ctx.namespace)
            )
        return policies


class PluginRegistry:
    """Maps validation-plugin names from chaincode definitions to plugin
    instances (reference txvalidator/plugin/plugin.go MapBasedMapper)."""

    def __init__(self, plans: bool = True):
        self._plugins: dict[str, object] = {"vscc": BuiltinV20Plugin(plans=plans)}

    def register(self, name: str, plugin) -> None:
        self._plugins[name] = plugin

    def plugin(self, name: str):
        p = self._plugins.get(name or "vscc")
        if p is None:
            raise KeyError(f"validation plugin {name!r} not registered")
        return p


__all__ = [
    "ValidationContext",
    "RwsetFootprint",
    "IllegalWritesetError",
    "parse_footprint",
    "PendingValidation",
    "DeferredValidation",
    "PolicyProvider",
    "BuiltinV20Plugin",
    "PluginRegistry",
]
