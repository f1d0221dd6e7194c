"""Block validation with whole-block batched signature verification.

This is the north-star rework (BASELINE.json): the reference's
txvalidator v20 (core/committer/txvalidator/v20/validator.go:180-265)
validates each tx in its own goroutine, and every tx serially verifies
1 creator signature + K endorsement signatures through per-identity
`msp.Identity.Verify` calls.  Here validation is three phases:

  1. **Collect** (host): per-tx syntactic checks (envelope/header shape,
     channel id, tx-id binding, duplicate tx ids, proposal-hash binding —
     reference core/common/validation/msgvalidation.go:26-330), identity
     deserialization/validation, and endorsement-policy *preparation*
     (fabric_tpu.policies two-phase protocol).  No crypto.
  2. **Verify** (device): ONE `CSP.verify_batch` over every creator and
     endorsement signature of the whole block, and, where the block
     carries anonymous (Idemix) creators, ONE batched verify of their
     credential proofs and pseudonym signatures beside it (the two
     kinds of lane, `_ItemSink` and `_IdemixSink`; both go out at the
     end of collect, both are waited for in verify_wait).
  3. **Finish** (host): creator mask -> BAD_CREATOR_SIGNATURE; policy
     closures over the mask -> ENDORSEMENT_POLICY_FAILURE; MVCC runs later
     in the ledger commit (kvledger).

The endorsement-policy check is dispatched through a pluggable map like
the reference's validation-plugin framework (core/handlers/validation);
the builtin plugin evaluates the channel/chaincode endorsement policy.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import os
import threading
import time

from fabric_tpu.common import tracing, workpool
from fabric_tpu.devtools import faultline, knob_registry
from fabric_tpu.ledger.txmgmt import VALIDATION_PARAMETER
from fabric_tpu.peer.validation_plugins import (
    IllegalWritesetError,
    PluginRegistry,
    PolicyProvider,
    ValidationContext,
    parse_footprint,
)
from fabric_tpu.protos.common import common_pb2
from fabric_tpu.protos.peer import (
    chaincode_event_pb2,
    proposal_pb2,
    proposal_response_pb2,
    transaction_pb2,
)
from fabric_tpu import protoutil
from fabric_tpu.protoutil import SignedData

V = transaction_pb2

# blocks below this tx count collect serially even when a pool width is
# configured — the chunking overhead would outweigh the parse fan-out
_PARALLEL_MIN_TXS = 32


class _ItemSink:
    """Global verify-item collector with structural dedup.

    An implicit-meta policy (e.g. MAJORITY Endorsement over N orgs)
    prepares every sub-policy against the same endorsement set; without
    interning, each sub-policy re-verifies the same (key, digest, sig)
    triples — the reference pays exactly that cost in repeated
    identity.Verify calls (common/policies/policy.go:365 per
    EvaluateSignedData).  Here identical triples collapse to ONE device
    lane and every pending keeps index lists into the shared mask."""

    def __init__(self, dedup: bool = True):
        self.items: list = []
        self._index: dict = {}
        self._dedup = dedup
        # a lone block's first provider chunk, once handed over: its
        # lanes (items[:early_lanes]) and their collector
        self.early_lanes = 0
        self._early = None
        # the other kind of lane: anonymous creators' deferred items
        self.idemix = _IdemixSink()

    def add(self, item) -> int:
        if not self._dedup:
            self.items.append(item)
            return len(self.items) - 1
        k = (item.key.x, item.key.y, item.digest, item.signature)
        i = self._index.get(k)
        if i is None:
            i = len(self.items)
            self._index[k] = i
            self.items.append(item)
        return i

    def add_many(self, items) -> list[int]:
        return [self.add(it) for it in items]

    def hand_early(self, csp, lanes: int) -> None:
        """Hand the provider the first `lanes` items now, to be
        dispatched before this returns; the sink goes on filling.  Its
        indices stay what they were: the sink only appends, and a later
        duplicate of an item handed early gets the early index."""
        self._early = csp.verify_batch_async(self.items[:lanes], flush=True)
        self.early_lanes = lanes

    def hand_over(self, csp):
        """The collector of the block's mask, over every item in sink
        order: one batch, or after `hand_early` the early chunk's mask
        and the rest's joined."""
        early, rest = self._early, self.items
        if early is None:
            return csp.verify_batch_async(rest) if rest else (lambda: [])
        rest = rest[self.early_lanes:]
        if not rest:
            return early
        tail = csp.verify_batch_async(rest)

        def collect():
            # the tail first: its first collector is what dispatches it
            # (the early chunk's flush is out already), and the device
            # runs the two in order
            mask = tail()
            return early() + mask

        return collect


class _IdemixSink:
    """A block's deferred Idemix items beside the ECDSA sink: per
    Idemix MSP (an issuer key each) the credential-proof and
    pseudonym-signature items of its creators, in tx order.  Nothing
    is interned: every creator is a fresh pseudonym."""

    def __init__(self):
        self.by_msp: dict = {}  # IdemixMSP -> [proof, nym, proof, nym, ..]

    def add(self, creator, items: tuple) -> tuple:
        """(msp, index of the proof item; the nym item follows it)."""
        lst = self.by_msp.get(creator.msp)
        if lst is None:
            lst = self.by_msp[creator.msp] = []
        lst.extend(items)
        return creator.msp, len(lst) - 2

    def dispatch(self) -> list:
        """One asynchronous batched verify an MSP: [(msp, collector)]."""
        return [
            (msp, msp.verify_items_async(items))
            for msp, items in self.by_msp.items()
        ]


class _IdemixCreator:
    """What `_parse_tx` hands `_integrate_tx` for an anonymous creator
    where an X.509 creator has its `VerifyBatchItem`."""

    __slots__ = ("creator", "items")

    def __init__(self, creator, payload: bytes, signature: bytes):
        self.creator = creator
        self.items = creator.deferred_items(payload, signature)


@dataclasses.dataclass
class _TxWork:
    """Per-tx deferred crypto: creator item index + per-namespace plugin
    pendings, plus the state-metadata footprint for key-level
    endorsement conflict detection."""

    creator_item: int | None = None
    idemix_item: tuple | None = None
    # an anonymous creator: (msp, index) of its credential-proof item in
    # the block's Idemix sink, the pseudonym-signature item right after
    pendings: list = dataclasses.field(default_factory=list)
    # [(PendingValidation, [item index, ...])] — one per written namespace
    touched_keys: frozenset = frozenset()  # {(ns_or_hashns, key)}
    rwset: bytes | None = None
    # marshaled TxReadWriteSet, handed to the committer so the ledger
    # commit skips re-walking every envelope (kvledger extract_rwsets)
    footprint: object | None = None
    # the ONE RwsetFootprint parse of this tx's rwset — carries the
    # decoded KVRWSets down the commit path (MVCC + history) so nothing
    # downstream re-unmarshals the rwset wire format
    txid: str | None = None
    # chdr.tx_id when the envelope parsed far enough to yield one; the
    # block store indexes from these instead of re-parsing every envelope
    meta_keys: frozenset = frozenset()
    # keys whose VALIDATION_PARAMETER this tx rewrites; once the tx is
    # VALID, later in-block txs touching them are invalidated
    deferred: bool = False
    # one of its pendings is a DeferredValidation: its policies are
    # resolved in the policy stage, once the block it waits on landed


class _BlockWorks(list):
    """A block's `_TxWork`s in tx order, and beside them what of the
    block's key-level decisions waits for an earlier block's commit
    (`_Deferral`; None for a block that defers nothing: every block of
    a channel without key-level policies, and every lone block) and
    the committed state metadata its plugins look up (`_KeyLevelMemo`):
    both stages of THIS block read it, whatever blocks are collected
    between them."""

    __slots__ = ("deferral", "keylevel")

    def __init__(self, n: int, keylevel: "_KeyLevelMemo"):
        super().__init__([_TxWork() for _ in range(n)])
        self.deferral = None
        self.keylevel = keylevel


@dataclasses.dataclass
class _Deferral:
    window: "_KeyWindow"
    waits_on: int  # the newest block a deferred decision depends on
    txs: int  # the block's transactions whose decision is deferred


class _KeyLevelCount:
    """One stage's state-metadata lookups (`_KeyLevelMemo.lookup`): how
    many the plugins asked, how many of them went to the ledger one by
    one, the pairs the stage's bulk read fetched, the wall of both
    kinds of read, and the distinct parameters the lookups met."""

    __slots__ = ("reads", "point_reads", "bulk_keys", "seconds", "params")

    def __init__(self):
        self.reads = 0
        self.point_reads = 0
        self.bulk_keys = 0
        self.seconds = 0.0
        self.params: set = set()


class _KeyLevelMemo:
    """ONE block's view of the committed state metadata: what its
    plugins' `ValidationContext.state_metadata` answers from.  A stage
    fills it with one bulk read of the pairs its transactions will ask
    for (`fill`), and `lookup` answers from memory; a pair it does not
    hold (a lane parsed inline, the Python collector, a custom plugin's
    own key) is read from the ledger as it always was, and remembered.
    Flags never depend on what a bulk read covered.

    `pending` is the block's `_KeyWindow.pending` view: a pair it
    names is neither fetched nor remembered while the block is
    collected, since the commit that decides its parameter has not
    landed.  `landed()` opens the policy stage, which the validator
    enters only after that commit: from then on every pair is read.
    `remember=False` (faithful mode) keeps a ledger read a lookup."""

    __slots__ = ("_ledger", "_held", "_pending", "count")

    def __init__(self, ledger, pending, remember: bool):
        self._ledger = ledger
        self._held: dict | None = {} if remember else None
        self._pending = pending
        self.count = _KeyLevelCount()

    def lookup(self, ns: str, key: str) -> dict[str, bytes]:
        count = self.count
        count.reads += 1
        held = self._held
        pair = (ns, key)
        entries = None if held is None else held.get(pair)
        if entries is None:
            t0 = time.perf_counter()
            entries = self._ledger.get_state_metadata(ns, key)
            count.point_reads += 1
            count.seconds += time.perf_counter() - t0
            if held is not None and self._settled(pair):
                held[pair] = entries
        raw = entries.get(VALIDATION_PARAMETER)
        if raw:
            count.params.add(raw)
        return entries

    def _settled(self, pair: tuple) -> bool:
        pending = self._pending
        return pending is None or pending(pair) is None

    def fill(self, pairs) -> None:
        """One bulk read of those of `pairs` that are settled and not
        held yet."""
        held = self._held
        if held is None:
            return
        want = [p for p in pairs if p not in held and self._settled(p)]
        if not want:
            return
        t0 = time.perf_counter()
        got = self._ledger.get_state_metadata_many(want)
        held.update(got)
        self.count.bulk_keys += len(got)
        self.count.seconds += time.perf_counter() - t0

    def landed(self) -> _KeyLevelCount:
        """The commit the block's deferred decisions waited for has
        landed: nothing is pending any more, and the policy stage
        counts its own lookups."""
        self._pending = None
        self.count = _KeyLevelCount()
        return self.count


def _no_metadata(ns: str) -> bool:
    """`ValidationContext.ns_has_metadata` of a block whose collect
    found the committed state without any."""
    return False


def _metadata_pairs(footprints, may_hold=None) -> dict:
    """The (ns_or_hashns, key) pairs whose committed metadata the
    builtin plugin looks up for these footprints (`_policies`: every
    key a namespace's action writes or re-metas, public or of a
    collection), first seen first; `may_hold` leaves out the
    namespaces whose lookups the plugin skips."""
    pairs: dict = {}
    for fp in footprints:
        for ns, entry in fp.per_ns.items():
            if not entry["writes"]:
                continue
            if may_hold is None or may_hold(ns):
                for k in entry["pub"]:
                    pairs[(ns, k)] = None
                for k in entry["meta"]:
                    pairs[(ns, k)] = None
            for hashed in (entry["coll"], entry["coll_meta"]):
                for _coll, hns, k in hashed:
                    if may_hold is None or may_hold(hns):
                        pairs[(hns, k)] = None
    return pairs


class _KeyWindow:
    """The keys whose VALIDATION_PARAMETER the blocks in flight in ONE
    pipeline may still change: block number -> its (ns_or_hashns, key)
    pairs, from the end of the block's collect until its commit has
    landed (durable and readable: `_Landed`).  Only a block that may
    change a parameter (a metadata write, or a delete, of a transaction
    its collect did not refuse) is ever in it, so on a channel without
    key-level policies it stays empty and nobody takes its lock.

    Upstream validates block k+1 after it committed block k; here k+1
    is collected while k is verified or committed.  A transaction of
    k+1 that writes a key in this window is therefore DECIDED only once
    the newest block that may change the key's parameter has landed
    (its signatures go to the device with everybody else's), and reads
    the parameter then; every other transaction reads what is
    committed, which no block in flight changes."""

    def __init__(self):
        self._cond = threading.Condition()
        self._writes: dict[int, frozenset] = {}
        self._failed: BaseException | None = None

    def pending(self) -> dict | None:
        """(ns, key) -> the newest block in flight that may change its
        parameter, as of now; None while no block in flight changes
        any.  A block takes this ONCE, before it reads anything of the
        state: what has left the window by then is committed and
        readable, what is still in it stays pending for the whole
        block."""
        if not self._writes:
            return None
        with self._cond:
            out: dict = {}
            for num in sorted(self._writes):
                out.update(dict.fromkeys(self._writes[num], num))
        return out or None

    def add(self, num: int, keys: frozenset) -> None:
        with self._cond:
            self._writes[num] = keys

    def land(self, num: int) -> None:
        if num in self._writes:
            with self._cond:
                self._writes.pop(num, None)
                self._cond.notify_all()

    def abort(self, exc: BaseException) -> None:
        """The commits this window waits for will not come."""
        with self._cond:
            self._failed = exc
            self._cond.notify_all()

    def await_landed(self, num: int) -> None:
        with self._cond:
            while num in self._writes:
                if self._failed is not None:
                    raise self._failed
                self._cond.wait()


class _Landed:
    """What `validate_pipeline` hands its `release` for a yielded
    block: the caller runs it once the block's commit has landed, and
    the block's txids leave the duplicate window (the ledger's index
    holds them) and its keys the key window (the state holds their
    parameters).  `abort(exc)` is for a caller whose commit failed: a
    validator that waits for a commit in this pipeline wakes with
    `exc` instead of waiting for ever."""

    __slots__ = ("_seen", "_txids", "_window", "_num")

    def __init__(self, seen: set, txids: set, window: _KeyWindow, num: int):
        self._seen, self._txids = seen, txids
        self._window, self._num = window, num

    def __call__(self) -> None:
        self._seen.difference_update(self._txids)
        self._window.land(self._num)

    def abort(self, exc: BaseException) -> None:
        self._window.abort(exc)


class KeyLevelTally:
    """The process's key-level (state-based) endorsement work, from its
    start: `lookups` of committed state metadata, decisions `deferred`
    to the policy stage, the `waits` for a commit those took, and of
    the last RECENT blocks that went through a pipeline their number
    and their deferred decisions, oldest first.  A benchmark's
    condition reads it (benchmarks/conditions/keylevel-shape.py); an
    operator reads the same on /metrics
    (validator_keylevel_lookups_total, validator_keylevel_deferred_total)."""

    RECENT = 16384

    def __init__(self):
        self._lock = threading.Lock()
        self.lookups = 0
        self.deferred = 0
        self.waits = 0
        self._recent: collections.deque = collections.deque(maxlen=self.RECENT)

    def note(self, lookups: int = 0, deferred: int = 0, waits: int = 0) -> None:
        with self._lock:
            self.lookups += lookups
            self.deferred += deferred
            self.waits += waits

    def block_done(self, num: int, deferred: int) -> None:
        self._recent.append((num, deferred))  # a deque's append is atomic

    def snapshot(self) -> dict:
        with self._lock:
            return {"lookups": self.lookups, "deferred": self.deferred,
                    "waits": self.waits, "recent_blocks": list(self._recent)}


_KEYLEVEL = KeyLevelTally()


def keylevel_tally() -> dict:
    """See KeyLevelTally."""
    return _KEYLEVEL.snapshot()


class ToleratedTally:
    """The endorsement lanes the device's mask refused in transactions
    that came out of the policy stage VALID all the same (their policy
    was met without them), from the process's start, and of the last
    RECENT blocks their number and that count, oldest first.  A
    benchmark's condition reads it
    (benchmarks/conditions/mixedcc-shape.py); an operator reads the
    same on /metrics (validator_tolerated_bad_endorsements_total)."""

    RECENT = KeyLevelTally.RECENT

    def __init__(self):
        self.lanes = 0
        self._recent: collections.deque = collections.deque(maxlen=self.RECENT)

    def block_done(self, num: int, lanes: int) -> None:
        # one thread finishes a validator's blocks, in order
        self.lanes += lanes
        self._recent.append((num, lanes))

    def snapshot(self) -> dict:
        return {"tolerated_bad_lanes": self.lanes,
                "recent_blocks": list(self._recent)}


_TOLERATED = ToleratedTally()


def tolerated_tally() -> dict:
    """See ToleratedTally."""
    return _TOLERATED.snapshot()


def _refused_lanes(mask: list) -> set:
    """The indices of a mask's false bits (`list.index`: a scan at C
    speed over a mask that is nearly all true)."""
    refused: set = set()
    if not isinstance(mask, list):
        mask = list(mask)
    j = -1
    try:
        while True:
            j = mask.index(False, j + 1)
            refused.add(j)
    except ValueError:
        return refused


def _commit_assist(works: list, envs: list, bspan):
    """ONE per-block assist bundle for KVLedger.commit, from either
    entry point (validate, validate_pipeline): the marshaled rwsets,
    the already-decoded footprints (MVCC + history reuse), the txids
    (block-store index), and the envelope bytes (the store
    splice-serializes instead of re-encoding 1-2 MB).  A lane the
    collect could not parse leaves None at its position, and the ledger
    falls back to its own parse there."""
    from fabric_tpu.ledger.kvledger import CommitAssist

    return CommitAssist(
        rwsets=[w.rwset for w in works],
        footprints=[w.footprint for w in works],
        txids=[w.txid for w in works],
        env_bytes=envs,
        # carries the block's trace root to whoever commits (the
        # committer thread in store_stream) so the commit stages join
        # the same per-block trace; None while tracing is disarmed
        trace_ctx=bspan.ctx,
    )


@dataclasses.dataclass
class _ParsedTx:
    """The shared-state-free half of one tx's collect, produced by
    ``_parse_tx`` — safe to compute on any pool worker.  Everything
    order-dependent (sink index assignment, the duplicate-txid window,
    policy prepare against the per-block plan caches) happens later in
    ``_integrate_tx``, strictly in tx order, so a parallel collect is
    byte-identical to the serial one by construction.

    The three flag slots mirror the serial check sequence exactly:
    ``pre_flag`` fires before the creator item would join the sink,
    ``mid_flag`` after the creator item but before the duplicate-txid
    stage (so the txid never registers), and ``post_flag`` after the
    txid registered (so a later duplicate still collides with it)."""

    hdr_txid: str | None = None  # chdr.tx_id for the block-store index
    pre_flag: int | None = None
    creator_item: object | None = None
    mid_flag: int | None = None
    txid: str | None = None  # reached the duplicate-check stage
    dup_checked: bool = False  # serial path: dup probe already ran at
    # parse time (back-to-back with integrate) — don't re-probe
    post_flag: int = V.VALID
    signed: list = dataclasses.field(default_factory=list)
    cc_id: str = ""
    rwset: bytes = b""
    footprint: object | None = None  # parsed RwsetFootprint when usable


def _both(ecdsa, idemix: list):
    """A block's collector when it holds both kinds of lane: the ECDSA
    mask, with the Idemix masks (by MSP) riding on it."""

    def collect():
        mask = _MaskWithIdemix(ecdsa())
        mask.idemix = {msp: c() for msp, c in idemix}
        return mask

    return collect


class _MaskWithIdemix(list):
    """The ECDSA mask of a block that also carries anonymous creators."""

    idemix: dict


class _CreatorMemo(dict):
    """A block's creators: serialized identity -> the identity, or None
    for one the channel's MSPs refuse, and what filling it cost.  The
    memo is per block, so `len()` is the block's distinct creators;
    `validations` counts the identities deserialised and validated
    afresh (the memo's misses: every creator once, and in faithful
    mode every transaction), `seconds` their wall, and `chain_batch`
    those whose chain signature the block's one native call decided
    (`_creators_ahead`; 0 where each was checked in place), and
    `native_parse` those whose certificate the native reader read in
    the call beside it (0 where each was parsed in place)."""

    __slots__ = ("validations", "seconds", "chain_batch", "native_parse")

    def __init__(self):
        super().__init__()
        self.validations = 0
        self.seconds = 0.0
        self.chain_batch = 0
        self.native_parse = 0


class TxValidator:
    """Reference TxValidator.Validate equivalent; `Validate` mutates the
    block's TRANSACTIONS_FILTER metadata like the reference does.

    Endorsement checking dispatches through the validation-plugin
    registry once per written namespace (reference plugindispatcher
    dispatcher.go:190 validates *each* written namespace against its own
    chaincode's plugin and policy); the builtin plugin implements
    chaincode-level, collection-level, and key-level (state-based)
    endorsement.  A tx touching a key whose VALIDATION_PARAMETER an
    earlier VALID tx in the same block rewrote is invalidated, exactly
    like the reference's ValidationParameterUpdatedError
    (statebased/vpmanagerimpl.go:219, validator_keylevel.go:45)."""

    def __init__(
        self,
        channel_id: str,
        ledger,
        bundle,
        csp,
        definition_provider=None,
        plugin_registry: PluginRegistry | None = None,
        faithful: bool = False,
        collect_pool=None,
        collect_width: int | None = None,
        metrics=None,
    ):
        """`faithful=True` reproduces the reference's validation cost
        model for baseline measurement: no verify-item interning, no
        endorsement-plan caching, and no per-block creator memo, so
        every sub-policy re-verifies its signatures per tx exactly as
        common/policies/policy.go:365 does.  (Block digesting still
        runs in the shared native collect pass — hashing cost is
        charged identically to both paths.)  Results are identical;
        only the work amortization differs.

        `collect_width` > 1 fans the per-tx collect's parse half across
        `collect_pool` (default: the process workpool) in that many
        deterministic chunks; None reads FABRIC_TPU_COLLECT_POOL, 0
        keeps collect serial.  Faithful mode is always serial — the
        baseline must reproduce the reference's cost model.

        `metrics` (a common.metrics.ValidateMetrics) adds per-stage
        collect/verify_wait/policy histograms on /metrics; the
        cumulative splits are always kept in validate_stage_seconds
        (benchmarks/run.py reads them)."""
        self.channel_id = channel_id
        self._ledger = ledger
        self._bundle = bundle
        self._csp = csp
        self._definitions = definition_provider
        self._faithful = faithful
        # committed-state metadata oracle (None on ledgers without one):
        # lets the builtin plugin skip per-key VALIDATION_PARAMETER
        # lookups for namespaces that have never stored metadata.
        # Memoized per block (_start_block) — statedb re-loads its
        # namespace set at every commit, so a fresh memo per block sees
        # commits land while staying O(1) per tx.
        self._ns_meta = (
            None
            if faithful
            else getattr(ledger, "may_have_state_metadata", None)
        )
        self._ns_meta_block = None  # per-block memoized wrapper
        # whether ANY namespace of the committed state holds metadata,
        # asked once a block: only then is a block's collect worth a
        # pass over its footprints and a bulk read before the plugins
        # run (`_KeyLevelMemo.fill`).  Faithful mode reads a key a
        # lookup, as upstream's GetStateMetadata does.
        self._holds_meta = (
            None
            if faithful
            else getattr(ledger, "holds_state_metadata", None)
        )
        # key-level endorsement, per block (see _KeyWindow): what the
        # blocks in flight may still change, as the block in hand found
        # it when its collect began (`dict.get`; None: nothing), the
        # keys whose parameter the block in hand may change itself, and
        # the committed-metadata lookup of the block in hand's memo
        # (its `ValidationContext`s keep it: the block's policy stage
        # runs after later blocks' collects have replaced it here)
        self._pending_block = None
        self._param_keys: set = set()
        self._metadata_block = None
        self._registry = plugin_registry or PluginRegistry(plans=not faithful)
        self._policy_provider = PolicyProvider(
            bundle.policy_manager, bundle.msp_manager, definition_provider
        )
        # parallel-collect configuration: a width of 0/1 keeps collect
        # serial; widths are chunk counts over the shared bounded pool
        # (workpool.run_chunked), so results merge in tx order.
        # `_collect_explicit` records whether the width was CHOSEN
        # (ctor arg or env knob) rather than defaulted: the native-
        # assisted path only fans out when chosen — its remaining
        # per-tx host work is a GIL-held protobuf decode (the C++
        # walker already did the GIL-releasing hashing), measured
        # net-negative under default fan-out — while the pure-Python
        # path's heavy stages (hash_batch over multi-KB messages,
        # creator deserialization) release the GIL and win.
        env_set = bool(
            knob_registry.raw("FABRIC_TPU_COLLECT_POOL").strip()
        )
        self._collect_explicit = collect_width is not None or env_set
        if faithful:
            self._collect_width = 0
        elif collect_width is not None:
            self._collect_width = max(0, collect_width)
        else:
            self._collect_width = workpool.stage_width(
                "FABRIC_TPU_COLLECT_POOL"
            )
        self._collect_pool = collect_pool
        # cumulative per-stage validate timing (seconds): host collect,
        # device-verify wait, and host policy/finish — the validate-side
        # counterpart of KVLedger.commit_stage_seconds
        self.validate_stage_seconds: dict[str, float] = {}
        self._metrics = metrics
        # blocks whose collect actually fanned out (the tier-1 smoke
        # asserts the parallel path ran, not just that flags matched)
        self.parallel_collect_blocks = 0
        # lone blocks whose first provider chunk went to the device
        # while the rest was still collected (`_ItemSink.hand_early`)
        self.early_flush_blocks = 0
        # the CommitAssist of the block validate() saw last, until
        # take_assist() hands it over
        self._assist = None
        # of the block in hand's collect: the plugin prepares (one a
        # transaction and written namespace) and those of them whose
        # namespace a committed definition, not the channel's default,
        # decided (collect{namespace_prepares, definitions_resolved})
        self._namespace_prepares = 0
        self._definitions_resolved = 0

    def _plan_counts(self) -> tuple:
        """The builtin plugin's plan-cache outcomes so far (hits,
        misses, clears, and the hits on a plan other identities built)
        and the seconds it spent building plans; zeros where another
        plugin stands under its name."""
        plugin = self._registry.plugin("vscc")
        return (getattr(plugin, "plan_hits", 0),
                getattr(plugin, "plan_misses", 0),
                getattr(plugin, "plan_clears", 0),
                getattr(plugin, "plan_shared_hits", 0),
                getattr(plugin, "plan_build_s", 0.0))

    def _count_keylevel(self, count: _KeyLevelCount, deferred: int = 0,
                        waits: int = 0) -> None:
        """A stage's key-level work onto the process's tally and the
        peer's /metrics; called only by a block that did any."""
        _KEYLEVEL.note(count.reads, deferred, waits)
        m = self._metrics
        if m is None:
            return
        if count.reads:
            m.keylevel_lookups.With("channel", self.channel_id).add(count.reads)
        if count.point_reads:
            m.keylevel_point_reads.With(
                "channel", self.channel_id
            ).add(count.point_reads)
        if deferred:
            m.keylevel_deferred.With("channel", self.channel_id).add(deferred)

    def _count_plans(self, before: tuple) -> tuple:
        """The plan cache's outcomes and build seconds since `before`,
        the outcomes onto /metrics."""
        delta = tuple(a - b for a, b in zip(self._plan_counts(), before))
        m = self._metrics
        if m is not None:
            # the four outcomes ("shared" is a part of "hit"); the
            # fifth of `delta` is seconds
            for outcome, n in zip(("hit", "miss", "cleared", "shared"), delta):
                if n:
                    m.plan_cache.With("outcome", outcome).add(n)
        return delta

    def _plugin_for(self, namespace: str):
        name = "vscc"
        if self._definitions is not None:
            info = self._definitions.validation_info(namespace)
            if info is not None:
                name = info[0] or "vscc"
                self._definitions_resolved += 1
        return self._registry.plugin(name)

    # -- phase 1: per-tx syntactic validation + collection ----------------

    def _creator_identity(self, creator_bytes: bytes, memo: "_CreatorMemo",
                          lock: threading.Lock | None = None):
        """Deserialize + channel-validate a creator, memoized per block —
        where a channel has a handful of clients a 1000-tx block
        carries a handful of distinct certs, and the per-call MSP cache
        still pays a lock + LRU shuffle per tx.  Where it has thousands
        (one Fabric CA enrolment certificate a user) a block carries
        some 500, which `CachedMSP`'s 100 entries do not save either
        (benchmarks/configs/manyclients-10k.json; the memo counts
        them): the native-walker collect fills the memo for the whole
        block first (`_creators_ahead`) and this only hits; a miss
        here, one identity at a time (a parse, one OpenSSL chain
        signature, the validity, CRL and role checks), is the Python
        collector's, a lane the walker handed back, or faithful mode.
        Returns None when invalid.  Faithful mode bypasses the memo
        (the reference pays this per tx).

        `lock` guards the memo's WRITE when parallel collect workers
        share it; the hit-path read is deliberately lock-free (a dict
        probe is atomic under the GIL, and entries are write-once) so
        the 99%-hit case costs nothing extra.  Two workers may race to
        compute the same creator — setdefault keeps the first result,
        and either result is structurally identical, so downstream sink
        dedup (which keys on key/digest/signature bytes, never object
        identity) is unaffected."""
        if not self._faithful and creator_bytes in memo:
            return memo[creator_bytes]
        t0 = time.perf_counter()
        mgr = self._bundle.msp_manager
        try:
            creator_of = getattr(mgr, "deserialize_creator", None)
            if creator_of is not None:
                # an anonymous (Idemix) creator comes back with its
                # credential proof deferred: it joins the block's
                # Idemix sink, as an X.509 creator's signature joins
                # the ECDSA sink
                ident = creator_of(creator_bytes)
            else:
                ident = mgr.deserialize_identity(creator_bytes)
                mgr.validate(ident)
        except Exception:
            ident = None
        dt = time.perf_counter() - t0
        with lock if lock is not None else contextlib.nullcontext():
            memo.validations += 1
            memo.seconds += dt
            return memo.setdefault(creator_bytes, ident)

    def _creators_ahead(self, creators, memo: "_CreatorMemo") -> None:
        """Fill the block's memo with its distinct creators (first-seen
        order) in ONE call on the channel's MSPs: each deserialised and
        validated as `_creator_identity` would, one identity at a time,
        but with the certificates the MSP caches do not hold read in
        one native call and all their chain signatures checked in
        another, neither holding the interpreter's lock (the
        committer's thread gets it meanwhile).  An anonymous (Idemix)
        creator goes through its own door inside that call, by its MSP.
        Which transaction is refused for which reason stays the per-tx
        loop's: this only decides who a creator is."""
        mgr = self._bundle.msp_manager
        batch = getattr(mgr, "deserialize_creators", None)
        if batch is None:
            return
        t0 = time.perf_counter()
        distinct = list(dict.fromkeys(creators))
        read0 = mgr.tally()["creator_parses"]["native"]
        idents, decided = batch(distinct)
        memo.update(zip(distinct, idents))
        memo.validations += len(distinct)
        memo.chain_batch += decided
        memo.native_parse += mgr.tally()["creator_parses"]["native"] - read0
        memo.seconds += time.perf_counter() - t0

    def _collect_tx(self, env_bytes: bytes, seen_txids: set, sink: _ItemSink, work: _TxWork, memo: dict) -> int:
        """Serial per-tx collect: the pure parse half composed with the
        order-dependent integration half (the parallel path runs the
        same two halves with the parses fanned out).  Serial-only
        optimization: the duplicate-txid probe runs INSIDE the parse,
        right where the old single-pass code checked it, so a duplicate
        skips the expensive transaction decode/hash/footprint tail —
        safe here because parse and integrate run back-to-back with no
        interleaving, so the window cannot change in between."""
        return self._integrate_tx(
            self._parse_tx(
                env_bytes, memo,
                dup_check=lambda t: (
                    t in seen_txids or self._ledger.tx_id_exists(t)
                ),
            ),
            seen_txids, sink, work,
        )

    def _parse_tx(self, env_bytes: bytes, memo: dict,
                  memo_lock: threading.Lock | None = None,
                  dup_check=None) -> _ParsedTx:
        """The shared-state-free half of one tx's collect — protobuf
        decode, creator deserialization, digest computation, rwset
        footprint parse.  Touches no sink, no txid window, and no policy
        caches, so any pool worker may run it; every check lands in the
        _ParsedTx flag slot matching its exact position in the serial
        sequence (see _ParsedTx)."""
        p = _ParsedTx()
        # chaos seam: faultfuzz campaigns crash/delay inside the
        # (possibly pooled) collect stage through this point
        faultline.point("collect.tx")
        try:
            env = common_pb2.Envelope.FromString(env_bytes)
            if not env.payload:
                p.pre_flag = V.NIL_ENVELOPE
                return p
            payload = common_pb2.Payload.FromString(env.payload)
            chdr = common_pb2.ChannelHeader.FromString(payload.header.channel_header)
            shdr = common_pb2.SignatureHeader.FromString(payload.header.signature_header)
        except Exception:
            p.pre_flag = V.BAD_PAYLOAD
            return p
        p.hdr_txid = chdr.tx_id or None  # for the block store's txid index
        if not shdr.creator or not shdr.nonce:
            p.pre_flag = V.BAD_COMMON_HEADER
            return p
        if chdr.channel_id != self.channel_id:
            p.pre_flag = V.BAD_CHANNEL_HEADER
            return p
        if chdr.epoch != 0:
            p.pre_flag = V.BAD_CHANNEL_HEADER
            return p

        # creator must deserialize and be valid under a channel MSP
        creator = self._creator_identity(shdr.creator, memo, memo_lock)
        if creator is None:
            p.pre_flag = V.BAD_CREATOR_SIGNATURE
            return p
        # creator signature over the payload bytes (checkSignatureFromCreator)
        if getattr(creator, "proof_deferred", False):
            p.creator_item = _IdemixCreator(
                creator, env.payload, env.signature
            )
        else:
            p.creator_item = creator.verification_item(
                env.payload, env.signature
            )

        if chdr.type == common_pb2.CONFIG:
            # config txs are validated/applied by the channel config engine
            p.mid_flag = V.VALID
            return p
        if chdr.type != common_pb2.ENDORSER_TRANSACTION:
            p.mid_flag = V.UNKNOWN_TX_TYPE
            return p

        # tx-id binding (CheckTxID); the duplicate check itself runs at
        # integration time, in tx order, against the live window
        if not chdr.tx_id or not protoutil.check_tx_id(chdr.tx_id, shdr.nonce, shdr.creator):
            p.mid_flag = V.BAD_PROPOSAL_TXID
            return p
        p.txid = chdr.tx_id
        if dup_check is not None:
            # serial fast path (see _collect_tx): the one dup probe
            # runs here — a known duplicate skips the expensive tail
            # like the old single-pass collect did, and a clean txid is
            # NOT re-probed at integration
            p.dup_checked = True
            if dup_check(chdr.tx_id):
                p.post_flag = V.DUPLICATE_TXID
                return p

        try:
            tx = transaction_pb2.Transaction.FromString(payload.data)
            if not tx.actions:
                p.post_flag = V.NIL_TXACTION
                return p
            cap = transaction_pb2.ChaincodeActionPayload.FromString(tx.actions[0].payload)
            prp_bytes = cap.action.proposal_response_payload
            prp = proposal_response_pb2.ProposalResponsePayload.FromString(prp_bytes)
            action = proposal_pb2.ChaincodeAction.FromString(prp.extension)
        except Exception:
            p.post_flag = V.BAD_PAYLOAD
            return p
        # proposal-hash binding: endorsers signed over this exact proposal.
        # GetProposalHash2 semantics (reference msgvalidation.go:233,
        # txutils.go:431): hash the committed ccpp bytes RAW, never
        # parsing them — a committed payload that still carries transient
        # data (or any other byte difference from the endorsed preimage)
        # simply hashes differently -> BAD_RESPONSE_PAYLOAD.
        want = protoutil.proposal_hash2(
            payload.header.channel_header,
            payload.header.signature_header,
            cap.chaincode_proposal_payload,
        )
        if prp.proposal_hash != want:
            p.post_flag = V.BAD_RESPONSE_PAYLOAD
            return p
        if not cap.action.endorsements:
            p.post_flag = V.ENDORSEMENT_POLICY_FAILURE
            return p

        # chaincode-id consistency: header extension vs ChaincodeAction
        # (reference dispatcher.go:129-157)
        try:
            hdr_ext = proposal_pb2.ChaincodeHeaderExtension.FromString(
                chdr.extension
            )
        except Exception:
            p.post_flag = V.BAD_HEADER_EXTENSION
            return p
        cc_id = hdr_ext.chaincode_id.name
        if not cc_id:
            p.post_flag = V.INVALID_CHAINCODE
            return p
        if action.chaincode_id.name != cc_id:
            p.post_flag = V.INVALID_CHAINCODE
            return p
        # a chaincode event must name the invoked chaincode
        # (dispatcher.go:161-169)
        if action.events:
            try:
                ev = chaincode_event_pb2.ChaincodeEvent.FromString(
                    action.events
                )
            except Exception:
                p.post_flag = V.INVALID_OTHER_REASON
                return p
            if ev.chaincode_id != cc_id:
                p.post_flag = V.INVALID_OTHER_REASON
                return p

        # endorsement policy: each endorsement signs prp_bytes || endorser.
        # Digests are precomputed so policy prepare hits the plan cache
        # (and the device path skips host-side re-hashing) — and they go
        # through the CSP seam (fabriclint's csp-seam rule) as ONE
        # hash_batch per tx.
        msgs = [prp_bytes + e.endorser for e in cap.action.endorsements]
        digests = self._csp.hash_batch(msgs)
        p.signed = [
            SignedData(m, e.endorser, e.signature, digest=d)
            for m, e, d in zip(msgs, cap.action.endorsements, digests)
        ]
        p.cc_id = cc_id
        p.rwset = bytes(action.results)
        # the rwset decode is the largest single collect cost
        # (parse_footprint docstring) — do it here, on the worker; the
        # failure codes land exactly where _prepare_namespaces would
        # have produced them (after the txid registered)
        try:
            p.footprint = parse_footprint(p.rwset)
        except IllegalWritesetError:
            p.post_flag = V.ILLEGAL_WRITESET
        except Exception:
            p.post_flag = V.BAD_RWSET
        return p

    def _integrate_tx(self, p: _ParsedTx, seen_txids: set,
                      sink: _ItemSink, work: _TxWork) -> int:
        """The order-dependent half: sink index assignment, the
        duplicate-txid window, and policy prepare — always in tx order
        on the collecting thread, so flags, sink order, and dedup
        indices are byte-identical whether the parses ran serial or
        fanned out."""
        work.txid = p.hdr_txid
        if p.pre_flag is not None:
            return p.pre_flag
        if type(p.creator_item) is _IdemixCreator:
            work.idemix_item = sink.idemix.add(
                p.creator_item.creator, p.creator_item.items
            )
        else:
            work.creator_item = sink.add(p.creator_item)
        if p.mid_flag is not None:
            return p.mid_flag
        # duplicate detection (checkTxIdDupsLedger): the txid registers
        # even when a later stage fails, exactly as the serial path does
        # (an early serial-path verdict arrives as post_flag and never
        # registers — the txid is already in the window or the ledger)
        if p.post_flag == V.DUPLICATE_TXID:
            return V.DUPLICATE_TXID
        if not p.dup_checked and (
            p.txid in seen_txids or self._ledger.tx_id_exists(p.txid)
        ):
            return V.DUPLICATE_TXID
        seen_txids.add(p.txid)
        if p.post_flag != V.VALID:
            return p.post_flag
        return self._prepare_namespaces(
            work, p.signed, p.cc_id, p.rwset, sink,
            footprint=p.footprint,
        )

    # -- the three-phase validate -----------------------------------------

    def validate(self, block: common_pb2.Block) -> list[int]:
        self._assist = None  # never an earlier block's, if this raises
        # one block and nothing behind it: no next block's collect will
        # hide its flush, so it may hand its first chunk over early
        block, flags, works, collect, envs, bspan = self._start_block(
            block, set(), lone=True
        )
        flags = self._finish_block(block, flags, works, collect, bspan)
        self._assist = _commit_assist(works, envs, bspan)
        return flags

    def take_assist(self):
        """What validate() learned of the block it saw last, as the
        CommitAssist validate_pipeline hands out a block; handed over
        once (Committer.store_block passes it to KVLedger.commit), so
        nothing of a committed block stays alive here."""
        assist, self._assist = self._assist, None
        return assist

    def validate_pipeline(self, blocks, depth: int = 2, release=None,
                          rwsets_out=None):
        """Pipelined validation: yields per-block flag lists in order,
        keeping up to `depth` blocks in flight so block k+1's host
        collect phase overlaps block k's device verify (the reference
        achieves throughput with goroutine fan-out inside one block;
        the TPU build overlaps across blocks instead).

        Duplicate-txid detection spans the ledger plus every block still
        in flight in this pipeline.  By default a block's txids leave
        the window once its flags are finished — correct for callers
        that commit each block before pulling the next flags.  A caller
        that commits asynchronously (Committer.store_stream) passes
        `release`: for every yielded block it receives a zero-arg
        callable (a `_Landed`) and the txid window stays open until
        that callable runs (after the commit lands, when
        ledger.tx_id_exists takes over detection — no gap either way).

        Key-level (state-based) endorsement is decided as a validator
        that commits every block before it validates the next decides
        it, at any depth (`_KeyWindow`): a transaction that writes a
        key whose VALIDATION_PARAMETER a block still in flight may
        change sends its signatures to the device with its block and
        has its policies resolved in the policy stage, once that
        block's commit has landed — when the generator is resumed
        after the block's flags, or when `release`'s callable has run.
        A caller whose commit fails calls that callable's `abort(exc)`,
        and a validator waiting here raises `exc`.  Every other
        transaction is decided as it always was, and a stream whose
        blocks change no parameter never waits."""
        q: collections.deque = collections.deque()
        seen_txids: set[str] = set()
        window = _KeyWindow()

        def finish(started):
            block, flags, works, collect, envs, bspan, txids = started
            num = block.header.number
            flags = self._finish_block(block, flags, works, collect, bspan)
            deferral = works.deferral
            _KEYLEVEL.block_done(num, 0 if deferral is None else deferral.txs)
            if rwsets_out is not None:
                rwsets_out(_commit_assist(works, envs, bspan))
            if release is None:
                seen_txids.difference_update(txids)  # close the window
            else:
                release(_Landed(seen_txids, txids, window, num))
            return flags, num

        def land(num):
            # the caller took the flags and came back for more: it has
            # committed the block (see above)
            if release is None:
                window.land(num)

        for block in blocks:
            before = set(seen_txids)
            started = self._start_block(block, seen_txids, window=window)
            q.append(started + (seen_txids - before,))
            if len(q) >= depth:
                flags, num = finish(q.popleft())
                yield flags
                land(num)
        while q:
            flags, num = finish(q.popleft())
            yield flags
            land(num)

    def _collect_fanout(self, n: int, native: bool = False) -> int:
        """Chunk count for this block's parallel collect; 0/1 = serial.
        Small blocks stay serial — the fan-out overhead (futures, chunk
        lists) only amortizes past a few dozen txs.  The native path
        fans out only on an EXPLICIT width (see __init__)."""
        width = self._collect_width
        if width <= 1 or n < _PARALLEL_MIN_TXS:
            return 0
        if native and not self._collect_explicit:
            return 0
        return min(width, n)

    def _start_block(self, block: common_pb2.Block, seen_txids: set,
                     lone: bool = False, window: "_KeyWindow | None" = None):
        """Phases 1+2: collect every tx, dispatch the device verify.
        `lone`: the caller validates this block alone (`validate`), so
        the flush overlaps nothing unless the collect itself does.
        `window`: the pipeline's record of the key-level parameters its
        blocks in flight may still change; None where every earlier
        block is committed (`validate`)."""
        t0 = time.perf_counter()
        num = block.header.number
        # detached per-block root: its children (collect here,
        # verify_wait/policy in _finish_block, the commit stages on the
        # committer thread via CommitAssist.trace_ctx) attach explicitly
        # — blocks overlap in the pipeline, so the root cannot live on
        # this thread's span stack
        bspan = tracing.begin(
            "block", detach=True, cat="pipeline", block=num,
        )
        try:
            return self._start_block_traced(
                block, seen_txids, bspan, num, t0, lone, window
            )
        except BaseException:
            # detached roots are off the stack-repair path: end the
            # block root here or a crash mid-collect leaves every
            # recorded stage span pointing at a parent id absent from
            # the flight-recorder dump — the one trace that matters
            bspan.annotate(aborted=True)
            bspan.end()
            raise

    def _start_block_traced(self, block, seen_txids, bspan, num, t0, lone,
                            window):
        with tracing.attached(bspan.ctx), tracing.span(
            "collect", cat="stage", block=num,
        ) as cspan:
            envs = list(block.data.data)  # ONE materialization of the
            # envelope byte strings (each repeated-field access copies)
            n = len(envs)
            flags = [V.NOT_VALIDATED] * n
            sink = _ItemSink(dedup=not self._faithful)

            memo = _CreatorMemo()  # per-block creator-identity memo
            self._policy_provider.begin_block()
            # BEFORE anything of the state is read (the namespace memo
            # below included): see _KeyWindow.pending
            pending = None if window is None else window.pending()
            self._pending_block = None if pending is None else pending.get
            self._param_keys = set()
            keylevel = _KeyLevelMemo(
                self._ledger, self._pending_block, not self._faithful
            )
            self._metadata_block = keylevel.lookup
            works = _BlockWorks(n, keylevel)
            count = keylevel.count
            plans0 = self._plan_counts()
            self._namespace_prepares = self._definitions_resolved = 0
            # ONE check a block of what the ledger already knows, after
            # the window was read: a state without metadata (every
            # block of a channel without key-level policies) takes no
            # pass over footprints, no read and no question a
            # namespace.  What lands while the block is collected was
            # in the window, so its keys are pending and decided in
            # the policy stage: no other key has a parameter.
            holds = self._holds_meta
            ahead = holds is not None and holds()
            raw_meta = self._ns_meta
            if holds is not None and not ahead:
                self._ns_meta_block = _no_metadata
            elif raw_meta is not None:
                meta_memo: dict = {}

                def ns_meta(ns, _memo=meta_memo, _raw=raw_meta):
                    v = _memo.get(ns)
                    if v is None:
                        v = _memo[ns] = _raw(ns)
                    return v

                self._ns_meta_block = ns_meta
            else:
                self._ns_meta_block = None
            native = self._collect_native(
                envs, seen_txids, sink, works, flags, memo, lone, ahead
            )
            if not native:
                width = self._collect_fanout(n)
                if width:
                    # fan the pure parse half out in deterministic
                    # chunks; integration (sink indices, dup window,
                    # policy prepare) stays on this thread in strict
                    # tx order
                    memo_lock = threading.Lock()
                    parsed = workpool.run_chunked(
                        self._collect_pool or workpool.default_pool(),
                        lambda off, chunk: [
                            self._parse_tx(e, memo, memo_lock)
                            for e in chunk
                        ],
                        envs, width,
                    )
                    self.parallel_collect_blocks += 1
                    for i in range(n):
                        flags[i] = self._integrate_tx(
                            parsed[i], seen_txids, sink, works[i]
                        )
                else:
                    for i in range(n):
                        flags[i] = self._collect_tx(
                            envs[i], seen_txids, sink, works[i], memo
                        )

            collect = sink.hand_over(self._csp)
            if sink.early_lanes:
                self.early_flush_blocks += 1
            if window is not None and self._param_keys:
                window.add(num, frozenset(self._param_keys))
            if pending is not None:
                self._defer(works, window)
            if count.reads:
                self._count_keylevel(count)
            plans = self._count_plans(plans0)
            if sink.idemix.by_msp:
                # the block's Idemix items go out here too, as ONE
                # asynchronous batched verify (an MSP), before
                # verify_wait: device and host half of block n overlap
                # collect of block n+1 and commit of block n-1
                collect = _both(collect, sink.idemix.dispatch())
            if tracing.enabled():
                # the root says how large the block was: a reader can
                # then tell a 3-tx block's cost from a 500-tx block's
                bspan.annotate(txs=n)
                cspan.annotate(
                    creators=len(memo),
                    creator_validations=memo.validations,
                    creator_ms=memo.seconds * 1e3,
                    creator_chain_batch=memo.chain_batch,
                    creator_native_parse=memo.native_parse,
                    early_lanes=sink.early_lanes,
                    keylevel_reads=count.reads,
                    keylevel_ms=count.seconds * 1e3,
                    keylevel_policies=len(count.params),
                    keylevel_bulk_keys=count.bulk_keys,
                    keylevel_point_reads=count.point_reads,
                    plan_hits=plans[0], plan_misses=plans[1],
                    plan_clears=plans[2], plan_shared_hits=plans[3],
                    plan_build_ms=plans[4] * 1e3,
                    namespace_prepares=self._namespace_prepares,
                    definitions_resolved=self._definitions_resolved,
                )
        self._observe_stage("collect", time.perf_counter() - t0)
        # inside collect, not beside it: what of the stage went to
        # identities the block's memo did not hold
        self._observe_stage("creators", memo.seconds)
        return block, flags, works, collect, envs, bspan

    @staticmethod
    def _defer(works: "_BlockWorks", window: "_KeyWindow") -> None:
        """Note on the block's works which of its transactions wait for
        an earlier block's commit, and for which block."""
        txs, waits_on = 0, -1
        for w in works:
            if w.deferred:
                txs += 1
                waits_on = max(waits_on, max(
                    p.waits_on for p, _idxs in w.pendings
                    if p.waits_on is not None
                ))
        if txs:
            works.deferral = _Deferral(window, waits_on, txs)

    def _collect_native(self, data, seen_txids, sink: _ItemSink, works, flags, memo: dict,
                        lone: bool = False, ahead: bool = False) -> bool:
        """Native-assisted collect: one C++ pass walks every envelope's
        wire format (syntactic checks + SHA-256 digests, collect.cc),
        then this glue does only identity/policy work per tx.  `data` is
        the block's materialized envelope byte list.  Returns False when
        the native library is unavailable (caller runs the pure-Python
        path).

        EVERY lane the walker does not declare fully well-formed
        (status < 0) re-runs the pure-Python collector for that tx.
        Validation flags are consensus state, and the walker's
        strictness can never be byte-for-byte identical to python's
        protobuf decoder on arbitrary garbage (the envelope fuzzer found
        a mangled envelope python rejects outright but the walker
        half-parses, shifting which failure stage — and which flag —
        fires); deriving all failure flags from the one canonical
        python path makes the engines agree by construction.  Honest
        blocks contain no malformed envelopes, so the fallback costs
        nothing on the hot path, and an adversarial block degrades to
        at worst the pure-python engine's cost.

        The glue knows both kinds of creator: an X.509 creator's
        signature joins the ECDSA sink over the walker's payload digest,
        an anonymous (Idemix) creator's credential proof and pseudonym
        signature join the block's Idemix sink (`_IdemixSink`), exactly
        as the Python half (`_parse_tx` / `_integrate_tx`) does.

        A `lone` block (nothing behind it whose collect would hide its
        flush) asks the provider where its lanes would be cut
        (`early_chunk`, of the walker's count: a creator and the
        endorsements of every lane it accepted) and hands exactly that
        many to the device as soon as the sink holds them, so the first
        chunk's kernel runs under the rest of this loop.

        `ahead`: the state holds metadata, so the plugins will look up
        the written keys' VALIDATION_PARAMETERs: the footprints are
        parsed before the glue loop and the block's memo fetches those
        keys' metadata in ONE read (`_KeyLevelMemo.fill`), where the
        loop would make a ledger read a key."""
        from fabric_tpu import native
        from fabric_tpu.csp.api import VerifyBatchItem

        if not native.available():
            return False
        offs = [0]
        for d in data:
            offs.append(offs[-1] + len(d))
        import numpy as np

        buf = b"".join(data)
        co = native.collect_block(
            buf, np.asarray(offs, np.int64), self.channel_id.encode()
        )
        if co is None:
            return False
        digs = bytes(co["payload_digest"])
        edigs = bytes(co["e_digest"])

        def sl(off, ln):
            return buf[off:off + ln]

        # one bulk numpy->python conversion; per-element indexing of
        # numpy arrays costs a scalar-boxing allocation per access
        status_l = co["status"].tolist()
        txid_off_pre = co["txid_off"].tolist()
        txid_len_pre = co["txid_len"].tolist()
        # one bulk ledger probe for the whole block's duplicate-txid
        # check (the reference pays a store get per tx, validator.go:459)
        if hasattr(self._ledger, "tx_ids_exist"):
            probe = {
                buf[txid_off_pre[i]:txid_off_pre[i] + txid_len_pre[i]].decode()
                for i in range(len(data))
                if txid_len_pre[i]
            }
            ledger_dups = self._ledger.tx_ids_exist(probe)
            txid_known = lambda t: t in ledger_dups  # noqa: E731
        else:
            txid_known = self._ledger.tx_id_exists
        ident_intern: dict = {}  # endorser cert slice -> canonical object
        creator_l = [
            sl(off, ln) for off, ln in zip(
                co["creator_off"].tolist(), co["creator_len"].tolist()
            )
        ]
        if not self._faithful:
            # the creators of the lanes the walker accepted, known
            # before the glue loop starts: validated as one batch, so
            # the loop's _creator_identity only hits
            self._creators_ahead(
                [c for c, st in zip(creator_l, status_l) if st >= 0], memo
            )
        sig_off_l = co["sig_off"].tolist()
        sig_len_l = co["sig_len"].tolist()
        txid_off_l = txid_off_pre
        txid_len_l = txid_len_pre
        prp_off_l = co["prp_off"].tolist()
        prp_len_l = co["prp_len"].tolist()
        rwset_off_l = co["rwset_off"].tolist()
        rwset_len_l = co["rwset_len"].tolist()
        ccid_off_l = co["ccid_off"].tolist()
        ccid_len_l = co["ccid_len"].tolist()
        endo_start_l = co["endo_start"].tolist()
        endo_count_l = co["endo_count"].tolist()
        ee_off = co["e_endorser_off"].tolist()
        ee_len = co["e_endorser_len"].tolist()
        es_off = co["e_sig_off"].tolist()
        es_len = co["e_sig_len"].tolist()

        # prefetch over the walker-validated endorser lanes: the rwset
        # footprint decode — the glue loop's largest per-tx cost — fans
        # out in deterministic chunks (a chosen width), or runs here
        # ahead of the loop (`ahead`: the same parses in the same
        # order, none twice); the glue loop below then runs unchanged
        # with footprints in hand, so flags/sink order are
        # byte-identical to the serial pass.  A failed parse
        # carries its flag code (int) in place of the footprint,
        # applied at the exact point _prepare_namespaces would have
        # produced it.  (Creator identities are not prefetched by the
        # pool: they were validated above, as one batch on this thread,
        # whose certificates and chain signatures go through two native
        # calls without the interpreter's lock.  The `creators` stage
        # clock and `collect{creator_ms, creator_chain_batch,
        # creator_native_parse}` say what a block's identities cost and
        # how many of them those calls took.)
        prefetched: list | None = None
        width = self._collect_fanout(len(data), native=True)
        if width or ahead:
            def _prefetch(off, lanes):
                out = []
                for i in lanes:
                    # chaos seam: faultfuzz crash/delay inside the
                    # pooled collect stage
                    faultline.point("collect.tx")
                    try:
                        fp = parse_footprint(
                            sl(rwset_off_l[i], rwset_len_l[i])
                        )
                    except IllegalWritesetError:
                        fp = V.ILLEGAL_WRITESET
                    except Exception:
                        fp = V.BAD_RWSET
                    out.append(fp)
                return out

            # endorser lanes only (1 = CONFIG: no rwset), minus lanes
            # the duplicate-txid stage will discard anyway (window +
            # the bulk ledger probe above) — the old path never parsed
            # a duplicate's rwset and the prefetch must not either.
            # A lane skipped here but clean at glue time (a racing
            # window release) just parses inline; flags never depend
            # on prefetch coverage.
            lanes = []
            for i in range(len(data)):
                st = status_l[i]
                if st < 0 or st == 1:
                    continue
                if txid_len_l[i]:
                    try:
                        t = buf[
                            txid_off_l[i]:txid_off_l[i] + txid_len_l[i]
                        ].decode()
                    except UnicodeDecodeError:
                        continue  # glue falls this lane back anyway
                    if t in seen_txids or txid_known(t):
                        continue
                lanes.append(i)
            if width:
                got = workpool.run_chunked(
                    self._collect_pool or workpool.default_pool(),
                    _prefetch, lanes, width,
                )
                self.parallel_collect_blocks += 1
            else:
                got = _prefetch(0, lanes)
            prefetched = [None] * len(data)
            for i, fp in zip(lanes, got):
                prefetched[i] = fp
            if ahead:
                works.keylevel.fill(_metadata_pairs(
                    (fp for fp in got if not isinstance(fp, int)),
                    self._ns_meta_block,
                ))

        cut = None
        if lone and not self._faithful:
            early_chunk = getattr(self._csp, "early_chunk", None)
            if early_chunk is not None:
                ok = co["status"] >= 0
                cut = early_chunk(
                    int(ok.sum()) + int(co["endo_count"][ok].sum())
                )
        items = sink.items

        for i in range(len(data)):
            if cut is not None and len(items) >= cut:
                sink.hand_early(self._csp, cut)
                cut = None
            st = status_l[i]
            if st < 0:  # python re-derives every non-valid lane
                flags[i] = self._collect_tx(
                    data[i], seen_txids, sink, works[i], memo
                )
                continue
            # creator deserialize + validate (reference flag precedence:
            # BAD_CREATOR_SIGNATURE wins over later-stage failures)
            creator = self._creator_identity(creator_l[i], memo)
            if creator is None:
                flags[i] = V.BAD_CREATOR_SIGNATURE
                continue
            w = works[i]
            if getattr(creator, "proof_deferred", False):
                # an anonymous creator: its pseudonym signature is over
                # the payload bytes themselves (no digest lane), which
                # the walker does not slice out; one Envelope decode
                env = common_pb2.Envelope.FromString(data[i])
                w.idemix_item = sink.idemix.add(
                    creator,
                    creator.deferred_items(env.payload, env.signature),
                )
            else:
                w.creator_item = sink.add(
                    VerifyBatchItem(
                        creator.public_key,
                        digs[32 * i:32 * i + 32],
                        sl(sig_off_l[i], sig_len_l[i]),
                    )
                )
            if st == 1:  # CONFIG tx: creator signature only
                flags[i] = V.VALID
                continue

            try:
                # C++ pre-validates both as UTF-8 (64-hex txid; the
                # chaincode-id string check in collect.cc), so this is
                # defense in depth — and it must run BEFORE the txid
                # registers, so a fallback lane replays through
                # _collect_tx without colliding with itself
                txid = sl(txid_off_l[i], txid_len_l[i]).decode()
                cc_id = sl(ccid_off_l[i], ccid_len_l[i]).decode()
            except UnicodeDecodeError:
                flags[i] = self._collect_tx(
                    data[i], seen_txids, sink, works[i], memo
                )
                continue

            # dup-txid stage: the txid registers even when a LATER check
            # fails (the reference adds to the dedup set right here too)
            w.txid = txid
            if txid in seen_txids or txid_known(txid):
                flags[i] = V.DUPLICATE_TXID
                continue
            seen_txids.add(txid)

            prp_bytes = sl(prp_off_l[i], prp_len_l[i])
            rwset_bytes = sl(rwset_off_l[i], rwset_len_l[i])
            es, ec = endo_start_l[i], endo_count_l[i]
            # intern the endorser identity slices: a block repeats the
            # same handful of ~1KB certs thousands of times, and fresh
            # bytes objects re-hash fully at every endorsement-plan
            # cache lookup (validation_plugins._plan_pending keys on
            # the identity tuple) — the intern makes every repeat the
            # SAME object with its hash computed once
            signed = [
                SignedData(
                    b"",
                    ident_intern.setdefault(
                        _ik := sl(ee_off[k], ee_len[k]), _ik
                    ),
                    sl(es_off[k], es_len[k]),
                    digest=edigs[32 * k:32 * k + 32],
                )
                for k in range(es, es + ec)
            ]
            fp = prefetched[i] if prefetched is not None else None
            if isinstance(fp, int):
                # the prefetch already failed this rwset; the flag lands
                # here — after the txid registered — exactly where the
                # inline parse would have failed
                flags[i] = fp
                continue
            flags[i] = self._prepare_namespaces(
                w, signed, cc_id, rwset_bytes, sink, footprint=fp
            )
        return True

    def _prepare_namespaces(self, w, signed, cc_id, rwset_bytes,
                            sink: _ItemSink, footprint=None) -> int:
        """Shared tail of collect: rwset footprint + per-written-namespace
        plugin prepare (dispatcher.go:158-218 wrNamespace loop).
        `footprint` carries a parse the (possibly pooled) prefetch
        already did; None parses inline."""
        if footprint is None:
            try:
                footprint = parse_footprint(rwset_bytes)
            except IllegalWritesetError:
                return V.ILLEGAL_WRITESET
            except Exception:
                return V.BAD_RWSET

        namespaces = [cc_id] + [
            ns
            for ns, entry in footprint.per_ns.items()
            if entry["writes"] and ns != cc_id
        ]
        self._namespace_prepares += len(namespaces)
        for ns in namespaces:
            ctx = ValidationContext(
                channel_id=self.channel_id,
                namespace=ns,
                tx_pos=-1,
                endorsements=signed,
                rwset_bytes=rwset_bytes,
                policy_provider=self._policy_provider,
                state_metadata=self._metadata_block,
                footprint=footprint,
                ns_has_metadata=self._ns_meta_block,
                pending=self._pending_block,
            )
            try:
                pending = self._plugin_for(ns).prepare(ctx)
            except Exception:
                return V.INVALID_OTHER_REASON
            w.pendings.append((pending, sink.add_many(pending.items)))
            if ctx.pending is not None and getattr(
                pending, "waits_on", None
            ) is not None:
                w.deferred = True
        w.touched_keys = footprint.touched
        w.rwset = rwset_bytes
        w.footprint = footprint
        w.meta_keys = frozenset(footprint.meta_writes)
        if w.meta_keys or footprint.deletes:
            # what a later block has to find committed before it can
            # decide a transaction that writes one of these (_KeyWindow)
            self._param_keys.update(w.meta_keys, footprint.deletes)
        return V.VALID

    def _observe_stage(self, stage: str, dt: float) -> None:
        acc = self.validate_stage_seconds
        acc[stage] = acc.get(stage, 0.0) + dt
        if self._metrics is not None:
            self._metrics.stage_duration.With(
                "channel", self.channel_id, "stage", stage
            ).observe(dt)

    def _finish_block(self, block, flags, works, collect,
                      bspan=None) -> list[int]:
        # the per-block root must reach the recorder even when verify
        # or policy raises (injected crashes included) — crash traces
        # are exactly where the causal root matters
        try:
            return self._finish_block_traced(
                block, flags, works, collect, bspan
            )
        except BaseException:
            if bspan is not None:
                bspan.annotate(aborted=True)
            raise
        finally:
            if bspan is not None:
                bspan.end()

    def _finish_block_traced(self, block, flags, works, collect,
                             bspan) -> list[int]:
        n = len(flags)
        ctx = None if bspan is None else bspan.ctx
        num = block.header.number
        t0 = time.perf_counter()
        with tracing.attached(ctx), tracing.span(
            "verify_wait", cat="stage", block=num,
        ):
            mask = collect()
        t1 = time.perf_counter()
        self._observe_stage("verify_wait", t1 - t0)

        # key-level decisions that depend on an earlier block's commit:
        # the lanes are verified (nothing above waited for a commit),
        # the decision now waits until that commit has landed
        deferral = getattr(works, "deferral", None)
        if deferral is not None:
            with tracing.attached(ctx), tracing.span(
                "policy.await_commit", cat="stage", block=num,
                waits_on=deferral.waits_on, txs=deferral.txs,
            ):
                deferral.window.await_landed(deferral.waits_on)
            t1, t_wait = time.perf_counter(), t1
            self._observe_stage("await_commit", t1 - t_wait)
            count = works.keylevel.landed()
            plans0 = self._plan_counts()

        # phase 3: in-order finish.  All policy evaluations read the
        # COMMITTED (pre-block) metadata — the reference does the same,
        # since GetValidationParameterForKey fetches from the ledger
        # before the block lands (vpmanagerimpl.go:293-340).  The only
        # in-block interaction: a tx touching a key whose
        # VALIDATION_PARAMETER an earlier VALID tx rewrote is invalidated
        # (ValidationParameterUpdatedError -> policyErr ->
        # ENDORSEMENT_POLICY_FAILURE, never re-evaluated under the new
        # policy).
        updated: set[tuple[str, str]] = set()
        # the lanes the mask refused: a handful a block, and a VALID
        # transaction that holds one had its policy met without it
        refused = _refused_lanes(mask)
        tolerated = 0
        with tracing.attached(ctx), tracing.span(
            "policy", cat="stage", block=num,
        ) as pspan:
            if deferral is not None:
                # what the deferred transactions' policies will look up
                # and the memo does not hold yet (their pending keys,
                # above all), in one read of the state as it landed
                works.keylevel.fill(_metadata_pairs(
                    w.footprint for i, w in enumerate(works)
                    if w.deferred and flags[i] == V.VALID
                ))
            for i in range(n):
                if flags[i] != V.VALID:
                    continue
                w = works[i]
                if w.creator_item is not None and not mask[w.creator_item]:
                    flags[i] = V.BAD_CREATOR_SIGNATURE
                    continue
                if w.idemix_item is not None:
                    # a failed credential proof or a failed pseudonym
                    # signature: upstream checkSignatureFromCreator
                    # gives both this code
                    msp, j = w.idemix_item
                    im = mask.idemix[msp]
                    if not (im[j] and im[j + 1]):
                        flags[i] = V.BAD_CREATOR_SIGNATURE
                        continue
                if w.touched_keys & updated:
                    flags[i] = V.ENDORSEMENT_POLICY_FAILURE
                    continue
                try:
                    ok = all(
                        p.finish([mask[j] for j in idxs])
                        for p, idxs in w.pendings
                    )
                except Exception:
                    if not w.deferred:
                        raise
                    # policies resolved here and not in collect: what
                    # `prepare` raising gives there (_prepare_namespaces)
                    flags[i] = V.INVALID_OTHER_REASON
                    continue
                if not ok:
                    flags[i] = V.ENDORSEMENT_POLICY_FAILURE
                    continue
                updated.update(w.meta_keys)
                if refused:
                    for _p, idxs in w.pendings:
                        if not refused.isdisjoint(idxs):
                            # a lane once, whichever of the
                            # transaction's namespaces it stands in
                            tolerated += len(refused.intersection(
                                j for _q, more in w.pendings for j in more
                            ))
                            break

            protoutil.set_tx_filter(block, bytes(flags))
            _TOLERATED.block_done(num, tolerated)
            if tolerated and self._metrics is not None:
                self._metrics.tolerated_bad_endorsements.With(
                    "channel", self.channel_id
                ).add(tolerated)
            if deferral is not None:
                self._count_keylevel(count, deferred=deferral.txs, waits=1)
                plans = self._count_plans(plans0)
                pspan.annotate(
                    deferred=deferral.txs, deferred_reads=count.reads,
                    deferred_ms=count.seconds * 1e3,
                    deferred_bulk_keys=count.bulk_keys,
                    deferred_point_reads=count.point_reads,
                    plan_hits=plans[0], plan_misses=plans[1],
                    plan_clears=plans[2], plan_shared_hits=plans[3],
                    plan_build_ms=plans[4] * 1e3,
                    tolerated_bad_lanes=tolerated,
                )
            else:
                pspan.annotate(deferred=0, tolerated_bad_lanes=tolerated)
        self._observe_stage("policy", time.perf_counter() - t1)
        return flags


__all__ = ["TxValidator"]
